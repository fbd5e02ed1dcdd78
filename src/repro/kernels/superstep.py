"""Fused pallas superstep update: histogram scatter + FIFO compaction.

The MC sweep kernels (``repro.core.sweep``, ``fleet_sweep``,
``gen_sweep``) amortize their latency-histogram scatter and
buffer/clock rebase to one call per superstep block.  Profiling shows
the scatter IS the hot loop on CPU — stubbing it out of a request-level
sweep dispatch raises jobs/sec ~5× — so this module gives that
superstep boundary two interchangeable implementations:

- ``backend="lax"``: exactly the pre-pallas op sequence
  (``hist.bit_bins`` → ``engine.scatter_hist``/``scatter_hist_sums``,
  ``engine.fifo_pop_shift`` → subtract), kept as the bitwise reference;
- ``backend="pallas"``: one fused ``pl.pallas_call`` per superstep that
  bins the block's latencies, accumulates the histogram by one-hot
  reduction (and the sketch's per-bin latency sums in the same pass),
  and — for the generate kernel — compacts the FIFO tail buffer with
  the clock rebase folded in.  On a TPU it lowers through Mosaic; off
  the TPU it runs in interpret mode, which only tests use: there it is
  several times slower than the lax path.

Histogram counts are integer accumulations in both backends, so the
two paths are bitwise identical (asserted by the backend-parity
tests); the sketch's float per-bin sums may differ in the last ulp
(reduction order), which is why percentiles are reconstructed from
counts only.

Backend selection: explicit ``superstep_backend=`` on the sweep entry
points > the ``REPRO_SUPERSTEP_BACKEND`` env var > ``"auto"`` (pallas
on a TPU, lax elsewhere).  The resolved backend is a compile-time
kernel-builder argument, so it is part of the ``engine.kernel_cache``
key — a pallas-path kernel can never be served for a lax-path request.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple

import numpy as np

from repro.core import engine
from repro.core import hist as hist_mod

__all__ = ["BACKENDS", "ENV_VAR", "resolve_backend", "hist_update",
           "fifo_compact"]

BACKENDS = ("auto", "lax", "pallas")
ENV_VAR = "REPRO_SUPERSTEP_BACKEND"


def resolve_backend(backend: Optional[str]) -> str:
    """Resolve a backend request to ``"lax"`` or ``"pallas"``.

    ``None``/``"auto"`` consults ``REPRO_SUPERSTEP_BACKEND``, then
    picks by platform (see module docstring).  The result is what the
    kernel builders bake in — and key their cache entries on."""
    b = "auto" if backend is None else str(backend)
    if b == "auto":
        b = os.environ.get(ENV_VAR, "auto")
    if b == "auto":
        import jax
        b = "pallas" if jax.default_backend() == "tpu" else "lax"
    if b not in ("lax", "pallas"):
        raise ValueError(f"unknown superstep backend {b!r}; pick from "
                         f"{BACKENDS} (or set {ENV_VAR})")
    return b


def _interpret() -> bool:
    import jax
    return jax.default_backend() != "tpu"


# points per grid step of the fused kernels: the sublane tile, so the
# (points, bins) histogram blocks are (8, 128)-aligned or full-extent
_POINT_TILE = 8


def _point_batched(kernel):
    """Run ``kernel`` — written over an explicit leading point axis —
    from per-point code.  The sweep kernels call the fused ops inside a
    ``vmap`` over grid points; left to itself, ``vmap`` of a
    ``pallas_call`` adds a grid axis whose block covers ONE point, a
    block shape Mosaic refuses.  ``custom_vmap`` hands the whole point
    axis to ``kernel`` instead; an unbatched call runs it over a single
    point."""
    import jax
    import jax.numpy as jnp

    @jax.custom_batching.custom_vmap
    def op(*args):
        return tuple(o[0] for o in kernel(*(a[None] for a in args)))

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        outs = kernel(*args)
        return outs, tuple(True for _ in outs)

    return op


def _pad_points(arrays, tile: int):
    """Zero-pad the leading point axis to a multiple of ``tile``."""
    import jax.numpy as jnp

    n = arrays[0].shape[0]
    pad = (-n) % tile
    if not pad:
        return arrays, n
    return [jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
            for a in arrays], n


# ---------------------------------------------------------------------------
# fused histogram update
# ---------------------------------------------------------------------------

def _hist_body(lats_ref, inc_ref, *refs, shift: int, base: int,
               n_bins: int, with_sums: bool):
    """One-hot histogram accumulation for a tile of points.

    Per point, each superstep row's ``bin = clip((bits(lat) >> shift)
    - base)`` (the bit-pattern binning of ``hist.bit_bins``) is
    compared against a bins-by-lanes iota, and the hits accumulate in a
    (n_bins, width) VMEM tile on the vector unit.  One matmul with a
    ones row then folds the lanes into the (1, n_bins) histogram row:
    the per-element counts are at most the row count, exact in bf16,
    and the MXU accumulates them exactly in f32, so the counts match the
    lax scatter bitwise.  The sketch's per-bin latency sums ride the
    same pass — the "fused" part — and fold at full f32 precision."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    if with_sums:
        hist_ref, sums_ref, hist_out, sums_out, cnt_acc, sum_acc = refs
    else:
        hist_ref, hist_out, cnt_acc = refs
    tile, rows, width = lats_ref.shape
    bins_iota = lax.broadcasted_iota(jnp.int32, (n_bins, width), 0)
    lanes_to_row = (((1,), (1,)), ((), ()))      # ones @ acc^T

    for p in range(tile):
        cnt_acc[...] = jnp.zeros_like(cnt_acc)
        if with_sums:
            sum_acc[...] = jnp.zeros_like(sum_acc)

        def row(r, carry, p=p):
            lat = lats_ref[p, pl.ds(r, 1), :]
            bits = lax.bitcast_convert_type(lat, jnp.int32)
            b = jnp.clip((bits >> shift) - base, 0, n_bins - 1)
            hit = (bins_iota == b) & (inc_ref[p, pl.ds(r, 1), :] != 0)
            cnt_acc[...] += hit.astype(jnp.int32)
            if with_sums:
                sum_acc[...] += jnp.where(hit, lat, 0.0)
            return carry

        lax.fori_loop(0, rows, row, 0)
        counts = lax.dot_general(
            jnp.ones((8, width), jnp.bfloat16),
            cnt_acc[...].astype(jnp.bfloat16), lanes_to_row,
            preferred_element_type=jnp.float32)
        hist_out[p:p + 1, :] = (hist_ref[p:p + 1, :]
                                + counts[0:1].astype(jnp.int32))
        if with_sums:
            sums = lax.dot_general(
                jnp.ones((8, width), jnp.float32), sum_acc[...],
                lanes_to_row, precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            sums_out[p:p + 1, :] = sums_ref[p:p + 1, :] + sums[0:1]


def _pallas_hist_points(*args, shift: int, base: int, n_bins: int):
    """``_hist_body`` over an explicit point axis: ``lats``/``inc`` are
    (points, rows, width), the histograms (points, n_bins)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lats, inc, *hists = args
    with_sums = len(hists) == 2
    tile = _POINT_TILE
    (lats, inc, *hists), n = _pad_points(
        [lats.astype(jnp.float32), inc.astype(jnp.int32), *hists], tile)
    n_pad, rows, width = lats.shape
    if rows > 256:
        # the lane fold multiplies per-element counts (<= rows) in bf16,
        # whose integers are exact only up to 256
        raise ValueError(f"{rows} histogram rows per superstep; the "
                         "fused update takes at most 256")
    row_spec = pl.BlockSpec((tile, rows, width), lambda i: (i, 0, 0))
    bin_spec = pl.BlockSpec((tile, n_bins), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((n_pad, n_bins), jnp.int32)]
    scratch = [pltpu.VMEM((n_bins, width), jnp.int32)]
    if with_sums:
        out_shape.append(jax.ShapeDtypeStruct((n_pad, n_bins),
                                              jnp.float32))
        scratch.append(pltpu.VMEM((n_bins, width), jnp.float32))
    body = functools.partial(_hist_body, shift=shift, base=base,
                             n_bins=n_bins, with_sums=with_sums)
    out = pl.pallas_call(
        body, grid=(n_pad // tile,),
        in_specs=[row_spec, row_spec] + [bin_spec] * len(hists),
        out_specs=[bin_spec] * len(hists),
        out_shape=out_shape, scratch_shapes=scratch,
        input_output_aliases={2 + i: i for i in range(len(hists))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(), name="superstep_hist",
    )(lats, inc, *hists)
    return tuple(o[:n] for o in out)


def _pallas_hist(hists: Sequence, lats, inc, *, shift: int, base: int,
                 n_bins: int) -> Tuple:
    op = _point_batched(functools.partial(
        _pallas_hist_points, shift=shift, base=base, n_bins=n_bins))
    return op(lats, inc, *hists)


def hist_update(hists: Sequence, lats, inc, *, n_bins: int,
                backend: str, sketch: bool = False,
                hist_rows: Optional[np.ndarray] = None) -> Tuple:
    """Per-superstep histogram update (trace-time: call inside a jit
    kernel).  ``hists`` is ``(counts,)`` or ``(counts, sums)`` — the
    sketch mode's two accumulators; ``lats``/``inc`` are the stacked
    ``(block, width)`` scan outputs; ``hist_rows`` thins the block to
    the fixed subsample first (same contract as
    ``engine.scatter_hist``).  Returns the updated tuple."""
    if hist_rows is not None and len(hist_rows) < lats.shape[0]:
        lats, inc = lats[hist_rows], inc[hist_rows]
    shift, base, _ = hist_mod.bin_params(sketch)
    if backend == "pallas":
        return _pallas_hist(tuple(hists), lats, inc, shift=shift,
                            base=base, n_bins=n_bins)
    if backend != "lax":
        raise ValueError(f"unresolved superstep backend {backend!r}")
    bins = hist_mod.bit_bins(lats, n_bins, sketch)
    out = (engine.scatter_hist(hists[0], bins, inc),)
    if len(hists) == 2:
        out = out + (engine.scatter_hist_sums(hists[1], bins, inc,
                                              lats),)
    return out


# ---------------------------------------------------------------------------
# fused FIFO compaction + clock rebase
# ---------------------------------------------------------------------------

def _compact_body(k_ref, now_ref, buf_ref, out_ref, *, n: int):
    """Drop the k oldest entries of each point's linear FIFO buffer and
    rebase the survivors by -now in one pass: out[i] = buf[k+i] - now
    (0 - now past the end, matching the lax zeros-pad + slice
    sequence).  The shift is a lane rotation by the point's own k,
    read from SMEM; the wrapped-around lanes are masked to zero."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, width = buf_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    base = pl.program_id(0) * tile
    for p in range(tile):
        k = k_ref[base + p]
        row = pltpu.roll(buf_ref[p:p + 1, :], (width - k) % width, 1)
        out_ref[p:p + 1, :] = (jnp.where(lane < n - k, row, 0.0)
                               - now_ref[base + p])


def _pallas_compact_points(buf, k, now):
    """``_compact_body`` over an explicit point axis: ``buf`` is
    (points, n), ``k``/``now`` (points,).  The buffer is padded to a
    whole number of lane tiles for the rotation; the per-point scalars
    go to SMEM whole, as scalar-prefetch operands."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = _POINT_TILE
    (buf, k, now), n_pts = _pad_points(
        [buf, k.astype(jnp.int32), now.astype(jnp.float32)], tile)
    n_pad, n = buf.shape
    width = -(-n // 128) * 128
    if width > n:
        buf = jnp.concatenate(
            [buf, jnp.zeros((n_pad, width - n), buf.dtype)], axis=1)
    spec = pl.BlockSpec((tile, width), lambda i, k_s, now_s: (i, 0))
    out = pl.pallas_call(
        functools.partial(_compact_body, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_pad // tile,),
            in_specs=[spec], out_specs=spec),
        out_shape=jax.ShapeDtypeStruct((n_pad, width), buf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(), name="superstep_compact",
    )(k, now, buf)
    return (out[:n_pts, :n],)


def fifo_compact(buf, k, now, *, backend: str):
    """Per-superstep FIFO re-compaction with the clock rebase folded in
    (trace-time): equivalent to ``engine.fifo_pop_shift(buf, k,
    len(buf)) - now``, which is exactly what the lax backend runs."""
    if backend == "pallas":
        return _point_batched(_pallas_compact_points)(buf, k, now)[0]
    if backend != "lax":
        raise ValueError(f"unresolved superstep backend {backend!r}")
    return engine.fifo_pop_shift(buf, k, buf.shape[0]) - now
