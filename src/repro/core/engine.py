"""Unified superstep engine: the machinery every Monte Carlo sweep
kernel shares, plus the multi-device dispatch layer.

The three jit kernels (request-level ``repro.core.sweep.sweep``, the
k-replica fleet ``fleet_sweep``, the token-level ``gen_sweep``) used to
re-implement the same building blocks — constructive Poisson window
draws, capacity-clamped FIFO buffer ops, superstep histogram scatter,
fold_in per-point PRNG keys, repeated-last-point grid padding, and a
per-kernel ``jax.pmap`` wrapper with its own padding arithmetic.  This
module is the single home for all of it:

- **Per-point keys** (``point_keys``): ``fold_in(PRNGKey(seed), i)``
  per global point index, so a grid dispatched as one vmap batch,
  sharded over devices, or split into several dispatches
  (``Grid.take`` + ``key_offset``) produces bitwise-identical per-point
  results.  This is the contract that makes sharding invisible.
- **Sharded dispatch** (``resolve_shards`` / ``shard_kernel`` /
  ``dispatch``): the default execution mode is ``shard_map`` over a 1-D
  device mesh — one jit-compiled program whose vmapped per-point kernel
  runs on an ``n/n_dev`` slice of the grid per device.  Unlike the
  deprecated ``jax.pmap`` path it replaces, arrays keep their flat
  point axis (no leading device axis to reshape around), padding is
  implemented once (``pad_tail``: repeat the last point up to a
  device-divisible count, slice the outputs back), and the kernels'
  carry buffers alias in place inside the scan (see ``shard_kernel``
  on donation).  Per-point results are bitwise independent of the shard
  count: every lane computes the same per-point program from the same
  fold_in key, and no cross-point collective exists anywhere in the
  kernels.
- **Trace-time kernel helpers** (``exp_gaps`` / ``exp_offsets`` /
  ``fifo_append`` / ``fifo_pop_shift`` / ``accept_window`` /
  ``push_poisson_window`` / ``scatter_hist``): the constructive
  Poisson-process draw (arrival epochs are partial sums of Exp(1)/λ
  gaps — exact, branch-free, no Poisson sampler), the contiguous
  tail-append / prefix-pop buffer ops every kernel's FIFO waiting room
  is built from (contiguous ``dynamic_slice``/``dynamic_update_slice``
  lower to vectorized copies on every XLA backend; element-wise
  scatters with computed indices are ~an order of magnitude slower
  under vmap on CPU), and the thinned superstep histogram scatter.
- **Admission-control ops** (``push_poisson_window_loss`` /
  ``renege_prefix`` / ``orbit_draws`` / ``orbit_file``): the shared
  implementation of the loss regimes every kernel exposes — a
  room-aware window push for the immediate-reject ("429") overflow
  mode, the deadline-renege prefix pop (expired jobs form a contiguous
  FIFO prefix because arrival times are ascending), and the bounded
  retry orbit (lost jobs re-arrive after Exp(retry_rate) backoff; the
  per-step re-arrival count is an exact Binomial thinning drawn from a
  fixed-shape uniform block so RNG consumption never depends on
  state).
- **Adaptive capacity sizing** (``queue_capacity`` /
  ``window_capacity``): ``q_cap``/``a_cap`` are compile-time *shape*
  parameters; the kernels used to default them to a global worst case
  (e.g. ``q_cap=1024`` for every request-level sweep).  These helpers
  size them from the grid actually being dispatched — occupancy scale
  ``m = λτ₀/(1−u)`` (u = effective utilization, finite-b_max aware)
  plus a fluctuation term ``∝ √(m/(1−u²))`` from the AR(1)-like
  batch-size recursion — so light grids stop paying worst-case buffer
  passes.  Overflow is still detected, never silent: the kernels count
  every clamped arrival in ``buffer_dropped`` and a correct run has
  ``buffer_dropped == 0`` (asserted by the tests).  This capacity
  witness is distinct from ``overflow_dropped`` — the *measured*
  losses of a finite ``q_max`` waiting room, a legitimate output.
- **Bounded kernel caches** (``kernel_cache``): an LRU for the
  compile-time-specialized kernel builders.  Long grid campaigns walk
  many truncation/capacity shapes; an unbounded cache accumulates one
  compiled XLA program per shape forever.  Eviction calls the wrapped
  function's ``clear_cache()`` (every ``jax.jit`` wrapper has one), so
  the compiled executables are actually released, not just the Python
  wrapper.  The cache keys on the builder's FULL positional argument
  tuple — every compile-time flag (superstep backend, sketch mode, the
  metrics tap) must be a builder argument, never a closure or global,
  so a kernel specialized one way can never be served for a request
  specialized another (asserted by the cache-key regression tests).

The fused histogram/FIFO superstep update (pallas kernel + lax
fallback) lives in ``repro.kernels.superstep``; ``scatter_hist`` /
``scatter_hist_sums`` here are its lax building blocks, kept in the
engine so the fallback path is exactly the pre-pallas op sequence
(bitwise-pinned by the backend-parity tests).

JAX is imported lazily inside functions: building grids and calling
``enable_host_devices`` must not initialize the JAX backend (the
``XLA_FLAGS`` device-count override only takes effect before first
backend use), and ``repro.core.grid`` stays importable without JAX.

Why sharding preserves the simulation's correctness argument: each
kernel's per-point program is a deterministic function of (params[i],
fold_in(seed, key_offset + i)) — the regenerative batch-by-batch /
event-by-event law argued exact in docs/theory.md.  ``shard_map`` only
partitions the *point axis*; it changes which device evaluates a lane,
never what the lane computes.  See docs/theory.md §"Superstep engine".
"""
from __future__ import annotations

import os
from collections import OrderedDict
from pathlib import Path
from typing import (Any, Callable, Dict, NamedTuple, Optional, Sequence,
                    Union)

import numpy as np

__all__ = ["enable_host_devices", "init_compile_cache", "point_keys",
           "point_keys_at", "welford_block", "resolve_shards",
           "shard_kernel", "pad_tail", "dispatch", "dispatch_device",
           "KernelPlan", "point_sum", "exp_gaps",
           "exp_offsets", "fifo_append", "fifo_pop_shift",
           "accept_window", "push_poisson_window",
           "push_poisson_window_loss", "renege_prefix", "orbit_draws",
           "orbit_file", "scatter_hist", "scatter_hist_sums",
           "queue_capacity", "window_capacity", "orbit_capacity",
           "kernel_cache"]

ShardSpec = Union[None, bool, int]


def enable_host_devices(n: Optional[int] = None) -> None:
    """Expose CPU cores as separate XLA host devices so the sweep
    kernels can shard a grid across them.  Must run before the first
    JAX backend initialization (call it at script/module import time);
    a no-op if the flag is already set or only one core exists."""
    if "xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        return
    n = n or os.cpu_count() or 1
    if n > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()


def init_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home, and return
    it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
    and nothing is changed; otherwise the cache goes to ``.jax_cache``
    at the root of this checkout.  The path is part of each entry's
    key, so it never depends on a temp name, a PID or the time.  Call
    it from an entry point, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# per-point PRNG keys
# ---------------------------------------------------------------------------

def point_keys(seed: int, offset: int, n: int):
    """Per-point PRNG keys via ``fold_in(PRNGKey(seed), point_index)``.

    Unlike ``random.split(key, n)`` — whose i-th key depends on n — a
    point's key depends only on its global index, so a grid dispatched
    in one vmap batch, sharded over devices, or split into several
    dispatches (``Grid.take`` + ``key_offset``) produces
    bitwise-identical per-point results."""
    import jax
    import jax.numpy as jnp
    from jax import random

    base = random.PRNGKey(seed)
    return jax.vmap(lambda i: random.fold_in(base, i))(
        jnp.arange(offset, offset + n))


def point_keys_at(seed: int, indices):
    """``point_keys`` for an arbitrary array of global point indices.

    The adaptive campaign's refine pass compacts unconverged points
    into dense chunks, so the indices it dispatches are no longer a
    contiguous ``offset + arange`` run.  Each lane still gets
    ``fold_in(PRNGKey(seed), global_index)`` — the same key the point
    would have received in a contiguous dispatch — which is exactly the
    contract that makes compaction invisible to per-point results."""
    import jax
    import jax.numpy as jnp
    from jax import random

    base = random.PRNGKey(seed)
    return jax.vmap(lambda i: random.fold_in(base, i))(
        jnp.asarray(indices, dtype=jnp.int32))


def welford_block(bm, d_sum, d_n):
    """One Welford update of the batch-means accumulator ``bm =
    (mean, m2, n_blocks)`` with a block of ``d_n`` jobs whose latencies
    sum to ``d_sum`` (trace-time; call once per superstep).

    The block mean ``d_sum / d_n`` is one sample of the batch-means
    sequence; Welford's recurrence keeps the running mean and centered
    second moment M2 = Σ (x_j − x̄)² without the catastrophic
    cancellation a raw sum-of-squares would suffer in f32.  Blocks that
    completed no measured jobs are skipped (the update is gated, the
    count does not advance), so idle warmup supersteps never dilute the
    variance estimate.  Host-side post-processing turns (m2, n) into a
    standard error: ``sqrt(m2 / (n·(n−1)))``."""
    import jax.numpy as jnp

    mean, m2, n = bm
    has = d_n > 0
    x = d_sum / jnp.maximum(d_n, 1).astype(d_sum.dtype)
    n1 = n + has.astype(n.dtype)
    delta = x - mean
    mean1 = mean + delta / jnp.maximum(n1, 1).astype(d_sum.dtype)
    m21 = m2 + delta * (x - mean1)
    return (jnp.where(has, mean1, mean), jnp.where(has, m21, m2), n1)


# ---------------------------------------------------------------------------
# sharded dispatch (the shard_map layer that replaced jax.pmap)
# ---------------------------------------------------------------------------

def resolve_shards(shard: ShardSpec, n_points: int) -> int:
    """Number of mesh shards for a dispatch.

    ``None``/``True`` → every visible device; ``False`` → 1; an int →
    that many shards (clamped to the visible device count — per-point
    results are shard-count invariant, so clamping is harmless).
    Always clamped to the point count."""
    import jax

    if shard is False:
        return 1
    avail = len(jax.devices())
    if shard is None or shard is True:
        n_dev = avail
    else:
        n_dev = int(shard)
        if n_dev < 1:
            raise ValueError(f"shard must be >= 1 (got {shard})")
    return max(1, min(n_dev, avail, n_points))


def shard_kernel(vm: Callable, n_dev: int, *,
                 donate: Sequence[int] = ()) -> Callable:
    """Wrap a vmapped per-point kernel ``vm(params, keys)`` for
    ``n_dev``-way sharded dispatch.

    ``n_dev == 1`` is a plain ``jax.jit``; otherwise the kernel runs
    under ``shard_map`` over a 1-D device mesh, each device vmapping
    its slice of the point axis — still one jit-compiled program, no
    leading device axis.

    On buffer donation: the kernels' large buffers are all *scan
    carries* (FIFO rings, histograms, accumulators), which XLA's
    while-loop lowering already aliases in place — nothing to donate
    there.  The dispatch *inputs* (params, keys) are tiny and alias no
    output shape/dtype, so donating them only triggers XLA's "donated
    buffers were not usable" warning; ``donate`` therefore defaults to
    empty and exists for callers whose kernels do return an
    input-shaped buffer."""
    import jax

    if n_dev <= 1:
        return jax.jit(vm, donate_argnums=tuple(donate))
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("points",))
    spec = PartitionSpec("points")
    # check_vma=False: the kernels are purely per-point vmaps (no
    # collectives), so shard_map's varying-axes check adds nothing —
    # and pallas_call has no such rule at all, which used to make
    # every fused-pallas dispatch crash under a multi-device mesh
    return jax.jit(jax.shard_map(vm, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=False),
                   donate_argnums=tuple(donate))


def pad_tail(a, pad: int):
    """Pad an array's point axis by repeating its last entry ``pad``
    times — THE grid-padding rule for point counts not divisible by the
    shard count.  Per-point fold_in keys make the duplicate lanes
    compute the (discarded) last point again rather than perturbing
    anything; ``dispatch`` slices the outputs back to the true count.
    One implementation, shared by every kernel (it used to be
    duplicated, and separately tested, per kernel)."""
    if pad <= 0:
        return a
    import jax.numpy as jnp
    return jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)])


class KernelPlan(NamedTuple):
    """A fully-resolved kernel dispatch, pre-transfer: the compiled
    (cached) kernel plus its packed device inputs.

    The three sweep entry points build one of these (``sweep_plan``/
    ``fleet_plan``/``gen_plan``) and immediately ``dispatch`` it; the
    campaign driver builds one per chunk and routes it through
    ``dispatch_device`` instead, keeping the outputs on device for the
    streaming reduction.  ``sketch``/``has_loss`` record the output
    schema the kernel was compiled with (whether ``hist_sums`` and the
    loss counters are present).  ``supersteps`` is the number of
    supersteps one run of the kernel scans, each of ``superstep_len``
    steps and one histogram update."""

    kernel: Callable
    params: Dict[str, Any]
    keys: Any
    n: int
    n_dev: int
    sketch: bool
    has_loss: bool
    supersteps: int
    superstep_len: int


def dispatch_device(kernel: Callable, params: Dict[str, Any], keys,
                    n: int, n_dev: int):
    """``dispatch`` minus the host transfer: pads every input's point
    axis to an ``n_dev``-divisible count (``pad_tail``) and runs the
    (possibly shard_map-wrapped) kernel, returning the *device* output
    arrays still at the padded point count, plus the pad width.

    This is the streaming-campaign entry: the caller feeds the device
    outputs straight into an on-device reduction (masking the ``pad``
    duplicate lanes) so only O(bins + K) aggregates ever cross to the
    host, instead of O(points × bins) per-point buffers."""
    pad = (-n) % n_dev
    if pad:
        params = {k: pad_tail(v, pad) for k, v in params.items()}
        keys = pad_tail(keys, pad)
    return kernel(params, keys), pad


def dispatch(kernel: Callable, params: Dict[str, Any], keys, n: int,
             n_dev: int) -> Dict[str, np.ndarray]:
    """Run one sharded kernel dispatch over ``n`` points.

    Pads every input's point axis to an ``n_dev``-divisible count
    (``pad_tail``), runs the (possibly shard_map-wrapped) kernel, and
    returns host numpy outputs sliced back to ``n`` points."""
    import jax

    out, pad = dispatch_device(kernel, params, keys, n, n_dev)
    out = jax.device_get(out)
    if pad:
        out = {k: v[:n] for k, v in out.items()}
    return out


# ---------------------------------------------------------------------------
# trace-time kernel building blocks (call inside a jit kernel)
# ---------------------------------------------------------------------------

def point_sum(x):
    """Sum of a per-point float vector in a fixed pairwise order.

    ``jnp.sum`` leaves the addition order to the backend, which may
    vectorize a vmapped reduction differently for different point
    counts, so a point's f32 total could change in its last ulp with the
    dispatch width or shard count.  Halving with elementwise adds pins
    the order, keeping per-point results bitwise invariant to how the
    grid is split (the ``point_keys`` contract)."""
    import jax.numpy as jnp

    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    if width > n:
        x = jnp.concatenate([x, jnp.zeros((width - n,), x.dtype)])
    while width > 1:
        width //= 2
        x = x[:width] + x[width:]
    return x[0]


def exp_gaps(key, n: int, rate):
    """n i.i.d. Exp(rate) inter-arrival gaps (one vectorized draw)."""
    from jax import random
    return random.exponential(key, (n,)) / rate


def exp_offsets(key, n: int, rate):
    """Constructive Poisson-process epochs: partial sums of n Exp(1)
    gaps, scaled by 1/rate.  Exact — the count inside a window of
    length w is exactly Poisson(rate·w) — and branch-free."""
    import jax.numpy as jnp
    from jax import random
    return jnp.cumsum(random.exponential(key, (n,))) / rate


def fifo_append(buf, pos, block):
    """Contiguous FIFO tail-append: write ``block`` at ``buf[pos:]``.

    The whole fixed-size block is written unconditionally; entries past
    the accepted count land in the free region, where they stay garbage
    until a later append overwrites them — the shared buffer invariant
    of every kernel ("live slots are exactly the tracked range")."""
    from jax import lax
    return lax.dynamic_update_slice(buf, block, (pos,))


def fifo_pop_shift(buf, k, max_shift: int):
    """Drop the ``k`` oldest entries of a linear-compacted FIFO buffer
    by shifting the remainder down (``k <= max_shift`` statically).
    Contiguous ``dynamic_slice`` — a vectorized copy, not a scatter."""
    import jax.numpy as jnp
    from jax import lax
    n = buf.shape[0]
    return lax.dynamic_slice(
        jnp.concatenate([buf, jnp.zeros((max_shift,), buf.dtype)]),
        (k,), (n,))


def accept_window(count, q, q_cap: int):
    """Clamp a window's arrival count by queue capacity: returns
    ``(accepted, overflow)`` — overflow feeds the ``buffer_dropped``
    counter (a correct run has ``buffer_dropped == 0``)."""
    import jax.numpy as jnp
    a = jnp.minimum(count, q_cap - q)
    return a, count - a


def push_poisson_window(buf, q, dropped, key, rate, t0, win, *,
                        a_cap: int, q_cap: int):
    """Append the Poisson-process arrivals of a window of length
    ``win`` starting at ``t0`` to a linear-compacted FIFO buffer,
    FIFO-ordered.  Uses the constructive definition (``exp_offsets``)
    so it is exact and needs no Poisson sampler; ``dropped`` counts
    both arrivals beyond ``a_cap`` per window (detected via the
    sentinel (a_cap+1)-th gap) and arrivals clamped by queue
    capacity (the ``buffer_dropped`` capacity witness)."""
    import jax.numpy as jnp

    i32, f32 = jnp.int32, jnp.float32
    offs = exp_offsets(key, a_cap + 1, rate)
    count = jnp.sum(offs[:-1] <= win).astype(i32)
    dropped = dropped + (offs[-1] <= win).astype(i32)
    a, over = accept_window(count, q, q_cap)
    dropped = dropped + over
    buf = fifo_append(buf, q, (t0 + offs[:-1]).astype(f32))
    return buf, q + a, dropped


def push_poisson_window_loss(buf, q, dropped, key, rate, t0, win, *,
                             a_cap: int, q_cap: int, room):
    """``push_poisson_window`` with a *physical* waiting-room bound.

    ``room`` is the per-point admission limit each arrival is tested
    against at its own epoch (the immediate-reject "429" regime — for
    the "drop" regime pass ``room = q_cap`` and trim at formation
    instead).  Occupancy only grows inside a window, so admission is
    prefix-greedy: exactly the first ``(room − q)⁺`` arrivals enter.
    Returns ``(buf, q, dropped, accepted, rejected)`` — ``rejected``
    is a *measured* loss (``overflow_dropped``), while ``dropped``
    keeps counting only the ``a_cap`` sentinel + buffer clamp, the
    ``buffer_dropped`` capacity witness."""
    import jax.numpy as jnp

    i32, f32 = jnp.int32, jnp.float32
    offs = exp_offsets(key, a_cap + 1, rate)
    count = jnp.sum(offs[:-1] <= win).astype(i32)
    dropped = dropped + (offs[-1] <= win).astype(i32)
    admit = jnp.minimum(count, jnp.maximum(room - q, 0).astype(i32))
    rejected = count - admit
    a, over = accept_window(admit, q, q_cap)
    dropped = dropped + over
    buf = fifo_append(buf, q, (t0 + offs[:-1]).astype(f32))
    return buf, q + a, dropped, a, rejected


def renege_prefix(buf, q, now, deadline, max_pop: int):
    """Pop the deadline-expired jobs from a linear-compacted FIFO wait
    buffer of arrival times.  Arrival times ascend, so the expired jobs
    (age ``now − buf[i] > deadline``) form a contiguous prefix — one
    mask-count plus one ``fifo_pop_shift``.  ``deadline <= 0`` disables
    reneging.  Returns ``(buf, q, n_expired)``."""
    import jax.numpy as jnp

    idx = jnp.arange(buf.shape[0])
    n_exp = jnp.sum((idx < q) & (buf < now - deadline)).astype(jnp.int32)
    n_exp = jnp.where(deadline > 0, n_exp, 0)
    buf = fifo_pop_shift(buf, n_exp, max_pop)
    return buf, q - n_exp, n_exp


def orbit_draws(key, R, p, r_cap: int):
    """Number of retry-orbit jobs re-arriving this step: an exact
    Binomial(R, p) thinning (each orbit job independently fires with
    probability ``p = 1 − exp(−retry_rate·elapsed)``), drawn from a
    fixed ``r_cap``-shaped uniform block so the kernel's RNG
    consumption never depends on the traced orbit size."""
    import jax.numpy as jnp
    from jax import random

    u = random.uniform(key, (r_cap,))
    return jnp.sum((jnp.arange(r_cap) < R) & (u < p)).astype(jnp.int32)


def orbit_file(R, lost_a, lost_b, r_cap: int, enabled):
    """File this step's losses into the bounded retry orbit.

    ``lost_a`` has priority over ``lost_b`` for the remaining orbit
    room (the kernels pass abandoned, then overflow).  Losses that do
    not fit (orbit at ``r_cap``) — or all of them when ``enabled`` is
    false (``retry_rate == 0``) — stay in their class as *terminal*
    losses.  Returns ``(R, final_a, final_b)``."""
    import jax.numpy as jnp

    room = jnp.where(enabled, jnp.maximum(r_cap - R, 0), 0)
    take_a = jnp.minimum(lost_a, room)
    take_b = jnp.minimum(lost_b, room - take_a)
    return R + take_a + take_b, lost_a - take_a, lost_b - take_b


def scatter_hist(hist, bins, inc, hist_rows=None):
    """One flattened scatter-add of a superstep block's histogram rows
    (optionally thinned to the fixed ``hist_rows`` subsample).  The
    per-call cost of a scatter under vmap dwarfs its per-element cost
    on CPU, so the superstep kernels batch a whole block per call."""
    import jax.numpy as jnp
    if hist_rows is not None and len(hist_rows) < bins.shape[0]:
        bins, inc = bins[hist_rows], inc[hist_rows]
    return hist.at[bins.reshape(-1)].add(
        inc.reshape(-1).astype(jnp.int32))


def scatter_hist_sums(sums, bins, inc, vals):
    """Companion scatter for the streaming-sketch mode: accumulate the
    measured latencies (``vals`` where ``inc``) into per-bin float sums
    alongside the counts, so streaming consumers can report in-bin
    means without keeping samples.  Same flattened-block amortization
    as ``scatter_hist``; callers thin ``bins``/``inc``/``vals``
    together before the call."""
    import jax.numpy as jnp
    masked = jnp.where(inc, vals, 0.0).reshape(-1)
    return sums.at[bins.reshape(-1)].add(masked)


# ---------------------------------------------------------------------------
# adaptive capacity sizing
# ---------------------------------------------------------------------------

def _pow2ceil(x: float) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(1.0, float(x))))))


def _occupancy_scale(lam, alpha, tau0, b_max, wait_max=0.0):
    """Per-point (mean, sd) scale of the waiting-room occupancy.

    Effective utilization is finite-b_max aware: a capped server
    saturates at λ·(α + τ0/b_max) → 1, not λα → 1.  The mean occupancy
    scale is the batch fixed-cost window's worth of arrivals inflated
    by 1/(1−u) (the paper's E[B] ≈ λτ₀/(1−ρ) law, Remark 5), plus the
    timeout policy's deliberate accumulation λ·wait_max.  The sd comes
    from the AR(1)-like batch recursion B' ~ Poisson(λ·τ(B)), whose
    stationary variance is the per-window variance inflated by
    1/(1−u²)."""
    lam = np.asarray(lam, dtype=np.float64)
    cap = np.where(np.asarray(b_max) > 0, np.asarray(b_max), np.inf)
    u = np.clip(lam * (np.asarray(alpha) + np.asarray(tau0) / cap),
                0.0, 0.98)
    m = lam * np.asarray(tau0) / (1.0 - u) + lam * np.asarray(wait_max)
    sd = np.sqrt(np.maximum(m, 1.0) / np.maximum(1.0 - u * u, 0.04))
    return m, sd


def completion_inflation(lam, alpha, tau0, b_max, mtbf, mttr,
                         restart=None, throttle=None) -> np.ndarray:
    """Per-point multiplicative service-time inflation E[C]/s from the
    breakdown/repair regime, evaluated at each point's occupancy-scale
    batch size.  Preempt-resume (and fail-drop) inflate by 1 + ξ·mttr
    (ξ = 1/MTBF); preempt-restart re-executes the batch from scratch a
    Geometric number of times, the classical
    E[C] = (1/ξ + mttr)·(e^{ξs} − 1), which *exponentiates* in ξ·s.
    Clipped to [1, 64]: beyond that the point is far past ρ_eff = 1 and
    no finite buffer sizing is meaningful anyway."""
    lam64 = np.asarray(lam, dtype=np.float64)
    mtbf64 = np.asarray(mtbf, dtype=np.float64) * np.ones_like(lam64)
    r = np.asarray(mttr, dtype=np.float64) * np.ones_like(lam64)
    xi = np.where(mtbf64 > 0, 1.0 / np.maximum(mtbf64, 1e-300), 0.0)
    m0, _ = _occupancy_scale(lam, alpha, tau0, b_max)
    cap = np.where(np.asarray(b_max) > 0, np.asarray(b_max), np.inf)
    b_eff = np.minimum(np.maximum(m0, 1.0), cap)
    s_b = (np.asarray(alpha, dtype=np.float64) * b_eff
           + np.asarray(tau0, dtype=np.float64))
    infl = 1.0 + xi * r
    if restart is not None:
        xs = np.minimum(xi * s_b, 32.0)
        infl_restart = ((1.0 / np.maximum(xi, 1e-300) + r)
                        * np.expm1(xs) / np.maximum(s_b, 1e-300))
        rmask = np.asarray(restart, dtype=bool) \
            * np.ones_like(lam64, dtype=bool)
        infl = np.where(rmask & (xi > 0),
                        np.maximum(infl_restart, infl), infl)
    if throttle is not None:
        infl = infl * np.maximum(
            np.asarray(throttle, dtype=np.float64), 1.0)
    return np.clip(np.where(xi > 0, infl, 1.0), 1.0, 64.0)


def queue_capacity(lam, alpha, tau0, b_max, wait_max=0.0, *,
                   q_max=None, mtbf=None, mttr=None, restart=None,
                   throttle=None, floor: int = 64,
                   ceil: int = 8192) -> int:
    """Adaptive ``q_cap`` for a request-level grid: sized from the
    dispatched grid's own maximum load instead of a global worst case.

    Power-of-two bucketed (bounds recompiles across campaigns), with a
    ~10σ fluctuation margin over the occupancy scale so multi-thousand
    -step runs keep ``buffer_dropped == 0`` (overflow is still counted,
    never silent — the kernels report it and the tests assert on it).

    A finite waiting room caps a point's need regardless of its load:
    with ``q_max`` given, a ``q_max > 0`` point never holds more than
    ``q_max`` waiting jobs plus one window's worth of pre-trim ("drop"
    mode) arrivals — this is what keeps super-critical (ρ > 1) loss
    points inside finite buffers.

    Breakdown/repair points (``mtbf``/``mttr`` given, with ``restart``
    a per-point preempt-restart mask and ``throttle`` the degraded-
    phase factor) size against the *completion-time* law instead of
    the bare service time: the occupancy scale inflates by E[C]/s
    (restart re-execution exponentiates in s/MTBF — see
    ``completion_inflation``), and an additive repair-burst margin
    λ·mttr + 10σ covers the arrivals that pile up across a repair
    window, keeping ``buffer_dropped == 0`` the witness at MTTR up to
    ~10·τ[b_max]."""
    lam64 = np.asarray(lam, dtype=np.float64)
    alpha_eff = np.asarray(alpha, dtype=np.float64) * np.ones_like(lam64)
    tau0_eff = np.asarray(tau0, dtype=np.float64) * np.ones_like(lam64)
    burst = 0.0
    if mtbf is not None and np.any(np.asarray(mtbf) > 0):
        infl = completion_inflation(lam, alpha, tau0, b_max, mtbf,
                                    0.0 if mttr is None else mttr,
                                    restart=restart, throttle=throttle)
        alpha_eff = alpha_eff * infl
        tau0_eff = tau0_eff * infl
        lr = lam64 * (np.asarray(mttr, dtype=np.float64)
                      * np.ones_like(lam64))
        # repairs cluster inside busy periods: two back-to-back mean
        # repairs' worth of arrivals plus a 10σ Poisson margin
        burst = 2.0 * lr + 10.0 * np.sqrt(lr + 1.0)
    m, sd = _occupancy_scale(lam, alpha_eff, tau0_eff, b_max, wait_max)
    need = np.maximum(m + 10.0 * sd, 0.0) + burst + 32.0
    if q_max is not None:
        qm = np.asarray(q_max, dtype=np.float64) * np.ones_like(lam64)
        cap = np.where(np.asarray(b_max) > 0, np.asarray(b_max), np.inf)
        b_eff = np.minimum(np.maximum(qm, 1.0), cap)
        w_mu = lam64 * (alpha_eff * b_eff + tau0_eff
                        + np.asarray(wait_max))
        room_need = qm + w_mu + 10.0 * np.sqrt(w_mu + 1.0) \
            + burst + 32.0
        # the room bound caps the load estimate, but the buffer must
        # still physically hold a full waiting room (the plan layer
        # rejects q_cap < q_max) — a lightly-loaded q_max = 256 chunk
        # would otherwise size below its own room
        need = np.where(qm > 0,
                        np.minimum(np.maximum(need, qm + 1.0), room_need),
                        need)
    need = float(np.max(need))
    b_top = float(np.max(np.where(np.asarray(b_max) > 0, b_max, 0)))
    return int(min(ceil, max(floor, _pow2ceil(max(need, 2.0 * b_top)))))


def window_capacity(lam, window, *, slack: float = 8.0, floor: int = 16,
                    bucket: int = 16, ceil: int = 4096) -> int:
    """Adaptive ``a_cap``: arrivals that must be visible inside one
    indivisible kernel window (one service period, one decode-step +
    batched-prefill run, …).  Poisson mean + ``slack``·√mean tail
    margin, bucketed to multiples of ``bucket`` to bound recompiles."""
    mu = float(np.max(np.asarray(lam, dtype=np.float64)
                      * np.asarray(window, dtype=np.float64)))
    need = mu + slack * np.sqrt(mu + 1.0) + slack
    return int(min(ceil, max(floor, -(-int(np.ceil(need)) // bucket)
                             * bucket)))


def orbit_capacity(lam, retry_rate, *, floor: int = 16,
                   ceil: int = 1024) -> int:
    """Adaptive ``r_cap``: the retry orbit's compile-time bound.

    The orbit's drift balances at ``R* = λ/retry_rate`` even when
    *every* arrival is lost (input rate ≤ λ, output rate R·retry_rate),
    so ``R* + 10·√R*`` bounds its excursions; power-of-two bucketed.
    Reaching ``r_cap`` is a modeled regime (the excess loss becomes
    terminal — a finite retry budget), not a silent clamp."""
    lam64 = np.asarray(lam, dtype=np.float64)
    rr = np.asarray(retry_rate, dtype=np.float64) * np.ones_like(lam64)
    r_star = np.where(rr > 0, lam64 / np.maximum(rr, 1e-12), 0.0)
    need = float(np.max(r_star + 10.0 * np.sqrt(r_star + 1.0))) + 8.0
    return int(min(ceil, max(floor, _pow2ceil(need))))


# ---------------------------------------------------------------------------
# bounded kernel caches
# ---------------------------------------------------------------------------

class _KernelCache:
    """LRU over a kernel-builder function, keyed by the builder's
    (hashable) compile-time arguments.

    Eviction calls ``clear_cache()`` on the evicted value when present
    — every ``jax.jit`` wrapper has one — so the compiled XLA programs
    a long grid campaign walks through are released instead of
    accumulating for the life of the process."""

    def __init__(self, fn: Callable, maxsize: int):
        self.fn = fn
        self.maxsize = int(maxsize)
        self.builds = 0
        self.evictions = 0
        self._cache: "OrderedDict" = OrderedDict()
        self.__name__ = getattr(fn, "__name__", "kernel")
        self.__doc__ = fn.__doc__

    def __call__(self, *key):
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            return hit
        val = self.fn(*key)
        self.builds += 1
        self._cache[key] = val
        while len(self._cache) > self.maxsize:
            _, old = self._cache.popitem(last=False)
            self.evictions += 1
            self._release(old)
        return val

    @staticmethod
    def _release(val) -> None:
        clear = getattr(val, "clear_cache", None)
        if callable(clear):
            clear()

    def cache_len(self) -> int:
        return len(self._cache)

    def cache_keys(self):
        return list(self._cache.keys())

    def cache_clear(self) -> None:
        for val in self._cache.values():
            self._release(val)
        self._cache.clear()


def kernel_cache(maxsize: int) -> Callable[[Callable], _KernelCache]:
    """Decorator: bound a kernel builder with an evicting LRU (see
    ``_KernelCache``).  Drop-in for ``functools.lru_cache`` at the
    builder call sites, plus ``builds``/``evictions``/``cache_len()``
    introspection the cache-eviction regression tests use."""
    def deco(fn: Callable) -> _KernelCache:
        return _KernelCache(fn, maxsize)
    return deco
