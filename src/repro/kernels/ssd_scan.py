"""Pallas TPU Mamba2 SSD chunked scan.

Grid = (B, nh, S/chunk) with the chunk axis sequential: the running SSM
state h (hd × ds) persists in VMEM scratch across chunks. Each program
computes one head's chunk in the dual quadratic form (two MXU matmuls for
the intra-chunk part) plus the inter-chunk contribution C·h_prev, then
updates the carried state — the TPU-native realization of the SSD
algorithm's matmul-rich structure.

Inputs are pre-activation (post-conv, post-softplus): x (B,S,nh,hd),
dt (B,S,nh), A (nh,), Bmat/Cmat (B,S,g,ds) with heads grouped g | nh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_ref, *,
            cs: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                                     # scalar A (negative)
    x = x_ref[0].astype(jnp.float32)                 # (cs, hd)
    dt = dt_ref[0].astype(jnp.float32)               # (cs, 1) -> (cs,)
    dt = dt.reshape(cs)
    bm = b_ref[0].astype(jnp.float32)                # (cs, ds)
    cm = c_ref[0].astype(jnp.float32)                # (cs, ds)

    da = dt * a                                      # (cs,)
    cum = jnp.cumsum(da)                             # (cs,)
    total = cum[cs - 1]

    # intra-chunk dual form
    diff = cum[:, None] - cum[None, :]               # (cs, cs)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1))
    L = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # (cs,cs)
    M = scores * L * dt[None, :]
    y = jax.lax.dot(M, x)                            # (cs, hd)

    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                   # (hd, ds)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())))             # (cs, hd)

    y_ref[0] = y.astype(y_ref.dtype)

    # state update: h <- h*exp(total) + Σ_j decay_j dt_j x_j ⊗ B_j
    decay = jnp.exp(total - cum) * dt                # (cs,)
    xw = x * decay[:, None]                          # (cs, hd)
    h_new = h * jnp.exp(total) + jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())))            # (hd, ds)
    h_ref[...] = h_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             bmat: jnp.ndarray, cmat: jnp.ndarray, *, chunk: int = 256,
             interpret: bool = False) -> jnp.ndarray:
    """Returns y (B,S,nh,hd) = SSD(x, dt, A, B, C) (no D skip term)."""
    b, s, nh, hd = x.shape
    g, ds = bmat.shape[2], bmat.shape[3]
    rep = nh // g
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    xf = x.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)
    dtf = dt.transpose(0, 2, 1).reshape(b * nh, s, 1)
    af = jnp.tile(a.astype(jnp.float32), b)
    bf = bmat.transpose(0, 2, 1, 3).reshape(b * g, s, ds)
    cf = cmat.transpose(0, 2, 1, 3).reshape(b * g, s, ds)

    def xh_map(bh, ih, ic):
        del ih
        return (bh, ic, 0)

    def bc_map(bh, ih, ic):
        del ih
        bb = bh // nh
        hh = bh % nh
        return (bb * g + hh // rep, ic, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, cs=chunk, nc=nc),
        grid=(b * nh, 1, nc),
        in_specs=[
            pl.BlockSpec((1,), lambda bh, ih, ic: (bh,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, hd), xh_map),
            pl.BlockSpec((1, chunk, 1), xh_map),
            pl.BlockSpec((1, chunk, ds), bc_map),
            pl.BlockSpec((1, chunk, ds), bc_map),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), xh_map),
        out_shape=jax.ShapeDtypeStruct((b * nh, s, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(af, xf, dtf, bf, cf)
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)
