"""Pallas TPU batched GQA decode attention (flash-decode style).

The serving hot path: one query token per sequence against a long KV cache.
Grid = (B·KV, S/bk) — each program owns the G = H/KV query heads of one
kv-head and streams cache blocks through VMEM, merging partial softmax
statistics (running max / denominator) in scratch. Length masking admits
only the valid prefix of each row's cache; sliding-window masking prunes
the long_500k configuration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bk: int, nk: int, scale: float, window: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # (G, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    length = len_ref[0]                               # scalar: cache fill

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G,bk)
    pos_k = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos_k <= length                            # includes self slot
    if window:
        mask &= length - pos_k < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, window: int = 0,
                     bk: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q: (B,H,hd) one token per row; k/v: (B,S,KV,hd) cache (the slot at
    index lengths[b] must already hold the new token's k/v);
    lengths: (B,) int32. Returns (B,H,hd)."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    assert s % bk == 0, (s, bk)
    nk = s // bk
    scale = hd ** -0.5

    qf = q.reshape(b, kv, g, hd).reshape(b * kv, g, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
    lf = jnp.repeat(lengths.astype(jnp.int32), kv)

    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, nk=nk, scale=scale, window=window),
        grid=(b * kv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda bh, ik: (bh,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, hd), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bh, ik: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lf, qf, kf, vf)
    return out.reshape(b, kv, g, hd).reshape(b, h, hd)
