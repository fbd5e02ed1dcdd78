"""Pallas TPU flash attention (prefill): blocked causal attention with
online softmax.

Tiling: grid = (B·H, S/bq, S/bk); the kv axis is innermost/sequential, so
the per-(head, q-block) running max / denominator / accumulator live in VMEM
scratch across kv steps. Block shapes are MXU-aligned (multiples of 128 at
production sizes; tests sweep smaller interpret-mode tiles).

Supports GQA (kv-head folding via the k/v index maps), causal masking and
sliding-window masking — the long-context serving configuration.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, nk: int, scale: float, causal: bool,
            window: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

    pos_q = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    pos_k = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= pos_k <= pos_q
    if window:
        mask &= pos_q - pos_k < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / (l_ref[...] + 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B,S,H,hd); k/v: (B,S,KV,hd). Returns (B,S,H,hd)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = hd ** -0.5

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        bb = bh // h
        hh = bh % h
        return (bb * kv + hh // g, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                          causal=causal, window=window),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
