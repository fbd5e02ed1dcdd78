"""Attention blocks: GQA (optionally biased / QK-normed / sliding-window),
DeepSeek-V2 MLA (latent KV), and cross-attention for enc-dec models.

Conventions
-----------
- Full-sequence path (train / prefill): ``apply_attention(... , kv_write=...)``
  returns ``(out, (k, v))`` so the caller can populate a KV cache.
- Decode path: ``gqa_decode`` / ``mla_decode`` take a cache of fixed length
  ``S_max`` (one layer's, or the layer stack's with the layer's index),
  per-sequence fill ``lengths (B,)``, write the new token's K/V rows at
  index ``lengths`` (``write_rows``) and attend over the valid prefix
  (+ itself).
- Long sequences use a q-block-chunked computation (lax.scan over query
  blocks) so the score matrix never materialises at (S, S) — the pure-JAX
  analogue of the Pallas flash kernel in ``repro.kernels``.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import _init_w, apply_norm
from repro.models.rope import apply_rope, yarn_mscale

Params = Dict[str, jnp.ndarray]

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# §Perf T2: q-block-chunked attention whenever S ≥ 4096 (was: only > 4096)
# — the unchunked 4k train path materialized (B,S,H,S) f32 scores: 108 GiB
# of temp per device on qwen1.5-4b train_4k, 7× over v5e HBM.
CHUNK_THRESHOLD = 2048
Q_CHUNK = 1024


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype, *, cross: bool = False,
             d_model: Optional[int] = None, num_heads: Optional[int] = None,
             head_dim: Optional[int] = None,
             num_kv_heads: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    h = num_heads or cfg.num_heads
    kv = num_kv_heads or cfg.num_kv_heads
    hd = head_dim or cfg.head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": _init_w(ks[0], (d, h, hd), dtype),
        "wk": _init_w(ks[1], (d, kv, hd), dtype),
        "wv": _init_w(ks[2], (d, kv, hd), dtype),
        "wo": _init_w(ks[3], (h, hd, d), dtype, scale=(h * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype=dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype=dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype=dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype=dtype)
        p["k_norm"] = jnp.ones((hd,), dtype=dtype)
    return p


def init_mla(key, cfg: ModelConfig, dtype) -> Params:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": _init_w(ks[0], (d, h, qd), dtype),
        "w_dkv": _init_w(ks[1], (d, m.kv_lora_rank), dtype),
        "w_kpe": _init_w(ks[2], (d, m.qk_rope_head_dim), dtype),
        "norm_ckv": jnp.ones((m.kv_lora_rank,), dtype=dtype),
        "w_uk": _init_w(ks[3], (m.kv_lora_rank, h, m.qk_nope_head_dim),
                        dtype),
        "w_uv": _init_w(ks[4], (m.kv_lora_rank, h, m.v_head_dim), dtype),
        "wo": _init_w(ks[5], (h, m.v_head_dim, d),
                      dtype, scale=(h * m.v_head_dim) ** -0.5),
    }


# ---------------------------------------------------------------------------
# core scaled-dot-product with GQA grouping
# ---------------------------------------------------------------------------

def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray,
                scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,S,H,hd), k: (B,T,KV,hd) -> scores (B,S,H,T) in f32, times
    ``scale`` (default hd^-½).

    Low-precision operands feed the dot directly (MXU-native bf16 with f32
    accumulation via preferred_element_type) — §Perf D3: an explicit
    .astype(f32) on the KV cache materialized full-size f32 copies and
    tripled decode HBM traffic.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qr = q.reshape(b, s, kv, g, hd)
    sc = jnp.einsum("bskgh,btkh->bskgt", qr, k,
                    preferred_element_type=jnp.float32)
    return sc.reshape(b, s, h, k.shape[1]) * (
        hd ** -0.5 if scale is None else scale)


def _gqa_out(p_attn: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """p_attn: (B,S,H,T) f32, v: (B,T,KV,hd) -> (B,S,H,hd) f32."""
    b, s, h, t = p_attn.shape
    kv = v.shape[2]
    g = h // kv
    # match the value dtype for the dot (bf16 probs on bf16 caches); keep
    # f32 accumulation via preferred_element_type
    pa = p_attn.astype(v.dtype).reshape(b, s, kv, g, t)
    out = jnp.einsum("bskgt,btkh->bskgh", pa, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, v.shape[3])


def _masked_softmax(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    e = jnp.where(mask, e, 0.0)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)


def _mask(pos_q: jnp.ndarray, pos_k: jnp.ndarray, *, causal: bool,
          window: int, kv_len: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Boolean mask (…, S, T). pos_q: (S,) or (B,S); pos_k: (T,) or (B,T)."""
    pq = pos_q[..., :, None]
    pk = pos_k[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(pq.shape, pk.shape), dtype=bool)
    if causal:
        m &= pk <= pq
    if window:
        m &= pq - pk < window
    if kv_len is not None:
        m &= pk < kv_len[..., None, None]
    return m


def sdpa(q, k, v, mask, scale: Optional[float] = None) -> jnp.ndarray:
    """Full (non-chunked) masked attention. mask broadcast to (B,S,1,T)."""
    scores = _gqa_scores(q, k, scale)
    p = _masked_softmax(scores, mask[..., :, None, :]
                        if mask.ndim == q.ndim - 1 else mask)
    return _gqa_out(p, v).astype(q.dtype)


def chunked_sdpa(q, k, v, pos_q, pos_k, *, causal: bool, window: int,
                 q_chunk: int = Q_CHUNK,
                 scale: Optional[float] = None) -> jnp.ndarray:
    """Query-block-chunked attention: score matrix is (chunk, T) at a time.

    pos_q/pos_k must be 1-D (shared across batch) for this path.
    """
    b, s, h, hd = q.shape
    hd_v = v.shape[-1]
    n = s // q_chunk
    assert s % q_chunk == 0, f"seq {s} not divisible by q_chunk {q_chunk}"
    qs = q.reshape(b, n, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pqs = pos_q.reshape(n, q_chunk)

    def body(_, xs):
        qc, pq = xs
        mask = _mask(pq, pos_k, causal=causal, window=window, kv_len=None)
        out = sdpa(qc, k, v, mask[None], scale)
        return None, out

    _, outs = jax.lax.scan(body, None, (qs, pqs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd_v)


# ---------------------------------------------------------------------------
# GQA full-sequence / decode
# ---------------------------------------------------------------------------

def _shard_heads(x: jnp.ndarray) -> jnp.ndarray:
    """§Perf T1: pad-shard the head axis of (B,S,H,hd) activations over the
    model mesh axis (set REPRO_SHARD_HEADS_AXIS=model in mesh programs).
    Uneven head counts (qwen4b 20, phi4 kv 8) are padded by GSPMD — far
    cheaper than the replicated score tensors head_dim-sharding caused."""
    axis = os.environ.get("REPRO_SHARD_HEADS_AXIS")
    if not axis:
        return x
    # UNCONSTRAINED on every other dim: pinning them to None would REPLICATE
    # the batch axis — GSPMD then all-gathered the full global batch
    # (measured 20 GB/layer on qwen1.5-4b train_4k, §Perf T1c refutation).
    u = PartitionSpec.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        x, PartitionSpec(*([u] * (x.ndim - 2)), axis, u))


def _project_qkv(p: Params, cfg: ModelConfig, x, positions, *,
                 rope: bool = True):
    q = _shard_heads(jnp.einsum("bsd,dhk->bshk", x, p["wq"]))
    k = _shard_heads(jnp.einsum("bsd,dhk->bshk", x, p["wk"]))
    v = _shard_heads(jnp.einsum("bsd,dhk->bshk", x, p["wv"]))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = apply_norm({"scale": p["q_norm"]}, q, "rmsnorm")
        k = apply_norm({"scale": p["k_norm"]}, k, "rmsnorm")
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta,
                       cfg.partial_rotary_factor, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta,
                       cfg.partial_rotary_factor, cfg.rope_scaling)
    return q, k, v


def gqa_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, *, causal: bool = True,
                window: int = 0, rope: bool = True
                ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention. positions: (S,). Returns (out, (k, v))."""
    q, k, v = _project_qkv(p, cfg, x, positions, rope=rope)
    kc, vc = k, v                    # cache keeps the compact GQA layout
    if os.environ.get("REPRO_SHARD_HEADS_AXIS") and k.shape[2] < q.shape[2]:
        # §Perf T5: under head sharding, the (kv, group)-factorized score
        # einsum gives GSPMD conflicting axis shardings (involuntary full
        # rematerialization + 24 GB score all-gathers on internvl2 kv=2).
        # Repeating k/v to the full head count keeps one clean head axis;
        # the repeated activations are small next to the scores.
        g = q.shape[2] // k.shape[2]
        k = _shard_heads(jnp.repeat(k, g, axis=2))
        v = _shard_heads(jnp.repeat(v, g, axis=2))
    s = x.shape[1]
    if s > CHUNK_THRESHOLD and positions.ndim == 1:
        out = chunked_sdpa(q, k, v, positions, positions,
                           causal=causal, window=window)
    else:
        mask = _mask(positions, positions, causal=causal, window=window,
                     kv_len=None)
        out = sdpa(q, k, v, mask[None])
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (kc, vc)


def gqa_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray,
               cache: Dict[str, jnp.ndarray], lengths: jnp.ndarray, *,
               window: int = 0, rope: bool = True,
               layer: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-token decode. x: (B,1,d); cache k/v: (B,S_max,KV,hd)
    (bf16/f32, or int8 + per-slot scales when kv_quantized()), or the
    layer stack's (R,B,S_max,KV,hd) with this block's index ``layer``.
    Writes the new token's rows (``write_rows``) and attends over this
    layer's cache; returns the written k/v leaves (stacked if given so)."""
    q, k_new, v_new = _project_qkv(p, cfg, x, lengths[:, None], rope=rope)
    if "k_scale" in cache:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        rows = {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    else:
        rows = {"k": k_new, "v": v_new}
    new_cache = {n: write_rows(cache[n], r, lengths, layer)
                 for n, r in rows.items()}
    c = {n: layer_view(t, layer) for n, t in new_cache.items()}
    t = c["k"].shape[1]
    pos_k = jnp.arange(t)[None, :]                      # (1, T)
    mask = _mask(lengths[:, None], pos_k, causal=True, window=window,
                 kv_len=None)                           # (B, 1, T)
    if "k_scale" in c:
        # dequant-fused dots: scores[t] = (q·k_i8[t])·kscale[t];
        # out = Σ_t (p[t]·vscale[t])·v_i8[t] — scales factor out of the dot
        kc = c["k"]
        sc = _gqa_scores(q, kc.astype(q.dtype))
        kv = kc.shape[2]
        g = q.shape[2] // kv
        ksc = jnp.repeat(c["k_scale"][..., 0], g, axis=2) \
            if g > 1 else c["k_scale"][..., 0]
        sc = sc * ksc.transpose(0, 2, 1)[:, None, :, :]
        pattn = _masked_softmax(sc, mask[:, :, None, :])
        vsc = jnp.repeat(c["v_scale"][..., 0], g, axis=2) \
            if g > 1 else c["v_scale"][..., 0]
        pattn = pattn * vsc.transpose(0, 2, 1)[:, None, :, :]
        out = _gqa_out(pattn, c["v"].astype(q.dtype)).astype(q.dtype)
    else:
        out = sdpa(q, c["k"], c["v"], mask)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def kv_quantized() -> bool:
    """§Perf P5: int8 KV cache (REPRO_KV_INT8=1) — halves decode cache
    bytes; per-(position, kv-head) scales keep the dot factorable."""
    return os.environ.get("REPRO_KV_INT8") == "1"


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (..., hd) -> (int8 codes, f32 scale (..., 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def time_axis_may_be_sharded() -> bool:
    """True when the trace runs under a mesh of more than one device
    (``jax.set_mesh``): there the decode cache's time axis may be split
    over devices (``launch.sharding.cache_specs`` puts it on "model")."""
    mesh = jax.sharding.get_abstract_mesh()
    return not mesh.empty and mesh.size > 1


def layer_view(cache: jnp.ndarray,
               layer: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Layer ``layer`` of a stacked (R,B,S,...) cache leaf; the leaf
    itself when ``layer`` is None."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def write_rows(cache: jnp.ndarray, new: jnp.ndarray, lengths: jnp.ndarray,
               layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Write new (B,1,...) into cache (B,S,...) at time lengths[b] of row
    b, or into layer ``layer`` of a stacked (R,B,S,...) cache.

    Where the time axis is whole on the device this is a scatter of the B
    rows, in place on a loop-carried cache: the rest of the cache is
    neither read nor copied. A row whose length is past the end is not
    written. Where a mesh may shard the time axis, a layer's own cache
    (``layer`` None) is rewritten by ``_scatter_time``'s mask-select."""
    if time_axis_may_be_sharded():
        return _scatter_time(cache, new, lengths)
    rows = jnp.arange(new.shape[0])
    idx = (rows, lengths) if layer is None else (layer, rows, lengths)
    return cache.at[idx].set(new[:, 0].astype(cache.dtype), mode="drop")


def _scatter_time(cache: jnp.ndarray, new: jnp.ndarray,
                  lengths: jnp.ndarray) -> jnp.ndarray:
    """Write new (B,1,...) into cache (B,S,...) at per-row index lengths,
    as a mask-select over every position of the cache.

    Used only where the time axis may be sharded over a mesh
    (``write_rows``, ``time_axis_may_be_sharded``): a mask-select
    partitions cleanly when the cache is sequence-sharded (§Perf D1). A
    vmapped dynamic_update_slice was tried there (§Perf D2) and REFUTED:
    GSPMD turns the dynamic index on the sharded dim into all-gathers
    (bytes 6.4e10 → 1.25e11 on qwen4b decode_32k; the row scatter on
    qwen1.5-0.5b decode_32k: bytes 1.13e10 → 2.31e10, all-gathers
    9.8e4 → 1.15e6 B, 16x16 dry run). On one device it costs a read and a
    write of the whole layer cache per step, which the in-place row write
    avoids.
    """
    t = cache.shape[1]
    mask = (jnp.arange(t)[None, :] == lengths[:, None])      # (B, S)
    mask = mask.reshape(mask.shape + (1,) * (cache.ndim - 2))
    return jnp.where(mask, new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_kv(p: Params, enc: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = jnp.einsum("btd,dhk->bthk", enc, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


def cross_attend(p: Params, x: jnp.ndarray, k: jnp.ndarray,
                 v: jnp.ndarray) -> jnp.ndarray:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    t = k.shape[1]
    mask = jnp.ones((1, x.shape[1], t), dtype=bool)
    out = sdpa(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 latent attention)
# ---------------------------------------------------------------------------

def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale of MLA: (qk_nope + qk_rope)^-½, times mscale² of
    ``mscale_all_dim`` under YaRN (DeepSeek-V2's published attention)."""
    m, rs = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_q(p: Params, cfg: ModelConfig, x, positions):
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_pe = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta,
                      scaling=cfg.rope_scaling)
    return q_nope, q_pe


def _mla_latent(p: Params, cfg: ModelConfig, x, positions):
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv = apply_norm({"scale": p["norm_ckv"]}, c_kv, "rmsnorm")
    k_pe = jnp.einsum("bsd,dr->bsr", x, p["w_kpe"])[:, :, None, :]
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta,
                      scaling=cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_pe


def mla_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, *, causal: bool = True,
                window: int = 0
                ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence MLA (expanded form). Returns (out, (c_kv, k_pe))."""
    m = cfg.mla
    q_nope, q_pe = _mla_q(p, cfg, x, positions)
    c_kv, k_pe = _mla_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    # concat nope+rope per head; k_pe broadcast over heads
    h = cfg.num_heads
    k_pe_h = jnp.broadcast_to(k_pe[:, :, None, :],
                              k_nope.shape[:3] + (m.qk_rope_head_dim,))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_h], axis=-1)
    s = x.shape[1]
    scale = mla_scale(cfg)
    if s > CHUNK_THRESHOLD and positions.ndim == 1:
        out = chunked_sdpa(q, k, v, positions, positions, causal=causal,
                           window=window, scale=scale)
    else:
        mask = _mask(positions, positions, causal=causal, window=window,
                     kv_len=None)
        out = sdpa(q, k, v, mask[None], scale)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (c_kv, k_pe)


def mla_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray,
               cache: Dict[str, jnp.ndarray], lengths: jnp.ndarray, *,
               window: int = 0, layer: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Absorbed-form MLA decode: attention runs in the latent space.

    cache: {"c_kv": (B,S,rank), "k_pe": (B,S,rope)}, or the layer stack's
    (R,B,S,·) with this block's index ``layer`` (as ``gqa_decode``).
    score[h,t] = q_nope[h]·(W_uk[h] c_kv[t]) + q_pe[h]·k_pe[t]
               = (q_nope[h] W_uk[h]) · c_kv[t] + q_pe[h]·k_pe[t]
    out[h]     = Σ_t p[t] (W_uv[h] c_kv[t]) = W_uv[h] (Σ_t p[t] c_kv[t]).
    """
    q_nope, q_pe = _mla_q(p, cfg, x, lengths[:, None])
    c_new, kpe_new = _mla_latent(p, cfg, x, lengths[:, None])
    new_cache = {"c_kv": write_rows(cache["c_kv"], c_new, lengths, layer),
                 "k_pe": write_rows(cache["k_pe"], kpe_new, lengths, layer)}
    c_cache = layer_view(new_cache["c_kv"], layer)
    kpe_cache = layer_view(new_cache["k_pe"], layer)
    # absorb W_uk into q:  (B,1,H,nope) x (rank,H,nope) -> (B,1,H,rank)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope.astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))
    sc = jnp.einsum("bshr,btr->bsht", q_abs,
                    c_cache.astype(jnp.float32))
    sc += jnp.einsum("bshk,btk->bsht", q_pe.astype(jnp.float32),
                     kpe_cache.astype(jnp.float32))
    sc *= mla_scale(cfg)
    t = c_cache.shape[1]
    mask = _mask(lengths[:, None], jnp.arange(t)[None, :], causal=True,
                 window=window, kv_len=None)             # (B,1,T)
    pattn = _masked_softmax(sc, mask[:, :, None, :])
    ctx = jnp.einsum("bsht,btr->bshr", pattn, c_cache.astype(jnp.float32))
    out = jnp.einsum("bshr,rhk->bshk", ctx,
                     p["w_uv"].astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache
