"""Model assembly: dense / MoE / SSM / hybrid / enc-dec / VLM transformers.

Layer stacks are grouped into a repeating pattern of period ``p`` (dense: 1,
DeepSeek-V2: 1 after a leading dense layer, Jamba: 8) and executed with
``lax.scan`` over the repeats — one compiled block body regardless of depth,
which keeps multi-pod lowering tractable for 64-layer models.

Public API (used by registry / launch / serving):
    init_params(cfg, key)                      -> params
    forward(cfg, params, batch, window=0)      -> (logits, aux_loss)
    prefill(cfg, params, batch, cache_len, window=0) -> (logits, cache)
    decode_step(cfg, params, tokens, cache, lengths, window=0)
                                               -> (logits, cache)
    (``expert_slots=True``: + the slots each held expert received, per
    MoE layer)
    init_cache(cfg, batch, cache_len)          -> cache pytree
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2 as ssm
from repro.models.layers import (_dtype, apply_mlp, apply_norm, embed,
                                 init_embedding, init_mlp, init_norm,
                                 unembed, _init_w)
from repro.models.moe import apply_moe, init_moe

Params = Dict[str, Any]


def _scan_unroll() -> Any:
    """Scan unroll factor for the layer stack. The dry-run sets
    REPRO_SCAN_UNROLL=full so XLA's cost analysis (which counts while-loop
    bodies once, not ×trip-count) sees every layer's flops/bytes."""
    v = os.environ.get("REPRO_SCAN_UNROLL", "1")
    return True if v == "full" else int(v)


def _remat_group(r: int) -> int:
    """§Perf P2: group size for two-level (√L) rematerialization. 0/1 =
    single-level. Chooses the largest divisor of r not exceeding the
    requested group (default off; the dry-run sets REPRO_REMAT_GROUP)."""
    want = int(os.environ.get("REPRO_REMAT_GROUP", "0") or 0)
    if want <= 1 or r <= 2:
        return 1
    g = min(want, r)
    while r % g:
        g -= 1
    return g


def _shard_seq(x: jnp.ndarray) -> jnp.ndarray:
    """§Perf T3 (sequence parallelism, Korthikanti et al.): between blocks
    the residual stream is sharded on the sequence axis over the model
    axis (REPRO_SHARD_SEQ_AXIS=model). Norm/residual elementwise work runs
    on 1/|model| of the tokens and GSPMD converts the tensor-parallel
    all-reduces into cheaper reduce-scatter / all-gather pairs."""
    axis = os.environ.get("REPRO_SHARD_SEQ_AXIS")
    if not axis or x.ndim != 3 or x.shape[1] % 16:
        return x
    u = jax.sharding.PartitionSpec.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(u, axis, u))


# ---------------------------------------------------------------------------
# Layer pattern
# ---------------------------------------------------------------------------

def layer_specs(cfg: ModelConfig) -> List[Tuple[str, bool]]:
    kinds = cfg.layer_kinds()
    moes = cfg.moe_layers()
    return list(zip(kinds, moes))


def split_pattern(cfg: ModelConfig) -> Tuple[int, int, int]:
    """Return (n_lead, period, repeats) for the layer stack."""
    specs = layer_specs(cfg)
    lead = cfg.moe.first_dense if cfg.moe else 0
    rest = specs[lead:]
    p = cfg.attn_layer_period or 1
    if cfg.moe and cfg.moe.moe_layer_period > 1:
        p = math.lcm(p, cfg.moe.moe_layer_period)
    assert len(rest) % p == 0, (cfg.name, len(rest), p)
    for i, s in enumerate(rest):
        assert s == rest[i % p], f"{cfg.name}: stack not periodic at {i}"
    return lead, p, len(rest) // p


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, moe_flag: bool, dtype,
               *, cross: bool = False) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {"norm1": init_norm(ks[0], cfg.d_model, cfg.norm, dtype)}
    if kind == "attn":
        if cfg.mla is not None:
            p["attn"] = attn.init_mla(ks[1], cfg, dtype)
        else:
            p["attn"] = attn.init_gqa(ks[1], cfg, dtype)
        if cross:
            p["norm_x"] = init_norm(ks[2], cfg.d_model, cfg.norm, dtype)
            p["xattn"] = attn.init_gqa(ks[2], cfg, dtype, cross=True)
    else:
        p["ssm"] = ssm.init_mamba2(ks[1], cfg.d_model, cfg.ssm, dtype)
    if moe_flag or cfg.d_ff:
        p["norm2"] = init_norm(ks[3], cfg.d_model, cfg.norm, dtype)
        if moe_flag:
            p["ffn"] = init_moe(ks[4], cfg.d_model, cfg.moe, cfg.activation,
                                dtype)
        else:
            p["ffn"] = init_mlp(ks[4], cfg.d_model, cfg.d_ff, cfg.activation,
                                dtype)
    return p


def _pad_time(x: jnp.ndarray, target: int) -> jnp.ndarray:
    """Pad axis 1 (time) of x up to `target`."""
    if x.shape[1] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, target - x.shape[1])
    return jnp.pad(x, pad)


def apply_block(cfg: ModelConfig, bp: Params, kind: str, moe_flag: bool,
                x: jnp.ndarray, *, mode: str,
                positions: Optional[jnp.ndarray] = None,
                lengths: Optional[jnp.ndarray] = None,
                cache: Optional[Params] = None,
                cache_len: int = 0, window: int = 0, causal: bool = True,
                cross_enc: Optional[jnp.ndarray] = None,
                layer: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray,
                           Optional[jnp.ndarray]]:
    """Apply one block. mode: 'full' | 'prefill' | 'decode'. Returns (x,
    cache, aux loss, slots each held expert received or None).

    In decode, ``cache`` is this block's cache, or with ``layer`` given
    the layer stack's, whose leaves lead with the layer axis; the block
    writes its own rows and state into it and returns it."""
    aux = jnp.zeros((), jnp.float32)
    slots = None
    new_cache: Optional[Params] = None
    h = apply_norm(bp["norm1"], x, cfg.norm)
    rope = not cfg.learned_positions
    if kind == "attn":
        if mode == "decode":
            if cfg.mla is not None:
                a, kv = attn.mla_decode(bp["attn"], cfg, h, cache, lengths,
                                        window=window, layer=layer)
            else:
                a, kv = attn.gqa_decode(bp["attn"], cfg, h, cache,
                                        lengths, window=window, rope=rope,
                                        layer=layer)
            new_cache = dict(cache)
            new_cache.update(kv)
        else:
            if cfg.mla is not None:
                a, (c_kv, k_pe) = attn.mla_forward(
                    bp["attn"], cfg, h, positions, causal=causal,
                    window=window)
                if mode == "prefill":
                    new_cache = {"c_kv": _pad_time(c_kv, cache_len),
                                 "k_pe": _pad_time(k_pe, cache_len)}
            else:
                a, (k, v) = attn.gqa_forward(
                    bp["attn"], cfg, h, positions, causal=causal,
                    window=window, rope=rope)
                if mode == "prefill":
                    if attn.kv_quantized():
                        kq, ks = attn.quantize_kv(k)
                        vq, vs = attn.quantize_kv(v)
                        new_cache = {
                            "k": _pad_time(kq, cache_len),
                            "k_scale": _pad_time(ks, cache_len),
                            "v": _pad_time(vq, cache_len),
                            "v_scale": _pad_time(vs, cache_len)}
                    else:
                        new_cache = {"k": _pad_time(k, cache_len),
                                     "v": _pad_time(v, cache_len)}
        x = x + a
        if "xattn" in bp:
            hx = apply_norm(bp["norm_x"], x, cfg.norm)
            if mode == "decode":       # read-only: stays in the cache
                ck = attn.layer_view(cache["cross_k"], layer)
                cv = attn.layer_view(cache["cross_v"], layer)
            else:
                ck, cv = attn.cross_kv(bp["xattn"], cross_enc)
                if mode == "prefill":
                    new_cache["cross_k"] = ck
                    new_cache["cross_v"] = cv
            x = x + attn.cross_attend(bp["xattn"], hx, ck, cv)
    else:
        if mode == "decode":            # small state: rewritten whole
            a, st = ssm.mamba2_decode(
                bp["ssm"], cfg.d_model, cfg.ssm, h,
                jax.tree.map(lambda c: attn.layer_view(c, layer), cache))
            new_cache = st if layer is None else jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n, layer, 0), cache, st)
        else:
            a, sc = ssm.mamba2_forward(bp["ssm"], cfg.d_model, cfg.ssm, h)
            if mode == "prefill":
                new_cache = sc
        x = x + a
    if "ffn" in bp:
        h2 = apply_norm(bp["norm2"], x, cfg.norm)
        if moe_flag:
            f, aux, slots = apply_moe(bp["ffn"], cfg.moe, h2, cfg.activation)
        else:
            f = apply_mlp(bp["ffn"], h2, cfg.activation)
        x = x + f
    return x, new_cache, aux, slots


# ---------------------------------------------------------------------------
# Cache init (abstract-shape friendly)
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                 dtype, *, cross: bool = False) -> Params:
    if kind == "attn":
        if cfg.mla is not None:
            m = cfg.mla
            c = {"c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
                 "k_pe": jnp.zeros((batch, cache_len, m.qk_rope_head_dim),
                                   dtype)}
        elif attn.kv_quantized():
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            c = {"k": jnp.zeros((batch, cache_len, kv, hd), jnp.int8),
                 "k_scale": jnp.zeros((batch, cache_len, kv, 1),
                                      jnp.float32),
                 "v": jnp.zeros((batch, cache_len, kv, hd), jnp.int8),
                 "v_scale": jnp.zeros((batch, cache_len, kv, 1),
                                      jnp.float32)}
        else:
            c = {"k": jnp.zeros((batch, cache_len, cfg.num_kv_heads,
                                 cfg.head_dim), dtype),
                 "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads,
                                 cfg.head_dim), dtype)}
        if cross:
            e = cfg.encoder
            c["cross_k"] = jnp.zeros((batch, e.n_ctx, cfg.num_heads,
                                      cfg.head_dim), dtype)
            c["cross_v"] = jnp.zeros((batch, e.n_ctx, cfg.num_heads,
                                      cfg.head_dim), dtype)
        return c
    s = cfg.ssm
    return {"conv_x": jnp.zeros((batch, s.d_conv - 1,
                                 s.d_inner(cfg.d_model)), dtype),
            "conv_bc": jnp.zeros((batch, s.d_conv - 1,
                                  2 * s.n_groups * s.d_state), dtype),
            "ssm": jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim,
                              s.d_state), jnp.float32)}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Params:
    dtype = _dtype(cfg.dtype)
    lead, p, r = split_pattern(cfg)
    specs = layer_specs(cfg)
    cross = _is_encdec(cfg)
    cache: Params = {
        "lead": [_block_cache(cfg, specs[i][0], batch, cache_len, dtype,
                              cross=cross) for i in range(lead)],
        "stack": [
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (r,) + x.shape),
                _block_cache(cfg, specs[lead + j][0], batch, cache_len,
                             dtype, cross=cross))
            for j in range(p)
        ],
    }
    return cache


def _is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encoder is not None and cfg.encoder.num_layers > 0


# ---------------------------------------------------------------------------
# Params init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg.dtype)
    lead, p, r = split_pattern(cfg)
    specs = layer_specs(cfg)
    cross = _is_encdec(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "norm_f": init_norm(keys[1], cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _init_w(keys[2], (cfg.d_model, cfg.vocab_size),
                                    dtype)
    if cfg.learned_positions:
        params["pos_embed"] = init_embedding(
            keys[3], cfg.max_position_embeddings
            if cfg.max_position_embeddings <= 65536 else 65536,
            cfg.d_model, dtype)

    lk = jax.random.split(keys[4], max(lead, 1))
    params["lead"] = [
        init_block(lk[i], cfg, specs[i][0], specs[i][1], dtype, cross=cross)
        for i in range(lead)]

    stacks = []
    for j in range(p):
        kind, mf = specs[lead + j]
        per_rep = [init_block(jax.random.fold_in(keys[5], j * r + i), cfg,
                              kind, mf, dtype, cross=cross)
                   for i in range(r)]
        stacks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))
    params["stack"] = stacks

    if cross:
        e = cfg.encoder
        ek = jax.random.split(keys[6], e.num_layers + 2)
        enc_cfg = encoder_cfg(cfg)
        enc_blocks = [init_block(ek[i], enc_cfg, "attn", False, dtype)
                      for i in range(e.num_layers)]
        params["encoder"] = {
            "pos": init_embedding(ek[-2], e.n_ctx, enc_cfg.d_model, dtype),
            "stack": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_blocks),
            "norm": init_norm(ek[-1], enc_cfg.d_model, cfg.norm, dtype),
        }
    return params


def encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    e = cfg.encoder
    d = e.d_model or cfg.d_model
    h = e.num_heads or cfg.num_heads
    return ModelConfig(
        name="enc", family="dense", source="", num_layers=e.num_layers,
        d_model=d, num_heads=h, num_kv_heads=h, head_dim=d // h,
        d_ff=e.d_ff or cfg.d_ff, vocab_size=0, qkv_bias=cfg.qkv_bias,
        activation=cfg.activation, norm=cfg.norm, learned_positions=True)


# ---------------------------------------------------------------------------
# Encoder (whisper-style, over stub frame embeddings)
# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, params: Params, frames: jnp.ndarray,
           *, remat: bool = False) -> jnp.ndarray:
    enc = params["encoder"]
    ecfg = encoder_cfg(cfg)
    x = frames + enc["pos"][None, : frames.shape[1]]
    positions = jnp.arange(frames.shape[1])

    def body(h, bp):
        h, _, _, _ = apply_block(ecfg, bp, "attn", False, h, mode="full",
                                 positions=positions, causal=False)
        return h, None

    if remat:  # §Perf W1: un-remat'd encoder kept 24L of activations live
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, enc["stack"], unroll=_scan_unroll())
    return apply_norm(enc["norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# Main stack runner
# ---------------------------------------------------------------------------

def _run_stack(cfg: ModelConfig, params: Params, x: jnp.ndarray, *,
               mode: str, positions=None, lengths=None, cache=None,
               cache_len: int = 0, window: int = 0, cross_enc=None,
               remat: bool = False):
    """Returns (x, cache, aux loss, slots): ``slots`` (MoE layers, held)
    int32 counts the slots each held expert received, layer by layer
    (None without MoE layers)."""
    lead, p, r = split_pattern(cfg)
    specs = layer_specs(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Params = {"lead": [], "stack": []}
    slots = []

    for i in range(lead):
        c = cache["lead"][i] if cache is not None else None
        x, nc, aux, sl = apply_block(
            cfg, params["lead"][i], specs[i][0], specs[i][1], x, mode=mode,
            positions=positions, lengths=lengths, cache=c,
            cache_len=cache_len, window=window, cross_enc=cross_enc)
        aux_total += aux
        new_cache["lead"].append(nc)
        if sl is not None:
            slots.append(sl[None])

    offsets = [specs[lead + j] for j in range(p)]
    # Decode on a device that holds the cache's time axis whole carries the
    # stacked cache through the layer scan: each block writes its new rows
    # in place at its layer index, so no per-step op stacks, selects over
    # or copies a whole cache. Where a mesh may shard that axis, decode
    # reads the cache as xs and emits each layer's mask-selected cache as
    # ys, as prefill emits its cache (§Perf D2, attention.write_rows).
    carried = mode == "decode" and not attn.time_axis_may_be_sharded()

    def body(carry, xs):
        h, stack = carry
        bps, i, cs = xs
        cs = list(stack if carried else cs)
        aux = jnp.zeros((), jnp.float32)
        sls = []
        for j in range(p):
            kind, mf = offsets[j]
            h = _shard_seq(h)
            h, cs[j], a, sl = apply_block(
                cfg, bps[j], kind, mf, h, mode=mode, positions=positions,
                lengths=lengths, cache=cs[j], cache_len=cache_len,
                window=window, cross_enc=cross_enc,
                layer=i if carried else None)
            aux += a
            if sl is not None:
                sls.append(sl)
        stats = (aux, tuple(sls))
        out = stats if carried or mode == "full" else (tuple(cs), stats)
        return (h, tuple(cs) if carried else None), out

    if remat:
        body = jax.checkpoint(body)

    xs_cache = (tuple(cache["stack"]) if mode == "decode" and not carried
                else (None,) * p)
    xs = (tuple(params["stack"]), jnp.arange(r), xs_cache)
    carry = (x, tuple(cache["stack"]) if carried else None)

    group = _remat_group(r) if (remat and mode == "full") else 1
    if group > 1:
        # §Perf P2 (√L remat): outer scan over R/g checkpointed groups,
        # inner scan over g layer-periods — saved residuals drop from R·x
        # to (R/g + g)·x at the cost of one extra recompute level.
        xs_g = jax.tree.map(
            lambda t: t.reshape((r // group, group) + t.shape[1:]), xs)

        @jax.checkpoint
        def outer(c, xsg):
            return jax.lax.scan(body, c, xsg, unroll=_scan_unroll())

        (x, stack), ys = jax.lax.scan(outer, carry, xs_g,
                                      unroll=_scan_unroll())
        ys = jax.tree.map(lambda t: t.reshape((r,) + t.shape[2:]), ys)
    else:
        (x, stack), ys = jax.lax.scan(body, carry, xs, unroll=_scan_unroll())
    if carried:
        new_cache["stack"] = list(stack)
        stats = ys
    elif mode == "full":
        new_cache = None
        stats = ys
    else:
        new_cache["stack"] = list(ys[0])
        stats = ys[1]
    aux_total += jnp.sum(stats[0])
    if stats[1]:                # (repeats, MoE blocks a period, held)
        st = jnp.stack(stats[1], axis=1)
        slots.append(st.reshape((-1,) + st.shape[2:]))
    slots = jnp.concatenate(slots) if slots else None
    return x, new_cache, aux_total, slots


def _embed_in(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
              positions) -> jnp.ndarray:
    x = embed(params["embed"], tokens)
    if cfg.learned_positions:
        x = x + jnp.take(params["pos_embed"], positions, axis=0)
    return x


def _logits(cfg: ModelConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = apply_norm(params["norm_f"], x, cfg.norm)
    if cfg.tie_embeddings:
        return unembed(params["embed"], x, tied=True)
    return unembed(params["unembed"], x, tied=False)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward_hidden(cfg: ModelConfig, params: Params,
                   batch: Dict[str, jnp.ndarray], *, window: int = 0,
                   remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like forward() but stops before the unembedding: returns the final
    (pre-norm_f) hidden states — the §Perf P1 chunked-cross-entropy path
    computes logits per sequence chunk from these instead of
    materializing (B,S,V)."""
    logits, aux = forward(cfg, params, batch, window=window, remat=remat,
                          _return_hidden=True)
    return logits, aux


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            *, window: int = 0, remat: bool = False,
            _return_hidden: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward (training). batch: tokens (B,S) [+ frames /
    patch_embeds]. Returns (logits (B,S',V), aux_loss)."""
    tokens = batch["tokens"]
    s = tokens.shape[1]
    cross_enc = None
    if _is_encdec(cfg):
        cross_enc = encode(cfg, params, batch["frames"], remat=remat)
        positions = jnp.arange(s)
        x = _embed_in(cfg, params, tokens, positions)
    elif cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"]
        positions = jnp.arange(pe.shape[1] + s)
        x = jnp.concatenate(
            [pe.astype(_dtype(cfg.dtype)),
             _embed_in(cfg, params, tokens, positions[pe.shape[1]:])],
            axis=1)
    else:
        positions = jnp.arange(s)
        x = _embed_in(cfg, params, tokens, positions)
    x, _, aux, _ = _run_stack(cfg, params, x, mode="full",
                              positions=positions, window=window,
                              cross_enc=cross_enc, remat=remat)
    if _return_hidden:
        return x, aux
    return _logits(cfg, params, x), aux


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            cache_len: int, *, window: int = 0, expert_slots: bool = False
            ) -> Tuple:
    tokens = batch["tokens"]
    s = tokens.shape[1]
    cross_enc = None
    if _is_encdec(cfg):
        cross_enc = encode(cfg, params, batch["frames"])
    positions = jnp.arange(s)
    x = _embed_in(cfg, params, tokens, positions)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"]
        positions = jnp.arange(pe.shape[1] + s)
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
    x, cache, _, slots = _run_stack(cfg, params, x, mode="prefill",
                                    positions=positions, cache_len=cache_len,
                                    window=window, cross_enc=cross_enc)
    logits = _logits(cfg, params, x[:, -1:])
    return (logits, cache, slots) if expert_slots else (logits, cache)


def decode_step(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, lengths: jnp.ndarray, *, window: int = 0,
                expert_slots: bool = False) -> Tuple:
    """tokens: (B,1); lengths: (B,) current fill of each cache row."""
    positions = lengths[:, None]
    if cfg.learned_positions:
        positions = jnp.clip(positions, 0, params["pos_embed"].shape[0] - 1)
    x = _embed_in(cfg, params, tokens, positions)
    x, new_cache, _, slots = _run_stack(cfg, params, x, mode="decode",
                                        lengths=lengths, cache=cache,
                                        window=window)
    logits = _logits(cfg, params, x)
    return (logits, new_cache, slots) if expert_slots \
        else (logits, new_cache)
