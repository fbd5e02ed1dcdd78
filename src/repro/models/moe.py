"""Mixture-of-experts FFN: dropless, over the routed experts a layer holds.

The router scores every published expert in float32, and each token
takes its ``top_k`` experts by softmax probability (greedy); those
probabilities weight the experts' outputs as they are, unnormalised
(DeepSeek-V2's ``norm_topk_prob`` false, ``routed_scaling_factor`` 1).

A layer may hold a share of the routed experts, the contiguous range
``[expert_offset, expert_offset + held)`` of ``MoEConfig`` (expert
parallelism). It computes the (token, expert) slots whose expert it
holds and nothing else: what the other shares' experts add is theirs to
add. No slot is dropped. The held slots are sorted by expert and run
through one grouped matmul a projection (``jax.lax.ragged_dot``, which
the TPU compiler lowers to Mosaic calls named ``ragged-dot``); their
outputs, weighted, are added back to their tokens. Shared experts run on
every token.

Tokens are taken ``TOKEN_CHUNK`` at a time where a call has many of them
(prefill), so that the sorted copies stay small; each token's result is
the same either way.

Where the trace runs under a mesh of more than one device
(``experts_may_be_sharded``), ``launch.sharding`` puts the routed experts
on the "model" axis, and the sort, gather and grouped matmuls over them
partition badly: the dry runs of the MoE models on the 16x16 and
2x16x16 meshes read 4-9x the bytes and 30-60x the temporaries in
training, and up to 3.8x the all-gathers in prefill. There the layer keeps GShard's capacity dispatch (dense one-hot
dispatch/combine einsums over a per-group capacity axis, which shard
cleanly), holds every expert, and drops a slot beyond an expert's
capacity.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers import _init_w

Params = Dict[str, jnp.ndarray]

TOKEN_CHUNK = 4096
DEFAULT_GROUP = 2048        # tokens a dispatch group (capacity path)


def init_moe(key, d_model: int, moe: MoEConfig, activation: str,
             dtype) -> Params:
    ks = jax.random.split(key, 7)
    e, f = moe.held(), moe.d_expert
    p: Params = {
        "router": _init_w(ks[0], (d_model, moe.num_experts), jnp.float32),
        "w_gate": _init_w(ks[1], (e, d_model, f), dtype),
        "w_up": _init_w(ks[2], (e, d_model, f), dtype),
        "w_down": _init_w(ks[3], (e, f, d_model), dtype),
    }
    if moe.num_shared_experts:
        fs = moe.num_shared_experts * moe.d_shared
        p["shared"] = {
            "w_gate": _init_w(ks[4], (d_model, fs), dtype),
            "w_up": _init_w(ks[5], (d_model, fs), dtype),
            "w_down": _init_w(ks[6], (fs, d_model), dtype),
        }
    return p


def _act(x: jnp.ndarray, activation: str) -> jnp.ndarray:
    return jax.nn.silu(x) if activation == "swiglu" else jax.nn.gelu(x)


def _routed(p: Params, moe: MoEConfig, xf: jnp.ndarray, activation: str):
    """The held experts' part for tokens xf (T, d): out (T, d) f32, the
    slots each held expert received (held,) int32, and for the aux loss
    the router's probabilities (T, E) and the routed experts (T, K)."""
    t, d = xf.shape
    k, held = moe.top_k, moe.held()
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    local = top_idx - moe.expert_offset
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    # a token takes an expert once, so at most min(k, held) of its slots
    # are held here: the first t·min(k, held) of the sorted slots hold
    # every held slot, whatever the routing
    order = jnp.argsort(key, stable=True)[:t * min(k, held)]
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    tok = order // k
    xs = jnp.take(xf, tok, axis=0)
    gate = jax.lax.ragged_dot(xs, p["w_gate"], sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, p["w_up"], sizes,
                            preferred_element_type=jnp.float32)
    h = (_act(gate, activation) * up).astype(xf.dtype)
    y = jax.lax.ragged_dot(h, p["w_down"], sizes,
                           preferred_element_type=jnp.float32)
    w = jnp.take(top_p.reshape(-1), order)
    held_row = jnp.arange(order.shape[0]) < jnp.sum(sizes)
    y = jnp.where(held_row[:, None], y * w[:, None], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(y)
    return out, sizes, probs, top_idx


def experts_may_be_sharded() -> bool:
    """True when the trace runs under a mesh of more than one device
    (``jax.set_mesh``), where the routed experts may be split over
    devices (``launch.sharding.param_specs`` puts them on "model")."""
    mesh = jax.sharding.get_abstract_mesh()
    return not mesh.empty and mesh.size > 1


def _capacity(tokens_per_group: int, moe: MoEConfig) -> int:
    c = int(tokens_per_group * moe.top_k * moe.capacity_factor
            / moe.num_experts) + 1
    return max(4, c + (-c) % 4)


def _route(logits: jnp.ndarray, moe: MoEConfig, capacity: int
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """GShard top-k dispatch.

    logits: (G, T, E) f32.
    Returns (dispatch (G,T,E,C) bool-ish, combine (G,T,E,C), aux_loss ()).
    """
    g, t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, moe.top_k)        # (G,T,K)

    # expert one-hot per routing slot
    sel = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)     # (G,T,K,E)

    # position within each expert, counted over (slot-major, token-minor)
    # flatten slots so slot k of token t comes after slot k of token t-1
    sel_f = sel.transpose(0, 2, 1, 3).reshape(g, moe.top_k * t, e)
    pos_f = (jnp.cumsum(sel_f, axis=1) - sel_f)              # (G,K*T,E)
    pos = pos_f.reshape(g, moe.top_k, t, e).transpose(0, 2, 1, 3)
    in_cap = (pos < capacity) & (sel > 0)                    # (G,T,K,E)

    pos_idx = jnp.sum(pos * sel, axis=-1).astype(jnp.int32)  # (G,T,K)
    cap_oh = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)

    # dispatch[t,e,c] = Σ_k sel[t,k,e] * in_cap * onehot_c
    disp = jnp.einsum("gtke,gtkc->gtec",
                      sel * in_cap.astype(jnp.float32), cap_oh)
    comb = jnp.einsum("gtke,gtkc->gtec",
                      sel * in_cap.astype(jnp.float32)
                      * top_p[..., None], cap_oh)

    # load-balance aux loss (Switch/GShard): E · Σ_e f_e · P_e
    frac = jnp.mean(jnp.sum(sel * in_cap.astype(jnp.float32), axis=2),
                    axis=1)                                  # (G,E)
    mean_p = jnp.mean(probs, axis=1)                         # (G,E)
    aux = e * jnp.mean(jnp.sum(frac * mean_p, axis=-1))
    return disp, comb, aux


def _expert_mlp(p: Params, xin: jnp.ndarray, activation: str) -> jnp.ndarray:
    """xin: (G,E,C,d) -> (G,E,C,d) through each expert's own MLP."""
    gte = jnp.einsum("gecd,edf->gecf", xin, p["w_gate"])
    up = jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    return jnp.einsum("gecf,efd->gecd", _act(gte, activation) * up,
                      p["w_down"])


def _dispatched(p: Params, moe: MoEConfig, x: jnp.ndarray, activation: str):
    """The routed part by GShard capacity dispatch, over every expert:
    out (B,S,d), aux loss (), slots each expert kept (E,) int32."""
    if moe.held() != moe.num_experts:
        raise ValueError("capacity dispatch holds every routed expert")
    b, s, d = x.shape
    t_total = b * s
    tg = min(DEFAULT_GROUP, t_total)
    # pad to a multiple of tg
    pad = (-t_total) % tg
    xf = x.reshape(t_total, d)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, d), x.dtype)], axis=0)
    g = xf.shape[0] // tg
    xg = xf.reshape(g, tg, d)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    disp, comb, aux = _route(logits, moe, _capacity(tg, moe))

    xin = jnp.einsum("gtec,gtd->gecd", disp.astype(x.dtype), xg)
    xout = _expert_mlp(p, xin, activation)
    yg = jnp.einsum("gtec,gecd->gtd", comb.astype(x.dtype), xout)
    sizes = jnp.sum(disp, axis=(0, 1, 3)).astype(jnp.int32)
    return yg.reshape(-1, d)[:t_total].reshape(b, s, d), aux, sizes


def apply_moe(p: Params, moe: MoEConfig, x: jnp.ndarray, activation: str
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B,S,d) -> (out (B,S,d), load-balance aux loss (), slots each
    held expert received (held,) int32)."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    if experts_may_be_sharded():
        y, aux, sizes = _dispatched(p, moe, x, activation)
    else:
        if t > TOKEN_CHUNK and t % TOKEN_CHUNK == 0:
            out, sizes, probs, top_idx = jax.lax.map(
                lambda xc: _routed(p, moe, xc, activation),
                xf.reshape(t // TOKEN_CHUNK, TOKEN_CHUNK, d))
            out, sizes = out.reshape(t, d), jnp.sum(sizes, axis=0)
        else:
            out, sizes, probs, top_idx = _routed(p, moe, xf, activation)
        y = out.astype(x.dtype).reshape(b, s, d)

        # load-balance aux loss (Switch/GShard) over all the published
        # experts: E · Σ_e f_e · P_e
        e = moe.num_experts
        frac = jnp.bincount(top_idx.reshape(-1), length=e) / t
        aux = e * jnp.sum(frac * jnp.mean(probs.reshape(t, e), axis=0))

    if "shared" in p:
        sh = p["shared"]
        gt = jnp.einsum("bsd,df->bsf", x, sh["w_gate"])
        up = jnp.einsum("bsd,df->bsf", x, sh["w_up"])
        y = y + jnp.einsum("bsf,fd->bsd", _act(gt, activation) * up,
                           sh["w_down"])
    return y, aux, sizes
