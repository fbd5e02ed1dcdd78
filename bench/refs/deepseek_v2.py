"""A plain forward pass of DeepSeek-V2(-Lite), and its weights.

Independent of the program: straightforward ``jax.numpy`` over the
whole sequence, no cache, no batching tricks, following the published
architecture (``deepseek_v2`` in Hugging Face transformers, and
arXiv:2405.04434): token embedding; per layer RMSNorm → multi-head
latent attention in its expanded form (q = x W_q per head, split into
128 "nope" and 64 rotary channels; a 512-wide latent c = RMSNorm(x W_dkv)
expanded to per-head keys c W_uk and values c W_uv; one shared rotary
key x W_kpe; YaRN rotary frequencies; softmax scale 192^-½ · mscale² of
``mscale_all_dim``) → causal softmax attention → output projection,
residual; RMSNorm → the FFN, residual; final RMSNorm; untied LM head.
Layer 0's FFN is a dense SwiGLU; the others are mixtures of experts: a
float32 router over all the published experts, softmax, the top
``num_experts_per_tok`` by score (greedy), their probabilities as
weights, unnormalised; the routed part computed densely — every held
expert over every token, times the gate mask — plus the shared experts
on every token.

A configuration may hold a share of the routed experts (expert
parallelism, ``n_routed_experts`` of ``router_width`` from
``held_expert_offset``): the reference then adds what those experts give
and nothing for the others, as the program does.

Departure: the published checkpoint interleaves the rotary channels of
q_pe and k_pe; both this reference and the program rotate halves. With
weights drawn at random the two layouts are the same model (a fixed
permutation of those weight columns).

``make_weights`` builds the weights from a seed on the device in one
jitted call, in the dtype they are served in and the nested layout the
serving engine takes (``lead`` = [layer 0], ``stack`` = the MoE layers
stacked on a leading axis).  ``forward`` computes in float32 at
``highest`` matmul precision, each layer's weights cast as the layer
runs, or, for the control, with every matmul operand rounded to
float8_e4m3 (``refs.qwen``'s rounding, the step below the bfloat16 the
configuration states).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.qwen import _f8


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "E": cfg["router_width"], "held": cfg["n_routed_experts"],
            "off": cfg["held_expert_offset"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "k": cfg["num_experts_per_tok"], "L": cfg["num_hidden_layers"],
            "lead": cfg["first_k_dense_replace"], "V": cfg["vocab_size"]}


def _attn_shapes(n: dict, lead: tuple) -> dict:
    d, h, r = n["d"], n["h"], n["r"]
    return {
        "wq": (lead + (d, h, n["nope"] + n["rope"]), d ** -0.5),
        "w_dkv": (lead + (d, r), d ** -0.5),
        "w_kpe": (lead + (d, n["rope"]), d ** -0.5),
        "norm_ckv": (lead + (r,), 0.02),
        "w_uk": (lead + (r, h, n["nope"]), r ** -0.5),
        "w_uv": (lead + (r, h, n["vd"]), r ** -0.5),
        "wo": (lead + (h, n["vd"], d), (h * n["vd"]) ** -0.5),
    }


def top_k_mass(n_experts: int, k: int, draws: int = 1 << 16) -> float:
    """Mean summed probability of the top k of a softmax over
    ``n_experts`` logits of unit spread (the router ``make_weights``
    draws), over a fixed set of draws: 0.361 for 64 experts and k = 6."""
    z = np.random.default_rng(0).standard_normal((draws, n_experts))
    p = np.exp(z - z.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    return float(np.sort(p, 1)[:, -k:].sum(1).mean())


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``: projections N(0, 1/fan_in), the
    router included (float32); the routed experts' output projections
    divided by the router's mean top-k mass (``top_k_mass``), since the
    gates are not renormalised and a trained expert carries its share
    at that weight; embedding and head N(0, 0.02²); norm scales
    1 + N(0, 0.02²)."""
    n = dims(cfg)
    d, L, lead = n["d"], n["L"], n["lead"]
    m = (L - lead,)
    shapes = {}
    for pre, ld in (("lead.", (lead,)), ("moe.", m)):
        shapes.update({pre + k: v for k, v in _attn_shapes(n, ld).items()})
        shapes[pre + "norm1"] = (ld + (d,), 0.02)
        shapes[pre + "norm2"] = (ld + (d,), 0.02)
    shapes.update({
        "lead.w_gate": ((lead, d, n["f"]), d ** -0.5),
        "lead.w_up": ((lead, d, n["f"]), d ** -0.5),
        "lead.w_down": ((lead, n["f"], d), n["f"] ** -0.5),
        "moe.router": (m + (d, n["E"]), d ** -0.5),
        "moe.w_gate": (m + (n["held"], d, n["fe"]), d ** -0.5),
        "moe.w_up": (m + (n["held"], d, n["fe"]), d ** -0.5),
        "moe.w_down": (m + (n["held"], n["fe"], d), n["fe"] ** -0.5
                       / top_k_mass(n["E"], n["k"])),
        "moe.s_gate": (m + (d, n["fs"]), d ** -0.5),
        "moe.s_up": (m + (d, n["fs"]), d ** -0.5),
        "moe.s_down": (m + (n["fs"], d), n["fs"] ** -0.5),
        "embed": ((n["V"], d), 0.02),
        "unembed": ((d, n["V"]), 0.02),
        "norm_f": ((d,), 0.02),
    })

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        w = {}
        for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items())):
            x = jax.random.normal(k, shape, jnp.float32) * scale
            if name.rsplit(".", 1)[-1].startswith("norm"):
                x = x + 1.0
            w[name] = x if name == "moe.router" else x.astype(dtype)

        def layer(pre, ffn):
            attn = {k: w[pre + k] for k in _attn_shapes(n, ())}
            attn["norm_ckv"] = w[pre + "norm_ckv"]
            return {"norm1": {"scale": w[pre + "norm1"]},
                    "norm2": {"scale": w[pre + "norm2"]},
                    "attn": attn, "ffn": ffn}

        dense = layer("lead.", {k: w["lead." + k]
                                for k in ("w_gate", "w_up", "w_down")})
        moe = layer("moe.", {
            "router": w["moe.router"],
            **{k: w["moe." + k] for k in ("w_gate", "w_up", "w_down")},
            "shared": {"w_gate": w["moe.s_gate"], "w_up": w["moe.s_up"],
                       "w_down": w["moe.s_down"]}})
        return {"embed": w["embed"], "unembed": w["unembed"],
                "norm_f": {"scale": w["norm_f"]},
                "lead": [jax.tree.map(lambda a, i=i: a[i], dense)
                         for i in range(lead)],
                "stack": [moe]}

    return build(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def yarn_inv_freq(cfg: dict) -> jnp.ndarray:
    """YaRN inverse frequencies of the rotary channels
    (``DeepseekV2YarnRotaryEmbedding``)."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    expo = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** expo
    inter = 1.0 / (rs["factor"] * base ** expo)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    return s * m * m


def _rope(x, cfg):
    """x: (B, S, H, rope), rotate-half; cos and sin times mscale over
    mscale_all_dim's."""
    rs = cfg["rope_scaling"]
    m = (yarn_get_mscale(rs["factor"], rs["mscale"])
         / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    s, dim = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)
    cos = jnp.cos(ang)[None, :, None, :] * m
    sin = jnp.sin(ang)[None, :, None, :] * m
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(spec, a, b, a_axis, b_axis, fp8: bool):
    if fp8:
        a, b = _f8(a, a_axis), _f8(b, b_axis)
    return jnp.einsum(spec, a, b, precision="highest")


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _attention(cfg, a, x, eps, fp8):
    n = dims(cfg)
    s = x.shape[1]
    q = _mm("bsd,dhk->bshk", x, a["wq"], -1, 0, fp8)
    q_nope, q_pe = q[..., :n["nope"]], _rope(q[..., n["nope"]:], cfg)
    c = _rms(_mm("bsd,dr->bsr", x, a["w_dkv"], -1, 0, fp8), a["norm_ckv"],
             eps)
    k_pe = _rope(_mm("bsd,dr->bsr", x, a["w_kpe"], -1, 0, fp8)[:, :, None],
                 cfg)
    k_nope = _mm("bsr,rhk->bshk", c, a["w_uk"], -1, 0, fp8)
    v = _mm("bsr,rhk->bshk", c, a["w_uv"], -1, 0, fp8)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:3] + (n["rope"],))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    sc = _mm("bshk,bthk->bhst", q, k, -1, -1, fp8) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = _mm("bhst,bthk->bshk", pr, v, -1, 1, fp8)
    return _mm("bshk,hkd->bsd", o, a["wo"], (-2, -1), (0, 1), fp8)


def _swiglu(x, wg, wu, wd, fp8):
    g = _mm("bsd,df->bsf", x, wg, -1, 0, fp8)
    u = _mm("bsd,df->bsf", x, wu, -1, 0, fp8)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, wd, -1, 0, fp8)


def routing(cfg: dict, router, x, fp8: bool = False):
    """Gate weights over all the published experts (B, S, E): the top-k
    softmax probabilities where chosen, 0 elsewhere; the chosen experts
    (B, S, k); and the margin of the k-th over the next, relative to the
    k-th's probability (B, S)."""
    n = dims(cfg)
    probs = jax.nn.softmax(_mm("bsd,de->bse", x, router, -1, 0, fp8), -1)
    top_p, top_i = jax.lax.top_k(probs, n["k"] + 1)
    top_p, top_i, nxt = top_p[..., :-1], top_i[..., :-1], top_p[..., -1]
    gates = jnp.sum(jax.nn.one_hot(top_i, n["E"]) * top_p[..., None], -2)
    return gates, top_i, 1.0 - nxt / top_p[..., -1]


def experts(cfg, f, x, fp8: bool = False):
    """The held routed experts, each over every token, times the gate
    mask; plus the shared experts."""
    n = dims(cfg)
    gates, top_i, margin = routing(cfg, f["router"], x, fp8)
    held = gates[..., n["off"]:n["off"] + n["held"]]      # (B, S, held)
    g = _mm("bsd,edf->bsef", x, f["w_gate"], -1, 1, fp8)
    u = _mm("bsd,edf->bsef", x, f["w_up"], -1, 1, fp8)
    y = _mm("bsef,efd->bsed", jax.nn.silu(g) * u, f["w_down"], -1, 1, fp8)
    routed = jnp.sum(y * held[..., None], axis=2)
    sh = f["shared"]
    return routed + _swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"],
                            fp8), (top_i, margin)


def forward(cfg: dict, weights, tokens, first: int, *, fp8: bool = False,
            with_routing: bool = False):
    """Logits (B, S − first, V) at positions ``first`` … S−1 of
    ``tokens`` (B, S); float32 throughout.  ``with_routing``: also the
    experts each MoE layer chose, (MoE layers, B, S, k), and the margin
    of its k-th choice (``routing``), (MoE layers, B, S)."""
    eps = cfg["rms_norm_eps"]

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)

    def block(x, p, ffn):
        p = f32(p)
        x = x + _attention(cfg, p["attn"], _rms(x, p["norm1"]["scale"], eps),
                           eps, fp8)
        return ffn(x, p, _rms(x, p["norm2"]["scale"], eps))

    def dense(x, p, hx):
        f = p["ffn"]
        return x + _swiglu(hx, f["w_gate"], f["w_up"], f["w_down"], fp8)

    for p in weights["lead"]:
        x = block(x, p, dense)

    def moe(x, p):
        def ffn(x, p, hx):
            y, chosen = experts(cfg, p["ffn"], hx, fp8)
            return x + y, chosen
        return block(x, p, ffn)

    x, chosen = jax.lax.scan(moe, x, weights["stack"][0])
    x = _rms(x[:, first:], weights["norm_f"]["scale"].astype(jnp.float32),
             eps)
    logits = _mm("bsd,dv->bsv", x, weights["unembed"].astype(jnp.float32),
                 -1, 0, fp8)
    return (logits, chosen) if with_routing else logits
