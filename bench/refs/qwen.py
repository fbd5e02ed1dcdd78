"""A plain forward pass of a qwen2-layout decoder, and its weights.

Independent of the program: straightforward ``jax.numpy`` over the
whole sequence, no cache, no batching tricks, following the published
architecture (Qwen1.5 = ``qwen2`` in Hugging Face transformers): token
embedding; per layer RMSNorm → q/k/v projections with bias → rotary
embedding (rotate-half, θ = rope_theta) → causal softmax attention →
output projection, residual; RMSNorm → SwiGLU MLP, residual; final
RMSNorm; LM head tied to the embedding.

``make_weights`` builds the weights from a seed on the device in one
jitted call, in the dtype they are served in, in the nested-dict layout
the serving engine takes (``embed``, ``norm_f``, ``stack`` holding the
layers stacked on a leading axis).  The benchmark hands the same
weights to the program and to this reference.

``forward`` computes in float32 at ``highest`` matmul precision, or, for
the benchmark's control, with every matmul operand rounded to
float8_e4m3 (per-row scales for activations, per-output-column scales
for weights; the step below the bfloat16 the configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": d // h,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"]}


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``: projections N(0, 1/fan_in),
    embedding N(0, 0.02²), biases N(0, 0.02²), norm scales
    1 + N(0, 0.02²)."""
    n = dims(cfg)
    d, h, kv, hd, f, L, V = (n[k] for k in ("d", "h", "kv", "hd", "f", "L",
                                            "V"))
    shapes = {
        "wq": ((L, d, h, hd), d ** -0.5), "wk": ((L, d, kv, hd), d ** -0.5),
        "wv": ((L, d, kv, hd), d ** -0.5),
        "wo": ((L, h, hd, d), (h * hd) ** -0.5),
        "bq": ((L, h, hd), 0.02), "bk": ((L, kv, hd), 0.02),
        "bv": ((L, kv, hd), 0.02),
        "w_gate": ((L, d, f), d ** -0.5), "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
        "norm1": ((L, d), 0.02), "norm2": ((L, d), 0.02),
        "embed": ((V, d), 0.02), "norm_f": ((d,), 0.02),
    }

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        w = {}
        for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items())):
            x = jax.random.normal(k, shape, jnp.float32) * scale
            if name.startswith("norm"):
                x = x + 1.0
            w[name] = x.astype(dtype)
        layer = {"norm1": {"scale": w["norm1"]},
                 "norm2": {"scale": w["norm2"]},
                 "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq",
                                            "bk", "bv")},
                 "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
        return {"embed": w["embed"], "norm_f": {"scale": w["norm_f"]},
                "lead": [], "stack": [layer]}

    return build(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def _f8(x, axis):
    """Round to float8_e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, a_axis, b_axis, fp8: bool):
    if fp8:
        a, b = _f8(a, a_axis), _f8(b, b_axis)
    return jnp.einsum(spec, a, b, precision="highest")


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd), rotate-half convention."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(cfg: dict, weights, tokens, first: int, *, fp8: bool = False):
    """Logits (B, S − first, V) at positions ``first`` … S−1 of
    ``tokens`` (B, S); float32 throughout."""
    n = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    layers = w["stack"][0]
    x = jnp.take(w["embed"], tokens, axis=0)
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    g = n["h"] // n["kv"]

    def layer(x, p):
        a = p["attn"]
        hx = _rms(x, p["norm1"]["scale"], eps)
        q = _mm("bsd,dhk->bshk", hx, a["wq"], -1, 0, fp8) + a["bq"]
        k = _mm("bsd,dhk->bshk", hx, a["wk"], -1, 0, fp8) + a["bk"]
        v = _mm("bsd,dhk->bshk", hx, a["wv"], -1, 0, fp8) + a["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = _mm("bshk,bthk->bhst", q, k, -1, -1, fp8) * n["hd"] ** -0.5
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = _mm("bhst,bthk->bshk", pr, v, -1, 1, fp8)
        x = x + _mm("bshk,hkd->bsd", o, a["wo"], (-2, -1), (0, 1), fp8)
        f = p["ffn"]
        hx = _rms(x, p["norm2"]["scale"], eps)
        gate = _mm("bsd,df->bsf", hx, f["w_gate"], -1, 0, fp8)
        up = _mm("bsd,df->bsf", hx, f["w_up"], -1, 0, fp8)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, f["w_down"], -1,
                    0, fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x[:, first:], w["norm_f"]["scale"], eps)
    return _mm("bsd,vd->bsv", x, w["embed"], -1, -1, fp8)
