"""Model FLOPs of DeepSeek-V2 requests and the least time of the expert
layer's grouped matmuls, from the configuration file and the routing
counters the program returns.

FLOPs are 2 per multiply-add of the model's own mathematics, as the
program computes it:
- MLA, prefill in the expanded form: per token the q, latent, rotary-key
  and output projections and the expansion of the latent into per-head
  keys and values; per (token, attended position) the scores over the
  192 query channels and the weighted sum over the 128 value channels;
- MLA, decode in the absorbed form: per token the same projections but
  no expansion, the query absorbed into the latent (W_uk) and the
  context expanded out of it (W_uv); per attended position the scores
  over the 512 latent and 64 rotary channels and the weighted sum of
  latents;
- the dense SwiGLU of the leading layers, the shared experts and the
  router of every MoE layer, on every token;
- the routed experts on the (token, expert) slots the held experts
  actually received (the counters), a SwiGLU each;
- the untied LM head at the positions whose logits are used (the last
  prompt position and each decode step).
Norms, rotary embeddings, softmax and the sort are left out (under 1%).
"""
from __future__ import annotations

import numpy as np

from bench.refs.deepseek_v2 import dims as _n


def _proj(n: dict) -> int:
    """Multiply-adds of the attention projections a token, one layer."""
    return (n["d"] * n["h"] * (n["nope"] + n["rope"]) + n["d"] * n["r"]
            + n["d"] * n["rope"] + n["h"] * n["vd"] * n["d"])


def _ffn(n: dict) -> float:
    """Multiply-adds of the FFNs a token over all layers, without the
    routed experts."""
    moe = n["L"] - n["lead"]
    return (n["lead"] * 3 * n["d"] * n["f"]
            + moe * (3 * n["d"] * n["fs"] + n["d"] * n["E"]))


def prefill(cfg: dict, prompt: int) -> float:
    """One request's prefill without the routed slots: every prompt
    token through the stack (causal attention), the head at the last
    position."""
    n = _n(cfg)
    per_tok = _proj(n) + n["r"] * n["h"] * (n["nope"] + n["vd"])
    per_ctx = n["h"] * (n["nope"] + n["rope"] + n["vd"])
    ctx = prompt * (prompt + 1) / 2.0
    return 2.0 * (n["L"] * (prompt * per_tok + ctx * per_ctx)
                  + prompt * _ffn(n) + n["d"] * n["V"])


def decode(cfg: dict, prompt: int, steps: int) -> float:
    """One request's ``steps`` cached decode steps without the routed
    slots: step i attends over prompt + i + 1 positions."""
    n = _n(cfg)
    per_tok = _proj(n) + n["h"] * n["r"] * (n["nope"] + n["vd"])
    per_ctx = n["h"] * (2 * n["r"] + n["rope"])
    ctx = sum(prompt + i + 1 for i in range(steps))
    return 2.0 * (n["L"] * (steps * per_tok + ctx * per_ctx)
                  + steps * (_ffn(n) + n["d"] * n["V"]))


def request(cfg: dict, prompt: int, gen: int) -> float:
    return prefill(cfg, prompt) + decode(cfg, prompt, gen)


def routed(cfg: dict, slots) -> float:
    """FLOPs of ``slots`` (token, expert) slots through their experts."""
    n = _n(cfg)
    return 2.0 * 3 * n["d"] * n["fe"] * float(np.sum(slots))


def moe_least_time(cfg: dict, slots, peaks: dict,
                   weight_bytes: int = 2) -> dict:
    """The least time of the grouped matmuls of one batch, from its
    counters ``slots`` (steps, MoE layers, held): in each layer-step,
    the larger of its FLOPs over the chip's bf16 peak and its bytes over
    the chip's memory bandwidth, summed.  Bytes: the three projections
    of each held expert that received a slot, read once; per slot the
    token's row read by the gate and up projections, their float32
    outputs written, the activation read by the down projection and its
    float32 output written."""
    n = _n(cfg)
    d, fe = n["d"], n["fe"]
    s = np.asarray(slots, np.float64)
    active = np.sum(s > 0, axis=-1)
    per = np.sum(s, axis=-1)
    flops = 2.0 * 3 * d * fe * per
    nbytes = (active * 3 * d * fe * weight_bytes
              + per * (2 * d * weight_bytes + 2 * fe * 4 + fe * weight_bytes
                       + d * 4))
    least = np.maximum(flops / peaks["bf16_flops_per_s"],
                       nbytes / peaks["hbm_bytes_per_s"])
    return {"least_s": float(np.sum(least)), "flops": float(np.sum(flops)),
            "bytes": float(np.sum(nbytes)),
            "memory_bound_share": float(np.mean(
                nbytes / peaks["hbm_bytes_per_s"]
                >= flops / peaks["bf16_flops_per_s"]))}
