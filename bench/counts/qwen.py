"""Model FLOPs of a dense decoder (qwen2 layout) from its config file.

Counted as 2 FLOPs per multiply-add of the model's own mathematics:
the q/k/v/o projections, the SwiGLU MLP, attention scores and the
weighted sum over the positions each token attends to (causal), and
the tied LM head on the positions whose logits are used.  Norms,
rotary embeddings, softmax and biases are left out (well under 1%).
"""
from __future__ import annotations


def _per_token_dense(cfg: dict) -> float:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    proj = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    return 2.0 * cfg["num_hidden_layers"] * (proj + mlp)


def _attn(cfg: dict, ctx: float) -> float:
    """FLOPs of scores plus weighted sum for one token over ``ctx``
    positions, all layers."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return 2.0 * 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * hd * ctx


def _head(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill(cfg: dict, prompt: int) -> float:
    """One request's prefill: every prompt token through the stack,
    causal attention, logits at the last position only."""
    causal_ctx = prompt * (prompt + 1) / 2.0
    return prompt * _per_token_dense(cfg) + _attn(cfg, causal_ctx) \
        + _head(cfg)


def decode(cfg: dict, prompt: int, steps: int) -> float:
    """One request's ``steps`` cached decode steps after a prompt of
    ``prompt`` tokens: step i attends over prompt + i + 1 positions."""
    ctx = sum(prompt + i + 1 for i in range(steps))
    return steps * (_per_token_dense(cfg) + _head(cfg)) + _attn(cfg, ctx)


def request(cfg: dict, prompt: int, gen: int) -> float:
    return prefill(cfg, prompt) + decode(cfg, prompt, gen)
