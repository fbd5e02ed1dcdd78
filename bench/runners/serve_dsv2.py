"""Runner: DeepSeek-V2 served through ``InferenceEngine``, holding its
chip's share of the routed experts.

The same closed loop and check as ``serve``: back-to-back full batches
through ``run_batch``, timed over the whole loop with the host's input
preparation; ``gen_tokens_per_s`` counts the generated tokens of the
completed batches.  The weights are the benchmark's
(``refs.deepseek_v2.make_weights``), handed to the engine.  Every call of
the engine's compiled program is tapped for its prompt and generated
tokens; the engine keeps each batch's routing counters
(``expert_slots``), which the window collects and sums after its clock
stops.

The check samples requests from the seed, the last one served among
them, and runs the plain float32 reference over each prompt with its
served tokens: the number compared is the widest gap by which a served
token's logit lies below the reference's best at its position.
"""
from __future__ import annotations

import time

import numpy as np

from bench.counts import deepseek_v2 as counts
from bench.refs import deepseek_v2 as ref
from bench.runners.serve import collect


def model_config(cfg: dict):
    """The program's configuration from the Hugging Face keys; the
    routing the program implements is checked, not assumed."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    RopeScaling)
    rs = cfg["rope_scaling"]
    want = {"scoring_func": "softmax", "topk_method": "greedy",
            "norm_topk_prob": False, "routed_scaling_factor": 1,
            "moe_layer_freq": 1, "q_lora_rank": None,
            "tie_word_embeddings": False, "attention_bias": False,
            "rms_norm_eps": 1e-6}
    bad = {k: cfg[k] for k, v in want.items() if cfg[k] != v}
    if bad or rs["type"] != "yarn":
        raise ValueError(f"the program does not implement {bad or rs}")
    return ModelConfig(
        name=cfg["name"], family="moe", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=0,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation="swiglu", norm="rmsnorm",
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        max_position_embeddings=cfg["max_position_embeddings"],
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(num_experts=cfg["router_width"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      num_shared_experts=cfg["n_shared_experts"],
                      d_shared=cfg["moe_intermediate_size"],
                      first_dense=cfg["first_k_dense_replace"],
                      held_experts=cfg["n_routed_experts"],
                      expert_offset=cfg["held_expert_offset"]),
        dtype=cfg["torch_dtype"])


def setup(cfg: dict, traffic: dict, seed: int, devices) -> dict:
    import jax
    from repro.serving import InferenceEngine

    weights = ref.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    eng = InferenceEngine(model_config(cfg), workload="generate",
                          seq_len=traffic["prompt_tokens"],
                          gen_tokens=traffic["gen_tokens"],
                          max_batch=traffic["max_batch"],
                          seed=seed % (2 ** 31 - 1), params=weights)
    log = []

    def tapped(fn):
        def call(params, batch):
            out = fn(params, batch)
            log.append((batch["tokens"], out[0]))
            return out
        return call

    eng._fns = {b: tapped(fn) for b, fn in eng._fns.items()}
    eng.run_batch(traffic["max_batch"])
    log.clear()
    return {"eng": eng, "weights": weights, "log": log, "cfg": cfg,
            "traffic": traffic, "seed": seed}


def window(st: dict, seconds: float, span) -> dict:
    import jax

    eng, t, cfg = st["eng"], st["traffic"], st["cfg"]
    st["log"].clear()
    b = t["max_batch"]
    sizes, slots = [], []
    t0 = time.perf_counter()
    while not sizes or time.perf_counter() - t0 < seconds:
        with span("bench.batch"):
            eng.run_batch(b)
        sizes.append(b)
        slots.append(eng.expert_slots)
    elapsed = time.perf_counter() - t0
    slots = [np.asarray(s) for s in jax.device_get(slots)]
    n_req = len(sizes) * b
    counters = {"requests": n_req, "batches": len(sizes),
                "model_flops": counts.request(cfg, t["prompt_tokens"],
                                              t["gen_tokens"]) * n_req
                + sum(counts.routed(cfg, s) for s in slots),
                "routed_slots": int(sum(s.sum() for s in slots)),
                "expert_layer_steps": int(sum((s > 0).sum()
                                              for s in slots)),
                "expert_slots": slots}
    return {"elapsed_s": elapsed, "sizes": sizes,
            "e2e": {"gen_tokens_per_s": n_req * t["gen_tokens"] / elapsed},
            "counters": counters}


def _gaps(logits, tokens):
    """Per position: the reference's best logit minus the served
    token's."""
    import jax.numpy as jnp
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return best - got


def _forward(cfg, p: int):
    import jax
    return jax.jit(lambda w, tk, f8: ref.forward(cfg, w, tk, p - 1, fp8=f8),
                   static_argnums=2)


def served_gaps(cfg: dict, weights, prompts: np.ndarray,
                served: np.ndarray, limit: float):
    """Widest gap per request of its served tokens under the reference,
    and the sequences (prompt, t0, served) it was read on.  As in
    ``serve.served_gaps``: the prefill's own token t0 is not returned by
    the engine, so the reference's best token after the prompt, and up
    to three more within ``limit`` of it, are tried and the best-fitting
    one kept."""
    import jax.numpy as jnp

    fwd = _forward(cfg, prompts.shape[1])
    head = np.asarray(fwd(weights, jnp.asarray(prompts), False)[:, 0])
    gap0 = head.max(-1, keepdims=True) - head
    out = np.full(len(prompts), np.inf)
    seqs = np.zeros((len(prompts), prompts.shape[1] + served.shape[1]),
                    np.int32)
    for i in range(len(prompts)):
        order = np.argsort(gap0[i], kind="stable")
        cands = [order[0]] + [t for t in order[1:4] if gap0[i, t] <= limit]
        for t0 in cands:
            seq = np.concatenate([prompts[i], [t0], served[i, :-1]])
            lg = fwd(weights, jnp.asarray(seq[None], jnp.int32), False)
            want = jnp.asarray(np.concatenate([[t0], served[i]])[None],
                               jnp.int32)
            g = float(jnp.max(_gaps(lg, want)))
            if g < out[i]:
                out[i], seqs[i] = g, seq
    return out, seqs


def control_gaps(cfg: dict, weights, seqs: np.ndarray, prompt: int):
    """The control: on the same sequences, the widest gap under the
    float32 reference of the tokens that the float8 reference puts
    first at each position."""
    import jax.numpy as jnp

    fwd = _forward(cfg, prompt)
    out = np.zeros(len(seqs))
    for i, seq in enumerate(seqs):
        tk = jnp.asarray(seq[None], jnp.int32)
        lg = fwd(weights, tk, False)
        lq = fwd(weights, tk, True)
        out[i] = float(jnp.max(_gaps(lg, jnp.argmax(lq, -1).astype(
            jnp.int32))))
    return out


def check(st: dict, w: dict, limits: dict) -> dict:
    prompts, served, n_req = collect(st, w, st["traffic"]["sample_requests"])
    weights, cfg = st.pop("weights"), st["cfg"]
    eng = st.pop("eng")
    del eng.params, eng
    st["log"].clear()
    gaps, _ = served_gaps(cfg, weights, prompts, served,
                          limits["logit_gap_max"])
    return {"numbers": {"logit_gap_max": float(gaps.max())},
            "attempted": n_req, "failed": 0,
            "detail": {"sampled": len(gaps),
                       "served_tokens_compared": int(served.size)}}
