"""Runner: a real model served through ``InferenceEngine``.

A closed loop at the cap: back-to-back full batches through
``run_batch``, timed over the whole loop, host input preparation
included; ``gen_tokens_per_s`` counts the generated tokens of the
completed batches.

The weights are the benchmark's (``refs.qwen.make_weights``), handed to
the engine.  Every call of the engine's compiled program is tapped for
its prompt tokens and generated tokens.  The check samples requests from
the seed, the last one served among them, and runs the plain float32
reference over each prompt with its served tokens: the number compared
is the widest gap by which a served token's logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import time

import numpy as np

from bench import traffic as traffic_mod
from bench.counts import qwen as qwen_counts
from bench.refs import qwen as ref


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    n = ref.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=n["L"], d_model=n["d"], num_heads=n["h"],
        num_kv_heads=n["kv"], head_dim=n["hd"], d_ff=n["f"],
        vocab_size=n["V"], qkv_bias=True, activation="swiglu",
        norm="rmsnorm", rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
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
                          seed=seed % (2 ** 31 - 1))
    eng.params = weights
    log = []

    def tapped(fn):
        def call(params, batch):
            out = fn(params, batch)
            log.append((batch["tokens"], out))
            return out
        return call

    eng._fns = {b: tapped(fn) for b, fn in eng._fns.items()}
    eng.run_batch(traffic["max_batch"])
    log.clear()
    return {"eng": eng, "weights": weights, "log": log, "cfg": cfg,
            "traffic": traffic, "seed": seed}


def window(st: dict, seconds: float, span) -> dict:
    eng, t, cfg = st["eng"], st["traffic"], st["cfg"]
    st["log"].clear()
    b = t["max_batch"]
    sizes = []
    t0 = time.perf_counter()
    while not sizes or time.perf_counter() - t0 < seconds:
        with span("bench.batch"):
            eng.run_batch(b)
        sizes.append(b)
    elapsed = time.perf_counter() - t0
    per_req = qwen_counts.request(cfg, t["prompt_tokens"], t["gen_tokens"])
    return {"elapsed_s": elapsed, "sizes": sizes,
            "e2e": {"gen_tokens_per_s": len(sizes) * b * t["gen_tokens"]
                    / elapsed},
            "counters": {"requests": len(sizes) * b,
                         "batches": len(sizes),
                         "model_flops": per_req * len(sizes) * b}}


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
    and the sequences (prompt, t0, served) it was read on.

    ``served`` (n, gen) are the engine's outputs, which start after the
    prefill's own greedy token t0; t0 is not returned by the engine, so
    the reference's best token after the prompt, and up to three more
    within ``limit`` of it, are tried and the best-fitting one kept."""
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


def collect(st: dict, w: dict, n_sample: int):
    """Host copies of the sampled requests' prompts and served tokens."""
    import jax
    rows = []                       # (batch index, row) of real requests
    for bi, b in enumerate(w["sizes"]):
        rows.extend((bi, r) for r in range(b))
    pick = traffic_mod.sample(st["seed"], len(rows), n_sample,
                              must=[len(rows) - 1])
    prompts, served = [], []
    for j in pick:
        bi, r = rows[j]
        tok, out = jax.device_get(st["log"][bi])
        prompts.append(np.asarray(tok)[r])
        served.append(np.asarray(out)[r])
    return np.stack(prompts).astype(np.int32), np.stack(served), len(rows)


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
