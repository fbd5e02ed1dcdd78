"""Decode writes only the new token's K/V rows into the cache.

``transformer.decode_step`` carries the stacked cache through the layer
scan and writes each layer's new rows in place at ``(layer, b,
lengths[b])``.  These tests hold it to that: the returned cache equals the
input bit for bit everywhere but those slots, the slots hold what the
mask-select formulation (the one kept for a time axis sharded over a mesh)
writes, and the generate program has no op over a whole cache-shaped
tensor per step.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import attention as attn
from repro.models import build
from repro.models import transformer as tfm
from repro.serving import InferenceEngine

TIME_LEAVES = ("k", "v", "k_scale", "v_scale", "c_kv", "k_pe")
READ_ONLY_LEAVES = ("cross_k", "cross_v")

# (case id, arch, dtype, int8 KV cache)
CASES = [
    ("bf16", "qwen1.5-0.5b", "bfloat16", False),
    ("int8", "qwen1.5-0.5b", "float32", True),
    ("mla", "deepseek-v2-lite-16b", "float32", False),
    ("hybrid", "jamba-v0.1-52b", "float32", False),
    ("cross", "whisper-medium", "float32", False),
]


def _leaves(cache):
    """(name, stacked, leaf) of every cache leaf, lead layers first."""
    for c in cache["lead"]:
        for n, x in c.items():
            yield n, False, x
    for c in cache["stack"]:
        for n, x in c.items():
            yield n, True, x


def _written(leaf, stacked, lengths):
    """Boolean mask of the slots (layer, b, lengths[b]) of a leaf."""
    b, t = leaf.shape[int(stacked)], leaf.shape[int(stacked) + 1]
    m = np.arange(t)[None, :] == np.asarray(lengths)[:, None]   # (B, T)
    m = m.reshape(m.shape + (1,) * (leaf.ndim - 2 - int(stacked)))
    return np.broadcast_to(m[None] if stacked else m, leaf.shape)


def _setup(arch, dtype, monkeypatch, int8):
    if int8:
        monkeypatch.setenv("REPRO_KV_INT8", "1")
    cfg = dataclasses.replace(reduced(get_config(arch)), dtype=dtype)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    b, s, cache_len = 3, 16, 24
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "audio" and cfg.encoder is not None:
        batch["frames"] = jax.random.normal(
            key, (b, cfg.encoder.n_ctx, cfg.d_model)) * 0.02
    _, cache = bundle.prefill(params, batch, cache_len)
    lengths = jnp.asarray([9, 16, 12], jnp.int32)      # rows differ
    tok = jax.random.randint(jax.random.PRNGKey(2), (b, 1), 0,
                             cfg.vocab_size)
    return cfg, params, cache, tok, lengths


@pytest.mark.parametrize("case,arch,dtype,int8", CASES,
                         ids=[c[0] for c in CASES])
def test_decode_step_writes_only_the_new_rows(case, arch, dtype, int8,
                                              monkeypatch):
    cfg, params, cache, tok, lengths = _setup(arch, dtype, monkeypatch,
                                              int8)
    step = functools.partial(tfm.decode_step, cfg)
    lg, new = jax.jit(step)(params, tok, cache, lengths)
    # the same step as a mask-select over each layer's whole cache
    monkeypatch.setattr(attn, "time_axis_may_be_sharded", lambda: True)
    lg_sel, sel = jax.jit(step)(params, tok, cache, lengths)

    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(lg_sel, np.float32),
                               rtol=1e-5, atol=1e-5)
    seen = set()
    for (n, stacked, old), (_, _, got), (_, _, want) in zip(
            _leaves(cache), _leaves(new), _leaves(sel)):
        old, got, want = map(np.asarray, (old, got, want))
        assert got.dtype == old.dtype and got.shape == old.shape
        if n in READ_ONLY_LEAVES:
            np.testing.assert_array_equal(got, old)
        if n not in TIME_LEAVES:
            continue
        seen.add(n)
        w = _written(old, stacked, lengths)
        np.testing.assert_array_equal(got[~w], old[~w])
        np.testing.assert_array_equal(got[w], want[w])
        assert not np.array_equal(got[w], old[w]), n   # the rows did change
    want_leaves = {"bf16": {"k", "v"},
                   "int8": {"k", "v", "k_scale", "v_scale"},
                   "mla": {"c_kv", "k_pe"}}.get(case, {"k", "v"})
    assert seen == want_leaves
    if int8:
        assert cache["stack"][0]["k"].dtype == jnp.int8


def test_row_past_the_end_is_not_written():
    """A row whose length has run past the cache (an idle slot of the
    continuous engine) leaves its cache as it was, as the mask-select
    did."""
    cache = jnp.arange(2 * 3 * 4 * 2, dtype=jnp.float32).reshape(2, 3, 4, 2)
    new = -jnp.ones((3, 1, 2))
    out = attn.write_rows(cache, new, jnp.asarray([1, 4, 9]),
                          layer=jnp.asarray(1))
    want = np.asarray(cache).copy()
    want[1, 0, 1] = -1
    np.testing.assert_array_equal(np.asarray(out), want)


# ---------------------------------------------------------------------------
# structure of the engine's generate program
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(x, "jaxpr", x)
            if hasattr(j, "eqns"):
                yield j


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for j in _sub_jaxprs(e):
            yield from _eqns(j)


@pytest.fixture(scope="module")
def generate_program():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    eng = InferenceEngine(cfg, workload="generate", seq_len=8,
                          gen_tokens=3, max_batch=4)
    b, t = 4, 8 + 3 + 1
    _, p, r = tfm.split_pattern(cfg)
    batch = {"tokens": jnp.zeros((b, 8), jnp.int32)}
    layer = (b, t, cfg.num_kv_heads, cfg.head_dim)
    return eng, eng.params, batch, layer, (r,) + layer


def test_generate_program_selects_over_no_cache(generate_program):
    """No select in the lowered program produces a layer's cache or the
    stacked cache (the per-step mask-select write)."""
    eng, params, batch, layer, stacked = generate_program
    text = eng._fns[4].lower(params, batch).as_text()
    shapes = {"x".join(map(str, s)) + "x" for s in (layer, stacked)}
    selects = [ln for ln in text.splitlines() if "stablehlo.select" in ln]
    assert selects                         # the check reads real selects
    bad = [ln.strip() for ln in selects
           if any(f"tensor<{s}" in ln for s in shapes)]
    assert not bad, bad[:3]


def test_layer_scan_emits_no_cache(generate_program):
    """The decode loop's layer scan carries the stacked cache and emits
    no cache-shaped stacked output (ys) per step."""
    eng, params, batch, layer, stacked = generate_program
    jaxpr = jax.make_jaxpr(eng._fns[4])(params, batch).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    decode = [e for e in scans if e.params["length"] == 3]
    assert len(decode) == 1
    inner = [e for e in _eqns(decode[0].params["jaxpr"].jaxpr)
             if e.primitive.name == "scan"]
    assert inner                           # the layer scan is there
    for e in inner:
        nc = e.params["num_carry"]
        ys = [v.aval.shape for v in e.outvars[nc:]]
        carry = [v.aval.shape for v in e.outvars[:nc]]
        assert stacked not in ys, ys
        assert stacked in carry, carry
