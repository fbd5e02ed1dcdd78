"""Per-architecture smoke tests: reduced variant of each assigned family,
one forward + one train step on CPU, shape and NaN assertions, plus
prefill→decode consistency against the full-sequence reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_archs, reduced
from repro.models import build, input_specs
from repro.train import AdamWConfig, init_state, make_train_step

ARCHS = list_archs()


def _batch(cfg, key, b, s):
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.family == "vlm" and cfg.encoder is not None:
        batch["patch_embeds"] = jax.random.normal(
            key, (b, cfg.encoder.n_ctx, cfg.d_model)) * 0.02
    if cfg.family == "audio" and cfg.encoder is not None:
        batch["frames"] = jax.random.normal(
            key, (b, cfg.encoder.n_ctx, cfg.d_model)) * 0.02
    return batch


@pytest.fixture(scope="module")
def rigs():
    """Init each reduced arch once per test session."""
    out = {}
    for a in ARCHS:
        cfg = reduced(get_config(a))
        bundle = build(cfg)
        params = bundle.init(jax.random.PRNGKey(0))
        out[a] = (cfg, bundle, params)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_limits(arch):
    cfg = reduced(get_config(arch))
    assert cfg.num_layers == 2
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nans(arch, rigs):
    cfg, bundle, params = rigs[arch]
    b, s = 2, 64
    logits, aux = bundle.forward(params, _batch(cfg, jax.random.PRNGKey(1),
                                                b, s))
    assert logits.shape[0] == b and logits.shape[-1] == cfg.vocab_size
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_loss_no_nans(arch, rigs):
    cfg, bundle, params = rigs[arch]
    b, s = 2, 64
    key = jax.random.PRNGKey(2)
    batch = _batch(cfg, key, b, s)
    batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3,
                                                    total_steps=10,
                                                    warmup_steps=1)))
    opt_state = init_state(params)
    p, opt_state, m1 = step(params, opt_state, batch)
    p, opt_state, m2 = step(p, opt_state, batch)   # same batch: must drop
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, rigs):
    cfg, bundle, params = rigs[arch]
    b, s, extra = 2, 32, 3
    key = jax.random.PRNGKey(3)
    full = _batch(cfg, key, b, s + extra)
    toks = full["tokens"]
    pre = dict(full)
    pre["tokens"] = toks[:, :s]
    offset = cfg.encoder.n_ctx if cfg.family == "vlm" else 0
    ref, _ = bundle.forward(params, full)
    lg, cache = bundle.prefill(params, pre, s + extra + offset)
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(ref[:, s - 1 + offset]),
                               rtol=3e-4, atol=3e-4)
    lengths = jnp.full((b,), s + offset, jnp.int32)
    for t in range(extra):
        lg, cache = bundle.decode_step(params, toks[:, s + t:s + t + 1],
                                       cache, lengths)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(ref[:, s + t + offset]),
                                   rtol=3e-4, atol=3e-4)
        lengths = lengths + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward_unequal_lengths(arch, rigs):
    """Rows of unequal length in one decode batch (as the continuous
    engine's slot pool has them): each row prefilled alone with its own
    prompt length into one cache, then decoded together; every row's
    logits match the full-sequence reference at its own position."""
    cfg, bundle, params = rigs[arch]
    lens, extra = (20, 32, 27), 3
    b, total = len(lens), max(lens) + extra
    full = _batch(cfg, jax.random.PRNGKey(7), b, total)
    toks = full["tokens"]
    offset = cfg.encoder.n_ctx if cfg.family == "vlm" else 0
    ref, _ = bundle.forward(params, full)
    prefill = jax.jit(bundle.prefill, static_argnums=2)
    decode = jax.jit(bundle.decode_step)
    rows = []
    for i, n in enumerate(lens):
        pre = {k: v[i:i + 1] for k, v in full.items()}
        pre["tokens"] = toks[i:i + 1, :n]
        lg, c = prefill(params, pre, total + offset)
        np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                   np.asarray(ref[i, n - 1 + offset]),
                                   rtol=3e-4, atol=3e-4)
        rows.append(c)
    cache = {"lead": jax.tree.map(lambda *x: jnp.concatenate(x, 0),
                                  *[c["lead"] for c in rows]),
             "stack": jax.tree.map(lambda *x: jnp.concatenate(x, 1),
                                   *[c["stack"] for c in rows])}
    lengths = jnp.asarray(lens, jnp.int32) + offset
    for t in range(extra):
        idx = jnp.asarray(lens) + t
        tok = jnp.take_along_axis(toks, idx[:, None], axis=1)
        lg, cache = decode(params, tok, cache, lengths)
        want = ref[jnp.arange(b), idx + offset]
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)
        lengths = lengths + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_sliding_window_decode(arch, rigs):
    """Windowed decode runs and, for attention archs, differs from full
    attention when the context exceeds the window."""
    cfg, bundle, params = rigs[arch]
    b, s = 2, 48
    key = jax.random.PRNGKey(4)
    pre = _batch(cfg, key, b, s)
    offset = cfg.encoder.n_ctx if cfg.family == "vlm" else 0
    _, cache = bundle.prefill(params, pre, s + 2 + offset)
    lengths = jnp.full((b,), s + offset, jnp.int32)
    tok = pre["tokens"][:, -1:]
    lg_full, _ = bundle.decode_step(params, tok, cache, lengths)
    lg_win, _ = bundle.decode_step(params, tok, cache, lengths, window=8)
    assert not bool(jnp.any(jnp.isnan(lg_win)))
    if cfg.has_attention():
        assert float(jnp.max(jnp.abs(lg_win - lg_full))) > 1e-6


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_abstract(arch, shape):
    """input_specs builds pure ShapeDtypeStructs for all 40 combos —
    no allocation, correct batch dims."""
    cfg = get_config(arch)
    spec = input_specs(cfg, SHAPES[shape])
    leaves = jax.tree.leaves(spec)
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    sh = SHAPES[shape]
    assert spec["tokens"].shape[0] == sh.global_batch
    if sh.kind == "decode":
        assert spec["tokens"].shape[1] == 1
        assert "cache" in spec


def test_int8_kv_cache_roundtrip(monkeypatch):
    """§Perf P5: int8 KV cache — cache dtype switches, decode stays within
    quantization tolerance of the bf16-cache reference."""
    monkeypatch.setenv("REPRO_KV_INT8", "1")
    cfg = reduced(get_config("qwen1.5-4b"))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    key = jax.random.PRNGKey(6)
    toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size)
    ref, _ = bundle.forward(params, {"tokens": toks})
    lg, cache = bundle.prefill(params, {"tokens": toks[:, :s]}, s + 1)
    assert cache["stack"][0]["k"].dtype == jnp.int8
    assert "k_scale" in cache["stack"][0]
    lengths = jnp.full((b,), s, jnp.int32)
    lg, _ = bundle.decode_step(params, toks[:, s:], cache, lengths)
    err = float(jnp.max(jnp.abs(lg[:, 0] - ref[:, s])))
    assert err < 0.1, err                   # int8 noise, not divergence
    monkeypatch.delenv("REPRO_KV_INT8")
    # plain path unaffected
    _, cache2 = bundle.prefill(params, {"tokens": toks[:, :s]}, s + 1)
    assert "k_scale" not in cache2["stack"][0]
