"""The plain references against closed forms and against each other."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.refs import qwen, queue_chain, queue_sim

A, T0 = 0.1438, 1.8874


@pytest.mark.parametrize("rho", [0.2, 0.6, 0.9])
def test_chain_is_md1_at_cap_one(rho):
    # b_max = 1 is M/D/1 with service s: Pollaczek-Khinchine
    s = A + T0
    lam = rho / s
    want = s + lam * s * s / (2.0 * (1.0 - rho))
    assert queue_chain.mean_latency(lam, A, T0, 1) == pytest.approx(
        want, rel=1e-9)


def test_simulator_matches_chain_in_float32():
    b = np.array([4, 32, 4, 32])
    rho = np.array([0.3, 0.3, 0.8, 0.8])
    lam = rho * b / (A * b + T0)
    reps = 48
    sim = jax.jit(jax.vmap(lambda l, bb, k: queue_sim.simulate(
        l, A, T0, bb, k, n_batches=256, warmup=25, q_cap=128, a_cap=64,
        b_top=32)))
    keys = jax.random.split(jax.random.PRNGKey(3), 4 * reps)
    out = jax.device_get(sim(np.repeat(lam, reps).astype(np.float32),
                             np.repeat(b, reps), keys))
    ew = np.asarray(out["mean_latency"], np.float64).reshape(4, reps)
    exact = np.array([queue_chain.mean_latency(l, A, T0, int(bb))
                      for l, bb in zip(lam, b)])
    z = (ew.mean(1) - exact) / (ew.std(1, ddof=1) / np.sqrt(reps))
    assert np.all(np.abs(z) < 4.5), z
    hist = np.asarray(out["hist"], np.float64).sum(1)
    assert np.array_equal(hist, np.asarray(out["n_jobs"], np.float64))


def test_qwen_reference_matches_the_program_forward():
    from bench.runners.serve import model_config
    from bench.tests.small import QWEN_TINY, cell
    from repro.models import build

    cfg = dict(cell("qwen05.saturated")["cfg"], torch_dtype="float32")
    cfg.update(QWEN_TINY)
    w = qwen.make_weights(cfg, 3, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = build(model_config(cfg)).forward(w, {"tokens": toks})
    want = qwen.forward(cfg, w, toks, 0)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_float8_reference_is_coarser():
    from bench.tests.small import QWEN_TINY, cell
    cfg = dict(cell("qwen05.saturated")["cfg"])
    cfg.update(QWEN_TINY)
    w = qwen.make_weights(cfg, 4)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (1, 16)), jnp.int32)
    f32 = qwen.forward(cfg, w, toks, 0)
    f8 = qwen.forward(cfg, w, toks, 0, fp8=True)
    rel = float(jnp.sqrt(jnp.mean((f8 - f32) ** 2) / jnp.mean(f32 ** 2)))
    assert 1e-3 < rel < 0.5, rel
