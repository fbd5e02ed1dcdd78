"""End-to-end serving-engine tests: real model, dynamic batching, Poisson
load — the system-level behaviour the paper characterizes."""
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import BatchAllWaiting, CappedBatch, TimeoutBatch, phi
from repro.core.analytic import LinearServiceModel
from repro.core.calibrate import fit_service_model
from repro.serving import InferenceEngine

V100 = LinearServiceModel(alpha=0.1438, tau0=1.8874)


@pytest.fixture(scope="module")
def engine():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    eng = InferenceEngine(cfg, workload="forward", seq_len=32, max_batch=16)
    eng.warmup()
    return eng


class _LinearEngine(InferenceEngine):
    """The engine's bucketing, calibration and event loop over the
    deterministic law τ(b) = α·b + τ0 evaluated at the bucket size: what
    the real engine measures, minus the host-clock noise that a loaded
    test machine adds to it.  No model is built."""

    def __init__(self, model: LinearServiceModel):
        self.model = model
        self.max_batch = 16
        self.buckets = [1, 2, 4, 8, 16]

    def run_batch(self, b: int) -> float:
        return float(self.model.tau(self.bucket_of(b)))


def test_calibration_linear(engine):
    # the real model: one positive, finite median time per bucket
    b, t = engine.calibrate(samples=3)
    assert list(b) == engine.buckets
    assert np.all(np.isfinite(t)) and np.all(t > 0)
    # the fit recovers a linear law exactly from what calibrate measures
    b, t = _LinearEngine(V100).calibrate(samples=3)
    model, r2 = fit_service_model(b, t)
    assert model.alpha == pytest.approx(V100.alpha, rel=1e-9)
    assert model.tau0 == pytest.approx(V100.tau0, rel=1e-9)
    assert r2 == pytest.approx(1.0, abs=1e-12)
    # throughput increases with batch size (Assumption 1(i))
    mu = b / t
    assert mu[-1] > mu[0]


def test_serve_poisson_basic(engine):
    model, _ = engine.fit_service_model(samples=3)
    lam = 0.3 / model.alpha
    res = engine.serve_poisson(lam, n_jobs=120, seed=0)
    assert res.n_jobs == 120
    assert res.mean_latency > 0
    assert 1.0 <= res.mean_batch <= engine.max_batch
    assert 0 < res.utilization <= 1.0
    # sojourn ≥ the single-job service floor for every request
    stub = _LinearEngine(V100)
    res = stub.serve_poisson(0.3 / V100.alpha, n_jobs=120, seed=0,
                             warmup=False)
    assert res.latencies.min() >= float(V100.tau(1))


def test_batching_kicks_in_under_load(engine):
    model, _ = engine.fit_service_model(samples=3)
    lo = engine.serve_poisson(0.05 / model.alpha, n_jobs=60, seed=1)
    hi = engine.serve_poisson(0.6 / model.alpha, n_jobs=200, seed=1)
    assert hi.mean_batch > lo.mean_batch   # Theorem 1 in the real system


def test_capped_policy_respects_bmax(engine):
    model, _ = engine.fit_service_model(samples=3)
    res = engine.serve_poisson(0.5 / model.alpha, n_jobs=150,
                               policy=CappedBatch(cap=4), seed=2)
    assert res.batch_sizes.max() <= 4


def test_timeout_policy_increases_batch(engine):
    """Timeout batching accumulates larger batches at light load (and pays
    latency for it — the beyond-paper comparison)."""
    model, _ = engine.fit_service_model(samples=3)
    lam = 0.15 / model.alpha
    nowait = engine.serve_poisson(lam, n_jobs=100,
                                  policy=BatchAllWaiting(), seed=3)
    wait = engine.serve_poisson(
        lam, n_jobs=100,
        policy=TimeoutBatch(max_wait=20 * model.tau0, target=8, cap=16),
        seed=3)
    assert wait.mean_batch >= nowait.mean_batch
    assert wait.mean_latency >= nowait.mean_latency * 0.9


def test_measured_latency_tracks_phi(engine):
    """Fig.-11 analogue: measured E[W] is the same order as φ(λ) and the
    bound degrades gracefully (buckets/noise put the real curve near or
    above φ, never far below)."""
    model, _ = engine.fit_service_model(samples=3)
    lam = 0.4 / model.alpha
    res = engine.serve_poisson(lam, n_jobs=250, seed=4)
    bound = float(phi(lam, model.alpha, model.tau0))
    assert res.mean_latency > 0.3 * bound
    assert res.mean_latency < 10.0 * bound


def test_generate_workload():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    eng = InferenceEngine(cfg, workload="generate", seq_len=16,
                          gen_tokens=3, max_batch=4)
    t = eng.run_batch(2)
    assert t > 0
    res = eng.serve_poisson(5.0, n_jobs=12, seed=0)
    assert res.n_jobs == 12


def test_bucketing_is_stairlike(engine):
    """Bucketed execution: batch 3 runs at the bucket-4 cost (the stair
    structure the paper observes on ResNet50)."""
    assert engine.bucket_of(3) == 4
    assert engine.bucket_of(4) == 4
    assert engine.bucket_of(5) == 8
    assert engine.bucket_of(16) == 16
