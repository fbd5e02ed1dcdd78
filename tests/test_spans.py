"""Program spans (repro.core.metrics): the ring, parent links, the
compile listener, and the spans the campaign driver and the serving
engine open."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import metrics
from repro.core.analytic import LinearServiceModel
from repro.core.campaign import campaign
from repro.core.grid import SweepGrid
from repro.serving import InferenceEngine

V100 = LinearServiceModel(alpha=0.1438, tau0=1.8874)
CHUNK_SPANS = {"campaign.plan", "campaign.dispatch", "campaign.fold",
               "campaign.wait", "campaign.drain"}


def test_parent_links_and_the_ring_bound():
    log = metrics.SpanLog(maxlen=3)
    with log.span("outer", k=1) as outer:
        with log.span("inner") as inner:
            pass
        outer.attrs["late"] = 2
    assert inner.seconds >= 0 and outer.seconds >= inner.seconds
    (a, b) = log.spans()
    assert (a.name, a.parent) == ("inner", "outer")
    assert (b.name, b.parent, b.attrs) == ("outer", None,
                                           {"k": 1, "late": 2})
    assert b.start_ns <= a.start_ns <= a.end_ns <= b.end_ns
    for i in range(5):
        with log.span(f"s{i}"):
            pass
    assert [s.name for s in log.spans()] == ["s2", "s3", "s4"]


def test_a_span_closes_when_its_block_raises():
    log = metrics.SpanLog()
    with pytest.raises(ValueError):
        with log.span("outer"):
            with log.span("inner"):
                raise ValueError("boom")
    with log.span("after"):
        pass
    assert [(s.name, s.parent) for s in log.spans()] == [
        ("inner", "outer"), ("outer", None), ("after", None)]


def test_parents_are_per_thread():
    log = metrics.SpanLog()

    def other():
        with log.span("other"):
            log.record("timed", 1, 2)

    with log.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        log.record("here", 3, 4)
    by = {s.name: s.parent for s in log.spans()}
    assert by == {"timed": "other", "other": None, "here": "main",
                  "main": None}


def test_the_listener_sees_a_fresh_jits_compile():
    f = jax.jit(lambda x: x * 3.25 + 1.5)
    with metrics.span("test.compile") as outer:
        f(jnp.ones(7)).block_until_ready()
    mine = [s for s in metrics.spans()
            if outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns]
    compiles = [s for s in mine if s.name == "jax.compile"]
    assert compiles and all(s.parent == "test.compile" for s in compiles)
    assert any(s.name == "jax.trace" for s in mine)


@pytest.fixture(scope="module")
def warm_campaign():
    n = 384
    b = np.full(n, 8, np.int32)
    lam = (np.linspace(0.3, 0.9, n) * 8 / (V100.alpha * 8 + V100.tau0))
    g = SweepGrid.from_points(lam.astype(np.float32), V100.alpha,
                              V100.tau0, b_max=b)
    campaign(g, chunk_size=128, n_batches=64, seed=5)      # compiles
    res = campaign(g, chunk_size=128, n_batches=64, seed=5)
    call = [s for s in metrics.spans() if s.name == "campaign"][-1]
    kids = sorted((s for s in metrics.spans() if s.parent == "campaign"
                   and call.start_ns <= s.start_ns
                   and s.end_ns <= call.end_ns),
                  key=lambda s: s.start_ns)
    return res, call, kids


def test_campaign_spans_tile_the_call(warm_campaign):
    res, call, kids = warm_campaign
    assert call.attrs == {"points": 384, "chunks": 3, "mode": "pipelined"}
    assert {s.name for s in kids} == CHUNK_SPANS | {"campaign.result"}
    for a, b in zip(kids, kids[1:]):
        assert a.end_ns <= b.start_ns            # one after another
    covered = sum(s.end_ns - s.start_ns for s in kids)
    assert covered >= 0.95 * (call.end_ns - call.start_ns)
    for ci in range(res.n_chunks):
        mine = [s for s in kids if s.attrs.get("chunk") == ci]
        assert {s.name for s in mine} == CHUNK_SPANS
    plan = next(s for s in kids if s.name == "campaign.plan")
    # 64 service completions: two supersteps of 32
    assert (plan.attrs["steps_per_superstep"],
            plan.attrs["supersteps"]) == (32, 2)


def test_pipelined_rows_time_chunks_from_their_spans(warm_campaign):
    res, _, kids = warm_campaign
    assert not hasattr(res, "wall_s")
    for row in res.rows:
        assert "wall_s" not in row
        mine = {s.name: (s.end_ns - s.start_ns) * 1e-9 for s in kids
                if s.attrs.get("chunk") == row["chunk"]}
        host = (mine["campaign.plan"] + mine["campaign.dispatch"]
                + mine["campaign.fold"])
        assert row["host_s"] == pytest.approx(host, abs=2e-6)
        assert row["wait_s"] == pytest.approx(mine["campaign.wait"],
                                              abs=2e-6)


def test_run_batch_prepares_before_it_runs():
    cfg = reduced(get_config("qwen1.5-0.5b"))
    eng = InferenceEngine(cfg, workload="generate", seq_len=8,
                          gen_tokens=2, max_batch=2)
    eng.run_batch(2)
    t = eng.run_batch(2)
    batch = [s for s in metrics.spans() if s.name == "engine.batch"][-1]
    mine = [s for s in metrics.spans() if s.parent == "engine.batch"
            and batch.start_ns <= s.start_ns and s.end_ns <= batch.end_ns]
    prep, run = sorted(mine, key=lambda s: s.start_ns)
    assert (prep.name, run.name) == ("engine.prepare", "engine.run")
    assert prep.end_ns <= run.start_ns
    assert batch.attrs == {"b": 2, "bucket": 2}
    # the returned clock is the run span's, input preparation left out
    assert 0 < t <= (run.end_ns - run.start_ns) * 1e-9
