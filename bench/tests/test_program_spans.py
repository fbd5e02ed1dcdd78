"""The program-span readers on synthetic spans: alignment to a reduced
trace by a planted offset, each reader's value, and None where the
program keeps no spans."""
import importlib.util

import pytest

from bench import program_spans
from bench.run import BENCH
from repro.core import metrics
from repro.core.metrics import Span

MS = 1_000_000                       # ns
OFF = 7_000_000_123                  # trace clock - program clock, ns


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _answer(t0):
    """One two-chunk ``campaign()`` call starting at ``t0`` (program
    clock), its child spans tiling it: 20 ms of host work a chunk
    before the waits, 3,000 + 2,000 ms of waiting, 1 ms a drain and 1 ms
    for the result; plus one trace inside a plan span."""
    kids, t = [], t0
    for ci in (0, 1):
        for name, ms in (("campaign.plan", 12), ("campaign.dispatch", 3),
                         ("campaign.fold", 5)):
            attrs = {"chunk": ci}
            if name == "campaign.plan":
                attrs.update(steps_per_superstep=32, supersteps=8)
            kids.append(Span(name, "campaign", t, t + ms * MS, attrs))
            t += ms * MS
    kids.append(Span("jax.trace", "campaign.plan", t0 + MS, t0 + 2 * MS,
                     {"fun_name": "_threefry_fold_in"}))
    for ci, wait in ((0, 3000), (1, 2000)):
        kids.append(Span("campaign.wait", "campaign", t, t + wait * MS,
                         {"chunk": ci}))
        t += wait * MS
        kids.append(Span("campaign.drain", "campaign", t, t + MS,
                         {"chunk": ci}))
        t += MS
    kids.append(Span("campaign.result", "campaign", t, t + MS, {}))
    t += MS
    return kids + [Span("campaign", None, t0, t,
                        {"points": 8192, "chunks": 2})]


def _batch(t0, prep_ms=2, run_ms=5000):
    t1 = t0 + prep_ms * MS
    t2 = t1 + run_ms * MS
    return [Span("engine.prepare", "engine.batch", t0, t1, {}),
            Span("engine.run", "engine.batch", t1, t2, {}),
            Span("engine.batch", None, t0, t2, {"b": 32, "bucket": 32})]


def _ctx(prog, bench, calls, counter, busy=()):
    """A reduced trace over the window of the ``calls`` last ``bench``
    program spans, their benchmark spans exactly ``OFF`` later."""
    mine = sorted((s for s in prog if s.name == bench[1]),
                  key=lambda s: s.start_ns)[-calls:]
    lo, hi = mine[0].start_ns + OFF - MS, mine[-1].end_ns + OFF + MS
    spans = [("bench.window", lo, hi)] + [
        (bench[0], s.start_ns + OFF, s.end_ns + OFF) for s in mine]
    logs = []
    red = {"spans": spans, "window_s": (hi - lo) * 1e-9,
           "devices": {"/device:TPU:0": {"busy": list(busy)}}}
    return {"trace": red, "counters": {counter: calls},
            "log": logs.append, "logs": logs}


@pytest.fixture
def campaign_ctx(monkeypatch):
    # the set-up's warm answer, then two answers in the window
    prog = _answer(0) + _answer(20_000 * MS) + _answer(40_000 * MS)
    monkeypatch.setattr(metrics, "spans", lambda: list(prog))
    a1 = 20_000 * MS + OFF
    # the device idles from 160 to 1,000 ms into the window's first
    # answer, inside its first wait
    busy = [(a1 - MS, a1 + 160 * MS), (a1 + 1000 * MS, 10 ** 15)]
    return _ctx(prog, ("bench.answer", "campaign"), 2, "answers", busy)


def test_alignment_recovers_the_planted_offset(campaign_ctx):
    prog = sorted(metrics.spans(), key=lambda s: s.start_ns)
    got = program_spans.align(campaign_ctx["trace"]["spans"], prog,
                              "bench.answer", "campaign", 2,
                              campaign_ctx["log"])
    window = [s for s in prog if s.start_ns >= 20_000 * MS]
    assert len(got) == len(window)
    assert sorted((a.name, a.start, a.end) for a in got) == sorted(
        (s.name, s.start_ns + OFF, s.end_ns + OFF) for s in window)
    assert campaign_ctx["logs"] == ["program spans: 2 bench.answer/campaign"
                                    " pairs, residual start 0.0 ms, end "
                                    "0.0 ms"]


def test_campaign_host_and_wait_per_chunk(campaign_ctx):
    # per answer: 2 x 20 ms issuing chunks, 2 x 1 ms draining; 5,000 ms
    # waiting on summaries and 1 ms on the result; 4 chunks in all
    host = _reader("campaign_host_ms_per_chunk")(campaign_ctx)
    wait = _reader("campaign_wait_ms_per_chunk")(campaign_ctx)
    assert host == pytest.approx(2 * 42 / 4)
    assert wait == pytest.approx(2 * 5001 / 4)
    assert _reader("compiles_in_window.campaign")(campaign_ctx) == 0
    logs = "\n".join(campaign_ctx["logs"])
    assert "named spans cover 100.0%" in logs
    assert "steps_per_superstep=32, supersteps=8" in logs
    assert "jax.trace: 2 in the window" in logs
    # aligned once per run, however many readers ask
    assert sum(s.startswith("program spans:")
               for s in campaign_ctx["logs"]) == 1


def test_idle_gaps_are_labelled_by_the_innermost_program_span(
        campaign_ctx):
    spans = program_spans.window_spans(campaign_ctx, "bench.answer",
                                       "campaign", "answers")
    gaps = program_spans.idle_gaps(campaign_ctx["trace"], spans)
    assert gaps == [(pytest.approx(840 * MS), "campaign.wait")]
    assert "idle gaps > 100 us by program span" in "\n".join(
        campaign_ctx["logs"])


def test_engine_prepare_per_batch_and_compiles(monkeypatch):
    prog = (_batch(0) + _batch(10_000 * MS, prep_ms=3)
            + _batch(20_000 * MS, prep_ms=1))
    compile_span = Span("jax.compile", "engine.run", 15_000 * MS,
                        15_500 * MS, {"fun_name": "run"})
    prog.append(compile_span)
    monkeypatch.setattr(metrics, "spans", lambda: list(prog))
    ctx = _ctx(prog, ("bench.batch", "engine.batch"), 2, "batches")
    assert _reader("engine_prepare_ms_per_batch")(ctx) == pytest.approx(2)
    assert _reader("compiles_in_window.tput")(ctx) == 1


@pytest.mark.parametrize("name", [
    "campaign_host_ms_per_chunk", "campaign_wait_ms_per_chunk",
    "compiles_in_window.campaign", "engine_prepare_ms_per_batch",
    "compiles_in_window.tput"])
def test_no_program_spans_no_value(monkeypatch, name):
    monkeypatch.setattr(metrics, "spans", lambda: [])
    counter = "batches" if "tput" in name or "engine" in name else "answers"
    red = {"spans": [("bench.window", 0, 10 * MS),
                     ("bench.answer", MS, 2 * MS),
                     ("bench.batch", MS, 2 * MS)],
           "window_s": 0.01, "devices": {"/device:TPU:0": {"busy": []}}}
    ctx = {"trace": red, "counters": {counter: 1}, "log": lambda m: None}
    assert _reader(name)(ctx) is None


def test_a_program_without_the_span_log_gives_no_spans(monkeypatch):
    monkeypatch.delattr(metrics, "spans")
    assert program_spans.program_log() == []
