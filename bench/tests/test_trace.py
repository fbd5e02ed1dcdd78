"""The trace reduction on a small synthetic trace whose answers are
known by hand."""
import pytest
from jax.profiler import ProfileData

from bench import trace


def _ev(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, lines, names):
    body = "".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0 '
        + " ".join(_ev(*e) for e in evs) + " }\n"
        for i, (ln, evs) in enumerate(lines))
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{n}" }} }}\n' for k, n in names.items())
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


NAMES = {1: "fusion.1", 2: "_hist_body_kernel", 3: "jit_run_point(7)",
         4: "jit_fold(9)"}
# device 0: ops [100,300) and [200,500) overlap -> busy [100,500);
# op [700,800); window [0,1000) -> busy 500 ns, idle 50%
DEV0 = [("XLA Ops", [(1, 100, 200), (2, 200, 300), (1, 700, 100)]),
        ("XLA Modules", [(3, 100, 400), (4, 700, 100)])]
# device 1: one op [0, 900) clipped to the window -> busy 900 ns
DEV1 = [("XLA Ops", [(1, 0, 900)]), ("XLA Modules", [(3, 0, 900)])]
HOST = [("python", [(1, 0, 1000), (2, 50, 600), (3, 550, 300)])]


@pytest.fixture(scope="module")
def red():
    txt = (_plane(1, "/device:TPU:0", DEV0, NAMES)
           + _plane(2, "/device:TPU:1", DEV1, NAMES)
           + _plane(3, "/host:CPU", HOST,
                    {1: "bench.window", 2: "bench.answer",
                     3: "bench.host_work"}))
    return trace.reduce(ProfileData.from_text_proto(txt))


def test_union_and_idle(red):
    assert red["window_s"] == pytest.approx(1000e-9)
    d0, d1 = red["devices"].values()
    assert d0["busy_s"] == pytest.approx(500e-9)
    assert d1["busy_s"] == pytest.approx(900e-9)
    assert trace.idle_share(red) == pytest.approx([0.5, 0.1])


def test_time_by_name(red):
    d0 = red["devices"]["/device:TPU:0"]
    assert d0["ops_s"]["fusion.1"] == pytest.approx(300e-9)
    assert d0["ops_n"]["fusion.1"] == 2
    assert d0["modules_s"]["jit_run_point"] == pytest.approx(400e-9)
    t, n = trace.summed(red, "modules",
                        lambda name: name.startswith("jit_run_point"))
    assert (t, n) == (pytest.approx(1300e-9), 2)
    t, n = trace.summed(red, "ops", lambda name: "_hist_body" in name)
    assert (t, n) == (pytest.approx(300e-9), 1)
    assert red["dropped_s"] == 0


def test_idle_gaps_labelled_by_innermost_span(red):
    # device 0's gaps: [0,100) under bench.answer, [500,700) midpoint
    # 600 under bench.host_work, [800,1000) under bench.window only
    gaps = dict((label, t) for t, label in red["idle_gaps"])
    assert gaps["bench.answer"] == pytest.approx(100e-9)
    assert gaps["bench.host_work"] == pytest.approx(200e-9)
    assert gaps["bench.window"] == pytest.approx(200e-9)
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][1] == pytest.approx(200e-9)


def test_dropped_buffers_end_the_window():
    # the profiler lost [600, 1000): only what precedes it is read
    dev = DEV0 + [("XLA TraceMe", [(5, 600, 400)])]
    txt = (_plane(1, "/device:TPU:0", dev,
                  {**NAMES, 5: trace.DROPPED})
           + _plane(3, "/host:CPU", HOST,
                    {1: "bench.window", 2: "bench.answer",
                     3: "bench.host_work"}))
    red = trace.reduce(ProfileData.from_text_proto(txt))
    assert red["window_s"] == pytest.approx(600e-9)
    assert red["dropped_s"] == pytest.approx(400e-9)
    assert red["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(
        400e-9)
    # events seen whole still count by name, wherever they lie
    assert red["devices"]["/device:TPU:0"]["modules_n"]["jit_fold"] == 1


def test_union_helpers():
    assert trace.union([(5, 9), (0, 2), (1, 3), (8, 12)], 0, 10) == \
        [(0, 3), (5, 10)]
    assert trace.gaps([(0, 3), (5, 10)], 0, 12) == [(3, 5), (10, 12)]


def test_no_device_plane_is_an_error():
    txt = _plane(3, "/host:CPU", HOST, {1: "bench.window",
                                        2: "bench.answer",
                                        3: "bench.host_work"})
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(txt))


def test_readers_on_the_synthetic_trace(red):
    import json
    from bench.run import BENCH, read_metric
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    counters = {"chunk": 8, "cycles_per_point": 32,
                "chunks": 1, "jobs": 1000, "n_bins": 512,
                "model_flops": 1e3}
    ctx = {"trace": red, "counters": counters, "peaks": peaks,
           "log": lambda msg: None}
    # two chips, one jit_run_point run each of 8 / 2 points x 32 cycles
    assert read_metric("sweep_ns_per_point_cycle", ctx) == pytest.approx(
        1e9 * 1300e-9 / (2 * 4 * 32))
    assert read_metric("device_idle.tput", ctx) == pytest.approx(30.0)
    assert read_metric("model_mfu.tput", ctx) == pytest.approx(
        100 * 1e3 / (1000e-9 * 2 * 197e12))
    # no Mosaic call in this trace: the reader finds nothing to read
    assert read_metric("superstep_roofline", ctx) is None


MOSAIC = ('%closed_call.36 = s32[8,512] custom-call(...), '
          'custom_call_target="tpu_custom_call"')


def _runs_trace():
    # one device: two whole runs of jit_run_point with 2 Mosaic calls of
    # 1 us each, a run cut short that holds a third, and a call outside
    ops = [(MOSAIC, 1000, 2000), ("fusion.1", 2000, 3000),
           (MOSAIC, 3000, 4000), (MOSAIC, 11000, 12000),
           (MOSAIC, 13000, 14000), (MOSAIC, 21000, 22000),
           (MOSAIC, 30000, 31000)]
    mods = [("jit_run_point(3)", 0, 10000), ("jit_run_point(3)", 10000,
                                             20000),
            ("jit_fold(4)", 20000, 30000)]
    return {"window_s": 1.0, "devices": {"/device:TPU:0": {
        "events": {"ops": ops, "modules": mods}}}}


def test_ops_nested_in_whole_runs():
    got = trace.nested(_runs_trace(),
                       lambda n: n.startswith("jit_run_point"),
                       lambda n: "tpu_custom_call" in n)
    assert got == [(2, pytest.approx(2e-6)), (2, pytest.approx(2e-6))]


def test_superstep_roofline_per_call():
    import json
    from bench.counts import superstep
    from bench.run import BENCH, read_metric
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    counters = {"chunk": 8, "cycles_per_point": 64, "chunks": 2,
                "jobs": 4000, "n_bins": 512}
    got = read_metric("superstep_roofline", {
        "trace": _runs_trace(), "counters": counters, "peaks": peaks,
        "log": lambda msg: None})
    # 2 chunks x 2 calls counted in the trace: 1000 jobs a call over 8
    # points' histograms, 1 us a call
    want = superstep.least_time(1000, 8, 1, 512, peaks)["least_s"] / 1e-6
    assert got == pytest.approx(100 * want)
