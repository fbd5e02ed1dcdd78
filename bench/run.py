#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data, found by name: its entry in
``BENCHMARK.json`` (configuration, traffic, chips), the configuration
file it names, ``bench/traffic/<traffic>.json`` (which names the runner,
``bench/runners/<runner>.py``), the limits of its correctness check in
``bench/limits/<cell>.json``, and for ``--trace 1`` one reader per
per-layer metric, ``bench/metrics/<metric>.py``.

A run: set-up (weights or grid from the seed, warm-up of the cell's own
shapes; timed as ``setup_s``), the measured window of ``--seconds``
(``--trace 0``) or a short traced window (``--trace 1``), the device's
peak memory, then the check against the plain references.  The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, spec: dict | None = None) -> dict:
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    reported = {m["name"] for m in spec["end_to_end"]
                if name in m.get("workloads", [name])}
    e2e = [m for m in spec["end_to_end"] if m["name"] in reported]
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"name": name, "cell": cell, "cfg": cfg, "traffic": traffic,
            "limits": limits, "e2e": e2e, "per_layer": layer}


def read_metric(name: str, ctx: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """Set-up, window, check; returns the result object.  Tests call this
    with CPU devices and small configurations."""
    import jax

    runner = importlib.import_module(f"bench.runners.{c['traffic']['runner']}")
    t0 = time.perf_counter()
    st = runner.setup(c["cfg"], c["traffic"], seed, devices)
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s}")

    red = None
    if trace:
        tdir = OUT / f"trace-{c['name']}-{seed}"
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        jax.profiler.start_trace(str(tdir))
        try:
            with span("bench.window"):
                w = runner.window(st, c["traffic"]["trace_seconds"], span)
        finally:
            jax.profiler.stop_trace()
        from bench import trace as trace_mod
        from jax.profiler import ProfileData
        red = trace_mod.reduce(ProfileData.from_file(
            trace_mod.find_xplane(str(tdir))))
        shutil.rmtree(tdir, ignore_errors=True)
        for dev, d in red["devices"].items():
            for key in ("modules_s", "ops_s"):
                top = sorted(d[key].items(), key=lambda kv: -kv[1])[:8]
                log(f"trace {dev} {key}: " + ", ".join(
                    f"{n[:120]}={t:.6f}" for n, t in top))
    else:
        w = runner.window(st, seconds, span)
    log(f"window_s={w['elapsed_s']}")

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    t1 = time.perf_counter()
    chk = runner.check(st, w, c["limits"])
    log(f"check_s={time.perf_counter() - t1}")

    metrics = {}
    if trace:
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
        kind = devices[0].device_kind
        if kind not in peaks:
            raise ValueError(f"no peaks for device kind {kind!r} in "
                             "bench/peaks.json")
        ctx = {"trace": red, "counters": w["counters"], "cfg": c["cfg"],
               "traffic": c["traffic"], "cell": c["name"], "log": log,
               "peaks": peaks[kind], "device_kind": kind}
        for m in c["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in c["e2e"]:
            v = setup_s if m["name"] == "setup_s" else w["e2e"][m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    compared = {k: {"value": v, "limit": c["limits"][k]}
                for k, v in chk["numbers"].items()}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": chk["attempted"],
              "failed": chk["failed"], "metrics": metrics, "device": device}
    if red is not None:
        from bench import trace as trace_mod
        device["busy_s"] = (sum(d["busy_s"] for d in red["devices"].values())
                            / len(red["devices"]))
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace_mod.breakdown(red)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = load_cell(args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from repro.core.engine import init_compile_cache

    init_compile_cache()
    # every program of the cell goes into the cache, the short ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    chips = c["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    result = run_cell(c, args.seed, args.seconds, bool(args.trace),
                      devs[:chips])
    for k, v in result["compared"].items():
        log(f"compared {k}={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
