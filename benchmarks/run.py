"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per measurement) and,
unless ``--no-json`` is given, writes a machine-readable
``BENCH_<module>.json`` per module (wall clock, per-row payloads, and
points/sec for dispatch rows) so the perf trajectory is tracked across
PRs.

  python -m benchmarks.run             # everything (≈ minutes)
  python -m benchmarks.run --quick     # smaller sims, fewer served jobs
  python -m benchmarks.run --only fig4 # single module
  python -m benchmarks.run --json-dir out/   # JSON location (default .)
  python -m benchmarks.run --quick --compare benchmarks/baselines/
      # after running, diff wall clock + payloads against the committed
      # baselines; exit nonzero on a >25% wall-clock regression

``--compare`` also works without running anything (``--only none``) if
the ``--json-dir`` already holds fresh BENCH JSONs.  The report is
printed and written to ``BENCH_compare.txt`` in ``--json-dir`` (CI
uploads it as an artifact).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# wall-clock regression tolerance for --compare (shared-CI-runner noise
# plus real regressions; deliberately loose — payload deltas catch the
# rest)
WALL_REGRESSION_TOL = 0.25

# payload keys worth diffing between baseline and current rows: rates
# and speedups (higher = better); absolute seconds are covered by the
# module wall clock
_RATE_KEYS = ("points_per_sec", "jobs_per_sec")

# hard payload gates asserted by --compare on the CURRENT run (not
# deltas — absolute contracts a PR must not break).  Each entry:
# (module, row name, payload key, predicate, failure message).
PAYLOAD_GATES = (
    ("adaptive", "adaptive/job_savings", "job_savings",
     lambda v: float(v) >= 3.0,
     "adaptive campaign must save >=3x simulated jobs"),
    ("adaptive", "adaptive/job_savings", "matched",
     lambda v: bool(v),
     "adaptive campaign missed the baseline max-CI target"),
    ("adaptive", "adaptive/job_savings", "buffer_dropped",
     lambda v: int(v) == 0,
     "buffer drops invalidate the matched-precision comparison"),
    ("availability", "availability/fleet_dispatch", "buffer_dropped",
     lambda v: int(v) == 0,
     "queue_capacity headroom must absorb repair backlogs without "
     "buffer drops (satellite S1's sizing contract)"),
    ("availability", "availability/chain_crosscheck", "mean_rel_err",
     lambda v: float(v) < 0.03,
     "failure-regime MC drifted from the completion-time chain"),
    ("availability", "availability/chain_crosscheck", "max_abs_z",
     lambda v: float(v) < 3.5,
     "a chain-crosscheck cell deviates beyond its Monte Carlo error"),
    ("availability", "availability/mtbf_inf_reduction", "bitwise_equal",
     lambda v: bool(v),
     "MTBF=inf points must be bitwise identical to the base kernel"),
)


def _check_payload_gates(cur: dict) -> list:
    """Evaluate PAYLOAD_GATES against the current run's BENCH docs.
    A module absent from the run is not gated (e.g. ``--only fig4``);
    a PRESENT module missing the gated row/key fails loudly."""
    fails = []
    for mod, row_name, key, pred, msg in PAYLOAD_GATES:
        doc = cur.get(mod)
        if doc is None:
            continue
        row = next((r for r in doc.get("rows") or []
                    if isinstance(r, dict) and r.get("name") == row_name),
                   None)
        payload = (row or {}).get("payload") or {}
        if key not in payload:
            fails.append((mod, f"{row_name}: missing gated payload "
                               f"key {key!r}"))
            continue
        try:
            ok = pred(payload[key])
        except (TypeError, ValueError):
            ok = False
        if not ok:
            fails.append((mod, f"{row_name}: {key}={payload[key]!r} "
                               f"— {msg}"))
    return fails


def _load_bench(dirpath: Path) -> dict:
    docs = {}
    for p in sorted(dirpath.glob("BENCH_*.json")):
        try:
            doc = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError) as e:  # noqa: PERF203
            print(f"--compare: skipping unreadable {p}: {e}")
            continue
        docs[doc.get("module", p.stem.replace("BENCH_", ""))] = doc
    return docs


def _row_rates(doc: dict) -> dict:
    out = {}
    for row in doc.get("rows") or []:
        # tolerate hand-edited / truncated baselines: a malformed row
        # (non-dict, or missing its name) is just not comparable
        if not isinstance(row, dict) or not row.get("name"):
            continue
        rates = {}
        candidates = {k: row.get(k) for k in _RATE_KEYS}
        candidates["speedup"] = (row.get("payload") or {}).get("speedup")
        for k, v in candidates.items():
            # campaign rows carry structural payloads (fingerprints,
            # sketch-only summaries) where a rate key may be absent or
            # non-numeric — such a row is just not rate-comparable
            try:
                rates[k] = float(v)
            except (TypeError, ValueError):
                continue
        if rates:
            out[row["name"]] = rates
    return out


def compare_runs(baseline_dir: Path, current_dir: Path) -> tuple:
    """Per-module wall-clock and payload deltas vs the committed
    baselines.  Returns (report_lines, regressed_module_names)."""
    base, cur = _load_bench(baseline_dir), _load_bench(current_dir)
    lines = [f"benchmark comparison: {current_dir} vs baseline "
             f"{baseline_dir}",
             f"{'module':<12} {'base_s':>8} {'now_s':>8} {'delta':>8}"]
    regressed = []
    for mod in sorted(set(base) & set(cur)):
        b, c = base[mod], cur[mod]
        if b.get("quick") != c.get("quick"):
            lines.append(f"{mod:<12} SKIP (quick flag differs: baseline="
                         f"{b.get('quick')} current={c.get('quick')})")
            continue
        try:
            bw, cw = float(b["wall_s"]), float(c["wall_s"])
        except (KeyError, TypeError, ValueError):
            lines.append(
                f"{mod:<12} SKIP (missing/non-numeric wall_s: baseline="
                f"{b.get('wall_s')!r} current={c.get('wall_s')!r})")
            continue
        if bw > 0:
            delta = (cw - bw) / bw
            flag = ""
            if delta > WALL_REGRESSION_TOL:
                flag = "  << REGRESSION"
                regressed.append(mod)
            lines.append(f"{mod:<12} {bw:8.2f} {cw:8.2f} "
                         f"{delta:+8.1%}{flag}")
        else:
            # a zero/negative baseline wall clock cannot gate anything
            # (the delta is undefined) — report it, never flag it
            lines.append(f"{mod:<12} {bw:8.2f} {cw:8.2f} {'n/a':>8}"
                         "  (degenerate baseline wall_s; not gated)")
        brates, crates = _row_rates(b), _row_rates(c)
        for name in sorted(set(brates) & set(crates)):
            for key in sorted(set(brates[name]) & set(crates[name])):
                bv, cv = float(brates[name][key]), float(crates[name][key])
                if bv <= 0:
                    continue
                rd = (cv - bv) / bv
                if abs(rd) >= 0.10:     # only report moving payloads
                    lines.append(f"    {name} {key}: {bv:.6g} -> "
                                 f"{cv:.6g} ({rd:+.1%})")
    for mod in sorted(set(base) - set(cur)):
        lines.append(f"{mod:<12} MISSING from current run")
    for mod in sorted(set(cur) - set(base)):
        lines.append(f"{mod:<12} NEW (no baseline)")
    gate_fails = _check_payload_gates(cur)
    for mod, msg in gate_fails:
        lines.append(f"GATE FAIL [{mod}] {msg}")
        if mod not in regressed:
            regressed.append(mod)
    if regressed:
        lines.append(f"FAIL: wall-clock regression >"
                     f"{WALL_REGRESSION_TOL:.0%} or payload-gate "
                     "failure in: " + ", ".join(regressed))
    else:
        lines.append("OK: no module regressed beyond "
                     f"{WALL_REGRESSION_TOL:.0%}; payload gates pass")
    return lines, regressed


def _row_json(row) -> dict:
    d = {"name": row.name, "us_per_call": round(row.us_per_call, 1)}
    payload = row.payload or {}
    d["payload"] = {k: v for k, v in payload.items()}
    # throughput rates only make sense for rows that actually timed the
    # work named in the payload (dispatch/loop rows, ≥ms-scale) — a
    # derived summary row also carries points/jobs keys but only times
    # building its result dict
    if row.us_per_call >= 1e4:
        points = payload.get("points")
        if points:
            d["points_per_sec"] = round(points / (row.us_per_call / 1e6),
                                        2)
        jobs = payload.get("total_jobs", payload.get("jobs"))
        if jobs:
            d["jobs_per_sec"] = round(jobs / (row.us_per_call / 1e6), 1)
    return d


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<module>.json files")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_<module>.json")
    ap.add_argument("--metrics-dir", default=None,
                    help="directory for streaming campaign metrics "
                         "(JSONL + Prometheus text); defaults to "
                         "--json-dir")
    ap.add_argument("--compare", default=None, metavar="BASELINE_DIR",
                    help="after running, diff --json-dir against the "
                         "baseline BENCH JSONs in this directory; exit "
                         "nonzero on a >25%% wall-clock regression")
    args = ap.parse_args()
    if args.compare and args.no_json:
        # --no-json writes nothing into --json-dir, so the comparison
        # would silently diff stale (or missing) files
        sys.exit("--compare needs the fresh BENCH JSONs; "
                 "drop --no-json")

    from benchmarks import (adaptive, availability, backpressure,
                            campaign, continuous, fig4_latency_bound,
                            fig5_utilization, fig6_energy,
                            fig7_tradeoff, fig8_finite_bmax,
                            fig9_batch_times, fig11_served_latency,
                            policies, replicas, roofline, superstep,
                            table1_throughput, tails)
    from repro.core.engine import init_compile_cache

    init_compile_cache()
    modules = {
        "table1": lambda: table1_throughput.run(),
        "fig4": lambda: fig4_latency_bound.run(
            n_batches=1_000 if args.quick else 4_000),
        "fig5": lambda: fig5_utilization.run(
            dense_K=2048 if args.quick else 4096),
        "fig6": lambda: fig6_energy.run(
            n_jobs=30_000 if args.quick else 100_000),
        "fig7": lambda: fig7_tradeoff.run(
            n_batches=800 if args.quick else 3_000),
        "fig8": lambda: fig8_finite_bmax.run(
            n_batches=1_000 if args.quick else 4_000,
            dense_K=2048 if args.quick else 4096),
        "fig9": lambda: fig9_batch_times.run(
            samples=2 if args.quick else 3,
            max_batch=16 if args.quick else 32),
        "fig11": lambda: fig11_served_latency.run(
            n_jobs=80 if args.quick else 200),
        "policies": lambda: policies.run(
            n_jobs=30_000 if args.quick else 100_000),
        "continuous": lambda: continuous.run(
            n_steps=2_048 if args.quick else 4_096),
        "tails": lambda: tails.run(
            n_batches=1_500 if args.quick else 6_000),
        "replicas": lambda: replicas.run(
            n_steps=1_500 if args.quick else 4_000),
        "backpressure": lambda: backpressure.run(
            n_batches=1_200 if args.quick else 3_000),
        "availability": lambda: availability.run(
            n_steps=2_000 if args.quick else 6_000,
            chain_batches=3_000 if args.quick else 6_000),
        "roofline": lambda: roofline.run(),
        "superstep": lambda: superstep.run(
            n_batches=1_024 if args.quick else 3_000,
            metrics_dir=args.metrics_dir or args.json_dir),
        "campaign": lambda: campaign.run(quick=args.quick),
        "adaptive": lambda: adaptive.run(quick=args.quick),
    }
    if args.only:
        modules = {k: v for k, v in modules.items() if k == args.only}
        if not modules and args.only != "none":
            sys.exit(f"unknown module {args.only!r}")

    json_dir = Path(args.json_dir)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in modules.items():
        t0 = time.perf_counter()
        try:
            rows = list(fn())
        except Exception as e:  # noqa: BLE001
            # report the module and carry on with the rest; the exit
            # code below still says the run failed
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            failed.append(name)
            continue
        wall_s = time.perf_counter() - t0
        for row in rows:
            print(row.csv(), flush=True)
        if args.no_json:
            continue
        doc = {"module": name, "wall_s": round(wall_s, 3),
               "quick": bool(args.quick),
               "rows": [_row_json(r) for r in rows]}
        json_dir.mkdir(parents=True, exist_ok=True)
        path = json_dir / f"BENCH_{name}.json"
        path.write_text(json.dumps(doc, indent=1, default=str) + "\n")

    if args.compare:
        lines, regressed = compare_runs(Path(args.compare), json_dir)
        report = "\n".join(lines) + "\n"
        print(report, end="", flush=True)
        json_dir.mkdir(parents=True, exist_ok=True)
        (json_dir / "BENCH_compare.txt").write_text(report)
        if regressed:
            sys.exit(1)
    if failed:
        sys.exit(f"module(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
