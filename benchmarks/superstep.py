"""Tentpole benchmark: fused pallas superstep kernel vs the lax path.

Three measurements over one pinned V100 grid, all in streaming-sketch
mode (64 fixed bins — the campaign-scale histogram configuration the
fused kernel targets):

- ``lax_sketch_dispatch`` / ``pallas_sketch_dispatch``: the same sweep,
  warm (cold compile happens before timing), through the two superstep
  backends.  Both rows carry ``total_jobs`` so ``run.py`` derives
  jobs/sec per backend — the headline fused-vs-reference rate.
- ``fused_speedup``: the warm-time ratio plus a bitwise witness that
  the two backends produced identical histograms and job counts (the
  fused kernel is a drop-in, not an approximation).
- ``tapped_campaign``: the same dispatch with a ``MetricsTap``
  attached, streaming one JSONL record per superstep plus a
  Prometheus-style text file (``--metrics-dir``); the payload reports
  how many supersteps/lane-records flowed through ``io_callback``.

Caps are pinned once from the full grid via ``sweep_caps`` so every
row (and any future split of this grid) shares identical kernel
shapes.
"""
from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

from benchmarks.common import Row, V100, enable_host_devices, timed

enable_host_devices()          # before any JAX backend initialization

B_MAX = 8
RHOS = [0.3, 0.5, 0.7, 0.8, 0.9, 0.95]


def run(n_batches: int = 3000,
        metrics_dir: Optional[str] = None) -> List[Row]:
    from repro.core.analytic import stability_limit
    from repro.core.grid import SweepGrid
    from repro.core.metrics import MetricsTap
    from repro.core.sweep import sweep, sweep_caps
    from repro.kernels.superstep import resolve_backend

    rows: List[Row] = []
    lim = stability_limit(V100.alpha, V100.tau0, B_MAX)
    grid = SweepGrid.from_product([r * lim for r in RHOS],
                                  [V100.alpha], [V100.tau0],
                                  b_maxes=(B_MAX,))
    caps = sweep_caps(grid, q_cap=64)

    results = {}

    def dispatch(backend):
        def fn():
            r = sweep(grid, n_batches=n_batches, seed=7, sketch=True,
                      superstep_backend=backend, **caps)
            results[backend] = r
            return {"points": len(grid), "n_batches": n_batches,
                    "backend": backend,
                    "total_jobs": int(r.n_jobs.sum())}
        return fn

    for backend in ("lax", "pallas"):
        fn = dispatch(backend)
        fn()                                   # cold: compile + run
        rows.append(timed(fn, f"superstep/{backend}_sketch_dispatch"))

    t_lax = rows[-2].us_per_call
    t_pallas = rows[-1].us_per_call

    def fused_speedup():
        bitwise = (np.array_equal(results["lax"].hist,
                                  results["pallas"].hist)
                   and np.array_equal(results["lax"].n_jobs,
                                      results["pallas"].n_jobs))
        return {"auto_backend": resolve_backend(None),
                "lax_s": t_lax / 1e6, "pallas_s": t_pallas / 1e6,
                "speedup": t_lax / t_pallas,
                "bitwise_equal": bool(bitwise)}
    rows.append(timed(fused_speedup, "superstep/fused_speedup"))

    def tapped_campaign():
        mdir = metrics_dir or "."
        os.makedirs(mdir, exist_ok=True)
        jsonl = os.path.join(mdir, "superstep_metrics.jsonl")
        prom = os.path.join(mdir, "superstep_metrics.prom")
        open(jsonl, "w").close()               # fresh campaign file
        with MetricsTap(jsonl, prom, label="bench_campaign",
                        expected_points=len(grid)) as tap:
            r = sweep(grid, n_batches=n_batches, seed=7, sketch=True,
                      metrics_tap=tap, **caps)
        s = tap.summary()
        return {"points": len(grid),
                "total_jobs": int(r.n_jobs.sum()),
                "supersteps": s["supersteps"],
                "records": s["records"],
                "jsonl": jsonl}
    rows.append(timed(tapped_campaign, "superstep/tapped_campaign"))
    return rows
