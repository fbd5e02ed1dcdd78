"""Runner: streamed campaigns of the paper's server over a load grid.

An answer is one ``campaign()`` call (pipelined mode, the default
superstep backend) over the cell's whole grid; the window repeats
answers on seeds s+1, s+2, … until ``seconds`` have passed and finishes
the answer in flight.  ``points_per_s`` is the grid points answered
over the time from the first answer's start to the last one's end.

Correctness compares what the window's answers produced, all of it:
the per-point results that the kernel handed to the fold (tapped at
``engine.dispatch_device``, the campaign's dispatch) and each answer's
merged accumulator, against plain references that import nothing of
the program — the exact chain (``refs.queue_chain``) for E[W] and a
NumPy float64/int64 fold for the accumulator.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.refs import queue_chain

Z95 = 1.959963984540054          # two-sided 95% normal quantile


def grid_arrays(cfg: dict, traffic: dict) -> dict:
    """The cell's grid: ``levels`` loads evenly spaced in
    [rho_lo, rho_hi] of each cap's stability limit, every cap, and
    ``replicas`` independent copies of each (load, cap) cell, laid out
    cell-fastest so every chunk covers every load."""
    a, t0 = cfg["alpha_ms"], cfg["tau0_ms"]
    caps = np.asarray(traffic["caps"])
    rho = np.repeat(np.linspace(traffic["rho_lo"], traffic["rho_hi"],
                                traffic["levels"]), len(caps))
    b = np.tile(caps, traffic["levels"])
    lam = (rho * b / (a * b + t0)).astype(np.float32)
    n_cells = len(lam)
    r = traffic["replicas"]
    return {"lam": np.tile(lam, r), "b_max": np.tile(b, r),
            "alpha": np.float32(a), "tau0": np.float32(t0),
            "cell": np.tile(np.arange(n_cells), r), "n_cells": n_cells}


class _Tap:
    """Keeps the device outputs of every kernel dispatch the campaign
    makes while armed; the fold gets them unchanged."""

    def __init__(self):
        self.outs = []

    @contextlib.contextmanager
    def armed(self):
        from repro.core import engine
        orig = engine.dispatch_device

        def tapped(kernel, params, keys, n, n_dev):
            out, pad = orig(kernel, params, keys, n, n_dev)
            self.outs.append(out)
            return out, pad

        engine.dispatch_device = tapped
        try:
            yield
        finally:
            engine.dispatch_device = orig


def setup(cfg: dict, traffic: dict, seed: int, devices) -> dict:
    from repro.core.campaign import campaign
    from repro.core.grid import SweepGrid
    from repro.core.sweep import sweep_caps

    g = grid_arrays(cfg, traffic)
    grid = SweepGrid.from_points(g["lam"], g["alpha"], g["tau0"],
                                 b_max=g["b_max"])
    caps = sweep_caps(grid)
    kw = dict(chunk_size=traffic["chunk"], n_batches=traffic["n_batches"],
              caps=caps, shard=len(devices))
    # warm-up: one chunk of the grid at the pinned caps compiles the
    # kernel and the fold at the shapes every answer uses
    first = grid.take(np.arange(traffic["chunk"]))
    campaign(first, seed=seed, **kw)
    return {"grid": grid, "g": g, "kw": kw, "seed": seed,
            "traffic": traffic, "cfg": cfg}


def window(st: dict, seconds: float, span) -> dict:
    from repro.core.campaign import campaign

    tap = _Tap()
    answers = []
    t_start = time.perf_counter()
    with tap.armed():
        while True:
            i = len(answers)
            with span("bench.answer"):
                res = campaign(st["grid"], seed=st["seed"] + 1 + i,
                               **st["kw"])
            answers.append({"acc": res.acc, "rows": res.rows,
                            "completed": res.completed,
                            "outs": tap.outs[-res.n_chunks:]})
            if time.perf_counter() - t_start >= seconds:
                break
    elapsed = time.perf_counter() - t_start
    n = len(st["grid"])
    t = st["traffic"]
    return {"elapsed_s": elapsed, "answers": answers,
            "e2e": {"points_per_s": n * len(answers) / elapsed},
            "counters": {"answers": len(answers), "points": n * len(answers),
                         "chunks": sum(len(a["rows"]) for a in answers),
                         "jobs": sum(int(a["acc"]["jobs"]) for a in answers),
                         "chunk": t["chunk"],
                         "cycles_per_point": t["n_batches"],
                         "n_bins": int(tap.outs[0]["hist"].shape[-1])}}


def _per_point(answer: dict, n: int) -> dict:
    """The answer's per-point results, in global point order."""
    import jax
    keys = ("mean_latency", "n_jobs", "hist", "dropped", "n_batches",
            "utilization", "mean_batch", "lat_bm_m2", "lat_bm_n")
    parts = {k: [] for k in keys}
    for row, out in zip(answer["rows"], answer["outs"]):
        host = jax.device_get({k: out[k] for k in keys})
        for k in keys:
            parts[k].append(np.asarray(host[k])[:row["points"]])
    pp = {k: np.concatenate(v) for k, v in parts.items()}
    assert len(pp["n_jobs"]) == n, (len(pp["n_jobs"]), n)
    return pp


def reference_fold(pp: dict, lam: np.ndarray, k_top: int,
                   float_dtype=np.float64, int_dtype=np.int64) -> dict:
    """The accumulator by its definition: a sequential left fold, in
    global point order, of every finite point (float64 sums, int64
    counts; the control passes narrower types)."""
    f, i = float_dtype, int_dtype
    lat = pp["mean_latency"].astype(f)
    util = pp["utilization"].astype(f)
    batch = pp["mean_batch"].astype(f)
    lamf = lam.astype(f)
    m2 = pp["lat_bm_m2"].astype(f)
    finite = (np.isfinite(lat) & np.isfinite(util) & np.isfinite(batch)
              & np.isfinite(lamf) & np.isfinite(m2))
    w = finite.astype(i)
    wf = finite.astype(f)
    jobs = pp["n_jobs"].astype(i)

    def seq(x):
        return np.cumsum(np.where(finite, x, 0).astype(f), dtype=f)[-1]

    acc = {
        "hist": (pp["hist"].astype(i) * w[:, None]).sum(0, dtype=i),
        "points": w.sum(dtype=i), "jobs": (jobs * w).sum(dtype=i),
        "batches": (pp["n_batches"].astype(i) * w).sum(dtype=i),
        "buffer_dropped": (pp["dropped"].astype(i) * w).sum(dtype=i),
        "n_in_slo": (jobs * w).sum(dtype=i),
        "n_fresh": (jobs * w).sum(dtype=i),
        "quarantined_points": (~finite).sum(dtype=i),
        "sum_latency_jobs": seq(lat * jobs.astype(f) * wf),
        "sum_latency": seq(lat * wf), "sum_util": seq(util * wf),
        "sum_batch": seq(batch * wf),
    }
    nb = pp["lat_bm_n"].astype(f)
    ci = Z95 * np.sqrt(m2 / np.maximum(nb - 1.0, 1.0) / np.maximum(nb, 1.0))
    ci = np.where(finite & (nb >= 2.0), ci, 0.0)
    acc["max_ci"] = ci.max(initial=0.0)
    for name, vals in (("lat", lat), ("good", lamf)):
        tv = np.full(k_top, -np.inf)
        ti = np.full(k_top, -1, np.int64)
        for g in np.flatnonzero(finite):
            am = int(np.argmin(tv))
            if vals[g] > tv[am]:
                tv[am], ti[am] = vals[g], g
        acc[f"top_{name}_val"], acc[f"top_{name}_idx"] = tv, ti
    return acc


def fold_gap(dev: dict, ref: dict) -> float:
    """Largest relative gap between two accumulators, key by key."""
    worst = 0.0
    for k, r in ref.items():
        d = np.asarray(dev[k], np.float64)
        r = np.asarray(r, np.float64)
        if k.endswith("_idx"):
            gap = float(np.any(d != r))
        else:
            same = d == r
            den = np.maximum(np.abs(r), 1.0 if k in ("hist",) or
                             np.issubdtype(np.asarray(ref[k]).dtype,
                                           np.integer) else 1e-300)
            gap = float(np.max(np.where(same, 0.0,
                                        np.abs(d - r) / den), initial=0.0))
        worst = max(worst, gap)
    return worst


def compare(st: dict, per_answer: list, accs: list) -> dict:
    """The compared numbers from per-point results and accumulators."""
    g = st["g"]
    n_cells = g["n_cells"]
    lam64 = g["lam"][:n_cells].astype(np.float64)
    b = g["b_max"][:n_cells]
    exact = np.array([queue_chain.mean_latency(
        float(lam64[c]), float(g["alpha"]), float(g["tau0"]), int(b[c]))
        for c in range(n_cells)])
    ew = np.concatenate([np.asarray(pp["mean_latency"], np.float64)
                         for pp in per_answer])
    cell = np.tile(g["cell"], len(per_answer))
    z2 = np.empty(n_cells)
    for c in range(n_cells):
        x = ew[cell == c]
        se = x.std(ddof=1) / np.sqrt(len(x))
        z2[c] = ((x.mean() - exact[c]) / se) ** 2 if se > 0 else np.inf
    hist_gap = max(float(np.max(np.abs(
        np.asarray(pp["hist"], np.float64).sum(1)
        - np.asarray(pp["n_jobs"], np.float64)))) for pp in per_answer)
    # independent replicas never agree to the last bit: a result that
    # repeats another point's of its cell was copied, not simulated
    key = np.stack([ew, np.concatenate([np.asarray(pp["n_jobs"], np.float64)
                                        for pp in per_answer])], 1)
    dup = 0
    for c in range(n_cells):
        x = key[cell == c]
        dup += len(x) - len(np.unique(x, axis=0))
    fgap = max(fold_gap(acc, reference_fold(pp, g["lam"],
                                            len(acc["top_lat_val"])))
               for pp, acc in zip(per_answer, accs))
    return {"ew_chi2": float(np.mean(z2)), "dup_points": float(dup),
            "hist_count_gap": hist_gap, "fold_rel_gap": fgap}


def check(st: dict, w: dict, limits: dict) -> dict:
    n = len(st["grid"])
    per_answer = [_per_point(a, n) for a in w["answers"]]
    for a in w["answers"]:
        a["outs"] = None             # the program's device buffers
    nums = compare(st, per_answer, [a["acc"] for a in w["answers"]])
    failed = 0
    for pp in per_answer:
        bad = (pp["dropped"] > 0) | ~np.isfinite(pp["mean_latency"])
        failed += int(bad.sum())
    failed += sum(n for a in w["answers"] if not a["completed"])
    return {"numbers": nums, "attempted": n * len(w["answers"]),
            "failed": failed}
