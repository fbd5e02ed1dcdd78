#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's sound
runs on many seeds, and the control on the same seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds 101,102,... \
        --seconds <s> [--control N]

For each seed: the cell's own set-up and a window of ``--seconds`` at
the cell's own sizes and load, the check's numbers for it, and the
control's numbers.  The control puts a plain reference in the program's
place, computed one precision step below what the configuration states:

- served models (bfloat16): at every position of the sampled requests'
  prompts and served tokens, the token that a float8_e4m3 reference
  puts first, read by its gap under the float32 reference;
- the simulator (float32 kernel, float64/int64 fold): the plain
  simulator ``refs.queue_sim`` in bfloat16 for the per-point results,
  on the same grid and as many answers as the window made, and the fold
  in float32/int32.

One JSON line per seed goes to standard output; the benchmark's own
runs never run the control.  Limits are set in ``bench/limits/<cell>.json``
from these readings (PERF.md records which).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def campaign_control(st: dict, n_answers: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.refs import queue_sim
    from bench.runners import campaign as run_c
    from repro.core.sweep import sweep_caps

    g, t = st["g"], st["traffic"]
    caps = sweep_caps(st["grid"])
    sim = jax.jit(jax.vmap(lambda lam, b, key: queue_sim.simulate(
        lam, g["alpha"], g["tau0"], b, key, n_batches=t["n_batches"],
        warmup=t["n_batches"] // 10, q_cap=caps["q_cap"],
        a_cap=caps["a_cap"], b_top=int(np.max(g["b_max"])),
        dtype=jnp.bfloat16)))
    per_answer, accs = [], []
    for i in range(n_answers):
        keys = jax.random.split(jax.random.PRNGKey((seed + i) % 2 ** 31),
                                len(g["lam"]))
        out = jax.device_get(sim(g["lam"], g["b_max"], keys))
        pp = {"mean_latency": np.asarray(out["mean_latency"], np.float32),
              "n_jobs": np.asarray(out["n_jobs"], np.float32),
              "hist": np.asarray(out["hist"], np.float32),
              "dropped": np.zeros(len(g["lam"]), np.int32),
              "n_batches": np.full(len(g["lam"]), t["n_batches"], np.int32),
              "utilization": np.ones(len(g["lam"]), np.float32),
              "mean_batch": np.ones(len(g["lam"]), np.float32),
              "lat_bm_m2": np.asarray(out["lat_bm_m2"], np.float32),
              "lat_bm_n": np.asarray(out["lat_bm_n"], np.float32)}
        per_answer.append(pp)
        accs.append(run_c.reference_fold(pp, g["lam"], 16,
                                         float_dtype=np.float32,
                                         int_dtype=np.int32))
    return run_c.compare(st, per_answer, accs)


def read_seed(c: dict, seed: int, seconds: float, devs,
              control: bool = True) -> dict:
    """One seed: set-up, window, the check's numbers, and (where
    ``control``) the control's."""
    import importlib

    from bench.run import span
    runner = importlib.import_module(
        f"bench.runners.{c['traffic']['runner']}")
    t0 = time.perf_counter()
    st = runner.setup(c["cfg"], c["traffic"], seed, devs)
    w = runner.window(st, seconds, span)
    row = {"seed": seed, "window_s": w["elapsed_s"], **w["e2e"]}
    if c["traffic"]["runner"] == "serve":
        from bench.runners import serve
        prompts, served, _ = serve.collect(
            st, w, c["traffic"]["sample_requests"])
        weights = st.pop("weights")
        del st["eng"]
        st["log"].clear()
        gaps, seqs = serve.served_gaps(c["cfg"], weights, prompts, served,
                                       c["limits"]["logit_gap_max"])
        row["logit_gap_max"] = float(gaps.max())
        row["gaps"] = [float(x) for x in gaps]
        if not control:
            row["total_s"] = time.perf_counter() - t0
            return row
        cg = serve.control_gaps(c["cfg"], weights, seqs,
                                c["traffic"]["prompt_tokens"])
        row["control.logit_gap_max"] = float(cg.max())
        row["control.gaps"] = [float(x) for x in cg]
    else:
        chk = runner.check(st, w, c["limits"])
        row.update(chk["numbers"], failed=chk["failed"],
                   answers=len(w["answers"]))
        print(f"seed {seed}: {chk['numbers']} after "
              f"{time.perf_counter() - t0} s", file=sys.stderr, flush=True)
        if control:
            t1 = time.perf_counter()
            ctl = campaign_control(st, len(w["answers"]), seed)
            row.update({f"control.{k}": v for k, v in ctl.items()},
                       control_s=time.perf_counter() - t1)
    row["total_s"] = time.perf_counter() - t0
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=None,
                    help="run the control on the first N seeds only "
                         "(default: every seed)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench.run import load_cell
    from repro.core.engine import init_compile_cache

    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    c = load_cell(args.workload)
    devs = jax.devices()[:c["cell"]["chips"]]
    seeds = [int(s) for s in args.seeds.split(",")]
    n_ctl = len(seeds) if args.control is None else args.control
    for i, seed in enumerate(seeds):
        print(json.dumps(read_seed(c, seed, args.seconds, devs,
                                   control=i < n_ctl)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
