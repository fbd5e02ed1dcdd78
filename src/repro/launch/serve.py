"""Cluster serving entrypoint: the dynamic-batching engine (paper's system)
driven by a Poisson load generator, on this host's devices.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --rho 0.5 --jobs 300
"""
from __future__ import annotations

import argparse

from repro.configs import get_config, list_archs, reduced as reduce_cfg
from repro.core import BatchAllWaiting, CappedBatch, TimeoutBatch, phi
from repro.core.engine import init_compile_cache
from repro.serving import InferenceEngine

POLICIES = {
    "batch-all": lambda a: BatchAllWaiting(),
    "capped": lambda a: CappedBatch(cap=a.max_batch),
    "timeout": lambda a: TimeoutBatch(cap=a.max_batch),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--full", action="store_true",
                    help="published widths (one accelerator device); "
                         "default reduced")
    ap.add_argument("--workload", default="forward",
                    choices=["forward", "generate"])
    ap.add_argument("--rho", type=float, default=0.5)
    ap.add_argument("--jobs", type=int, default=300)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--policy", default="batch-all", choices=list(POLICIES))
    args = ap.parse_args()

    init_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduce_cfg(cfg)
    eng = InferenceEngine(cfg, workload=args.workload, seq_len=32,
                          max_batch=args.max_batch)
    model, r2 = eng.fit_service_model(samples=3)
    print(f"calibrated: alpha={model.alpha * 1e3:.3f} ms "
          f"tau0={model.tau0 * 1e3:.3f} ms (R^2={r2:.4f})")
    lam = args.rho / model.alpha
    res = eng.serve_poisson(lam, n_jobs=args.jobs,
                            policy=POLICIES[args.policy](args), seed=0)
    bound = float(phi(lam, model.alpha, model.tau0))
    print(f"rho={args.rho}: served {res.n_jobs} jobs  "
          f"E[W]={res.mean_latency * 1e3:.1f} ms (phi={bound * 1e3:.1f} ms) "
          f"E[B]={res.mean_batch:.1f} util={res.utilization:.3f} "
          f"p99={res.latency_p99 * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
