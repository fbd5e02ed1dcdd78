#!/usr/bin/env python3
"""Smoke run of the whole system on one TPU chip, through its entry points.

    python3 chip_smoke.py                # one chip: serve, simulate,
                                         # campaign, exact
    python3 chip_smoke.py --four-chips   # the sharded simulator only,
                                         # four chips against one

Phases, each in this one process (a chip belongs to one process):

- serve: qwen1.5-0.5b at its published widths (24 layers, d_model 1024,
  vocab 151936, bf16) with seeded random weights, through
  ``InferenceEngine(workload="generate")``: calibrate τ[b] per batch
  bucket, fit α and τ0, serve Poisson requests at half the fitted law's
  stability limit, and check prefill + cached decode logits against a
  float32 full forward pass.
- simulate: ``evaluate(..., backend="sweep")`` over a 4096-point grid at
  Table 1's V100 constants, with the default superstep backend; E[W]
  against the exact chain, and the Pallas histogram counts against the
  lax reference.
- campaign: a pipelined campaign at two chunk sizes; the merged
  accumulators must be bitwise equal.
- exact: ``markov.solve_grid`` over a (λ, b_max) surface against the
  NumPy structured solver.

Every phase prints one line of numbers.  A failed phase is reported and
the run goes on, but the exit code is then 1 and the closing JSON line
is not printed.  Without a TPU the script exits 1 before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core.analytic import LinearServiceModel  # noqa: E402

V100 = LinearServiceModel(alpha=0.1438, tau0=1.8874)  # ms, paper Table 1
SEED = 0

# E[W] of every simulated point must lie within this many of its own
# 95% CI half-widths of the exact chain.  The batch-means CI is itself
# estimated, and 4096 points sample its tail; 5 half-widths is ~10 σ.
CI_SLACK = 5.0

# Relative RMS error allowed between the bf16 model's logits (prefill,
# then decode through the KV cache) and a float32 forward pass of the
# same bf16-valued weights.  bf16 keeps 8 mantissa bits, so each
# rounding of an activation is off by up to 2^-9 relative; the 24
# layers round the residual stream and its branch outputs ~100 times,
# which grows like sqrt(100) * 2^-9 ~ 0.02 if the errors are
# independent.  A broken cache or position shifts the logits by O(1).
LOGITS_RTOL = 0.05


def _line(phase: str, **nums) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in nums.items()),
          flush=True)


def paper_grid(n: int):
    """Table 1's V100 law at ``n // 64`` load levels in ρ ∈ [0.1, 0.9]
    of each cap's stability limit, four finite caps, and 16 replicas of
    every (ρ, b_max) cell (each on its own random stream)."""
    from repro.core.grid import SweepGrid

    levels = n // 64
    rho = np.repeat(np.linspace(0.1, 0.9, levels), 4)
    b = np.tile([4, 8, 16, 32], levels)
    lam = rho * b / (V100.alpha * b + V100.tau0)
    return SweepGrid.from_points(np.tile(lam, 16), V100.alpha, V100.tau0,
                                 b_max=np.tile(b, 16))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(cfg=None, *, prompt: int = 128, gen: int = 32,
                max_batch: int = 8, n_jobs: int = 64, rho: float = 0.5):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import CappedBatch, markov, phi
    from repro.core.calibrate import fit_service_model
    from repro.models import build
    from repro.serving import InferenceEngine

    cfg = cfg or get_config("qwen1.5-0.5b")
    eng = InferenceEngine(cfg, workload="generate", seq_len=prompt,
                          gen_tokens=gen, max_batch=max_batch, seed=SEED)
    b, tau = eng.calibrate(samples=5)
    model_s, r2 = fit_service_model(b, tau)
    # the engine's clock is in seconds; the queueing core's in ms
    model = LinearServiceModel(model_s.alpha * 1e3, model_s.tau0 * 1e3)
    lam_ms = rho * max_batch / (model.alpha * max_batch + model.tau0)
    res = eng.serve_poisson(lam_ms * 1e3, n_jobs=n_jobs,
                            policy=CappedBatch(cap=max_batch), seed=SEED,
                            warmup=False)
    ew, p99 = res.mean_latency * 1e3, res.latency_p99 * 1e3
    chain = markov.solve(lam_ms, model, b_max=max_batch).mean_latency
    assert res.n_jobs == n_jobs and np.all(np.isfinite(res.latencies))
    assert res.latencies.min() >= 0.5 * tau[0] and p99 >= ew
    assert np.all(tau > 0) and res.batch_sizes.max() <= max_batch

    # logits: bf16 prefill, then decode through the cache, against the
    # float32 full forward pass over the same tokens
    bundle = build(cfg)
    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    ref = build(ref_cfg)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    steps = 8
    toks = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(2, prompt + steps)), jnp.int32)
    prefill = jax.jit(bundle.prefill, static_argnums=2)
    decode = jax.jit(bundle.decode_step)
    lg, cache = prefill(eng.params, {"tokens": toks[:, :prompt]},
                        prompt + steps)
    got = [lg[:, 0]]
    lengths = jnp.full((2,), prompt, jnp.int32)
    for i in range(steps - 1):
        lg, cache = decode(eng.params, toks[:, prompt + i:prompt + i + 1],
                           cache, lengths + i)
        got.append(lg[:, 0])
    got = jnp.stack(got, axis=1).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(ref.forward)(params32, {"tokens": toks})
    want = want[:, prompt - 1:prompt + steps - 1]
    err = float(jnp.sqrt(jnp.mean((got - want) ** 2)
                         / jnp.mean(want ** 2)))
    _line("serve", arch=cfg.name, layers=cfg.num_layers,
          d_model=cfg.d_model, vocab=cfg.vocab_size, dtype=cfg.dtype,
          tau_ms="[" + ",".join(f"{int(bb)}:{t * 1e3:.4f}"
                                for bb, t in zip(b, tau)) + "]",
          alpha_ms=model.alpha, tau0_ms=model.tau0, r2=r2,
          lam_per_ms=lam_ms, rho_of_limit=rho, jobs=res.n_jobs,
          mean_batch=res.mean_batch, EW_ms=ew, p99_ms=p99,
          phi_ms=float(phi(lam_ms, model.alpha, model.tau0)),
          chain_EW_ms=chain, logits_rel_rms=err, logits_rtol=LOGITS_RTOL)
    assert err <= LOGITS_RTOL, f"logits off by {err} (> {LOGITS_RTOL})"


# ---------------------------------------------------------------------------
# simulate
# ---------------------------------------------------------------------------

def simulate_phase(n: int = 4096, n_batches: int = 2048):
    import jax

    from repro.core import evaluate, markov
    from repro.core.sweep import sweep, sweep_plan
    from repro.kernels.superstep import resolve_backend

    grid = paper_grid(n)
    backend = resolve_backend(None)
    plan = sweep_plan(grid, n_batches=n_batches, seed=SEED)
    hlo = plan.kernel.lower(plan.params, plan.keys).compile().as_text()
    mosaic = "tpu_custom_call" in hlo
    if jax.default_backend() == "tpu":
        assert backend == "pallas" and mosaic, (backend, mosaic)

    t0 = time.perf_counter()
    res = evaluate(grid, backend="sweep", n_batches=n_batches, seed=SEED)
    wall = time.perf_counter() - t0
    ew = np.array([r.mean_latency for r in res])
    ci = np.array([r.ci_halfwidth for r in res])
    cells = n // 16
    exact = np.array([markov.solve(float(grid.lam[i]), V100,
                                   b_max=float(grid.b_max[i])).mean_latency
                      for i in range(cells)])
    z = np.abs(ew - np.tile(exact, 16)) / ci

    t0 = time.perf_counter()
    fused = sweep(grid, n_batches=n_batches, seed=SEED)
    t1 = time.perf_counter()
    plain = sweep(grid, n_batches=n_batches, seed=SEED,
                  superstep_backend="lax")
    t2 = time.perf_counter()
    same_hist = bool(np.array_equal(fused.hist, plain.hist))
    _line("simulate", points=n, n_batches=n_batches, backend=backend,
          mosaic_call=mosaic, evaluate_wall_s=wall,
          default_warm_s=t1 - t0, lax_with_compile_s=t2 - t1,
          jobs=int(fused.n_jobs.sum()),
          buffer_dropped=int(fused.buffer_dropped.sum()),
          max_ci_ms=float(np.nanmax(ci)), max_z=float(np.nanmax(z)),
          median_z=float(np.nanmedian(z)), ci_slack=CI_SLACK,
          hist_pallas_eq_lax=same_hist,
          evaluate_eq_sweep=bool(np.array_equal(ew, fused.mean_latency)))
    assert int(fused.buffer_dropped.sum()) == 0
    assert np.all(np.isfinite(ci)) and np.all(z <= CI_SLACK), z.max()
    assert same_hist
    assert np.array_equal(ew, fused.mean_latency)


# ---------------------------------------------------------------------------
# campaign
# ---------------------------------------------------------------------------

def campaign_phase(n: int = 16384, chunk: int = 4096,
                   n_batches: int = 1024):
    from repro.core.campaign import campaign

    grid = paper_grid(n)
    runs = []
    for c in (chunk, chunk // 2):
        t0 = time.perf_counter()
        r = campaign(grid, chunk_size=c, n_batches=n_batches, seed=SEED)
        runs.append((r, time.perf_counter() - t0))
    (a, wa), (b, wb) = runs
    same = all(np.array_equal(a.acc[k], b.acc[k]) for k in a.acc)
    _line("campaign", points=n, chunks=f"{a.n_chunks}x{a.chunk_size}"
          f"|{b.n_chunks}x{b.chunk_size}", n_batches=n_batches,
          jobs=a.totals["jobs"], buffer_dropped=a.totals["buffer_dropped"],
          quarantined=a.quarantined_points + b.quarantined_points,
          mean_latency_ms=a.mean_latency, p99_ms=a.percentiles()[2],
          max_ci_ms=a.max_ci_halfwidth, wall_s=f"{wa:.3f}|{wb:.3f}",
          bitwise_equal=same, fingerprint=a.fingerprint()[:16])
    assert a.completed and b.completed and a.n_chunks >= 4
    assert a.quarantined_points == 0 and b.quarantined_points == 0
    assert a.totals["buffer_dropped"] == 0
    assert same, "chunk size changed the merged accumulator"


# ---------------------------------------------------------------------------
# exact
# ---------------------------------------------------------------------------

def exact_phase(fracs=(0.1, 0.3, 0.5, 0.7, 0.9), b_maxes=(1, 4, 16, 64)):
    from repro.core.grid import MarkovGrid
    from repro.core.markov import solve_grid

    grid = MarkovGrid.from_fracs(fracs, V100.alpha, V100.tau0,
                                 b_maxes=b_maxes)
    t0 = time.perf_counter()
    dev = solve_grid(grid)
    wall = time.perf_counter() - t0
    ref = solve_grid(grid, truncation=dev.truncation, method="numpy")
    rel = max(float(np.max(np.abs(getattr(dev, k) - getattr(ref, k))
                           / np.abs(getattr(ref, k))))
              for k in ("mean_latency", "mean_batch", "batch_m2",
                        "utilization", "mean_queue"))
    _line("exact", cells=len(grid), truncation=dev.truncation,
          wall_s=wall, max_tail_mass=float(dev.tail_mass.max()),
          max_rel_err=rel)
    assert rel <= 1e-10, rel


# ---------------------------------------------------------------------------
# four chips against one
# ---------------------------------------------------------------------------

def sharded_phase(n: int = 4096, n_batches: int = 1024,
                  campaign_points: int = 16384):
    import jax

    from repro.core import engine
    from repro.core.campaign import campaign
    from repro.core.sweep import sweep, sweep_plan

    grid = paper_grid(n)
    plan = sweep_plan(grid, n_batches=n_batches, seed=SEED)
    out, _ = engine.dispatch_device(plan.kernel, plan.params, plan.keys,
                                    plan.n, plan.n_dev)
    spans = len(out["mean_latency"].sharding.device_set)
    n_dev = len(jax.devices())
    assert plan.n_dev == n_dev and spans == n_dev, (plan.n_dev, spans)

    many = sweep(grid, n_batches=n_batches, seed=SEED)
    one = sweep(grid, n_batches=n_batches, seed=SEED, shard=1)
    fields = [f.name for f in dataclasses.fields(many)
              if isinstance(getattr(many, f.name), np.ndarray)]
    differ = [f for f in fields
              if not np.array_equal(getattr(many, f), getattr(one, f),
                                    equal_nan=True)]

    cgrid = paper_grid(campaign_points)
    ca = campaign(cgrid, n_batches=n_batches // 2, seed=SEED)
    cb = campaign(cgrid, n_batches=n_batches // 2, seed=SEED, shard=1)
    same_acc = all(np.array_equal(ca.acc[k], cb.acc[k]) for k in ca.acc)
    _line("sharded", devices=n_dev, mesh_span=spans, points=n,
          fields_compared=len(fields), fields_differ=differ or "none",
          campaign_points=campaign_points, campaign_bitwise=same_acc,
          jobs=int(many.n_jobs.sum()))
    assert not differ, differ
    assert same_acc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded simulator, over four "
                         "chips against one")
    args = ap.parse_args()

    import jax

    from repro.core.engine import init_compile_cache

    init_compile_cache()
    devs = jax.devices()
    want = 4 if args.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    print(f"device: {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}", flush=True)

    phases = ([sharded_phase] if args.four_chips else
              [serve_phase, simulate_phase, campaign_phase, exact_phase])
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # noqa: BLE001 -- report, run the rest, exit 1
            traceback.print_exc()
            failed.append(phase.__name__)
        print(f"{phase.__name__}: {time.perf_counter() - t0:.1f} s "
              f"(with compiles)", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
