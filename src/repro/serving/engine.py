"""Dynamic-batching inference engine — the system the paper characterizes.

The engine executes REAL JAX models (the reduced assigned architectures on
CPU; the full ones on one accelerator device via launch/serve.py --full)
under the paper's batch-service discipline:

- requests arrive (Poisson load generator, MLPerf-Server-Scenario style),
- whenever the server is free, a batching policy (default: the paper's
  batch-all-waiting, Eq. 2) forms the next batch from the queue,
- the batch is padded to a compiled *bucket* size (XLA shapes are static;
  buckets are powers of two up to max_batch — this produces exactly the
  stair-like τ^[b] the paper measures on ResNet50, Fig. 9/10),
- the batch runs to completion; per-request latency = departure − arrival.

Measurement uses a *virtual-clock, trace-driven* design: arrivals are drawn
on a virtual Poisson timeline, while service durations are the measured
wall-clock times of the real JAX executions. Since the modelled server is
single-threaded FCFS-batch, the queueing dynamics are exactly reproduced
without threading noise — the latency samples are the real-system analogue
of the paper's Fig. 11 measurements.

Workloads:
  'forward'  — one full forward pass over a fixed-length input (the
               classification-style job of the paper's experiments)
  'generate' — prefill(prompt_len) + gen_tokens KV-cache decode steps;
               for MoE models the program also returns the slots each
               held expert received, per step (prefill first) and MoE
               layer, kept per batch in ``expert_slots``
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import metrics
from repro.core.calibrate import fit_service_model
from repro.core.policy import BatchAllWaiting, BatchPolicy
from repro.models import build
from repro.models.registry import ModelBundle


def _buckets(max_batch: int) -> List[int]:
    out = [1]
    while out[-1] < max_batch:
        out.append(min(out[-1] * 2, max_batch))
    return out


@dataclass
class ServeResult:
    lam: float
    n_jobs: int
    mean_latency: float
    latency_p50: float
    latency_p95: float
    latency_p99: float
    mean_batch: float
    utilization: float
    batch_sizes: np.ndarray = field(repr=False)
    latencies: np.ndarray = field(repr=False)
    bucket_of: Dict[int, int] = field(default_factory=dict, repr=False)


class InferenceEngine:
    """Single-logical-server dynamic-batching engine over a real model."""

    def __init__(self, cfg: ModelConfig, *, workload: str = "forward",
                 seq_len: int = 64, gen_tokens: int = 4,
                 max_batch: int = 64, seed: int = 0, params=None):
        """``params``: the model's weights; without them the engine makes
        random ones from ``seed``."""
        self.cfg = cfg
        self.bundle: ModelBundle = build(cfg)
        self.workload = workload
        self.seq_len = seq_len
        self.gen_tokens = gen_tokens
        self.max_batch = max_batch
        self.buckets = _buckets(max_batch)
        self.params = (self.bundle.init(jax.random.PRNGKey(seed))
                       if params is None else params)
        self.expert_slots: Optional[jnp.ndarray] = None
        self._fns: Dict[int, Callable] = {}
        self._rng = np.random.default_rng(seed)
        self._build_fns()

    # ------------------------------------------------------------------
    def _make_batch(self, b: int) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        s = self.seq_len
        batch = {"tokens": jnp.asarray(
            self._rng.integers(0, cfg.vocab_size, size=(b, s)), jnp.int32)}
        if cfg.family == "vlm" and cfg.encoder is not None:
            batch["patch_embeds"] = jnp.zeros(
                (b, cfg.encoder.n_ctx, cfg.d_model), jnp.float32)
        if cfg.family == "audio" and cfg.encoder is not None:
            batch["frames"] = jnp.zeros(
                (b, cfg.encoder.n_ctx, cfg.d_model), jnp.float32)
        return batch

    def _build_fns(self) -> None:
        bundle, cfg = self.bundle, self.cfg

        if self.workload == "forward":
            def run(params, batch):
                logits, _ = bundle.forward(params, batch)
                return jnp.argmax(logits[:, -1], axis=-1)
            fn = jax.jit(run)
            for b in self.buckets:
                self._fns[b] = fn
        elif self.workload == "generate":
            cache_len = self.seq_len + self.gen_tokens + 1
            gen_tokens = self.gen_tokens
            moe = cfg.moe is not None

            def run(params, batch):
                logits, cache, *slots = bundle.prefill(
                    params, batch, cache_len, expert_slots=moe)
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                bsz = tok.shape[0]
                offset = (cfg.encoder.n_ctx
                          if cfg.family == "vlm" and cfg.encoder else 0)
                lengths = jnp.full((bsz,), batch["tokens"].shape[1] + offset,
                                   jnp.int32)

                def step(carry, _):
                    tok, cache, lengths = carry
                    lg, cache, *sl = bundle.decode_step(
                        params, tok, cache, lengths, expert_slots=moe)
                    tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    return (tok, cache, lengths + 1), (tok[:, 0], *sl)

                (_, _, _), (toks, *steps) = jax.lax.scan(
                    step, (tok, cache, lengths), None, length=gen_tokens)
                if not moe:
                    return toks.T
                # (1 + gen_tokens, MoE layers, held): prefill, then steps
                return toks.T, jnp.concatenate([slots[0][None], steps[0]])
            fn = jax.jit(run)
            for b in self.buckets:
                self._fns[b] = fn
        else:
            raise ValueError(self.workload)

    def bucket_of(self, b: int) -> int:
        for bb in self.buckets:
            if b <= bb:
                return bb
        return self.buckets[-1]

    # ------------------------------------------------------------------
    def run_batch(self, b: int) -> float:
        """Execute one batch of b requests; return wall seconds.  The
        inputs reach the device before the clock starts, and the clock
        stops when the outputs are ready, so the time is the batch's
        device service time plus dispatch.  Spans: ``engine.batch``
        over the call, ``engine.prepare`` over the input preparation
        the clock leaves out, ``engine.run`` over what it times."""
        bb = self.bucket_of(b)
        with metrics.span("engine.batch", b=b, bucket=bb):
            with metrics.span("engine.prepare"):
                batch = jax.block_until_ready(self._make_batch(bb))
            with metrics.span("engine.run"):
                t0 = time.perf_counter()
                out = self._fns[bb](self.params, batch)
                jax.block_until_ready(out)
                t = time.perf_counter() - t0
        if isinstance(out, tuple):
            self.expert_slots = out[1]
        return t

    def warmup(self) -> None:
        for b in self.buckets:
            self.run_batch(b)

    # ------------------------------------------------------------------
    def calibrate(self, batch_sizes: Optional[Sequence[int]] = None,
                  samples: int = 5) -> Tuple[np.ndarray, np.ndarray]:
        """Measure τ^[b] (median of `samples`) for each bucket size —
        the paper's MultiStream-Scenario measurement (Fig. 9)."""
        bs = list(batch_sizes or self.buckets)
        self.warmup()
        med = []
        for b in bs:
            ts = [self.run_batch(b) for _ in range(samples)]
            med.append(float(np.median(ts)))
        return np.asarray(bs, float), np.asarray(med)

    def fit_service_model(self, samples: int = 5):
        b, t = self.calibrate(samples=samples)
        return fit_service_model(b, t)

    # ------------------------------------------------------------------
    def serve_poisson(self, lam: float, n_jobs: int = 500,
                      policy: BatchPolicy = BatchAllWaiting(),
                      seed: int = 0, warmup: bool = True) -> ServeResult:
        """Serve a Poisson(λ) request trace (λ in jobs per *second* of
        virtual time; service times are real measured wall seconds)."""
        if warmup:
            self.warmup()
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(1.0 / lam, size=n_jobs))
        i = 0                      # next arrival index not yet queued
        now = 0.0
        busy = 0.0
        waiting: List[float] = []  # arrival times
        lat: List[float] = []
        batches: List[int] = []
        while len(lat) < n_jobs:
            if not waiting:
                # jump to next arrival
                now = max(now, arrivals[i])
                while i < n_jobs and arrivals[i] <= now:
                    waiting.append(arrivals[i])
                    i += 1
            # policy may delay service (timeout batching)
            start = policy.release_time(now, waiting[0], len(waiting))
            if start > now:
                # admit arrivals that land before the delayed start
                while i < n_jobs and arrivals[i] <= start:
                    waiting.append(arrivals[i])
                    i += 1
                now = start
            b = policy.take(len(waiting))
            batch_arr = waiting[:b]
            waiting = waiting[b:]
            svc = self.run_batch(b)
            depart = now + svc
            lat.extend(depart - a for a in batch_arr)
            batches.append(b)
            busy += svc
            while i < n_jobs and arrivals[i] <= depart:
                waiting.append(arrivals[i])
                i += 1
            now = depart
        latv = np.asarray(lat[:n_jobs])
        bsv = np.asarray(batches)
        return ServeResult(
            lam=lam, n_jobs=n_jobs,
            mean_latency=float(latv.mean()),
            latency_p50=float(np.percentile(latv, 50)),
            latency_p95=float(np.percentile(latv, 95)),
            latency_p99=float(np.percentile(latv, 99)),
            mean_batch=float(bsv.mean()),
            utilization=float(busy / now) if now > 0 else 0.0,
            batch_sizes=bsv,
            latencies=latv,
            bucket_of={b: self.bucket_of(b) for b in range(1,
                                                           self.max_batch
                                                           + 1)},
        )
