"""Vectorized JAX Monte Carlo sweep engine for the batch-service queue.

The scalar event simulator (``repro.core.simulate``) runs one
(λ, α, τ0, b_max, dist, policy) point per call.  This module simulates the
same regenerative batch-by-batch dynamics entirely in JAX — one
``lax.scan`` step per *service completion* — and ``vmap``s the kernel over
a parameter grid, so thousands of points run in a single jit-compiled
device dispatch.

Why batch-by-batch is exact (see docs/theory.md §"Regenerative sweep
kernel" for the full argument): under every policy modelled here the
server, once it starts a batch, is oblivious to the queue until the batch
departs.  Between consecutive service starts the only events are Poisson
arrivals, so the whole trajectory is determined by, per service period,
(i) the arrival *count* A ~ Poisson(λ·s) and (ii) the arrival *epochs*,
which conditional on A = a are the order statistics of a i.i.d.
Uniform(period) draws.  The kernel samples exactly that: a Poisson count,
then sorted uniforms — no per-event loop, fixed shapes, scan-friendly.

State per grid point is a fixed-capacity linear FIFO buffer of arrival
times (``q_cap`` waiting slots) plus O(1) accumulators; all times are
kept relative to the last batch departure, so float32 precision is set
by queue sojourn magnitudes rather than total simulated time.  Per-job
latencies are exact (arrival → batch departure); percentiles are
estimated from a
log-spaced histogram binned by float32 bit pattern (2**3 bins per
octave, ~9% per-bin resolution refined by in-bin interpolation — and
no transcendentals inside the scan).  If the queue or the per-period
arrival draw would overflow its fixed capacity, excess arrivals are
dropped and counted in ``dropped`` — a correct run has ``dropped == 0``
everywhere (asserted by the tests).

Policies (the three in ``repro.core.policy``) are encoded per point by
(``b_max``, ``wait_max``, ``wait_target``):

- BatchAllWaiting:  b_max = 0 (∞), wait_max = 0
- CappedBatch(cap): b_max = cap,   wait_max = 0
- TimeoutBatch:     b_max = cap, wait_max > 0, wait_target = target —
  when fewer than ``wait_target`` jobs wait, service is delayed until
  ``oldest arrival + wait_max``; jobs arriving during the delay join the
  batch (up to the cap).  One simplification vs. a fully event-driven
  timeout: reaching ``wait_target`` *during* the delay does not cut the
  delay short.  The scalar simulator has no timeout mode, so this engine
  is the reference implementation for that policy.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, random

from repro.core import engine, metrics, variance
from repro.core.engine import ShardSpec
from repro.core.grid import (  # noqa: F401  (re-exported for back-compat)
    DIST_CODE, DIST_NAME, FAIL_DISC_CODE, FAIL_DISC_NAME, OVERFLOW_CODE,
    OVERFLOW_NAME, ROUTE_CODE, ROUTE_NAME, FleetGrid, FleetResult,
    SweepGrid, SweepResult)
from repro.core.hist import (SKETCH_BINS, hist_edges,
                             hist_percentiles as _hist_percentiles,
                             sketch_edges, thinned_rows)
from repro.kernels import superstep as _ss

__all__ = ["DIST_CODE", "DIST_NAME", "OVERFLOW_CODE", "OVERFLOW_NAME",
           "ROUTE_CODE", "ROUTE_NAME", "SweepGrid", "SweepResult",
           "FleetGrid", "FleetResult", "sweep", "fleet_sweep",
           "sweep_caps", "fleet_caps", "hist_edges"]

# per-point fold_in keys live in the shared engine layer now; the alias
# keeps older import sites working
_point_keys = engine.point_keys

# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

# scan steps per superstep: the histogram scatter (single-server and
# fleet kernels) and the fleet kernel's full-buffer clock rebase are
# amortized to one pass per _REBASE_EVERY steps
_REBASE_EVERY = 32


_OV_REJECT = OVERFLOW_CODE["reject"]


# preempt-restart re-execution attempts explicitly materialized per
# step (fixed-shape RNG).  The geometric attempt count is truncated
# here; tests pick regimes with P(fail) ≤ 0.4 per attempt, where
# P(> 16 failures) ≈ 4e-7 is far below MC noise (the numpy mirrors
# sample the unbounded law).
_FAIL_ATTEMPTS = 16
# failure-clock fold_in salt — distinct from the retry orbit's 0x0b17
# so neither perturbs the other's (or the main) key stream
_FAIL_SALT = 0x0f41


@engine.kernel_cache(maxsize=32)
def _build_kernel(n_batches: int, warmup: int, q_cap: int, a_cap: int,
                  n_bins: int, has_timeout: bool, all_det: bool,
                  has_loss: bool, r_cap: int, has_fail: bool,
                  ss_backend: str, use_sketch: bool, tap, n_dev: int):
    """Compile-time specialization of the per-point scan kernel.

    The waiting room is a *linear compacted* buffer: waiting jobs always
    occupy ``buf[0:q]`` in FIFO order.  Pops read the contiguous prefix
    and shift the remainder down with ``lax.dynamic_slice``; pushes
    append with ``lax.dynamic_update_slice``.  Contiguous slices lower
    to vectorized copies on every XLA backend, unlike element-wise
    scatters with computed indices (a ring-buffer formulation of this
    kernel was ~20× slower on CPU for exactly that reason).  Slots
    beyond ``q`` hold garbage from past appends; they can only become
    live through a later append that overwrites them first, so the
    invariant "``buf[0:q]`` = the waiting jobs, oldest first" holds
    throughout.

    ``has_loss = False`` traces exactly the pre-admission-control
    kernel (every loss op sits behind this compile-time flag), so
    loss-free grids keep their bitwise-pinned results.  With
    ``has_loss = True`` the step adds, in order: reject-mode admission
    inside every window push (prefix-greedy against the per-point
    ``room``), deadline reneging of the expired FIFO prefix at the
    formation epoch, the drop-mode tail trim to ``q_max`` after the
    pop, and the bounded retry orbit assessed at the departure epoch
    (re-arrivals join with arrival time ``depart``; a batch emptied by
    reneging has ``b = 0``, costs no service time, and the next step
    idles).

    ``has_fail = True`` adds the breakdown/repair regime (every op
    behind this compile-time flag, so failure-free grids keep their
    bitwise-pinned results): an exponential failure clock at rate
    ξ = 1/MTBF runs while the batch executes, repairs are
    Exp(mttr), and the in-flight batch is handled by the point's
    ``fail_disc`` — *resume* (service s is interrupted by
    M ~ Poisson(ξ·s) repairs, completion C = s + Σ repairs),
    *restart* (a Geometric number of attempts each losing a
    TruncExp(ξ, s) partial execution plus a repair, then the full s;
    truncated at ``_FAIL_ATTEMPTS``), or *drop* (the batch aborts at
    its first failure epoch E < s, its b jobs are filed through the
    abandonment/retry-orbit path, and only the repair follows — drop
    grids therefore always compile ``has_loss``).  A batch following
    a repair runs degraded: its service mean scales by the point's
    ``throttle``.  All failure randomness derives from a fold_in
    key, so it never perturbs the base key stream."""

    i32 = jnp.int32
    f32 = jnp.float32
    #  append region starts at q <= q_cap; the retry block appends after
    #  the service-window block, also at q <= q_cap
    buf_len = q_cap + a_cap + (r_cap if has_loss else 0)
    slots = jnp.arange(q_cap)

    def run_point(p, key):
        lam, alpha, tau0 = p["lam"], p["alpha"], p["tau0"]
        b_max = jnp.where(p["b_max"] > 0, p["b_max"], q_cap).astype(i32)
        dist, cv = p["dist"], p["cv"]
        wait_max, wait_target = p["wait_max"], p["wait_target"]
        if has_loss:
            q_lim = p["q_max"].astype(i32)
            deadline = p["deadline"]
            retry_rate = p["retry_rate"]
            retry_on = retry_rate > 0.0
            is_reject = p["overflow"] == _OV_REJECT
            # instantaneous-admission bound ("429"): binds per arrival
            # in reject mode, q_cap (buffer only) in drop mode
            roomv = jnp.where((q_lim > 0) & is_reject, q_lim, q_cap)
            # formation-epoch bound ("503"): drop mode trims the newest
            # waiting jobs beyond q_max after each pop
            trim_to = jnp.where((q_lim > 0) & ~is_reject, q_lim, q_cap)
            # retries re-enter against the physical room in both modes
            retry_room = jnp.where(q_lim > 0,
                                   jnp.minimum(q_lim, q_cap), q_cap)
        if has_fail:
            mtbf, mttr = p["mtbf"], p["mttr"]
            throttle = p["throttle"]
            fd = p["fail_disc"]
            is_restart, is_drop = fd == 1, fd == 2
            xi = jnp.where(mtbf > 0.0, 1.0 / jnp.maximum(mtbf, 1e-30),
                           0.0)

        def push_arrivals(buf, q, dropped, lost_ov, offered, k_u, rate,
                          t0, win):
            """Constructive Poisson window push — the shared engine
            helpers (exp-gap/cumsum epochs, sentinel coverage detection,
            capacity clamp, contiguous tail-append; see
            ``engine.push_poisson_window`` for the exactness argument).
            The loss variant additionally tests each arrival against the
            per-point admission ``room`` and accounts the rejected ones
            as measured overflow losses."""
            if has_loss:
                buf, q, dropped, acc, rej = \
                    engine.push_poisson_window_loss(
                        buf, q, dropped, k_u, rate, t0, win,
                        a_cap=a_cap, q_cap=q_cap, room=roomv)
                return buf, q, dropped, lost_ov + rej, offered + acc + rej
            buf, q, dropped = engine.push_poisson_window(
                buf, q, dropped, k_u, rate, t0, win, a_cap=a_cap,
                q_cap=q_cap)
            return buf, q, dropped, lost_ov, offered

        def step(state, i):
            # All times in the step are RELATIVE to the previous batch
            # departure (the buffer is rebased by -depart at the end),
            # so float32 precision is set by queue sojourn magnitudes,
            # not by total simulated time — n_batches can grow without
            # degrading per-job latency resolution.
            if has_fail:
                state, (deg, nfail, dtime, lwork) = \
                    state[:-4], state[-4:]
            if has_loss:
                (q, buf, key, lat_sum, lat_n, sum_b, sum_b2, sum_bs,
                 n_meas, busy, span, q_max, dropped,
                 orbit, ov_n, ab_n, slo_n, fresh_n, retry_n) = state
            else:
                (q, buf, key, lat_sum, lat_n, sum_b, sum_b2, sum_bs,
                 n_meas, busy, span, q_max, dropped) = state
            # the split count must not depend on has_loss — split(k, n)
            # re-keys ALL children when n changes, which would unpin the
            # neutral-grid bitwise reduction; the orbit key is derived
            # by fold_in instead
            ks = random.split(key, 5)
            key = ks[0]
            if has_loss:
                korb = random.fold_in(ks[0], 0x0b17)
            zero = jnp.zeros((), i32)
            lost_ov = lost_ab = fresh = zero

            # idle period: the step begins when a job arrives to an
            # empty system (a.s. exactly one arrival ends the idle);
            # the queue is empty, so the slot index is statically 0
            empty = q == 0
            gap = random.exponential(ks[1]) / lam
            now = jnp.where(empty, gap, 0.0)
            buf = buf.at[0].set(jnp.where(empty, now, buf[0]))
            q = q + empty.astype(i32)
            fresh = fresh + empty.astype(i32)

            # optional timeout delay before service starts
            if has_timeout:
                oldest = buf[0]
                do_wait = (wait_max > 0.0) & (q < wait_target)
                release = jnp.where(
                    do_wait, jnp.maximum(now, oldest + wait_max), now)
                buf, q, dropped, lost_ov, fresh = push_arrivals(
                    buf, q, dropped, lost_ov, fresh, ks[2], lam, now,
                    release - now)
            else:
                release = now

            if has_loss:
                # deadline reneging at the formation epoch: expired
                # jobs are a contiguous FIFO prefix (ascending ages)
                buf, q, n_exp = engine.renege_prefix(
                    buf, q, release, deadline, q_cap)
                lost_ab = lost_ab + n_exp

            # form the batch: policy take = min(waiting, cap), FIFO
            b = jnp.minimum(q, b_max)
            mean_s = alpha * b.astype(f32) + tau0
            if all_det:
                s = mean_s
            else:
                kshape = jnp.where(dist == 1, 1.0, 1.0 / (cv * cv))
                g = random.gamma(ks[3], kshape) / kshape
                s = jnp.where(dist == 0, mean_s, mean_s * g)
            if has_loss:
                # a queue emptied by reneging forms no batch: no
                # service time elapses and the next step idles
                s = jnp.where(b > 0, s, 0.0)
            if has_fail:
                # degraded phase: the first batch after a repair runs
                # at throttle×τ (consumed here, re-armed on failure)
                s = s * jnp.where(deg, throttle, 1.0)
                kf = random.fold_in(ks[0], _FAIL_SALT)
                kf1, kf2, kf3, kf4 = random.split(kf, 4)
                fail_on = (mtbf > 0.0) & (b > 0)
                # preempt-resume: M ~ Poisson(ξ·s) mid-batch failures,
                # each inserting an Exp(mttr) repair (sum of M unit
                # exponentials = Gamma(M), exact and fixed-shape)
                M = random.poisson(kf1, jnp.where(fail_on, xi * s, 0.0))
                rep_res = mttr * random.gamma(
                    kf2, jnp.maximum(M, 1).astype(f32))
                rep_res = jnp.where(M > 0, rep_res, 0.0)
                # preempt-restart: attempt i fails iff its Exp-clock
                # epoch E_i lands inside s, losing the partial work E_i
                # plus a repair R_i; the first surviving attempt runs
                # the full s (geometric count, truncated at the block)
                e_blk = random.exponential(kf3, (_FAIL_ATTEMPTS,)) \
                    * jnp.where(mtbf > 0.0, mtbf, 1.0)
                r_blk = random.exponential(kf4, (_FAIL_ATTEMPTS,)) \
                    * mttr
                pre = jnp.cumprod((e_blk < s).astype(f32))
                n_rst = jnp.sum(pre).astype(i32)
                lost_rst = engine.point_sum(pre * e_blk)
                rep_rst = engine.point_sum(pre * r_blk)
                # fail-drop: the batch aborts at its first failure
                # epoch; only the repair follows (jobs are filed
                # through the abandonment path at the departure epoch)
                e1, r1 = e_blk[0], r_blk[0]
                aborts = fail_on & is_drop & (e1 < s)
                n_f = jnp.where(
                    fail_on,
                    jnp.where(is_restart, n_rst,
                              jnp.where(is_drop, aborts.astype(i32),
                                        M)),
                    0)
                rep = jnp.where(
                    fail_on,
                    jnp.where(is_restart, rep_rst,
                              jnp.where(is_drop,
                                        jnp.where(aborts, r1, 0.0),
                                        rep_res)),
                    0.0)
                lost = jnp.where(fail_on & is_restart, lost_rst, 0.0)
                lost = jnp.where(aborts, e1, lost)
                s_busy = jnp.where(aborts, 0.0, s)
                comp = s + rep + jnp.where(fail_on & is_restart,
                                           lost_rst, 0.0)
                comp = jnp.where(aborts, e1 + r1, comp)
                deg = fail_on & (n_f > 0)
            else:
                comp = s
            depart = release + comp

            # pop the b oldest jobs (the buffer prefix); their latency
            # ends at `depart`; shift the remainder down by b
            popmask = slots < b
            lats = jnp.where(popmask, depart - buf[:q_cap], 0.0)
            if has_fail:
                # an aborted (fail-drop) batch completes nothing: its
                # jobs leave through the abandonment path, not as
                # latency samples
                lats = jnp.where(aborts, 0.0, lats)
                popmask = popmask & ~aborts
            buf = engine.fifo_pop_shift(buf, b, q_cap)
            q = q - b

            if has_loss:
                # drop-mode ("503") eviction: the newest waiting jobs
                # beyond q_max leave at the formation epoch
                trim = jnp.maximum(q - trim_to, 0)
                q = q - trim
                lost_ov = lost_ov + trim

            # arrivals during the service period join the queue; under
            # failures the window is the full wall-clock completion
            # (repairs and rework included — the clock advances to
            # `depart = release + comp`, so arrivals during repairs
            # must be generated too, or the Poisson stream gets gaps)
            buf, q, dropped, lost_ov, fresh = push_arrivals(
                buf, q, dropped, lost_ov, fresh, ks[4], lam, release,
                comp if has_fail else s)

            meas = i >= warmup
            if has_loss:
                # bounded retry orbit, assessed at the departure epoch:
                # each orbit job fires with p = 1 − exp(−rate·elapsed)
                # (exact Binomial thinning, fixed-shape RNG); admitted
                # re-arrivals join with arrival time `depart`, the rest
                # return to the orbit.  THEN this step's fresh losses
                # are filed — abandoned before overflow — and whatever
                # the orbit cannot hold becomes a terminal loss.
                if has_fail:
                    # fail-drop: the aborted batch's b jobs re-enter
                    # through the abandonment/retry path (filed below,
                    # abandoned-first), eligible from the next step
                    lost_ab = lost_ab + jnp.where(aborts, b, zero)
                p_fire = 1.0 - jnp.exp(-retry_rate * depart)
                n_r = engine.orbit_draws(korb, orbit, p_fire, r_cap)
                orbit = orbit - n_r
                admit_r = jnp.minimum(
                    n_r, jnp.maximum(retry_room - q, 0))
                orbit = orbit + (n_r - admit_r)
                buf = engine.fifo_append(
                    buf, q, jnp.full((r_cap,), depart, f32))
                q = q + admit_r
                orbit, term_ab, term_ov = engine.orbit_file(
                    orbit, lost_ab, lost_ov, r_cap, retry_on)
                mi = meas.astype(i32)
                ab_n = ab_n + mi * term_ab
                ov_n = ov_n + mi * term_ov
                fresh_n = fresh_n + mi * fresh
                retry_n = retry_n + mi * n_r
                b_done = jnp.where(aborts, zero, b) if has_fail else b
                in_slo = jnp.where(
                    deadline > 0.0,
                    jnp.sum((popmask & (lats <= deadline))
                            .astype(i32)), b_done)
                slo_n = slo_n + mi * in_slo

            # rebase the clock: the departure becomes the next origin
            buf = buf - depart

            # accumulate statistics after warmup
            mf = meas.astype(jnp.float32)
            bf = b.astype(jnp.float32)
            if has_fail:
                # batch-level stats count COMPLETED batches only; the
                # service a job experiences is the completion time C
                # (execution + rework + repairs).  busy accumulates
                # productive execution only — repairs and lost restart
                # work are tracked separately (down_time, lost_work)
                mfc = mf * (1.0 - aborts.astype(jnp.float32))
                lat_sum = lat_sum + mfc * engine.point_sum(lats)
                lat_n = lat_n + jnp.where(meas & ~aborts, b, 0)
                sum_b = sum_b + mfc * bf
                sum_b2 = sum_b2 + mfc * bf * bf
                sum_bs = sum_bs + mfc * bf * comp
                if has_loss:
                    n_meas = n_meas \
                        + (meas & (b > 0) & ~aborts).astype(i32)
                else:
                    n_meas = n_meas + meas.astype(i32)
                busy = busy + mf * s_busy
                mi_f = meas.astype(i32)
                nfail = nfail + mi_f * n_f
                dtime = dtime + mf * rep
                lwork = lwork + mf * lost
            else:
                lat_sum = lat_sum + mf * engine.point_sum(lats)
                lat_n = lat_n + jnp.where(meas, b, 0)
                sum_b = sum_b + mf * bf
                sum_b2 = sum_b2 + mf * bf * bf
                sum_bs = sum_bs + mf * bf * s
                if has_loss:
                    # a b = 0 step (queue emptied by reneging) is not a
                    # batch; wall-clock/busy accumulators are untouched
                    # anyway (s = 0, depart = release)
                    n_meas = n_meas + (meas & (b > 0)).astype(i32)
                else:
                    n_meas = n_meas + meas.astype(i32)
                busy = busy + mf * s
            span = span + mf * depart     # wall-clock advanced this step
            q_max = jnp.maximum(q_max, q)

            # the histogram update — whose per-call cost under vmap
            # dwarfs its per-element cost on CPU — is amortized to the
            # superstep wrapper (the fused pallas/lax boundary in
            # repro.kernels.superstep); raw latencies ride out as scan
            # outputs and are binned there
            if has_loss:
                out_state = (q, buf, key, lat_sum, lat_n, sum_b, sum_b2,
                             sum_bs, n_meas, busy, span, q_max, dropped,
                             orbit, ov_n, ab_n, slo_n, fresh_n, retry_n)
            else:
                out_state = (q, buf, key, lat_sum, lat_n, sum_b, sum_b2,
                             sum_bs, n_meas, busy, span, q_max, dropped)
            if has_fail:
                out_state = out_state + (deg, nfail, dtime, lwork)
            return out_state, (lats, popmask & meas)

        def superstep(carry, i_base):
            state, bm, hists = carry
            s0, n0 = state[3], state[4]
            state, (lats, inc) = lax.scan(
                step, state, i_base + jnp.arange(_REBASE_EVERY))
            hists = _ss.hist_update(hists, lats, inc, n_bins=n_bins,
                                    backend=ss_backend, sketch=use_sketch)
            # one batch-means sample per superstep: the mean latency of
            # the jobs that completed inside this 32-step block
            bm = engine.welford_block(bm, state[3] - s0, state[4] - n0)
            metrics.tap_superstep(
                tap, i_base // _REBASE_EVERY, queue=state[0],
                jobs=state[4], busy=state[9], span=state[10],
                dropped=state[12],
                overflow=state[14] if has_loss else 0,
                abandoned=state[15] if has_loss else 0)
            return (state, bm, hists), None

        init = (jnp.zeros((), i32),
                jnp.zeros((buf_len,), f32), key,
                jnp.zeros((), f32), jnp.zeros((), i32),   # lat_sum, lat_n
                jnp.zeros((), f32), jnp.zeros((), f32),   # sum_b, sum_b2
                jnp.zeros((), f32),                       # sum_bs
                jnp.zeros((), i32), jnp.zeros((), f32),   # n_meas, busy
                jnp.zeros((), f32), jnp.zeros((), i32),   # span, q_max
                jnp.zeros((), i32))
        if has_loss:
            init = init + tuple(jnp.zeros((), i32) for _ in range(6))
        if has_fail:
            init = init + (jnp.zeros((), bool),      # degraded phase
                           jnp.zeros((), i32),       # n_failures
                           jnp.zeros((), f32),       # down_time
                           jnp.zeros((), f32))       # lost_work
        bm0 = (jnp.zeros((), f32), jnp.zeros((), f32), jnp.zeros((), i32))
        hists0 = (jnp.zeros((n_bins,), i32),)
        if use_sketch:
            hists0 = hists0 + (jnp.zeros((n_bins,), f32),)
        (state, bm, hists), _ = lax.scan(
            superstep, (init, bm0, hists0),
            jnp.arange(n_batches // _REBASE_EVERY) * _REBASE_EVERY)
        (_, _, _, lat_sum, lat_n, sum_b, sum_b2, sum_bs, n_meas,
         busy, span, _q_max, dropped) = state[:13]

        jobs = jnp.maximum(lat_n, 1).astype(jnp.float32)
        nb = jnp.maximum(n_meas, 1).astype(jnp.float32)
        out = {
            "mean_latency": lat_sum / jobs,
            "mean_batch": sum_b / nb,
            "batch_m2": sum_b2 / nb,
            "mean_service": sum_bs / jnp.maximum(sum_b, 1e-30),
            "utilization": busy / jnp.maximum(span, 1e-30),
            "n_jobs": lat_n,
            "n_batches": n_meas,
            "max_queue": _q_max,
            "dropped": dropped,
            "lat_bm_m2": bm[1],
            "lat_bm_n": bm[2],
            "hist": hists[0],
        }
        if use_sketch:
            out["hist_sums"] = hists[1]
        if has_loss:
            (_orbit, ov_n, ab_n, slo_n, fresh_n, retry_n) = state[13:19]
            out.update(overflow_dropped=ov_n, abandoned=ab_n,
                       n_in_slo=slo_n, n_fresh=fresh_n, n_retry=retry_n)
        if has_fail:
            (_deg, nfail, dtime, lwork) = state[-4:]
            out.update(n_failures=nfail, down_time=dtime,
                       lost_work=lwork, span=span)
        return out

    return engine.shard_kernel(jax.vmap(run_point), n_dev)


def _require_pinned_caps(kind: str, key_offset: int, **pinned) -> None:
    """The split-dispatch contract: ``key_offset != 0`` marks a chunk
    of a larger campaign, but the adaptive capacity defaults derive
    from *this chunk's* grid — different chunks would compile different
    shapes and the split would no longer reduce to the whole-grid
    dispatch.  Raise unless every grid-derived cap was pinned by the
    caller (PR 6 documented this caveat; this enforces it)."""
    missing = [k for k, ok in pinned.items() if not ok]
    if missing:
        raise ValueError(
            f"{kind}(key_offset={key_offset}) dispatches a chunk of a "
            f"split campaign, but {', '.join(missing)} would be sized "
            f"adaptively from this chunk's own grid — chunks would "
            f"compile different shapes than the whole-grid dispatch. "
            f"Pin them from the FULL grid, e.g. "
            f"**{kind}_caps(full_grid).")


def sweep_caps(grid: SweepGrid, *, q_cap: Optional[int] = None) -> dict:
    """The compile-time capacities ``sweep`` would derive from ``grid``
    — compute them once on the FULL campaign grid and splat into every
    chunk of a split dispatch (``sweep(chunk, key_offset=...,
    **sweep_caps(full_grid))``), so all chunks compile the same shapes
    as the whole-grid run.  Pass ``q_cap`` to mirror a pinned queue
    capacity.  Returns ``q_cap``/``a_cap`` (+ ``r_cap`` on loss
    grids)."""
    has_timeout = bool(np.any(grid.wait_max > 0.0))
    all_det = bool(np.all(grid.dist == DIST_CODE["det"]))
    has_loss = grid.has_loss
    has_fail = grid.has_fail
    if q_cap is None:
        fail_kw = {}
        if has_fail:
            # failure points inflate the busy period (rework + repair):
            # size the room for the completion-time law, not raw τ[b]
            fail_kw = dict(
                mtbf=grid.mtbf, mttr=grid.mttr,
                restart=grid.fail_disc == FAIL_DISC_CODE["restart"],
                throttle=grid.throttle)
        q_cap = engine.queue_capacity(grid.lam, grid.alpha, grid.tau0,
                                      grid.b_max, grid.wait_max,
                                      q_max=grid.q_max if has_loss
                                      else None, **fail_kw)
    if has_fail:
        # a failed batch's completion time has no deterministic bound,
        # so the provable window-capacity path is unavailable
        a_cap = int(q_cap)
    elif all_det and not has_timeout and not np.any(grid.b_max == 0):
        # deterministic service with a finite cap hard-bounds the
        # service window at α·b_max + τ0, so the per-window arrival
        # draw can be provably window-sized; random service or an
        # unbounded batch has no such bound (a queue excursion can
        # stretch the window toward τ(q_cap)), so those keep the
        # conservative a_cap = q_cap coupling
        window = grid.alpha * grid.b_max + grid.tau0
        a_cap = min(int(q_cap),
                    engine.window_capacity(grid.lam, window))
    else:
        a_cap = int(q_cap)
    caps = dict(q_cap=int(q_cap), a_cap=int(a_cap))
    if has_loss:
        caps["r_cap"] = int(engine.orbit_capacity(grid.lam,
                                                  grid.retry_rate))
    return caps


def sweep_plan(grid: SweepGrid, *, n_batches: int = 3000,
               warmup: Optional[int] = None, q_cap: Optional[int] = None,
               a_cap: Optional[int] = None, r_cap: Optional[int] = None,
               n_bins: int = 512, seed: int = 0, key_offset: int = 0,
               shard: ShardSpec = None, sketch: bool = False,
               superstep_backend: Optional[str] = None,
               metrics_tap=None) -> engine.KernelPlan:
    """Everything ``sweep`` does before the device dispatch: validate
    the grid, derive (or check) the compile-time caps, fetch the cached
    compiled kernel, and pack params/keys.  Same signature as ``sweep``;
    returns an ``engine.KernelPlan``.  ``sweep`` dispatches the plan and
    post-processes to a ``SweepResult``; the campaign driver
    (``repro.core.campaign``) dispatches it through
    ``engine.dispatch_device`` and reduces on device instead."""
    if len(grid) == 0:
        raise ValueError("empty grid")
    if warmup is not None and not 0 <= warmup < int(n_batches):
        raise ValueError(f"warmup {warmup} must lie in [0, {n_batches})")
    # the kernel scatters its histogram once per _REBASE_EVERY steps
    n_batches = -(-int(n_batches) // _REBASE_EVERY) * _REBASE_EVERY
    if warmup is None:
        warmup = max(1, n_batches // 10)
    has_timeout = bool(np.any(grid.wait_max > 0.0))
    all_det = bool(np.all(grid.dist == DIST_CODE["det"]))
    has_loss = grid.has_loss
    has_fail = grid.has_fail
    if key_offset:
        # a_cap is only grid-derived on the window-capacity path; the
        # a_cap = q_cap fallback follows from a pinned q_cap
        _require_pinned_caps(
            "sweep", key_offset,
            q_cap=q_cap is not None,
            a_cap=(a_cap is not None or has_fail
                   or not (all_det and not has_timeout
                           and not np.any(grid.b_max == 0))),
            r_cap=not has_loss or r_cap is not None)
    if q_cap is None or a_cap is None or (has_loss and r_cap is None):
        caps = sweep_caps(grid, q_cap=q_cap)
        q_cap = caps["q_cap"] if q_cap is None else q_cap
        a_cap = caps["a_cap"] if a_cap is None else a_cap
        if has_loss and r_cap is None:
            r_cap = caps["r_cap"]
    if not has_loss:
        r_cap = 0
    if a_cap > q_cap:
        raise ValueError("a_cap must be <= q_cap (ring-buffer invariant)")
    if np.any(grid.b_max > q_cap):
        raise ValueError("b_max exceeds q_cap; raise q_cap")
    if has_loss and np.any(grid.q_max > q_cap):
        raise ValueError("q_max exceeds q_cap; raise q_cap")
    if sketch:
        n_bins = SKETCH_BINS
    n = len(grid)
    ss_backend = _ss.resolve_backend(superstep_backend)
    n_dev = engine.resolve_shards(shard, n)
    if metrics_tap is not None:
        # io_callback under shard_map is outside the pinned-jax
        # contract; bitwise shard invariance makes this timing-only
        n_dev = 1
    kernel = _build_kernel(int(n_batches), int(warmup), int(q_cap),
                           int(a_cap), int(n_bins), has_timeout, all_det,
                           has_loss, int(r_cap), has_fail, ss_backend,
                           bool(sketch), metrics_tap, n_dev)

    params = {
        "lam": jnp.asarray(grid.lam), "alpha": jnp.asarray(grid.alpha),
        "tau0": jnp.asarray(grid.tau0), "b_max": jnp.asarray(grid.b_max),
        "dist": jnp.asarray(grid.dist), "cv": jnp.asarray(grid.cv),
        "wait_max": jnp.asarray(grid.wait_max),
        "wait_target": jnp.asarray(grid.wait_target),
    }
    if has_loss:
        params.update(
            q_max=jnp.asarray(grid.q_max),
            deadline=jnp.asarray(grid.deadline),
            overflow=jnp.asarray(grid.overflow),
            retry_rate=jnp.asarray(grid.retry_rate))
    if grid.has_fail:
        params.update(
            mtbf=jnp.asarray(grid.mtbf),
            mttr=jnp.asarray(grid.mttr),
            fail_disc=jnp.asarray(grid.fail_disc),
            throttle=jnp.asarray(grid.throttle))
    keys = engine.point_keys(seed, key_offset, n)
    return engine.KernelPlan(kernel=kernel, params=params, keys=keys,
                             n=n, n_dev=n_dev, sketch=bool(sketch),
                             has_loss=has_loss,
                             supersteps=n_batches // _REBASE_EVERY,
                             superstep_len=_REBASE_EVERY)


def sweep(grid: SweepGrid, *, n_batches: int = 3000,
          warmup: Optional[int] = None, q_cap: Optional[int] = None,
          a_cap: Optional[int] = None, r_cap: Optional[int] = None,
          n_bins: int = 512, seed: int = 0, key_offset: int = 0,
          shard: ShardSpec = None, sketch: bool = False,
          superstep_backend: Optional[str] = None,
          metrics_tap=None) -> SweepResult:
    """Simulate every grid point for ``n_batches`` service completions in
    one jit-compiled device dispatch, sharded over the visible devices
    by default.  ``n_batches`` rounds up to a multiple of the superstep
    length (32): the per-job latency histogram is scattered once per
    superstep block rather than once per step (the scatter's per-call
    cost under vmap dwarfs its per-element cost on CPU).

    ``q_cap`` bounds the waiting-room and ``a_cap`` the per-service-period
    arrival draw; both are *shape* parameters (compile-time), so points
    whose dynamics exceed them clamp and report via ``buffer_dropped``.
    The
    default (``None``) sizes them adaptively from the dispatched grid's
    own maximum load (``engine.queue_capacity``) instead of a global
    worst case; pass explicit values to pin the compiled shape.
    ``shard`` picks the device-mesh width (``None`` → all visible
    devices — on CPU, set ``XLA_FLAGS=--xla_force_host_platform_``
    ``device_count=<cores>`` before the first JAX call, e.g. via
    ``engine.enable_host_devices``; ``False``/1 → single device; an int
    → that many shards).  Per-point fold_in keys make per-point results
    bitwise-invariant to the shard count.

    Grids with loss regimes (any of ``q_max``/``deadline``/``retry_rate``
    set) compile the loss-capable kernel variant; ``r_cap`` bounds the
    retry orbit (defaults adaptively via ``engine.orbit_capacity``).
    Loss-free grids trace the identical pre-admission-control kernel, so
    their results stay bitwise-pinned.

    Split dispatches (``key_offset != 0``) must pin every cap the
    defaults would derive from the grid — pass ``**sweep_caps(
    full_grid)`` — or this raises (chunks would otherwise compile
    different shapes than the whole-grid run).

    ``sketch=True`` swaps the 512-bin full histogram for the 64-bin
    bounded-memory streaming quantile sketch (``repro.core.hist``):
    per-point memory stops scaling with campaign-grade ``n_bins``,
    percentiles carry the pinned ``hist.SKETCH_REL_ERR`` bound, and the
    result additionally holds the per-bin latency sums (``hist_sums``).
    ``superstep_backend`` picks the fused superstep implementation
    (``"lax"``/``"pallas"``/``"auto"`` — see
    ``repro.kernels.superstep``); counts are bitwise identical across
    backends.  ``metrics_tap`` attaches a ``repro.core.metrics
    .MetricsTap`` that streams per-superstep telemetry to the host via
    ``io_callback`` — numerics are untouched, but the dispatch runs
    single-shard.
    """
    plan = sweep_plan(grid, n_batches=n_batches, warmup=warmup,
                      q_cap=q_cap, a_cap=a_cap, r_cap=r_cap,
                      n_bins=n_bins, seed=seed, key_offset=key_offset,
                      shard=shard, sketch=sketch,
                      superstep_backend=superstep_backend,
                      metrics_tap=metrics_tap)
    n, has_loss, sketch = plan.n, plan.has_loss, plan.sketch
    out = engine.dispatch(plan.kernel, plan.params, plan.keys, n,
                          plan.n_dev)

    n_jobs = np.asarray(out["n_jobs"])
    if has_loss:
        loss_kw = dict(
            overflow_dropped=np.asarray(out["overflow_dropped"]),
            abandoned=np.asarray(out["abandoned"]),
            n_in_slo=np.asarray(out["n_in_slo"]),
            n_fresh=np.asarray(out["n_fresh"]),
            n_retry=np.asarray(out["n_retry"]))
    else:
        # a loss-free grid completes every measured arrival in SLO
        loss_kw = dict(
            overflow_dropped=np.zeros_like(n_jobs),
            abandoned=np.zeros_like(n_jobs),
            n_in_slo=n_jobs.copy(),
            n_fresh=n_jobs.copy(),
            n_retry=np.zeros_like(n_jobs))

    p50, p95, p99 = _hist_percentiles(
        out["hist"], (50, 95, 99),
        edges=sketch_edges() if sketch else None)
    if metrics_tap is not None:
        metrics_tap.observe_summary(
            kind="sweep", points=n, jobs_total=int(n_jobs.sum()),
            p50_median=float(np.nanmedian(p50)),
            p95_median=float(np.nanmedian(p95)),
            p99_median=float(np.nanmedian(p99)))
    stderr, ci = variance.batch_means_stats(out["lat_bm_m2"],
                                            out["lat_bm_n"])
    fail_kw = {}
    if grid.has_fail:
        fail_kw = dict(
            n_failures=np.asarray(out["n_failures"]),
            down_time=np.asarray(out["down_time"], dtype=np.float64),
            lost_work=np.asarray(out["lost_work"], dtype=np.float64),
            span=np.asarray(out["span"], dtype=np.float64))
    return SweepResult(
        grid=grid,
        mean_latency=np.asarray(out["mean_latency"], dtype=np.float64),
        latency_p50=p50, latency_p95=p95, latency_p99=p99,
        mean_batch=np.asarray(out["mean_batch"], dtype=np.float64),
        batch_m2=np.asarray(out["batch_m2"], dtype=np.float64),
        mean_service=np.asarray(out["mean_service"], dtype=np.float64),
        utilization=np.clip(
            np.asarray(out["utilization"], dtype=np.float64), 0.0, 1.0),
        n_jobs=n_jobs,
        n_batches=np.asarray(out["n_batches"]),
        max_queue=np.asarray(out["max_queue"]),
        buffer_dropped=np.asarray(out["dropped"]),
        hist=np.asarray(out["hist"]),
        hist_sums=(np.asarray(out["hist_sums"], dtype=np.float64)
                   if sketch else None),
        stderr=stderr, ci_halfwidth=ci,
        n_blocks=np.asarray(out["lat_bm_n"]),
        **loss_kw, **fail_kw,
    )


# ---------------------------------------------------------------------------
# the fleet kernel: k replica queues + routing per grid point
# ---------------------------------------------------------------------------

@engine.kernel_cache(maxsize=16)
def _build_fleet_kernel(n_steps: int, warmup: int, k_max: int, q_cap: int,
                        a_cap: int, pop_cap: int, n_bins: int,
                        has_timeout: bool, all_det: bool, has_jsq: bool,
                        has_loss: bool, r_cap: int, has_fail: bool,
                        hist_every: int, ss_backend: str,
                        use_sketch: bool, tap, n_dev: int):
    """Compile-time specialization of the per-point fleet scan kernel.

    Unlike the single-server kernel — one scan step per *service period*
    with bulk arrival draws — a fleet's replicas overlap in time and a
    router (JSQ especially) must see the queue state *at each arrival*,
    so the fleet kernel steps event-by-event: each scan step processes
    exactly one replica *decision* (a service completion, usually
    rolling straight into the next batch start) after routing, in one
    vectorized block, every arrival that precedes it.  Between two
    decisions no batch departs, so the routing sequence inside the
    window is closed-form even for JSQ (discrete water-filling over the
    load vector) — no per-arrival loop anywhere.  Per replica the
    dynamics stay the exact regenerative batch law (see docs/theory.md
    §"Fleet routing"); the window machinery only resolves the
    *interleaving* across replicas.

    State per point is a flat ``(k_max · q_cap,)`` stack of per-replica
    FIFO rings (row r = replica r's waiting arrivals from ``head[r]``,
    oldest first; pushes scatter at the tail, pops advance the head)
    plus per-replica ``(k_max,)`` vectors: waiting count ``q``, ring
    ``head``, in-flight batch size ``in_service``, a ``committed`` flag
    (a decision is pending) and its time ``t_free``.  The global arrival stream is carried as ``next_arr``
    (the next arrival epoch, pre-drawn), so no arrival is ever discarded
    between windows; if more than ``a_cap`` arrivals precede one event,
    the event is deferred to the next outer step, which resumes routing
    where this one stopped — exact, it just spends an extra step.  Only
    a replica queue exceeding ``q_cap`` actually loses arrivals, counted
    in ``buffer_dropped`` (a correct run has ``buffer_dropped == 0``,
    the same convention as the single-server kernel).  All times are
    rebased to
    the last processed event, keeping float32 precision window-sized.

    Replica invariant: a replica is *free* (not committed) iff its queue
    is empty — a completion that leaves jobs immediately schedules the
    next decision, and an arrival routed to a free replica schedules one
    at its own epoch (plus the policy's timeout delay).  Hence every
    batch start happens at a scheduled decision and is handled uniformly
    in the outer step.

    ``has_loss = True`` adds, all behind this compile-time flag:
    reject-mode arrival admission against the per-replica room (a
    rejected arrival is a measured overflow, not a capacity artifact),
    deadline reneging of the deciding replica's expired FIFO prefix at
    each of its decision events (which requires ``pop_cap = q_cap`` so
    the row gather sees every waiting job), the drop-mode tail trim
    after each pop, and the bounded retry orbit assessed once per
    event: the orbit's re-arrival block is routed whole to ONE replica
    by the point's own routing discipline — retries are bursty
    re-submissions of a single client batch, and a one-destination
    block keeps the scatter O(r_cap) instead of O(r_cap·k).  A deciding
    replica whose queue empties by reneging forms no batch and
    un-commits (it can go free with jobs expired, unlike the lossless
    kernel where committed ⇒ work pending).

    ``has_fail = True`` threads the breakdown/repair regime through the
    fleet: a forming replica draws its whole completion time (service +
    discipline-dependent rework/repairs, same law as the single-server
    kernel) AT formation — exact, because the law is independent of
    later state, and it preserves the latency-at-batch-start property
    above.  A replica whose drawn completion contains at least one
    failure is flagged *impaired* until its next decision; routing
    steers around impaired replicas (JSQ adds an ``IMP_LOAD`` penalty,
    random/round-robin rank-select over the un-impaired actives,
    falling back to all actives when every replica is impaired), which
    makes failover cost measurable.  Fail-drop aborts route the
    batch's jobs through the abandonment/retry path.
    """
    i32 = jnp.int32
    f32 = jnp.float32
    INF = jnp.float32(3.0e38)
    BIG_LOAD = jnp.int32(2 ** 20)   # inactive-replica load; keeps the
    IMP_LOAD = jnp.int32(2 ** 19)   # impaired-replica routing penalty
    slots = jnp.arange(pop_cap)     # JSQ compare free of i32 overflow
    ridx = jnp.arange(k_max)
    R_RANDOM, R_RR = ROUTE_CODE["random"], ROUTE_CODE["round_robin"]

    # rebase cadence: full-buffer clock rebases (the only whole-buffer
    # passes in the kernel) run once per _REBASE_EVERY events; in
    # between, times grow to ~32 windows, well within float32 for
    # ms-scale runs
    REBASE_EVERY = _REBASE_EVERY

    def run_point(p, key):
        lam, alpha, tau0 = p["lam"], p["alpha"], p["tau0"]
        b_max = jnp.where(p["b_max"] > 0, p["b_max"], q_cap).astype(i32)
        dist, cv = p["dist"], p["cv"]
        wait_max, wait_target = p["wait_max"], p["wait_target"]
        k = jnp.clip(p["k"], 1, k_max).astype(i32)
        routing = p["routing"]
        active = ridx < k
        if has_loss:
            q_lim = p["q_max"].astype(i32)
            deadline = p["deadline"]
            retry_rate = p["retry_rate"]
            retry_on = retry_rate > 0.0
            is_reject = p["overflow"] == _OV_REJECT
            # instantaneous per-replica admission bound ("429") vs the
            # physical ring in drop mode ("503": buffer, evict later)
            roomv = jnp.where((q_lim > 0) & is_reject, q_lim, q_cap)
            trim_to = jnp.where((q_lim > 0) & ~is_reject, q_lim, q_cap)
            retry_room = jnp.where(q_lim > 0,
                                   jnp.minimum(q_lim, q_cap), q_cap)
        if has_fail:
            mtbf, mttr = p["mtbf"], p["mttr"]
            throttle = p["throttle"]
            fd = p["fail_disc"]
            is_restart, is_drop = fd == 1, fd == 2
            xi = jnp.where(mtbf > 0.0, 1.0 / jnp.maximum(mtbf, 1e-30),
                           0.0)

        def step(state, x):
            i, kstep = x
            if has_fail:
                state, (deg, imp, nfail, dtime, lwork) = \
                    state[:-5], state[-5:]
            if has_loss:
                (q, head, buf, in_service, committed, t_free, next_arr,
                 rr, clock, lat_sum, lat_n, sum_b, sum_b2, sum_bs,
                 n_meas, busy, span, q_max, dropped, jobs_rep,
                 orbit, ov_n, ab_n, slo_n, fresh_n, retry_n) = state
            else:
                (q, head, buf, in_service, committed, t_free, next_arr,
                 rr, clock, lat_sum, lat_n, sum_b, sum_b2, sum_bs,
                 n_meas, busy, span, q_max, dropped, jobs_rep) = state
            # split count must not depend on has_loss (split(k, n)
            # re-keys all children with n); the orbit key folds in
            ksvc, karr = random.split(kstep)
            if has_loss:
                korb = random.fold_in(kstep, 0x0b17)

            # per-window randomness, drawn as two vectorized blocks; the
            # block shape is fixed, so key consumption never depends on
            # data and vmap-sharding a grid cannot perturb a point
            ka, kb = random.split(karr)
            u_route = random.uniform(ka, (a_cap,))
            gaps = engine.exp_gaps(kb, a_cap, lam)

            # 1) route the arrivals that precede the earliest pending
            #    decision.  No departures happen inside the window, so
            #    every routing discipline admits a closed-form, fully
            #    vectorized destination sequence — random and
            #    round-robin are state-free, and JSQ is discrete
            #    water-filling (each arrival tops up the lowest current
            #    load, ties to the lowest index), whose j-th destination
            #    follows from level cumsums.  The sequence is
            #    prefix-stable: truncating the window (below) cannot
            #    change the destinations of earlier arrivals.
            t_dep0 = jnp.min(jnp.where(committed, t_free, INF))
            offs = jnp.concatenate([jnp.zeros((1,), f32),
                                    jnp.cumsum(gaps)])
            ts_ext = next_arr + offs                       # (a_cap + 1,)
            ts = ts_ext[:a_cap]
            jidx = jnp.arange(a_cap)

            if has_fail:
                # route around impaired replicas.  ``imp`` is constant
                # between two decisions (it only flips at formations),
                # so the per-window closed-form destination sequences
                # remain exact.  When EVERY active replica is impaired
                # the mask falls back to all actives — arrivals are
                # never stalled, only steered.
                avail = active & ~imp
                eff = jnp.where(jnp.any(avail), avail, active)
                n_eff = jnp.sum(eff.astype(i32))
                cum_eff = jnp.cumsum(eff.astype(i32))
                rank = jnp.minimum(
                    (u_route * n_eff.astype(f32)).astype(i32), n_eff - 1)
                dest_rand = jnp.sum(
                    jnp.where(eff[None, :]
                              & (cum_eff[None, :] == rank[:, None] + 1),
                              ridx[None, :], 0), axis=1)
                # round-robin: the j-th arrival starts its scan at the
                # cursor and takes the cyclically-next available replica
                start = (rr + jidx) % k
                cyc = (ridx[None, :] - start[:, None]) % k
                cyc = jnp.where(eff[None, :], cyc, BIG_LOAD)
                dest_rr = jnp.argmin(cyc, axis=1).astype(i32)
            else:
                dest_rand = jnp.minimum(
                    (u_route * k.astype(f32)).astype(i32), k - 1)
                dest_rr = (rr + jidx) % k
            if has_jsq:
                # JSQ water-filling: S(c) = arrivals needed to raise
                # every load below level c up to c; arrival j fills
                # level c_j = max{c : S(c) <= j} and lands on the
                # (j - S(c_j))-th replica (by index) among those with
                # load <= c_j
                load = jnp.where(active, q + in_service, BIG_LOAD)
                if has_fail:
                    # impaired replicas sort after every healthy load
                    # but before inactive rows (auto-fallback when all
                    # are impaired)
                    load = load + jnp.where(imp & active, IMP_LOAD, 0)
                lmin = jnp.min(load)
                cgrid = lmin + jnp.arange(a_cap + 1)
                S = jnp.sum(
                    jnp.maximum(cgrid[:, None] - load[None, :], 0),
                    axis=1)                            # (a_cap + 1,)
                filled = S[None, :] <= jidx[:, None]   # (a_cap, ·)
                cj = lmin + jnp.sum(filled.astype(i32), axis=1) - 1
                s_at = jnp.max(jnp.where(filled, S[None, :], 0), axis=1)
                rank = jidx - s_at
                sel = load[None, :] <= cj[:, None]     # (a_cap, k)
                cum = jnp.cumsum(sel.astype(i32), axis=1)
                dest_jsq = jnp.sum(
                    jnp.where(sel & (cum == (rank + 1)[:, None]),
                              ridx[None, :], 0), axis=1)
                dest = jnp.where(routing == R_RANDOM, dest_rand,
                                 jnp.where(routing == R_RR, dest_rr,
                                           dest_jsq)).astype(i32)
            else:
                dest = jnp.where(routing == R_RANDOM, dest_rand,
                                 dest_rr).astype(i32)

            # a free replica's first arrival schedules its batching
            # decision (free ⇒ its queue was empty, so that job is the
            # oldest); a scheduled decision earlier than t_dep0 shrinks
            # the window.  Including a first-arrival candidate that lies
            # beyond the final window is harmless: rel >= its arrival
            # epoch >= t_dep, so it can never be the min.
            oh_a = dest[:, None] == ridx[None, :]          # (a_cap, k)
            t_first = jnp.min(jnp.where(oh_a, ts[:, None], INF), axis=0)
            if has_timeout:
                do_wait = (wait_max > 0.0) & (wait_target > 1)
                rel_k = jnp.where(do_wait, t_first + wait_max, t_first)
            else:
                rel_k = t_first
            free = active & ~committed
            t_dep = jnp.minimum(t_dep0,
                                jnp.min(jnp.where(free, rel_k, INF)))
            # the processed prefix closes AT the event: with no timeout
            # the window-defining first arrival sits exactly at t_dep
            # (rel == t_first bitwise), and it belongs to the window;
            # arrival epochs are continuous, so a non-scheduling arrival
            # landing exactly on t_dep has probability zero
            sched = free & (t_first <= t_dep)
            committed = committed | sched
            t_free = jnp.where(sched, rel_k, t_free)

            proc = ts <= t_dep
            rr = jnp.where(routing == R_RR,
                           (rr + jnp.sum(proc.astype(i32))) % k, rr)
            # first unprocessed arrival epoch carries to the next step;
            # if even the post-block epoch precedes the event, the event
            # is deferred — the next step keeps routing (exact, just
            # costs an extra step; only queue overflow drops, below)
            unproc = jnp.where(ts_ext > t_dep, ts_ext, INF)
            mn = jnp.min(unproc)
            next_arr = jnp.where(mn < INF, mn, ts_ext[-1])
            do_event = ts_ext[-1] > t_dep

            # bulk FIFO push: each replica row is a ring (head = oldest
            # waiting job); arrival j lands at ring slot head[dest[j]] +
            # q[dest[j]] + (# earlier accepted window arrivals there) —
            # one flattened a_cap-element scatter per step, and pops
            # below just advance heads (no row shifting)
            onehot = oh_a & proc[:, None]                  # (a_cap, k)
            prior = jnp.cumsum(onehot.astype(i32), axis=0) \
                - onehot.astype(i32)
            prior_self = jnp.sum(prior * onehot.astype(i32), axis=1)
            fill = jnp.sum(jnp.where(onehot, q[None, :], 0), axis=1) \
                + prior_self
            if has_loss:
                # admission against the per-replica room; a turned-away
                # arrival is a measured overflow loss, not a capacity
                # artifact (prefix-greedy: later window arrivals still
                # see the fill the rejected one never added, matching
                # the per-arrival 429 semantics)
                ok = proc & (fill < roomv)
                lost_ov = jnp.sum((proc & ~ok).astype(i32))
                lost_ab = jnp.zeros((), i32)
            else:
                ok = proc & (fill < q_cap)
                dropped = dropped + jnp.sum((proc & ~ok).astype(i32))
            pos = (jnp.sum(jnp.where(onehot, head[None, :], 0), axis=1)
                   + fill) % q_cap
            flat = jnp.where(ok, dest * q_cap + pos, k_max * q_cap)
            buf = buf.at[flat].set(ts, mode="drop")
            q = q + jnp.sum((onehot & ok[:, None]).astype(i32), axis=0)

            # 2) the event: earliest committed replica decides.  The
            #    (k,) updates stay dense one-hot ops; the batch is read
            #    as a pop_cap-wide wrapped gather from the ring
            t_pend = jnp.where(committed, t_free, INF)
            r = jnp.argmin(t_pend).astype(i32)
            t_ev = jnp.min(t_pend)
            oh = (ridx == r) & do_event
            release = jnp.any(jnp.where(oh, in_service, 1) == 0)
            qr = jnp.sum(jnp.where(oh, q, 0))
            hr = jnp.sum(jnp.where(oh, head, 0))
            row = jnp.take(buf,
                           r * q_cap + (hr + slots) % q_cap,
                           mode="clip")

            if has_loss:
                # deadline reneging: the deciding replica's expired jobs
                # are a contiguous FIFO prefix of its row (pop_cap =
                # q_cap whenever a deadline is set, so the gather covers
                # the whole queue); qr = 0 masks this when no event
                # fires, and t_ev = INF makes the age test vacuous then
                n_exp = jnp.sum(((slots < qr)
                                 & (row < t_ev - deadline)).astype(i32))
                n_exp = jnp.where(deadline > 0.0, n_exp, 0)
                qr = qr - n_exp
                row = lax.dynamic_slice(
                    jnp.concatenate([row, jnp.zeros((pop_cap,), f32)]),
                    (n_exp,), (pop_cap,))
                lost_ab = lost_ab + n_exp

            # a completion whose queue holds jobs re-decides right away:
            # with no (applicable) timeout delay it starts the next batch
            # in this same step; a delayed one schedules the release
            if has_timeout:
                want_delay = (wait_max > 0.0) & (qr < wait_target) \
                    & (row[0] + wait_max > t_ev)
                rel_next = jnp.where(want_delay, row[0] + wait_max, t_ev)
                # qr is 0 unless an event fires ⇒ form is do_event-masked
                form = release | ((qr > 0) & ~want_delay)
            else:
                rel_next = t_ev
                form = release | (qr > 0)
            if has_loss:
                # reneging can empty a committed replica's queue: the
                # scheduled release then forms nothing and un-commits
                form = form & (qr > 0)

            # batch formation (release events and immediate re-starts)
            b = jnp.minimum(qr, b_max)
            mean_s = alpha * b.astype(f32) + tau0
            if all_det:
                s = mean_s
            else:
                kshape = jnp.where(dist == 1, 1.0, 1.0 / (cv * cv))
                g = random.gamma(ksvc, kshape) / kshape
                s = jnp.where(dist == 0, mean_s, mean_s * g)
            if has_fail:
                # whole completion time drawn AT formation (same law as
                # the single-server kernel; exact because the law is
                # independent of later state, and it keeps `depart`
                # known at batch start)
                deg_r = jnp.any(oh & deg)
                s = s * jnp.where(deg_r, throttle, 1.0)
                kf = random.fold_in(kstep, _FAIL_SALT)
                kf1, kf2, kf3, kf4 = random.split(kf, 4)
                fail_on = (mtbf > 0.0) & form & (b > 0)
                M = random.poisson(kf1, jnp.where(fail_on, xi * s, 0.0))
                rep_res = mttr * random.gamma(
                    kf2, jnp.maximum(M, 1).astype(f32))
                rep_res = jnp.where(M > 0, rep_res, 0.0)
                e_blk = random.exponential(kf3, (_FAIL_ATTEMPTS,)) \
                    * jnp.where(mtbf > 0.0, mtbf, 1.0)
                r_blk = random.exponential(kf4, (_FAIL_ATTEMPTS,)) \
                    * mttr
                pre = jnp.cumprod((e_blk < s).astype(f32))
                n_rst = jnp.sum(pre).astype(i32)
                lost_rst = engine.point_sum(pre * e_blk)
                rep_rst = engine.point_sum(pre * r_blk)
                e1, r1 = e_blk[0], r_blk[0]
                aborts = fail_on & is_drop & (e1 < s)
                n_f = jnp.where(
                    fail_on,
                    jnp.where(is_restart, n_rst,
                              jnp.where(is_drop, aborts.astype(i32),
                                        M)),
                    0)
                rep = jnp.where(
                    fail_on,
                    jnp.where(is_restart, rep_rst,
                              jnp.where(is_drop,
                                        jnp.where(aborts, r1, 0.0),
                                        rep_res)),
                    0.0)
                lost = jnp.where(fail_on & is_restart, lost_rst, 0.0)
                lost = jnp.where(aborts, e1, lost)
                s_busy = jnp.where(aborts, 0.0, s)
                comp = s + rep + jnp.where(fail_on & is_restart,
                                           lost_rst, 0.0)
                comp = jnp.where(aborts, e1 + r1, comp)
                # impaired from formation until the next decision;
                # degraded applies to the replica's NEXT batch
                imp = jnp.where(oh, fail_on & (n_f > 0), imp)
                deg = jnp.where(oh & form, fail_on & (n_f > 0), deg)
            else:
                comp = s
            depart = t_ev + comp
            # per-job latency ops run on pop_cap slots only — b never
            # exceeds pop_cap (= max b_max, or q_cap when some point
            # batches unboundedly)
            popmask = slots < b
            lats = jnp.where(popmask, depart - row, 0.0)
            if has_fail:
                # an aborted (fail-drop) batch completes nothing; its
                # jobs re-enter through the abandonment path below
                lats = jnp.where(aborts, 0.0, lats)
                popmask = popmask & ~aborts

            if has_loss:
                # prefix removals (reneged + popped) advance the head;
                # the drop-mode trim evicts the NEWEST waiting jobs
                # beyond q_max at the formation epoch, a tail cut that
                # only shrinks q (later pushes overwrite the slots)
                trim = jnp.where(form,
                                 jnp.maximum(qr - b - trim_to, 0), 0)
                lost_ov = lost_ov + trim
                take = n_exp + jnp.where(form, b, 0)
                q = q - jnp.where(oh, take + trim, 0)
                head = jnp.where(oh, (hr + take) % q_cap, head)
            else:
                q = q - jnp.where(oh & form, b, 0)
                head = jnp.where(oh & form, (hr + b) % q_cap, head)
            in_service = jnp.where(oh, jnp.where(form, b, 0), in_service)
            committed = jnp.where(oh, form | (qr > 0), committed)
            t_free = jnp.where(oh, jnp.where(form, depart, rel_next),
                               t_free)

            # 3) statistics (latency recorded at batch start — the depart
            #    epoch is already known under every modelled policy)
            meas = i >= warmup
            mstart = meas & form
            mf = mstart.astype(f32)
            bf = b.astype(f32)
            if has_fail:
                # completed-batch stats only; busy counts productive
                # execution (repairs → down_time, rework → lost_work)
                mfc = mf * (1.0 - aborts.astype(f32))
                lat_sum = lat_sum + mfc * engine.point_sum(lats)
                lat_n = lat_n + jnp.where(mstart & ~aborts, b, 0)
                sum_b = sum_b + mfc * bf
                sum_b2 = sum_b2 + mfc * bf * bf
                sum_bs = sum_bs + mfc * bf * comp
                n_meas = n_meas + (mstart & ~aborts).astype(i32)
                busy = busy + mf * s_busy
                nfail = nfail + mstart.astype(i32) * n_f
                dtime = dtime + mf * rep
                lwork = lwork + mf * lost
                jobs_rep = jobs_rep \
                    + jnp.where(oh & mstart & ~aborts, b, 0)
            else:
                lat_sum = lat_sum + mf * engine.point_sum(lats)
                lat_n = lat_n + jnp.where(mstart, b, 0)
                sum_b = sum_b + mf * bf
                sum_b2 = sum_b2 + mf * bf * bf
                sum_bs = sum_bs + mf * bf * s
                n_meas = n_meas + mstart.astype(i32)
                busy = busy + mf * s
                jobs_rep = jobs_rep + jnp.where(oh & mstart, b, 0)
            span = span + (meas & do_event).astype(f32) * (t_ev - clock)
            q_max = jnp.maximum(q_max, jnp.max(q))

            if has_loss:
                if has_fail:
                    # fail-drop: the aborted batch's jobs are filed
                    # through the abandonment/retry path (below,
                    # abandoned-first)
                    lost_ab = lost_ab + jnp.where(aborts, b, 0)
                b_done = jnp.where(aborts, 0, b) if has_fail else b
                in_slo = jnp.where(
                    deadline > 0.0,
                    jnp.sum((popmask & (lats <= deadline)).astype(i32)),
                    b_done)
                # bounded retry orbit, assessed once per processed
                # event (exact Binomial thinning over the inter-event
                # gap, fixed-shape RNG).  The firing block re-arrives
                # at t_ev and is routed WHOLE to one replica by the
                # point's own discipline — retries model one client's
                # bursty re-submission, and a single destination keeps
                # the scatter O(r_cap); round-robin reuses the cursor
                # without advancing it (the arrival stream owns it)
                k_draw, k_route = random.split(korb)
                elapsed = jnp.maximum(t_ev - clock, 0.0)
                p_fire = jnp.where(
                    do_event, 1.0 - jnp.exp(-retry_rate * elapsed), 0.0)
                n_r = engine.orbit_draws(k_draw, orbit, p_fire, r_cap)
                orbit = orbit - n_r
                u_r = random.uniform(k_route)
                load2 = jnp.where(active, q + in_service, BIG_LOAD)
                if has_fail:
                    # the retry block also steers around impaired
                    # replicas, with the same all-impaired fallback
                    avail2 = active & ~imp
                    eff2 = jnp.where(jnp.any(avail2), avail2, active)
                    n_eff2 = jnp.sum(eff2.astype(i32))
                    cum2 = jnp.cumsum(eff2.astype(i32))
                    rank2 = jnp.minimum(
                        (u_r * n_eff2.astype(f32)).astype(i32),
                        n_eff2 - 1)
                    d_rand = jnp.sum(
                        jnp.where(eff2 & (cum2 == rank2 + 1), ridx, 0))
                    cyc2 = jnp.where(eff2, (ridx - rr % k) % k,
                                     BIG_LOAD)
                    d_rr = jnp.argmin(cyc2).astype(i32)
                    load2 = load2 + jnp.where(imp & active, IMP_LOAD, 0)
                else:
                    d_rand = jnp.minimum(
                        (u_r * k.astype(f32)).astype(i32), k - 1)
                    d_rr = rr % k
                d_jsq = jnp.argmin(load2).astype(i32)
                dest_r = jnp.where(
                    routing == R_RANDOM, d_rand,
                    jnp.where(routing == R_RR, d_rr, d_jsq)
                ).astype(i32)
                oh_r = ridx == dest_r
                q_d = jnp.sum(jnp.where(oh_r, q, 0))
                h_d = jnp.sum(jnp.where(oh_r, head, 0))
                admit_r = jnp.minimum(
                    n_r, jnp.maximum(retry_room - q_d, 0))
                orbit = orbit + (n_r - admit_r)
                jr = jnp.arange(r_cap)
                flat_r = jnp.where(
                    jr < admit_r,
                    dest_r * q_cap + (h_d + q_d + jr) % q_cap,
                    k_max * q_cap)
                buf = buf.at[flat_r].set(t_ev, mode="drop")
                q = q + jnp.where(oh_r, admit_r, 0)
                # an idle destination schedules its decision at t_ev
                # (plus the policy's timeout delay), like any arrival
                was_comm = jnp.any(oh_r & committed)
                if has_timeout:
                    do_wait_r = (wait_max > 0.0) & (wait_target > 1)
                    rel_r = jnp.where(do_wait_r, t_ev + wait_max, t_ev)
                else:
                    rel_r = t_ev
                sched_r = (~was_comm) & (admit_r > 0)
                committed = committed | (oh_r & sched_r)
                t_free = jnp.where(oh_r & sched_r, rel_r, t_free)
                # file this step's fresh losses — abandoned first, then
                # overflow; whatever the orbit cannot hold (or retries
                # are off) is a terminal loss in its own class
                orbit, term_ab, term_ov = engine.orbit_file(
                    orbit, lost_ab, lost_ov, r_cap, retry_on)
                mi = meas.astype(i32)
                ab_n = ab_n + mi * term_ab
                ov_n = ov_n + mi * term_ov
                slo_n = slo_n + jnp.where(mstart, in_slo, 0)
                fresh_n = fresh_n + mi * jnp.sum(proc.astype(i32))
                retry_n = retry_n + mi * n_r

            # the clock tracks the last processed event; the full-buffer
            # rebase — and the histogram update, whose per-call cost
            # under vmap dwarfs its per-element cost — are amortized to
            # the superstep wrapper (raw latencies ride out as scan
            # outputs and are binned there)
            clock = jnp.where(do_event, t_ev, clock)

            out_state = (q, head, buf, in_service, committed, t_free,
                         next_arr, rr, clock, lat_sum, lat_n, sum_b,
                         sum_b2, sum_bs, n_meas, busy, span, q_max,
                         dropped, jobs_rep)
            if has_loss:
                out_state = out_state + (orbit, ov_n, ab_n, slo_n,
                                         fresh_n, retry_n)
            if has_fail:
                out_state = out_state + (deg, imp, nfail, dtime, lwork)
            return out_state, (lats, popmask & mstart)

        # histogram thinning: scatter-adds cost per *element* under
        # vmap, so hist_every > 1 records only an unbiased 1-in-N batch
        # subsample.  Means/counters always use every job; only the
        # percentile sample thins.
        hist_rows = thinned_rows(REBASE_EVERY, hist_every)

        def superstep(state, x):
            i_base, k_sup = x
            *inner, bm_mean, bm_m2, bm_nb, hists = state
            s0, n0 = inner[9], inner[10]
            inner, (lats, inc) = lax.scan(
                step, tuple(inner),
                (i_base + jnp.arange(REBASE_EVERY),
                 random.split(k_sup, REBASE_EVERY)))
            hists = _ss.hist_update(hists, lats, inc, n_bins=n_bins,
                                    backend=ss_backend,
                                    sketch=use_sketch,
                                    hist_rows=hist_rows)
            bm_mean, bm_m2, bm_nb = engine.welford_block(
                (bm_mean, bm_m2, bm_nb), inner[9] - s0, inner[10] - n0)
            # rebase time to the last processed event (one buffer pass
            # per REBASE_EVERY events)
            (q, head, buf, in_service, committed, t_free, next_arr, rr,
             clock, *accs) = inner
            metrics.tap_superstep(
                tap, i_base // REBASE_EVERY, queue=jnp.sum(q),
                jobs=accs[1], busy=accs[6], span=accs[7],
                dropped=accs[9],
                overflow=accs[12] if has_loss else 0,
                abandoned=accs[13] if has_loss else 0)
            return (q, head, buf - clock, in_service, committed,
                    t_free - clock, next_arr - clock, rr,
                    jnp.zeros((), f32), *accs, bm_mean, bm_m2, bm_nb,
                    hists), None

        n_super = n_steps // REBASE_EVERY
        key, k0 = random.split(key)
        init = (jnp.zeros((k_max,), i32),              # q
                jnp.zeros((k_max,), i32),              # head (ring)
                jnp.zeros((k_max * q_cap,), f32),      # buf (flat)
                jnp.zeros((k_max,), i32),              # in_service
                jnp.zeros((k_max,), bool),             # committed
                jnp.full((k_max,), INF, f32),          # t_free
                random.exponential(k0) / lam,          # next_arr
                jnp.zeros((), i32),                    # rr
                jnp.zeros((), f32),                    # clock
                jnp.zeros((), f32), jnp.zeros((), i32),  # lat_sum, lat_n
                jnp.zeros((), f32), jnp.zeros((), f32),  # sum_b, sum_b2
                jnp.zeros((), f32),                      # sum_bs
                jnp.zeros((), i32), jnp.zeros((), f32),  # n_meas, busy
                jnp.zeros((), f32), jnp.zeros((), i32),  # span, q_max
                jnp.zeros((), i32),                      # dropped
                jnp.zeros((k_max,), i32))                # jobs_rep
        if has_loss:
            # orbit, ov_n, ab_n, slo_n, fresh_n, retry_n
            init = init + tuple(jnp.zeros((), i32) for _ in range(6))
        if has_fail:
            init = init + (jnp.zeros((k_max,), bool),   # degraded
                           jnp.zeros((k_max,), bool),   # impaired
                           jnp.zeros((), i32),          # n_failures
                           jnp.zeros((), f32),          # down_time
                           jnp.zeros((), f32))          # lost_work
        init = init + (jnp.zeros((), f32), jnp.zeros((), f32),
                       jnp.zeros((), i32))              # batch-means bm
        hists0 = (jnp.zeros((n_bins,), i32),)            # hist (superstep)
        if use_sketch:
            hists0 = hists0 + (jnp.zeros((n_bins,), f32),)
        init = init + (hists0,)
        state, _ = lax.scan(
            superstep, init,
            (jnp.arange(n_super) * REBASE_EVERY,
             random.split(key, n_super)))
        (lat_sum, lat_n, sum_b, sum_b2, sum_bs, n_meas, busy, span,
         q_max, dropped, jobs_rep) = state[9:20]
        bm_m2, bm_nb = state[-3], state[-2]
        hists = state[-1]

        jobs = jnp.maximum(lat_n, 1).astype(f32)
        nb = jnp.maximum(n_meas, 1).astype(f32)
        out = {
            "mean_latency": lat_sum / jobs,
            "mean_batch": sum_b / nb,
            "batch_m2": sum_b2 / nb,
            "mean_service": sum_bs / jnp.maximum(sum_b, 1e-30),
            "utilization": busy / jnp.maximum(
                k.astype(f32) * span, 1e-30),
            "n_jobs": lat_n,
            "n_batches": n_meas,
            "max_queue": q_max,
            "dropped": dropped,
            "lat_bm_m2": bm_m2,
            "lat_bm_n": bm_nb,
            "hist": hists[0],
            "jobs_by_replica": jobs_rep,
        }
        if use_sketch:
            out["hist_sums"] = hists[1]
        if has_loss:
            (_orbit, ov_n, ab_n, slo_n, fresh_n, retry_n) = state[20:26]
            out.update(overflow_dropped=ov_n, abandoned=ab_n,
                       n_in_slo=slo_n, n_fresh=fresh_n, n_retry=retry_n)
        if has_fail:
            fs = 20 + (6 if has_loss else 0)
            (_deg, _imp, nfail, dtime, lwork) = state[fs:fs + 5]
            out.update(n_failures=nfail, down_time=dtime,
                       lost_work=lwork, span=span)
        return out

    return engine.shard_kernel(jax.vmap(run_point), n_dev)


def fleet_caps(grid: FleetGrid, *, q_cap: Optional[int] = None) -> dict:
    """The compile-time capacities ``fleet_sweep`` would derive from
    ``grid`` — compute once on the FULL campaign grid and splat into
    every chunk of a split dispatch (``fleet_sweep(chunk,
    key_offset=..., **fleet_caps(full_grid))``).  ``a_cap`` is a static
    default (never grid-derived), so only ``q_cap`` (+ ``r_cap`` on
    loss grids) appear here."""
    has_loss = grid.has_loss
    if q_cap is None:
        fail_kw = {}
        if grid.has_fail:
            # the per-replica room must absorb the completion-time
            # inflation (rework + repairs) of the failure points
            fail_kw = dict(
                mtbf=grid.mtbf, mttr=grid.mttr,
                restart=grid.fail_disc == FAIL_DISC_CODE["restart"],
                throttle=grid.throttle)
        q_cap = engine.queue_capacity(grid.lam / np.maximum(grid.k, 1),
                                      grid.alpha, grid.tau0, grid.b_max,
                                      grid.wait_max,
                                      q_max=grid.q_max if has_loss
                                      else None, **fail_kw)
    caps = dict(q_cap=int(q_cap))
    if has_loss:
        caps["r_cap"] = int(engine.orbit_capacity(grid.lam,
                                                  grid.retry_rate))
    return caps


def fleet_plan(grid: FleetGrid, *, n_steps: int = 6000,
               warmup: Optional[int] = None, q_cap: Optional[int] = None,
               a_cap: int = 32, r_cap: Optional[int] = None,
               n_bins: int = 512, seed: int = 0,
               key_offset: int = 0, hist_every: int = 1,
               shard: ShardSpec = None, sketch: bool = False,
               superstep_backend: Optional[str] = None,
               metrics_tap=None) -> engine.KernelPlan:
    """``sweep_plan``'s fleet analogue: everything ``fleet_sweep`` does
    before the device dispatch, returned as an ``engine.KernelPlan``."""
    if not isinstance(grid, FleetGrid):
        raise TypeError("fleet_sweep needs a FleetGrid "
                        "(see FleetGrid.from_points/from_product)")
    if len(grid) == 0:
        raise ValueError("empty grid")
    # the kernel rebases its clock once per _REBASE_EVERY events
    n_steps = -(-int(n_steps) // _REBASE_EVERY) * _REBASE_EVERY
    if warmup is None:
        warmup = max(1, n_steps // 10)
    if not 0 <= warmup < n_steps:
        raise ValueError(f"warmup {warmup} must lie in [0, {n_steps})")
    if np.any(grid.k < 1):
        raise ValueError("k must be >= 1")
    has_loss = grid.has_loss
    if key_offset:
        _require_pinned_caps(
            "fleet", key_offset,
            q_cap=q_cap is not None,
            r_cap=not has_loss or r_cap is not None)
    # the per-replica ring is sized from the per-replica load λ/k
    # (fleet_caps); a_cap is a static default, never grid-derived
    if q_cap is None or (has_loss and r_cap is None):
        caps = fleet_caps(grid, q_cap=q_cap)
        q_cap = caps["q_cap"] if q_cap is None else q_cap
        if has_loss and r_cap is None:
            r_cap = caps["r_cap"]
    if not has_loss:
        r_cap = 0
    if np.any(grid.b_max > q_cap):
        raise ValueError("b_max exceeds q_cap; raise q_cap")
    if not set(np.unique(grid.routing)) <= set(ROUTE_CODE.values()):
        raise ValueError(f"unknown routing code in grid "
                         f"(valid: {ROUTE_CODE})")
    if has_loss and np.any(grid.q_max > q_cap):
        raise ValueError("q_max exceeds q_cap; raise q_cap")

    k_max = int(grid.k.max())
    has_timeout = bool(np.any(grid.wait_max > 0.0))
    all_det = bool(np.all(grid.dist == DIST_CODE["det"]))
    # all-finite-b_max grids get narrower per-job latency ops — unless
    # a deadline is set, whose renege scan must see the whole ring
    pop_cap = (int(q_cap)
               if np.any(grid.b_max == 0)
               or (has_loss and np.any(grid.deadline > 0.0))
               else int(grid.b_max.max()))
    has_jsq = bool(np.any(grid.routing == ROUTE_CODE["jsq"]))
    if sketch:
        n_bins = SKETCH_BINS
    n = len(grid)
    ss_backend = _ss.resolve_backend(superstep_backend)
    n_dev = engine.resolve_shards(shard, n)
    if metrics_tap is not None:
        # io_callback under shard_map is outside the pinned-jax
        # contract; bitwise shard invariance makes this timing-only
        n_dev = 1
    kernel = _build_fleet_kernel(int(n_steps), int(warmup), k_max,
                                 int(q_cap), int(a_cap), pop_cap,
                                 int(n_bins), has_timeout, all_det,
                                 has_jsq, has_loss, int(r_cap),
                                 grid.has_fail, int(hist_every),
                                 ss_backend, bool(sketch), metrics_tap,
                                 n_dev)

    params = {
        "lam": jnp.asarray(grid.lam), "alpha": jnp.asarray(grid.alpha),
        "tau0": jnp.asarray(grid.tau0), "b_max": jnp.asarray(grid.b_max),
        "dist": jnp.asarray(grid.dist), "cv": jnp.asarray(grid.cv),
        "wait_max": jnp.asarray(grid.wait_max),
        "wait_target": jnp.asarray(grid.wait_target),
        "k": jnp.asarray(grid.k), "routing": jnp.asarray(grid.routing),
    }
    if has_loss:
        params.update(
            q_max=jnp.asarray(grid.q_max),
            deadline=jnp.asarray(grid.deadline),
            overflow=jnp.asarray(grid.overflow),
            retry_rate=jnp.asarray(grid.retry_rate))
    if grid.has_fail:
        params.update(
            mtbf=jnp.asarray(grid.mtbf),
            mttr=jnp.asarray(grid.mttr),
            fail_disc=jnp.asarray(grid.fail_disc),
            throttle=jnp.asarray(grid.throttle))
    keys = engine.point_keys(seed, key_offset, n)
    return engine.KernelPlan(kernel=kernel, params=params, keys=keys,
                             n=n, n_dev=n_dev, sketch=bool(sketch),
                             has_loss=has_loss,
                             supersteps=n_steps // _REBASE_EVERY,
                             superstep_len=_REBASE_EVERY)


def fleet_sweep(grid: FleetGrid, *, n_steps: int = 6000,
                warmup: Optional[int] = None, q_cap: Optional[int] = None,
                a_cap: int = 32, r_cap: Optional[int] = None,
                n_bins: int = 512, seed: int = 0,
                key_offset: int = 0, hist_every: int = 1,
                shard: ShardSpec = None, sketch: bool = False,
                superstep_backend: Optional[str] = None,
                metrics_tap=None) -> FleetResult:
    """Simulate every fleet point for ``n_steps`` replica decisions in one
    jit+vmap device dispatch.

    ``n_steps`` counts fleet-wide *events*: at moderate/high load nearly
    every event is a service completion that immediately starts the next
    batch, so the fleet processes roughly ``n_steps`` batches in total —
    size it ``k×`` larger to give each replica the run length a
    single-server ``sweep`` would get.  (Idle→busy transitions and
    arrival windows denser than ``a_cap`` consume extra events, so
    low-load and very-high-load points complete somewhat fewer batches.)
    ``q_cap`` bounds each replica's waiting room; overflowing it is the
    one true capacity loss, counted in ``buffer_dropped`` (a correct
    run has ``buffer_dropped == 0``); the default (``None``) sizes it
    adaptively from the grid's per-replica load
    (``engine.queue_capacity`` at rate
    λ/k).  ``a_cap`` only tiles the arrival routing — a denser window
    defers its event a step, exact but slower, so size ``a_cap`` near
    the expected batch size.  ``hist_every = N > 1`` records a 1-in-N
    batch subsample in the latency histogram (the scatter-add is the
    costliest op on CPU); means and counters always use every job, only
    the percentile sample thins.  ``shard`` picks the device-mesh width
    for the shard_map dispatch (``None`` → all visible devices — on
    CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count=``
    ``<cores>`` before the first JAX call; ``False``/1 → single device;
    an int → that many shards); per-point keys are global, so sharding
    never changes a point's result.

    Grids with loss regimes (``q_max``/``deadline``/``retry_rate``)
    compile the loss-capable kernel variant; ``q_max`` bounds each
    replica's waiting room and ``r_cap`` the shared retry orbit
    (defaults via ``engine.orbit_capacity``).  A deadline forces
    ``pop_cap = q_cap`` (the renege scan must see the whole queue).
    Loss-free grids trace the identical pre-admission-control kernel.

    Split dispatches (``key_offset != 0``) must pin the grid-derived
    caps — pass ``**fleet_caps(full_grid)`` — or this raises.
    ``sketch``/``superstep_backend``/``metrics_tap`` behave as in
    ``sweep``.
    """
    plan = fleet_plan(grid, n_steps=n_steps, warmup=warmup, q_cap=q_cap,
                      a_cap=a_cap, r_cap=r_cap, n_bins=n_bins, seed=seed,
                      key_offset=key_offset, hist_every=hist_every,
                      shard=shard, sketch=sketch,
                      superstep_backend=superstep_backend,
                      metrics_tap=metrics_tap)
    n, has_loss, sketch = plan.n, plan.has_loss, plan.sketch
    out = engine.dispatch(plan.kernel, plan.params, plan.keys, n,
                          plan.n_dev)

    n_jobs = np.asarray(out["n_jobs"])
    if has_loss:
        loss_kw = dict(
            overflow_dropped=np.asarray(out["overflow_dropped"]),
            abandoned=np.asarray(out["abandoned"]),
            n_in_slo=np.asarray(out["n_in_slo"]),
            n_fresh=np.asarray(out["n_fresh"]),
            n_retry=np.asarray(out["n_retry"]))
    else:
        loss_kw = dict(
            overflow_dropped=np.zeros_like(n_jobs),
            abandoned=np.zeros_like(n_jobs),
            n_in_slo=n_jobs.copy(),
            n_fresh=n_jobs.copy(),
            n_retry=np.zeros_like(n_jobs))

    p50, p95, p99 = _hist_percentiles(
        out["hist"], (50, 95, 99),
        edges=sketch_edges() if sketch else None)
    if metrics_tap is not None:
        metrics_tap.observe_summary(
            kind="fleet", points=n, jobs_total=int(n_jobs.sum()),
            p50_median=float(np.nanmedian(p50)),
            p95_median=float(np.nanmedian(p95)),
            p99_median=float(np.nanmedian(p99)))
    stderr, ci = variance.batch_means_stats(out["lat_bm_m2"],
                                            out["lat_bm_n"])
    fail_kw = {}
    if grid.has_fail:
        fail_kw = dict(
            n_failures=np.asarray(out["n_failures"]),
            down_time=np.asarray(out["down_time"], dtype=np.float64),
            lost_work=np.asarray(out["lost_work"], dtype=np.float64),
            span=np.asarray(out["span"], dtype=np.float64))
    return FleetResult(
        grid=grid,
        mean_latency=np.asarray(out["mean_latency"], dtype=np.float64),
        latency_p50=p50, latency_p95=p95, latency_p99=p99,
        mean_batch=np.asarray(out["mean_batch"], dtype=np.float64),
        batch_m2=np.asarray(out["batch_m2"], dtype=np.float64),
        mean_service=np.asarray(out["mean_service"], dtype=np.float64),
        utilization=np.clip(
            np.asarray(out["utilization"], dtype=np.float64), 0.0, 1.0),
        n_jobs=n_jobs,
        n_batches=np.asarray(out["n_batches"]),
        max_queue=np.asarray(out["max_queue"]),
        buffer_dropped=np.asarray(out["dropped"]),
        hist=np.asarray(out["hist"]),
        hist_sums=(np.asarray(out["hist_sums"], dtype=np.float64)
                   if sketch else None),
        stderr=stderr, ci_halfwidth=ci,
        n_blocks=np.asarray(out["lat_bm_n"]),
        jobs_by_replica=np.asarray(out["jobs_by_replica"]),
        **loss_kw, **fail_kw,
    )
