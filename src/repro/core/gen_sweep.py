"""Vectorized token-level (generate) sweep kernel.

The request-level kernels in ``repro.core.sweep`` advance one scan step
per *batch*; autoregressive generation is finer-grained — a request is a
prefill of ``prompt_len`` tokens plus ``gen_tokens`` decode steps, and
iteration-level (Orca/vLLM-style) schedulers re-decide the batch at
every decode step.  This module simulates both disciplines of
``repro.core.continuous_sim`` entirely in JAX — one ``lax.scan`` step
per scheduler *decision* — and ``vmap``s the kernel over a ``GenGrid``,
so a dense (load, prompt_len, gen_tokens, max_active, discipline) grid
runs in a single jit-compiled device dispatch.

One scan step is one cycle of the iteration-level scheduler:

1. if the system is empty, jump the clock to the next Poisson arrival
   (memorylessness — exactly one arrival ends the idle period),
2. admit waiting requests into free decode slots, FIFO, paying one
   *batched* prefill  α_p·(prompt·n_join) + τ0_p  inline,
3. run decode steps over the b active sequences (α_d·b + τ0_d each),
   retiring sequences whose remaining-token count hits zero, and
4. push the Poisson arrivals of the elapsed window into the waiting
   ring (the same constructive exp-gap/cumsum draw as the
   request-level kernels — see docs/theory.md).

Step 3 uses *run-length event skipping*: between scheduler events the
active set is frozen — no admission can happen before the next step
boundary that follows an arrival (continuous) or the batch end
(static), and no sequence retires before the smallest remaining-token
count runs out — so the kernel advances j identical decode steps in
closed form (time j·(α_d·b + τ0_d), batch-size moments weighted by j)
and pays one scan step per *event*, not per token.  A static batch is
one scan step; a lightly loaded continuous server spends ~1 step per
request instead of ~gen_tokens.  This is the token-level analogue of
the request-level kernel's batch-by-batch regeneration argument, and
it is exact for the same reason (docs/theory.md §"Token-level service
law").

The two disciplines differ ONLY in the admission gate of step 2:

- ``continuous`` admits whenever free slots exist (up to ``max_active``);
- ``static`` admits only when NO sequence is active — admitted requests
  then decode in lockstep and finish together, which reproduces the
  paper's batch-held-to-completion service
  prefill(b·prompt) + gen_tokens·decode(b) exactly, with ``max_active``
  playing the role of b_max.

So one kernel covers both, and the discipline is a per-point grid axis.

State per grid point is a *tail-pointer* FIFO buffer of waiting arrival
epochs: the waiting jobs are ``buf[head:tail]`` oldest-first, admission
pops by advancing ``head`` (no data movement), window arrivals append
at ``tail`` with one contiguous ``dynamic_update_slice`` (element-wise
scatters with computed indices lower ~an order of magnitude slower
under vmap on CPU), and the buffer is re-compacted to ``head = 0`` once
per superstep — so the per-step cost of the waiting room is O(appended)
instead of the O(q_cap) shift a compacted buffer pays.  On top of that
sit a fixed ``s_cap``-slot decode pool (remaining-token count and
arrival epoch per slot) and the carried next-arrival epoch
``next_arr``, so no arrival is ever discarded between windows.  All
randomness is drawn in one block per superstep (per-step threefry calls
are the other dominant per-point cost of a wide vmap on CPU), and all
times are relative to the current superstep origin; the clock is
rebased — and the buffer compacted, and the bit-binned latency
histogram scattered — once per ``_REBASE_EVERY`` steps (the superstep
amortization proven in the fleet kernel).  Capacity overflows (waiting
jobs beyond ``q_cap``; more than ``a_cap`` arrivals inside one window
even after the run shrinks to a single decode step) clamp and count in
``buffer_dropped`` — a correct run has ``buffer_dropped == 0``
(asserted by tests).  Admission-control losses (finite ``q_max``,
deadlines, retries — see ``repro.core.grid``) are separate *measured*
outputs: ``overflow_dropped`` / ``abandoned`` and the goodput fractions
derived from them.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, random

from repro.core import engine, metrics, variance
from repro.core.engine import ShardSpec
from repro.core.grid import (  # noqa: F401  (re-exported for callers)
    DISC_CODE, DISC_NAME, FAIL_DISC_CODE, OVERFLOW_CODE, GenGrid,
    GenResult)
from repro.core.sweep import _FAIL_ATTEMPTS, _FAIL_SALT
from repro.core.hist import (SKETCH_BINS, hist_edges,
                             hist_percentiles as _hist_percentiles,
                             sketch_edges, thinned_rows)
from repro.kernels import superstep as _ss

__all__ = ["DISC_CODE", "DISC_NAME", "GenGrid", "GenResult", "gen_sweep",
           "gen_caps"]

_OV_REJECT = OVERFLOW_CODE["reject"]

_REBASE_EVERY = 16          # scan steps per clock rebase + hist scatter
#   (smaller than the fleet kernel's 32: the tail buffer — and with it
#   the scan carry — scales with the rebase window, and the carry copy
#   is a first-order per-step cost on CPU)
_STEP_BUCKET = 2048         # n_steps rounds up to this (bounds recompiles)


@engine.kernel_cache(maxsize=16)
def _build_gen_kernel(n_steps: int, warmup: int, s_cap: int, q_cap: int,
                      a_cap: int, n_bins: int, has_loss: bool,
                      r_cap: int, has_fail: bool, hist_every: int,
                      ss_backend: str, use_sketch: bool, tap,
                      n_dev: int):
    """Compile-time specialization of the per-point token-level kernel.

    ``s_cap`` (grid max of ``max_active``) sizes the decode pool;
    ``q_cap`` the waiting buffer; ``a_cap`` the pre-drawn arrival chain
    per step (size it near λ × one decode step — a denser window only
    shrinks the run via ``k_cov`` below, exact but slower; drops need
    more than ``a_cap`` arrivals inside a single decode step).

    ``has_loss = False`` traces exactly the pre-admission-control
    kernel (loss-free grids stay bitwise-pinned).  ``has_loss = True``
    adds, per step: deadline reneging of expired waiting jobs after
    the idle jump (a head advance — waiting epochs are FIFO-sorted, so
    the expired set is a prefix), reject-mode admission of the window
    arrivals against the per-point room (prefix-greedy: occupancy only
    grows inside a run), the drop-mode tail trim to ``q_max`` after
    admission, and the bounded retry orbit assessed at each run end
    (re-arrivals join the tail at ``t_end``).  Reneging can empty an
    otherwise-idle queue: that step forms no batch (``b = 0``),
    advances no time, and the next step idles.

    ``has_fail = True`` adds the breakdown/repair regime at *run*
    granularity (a run — prefill + k identical decode steps — is the
    unit of preemptible work here): an exponential failure clock at
    rate ξ = 1/MTBF runs over the run's busy span w, *resume* extends
    the run end by M ~ Poisson(ξ·w) Exp(mttr) repairs, *restart*
    prepends the geometric lost-attempt block (each losing a
    TruncExp(ξ, w) partial execution plus a repair), and *drop* aborts
    the run at its first failure epoch — ALL of the run's active
    sequences are filed through the abandonment/retry path (partial
    decode progress is not resumed; the waiting queue is untouched).
    Arrivals during repairs join the queue normally (the window push
    uses the extended run end).  A run following a repair executes
    degraded: prefill and per-step decode times scale by the point's
    ``throttle``.  Failure randomness comes from a fold_in key block,
    leaving the base key stream untouched."""

    i32 = jnp.int32
    f32 = jnp.float32
    INF = jnp.float32(3.0e38)
    BIG = jnp.int32(2 ** 24)
    DISC_CONT = DISC_CODE["continuous"]
    # tail headroom past the q_cap waiting room between compactions,
    # the tighter of two bounds on (tail − q): (a) per-step appends —
    # every accepted arrival plus one idle consume per step,
    # ≤ (a_cap + 2)·R; (b) conservation — tail = waiting + popped,
    # waiting is clamped at q_cap (the leading term) and pops are
    # ≤ s_cap joiners (+1) per step, so ≤ (s_cap + 1)·R.  Appends write
    # a whole (a_cap + 1) block past the tail.  The buffer rides in the
    # scan carry, whose copy is a first-order per-step cost on CPU —
    # the tighter bound is a direct kernel speedup.
    #   With loss regimes, retries append ≤ r_cap more per step (a
    # whole r_cap block write), and reneging breaks the conservation
    # bound (b) (a renege pops up to q_cap in one step), so only the
    # append bound (a) applies.
    if has_loss:
        buf_len = (q_cap + (a_cap + 2 + r_cap) * _REBASE_EVERY
                   + a_cap + 1 + r_cap)
    else:
        buf_len = q_cap + min((a_cap + 2) * _REBASE_EVERY,
                              (s_cap + 1) * _REBASE_EVERY) + a_cap + 1
    REBASE_EVERY = _REBASE_EVERY

    def run_point(p, key):
        lam = p["lam"]
        a_d, t0_d = p["alpha_decode"], p["tau0_decode"]
        a_p, t0_p = p["alpha_prefill"], p["tau0_prefill"]
        prompt = p["prompt_len"].astype(f32)
        gen = p["gen_tokens"].astype(i32)
        cap = jnp.clip(p["max_active"], 1, s_cap).astype(i32)
        disc = p["discipline"]
        if has_loss:
            q_lim = p["q_max"].astype(i32)
            deadline = p["deadline"]
            retry_rate = p["retry_rate"]
            retry_on = retry_rate > 0.0
            is_reject = p["overflow"] == _OV_REJECT
            roomv = jnp.where((q_lim > 0) & is_reject, q_lim, q_cap)
            trim_to = jnp.where((q_lim > 0) & ~is_reject, q_lim, q_cap)
            retry_room = jnp.where(q_lim > 0,
                                   jnp.minimum(q_lim, q_cap), q_cap)
            idxb = jnp.arange(buf_len)
            jr = jnp.arange(r_cap)
        if has_fail:
            mtbf, mttr = p["mtbf"], p["mttr"]
            throttle = p["throttle"]
            fd = p["fail_disc"]
            is_restart, is_drop = fd == 1, fd == 2
            xi = jnp.where(mtbf > 0.0, 1.0 / jnp.maximum(mtbf, 1e-30),
                           0.0)

        def step(state, x):
            if has_fail:
                state, (deg, nfail, dtime, lwork) = \
                    state[:-4], state[-4:]
            if has_loss and has_fail:
                i, gaps, u_row, kfail = x
            elif has_loss:
                i, gaps, u_row = x
            elif has_fail:
                i, gaps, kfail = x
            else:
                i, gaps = x
            if has_loss:
                (head, tail, buf, rem, arr_s, now, next_arr, lat_sum,
                 lat_n, sum_b, sum_b2, n_meas, busy, span, q_max,
                 dropped, orbit, ov_n, ab_n, slo_n, fresh_n,
                 retry_n) = state
            else:
                (head, tail, buf, rem, arr_s, now, next_arr, lat_sum,
                 lat_n, sum_b, sum_b2, n_meas, busy, span, q_max,
                 dropped) = state
            q = tail - head

            t_step0 = now
            active = rem > 0
            n_act = jnp.sum(active.astype(i32))

            # 1) idle: system empty — jump to the carried next arrival
            #    and enqueue it.  The write lands at the tail
            #    unconditionally (past-tail slots are garbage until a
            #    later append overwrites them, so a non-idle step's
            #    write is harmless); only the tail advance is gated.
            due = (q == 0) & (n_act == 0)
            now = jnp.where(due, jnp.maximum(now, next_arr), now)
            buf = lax.dynamic_update_slice(buf, next_arr[None], (tail,))
            tail = tail + due.astype(i32)
            q = q + due.astype(i32)

            if has_loss:
                # deadline reneging at the scheduler epoch: the live
                # range buf[head:tail] is FIFO-sorted arrival epochs,
                # so the expired set is a prefix — a pure head advance.
                # (The idle arrival just enqueued has age 0.)
                live = (idxb >= head) & (idxb < tail)
                n_exp = jnp.sum(
                    (live & (buf < now - deadline)).astype(i32))
                n_exp = jnp.where(deadline > 0.0, n_exp, 0)
                head = head + n_exp
                q = q - n_exp
                lost_ab = n_exp
                lost_ov = jnp.zeros((), i32)

            # the pre-drawn arrival chain: epochs strictly after
            # next_arr; entry 0 IS next_arr (consumed above in the idle
            # case), the last entry is the coverage sentinel
            ts_ext = next_arr + jnp.concatenate(
                [jnp.zeros((1,), f32), jnp.cumsum(gaps)]) / lam

            # 2) admission gate: continuous fills any free slot; static
            #    only starts a fresh batch on an idle server (batch held
            #    to completion).  Joiners are the FIFO prefix
            #    buf[head:head+n_join]; slot s with free-rank r < n_join
            #    reads buf[head + r]; the pop just advances the head.
            gate = (disc == DISC_CONT) | (n_act == 0)
            n_join = jnp.where(gate, jnp.minimum(q, cap - n_act), 0)
            t_pf = jnp.where(n_join > 0,
                             a_p * prompt * n_join.astype(f32) + t0_p,
                             0.0)
            if has_fail:
                # degraded run after a repair: prefill and per-step
                # decode time scale by throttle (consumed this run,
                # re-armed below on failure)
                thr = jnp.where(deg, throttle, 1.0)
                t_pf = t_pf * thr
            rank = jnp.cumsum((~active).astype(i32)) - 1
            take = ~active & (rank < n_join)
            j_times = jnp.take(buf, jnp.clip(head + rank, 0,
                                             buf_len - 1))
            arr_s = jnp.where(take, j_times, arr_s)
            rem = jnp.where(take, gen, rem)
            head = head + n_join
            q = q - n_join

            if has_loss:
                # drop-mode ("503") eviction at the formation epoch:
                # the NEWEST waiting jobs beyond q_max leave by a tail
                # cut (later appends overwrite the slots)
                trim = jnp.maximum(q - trim_to, 0)
                tail = tail - trim
                q = q - trim
                lost_ov = lost_ov + trim

            # 3) run length: decode j identical steps in closed form
            #    until the next event — the earliest retirement
            #    (min remaining tokens), the first step boundary past
            #    the next pending arrival (only when it could be
            #    admitted: continuous AND a slot stays free), or the
            #    edge of the pre-drawn arrival coverage
            b = n_act + n_join
            dt = a_d * b.astype(f32) + t0_d
            if has_fail:
                dt = dt * thr
            if has_loss:
                # reneging can empty an otherwise-idle queue: b = 0
                # forms no batch and the step advances no time (the
                # next step idles); dt keeps a safe divisor
                has_b = b > 0
                dt = jnp.where(has_b, dt, 1.0)
            t0r = now + t_pf
            m_min = jnp.min(jnp.where(rem > 0, rem, BIG))
            na = jnp.min(jnp.where(ts_ext > now, ts_ext, INF))
            watch = (disc == DISC_CONT) & (b < cap)
            k_arr = jnp.where(
                watch & (na < INF),
                jnp.ceil((na - t0r) / dt).astype(i32), BIG)
            k_cov = jnp.floor((ts_ext[-1] - t0r) / dt).astype(i32)
            k = jnp.clip(jnp.minimum(jnp.minimum(m_min, k_arr), k_cov),
                         1, BIG)
            if has_loss:
                k = jnp.where(has_b, k, 1)
            kf = k.astype(f32)
            t_end = t0r + kf * dt
            if has_loss:
                t_end = jnp.where(has_b, t_end, now)
            if has_fail:
                # breakdown/repair over the run's busy span w (prefill
                # + k decode steps, the preemptible unit of work here);
                # the extended t_end feeds the window push below, so
                # arrivals during repairs join the queue normally
                w = t_pf + kf * dt
                if has_loss:
                    w = jnp.where(has_b, w, 0.0)
                kf1, kf2, kf3, kf4 = random.split(kfail, 4)
                fail_on = (mtbf > 0.0) & (w > 0.0)
                M = random.poisson(kf1, jnp.where(fail_on, xi * w, 0.0))
                rep_res = mttr * random.gamma(
                    kf2, jnp.maximum(M, 1).astype(f32))
                rep_res = jnp.where(M > 0, rep_res, 0.0)
                e_blk = random.exponential(kf3, (_FAIL_ATTEMPTS,)) \
                    * jnp.where(mtbf > 0.0, mtbf, 1.0)
                r_blk = random.exponential(kf4, (_FAIL_ATTEMPTS,)) \
                    * mttr
                pre = jnp.cumprod((e_blk < w).astype(f32))
                n_rst = jnp.sum(pre).astype(i32)
                lost_rst = engine.point_sum(pre * e_blk)
                rep_rst = engine.point_sum(pre * r_blk)
                e1, r1 = e_blk[0], r_blk[0]
                aborts = fail_on & is_drop & (e1 < w)
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
                ext = jnp.where(
                    fail_on,
                    jnp.where(is_restart, lost_rst + rep_rst,
                              jnp.where(is_drop, 0.0, rep_res)),
                    0.0)
                t_end = jnp.where(aborts, now + e1 + r1, t_end + ext)
                deg = fail_on & (n_f > 0)

            # 4) window arrivals (now, t_end] join the waiting buffer.
            #    The pushable block is the chain minus the consumed
            #    entry 0 in the idle case — a dynamic one-entry shift —
            #    and its accepted prefix is contiguous (the chain is
            #    sorted and starts past ``now``), so one contiguous
            #    ``dynamic_update_slice`` at q appends it FIFO.  The
            #    sentinel stays beyond the window by construction of
            #    ``k_cov`` and carries as a future ``next_arr``; if even
            #    a single-step window outruns the chain, the unseen
            #    arrivals are dropped+counted.
            ts_push = lax.dynamic_slice(ts_ext, (due.astype(i32),),
                                        (a_cap + 1,))
            count = jnp.sum(((ts_push > now)
                             & (ts_push <= t_end)).astype(i32))
            if has_loss:
                # admission against the per-point room: occupancy only
                # grows inside a run, so the accepted set is exactly
                # the first (room − q)⁺ arrivals — per-arrival 429
                # semantics with one contiguous append.  A turned-away
                # arrival is a measured overflow; only the coverage
                # sentinel still feeds the buffer_dropped witness.
                a = jnp.minimum(count, jnp.maximum(roomv - q, 0))
                lost_ov = lost_ov + (count - a)
                dropped = dropped + (ts_ext[-1] <= t_end).astype(i32)
            else:
                a = jnp.minimum(count, q_cap - q)
                dropped = dropped + (count - a) \
                    + (ts_ext[-1] <= t_end).astype(i32)
            buf = lax.dynamic_update_slice(buf, ts_push.astype(f32),
                                           (tail,))
            tail = tail + a
            q = q + a
            unproc = jnp.where(ts_ext > t_end, ts_ext, INF)
            mn = jnp.min(unproc)
            next_arr = jnp.where(mn < INF, mn, ts_ext[-1])

            # 5) the decode run retires exactly the rem == k sequences
            #    (k <= m_min, so no retirement happens mid-run)
            rem = jnp.where(rem > 0, rem - k, 0)
            fin = (take | active) & (rem == 0)
            if has_fail:
                # an aborted (fail-drop) run completes nothing: every
                # active sequence is dropped whole (no partial-progress
                # resume) and filed through the abandonment path below
                fin = fin & ~aborts
                rem = jnp.where(aborts, 0, rem)
            lats = jnp.where(fin, t_end - arr_s, 0.0)
            now = t_end

            # statistics after warmup, weighted by the run length so
            # they equal the per-decode-step accounting of the numpy
            # reference; span includes the idle gap, so utilization =
            # busy/span matches its whole-interval clock
            meas = i >= warmup
            mf = meas.astype(f32)
            bf = b.astype(f32)
            n_fin = jnp.sum(fin.astype(i32))
            lat_sum = lat_sum + mf * engine.point_sum(lats)
            lat_n = lat_n + jnp.where(meas, n_fin, 0)
            if has_fail:
                # decode-step stats count completed runs only; busy is
                # productive execution (repairs → down_time, rework and
                # aborted partials → lost_work)
                mfc = mf * (1.0 - aborts.astype(f32))
                sum_b = sum_b + mfc * kf * bf
                sum_b2 = sum_b2 + mfc * kf * bf * bf
                ran = (~aborts) if not has_loss else (has_b & ~aborts)
                n_meas = n_meas + jnp.where(meas & ran, k, 0)
                busy = busy \
                    + mfc * jnp.where(ran, t_pf + kf * dt, 0.0)
                nfail = nfail + meas.astype(i32) * n_f
                dtime = dtime + mf * rep
                lwork = lwork + mf * lost
            else:
                sum_b = sum_b + mf * kf * bf
                sum_b2 = sum_b2 + mf * kf * bf * bf
                if has_loss:
                    n_meas = n_meas + jnp.where(meas & has_b, k, 0)
                    busy = busy \
                        + mf * jnp.where(has_b, t_pf + kf * dt, 0.0)
                else:
                    n_meas = n_meas + jnp.where(meas, k, 0)
                    busy = busy + mf * (t_pf + kf * dt)
            span = span + mf * (t_end - t_step0)
            q_max = jnp.maximum(q_max, q)

            if has_loss:
                # bounded retry orbit, assessed at the run end (exact
                # Binomial thinning over the whole step, pre-drawn
                # uniform block); admitted re-arrivals join the tail
                # with arrival epoch t_end
                if has_fail:
                    # fail-drop: the aborted run's b sequences re-enter
                    # through the abandonment/retry path (filed below,
                    # abandoned-first)
                    lost_ab = lost_ab + jnp.where(aborts, b, 0)
                p_fire = 1.0 - jnp.exp(-retry_rate * (t_end - t_step0))
                n_r = jnp.sum(((jr < orbit)
                               & (u_row < p_fire)).astype(i32))
                orbit = orbit - n_r
                admit_r = jnp.minimum(
                    n_r, jnp.maximum(retry_room - q, 0))
                orbit = orbit + (n_r - admit_r)
                buf = lax.dynamic_update_slice(
                    buf, jnp.full((r_cap,), t_end, f32), (tail,))
                tail = tail + admit_r
                q = q + admit_r
                # file this step's fresh losses — abandoned first
                orbit, term_ab, term_ov = engine.orbit_file(
                    orbit, lost_ab, lost_ov, r_cap, retry_on)
                mi = meas.astype(i32)
                ab_n = ab_n + mi * term_ab
                ov_n = ov_n + mi * term_ov
                in_slo = jnp.where(
                    deadline > 0.0,
                    jnp.sum((fin & (lats <= deadline)).astype(i32)),
                    n_fin)
                slo_n = slo_n + mi * in_slo
                fresh_n = fresh_n + mi * (due.astype(i32) + count)
                retry_n = retry_n + mi * n_r

            # raw latencies ride out to the superstep, which does the
            # bit-binning once per block (three fewer ops per step)
            out_state = (head, tail, buf, rem, arr_s, now, next_arr,
                         lat_sum, lat_n, sum_b, sum_b2, n_meas, busy,
                         span, q_max, dropped)
            if has_loss:
                out_state = out_state + (orbit, ov_n, ab_n, slo_n,
                                         fresh_n, retry_n)
            if has_fail:
                out_state = out_state + (deg, nfail, dtime, lwork)
            return out_state, (lats, fin & meas)

        # histogram thinning (same contract as the fleet kernel): a
        # fixed scrambled 1-in-N step subsample feeds the percentile
        # histogram; means/counters always use every step.  NOTE: with
        # run-length skipping a static batch is ONE step, so thinning
        # is unbiased across batches; still prefer hist_every = 1 when
        # percentiles matter.
        hist_rows = thinned_rows(REBASE_EVERY, hist_every)

        def superstep(state, x):
            i_base, k_sup = x
            *state, bm_mean, bm_m2, bm_nb, hists = state
            state = tuple(state)
            s0, n0 = state[7], state[8]
            # one block draw per superstep, consumed row-wise by the
            # inner scan — per-step threefry calls would dominate the
            # per-point cost of a wide vmap on CPU.  The retry block
            # folds in its own key so the arrival draw stays
            # bitwise-pinned for loss-free points of a mixed grid.
            arr_gaps = random.exponential(k_sup,
                                          (REBASE_EVERY, a_cap + 1))
            if has_loss:
                retry_u = random.uniform(random.fold_in(k_sup, 0x0b17),
                                         (REBASE_EVERY, r_cap))
                xs = (i_base + jnp.arange(REBASE_EVERY), arr_gaps,
                      retry_u)
            else:
                xs = (i_base + jnp.arange(REBASE_EVERY), arr_gaps)
            if has_fail:
                # Poisson/Gamma repair draws have traced rates, so the
                # failure randomness rides as per-step keys, derived by
                # fold_in (the base block draws stay bitwise-pinned)
                fkeys = random.split(
                    random.fold_in(k_sup, _FAIL_SALT), REBASE_EVERY)
                xs = xs + (fkeys,)
            state, (lats, inc) = lax.scan(step, state, xs)
            hists = _ss.hist_update(hists, lats, inc, n_bins=n_bins,
                                    backend=ss_backend,
                                    sketch=use_sketch,
                                    hist_rows=hist_rows)
            bm_mean, bm_m2, bm_nb = engine.welford_block(
                (bm_mean, bm_m2, bm_nb), state[7] - s0, state[8] - n0)
            # rebase the clock to the superstep end and re-compact the
            # tail buffer to head = 0: the only whole-buffer passes in
            # the kernel, paid once per REBASE_EVERY steps — fused with
            # the clock rebase in repro.kernels.superstep
            (head, tail, buf, rem, arr_s, now, next_arr, *accs) = state
            buf = _ss.fifo_compact(buf, head, now, backend=ss_backend)
            arr_s = jnp.where(rem > 0, arr_s - now, 0.0)
            metrics.tap_superstep(
                tap, i_base // REBASE_EVERY, queue=tail - head,
                jobs=accs[1], busy=accs[5], span=accs[6],
                dropped=accs[8],
                overflow=accs[10] if has_loss else 0,
                abandoned=accs[11] if has_loss else 0)
            return (jnp.zeros((), i32), tail - head, buf, rem, arr_s,
                    jnp.zeros((), f32), next_arr - now,
                    *accs, bm_mean, bm_m2, bm_nb, hists), None

        key, k0 = random.split(key)
        init = (jnp.zeros((), i32),                    # head
                jnp.zeros((), i32),                    # tail
                jnp.zeros((buf_len,), f32),            # buf
                jnp.zeros((s_cap,), i32),              # rem
                jnp.zeros((s_cap,), f32),              # arr_s
                jnp.zeros((), f32),                    # now
                random.exponential(k0) / lam,          # next_arr
                jnp.zeros((), f32), jnp.zeros((), i32),  # lat_sum, lat_n
                jnp.zeros((), f32), jnp.zeros((), f32),  # sum_b, sum_b2
                jnp.zeros((), i32), jnp.zeros((), f32),  # n_meas, busy
                jnp.zeros((), f32), jnp.zeros((), i32),  # span, q_max
                jnp.zeros((), i32))                      # dropped
        if has_loss:
            # orbit, ov_n, ab_n, slo_n, fresh_n, retry_n
            init = init + tuple(jnp.zeros((), i32) for _ in range(6))
        if has_fail:
            init = init + (jnp.zeros((), bool),         # degraded
                           jnp.zeros((), i32),          # n_failures
                           jnp.zeros((), f32),          # down_time
                           jnp.zeros((), f32))          # lost_work
        init = init + (jnp.zeros((), f32), jnp.zeros((), f32),
                       jnp.zeros((), i32))              # batch-means bm
        hists0 = (jnp.zeros((n_bins,), i32),)            # hist
        if use_sketch:
            hists0 = hists0 + (jnp.zeros((n_bins,), f32),)
        init = init + (hists0,)
        n_super = n_steps // REBASE_EVERY
        state, _ = lax.scan(
            superstep, init,
            (jnp.arange(n_super) * REBASE_EVERY,
             random.split(key, n_super)))
        (lat_sum, lat_n, sum_b, sum_b2, n_meas, busy, span, q_max,
         dropped) = state[7:16]
        bm_m2, bm_nb = state[-3], state[-2]
        hists = state[-1]

        jobs = jnp.maximum(lat_n, 1).astype(f32)
        nst = jnp.maximum(n_meas, 1).astype(f32)
        out = {
            "mean_latency": lat_sum / jobs,
            "mean_batch": sum_b / nst,
            "batch_m2": sum_b2 / nst,
            "utilization": busy / jnp.maximum(span, 1e-30),
            "n_jobs": lat_n,
            "n_steps": n_meas,
            "max_queue": q_max,
            "dropped": dropped,
            "lat_bm_m2": bm_m2,
            "lat_bm_n": bm_nb,
            "hist": hists[0],
        }
        if use_sketch:
            out["hist_sums"] = hists[1]
        if has_loss:
            (_orbit, ov_n, ab_n, slo_n, fresh_n, retry_n) = state[16:22]
            out.update(overflow_dropped=ov_n, abandoned=ab_n,
                       n_in_slo=slo_n, n_fresh=fresh_n, n_retry=retry_n)
        if has_fail:
            fs = 16 + (6 if has_loss else 0)
            (_deg, nfail, dtime, lwork) = state[fs:fs + 4]
            out.update(n_failures=nfail, down_time=dtime,
                       lost_work=lwork, span=span)
        return out

    return engine.shard_kernel(jax.vmap(run_point), n_dev)


def gen_caps(grid: GenGrid, *, q_cap: Optional[int] = None) -> dict:
    """The compile-time capacities ``gen_sweep`` would derive from
    ``grid`` — compute once on the FULL campaign grid and splat into
    every chunk of a split dispatch (``gen_sweep(chunk,
    key_offset=..., **gen_caps(full_grid))``), so all chunks compile
    the same shapes as the whole-grid run."""
    has_loss = grid.has_loss
    has_fail = grid.has_fail
    fail_kw = {}
    if has_fail:
        fail_kw = dict(
            mtbf=grid.mtbf, mttr=grid.mttr,
            restart=grid.fail_disc == FAIL_DISC_CODE["restart"],
            throttle=grid.throttle)
    if q_cap is None:
        q_cap = engine.queue_capacity(
            grid.lam, grid.equivalent_alpha, grid.equivalent_tau0,
            grid.max_active,
            q_max=grid.q_max if has_loss else None, **fail_kw)
    # the densest indivisible window: the batched prefill of a full
    # batch plus the decode step it precedes
    window = (grid.alpha_prefill * grid.prompt_len * grid.max_active
              + grid.tau0_prefill
              + grid.alpha_decode * grid.max_active
              + grid.tau0_decode)
    a_cap = int(engine.window_capacity(grid.lam, window))
    if has_fail:
        # repairs/rework stretch a run past its nominal span, and the
        # arrival chain must still cover the extended window: scale by
        # the completion inflation and add an MTTR burst allowance
        infl = float(np.max(engine.completion_inflation(
            grid.lam, grid.equivalent_alpha, grid.equivalent_tau0,
            grid.max_active, **fail_kw)))
        burst = float(np.max(2.0 * grid.lam * grid.mttr
                             + 10.0 * np.sqrt(grid.lam * grid.mttr
                                              + 1.0)))
        a_cap = int(np.ceil(a_cap * infl + burst))
    caps = dict(q_cap=int(q_cap), a_cap=a_cap)
    if has_loss:
        caps["r_cap"] = int(engine.orbit_capacity(grid.lam,
                                                  grid.retry_rate))
    return caps


def gen_plan(grid: GenGrid, *, n_steps: int = 4096,
             warmup: Optional[int] = None, q_cap: Optional[int] = None,
             a_cap: Optional[int] = None, r_cap: Optional[int] = None,
             n_bins: int = 512,
             seed: int = 0, key_offset: int = 0, hist_every: int = 1,
             shard: ShardSpec = None, sketch: bool = False,
             superstep_backend: Optional[str] = None,
             metrics_tap=None) -> engine.KernelPlan:
    """``sweep_plan``'s token-level analogue: everything ``gen_sweep``
    does before the device dispatch, as an ``engine.KernelPlan``."""
    if not isinstance(grid, GenGrid):
        raise TypeError("gen_sweep needs a GenGrid "
                        "(see GenGrid.from_points/from_product)")
    if len(grid) == 0:
        raise ValueError("empty grid")
    n_steps = -(-int(n_steps) // _STEP_BUCKET) * _STEP_BUCKET
    if warmup is None:
        warmup = max(1, n_steps // 10)
    if not 0 <= warmup < n_steps:
        raise ValueError(f"warmup {warmup} must lie in [0, {n_steps})")
    s_cap = int(grid.max_active.max())
    has_loss = grid.has_loss
    if key_offset:
        from repro.core.sweep import _require_pinned_caps
        _require_pinned_caps(
            "gen", key_offset,
            q_cap=q_cap is not None, a_cap=a_cap is not None,
            r_cap=not has_loss or r_cap is not None)
    if q_cap is None or a_cap is None or (has_loss and r_cap is None):
        caps = gen_caps(grid, q_cap=q_cap)
        q_cap = caps["q_cap"] if q_cap is None else q_cap
        a_cap = caps["a_cap"] if a_cap is None else a_cap
        if has_loss and r_cap is None:
            r_cap = caps["r_cap"]
    if not has_loss:
        r_cap = 0
    if s_cap > q_cap:
        raise ValueError("max_active exceeds q_cap; raise q_cap")
    if not set(np.unique(grid.discipline)) <= set(DISC_CODE.values()):
        raise ValueError(f"unknown discipline code in grid "
                         f"(valid: {DISC_CODE})")
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
    kernel = _build_gen_kernel(int(n_steps), int(warmup), s_cap,
                               int(q_cap), int(a_cap), int(n_bins),
                               has_loss, int(r_cap), grid.has_fail,
                               int(hist_every), ss_backend,
                               bool(sketch), metrics_tap, n_dev)

    params = {
        "lam": jnp.asarray(grid.lam),
        "alpha_decode": jnp.asarray(grid.alpha_decode),
        "tau0_decode": jnp.asarray(grid.tau0_decode),
        "alpha_prefill": jnp.asarray(grid.alpha_prefill),
        "tau0_prefill": jnp.asarray(grid.tau0_prefill),
        "prompt_len": jnp.asarray(grid.prompt_len),
        "gen_tokens": jnp.asarray(grid.gen_tokens),
        "max_active": jnp.asarray(grid.max_active),
        "discipline": jnp.asarray(grid.discipline),
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


def gen_sweep(grid: GenGrid, *, n_steps: int = 4096,
              warmup: Optional[int] = None, q_cap: Optional[int] = None,
              a_cap: Optional[int] = None, r_cap: Optional[int] = None,
              n_bins: int = 512,
              seed: int = 0, key_offset: int = 0, hist_every: int = 1,
              shard: ShardSpec = None, sketch: bool = False,
              superstep_backend: Optional[str] = None,
              metrics_tap=None) -> GenResult:
    """Simulate every grid point for ``n_steps`` scheduler decisions in
    one jit+vmap device dispatch.

    ``n_steps`` counts scan steps; each advances a *run* of identical
    decode steps up to the next scheduler event, so a point completes
    roughly one request per 1–3 steps at low load and
    ``E[b]/gen_tokens`` requests per step at high load.  The value is
    rounded up to a multiple of ``_STEP_BUCKET`` so nearby sizes share
    one compiled kernel.  ``q_cap`` bounds the waiting buffer and
    ``a_cap`` the arrival chain visible per step; exceeding either
    clamps and counts in ``buffer_dropped`` (a correct run has
    ``buffer_dropped == 0``).  The defaults (``None``) size both
    adaptively
    from the dispatched grid: ``q_cap`` from the static-equivalent
    request-level law (``GenGrid.equivalent_alpha``/``equivalent_tau0``
    through ``engine.queue_capacity``), ``a_cap`` from the densest
    indivisible window — a full-batch batched prefill plus one decode
    step at the grid's highest λ (``engine.window_capacity``).
    Per-point PRNG keys come from
    ``fold_in(PRNGKey(seed), key_offset + i)``, so a grid sharded into
    several dispatches (``GenGrid.take`` + ``key_offset``) is
    bitwise-identical to the one-dispatch run — provided the dispatches
    share compiled shapes: split chunks (``key_offset != 0``) must pin
    ``q_cap``/``a_cap`` (and ``r_cap`` on loss grids) or this raises —
    pass ``**gen_caps(full_grid)`` (the adaptive defaults are sized per
    dispatched grid).
    ``shard`` picks the
    device-mesh width for the shard_map dispatch (same contract as
    ``fleet_sweep``: ``None`` → all visible devices, ``False``/1 →
    single device, an int → that many shards); per-point results are
    shard-count invariant.  ``sketch``/``superstep_backend``/
    ``metrics_tap`` behave as in ``repro.core.sweep.sweep``.
    """
    plan = gen_plan(grid, n_steps=n_steps, warmup=warmup, q_cap=q_cap,
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
            kind="gen", points=n, jobs_total=int(n_jobs.sum()),
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
    return GenResult(
        grid=grid,
        mean_latency=np.asarray(out["mean_latency"], dtype=np.float64),
        latency_p50=p50, latency_p95=p95, latency_p99=p99,
        mean_batch=np.asarray(out["mean_batch"], dtype=np.float64),
        batch_m2=np.asarray(out["batch_m2"], dtype=np.float64),
        utilization=np.clip(
            np.asarray(out["utilization"], dtype=np.float64), 0.0, 1.0),
        n_jobs=n_jobs,
        n_steps=np.asarray(out["n_steps"]),
        max_queue=np.asarray(out["max_queue"]),
        buffer_dropped=np.asarray(out["dropped"]),
        hist=np.asarray(out["hist"]),
        hist_sums=(np.asarray(out["hist_sums"], dtype=np.float64)
                   if sketch else None),
        stderr=stderr, ci_halfwidth=ci,
        n_blocks=np.asarray(out["lat_bm_n"]),
        **loss_kw, **fail_kw,
    )
