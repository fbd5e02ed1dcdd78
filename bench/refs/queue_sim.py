"""A plain batch-by-batch simulator of the capped batch-all-waiting queue.

Independent of the program.  One scan step per service completion:
the server takes b = min(q, b_max) waiting jobs (after an idle gap,
the arrival that ends it starts a batch of one), serves them in
τ[b] = α·b + τ0, and the arrivals of the service period, a Poisson
process of rate λ drawn as exponential gaps, join the FIFO.  Times are
kept relative to the last departure.  The FIFO moves by static shifts
selected by the bits of the (per-point) shift, with no gather or
scatter, which an accelerator serialises under ``vmap``.  Every quantity is computed in ``dtype``: the
benchmark's control runs it in bfloat16, the step below the float32
that the configuration states for the sweep kernel, and puts its
per-point results in the program's place.

Returns per point what the comparison reads: the mean latency, the
measured job count, the latency histogram (8 bins per octave), the
batch count and the regenerative batch-means pair (m2, n) over blocks
of ``BLOCK`` batches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


N_BINS = 512        # 8 per octave, bin 256 holds [1, 2^(1/8)) ms
BLOCK = 32          # batches per batch-means block


def _shift(x, k, bits: int, left: bool):
    """``x`` shifted by ``k`` (< 2**bits) slots, zeros shifted in."""
    n = x.shape[0]
    zero = jnp.zeros((), x.dtype)
    for j in range(bits):
        w = 1 << j
        if w >= n:
            moved = jnp.zeros_like(x)
        elif left:
            moved = jnp.concatenate([x[w:], jnp.full((w,), zero)])
        else:
            moved = jnp.concatenate([jnp.full((w,), zero), x[:n - w]])
        x = jnp.where(((k >> j) & 1) == 1, moved, x)
    return x


def simulate(lam, alpha, tau0, b_max, key, *, n_batches: int,
             warmup: int, q_cap: int, a_cap: int, b_top: int,
             dtype=jnp.float32):
    """Simulate one point; vmap it over a grid.  ``lam``/``alpha``/
    ``tau0`` are per millisecond / milliseconds; ``b_max`` an int, at
    most ``b_top``, the largest cap of the grid.  A batch takes jobs
    from the head slots ``[0, b_top)``, and the histogram counts them by
    comparison against every bin."""
    dt = dtype
    lam, alpha, tau0 = (jnp.asarray(x, dt) for x in (lam, alpha, tau0))
    slots = jnp.arange(q_cap)
    head = jnp.arange(b_top)
    bin_ids = jnp.arange(N_BINS)
    b_bits = int(b_top).bit_length()
    q_bits = int(q_cap - 1).bit_length()

    def step(carry, i):
        buf, q, key, lat_sum, lat_n, hist, bm_sum, bm_n, bm = carry
        key, k_idle, k_arr = jax.random.split(key, 3)
        idle = q == 0
        gap = (jax.random.exponential(k_idle, dtype=jnp.float32)
               .astype(dt) / lam)
        start = jnp.where(idle, gap, jnp.zeros((), dt))
        buf = jnp.where(idle & (slots == 0), gap, buf)
        q = jnp.where(idle, 1, q)
        b = jnp.minimum(q, b_max)
        s = alpha * b.astype(dt) + tau0
        depart = start + s
        popped = head < b
        lats = jnp.where(popped, depart - buf[:b_top], jnp.zeros((), dt))
        meas = i >= warmup
        lat_sum = lat_sum + jnp.where(meas, jnp.sum(lats), 0).astype(dt)
        lat_n = lat_n + jnp.where(meas, b, 0).astype(dt)
        bits = jnp.log2(jnp.maximum(lats.astype(jnp.float32), 1e-30))
        bins = jnp.clip(jnp.floor(bits * 8.0).astype(jnp.int32) + 256,
                        0, N_BINS - 1)
        inc = (popped & meas).astype(dt)
        hist = hist + jnp.sum(jnp.where(bins[:, None] == bin_ids,
                                        inc[:, None], jnp.zeros((), dt)), 0)
        # the service period's arrivals: a Poisson process of rate λ
        # from the start of service, as many as fall before it ends
        epochs = (jnp.cumsum(jax.random.exponential(k_arr, (a_cap,)))
                  .astype(dt) / lam)
        count = jnp.sum(epochs < s).astype(jnp.int32)
        left = q - b
        kept = _shift(buf, b, b_bits, left=True)
        new = _shift(jnp.concatenate([start + epochs,
                                      jnp.zeros(q_cap - a_cap, dt)]),
                     left, q_bits, left=False)
        buf = jnp.where(slots < left, kept,
                        jnp.where(slots < left + count, new,
                                  jnp.zeros((), dt)))
        q = jnp.minimum(left + count, q_cap)
        buf = buf - depart
        # one batch-means sample per block of measured batches
        bm_sum = bm_sum + jnp.where(meas, jnp.sum(lats), 0).astype(dt)
        bm_n = bm_n + jnp.where(meas, b, 0).astype(dt)
        close = meas & ((i - warmup + 1) % BLOCK == 0)
        x = bm_sum / jnp.maximum(bm_n, 1).astype(dt)
        mean, m2, n = bm
        n1 = n + close.astype(dt)
        d = x - mean
        mean1 = mean + d / jnp.maximum(n1, 1).astype(dt)
        m21 = m2 + d * (x - mean1)
        bm = (jnp.where(close, mean1, mean), jnp.where(close, m21, m2),
              n1)
        bm_sum = jnp.where(close, 0, bm_sum).astype(dt)
        bm_n = jnp.where(close, 0, bm_n).astype(dt)
        return (buf, q, key, lat_sum, lat_n, hist, bm_sum, bm_n, bm), None

    zero = jnp.zeros((), dt)
    init = (jnp.zeros(q_cap, dt), jnp.int32(0), key, zero, zero,
            jnp.zeros(N_BINS, dt), zero, zero, (zero, zero, zero))
    (_, _, _, lat_sum, lat_n, hist, _, _, bm), _ = lax.scan(
        step, init, jnp.arange(n_batches))
    return {"mean_latency": lat_sum / jnp.maximum(lat_n, 1).astype(dt),
            "n_jobs": lat_n, "hist": hist, "lat_bm_m2": bm[1],
            "lat_bm_n": bm[2]}
