"""Exact stationary E[W] of the capped batch-all-waiting queue.

Plain NumPy, independent of the program: the embedded chain at service
completions, L = jobs left waiting, truncated at K.  From level l a
batch of b = min(max(l, 1), b_max) is served in τ[b] = α·b + τ0
(deterministic); from l = 0 the server first idles until the next
arrival, which then starts a batch of one.  Arrivals during a service
are Poisson(λ·τ[b]), so L' = (l − b)⁺ + Poisson(λ·τ[b]).  E[W] follows
by renewal reward over one completion-to-completion cycle and Little's
law.  K doubles until the stationary mass at K is below ``tail_tol``.
"""
from __future__ import annotations

import math

import numpy as np


def _poisson_rows(means: np.ndarray, kmax: int) -> np.ndarray:
    j = np.arange(kmax + 1, dtype=float)
    logfact = np.concatenate([[0.0], np.cumsum(np.log(j[1:]))])
    logp = j[None, :] * np.log(means)[:, None] - logfact[None, :] \
        - means[:, None]
    return np.exp(logp)


def mean_latency(lam: float, alpha: float, tau0: float, b_max: int,
                 *, tail_tol: float = 1e-12, k_max: int = 8192) -> float:
    """Stationary mean latency (arrival to batch departure), in the
    units of α and τ0, for arrival rate ``lam`` per that unit."""
    k = 64
    while True:
        ls = np.arange(k + 1)
        b = np.minimum(np.maximum(ls, 1), b_max)
        tau = alpha * b + tau0
        carry = ls - np.minimum(ls, b)
        rows = _poisson_rows(lam * tau, k)
        P = np.zeros((k + 1, k + 1))
        for l in range(k + 1):
            width = k - carry[l]
            p = rows[l, :width + 1].copy()
            p[-1] += max(0.0, 1.0 - p.sum())
            P[l, carry[l]:carry[l] + width + 1] = p
        A = (P - np.eye(k + 1)).T
        A[-1, :] = 1.0
        rhs = np.zeros(k + 1)
        rhs[-1] = 1.0
        pi = np.clip(np.linalg.solve(A, rhs), 0.0, None)
        pi /= pi.sum()
        if pi[-1] <= tail_tol or k >= k_max:
            break
        k *= 2
    idle = np.where(ls == 0, 1.0 / lam, 0.0)
    in_sys = np.maximum(ls, 1).astype(float)
    integral = in_sys * tau + lam * tau ** 2 / 2.0
    e_l = float(pi @ integral) / float(pi @ (idle + tau))
    if not math.isfinite(e_l):
        raise ArithmeticError(f"chain diverged at lam={lam}, b={b_max}")
    return e_l / lam
