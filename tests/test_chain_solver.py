"""Structured exact-chain solver: parity, witnesses, and guards.

The banded level-recursion solver (``repro.core.chain_solver``) must be
*indistinguishable* from the dense LU reference it replaced — the
parity matrix below pins it to ≤ 1e-10 on both π and E[W] across
load regimes, b_max ladders (including an ∞-proxy), and service-model
fits — and its three entry points (scalar ``solve``, warm-started
``solve_batch``, one-dispatch ``solve_grid``) must agree with each
other to the same tolerance.
"""
import math

import numpy as np
import pytest

from repro.core import chain_solver as cs
from repro.core import markov as mk
from repro.core.analytic import LinearServiceModel, stability_limit
from repro.core.evaluate import evaluate
from repro.core.grid import MarkovGrid

V100 = LinearServiceModel(alpha=0.1438, tau0=1.8874)   # published fits
P4 = LinearServiceModel(alpha=0.5833, tau0=1.4284)
SYNTH = LinearServiceModel(alpha=0.31, tau0=0.57)      # plain affine

MODELS = [("v100", V100), ("p4", P4), ("synth", SYNTH)]


def _lam(model, b_max, rho):
    return rho * stability_limit(model.alpha, model.tau0, b_max)


class TestStructuredVsDense:
    """The acceptance parity matrix: structured == dense LU ≤ 1e-10
    on E[W] and π, at the same truncation."""

    @pytest.mark.parametrize("name,model", MODELS)
    @pytest.mark.parametrize("b_max", [1, 4, 32])
    @pytest.mark.parametrize("rho", [0.2, 0.6, 0.9])
    def test_parity(self, name, model, b_max, rho):
        lam = _lam(model, b_max, rho)
        rs = mk.solve(lam, model, b_max=b_max, truncation=512,
                      method="struct")
        rd = mk.solve(lam, model, b_max=b_max, truncation=512,
                      method="dense")
        assert rs.method == "struct" and rd.method == "dense"
        assert rs.mean_latency == pytest.approx(rd.mean_latency,
                                                rel=1e-10)
        assert np.max(np.abs(rs.pi - rd.pi)) <= 1e-10
        assert rs.utilization == pytest.approx(rd.utilization, rel=1e-10)
        assert rs.mean_batch == pytest.approx(rd.mean_batch, rel=1e-10)

    def test_parity_inf_proxy(self):
        """b_max = 256 at a λ far below the cap is an ∞-proxy: the
        chain never meets its cap, so the structured answer must also
        match the *actual* b_max = ∞ dense solve."""
        lam = 0.6 / V100.alpha                        # ρ = 0.6
        rs = mk.solve(lam, V100, b_max=256, truncation=1024,
                      method="struct")
        rd = mk.solve(lam, V100, b_max=256, truncation=1024,
                      method="dense")
        rinf = mk.solve(lam, V100, truncation=1024)   # ∞ → dense path
        assert rs.mean_latency == pytest.approx(rd.mean_latency,
                                                rel=1e-10)
        assert rs.mean_latency == pytest.approx(rinf.mean_latency,
                                                rel=1e-9)

    def test_gth_equals_banded_lapack(self):
        """The two CPU paths over the same band agree near machine
        precision (they are different factorizations of one matrix)."""
        lam = _lam(V100, 32, 0.9)
        ch = cs.build_chain(lam, V100, 32, 1024)
        pi_g = cs.solve_pi_gth(ch)
        pi_b = cs.solve_pi_banded(ch)
        assert np.max(np.abs(pi_g - pi_b)) <= 1e-13


class TestThreeWayAgreement:
    """solve vs solve_batch vs vmapped-JAX solve_grid."""

    def test_scalar_vs_batch_vs_grid(self):
        b_maxes = [2, 8, 32]
        fracs = [0.3, 0.7, 0.9]
        grid = MarkovGrid.from_fracs(fracs, V100.alpha, V100.tau0,
                                     b_maxes=b_maxes)
        K = 512
        gj = mk.solve_grid(grid, truncation=K, method="jax")
        gn = mk.solve_grid(grid, truncation=K, method="numpy")
        assert np.max(np.abs(gj.mean_latency - gn.mean_latency)
                      / gn.mean_latency) <= 1e-10
        for b in b_maxes:
            sel = grid.b_max == b
            lams = grid.lam[sel]
            batch = mk.solve_batch(list(lams), V100, b_max=b,
                                   truncation=K)
            for j, (lam, rb) in enumerate(zip(lams, batch)):
                rs = mk.solve(float(lam), V100, b_max=b, truncation=K)
                i = int(np.nonzero(sel)[0][j])
                assert rs.mean_latency == pytest.approx(
                    rb.mean_latency, rel=1e-12)
                assert rs.mean_latency == pytest.approx(
                    float(gj.mean_latency[i]), rel=1e-10)
                assert rs.tail_mass == pytest.approx(
                    float(gj.tail_mass[i]), abs=1e-12)

    def test_grid_jax_low_load_wide_bmax(self):
        """Regression: cells whose Poisson window is narrower than
        b_max (low load, large cap) must still dispatch — the down-move
        span D is clamped to the band width."""
        grid = MarkovGrid.from_fracs([0.1, 0.2], V100.alpha, V100.tau0,
                                     b_maxes=[128])
        gj = mk.solve_grid(grid, truncation=512, method="jax")
        gn = mk.solve_grid(grid, truncation=512, method="numpy")
        assert np.max(np.abs(gj.mean_latency - gn.mean_latency)
                      / gn.mean_latency) <= 1e-10

    def test_evaluate_markov_grid_backend(self):
        grid = MarkovGrid.from_fracs([0.4, 0.8], V100.alpha, V100.tau0,
                                     b_maxes=[4, 16])
        res = evaluate(grid, backend="markov", method="numpy")
        assert len(res) == 4
        for i, r in enumerate(res):
            ref = mk.solve(float(grid.lam[i]), V100,
                           b_max=float(grid.b_max[i]))
            assert r.backend == "markov"
            assert r.mean_latency == pytest.approx(ref.mean_latency,
                                                   rel=1e-8)
            r.check()

    def test_evaluate_rejects_markov_grid_elsewhere(self):
        grid = MarkovGrid.from_fracs([0.5], V100.alpha, V100.tau0,
                                     b_maxes=[4])
        with pytest.raises(ValueError, match="markov"):
            evaluate(grid, backend="sweep")


class TestTruncationWitness:
    """π[K] is the a-posteriori truncation witness; growing K must
    drive it down (to zero once the band clears the bulk)."""

    def test_tail_mass_monotone_under_K_growth(self):
        lam = _lam(V100, 32, 0.95)
        tails = [mk.solve(lam, V100, b_max=32, truncation=K,
                          method="struct").tail_mass
                 for K in (128, 256, 512, 1024)]
        for a, b in zip(tails, tails[1:]):
            assert b <= a * 1.01 + 1e-300
        assert tails[-1] < 1e-12

    def test_adaptive_meets_tolerance(self):
        lam = _lam(V100, 16, 0.9)
        r = mk.solve(lam, V100, b_max=16, tail_tol=1e-10)
        assert r.method == "struct"
        assert r.tail_mass <= 1e-10

    def test_grid_adaptive_meets_tolerance(self):
        grid = MarkovGrid.from_fracs([0.5, 0.95], V100.alpha, V100.tau0,
                                     b_maxes=[8, 64])
        res = mk.solve_grid(grid, method="numpy")
        assert float(res.tail_mass.max()) <= 1e-10


class TestGuardsAndDomain:
    """The truncation caps are per-method now: dense keeps the hard
    0.5 GB guard, the structured path goes far deeper."""

    def test_dense_hard_cap_still_raises(self):
        with pytest.raises(ValueError, match="dense"):
            mk.solve(1.0, V100, b_max=8, truncation=20_000,
                     method="dense")
        with pytest.raises(ValueError):
            mk.solve(1.0, V100, truncation=20_000)    # ∞ → dense

    def test_structured_goes_past_the_dense_cap(self):
        # 32768 would be an 8.6 GB dense matrix; the band is ~20 MB
        lam = _lam(V100, 4, 0.5)
        r = mk.solve(lam, V100, b_max=4, truncation=32_768,
                     method="struct")
        ref = mk.solve(lam, V100, b_max=4)
        assert r.truncation == 32_768
        assert r.mean_latency == pytest.approx(ref.mean_latency,
                                               rel=1e-9)

    def test_band_detachment_raises_and_auto_falls_back(self):
        lam = 2.0 * stability_limit(V100.alpha, V100.tau0, 256)
        with pytest.raises(ValueError, match="dense"):
            mk.solve(lam, V100, b_max=256, truncation=256,
                     method="struct")
        r = mk.solve(lam, V100, b_max=256, truncation=256)  # auto
        assert r.method == "dense"

    def test_solve_batch_auto_falls_back_like_solve(self):
        """Regression: solve and solve_batch must stay interchangeable
        — an out-of-domain λ falls back to dense in both, and in-domain
        λs in the same batch stay structured."""
        lim = stability_limit(V100.alpha, V100.tau0, 256)
        lams = [0.5 * lim, 2.0 * lim]
        batch = mk.solve_batch(lams, V100, b_max=256, truncation=256)
        assert batch[0].method == "struct"
        assert batch[1].method == "dense"
        for lam, rb in zip(lams, batch):
            rs = mk.solve(lam, V100, b_max=256, truncation=256)
            assert rb.mean_latency == pytest.approx(rs.mean_latency,
                                                    rel=1e-10)

    def test_markov_grid_requires_finite_bmax(self):
        with pytest.raises(ValueError, match="finite"):
            MarkovGrid.from_points([1.0], V100.alpha, V100.tau0,
                                   b_max=0)

    def test_grid_rejects_out_of_domain_cell(self):
        lam = 2.0 * stability_limit(V100.alpha, V100.tau0, 256)
        grid = MarkovGrid.from_points([lam], V100.alpha, V100.tau0,
                                      b_max=256)
        with pytest.raises(ValueError, match="domain"):
            mk.solve_grid(grid, truncation=256, method="numpy")


class TestBandConstruction:
    """Structural invariants of the band the recursions rely on."""

    def test_rows_are_stochastic_and_banded(self):
        lam = _lam(V100, 16, 0.8)
        ch = cs.build_chain(lam, V100, 16, 512)
        assert ch.B.shape == (513, ch.V + 1)
        np.testing.assert_allclose(ch.B.sum(axis=1), 1.0, atol=1e-12)
        assert np.all(np.diff(ch.c) >= 0)             # monotone offsets
        assert np.all(ch.c[1:] < np.arange(1, 513))   # attached band
        # repeating region: identical Toeplitz rows (shifted by 1)
        mid = 100
        np.testing.assert_allclose(ch.B[mid], ch.B[mid + 1], atol=0)

    def test_band_matches_dense_rows(self):
        lam = _lam(V100, 8, 0.7)
        K = 256
        ch = cs.build_chain(lam, V100, 8, K)
        s = mk._ChainStructure(V100, 8, K)
        P = mk._transition_matrix(lam, s, K)
        dense_from_band = np.zeros((K + 1, K + 1))
        for l in range(K + 1):
            w = ch.width[l]
            dense_from_band[l, ch.c[l]:ch.c[l] + w + 1] = ch.B[l, :w + 1]
        assert np.max(np.abs(dense_from_band - P)) < 1e-15


class TestX64Discipline:
    """S1: the grid kernel's build-time constants are baked into the
    trace, so the builder must run inside an enable_x64 scope — the
    PR 4 footgun (silent float32 truncation) is now a build error."""

    def test_build_outside_x64_raises(self):
        cs._build_grid_kernel.cache_clear()
        with pytest.raises(RuntimeError, match="enable_x64"):
            cs._build_grid_kernel(64, 16, 8)
        assert cs._build_grid_kernel.cache_len() == 0

    def test_every_band_path_output_is_float64(self):
        import jax
        import jax.numpy as jnp

        with jax.enable_x64(True):
            kernel = cs._build_grid_kernel(64, 16, 8)
            out = jax.eval_shape(
                kernel,
                jnp.zeros((2,), jnp.float64),
                jnp.zeros((2,), jnp.float64),
                jnp.zeros((2,), jnp.float64),
                jnp.zeros((2,), jnp.int32))
            bad = {k: v.dtype for k, v in out.items()
                   if v.dtype != jnp.float64}
            assert not bad, f"float64 dropped in: {bad}"

    def test_grid_solve_builds_inside_x64_and_stays_exact(self):
        """grid_solve (which owns the enable_x64 scope) must agree
        with the pure-NumPy float64 solver to near machine precision —
        any float32 intermediate on the band path would blow this
        tolerance by ~8 orders of magnitude."""
        cs._build_grid_kernel.cache_clear()
        lam = _lam(V100, 8, 0.9)
        out_j = cs.grid_solve([lam], [V100.alpha], [V100.tau0], [8],
                              256, method="jax")
        out_n = cs.grid_solve([lam], [V100.alpha], [V100.tau0], [8],
                              256, method="numpy")
        for k in out_j:
            # tail_mass is O(1e-23): summation-order noise alone moves
            # it at the ~1e-10 level, so it gets a slightly looser rel
            rel = 1e-6 if k == "tail_mass" else 1e-10
            assert out_j[k][0] == pytest.approx(out_n[k][0],
                                                rel=rel, abs=1e-300)
