"""Fused superstep kernel (repro.kernels.superstep): backend
resolution, bitwise lax/pallas parity on all three sweep kernels (the
pallas path runs in interpret mode on CPU), the streaming-sketch mode,
the split-dispatch pinned-caps contract, and the kernel-cache keying
the backend flags ride on.

Parity is *bitwise* by design: histogram counts are integer
accumulations in both backends, and the fused FIFO compaction moves
the same values the lax pad+slice sequence does.
"""
import numpy as np
import pytest

from repro.core import engine
from repro.core.analytic import LinearServiceModel
from repro.core.continuous_sim import GenServiceModel
from repro.core.gen_sweep import GenGrid, gen_caps, gen_sweep
from repro.core.grid import FleetGrid, SweepGrid
from repro.core.hist import SKETCH_BINS
from repro.core.sweep import (fleet_caps, fleet_sweep, sweep,
                              sweep_caps)
from repro.kernels import superstep as ss

V100 = LinearServiceModel(alpha=0.1438, tau0=1.8874)
GMODEL = GenServiceModel(alpha_decode=0.14, tau0_decode=1.9,
                         alpha_prefill=0.002, tau0_prefill=0.9)


def _sweep_grid():
    return SweepGrid.from_product([1.0, 2.5], [V100.alpha],
                                  [V100.tau0], b_maxes=(8,))


class TestResolveBackend:
    def test_explicit_wins(self, monkeypatch):
        monkeypatch.setenv(ss.ENV_VAR, "pallas")
        assert ss.resolve_backend("lax") == "lax"
        assert ss.resolve_backend("pallas") == "pallas"

    def test_env_overrides_auto(self, monkeypatch):
        monkeypatch.setenv(ss.ENV_VAR, "lax")
        assert ss.resolve_backend(None) == "lax"
        monkeypatch.setenv(ss.ENV_VAR, "pallas")
        assert ss.resolve_backend("auto") == "pallas"

    def test_auto_is_bin_count_aware_on_cpu(self, monkeypatch):
        # auto follows the platform alone: Mosaic on a TPU, and lax
        # elsewhere, where the fused kernel could only be interpreted
        import jax
        monkeypatch.delenv(ss.ENV_VAR, raising=False)
        want = "pallas" if jax.default_backend() == "tpu" else "lax"
        assert ss.resolve_backend(None) == want
        assert ss.resolve_backend("auto") == want

    def test_unknown_backend_raises(self, monkeypatch):
        with pytest.raises(ValueError, match="unknown superstep"):
            ss.resolve_backend("nope")
        monkeypatch.setenv(ss.ENV_VAR, "bogus")
        with pytest.raises(ValueError, match="unknown superstep"):
            ss.resolve_backend(None)


class TestFusedOps:
    """The two fused ops against their lax references, standalone."""

    def test_hist_update_bitwise(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        lats = jnp.asarray(rng.lognormal(1.0, 1.5, (32, 16)),
                           dtype=jnp.float32)
        inc = jnp.asarray(rng.random((32, 16)) < 0.7)
        h0 = (jnp.zeros((512,), jnp.int32),)

        def run(backend):
            return jax.jit(lambda h, l, i: ss.hist_update(
                h, l, i, n_bins=512, backend=backend))(h0, lats, inc)
        out_l, out_p = run("lax"), run("pallas")
        assert np.array_equal(out_l[0], out_p[0])
        assert int(np.sum(out_l[0])) == int(np.sum(np.asarray(inc)))

    def test_hist_update_sketch_sums(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        lats = jnp.asarray(rng.lognormal(0.5, 1.0, (16, 8)),
                           dtype=jnp.float32)
        inc = jnp.asarray(rng.random((16, 8)) < 0.5)
        h0 = (jnp.zeros((SKETCH_BINS,), jnp.int32),
              jnp.zeros((SKETCH_BINS,), jnp.float32))

        def run(backend):
            return jax.jit(lambda h, l, i: ss.hist_update(
                h, l, i, n_bins=SKETCH_BINS, backend=backend,
                sketch=True))(h0, lats, inc)
        out_l, out_p = run("lax"), run("pallas")
        assert np.array_equal(out_l[0], out_p[0])       # counts: bitwise
        np.testing.assert_allclose(out_l[1], out_p[1], rtol=1e-6)
        # per-bin sums integrate exactly the counted latencies
        want = float(np.sum(np.where(np.asarray(inc),
                                     np.asarray(lats), 0.0)))
        assert float(np.sum(out_l[1])) == pytest.approx(want, rel=1e-6)

    def test_fifo_compact_matches_pop_shift(self):
        import jax
        import jax.numpy as jnp

        buf = jnp.asarray(np.arange(10, 26, dtype=np.float32))
        for k in (0, 3, 16):
            now = jnp.float32(2.5)
            kk = jnp.int32(k)
            out_l = jax.jit(lambda b, k_, n: ss.fifo_compact(
                b, k_, n, backend="lax"))(buf, kk, now)
            out_p = jax.jit(lambda b, k_, n: ss.fifo_compact(
                b, k_, n, backend="pallas"))(buf, kk, now)
            assert np.array_equal(out_l, out_p), k
        with pytest.raises(ValueError, match="unresolved"):
            ss.fifo_compact(buf, jnp.int32(1), jnp.float32(0.0),
                            backend="auto")


class TestBackendParity:
    """Whole-kernel dispatches, lax vs pallas, bitwise."""

    def test_sweep_parity(self):
        g = _sweep_grid()
        kw = dict(n_batches=256, q_cap=64, seed=3)
        rl = sweep(g, superstep_backend="lax", **kw)
        rp = sweep(g, superstep_backend="pallas", **kw)
        assert np.array_equal(rl.hist, rp.hist)
        for f in ("mean_latency", "n_jobs", "latency_p99"):
            assert np.array_equal(getattr(rl, f), getattr(rp, f)), f
        assert rl.hist_sums is None

    def test_sweep_sketch_parity_and_totals(self):
        g = _sweep_grid()
        kw = dict(n_batches=256, q_cap=64, seed=3)
        full = sweep(g, superstep_backend="lax", **kw)
        rl = sweep(g, sketch=True, superstep_backend="lax", **kw)
        rp = sweep(g, sketch=True, superstep_backend="pallas", **kw)
        assert rl.hist.shape == (len(g), SKETCH_BINS)
        assert np.array_equal(rl.hist, rp.hist)
        assert rl.hist_sums is not None and rl.hist_sums.shape == \
            rl.hist.shape
        # the sketch re-bins the same measured jobs, never drops any
        assert np.array_equal(rl.hist.sum(axis=1),
                              full.hist.sum(axis=1))
        # sketch edges flow into the percentile reconstruction
        assert np.array_equal(rl.hist_bin_edges, rp.hist_bin_edges)
        assert len(rl.hist_bin_edges) == SKETCH_BINS + 1

    def test_gen_parity(self):
        g = GenGrid.from_product([0.05, 0.1], GMODEL,
                                 prompt_lens=(128,), gen_tokens=(16,),
                                 max_actives=(8,),
                                 disciplines=("continuous",))
        kw = dict(n_steps=256, q_cap=64, a_cap=16, seed=5)
        rl = gen_sweep(g, superstep_backend="lax", **kw)
        rp = gen_sweep(g, superstep_backend="pallas", **kw)
        assert np.array_equal(rl.hist, rp.hist)
        for f in ("mean_latency", "n_jobs", "mean_batch"):
            assert np.array_equal(getattr(rl, f), getattr(rp, f)), f

    def test_fleet_parity_with_thinning(self):
        g = FleetGrid.from_points([2.0, 4.0], V100.alpha, V100.tau0,
                                  k=[2, 2])
        kw = dict(n_steps=256, q_cap=64, a_cap=16, hist_every=2,
                  seed=7)
        rl = fleet_sweep(g, superstep_backend="lax", **kw)
        rp = fleet_sweep(g, superstep_backend="pallas", **kw)
        assert np.array_equal(rl.hist, rp.hist)
        for f in ("mean_latency", "n_jobs"):
            assert np.array_equal(getattr(rl, f), getattr(rp, f)), f


class TestSplitCapsContract:
    """key_offset != 0 (a chunk of a split campaign) must pin every
    grid-derived capacity — PR 6 documented the footgun, this enforces
    it (and the *_caps helpers make pinning one line)."""

    def test_sweep_split_requires_pinned_caps(self):
        g = _sweep_grid()
        with pytest.raises(ValueError, match="sweep_caps"):
            sweep(g.take(slice(1, None)), n_batches=64, seed=0,
                  key_offset=1)

    def test_gen_split_requires_pinned_caps(self):
        g = GenGrid.from_product([0.05, 0.1], GMODEL,
                                 prompt_lens=(128,), gen_tokens=(16,),
                                 max_actives=(8,),
                                 disciplines=("continuous",))
        with pytest.raises(ValueError, match="gen_caps"):
            gen_sweep(g.take(slice(1, None)), n_steps=64, seed=0,
                      key_offset=1)

    def test_fleet_split_requires_pinned_caps(self):
        g = FleetGrid.from_points([2.0, 4.0], V100.alpha, V100.tau0,
                                  k=[2, 2])
        with pytest.raises(ValueError, match="fleet_caps"):
            fleet_sweep(g.take(slice(1, None)), n_steps=64, seed=0,
                        key_offset=1)

    def test_caps_pinned_split_is_bitwise_whole(self):
        g = SweepGrid.from_product([1.0, 2.0, 3.0], [V100.alpha],
                                   [V100.tau0], b_maxes=(8,))
        caps = sweep_caps(g)
        assert set(caps) == {"q_cap", "a_cap"}
        kw = dict(n_batches=256, seed=11, **caps)
        full = sweep(g, **kw)
        a = sweep(g.take(slice(0, 2)), **kw)
        b = sweep(g.take(slice(2, None)), key_offset=2, **kw)
        for f in ("mean_latency", "n_jobs"):
            assert np.array_equal(
                getattr(full, f),
                np.concatenate([getattr(a, f), getattr(b, f)])), f
        assert np.array_equal(full.hist,
                              np.concatenate([a.hist, b.hist]))

    def test_caps_helpers_cover_loss_grids(self):
        g = SweepGrid.from_product([1.0], [V100.alpha], [V100.tau0],
                                   b_maxes=(8,), q_maxes=(16,),
                                   retry_rates=(0.1,))
        caps = sweep_caps(g)
        assert "r_cap" in caps
        fg = FleetGrid.from_points([2.0], V100.alpha, V100.tau0, k=[2])
        assert set(fleet_caps(fg)) == {"q_cap"}
        gg = GenGrid.from_product([0.05], GMODEL, prompt_lens=(64,),
                                  gen_tokens=(8,), max_actives=(8,),
                                  disciplines=("continuous",))
        assert set(gen_caps(gg)) == {"q_cap", "a_cap"}


class TestKernelCacheKeys:
    """S4: the backend/sketch flags are kernel-builder arguments, so
    the LRU can never serve a kernel compiled for the other
    configuration."""

    def test_backend_and_sketch_get_distinct_entries(self):
        from repro.core import sweep as sweep_mod

        g = _sweep_grid()
        sweep_mod._build_kernel.cache_clear()
        kw = dict(n_batches=64, q_cap=32, seed=0)
        sweep(g, superstep_backend="lax", **kw)
        assert sweep_mod._build_kernel.cache_len() == 1
        sweep(g, superstep_backend="pallas", **kw)
        assert sweep_mod._build_kernel.cache_len() == 2
        sweep(g, superstep_backend="pallas", sketch=True, **kw)
        assert sweep_mod._build_kernel.cache_len() == 3
        # same config again: served from cache, no rebuild
        builds = sweep_mod._build_kernel.builds
        sweep(g, superstep_backend="lax", **kw)
        assert sweep_mod._build_kernel.builds == builds
        # both backends present in the key tuples
        flat = [str(k) for k in sweep_mod._build_kernel.cache_keys()]
        assert any("pallas" in k for k in flat)
        assert any("'lax'" in k for k in flat)

    def test_lru_no_key_collision_on_eviction(self):
        """Direct _KernelCache exercise: near-identical keys differing
        only in the backend slot stay distinct through eviction."""
        @engine.kernel_cache(maxsize=2)
        def build(shape, backend):
            return (shape, backend, object())

        a = build(64, "lax")
        b = build(64, "pallas")
        assert a is not b
        assert build(64, "lax") is a              # hit refreshes LRU
        build(128, "lax")                         # evicts (64, pallas)
        assert build.evictions == 1
        assert build.cache_len() == 2
        b2 = build(64, "pallas")                  # rebuilt, not stale
        assert b2 is not b and build.builds == 4
