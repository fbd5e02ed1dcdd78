"""Compile the simulator's main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a v5e that is described and not attached.  What it
refuses here (block shapes Mosaic cannot tile, programs that do not fit
the device) would otherwise surface only on the chip.  The compiles are
at real widths: the campaign's default chunk of 4096 points, the full
512-bin histogram and the 64-bin sketch.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and the test workers all import this file.  Off the chip the
superstep kernels run in Pallas interpret mode, which these tests turn
off for their own compiles.
"""
import os

import numpy as np
import pytest

from repro.core.analytic import LinearServiceModel
from repro.core.grid import GenGrid, SweepGrid
from repro.core.hist import SKETCH_BINS
from repro.kernels import superstep as ss

V100 = LinearServiceModel(alpha=0.1438, tau0=1.8874)
CHUNK = 4096            # campaign(chunk_size=...) default


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(one_chip, monkeypatch):
    """Shape-only stand-ins on the described chip, with the Pallas
    kernels lowered through Mosaic and the compile cache off (a compile
    for a described chip is written to the cache but cannot be read
    back without one)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core import gen_sweep, sweep

    monkeypatch.setattr(ss, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the kernel builders cache whole traced programs: clear them so a
    # kernel traced in interpret mode is never reused here, or after
    builders = (sweep._build_kernel, sweep._build_fleet_kernel,
                gen_sweep._build_gen_kernel)
    for build in builders:
        build.cache_clear()
    yield lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                         if not hasattr(a, "dtype")
                                         else a.dtype, sharding=one_chip)
    for build in builders:
        build.cache_clear()
    jax.config.update("jax_enable_compilation_cache", was)


def _paper_grid(n: int) -> SweepGrid:
    """Table 1's V100 law over rho in [0.1, 0.9] and four finite caps."""
    b = np.tile([4, 8, 16, 32], n // 4)
    rho = np.repeat(np.linspace(0.1, 0.9, n // 4), 4)
    return SweepGrid.from_points(rho * b / (V100.alpha * b + V100.tau0),
                                 V100.alpha, V100.tau0, b_max=b)


def _plan_hlo(plan, spec) -> str:
    import jax
    compiled = plan.kernel.lower(jax.tree.map(spec, plan.params),
                                 spec(plan.keys)).compile()
    return compiled.as_text()


@pytest.mark.parametrize("n_bins,sketch", [(512, False),
                                           (SKETCH_BINS, True)])
def test_hist_update_lowers_through_mosaic(for_chip, n_bins, sketch):
    """The fused histogram update, vmapped over the points of a chunk
    the way the kernels call it, at the widest per-step row (q_cap)."""
    import jax
    import jax.numpy as jnp

    rows, width = 32, 1024
    hists = (jnp.zeros((CHUNK, n_bins), jnp.int32),)
    if sketch:
        hists += (jnp.zeros((CHUNK, n_bins), jnp.float32),)
    lats = jax.ShapeDtypeStruct((CHUNK, rows, width), jnp.float32)
    inc = jax.ShapeDtypeStruct((CHUNK, rows, width), jnp.bool_)

    def update(h, lat, i):
        return jax.vmap(lambda h, lat, i: ss.hist_update(
            h, lat, i, n_bins=n_bins, backend="pallas",
            sketch=sketch))(h, lat, i)
    compiled = jax.jit(update).lower(
        jax.tree.map(for_chip, hists), for_chip(lats),
        for_chip(inc)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sweep_kernel_compiles_at_campaign_chunk(for_chip):
    """One whole sweep program at the campaign's default chunk width,
    with the fused superstep update in it."""
    from repro.core.sweep import sweep_plan

    plan = sweep_plan(_paper_grid(CHUNK), superstep_backend="pallas",
                      shard=1)
    assert "tpu_custom_call" in _plan_hlo(plan, for_chip)


def test_fleet_kernel_compiles_at_campaign_chunk(for_chip):
    """The k-replica routing kernel at a campaign chunk, JSQ routing
    over 1-8 replicas, with the fused superstep update in it."""
    from repro.core.grid import FleetGrid
    from repro.core.sweep import fleet_plan

    k = np.tile([1, 2, 4, 8], CHUNK // 4)
    lam = 0.7 * k * 16 / (V100.alpha * 16 + V100.tau0)
    g = FleetGrid.from_points(lam, V100.alpha, V100.tau0, k=k,
                              routing="jsq", b_max=16)
    plan = fleet_plan(g, superstep_backend="pallas", shard=1)
    assert "tpu_custom_call" in _plan_hlo(plan, for_chip)


def test_gen_kernel_compiles_with_fused_compaction(for_chip):
    """The generate kernel at a campaign chunk: both fused ops, the
    histogram update and ``fifo_compact`` at the kernel's own FIFO
    buffer length, lower through Mosaic."""
    from repro.core.gen_sweep import gen_plan

    g = GenGrid.from_points(np.linspace(0.01, 0.1, CHUNK), 0.14, 1.9,
                            0.002, 0.9, prompt_len=64, gen_tokens=32,
                            max_active=16, discipline="continuous")
    hlo = _plan_hlo(gen_plan(g, superstep_backend="pallas", shard=1),
                    for_chip)
    assert hlo.count("tpu_custom_call") >= 2


def test_campaign_fold_compiles_under_x64(for_chip):
    """The on-device fold keeps f64 sums and i64 counters; the v5e has
    no 64-bit unit, so XLA emulates them — this pins that it does."""
    import jax
    import jax.numpy as jnp

    from repro.core import campaign as cp

    with jax.enable_x64(True):
        acc = jax.tree.map(for_chip, cp._init_acc(512, cp.DEFAULT_TOP_K))
        i32, f32 = jnp.int32, jnp.float32
        chunk = {k: jax.ShapeDtypeStruct((CHUNK,), f32)
                 for k in ("mean_latency", "utilization", "mean_batch",
                           "lam", "lat_bm_m2")}
        chunk.update({k: jax.ShapeDtypeStruct((CHUNK,), i32)
                      for k in ("n_jobs", "dropped", "batches",
                                "lat_bm_n")})
        chunk["hist"] = jax.ShapeDtypeStruct((CHUNK, 512), i32)
        fold = cp._build_fold(CHUNK, 512, cp.DEFAULT_TOP_K, False, False,
                              True, True)
        compiled = fold.lower(
            acc, jax.tree.map(for_chip, chunk),
            for_chip(jax.ShapeDtypeStruct((CHUNK,), jnp.int64)),
            for_chip(jax.ShapeDtypeStruct((), jnp.int64))).compile()
    assert compiled.memory_analysis() is not None


def test_chain_solver_level_scan_compiles_under_x64(for_chip):
    """The exact chain's float64 level scan at a (λ, b_max) surface's
    truncation and band shapes."""
    import jax
    import jax.numpy as jnp

    from repro.core import chain_solver as cs

    with jax.enable_x64(True):
        cells = jax.ShapeDtypeStruct((64,), jnp.float64)
        kernel = cs._build_grid_kernel(256, 96, 32)
        compiled = kernel.lower(
            for_chip(cells), for_chip(cells), for_chip(cells),
            for_chip(jax.ShapeDtypeStruct((64,), jnp.int32))).compile()
    assert compiled.memory_analysis() is not None
    cs._build_grid_kernel.cache_clear()


def test_expert_layer_compiles_to_named_grouped_matmuls(for_chip):
    """DeepSeek-V2-Lite's expert layer at its published widths, holding
    8 of its 64 routed experts, at a decode step of 64 tokens: the
    three grouped matmuls are Mosaic calls named ``ragged-dot``, the
    name by which ``bench/metrics/moe_roofline.dsv2.py`` finds them in
    the trace."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import moe

    cfg = get_config("deepseek-v2-lite-16b")
    share = dataclasses.replace(cfg.moe, held_experts=8)
    params = jax.eval_shape(lambda: moe.init_moe(
        jax.random.PRNGKey(0), cfg.d_model, share, "swiglu", jnp.bfloat16))
    x = jax.ShapeDtypeStruct((64, 1, cfg.d_model), jnp.bfloat16)
    hlo = jax.jit(lambda p, x: moe.apply_moe(p, share, x, "swiglu")[::2]
                  ).lower(jax.tree.map(for_chip, params),
                          for_chip(x)).compile().as_text()
    calls = re.findall(r"%(ragged-dot[\w.-]*) = .*custom_call_target="
                       r"\"tpu_custom_call\"", hlo)
    assert len([c for c in calls if "metadata" not in c]) == 3, calls
