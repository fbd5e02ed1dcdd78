"""The expert layer and DeepSeek-V2's latent attention against plain
references, on seeded random weights at a size the CPU holds.

- the chip's share of the experts ties to the model: the routed parts of
  all the shares, with the shared experts counted once, add up to the
  uncut layer of the plain reference (``bench/refs/deepseek_v2.py``);
- the layer is dropless: a routing skewed onto one expert, beyond what a
  capacity of 1.25× its mean share would hold, is served whole; under a
  multi-device mesh it keeps GShard's capacity dispatch, which with room
  for every slot gives the same numbers;
- prefill and then decoding through the latent cache agree with the
  reference's full forward pass, on logits;
- YaRN frequencies and the softmax scale equal the published formulas
  written out by hand, and models without rope scaling keep theirs.

Tolerances are for float32 at ``highest`` matmul precision, where two
orders of the same sums differ by ~1e-6 of their size.
"""
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.refs import deepseek_v2 as ref  # noqa: E402
from bench.runners.serve_dsv2 import model_config  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import MoEConfig  # noqa: E402
from repro.models import attention, build, moe, rope  # noqa: E402

D, FE, E, K = 32, 48, 16, 6

# the published configuration's keys at a size the CPU holds: every
# kind of layer (one dense, MoE after it), MLA with YaRN, 8 of 64
# experts held from expert 8 on
PUBLISHED = json.loads(
    (ROOT / "bench/configs/deepseek-v2-lite-ep8.json").read_text())
SMALL = {**PUBLISHED, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 4, "vocab_size": 256, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
         "moe_intermediate_size": 32, "n_routed_experts": 8,
         "held_expert_offset": 8, "torch_dtype": "float32"}


def _layer(key, held=E):
    ks = jax.random.split(key, 7)
    n = lambda k, shape, fan: (jax.random.normal(k, shape)  # noqa: E731
                               * fan ** -0.5)
    return {"router": n(ks[0], (D, E), D),
            "w_gate": n(ks[1], (held, D, FE), D),
            "w_up": n(ks[2], (held, D, FE), D),
            "w_down": n(ks[3], (held, FE, D), FE),
            "shared": {"w_gate": n(ks[4], (D, 2 * FE), D),
                       "w_up": n(ks[5], (D, 2 * FE), D),
                       "w_down": n(ks[6], (2 * FE, D), 2 * FE)}}


def _ref_cfg(held, off=0):
    return {"hidden_size": D, "moe_intermediate_size": FE,
            "router_width": E, "n_routed_experts": held,
            "held_expert_offset": off, "n_shared_experts": 2,
            "num_experts_per_tok": K, **{k: 0 for k in (
                "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "num_hidden_layers", "first_k_dense_replace",
                "vocab_size")}}


def _shared(p, x):
    sh = p["shared"]
    return (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_down"]


def test_the_shares_add_up_to_the_uncut_layer():
    p = _layer(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 20, D))
    shares, held = 8, E // 8
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.experts(_ref_cfg(E), p, x)
        total = _shared(p, x)
        for s in range(shares):
            part = {k: v[s * held:(s + 1) * held]
                    for k, v in p.items() if k.startswith("w_")}
            part["router"] = p["router"]
            cfg = MoEConfig(num_experts=E, top_k=K, d_expert=FE,
                            held_experts=held, expert_offset=s * held)
            y, _, slots = moe.apply_moe(part, cfg, x, "swiglu")
            assert slots.shape == (held,)
            total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)


def test_a_skewed_routing_is_served_whole():
    p = _layer(jax.random.PRNGKey(2))
    u = jax.random.normal(jax.random.PRNGKey(3), (D,))
    u = u / jnp.linalg.norm(u)
    # every token leans towards u, and expert 0's router column is u:
    # expert 0 is chosen by (nearly) every token
    p["router"] = p["router"].at[:, 0].set(8.0 * u)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, D)) + 2.0 * u
    cfg = MoEConfig(num_experts=E, top_k=K, d_expert=FE,
                    num_shared_experts=2, d_shared=FE)
    with jax.default_matmul_precision("highest"):
        y, _, slots = moe.apply_moe(p, cfg, x, "swiglu")
        want, _ = ref.experts(_ref_cfg(E), p, x)
    t = x.shape[0] * x.shape[1]
    mean = t * K / E
    assert int(slots[0]) > 2 * mean
    # a GShard capacity of 1.25x the mean share would have dropped slots
    assert int(slots[0]) > int(mean * 1.25) + 1
    assert int(jnp.sum(slots)) == t * K
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_under_a_mesh_the_layer_keeps_capacity_dispatch(monkeypatch):
    p = _layer(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, D))
    # room for every slot: an expert takes a token at most once
    cfg = MoEConfig(num_experts=E, top_k=K, d_expert=FE,
                    num_shared_experts=2, d_shared=FE,
                    capacity_factor=E / K)
    layer = jax.make_jaxpr(lambda p, x: moe.apply_moe(p, cfg, x, "swiglu"))
    assert "ragged_dot" in str(layer(p, x))
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh):
        assert moe.experts_may_be_sharded()
        assert "ragged_dot" not in str(layer(p, x))
    with jax.default_matmul_precision("highest"):
        y, aux, slots = moe.apply_moe(p, cfg, x, "swiglu")
        monkeypatch.setattr(moe, "experts_may_be_sharded", lambda: True)
        yc, auxc, slotsc = moe.apply_moe(p, cfg, x, "swiglu")
        share = dataclasses.replace(cfg, held_experts=E // 2)
        with pytest.raises(ValueError):
            moe.apply_moe(p, share, x, "swiglu")
    np.testing.assert_allclose(np.asarray(yc), np.asarray(y), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(slotsc), np.asarray(slots))
    assert float(auxc) == pytest.approx(float(aux), rel=1e-6)


def test_prefill_then_decode_matches_the_reference_forward():
    mcfg = model_config(dict(SMALL, name="small"))
    assert mcfg.moe.held() == 8 and mcfg.moe.expert_offset == 8
    w = ref.make_weights(SMALL, 5, dtype=jnp.float32)
    bundle = build(mcfg)
    b, s, steps = 2, 16, 8
    toks = jnp.asarray(np.random.default_rng(6).integers(
        0, SMALL["vocab_size"], (b, s + steps)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(SMALL, w, toks, s - 1)
        lg, cache, slots = jax.jit(lambda w, t: bundle.prefill(
            w, {"tokens": t}, s + steps, expert_slots=True))(w, toks[:, :s])
        got = [lg[:, 0]]
        step = jax.jit(lambda w, t, c, n: bundle.decode_step(
            w, t, c, n, expert_slots=True))
        lengths = jnp.full((b,), s, jnp.int32)
        for i in range(steps):
            lg, cache, sl = step(w, toks[:, s + i:s + i + 1], cache,
                                 lengths + i)
            got.append(lg[:, 0])
            assert sl.shape == (2, 8)        # MoE layers, held experts
    assert slots.shape == (2, 8) and int(jnp.sum(slots)) <= b * s * 6
    got = jnp.stack(got, axis=1)
    assert got.shape == want[:, :steps + 1].shape
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, :steps + 1]),
                               rtol=1e-4, atol=1e-4)


def test_param_counts_state_the_share_and_the_published_model():
    pc = model_config(PUBLISHED).param_counts()
    w = jax.eval_shape(lambda: ref.make_weights(PUBLISHED, 0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(w))
    assert held == 3_110_989_312
    # the count leaves out the norms' scales: 27 × (2 × 2048 + 512) + 2048
    assert pc["total"] == held - (27 * (2 * 2048 + 512) + 2048)
    # the published model: 15.7B, of which 2.4B active a token besides
    # the input embedding
    assert pc["published"] == pytest.approx(15.7e9, rel=0.01)
    assert pc["active"] - 102400 * 2048 == pytest.approx(2.4e9, rel=0.03)


def test_yarn_frequencies_and_scale_by_hand():
    cfg = get_config("deepseek-v2-lite-16b")
    rs = cfg.rope_scaling
    dim, base, factor = 64, 10000.0, 40.0
    # correction dims: d·ln(4096 / (β·2π)) / (2·ln θ), floored / ceiled
    low = math.floor(dim * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(dim * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    want = []
    for i in range(dim // 2):
        extra = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    got = rope.rope_freqs(dim, base, scaling=rs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(SMALL | {
        "qk_rope_head_dim": 64})), want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert rope.yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert m * m == pytest.approx(1.5897, abs=1e-4)
    assert rope.rope_mscale(rs) == 1.0
    assert attention.mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


def test_no_rope_scaling_keeps_the_plain_frequencies():
    cfg = get_config("deepseek-v2-lite-16b")
    plain = dataclasses.replace(cfg, rope_scaling=None)
    assert attention.mla_scale(plain) == 192 ** -0.5
    inv = rope.rope_freqs(64, 1e6)
    want = 1.0 / (1e6 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64))
    np.testing.assert_array_equal(np.asarray(inv), np.asarray(want))
