"""The DeepSeek-V2 cell at a size a CPU holds: its reference against the
program, its control, planted faults, and its counts by hand."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.counts import deepseek_v2 as counts
from bench.readings_dsv2 import read_seed
from bench.refs import deepseek_v2 as ref
from bench.runners import serve_dsv2
from bench.tests.small import cell

NAME = "dsv2lite.saturated"
# one batch of 8 requests, each sampled, of 32 generated tokens: 256
# served tokens compared, where small.py's traffic compares 24
TRAFFIC = {"max_batch": 8, "gen_tokens": 32, "sample_requests": 8}
# this cell's own limit at that size (small.py's 0.009 is qwen's): over
# 20 seeds of one batch on the CPU the sound runs read 0.0002-0.0212,
# the float8 control 0.0965-0.209, a wrong held offset 0.0577-0.587 and
# a decode step that skips its row write 0.605-0.934; 0.035 is the
# geometric mean of the sound runs' highest and the offset's lowest
LIMIT = 0.035
SEEDS = [2 ** 31 + 1, 2 ** 31 + 2, 2 ** 31 + 3]


def _cell():
    c = cell(NAME, **TRAFFIC)
    c["limits"] = {"logit_gap_max": LIMIT}
    return c


def test_reference_matches_the_program_forward():
    from repro.models import build

    cfg = dict(cell(NAME)["cfg"], torch_dtype="float32")
    w = ref.make_weights(cfg, 3, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = build(serve_dsv2.model_config(cfg)).forward(
            w, {"tokens": toks})
    want = ref.forward(cfg, w, toks, 0)
    # float32 at highest precision: the two orders of the same sums
    # differ by ~1e-6 of the logits' size
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_control_is_not_correct():
    c = _cell()
    # a window of no length runs one batch: the same requests everywhere
    row = read_seed(c, 2 ** 31 + 17, 0.0, jax.devices()[:1])
    assert row["logit_gap_max"] <= LIMIT, row
    assert row["control.logit_gap_max"] > LIMIT, row
    assert 0.0 <= row["routing.differ_share"] < 1.0
    assert row["routed_slots"] > 0


def _run(seed):
    # a window of no length runs one batch: the same requests everywhere
    c = _cell()
    return run.run_cell(c, seed, 0.0, False,
                        jax.devices()[:c["cell"]["chips"]])


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(seed):
    res = _run(seed)
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("seed", SEEDS)
def test_wrong_held_offset(monkeypatch, seed):
    orig = serve_dsv2.model_config

    def shifted(cfg):
        m = orig(cfg)
        return dataclasses.replace(m, moe=dataclasses.replace(
            m.moe, expert_offset=m.moe.expert_offset + m.moe.held()))
    monkeypatch.setattr(serve_dsv2, "model_config", shifted)
    assert not _run(seed)["correct"]


def test_one_expert_left_out(monkeypatch):
    """A weak fault at this size: one held expert of 8 (of 64) in one MoE
    layer moves about as many logits as bfloat16 does, and over 20 seeds
    it read 0.0036-0.0947, above the limit on 9. A check by served
    tokens sees it only where it changes one, so it is asked to show on
    one of the sound runs' seeds."""
    orig = jax.lax.ragged_dot
    d = _cell()["cfg"]["hidden_size"]

    def without_first(lhs, rhs, group_sizes, **kw):
        out = orig(lhs, rhs, group_sizes, **kw)
        if rhs.shape[-1] != d:
            return out                  # not the down projection
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < group_sizes[0], 0.0, out).astype(out.dtype)
    monkeypatch.setattr(jax.lax, "ragged_dot", without_first)
    assert not all(_run(seed)["correct"] for seed in SEEDS)


@pytest.mark.parametrize("seed", SEEDS)
def test_decode_step_skips_the_row_write(monkeypatch, seed):
    from repro.models import attention
    monkeypatch.setattr(attention, "write_rows",
                        lambda cache, new, lengths, layer=None: cache)
    assert not _run(seed)["correct"]


def test_counts_by_hand():
    cfg = {"hidden_size": 4, "num_attention_heads": 2, "kv_lora_rank": 3,
           "qk_nope_head_dim": 2, "qk_rope_head_dim": 1, "v_head_dim": 2,
           "intermediate_size": 5, "moe_intermediate_size": 3,
           "router_width": 8, "n_routed_experts": 2, "held_expert_offset": 0,
           "n_shared_experts": 2, "num_experts_per_tok": 2,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "vocab_size": 7}
    # a token, one layer: q 4x2x3, latent 4x3, rotary key 4x1, out 2x2x4
    proj = 24 + 12 + 4 + 16
    expand = 3 * 2 * (2 + 2)            # prefill: latent -> k_nope and v
    absorb = 2 * 2 * 3 + 2 * 3 * 2      # decode: q into the latent, out
    # FFNs a token: dense 3x4x5 once; 2 MoE layers of shared 3x4x6 and a
    # router 4x8
    ffn = 60 + 2 * (72 + 32)
    head = 4 * 7
    # prefill of 3 tokens: causal contexts 1+2+3, scores 2x3 + sum 2x2
    want_pre = 2 * (3 * (3 * (proj + expand)) + 3 * 6 * (6 + 4)
                    + 3 * ffn + head)
    assert counts.prefill(cfg, 3) == pytest.approx(want_pre)
    # 2 decode steps over 4 and 5 positions: scores 2x(3+1), sum 2x3
    want_dec = 2 * (3 * (2 * (proj + absorb)) + 3 * 9 * (8 + 6)
                    + 2 * (ffn + head))
    assert counts.decode(cfg, 3, 2) == pytest.approx(want_dec)
    assert counts.request(cfg, 3, 2) == pytest.approx(want_pre + want_dec)
    assert counts.routed(cfg, [[5, 1]]) == pytest.approx(2 * 3 * 4 * 3 * 6)

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 1000.0}
    # two layer-steps: one expert with 2 slots; none
    lt = counts.moe_least_time(cfg, [[[2, 0], [0, 0]]], peaks)
    wbytes = 3 * 4 * 3 * 2              # one expert's three projections
    act = 2 * (2 * 4 * 2 + 2 * 3 * 4 + 3 * 2 + 4 * 4)
    assert lt["bytes"] == wbytes + act
    assert lt["flops"] == 2 * 3 * 4 * 3 * 2
    assert lt["least_s"] == pytest.approx(max(lt["flops"] / 100.0,
                                              lt["bytes"] / 1000.0))
