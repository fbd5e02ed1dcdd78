"""The operation and byte counts, against sums written out by hand."""
import json

import pytest

from bench.counts import qwen, superstep
from bench.run import BENCH

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def test_superstep_least_time():
    lt = superstep.least_time(jobs=1000, points=8, calls_per_point=2,
                              n_bins=512, peaks=PEAKS)
    assert lt["bytes"] == 4 * 1000 + 2 * 4 * 512 * 8 * 2
    assert lt["ops"] == 4 * 1000
    assert lt["bound"] == "memory"
    assert lt["least_s"] == pytest.approx(lt["bytes"] / 819e9)


def test_qwen_flops_by_hand():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "num_hidden_layers": 2, "num_attention_heads": 2,
           "num_key_value_heads": 1, "vocab_size": 10}
    # per token per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3x8x16
    dense = 2 * 2 * (64 + 32 + 32 + 64 + 384)
    head = 2 * 8 * 10
    attn = lambda ctx: 2 * 2 * 2 * 2 * 4 * ctx  # noqa: E731
    assert qwen.prefill(cfg, 3) == pytest.approx(
        3 * dense + attn(1 + 2 + 3) + head)
    assert qwen.decode(cfg, 3, 2) == pytest.approx(
        2 * (dense + head) + attn(4 + 5))
    assert qwen.request(cfg, 3, 2) == pytest.approx(
        qwen.prefill(cfg, 3) + qwen.decode(cfg, 3, 2))


def test_peaks_table_is_keyed_by_device_kind():
    table = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
