"""Cells at a size a CPU test can hold, built like ``run.load_cell``."""
import copy

from bench.run import load_cell

QWEN_TINY = {"hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 4, "vocab_size": 512}
GRID_TINY = {"levels": 8, "replicas": 4, "chunk": 64, "n_batches": 64}
SERVE_TINY = {"max_batch": 4, "prompt_tokens": 16, "gen_tokens": 8,
              "sample_requests": 3, "trace_seconds": 1}


def cell(name: str, **traffic) -> dict:
    """A cell of BENCHMARK.json at CPU size."""
    c = copy.deepcopy(load_cell(name))
    if c["traffic"]["runner"] == "campaign":
        c["traffic"].update(GRID_TINY)
        # a handful of replicas of short runs: the start-up transient of
        # the highest loads weighs more than at the cell's size
        c["limits"] = dict(c["limits"], ew_chi2=4.0)
    else:
        c["cfg"].update(QWEN_TINY)
        c["traffic"].update(SERVE_TINY)
        # the tiny model's logits spread ~4x less than the real one's:
        # over 12 seeds of one batch, sound runs read at most 0.0048
        # and the float8 control at least 0.0125
        c["limits"] = {"logit_gap_max": 0.009}
    c["traffic"].update(traffic)
    return c
