"""Model FLOPs of the requests served in the traced window
(``counts/qwen.request``: prefill and cached decode, attention and the
LM head included) over the window's length times the chips' bf16
peak, in percent."""


def read(ctx):
    flops = ctx["counters"]["model_flops"]
    red, peaks = ctx["trace"], ctx["peaks"]
    if flops <= 0:
        return None
    chips = len(red["devices"])
    return 100.0 * flops / (red["window_s"] * chips
                            * peaks["bf16_flops_per_s"])
