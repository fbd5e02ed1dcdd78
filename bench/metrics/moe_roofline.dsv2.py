"""The expert layer's grouped matmuls' share of their roofline, in
percent: the least time of the window's calls
(``counts/deepseek_v2.moe_least_time`` over the routing counters of
each of the window's batches, ``expert_slots``: in each layer-step the
larger of the routed slots' FLOPs over the bf16 peak and the bytes over
the memory bandwidth, the held experts that received a slot read once)
over the device time of
the ``ragged-dot`` Mosaic calls that ``jax.lax.ragged_dot`` becomes,
found in the trace by their HLO instruction name (the calls that
compute each call's group metadata are left out of both).  Nothing to
read where the program has no such calls."""
from bench import trace
from bench.counts import deepseek_v2 as counts

KERNEL = "ragged-dot"
METADATA = "ragged-dot-metadata"


def _head(name: str) -> str:
    """An op event's instruction name: the head of its HLO text."""
    return name.lstrip("%").split(" ", 1)[0]


def read(ctx):
    slots = ctx["counters"].get("expert_slots")
    if not slots:
        return None
    least = sum(counts.moe_least_time(ctx["cfg"], s, ctx["peaks"])[
        "least_s"] for s in slots)
    t, n = trace.summed(ctx["trace"], "ops", lambda name: _head(
        name).startswith(KERNEL) and not _head(name).startswith(METADATA))
    if n == 0:
        return None
    ctx["log"](f"moe: {n} grouped-matmul calls, {t} s on the device, "
               f"least {least} s")
    return 100.0 * least / t
