"""The serving engine's input preparation per batch, in ms: the
window's ``engine.prepare`` spans (``_make_batch`` and the wait for its
inputs to reach the device, which ``run_batch``'s clock leaves out)
over its ``engine.batch`` spans, from the program's spans on the
trace's clock (``program_spans``)."""
from bench import program_spans


def read(ctx):
    spans = program_spans.window_spans(ctx, "bench.batch", "engine.batch",
                                       "batches")
    if spans is None:
        return None
    ns = {name: [s.ns for s in spans if s.name == name]
          for name in ("engine.batch", "engine.prepare", "engine.run")}
    if not ns["engine.batch"]:
        return None
    ctx["log"]("engine: " + ", ".join(
        f"{name} {len(v)} spans {sum(v) * 1e-6} ms"
        for name, v in ns.items()))
    return sum(ns["engine.prepare"]) * 1e-6 / len(ns["engine.batch"])
