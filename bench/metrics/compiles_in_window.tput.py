"""Programs compiled or loaded from the compile cache inside the
window (``jax.compile`` spans of the program's span log, aligned by
the ``bench.batch``/``engine.batch`` pairs).  A warm window makes
none."""
from bench import program_spans


def read(ctx):
    return program_spans.compiles(ctx, "bench.batch", "engine.batch",
                                  "batches")
