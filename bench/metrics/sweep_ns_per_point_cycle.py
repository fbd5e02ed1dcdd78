"""Device time of the sweep kernel's program per simulated point-cycle
(one service completion of one grid point), in ns.  The program is
``jit(vmap(run_point))`` of ``core/sweep.py``, found by its module
name; each run of it on a chip simulates that chip's share of a chunk
for the traffic's cycles, so the time is normalised per whole run seen
in the trace."""
from bench import trace

PROGRAM = "jit_run_point"


def read(ctx):
    t, n = trace.summed(ctx["trace"], "modules",
                        lambda name: name.startswith(PROGRAM))
    c = ctx["counters"]
    per_run = c["chunk"] / len(ctx["trace"]["devices"])
    if n == 0:
        return None
    return 1e9 * t / (n * per_run * c["cycles_per_point"])
