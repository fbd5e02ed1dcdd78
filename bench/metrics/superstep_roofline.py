"""The superstep histogram update's share of its roofline, in percent:
the least time the algorithm needs on this chip for one call
(``counts/superstep.least_time``: each job's latency read once, each
point's histogram read and written once) over the device time of one
call of the Mosaic kernel.

The sweep kernel's program (``jit_run_point``) holds one Mosaic call,
the histogram update, found by its custom-call target; the pallas_call
carries no name of its own yet.  The calls a run makes are counted in
the trace, inside each whole run of the program, and the histogram's
bins are the width of the kernel's ``hist`` output (a counter), so
neither is assumed.  Which bound applies is printed."""
from bench import trace
from bench.counts import superstep

PROGRAM = "jit_run_point"
TARGET = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    runs = [r for r in trace.nested(ctx["trace"],
                                    lambda n: n.startswith(PROGRAM),
                                    lambda n: TARGET in n) if r[0] > 0]
    if not runs:
        return None
    c = ctx["counters"]
    n_dev = len(ctx["trace"]["devices"])
    calls = sum(n for n, _ in runs)
    per_call = sum(t for _, t in runs) / calls
    calls_per_run = calls / len(runs)
    jobs_per_call = c["jobs"] / (c["chunks"] * n_dev * calls_per_run)
    lt = superstep.least_time(jobs_per_call, c["chunk"] / n_dev, 1,
                              c["n_bins"], ctx["peaks"])
    ctx["log"](f"superstep: {len(runs)} whole runs, {calls_per_run} calls "
               f"a run, {per_call} s a call, least {lt['least_s']} s "
               f"({lt['bound']} bound, {lt['bytes']} bytes)")
    return 100.0 * lt["least_s"] / per_call
