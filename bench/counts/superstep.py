"""Least device time of the superstep histogram update, from the
algorithm alone.

Each superstep call bins the latencies of the jobs served in its block
into every point's histogram.  What the algorithm must move: each job's
float32 latency read once, and each point's int32 histogram read and
written once per call.  What it must compute: a few integer operations
per job to find its bin and one increment, counted as 4.  The padded
FIFO slots a kernel may also read, and the one-hot compares it may do,
are the implementation's choice and are not counted, so the share of
this least time is a lower bound on waste and never passes 100% for a
sound timing.
"""
from __future__ import annotations

OPS_PER_JOB = 4


def least_time(jobs: int, points: int, calls_per_point: int, n_bins: int,
               peaks: dict) -> dict:
    """``jobs``: latencies binned; ``calls_per_point``: superstep calls
    each point made.  Returns the bytes, ops, both bounds in seconds and
    which one binds."""
    nbytes = 4 * jobs + 2 * 4 * n_bins * points * calls_per_point
    ops = OPS_PER_JOB * jobs
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["int8_ops_per_s"]
    return {"bytes": nbytes, "ops": ops, "t_mem_s": t_mem, "t_ops_s": t_ops,
            "least_s": max(t_mem, t_ops),
            "bound": "memory" if t_mem >= t_ops else "compute"}
