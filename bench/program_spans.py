"""The program's own spans (``repro.core.metrics``) on the trace's clock.

The program keeps its spans in memory, stamped with
``time.perf_counter_ns``; the profiler stamps the trace's host spans on
a clock of its own.  The benchmark's spans around calls into the
program give the offset between the two: each ``bench.answer`` encloses
one ``campaign()`` call (the program's ``campaign`` span), each
``bench.batch`` one ``run_batch`` call (``engine.batch``).  The
window's calls are the last ones the program made (set-up calls it
before the window, the check never), so the i-th benchmark span of the
trace pairs with the i-th of the window's program spans.  The offset is
the median of their start differences; the residual, the largest
|Δstart| and |Δend| after the offset, is logged.

The program spans read are those inside the ``bench.window`` span's
whole extent, also where the device buffer overflowed and the device
numbers cover only a prefix of it.  For every device idle gap of that
prefix longer than 100 µs, the innermost program span over its
midpoint is logged: what the host was doing while the chip idled.

A program without the span log gives no spans, and every reader None.
"""
from __future__ import annotations

from collections import defaultdict
from statistics import median
from typing import Any, Dict, List, NamedTuple, Optional

from bench import trace

GAP_NS = 100_000                 # idle gaps logged: longer than 100 µs


class Aligned(NamedTuple):
    """A program span on the trace's clock (ns)."""
    name: str
    parent: Optional[str]
    start: float
    end: float
    attrs: Dict[str, Any]

    @property
    def ns(self) -> float:
        return self.end - self.start


def program_log() -> list:
    """The program's closed spans, oldest first by start."""
    try:
        from repro.core.metrics import spans
    except ImportError:          # a program that keeps no spans
        return []
    return sorted(spans(), key=lambda s: s.start_ns)


def align(trace_spans: list, prog: list, bench: str, program: str,
          calls: int, log) -> Optional[List[Aligned]]:
    """Program spans ``prog`` inside the window of ``trace_spans`` (the
    reduced trace's ``bench.*`` spans), on the trace's clock, paired by
    ``bench`` spans around the last ``calls`` ``program`` spans.  None
    when there is nothing to pair."""
    window = [s for s in trace_spans if s[0] == trace.WINDOW_SPAN]
    marks = sorted((s for s in trace_spans if s[0] == bench),
                   key=lambda s: s[1])
    mine = [s for s in prog if s.name == program]
    pairs = list(zip(marks, mine[-calls:])) if calls > 0 else []
    if not window or not pairs:
        return None
    off = median(b[1] - p.start_ns for b, p in pairs)
    d_start = max(abs(b[1] - p.start_ns - off) for b, p in pairs)
    d_end = max(abs(b[2] - p.end_ns - off) for b, p in pairs)
    log(f"program spans: {len(pairs)} {bench}/{program} pairs, residual "
        f"start {d_start * 1e-6} ms, end {d_end * 1e-6} ms")
    lo, hi = window[0][1], window[0][2]
    return [Aligned(s.name, s.parent, s.start_ns + off, s.end_ns + off,
                    s.attrs) for s in prog
            if lo <= s.start_ns + off and s.end_ns + off <= hi]


def idle_gaps(red: dict, spans: List[Aligned]) -> List[tuple]:
    """``(gap ns, label)`` for device 0's idle gaps longer than
    ``GAP_NS`` in the kept part of the window, labelled by the
    innermost program span over the gap's midpoint, longest first."""
    lo = next(s[1] for s in red["spans"] if s[0] == trace.WINDOW_SPAN)
    hi = lo + red["window_s"] * 1e9
    busy = next(iter(red["devices"].values()))["busy"]
    out = []
    for s, e in trace.gaps(busy, lo, hi):
        if e - s > GAP_NS:
            mid = (s + e) / 2
            over = [sp for sp in spans if sp.start <= mid <= sp.end]
            label = (min(over, key=lambda sp: sp.ns).name if over
                     else "no program span")
            out.append((e - s, label))
    return sorted(out, reverse=True)


def window_spans(ctx: dict, bench: str, program: str,
                 calls_counter: str) -> Optional[List[Aligned]]:
    """The window's program spans for a cell whose runner counts its
    calls into the program as ``calls_counter``; aligned once a run
    (the readers of one run share ``ctx``), with the residual and the
    idle gaps by program span logged then."""
    key = ("program_spans", bench, program)
    if key not in ctx:
        log = ctx["log"]
        spans = align(ctx["trace"]["spans"], program_log(), bench, program,
                      int(ctx["counters"].get(calls_counter, 0)), log)
        if spans:
            gaps = idle_gaps(ctx["trace"], spans)
            by = defaultdict(lambda: [0, 0.0])
            for ns, label in gaps:
                by[label][0] += 1
                by[label][1] += ns * 1e-6
            log(f"idle gaps > {GAP_NS * 1e-3:g} us by program span: "
                + (", ".join(f"{k}: {n} gaps, {ms} ms"
                             for k, (n, ms) in by.items()) or "none"))
            for ns, label in gaps[:5]:
                log(f"  idle gap {ns * 1e-6} ms under {label}")
        ctx[key] = spans or None
    return ctx[key]


def compiles(ctx: dict, bench: str, program: str,
             calls_counter: str) -> Optional[int]:
    """``jax.compile`` spans in the window: programs compiled or loaded
    from the persistent cache (a cache load nests its ``jax.cache_load``
    inside one, so it counts once).  Traces and lowerings are logged."""
    spans = window_spans(ctx, bench, program, calls_counter)
    if spans is None:
        return None
    jax_spans = defaultdict(list)
    for s in spans:
        if s.name.startswith("jax."):
            jax_spans[s.name].append(s)
    for name, ss in sorted(jax_spans.items()):
        where = defaultdict(int)
        for s in ss:
            where[f"{s.attrs.get('fun_name', '?')} in {s.parent}"] += 1
        top = sorted(where.items(), key=lambda kv: -kv[1])[:6]
        ctx["log"](f"{name}: {len(ss)} in the window, "
                   f"{sum(s.ns for s in ss) * 1e-6} ms; "
                   + ", ".join(f"{k} x{n}" for k, n in top))
    return len(jax_spans["jax.compile"])


def campaign_split(ctx: dict) -> Optional[dict]:
    """The window's ``campaign`` calls split into host work and waiting:
    ``wait_ms`` is the time in ``campaign.wait`` and ``campaign.result``
    (the host blocked on the device), ``host_ms`` the rest of the calls'
    time, ``chunks`` the ``campaign.plan`` spans (one a chunk, one more
    per retried dispatch).  Each chunk's spans are logged."""
    spans = window_spans(ctx, "bench.answer", "campaign", "answers")
    if spans is None:
        return None
    calls = [s for s in spans if s.name == "campaign"]
    kids = [s for s in spans if s.parent == "campaign"]
    chunks = sum(s.name == "campaign.plan" for s in kids)
    if not calls or not chunks:
        return None
    total = sum(s.ns for s in calls)
    by = defaultdict(float)
    per_chunk = defaultdict(lambda: defaultdict(float))
    for s in kids:
        by[s.name] += s.ns
        if "chunk" in s.attrs:
            per_chunk[s.attrs["chunk"]][s.name] += s.ns
    wait = by["campaign.wait"] + by["campaign.result"]
    log = ctx["log"]
    log(f"campaign: {len(calls)} calls, {chunks} chunks, "
        f"{total * 1e-6} ms; named spans cover "
        f"{100.0 * sum(by.values()) / total}%: "
        + ", ".join(f"{k} {v * 1e-6} ms" for k, v in sorted(by.items())))
    for ci, d in sorted(per_chunk.items()):
        log(f"  chunk {ci}: " + ", ".join(f"{k} {v * 1e-6} ms"
                                          for k, v in d.items()))
    plan = next(s for s in kids if s.name == "campaign.plan")
    log("  kernel: " + ", ".join(f"{k}={v}" for k, v in plan.attrs.items()
                                 if k != "chunk"))
    return {"host_ms": (total - wait) * 1e-6, "wait_ms": wait * 1e-6,
            "chunks": chunks}
