"""Reduce a JAX profiler trace to the per-layer numbers of a run.

Only JAX is needed: ``jax.profiler.ProfileData`` reads the ``.xplane.pb``
that ``jax.profiler.start_trace`` writes.  Device planes are named
``/device:TPU:<n>``; each has an ``XLA Ops`` line (one event per op
executed) and an ``XLA Modules`` line (one event per program run, named
after the jitted function, e.g. ``jit_fold(123)``).  The benchmark's own
host spans (``jax.profiler.TraceAnnotation`` named ``bench.*``) are on
the host plane's ``python`` line.

Numbers:

- busy: the union of the intervals in which an op runs on a device,
  clipped to the traced window (the ``bench.window`` host span);
  idle share = 1 − busy / window;
- op and program time by name: the summed device durations;
- idle gaps: the intervals of the window in which device 0 runs
  nothing, each labelled by the innermost ``bench.*`` host span that
  covers its midpoint;
- ops nested in a program run: their count and time inside each whole
  run of a named program.

When the profiler's device buffer overflows it records a ``Trace
Buffers Dropped`` event over the span it lost.  Busy and idle are then
read only up to where the first loss begins (``dropped_s`` says how much
was cut), since a lost op would read as idle; times by name stay per
whole event seen, which a reader divides by the events it counts.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[str, float, float]        # (name, start_ns, end_ns)

WINDOW_SPAN = "bench.window"
DROPPED = "Trace Buffers Dropped"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line) -> List[Interval]:
    return [(e.name, float(e.start_ns), float(e.end_ns))
            for e in line.events]


def planes_of(profile) -> Tuple[Dict[str, Dict[str, List[Interval]]],
                                List[Interval]]:
    """Split a ``ProfileData`` into device lines and host spans:
    ``({device: {line name: events}}, [bench.* host spans])``."""
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    spans: List[Interval] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {ln.name: _events(ln)
                                   for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(ev for ev in _events(ln)
                             if ev[0].startswith("bench."))
    return dict(sorted(devices.items(),
                       key=lambda kv: int(kv[0].rsplit(":", 1)[1]))), spans


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged, clipped, sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(spans: List[Interval], t: float) -> str:
    inner = [sp for sp in spans if sp[1] <= t <= sp[2]]
    if not inner:
        return "outside bench spans"
    return min(inner, key=lambda sp: sp[2] - sp[1])[0]


def _by_name(events: List[Interval], lo: float, hi: float):
    """Seconds and counts by name of the events wholly in [lo, hi)."""
    secs: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for name, s, e in events:
        if lo <= s and e <= hi:
            n = _SUFFIX.sub("", name)
            secs[n] += (e - s) * 1e-9
            count[n] += 1
    return dict(secs), dict(count)


def reduce(profile, window: Optional[Tuple[float, float]] = None) -> dict:
    """Reduce a ``ProfileData`` (or anything with its ``planes``) to
    seconds.  ``window`` defaults to the ``bench.window`` host span."""
    devices, spans = planes_of(profile)
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    if window is None:
        marks = [sp for sp in spans if sp[0] == WINDOW_SPAN]
        if not marks:
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        window = (marks[0][1], marks[0][2])
    lo, hi = window
    drops = [s for lines in devices.values() for evs in lines.values()
             for n, s, e in evs if n == DROPPED and e > lo and s < hi]
    full = hi
    if drops:
        hi = max(lo, min(drops))
    per_dev = {}
    for name, lines in devices.items():
        ops = lines.get("XLA Ops", [])
        busy = union(((s, e) for _, s, e in ops), lo, hi)
        ops_s, ops_n = _by_name(ops, lo, full)
        mod_s, mod_n = _by_name(lines.get("XLA Modules", []), lo, full)
        per_dev[name] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "ops_s": ops_s, "ops_n": ops_n,
            "modules_s": mod_s, "modules_n": mod_n,
            "busy": busy,
            "events": {"ops": [ev for ev in ops
                               if lo <= ev[1] and ev[2] <= full],
                       "modules": [ev for ev in lines.get("XLA Modules", [])
                                   if lo <= ev[1] and ev[2] <= full]},
        }
    window_s = (hi - lo) * 1e-9
    first = next(iter(per_dev.values()))
    idle = sorted(((e - s) * 1e-9, _label(spans, (s + e) / 2))
                  for s, e in gaps(first["busy"], lo, hi))[::-1]
    return {"window_s": window_s, "dropped_s": (full - hi) * 1e-9,
            "devices": per_dev, "idle_gaps": idle,
            "spans": [sp for sp in spans if lo <= sp[1] <= hi]}


def idle_share(red: dict) -> List[float]:
    """Per-device idle share of the window, in [0, 1]."""
    return [1.0 - d["busy_s"] / red["window_s"]
            for d in red["devices"].values()]


def summed(red: dict, key: str, match) -> Tuple[float, int]:
    """Device seconds and event count, summed over devices, of the
    ``key`` entries (``ops`` or ``modules``) whose name satisfies
    ``match``."""
    t = n = 0
    for d in red["devices"].values():
        for name, secs in d[f"{key}_s"].items():
            if match(name):
                t += secs
                n += d[f"{key}_n"][name]
    return t, n


def nested(red: dict, outer, inner) -> List[Tuple[int, float]]:
    """For every whole program run whose name satisfies ``outer``, on
    every device: the count and summed seconds of the ops inside it
    whose name satisfies ``inner``."""
    out = []
    for d in red["devices"].values():
        ops = sorted((s, e) for n, s, e in d["events"]["ops"]
                     if inner(_SUFFIX.sub("", n)))
        starts = [s for s, _ in ops]
        for n, s, e in d["events"]["modules"]:
            if not outer(_SUFFIX.sub("", n)):
                continue
            i = bisect.bisect_left(starts, s)
            j = bisect.bisect_right(starts, e)
            inside = [(a, b) for a, b in ops[i:j] if b <= e]
            out.append((len(inside), sum(b - a for a, b in inside) * 1e-9))
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    ops: Dict[str, float] = defaultdict(float)
    for d in red["devices"].values():
        for n, t in d["ops_s"].items():
            ops[n] += t / len(red["devices"])
    idle: Dict[str, float] = defaultdict(float)
    for t, label in red["idle_gaps"]:
        idle[label] += t
    rank = lambda dd: sorted(dd.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    # an op's name is its HLO instruction; its head names it
    return {"device_ops": [[n[:160], t] for n, t in rank(ops)],
            "idle_gaps": [[n, t] for n, t in rank(idle)]}
