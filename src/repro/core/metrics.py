"""Streaming per-superstep observability for campaign-scale sweeps.

A :class:`MetricsTap` is a host-side sink the jit kernels stream
per-superstep scalars into via ``jax.experimental.io_callback`` —
queue depth, cumulative measured jobs, busy/span occupancy, and the
drop/abandon counters.  The callback fires once per (superstep, grid
lane) with the dispatch still on device; the tap aggregates lanes per
superstep under a lock (vmap gives no ordering guarantee) and flushes
one JSONL record per completed superstep, plus a Prometheus-style
text file rewritten atomically so an external scraper can watch a
campaign mid-flight.

Contract with the kernels:

- the tap is a *compile-time* kernel argument (it changes the traced
  computation), so it is part of the ``engine.kernel_cache`` key — a
  tapped kernel is never served for an untapped request and vice
  versa;
- the callback is unordered and side-effect-only: attaching a tap
  changes NOTHING about the dispatch's numeric outputs (asserted
  bitwise by tests/test_metrics.py);
- tapped dispatches force single-shard execution (``io_callback``
  under ``shard_map`` is not part of this repo's pinned-jax contract);
  the bitwise shard invariance of the engine means this changes
  timing only.

JSONL schema (one object per line):

- ``{"type": "superstep", "step": int, "lanes": int,
  "queue_depth_mean": float, "jobs_total": int, "occupancy": float,
  "dropped_total": int, "overflow_total": int, "abandoned_total": int,
  "wall_s": float, "jobs_per_sec": float | null, "label": str}``
- ``{"type": "summary", "label": str, ...caller scalars}`` — emitted
  by ``observe_summary`` (the sweep entry points report final
  points/jobs and sketch percentile medians this way).

``wall_s`` is host time since the tap first heard from the dispatch;
``jobs_per_sec`` is the incremental rate since the previously flushed
superstep (null for the first).

Program spans
-------------

The program's own host spans live here too: ``span(name, **attrs)``
times a block of host work on ``time.perf_counter_ns`` and, through a
``jax.profiler.TraceAnnotation`` of the same name, shows it on the host
plane of any profiler trace taken meanwhile.  Each closed span goes to
a bounded in-memory ring as ``Span(name, parent, start_ns, end_ns,
attrs)``, ``parent`` being the innermost span open on the same thread;
``spans()`` returns a snapshot.  The recorder is always on: a span
costs a few microseconds of host time, and the hot paths open a
handful per chunk or batch.

From the first span on, one ``jax.monitoring`` listener also records
JAX's compile activity as spans that end when JAX reports them:
``jax.trace`` (tracing to a jaxpr), ``jax.lower`` (jaxpr to MLIR),
``jax.compile`` (one backend compile, or one load from the persistent
cache) and ``jax.cache_load`` (the load, nested in its ``jax.compile``):
the enclosing span says which step compiled.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
import threading
import time
from collections import deque
from typing import IO, Any, Dict, List, NamedTuple, Optional

__all__ = ["MetricsTap", "tap_superstep", "Span", "SpanLog", "span",
           "spans"]

# per-lane scalar payload streamed by the kernels, in callback order
FIELDS = ("queue", "jobs", "busy", "span", "dropped", "overflow",
          "abandoned")


class MetricsTap:
    """Host-side aggregation sink for per-superstep kernel telemetry.

    Parameters
    ----------
    jsonl_path : append-target for one JSON object per superstep
        (optional — the tap still aggregates for ``summary()``).
    prom_path : Prometheus-style text file, atomically rewritten on
        every flush (optional).
    label : tag attached to every record / metric line.
    expected_points : grid size of the tapped dispatch.  When set, a
        superstep flushes as soon as all lanes reported (streaming);
        otherwise everything flushes on ``close()``.
    """

    FIELDS = FIELDS

    def __init__(self, jsonl_path: Optional[str] = None,
                 prom_path: Optional[str] = None, *,
                 label: str = "sweep",
                 expected_points: Optional[int] = None):
        self.label = str(label)
        self.expected_points = expected_points
        self._lock = threading.Lock()
        self._agg: dict = {}          # step -> accumulators
        self._flushed: set = set()
        self._t0: Optional[float] = None
        self._last_flush: Optional[tuple] = None  # (wall_s, jobs_total)
        self.supersteps = 0
        self.records = 0
        self.latest: dict = {}
        self._prom_path = os.fspath(prom_path) if prom_path else None
        self._jsonl: Optional[IO[str]] = (
            open(os.fspath(jsonl_path), "a") if jsonl_path else None)

    # -- host callback ------------------------------------------------

    def _record(self, step, queue, jobs, busy, span, dropped, overflow,
                abandoned):
        """io_callback target: one (superstep, lane) sample.  Runs on
        the host runtime thread — keep it allocation-light."""
        now = time.perf_counter()
        step = int(step)
        with self._lock:
            if self._t0 is None:
                self._t0 = now
            self.records += 1
            a = self._agg.get(step)
            if a is None:
                a = self._agg[step] = [0, 0.0, 0, 0.0, 0.0, 0, 0, 0]
            a[0] += 1          # lanes reported for this superstep
            a[1] += float(queue)
            a[2] += int(jobs)  # cumulative per lane → sum over lanes
            a[3] += float(busy)
            a[4] += float(span)
            a[5] += int(dropped)
            a[6] += int(overflow)
            a[7] += int(abandoned)
            if (self.expected_points is not None
                    and a[0] == self.expected_points
                    and step not in self._flushed):
                self._flush_locked(step, now)

    def _flush_locked(self, step: int, now: float) -> None:
        a = self._agg.pop(step)
        lanes = a[0]
        wall = now - (self._t0 or now)
        jobs_total = a[2]
        rate = None
        if self._last_flush is not None:
            dt = wall - self._last_flush[0]
            dj = jobs_total - self._last_flush[1]
            if dt > 0 and dj >= 0:
                rate = dj / dt
        rec = {
            "type": "superstep", "step": step, "lanes": lanes,
            "queue_depth_mean": a[1] / max(lanes, 1),
            "jobs_total": jobs_total,
            "occupancy": a[3] / a[4] if a[4] > 0 else 0.0,
            "dropped_total": a[5], "overflow_total": a[6],
            "abandoned_total": a[7], "wall_s": wall,
            "jobs_per_sec": rate, "label": self.label,
        }
        self._flushed.add(step)
        self._last_flush = (wall, jobs_total)
        self.supersteps += 1
        self.latest = rec
        self._emit(rec)
        self._write_prom(rec)

    # -- output -------------------------------------------------------

    def _emit(self, rec: dict) -> None:
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()

    def _write_prom(self, rec: dict) -> None:
        if self._prom_path is None:
            return
        tag = f'{{label="{self.label}"}}'
        lines = [
            "# HELP repro_supersteps_total supersteps flushed",
            "# TYPE repro_supersteps_total counter",
            f"repro_supersteps_total{tag} {self.supersteps}",
            "# HELP repro_jobs_total measured jobs completed",
            "# TYPE repro_jobs_total counter",
            f"repro_jobs_total{tag} {rec['jobs_total']}",
            "# HELP repro_queue_depth_mean mean queue depth over lanes",
            "# TYPE repro_queue_depth_mean gauge",
            f"repro_queue_depth_mean{tag} {rec['queue_depth_mean']:.6g}",
            "# HELP repro_occupancy busy fraction of simulated span",
            "# TYPE repro_occupancy gauge",
            f"repro_occupancy{tag} {rec['occupancy']:.6g}",
            "# HELP repro_dropped_total buffer-dropped jobs",
            "# TYPE repro_dropped_total counter",
            f"repro_dropped_total{tag} {rec['dropped_total']}",
            "# HELP repro_overflow_total admission-rejected jobs",
            "# TYPE repro_overflow_total counter",
            f"repro_overflow_total{tag} {rec['overflow_total']}",
            "# HELP repro_abandoned_total deadline-abandoned jobs",
            "# TYPE repro_abandoned_total counter",
            f"repro_abandoned_total{tag} {rec['abandoned_total']}",
            "# HELP repro_jobs_per_sec incremental measured-job rate",
            "# TYPE repro_jobs_per_sec gauge",
            f"repro_jobs_per_sec{tag} "
            f"{(rec['jobs_per_sec'] or 0.0):.6g}",
            "",
        ]
        d = os.path.dirname(self._prom_path) or "."
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".prom.tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                fh.write("\n".join(lines))
            os.replace(tmp, self._prom_path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def observe_summary(self, **scalars) -> None:
        """Append a free-form ``summary`` record (final percentiles,
        totals — whatever the caller wants on the wire).  NaNs are
        nulled so the line stays strict JSON."""
        clean = {k: (None if isinstance(v, float) and not
                     math.isfinite(v) else v)
                 for k, v in scalars.items()}
        with self._lock:
            self._emit({"type": "summary", "label": self.label,
                        **clean})

    def observe_chunk(self, **scalars) -> None:
        """Append a ``chunk`` record — the campaign driver streams one
        per completed chunk (its ``chunks.jsonl`` row: index, points,
        pad waste, loss totals, host and wait time) for mid-flight
        progress watching.

        Campaign tap contract: a tapped dispatch forces single-shard
        execution (see the class docstring), so the campaign does NOT
        attach the tap to every chunk — ``campaign(metrics_tap=...,
        tap_every=N)`` taps every N-th chunk's *dispatch* (full
        per-superstep telemetry for those chunks) and leaves the rest
        sharded; all chunks still stream this record.  Because a tap
        is bitwise-neutral and the engine is shard-invariant, tapped
        and untapped campaigns produce identical accumulators
        (asserted by tests/test_campaign.py)."""
        clean = {k: (None if isinstance(v, float) and not
                     math.isfinite(v) else v)
                 for k, v in scalars.items()}
        with self._lock:
            self._emit({"type": "chunk", "label": self.label,
                        **clean})

    def summary(self) -> dict:
        """Aggregate view so far (thread-safe snapshot)."""
        with self._lock:
            return {"supersteps": self.supersteps,
                    "records": self.records,
                    "pending": len(self._agg), **{
                        k: self.latest.get(k) for k in
                        ("jobs_total", "occupancy", "jobs_per_sec")}}

    def close(self) -> None:
        """Flush stragglers (in step order) and release the JSONL
        handle.  Idempotent."""
        now = time.perf_counter()
        with self._lock:
            for step in sorted(self._agg):
                self._flush_locked(step, now)
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None

    def __enter__(self) -> "MetricsTap":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def tap_superstep(tap: Optional[MetricsTap], step, **vals) -> None:
    """Trace-time hook: stream one superstep's scalars to ``tap``
    (no-op when ``tap`` is None, so kernels call it unconditionally).
    Missing fields default to 0 — the lossless kernels have no
    overflow/abandon counters."""
    if tap is None:
        return
    import jax.numpy as jnp
    from jax.experimental import io_callback

    args = [jnp.asarray(vals.get(f, 0)) for f in FIELDS]
    io_callback(tap._record, None, step, *args, ordered=False)


# ---------------------------------------------------------------------------
# program spans
# ---------------------------------------------------------------------------

RING = 65536                     # closed spans kept, newest last

# jax.monitoring duration events -> span names
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}


class Span(NamedTuple):
    """One closed span: host times in ``perf_counter_ns``."""
    name: str
    parent: Optional[str]
    start_ns: int
    end_ns: int
    attrs: Dict[str, Any]


class _Open:
    """An open span; ``attrs`` may still be added to inside the block,
    and ``seconds`` is its length once it has closed."""

    __slots__ = ("log", "name", "attrs", "parent", "start_ns", "end_ns",
                 "_ann")

    def __init__(self, log: "SpanLog", name: str, attrs: dict):
        self.log, self.name, self.attrs = log, name, attrs
        self.end_ns = None

    def __enter__(self) -> "_Open":
        from jax.profiler import TraceAnnotation

        stack = self.log._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self.log._stack().pop()
        self.log._ring.append(Span(self.name, self.parent, self.start_ns,
                                   self.end_ns, self.attrs))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanLog:
    """A bounded ring of closed spans, safe to share between threads.
    The module keeps one (``span``, ``spans``); a test may make its
    own."""

    def __init__(self, maxlen: int = RING):
        self._ring: deque = deque(maxlen=maxlen)
        self._local = threading.local()

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **attrs) -> _Open:
        return _Open(self, name, attrs)

    def record(self, name: str, start_ns: int, end_ns: int,
               **attrs) -> None:
        """Add a span that was timed elsewhere, under the innermost
        span open on this thread."""
        stack = self._stack()
        self._ring.append(Span(name, stack[-1] if stack else None,
                               start_ns, end_ns, attrs))

    def spans(self) -> List[Span]:
        return list(self._ring)

    def on_jax_duration(self, event: str, seconds: float, **kw) -> None:
        """``jax.monitoring`` duration listener: JAX reports an event
        when it ends, so the span ends now."""
        name = _JAX_EVENTS.get(event)
        if name is None:
            return
        end = time.perf_counter_ns()
        self.record(name, end - int(seconds * 1e9), end, **kw)


_LOG = SpanLog()
_listening = False
_listen_lock = threading.Lock()


def _listen() -> None:
    global _listening
    with _listen_lock:
        if not _listening:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _LOG.on_jax_duration)
            _listening = True


def span(name: str, **attrs) -> _Open:
    """Context manager: time the block as span ``name`` with ``attrs``
    (module docstring).  Yields the open span."""
    if not _listening:
        _listen()
    return _LOG.span(name, **attrs)


def spans() -> List[Span]:
    """Snapshot of the closed spans in the ring, oldest first."""
    return _LOG.spans()
