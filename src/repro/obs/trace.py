"""Span-based tracing and the per-query flight recorder.

A :class:`Span` is one named wall-clock interval with attributes and
children; a :class:`Recorder` builds a span tree through nested
``with rec.span("name"):`` blocks.  The serving stack threads the active
recorder through :func:`recording` (a contextvar), so deep layers —
``core/fused.py``'s per-kind group launches, per-shard probes and the DAG
merge program, ``serve/engine.py``'s drain and host transfer — attach
their spans without any signature plumbing: they call :func:`current`,
which returns the :data:`NULL_RECORDER` no-op singleton unless something
upstream is recording.

The **flight recorder** view: ``DiscoveryServer(trace=True)`` keeps a ring
buffer of per-request span trees (``DiscoveryResponse.trace`` carries each
request's own root), covering submit -> queue wait -> batch formation
(``form``) -> epoch pin -> ``execute``: plan compile or memo lookup
(``plan``), optimizer statistics (``optimize``), lowering to DAG programs
(``lower``), value hashing (``hash``), the capacity lookup (``capacity``),
per-kind fused dispatch (``probe:*``) -> per-shard probe (``shard:*``) ->
cross-shard merge (``merge``) -> ``drain`` -> host ``transfer``.  Counts
ride on the spans as attributes (``hash.misses``, ``optimize.stats_scans``
and the like), computed only while a recorder is enabled.
``server.dump_trace(path)`` exports the buffer as Chrome trace-event JSON
(:func:`chrome_trace`) loadable in Perfetto / ``chrome://tracing``.

Tracing is observation only: no span ever synchronizes the device, so
enabling it changes no ids and no scores (parity-tested).  Span durations on
the dispatch path are host time (enqueue, hashing, statistics), not device
time.  Device time comes from a ``jax.profiler`` trace: while a recorder is
enabled every :meth:`Recorder.span` also enters a
``jax.profiler.TraceAnnotation`` of the same name, so the spans land on the
host thread's line of the profiler's ``.xplane.pb``, on the profiler's
clock, beside the device ops and the host's own dispatch and transfer
events.  The span *tree* is contiguous wall-clock on the recorder's clock,
which is what makes queue + batch sum to end-to-end latency.

While a traced server runs, a ``gc.callbacks`` hook (:func:`gc_spans`)
records each garbage collection as a ``gc`` span under whatever span is
open on the collecting thread.

Clocks are injectable (``Recorder(now=...)``) so nesting/ordering tests run
on a fake clock with exact expected timestamps.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import json
import threading
import time
from dataclasses import dataclass, field


@dataclass
class Span:
    """One named interval.  ``t0``/``t1`` are seconds on the recorder's
    clock (``t1`` None while open); ``tid`` names the Chrome-trace track
    (inherited from the parent when unset)."""
    name: str
    t0: float
    t1: float | None = None
    attrs: dict = field(default_factory=dict)
    children: list = field(default_factory=list)
    tid: str | None = None

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def set(self, key: str, value):
        """Attach one attribute (no-op on the null span)."""
        self.attrs[key] = value
        return self

    def walk(self):
        """Yield this span and every descendant, depth-first."""
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, name: str):
        """First descendant (or self) with ``name``, else None."""
        for s in self.walk():
            if s.name == name:
                return s
        return None

    def render(self, indent: int = 0) -> str:
        """ASCII tree with millisecond durations (examples / debugging)."""
        pad = "  " * indent
        attrs = "".join(f" {k}={v}" for k, v in self.attrs.items())
        lines = [f"{pad}{self.name:<{max(28 - 2 * indent, 1)}s} "
                 f"{self.duration * 1e3:9.3f} ms{attrs}"]
        for c in self.children:
            lines.append(c.render(indent + 1))
        return "\n".join(lines)


class Recorder:
    """Builds span trees (see module docstring).  ``roots`` holds the
    top-level spans in creation order."""

    enabled = True

    def __init__(self, now=time.perf_counter):
        from jax.profiler import TraceAnnotation

        self.now = now
        self.roots: list = []
        self._stack: list = []
        self._annotation = TraceAnnotation
        self._gc_t0: float | None = None

    def _attach(self, span: Span):
        if self._stack:
            self._stack[-1].children.append(span)
        else:
            self.roots.append(span)

    @contextlib.contextmanager
    def span(self, name: str, tid: str | None = None, **attrs):
        with self._annotation(name):
            s = Span(name=name, t0=self.now(), attrs=attrs, tid=tid)
            self._attach(s)
            self._stack.append(s)
            try:
                yield s
            finally:
                self._stack.pop()
                s.t1 = self.now()

    def record(self, name: str, t0: float, t1: float,
               tid: str | None = None, **attrs) -> Span:
        """Attach one pre-measured interval (e.g. queue wait, whose start
        predates the recorder) under the currently open span.  Nothing goes
        to the profiler: the interval has already passed."""
        s = Span(name=name, t0=t0, t1=t1, attrs=attrs, tid=tid)
        self._attach(s)
        return s


class _NullSpan:
    """Shared inert span yielded by the null recorder's contexts."""
    name = "null"
    t0 = 0.0
    t1 = 0.0
    duration = 0.0
    tid = None
    children = ()

    def set(self, key, value):
        return self

    def walk(self):
        return iter(())

    def find(self, name):
        return None

    def render(self, indent: int = 0) -> str:
        return ""


class _NullSpanCtx:
    _span = _NullSpan()

    def __enter__(self):
        return self._span

    def __exit__(self, *exc):
        return False


class NullRecorder:
    """The disabled recorder: ``span`` is a reusable no-op context."""

    enabled = False
    roots: list = []
    _ctx = _NullSpanCtx()

    def span(self, name: str, tid: str | None = None, **attrs):
        return self._ctx

    def record(self, name: str, t0: float, t1: float,
               tid: str | None = None, **attrs):
        return _NullSpanCtx._span


NULL_RECORDER = NullRecorder()

#: the active recorder for this thread/task (contextvar: each thread that
#: never calls ``recording`` sees the null recorder)
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_recorder", default=NULL_RECORDER)


def current():
    """The active recorder (the no-op singleton unless inside
    :func:`recording`)."""
    return _ACTIVE.get()


@contextlib.contextmanager
def recording(recorder):
    """Make ``recorder`` the active recorder for the dynamic extent."""
    token = _ACTIVE.set(recorder)
    try:
        yield recorder
    finally:
        _ACTIVE.reset(token)


# ---------------------------------------------------------------------------
# garbage-collection spans
# ---------------------------------------------------------------------------

_gc_lock = threading.Lock()
_gc_users = 0


def _gc_callback(phase, info):
    rec = _ACTIVE.get()
    if not rec.enabled:
        return
    if phase == "start":
        rec._gc_t0 = rec.now()
    elif rec._gc_t0 is not None:
        rec.record("gc", rec._gc_t0, rec.now(),
                   generation=info["generation"])
        rec._gc_t0 = None


def gc_spans(on: bool):
    """Install (``on``) or release one user of the process-wide
    ``gc.callbacks`` hook that records a ``gc`` span (attribute
    ``generation``) under the span open on the thread that collected.
    Threads that are not recording pay one contextvar read per collection."""
    global _gc_users
    with _gc_lock:
        _gc_users += 1 if on else -1
        hooked = _gc_callback in gc.callbacks
        if _gc_users > 0 and not hooked:
            gc.callbacks.append(_gc_callback)
        elif _gc_users <= 0 and hooked:
            _gc_users = 0
            gc.callbacks.remove(_gc_callback)


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------

#: single logical process for the serving stack in exported traces
_PID = 1


def chrome_trace(roots, process_name: str = "blend-serve") -> dict:
    """Flatten span trees into the Chrome trace-event JSON format
    (https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):
    one complete (``"ph": "X"``) event per span, microsecond timestamps
    relative to the earliest span, plus metadata (``"ph": "M"``) events
    naming the process and tracks.

    Spans shared between trees (a batch subtree referenced by every request
    it served) are emitted exactly once, keyed by identity — Perfetto then
    shows one dispatcher track plus one track per request."""
    roots = list(roots)
    origin = min((s.t0 for s in roots), default=0.0)
    events = [{"name": "process_name", "ph": "M", "pid": _PID, "tid": 0,
               "args": {"name": process_name}}]
    seen: set = set()
    tids: dict = {}

    def tid_index(tid: str) -> int:
        if tid not in tids:
            tids[tid] = len(tids) + 1
            events.append({"name": "thread_name", "ph": "M", "pid": _PID,
                           "tid": tids[tid], "args": {"name": tid}})
        return tids[tid]

    def emit(span, inherited_tid: str):
        if id(span) in seen:
            return
        seen.add(id(span))
        tid = span.tid or inherited_tid
        t1 = span.t1 if span.t1 is not None else span.t0
        events.append({
            "name": span.name, "ph": "X", "pid": _PID,
            "tid": tid_index(tid),
            "ts": (span.t0 - origin) * 1e6,
            "dur": max(t1 - span.t0, 0.0) * 1e6,
            "args": {k: v for k, v in span.attrs.items()
                     if isinstance(v, (str, int, float, bool))},
        })
        for c in span.children:
            emit(c, tid)

    for i, root in enumerate(roots):
        emit(root, root.tid or f"trace-{i}")
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome(roots, path, process_name: str = "blend-serve"):
    """Write :func:`chrome_trace` JSON to ``path``; returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(roots, process_name=process_name), f)
    return path
