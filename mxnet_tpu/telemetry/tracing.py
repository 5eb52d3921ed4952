"""Span tracing — the one way the program marks host time.

``with telemetry.span("exec.stage"):`` does three things, from ONE pair
of clock reads:

* it ALWAYS enters a ``jax.profiler.TraceAnnotation("mx." + name)``, so
  whenever anyone has a ``jax.profiler`` session open (a benchmark's
  traced run, ``mx.profiler.profiler_set_state('run')``, an operator's
  ``jax.profiler.start_trace``) the span lies in the same
  ``.xplane.pb``, on the same clock, as the device's ``XLA Ops`` /
  ``XLA Modules`` lines.  The ``mx.`` prefix is added here and nowhere
  else, so a reader selects the program's spans by it;
* it adds its duration to the :class:`FitReport` open on this thread
  (``Module.fit`` opens one per call; see :func:`fit_scope`), under its
  name, with its self time — the duration minus what its child spans
  cover — and keeps its interval there.  A span on another thread
  (decode pools, loader workers) reaches the profiler and the ring, not
  the report, unless the thread that takes its product credits it
  (:func:`credit`: ``ImageRecordIter``'s producer; no interval);
* when ``telemetry.enabled()``, it appends ONE complete Chrome event
  (``"ph": "X"`` with a ``dur``) to a bounded ring, keyed by the real
  thread id — Perfetto renders nesting from the containment of
  (ts, dur) intervals per thread, which is why complete events (not
  B/E pairs) are the only correct encoding when spans from different
  threads interleave.  ``profiler.dump_profile()`` merges this ring
  into its Chrome trace.

The two clock reads are ``time.time_ns()``: the profiler's trace keeps
its events on that clock, shifted by the session's start, so a reader
that anchors one interval of the report in the trace (``fit``'s root
span inside the benchmark's window) lays all of them over the device's
lines.  A negative duration (the clock stepped back) counts as 0.

With no profiler session open and telemetry disabled a span costs two
clock reads, one ``TraceAnnotation`` enter/exit, one dict update and
one append (PERF.md §6 has the v5e host's figure).

Python's collector: a ``gc.callbacks`` hook puts every pass of the
oldest generation into the report of the ``fit`` open in the process,
whichever thread ran it, as a ``py.gc`` interval and the counters
``py.gc_ns`` and ``py.gc_passes``; it is no span row, so the self
times that tile ``fit`` stay as they are.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Span", "span", "count", "credit", "OFF_THREAD", "enabled",
           "FitReport", "fit_scope", "last_fit", "trace_events",
           "clear_trace", "record_events"]

OFF_THREAD = "(off thread)"   # the parent of a row credited from another

_RING_CAPACITY = 16384
_INTERVALS = 65536   # a report's intervals: a benchmark window whole
GC = "py.gc"         # the interval of a pass of the oldest generation
_ring = collections.deque(maxlen=_RING_CAPACITY)
_lock = threading.Lock()
_on = False          # telemetry.enabled(): the ring (and every sink) records
_last_fit = None     # the FitReport of the process's last finished fit
_open_fit = None     # the FitReport of the fit open in the process
_gc_t0 = 0           # where the collector's running pass began


class _ThreadState(threading.local):
    top = None       # innermost open Span of this thread
    report = None    # the FitReport open on this thread


_tls = _ThreadState()


def enabled():
    """Whether telemetry recording (span ring, step timeline, compile
    watch, JSONL) is on.  Spans reach the profiler and the fit report
    either way."""
    return _on


def set_enabled(on):
    global _on
    _on = bool(on)


class FitReport(object):
    """Where one ``fit`` call's host time went: per span name a count,
    total, self time, the longest instance and the step it fell in; the
    steps and epochs trained; named counters; and the intervals
    ``(name, start_ns, end_ns)`` of its thread's spans and of the
    collector's passes, on ``time.time_ns()``'s clock, the first
    :data:`_INTERVALS` of them (the rest are counted as dropped).  A
    name opened under more than one parent keeps the first parent it
    was seen under and sums over all."""

    __slots__ = ("steps", "epochs", "wall_ns", "start_ns", "end_ns",
                 "counters", "intervals", "intervals_dropped", "_spans")

    def __init__(self):
        self.steps = 0       # batches trained so far (the loop counts)
        self.epochs = 0
        self.wall_ns = 0     # the root span's duration
        self.start_ns = self.end_ns = 0    # and its interval
        self.counters = {}
        self.intervals = []
        self.intervals_dropped = 0
        self._spans = {}     # name -> [count, total, self, max, max_step,
        #                                parent]

    def add(self, name, parent, ns, self_ns, start_ns=None):
        """A span closed: its row, and, where it ran on this report's
        thread (``start_ns`` given), its interval."""
        if start_ns is not None:
            self.interval(name, start_ns, start_ns + ns)
        row = self._spans.get(name)
        if row is None:
            self._spans[name] = [1, ns, self_ns, ns, self.steps, parent]
            return
        row[0] += 1
        row[1] += ns
        row[2] += self_ns
        if ns > row[3]:
            row[3], row[4] = ns, self.steps

    def interval(self, name, start_ns, end_ns):
        if len(self.intervals) < _INTERVALS:
            self.intervals.append((name, start_ns, end_ns))
        else:
            self.intervals_dropped += 1

    def as_dict(self):
        return {
            "steps": self.steps, "epochs": self.epochs,
            "wall_ns": self.wall_ns, "start_ns": self.start_ns,
            "end_ns": self.end_ns, "counters": dict(self.counters),
            "spans": {name: {"count": r[0], "total_ns": r[1],
                             "self_ns": r[2], "max_ns": r[3],
                             "max_step": r[4], "parent": r[5]}
                      for name, r in self._spans.items()},
            "intervals": list(self.intervals),
            "intervals_dropped": self.intervals_dropped}


class Span(object):
    """Context manager marking one named region of host time (see the
    module docstring).  ``attrs`` (small JSON-able values) ride in the
    profiler event's stats and the ring event's ``args``.  After entry
    ``start_ns`` is the ``time.time_ns()`` read that opened it; after
    exit ``ns`` is the region's duration: callers that keep their own
    records (the step timeline) read it instead of a second clock."""

    __slots__ = ("name", "attrs", "ns", "start_ns", "_ann", "_parent",
                 "_child_ns")

    def __init__(self, name, **attrs):
        self.name = str(name)
        self.attrs = attrs
        self.ns = self.start_ns = 0

    def __enter__(self):
        # the annotation goes on first and comes off last, so a child's
        # interval in the profiler's trace lies inside its parent's
        self._ann = TraceAnnotation("mx." + self.name, **self.attrs)
        self._ann.__enter__()
        self._parent = _tls.top
        _tls.top = self
        self._child_ns = 0
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc):
        t0 = self.start_ns
        ns = time.time_ns() - t0
        if ns < 0:
            ns = 0
        self.ns = ns
        parent = _tls.top = self._parent
        if parent is not None:
            parent._child_ns += ns
        report = _tls.report
        if report is not None:
            report.add(self.name, parent.name if parent is not None
                       else None, ns, ns - self._child_ns, t0)
        if _on:
            ev = {"name": self.name, "cat": "telemetry", "ph": "X",
                  "ts": t0 * 1e-3, "dur": ns * 1e-3, "pid": 0,
                  "tid": threading.get_ident()}
            if self.attrs:
                ev["args"] = self.attrs
            with _lock:
                _ring.append(ev)
        self._ann.__exit__(*exc)
        return False


span = Span


def count(name, n=1):
    """Add ``n`` to the named counter of the fit report open on this
    thread; nothing where none is."""
    report = _tls.report
    if report is not None:
        report.counters[name] = report.counters.get(name, 0) + n


def credit(name, ns):
    """Add a span that ran on ANOTHER thread to the fit report open on
    this one: a producer thread clocks its stages where the work
    happens (the profiler's trace has them there) and hands the
    durations over with what it made; the thread that takes the product
    credits them.  The row's parent is :data:`OFF_THREAD`: its time is
    no part of this thread's, so it is no child of any span here and
    the self times that tile the ``fit`` call are those of the other
    rows."""
    report = _tls.report
    if report is not None:
        report.add(name, OFF_THREAD, ns, ns)


def _on_gc(phase, info):
    """``gc.callbacks``: a pass of the oldest generation while a ``fit``
    is open goes into its report (see the module docstring); the
    younger generations' passes return at once."""
    global _gc_t0
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_t0 = time.time_ns()
        return
    report = _open_fit
    if report is None:
        return
    t0 = _gc_t0
    ns = max(time.time_ns() - t0, 0)
    report.interval(GC, t0, t0 + ns)
    counters = report.counters
    counters["py.gc_ns"] = counters.get("py.gc_ns", 0) + ns
    counters["py.gc_passes"] = counters.get("py.gc_passes", 0) + 1


gc.callbacks.append(_on_gc)


@contextlib.contextmanager
def fit_scope():
    """One ``fit`` call: opens its :class:`FitReport` on this thread and
    the root span ``fit``, yields the report (the loop counts ``steps``
    and ``epochs`` on it), and on the way out, however the call ends,
    publishes it as :func:`last_fit`.  While it is open it is also the
    process's open report, where the collector's passes land."""
    global _last_fit, _open_fit
    report, outer, outer_open = FitReport(), _tls.report, _open_fit
    _tls.report = _open_fit = report
    root = Span("fit")
    try:
        with root:
            yield report
    finally:
        _tls.report, _open_fit = outer, outer_open
        report.wall_ns = root.ns
        report.start_ns, report.end_ns = root.start_ns, \
            root.start_ns + root.ns
        _last_fit = report


def last_fit():
    """The report of the process's most recent finished ``fit`` as a
    plain dict (None before the first): ``steps``, ``epochs``,
    ``wall_ns``, ``start_ns`` and ``end_ns`` (the root span's),
    ``counters``, ``spans[name] = {count, total_ns, self_ns, max_ns,
    max_step, parent}``, ``intervals`` (``(name, start_ns, end_ns)``
    in the order they closed) and ``intervals_dropped``.  It outlives
    the module that trained."""
    return None if _last_fit is None else _last_fit.as_dict()


def record_events(events):
    """Append pre-built Chrome-trace complete events to the span ring —
    how the serving request traces merge their phase events
    (queue-wait / coalesce / pad / device / resolve) into the ONE
    timeline ``profiler.dump_profile()`` renders. Each event must be a
    ``ph:"X"`` dict with ``ts``/``dur`` in microseconds."""
    with _lock:
        _ring.extend(events)


def trace_events():
    """Snapshot of the span ring as Chrome-trace event dicts."""
    with _lock:
        return list(_ring)


def clear_trace():
    with _lock:
        _ring.clear()
