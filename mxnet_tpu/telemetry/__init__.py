"""mxnet_tpu.telemetry — unified metrics, tracing, and step-timeline
observability.

Everything the stack measures — serving counters, pipeline host-wait,
checkpoint durations, train-step timings, XLA retraces — records into
ONE process-wide :class:`MetricsRegistry`, exportable as an append-only
JSONL event log and a Prometheus ``/metrics`` endpoint; host spans
(:func:`span`, the one primitive) lie in the ``jax.profiler`` trace on
the device's clock, feed every ``fit`` call's :func:`last_fit` report
and merge into the profiler's Chrome trace; the :class:`StepTimeline`
answers "why was step 412 slow" after the fact; the
:class:`CompileWatch` attributes every XLA retrace to a call site and
warns when one lands after the warmup boundary.

On top of those instruments sits the judgment layer: an
:class:`SLOTracker` evaluates declared serving objectives over
multi-window rolling burn rates (``slo.*`` gauges, fed by
``DynamicBatcher(slo=...)``), and the process
:class:`RegressionWatchdog` (:func:`health_watchdog`) compares live
step/eval windows against a pinned or self-calibrated baseline and
emits warn-once ``health.*`` incidents (:func:`health_report`; also
``GET /health`` on the MetricsServer).

Quick start::

    from mxnet_tpu import telemetry

    telemetry.enable(jsonl="run.jsonl", port=9100)  # both optional
    mod.fit(...)                                    # emits step records
    print(telemetry.timeline().slowest(3))          # worst steps
    print(telemetry.registry().snapshot())          # every counter
    telemetry.disable()

The contracts (ci.sh-gated, pinned by tests/test_telemetry.py):

* **zero-perturbation** — a telemetry-on ``fit`` trains to
  bitwise-identical params (host clocks only: no readback, no RNG);
* **disabled-mode cost** — one branch per recording call site
  (``telemetry.enabled()``); a span still reaches the profiler and the
  fit report (``telemetry.last_fit()``), about two microseconds each: no
  ring event, no timeline record, no sink write;
* **post-warmup silence** — the steady-state train loop performs zero
  XLA retraces (``compile.post_warmup_retraces`` stays 0).

Env: ``MXNET_TELEMETRY=1`` enables at import (the programmatic
``enable()`` twin); ``MXNET_TELEMETRY_JSONL`` / ``MXNET_TELEMETRY_PORT``
set the sink path / metrics port for that autostart.
"""
from __future__ import annotations

import os
import threading

from .compile_watch import CompileWatch
from .export import JsonlSink, MetricsServer, render_prometheus
from .flight import FlightRecorder, load_postmortem
from .health import RegressionWatchdog
from .introspect import ProgramInventory, analyze_compiled, aval_skeleton
from .registry import (Counter, Gauge, Histogram, MetricsRegistry, Scope,
                       instrument_value, DEFAULT_MS_BUCKETS)
from .slo import SLOTracker
from .timeline import StepTimeline
from . import tracing as _tracing
from .tracing import (OFF_THREAD, FitReport, Span, clear_trace, count,
                      credit, enabled, fit_scope, last_fit, record_events,
                      span, trace_events)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Scope",
    "instrument_value", "StepTimeline", "CompileWatch", "Span", "span",
    "count", "credit", "OFF_THREAD", "FitReport", "fit_scope", "last_fit",
    "JsonlSink", "MetricsServer", "render_prometheus",
    "ProgramInventory", "FlightRecorder", "load_postmortem",
    "analyze_compiled",
    "aval_skeleton",
    "SLOTracker", "RegressionWatchdog",
    "registry", "timeline", "compile_watch", "inventory",
    "flight_recorder", "dump_programs", "enable", "disable",
    "enabled", "jsonl_sink", "metrics_server", "log_event",
    "flush_metrics", "health_watchdog", "health_report",
    "serve_metrics", "trace_events", "clear_trace", "record_events",
    "set_active_pipeline", "active_pipeline", "DEFAULT_MS_BUCKETS",
]

_REGISTRY = MetricsRegistry()
_TIMELINE = StepTimeline()
_WATCH = None
_INVENTORY = None
_FLIGHT = None
_WATCHDOG = None
_lock = threading.Lock()
_state = {"sink": None, "server": None, "active_pipeline": None}


def registry():
    """The process-wide :class:`MetricsRegistry` every subsystem
    records into."""
    return _REGISTRY


def timeline():
    """The process-wide :class:`StepTimeline` the ``fit`` loop writes."""
    return _TIMELINE


def compile_watch():
    """The process-wide :class:`CompileWatch` (created on first use)."""
    global _WATCH
    with _lock:
        if _WATCH is None:
            _WATCH = CompileWatch()
        return _WATCH


def inventory():
    """The process-wide :class:`ProgramInventory` every compiled
    program registers into (created on first use)."""
    global _INVENTORY
    with _lock:
        if _INVENTORY is None:
            _INVENTORY = ProgramInventory(registry=_REGISTRY)
        return _INVENTORY


def dump_programs(path=None):
    """Analyze + dump the program inventory (see
    :meth:`ProgramInventory.dump_programs`)."""
    return inventory().dump_programs(path)


def flight_recorder():
    """The process-wide :class:`FlightRecorder` (created on first use;
    unarmed — and therefore silent — until :meth:`FlightRecorder.arm`,
    an :class:`~mxnet_tpu.dist.ElasticTrainer`, or
    ``MXNET_TELEMETRY_BLACKBOX`` points it at a directory)."""
    global _FLIGHT
    with _lock:
        if _FLIGHT is None:
            _FLIGHT = FlightRecorder()
        return _FLIGHT


def health_watchdog():
    """The process-wide :class:`RegressionWatchdog` (created on first
    use; unarmed — and therefore silent — until ``Module.fit`` arms it
    at the warmup boundary or :meth:`RegressionWatchdog.arm` is called
    explicitly)."""
    global _WATCHDOG
    with _lock:
        if _WATCHDOG is None:
            _WATCHDOG = RegressionWatchdog(registry=_REGISTRY,
                                           timeline=_TIMELINE)
        return _WATCHDOG


def health_report():
    """The watchdog's health state as JSON (armed/baseline/incidents/
    healthy) — also served as ``GET /health`` by the MetricsServer."""
    return health_watchdog().report()


def enable(jsonl=None, port=None):
    """Turn telemetry recording on. ``jsonl=`` opens an append-only
    event-log sink; ``port=`` serves the Prometheus endpoint (0 picks a
    free port). Idempotent; reconfigures sink/server when given."""
    with _lock:
        _tracing.set_enabled(True)
        if jsonl is not None:
            old = _state["sink"]
            if old is not None and old.path != str(jsonl):
                old.close()
                old = None
            if old is None:
                _state["sink"] = JsonlSink(jsonl)
        if port is not None and _state["server"] is None:
            _state["server"] = MetricsServer(_REGISTRY, port=port)
    return _state["server"]


def disable():
    """Turn recording off and release the sink/endpoint. Instruments
    and retained timeline records stay readable."""
    with _lock:
        _tracing.set_enabled(False)
        sink, _state["sink"] = _state["sink"], None
        server, _state["server"] = _state["server"], None
    if sink is not None:
        sink.close()
    if server is not None:
        server.close()


def jsonl_sink():
    """The live :class:`JsonlSink`, or None."""
    return _state["sink"]


def metrics_server():
    """The live :class:`MetricsServer`, or None."""
    return _state["server"]


def log_event(kind, payload):
    """Append one event line to the JSONL sink (no-op without one)."""
    sink = _state["sink"]
    if sink is not None:
        sink.write(kind, payload)


def flush_metrics(reason=""):
    """Append a full registry snapshot to the JSONL sink as one
    ``{"kind": "metrics"}`` line (the 'one line per flush' contract)."""
    sink = _state["sink"]
    if sink is not None:
        payload = {"metrics": _REGISTRY.snapshot()}
        if reason:
            payload["reason"] = str(reason)
        sink.write("metrics", payload)


def serve_metrics(port=0):
    """Start (or return the already-running) Prometheus endpoint."""
    with _lock:
        if _state["server"] is None:
            _state["server"] = MetricsServer(_REGISTRY, port=port)
        return _state["server"]


def set_active_pipeline(stats):
    """Publish the device-feed :class:`~mxnet_tpu.data.PipelineStats`
    the CURRENT fit trains through (None to clear). ``Speedometer`` and
    the fit epoch log read host-wait from here — the registry-backed
    replacement for sniffing the fit loop's locals."""
    _state["active_pipeline"] = stats


def active_pipeline():
    """The registered :class:`PipelineStats`, or None."""
    return _state["active_pipeline"]


def _autostart():
    blackbox = os.environ.get("MXNET_TELEMETRY_BLACKBOX")
    if blackbox:
        # arm the crash black box process-wide: fit faults, SIGTERM and
        # unhandled exceptions leave an atomic postmortem in this dir
        flight_recorder().arm(blackbox).install()
    if os.environ.get("MXNET_TELEMETRY", "0") != "1":
        return
    jsonl = os.environ.get("MXNET_TELEMETRY_JSONL") or None
    port = os.environ.get("MXNET_TELEMETRY_PORT")
    enable(jsonl=jsonl, port=int(port) if port else None)


_autostart()
