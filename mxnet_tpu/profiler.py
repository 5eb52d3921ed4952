"""Profiler (python/mxnet/profiler.py + src/engine/profiler.{h,cc}).

The reference stamps per-op OprExecStat inside the engine and dumps Chrome
trace JSON. TPU-natively, per-op timing lives in the XLA/TPU runtime: we
bridge to ``jax.profiler`` (XPlane traces, viewable in TensorBoard/Perfetto)
while preserving the reference API (profiler_set_config / set_state /
dump_profile) and emitting a Chrome-trace JSON of host-side step events.
While the state is 'run' every ``telemetry.span`` of the program lies in
that XPlane trace as an ``mx.*`` event, on the device's clock.
"""
from __future__ import annotations

import json
import os
import threading

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile"]

_config = {"mode": "symbolic", "filename": "profile.json"}
_state = "stop"
_lock = threading.Lock()
_jax_tracing = False
_ran_undumped = False  # profiling ran but no dump written yet


def _autostart():
    """Honor the reference's MXNET_PROFILER_AUTOSTART / MXNET_PROFILER_MODE
    env contract (docs/how_to/env_var.md:71-76): profiling begins at
    library init and the dump fires at exit."""
    if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") != "1":
        return
    mode = "all" if os.environ.get("MXNET_PROFILER_MODE", "0") == "1" \
        else "symbolic"
    profiler_set_config(mode=mode, filename=os.environ.get(
        "MXNET_PROFILER_FILENAME", "profile.json"))
    profiler_set_state("run")
    import atexit

    def _stop_and_dump():
        # sticky: dump whenever profiling ever ran and data may be
        # undumped (reference enable_output_ semantics,
        # initialize.cc:42-47) — neither a manual stop() nor a mid-run
        # dump may lose the tail of the trace
        was_running = _state == "run"
        if was_running:
            profiler_set_state("stop")
        if was_running or _ran_undumped:
            dump_profile()

    atexit.register(_stop_and_dump)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """mode: 'symbolic' or 'all' (MXSetProfilerConfig)."""
    _config["mode"] = mode
    _config["filename"] = filename


def profiler_set_state(state="stop"):
    """state: 'run' or 'stop' (MXSetProfilerState); also starts/stops a
    jax.profiler trace next to the chrome-trace output."""
    global _state, _jax_tracing, _ran_undumped
    if state == _state:
        return
    _state = state
    if state == "run":
        _ran_undumped = True
    trace_dir = os.path.splitext(_config["filename"])[0] + "_xplane"
    from . import engine as _engine
    if state == "run":
        _engine.get().profile_start()  # native per-op host stamps
        try:
            import jax
            jax.profiler.start_trace(trace_dir)
            _jax_tracing = True
        except Exception:
            _jax_tracing = False
    else:
        _engine.get().profile_stop()
        if _jax_tracing:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


_native_events = []  # drained from the engine, kept so dumps stay cumulative


def dump_profile():
    """Write accumulated events as Chrome tracing JSON (MXDumpProfile):
    the native engine's per-op stamps (OprExecStat equivalents) and the
    telemetry span ring (``mxnet_tpu.telemetry.span``, filled while
    telemetry is enabled), so one file carries the whole host-side
    timeline. Callable repeatedly — every event source accumulates
    across dumps."""
    from . import engine as _engine
    eng = _engine.get()
    # "symbolic" mode never emits per-op stamps — skip the temp-file
    # drain entirely rather than accumulating events nobody will see
    if eng.is_native and _config.get("mode") == "all":
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".json",
                                         delete=False) as tmp:
            path = tmp.name
        try:
            if eng.profile_dump(path) > 0:
                with open(path) as f:
                    fresh = json.load(f).get("traceEvents", [])
                with _lock:
                    _native_events.extend(fresh)
        finally:
            os.unlink(path)
    with _lock:
        events = []
        # "symbolic" mode (MXNET_PROFILER_MODE=0, the reference default)
        # reports executor/step regions only; "all" adds the engine's
        # per-imperative-op stamps (profiler.h:63-66 mode semantics)
        if _config.get("mode") == "all":
            events += list(_native_events)
        # the ring's stamps are wall-clock microseconds, like the
        # engine's; the XPlane trace holds the same spans itself
        from . import telemetry as _telemetry
        events += _telemetry.trace_events()
        data = {"traceEvents": events, "displayTimeUnit": "ms"}
        with open(_config["filename"], "w") as f:
            json.dump(data, f)
    global _ran_undumped
    _ran_undumped = False


_autostart()
