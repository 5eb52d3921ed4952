"""Engine — async host scheduling over the XLA runtime.

The reference's 2,001-LoC dependency engine (src/engine/, ThreadedEngine-
PerDevice) exists because HIP ops are eager and hazard-prone; it toposorts
ops by NDArray Var read/write dependencies and runs them on per-device
thread pools. On TPU, *device* ordering is XLA's job (every jitted call
returns a future-backed Array ordered by dataflow), so the engine's
remaining real work is HOST-side: input-pipeline stages, staging-buffer
fills, checkpoint writes, python callbacks — overlapped with device compute
but still hazard-ordered among themselves.

That host scheduler is native C++ (runtime/engine_core.cpp, bound in
runtime/core.py): per-var FIFO hazard queues (reads run concurrently,
writes serialize — threaded_engine.h ThreadedVar semantics), a priority
worker pool, WaitForVar/WaitForAll sync points, and per-op profiler stamps
(OprExecStat) dumped as Chrome trace JSON. This module keeps the python
fallback for compiler-less environments and honours the reference's env
contract: ``MXNET_ENGINE_TYPE=NaiveEngine`` makes every op synchronous (the
standard race-bisection tool, src/engine/naive_engine.cc);
``MXNET_CPU_WORKER_NTHREADS`` sizes the pool.
"""
from __future__ import annotations

import atexit
import os
import queue
import threading

__all__ = ["Engine", "get", "waitall", "is_naive"]

_NAIVE = os.environ.get("MXNET_ENGINE_TYPE", "") == "NaiveEngine"


def is_naive():
    return _NAIVE


class Engine:
    """Host-side async executor.

    Native path: C++ dependency engine with var hazards. Fallback: single
    FIFO worker thread (still async, no var tracking).
    """

    _inst = None

    def __init__(self, num_workers=None):
        self._native = None
        from .runtime._native_build import NativeBuildError
        from .runtime.core import NativeEngine
        try:
            self._native = NativeEngine(num_workers)
        except NativeBuildError as e:  # pragma: no cover - no g++
            import logging
            logging.getLogger(__name__).warning(
                "native engine unavailable, using the single-worker "
                "FIFO fallback: %s", e)
        if self._native is not None:
            # deterministic teardown: drain and JOIN the C++ worker pool
            # while the interpreter is still fully alive. Relying on
            # NativeEngine.__del__ during interpreter finalization races
            # a worker mid-callback against Python teardown and
            # intermittently aborts the process with "terminate called
            # without an active exception" (reproducible under CPU
            # contention with an in-flight async checkpoint save at
            # exit). Registered at creation: atexit is LIFO, so hooks
            # that SCHEDULE work at exit (CheckpointManager's drain,
            # registered later) run first, and shutdown's wait_all still
            # drains anything they pushed.
            atexit.register(self.shutdown)
        self._q = None
        if self._native is None:
            # the fallback has no per-var hazard tracking, so correctness
            # requires ONE worker: FIFO push order then serializes all
            # mutations (threaded_engine.h ThreadedVar semantics degrade to
            # a total order). MXNET_CPU_WORKER_NTHREADS>1 only takes effect
            # on the native engine.
            if num_workers is None:
                num_workers = int(os.environ.get(
                    "MXNET_CPU_WORKER_NTHREADS", 1))
            if num_workers > 1:
                import logging
                logging.getLogger(__name__).warning(
                    "python fallback engine runs a single worker to keep "
                    "var-hazard ordering; MXNET_CPU_WORKER_NTHREADS=%d "
                    "needs the native engine", num_workers)
            self._q = queue.Queue()
            if not _NAIVE:
                t = threading.Thread(target=self._worker, daemon=True)
                t.start()

    # ------------------------------------------------------------- fallback
    def _worker(self):
        while True:
            fn, done = self._q.get()
            try:
                fn()
            finally:
                done.set()
                self._q.task_done()

    def shutdown(self):
        """Drain pending ops and stop the native worker pool
        (idempotent; the interpreter-exit hook). Work pushed AFTER
        shutdown — late ``__del__``-driven host ops during final GC —
        degrades to synchronous execution, which is always safe."""
        native, self._native = self._native, None
        if native is None:
            return
        try:
            native.wait_all()
        except BaseException:  # noqa: BLE001 - exit path; job errors
            import logging    # already surfaced via their own waiters
            logging.getLogger(__name__).exception(
                "pending engine op failed during shutdown drain")
        native.close()

    # ------------------------------------------------------------------ API
    @property
    def is_native(self):
        return self._native is not None

    def new_var(self):
        """Engine::NewVariable — a dependency token for host buffers."""
        if self._native is not None:
            return self._native.new_var()
        return None

    def del_var(self, var):
        if self._native is not None and var is not None:
            self._native.del_var(var)

    def push(self, fn, const_vars=(), mutate_vars=(), priority=0, name="op"):
        """Engine::PushAsync — run fn() once all hazards clear.

        Returns a threading.Event set after fn completes (both paths)."""
        done = threading.Event()

        def run():
            try:
                fn()
            finally:
                done.set()

        if self._native is not None:
            self._native.push(run, const_vars, mutate_vars, priority, name)
        elif _NAIVE or not self._q:
            run()
        else:
            self._q.put((run, done))
        return done

    def push_async(self, fn):
        """Dependency-free host op; returns a waitable Event."""
        return self.push(fn)

    def wait_for_var(self, var):
        """Engine::WaitForVar — block until all pushed ops touching var ran."""
        if self._native is not None:
            if var is not None:
                self._native.wait_for_var(var)
        elif self._q is not None:
            # fallback has no per-var tracking; a full drain is the only
            # way to honor the WaitForVar contract
            self._q.join()

    def wait_for_all(self):
        if self._native is not None:
            self._native.wait_all()
        elif self._q is not None:
            self._q.join()
        import jax
        try:
            jax.effects_barrier()
        except Exception:  # pragma: no cover
            pass
        # Block on any outstanding device computation.
        try:
            jax.device_put(0).block_until_ready()
        except Exception:  # pragma: no cover
            pass

    # ------------------------------------------------------------- profiler
    def profile_start(self):
        if self._native is not None:
            self._native.profile_start()

    def profile_stop(self):
        if self._native is not None:
            self._native.profile_stop()

    def profile_dump(self, path, clear=True):
        """Dump native per-op stats as Chrome trace JSON; 0 if no native."""
        if self._native is not None:
            return self._native.profile_dump(path, clear)
        return 0


def get():
    if Engine._inst is None:
        Engine._inst = Engine()
    return Engine._inst


def waitall():
    """mx.nd.waitall — block until all pending host+device work is done."""
    get().wait_for_all()
