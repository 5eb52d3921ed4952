"""mxnet_tpu.serving — online inference: dynamic batching, a
shape-bucketed compiled-program cache, and backpressure.

The serving half of the production stack (training half: the fused
mesh Module + durable checkpoints). Three pieces:

* :class:`Predictor` — binds a trained/loaded Module for inference
  behind a compiled-program cache keyed by padded batch-size buckets;
  ``warmup()`` pre-compiles every bucket so steady-state traffic never
  triggers an XLA compile, and served rows are bitwise identical to
  ``Module.predict``.
* :class:`DynamicBatcher` — bounded request queue + background worker
  that coalesces concurrent requests into one bucket-padded launch
  within a ``max_wait_ms`` window; queue-full rejection, per-request
  timeouts, graceful shutdown. Hosts several named :class:`Tenant`
  models behind one queue (multi-model tenancy / canary rollout) with
  SLO-driven admission: a tenant whose own burn windows breach is shed
  (:class:`TenantShed`) while co-hosted tenants keep serving.
* :mod:`~mxnet_tpu.serving.cache` — the persistent compile cache:
  ``Predictor.warmup(cache_dir=...)`` serializes each bucket's
  compiled program into an atomic, crc-verified entry keyed by
  (params digest, precision mode, bucket, backend); a second replica
  warming from the same directory deserializes every bucket with ZERO
  XLA compiles and bitwise-identical served rows.
  ``MXNET_COMPILE_CACHE_DIR`` names the default AOT entry store
  (``<dir>/aot``); jax's own persistent compilation cache is placed
  by ``JAX_COMPILATION_CACHE_DIR`` or, from entry points, by
  :func:`enable_persistent_compile_cache`.
* :class:`DecodeEngine` (:mod:`~mxnet_tpu.serving.decode`) —
  continuous-batching step-wise serving for autoregressive sequence
  models: bucketed-by-length prefill programs, ONE device-resident
  slot-indexed decode state written/read by jitted scatter/gather, a
  scheduler that admits/retires sequences between steps under a fixed
  decode program shape (occupancy churn never retraces), per-sequence
  TTFT / per-token :class:`~mxnet_tpu.telemetry.SLOTracker` objectives
  — and token streams bitwise equal to unbatched decode at any
  occupancy.
* :class:`ServingStats` — one snapshot (``stats()``) of latency
  p50/p95/p99 (deadline-missed requests included, by their queue age),
  batch-fill ratio, queue depth, and compile counters; with telemetry
  enabled it also retains per-request phase-decomposed traces
  (``request_traces()`` — queue-wait / coalesce / pad / device /
  resolve, exported as per-bucket histograms and Chrome-trace events).

Judged by the telemetry layer: ``DynamicBatcher(slo=SLOTracker(...))``
evaluates declared latency/error/availability objectives over
multi-window burn rates (docs/api/telemetry.md "Serving SLOs").

Quick start::

    from mxnet_tpu.serving import Predictor, DynamicBatcher

    pred = Predictor(trained_module, max_batch_size=64)   # or
    # pred = Predictor.load("ckpt_dir", data_shapes=[("data", (1, 3, 28, 28))])
    pred.warmup()                      # compile every bucket pre-traffic
    with DynamicBatcher(pred, max_queue=256, max_wait_ms=2) as srv:
        fut = srv.submit(x)            # from any number of threads
        probs = fut.result()
    print(pred.stats())

See docs/api/serving.md for semantics and field reference.
"""
from __future__ import annotations

from . import cache
from .batcher import DynamicBatcher
from .cache import ExecutableCache, enable_persistent_compile_cache
from .decode import DecodeEngine, DecodeModel, DecodeRequest, LSTMCharLM
from .errors import (QueueFull, RequestAbandoned, RequestTimeout,
                     ServerClosed, TenantShed, WorkerCrashed)
from .predictor import Predictor
from .stats import ServingStats
from .tenancy import Tenant

__all__ = ["Predictor", "DynamicBatcher", "ServingStats", "Tenant",
           "DecodeEngine", "DecodeModel", "DecodeRequest", "LSTMCharLM",
           "ExecutableCache", "enable_persistent_compile_cache",
           "QueueFull", "RequestAbandoned", "RequestTimeout",
           "ServerClosed", "TenantShed", "WorkerCrashed"]
