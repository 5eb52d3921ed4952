"""Predictor — online inference over a trained Module with a
shape-bucketed compiled-program cache.

The reference's inference story is a blocking ``Module.predict`` loop
over a whole ``DataIter`` — fine for offline eval, useless for online
traffic: every new request shape would trace+compile a fresh XLA
program (seconds to minutes), and per-request launches at batch 1 waste
the device. The Predictor solves the compile half of that problem (the
``DynamicBatcher`` solves the utilization half):

* it binds one inference Module per **batch-size bucket** (powers of
  two up to ``max_batch_size`` by default), all sharing ONE set of
  device-resident parameter buffers through the existing
  ``shared_module`` path — on the fused mesh path that is the same
  ``MeshExecutorGroup`` staging machinery training uses, so a sharded
  (GSPMD/NamedSharding) module serves from the same mesh layout it
  trained on;
* a request of ``n`` rows is zero-padded up to the smallest bucket
  ``>= n`` and the outputs sliced back to ``n`` — steady-state traffic
  therefore only ever runs the pre-compiled bucket programs, never a
  new shape (``warmup()`` pre-compiles every bucket before traffic,
  and the compile counter in ``stats()`` pins "zero recompiles after
  warmup"). Padding is row-exact: an ``is_train=False`` forward is
  row-independent, so the served rows are bitwise identical to
  ``Module.predict`` on the same inputs (pinned by tests);
* requests larger than the top bucket are chunked across launches.

Parameters are snapshotted from the source module at construction
(``device_put`` of the same host values), so serving never races
training updates; rebuild the Predictor (or construct it from a
``CheckpointManager``) to pick up new weights.
"""
from __future__ import annotations

import logging
import os
import time

import numpy as onp

from .. import ndarray as nd
from ..base import MXNetError
from ..io import DataBatch
from ..module import Module
from ..module.base_module import pad_batch_rows  # shared pad rule
from .stats import ServingStats

__all__ = ["Predictor"]


class Predictor:
    """Bind a trained/loaded :class:`Module` for online inference.

    Parameters
    ----------
    module : Module
        Source of symbol + parameters. May be a live (bound) training
        module or an unbound ``Module.load`` result; its parameters are
        snapshotted — later training steps do not leak into serving.
    data_shapes : list of (name, shape), optional
        Input descriptors; the batch dimension is replaced per bucket.
        Defaults to the source module's bound ``data_shapes``.
    buckets : list of int, optional
        Explicit batch-size buckets. Each must be a positive multiple
        of the data-parallel factor (mesh ``dp`` axis, or the context
        count). Default: powers of two from ``dp`` up to
        ``max_batch_size``.
    max_batch_size : int
        Top bucket for the default power-of-two ladder (ignored when
        ``buckets`` is given). Larger requests are chunked.
    context : list of Context, optional
        Serving devices; defaults to the source module's contexts.
    calibration : CalibrationTable, optional
        Static per-site activation ranges (``precision.quant``) for a
        ``narrow_math`` policy: required by ``int8_serve`` (the int8
        activation scales must come from a calibration pass, not from
        in-program reductions); its digest keys the executable cache.
    """

    def __init__(self, module, data_shapes=None, buckets=None,
                 max_batch_size=32, context=None, logger=None,
                 latency_window=2048, calibration=None):
        if not isinstance(module, Module):
            raise MXNetError(
                "Predictor needs a plain Module (got %s); for wrapper "
                "modules serve the underlying Module"
                % type(module).__name__)
        self.logger = logger or logging.getLogger("mxnet_tpu.serving")
        self._stats = ServingStats(latency_window=latency_window)
        import threading
        self._lock = threading.RLock()

        # -- source introspection --------------------------------------
        symbol = module.symbol
        if module.binded and module.params_initialized:
            arg_params, aux_params = module.get_params()
        elif module.params_initialized and \
                getattr(module, "_arg_params", None) is not None:
            arg_params = module._arg_params
            aux_params = module._aux_params or {}
        else:
            raise MXNetError(
                "Predictor needs initialized parameters: bind+init the "
                "module, or load it from params files / a "
                "CheckpointManager first")
        # precision-mode gate (mxnet_tpu.precision): a checkpoint
        # trained under a mode (e.g. int8_act's quantized input seam)
        # served through a module bound under a DIFFERENT policy would
        # return silent garbage, not an error — refuse up front. The
        # recorded mode rides the checkpoint manifest; live modules
        # (never loaded from a manager entry) carry no recorded mode
        # and their own policy is authoritative.
        saved_mode = getattr(module, "_ckpt_precision_mode", None)
        live_mode = getattr(module, "precision_mode", "f32")
        if saved_mode is not None and saved_mode != live_mode:
            raise MXNetError(
                "refusing to serve: checkpoint was trained under "
                "precision mode %r but the module to bind runs %r — "
                "load with the matching precision= (or drop the "
                "override so the recorded mode is adopted)"
                % (saved_mode, live_mode))
        if data_shapes is None:
            if not module.binded:
                raise MXNetError(
                    "data_shapes is required when the source module is "
                    "not bound (e.g. a Module.load result)")
            data_shapes = module.data_shapes
        # structural identity for the persistent executable cache
        # (serving.cache): symbol + param shapes/dtypes, the SAME
        # digest rule checkpoint manifests record. A manager-restored
        # module carries the recorded digest — a disagreement means the
        # params were swapped after load, and adopting a cache entry
        # keyed on either digest could serve a stale executable.
        from ..checkpoint import pack_params, params_digest
        self._params_digest = params_digest(
            symbol.tojson(), pack_params(arg_params, aux_params))
        recorded = getattr(module, "_ckpt_params_digest", None)
        if recorded is not None and recorded != self._params_digest:
            raise MXNetError(
                "refusing to serve: the module's parameters no longer "
                "match the checkpoint manifest's recorded params digest "
                "(%s... != %s...) — the params were replaced after "
                "load; rebuild the module from its checkpoint"
                % (self._params_digest[:12], recorded[:12]))
        self._data_descs = [(name, tuple(shape))
                            for name, shape in data_shapes]
        contexts = list(context) if context is not None else \
            list(module._context)

        # -- bucket ladder ---------------------------------------------
        mesh_axes = module._mesh_axes
        dp = (mesh_axes or {}).get("dp", len(contexts))
        if buckets is None:
            # the ladder starts at 2 (not 1): XLA lowers a batch-1
            # matmul as a gemv with a different accumulation order, so
            # a 1-row bucket would break the bitwise-parity contract
            # with Module.predict; padding one zero row is free
            b, buckets = max(2, int(dp)), []
            while b <= max_batch_size:
                buckets.append(b)
                b *= 2
            if not buckets:
                raise MXNetError(
                    "max_batch_size=%d is smaller than the data-parallel "
                    "factor %d — no bucket fits" % (max_batch_size, dp))
        else:
            buckets = sorted({int(b) for b in buckets})
            if not buckets:
                raise MXNetError("buckets must not be empty")
            bad = [b for b in buckets if b <= 0 or b % dp]
            if bad:
                raise MXNetError(
                    "buckets %r must be positive multiples of the "
                    "data-parallel factor %d (mesh dp axis / context "
                    "count) so every bucket shards evenly" % (bad, dp))
            if buckets[0] == 1:
                raise MXNetError(
                    "a 1-row bucket breaks the bitwise-parity contract "
                    "(XLA's batch-1 gemv lowering accumulates in a "
                    "different order); use a minimum bucket of 2 — "
                    "padding the one extra row is free")
        self._buckets = buckets

        # -- one inference module per bucket, ONE set of param buffers -
        def _shapes_at(b):
            return [(name, (b,) + shape[1:])
                    for name, shape in self._data_descs]

        # serve under the source policy's EVAL-visible fields only: the
        # forward must see the same input casts (act_cast) and compute
        # dtype the training forward saw, but training-only levers —
        # remat, optimizer-state dtype, loss scaling — are stripped so
        # an inference-only bucket never builds a segmented-remat
        # evaluator or trips the fused-path requirement. The mode NAME
        # is kept for telemetry attribution.
        src_pol = getattr(module, "_precision", None)
        serve_pol = None
        if src_pol is not None:
            from ..precision import PrecisionPolicy
            narrow = getattr(src_pol, "narrow_math", None)
            table = calibration if calibration is not None \
                else getattr(src_pol, "calibration", None)
            if narrow == "int8" and table is None:
                raise MXNetError(
                    "precision mode %r needs a CalibrationTable "
                    "(static int8 activation scales): run "
                    "precision.quant.calibrate(...) and pass the "
                    "table via Predictor(calibration=...)"
                    % src_pol.name)
            serve_pol = PrecisionPolicy(
                name=src_pol.name, compute_dtype=src_pol.compute_dtype,
                act_cast=src_pol.act_cast,
                weight_quant=getattr(src_pol, "weight_quant", None),
                narrow_math=narrow, calibration=table,
                experimental=src_pol.experimental)
        elif calibration is not None:
            raise MXNetError(
                "Predictor(calibration=...) only applies to a module "
                "bound under a narrow_math precision mode (e.g. "
                "'int8_serve')")
        self._calibration = calibration if serve_pol is None \
            else serve_pol.calibration

        def _make(extra):
            return Module(symbol, data_names=module._data_names,
                          label_names=module._label_names,
                          logger=self.logger, context=contexts,
                          compute_dtype=module._compute_dtype,
                          mesh_axes=mesh_axes,
                          param_sharding=module._param_sharding,
                          precision=serve_pol,
                          _allow_fused=module._allow_fused, **extra)

        base = _make({})
        base.bind(data_shapes=_shapes_at(buckets[-1]), for_training=False)
        base.set_params(arg_params, aux_params)
        self._modules = {buckets[-1]: base}
        for b in buckets[:-1]:
            m = _make({})
            m.bind(data_shapes=_shapes_at(b), for_training=False,
                   shared_module=base)
            self._modules[b] = m
        self._base = base
        for b, m in self._modules.items():
            self._instrument(m)
            grp = m._exec_group
            if getattr(grp, "fused", False):
                # name this bucket's programs in the process
                # ProgramInventory (telemetry.introspect): the eval
                # program registers at warmup as "serving.b<k>.fwd_eval"
                grp._inventory_owner = "serving.b%d" % b
        self._warmed = False

    # ------------------------------------------------------------------
    @staticmethod
    def load(source, epoch=None, data_shapes=None, data_names=("data",),
             label_names=("softmax_label",), context=None, precision=None,
             **kwargs):
        """Predictor straight from a checkpoint: ``source`` is a legacy
        prefix (``epoch`` required), a ``CheckpointManager``, or a
        checkpoint directory (``epoch`` then selects a committed step,
        default the latest). Routes through :meth:`Module.load`, so the
        symbol rides in from the manifest on the manager path — which
        also adopts the entry's recorded precision mode; an explicit
        ``precision=`` that mismatches the recorded mode is REFUSED at
        Predictor construction (a wrong-mode serve is silent garbage)."""
        mkw = {}
        if precision is not None:
            mkw["precision"] = precision
        mod = Module.load(source, epoch, data_names=list(data_names),
                          label_names=list(label_names), context=context,
                          **mkw)
        return Predictor(mod, data_shapes=data_shapes, context=context,
                         **kwargs)

    # ------------------------------------------------------------------
    @property
    def buckets(self):
        return list(self._buckets)

    @property
    def max_batch_size(self):
        return self._buckets[-1]

    @property
    def output_names(self):
        return list(self._base.output_names)

    @property
    def data_names(self):
        return [name for name, _ in self._data_descs]

    def stats(self):
        """Snapshot of the serving counters: request outcomes, latency
        percentiles, batch-fill ratio, queue depth, compile count (see
        docs/api/serving.md for field semantics)."""
        return self._stats.snapshot()

    def _instrument(self, mod):
        """Count XLA traces through this module's eval functions — each
        jit trace runs the traced Python body exactly once, so wrapping
        the evaluator closure is an honest compile counter (and catches
        any accidental new input signature, not just new buckets)."""
        grp = mod._exec_group
        if not getattr(grp, "fused", False):
            # classic per-executor path jits at executor construction;
            # traces are not observable from here
            self._stats.compile_tracking = False
            return
        stats = self._stats
        for attr in ("_eval_fn", "_pipe_eval_fn"):
            inner = getattr(grp, attr, None)
            if inner is None:
                continue

            def counted(*a, __inner=inner, **kw):
                stats.note_compile()
                return __inner(*a, **kw)

            setattr(grp, attr, counted)

    # ------------------------------------------------------------------
    def _normalize(self, data):
        """Accept a numpy/jax/NDArray array (single-input nets), a
        list/tuple in ``data_names`` order, or a name->array dict;
        return (name->f32 raw array dict, n_rows). Feature dims are
        validated against the bound shapes so a malformed request fails
        at submit time, not on the batcher thread.

        Pre-staged (device-resident) inputs — e.g. the batches a
        :class:`mxnet_tpu.data.DeviceLoader` delivers — pass through
        WITHOUT a host round trip: a jax array stays on device (the
        pad/slice rule runs device-side) and the served rows remain
        bitwise equal to the same request from host memory (pinned by
        tests/test_data_pipeline.py)."""
        names = self.data_names
        if isinstance(data, dict):
            arrays = dict(data)
        elif isinstance(data, (list, tuple)):
            arrays = dict(zip(names, data))
        else:
            if len(names) != 1:
                raise ValueError(
                    "this net has %d inputs %r; pass a dict or a list"
                    % (len(names), names))
            arrays = {names[0]: data}
        missing = [n for n in names if n not in arrays]
        if missing:
            raise ValueError("request is missing input(s) %r" % missing)
        out, rows = {}, None
        for name, shape in self._data_descs:
            v = arrays[name]
            if hasattr(v, "_read"):
                v = v._read()
            if isinstance(v, onp.ndarray) or onp.isscalar(v) or \
                    isinstance(v, (list, tuple)):
                v = onp.ascontiguousarray(v, dtype=onp.float32)
            elif v.dtype != onp.float32:
                v = v.astype(onp.float32)
            if tuple(v.shape[1:]) != tuple(shape[1:]):
                raise ValueError(
                    "input %r has row shape %r, bound shape wants %r"
                    % (name, tuple(v.shape[1:]), tuple(shape[1:])))
            if rows is None:
                rows = v.shape[0]
            elif v.shape[0] != rows:
                raise ValueError(
                    "inputs disagree on row count: %d vs %d"
                    % (v.shape[0], rows))
            out[name] = v
        if not rows:
            raise ValueError("request has zero rows")
        return out, rows

    def bucket_for(self, n):
        """Smallest bucket that fits ``n`` rows (the top bucket for
        oversized requests — those are chunked)."""
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    # ------------------------------------------------------------------
    @property
    def params_digest(self):
        """Structural identity of (symbol, param shapes/dtypes) —
        the executable-cache key component checkpoint manifests record
        as ``params_digest``."""
        return self._params_digest

    def warmup_report(self):
        """Per-bucket outcome of the last :meth:`warmup`:
        ``{bucket: {"warmup_ms", "source"}}`` where ``source`` is
        ``"deserialized"`` (persistent-cache hit, zero XLA work),
        ``"compiled"`` (AOT compile + entry stored), or ``"jit"`` (no
        cache directory — classic lazy trace)."""
        return {b: dict(r) for b, r in
                getattr(self, "_warmup_report", {}).items()}

    def warmup(self, cache_dir=None):
        """Bring every bucket to a launchable executable BEFORE
        traffic; afterwards steady-state serving performs zero XLA
        compiles (``stats()['compiles']`` stays frozen — pinned by
        tests/test_serving.py). Returns the stats snapshot.

        ``cache_dir`` activates the persistent executable cache
        (module docstring of :mod:`mxnet_tpu.serving.cache`): each
        bucket either DESERIALIZES a crc-verified cache entry keyed by
        ``(params digest, precision mode, bucket, input signature,
        backend)`` — zero XLA compiles, the replica warm start — or
        compiles ahead-of-time and commits the entry atomically for
        the next replica. Any key mismatch (drifted params digest,
        wrong precision mode, different backend, corrupt or ``.tmp-*``
        entry) falls back LOUDLY to a fresh compile; a stale
        executable is never served silently. Defaults to
        ``$MXNET_COMPILE_CACHE_DIR/aot`` when that env var is set;
        explicit ``cache_dir`` values get an ``aot/`` subdirectory so
        jax's own persistent-cache files can share the root.

        Per-bucket compile/deserialize wall time publishes as
        ``serving.<i>.b<bucket>.warmup_ms`` gauges (also in
        ``stats()["warmup_ms"]``), hits/misses count into both the
        serving scope and ``compile.cache_hits``/``cache_misses``, and
        warmup traces are attributed to ``compile.warmup_compiles`` —
        never the training ``compile.retraces`` stream."""
        from .. import telemetry
        from . import cache as _cache
        if cache_dir is None:
            root = os.environ.get("MXNET_COMPILE_CACHE_DIR")
            cache_dir = os.path.join(root, "aot") if root else None
        else:
            cache_dir = os.path.join(str(cache_dir), "aot")
        store = _cache.ExecutableCache(cache_dir) if cache_dir else None
        watch = telemetry.compile_watch()
        for m in self._modules.values():
            watch.attach(m)
        report = {}
        with self._lock, watch.warmup_scope():
            for b in self._buckets:
                t0 = time.perf_counter()
                source = None
                if store is not None:
                    source = self._warm_bucket(b, store, watch)
                zeros = {name: onp.zeros((b,) + shape[1:], onp.float32)
                         for name, shape in self._data_descs}
                self._run_bucket(b, zeros, b, warmup=True)
                ms = (time.perf_counter() - t0) * 1000.0
                self._stats.note_warmup_bucket(b, ms, source)
                report[b] = {"warmup_ms": round(ms, 3),
                             "source": source or "jit"}
            self._warmed = True
        self._warmup_report = report
        return self.stats()

    def _warm_args(self, grp, bucket):
        """The exact ``(params, aux, inputs, rng)`` call structure a
        bucket launch uses — zeros staged through the SAME ``_stage``
        rule as traffic, so the lowered avals/shardings match every
        later request bitwise."""
        zeros = {name: onp.zeros((bucket,) + shape[1:], onp.float32)
                 for name, shape in self._data_descs}
        batch = DataBatch(
            data=[nd.NDArray(zeros[name])
                  for name, _ in self._data_descs],
            label=None, pad=0)
        inputs = grp._stage(batch, is_train=False)
        params = {n: buf._read() for n, buf in grp._param_dict.items()}
        aux = {n: buf._read() for n, buf in grp._aux_dict.items()}
        return params, aux, inputs, onp.zeros((2,), onp.uint32)

    def _bucket_cache_key(self, grp, bucket):
        from . import cache as _cache
        backend = _cache.backend_signature(
            mesh_axes=grp.mesh_axes, n_dev=int(grp.mesh.devices.size),
            device_kind=grp._device_kind, platform=grp._platform)
        input_sig = _cache.input_signature(self._data_descs)
        if self._calibration is not None:
            # two calibration passes may produce different static
            # scales — and therefore different programs — under the
            # same mode name and params digest: the table digest keeps
            # their executables apart
            input_sig += ";calib=%s" % self._calibration.digest()
        return _cache.cache_key(
            self._params_digest, grp.precision_mode_name(), bucket,
            input_sig, backend)

    def _warm_bucket(self, bucket, store, watch):
        """AOT-warm one bucket through the persistent executable
        cache: deserialize the entry (``"deserialized"``) or compile
        ahead-of-time and commit it (``"compiled"``). Either way the
        resulting executable is INSTALLED as the bucket's program —
        steady-state launches call it directly, with the jit wrapper
        (and any chance of a re-trace) out of the request path."""
        from . import cache as _cache
        grp = self._modules[bucket]._exec_group
        if not getattr(grp, "fused", False):
            return None   # classic per-executor path: nothing to AOT
        key = self._bucket_cache_key(grp, bucket)
        loaded, source = None, "compiled"
        try:
            payload, in_tree, out_tree = store.load(key)
            from jax.experimental import serialize_executable as _se
            # name the bucket's own devices (and their client): the
            # defaults are the default backend and EVERY device of it,
            # which a one-chip replica in a multi-chip process — or a
            # host-CPU replica on a chip machine — cannot launch on
            devs = list(grp.mesh.devices.flat)
            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree, backend=devs[0].client,
                execution_devices=devs)
            source = "deserialized"
        except _cache.CacheMiss as e:
            log = self.logger.info if e.reason == "absent" \
                else self.logger.warning
            log("serving bucket %d: executable cache %s — falling "
                "back to a fresh compile (%s)", bucket, e.reason,
                e.detail or store.path_for(key))
        except Exception as e:  # noqa: BLE001 - any deserialize failure
            self.logger.warning(
                "serving bucket %d: cached executable failed to "
                "deserialize (%s) — falling back to a fresh compile",
                bucket, e)
        if loaded is None:
            cached = grp._jits.get("fwd_eval")
            if cached is not None and not hasattr(cached, "lower"):
                # a previously installed (deserialized/AOT) executable
                # can't be re-lowered; drop it so _get_jit rebuilds the
                # traceable jit wrapper — re-warming after an evicted
                # entry must fall back to a fresh compile, not crash
                del grp._jits["fwd_eval"]
            fn = grp._get_jit("fwd_eval")
            # staged zeros + param reads are only needed to lower a
            # fresh compile — building them above the cache load would
            # add a device staging per bucket to every warm start
            args = self._warm_args(grp, bucket)
            # the lower() trace runs the instrumented evaluator body:
            # the compile counts into stats()['compiles'] and (via the
            # warmup scope) compile.warmup_compiles
            compiled = fn.lower(*args).compile()
            try:
                from jax.experimental import serialize_executable as _se
                payload, in_tree, out_tree = _se.serialize(compiled)
                store.store(key, payload, in_tree, out_tree)
            except Exception as e:  # noqa: BLE001 - cache is best-effort
                self.logger.warning(
                    "serving bucket %d: could not persist the compiled "
                    "executable (%s) — the next replica will recompile",
                    bucket, e)
            loaded = compiled
        grp._jits["fwd_eval"] = loaded
        if source == "deserialized":
            watch.note_cache_hit()
        else:
            watch.note_cache_miss()
        self._register_warm_program(grp, bucket, loaded, key, source)
        return source

    def _register_warm_program(self, grp, bucket, compiled, key,
                               source):
        """Thread the warm bucket through the introspection inventory:
        an ANALYTIC entry measured off the live executable (XLA cost
        analysis works on deserialized executables too), carrying the
        cache key + warm source in its meta — ``programs.*`` reports
        keep working on a warm replica whose jit handles never
        traced."""
        try:
            from .. import telemetry
            analysis = telemetry.analyze_compiled(compiled)
            name = telemetry.inventory().register(
                "%s.fwd_eval" % grp._inventory_owner, kind="fwd_eval",
                n_dev=int(grp.mesh.devices.size),
                device_kind=grp._device_kind,
                flops=analysis.get("flops"),
                bytes_accessed=analysis.get("bytes_accessed"),
                meta={"batch_size": bucket,
                      "mesh_axes": dict(grp.mesh_axes),
                      "warm_source": source, "cache_key": dict(key)})
            grp._program_notes.add("fwd_eval")
            grp._program_names["fwd_eval"] = name
        except Exception:  # noqa: BLE001 - introspection never breaks warmup
            pass

    def release(self):
        """Drop this Predictor's ``serving.<i>`` registry scope (see
        :meth:`ServingStats.release`) — call when discarding a
        Predictor in a long-lived multi-tenant process."""
        self._stats.release()

    def predict(self, data):
        """Serve one request synchronously (no batching): pad to the
        bucket, launch, slice. Returns a single numpy array for
        single-output nets, else a list in ``output_names`` order.
        Thread-safe; for concurrent callers prefer a
        :class:`DynamicBatcher`, which coalesces them into fewer,
        fuller launches."""
        from .. import telemetry
        tracing = telemetry.enabled()
        arrays, rows = self._normalize(data)
        t0 = time.perf_counter()
        self._stats.note_request()
        timing = {} if tracing else None
        outs = self._predict_rows(arrays, rows, timing=timing)
        t1 = time.perf_counter()
        self._stats.note_completed((t1 - t0) * 1000.0)
        if tracing:
            # direct path: no queue, no coalescing — the trace is pad +
            # device + the residual dispatch/slice overhead
            self._stats.note_trace(
                self._stats.new_request_id(), rows,
                self.bucket_for(rows), {
                    "pad_ms": timing.get("pad_ms", 0.0),
                    "device_ms": timing.get("device_ms", 0.0),
                    "resolve_ms": max(
                        (t1 - t0) * 1000.0 - timing.get("pad_ms", 0.0)
                        - timing.get("device_ms", 0.0), 0.0)})
        return outs[0] if len(outs) == 1 else outs

    def _predict_rows(self, arrays, rows, timing=None):
        """Serve ``rows`` normalized rows; always returns the list of
        per-output numpy arrays. The batcher calls this directly (it
        does its own request accounting). ``timing`` (a dict) receives
        accumulated ``pad_ms`` / ``device_ms`` clocks for the request
        trace — chunked oversized requests accumulate across launches."""
        from .. import faults as _faults
        if _faults.armed():
            # device-slowdown seam (kind=delay): a straggling or
            # thermally-throttled device — the latency lands in the
            # device_ms phase and the SLO burn windows, bytes unchanged
            _faults.check("serving.device", rows=rows)
        parts = []
        with self._lock:
            start = 0
            while start < rows:
                take = min(rows - start, self._buckets[-1])
                chunk = {k: v[start:start + take]
                         for k, v in arrays.items()} if (start or
                                                         take < rows) \
                    else arrays
                parts.append(self._run_bucket(self.bucket_for(take),
                                              chunk, take,
                                              timing=timing))
                start += take
        if len(parts) == 1:
            return parts[0]
        return [onp.concatenate([p[i] for p in parts])
                for i in range(len(parts[0]))]

    def _run_bucket(self, bucket, arrays, rows, warmup=False,
                    timing=None):
        """One device launch at ``bucket``: zero-pad the request rows
        up to the bucket's bound shape (the same ``pad_batch_rows``
        rule the predict/score epoch-tail fix uses) and slice the
        outputs back to the real rows."""
        from .. import telemetry
        mod = self._modules[bucket]
        t_pad = time.perf_counter() if timing is not None else 0.0
        batch = DataBatch(
            data=[nd.NDArray(pad_batch_rows(arrays[name], bucket))
                  for name, _ in self._data_descs],
            label=None, pad=bucket - rows)
        if timing is not None:
            t0 = time.perf_counter()
            timing["pad_ms"] = timing.get("pad_ms", 0.0) \
                + (t0 - t_pad) * 1000.0
        with telemetry.span("serving.launch", bucket=bucket, rows=rows):
            mod.forward(batch, is_train=False)
            outs = [o.asnumpy()[:rows] for o in mod.get_outputs()]
        if timing is not None:
            timing["device_ms"] = timing.get("device_ms", 0.0) \
                + (time.perf_counter() - t0) * 1000.0
        self._stats.note_batch(bucket, rows, warmup=warmup)
        return outs
