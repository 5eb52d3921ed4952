"""BaseModule — the canonical train/score/predict loops
(python/mxnet/module/base_module.py:952; ``fit`` at :368-519).
"""
from __future__ import annotations

import logging
import os
import time
from collections import namedtuple

from .. import faults as _faults
from .. import metric as metric_mod
from .. import ndarray as nd
from ..initializer import Uniform

__all__ = ["BaseModule", "BatchEndParam"]

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _as_list(obj):
    if obj is None:
        return []
    return obj if isinstance(obj, list) else [obj]


# process-level advisory dedupe (see BaseModule._warn_once): keyed by
# (key, rendered message) so fresh Module instances — bench reps,
# serving buckets — never re-spam an identical advisory
_WARNED_PROCESS = set()


def pad_batch_rows(arr, target_rows):
    """Zero-pad ``arr`` (NDArray, numpy, or jax array) along axis 0 up
    to ``target_rows`` and return the raw padded array — the ONE
    pad-and-slice rule every fixed-shape launch shares: the serving
    bucketer (``mxnet_tpu.serving.Predictor``) pads requests up to
    their batch bucket, and the predict/score epoch-tail fix
    (``Module._pad_eval_tail``) pads the final partial batch to the
    bound shape.  Host arrays pad host-side (staging stays one
    ``device_put``); device-resident arrays pad on device (a host
    round trip here would be a blocking readback)."""
    import numpy as onp
    vals = arr._read() if hasattr(arr, "_read") else arr
    n = vals.shape[0]
    if n >= target_rows:
        return vals
    if isinstance(vals, onp.ndarray):
        fill = onp.zeros((target_rows - n,) + vals.shape[1:], vals.dtype)
        return onp.concatenate([vals, fill])
    import jax.numpy as jnp
    fill = jnp.zeros((target_rows - n,) + tuple(vals.shape[1:]),
                     vals.dtype)
    return jnp.concatenate([vals, fill])


def stack_group_inputs(batches, data_names, label_names, sharding):
    """K batches -> {input name: stacked (K, batch, ...) block} — the
    ONE rule pairing a group's arrays with their bound input names
    (every data input; a label only when every batch in the group
    provides it).  Shared by the grouped train step
    (``Module._grouped_stage``) and the device-feed stager
    (``mxnet_tpu.data.DeviceLoader._stage_block``), so the two can
    never drift on label handling.  Blocks stack by
    :func:`_stack_batch_arrays` for staging onto ``sharding`` (host
    blocks contiguous, blocks on the mesh's backend stacked there)."""
    stacked = {}
    for i, name in enumerate(data_names):
        stacked[name] = _stack_batch_arrays(
            [b.data[i] for b in batches], sharding)
    if label_names and batches[0].label:
        for i, name in enumerate(label_names):
            if i < len(batches[0].label) and \
                    all(b.label[i] is not None for b in batches):
                stacked[name] = _stack_batch_arrays(
                    [b.label[i] for b in batches], sharding)
    return stacked


def _stack_batch_arrays(arrs, sharding):
    """K per-batch arrays -> one (K, batch, ...) block — the ONE
    stacking rule for every grouped launch (grouped training and
    grouped predict) that stages onto ``sharding``.  Host inputs —
    numpy values, and arrays on another backend than the sharding's,
    taken by their host view (``dist.staging.host_view``) — stack into
    one contiguous numpy block, so staging is a single ``device_put``;
    any input resident on the sharding's backend stacks with jnp on
    device (an ``onp.stack`` there would be K blocking readbacks).
    ``input.h2d_bytes`` counts here what is numpy as it comes in: once
    stacked, a block no longer shows whether a producer counted it."""
    import numpy as onp
    from .. import telemetry
    from ..dist.staging import host_view
    vals = []
    for a in arrs:
        v = a._read() if hasattr(a, "_read") else a
        if isinstance(v, onp.ndarray):
            telemetry.count("input.h2d_bytes", v.nbytes)
        vals.append(host_view(v, sharding))
    if all(isinstance(v, onp.ndarray) for v in vals):
        return onp.stack(vals)
    import jax.numpy as jnp
    return jnp.stack(vals)


def _poison_batch_seam(batch, module, epoch, nbatch):
    """The ``module.step`` numeric seam (armed plans only): a fired
    ``grad_nonfinite``/``loss_spike`` rule scales the step's first
    FLOATING data input by the injected factor (NaN / the spike
    value) — the deterministic spelling of a poisoned batch the
    training guardian must detect and roll past. Context carries the
    data coordinate (``epoch``/``nbatch``) plus the upcoming 0-based
    optimizer step (``step``). Device-resident batches scale on
    device; integer wire batches (u8 device-augment) pass through
    untouched (documented carve-out)."""
    factor = _faults.poison(
        "module.step", epoch=epoch, nbatch=nbatch,
        step=int(getattr(getattr(module, "_optimizer", None),
                         "num_update", -1)))
    if factor is None:
        return batch
    import numpy as onp
    from ..io import DataBatch
    data = list(batch.data)
    for i, d in enumerate(data):
        vals = d._read() if hasattr(d, "_read") else d
        dtype = getattr(vals, "dtype", None)
        if dtype is not None and \
                onp.issubdtype(onp.dtype(dtype), onp.floating):
            data[i] = nd.NDArray(vals * onp.dtype(dtype).type(factor))
            break
    return DataBatch(data=data, label=batch.label, pad=batch.pad,
                     index=getattr(batch, "index", None))


class BaseModule(object):
    """Abstract training-capable component: computation + parameters +
    the fit/score/predict drivers."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0
        self._warned_once = set()
        self._resume_skip = None  # (epoch, batches) mid-epoch resume

    def _warn_once(self, key, msg, *args):
        """Log ``msg`` at WARNING the first time it fires in this
        PROCESS, DEBUG afterwards.  The per-instance set alone was not
        enough: workloads that build a fresh Module per fit (bench
        reps, serving buckets, sweep scripts) re-warned the identical
        advisory through the root logger on every instance and drowned
        the bench output's tail.  The process-level set dedupes on the
        RENDERED message, so genuinely different advisories (other
        shapes, other reasons) still warn once each."""
        rendered = (msg % args) if args else msg
        if key in self._warned_once or \
                (key, rendered) in _WARNED_PROCESS:
            self.logger.debug(msg, *args)
        else:
            self._warned_once.add(key)
            _WARNED_PROCESS.add((key, rendered))
            self.logger.warning(msg, *args)

    # ------------------------------------------------------------------
    # high-level drivers
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        """One fused training step (base_module.py:191)."""
        self.forward(data_batch, is_train=True)
        self.backward()

    # -- shared driver plumbing ----------------------------------------
    def _eval_batches(self, eval_data, num_batch, reset):
        """Yield up to ``num_batch`` (index, batch) pairs — the limit /
        reset pattern every driver loop shares."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for index, batch in enumerate(eval_data):
            if index == num_batch:
                return
            yield index, batch

    def _fire(self, callbacks, epoch, nbatch, eval_metric, caller_locals):
        if not callbacks:
            return
        event = BatchEndParam(epoch=epoch, nbatch=nbatch,
                              eval_metric=eval_metric,
                              locals=caller_locals)
        for callback in _as_list(callbacks):
            callback(event)

    def _unpadded_outputs(self, batch, copy=False):
        # pad = iterator pad rows + any rows forward() itself added to
        # run an epoch-tail batch at the bound shape (_pad_eval_tail)
        pad = (batch.pad or 0) + getattr(self, "_eval_pad_extra", 0)
        keep = slice(None) if not pad else slice(0, -pad)
        outs = [out[keep] for out in self.get_outputs()]
        return [o.copy() for o in outs] if copy else outs

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate on a data iterator (base_module.py:196).

        With telemetry enabled every eval batch writes a
        :class:`StepTimeline` record with the SAME shape as the fit
        loops' (``loop="eval"``, streamed as ``{"kind": "eval_step"}``
        JSONL lines), so a served/eval regression is visible to the
        health watchdog on the same wire as a train-step one."""
        from .. import telemetry
        span = telemetry.span
        eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        seen = 0
        tl = telemetry.timeline() if telemetry.enabled() else None
        with span("score", epoch=epoch):
            batches = self._eval_batches(eval_data, num_batch, reset)
            while True:
                try:
                    with span("score.next") as s_next:
                        index, batch = next(batches)
                except StopIteration:
                    break
                with span("score.forward") as s_forward:
                    self.forward(batch, is_train=False)
                with span("score.metric") as s_metric:
                    self.update_metric(eval_metric, batch.label)
                    self._fire(batch_end_callback, epoch, index,
                               eval_metric, locals())
                seen = index + 1
                if tl is not None:
                    rec = tl.record(
                        epoch, index,
                        host_wait_ms=s_next.ns * 1e-6,
                        dispatch_ms=s_forward.ns * 1e-6,
                        metric_cb_ms=s_metric.ns * 1e-6,
                        loop="eval")
                    telemetry.log_event("eval_step", rec)
        if telemetry.enabled():
            telemetry.registry().counter("eval.batches").add(seen)
        if score_end_callback:
            self._fire(score_end_callback, epoch, seen, eval_metric,
                       locals())
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        for index, batch in self._eval_batches(eval_data, num_batch, reset):
            self.forward(batch, is_train=False)
            yield (self._unpadded_outputs(batch), index, batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False, batch_group=None):
        """Forward over an iterator, collecting outputs (base_module.py:293).

        ``batch_group=K`` (fused mesh path only) scores K batches per
        XLA launch through the stacked scoring program — on devices with
        multi-ms launch overhead this is the difference between
        launch-bound and compute-bound small-batch inference (PERF.md).
        Semantics are identical to the per-batch loop (pad handling,
        output order, merge_batches)."""
        group = getattr(self, "_exec_group", None)
        if batch_group and batch_group > 1:
            if getattr(group, "fused", False):
                assert self.binded and self.params_initialized
                if reset:
                    eval_data.reset()
                return self._predict_grouped(eval_data, num_batch,
                                             merge_batches, batch_group,
                                             always_output_list)
            self.logger.warning(
                "predict(batch_group=%d) requires the fused mesh "
                "executor group; falling back to per-batch scoring",
                batch_group)
        from .. import telemetry
        collected = []
        with telemetry.span("predict"):
            for _index, batch in self._eval_batches(eval_data, num_batch,
                                                    reset):
                self.forward(batch, is_train=False)
                collected.append(self._unpadded_outputs(batch, copy=True))
        if telemetry.enabled():
            telemetry.registry().counter(
                "eval.predict_batches").add(len(collected))
        return self._merge_predict_outputs(collected, merge_batches,
                                           always_output_list)

    @staticmethod
    def _merge_predict_outputs(output_list, merge_batches,
                               always_output_list):
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " \
                    "in mini-batches. Maybe bucketing is used?"
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def _predict_grouped(self, eval_data, num_batch, merge_batches,
                         batch_group, always_output_list):
        """K-batches-per-launch predict via the stacked scoring program."""
        group = self._exec_group
        data_names = [d[0] for d in group.data_shapes]
        label_names = getattr(group, "_label_names", [])
        output_list = []
        chunk, pads = [], []
        chunk_names = None  # data + provided-label names of this chunk

        def read(d):
            # _read() keeps device-resident batches on device (the
            # shared stacker keeps them there); .asnumpy() here would
            # be a blocking D2H per batch
            return d._read() if hasattr(d, "_read") else d

        def flush():
            if not chunk:
                return
            stacked = {name: _stack_batch_arrays([b[i] for b in chunk],
                                                 group._batch_sharding)
                       for i, name in enumerate(chunk_names)}
            outs = group.score_stacked(stacked)
            for k, pad in enumerate(pads):
                output_list.append([
                    nd.NDArray(o[k][:o.shape[1] - pad]) for o in outs])
            chunk.clear()
            pads.clear()

        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            arrs = [read(d) for d in eval_batch.data]
            names = list(data_names)
            # bound label inputs must stage like the per-batch path does
            # (zero-filled labels would silently change label-dependent
            # outputs, e.g. loss heads); names align with the non-None
            # label positions so a partial label list stages correctly
            if label_names and eval_batch.label:
                for name, lb in zip(label_names, eval_batch.label):
                    if lb is not None:
                        arrs.append(read(lb))
                        names.append(name)
            if chunk and (names != chunk_names
                          or arrs[0].shape != chunk[0][0].shape):
                flush()  # ragged tail batch gets its own (smaller) group
            chunk_names = names
            chunk.append(arrs)
            pads.append(eval_batch.pad or 0)
            if len(chunk) == batch_group:
                flush()
        flush()
        return self._merge_predict_outputs(output_list, merge_batches,
                                           always_output_list)

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, resume_from=None, batch_group=None,
            prefetch_to_device=None, guardian=None):
        """Train on a data iterator — the canonical loop
        (base_module.py:368-519).

        ``resume_from`` restarts an interrupted run: pass a
        :class:`mxnet_tpu.checkpoint.CheckpointManager` (or its
        directory path, or an already-restored ``Checkpoint``) and the
        latest committed entry's parameters, optimizer/updater states,
        and global RNG state are restored after init, with
        ``begin_epoch`` advanced past the checkpointed epoch. An empty
        manager is not an error — training simply starts fresh, which
        makes ``resume_from=`` safe to pass unconditionally.

        ``batch_group=K`` (fused mesh path) trains K batches per XLA
        launch: the loop assembles K iterator batches into ONE stacked
        host block, stages it with ONE ``device_put``, and runs K whole
        fwd+bwd+optimizer steps as one scanned device program
        (``MeshExecutorGroup.step_update_grouped``) — the
        iterations-per-loop pattern that amortizes fixed per-transfer
        and per-launch costs.  Numerics (params,
        optimizer state, lr schedule, metric values) match per-batch
        training exactly for rng-free nets; nets with rng ops (e.g.
        Dropout) draw independent per-step key streams inside the
        group instead of reproducing the host key sequence — same
        carve-out as the pipelined schedule.  ``batch_end_callback``
        fires once per group with ``nbatch`` = index of the group's
        last batch, and the epoch tail forms a final smaller group.
        Requires a fusable optimizer and a device-talliable metric;
        otherwise fit warns once and trains per batch.

        ``guardian=`` (a :class:`mxnet_tpu.guardian.Guardian`, a
        checkpoint-directory path, or ``MXNET_GUARDIAN=1`` +
        ``MXNET_GUARDIAN_DIR``) arms the training guardian: a
        device-resident numeric-health word rides the one-program
        train step (zero step-path readbacks) and is polled at each
        epoch boundary; a non-finite loss/grad/param, a loss spike, or
        an SDC parity-probe mismatch triggers rollback-and-skip — fit
        restores the newest verifiable checkpoint entry preceding the
        poisoned data coordinate and replays the deterministic stream
        with that batch excluded, bounded by the guardian's
        ``max_rollbacks``. Off (the default) it costs one branch and
        the fit digest is bitwise-identical to a build without it.

        ``prefetch_to_device=N`` (``True`` means depth 2) wraps
        ``train_data`` in a :class:`mxnet_tpu.data.DeviceLoader`: a
        background stager keeps a ring of N batches ALREADY resident
        on device (mesh-sharded on the fused path), so host decode,
        host->device transfer, and the device step fully overlap and
        the loop's own staging becomes a no-op on arrival.  Batches
        are bitwise identical to plain iteration — trained params
        stay bit-equal to an unprefetched run (CI-gated).  Composes
        with ``batch_group=K``: the stager assembles whole K-blocks
        and stages each through ``stage_stacked``, one transfer per
        K steps.  The per-epoch log reports the epoch's
        ``PipelineStats.host_wait_ms`` — nonzero means the input
        path, not the device, paced the epoch."""
        assert num_epoch is not None, "please specify number of epochs"
        from .. import telemetry
        # the root span `fit` and this call's report: every phase below
        # lands in it, and it is what `telemetry.last_fit()` returns
        with telemetry.fit_scope() as report:
            # u8 device-augment pipelines (mxnet_tpu.data.DeviceAugmentIter
            # / CachedDataset / ImageRecordIter(device_augment="defer"))
            # advertise their in-program augment spec; adopt it so the bind
            # below compiles the augment stage into the step program and
            # stages the 4x-smaller uint8 wire batches
            aug_spec = getattr(train_data, "device_augment_spec", None)
            if aug_spec and not self.binded and \
                    getattr(self, "_device_augment", None) == {}:
                self._device_augment = dict(aug_spec)

            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
            if monitor is not None:
                self.install_monitor(monitor)
            self.init_params(initializer=initializer, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)
            # never inherit a previous fit's mid-epoch skip marker: a resume
            # whose target epoch was outside [begin_epoch, num_epoch) would
            # otherwise leak it into a LATER fit and silently drop batches
            self._resume_skip = None
            if resume_from is not None:
                begin_epoch = self._resume_from(resume_from, begin_epoch)

            from .. import guardian as guardian_mod
            guardian = guardian_mod.resolve(guardian)
            if guardian is not None and \
                    not guardian.arm(self, begin_epoch):
                guardian = None     # cannot carry the sentinel; unguarded

            if validation_metric is None:
                validation_metric = eval_metric
            # materialize the validation metric ONCE for the whole fit: a
            # string here used to reach score() every epoch, which created
            # a FRESH metric object per eval pass — and a fresh metric
            # means a fresh device-tally token, so every epoch's eval
            # recompiled its fwd_eval_stat program (a per-epoch XLA compile
            # the CompileWatch flagged as a post-warmup retrace the moment
            # the introspection gate ran a multi-epoch eval fit)
            validation_metric = metric_mod.create(validation_metric)
            eval_metric = metric_mod.create(eval_metric)
            # fused mesh modules accumulate the metric on device inside the
            # train-step program (no per-batch readback; see
            # MeshExecutorGroup.enable_device_metric). No-op elsewhere.
            self._install_device_metric(eval_metric)

            group_k = int(batch_group) if batch_group else 0
            # monitor check is belt-and-braces: install_monitor already
            # re-binds fused modules onto the classic group, which fails
            # _fit_grouped_ready — but a grouped step has no per-batch
            # boundaries for taps, so gate on it explicitly
            if group_k > 1 and (monitor is not None or
                                not self._fit_grouped_ready(eval_metric)):
                self._warn_once(
                    "fit_batch_group",
                    "fit(batch_group=%d) needs the fused mesh path with a "
                    "fusable optimizer and a device-talliable metric (and "
                    "no monitor); falling back to per-batch training",
                    group_k)
                group_k = 0

            loader = None
            if prefetch_to_device:
                # created AFTER bind: the loader reads the bound executor
                # group's shardings so its background device_put lands each
                # per-device shard exactly where _stage would
                from ..data import DeviceLoader
                depth = 2 if prefetch_to_device is True \
                    else int(prefetch_to_device)
                loader = DeviceLoader(
                    train_data, module=self, depth=depth,
                    batch_group=group_k if group_k > 1 else None)
                train_data = loader
            try:
                self._fit_epochs(train_data, eval_data, eval_metric,
                                 validation_metric, begin_epoch, num_epoch,
                                 group_k, monitor, batch_end_callback,
                                 epoch_end_callback, eval_end_callback,
                                 eval_batch_end_callback, guardian, report)
            finally:
                if loader is not None:
                    loader.close()
                if guardian is not None:
                    guardian.disarm()

            # dist_async trains with a staleness-1 in-flight reduction per key;
            # quiesce so the final gradients are applied before fit returns
            # (kvstore.push contract)
            self._drain_async_kvstore()

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, begin_epoch, num_epoch, group_k,
                    monitor, batch_end_callback, epoch_end_callback,
                    eval_end_callback, eval_batch_end_callback,
                    guardian, report):
        """The epoch loop of ``fit`` (split out so the device-feed
        loader's lifetime can bracket it).

        Telemetry (``mxnet_tpu.telemetry``): the loop's phases are
        ``telemetry.span``s that tile it (``fit.epoch`` holding
        ``fit.next`` / ``fit.forward_backward`` / ``fit.update`` /
        ``fit.metric``, then ``fit.epoch_end``), always: they lie in any
        open profiler trace and fill ``report``, this call's
        :class:`~mxnet_tpu.telemetry.FitReport`. When telemetry is
        enabled, every step also writes one :class:`StepTimeline`
        record from the same clock reads (host-wait / dispatch /
        metric+callback / checkpoint, recompile flag) and one
        ``"step"`` JSONL line, and a :class:`CompileWatch` attaches to
        the executor group with the warmup boundary declared after the
        FIRST epoch of this fit (every steady shape — epoch tails, the
        eval pass — has compiled by then). The process
        RegressionWatchdog is armed at the same warmup boundary
        (``MXNET_TELEMETRY_WATCHDOG=0`` opts out) and polled between
        epochs — a steady-state slowdown, straggler or post-warmup
        retrace becomes ONE structured
        ``health.*`` incident. All clocks are host-side: no readback, no RNG
        touch, so trained params stay bitwise identical to a
        telemetry-off run (the zero-perturbation contract, ci.sh-gated).
        The device-feed loader's ``PipelineStats`` is published as
        ``telemetry.set_active_pipeline`` for the whole fit — that is
        where ``Speedometer`` reads host-wait from — independent of the
        enabled flag (it is a registration, not a recording)."""
        from .. import telemetry
        pipe_stats = getattr(train_data, "pipeline_stats", None)
        wait_seen = pipe_stats.snapshot()["host_wait_ms"] \
            if pipe_stats is not None else 0.0
        tl = watch = None
        if telemetry.enabled():
            tl = telemetry.timeline()
            watch = telemetry.compile_watch()
            watch.attach(self)
        telemetry.set_active_pipeline(pipe_stats)
        try:
            self._fit_epochs_inner(
                train_data, eval_data, eval_metric, validation_metric,
                begin_epoch, num_epoch, group_k, monitor,
                batch_end_callback, epoch_end_callback, eval_end_callback,
                eval_batch_end_callback, pipe_stats, wait_seen, tl, watch,
                guardian, report)
        except BaseException as exc:
            # crash black box: an exception escaping the train loop —
            # WorkerLost, preemption, a real bug — commits a postmortem
            # of the last retained step records before unwinding, IF a
            # FlightRecorder has been armed (ElasticTrainer arms one;
            # MXNET_TELEMETRY_BLACKBOX arms at import). Unarmed: no-op.
            recorder = telemetry.flight_recorder()
            if recorder.armed:
                try:
                    recorder.dump("fit: %s: %s" % (type(exc).__name__,
                                                   exc))
                except Exception:  # noqa: BLE001 - never mask the fault
                    self.logger.exception("flight-recorder dump failed")
            raise
        finally:
            telemetry.set_active_pipeline(None)
            if watch is not None:
                # a later fit's first epoch may legitimately compile
                watch.reset_warmup()

    def _fit_epochs_inner(self, train_data, eval_data, eval_metric,
                          validation_metric, begin_epoch, num_epoch,
                          group_k, monitor, batch_end_callback,
                          epoch_end_callback, eval_end_callback,
                          eval_batch_end_callback, pipe_stats, wait_seen,
                          tl, watch, guardian, report):
        from .. import telemetry
        span = telemetry.span
        wd = None   # regression watchdog, armed at the warmup boundary
        # a while loop, not a range: the guardian's rollback-and-skip
        # re-enters an EARLIER epoch after restoring a pre-poison
        # checkpoint; "warmed" replaces the epoch == begin_epoch test
        # so the warmup boundary is the first HEALTHY epoch end
        warmed = False
        epoch = begin_epoch
        while epoch < num_epoch:
            tic = time.time()
            eval_metric.reset()
            if hasattr(train_data, "set_epoch"):
                # pin the iterator's epoch coordinate to the TRUE epoch
                # index: a resumed run then replays exactly the stream
                # the uninterrupted run saw at this epoch (ShardedDataIter
                # / VirtualFeed seed by (seed, epoch, batch, rank))
                train_data.set_epoch(epoch)
            skip = 0
            if self._resume_skip and self._resume_skip[0] == epoch:
                # mid-epoch resume (step-granular checkpoint): the first
                # `skip` batches of this epoch were already trained
                # before the preemption — pull and discard them so the
                # stream position matches the checkpointed trajectory
                skip = self._resume_skip[1]
                self._resume_skip = None
            if guardian is not None:
                guardian.begin_epoch(self, epoch)
            mid_verdict = None
            with span("fit.epoch", epoch=epoch):
                if group_k > 1:
                    mid_verdict = self._fit_epoch_grouped(
                        train_data, epoch, group_k, eval_metric,
                        batch_end_callback, report, tl, watch,
                        skip=skip, guardian=guardian)
                else:
                    nbatch = -1
                    data_iter = iter(train_data)
                    if skip and hasattr(train_data, "skip_batches"):
                        # iterators with a cheap position-only advance
                        # (ShardedDataIter/VirtualFeed) skip without
                        # paying transform/staging for discarded data
                        nbatch += train_data.skip_batches(skip)
                    else:
                        for _ in range(skip):
                            try:
                                next(data_iter)
                            except StopIteration:
                                break
                            nbatch += 1
                    while True:
                        step = report.steps
                        try:
                            # the pull that ends the epoch is clocked
                            # like any other: its time is the epoch's
                            with span("fit.next", step=step) as s_next:
                                data_batch = next(data_iter)
                        except StopIteration:
                            break
                        nbatch += 1
                        if guardian is not None and \
                                guardian.should_skip(epoch, nbatch):
                            # a convicted coordinate: pull and DISCARD
                            # (the stream position advances, the
                            # poisoned batch never trains)
                            guardian.note_skipped(epoch, nbatch)
                            continue
                        if _faults.armed():
                            data_batch = _poison_batch_seam(
                                data_batch, self, epoch, nbatch)
                        n_traces = watch.count if watch is not None else 0
                        if monitor is not None:
                            monitor.tic()
                        with span("fit.forward_backward",
                                  step=step) as s_fwd_bwd:
                            self.forward_backward(data_batch)
                        with span("fit.update", step=step) as s_update:
                            self.update()
                        if guardian is not None:
                            guardian.note_step(epoch, nbatch)
                        s_metric = span("fit.metric", step=step)
                        try:
                            with s_metric:
                                self.update_metric(eval_metric,
                                                   data_batch.label)
                                if monitor is not None:
                                    monitor.toc_print()
                                self._fire(batch_end_callback, epoch,
                                           nbatch, eval_metric, locals())
                        finally:
                            # the record is written even when a callback
                            # raises (WorkerLost, preemption hooks): the
                            # FAILING step must appear in the timeline —
                            # it is the flight-recorder postmortem's
                            # last record
                            if tl is not None:
                                self._timeline_step(
                                    tl, epoch, nbatch, s_next.ns,
                                    s_fwd_bwd.ns + s_update.ns,
                                    s_metric.ns, 1,
                                    watch.count > n_traces)
                        report.steps += 1
                        if guardian is not None:
                            # window-boundary poll (long epochs): a
                            # full ring since the last bracket is
                            # judged NOW, before the spike scrolls out
                            mid_verdict = guardian.maybe_poll_window(
                                self, epoch)
                            if mid_verdict is not None:
                                break

            # everything between two epochs: the metric drain (a
            # readback that waits for every step), the guardian's poll,
            # parameter sync, callbacks, eval and the iterator's reset
            with span("fit.epoch_end", epoch=epoch):
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                     val)
                # what the symbol's ops counted on the device this
                # epoch, read with the metric's drain
                for name, val in self._read_op_counters().items():
                    telemetry.count(name, val)
                cost = time.time() - tic
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, cost)
                if pipe_stats is not None:
                    # the epoch's slice of the cumulative host-wait
                    # clock: how long THIS epoch's steps sat blocked on
                    # the input path (0 = the device feed fully hid
                    # decode+transfer)
                    snap = pipe_stats.snapshot()
                    wait_ms = snap["host_wait_ms"] - wait_seen
                    wait_seen = snap["host_wait_ms"]
                    self.logger.info(
                        "Epoch[%d] Host-wait=%.1fms (%.1f%% of epoch, "
                        "ring high-water %d/%d)", epoch, wait_ms,
                        100.0 * wait_ms / max(cost * 1000.0, 1e-9),
                        snap["ring_high_water"], snap["ring_depth"])

                if guardian is not None:
                    # the off-path judgment pass, BEFORE the epoch-end
                    # callback: a poisoned epoch must neither checkpoint
                    # nor eval — rollback restores a pre-poison entry
                    # and re-enters the (possibly earlier) epoch with
                    # the convicted batch excluded from the replayed
                    # stream
                    verdict = mid_verdict if mid_verdict is not None \
                        else guardian.poll(self, epoch)
                    if verdict is not None:
                        epoch = guardian.rollback(self, verdict)
                        train_data.reset()
                        continue

                # classic modules keep the reference's unconditional
                # epoch-end get_params+set_params (it is load-bearing:
                # bucketing keeps sibling executors coherent through
                # it); the fused Module overrides _epoch_end_sync to
                # skip the ~1s packed readback when no callback consumes
                # the params — its device params are the single
                # authority, so nothing needs re-broadcast
                params = self._epoch_end_sync(
                    epoch_end_callback is not None)
                if epoch_end_callback is not None:
                    with span("fit.epoch_end_callback",
                              epoch=epoch) as s_callback:
                        arg_params, aux_params = params
                        for callback in _as_list(epoch_end_callback):
                            callback(epoch, self.symbol, arg_params,
                                     aux_params)
                    if tl is not None:
                        # checkpoint staging dominates this callback
                        # slot; attributed to the step it actually
                        # delayed. The epoch's step JSONL lines already
                        # streamed, so the sink gets this as its own
                        # event instead
                        cb_ms = s_callback.ns * 1e-6
                        tl.note_checkpoint(cb_ms)
                        telemetry.log_event(
                            "checkpoint",
                            {"epoch": epoch,
                             "checkpoint_ms": round(cb_ms, 3)})

                if eval_data:
                    with span("fit.eval", epoch=epoch):
                        res = self.score(
                            eval_data, validation_metric,
                            score_end_callback=eval_end_callback,
                            batch_end_callback=eval_batch_end_callback,
                            epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)

                train_data.reset()
                if tl is not None:
                    wd = self._telemetry_epoch_end(watch, wd, warmed,
                                                   epoch)
                warmed = True
                report.epochs += 1
                epoch += 1

    def _telemetry_epoch_end(self, watch, wd, warmed, epoch):
        """What an enabled telemetry does between two epochs; returns
        the regression watchdog (armed at the first healthy epoch's end,
        polled at every later one)."""
        from .. import telemetry
        if not warmed:
            # every steady-state shape (epoch tails, grouped tail
            # blocks, the eval pass) has now traced once: from here
            # on a retrace is a performance bug worth a warning
            watch.mark_warmup_done()
            if os.environ.get("MXNET_TELEMETRY_WATCHDOG", "1") != "0":
                # arm the regression watchdog at the same boundary:
                # records from here on are steady state. Baseline
                # comes from a committed snapshot when pinned
                # (MXNET_TELEMETRY_BASELINE), else the first polled
                # window self-calibrates. Polls run between epochs
                # — host arithmetic only, never on the step path.
                # Diagnostics, never fit control: a bad baseline
                # path must not kill the training run at the epoch
                # boundary.
                try:
                    wd = telemetry.health_watchdog().arm(
                        baseline=os.environ.get(
                            "MXNET_TELEMETRY_BASELINE") or None)
                except Exception:  # noqa: BLE001
                    self.logger.exception(
                        "health watchdog failed to arm; "
                        "continuing unwatched")
                    wd = None
        elif wd is not None:
            try:
                wd.poll()
            except Exception:  # noqa: BLE001 - diagnostics only
                self.logger.exception("health watchdog poll failed")
        # loss-scaler skip decisions, polled off-path at the
        # same boundary loss_scale() is read: a skip storm
        # becomes a precision.scale_skips gauge the watchdog's
        # absolute judge watches (one readback per epoch, only
        # when a scaling policy is live)
        skips = getattr(self._exec_group, "scale_skips",
                        lambda: None)() \
            if getattr(self, "_exec_group", None) is not None \
            else None
        if skips is not None:
            telemetry.registry().gauge(
                "precision.scale_skips").set(skips)
        telemetry.flush_metrics("epoch %d" % epoch)
        return wd

    def _timeline_step(self, tl, epoch, nbatch, next_ns, dispatch_ns,
                       metric_ns, batch_group, recompile):
        """One step's (or group's) :class:`StepTimeline` record and
        ``"step"`` JSONL line, from the clock reads of the loop's
        spans."""
        from .. import telemetry
        rec = tl.record(epoch, nbatch, host_wait_ms=next_ns * 1e-6,
                        dispatch_ms=dispatch_ns * 1e-6,
                        metric_cb_ms=metric_ns * 1e-6,
                        batch_group=batch_group, recompile=recompile)
        telemetry.log_event("step", rec)

    def _fit_epoch_grouped(self, train_data, epoch, group_k, eval_metric,
                           batch_end_callback, report, tl=None, watch=None,
                           skip=0, guardian=None):
        """One epoch of K-batches-per-program training (``fit``'s
        ``batch_group`` path).  Assembly of block N+1 runs on the host
        while the device computes block N, and the single ``device_put``
        per block is issued asynchronously — double-buffered staging
        falls out of the readback-free loop, no extra machinery.  The
        epoch tail (fewer than K batches left) forms its own smaller
        group; a batch whose shapes disagree with the open group also
        flushes first (bucketed iterators).

        The spans carry the per-batch loop's names around the same
        work, so ``report`` has the same shape: the K pulls are K
        ``fit.next``, assembling and staging the block is
        ``fit.forward_backward``, the scanned launch ``fit.update``.
        With telemetry enabled (``tl`` = the StepTimeline, ``watch`` =
        the CompileWatch) each GROUP writes one step record: the K
        iterator pulls' accumulated host-wait, the staging and the
        scanned launch's dispatch time, and ``batch_group`` = the
        group's true size."""
        from .. import telemetry
        span = telemetry.span
        group = []
        group_nbatches = []   # each member's nbatch (skips make gaps)
        nbatch = -1
        wait_ns = [0]   # host-wait accumulated across the open group

        def _flush(last_nbatch, caller_locals):
            step = report.steps
            n_traces = watch.count if watch is not None else 0
            group_n = len(group)
            if guardian is not None:
                # ordinal->nbatch bookkeeping BEFORE the launch: the
                # scanned program counts each of the K steps
                for nb in group_nbatches:
                    guardian.note_step(epoch, nb)
            with span("fit.forward_backward", step=step) as s_fwd_bwd:
                staged = self._grouped_stage(group)
            dispatch_ns = s_fwd_bwd.ns
            stepped = False
            if staged is not None:
                with span("fit.update", step=step) as s_update:
                    stepped = self._grouped_update(staged)
                dispatch_ns += s_update.ns
            if not stepped:
                # gate said grouped was possible but the step declined
                # (e.g. optimizer swapped mid-fit): keep exact semantics
                # by training this group per batch
                for b in group:
                    with span("fit.forward_backward",
                              step=step) as s_fwd_bwd:
                        self.forward_backward(b)
                    with span("fit.update", step=step) as s_update:
                        self.update()
                    dispatch_ns += s_fwd_bwd.ns + s_update.ns
                    with span("fit.metric", step=step):
                        self.update_metric(eval_metric, b.label)
            s_metric = span("fit.metric", step=step)
            try:
                with s_metric:
                    if stepped:
                        # the group's K statistics are already in the
                        # device tally; this consumes the step-done flag
                        # like the per-batch loop's update_metric does
                        self.update_metric(eval_metric, group[-1].label)
                    self._fire(batch_end_callback, epoch, last_nbatch,
                               eval_metric, caller_locals)
            finally:
                # record even on a raising callback — the failing
                # group must be the postmortem's last record (same
                # contract as the per-batch loop)
                if tl is not None:
                    self._timeline_step(
                        tl, epoch, last_nbatch, wait_ns[0],
                        dispatch_ns, s_metric.ns, group_n,
                        watch.count > n_traces)
            report.steps += group_n
            wait_ns[0] = 0
            del group[:]
            del group_nbatches[:]

        def _shape_sig(b):
            # data AND label shapes: a label-shape change mid-group
            # would otherwise crash the block stack instead of flushing
            sig = [tuple(d.shape) for d in b.data]
            for lb in (b.label or []):
                sig.append(tuple(lb.shape) if lb is not None else None)
            return sig

        open_sig = None
        data_iter = iter(train_data)
        # mid-epoch resume fast-forward (checkpoint commits land on
        # group boundaries, so the skip is always group-aligned)
        if skip and hasattr(train_data, "skip_batches"):
            nbatch += train_data.skip_batches(skip)
        else:
            for _ in range(skip):
                try:
                    next(data_iter)
                except StopIteration:
                    break
                nbatch += 1
        while True:
            try:
                with span("fit.next",
                          step=report.steps + len(group)) as s_next:
                    data_batch = next(data_iter)
            except StopIteration:
                break
            nbatch += 1
            if guardian is not None and \
                    guardian.should_skip(epoch, nbatch):
                # the convicted batch drops out of its group (the tail
                # group forms one batch smaller, same as an epoch tail)
                guardian.note_skipped(epoch, nbatch)
                continue
            if _faults.armed():
                data_batch = _poison_batch_seam(data_batch, self, epoch,
                                                nbatch)
            wait_ns[0] += s_next.ns
            sig = _shape_sig(data_batch)
            if group and sig != open_sig:
                _flush(nbatch - 1, locals())
            if not group:
                open_sig = sig
            group.append(data_batch)
            group_nbatches.append(nbatch)
            if len(group) == group_k:
                _flush(nbatch, locals())
                if guardian is not None:
                    # window-boundary poll at a group boundary (the
                    # per-batch loop's long-epoch seam, K at a time)
                    verdict = guardian.maybe_poll_window(self, epoch)
                    if verdict is not None:
                        return verdict
        if group:
            _flush(nbatch, locals())
        return None

    def _fit_grouped_ready(self, eval_metric):
        """Whether ``fit(batch_group=K)`` can run grouped device steps.
        Default: no — the fused mesh Module overrides."""
        return False

    def _grouped_stage(self, batches):
        """First half of a grouped step: assemble K batches into one
        block per input and stage it on the device.  Returns what
        :meth:`_grouped_update` takes, or None to decline (the default),
        and the caller falls back to per-batch steps."""
        return None

    def _grouped_update(self, staged):
        """Second half: train the staged block as ONE scanned device
        program.  Returns True when handled."""
        return False

    def _resume_from(self, resume_from, begin_epoch):
        """Restore training state from a checkpoint and return the epoch
        to continue at (``fit(resume_from=...)`` plumbing). Accepts a
        CheckpointManager, its directory path, or a restored
        ``Checkpoint``; a manager with no committed entry resumes
        nothing and returns ``begin_epoch`` unchanged."""
        from .. import random as random_mod
        from ..checkpoint import CheckpointManager, split_params
        if isinstance(resume_from, str):
            resume_from = CheckpointManager(resume_from)
        if isinstance(resume_from, CheckpointManager):
            if resume_from.latest() is None:
                self.logger.info(
                    "resume_from: no committed checkpoint in %s; "
                    "starting fresh", resume_from.directory)
                return begin_epoch
            ckpt = resume_from.restore()
        else:
            ckpt = resume_from
        arg_np, aux_np = split_params(ckpt.params)
        self.set_params(
            {k: nd.array(v, dtype=v.dtype) for k, v in arg_np.items()},
            {k: nd.array(v, dtype=v.dtype) for k, v in aux_np.items()})
        if ckpt.optimizer_state is not None and \
                hasattr(self, "load_optimizer_states"):
            self.load_optimizer_states(ckpt.optimizer_state)
        if ckpt.rng is not None:
            random_mod.set_state(ckpt.rng)
        epoch = int(ckpt.extra.get("epoch", ckpt.step))
        nbatch = ckpt.extra.get("nbatch")
        if nbatch is not None:
            # a STEP-granular entry (ElasticTrainer's per-K-updates
            # commits): re-enter the interrupted epoch and fast-forward
            # past the batches already trained. The data stream replays
            # deterministically (fit pins the iterator's epoch via
            # set_epoch), so the resumed trajectory is the continuous
            # one — the elastic-resume bitwise contract.
            self._resume_skip = (epoch, int(nbatch) + 1)
            self.logger.info(
                "resumed from checkpoint step %d (continuing at epoch "
                "%d, skipping %d trained batch(es))", ckpt.step, epoch,
                int(nbatch) + 1)
            return epoch
        self.logger.info("resumed from checkpoint step %d "
                         "(continuing at epoch %d)", ckpt.step, epoch + 1)
        return epoch + 1

    # ------------------------------------------------------------------
    # properties / abstract interface
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        from ..checkpoint import save_params_file
        arg_params, aux_params = self.get_params()
        save_params_file(fname, arg_params, aux_params)

    def load_params(self, fname):
        from ..checkpoint import load_params_file
        arg_params, aux_params = load_params_file(fname)
        self.set_params(arg_params, aux_params)

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def _install_device_metric(self, eval_metric):
        """Hook for subclasses that can tally the metric on device inside
        the fused train step; the default (host ``update_metric``) path
        needs nothing."""

    def _read_op_counters(self):
        """Hook: what the symbol's ops counted on the device since the
        last call, ``{name: number}`` (``registry.count``)."""
        return {}

    def _drain_async_kvstore(self):
        """Flush a dist_async store's in-flight reductions at fit end.
        Wrapper modules (Bucketing/Sequential) forward to the module(s)
        that actually own a kvstore."""
        kv = getattr(self, "_kvstore", None)
        if kv is not None and "async" in getattr(kv, "type", ""):
            kv.barrier()

    def _epoch_end_params(self):
        """Params handed to epoch_end_callback. The default refreshes and
        re-broadcasts like the reference loop; the fused Module skips the
        redundant re-upload (device params are authoritative there)."""
        arg_params, aux_params = self.get_params()
        self.set_params(arg_params, aux_params)
        return arg_params, aux_params

    def _epoch_end_sync(self, need_params):
        """End-of-epoch parameter refresh inside ``fit``. The default is
        the reference's unconditional get+set round trip (base_module.py
        :468-471 in the reference) — classic groups rely on the
        re-broadcast. Returns the params when ``need_params``."""
        return self._epoch_end_params()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
