"""DeviceLoader — a bounded ring of batches already resident on device.

The reference overlaps host decode with compute through
``PrefetcherIter``'s host-side double buffer (iter_prefetcher.h:129) —
but on an accelerator the host->device TRANSFER is a third pipeline
stage the reference never had to hide: unhidden, every ``device_put``
sits on the step's critical path.  The DeviceLoader is the tf.data/infeed design
for this stack: a background stager thread pulls host batches from any
``DataIter`` and dispatches ``jax.device_put`` for batch i+1/i+2 while
the device still computes batch i, keeping a bounded ring (depth 2-3)
of batches ALREADY on device.  Host decode, transfer, and compute then
fully overlap; the consumer's ``next()`` only ever waits when the input
path truly cannot keep up — and that wait is measured
(``PipelineStats.host_wait_ms``), not guessed.

Placement is mesh-aware: bound to a fused-mesh ``Module``, each input
is placed with the group's ``NamedSharding`` (``device_put`` splits the
host array into per-device shards directly — no host-side concat, no
intermediate single-device copy), so ``Module.fit``'s own ``_stage``
becomes a no-op on already-resident arrays and the trained parameters
stay BITWISE equal to an unprefetched run.

One source class opts OUT of background staging: an iterator whose
delivery launches a collective device program (``ShardedCachedDataset``
— its dp-sharded gather all-gathers rows across shards) advertises
``background_pull_safe = False``, and the loader pulls it on the
consumer thread instead.  Collectives must enqueue in program order on
every device; racing the training step's collectives from a stager
thread interleaves the per-device rendezvous — a deadlock on XLA:CPU
and a cross-host ordering hazard on a real pod.  Nothing is lost: the
gather output is already device-resident, so there is no transfer for
the ring to hide.  With ``batch_group=K`` the
stager assembles K iterator batches into one contiguous ``(K, B, ...)``
host block and stages it through the group's shared ``stage_stacked``
helper — one transfer per K steps, the grouped train program consumes
the block without re-staging.
"""
from __future__ import annotations

import threading
import time

import numpy as onp

from ..base import MXNetError
from .. import faults as _faults
from .. import ndarray as nd
from ..io import DataBatch, DataIter
from .stats import PipelineStats

__all__ = ["DeviceLoader"]

_END = object()


def _host_value(arr):
    return arr._read() if hasattr(arr, "_read") else arr


def _batch_wire_stats(batches):
    """(bytes, dtype) a group of batches puts on the transport: the
    sum of every HOST array's nbytes (a device-resident array — e.g.
    a CachedDataset gather output — passes through ``device_put``
    without a transfer and counts 0), and the IMAGE (first data
    entry) dtype — uint8 on the u8 wire path, float32 on the classic
    host-assemble path."""
    total = 0
    for b in batches:
        for a in b.data:
            v = _host_value(a)
            if isinstance(v, onp.ndarray):
                total += int(v.nbytes)
    first = _host_value(batches[0].data[0])
    return total, getattr(first, "dtype", None)


class DeviceLoader(DataIter):
    """Wrap ``data_iter`` so every delivered batch is device-resident.

    Parameters
    ----------
    data_iter : DataIter
        Host-side source (NDArrayIter, ImageRecordIter, a
        :class:`TransformIter`, ...).  Pulled from the stager thread
        only.
    module : Module, optional
        A BOUND module: its executor group supplies the target
        shardings (batch inputs on the ``dp`` axis; ``(K, B, ...)``
        blocks through ``stage_stacked``).  Without a module, batches
        are placed whole on the default device — fine for a single
        device, wrong for a mesh.
    depth : int
        Ring bound: maximum batches resident on device at once
        (2-3 is the sweet spot — enough to hide one transfer behind
        one step without tying up HBM).
    batch_group : int, optional
        Stage blocks of K batches through ``stage_stacked`` for
        ``fit(batch_group=K)`` — one transfer and one scanned program
        per K steps.  The epoch tail forms a final smaller block.
    stats : PipelineStats, optional
        Shared counter block; a fresh one is created by default and
        exposed as ``.pipeline_stats`` (``Speedometer`` and the fit
        epoch log read it from there).
    close_source : bool
        Also close ``data_iter`` (when it has a ``close``) from this
        loader's ``close()``.  Default False: the loader does not own
        an iterator the caller built — ``fit(prefetch_to_device=)``
        closes only the loader it created, never the caller's
        iterator.
    """

    def __init__(self, data_iter, module=None, depth=2, batch_group=None,
                 stats=None, close_source=False, restart_on_error=None):
        super().__init__(getattr(data_iter, "batch_size", 0))
        depth = int(depth)
        if depth < 1:
            raise MXNetError("depth must be >= 1 (got %d)" % depth)
        if restart_on_error is None:
            import os
            restart_on_error = os.environ.get(
                "MXNET_FAULT_STAGER_RESTART", "0") == "1"
        # error-propagation contract: a stager error is always
        # delivered IN ORDER on the consumer thread; by default the
        # epoch is then over (reset() recovers). With
        # ``restart_on_error`` the stager instead relaunches after the
        # delivery, so a consumer that catches the error keeps
        # iterating the surviving stream (the chaos-soak posture).
        self._restart_on_error = bool(restart_on_error)
        group = int(batch_group) if batch_group else 0
        if group == 1:
            group = 0
        self._iter = data_iter
        self._depth = depth
        self._group = group
        self._close_source = bool(close_source)
        self._owns_stats = stats is None
        self.pipeline_stats = stats or PipelineStats(ring_depth=depth)
        self.pipeline_stats.ring_depth = depth
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self._data_names = [d[0] for d in self.provide_data]
        self._label_names = [d[0] for d in (self.provide_label or [])]

        self._group_handle = None
        if module is not None:
            grp = getattr(module, "_exec_group", None)
            if grp is None or not getattr(grp, "fused", False):
                # classic per-executor groups slice the batch per
                # context host-side; background-staging whole batches
                # would be wasted work there
                module = None
            else:
                self._group_handle = grp
        self._module = module
        # wire-format attribution: where the augment stage runs for
        # batches staged through this loader, and (set per stage) what
        # dtype crossed the transport
        grp = self._group_handle
        self.pipeline_stats.augment_placement = \
            "device" if grp is not None and \
            getattr(grp, "_device_augment", None) else \
            getattr(data_iter, "augment_placement", None) or "host"
        # u8 pipelines advertise their spec; forward it so a manually
        # built DeviceLoader can still be handed straight to fit()
        self.device_augment_spec = getattr(data_iter,
                                           "device_augment_spec", None)

        # a source whose delivery launches COLLECTIVE device programs
        # (ShardedCachedDataset's dp-sharded gather) must be pulled on
        # the CONSUMER thread: collectives enqueue in program order on
        # every device, and a background launch racing the training
        # step's collectives can interleave the per-device rendezvous
        # (deadlock on XLA:CPU, ordering hazard on a pod).  Such
        # batches are already device-resident — there is no transfer
        # for the ring to hide — so the loader degrades to a
        # pass-through that still keeps the stats wire.
        self._passthrough = not getattr(data_iter,
                                        "background_pull_safe", True)
        self._cond = threading.Condition()
        self._ring = []          # staged entries, delivery order
        self._closed = False
        self._stager = None
        self._start_epoch(reset_source=False)

    # -- staging -------------------------------------------------------
    def _stage_batch(self, batch):
        """Place one host batch on device, preserving the exact bytes
        ``MeshExecutorGroup._stage`` would transfer: with a group the
        put IS its staging rule (``stage_sharded``: an array on another
        backend than the mesh's goes up from its host view)."""
        import jax
        from ..dist.staging import stage_sharded
        grp = self._group_handle

        def place(v):
            if grp is None:
                return jax.device_put(v)
            return stage_sharded(v, grp._batch_sharding,
                                 (grp.batch_size,) + tuple(v.shape[1:]))

        def put(arr):
            v = _host_value(arr)
            if _faults.armed():
                # transient transfer fault: healed by the shared
                # bounded-backoff retry — the SAME bytes land on
                # retry, so trained params stay bitwise identical.
                # The retry scaffolding lives under the armed branch:
                # unarmed staging pays one branch, nothing more.
                def attempt():
                    _faults.check("data.device_put")
                    return place(v)
                return _faults.retry(attempt, site="data.device_put")
            return place(v)

        data = [nd.NDArray(put(d)) for d in batch.data]
        label = None
        if batch.label:
            label = [None if lb is None else nd.NDArray(put(lb))
                     for lb in batch.label]
        return DataBatch(data=data, label=label, pad=batch.pad,
                         index=batch.index,
                         provide_data=batch.provide_data,
                         provide_label=batch.provide_label)

    def _stage_block(self, batches):
        """K host batches -> ONE contiguous (K, B, ...) block per input,
        staged through the group's ``stage_stacked`` (one ``device_put``
        per input).  Delivered as per-batch views onto the block, each
        carrying the staged dict so ``Module._grouped_stage`` can hand
        the block straight to the scanned program."""
        from ..module.base_module import stack_group_inputs
        # the stacking rule: host batches (numpy, or arrays on another
        # backend than the mesh's) form ONE contiguous numpy block
        # (single device_put), batches resident on the mesh's backend
        # (CachedDataset gathers) stack with jnp ON DEVICE — an
        # onp.stack there would be K blocking readbacks
        stacked = stack_group_inputs(
            batches, self._data_names, self._label_names,
            self._group_handle._batch_sharding)
        if _faults.armed():
            def attempt():
                _faults.check("data.device_put", group=len(batches))
                return self._group_handle.stage_stacked(stacked)
            staged = _faults.retry(attempt, site="data.device_put")
        else:
            staged = self._group_handle.stage_stacked(stacked)
        out = []
        for j, b in enumerate(batches):
            # augmented groups: stage_stacked consumed the wire param
            # arrays and replaced the u8 block with the f32 model view
            # — the views carry whatever inputs the staged block kept
            data = [nd.NDArray(staged[n][j]) for n in self._data_names
                    if n in staged]
            label = None
            if b.label:
                label = [nd.NDArray(staged[n][j]) if n in staged
                         else b.label[i]
                         for i, n in enumerate(self._label_names)
                         if i < len(b.label)]
            view = DataBatch(data=data, label=label, pad=b.pad,
                             index=b.index)
            view._staged_block = staged
            view._staged_index = j
            view._staged_size = len(batches)
            out.append(view)
        return out

    def _stage_entry(self):
        """Pull + stage the next ring entry (a list of delivered
        batches).  Returns _END at epoch end, an exception to re-raise
        in order, or the staged batches."""
        from .. import telemetry
        if _faults.armed():
            # stager-crash seam: raises BEFORE any source pull, so a
            # restarted stager resumes the stream with nothing lost.
            # Transient kinds heal in place through the shared retry;
            # permanent kinds escape to the consumer as the crash.
            _faults.retry(
                lambda: _faults.check("data.stager", group=self._group),
                site="data.stager")
        if self._group:
            pulled = []
            for _ in range(self._group):
                try:
                    pulled.append(self._iter.next())
                except StopIteration:
                    break
            if not pulled:
                return _END
            nbytes, dtype = _batch_wire_stats(pulled)
            t0 = time.perf_counter()
            with telemetry.span("data.stage_block", k=len(pulled)):
                if self._group_handle is not None and len(pulled) > 0 and \
                        self._uniform_shapes(pulled):
                    staged = self._stage_block(pulled)
                else:
                    staged = [self._stage_batch(b) for b in pulled]
            rows = sum(b.data[0].shape[0] for b in staged)
            self.pipeline_stats.note_staged(rows, time.perf_counter() - t0,
                                            nbytes, dtype)
            return staged
        try:
            batch = self._iter.next()
        except StopIteration:
            return _END
        nbytes, dtype = _batch_wire_stats([batch])
        t0 = time.perf_counter()
        with telemetry.span("data.stage"):
            staged = self._stage_batch(batch)
        self.pipeline_stats.note_staged(staged.data[0].shape[0],
                                        time.perf_counter() - t0,
                                        nbytes, dtype)
        return [staged]

    @staticmethod
    def _uniform_shapes(batches):
        """A block must stack; ragged shapes (bucketed iterators) fall
        back to per-batch staging — fit's grouped loop flushes on the
        shape change anyway."""
        def sig(b):
            s = [tuple(d.shape) for d in b.data]
            for lb in (b.label or []):
                s.append(tuple(lb.shape) if lb is not None else None)
            return s

        first = sig(batches[0])
        return all(sig(b) == first for b in batches[1:])

    # -- stager thread -------------------------------------------------
    def _run_stager(self, epoch):
        while True:
            with self._cond:
                while not self._stop and len(self._ring) >= self._depth:
                    if not self._noted_full:
                        self._noted_full = True
                        self.pipeline_stats.note_ring_full()
                    self._cond.wait(0.05)
                if self._stop:
                    return
                self._noted_full = False
            try:
                entry = self._stage_entry()
            except Exception as exc:  # noqa: BLE001 — re-raised in order
                entry = exc
            with self._cond:
                if self._stop or epoch != self._live_epoch:
                    return
                self._ring.append(entry)
                self.pipeline_stats.note_ring(len(self._ring))
                self._cond.notify_all()
                if entry is _END or isinstance(entry, BaseException):
                    return

    def _start_epoch(self, reset_source):
        self._stop_stager()
        if reset_source:
            self._iter.reset()
        with self._cond:
            self._ring = []
            self._pending = []   # staged batches popped but undelivered
            self._stop = False
            self._exhausted = False
            self._noted_full = False
            self._live_epoch = getattr(self, "_live_epoch", -1) + 1
        if not reset_source:
            # construction: start pre-filling right away.  After a
            # reset() the stager restarts LAZILY on the first next():
            # an eager restart would pull batches from the source that
            # a close() (e.g. fit's, after the final epoch's reset)
            # silently drops — the caller's iterator must come out of
            # a prefetched fit in the same state a plain fit leaves it
            self._launch_stager()

    def _launch_stager(self):
        if self._stager is not None:
            return
        if not self._passthrough and \
                not getattr(self._iter, "background_pull_safe", True):
            # re-evaluated at every (lazy, per-epoch) launch, not just
            # construction: a sharded cache built against a module that
            # binds AFTER the loader flips unsafe once its collective
            # gather exists — a stale construction-time snapshot would
            # background exactly the launch this protocol serializes
            self._passthrough = True
        if self._passthrough:
            return
        with self._cond:
            epoch = self._live_epoch
        self._stager = threading.Thread(
            target=self._run_stager, args=(epoch,),
            name="mxtpu-device-stager", daemon=True)
        self._stager.start()

    def _restart_stager(self):
        """Recover from a delivered stager error: join the (already
        returned) stager thread and rebase the epoch tag so a fresh
        stager relaunches on the next ``next()``, continuing the
        source stream from where the crash left it."""
        from .. import telemetry
        self._stop_stager()
        with self._cond:
            self._stop = False
            self._exhausted = False
            self._noted_full = False
            self._live_epoch += 1
        telemetry.registry().counter("data.stager_restarts").add()

    def _stop_stager(self):
        stager = self._stager
        if stager is None:
            return
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        stager.join()
        self._stager = None
        with self._cond:
            self._ring = []
            self._pending = []

    # -- DataIter surface ----------------------------------------------
    def _next_passthrough(self):
        """Consumer-thread pull for collective-gather sources: one
        batch through the normal staging rule (a no-op device_put for
        the already-resident gather output), with delivery/staging
        stats kept so the pipeline wire reads the same."""
        t0 = time.perf_counter()
        batch = self._iter.next()       # StopIteration ends the epoch
        nbytes, dtype = _batch_wire_stats([batch])
        t1 = time.perf_counter()
        staged = self._stage_batch(batch)
        self.pipeline_stats.note_staged(staged.data[0].shape[0],
                                        time.perf_counter() - t1,
                                        nbytes, dtype)
        self.pipeline_stats.note_delivered(staged.data[0].shape[0],
                                           t1 - t0)
        return staged

    def next(self):
        if self._closed:
            raise MXNetError("DeviceLoader is closed")
        if self._passthrough:
            return self._next_passthrough()
        if self._stager is None:
            self._launch_stager()
            if self._passthrough:
                # the lazy launch just re-evaluated the source's
                # background_pull_safe and flipped to pass-through (a
                # cache finalized with a collective gather since the
                # last epoch): route there instead of waiting on a
                # ring no stager will ever fill
                return self._next_passthrough()
        if self._pending:
            batch = self._pending.pop(0)
            self.pipeline_stats.note_delivered(batch.data[0].shape[0],
                                               0.0)
            return batch
        t0 = time.perf_counter()
        with self._cond:
            if self._exhausted:
                # the stager exited at epoch end (or on an error it
                # already delivered) — keep raising StopIteration like
                # every DataIter does until reset(), instead of waiting
                # on a ring that can never refill
                raise StopIteration
            while not self._ring:
                if self._stop:
                    raise MXNetError("DeviceLoader was reset/closed "
                                     "while a next() was blocked")
                self._cond.wait(0.05)
            entry = self._ring.pop(0)
            if entry is _END or (isinstance(entry, BaseException)
                                 and not self._restart_on_error):
                self._exhausted = True
            self.pipeline_stats.note_ring(len(self._ring))
            self._cond.notify_all()
        wait = time.perf_counter() - t0
        if entry is _END:
            raise StopIteration
        if isinstance(entry, BaseException):
            if self._restart_on_error:
                # the stager exited when it delivered this error; join
                # it and relaunch LAZILY so a consumer that catches the
                # error keeps iterating the surviving stream
                self._restart_stager()
            raise entry
        batch = entry[0]
        self._pending = list(entry[1:])
        self.pipeline_stats.note_delivered(batch.data[0].shape[0], wait)
        return batch

    def iter_next(self):
        try:
            self._current = self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._current.data

    def getlabel(self):
        return self._current.label

    def getpad(self):
        return self._current.pad

    def getindex(self):
        return self._current.index

    def _note_cache_stats(self):
        """Forward the source dataset-cache's resolved tier/bytes into
        the pipeline stats (once it finalizes) — the watchdog and
        bench then read the same wire the cache resolved."""
        info_fn = getattr(self._iter, "cache_info", None)
        if info_fn is None:
            return
        try:
            info = info_fn()
        except Exception:  # noqa: BLE001 — attribution, never delivery
            return
        if info.get("tier"):
            self.pipeline_stats.note_cache(
                info["tier"],
                info.get("shard_bytes", info.get("bytes", 0)),
                info.get("rows", 0))

    def reset(self):
        """Rewind for a fresh epoch: cancel+join the stager and reset
        the source; the stager restarts lazily on the next ``next()``,
        so a reset consumes NOTHING from the source.  Repeatedly
        callable; never delivers a stale pre-reset batch."""
        if self._closed:
            raise MXNetError("DeviceLoader is closed")
        self._start_epoch(reset_source=True)
        # a CachedDataset/ShardedCachedDataset source finalizes its
        # cache inside its reset(): pick up the resolved tier now
        self._note_cache_stats()

    def set_epoch(self, epoch):
        """Forward ``fit``'s epoch-coordinate pin to the source (the
        seeded-stream iterators: DeviceAugmentIter, CachedDataset,
        ShardedDataIter).  A no-op when the source is already at
        ``epoch`` — the construction-time prefill stays valid; a real
        rebase cancels the stager and drops any batches staged under
        the stale coordinate (the stager restarts lazily)."""
        if self._closed:
            raise MXNetError("DeviceLoader is closed")
        fwd = getattr(self._iter, "set_epoch", None)
        if fwd is None:
            return
        self._note_cache_stats()
        coord = getattr(self._iter, "epoch_coord", None)
        if coord is None:
            # coordinate-less wrapper (e.g. a PrefetchingIter over
            # non-pinnable sources): its set_epoch is a no-op by the
            # protocol contract (sources that ACT on set_epoch expose
            # epoch_coord), so forward the pin without paying a rebase
            # — dropping the ring every epoch would defeat the prefill
            fwd(epoch)
            return
        if coord == int(epoch):
            return
        self._stop_stager()
        # the dropped ring batches were already PULLED from the source
        # under the stale coordinate — rewind it before pinning, or the
        # rebased epoch would start short by the prefilled batches
        self._iter.reset()
        fwd(epoch)
        with self._cond:
            self._ring = []
            self._pending = []
            self._stop = False
            self._exhausted = False
            self._noted_full = False
            self._live_epoch += 1

    # -- lifecycle -----------------------------------------------------
    def close(self):
        """Stop and join the stager thread, dropping the ring
        (idempotent).  The source iterator is left usable unless the
        loader was built with ``close_source=True``."""
        if self._closed:
            return
        self._closed = True
        self._stop_stager()
        if self._owns_stats:
            # this loader created the stats: retire their registry
            # scope so fit-per-call workloads don't grow the registry
            # unboundedly (the object stays readable for post-mortems)
            self.pipeline_stats.release()
        if self._close_source:
            inner_close = getattr(self._iter, "close", None)
            if callable(inner_close):
                inner_close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
