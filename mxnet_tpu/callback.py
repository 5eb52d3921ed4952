"""Training callbacks.

API counterpart of the reference's python/mxnet/callback.py. Two kinds:

- epoch callbacks ``f(epoch, symbol, arg_params, aux_params)`` invoked by
  ``Module.fit`` after each epoch (checkpointing lives here);
- batch callbacks ``f(BatchEndParam)`` invoked after every batch
  (throughput logging, progress display).

TPU note: train steps dispatch asynchronously — a batch callback that
only looks at ``param.nbatch`` measures the host-side dispatch rate, not
device progress. Callbacks that read ``param.eval_metric`` force the
outputs to materialize, which synchronizes with the device; that is why
``Speedometer`` readings with a metric attached are the honest ones.
"""
from __future__ import annotations

import logging
import math
import time

__all__ = ["module_checkpoint", "do_checkpoint", "log_train_metric",
           "Speedometer", "ProgressBar", "LogValidationMetricsCallback"]


def module_checkpoint(mod, prefix=None, period=1,
                      save_optimizer_states=False, manager=None,
                      async_save=True):
    """Epoch callback: save ``mod`` every ``period`` epochs as
    ``prefix-%04d.params`` (+ ``.states``).

    With ``manager=`` (a :class:`mxnet_tpu.checkpoint
    .CheckpointManager`) the save commits a durable step entry per
    epoch — atomic, async by default (the next epoch's first train
    step overlaps the disk write), sharded per local device shard. The
    step number is the 0-based epoch index just completed, which is
    what ``fit(resume_from=manager)`` reads to continue at the next
    epoch. ``prefix`` may then be omitted; if both are given, the
    legacy prefix files are still written too (for tooling that
    consumes them)."""
    if prefix is None and manager is None:
        raise ValueError("module_checkpoint needs a prefix or a manager")
    period = max(1, int(period))

    def _callback(iter_no, sym=None, arg=None, aux=None):
        epoch = iter_no + 1
        if epoch % period == 0:
            if manager is not None:
                mod.save_checkpoint(prefix, iter_no, save_optimizer_states,
                                    manager=manager, async_save=async_save)
            if prefix is not None:
                mod.save_checkpoint(prefix, epoch, save_optimizer_states)

    return _callback


def do_checkpoint(prefix, period=1):
    """Epoch callback: save the passed symbol+params every ``period``
    epochs (the FeedForward-era twin of :func:`module_checkpoint`)."""
    from .model import save_checkpoint
    period = max(1, int(period))

    def _callback(iter_no, sym, arg, aux):
        epoch = iter_no + 1
        if epoch % period == 0:
            save_checkpoint(prefix, epoch, sym, arg, aux)

    return _callback


def log_train_metric(period, auto_reset=False):
    """Batch callback: log the training metric every ``period`` batches,
    optionally resetting it afterwards (windowed rather than running
    averages)."""

    def _callback(param):
        metric = param.eval_metric
        if metric is None or param.nbatch % period != 0:
            return
        for name, value in metric.get_name_value():
            logging.info("Iter[%d] Batch[%d] Train-%s=%f",
                         param.epoch, param.nbatch, name, value)
        if auto_reset:
            metric.reset()

    return _callback


class Speedometer(object):
    """Batch callback: log samples/sec (and the training metric, if one
    is attached) every ``frequent`` batches. The window restarts at every
    epoch boundary (detected by ``nbatch`` wrapping backwards).

    Stride-aware: ``fit(batch_group=K)`` fires the callback once per
    group with ``nbatch`` advancing by K, so the window counts the
    batches actually seen since the last log (identical behavior at
    stride 1) and the rate is computed from that true count. The metric
    read below is the window's ONE device-tally drain — it happens at a
    group boundary, never mid-group.

    When ``fit`` trains from the async device-feed pipeline
    (``prefetch_to_device=`` / a :class:`mxnet_tpu.data.DeviceLoader`),
    each log line also carries the window's **host-wait fraction** —
    the share of the window's wall time the loop spent blocked on the
    input path (``PipelineStats.host_wait_ms``, read from the
    telemetry registry's active-pipeline slot — ``fit`` publishes the
    loader it trains through via ``telemetry.set_active_pipeline``).
    ~0% means decode + transfer are fully hidden behind the device
    step; a large value means the epoch is input-bound — visible in
    the training log."""

    def __init__(self, batch_size, frequent=50):
        self.batch_size = batch_size
        self.frequent = frequent
        self._tic = None
        self._last_count = 0
        self._seen = 0
        self._wait_seen = None

    @staticmethod
    def _pipeline_stats(param):
        """The PipelineStats of the device-feed loader the CURRENT fit
        trains through (None when fit is host-fed): the telemetry
        registry's active-pipeline registration, which replaced the old
        hack of sniffing ``train_data`` out of the fit loop's locals."""
        from . import telemetry
        return telemetry.active_pipeline()

    def __call__(self, param):
        count = param.nbatch
        # <= not <: nbatch strictly increases WITHIN an epoch, so an
        # equal count is also a new epoch (single-group/single-batch
        # epochs repeat the same nbatch every epoch — with < the wrap
        # never fired and the window silently spanned epochs)
        if count <= self._last_count:
            self._tic = None  # new epoch: restart the timing window
            self._seen = 0
        delta = count - self._last_count
        self._last_count = count

        stats = self._pipeline_stats(param)
        if self._tic is None:
            self._tic = time.time()
            self._seen = 0
            self._wait_seen = stats.snapshot()["host_wait_ms"] \
                if stats is not None else None
            return
        self._seen += delta
        if self._seen < self.frequent:
            return

        elapsed = time.time() - self._tic
        speed = self._seen * self.batch_size / elapsed
        wait_txt = ""
        if stats is not None and self._wait_seen is not None:
            # the window's slice of the cumulative host-wait clock,
            # as a fraction of the window's wall time
            wait_ms = stats.snapshot()["host_wait_ms"] - self._wait_seen
            wait_txt = "\thost-wait=%.1f%%" % (
                100.0 * wait_ms / max(elapsed * 1000.0, 1e-9))
        metric = param.eval_metric
        if metric is not None:
            # reading the metric materializes outputs -> device-synced rate
            pairs = metric.get_name_value()
            metric.reset()
            for name, value in pairs:
                logging.info(
                    "Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec"
                    "\tTrain-%s=%f%s",
                    param.epoch, count, speed, name, value, wait_txt)
        else:
            logging.info("Iter[%d] Batch [%d]\tSpeed: %.2f samples/sec%s",
                         param.epoch, count, speed, wait_txt)
        self._tic = time.time()
        self._seen = 0
        self._wait_seen = stats.snapshot()["host_wait_ms"] \
            if stats is not None else None


class ProgressBar(object):
    """Batch callback: text progress bar over ``total`` batches."""

    def __init__(self, total, length=80):
        self.bar_len = length
        self.total = total

    def __call__(self, param):
        done = int(round(self.bar_len * param.nbatch / float(self.total)))
        pct = math.ceil(100.0 * param.nbatch / float(self.total))
        logging.info("[%s] %s%%\r",
                     "=" * done + "-" * (self.bar_len - done), pct)


class LogValidationMetricsCallback(object):
    """Eval-end callback: log every validation metric for the epoch."""

    def __call__(self, param):
        if not param.eval_metric:
            return
        for name, value in param.eval_metric.get_name_value():
            logging.info("Epoch[%d] Validation-%s=%f",
                         param.epoch, name, value)
