"""Evaluation metrics (reference surface: python/mxnet/metric.py:490).

Same ``EvalMetric`` hierarchy, registry and ``create`` contract as the
reference, but the bodies are TPU-first redesigns rather than ports:

* host ``update`` paths are vectorized numpy (no per-sample Python loops);
* every decomposable builtin also publishes a jax-traceable *fused
  statistic* (:meth:`EvalMetric.fused_stat`) so the mesh Module path can
  accumulate ``(sum, count)`` on device **inside** the fused train step.
  A device->host readback blocks the host on the device, so the
  reference's per-batch ``asnumpy`` metric feed (executor_group.py:510)
  would serialize every step; the fused tally is drained with a
  single readback only when ``get()`` is called (epoch end / Speedometer
  tick). Host and device paths are pinned equal by
  tests/test_device_metric.py.

Subclass contract (kept from the reference): ``self.sum_metric`` /
``self.num_inst`` accumulators, list-valued when ``num`` is given.
"""
from __future__ import annotations

import math

import numpy

from .base import string_types  # noqa: F401  (re-exported for parity)

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy", "Loss",
           "Torch", "Caffe", "CustomMetric", "np", "create"]


def _as_np(x):
    """NDArray / device array / array-like -> host numpy array."""
    if hasattr(x, "asnumpy"):
        return x.asnumpy()
    return numpy.asarray(x)


def check_label_shapes(labels, preds, shape=0):
    """Raise when the label / prediction structure disagrees."""
    got = (labels.shape, preds.shape) if shape else (len(labels), len(preds))
    if got[0] != got[1]:
        raise ValueError("Shape of labels {} does not match shape of "
                         "predictions {}".format(*got))


class EvalMetric(object):
    """Base class for evaluation metrics.

    Tracks a running ``sum_metric / num_inst`` ratio (list-valued when
    ``num`` outputs are scored separately). A metric may additionally be
    bound to a device-side tally by the fused Module path; the tally is
    folded into the host accumulators lazily, on the first ``get()``.
    """

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self._dev_read = None   # () -> numpy (n_slots, 2) device tally
        self._dev_zero = None   # () -> None, resets the device tally
        self.reset()

    # -- accumulation ---------------------------------------------------
    def update(self, label, pred):
        raise NotImplementedError()

    def reset(self):
        many = self.num is not None
        self.sum_metric = [0.0] * self.num if many else 0.0
        self.num_inst = [0] * self.num if many else 0
        if self._dev_zero is not None:
            self._dev_zero()

    # -- reporting ------------------------------------------------------
    def get(self):
        self._drain_device()
        if self.num is None:
            if not self.num_inst:
                return (self.name, float("nan"))
            return (self.name, self.sum_metric / self.num_inst)
        values = [s / n if n else float("nan")
                  for s, n in zip(self.sum_metric, self.num_inst)]
        return (["%s_%d" % (self.name, i) for i in range(self.num)], values)

    def get_name_value(self):
        names, values = self.get()
        names = names if isinstance(names, list) else [names]
        values = values if isinstance(values, list) else [values]
        return list(zip(names, values))

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))

    # -- fused-step bridge ----------------------------------------------
    def fused_stat(self):
        """Device-side statistic for the fused train step, or ``None``.

        When not ``None``: a callable ``stat(jnp, labels, preds) ->
        (sum, count)`` pair of scalars, traceable under ``jax.jit`` and
        numerically equal to what ``update`` would add to
        ``sum_metric`` / ``num_inst`` for the same batch. Metrics whose
        accumulation is not a plain pair-sum (e.g. :class:`CustomMetric`)
        return ``None`` and keep the host path.
        """
        return None

    def _leaf_stats(self):
        """Flat list of per-row stat callables (None entries = host-only)."""
        return [self.fused_stat()]

    def _bind_device_tally(self, reader, zeroer):
        """Attach a device tally (called by the fused Module path)."""
        self._dev_read = reader
        self._dev_zero = zeroer

    def _unbind_device_tally(self):
        self._dev_read = self._dev_zero = None

    def _drain_device(self):
        """Fold the device tally into the host accumulators (one readback)."""
        if self._dev_read is None:
            return
        tally = numpy.asarray(self._dev_read())
        self._dev_zero()
        self._fold_tally(tally)

    def _fold_tally(self, tally):
        self.sum_metric += float(tally[0, 0])
        self.num_inst += int(round(float(tally[0, 1])))

    def _n_slots(self):
        """Rows this metric occupies in a shared device tally."""
        return 1


class CompositeEvalMetric(EvalMetric):
    """Manage several metrics as one (reference CompositeEvalMetric)."""

    def __init__(self, metrics=None, **kwargs):
        super().__init__("composite", **kwargs)
        self.metrics = [] if metrics is None else metrics

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update(self, labels, preds):
        for child in self.metrics:
            child.update(labels, preds)

    def reset(self):
        for child in getattr(self, "metrics", []):
            child.reset()
        if getattr(self, "_dev_zero", None) is not None:
            self._dev_zero()

    def get(self):
        self._drain_device()
        parts = [child.get() for child in self.metrics]
        return ([p[0] for p in parts], [p[1] for p in parts])

    def _leaf_stats(self):
        flat = []
        for child in self.metrics:
            flat.extend(child._leaf_stats())
        return flat

    def fused_stat(self):
        # flattened leaf rows so nested composites line up with the
        # recursive _fold_tally / _n_slots row layout; returns a LIST of
        # per-leaf (sum, count) pairs
        stats = self._leaf_stats()
        if not stats or any(s is None for s in stats):
            return None

        def stat(jnp, labels, preds):
            return [s(jnp, labels, preds) for s in stats]

        stat.n_slots = len(stats)
        return stat

    def _fold_tally(self, tally):
        row = 0
        for child in self.metrics:
            n = child._n_slots()
            child._fold_tally(tally[row:row + n])
            row += n

    def _n_slots(self):
        return sum(child._n_slots() for child in self.metrics)


def _decide_labels(scores, label_shape):
    """Reference rule (metric.py Accuracy / ndarray argmax_channel): when
    prediction and label shapes differ, class scores live on axis 1."""
    if scores.ndim > 1 and scores.shape != tuple(label_shape):
        return scores.argmax(axis=1)
    return scores


class Accuracy(EvalMetric):
    """Classification accuracy; ``pred_index`` scores one output of a
    multi-output (Grouped) symbol — e.g. ``Accuracy(pred_index=0)`` for a
    (softmax, aux_loss) group where only output 0 has a label."""

    def __init__(self, pred_index=None):
        super().__init__("accuracy")
        self.pred_index = pred_index

    def _select(self, preds):
        if self.pred_index is None:
            return preds
        return preds[self.pred_index:self.pred_index + 1]

    def update(self, labels, preds):
        preds = self._select(preds)
        check_label_shapes(labels, preds)
        for lab, out in zip(labels, preds):
            decided = _decide_labels(_as_np(out), tuple(lab.shape))
            got = decided.astype("int64").ravel()
            want = _as_np(lab).astype("int64").ravel()
            check_label_shapes(want, got)
            self.sum_metric += int((got == want).sum())
            self.num_inst += want.size

    def fused_stat(self):
        select = self._select

        def stat(jnp, labels, preds):
            hits = jnp.float32(0.0)
            seen = 0
            for lab, out in zip(labels, select(preds)):
                decided = out.argmax(axis=1) \
                    if out.ndim > 1 and out.shape != lab.shape else out
                eq = decided.astype(jnp.int32).ravel() == \
                    lab.astype(jnp.int32).ravel()
                hits = hits + eq.sum().astype(jnp.float32)
                seen += eq.size
            return hits, jnp.float32(seen)

        return stat


class TopKAccuracy(EvalMetric):
    """Fraction of samples whose label lands in the top-k scores.

    Host path selects the k-set with ``argpartition`` (O(C) per row vs the
    reference's full sort); tie-breaking at the k-boundary is unspecified,
    as in the reference.
    """

    def __init__(self, top_k=1):
        super().__init__("top_k_accuracy")
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += "_%d" % self.top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, out in zip(labels, preds):
            scores = _as_np(out).astype("float32")
            want = _as_np(lab).astype("int64").ravel()
            if scores.ndim == 1:
                hits = int((scores.astype("int64") == want).sum())
            else:
                assert scores.ndim == 2, \
                    "predictions must be at most 2-dimensional"
                k = min(self.top_k, scores.shape[1])
                kset = numpy.argpartition(scores, -k, axis=1)[:, -k:]
                hits = int((kset == want[:, None]).any(axis=1).sum())
            self.sum_metric += hits
            self.num_inst += want.size

    def fused_stat(self):
        top_k = self.top_k

        def stat(jnp, labels, preds):
            import jax.lax as lax
            hits = jnp.float32(0.0)
            seen = 0
            for lab, out in zip(labels, preds):
                want = lab.astype(jnp.int32).ravel()
                if out.ndim == 1:
                    eq = out.astype(jnp.int32) == want
                    hits = hits + eq.sum().astype(jnp.float32)
                else:
                    k = min(top_k, out.shape[1])
                    _, kset = lax.top_k(out.astype(jnp.float32), k)
                    inset = (kset == want[:, None]).any(axis=1)
                    hits = hits + inset.sum().astype(jnp.float32)
                seen += want.size
            return hits, jnp.float32(seen)

        return stat


class F1(EvalMetric):
    """Binary-classification F1, averaged per batch (reference F1)."""

    def __init__(self):
        super().__init__("f1")

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, out in zip(labels, preds):
            scores = _as_np(out)
            want = _as_np(lab).astype("int64").ravel()
            check_label_shapes(want, scores)
            if numpy.unique(want).size > 2:
                raise ValueError(
                    "F1 currently only supports binary classification.")
            got = scores.argmax(axis=1)
            tp = int(((got == 1) & (want == 1)).sum())
            fp = int(((got == 1) & (want == 0)).sum())
            fn = int(((got == 0) & (want == 1)).sum())
            precision = tp / (tp + fp) if tp + fp else 0.0
            recall = tp / (tp + fn) if tp + fn else 0.0
            both = precision + recall
            self.sum_metric += 2.0 * precision * recall / both if both else 0.0
            self.num_inst += 1


class Perplexity(EvalMetric):
    """exp(mean negative log-likelihood); ``ignore_label`` masks padding."""

    def __init__(self, ignore_label, axis=-1):
        super().__init__("Perplexity")
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        nll, count = 0.0, 0
        for lab, out in zip(labels, preds):
            probs = _as_np(out)
            probs = probs.reshape(-1, probs.shape[-1])
            ids = _as_np(lab).astype("int64").ravel()
            chosen = probs[numpy.arange(ids.size), ids]
            keep = numpy.ones(ids.size, bool) if self.ignore_label is None \
                else ids != self.ignore_label
            nll -= float(numpy.log(numpy.maximum(chosen, 1e-10))[keep].sum())
            count += int(keep.sum())
        self.sum_metric += nll
        self.num_inst += count

    def get(self):
        self._drain_device()
        if not self.num_inst:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))

    def fused_stat(self):
        ignore = self.ignore_label

        def stat(jnp, labels, preds):
            nll = jnp.float32(0.0)
            count = jnp.float32(0.0)
            for lab, out in zip(labels, preds):
                probs = out.reshape(-1, out.shape[-1]).astype(jnp.float32)
                ids = lab.astype(jnp.int32).ravel()
                chosen = jnp.take_along_axis(
                    probs, ids[:, None], axis=1)[:, 0]
                logp = jnp.log(jnp.maximum(chosen, 1e-10))
                if ignore is None:
                    nll = nll - logp.sum()
                    count = count + jnp.float32(ids.size)
                else:
                    keep = (ids != ignore).astype(jnp.float32)
                    nll = nll - (logp * keep).sum()
                    count = count + keep.sum()
            return nll, count

        return stat


class _BatchScore(EvalMetric):
    """Regression-style metrics: one score per (label, pred) pair."""

    def _flat_pair(self, lab, out):
        want, got = _as_np(lab), _as_np(out)
        return (want.reshape(want.shape[0], -1),
                got.reshape(got.shape[0], -1))

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, out in zip(labels, preds):
            want, got = self._flat_pair(lab, out)
            self.sum_metric += float(self._score(numpy, want, got))
            self.num_inst += 1

    def fused_stat(self):
        score = self._score

        def stat(jnp, labels, preds):
            total = jnp.float32(0.0)
            for lab, out in zip(labels, preds):
                want = lab.reshape(lab.shape[0], -1).astype(jnp.float32)
                got = out.reshape(out.shape[0], -1).astype(jnp.float32)
                total = total + score(jnp, want, got)
            return total, jnp.float32(len(preds))

        return stat


class MAE(_BatchScore):
    def __init__(self):
        super().__init__("mae")

    @staticmethod
    def _score(xp, want, got):
        return xp.abs(want - got).mean()


class MSE(_BatchScore):
    def __init__(self):
        super().__init__("mse")

    @staticmethod
    def _score(xp, want, got):
        return ((want - got) ** 2).mean()


class RMSE(_BatchScore):
    def __init__(self):
        super().__init__("rmse")

    @staticmethod
    def _score(xp, want, got):
        return xp.sqrt(((want - got) ** 2).mean())


class CrossEntropy(EvalMetric):
    """Mean -log p(label) over samples; ``pred`` rows are probabilities."""

    def __init__(self, eps=1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for lab, out in zip(labels, preds):
            probs = _as_np(out)
            ids = _as_np(lab).ravel().astype("int64")
            assert ids.size == probs.shape[0]
            chosen = probs[numpy.arange(ids.size), ids]
            self.sum_metric += float(-numpy.log(chosen + self.eps).sum())
            self.num_inst += ids.size

    def fused_stat(self):
        eps = self.eps

        def stat(jnp, labels, preds):
            total = jnp.float32(0.0)
            seen = 0
            for lab, out in zip(labels, preds):
                ids = lab.astype(jnp.int32).ravel()
                chosen = jnp.take_along_axis(
                    out.astype(jnp.float32), ids[:, None], axis=1)[:, 0]
                total = total - jnp.log(chosen + eps).sum()
                seen += ids.size
            return total, jnp.float32(seen)

        return stat


class Loss(EvalMetric):
    """Mean of the raw outputs (for MakeLoss heads)."""

    def __init__(self):
        super().__init__("loss")

    def update(self, _, preds):
        for out in preds:
            self.sum_metric += float(_as_np(out).sum())
            self.num_inst += out.size

    def fused_stat(self):
        def stat(jnp, labels, preds):
            total = jnp.float32(0.0)
            seen = 0
            for out in preds:
                total = total + out.astype(jnp.float32).sum()
                seen += out.size
            return total, jnp.float32(seen)

        return stat


class Torch(Loss):
    def __init__(self, name="torch"):
        super(Loss, self).__init__(name)


class Caffe(Torch):
    def __init__(self):
        super().__init__("caffe")


class CustomMetric(EvalMetric):
    """Host-only metric from a user ``feval(label, pred)`` callable."""

    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if "<" in name:
                name = "custom(%s)" % name
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for out, lab in zip(preds, labels):
            got = self._feval(_as_np(lab), _as_np(out))
            if isinstance(got, tuple):
                part_sum, part_n = got
                self.sum_metric += part_sum
                self.num_inst += part_n
            else:
                self.sum_metric += got
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy eval function as a metric (reference ``metric.np``)."""

    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


_REGISTRY = {
    "acc": Accuracy, "accuracy": Accuracy, "ce": CrossEntropy,
    "f1": F1, "mae": MAE, "mse": MSE, "rmse": RMSE,
    "top_k_accuracy": TopKAccuracy, "perplexity": Perplexity,
    "loss": Loss,
}


def create(metric, **kwargs):
    """Create a metric from a name / callable / list (``metric.create``)."""
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(child)
        return composite
    try:
        return _REGISTRY[metric.lower()](**kwargs)
    except Exception:
        raise ValueError("Metric must be either callable or in {}".format(
            sorted(_REGISTRY)))
