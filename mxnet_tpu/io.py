"""Data iterators (python/mxnet/io.py:747 + src/io/ registered iterators).

The reference's C++ iterator chain (parser → augmenter → normalizer →
batcher → prefetcher, SURVEY.md §2.4) becomes host-side numpy stages feeding
device transfer; ``PrefetchingIter`` reproduces the dmlc::ThreadedIter
double-buffering (iter_prefetcher.h:129) with a background thread so input
decode overlaps TPU steps. ImageRecordIter lives in image.py / recordio.py.
"""
from __future__ import annotations

import collections
import gzip
import os
import struct
import threading

import numpy as onp

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray, array

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "MNISTIter",
           "CSVIter", "ResizeIter", "PrefetchingIter"]


class DataDesc(collections.namedtuple("DataDesc", ["name", "shape"])):
    """Name+shape (+dtype/layout) descriptor for a data source."""

    def __new__(cls, name, shape, dtype=onp.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype,
                                          self.layout)

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch(object):
    """One mini-batch: lists of data/label NDArrays + pad/index."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter(object):
    """Base data iterator (python/mxnet/io.py DataIter)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def __iter__(self):
        return self

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


class ResizeIter(DataIter):
    """Resize another iterator to ``size`` batches per epoch (io.py:199)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def set_epoch(self, epoch):
        """Forward fit's epoch-coordinate pin to the wrapped iterator.

        Seeded-stream sources then replay deterministically on
        resume."""
        fwd = getattr(self.data_iter, "set_epoch", None)
        if fwd is not None:
            fwd(epoch)

    @property
    def epoch_coord(self):
        return getattr(self.data_iter, "epoch_coord", None)

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Background-thread prefetcher (io.py:285; the reference's C++
    PrefetcherIter wraps dmlc::ThreadedIter the same way).  Not needed
    around ``ImageRecordIter``, which runs ahead of its caller by
    itself (wrapped, it is still correct, and one thread more).

    Lifecycle: ``close()`` (or the context-manager exit) stops and
    JOINS the worker threads — they used to be fire-and-forget daemons
    that leaked one thread per iterator instance and could race a
    late ``reset()``.  ``reset()`` is safe to call repeatedly and
    while a prefetch is in flight: it synchronizes on the in-flight
    fetch completing before the underlying iterators rewind, so no
    worker ever reads a source mid-reset (the one pre-reset batch a
    worker already fetched is discarded, matching the reference's
    ThreadedIter semantics).  For the N-worker transformed version of
    this pattern see :class:`mxnet_tpu.data.TransformIter`; for
    device-resident double buffering,
    :class:`mxnet_tpu.data.DeviceLoader`."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                # timed wait so a close() that lands between this
                # worker's data_taken.clear() and its next wait cannot
                # strand it (close's set() would be consumed by the
                # clear and a bare wait() would sleep forever — the
                # join-hang this close/join design replaces)
                while not self.data_taken[i].wait(0.1):
                    if not self.started:
                        return
                if not self.started:
                    return
                try:
                    self.next_batch[i] = self.iters[i].next()
                except StopIteration:
                    self.next_batch[i] = None
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)]
        for thread in self.prefetch_threads:
            thread.start()

    def close(self):
        """Stop and join the prefetch workers (idempotent).

        The prefetcher cannot be used afterwards; the wrapped source
        iterators stay usable (they belong to the caller).  Also runs
        via the context-manager exit and (best-effort) the
        finalizer."""
        if not getattr(self, "started", False):
            return
        self.started = False
        for e in self.data_taken:
            e.set()
        for thread in self.prefetch_threads:
            thread.join()

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

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(r[x[0]], x[1])
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        """Rewind every source for a fresh epoch (safe to repeat).

        Waits for any in-flight prefetch to land first (so the
        sources are never rewound under a concurrent fetch) and
        discards that pre-reset batch; calling it again immediately —
        or after the epoch exhausted — is safe and does the same
        dance."""
        if not self.started:
            raise MXNetError("PrefetchingIter is closed")
        for e in self.data_ready:
            e.wait()
        for i in self.iters:
            i.reset()
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def set_epoch(self, epoch):
        """Forward fit's epoch-coordinate pin to every source.

        Cheap when nothing actually moves: sources already at
        ``epoch``, and coordinate-less wrappers (whose ``set_epoch``
        is a no-op by the protocol contract — sources that ACT on the
        pin expose ``epoch_coord``) just receive the forward and the
        prefetched batch stays valid.  A real rebase waits for the
        in-flight prefetch, discards it, REWINDS every source (the
        discarded batch was already pulled from all of them under the
        stale coordinate) and pins the new epoch."""
        if not self.started:
            raise MXNetError("PrefetchingIter is closed")
        fwds = [getattr(i, "set_epoch", None) for i in self.iters]
        if not any(fwds):
            return
        if all(fwd is None
               or getattr(i, "epoch_coord", None) in (None, int(epoch))
               for i, fwd in zip(self.iters, fwds)):
            # forward ONLY to coordinate-less wrappers (their pin is a
            # no-op by contract).  A source already AT the epoch must
            # NOT be re-pinned: reset()'s eager prefetch consumed its
            # draw 0, and zeroing its sequence counter would make the
            # next batch re-draw it
            for i, fwd in zip(self.iters, fwds):
                if fwd is not None and \
                        getattr(i, "epoch_coord", None) is None:
                    fwd(epoch)
            return
        for e in self.data_ready:
            e.wait()
        # the discarded in-flight batch was pulled from EVERY source:
        # rewind them all (not just the pinnable ones), or co-iterated
        # label/data streams would skew by one batch after the rebase
        for i, fwd in zip(self.iters, fwds):
            i.reset()
            if fwd is not None:
                fwd(epoch)
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    @property
    def epoch_coord(self):
        """The sources' common epoch coordinate (None when mixed or
        none are pinnable) — lets an outer DeviceLoader's no-op check
        keep its prefill instead of rebasing spuriously."""
        coords = {getattr(i, "epoch_coord", None) for i in self.iters}
        coords.discard(None)
        return coords.pop() if len(coords) == 1 else None

    def iter_next(self):
        if not self.started:
            raise MXNetError("PrefetchingIter is closed")
        for e in self.data_ready:
            e.wait()
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iterators"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Number of entry mismatches between iterators"
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad, self.next_batch[0].index)
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _init_data(data, allow_empty, default_name):
    """Normalize input to list of (name, numpy) pairs (io.py _init_data)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (onp.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = collections.OrderedDict([(default_name, data[0])])
        else:
            data = collections.OrderedDict(
                [("_%d_%s" % (i, default_name), d)
                 for i, d in enumerate(data)])
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them "
                        "or dict with them as values")
    out = collections.OrderedDict()
    for k, v in data.items():
        if isinstance(v, NDArray):
            v = v.asnumpy()
        out[k] = onp.asarray(v)
    return list(out.items())


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (io.py:457)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)

        self.idx = onp.arange(self.data[0][1].shape[0])
        if shuffle:
            onp.random.shuffle(self.idx)
            self.data = [(k, v[self.idx]) for k, v in self.data]
            self.label = [(k, v[self.idx]) for k, v in self.label]

        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - \
                self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]

        self.data_list = [x[1] for x in self.data] + [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % \
                self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None)
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [array(x[1][self.cursor:self.cursor + self.batch_size],
                          dtype=x[1].dtype) for x in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [array(onp.concatenate((x[1][self.cursor:],
                                       x[1][:pad]), axis=0),
                      dtype=x[1].dtype) for x in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class MNISTIter(DataIter):
    """MNIST idx-format reader (src/io/iter_mnist.cc:241) — supports the
    gzipped or raw idx files; ``flat`` yields (n, 784)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128,
                 shuffle=True, flat=False, silent=False, seed=0,
                 input_shape=None, **kwargs):
        super().__init__(batch_size)
        imgs = self._read_idx(image)
        labels = self._read_idx(label)
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, imgs.shape[1], imgs.shape[2])
        imgs = imgs.astype(onp.float32) / 255.0
        if shuffle:
            rng = onp.random.RandomState(seed)
            perm = rng.permutation(imgs.shape[0])
            imgs, labels = imgs[perm], labels[perm]
        self._iter = NDArrayIter(imgs, labels.astype(onp.float32),
                                 batch_size=batch_size,
                                 last_batch_handle="discard")
        self.provide_data = self._iter.provide_data
        self.provide_label = self._iter.provide_label

    @staticmethod
    def _read_idx(path):
        if not os.path.exists(path):
            if os.path.exists(path + ".gz"):
                path = path + ".gz"
            else:
                raise MXNetError("MNIST file %s not found" % path)
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            magic = struct.unpack(">I", f.read(4))[0]
            ndim = magic & 0xFF
            dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
            data = onp.frombuffer(f.read(), dtype=onp.uint8)
        return data.reshape(dims)

    def reset(self):
        self._iter.reset()

    def next(self):
        return self._iter.next()

    def iter_next(self):
        return self._iter.iter_next()


class CSVIter(DataIter):
    """CSV reader (src/io/iter_csv.cc:132)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = onp.loadtxt(data_csv, delimiter=",", dtype=onp.float32,
                           ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = onp.loadtxt(label_csv, delimiter=",", dtype=onp.float32,
                                ndmin=2)
            label = label.reshape((-1,) + tuple(label_shape))
            if label.shape[1:] == (1,):
                label = label.reshape(-1)
        else:
            label = onp.zeros((data.shape[0],), dtype=onp.float32)
        handle = "pad" if round_batch else "discard"
        self._iter = NDArrayIter(data, label, batch_size=batch_size,
                                 last_batch_handle=handle)
        self.provide_data = self._iter.provide_data
        self.provide_label = self._iter.provide_label

    def reset(self):
        self._iter.reset()

    def next(self):
        return self._iter.next()


def __getattr__(name):
    """Lazy re-exports from image.py (mx.io.ImageRecordIter compat —
    registered in src/io/iter_image_recordio_2.cc in the reference)."""
    if name in ("ImageRecordIter", "ImageIter", "ImageRecordUInt8Iter"):
        from . import image
        if name == "ImageRecordUInt8Iter":
            return image.ImageRecordIter
        return getattr(image, name)
    raise AttributeError("module 'mxnet_tpu.io' has no attribute %r" % name)
