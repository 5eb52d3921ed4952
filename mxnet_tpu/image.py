"""Image iterators + augmentations (python/mxnet/image.py:559 and the C++
augmenter chain src/io/image_aug_default.cc).

Decode uses PIL (cv2 when present); augmentation math is numpy; the batch
assembly hot loop (normalize/mirror/crop, HWC→CHW) runs in the native
OpenMP runtime (runtime/recordio.cpp assemble_batch).
"""
from __future__ import annotations

import collections
import io as _pyio
import logging
import os
import random
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as onp

from . import ndarray as nd
from . import recordio
from .base import MXNetError
from .context import current_context
from .io import DataIter, DataBatch, DataDesc
from . import runtime

__all__ = ["imdecode", "scale_down", "resize_short", "fixed_crop",
           "random_crop", "center_crop", "color_normalize",
           "random_size_crop", "ResizeAug", "RandomCropAug",
           "RandomSizedCropAug", "CenterCropAug", "HorizontalFlipAug",
           "ColorNormalizeAug", "CastAug", "CreateAugmenter", "ImageIter",
           "ImageRecordIter"]


def imdecode(buf, to_rgb=True):
    """Decode image bytes to a HWC uint8 numpy array."""
    try:
        import cv2
        img = cv2.imdecode(onp.frombuffer(buf, dtype=onp.uint8), 1)
        if to_rgb:
            img = img[:, :, ::-1]
        return img
    except ImportError:
        from PIL import Image
        img = onp.asarray(Image.open(_pyio.BytesIO(bytes(buf))).convert("RGB"))
        if not to_rgb:
            img = img[:, :, ::-1]
        return img


def _resize(img, w, h):
    try:
        import cv2
        return cv2.resize(img, (w, h), interpolation=cv2.INTER_LINEAR)
    except ImportError:
        from PIL import Image
        return onp.asarray(Image.fromarray(img).resize((w, h),
                                                       Image.BILINEAR))


def scale_down(src_size, size):
    """Scale size down to fit in src_size (image.py scale_down)."""
    w, h = size
    sw, sh = src_size
    if sh < h:
        w, h = float(w * sh) / h, sh
    if sw < w:
        w, h = sw, float(h * sw) / w
    return int(w), int(h)


def resize_short(src, size):
    """Resize so the shorter edge == size."""
    h, w = src.shape[:2]
    if h > w:
        new_h, new_w = size * h // w, size
    else:
        new_h, new_w = size, size * w // h
    return _resize(src, new_w, new_h)


def fixed_crop(src, x0, y0, w, h, size=None):
    out = src[y0:y0 + h, x0:x0 + w]
    if size is not None and (w, h) != size:
        out = _resize(out, size[0], size[1])
    return out


def random_crop(src, size):
    h, w = src.shape[:2]
    new_w, new_h = scale_down((w, h), size)
    x0 = random.randint(0, w - new_w)
    y0 = random.randint(0, h - new_h)
    out = fixed_crop(src, x0, y0, new_w, new_h, size)
    return out, (x0, y0, new_w, new_h)


def center_crop(src, size):
    h, w = src.shape[:2]
    new_w, new_h = scale_down((w, h), size)
    x0 = (w - new_w) // 2
    y0 = (h - new_h) // 2
    out = fixed_crop(src, x0, y0, new_w, new_h, size)
    return out, (x0, y0, new_w, new_h)


def color_normalize(src, mean, std=None):
    src = src.astype(onp.float32) - mean
    if std is not None:
        src = src / std
    return src


def random_size_crop(src, size, min_area=0.08, ratio=(3.0 / 4.0, 4.0 / 3.0)):
    """Random area+aspect crop (GoogLeNet-style, image.py random_size_crop)."""
    h, w = src.shape[:2]
    area = h * w
    for _ in range(10):
        new_area = random.uniform(min_area, 1.0) * area
        new_ratio = random.uniform(*ratio)
        new_w = int(round((new_area * new_ratio) ** 0.5))
        new_h = int(round((new_area / new_ratio) ** 0.5))
        if random.random() < 0.5:
            new_w, new_h = new_h, new_w
        if new_w <= w and new_h <= h:
            x0 = random.randint(0, w - new_w)
            y0 = random.randint(0, h - new_h)
            return fixed_crop(src, x0, y0, new_w, new_h, size), \
                (x0, y0, new_w, new_h)
    return center_crop(src, size)


# -- augmenter functors (image.py CreateAugmenter building blocks) ----------
def ResizeAug(size):
    def aug(src):
        return resize_short(src, size)
    return aug


def RandomCropAug(size):
    def aug(src):
        return random_crop(src, size)[0]
    return aug


def RandomSizedCropAug(size, min_area=0.08, ratio=(3. / 4., 4. / 3.)):
    def aug(src):
        return random_size_crop(src, size, min_area, ratio)[0]
    return aug


def CenterCropAug(size):
    def aug(src):
        return center_crop(src, size)[0]
    return aug


def HorizontalFlipAug(p=0.5):
    def aug(src):
        if random.random() < p:
            return src[:, ::-1]
        return src
    return aug


def ColorNormalizeAug(mean, std=None):
    def aug(src):
        return color_normalize(src, mean, std)
    return aug


def CastAug():
    def aug(src):
        return src.astype(onp.float32)
    return aug


def BrightnessJitterAug(brightness):
    def aug(src):
        alpha = 1.0 + random.uniform(-brightness, brightness)
        return onp.clip(src.astype(onp.float32) * alpha, 0, 255)
    return aug


def CreateAugmenter(data_shape, resize=0, rand_crop=False, rand_resize=False,
                    rand_mirror=False, mean=None, std=None, brightness=0,
                    contrast=0, saturation=0, **kwargs):
    """Build the standard augmenter list (image.py CreateAugmenter)."""
    auglist = []
    size = (data_shape[2], data_shape[1])
    if resize > 0:
        auglist.append(ResizeAug(resize))
    if rand_resize:
        assert rand_crop
        auglist.append(RandomSizedCropAug(size))
    elif rand_crop:
        auglist.append(RandomCropAug(size))
    else:
        auglist.append(CenterCropAug(size))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    if brightness:
        auglist.append(BrightnessJitterAug(brightness))
    if mean is True:
        mean = onp.array([123.68, 116.28, 103.53])
    if std is True:
        std = onp.array([58.395, 57.12, 57.375])
    if mean is not None:
        auglist.append(CastAug())
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


class ImageIter(DataIter):
    """Pure-python image iterator over .lst/imglist or RecordIO
    (python/mxnet/image.py ImageIter)."""

    def __init__(self, batch_size, data_shape, label_width=1,
                 path_imgrec=None, path_imglist=None, path_root=None,
                 shuffle=False, aug_list=None, imglist=None,
                 data_name="data", label_name="softmax_label", **kwargs):
        super().__init__(batch_size)
        assert path_imgrec or path_imglist or isinstance(imglist, list)
        if path_imgrec:
            self.rec = runtime.RecordFile(path_imgrec)
            self.imglist = None
            self.seq = list(range(len(self.rec)))
        else:
            self.rec = None
            if path_imglist:
                imglist = []
                with open(path_imglist) as fin:
                    for line in fin:
                        parts = line.strip().split("\t")
                        label = onp.array([float(x) for x in parts[1:-1]],
                                          dtype=onp.float32)
                        imglist.append((label, parts[-1]))
            else:
                imglist = [(onp.array([float(x[0])], dtype=onp.float32), x[1])
                           for x in imglist]
            self.imglist = imglist
            self.path_root = path_root or ""
            self.seq = list(range(len(imglist)))

        self.shuffle = shuffle
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self.aug_list = aug_list if aug_list is not None else \
            CreateAugmenter(data_shape, **kwargs)
        self.cur = 0
        self.data_name = data_name
        self.label_name = label_name
        self.provide_data = [DataDesc(data_name,
                                      (batch_size,) + self.data_shape)]
        self.provide_label = [DataDesc(label_name, (batch_size, label_width)
                                       if label_width > 1 else (batch_size,))]
        self.reset()

    def reset(self):
        if self.shuffle:
            random.shuffle(self.seq)
        self.cur = 0

    def next_sample(self):
        if self.cur >= len(self.seq):
            raise StopIteration
        idx = self.seq[self.cur]
        self.cur += 1
        if self.rec is not None:
            header, img_bytes = recordio.unpack(self.rec.read(idx))
            label = header.label
            img = imdecode(img_bytes)
            return label, img
        label, fname = self.imglist[idx]
        with open(os.path.join(self.path_root, fname), "rb") as f:
            img = imdecode(f.read())
        return label, img

    def next(self):
        c, h, w = self.data_shape
        batch_data = onp.zeros((self.batch_size, c, h, w), onp.float32)
        batch_label = onp.zeros((self.batch_size, self.label_width),
                                onp.float32)
        i = 0
        while i < self.batch_size:
            try:
                label, img = self.next_sample()
            except StopIteration:
                if i == 0:
                    raise
                break
            for aug in self.aug_list:
                img = aug(img)
            batch_data[i] = onp.asarray(img, onp.float32).transpose(2, 0, 1)
            batch_label[i] = onp.atleast_1d(label)[:self.label_width]
            i += 1
        pad = self.batch_size - i
        label_out = batch_label if self.label_width > 1 else \
            batch_label[:, 0]
        return DataBatch([nd.array(batch_data)], [nd.array(label_out)],
                         pad=pad)


def _decode_resize_crop(img_bytes, resize, th, tw, pick_crop):
    """Shared record-payload -> cropped uint8 HWC pipeline (thread and
    process decode paths must never diverge). ``pick_crop(h, w)`` ->
    (y0, x0) supplies the crop geometry."""
    if img_bytes[:6] == b"\x93NUMPY":
        # raw (uncompressed) payload from pack_img's npy fallback /
        # im2rec --encoding .npy: decode is a buffer view, the mode
        # for hosts where JPEG decode can't keep up with the chip
        img = onp.load(_pyio.BytesIO(bytes(img_bytes)), allow_pickle=False)
    else:
        img = imdecode(img_bytes)
    if resize > 0:
        img = resize_short(img, resize)
    h, w = img.shape[:2]
    if h < th or w < tw:
        img = _resize(img, max(tw, w), max(th, h))
        h, w = img.shape[:2]
    y0, x0 = pick_crop(h, w)
    return img[y0:y0 + th, x0:x0 + tw]


def _proc_worker_init(path):
    global _PROC_REC
    _PROC_REC = runtime.RecordFile(path)


def _proc_decode_one(args):
    """Decode+resize+crop one record in a worker process (uint8 HWC out).

    Crop geometry uses a per-record deterministic rng seeded from
    (seed, idx, epoch) — processes cannot share the parent's rng stream,
    and folding the epoch keeps crops varying across epochs."""
    idx, resize, th, tw, rand_crop, seed = args
    header, img_bytes = recordio.unpack(_PROC_REC.read(idx))

    def pick(h, w):
        if not rand_crop:
            return (h - th) // 2, (w - tw) // 2
        r = random.Random(seed ^ (idx * 2654435761 & 0xffffffff))
        return r.randint(0, h - th), r.randint(0, w - tw)

    img = _decode_resize_crop(img_bytes, resize, th, tw, pick)
    return img, onp.atleast_1d(header.label)


def _stack_chunk(decoded):
    """What a pool task hands back (the task adds its own start and end
    on ``time.time_ns()``'s clock): its images as ONE contiguous uint8
    block and its labels as one.  cv2 gives BGR, and RGB is a view that
    reads it backwards, a strided copy a pixel when it is stacked; made
    here the copies run on the pool's threads, and the thread that
    assembles the batch joins a few blocks.  One ``onp.stack`` of the
    256 views there hands the interpreter back 256 times while the pool
    and the caller want it: 76 ms alone, 147 beside them (PERF.md,
    PR 28)."""
    return (onp.stack([d[0] for d in decoded]),
            onp.stack([d[1] for d in decoded]))


def _proc_decode_chunk(chunk):
    t0 = time.time_ns()
    return _stack_chunk([_proc_decode_one(args) for args in chunk]) + (
        t0, time.time_ns())


_DECODE_CHUNK = 8    # records a pool task: 32 tasks a batch of 256
_READY = 2           # finished batches the producer keeps ahead of next()


class _Slot(object):
    """One batch the producer has begun, in stream order: where the
    stream stood before it (``before``: epoch, record cursor, batch
    index; ``rng``: the state of the iterator's draws there) and
    ``after`` it, its records and their decodes in the pool
    (``work``), and once ``done`` the batch or the error with what
    each stage took."""

    __slots__ = ("gen", "before", "after", "rng", "idxs", "pad", "work",
                 "done", "batch", "error", "stage_ns", "h2d", "decode_wall")

    def __init__(self, gen, before, after, rng, idxs, pad):
        self.gen, self.before, self.after, self.rng = gen, before, after, rng
        self.idxs, self.pad = idxs, pad
        self.work, self.done, self.batch, self.error = (), False, None, None
        self.stage_ns, self.h2d, self.decode_wall = {}, 0, 0

    def cancel(self):
        for fut in self.work:
            fut.cancel()


class _RunAhead(object):
    """What ``next()`` and the producer thread share.  It holds no
    reference to the iterator: a parked producer keeps a dropped
    iterator from nothing, and the finalizer stops it through this."""

    def __init__(self):
        # an RLock: a finalizer may run wherever the collector does
        self.cond = threading.Condition(threading.RLock())
        self.slots = collections.deque()   # begun and not taken
        self.cursor = None     # (epoch, cur, batch index) of the next slot
        self.gen = 0           # raised when a reset throws the slots away
        self.stop = False
        self.thread = None
        self.ctx = None        # the context the first next() ran under


def _run_ahead(ref, state):
    """The producer thread: one batch a turn while fewer than
    ``_READY`` wait to be taken.  It holds the iterator for a turn
    only."""
    while True:
        with state.cond:
            while not state.stop and \
                    sum(s.done for s in state.slots) >= _READY:
                state.cond.wait()
            if state.stop:
                return
        it = ref()
        if it is None:
            return
        it._produce(state)
        del it


def _stop_ahead(state, pool):
    """``close()`` and the finalizer: stop the producer, drop what it
    made, join it (but for a finalizer that runs on it), and let the
    decode pool go."""
    with state.cond:
        state.stop = True
        state.gen += 1
        for slot in state.slots:
            slot.cancel()
        state.slots.clear()
        state.cond.notify_all()
    thread = state.thread
    if thread is not None and thread is not threading.current_thread():
        thread.join()
    pool.shutdown(wait=False, cancel_futures=True)


class ImageRecordIter(DataIter):
    """RecordIO image iterator with threaded decode + native batch assembly
    (src/io/iter_image_recordio_2.cc ImageRecordIter).

    Decode runs on a thread pool (PIL/cv2 release the GIL) or, with
    ``preprocess_processes=N``, on a process pool (for hosts where decode
    is GIL/core-bound — the reference's decode farm,
    iter_image_recordio_2.cc). Augmentation geometry is chosen
    per-sample; the normalize/mirror/transpose hot loop either runs in
    the native OpenMP runtime (host path) or, with
    ``device_augment=True``, on the accelerator: the batch ships as
    uint8 NHWC (4x fewer host->device bytes than f32 CHW) and ONE
    jitted program does mirror+normalize+transpose device-side —
    the TPU-native replacement for iter_normalize.h.

    The iterator runs ahead of its caller, as the reference's does
    (iter_prefetcher.h): from the first ``next()`` on, a thread of its
    own decodes, assembles and puts the coming batches and keeps two
    finished ones waiting, the next batch's decodes in the pool while
    this one is assembled; ``next()`` takes the oldest.  It goes on
    into the next epoch, and ``reset()`` adopts what is waiting.  The
    stream does not depend on how far ahead it was: for one seed and
    one sequence of ``next`` / ``reset`` / ``set_epoch`` calls the
    batches are the same, bit for bit, as if each were made when asked
    for; a ``reset()`` or ``set_epoch()`` that leaves the stream the
    producer is on throws its batches away and puts ``rng`` back to
    where it stood before the oldest of them drew.  Every batch gets a
    fresh 64-byte-aligned host buffer that nothing writes again (the
    NDArray IS that memory and the chip reads it later), so batches a
    caller holds stay as they were.  ``close()`` stops and joins the
    thread and shuts the decode pool down (the iterator cannot be used
    afterwards); dropping the iterator does the same.

    ``device_augment="defer"`` goes one step further: the iterator
    emits raw uint8 NHWC wire batches plus deterministic per-batch
    augment-parameter draws and exposes ``device_augment_spec`` — the
    bound module then runs pad/crop/mirror/normalize as its own
    compiled device program at staging time
    (``mxnet_tpu.data.DeviceAugment``; kept separate from the train
    step so the step program's numerics stay bitwise-identical to the
    host-reference path), so random crop (``augment_pad``) composes
    with ``cache_decoded`` and draws replay across resume.
    """

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, rand_crop=False, rand_mirror=False,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                 std_b=1.0, scale=1.0, resize=-1, preprocess_threads=4,
                 preprocess_processes=0, device_augment=False,
                 augment_pad=0, cache_decoded=False, round_batch=True,
                 data_name="data", label_name="softmax_label", seed=0,
                 **kwargs):
        super().__init__(batch_size)
        self.rec = runtime.RecordFile(path_imgrec)
        self._path_imgrec = path_imgrec
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self.shuffle = shuffle
        self.rand_crop = rand_crop
        self.rand_mirror = rand_mirror
        self.mean = onp.array([mean_r, mean_g, mean_b], onp.float32)
        self.std = onp.array([std_r, std_g, std_b], onp.float32)
        self.scale = scale
        self.resize = resize
        self.round_batch = round_batch
        self.seed = seed
        self.rng = random.Random(seed)
        self.device_augment = device_augment
        self._device_fn = None
        # device_augment="defer": do NOT augment here at all — emit raw
        # uint8 NHWC wire batches plus the per-batch augment-parameter
        # draws of a DeviceAugment spec, and let the bound module
        # compile crop/mirror/normalize INTO the train-step program
        # (fit adopts device_augment_spec).  Decode geometry is then
        # always deterministic (center), so it composes with
        # cache_decoded AND rand_crop: crop randomness comes from the
        # in-program pad+crop (augment_pad), not from decode.
        self._defer = device_augment == "defer"
        self._aug_spec = None
        self._batch_seq = 0
        if self._defer:
            from .data.augment import DeviceAugment
            c, th, tw = self.data_shape
            if rand_crop and not augment_pad:
                # decode geometry is deterministic in defer mode; with
                # no pad the in-program crop window is 0x0 — rand_crop
                # would silently become a center crop
                raise ValueError(
                    "rand_crop with device_augment='defer' needs "
                    "augment_pad>0: crop randomness comes from the "
                    "in-program pad-and-crop, not from decode")
            self._aug_spec = DeviceAugment(
                (c, th, tw), rand_crop=rand_crop,
                rand_mirror=rand_mirror, pad=augment_pad,
                mean=self.mean, std=self.std, scale=scale, seed=seed)
            self.device_augment_spec = {data_name: self._aug_spec}
        elif augment_pad:
            raise ValueError(
                "augment_pad is the in-program pad-and-crop knob; it "
                "needs device_augment='defer'")
        if preprocess_processes > 0:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor
            # spawn, not fork: the parent typically holds an initialized
            # JAX/TPU client whose threads/state must not be forked
            self.pool = ProcessPoolExecutor(
                max_workers=preprocess_processes,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_proc_worker_init, initargs=(path_imgrec,))
            self._proc_mode = True
        else:
            self.pool = ThreadPoolExecutor(max_workers=preprocess_threads)
            self._proc_mode = False
        # RAM-cached decoded mode: JPEG decode is the host's bottleneck
        # (it runs once per image per EPOCH on the streaming path), but
        # the decoded geometry is deterministic when rand_crop is off —
        # so decode each image exactly ONCE into a uint8 NHWC cache and
        # serve every later batch as a fancy-index gather (memcpy-rate)
        # + uint8 transfer.  This is the iterator shape that feeds a
        # chip at compute rate from a modest host: per-epoch cost drops
        # from decode (~ms/img/core) to gather+DMA (~µs/img).  Memory:
        # N*H*W*C bytes host RAM (caller's tradeoff).  rand_mirror still
        # applies per draw (it acts on the gathered batch); rand_crop
        # needs fresh geometry per epoch and is rejected.
        self.cache_decoded = cache_decoded
        self._cache = None
        if cache_decoded and rand_crop and not self._defer:
            raise ValueError(
                "cache_decoded caches one deterministic decode per "
                "image; rand_crop needs fresh geometry every epoch — "
                "use the streaming path for random-crop training, or "
                "device_augment='defer' (crop runs in-program)")
        self._base_seq = list(range(len(self.rec)))
        self._orders = {}        # epoch -> shuffled order, the last two
        self.seq = self._base_seq
        self.cur = 0
        # NOTE on staging: each batch gets a FRESH host buffer, 64-byte
        # aligned (runtime.assemble_batch), so that nd.array of it on the
        # CPU jax device is that memory and not a copy of it, and the
        # executor group stages it to the mesh from there. A pooled
        # double-buffer ring (iter_prefetcher.h pattern) was tried and
        # reverted for the same reason: a recycled buffer would alias any
        # still-live batch NDArray (and downstream TPU transfers read the
        # alias asynchronously); running ahead changes nothing of that:
        # two batches wait, one is in assembly, each in memory of its
        # own. runtime.core.HostPool remains available (and
        # assemble_batch takes ``out=``) for callers that own the buffer
        # lifetime end-to-end.
        self._ahead = _RunAhead()
        self._finalizer = weakref.finalize(self, _stop_ahead, self._ahead,
                                           self.pool)
        # decode-time crop geometry: random only on the host-augment
        # streaming path; "defer" decodes deterministically (the
        # in-program pad+crop supplies the randomness)
        self._decode_rand_crop = bool(rand_crop) and not self._defer
        if self._defer:
            self.provide_data = self._aug_spec.data_descs(data_name,
                                                          batch_size)
        else:
            self.provide_data = [DataDesc(data_name,
                                          (batch_size,) + self.data_shape)]
        self._data_name = data_name
        self.provide_label = [DataDesc(label_name, (batch_size, label_width)
                                       if label_width > 1 else (batch_size,))]
        self.reset()

    def reset(self):
        self._epoch = getattr(self, "_epoch", -1) + 1
        self._reshuffle()
        self.cur = 0
        self._batch_seq = 0
        self._follow()

    def _order(self, epoch):
        """Epoch k's order is a pure function of ``(seed, k)`` —
        re-drawn from the FIXED base order, never cumulatively — so
        ``set_epoch(k)`` replays it exactly regardless of how many
        resets this process has seen (the resume-replay contract; a
        cumulative ``rng.shuffle`` would depend on the reset COUNT),
        and the producer can draw epoch k + 1's before ``reset()``
        asks for it.  The lists are shared and never written."""
        if not self.shuffle:
            return self._base_seq
        seq = self._orders.get(epoch)
        if seq is None:
            from .data.augment import fold_seed
            rs = onp.random.RandomState(
                fold_seed(self.seed ^ 0x5bd1e995, epoch, 0))
            seq = list(self._base_seq)
            rs.shuffle(seq)
            for old in list(self._orders):   # both threads come here
                if abs(old - epoch) > 1:
                    self._orders.pop(old, None)
            self._orders[epoch] = seq
        return seq

    def _reshuffle(self):
        self.seq = self._order(self._epoch)

    def set_epoch(self, epoch):
        """Pin the epoch coordinate (the resume-replay contract).

        Both the deferred-augment draws and the shuffle order are
        pure functions of the pinned coordinate, so a resumed fit
        replays the uninterrupted run's stream exactly."""
        self._epoch = int(epoch)
        self._batch_seq = 0
        self._reshuffle()
        self._follow()

    def _follow(self):
        """After ``reset()`` / ``set_epoch()`` moved this side's
        position: if the oldest batch the producer has begun starts
        exactly there (it went on into the epoch ``reset()`` has just
        set), what it holds is adopted.  Otherwise it is thrown away,
        ``rng`` put back to where it stood before the oldest of it
        drew, and the producer begins again from here."""
        state = self._ahead
        if state.thread is None:
            return
        here = (self._epoch, self.cur, self._batch_seq)
        with state.cond:
            if state.slots:
                if state.slots[0].before == here:
                    return
                self.rng.setstate(state.slots[0].rng)
                for slot in state.slots:
                    slot.cancel()
                state.slots.clear()
            state.gen += 1
            state.cursor = here
            state.cond.notify_all()

    def close(self):
        """Stop and join the producer thread and shut the decode pool
        down (idempotent).  The iterator cannot be used afterwards;
        dropping it does the same."""
        self._finalizer()

    @property
    def epoch_coord(self):
        return self._epoch

    def _decode_one(self, idx):
        header, img_bytes = recordio.unpack(self.rec.read(idx))
        c, th, tw = self.data_shape

        def pick(h, w):
            if not self._decode_rand_crop:
                return (h - th) // 2, (w - tw) // 2
            return self.rng.randint(0, h - th), self.rng.randint(0, w - tw)

        img = _decode_resize_crop(img_bytes, self.resize, th, tw, pick)
        return img, onp.atleast_1d(header.label)

    def _device_preprocess(self, imgs_u8, mirror):
        """uint8 NHWC batch -> normalized f32 NCHW, entirely on device.

        The transfer is the uint8 batch (4x smaller than the host path's
        f32 NCHW); mirror/normalize/transpose are one jitted program that
        XLA fuses — matching the host assemble_batch numerics exactly:
        out = (x - mean) / (std / scale)."""
        import jax

        if self._device_fn is None:
            import jax.numpy as jnp
            mean = self.mean
            std = self.std / self.scale

            def prep(x, mir):
                # XLA:TPU fuses a direct u8->f32 cast into the downstream
                # transpose as a byte-gather loop ~145x slower than the
                # i32-routed equivalent (measured before PR 1; not
                # re-measured on the current chip path) — route via i32
                xf = x.astype(jnp.int32).astype(jnp.float32)
                if mir is not None:
                    xf = jnp.where(mir[:, None, None, None] != 0,
                                   xf[:, :, ::-1, :], xf)
                xf = (xf - mean) / std
                return xf.transpose(0, 3, 1, 2)

            self._device_fn = jax.jit(prep)
        if mirror is None:
            fn = self._device_fn
            return fn(jax.device_put(imgs_u8), None)
        return self._device_fn(jax.device_put(imgs_u8),
                               jax.device_put(mirror))

    def _fill_cache(self):
        """Decode every record once (thread/process pool) into a uint8
        NHWC array + label array."""
        c, th, tw = self.data_shape
        n = len(self.rec)
        cache = onp.empty((n, th, tw, c), onp.uint8)
        lw = self.label_width
        labels = onp.empty((n, lw), onp.float32)
        all_idx = list(range(n))
        if self._proc_mode:
            ep_seed = self.seed
            work = [(i, self.resize, th, tw, False, ep_seed)
                    for i in all_idx]
            results = self.pool.map(_proc_decode_one, work, chunksize=16)
        else:
            results = self.pool.map(self._decode_one, all_idx)
        for i, (img, lab) in zip(all_idx, results):
            cache[i] = img
            labels[i] = lab[:lw]
        self._cache = (cache, labels)
        # the decode pool is never used again on this path
        self.pool.shutdown(wait=True)

    def next(self):
        if not self._finalizer.alive:
            raise MXNetError("ImageRecordIter is closed")
        if self.cur >= len(self.seq):
            raise StopIteration
        from . import telemetry
        state = self._ahead
        with state.cond:
            if state.thread is None:
                state.cursor = (self._epoch, self.cur, self._batch_seq)
                state.ctx = current_context()
                state.thread = threading.Thread(
                    target=_run_ahead, args=(weakref.ref(self), state),
                    name="ImageRecordIter-producer", daemon=True)
                state.thread.start()
            ready = bool(state.slots) and state.slots[0].done
            while not (state.slots and state.slots[0].done):
                if not state.thread.is_alive():
                    raise MXNetError("ImageRecordIter's producer thread "
                                     "ended without a batch")
                state.cond.wait(1.0)
            slot = state.slots.popleft()
            self._epoch, self.cur, self._batch_seq = slot.after
            self._reshuffle()
            state.cond.notify_all()
        # this side's account: whether the batch was waiting, and the
        # stages it went through on the producer's thread
        telemetry.count("input.ready" if ready else "input.waited")
        for name, ns in slot.stage_ns.items():
            telemetry.credit(name, ns)
        if slot.error is not None:
            raise slot.error
        telemetry.count("input.h2d_bytes", slot.h2d)
        if slot.decode_wall:
            telemetry.count("input.decode_wall_ns", slot.decode_wall)
        return slot.batch

    def _begin(self, state, gen):
        """Begin the batch at the producer's cursor: note where the
        stream stands, move the cursor past the batch and hand its
        records to the decode pool.  At an epoch's end it goes on into
        the next, as ``reset()`` will.  None where a reset has moved
        the stream meanwhile."""
        epoch, cur, batch_seq = state.cursor
        if cur >= len(self._base_seq):
            epoch, cur, batch_seq = epoch + 1, 0, 0
        seq = self._order(epoch)     # a new epoch's shuffle: not locked
        with state.cond:
            if state.gen != gen:     # else only this thread moves the
                return None          # cursor: what was read above holds
            idxs = seq[cur:cur + self.batch_size]
            pad = self.batch_size - len(idxs)
            if pad > 0 and self.round_batch:
                idxs = idxs + seq[:pad]
            slot = _Slot(gen, (epoch, cur, batch_seq),
                         (epoch, cur + self.batch_size,
                          batch_seq + bool(self._defer)),
                         self.rng.getstate(), idxs, pad)
            state.cursor = slot.after
            state.slots.append(slot)
        if self.cache_decoded:
            return slot
        try:
            chunks = [idxs[i:i + _DECODE_CHUNK]
                      for i in range(0, len(idxs), _DECODE_CHUNK)]
            if self._proc_mode:
                c, th, tw = self.data_shape
                ep_seed = self.seed ^ (epoch * 0x9e3779b1 & 0xffffffff)
                slot.work = [self.pool.submit(_proc_decode_chunk, [
                    (i, self.resize, th, tw, self._decode_rand_crop, ep_seed)
                    for i in chunk]) for chunk in chunks]
            else:
                slot.work = [self.pool.submit(self._decode_chunk, chunk)
                             for chunk in chunks]
        except Exception as err:        # the pool is shut: next() says so
            slot.error = err
        with state.cond:
            if state.gen != gen:     # thrown away before it had its work
                slot.cancel()
        return slot

    def _decode_chunk(self, idxs):
        t0 = time.time_ns()
        return _stack_chunk([self._decode_one(i) for i in idxs]) + (
            t0, time.time_ns())

    def _produce(self, state):
        """One turn of the producer: finish the oldest batch begun and
        not done.  Its decodes went to the pool a turn ago; the next
        batch's go there before this one is assembled, so the pool
        works under the OpenMP loop.  Only this thread draws, in batch
        order, and the batch's draws come before the next batch is
        begun: the draws are the serial iterator's."""
        with state.cond:
            gen = state.gen
            slot = next((s for s in state.slots if not s.done), None)
        if slot is None:
            slot = self._begin(state, gen)
            if slot is None:
                return
        try:
            self._make(state, slot)
        except Exception as err:        # next() raises it in its turn
            slot.error = err
        with state.cond:
            slot.done = True
            state.cond.notify_all()

    def _make(self, state, slot):
        from .telemetry import span
        epoch, _cur, batch_seq = slot.before
        idxs, ctx = slot.idxs, state.ctx
        # record read + JPEG decode + resize/crop of the batch: what this
        # thread still waits for the pool (or the cache's gather)
        with span("input.decode") as clock:
            if slot.error is not None:
                raise slot.error
            if self.cache_decoded:
                if self._cache is None:
                    self._fill_cache()
                cache, cl = self._cache
                imgs = cache[idxs]        # fancy-index gather: memcpy-rate
                labels = cl[idxs]
            else:
                blocks = [fut.result() for fut in slot.work]
        slot.stage_ns["input.decode"] = clock.ns
        if not self.cache_decoded:
            # the decode stage's own wall: its first task's start to
            # its last task's end, whoever waited for them
            slot.decode_wall = max(b[3] for b in blocks) - \
                min(b[2] for b in blocks)
        mirror = None
        if self.rand_mirror and not self._defer:
            with state.cond:
                if state.gen != slot.gen:
                    return          # thrown away: it draws nothing
                mirror = onp.array(
                    [self.rng.random() < 0.5 for _ in range(len(idxs))],
                    onp.uint8)
        self._begin(state, slot.gen)
        with span("input.assemble") as clock:
            if not self.cache_decoded:
                imgs = onp.concatenate([b[0] for b in blocks])
                labels = onp.concatenate([b[1] for b in blocks])
            label_out = labels if self.label_width > 1 else labels[:, 0]
            label_out = onp.asarray(label_out, onp.float32)
            if self._defer:
                # raw uint8 NHWC wire batch + the spec's per-batch
                # augment parameter draws, keyed (seed, epoch, batch
                # index) — the bound program does crop/mirror/normalize
                # in one fused stage (4x fewer staged bytes than f32
                # NCHW)
                spec = self._aug_spec
                params = spec.draw(self._data_name, epoch, batch_seq,
                                   imgs.shape[0])
                data = [imgs] + [
                    params[d.name]
                    for d in spec.param_descs(self._data_name,
                                              imgs.shape[0])]
            elif not self.device_augment:
                imgs = runtime.assemble_batch(
                    imgs, mean=self.mean, std=self.std / self.scale,
                    mirror=mirror)
        slot.stage_ns["input.assemble"] = clock.ns
        # the calls that hand host memory to jax.device_put (the wire
        # batch of the defer branch goes up in the executor group's
        # staging instead); the transfers complete later
        with span("input.put") as clock:
            label = nd.array(label_out, ctx=ctx)
            h2d = label_out.nbytes
            if not self._defer:
                h2d += imgs.nbytes
                if self.device_augment:
                    if mirror is not None:
                        h2d += mirror.nbytes
                    data = [nd.NDArray(self._device_preprocess(imgs,
                                                               mirror))]
                else:
                    data = [nd.array(imgs, ctx=ctx)]
        slot.stage_ns["input.put"] = clock.ns
        slot.h2d = h2d
        slot.batch = DataBatch(data, [label], pad=slot.pad)


# detection pipeline lives in its own module; re-exported here so the
# reference surface (mx.image / the C-API iterator registry) finds it
from .image_det import DetAugmenter, DetLabel, ImageDetRecordIter  # noqa: E402,F401

__all__ += ["DetLabel", "DetAugmenter", "ImageDetRecordIter"]
