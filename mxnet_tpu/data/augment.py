"""DeviceAugment — crop/flip/normalize compiled INTO the train program.

Rounds 2–5 found every fed pipeline host-bound
(``pipeline_bound_by: "host_cpu_decode"``): the reference's input path
(mshadow-backed ``io/`` iterators, ``iter_normalize.h``) augments and
float-converts every batch on the host and ships f32 NCHW — 4x the bytes of
the decoded uint8 image, plus a host normalize/transpose pass per
batch.  This module moves the whole augment stage onto the device:

* the iterator delivers **uint8 NHWC** wire batches (4x smaller from
  host to device than f32 NCHW) plus tiny per-batch *augment
  parameter* arrays (crop offsets, mirror flags);
* the bound :class:`~mxnet_tpu.module.MeshExecutorGroup` compiles
  pad -> per-row crop -> mirror -> u8->f32 cast -> normalize ->
  NHWC->NCHW transpose as ONE device program run at staging time
  (``_augment_jit``) — deliberately a SEPARATE program from the train
  step, because a different step-program preamble shifts XLA's
  layout/fusion choices and breaks bitwise parity (see
  :meth:`DeviceAugment.apply`); the cost is one small extra launch
  per staged batch, amortized K-fold by grouped staging;
* randomness is drawn HOST-side from ``(seed, epoch, batch_index)``
  with exactly :class:`~mxnet_tpu.data.TransformIter`'s SplitMix fold,
  so the delivered stream is bitwise identical at any worker count,
  replayable across ``reset()``/checkpoint resume (``set_epoch`` pins
  the epoch coordinate), and INDEPENDENT of the program's own rng
  stream (dropout keys never perturb augmentation);
* :meth:`DeviceAugment.apply_host` is the numpy reference
  implementation, pinned elementwise-equal to the in-program path by
  tests/test_device_augment.py — the host-reference fallback
  (``placement="host"``) trains to BIT-IDENTICAL params.

Eval (``is_train=False``) always takes the deterministic center-crop
variant with no mirror, so ``predict``/``score`` parity holds whatever
the training augmentation was.
"""
from __future__ import annotations

import os

import numpy as onp

from ..base import MXNetError
from ..io import DataBatch, DataDesc, DataIter

__all__ = ["DeviceAugment", "DeviceAugmentIter", "fold_seed",
           "crop_input_name", "mirror_input_name"]


def fold_seed(seed, epoch, index):
    """SplitMix-style fold of ``(seed, epoch, index)`` — the SAME
    constants as ``TransformIter._batch_seed``: adjacent batches land
    on unrelated streams and the value is a pure function of the
    stream POSITION, never of worker identity or wall time."""
    x = (int(seed) * 0x9e3779b97f4a7c15
         + int(epoch) * 0xbf58476d1ce4e5b9
         + int(index) * 0x94d049bb133111eb) & 0xffffffffffffffff
    x ^= x >> 31
    return x & 0x7fffffff


def crop_input_name(name):
    """Program-input name for a data input's per-row crop offsets."""
    return name + ".aug_crop"


def mirror_input_name(name):
    """Program-input name for a data input's per-row mirror flags."""
    return name + ".aug_mirror"


def _placement_default():
    return "host" if os.environ.get(
        "MXNET_DATA_DEVICE_AUGMENT", "1") == "0" else "device"


class DeviceAugment(object):
    """Declarative augment spec compiled into the step program.

    Parameters
    ----------
    shape : tuple
        Model-view ``(C, H, W)`` — what the symbol's ``data`` input
        consumes after augmentation.
    rand_crop : bool
        Random-crop an ``(H, W)`` window from the (padded) wire image
        during training.  Eval always center-crops.
    rand_mirror : bool
        Random horizontal flip (p=0.5) during training.
    pad : int
        Zero-pad ``pad`` pixels on every spatial edge IN-PROGRAM
        before cropping (the CIFAR pad-and-crop recipe: wire 32x32,
        pad 4, crop 32).
    mean, std : float or sequence
        Per-channel normalize.  The spec computes
        ``out = (x - mean) * (scale / std)`` with the factor
        precomputed in f32 ONCE on the host: a division by a
        non-power-of-two constant is not bitwise-stable between XLA's
        compiled program and the numpy reference (XLA may strength-
        reduce it to a reciprocal multiply), so the multiply IS the
        contract — both paths consume the identical f32 factor.
    scale : float
        Multiplied into the normalize as ``std / scale`` (reference
        ``ImageRecordIter(scale=)`` semantics; ``scale=1/255`` with
        mean 0/std 1 reproduces a plain ``x / 255`` feed).
    in_shape : tuple, optional
        Wire spatial size ``(H_in, W_in)`` the iterator actually
        delivers (default ``(H, W)``).  With ``H_in > H`` the crop
        window is ``H_in + 2*pad - H`` pixels (ImageNet-style
        decode-large-crop-small).
    seed : int
        Root of the per-batch parameter draws.
    """

    def __init__(self, shape, rand_crop=False, rand_mirror=False, pad=0,
                 mean=0.0, std=1.0, scale=1.0, in_shape=None, seed=0):
        c, h, w = (int(s) for s in shape)
        self.shape = (c, h, w)
        self.pad = int(pad)
        if self.pad < 0:
            raise MXNetError("pad must be >= 0 (got %d)" % self.pad)
        hin, win = (int(s) for s in (in_shape or (h, w)))
        self.in_shape = (hin, win)
        self._window = (hin + 2 * self.pad - h, win + 2 * self.pad - w)
        if self._window[0] < 0 or self._window[1] < 0:
            raise MXNetError(
                "crop target %r larger than padded wire image %r"
                % ((h, w), (hin + 2 * self.pad, win + 2 * self.pad)))
        self.rand_crop = bool(rand_crop)
        self.rand_mirror = bool(rand_mirror)
        self.mean = onp.broadcast_to(
            onp.asarray(mean, onp.float32), (c,)).copy()
        # ONE effective normalize factor, precomputed in f32 on the
        # host: both the compiled path and the numpy reference multiply
        # by this identical operand (see the class docstring for why a
        # division would break bitwise parity)
        self.std = onp.broadcast_to(
            onp.asarray(std, onp.float32), (c,)).copy()
        self.scale = float(scale)
        self._norm = (onp.float32(self.scale) / self.std) \
            .astype(onp.float32)
        self.seed = int(seed)

    # -- shapes ---------------------------------------------------------
    @property
    def wire_shape(self):
        """Per-image wire layout: ``(H_in, W_in, C)`` uint8 HWC."""
        return self.in_shape + (self.shape[0],)

    def model_shape(self, batch_size):
        """What the symbol sees: ``(B, C, H, W)`` f32 NCHW."""
        return (int(batch_size),) + self.shape

    @property
    def has_rand_crop(self):
        """Random crop only matters when there is crop freedom."""
        return self.rand_crop and (self._window[0] > 0
                                   or self._window[1] > 0)

    def data_descs(self, name, batch_size):
        """provide_data entries for a wire batch of this spec: the u8
        image block FIRST, then the augment-parameter inputs."""
        b = int(batch_size)
        descs = [DataDesc(name, (b,) + self.wire_shape,
                          dtype=onp.uint8, layout="NHWC")]
        descs.extend(self.param_descs(name, b))
        return descs

    def param_descs(self, name, batch_size):
        b = int(batch_size)
        descs = []
        if self.has_rand_crop:
            descs.append(DataDesc(crop_input_name(name), (b, 2),
                                  dtype=onp.int32, layout=None))
        if self.rand_mirror:
            descs.append(DataDesc(mirror_input_name(name), (b,),
                                  dtype=onp.uint8, layout=None))
        return descs

    # -- deterministic parameter draws ---------------------------------
    def draw(self, name, epoch, index, batch_size):
        """Per-batch augment parameters as ``{input name: host array}``
        — a pure function of ``(seed, epoch, index)``.  Draw order is
        part of the determinism contract: crop rows, crop cols, then
        mirror flags, always from one ``RandomState``."""
        rng = onp.random.RandomState(fold_seed(self.seed, epoch, index))
        b = int(batch_size)
        out = {}
        if self.has_rand_crop:
            wy, wx = self._window
            oy = rng.randint(0, wy + 1, size=b)
            ox = rng.randint(0, wx + 1, size=b)
            out[crop_input_name(name)] = onp.stack(
                [oy, ox], axis=1).astype(onp.int32)
        if self.rand_mirror:
            out[mirror_input_name(name)] = (
                rng.random_sample(b) < 0.5).astype(onp.uint8)
        return out

    # -- the compiled path ---------------------------------------------
    def _is_model_view(self, x):
        """True when ``x`` is already the augmented f32 NCHW tensor
        (a classic float iterator fed into an augment-bound program,
        or the group's zero-fill) — the program then passes it
        through untouched, so predict/score with pre-normalized
        batches keeps working."""
        return (x.dtype != onp.uint8
                and tuple(x.shape[1:]) == self.shape)

    def apply(self, x, crop=None, mirror=None, train=True):
        """uint8 NHWC wire batch -> normalized f32 NCHW, traced into
        the caller's XLA program.  ``crop``/``mirror`` are the staged
        per-row parameter arrays (ignored at eval: center crop, no
        mirror)."""
        import jax
        import jax.numpy as jnp
        if self._is_model_view(x):
            return x.astype(jnp.float32)
        c, h, w = self.shape
        b = x.shape[0]
        if self.pad:
            p = self.pad
            x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
        wy, wx = self._window
        if wy or wx:
            if train and self.has_rand_crop and crop is not None:
                def one(img, oy, ox):
                    return jax.lax.dynamic_slice(img, (oy, ox, 0),
                                                 (h, w, c))
                x = jax.vmap(one)(x, crop[:, 0], crop[:, 1])
            else:
                cy, cx = wy // 2, wx // 2
                x = x[:, cy:cy + h, cx:cx + w, :]
        if train and self.rand_mirror and mirror is not None:
            # mirror on the u8 bytes, before any arithmetic: bitwise
            # exactness against the host reference is then trivial
            x = jnp.where(mirror[:, None, None, None] != 0,
                          x[:, :, ::-1, :], x)
        # u8 -> f32 via i32: XLA:TPU fuses a direct u8->f32 cast into
        # the downstream transpose as a byte-gather loop ~145x slower
        # than the i32-routed equivalent (measured before PR 1; not
        # re-measured on the current chip path)
        xf = x.astype(jnp.int32).astype(jnp.float32)
        xf = (xf - self.mean) * self._norm
        # NOTE: the executor group runs this as its OWN jitted program
        # (MeshExecutorGroup._augment_jit), never fused into the train
        # step — a different step-program preamble shifts XLA's
        # layout/fusion choices and with them the model's reduction
        # rounding, which would break the bitwise host-reference
        # parity contract.  Standalone, every op here is elementwise/
        # gather (no reductions), so the output bytes equal
        # ``apply_host`` exactly for any batch shape.
        return xf.transpose(0, 3, 1, 2)

    # -- the host reference --------------------------------------------
    def apply_host(self, x, crop=None, mirror=None, train=True):
        """Numpy reference of :meth:`apply`, pinned ELEMENTWISE-EQUAL
        by tests — same pad/crop/mirror geometry, same f32 operand
        order.  The ``placement="host"`` fallback trains through this
        path to bit-identical params."""
        x = onp.asarray(x)
        if self._is_model_view(x):
            return x.astype(onp.float32, copy=False)
        c, h, w = self.shape
        if self.pad:
            p = self.pad
            x = onp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
        wy, wx = self._window
        if wy or wx:
            if train and self.has_rand_crop and crop is not None:
                rows = [img[oy:oy + h, ox:ox + w, :]
                        for img, (oy, ox) in zip(x, onp.asarray(crop))]
                x = onp.stack(rows)
            else:
                cy, cx = wy // 2, wx // 2
                x = x[:, cy:cy + h, cx:cx + w, :]
        if train and self.rand_mirror and mirror is not None:
            flip = onp.asarray(mirror).astype(bool)
            x = onp.where(flip[:, None, None, None],
                          x[:, :, ::-1, :], x)
        xf = x.astype(onp.int32).astype(onp.float32)
        xf = (xf - self.mean) * self._norm
        return onp.ascontiguousarray(xf.transpose(0, 3, 1, 2))

    def __repr__(self):
        return ("DeviceAugment(shape=%r, in_shape=%r, pad=%d, "
                "rand_crop=%r, rand_mirror=%r, seed=%d)"
                % (self.shape, self.in_shape, self.pad, self.rand_crop,
                   self.rand_mirror, self.seed))


class DeviceAugmentIter(DataIter):
    """Attach a :class:`DeviceAugment` to a u8-HWC-emitting source.

    ``placement="device"`` (default): batches pass through as uint8
    wire blocks plus the spec's per-batch parameter arrays, and the
    iterator exposes ``device_augment_spec`` so ``Module.fit`` binds
    the augment INTO the step program (u8 staged bytes, zero host
    float work).

    ``placement="host"`` (or ``MXNET_DATA_DEVICE_AUGMENT=0``): the
    SAME draws are applied host-side through :meth:`DeviceAugment
    .apply_host` and f32 NCHW model batches are delivered — the
    reference path the CI digest gate trains against.

    Epoch coordinate: ``reset()`` advances it, ``set_epoch`` (called
    by ``fit`` with the true epoch index) pins it — a resumed run
    replays exactly the stream the uninterrupted run saw.

    ``train=False`` builds the EVAL variant: no random draws — the
    device placement ships plain wire batches (the bound program
    center-crops at ``is_train=False`` anyway) and the host placement
    applies the deterministic ``apply_host(train=False)``, so both
    placements score the identical centered stream.
    """

    def __init__(self, data_iter, augment, data_name=None,
                 placement=None, train=True):
        super().__init__(getattr(data_iter, "batch_size", 0))
        self._iter = data_iter
        self._augment = augment
        src = data_iter.provide_data
        self._name = data_name or src[0][0]
        if tuple(src[0][1][1:]) != augment.wire_shape:
            raise MXNetError(
                "source delivers %r per image but the augment spec "
                "expects wire shape %r (uint8 HWC)"
                % (tuple(src[0][1][1:]), augment.wire_shape))
        self.placement = placement or _placement_default()
        if self.placement not in ("device", "host"):
            raise MXNetError("placement must be 'device' or 'host' "
                             "(got %r)" % (self.placement,))
        self.augment_placement = self.placement
        self._train = bool(train)
        b = self.batch_size
        if self.placement == "device":
            self.provide_data = augment.data_descs(self._name, b) \
                if self._train else \
                [DataDesc(self._name, (b,) + augment.wire_shape,
                          dtype=onp.uint8, layout="NHWC")]
            self.device_augment_spec = {self._name: augment}
        else:
            self.provide_data = [DataDesc(self._name,
                                          augment.model_shape(b))]
            self.device_augment_spec = {}
        self.provide_label = data_iter.provide_label
        self._epoch = 0
        self._seq = 0

    # -- epoch coordinate ----------------------------------------------
    @property
    def epoch_coord(self):
        return self._epoch

    def set_epoch(self, epoch):
        self._epoch = int(epoch)
        self._seq = 0

    def reset(self):
        self._iter.reset()
        self._epoch += 1
        self._seq = 0

    # -- iteration ------------------------------------------------------
    def next(self):
        batch = self._iter.next()
        aug = self._augment
        img = batch.data[0]
        img = img._read() if hasattr(img, "_read") else img
        params = aug.draw(self._name, self._epoch, self._seq,
                          img.shape[0]) if self._train else {}
        self._seq += 1
        if self.placement == "device":
            data = [img] + [params[d.name] for d in
                            aug.param_descs(self._name, img.shape[0])
                            if d.name in params]
        else:
            data = [aug.apply_host(
                onp.asarray(img),
                params.get(crop_input_name(self._name)),
                params.get(mirror_input_name(self._name)),
                train=self._train)]
        return DataBatch(data=data, label=batch.label, pad=batch.pad,
                         index=batch.index)

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

    def close(self):
        inner = getattr(self._iter, "close", None)
        if callable(inner):
            inner()
