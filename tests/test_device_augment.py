"""Device-side input path: u8 wire batches, the augment compiled as a
device program, and the HBM-resident dataset cache.

The contracts this file pins (ISSUE 9 acceptance):

* per-op host parity — ``DeviceAugment.apply`` (compiled) is
  ELEMENTWISE-EQUAL to ``apply_host`` (numpy) for crop/flip/normalize/
  pad, train and eval variants;
* determinism — the u8 stream is bitwise-replayable across
  ``reset()``/``set_epoch`` resume and across TransformIter worker
  counts (1/2/4);
* fed-fit digest invariance — params are bit-identical across augment
  placements (device vs the numpy host reference) and across dataset
  modes (streaming vs device-cached vs host-cached), alone and
  composed with ``prefetch_to_device`` + ``batch_group``;
* zero post-warmup retraces with augment + cache + prefetch + grouped
  steps enabled;
* the cache budget falls back to the host path gracefully;
* the once-per-process warning dedupe (the bench output's tail spam).
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.data import (CachedDataset, DeviceAugment,
                            DeviceAugmentIter, TransformIter)
from mxnet_tpu.io import NDArrayIter


def _conv_net():
    n = sym.Variable("data")
    n = sym.Convolution(n, num_filter=4, kernel=(3, 3), pad=(1, 1),
                        name="c1")
    n = sym.BatchNorm(n, name="bn", fix_gamma=False)
    n = sym.Activation(n, act_type="relu")
    n = sym.Pooling(n, kernel=(8, 8), pool_type="avg", name="pool")
    n = sym.Flatten(n)
    n = sym.FullyConnected(n, num_hidden=10, name="fc")
    return sym.SoftmaxOutput(n, name="softmax")


def _data(n=36, seed=1):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, 256, (n, 8, 8, 3)).astype(np.uint8),
            rng.randint(0, 10, n).astype(np.float32))


def _spec(**kw):
    args = dict(shape=(3, 8, 8), rand_crop=True, rand_mirror=True,
                pad=1, mean=(125.3, 123.0, 113.9),
                std=(51.6, 50.8, 51.3), scale=1.0, seed=3)
    args.update(kw)
    return DeviceAugment(**args)


def _src(Xu8, y, shuffle=False):
    return NDArrayIter(Xu8, y, batch_size=8, shuffle=shuffle)


def _fit(make_it, num_epoch=3, **fit_kw):
    mx.random.seed(42)
    np.random.seed(42)
    mod = mx.mod.Module(_conv_net(), context=[mx.cpu(0), mx.cpu(1)])
    it = make_it(mod)
    mod.fit(it, num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.init.Uniform(0.07), **fit_kw)
    return mod, it


def _assert_params_bit_equal(a, b, msg=""):
    for n, p in a._exec_group._param_dict.items():
        np.testing.assert_array_equal(
            np.asarray(p._read()),
            np.asarray(b._exec_group._param_dict[n]._read()),
            err_msg="%s:%s" % (msg, n))
    for n, p in a._exec_group._aux_dict.items():
        np.testing.assert_array_equal(
            np.asarray(p._read()),
            np.asarray(b._exec_group._aux_dict[n]._read()),
            err_msg="%s:aux:%s" % (msg, n))


# ----------------------------------------------------------------------
# DeviceAugment: compiled path == numpy host reference, per op
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kw", [
    dict(rand_crop=False, rand_mirror=False, pad=0),          # normalize
    dict(rand_crop=False, rand_mirror=True, pad=0),           # + mirror
    dict(rand_crop=True, rand_mirror=False, pad=1),           # + pad-crop
    dict(rand_crop=True, rand_mirror=True, pad=2),            # everything
    dict(rand_crop=True, rand_mirror=True, pad=0,
         in_shape=(12, 10)),                                  # crop-down
], ids=["normalize", "mirror", "padcrop", "all", "cropdown"])
def test_apply_matches_host_reference_elementwise(kw):
    import jax
    spec = _spec(**kw)
    rng = np.random.RandomState(0)
    x = rng.randint(0, 256, (8,) + spec.wire_shape).astype(np.uint8)
    params = spec.draw("data", epoch=2, index=5, batch_size=8)
    crop = params.get("data.aug_crop")
    mirror = params.get("data.aug_mirror")
    for train in (True, False):
        dev = np.asarray(jax.jit(
            lambda a, c, m: spec.apply(a, c, m, train=train))(
                x, crop, mirror))
        host = spec.apply_host(x, crop, mirror, train=train)
        np.testing.assert_array_equal(dev, host)
        assert dev.dtype == np.float32
        assert dev.shape == spec.model_shape(8)


def test_eval_variant_is_deterministic_center_crop():
    spec = _spec(pad=2)
    rng = np.random.RandomState(1)
    x = rng.randint(0, 256, (4, 8, 8, 3)).astype(np.uint8)
    p1 = spec.draw("data", 0, 0, 4)
    p2 = spec.draw("data", 5, 7, 4)
    a = spec.apply_host(x, p1["data.aug_crop"], p1["data.aug_mirror"],
                        train=False)
    b = spec.apply_host(x, p2["data.aug_crop"], p2["data.aug_mirror"],
                        train=False)
    np.testing.assert_array_equal(a, b)   # draws ignored at eval


def test_draws_are_pure_functions_of_coordinates():
    spec = _spec()
    a = spec.draw("data", 3, 11, 8)
    b = spec.draw("data", 3, 11, 8)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    c = spec.draw("data", 3, 12, 8)
    assert any(not np.array_equal(a[k], c[k]) for k in a)


# ----------------------------------------------------------------------
# stream determinism: worker counts, reset replay, set_epoch resume
# ----------------------------------------------------------------------
def _collect_epoch(it):
    out = []
    while True:
        try:
            b = it.next()
        except StopIteration:
            return out
        out.append([np.asarray(d._read() if hasattr(d, "_read") else d)
                    for d in b.data])


def test_stream_bitwise_invariant_across_worker_counts():
    Xu8, y = _data()
    spec = _spec()
    ref = None
    for workers in (1, 2, 4):
        it = TransformIter(DeviceAugmentIter(_src(Xu8, y), spec),
                           num_workers=workers)
        got = _collect_epoch(it)
        it.close()
        if ref is None:
            ref = got
            continue
        assert len(got) == len(ref)
        for bi, (ga, ra) in enumerate(zip(got, ref)):
            for da, dr in zip(ga, ra):
                np.testing.assert_array_equal(da, dr, err_msg=str(bi))


def test_set_epoch_replays_the_uninterrupted_stream():
    Xu8, y = _data()
    spec = _spec()
    # uninterrupted: epochs 0, 1, 2
    it = DeviceAugmentIter(_src(Xu8, y), spec)
    epochs = []
    for _ in range(3):
        epochs.append(_collect_epoch(it))
        it.reset()
    # "resumed": a FRESH pipeline pinned straight to epoch 2
    it2 = DeviceAugmentIter(_src(Xu8, y), spec)
    it2.set_epoch(2)
    replay = _collect_epoch(it2)
    assert len(replay) == len(epochs[2])
    for ga, ra in zip(replay, epochs[2]):
        for da, dr in zip(ga, ra):
            np.testing.assert_array_equal(da, dr)
    # and the epochs genuinely differ from one another (draws move)
    assert any(not np.array_equal(a, b) for a, b in
               zip(epochs[0][0], epochs[1][0]))


def test_device_loader_epoch_rebase_replays_without_losing_batches():
    """A DeviceLoader prefills its ring at construction (epoch coord
    0); set_epoch to a different coordinate must rewind the source
    before pinning — the prefilled batches were already pulled, and
    dropping them without a rewind would start the rebased epoch
    short (the resume-with-prefetch shape)."""
    import time
    from mxnet_tpu.data import DeviceLoader
    Xu8, y = _data()
    spec = _spec()
    ref_it = DeviceAugmentIter(_src(Xu8, y), spec)
    ref_it.set_epoch(3)
    ref = _collect_epoch(ref_it)
    loader = DeviceLoader(DeviceAugmentIter(_src(Xu8, y), spec),
                          depth=2)
    time.sleep(0.3)          # let the prefill pull at coord 0
    loader.set_epoch(3)
    got = _collect_epoch(loader)
    loader.close()
    assert len(got) == len(ref) == 5
    for ga, ra in zip(got, ref):
        for da, dr in zip(ga, ra):
            np.testing.assert_array_equal(da, dr)


def test_eval_iterator_identical_across_placements():
    """train=False builds the eval variant: both placements deliver
    the deterministic center-cropped stream (host placement must NOT
    randomly augment validation data)."""
    Xu8, y = _data()
    spec = _spec(pad=2)
    dev = DeviceAugmentIter(_src(Xu8, y), spec, train=False)
    host = DeviceAugmentIter(_src(Xu8, y), spec, placement="host",
                             train=False)
    for bd, bh in zip(_collect_epoch(dev), _collect_epoch(host)):
        # device placement ships the u8 wire (no draws attached); the
        # eval program's center crop must equal the host's apply_host
        assert len(bd) == 1 and bd[0].dtype == np.uint8
        ref = spec.apply_host(bd[0], None, None, train=False)
        np.testing.assert_array_equal(ref, bh[0])


# ----------------------------------------------------------------------
# fed-fit digest invariance
# ----------------------------------------------------------------------
def test_fit_device_placement_bit_equal_to_host_reference():
    Xu8, y = _data()
    spec = _spec()
    dev, it = _fit(lambda m: DeviceAugmentIter(_src(Xu8, y), spec))
    host, _ = _fit(lambda m: DeviceAugmentIter(_src(Xu8, y), spec,
                                               placement="host"))
    _assert_params_bit_equal(dev, host, "device-vs-host")
    # the structural half of the contract: the device run really bound
    # the augment (u8 wire) and the host run really did not
    assert dev._exec_group._device_augment
    assert not host._exec_group._device_augment


def test_fit_cached_modes_bit_equal_to_streaming():
    Xu8, y = _data()
    spec = _spec()
    stream, _ = _fit(lambda m: DeviceAugmentIter(_src(Xu8, y), spec))
    devc, itd = _fit(lambda m: CachedDataset(
        _src(Xu8, y), augment=spec, module=m, placement="device"))
    hostc, ith = _fit(lambda m: CachedDataset(
        _src(Xu8, y), augment=spec, module=m, placement="host"))
    _assert_params_bit_equal(stream, devc, "stream-vs-devcache")
    _assert_params_bit_equal(stream, hostc, "stream-vs-hostcache")
    assert itd.cache_info()["placement"] == "device"
    assert ith.cache_info()["placement"] == "host"
    assert itd.cache_info()["rows"] == len(Xu8)


def test_fit_cache_composes_with_prefetch_and_batch_group():
    """Cache + prefetch composed with grouped training is bit-equal to
    a streaming grouped run — grouped-vs-grouped, because the scanned
    K-step program is not bitwise-identical to per-batch training on
    CONV nets even without augmentation (XLA compiles the conv inside
    the scan body with different rounding; pre-existing, pinned
    bitwise only for the MLP family in test_data_pipeline)."""
    Xu8, y = _data()
    spec = _spec()
    plain, _ = _fit(lambda m: DeviceAugmentIter(_src(Xu8, y), spec),
                    batch_group=2)
    comp, _ = _fit(lambda m: CachedDataset(
        _src(Xu8, y), augment=spec, module=m, placement="device"),
        prefetch_to_device=2, batch_group=2)
    _assert_params_bit_equal(plain, comp, "grouped-vs-composed")
    assert plain.grouped_train_engaged()
    assert comp.grouped_train_engaged()


def test_zero_post_warmup_retraces_with_augment_and_cache():
    from mxnet_tpu import telemetry
    Xu8, y = _data()
    spec = _spec()
    telemetry.enable()
    watch = telemetry.compile_watch()
    before = watch.post_warmup_count
    mod, it = _fit(lambda m: CachedDataset(
        _src(Xu8, y), augment=spec, module=m, placement="device"),
        num_epoch=4, prefetch_to_device=2, batch_group=2)
    assert watch.post_warmup_count == before, watch.events()
    assert it.cache_info()["built_epoch"] == 0


# ----------------------------------------------------------------------
# cache sizing and fallback
# ----------------------------------------------------------------------
def test_cache_budget_falls_back_to_host(caplog):
    Xu8, y = _data()
    spec = _spec()
    with caplog.at_level(logging.WARNING):
        mod, it = _fit(lambda m: CachedDataset(
            _src(Xu8, y), augment=spec, module=m, budget_mb=1e-6))
    info = it.cache_info()
    assert info["placement"] == "host"
    assert any("budget" in r.getMessage() for r in caplog.records)
    # and the fallback still trains bit-identically to streaming
    stream, _ = _fit(lambda m: DeviceAugmentIter(_src(Xu8, y), spec))
    _assert_params_bit_equal(stream, mod, "budget-fallback")


def test_cache_placement_off_streams_forever():
    Xu8, y = _data()
    spec = _spec()
    it = CachedDataset(_src(Xu8, y), augment=spec, placement="off")
    for _ in range(3):
        assert len(_collect_epoch(it)) == 5   # 36 rows / 8 = 5 batches
        it.reset()
    assert it.cache_info()["placement"] is None


def test_cached_batches_bitwise_equal_host_vs_device():
    Xu8, y = _data()
    spec = _spec(rand_crop=False, rand_mirror=False, pad=0)
    streams = {}
    for placement in ("device", "host"):
        it = CachedDataset(_src(Xu8, y), augment=spec,
                           placement=placement)
        _collect_epoch(it)     # capture epoch
        it.reset()
        streams[placement] = _collect_epoch(it)
    for ba, bb in zip(streams["device"], streams["host"]):
        # device mode delivers the u8 gather output; host mode the
        # host fancy-index — same bytes
        np.testing.assert_array_equal(np.asarray(ba[0]),
                                      np.asarray(bb[0]))


# ----------------------------------------------------------------------
# the wire really is u8 (staged-bytes accounting)
# ----------------------------------------------------------------------
def test_pipeline_stats_record_u8_wire_and_placement():
    from mxnet_tpu.data import DeviceLoader
    Xu8, y = _data()
    spec = _spec()
    mx.random.seed(42)
    np.random.seed(42)
    mod = mx.mod.Module(_conv_net(), context=[mx.cpu(0), mx.cpu(1)])
    it = DeviceAugmentIter(_src(Xu8, y), spec)
    mod.fit(it, num_epoch=1, prefetch_to_device=2,
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Uniform(0.07))
    # fit closed its loader; its stats object remains readable through
    # the iterator? build one explicitly instead for the assertion
    with DeviceLoader(DeviceAugmentIter(_src(Xu8, y), spec),
                      module=mod, depth=2) as loader:
        list(loader)
        snap = loader.pipeline_stats.snapshot()
    assert snap["staged_dtype"] == "uint8"
    assert snap["augment_placement"] == "device"
    # u8 wire bytes per batch: image block + crop + mirror + labels —
    # about 4x smaller than the f32 NCHW equivalent
    f32_equiv = 8 * 3 * 8 * 8 * 4
    assert 0 < snap["staged_bytes_per_batch"] < 0.45 * f32_equiv


# ----------------------------------------------------------------------
# satellite: the re-entry advisories warn once per PROCESS
# ----------------------------------------------------------------------
def test_module_advisories_warn_once_per_process(caplog):
    from mxnet_tpu.module import base_module
    Xu8, y = _data()

    def double_fit():
        mod = mx.mod.Module(_conv_net(),
                            context=[mx.cpu(0), mx.cpu(1)])
        it = _src(Xu8.transpose(0, 3, 1, 2).astype(np.float32), y)
        for _ in range(2):
            mod.fit(it, num_epoch=1,
                    optimizer_params={"learning_rate": 0.1},
                    initializer=mx.init.Uniform(0.07))

    base_module._WARNED_PROCESS.clear()
    with caplog.at_level(logging.WARNING, logger="root"):
        double_fit()   # fresh module #1: warns once
        double_fit()   # fresh module #2: same advisory — silent
    binded = [r for r in caplog.records
              if "Already binded" in r.getMessage()
              and r.levelno == logging.WARNING]
    opt = [r for r in caplog.records
           if "optimizer already initialized" in r.getMessage()
           and r.levelno == logging.WARNING]
    assert len(binded) == 1, binded
    assert len(opt) == 1, opt
