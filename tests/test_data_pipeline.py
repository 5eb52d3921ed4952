"""mxnet_tpu.data — the async device-feed pipeline.

Pins the subsystem's hard contracts: the parallel transform stage is a
pure THROUGHPUT knob (bitwise batch parity at 1/2/4 workers,
deterministic augment seeding across resets), the DeviceLoader's
bounded ring backpressures instead of buffering an epoch (a slow
consumer never grows it past ``depth``), shutdown mid-epoch joins every
thread, staged batches land mesh-sharded exactly as ``_stage`` would
place them, and — the headline — ``Module.fit(prefetch_to_device=N)``
trains to BIT-EQUAL parameters vs an unprefetched fit, alone and
composed with ``batch_group=K``.  The conftest provisions 8 virtual
CPU devices, so multi-device meshes run without TPU hardware.
"""
import logging
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.data import DeviceLoader, PipelineStats, TransformIter
from mxnet_tpu.io import DataBatch, NDArrayIter


def _bn_mlp():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.BatchNorm(net, name="bn", fix_gamma=False)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _data(n=56, seed=1):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 6).astype(np.float32),
            rng.randint(0, 10, n).astype(np.float32))


def _augment(batch, rng):
    """A representative random augment: additive jitter drawn from the
    per-batch rng — bitwise-reproducible iff the seeding is."""
    d = batch.data[0].asnumpy()
    d = d + rng.uniform(-0.1, 0.1, size=d.shape).astype(np.float32)
    return DataBatch([mx.nd.array(d)], batch.label, pad=batch.pad)


# ----------------------------------------------------------------------
# TransformIter: the parallel transform stage
# ----------------------------------------------------------------------
def test_transform_worker_count_invariance():
    """The delivered stream is BITWISE identical at 1/2/4 workers:
    the augment rng keys on (seed, epoch, batch index), never on
    worker identity or completion order."""
    X, y = _data()
    streams = {}
    for nw in (1, 2, 4):
        with TransformIter(NDArrayIter(X, y, batch_size=8, shuffle=False),
                           transform=_augment, num_workers=nw,
                           seed=11) as it:
            streams[nw] = [(b.data[0].asnumpy(), b.label[0].asnumpy())
                           for b in it]
    assert len(streams[1]) == 7
    for nw in (2, 4):
        for (d1, l1), (dn, ln) in zip(streams[1], streams[nw]):
            np.testing.assert_array_equal(d1, dn)
            np.testing.assert_array_equal(l1, ln)


def test_transform_deterministic_seeding_across_resets():
    """Epoch k replays bitwise across iterator instances and worker
    counts (same (seed, epoch, index) keys), while distinct epochs
    draw distinct augment streams."""
    X, y = _data()

    def epochs(nw, n_epochs=3):
        out = []
        with TransformIter(NDArrayIter(X, y, batch_size=8, shuffle=False),
                           transform=_augment, num_workers=nw,
                           seed=5) as it:
            for _ in range(n_epochs):
                out.append([b.data[0].asnumpy() for b in it])
                it.reset()
        return out

    a, b = epochs(1), epochs(4)
    for ep_a, ep_b in zip(a, b):
        for d1, d2 in zip(ep_a, ep_b):
            np.testing.assert_array_equal(d1, d2)
    # different epochs -> different augment draws (the rng folds epoch)
    assert not np.array_equal(a[0][0], a[1][0])


def test_transform_identity_is_pure_prefetch():
    """transform=None delivers the source batches untouched, in
    order — an ordered bounded-depth PrefetchingIter."""
    X, y = _data()
    plain = [b.data[0].asnumpy()
             for b in NDArrayIter(X, y, batch_size=8, shuffle=False)]
    with TransformIter(NDArrayIter(X, y, batch_size=8, shuffle=False),
                       num_workers=3) as it:
        pre = [b.data[0].asnumpy() for b in it]
    assert len(pre) == len(plain)
    for p, q in zip(plain, pre):
        np.testing.assert_array_equal(p, q)


def test_transform_error_propagates_in_order():
    """A transform raising on batch j surfaces to the consumer at
    position j, not on a worker thread."""
    X, y = _data()

    def bad(batch, rng):
        if float(batch.data[0].asnumpy()[0, 0]) == float(X[16, 0]):
            raise ValueError("boom on batch 2")
        return batch

    with TransformIter(NDArrayIter(X, y, batch_size=8, shuffle=False),
                       transform=bad, num_workers=4) as it:
        assert next(it) is not None
        assert next(it) is not None
        with pytest.raises(ValueError, match="boom"):
            next(it)


def test_transform_mid_epoch_close_joins_threads():
    """close() mid-epoch (work in flight) joins the sequencer and the
    pool; nothing is left running."""
    X, y = _data(n=512)

    def slow(batch, rng):
        time.sleep(0.01)
        return batch

    it = TransformIter(NDArrayIter(X, y, batch_size=8, shuffle=False),
                       transform=slow, num_workers=4)
    next(it)
    seq = it._sequencer
    it.close()
    assert not seq.is_alive()
    assert it._pool._shutdown
    with pytest.raises(Exception):
        it.next()


# ----------------------------------------------------------------------
# DeviceLoader: the device-resident ring
# ----------------------------------------------------------------------
def _bound_module(nctx=2, batch=8):
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(i) for i in
                                            range(nctx)])
    mod.bind(data_shapes=[("data", (batch, 6))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Uniform(0.07))
    return mod


def test_device_loader_delivers_resident_sharded_batches():
    """2-device mesh: every delivered input is already placed with the
    group's NamedSharding (per-device shards direct from host — fit's
    own device_put becomes a no-op), bitwise equal to the host rows."""
    X, y = _data()
    mod = _bound_module(nctx=2)
    eg = mod._exec_group
    with DeviceLoader(NDArrayIter(X, y, batch_size=8, shuffle=False),
                      module=mod, depth=2) as loader:
        batches = list(loader)
        assert len(batches) == 7
        for k, b in enumerate(batches):
            arr = b.data[0]._read()
            assert arr.sharding == eg._batch_sharding, k
            assert b.label[0]._read().sharding == eg._batch_sharding
            np.testing.assert_array_equal(np.asarray(arr),
                                          X[8 * k:8 * (k + 1)])
        snap = loader.pipeline_stats.snapshot()
        assert snap["batches_delivered"] == 7
        assert snap["images_delivered"] == 56
        assert snap["ring_high_water"] <= 2


def test_device_loader_backpressure_bounds_ring():
    """A slow consumer must never grow the device-resident ring past
    ``depth`` — the stager blocks (counted in ring_full_waits)
    instead of OOMing HBM with the whole epoch."""
    X, y = _data(n=400)
    stats = PipelineStats()
    with DeviceLoader(NDArrayIter(X, y, batch_size=8, shuffle=False),
                      depth=3, stats=stats) as loader:
        seen = 0
        for _ in loader:
            time.sleep(0.005)  # consumer slower than the stager
            assert len(loader._ring) <= 3
            seen += 1
        snap = stats.snapshot()
        assert seen == 50
        assert snap["ring_high_water"] <= 3
        assert snap["ring_full_waits"] >= 1  # the stager DID block


def test_device_loader_reset_and_shutdown_mid_epoch():
    """reset() mid-epoch replays the full epoch (no stale pre-reset
    batch leaks through); close() mid-epoch joins the stager."""
    X, y = _data()
    loader = DeviceLoader(NDArrayIter(X, y, batch_size=8, shuffle=False),
                          depth=2)
    first = next(loader)
    np.testing.assert_array_equal(np.asarray(first.data[0]._read()),
                                  X[:8])
    loader.reset()
    loader.reset()  # repeated reset is safe
    batches = list(loader)
    assert len(batches) == 7
    for k, b in enumerate(batches):
        np.testing.assert_array_equal(np.asarray(b.data[0]._read()),
                                      X[8 * k:8 * (k + 1)])
    loader.reset()
    next(loader)
    stager = loader._stager
    loader.close()
    assert not stager.is_alive()
    loader.close()  # idempotent
    with pytest.raises(Exception):
        loader.reset()


def test_device_loader_grouped_blocks_via_stage_stacked():
    """batch_group=K: the stager stages ONE (K, B, ...) block per K
    batches through the group's stage_stacked (stacked sharding) and
    the delivered views carry the block — Module._grouped_stage's fast
    path hands it straight to the scanned program.  The epoch tail
    forms its own smaller block."""
    X, y = _data()
    mod = _bound_module(nctx=2)
    eg = mod._exec_group
    with DeviceLoader(NDArrayIter(X, y, batch_size=8, shuffle=False),
                      module=mod, depth=2, batch_group=3) as loader:
        batches = list(loader)
    assert len(batches) == 7
    blk = mx.mod.module.Module._staged_group_block(batches[:3])
    assert blk is not None and blk is batches[0]._staged_block
    assert blk["data"].sharding == eg._stacked_sharding()
    np.testing.assert_array_equal(np.asarray(blk["data"]),
                                  X[:24].reshape(3, 8, 6))
    # tail: 7 = 3 + 3 + 1
    assert batches[6]._staged_size == 1
    assert mx.mod.module.Module._staged_group_block(
        batches[6:]) is batches[6]._staged_block
    # a misaligned group must NOT match (generic stacking handles it)
    assert mx.mod.module.Module._staged_group_block(batches[1:4]) is None


# ----------------------------------------------------------------------
# fit integration: bitwise parity
# ----------------------------------------------------------------------
def _fit_run(X, y, prefetch=None, batch_group=None, nctx=2,
             num_epoch=2, wrap=None):
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(i) for i in
                                            range(nctx)])
    mx.random.seed(42)
    metric = mx.metric.Accuracy()
    it = NDArrayIter(X, y, batch_size=8, shuffle=False)
    if wrap is not None:
        it = wrap(it)
    mod.fit(it, num_epoch=num_epoch, eval_metric=metric,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.init.Uniform(0.07), batch_group=batch_group,
            prefetch_to_device=prefetch)
    if hasattr(it, "close"):
        it.close()
    return mod, metric.get_name_value()


def _assert_params_bit_equal(a, b):
    for n, p in a._exec_group._param_dict.items():
        np.testing.assert_array_equal(
            np.asarray(p._read()),
            np.asarray(b._exec_group._param_dict[n]._read()), err_msg=n)
    for n, p in a._exec_group._aux_dict.items():
        np.testing.assert_array_equal(
            np.asarray(p._read()),
            np.asarray(b._exec_group._aux_dict[n]._read()), err_msg=n)


def test_fit_prefetch_to_device_params_bit_equal():
    """The acceptance headline: fit(prefetch_to_device=2) on a
    2-device mesh lands on bit-equal params/aux/metric vs plain fit."""
    X, y = _data()
    plain, m0 = _fit_run(X, y)
    pre, m1 = _fit_run(X, y, prefetch=2)
    assert m0 == m1
    _assert_params_bit_equal(plain, pre)


def test_fit_prefetch_composes_with_batch_group():
    """prefetch_to_device=2 + batch_group=3 (staged K-blocks through
    the ring, scanned grouped program, 7-batch epoch -> 3+3+1): still
    bit-equal to the plain per-batch run, and the grouped program
    really engaged."""
    X, y = _data()
    plain, m0 = _fit_run(X, y)
    grouped, m1 = _fit_run(X, y, prefetch=2, batch_group=3)
    assert m0 == m1
    _assert_params_bit_equal(plain, grouped)
    assert grouped.grouped_train_engaged()


def test_fit_prefetch_with_transform_stage_parity():
    """The full pipeline — TransformIter augment workers feeding the
    DeviceLoader ring — matches a serial, unprefetched run of the
    SAME deterministic augment bitwise."""
    X, y = _data()

    class _SerialAugment:
        """The reference stream: same transform, same (seed=0, epoch,
        index) keys, applied inline on the consumer thread."""

        def __init__(self, it):
            self._it = it
            self._probe = TransformIter(NDArrayIter(X, y, batch_size=8),
                                        num_workers=1)
            self._probe.close()
            self._epoch = 0
            self._seq = 0
            self.provide_data = it.provide_data
            self.provide_label = it.provide_label
            self.batch_size = it.batch_size

        def __iter__(self):
            return self

        def __next__(self):
            batch = self._it.next()
            rng = np.random.RandomState(
                self._probe._batch_seed(self._epoch, self._seq))
            self._seq += 1
            return _augment(batch, rng)

        next = __next__

        def reset(self):
            self._it.reset()
            self._epoch += 1
            self._seq = 0

    def wrap_parallel(it):
        return TransformIter(it, transform=_augment, num_workers=4,
                             seed=0)

    serial, m0 = _fit_run(X, y, wrap=_SerialAugment)
    piped, m1 = _fit_run(X, y, prefetch=2, wrap=wrap_parallel)
    assert m0 == m1
    _assert_params_bit_equal(serial, piped)


def test_fit_prefetch_logs_host_wait(caplog):
    """fit's epoch log must surface PipelineStats.host_wait_ms, and
    Speedometer lines carry the window's host-wait fraction."""
    X, y = _data()
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)])
    it = NDArrayIter(X, y, batch_size=8, shuffle=False)
    with caplog.at_level(logging.INFO):
        mod.fit(it, num_epoch=1, prefetch_to_device=2,
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.init.Uniform(0.07),
                batch_end_callback=mx.callback.Speedometer(8, 3))
    msgs = [r.getMessage() for r in caplog.records]
    assert any("Host-wait=" in m for m in msgs), msgs
    speedo = [m for m in msgs if "samples/sec" in m]
    assert speedo and all("host-wait=" in m for m in speedo), speedo


def test_predictor_accepts_prestaged_inputs():
    """Serving: a device-resident request (the arrays a DeviceLoader
    delivers) is served without a host round trip and bitwise equal
    to the same rows from host memory."""
    import jax
    from mxnet_tpu.serving import Predictor

    X, y = _data()
    mod = _bound_module(nctx=2)
    pred = Predictor(mod, max_batch_size=8)
    host = pred.predict(X[:5])
    dev = pred.predict(jax.device_put(X[:5]))
    np.testing.assert_array_equal(host, dev)
    # straight from a DeviceLoader batch (mesh-sharded resident array)
    with DeviceLoader(NDArrayIter(X, y, batch_size=8, shuffle=False),
                      module=mod, depth=2) as loader:
        batch = next(loader)
    np.testing.assert_array_equal(pred.predict(X[:8]),
                                  pred.predict(batch.data[0]))


def test_exhausted_iterators_keep_raising_stop_iteration():
    """Regression: after the epoch-end sentinel is consumed the
    producer thread has exited — another next()/iter_next() must keep
    raising StopIteration / returning False (the DataIter contract),
    not block forever on results that can never arrive."""
    X, y = _data()
    with TransformIter(NDArrayIter(X, y, batch_size=8),
                       num_workers=2) as it:
        assert len(list(it)) == 7
        with pytest.raises(StopIteration):
            it.next()
        assert it.iter_next() is False
        it.reset()  # and reset still rewinds cleanly afterwards
        assert len(list(it)) == 7
    with DeviceLoader(NDArrayIter(X, y, batch_size=8), depth=2) as dl:
        assert len(list(dl)) == 7
        with pytest.raises(StopIteration):
            dl.next()
        assert dl.iter_next() is False
        dl.reset()
        assert len(list(dl)) == 7


def test_fit_prefetch_leaves_callers_iterator_usable():
    """Regression: fit(prefetch_to_device=) closes only the loader it
    created — the caller's iterator must survive for a second fit
    (resume/continue) or any later use."""
    X, y = _data()
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)])
    with TransformIter(NDArrayIter(X, y, batch_size=8),
                       num_workers=2) as it:
        for begin in (0, 1):
            mod.fit(it, num_epoch=begin + 1, begin_epoch=begin,
                    prefetch_to_device=2,
                    optimizer_params={"learning_rate": 0.1},
                    initializer=mx.init.Uniform(0.07))
        assert len(list(it)) == 7  # still alive after both fits


def test_device_loader_threads_named_and_daemonized():
    """Hygiene: pipeline threads are identifiable and daemonic, so an
    interpreter exit with a live loader cannot hang the process."""
    X, y = _data()
    with DeviceLoader(NDArrayIter(X, y, batch_size=8), depth=2) as dl:
        assert dl._stager.daemon
        assert dl._stager.name.startswith("mxtpu-device-stager")
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith("mxtpu-")]
    assert not alive, alive
