"""THE staging rule's backend comparison (`dist.staging.host_view`).

A `jax.Array` on another backend than the target sharding's devices (a
batch `nd.array` made under the default context on a TPU machine lives
on jax's CPU backend) is staged from its host view, as a numpy value
is: no copy between two PJRT clients.  Anything else reaches
`jax.device_put` as the very object passed in.  The suite has one
backend, so the off-backend cases monkeypatch the one function that
reads an array's platform.
"""
import io

import numpy as np
import pytest

import jax
import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu import telemetry as tel
from mxnet_tpu.data import DeviceLoader
from mxnet_tpu.dist import staging
from mxnet_tpu.io import DataBatch, NDArrayIter
from mxnet_tpu.module import base_module
from mxnet_tpu.telemetry import tracing

BATCH, FEAT, GROUP = 8, 6, 2
ROUTED = "exec.stage_host_routed_bytes"


def _own(counters):
    """A report's counters but the collector's (`py.gc_*`), which a pass
    of the oldest generation adds to whatever fit it falls in."""
    return {k: v for k, v in counters.items() if not k.startswith("py.gc_")}


def _mlp():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _data(n=32, seed=1):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, FEAT).astype(np.float32),
            rng.randint(0, 10, n).astype(np.float32))


def _bound(contexts=2):
    mx.random.seed(3)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(contexts)])
    mod.bind(data_shapes=[("data", (BATCH, FEAT))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    return mod


@pytest.fixture
def puts(monkeypatch):
    """Every value `jax.device_put` is handed, in order."""
    seen, real = [], jax.device_put

    def spy(x, *args, **kwargs):
        seen.append(x)
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", spy)
    return seen


@pytest.fixture
def off_backend(monkeypatch):
    """Every array reads as living on a backend the mesh is not on."""
    monkeypatch.setattr(staging, "_array_platform", lambda arr: "elsewhere")


def _per_batch_source(kind, seed=5):
    x = np.random.RandomState(seed).rand(BATCH, FEAT).astype(np.float32)
    return x if kind == "numpy" else mx.nd.array(x)._read()


def _stage_through(site, mod, source):
    """Stage `source` (a per-batch value; stacked sites get K of it)
    through one staging site; returns (value handed in, staged array)."""
    grp = mod._exec_group
    if site == "stage_sharded":
        return source, staging.stage_sharded(source, grp._batch_sharding)
    if site == "_stage":
        batch = DataBatch(data=[mx.nd.NDArray(source)
                                if isinstance(source, jax.Array) else source],
                          label=None)
        return source, grp._stage(batch)["data"]
    if isinstance(source, np.ndarray):
        block = np.stack([source] * GROUP)
    else:
        block = jax.numpy.stack([source] * GROUP)
    return block, grp.stage_stacked({"data": block})["data"]


SITES = ["stage_sharded", "_stage", "stage_stacked"]


@pytest.mark.parametrize("kind", ["numpy", "same_backend"])
@pytest.mark.parametrize("site", SITES)
def test_same_backend_and_numpy_values_take_todays_path(site, kind, puts):
    """The very object passed in reaches `jax.device_put`, and nothing
    is counted as routed through host memory."""
    mod = _bound()
    source = _per_batch_source(kind)
    with tracing.fit_scope() as report:
        del puts[:]
        handed, staged = _stage_through(site, mod, source)
    assert any(p is handed for p in puts)
    assert ROUTED not in report.counters
    assert staged.sharding.is_equivalent_to(
        mod._exec_group._batch_sharding if site != "stage_stacked"
        else mod._exec_group._stacked_sharding(), staged.ndim)
    assert np.array_equal(np.asarray(staged), np.asarray(handed))


@pytest.mark.parametrize("site", SITES)
def test_off_backend_array_is_staged_from_its_host_view(site, puts,
                                                        off_backend):
    """The value reaches `device_put` as a numpy view of the source's
    own buffer, lands bitwise, and its bytes are counted."""
    mod = _bound()
    source = _per_batch_source("off_backend")
    with tracing.fit_scope() as report:
        del puts[:]
        handed, staged = _stage_through(site, mod, source)
    assert not any(p is handed for p in puts)
    (view,) = [p for p in puts if isinstance(p, np.ndarray)
               and p.shape == handed.shape]
    assert view.ctypes.data == handed.unsafe_buffer_pointer()
    assert np.shares_memory(view, np.asarray(handed))
    assert not view.flags.writeable
    assert np.asarray(staged).tobytes() == np.asarray(handed).tobytes()
    assert len(staged.devices()) == 2
    assert _own(report.counters) == {ROUTED: handed.nbytes}


def test_global_array_addressed_in_part_is_left_alone(off_backend):
    """A not fully addressable array is not this process's to read
    out: it comes back as it is, whatever backend it reports."""
    from unittest import mock
    sharding = _bound()._exec_group._batch_sharding
    part = mock.Mock(spec=jax.Array, is_fully_addressable=False)
    assert isinstance(part, jax.Array)
    assert staging.host_view(part, sharding) is part
    whole = _per_batch_source("off_backend")
    assert isinstance(staging.host_view(whole, sharding), np.ndarray)


def _fit(it, **kw):
    mx.random.seed(11)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0), mx.cpu(1)])
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1}, **kw)
    return {k: v.asnumpy().tobytes() for k, v in mod.get_params()[0].items()}


def test_fit_on_one_backend_routes_nothing():
    """`NDArrayIter` yields `nd.array` batches; on the suite's one
    backend they are on the mesh's backend already."""
    X, y = _data()
    _fit(NDArrayIter(X, y, batch_size=BATCH, shuffle=False))
    assert tel.last_fit()["steps"] == 8
    assert ROUTED not in tel.last_fit()["counters"]


@pytest.mark.parametrize("batch_group", [None, GROUP])
def test_fit_with_off_backend_batches_trains_bitwise_the_same(
        batch_group, puts, monkeypatch):
    """Per-batch and grouped `fit`: every batch's bytes are routed
    once, the parameters come out bit for bit, and a grouped step
    stacks on the host (one numpy block, one put, per input)."""
    X, y = _data()
    kw = {"batch_group": batch_group} if batch_group else {}
    plain = _fit(NDArrayIter(X, y, batch_size=BATCH, shuffle=False), **kw)
    monkeypatch.setattr(staging, "_array_platform", lambda arr: "elsewhere")
    del puts[:]
    routed = _fit(NDArrayIter(X, y, batch_size=BATCH, shuffle=False), **kw)
    assert routed == plain
    rep = tel.last_fit()
    assert rep["steps"] == 8
    assert _own(rep["counters"]) == {ROUTED: 2 * (X.nbytes + y.nbytes)}
    # the iterator's own `nd.array` puts hand over (BATCH, FEAT) rows
    # too: staging's are the read-only host views, or the (K, ...) blocks
    blocks = [p for p in puts if isinstance(p, np.ndarray)
              and (p.shape == (batch_group, BATCH, FEAT) if batch_group
                   else p.shape == (BATCH, FEAT) and not p.flags.writeable)]
    assert len(blocks) == rep["steps"] // (batch_group or 1)


class _NumpyIter(mx.io.DataIter):
    """Batches of plain numpy rows: what no producer has counted."""

    def __init__(self, X, y):
        super().__init__()
        self.X, self.y, self.at = X, y, 0
        self.batch_size = BATCH
        self.provide_data = [("data", (BATCH, FEAT))]
        self.provide_label = [("softmax_label", (BATCH,))]

    def reset(self):
        self.at = 0

    def next(self):
        if self.at + BATCH > len(self.X):
            raise StopIteration
        rows = slice(self.at, self.at + BATCH)
        self.at += BATCH
        return DataBatch(data=[self.X[rows]], label=[self.y[rows]], pad=0)


@pytest.mark.parametrize("batch_group", [None, GROUP])
def test_numpy_batches_count_as_h2d_once_and_are_not_routed(batch_group):
    """A value that is no `jax.Array` yet is counted where staging first
    sees it: `_stage`'s put, or the stacker of a grouped step."""
    X, y = _data()
    kw = {"batch_group": batch_group} if batch_group else {}
    from_numpy = _fit(_NumpyIter(X, y), **kw)
    rep = tel.last_fit()
    assert rep["steps"] == 8
    assert _own(rep["counters"]) == {
        "input.h2d_bytes": 2 * (X.nbytes + y.nbytes)}
    assert from_numpy == _fit(
        NDArrayIter(X, y, batch_size=BATCH, shuffle=False), **kw)


def _jpeg_rec(tmp_path, n=10, hw=(12, 12)):
    from PIL import Image
    from mxnet_tpu import recordio
    path = str(tmp_path / "ten.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        buf = io.BytesIO()
        Image.fromarray(rng.randint(0, 255, hw + (3,), dtype=np.uint8)) \
            .save(buf, format="JPEG", quality=90)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i % 3), i, 0),
                                buf.getvalue()))
    rec.close()
    return path


def test_h2d_bytes_count_each_routed_batch_once(tmp_path, off_backend):
    """`ImageRecordIter` counts the bytes it hands to `jax.device_put`
    in `input.put`; staging routes the same bytes through host memory
    and does not count them into `input.h2d_bytes` again."""
    batch, shape = 5, (3, 12, 12)
    it = mx.io.ImageRecordIter(
        path_imgrec=_jpeg_rec(tmp_path), data_shape=shape,
        batch_size=batch, rand_mirror=True, preprocess_threads=2)
    net = sym.Flatten(sym.Variable("data"))
    net = sym.FullyConnected(net, num_hidden=3, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(0)])
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.01})
    rep = tel.last_fit()
    per_step = 4 * batch * 3 * 12 * 12 + 4 * batch
    assert rep["steps"] == 4
    counters = _own(rep["counters"])
    # the iterator runs ahead: each next() found its batch or waited
    assert counters.pop("input.ready", 0) + counters.pop("input.waited", 0) \
        == rep["steps"]
    assert counters.pop("input.decode_wall_ns") > 0
    assert counters == {"input.h2d_bytes": per_step * rep["steps"],
                        ROUTED: per_step * rep["steps"]}


def test_device_loader_stages_by_the_same_rule(puts, off_backend):
    """`DeviceLoader._stage_batch` puts through `stage_sharded`: an
    off-backend batch goes up from its host view and is delivered on
    the group's sharding, bitwise."""
    X, y = _data()
    mod = _bound()
    grp = mod._exec_group
    # its own copy of the rows: an aligned numpy block put onto the
    # CPU backend is aliased, not copied, and the loader reads X too
    batch = next(iter(NDArrayIter(X.copy(), y.copy(), batch_size=BATCH,
                                  shuffle=False)))
    source = batch.data[0]._read()
    with DeviceLoader(NDArrayIter(X, y, batch_size=BATCH, shuffle=False),
                      module=mod, depth=2) as loader:
        with tracing.fit_scope() as report:
            del puts[:]
            staged = loader._stage_batch(batch)
    label = batch.label[0]._read()
    # the loader's own thread stages its ring meanwhile: pick this
    # batch's puts by the buffers they view
    for arr in (source, label):
        (view,) = [p for p in puts if isinstance(p, np.ndarray)
                   and p.ctypes.data == arr.unsafe_buffer_pointer()]
        assert view.shape == arr.shape
    assert _own(report.counters) == {ROUTED: source.nbytes + label.nbytes}
    got = staged.data[0]._read()
    assert got.sharding.is_equivalent_to(grp._batch_sharding, got.ndim)
    assert np.asarray(got).tobytes() == X[:BATCH].tobytes()


@pytest.mark.parametrize("routed", [False, True])
def test_stacker_stacks_off_backend_arrays_on_the_host(routed, monkeypatch):
    """`_stack_batch_arrays`: arrays on the sharding's backend stack
    there (no readback); off-backend ones stack as host arrays into one
    numpy block, which `stage_stacked` then puts once."""
    if routed:
        monkeypatch.setattr(staging, "_array_platform",
                            lambda arr: "elsewhere")
    mod = _bound()
    sharding = mod._exec_group._batch_sharding
    arrs = [mx.nd.array(np.full((BATCH, FEAT), k, np.float32))
            for k in range(GROUP)]
    with tracing.fit_scope() as report:
        block = base_module._stack_batch_arrays(arrs, sharding)
    assert isinstance(block, np.ndarray if routed else jax.Array)
    assert block.shape == (GROUP, BATCH, FEAT)
    assert np.array_equal(np.asarray(block),
                          np.stack([a.asnumpy() for a in arrs]))
    assert _own(report.counters) == (
        {ROUTED: block.nbytes} if routed else {})
    mixed = base_module._stack_batch_arrays(
        [arrs[0], np.zeros((BATCH, FEAT), np.float32)], sharding)
    assert isinstance(mixed, np.ndarray if routed else jax.Array)


@pytest.mark.parametrize("shape,dtype", [((3,), np.uint8),
                                         ((7, 5), np.float32),
                                         ((2, 3, 4, 5), np.float64)])
def test_aligned_empty(shape, dtype):
    from mxnet_tpu import runtime
    out = runtime.aligned_empty(shape, dtype)
    assert out.shape == shape and out.dtype == dtype
    assert out.ctypes.data % 64 == 0
    assert out.flags.c_contiguous and out.flags.writeable


def test_assembled_batch_is_aliased_by_the_cpu_backend_not_copied():
    """The iterator's float32 batch starts on a 64-byte boundary, so
    `nd.array` of it under the default context is the same memory: no
    copy inside the CPU client for the batch's staging to wait for."""
    from mxnet_tpu import runtime
    imgs = np.random.RandomState(0).randint(0, 255, (5, 12, 12, 3),
                                            dtype=np.uint8)
    out = runtime.assemble_batch(imgs, mean=np.zeros(3, np.float32),
                                 std=np.ones(3, np.float32))
    assert out.ctypes.data % 64 == 0
    assert np.array_equal(out, imgs.transpose(0, 3, 1, 2).astype(np.float32))
    arr = mx.nd.array(out)._read()
    assert arr.unsafe_buffer_pointer() == out.ctypes.data
