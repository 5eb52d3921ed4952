"""Module-reachable tensor/model parallelism (VERDICT r2 #2).

``Module(mesh_axes=..., param_sharding=...)`` factorizes the bound
contexts into a named mesh and shards parameters per Megatron-style
rules; GSPMD slices the matmuls and inserts the collectives. These tests
pin (a) numerics vs the single-device run, (b) that parameters and
gradients are REALLY sharded (per-device shard shapes), and (c) the
error surface (no silent fallback to an unsharded model).

Reference surface being matched: the user-reachable ctx_group/
PlaceDevice intra-model placement (graph_executor.cc:318,
executor_group.py:77-231) — here upgraded to sharded tensor parallelism
through the same Module.fit entry point.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.io import NDArrayIter

MEGATRON_RULES = [
    # mxnet FullyConnected weight layout is (out, in):
    ("fc1_weight", ("tp", None)),   # column parallel (split outputs)
    ("fc1_bias", ("tp",)),
    ("fc2_weight", (None, "tp")),   # row parallel (split inputs)
]


def _mlp():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=64, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _data(n=64, d=32, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, d).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)
    return X, y


def _train(ctxs, steps=2, **kw):
    X, y = _data()
    it = NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=ctxs, **kw)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(3)
    np.random.seed(3)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    for _ in range(steps):
        it.reset()
        for b in it:
            mod.forward_backward(b)
            mod.update()
    return mod


def test_dp_tp_matches_single_device():
    ref = _train([mx.cpu(0)])
    tp = _train([mx.cpu(i) for i in range(8)],
                mesh_axes={"dp": 2, "tp": 4},
                param_sharding=MEGATRON_RULES)
    a = {k: v.asnumpy() for k, v in ref.get_params()[0].items()}
    b = {k: v.asnumpy() for k, v in tp.get_params()[0].items()}
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-4, atol=1e-5,
                                   err_msg=k)


def test_params_and_grads_really_sharded():
    mod = _train([mx.cpu(i) for i in range(8)], steps=1,
                 mesh_axes={"dp": 2, "tp": 4},
                 param_sharding=MEGATRON_RULES)
    eg = mod._exec_group
    w1 = eg._param_dict["fc1_weight"]._read()
    # (64, 32) split 4-way on dim 0 over tp -> each shard (16, 32)
    shard = w1.addressable_shards[0].data
    assert shard.shape == (16, 32), shard.shape
    assert str(w1.sharding.spec) in ("PartitionSpec('tp', None)",
                                     "PartitionSpec('tp',)")
    # gradients are read between backward() and update(): the fused
    # step that update() runs hands none back
    X, y = _data()
    mod.forward_backward(next(iter(NDArrayIter(
        X, y, batch_size=16, label_name="softmax_label"))))
    g1 = eg._grad_dict["fc1_weight"]._read()
    assert g1.addressable_shards[0].data.shape == (16, 32)
    w2 = eg._param_dict["fc2_weight"]._read()  # (10, 64) split on dim 1
    assert w2.addressable_shards[0].data.shape == (10, 16)
    # momentum state shards like its param after the fused step
    upd = mod._updater
    key = [i for i, n in enumerate(mod._param_names)
           if n == "fc1_weight"][0]
    st = upd.states[key]
    leaf = st[0] if isinstance(st, (tuple, list)) else st
    assert leaf._read().addressable_shards[0].data.shape == (16, 32)


def test_dp_tp_predict_matches():
    ref = _train([mx.cpu(0)], steps=1)
    tp = _train([mx.cpu(i) for i in range(8)], steps=1,
                mesh_axes={"dp": 2, "tp": 4},
                param_sharding=MEGATRON_RULES)
    X, _ = _data()
    it = NDArrayIter(X, batch_size=16)
    pa = ref.predict(it).asnumpy()
    it.reset()
    pb = tp.predict(it).asnumpy()
    np.testing.assert_allclose(pa, pb, rtol=2e-4, atol=1e-5)


def test_conv_bn_net_on_2axis_mesh():
    """A symbol with no sharded params still trains correctly on a
    2-axis mesh (pure dp semantics over dp axis, tp replicated)."""
    def net():
        s = sym.Variable("data")
        s = sym.Convolution(s, kernel=(3, 3), num_filter=8, pad=(1, 1),
                            name="conv1")
        s = sym.BatchNorm(s, name="bn1")
        s = sym.Activation(s, act_type="relu")
        s = sym.FullyConnected(sym.Flatten(s), num_hidden=10, name="fc")
        return sym.SoftmaxOutput(s, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.rand(32, 1, 8, 8).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.float32)

    def train(ctxs, **kw):
        it = NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
        mod = mx.mod.Module(net(), context=ctxs, **kw)
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mx.random.seed(5)
        np.random.seed(5)
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        it.reset()
        for b in it:
            mod.forward_backward(b)
            mod.update()
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    a = train([mx.cpu(0)])
    b = train([mx.cpu(i) for i in range(8)],
              mesh_axes={"dp": 4, "tp": 2})
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-4, atol=1e-5,
                                   err_msg=k)


def test_mesh_axes_error_surface():
    X, y = _data()
    it = NDArrayIter(X, y, batch_size=16, label_name="softmax_label")

    # product mismatch
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)],
                        mesh_axes={"dp": 2, "tp": 2})
    with pytest.raises(Exception, match="mesh_axes"):
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)

    # unknown axis in a rule
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)],
                        mesh_axes={"dp": 2, "tp": 4},
                        param_sharding=[("fc1_weight", ("ep", None))])
    with pytest.raises(Exception, match="mesh axis"):
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)

    # missing dp axis
    with pytest.raises(ValueError, match="dp"):
        mx.mod.Module(_mlp(), mesh_axes={"tp": 8})

    # not fused-eligible (batch 10 % dp=4 != 0) must raise, not silently
    # train unsharded
    it10 = NDArrayIter(X[:40], y[:40], batch_size=10,
                       label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)],
                        mesh_axes={"dp": 4, "tp": 2},
                        param_sharding=MEGATRON_RULES)
    with pytest.raises(ValueError, match="fused"):
        mod.bind(data_shapes=it10.provide_data,
                 label_shapes=it10.provide_label)
