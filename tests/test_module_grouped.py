"""Iterations-per-loop training: ``fit(batch_group=K)`` stages K batches
in ONE transfer and runs K whole train steps as ONE scanned XLA program
(MeshExecutorGroup.step_update_grouped).  These tests pin the hard
claim: grouped training is BIT-IDENTICAL to K sequential per-batch
steps — params, optimizer state, BN aux, and metric values — including
non-divisible epoch tails, schedules that change mid-group, and resume
from a durable checkpoint.  The conftest provisions 8 virtual CPU
devices, so multi-device meshes are exercised without TPU hardware.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.io import NDArrayIter


def _bn_mlp():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.BatchNorm(net, name="bn", fix_gamma=False)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _module(ctxs, opt="sgd", opt_kw=None, batch=8, **fit_less_kwargs):
    mx.random.seed(42)
    mod = mx.mod.Module(_bn_mlp(), context=ctxs, **fit_less_kwargs)
    mod.bind(data_shapes=[("data", (batch, 6))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Uniform(0.07))
    mod.init_optimizer(optimizer=opt,
                       optimizer_params=opt_kw or
                       {"learning_rate": 0.1, "momentum": 0.9,
                        "wd": 1e-4})
    return mod


def _batches(n, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        [mx.nd.array(rng.rand(batch, 6).astype(np.float32))],
        [mx.nd.array(rng.randint(0, 10, batch).astype(np.float32))])
        for _ in range(n)]


def _flat_states(updater):
    def flat(st):
        if st is None:
            return []
        if isinstance(st, (tuple, list)):
            return [x for s in st for x in flat(s)]
        return [np.asarray(st._read())]

    return {k: flat(st) for k, st in updater.states.items()}


def _assert_same_training_state(a, b):
    """params + aux + optimizer states bitwise equal between modules."""
    for n, p in a._exec_group._param_dict.items():
        np.testing.assert_array_equal(
            np.asarray(p._read()),
            np.asarray(b._exec_group._param_dict[n]._read()), err_msg=n)
    for n, p in a._exec_group._aux_dict.items():
        np.testing.assert_array_equal(
            np.asarray(p._read()),
            np.asarray(b._exec_group._aux_dict[n]._read()), err_msg=n)
    sa, sb = _flat_states(a._updater), _flat_states(b._updater)
    assert sorted(sa) == sorted(sb)
    for k in sa:
        for xa, xb in zip(sa[k], sb[k]):
            np.testing.assert_array_equal(xa, xb, err_msg=str(k))


def _stack_batches(batches):
    return {"data": np.stack([b.data[0].asnumpy() for b in batches]),
            "softmax_label": np.stack([b.label[0].asnumpy()
                                       for b in batches])}


def test_grouped_step_matches_sequential_sgd_adam():
    """One step_update_grouped over K batches == K sequential one-program
    steps, bitwise (params, momentum/Adam state, BN aux, last grads),
    on a 4-device mesh."""
    batches = _batches(3)
    for opt, kw in (("sgd", None), ("adam", {"learning_rate": 0.05})):
        ctxs = [mx.cpu(i) for i in range(4)]
        seq = _module(ctxs, opt, kw)
        for b in batches:
            seq.forward_backward(b)
            seq.update()
        grp = _module(ctxs, opt, kw)
        eg = grp._exec_group
        assert eg.step_update_grouped(
            grp._updater, eg.stage_stacked(_stack_batches(batches)))
        _assert_same_training_state(seq, grp)
        # the group's exposed outputs are the LAST step's, the same
        # buffers K sequential steps would leave behind; neither kind
        # of one-program step hands its gradients back
        for mod in (seq, grp):
            with pytest.raises(mx.base.MXNetError, match="not kept"):
                mod._exec_group._grad_dict[eg._grad_names[0]]._read()
        np.testing.assert_array_equal(
            seq.get_outputs()[0].asnumpy(), grp.get_outputs()[0].asnumpy())
        assert grp._optimizer.num_update == len(batches)


def test_fit_batch_group_matches_per_batch_with_tail():
    """fit(batch_group=3) over 7 batches/epoch (groups 3+3+1, remainder
    tail) x 2 epochs == per-batch fit, bitwise, metric values included."""
    n = 8 * 7
    rng = np.random.RandomState(1)
    X = rng.rand(n, 6).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)

    mods, values = [], []
    for bg in (None, 3):
        mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(i) for i in
                                                range(4)])
        mx.random.seed(42)
        metric = mx.metric.Accuracy()
        it = NDArrayIter(X, y, batch_size=8, shuffle=False)
        mod.fit(it, num_epoch=2, eval_metric=metric,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "wd": 1e-4},
                initializer=mx.init.Uniform(0.07), batch_group=bg)
        mods.append(mod)
        values.append(metric.get_name_value())
    assert values[0] == values[1], values
    _assert_same_training_state(mods[0], mods[1])
    assert mods[1].grouped_train_engaged()
    assert not mods[0].grouped_train_engaged()
    assert mods[0]._optimizer.num_update == \
        mods[1]._optimizer.num_update == 14


def test_grouped_lr_schedule_changes_mid_group():
    """The scheduler is consulted at every true per-batch num_update
    inside the group: FactorScheduler decaying every 2 updates with
    K=4 changes the lr MID-group, and the grouped trajectory still
    matches sequential bitwise."""
    def kw():
        return {"learning_rate": 0.2,
                "lr_scheduler": mx.lr_scheduler.FactorScheduler(
                    step=2, factor=0.5)}

    batches = _batches(4, seed=5)
    ctxs = [mx.cpu(0)]
    seq = _module(ctxs, "sgd", kw())
    for b in batches:
        seq.forward_backward(b)
        seq.update()
    grp = _module(ctxs, "sgd", kw())
    eg = grp._exec_group
    assert eg.step_update_grouped(
        grp._updater, eg.stage_stacked(_stack_batches(batches)))
    _assert_same_training_state(seq, grp)
    # both clocks advanced once per BATCH, and both schedules decayed
    assert grp._optimizer.num_update == seq._optimizer.num_update == 4
    assert grp._optimizer.lr_scheduler.base_lr == \
        seq._optimizer.lr_scheduler.base_lr < 0.2


def test_stage_stacked_helper():
    """The shared stacked-staging step (scoring + grouped training):
    one (K, B, ...) block per provided input, replicated group axis
    over the 'dp'-sharded batch axis, zero-fill for bound inputs the
    block omits, NDArray or raw array accepted."""
    mod = _module([mx.cpu(i) for i in range(4)])
    eg = mod._exec_group
    block = np.random.RandomState(0).rand(2, 8, 6).astype(np.float32)
    inputs = eg.stage_stacked({"data": mx.nd.array(block)})
    assert set(inputs) == {"data", "softmax_label"}
    np.testing.assert_allclose(np.asarray(inputs["data"]), block,
                               rtol=1e-6)
    assert inputs["softmax_label"].shape == (2, 8)
    assert not np.asarray(inputs["softmax_label"]).any()  # zero-filled
    # group axis replicated, batch axis on 'dp'
    assert inputs["data"].sharding.spec == eg._stacked_sharding().spec
    assert tuple(eg._stacked_sharding().spec)[:2] == (None, "dp")
    # raw numpy blocks stage identically
    inputs2 = eg.stage_stacked({"data": block})
    np.testing.assert_array_equal(np.asarray(inputs2["data"]), block)


def test_speedometer_group_stride(caplog):
    """Speedometer must report img/s at group granularity: nbatch
    advances by K per callback, the window counts batches actually
    seen, and stride-1 behavior is unchanged (logs at multiples of
    ``frequent``)."""
    from collections import namedtuple
    P = namedtuple("P", ["epoch", "nbatch", "eval_metric", "locals"])

    with caplog.at_level(logging.INFO):
        sp = mx.callback.Speedometer(batch_size=8, frequent=4)
        for nbatch in (2, 5, 8, 11):  # stride 3 (batch_group=3)
            sp(P(0, nbatch, None, None))
    logs = [r.message for r in caplog.records if "samples/sec" in
            r.message]
    # window opens at nbatch 2; by nbatch 8 six batches were seen
    # (>= frequent) -> one log; the 3 seen by nbatch 11 stay pending
    assert len(logs) == 1 and "Batch [8]" in logs[0], logs

    caplog.clear()
    with caplog.at_level(logging.INFO):
        sp = mx.callback.Speedometer(batch_size=8, frequent=4)
        for nbatch in range(9):  # classic per-batch stride
            sp(P(0, nbatch, None, None))
    logs = [r.message for r in caplog.records if "samples/sec" in
            r.message]
    assert len(logs) == 2, logs
    assert "Batch [4]" in logs[0] and "Batch [8]" in logs[1], logs

    # one callback per epoch (epoch length <= K): the repeated equal
    # nbatch is a NEW epoch — the window must reset instead of silently
    # spanning epochs (and absorbing eval/checkpoint time between them)
    import time
    sp = mx.callback.Speedometer(batch_size=8, frequent=1)
    sp(P(0, 3, None, None))
    tic0 = sp._tic
    assert tic0 is not None
    time.sleep(0.01)
    sp(P(1, 3, None, None))
    assert sp._seen == 0 and sp._tic > tic0


def test_fit_batch_group_falls_back_with_warning(caplog):
    """A bind that cannot run grouped device steps (classic per-executor
    group) must warn once and train per batch — silently ignoring
    batch_group would fake a 110ms-per-batch amortization."""
    rng = np.random.RandomState(0)
    X = rng.rand(32, 6).astype(np.float32)
    y = rng.randint(0, 10, 32).astype(np.float32)
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)],
                        _allow_fused=False)
    it = NDArrayIter(X, y, batch_size=8, shuffle=False)
    with caplog.at_level(logging.WARNING):
        mod.fit(it, num_epoch=1, batch_group=4,
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.init.Uniform(0.07))
    assert any("batch_group" in r.message for r in caplog.records), \
        caplog.records
    assert not mod.grouped_train_engaged()


def test_fit_batch_group_resume_from_checkpoint(tmp_path):
    """Step accounting at group granularity through a preempt/resume:
    grouped fit checkpointed per epoch, killed after epoch 1, resumed
    with fit(resume_from=manager) — final state matches the
    uninterrupted grouped run bitwise."""
    n = 8 * 5
    rng = np.random.RandomState(2)
    X = rng.rand(n, 6).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)

    def fresh():
        mx.random.seed(42)
        return mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)])

    def fit(mod, num_epoch, manager=None, resume=None, begin=0):
        cb = None
        if manager is not None:
            cb = mx.callback.module_checkpoint(
                mod, save_optimizer_states=True, manager=manager,
                async_save=False)
        it = NDArrayIter(X, y, batch_size=8, shuffle=False)
        mod.fit(it, num_epoch=num_epoch, batch_group=2,
                optimizer_params={"learning_rate": 0.1,
                                  "momentum": 0.9},
                initializer=mx.init.Uniform(0.07),
                epoch_end_callback=cb, resume_from=resume,
                begin_epoch=begin)
        return mod

    straight = fit(fresh(), 2)

    manager = mx.checkpoint.CheckpointManager(str(tmp_path / "ckpt"))
    fit(fresh(), 1, manager=manager)  # "preempted" after epoch 0 commit
    resumed = fit(fresh(), 2, resume=manager)
    _assert_same_training_state(straight, resumed)
    assert straight._optimizer.num_update == 10
