"""mxnet_tpu.scenarios — the pinned-workload scenario matrix.

Three tiers:

* **registry / contract engine** (fast): registration validation
  refuses every malformed scenario; each contract's failure modes are
  pinned one by one against synthetic result dicts, so a red row in
  ``SCENARIO_r01.json`` always names exactly the broken claim.
* **library regressions** (fast): the two stack bugs the matrix
  surfaced stay fixed — the guardian's spike metric degrading (not
  crashing) over a non-softmax head, and shared-module binds giving
  batch-shaped ``__lr_mult__ == 0`` state args their own buffers
  instead of asserting (the Predictor-over-RNN bucket ladder).
* **matrix** (slow): the full registered matrix runs green end to
  end, and the seeded chaos sweep heals to bitwise on a live
  scenario — the in-suite spelling of ci.sh's ``dryrun_scenarios``.
"""
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.scenarios import (AccuracyFloor, BitwiseRepeat, ChaosHeal,
                                 GaugePresent, ResumeParity, Scenario,
                                 ServingParity, Verdict, ZeroRetraces,
                                 evaluate, registry)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dummy(**over):
    """A minimal VALID scenario spec; tests perturb one field each."""
    kw = dict(name="dummy", features=("fit",),
              make_module=lambda: None, make_data=lambda mod: None,
              fit_kwargs={"num_epoch": 4}, score=lambda mod: 1.0,
              floor=0.5)
    kw.update(over)
    return Scenario(**kw)


# ---------------------------------------------------------------- registry

def test_registry_refuses_duplicate_name():
    registry.register(_dummy(name="dup_probe"))
    try:
        with pytest.raises(ValueError, match="already registered"):
            registry.register(_dummy(name="dup_probe"))
    finally:
        registry.unregister("dup_probe")
    assert "dup_probe" not in registry.names()


def test_registry_refuses_unknown_feature():
    with pytest.raises(ValueError, match="unknown feature"):
        _dummy(features=("fit", "warp_drive"))


def test_registry_requires_fit():
    with pytest.raises(ValueError, match="'fit' feature"):
        _dummy(features=("telemetry",))


def test_registry_chaos_tag_and_rules_must_agree():
    with pytest.raises(ValueError, match="chaos_rules but not"):
        _dummy(chaos_rules=("data.stager:transient@nth=1",))
    with pytest.raises(ValueError, match="no chaos_rules"):
        _dummy(features=("fit", "chaos"))


def test_registry_serving_tag_requires_probe():
    with pytest.raises(ValueError, match="no serving probe"):
        _dummy(features=("fit", "serving_predictor"))


def test_registry_floor_mode_and_resume_at_validated():
    with pytest.raises(ValueError, match="floor_mode"):
        _dummy(floor_mode="sideways")
    with pytest.raises(ValueError, match="resume_at"):
        _dummy(features=("fit", "checkpoint_resume"), resume_at=9)


def test_contract_list_derived_from_features():
    plain = _dummy()
    kinds = [type(c).__name__ for c in plain.contracts()]
    assert kinds == ["BitwiseRepeat", "ZeroRetraces", "AccuracyFloor"]
    full = _dummy(features=("fit", "telemetry", "checkpoint_resume",
                            "serving_predictor"),
                  gauges=("health.armed",), serving=lambda mod: {"ok": True})
    kinds = [type(c).__name__ for c in full.contracts()]
    assert kinds == ["BitwiseRepeat", "ZeroRetraces", "AccuracyFloor",
                     "GaugePresent", "ResumeParity", "ServingParity"]


def test_selected_names_env_knobs():
    all_names = registry.names()
    assert registry.selected_names(environ={}) == all_names
    two = ",".join(all_names[:2])
    assert registry.selected_names(
        environ={"MXNET_SCENARIOS": two}) == all_names[:2]
    # a typo must not silently shrink the matrix
    with pytest.raises(KeyError, match="unknown scenario"):
        registry.selected_names(environ={"MXNET_SCENARIOS": "tpyo"})
    assert registry.selected_names(
        environ={"MXNET_SCENARIO_FILTER": "LSTM"}) == \
        [n for n in all_names if "lstm" in n]
    assert registry.selected_names(
        environ={"MXNET_SCENARIOS": two,
                 "MXNET_SCENARIO_FILTER": "no-such-substring"}) == []


def test_catalog_covers_long_tail_and_pins_real_examples():
    names = set(registry.names())
    assert {"transformer_lm", "bucketing_lstm", "nce_loss",
            "ssd_toy"} <= names
    for sc in registry.scenarios():
        assert "fit" in sc.features
        if sc.example is not None:
            script, argv = sc.example
            assert os.path.exists(os.path.join(ROOT, "example", script))
            assert isinstance(argv, (list, tuple))
    # at least one scenario arms a chaos sweep (the heal-to-bitwise gate)
    assert any(sc.chaos_rules for sc in registry.scenarios())


# -------------------------------------------------------- contract engine

GOOD = {
    "digest": "a" * 64, "repeat_digest": "a" * 64,
    "post_warmup_retraces": 0, "accuracy": 0.97,
    "gauges": {"health.armed", "data.cache_shard_bytes"},
    "resume_digest": "a" * 64,
    "serving": {"ok": True, "detail": "rows bitwise"},
    "chaos": {"digest": "a" * 64, "reference": "a" * 64,
              "incidents": 2, "unfired": []},
}


def _one(contract, result):
    v = contract.check(result)
    assert isinstance(v, Verdict)
    return v


def test_bitwise_repeat_contract():
    assert _one(BitwiseRepeat(), GOOD).ok
    bad = dict(GOOD, repeat_digest="b" * 64)
    assert not _one(BitwiseRepeat(), bad).ok
    assert not _one(BitwiseRepeat(), {}).ok


def test_zero_retraces_contract():
    assert _one(ZeroRetraces(), GOOD).ok
    v = _one(ZeroRetraces(), dict(GOOD, post_warmup_retraces=3))
    assert not v.ok and "3" in v.detail
    assert not _one(ZeroRetraces(), {}).ok


def test_accuracy_floor_contract_directions():
    assert _one(AccuracyFloor(0.9), GOOD).ok
    assert not _one(AccuracyFloor(0.99), GOOD).ok
    # mode="max": perplexity-like, lower is better
    ppl = dict(GOOD, accuracy=1.7)
    assert _one(AccuracyFloor(2.5, mode="max"), ppl).ok
    assert not _one(AccuracyFloor(1.5, mode="max"), ppl).ok
    assert not _one(AccuracyFloor(0.5), dict(GOOD,
                                             accuracy=float("nan"))).ok
    assert not _one(AccuracyFloor(0.5), {}).ok
    with pytest.raises(ValueError):
        AccuracyFloor(0.5, mode="sideways")


def test_gauge_present_contract():
    assert _one(GaugePresent(("health.armed",)), GOOD).ok
    v = _one(GaugePresent(("health.armed", "slo.missing")), GOOD)
    assert not v.ok and "slo.missing" in v.detail
    assert not _one(GaugePresent(("health.armed",)), {}).ok


def test_resume_parity_contract():
    assert _one(ResumeParity(), GOOD).ok
    assert not _one(ResumeParity(), dict(GOOD,
                                         resume_digest="b" * 64)).ok
    assert not _one(ResumeParity(), {"digest": "a" * 64}).ok


def test_serving_parity_contract():
    assert _one(ServingParity(), GOOD).ok
    assert not _one(ServingParity(),
                    dict(GOOD, serving={"ok": False})).ok
    v = _one(ServingParity(), {})
    assert not v.ok and "did not report" in v.detail


def test_chaos_heal_contract_failure_modes():
    assert _one(ChaosHeal(), GOOD).ok
    v = _one(ChaosHeal(), dict(GOOD, chaos=dict(GOOD["chaos"],
                                                digest="b" * 64)))
    assert not v.ok and "diverged" in v.detail
    v = _one(ChaosHeal(), dict(GOOD, chaos=dict(
        GOOD["chaos"], unfired=["data.stager:transient@nth=99"])))
    assert not v.ok and "unfired" in v.detail
    v = _one(ChaosHeal(), dict(GOOD, chaos=dict(GOOD["chaos"],
                                                incidents=0)))
    assert not v.ok and "no incidents" in v.detail
    assert not _one(ChaosHeal(), dict(GOOD, chaos=None)).ok


def test_evaluate_turns_raises_into_failed_verdicts():
    class Broken(BitwiseRepeat):
        name = "broken"

        def check(self, result):
            raise RuntimeError("boom")

    verdicts, green = evaluate([Broken(), ZeroRetraces()], GOOD)
    assert not green
    assert verdicts[0].contract == "broken" and not verdicts[0].ok
    assert "boom" in verdicts[0].detail
    assert verdicts[1].ok          # a broken check hides nothing
    assert evaluate([ZeroRetraces()], GOOD)[1] is True


# ----------------------------------------------------- library regressions

def test_guardian_spike_stat_degrades_over_logistic_head(tmp_path,
                                                          caplog):
    """Matrix-surfaced regression: the guardian's default cross-entropy
    spike stat cannot trace over a LogisticRegressionOutput head's
    label/output shapes; that must degrade the health ring to the
    coarse output-mean scalar (with a warning), never crash the step
    trace (mesh_executor_group._health_update)."""
    rng = np.random.RandomState(0)
    X = rng.rand(128, 8).astype(np.float32)
    # multi-column 0/1 label: fine for the logistic head, fatal for
    # the default cross-entropy spike stat (ravel doubles the rows)
    y = np.stack([X.sum(axis=1) > 4.0, X[:, 0] > 0.5],
                 axis=1).astype(np.float32)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.LogisticRegressionOutput(
        net, mx.sym.Variable("softmax_label"), name="softmax")
    mod = mx.mod.Module(net)
    data = mx.io.NDArrayIter(X, label=y, batch_size=32)
    guard = mx.guardian.Guardian(str(tmp_path / "guardian"))
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.guardian"):
        mod.fit(data, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.initializer.Xavier(),
                eval_metric=mx.metric.MSE(),
                num_epoch=2, guardian=guard)
    assert any("falling back to the coarse" in r.message
               for r in caplog.records), \
        "spike-stat degrade warning not emitted"
    args, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in args.values())


def _state_net(num_hidden=8, batch=8):
    """FC head plus a batch-shaped non-learned state arg — the shape
    class an RNN cell's zero ``begin_state`` occupies (``__lr_mult__``
    0, first dim = batch)."""
    data = mx.sym.Variable("data")
    state = mx.sym.Variable("mix_begin_state", lr_mult=0.0,
                            shape=(batch, num_hidden))
    fc = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    return mx.sym.elemwise_add(fc, state, name="mix")


def test_shared_bind_gives_state_args_fresh_buffers():
    """Matrix-surfaced regression: a shared-module bind at a smaller
    batch (a Predictor bucket) must give batch-shaped lr_mult==0 state
    args their own zero buffers instead of asserting on the parent's
    shape, while still sharing every learned param buffer."""
    base = mx.mod.Module(_state_net(batch=8), label_names=[])
    base.bind(data_shapes=[("data", (8, 4))], for_training=False)
    base.init_params(mx.init.Xavier())

    small = mx.mod.Module(_state_net(batch=2), label_names=[])
    small.bind(data_shapes=[("data", (2, 4))], for_training=False,
               shared_module=base)            # raised AssertionError
    xb = np.arange(8 * 4, dtype=np.float32).reshape(8, 4) / 10.0
    base.forward(mx.io.DataBatch(data=[mx.nd.array(xb)]),
                 is_train=False)
    small.forward(mx.io.DataBatch(data=[mx.nd.array(xb[:2])]),
                  is_train=False)
    big = base.get_outputs()[0].asnumpy()
    cut = small.get_outputs()[0].asnumpy()
    # learned params shared bitwise -> identical rows on the same data
    np.testing.assert_array_equal(big[:2], cut)


def test_shared_bind_still_rejects_learned_param_mismatch():
    base = mx.mod.Module(_state_net(num_hidden=8), label_names=[])
    base.bind(data_shapes=[("data", (8, 4))], for_training=False)
    base.init_params(mx.init.Xavier())
    clash = mx.mod.Module(_state_net(num_hidden=16), label_names=[])
    with pytest.raises(MXNetError, match="learned param"):
        clash.bind(data_shapes=[("data", (8, 4))], for_training=False,
                   shared_module=base)


def test_predictor_serves_rnn_state_params_across_buckets():
    """The end-to-end shape of the same regression: a Predictor built
    over a module whose symbol carries batch-shaped begin-state vars
    binds its whole bucket ladder (every bucket a shared bind at a
    different batch) and serves rows bitwise-equal to the module."""
    from mxnet_tpu.serving import Predictor
    V, T = 12, 6
    cell = mx.rnn.FusedRNNCell(8, num_layers=1, mode="lstm",
                               prefix="lstm_")
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data, input_dim=V, output_dim=4,
                           name="embed")
    out, _ = cell.unroll(T, inputs=emb, merge_outputs=True)
    pred = mx.sym.FullyConnected(mx.sym.Reshape(out, shape=(-1, 8)),
                                 num_hidden=V, name="pred")
    net = mx.sym.Reshape(mx.sym.softmax(pred, axis=-1),
                         shape=(-1, T * V), name="rows")
    mod = mx.mod.Module(net, label_names=[])
    mod.bind(data_shapes=[("data", (8, T))], for_training=False)
    mod.init_params(mx.init.Xavier())
    tokens = np.arange(8 * T, dtype=np.float32).reshape(8, T) % V
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)]),
                is_train=False)
    ref = mod.get_outputs()[0].asnumpy()
    pr = Predictor(mod, max_batch_size=8)
    try:
        for rows in (1, 3, 8):     # distinct ladder buckets
            got = pr.predict(tokens[:rows])
            np.testing.assert_array_equal(ref[:rows],
                                          np.asarray(got))
    finally:
        pr.release()


# ----------------------------------------------------------------- matrix

@pytest.mark.slow
def test_full_matrix_green():
    """Every registered scenario holds its full contract set through
    the real fit/serving stack (the dryrun_scenarios gate, in-suite,
    without the chaos sweeps)."""
    from mxnet_tpu import scenarios
    report = scenarios.run_matrix()
    assert report["selected"] == registry.names()
    for name, row in report["scenarios"].items():
        bad = {c: v for c, v in row["contracts"].items()
               if not v["ok"]}
        assert row["green"], "scenario %s failed %r" % (name, bad)
        assert row["post_warmup_retraces"] == 0
    assert report["green"]


@pytest.mark.slow
def test_chaos_sweep_heals_to_bitwise():
    """The seeded chaos sweep on a live scenario: every planned rule
    fires, every incident heals, and the trained params land bitwise
    on the fault-free run."""
    from mxnet_tpu import scenarios
    row = scenarios.run_scenario(registry.get("nce_loss"), chaos=True)
    assert row["green"], row["contracts"]
    ch = row["chaos"]
    assert ch["incidents"] >= 1 and not ch["unfired"]
    assert ch["digest"] == row["digest"]
