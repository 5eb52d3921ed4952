"""mxnet_tpu.precision — opt-in precision modes with per-mode parity
contracts (bf16 optimizer state, low-bit casts, named remat policies).

Every mode is allowed to change numerics vs f32, but carries the same
contracts (docs/api/precision.md):

* within-mode bitwise reproducibility — same mode + seed -> identical
  params (incl. grouped steps and checkpoint save->restore->resume),
  with ZERO post-warmup retraces under CompileWatch;
* the f32 mode is byte-identical to no policy at all — params bitwise
  equal AND the compiled step program's analyzed bytes unchanged;
* the introspection witness — bf16 optimizer state must shrink the
  step program's argument bytes and cut the analytic optimizer-update
  account by exactly 20% (2 of the 5 param-sized sgd-momentum streams
  halve: 4*(3p+2p) -> 4*3p+2*2p);
* cross-mode optimizer-state restores are refused loudly (v2 envelope
  dtype check), legacy f32 payloads still load into an f32 Updater;
* serving refuses a checkpoint whose recorded mode mismatches the
  bound module's policy.
"""
import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.precision import (MODES, PrecisionPolicy, canon_dtype,
                                 canon_remat, mode_name, resolve,
                                 wrap_fused_apply)

BATCH = 8


def _bn_mlp():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.BatchNorm(net, name="bn", fix_gamma=False)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _module(opt="sgd", opt_kw=None, **kw):
    mx.random.seed(42)
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)], **kw)
    mod.bind(data_shapes=[("data", (BATCH, 6))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.init.Uniform(0.07))
    mod.init_optimizer(optimizer=opt,
                       optimizer_params=opt_kw or
                       {"learning_rate": 0.1, "momentum": 0.9,
                        "wd": 1e-4})
    return mod


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [mx.io.DataBatch(
        [mx.nd.array(rng.rand(BATCH, 6).astype(np.float32))],
        [mx.nd.array(rng.randint(0, 10, BATCH).astype(np.float32))])
        for _ in range(n)]


def _train(mod, n=6, seed=0):
    for b in _batches(n, seed=seed):
        mod.forward(b)
        mod.backward()
        mod.update()
    return _params(mod)


def _params(mod):
    return {n: np.asarray(p._read())
            for n, p in mod._exec_group._param_dict.items()}


def _assert_equal(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def _compiled_step(mod):
    """The bound one-program train step, re-acquired through the jit
    trace cache."""
    fn, structs = mod._exec_group._last_step
    return fn.lower(*structs).compile()


def _state_leaves(updater):
    def flat(st):
        if st is None:
            return []
        if isinstance(st, (tuple, list)):
            return [x for s in st for x in flat(s)]
        return [st]

    return [x for st in updater.states.values() for x in flat(st)]


# ------------------------------------------------------------------ policy
def test_mode_registry_and_resolve():
    assert resolve(None) is None                     # implicit f32
    assert resolve("f32") is MODES["f32"]
    assert resolve("combined").opt_state_dtype == "bfloat16"
    assert resolve("combined").remat == "dots"
    pol = PrecisionPolicy(opt_state_dtype="bf16")
    assert resolve(pol) is pol
    with pytest.raises(MXNetError):
        resolve("no_such_mode")
    assert mode_name(None) == "f32"
    assert mode_name(MODES["combined"]) == "combined"


def test_mode_env_default(monkeypatch):
    monkeypatch.setenv("MXNET_PRECISION_MODE", "bf16_opt")
    assert resolve(None) is MODES["bf16_opt"]
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)])
    assert mod.precision_mode == "bf16_opt"


def test_experimental_modes_gated(monkeypatch):
    monkeypatch.delenv("MXNET_PRECISION_EXPERIMENTAL", raising=False)
    with pytest.raises(MXNetError):
        resolve("int8_act")
    monkeypatch.setenv("MXNET_PRECISION_EXPERIMENTAL", "1")
    assert resolve("fp8").act_cast == "fp8"
    # narrow backward defaults a loss scale — resolved LAZILY at bind
    # time (loss_scale_config) so env knobs set after import still win
    from mxnet_tpu.precision import loss_scale_config
    cfg = loss_scale_config(resolve("fp8"))
    assert cfg["init"] == 2.0 ** 15 and cfg["window"] == 2000
    monkeypatch.setenv("MXNET_PRECISION_LOSS_SCALE", "1024")
    monkeypatch.setenv("MXNET_PRECISION_SCALE_WINDOW", "50")
    cfg = loss_scale_config(resolve("fp8"))
    assert cfg["init"] == 1024.0 and cfg["window"] == 50


def test_policy_canonicalization_and_naming():
    assert canon_dtype("f32") is None
    assert canon_dtype("bf16") == "bfloat16"
    with pytest.raises(MXNetError):
        canon_dtype("float16")
    assert canon_remat("none") is None
    assert canon_remat("dots_saveable") == "dots"
    assert canon_remat("offload_bn_stats") == "bn_stats"
    with pytest.raises(MXNetError):
        canon_remat("everything")
    # deterministic auto-name: the ci gate's two runs and a checkpoint
    # manifest must agree on the spelling
    a = PrecisionPolicy(opt_state_dtype="bf16", remat="dots_saveable")
    b = PrecisionPolicy(opt_state_dtype="bfloat16", remat="dots")
    assert a.name == b.name == "custom(opt=bfloat16,remat=dots)"
    assert PrecisionPolicy().is_default()
    assert not a.is_default()
    # loss-scale fields are part of the identity: a scale-only policy
    # changes numerics (the device scaler engages), so it must NOT
    # collide with the f32 baseline name — manifest adoption and the
    # serving refusal compare by name
    ls = PrecisionPolicy(loss_scale=1024)
    assert not ls.is_default()
    assert ls.name == "custom(ls=1024)"
    assert PrecisionPolicy(loss_scale=1024, loss_scale_window=64).name \
        == "custom(ls=1024,lsw=64)"


def test_policy_manifest_roundtrip_preserves_all_fields():
    """An ad-hoc policy reconstructed from its manifest record
    (mode name + describe() dict) must be field-identical — in
    particular the loss-scale window, whose doubling schedule changes
    the within-mode trajectory."""
    pol = PrecisionPolicy(compute_dtype="bf16", act_cast="int8",
                          loss_scale=512, loss_scale_window=100,
                          experimental=True)
    back = mx.mod.Module._policy_from_manifest(pol.name, pol.describe())
    assert back.describe() == pol.describe()


def test_fused_apply_wrapper_upcasts_and_rounds_back():
    import jax.numpy as jnp

    def fa(jnp, p, g, s, lr, wd):
        assert s.dtype == jnp.float32      # master math sees f32
        ns = s * 0.9 + g
        return p - lr * ns, ns

    wrapped = wrap_fused_apply(fa, "bfloat16")
    p = jnp.ones((4,), jnp.float32)
    g = jnp.full((4,), 0.123456789, jnp.float32)
    s = jnp.full((4,), 0.333, jnp.bfloat16)
    new_p, new_s = wrapped(jnp, p, g, s, 0.1, 0.0)
    assert new_s.dtype == jnp.bfloat16     # rounds back to storage
    ref = np.asarray(s, np.float32) * 0.9 + np.asarray(g)
    np.testing.assert_array_equal(np.asarray(new_s, np.float32),
                                  np.asarray(ref.astype(jnp.bfloat16),
                                             np.float32))
    # param update consumed the UNROUNDED f32 state
    np.testing.assert_array_equal(np.asarray(new_p),
                                  np.asarray(p) - 0.1 * ref)


# ------------------------------------------------------- training contracts
def test_f32_mode_is_byte_identical_to_no_policy():
    """precision='f32' must change NOTHING: params bitwise equal and
    the compiled step program's analyzed bytes identical to a module
    built without a policy (the satellite's gauges-byte-identical
    pin)."""
    from mxnet_tpu.telemetry.introspect import analyze_compiled
    plain = _module()
    named = _module(precision="f32")
    _assert_equal(_train(plain), _train(named))
    a = analyze_compiled(_compiled_step(plain))
    b = analyze_compiled(_compiled_step(named))
    assert a == b
    assert named.precision_mode == "f32"


def test_bf16_opt_state_dtype_and_within_mode_reproducibility():
    m1 = _module(precision="bf16_opt")
    p1 = _train(m1)
    leaves = _state_leaves(m1._updater)
    assert leaves and all(
        np.dtype(x.dtype).name == "bfloat16" for x in leaves)
    # same mode + seed -> bit-identical params
    _assert_equal(p1, _train(_module(precision="bf16_opt")))
    # ...and the mode genuinely engaged: the bf16-rounded momentum
    # trajectory differs from f32
    pf = _train(_module())
    assert any(not np.array_equal(p1[k], pf[k]) for k in p1)


def test_bf16_opt_adam_moments_narrowed():
    kw = {"learning_rate": 0.01}
    m = _module(opt="adam", opt_kw=kw, precision="bf16_opt")
    p1 = _train(m)
    leaves = _state_leaves(m._updater)
    assert len(leaves) >= 2 and all(
        np.dtype(x.dtype).name == "bfloat16" for x in leaves)
    _assert_equal(p1, _train(_module(opt="adam", opt_kw=kw,
                                     precision="bf16_opt")))


def test_grouped_steps_match_sequential_under_mode():
    """fit(batch_group=K)'s scanned program under bf16_opt stays
    bit-identical to K per-batch steps — params AND bf16 state."""
    bs = _batches(4)
    seq = _module(precision="bf16_opt")
    for b in bs:
        seq.forward(b)
        seq.backward()
        seq.update()
    grp = _module(precision="bf16_opt")
    stacked = {
        "data": np.stack([b.data[0].asnumpy() for b in bs]),
        "softmax_label": np.stack([b.label[0].asnumpy() for b in bs])}
    eg = grp._exec_group
    assert eg.step_update_grouped(grp._updater, eg.stage_stacked(stacked))
    _assert_equal(_params(seq), _params(grp))
    for a, b in zip(_state_leaves(seq._updater),
                    _state_leaves(grp._updater)):
        np.testing.assert_array_equal(np.asarray(a._read()),
                                      np.asarray(b._read()))


def test_combined_mode_reproducible_and_remat_modes_train():
    p1 = _train(_module(precision="combined"))
    _assert_equal(p1, _train(_module(precision="combined")))
    pol = PrecisionPolicy(remat="offload_bn_stats")
    p2 = _train(_module(precision=pol))
    _assert_equal(p2, _train(_module(precision=pol)))


def test_fit_zero_post_warmup_retraces(tmp_path):
    """The steady-state contract under the combined mode: after fit's
    first epoch declares the warmup boundary, the mode's train loop
    must never retrace (CompileWatch), and two seeded fits land on
    bit-identical params."""
    from mxnet_tpu import telemetry as tel

    def fit():
        mx.random.seed(11)
        np.random.seed(11)
        rng = np.random.RandomState(5)
        X = rng.rand(32, 6).astype(np.float32)
        y = rng.randint(0, 10, 32).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=BATCH,
                               label_name="softmax_label")
        mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)],
                            precision="combined")
        mod.fit(it, num_epoch=3, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1,
                                  "momentum": 0.9},
                initializer=mx.init.Uniform(0.07))
        args, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in args.items()}

    was = tel.enabled()
    tel.enable()
    try:
        p1 = fit()
        assert tel.compile_watch().post_warmup_count == 0
        p2 = fit()
        assert tel.compile_watch().post_warmup_count == 0
    finally:
        if not was:
            tel.disable()
    _assert_equal(p1, p2)


# ----------------------------------------------------- introspection witness
def test_byte_witness_argument_bytes_and_optimizer_account():
    """THE byte witness: bf16 optimizer state must shrink the step
    program's argument bytes (the state operands halve) and cut the
    analytic optimizer-update account by EXACTLY 20% — sgd-momentum's
    five param-sized streams (read w/g/m + write w/m) become
    4*(3p) + 2*(2p) of the f32 4*(3p+2p)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry.introspect import analyze_compiled

    f32 = _module()
    bf = _module(precision="bf16_opt")
    _train(f32, 2)
    _train(bf, 2)
    a = analyze_compiled(_compiled_step(f32))
    b = analyze_compiled(_compiled_step(bf))
    if a.get("argument_bytes"):     # memory analysis is backend-optional
        assert b["argument_bytes"] < a["argument_bytes"]
    assert b["bytes_accessed"] < a["bytes_accessed"]

    inv = telemetry.inventory()

    def account(mod):
        name = mod._exec_group._program_names["optimizer_update"]
        return inv.analyze(name)

    acc_f, acc_b = account(f32), account(bf)
    assert acc_f["bytes_accessed"] > 0
    np.testing.assert_allclose(
        acc_b["bytes_accessed"] / acc_f["bytes_accessed"], 0.8,
        rtol=1e-6)
    assert acc_b["meta"]["precision_mode"] == "bf16_opt"
    assert acc_f["meta"]["precision_mode"] == "f32"


def test_inventory_step_bytes_drop_under_bf16_opt_state():
    """The inventory's account of the train step (registered after the
    policy applied) carries the mode's true bytes: fewer under
    bf16_opt than under f32."""
    from mxnet_tpu import telemetry

    f32 = _module()
    bf = _module(precision="bf16_opt")
    _train(f32, 2)
    _train(bf, 2)
    inv = telemetry.inventory()

    def step_bytes(mod):
        a = inv.analyze(mod._exec_group._program_names["train_step"])
        assert a["flops"] > 0
        return a["bytes_accessed"]

    assert 0 < step_bytes(bf) < step_bytes(f32)


# ------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip_bf16_bit_exact(tmp_path):
    """save -> restore -> resume inside the mode is bit-exact: the v2
    envelope round-trips bf16 state leaves and the manifest's recorded
    mode is adopted by Module.load."""
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
    a = _module(precision="bf16_opt")
    _train(a, 3)
    a.save_checkpoint(None, 3, save_optimizer_states=True, manager=mgr,
                      async_save=False)
    b = mx.mod.Module.load(mgr, load_optimizer_states=True,
                           context=[mx.cpu(0)])
    assert b.precision_mode == "bf16_opt"
    b.bind(data_shapes=[("data", (BATCH, 6))],
           label_shapes=[("softmax_label", (BATCH,))])
    b.init_optimizer(optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1,
                                       "momentum": 0.9, "wd": 1e-4})
    for x, y in zip(_state_leaves(a._updater),
                    _state_leaves(b._updater)):
        assert np.dtype(y.dtype).name == "bfloat16"
        np.testing.assert_array_equal(np.asarray(x._read()),
                                      np.asarray(y._read()))
    # resumed trajectory == uninterrupted trajectory, bit for bit
    _assert_equal(_train(a, 3, seed=1), _train(b, 3, seed=1))


def test_cross_mode_state_restore_refused():
    bf = _module(precision="bf16_opt")
    _train(bf, 2)
    blob = bf._updater.get_states()
    with pytest.raises(MXNetError, match="state_dtype"):
        _module()._updater.set_states(blob)
    # and the reverse: f32 states into a bf16-mode Updater
    f32 = _module()
    _train(f32, 2)
    with pytest.raises(MXNetError, match="state_dtype"):
        _module(precision="bf16_opt")._updater.set_states(
            f32._updater.get_states())


def test_tampered_per_leaf_dtype_record_refused():
    """The v2 envelope's per-leaf dtype record is verified at restore:
    a payload whose recorded leaf dtypes disagree with its actual state
    leaves (corruption/hand-editing) is refused."""
    src = _module(precision="bf16_opt")
    _train(src, 2)
    payload = pickle.loads(src._updater.get_states())
    k = next(iter(payload["state_dtypes"]))
    payload["state_dtypes"][k] = "float32"
    with pytest.raises(MXNetError, match="inconsistent"):
        _module(precision="bf16_opt")._updater.set_states(
            pickle.dumps(payload))


def test_legacy_f32_payload_still_loads():
    """Pre-precision payloads (bare states dict, no dtype fields) keep
    loading into an f32-mode Updater."""
    src = _module()
    _train(src, 2)
    legacy = pickle.dumps(src._updater.states)
    dst = _module()
    dst._updater.set_states(legacy)
    for a, b in zip(_state_leaves(src._updater),
                    _state_leaves(dst._updater)):
        np.testing.assert_array_equal(np.asarray(a._read()),
                                      np.asarray(b._read()))


def test_elastic_resume_dp8_to_dp4_bf16(tmp_path):
    """The elastic contract composed with bf16 optimizer state: kill
    at a step between commits under dp=8 (virtual hosts), resume at
    dp=4 — params and the bf16 state come back bit-exact vs a
    continuous dp=4 run from the same committed entry."""
    import hashlib
    import shutil

    from mxnet_tpu import dist
    from mxnet_tpu.checkpoint import CheckpointManager

    rng = np.random.RandomState(0)
    X = rng.rand(256, 16).astype(np.float32)
    y = rng.randint(0, 10, 256).astype(np.float32)

    def _iter():
        return mx.io.NDArrayIter(X, y, batch_size=32,
                                 label_name="softmax_label")

    def _mlp():
        net = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    def factory(world):
        return mx.mod.Module(_mlp(), context=world.contexts(),
                             precision="bf16_opt")

    def digest(mod):
        h = hashlib.sha256()
        args, auxs = mod.get_params()
        for k in sorted(args):
            h.update(args[k].asnumpy().tobytes())
        return h.hexdigest()

    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              initializer=mx.initializer.Xavier())
    tmp = str(tmp_path)
    mgr = CheckpointManager(os.path.join(tmp, "ckpt"))
    cluster = dist.VirtualCluster(4)
    mx.random.seed(3)
    np.random.seed(3)
    tr = dist.ElasticTrainer(cluster, factory,
                             lambda w: w.feed(_iter()), mgr,
                             checkpoint_every_steps=4)
    mod = tr.fit(num_epoch=3, inject_fault=(14, (2, 3)), **kw)
    done = [e for e in tr.transcript if e["event"] == "finished"]
    assert done and done[0]["dp_width"] == 4
    resume_step = done[0]["resume_step"]

    src = os.path.join(tmp, "ckpt", "step_%08d" % resume_step)
    dst_dir = os.path.join(tmp, "baseline")
    shutil.copytree(src,
                    os.path.join(dst_dir, "step_%08d" % resume_step))
    cluster4 = dist.VirtualCluster(4).shrink((2, 3))
    mod2 = factory(cluster4)
    mx.random.seed(99)
    np.random.seed(99)
    mod2.fit(cluster4.feed(_iter()), num_epoch=3,
             resume_from=CheckpointManager(dst_dir), **kw)
    assert digest(mod) == digest(mod2)
    for a, b in zip(_state_leaves(mod._updater),
                    _state_leaves(mod2._updater)):
        assert np.dtype(a.dtype).name == "bfloat16"
        np.testing.assert_array_equal(np.asarray(a._read()),
                                      np.asarray(b._read()))

    from mxnet_tpu import telemetry
    telemetry.flight_recorder().disarm()
    telemetry.flight_recorder().pop_last_dump()


# ------------------------------------------------------------------ serving
def test_serving_refuses_mode_mismatch(tmp_path):
    from mxnet_tpu.serving import Predictor

    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
    a = _module(precision="bf16_opt")
    _train(a, 2)
    a.save_checkpoint(None, 1, save_optimizer_states=False, manager=mgr,
                      async_save=False)
    # explicit wrong-mode override is refused at construction
    wrong = mx.mod.Module.load(mgr, context=[mx.cpu(0)],
                               precision="f32")
    with pytest.raises(MXNetError, match="precision mode"):
        Predictor(wrong, data_shapes=[("data", (BATCH, 6))],
                  max_batch_size=BATCH)
    # dropping the override adopts the recorded mode and serves with
    # bitwise parity to Module.predict
    pred = Predictor.load(mgr, data_shapes=[("data", (BATCH, 6))],
                          context=[mx.cpu(0)], max_batch_size=BATCH)
    assert pred._base.precision_mode == "bf16_opt"
    X = np.random.RandomState(3).rand(4, 6).astype(np.float32)
    served = pred.predict(X)
    it = mx.io.NDArrayIter(X, None, batch_size=4)
    ref = a.predict(it).asnumpy()
    np.testing.assert_array_equal(np.asarray(served), ref[:4])


def test_serving_buckets_strip_training_only_policy_fields():
    """Predictor bucket modules keep the mode NAME (telemetry/roofline
    attribution) but carry only the eval-visible policy fields: remat
    and opt-state dtype are training-only, so inference buckets must
    not build segmented-remat evaluators or trip the fused-path
    requirement — and parity with Module.predict still holds."""
    from mxnet_tpu.serving import Predictor

    m = _module(precision="combined")
    _train(m, 2)
    pred = Predictor(m, data_shapes=[("data", (BATCH, 6))],
                     max_batch_size=BATCH)
    for bm in pred._modules.values():
        assert bm.precision_mode == "combined"
        assert bm._remat is None
        assert bm._precision.opt_state_dtype is None
    X = np.random.RandomState(5).rand(4, 6).astype(np.float32)
    served = pred.predict(X)
    ref = m.predict(mx.io.NDArrayIter(X, None, batch_size=4)).asnumpy()
    np.testing.assert_array_equal(np.asarray(served), ref[:4])


def test_manifest_record_wins_over_registry_drift(tmp_path):
    """A name hit in the live MODES registry is not provenance: when
    the registered mode's fields no longer match what the checkpoint
    recorded (register_mode overwrites names), the RECORDED policy —
    the numerics family the params were actually trained in — wins."""
    from mxnet_tpu.precision import register_mode

    register_mode(PrecisionPolicy("site_mode", opt_state_dtype="bf16"))
    try:
        mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
        a = _module(precision="site_mode")
        _train(a, 2)
        a.save_checkpoint(None, 1, save_optimizer_states=False,
                          manager=mgr, async_save=False)
        # the name now resolves to DIFFERENT fields
        register_mode(PrecisionPolicy("site_mode",
                                      opt_state_dtype="bf16",
                                      remat="dots"))
        b = mx.mod.Module.load(mgr, context=[mx.cpu(0)])
        assert b.precision_mode == "site_mode"
        assert b._precision.remat is None           # recorded fields won
        assert b._precision.opt_state_dtype == "bfloat16"
    finally:
        MODES.pop("site_mode", None)


# ------------------------------------------------- experimental narrow modes
def test_int8_act_reproducible_with_live_loss_scale(monkeypatch):
    monkeypatch.setenv("MXNET_PRECISION_EXPERIMENTAL", "1")
    m1 = _module(precision="int8_act")
    p1 = _train(m1, 4)
    _assert_equal(p1, _train(_module(precision="int8_act"), 4))
    # the device-resident scaler is live and readable off the hot path
    assert m1._exec_group.loss_scale() is not None
    assert m1._exec_group.loss_scale() >= 1.0
    # ...and well-defined from bind onward: before the first step the
    # configured init is reported, not None
    monkeypatch.delenv("MXNET_PRECISION_LOSS_SCALE", raising=False)
    fresh = _module(precision="int8_act")
    assert fresh._exec_group.loss_scale() == 2.0 ** 15
    # quantization engaged: params differ from the unquantized run
    pf = _train(_module(), 4)
    assert any(not np.array_equal(p1[k], pf[k]) for k in p1)


def test_loss_scale_transition_rule():
    """The AMP transition table, on device values: overflow halves and
    zeroes the growth counter; `window` consecutive finite steps
    double, clamped to [scale_min, scale_max]."""
    import jax.numpy as jnp

    from mxnet_tpu.module.mesh_executor_group import _ls_update

    cfg = {"window": 2, "scale_max": 2.0 ** 24, "scale_min": 1.0}
    scale = jnp.float32(1024.0)
    good = jnp.int32(0)
    # finite step: counter grows, scale holds
    s, g = _ls_update(jnp, cfg, scale, good, jnp.asarray(True))
    assert float(s) == 1024.0 and int(g) == 1
    # second finite step completes the window: scale doubles
    s, g = _ls_update(jnp, cfg, s, g, jnp.asarray(True))
    assert float(s) == 2048.0 and int(g) == 0
    # overflow: halve, reset counter
    s, g = _ls_update(jnp, cfg, s, jnp.int32(1), jnp.asarray(False))
    assert float(s) == 1024.0 and int(g) == 0
    # clamps
    s, _ = _ls_update(jnp, cfg, jnp.float32(2.0 ** 24), jnp.int32(1),
                      jnp.asarray(True))
    assert float(s) == 2.0 ** 24
    s, _ = _ls_update(jnp, cfg, jnp.float32(1.0), jnp.int32(0),
                      jnp.asarray(False))
    assert float(s) == 1.0


# ------------------------------------------------------------------ guards
def test_non_default_mode_requires_fused_path(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)],
                        precision="bf16_opt")
    with pytest.raises(ValueError, match="fused mesh path"):
        mod.bind(data_shapes=[("data", (BATCH, 6))],
                 label_shapes=[("softmax_label", (BATCH,))])
    # the f32 mode stays allowed everywhere (it changes nothing)
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)], precision="f32")
    mod.bind(data_shapes=[("data", (BATCH, 6))],
             label_shapes=[("softmax_label", (BATCH,))])


def test_optimizer_instance_state_dtype_conflict():
    from mxnet_tpu import optimizer as opt

    sgd = opt.SGD(momentum=0.9, learning_rate=0.1, state_dtype="f32")
    mod = mx.mod.Module(_bn_mlp(), context=[mx.cpu(0)],
                        precision="bf16_opt")
    mod.bind(data_shapes=[("data", (BATCH, 6))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.init.Uniform(0.07))
    # canon_dtype("f32") -> None == unset, so the policy's dtype wins
    mod.init_optimizer(optimizer=sgd)
    assert sgd.state_dtype == "bfloat16"
