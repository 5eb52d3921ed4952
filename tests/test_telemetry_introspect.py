"""mxnet_tpu.telemetry.introspect + flight — program introspection,
crash black box, and dist-labeled exports.

Pins the observability contracts ISSUE 7 lands:

* ``analyze_compiled`` is THE one cost/memory extraction rule (nonzero
  flops/bytes + memory audit on a real compiled program);
* every fused-module program registers with the ProgramInventory and
  analyzes lazily — with ZERO post-warmup retraces and BITWISE
  identical params while the whole introspection path is live;
* the program publishes times, bytes and counts and never a share of
  a peak: a step record holds exactly its host clocks, no
  ``mfu``-family gauge exists in fit or in serving, and nothing under
  ``mxnet_tpu/`` reads a peak from the environment (the one peak table
  is ``benchmark/peaks.py``);
* the FlightRecorder commits postmortems atomically: a crash mid-dump
  leaves only ``.tmp-*``, never a torn committed file;
* Prometheus/JSONL exports carry ``rank``/``process_count`` labels
  exactly when a multi-process dist runtime is installed —
  single-process output is byte-identical to the unlabeled form;
* the virtual-host feed folds per-host clocks into
  ``dist.straggler_ratio``.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu import telemetry as tel
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.telemetry.introspect import (ProgramInventory,
                                            analyze_compiled)


@pytest.fixture(autouse=True)
def _clean():
    tel.disable()
    tel.timeline().clear()
    tel.clear_trace()
    tel.flight_recorder().disarm()
    tel.flight_recorder().pop_last_dump()
    yield
    tel.disable()
    tel.timeline().clear()
    tel.clear_trace()
    tel.flight_recorder().disarm()
    tel.flight_recorder().pop_last_dump()
    tel.flight_recorder().uninstall()


def _mlp():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _data(n=64, seed=1):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, 6).astype(np.float32),
            rng.randint(0, 10, n).astype(np.float32))


def _fit(seed=11, epochs=2, **kw):
    X, y = _data()
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0)])
    it = NDArrayIter(X, y, batch_size=16, shuffle=False)
    mod.fit(it, num_epoch=epochs,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Uniform(0.07), **kw)
    return mod


def _params_bytes(mod):
    arg, aux = mod.get_params()
    return [np.ascontiguousarray(arg[k].asnumpy()).tobytes()
            for k in sorted(arg)] + \
           [np.ascontiguousarray(aux[k].asnumpy()).tobytes()
            for k in sorted(aux or {})]


# ----------------------------------------------------------------------
# analyze_compiled, and the yardstick the package does not hold
# ----------------------------------------------------------------------
def test_analyze_compiled_fields():
    import jax
    import jax.numpy as jnp

    comp = jax.jit(lambda a, b: jnp.dot(a, b) * 2.0).lower(
        np.ones((16, 16), np.float32),
        np.ones((16, 16), np.float32)).compile()
    a = analyze_compiled(comp)
    assert a["flops"] > 0 and a["bytes_accessed"] > 0
    for k in ("temp_bytes", "argument_bytes", "output_bytes",
              "alias_bytes"):
        assert k in a and a[k] >= 0
    assert a["argument_bytes"] == 2 * 16 * 16 * 4


def test_package_holds_no_peak_table_or_peak_option():
    """Only ``benchmark/`` divides work by time: the package exports no
    roofline arithmetic and no peak table, and no file under
    ``mxnet_tpu/`` reads a peak from the environment (the names are
    spelled in pieces so this file is not a match itself)."""
    import mxnet_tpu.telemetry.introspect as introspect
    for name in ("roofline", "device" + "_peaks", "BOUND_BY_CODES"):
        assert not hasattr(tel, name), name
        assert not hasattr(introspect, name), name
    for name in ("ProgramInventory", "analyze_compiled", "aval_skeleton"):
        assert hasattr(tel, name), name
    options = ["MXNET_" + "PEAK_" + tail for tail in ("TFLOPS", "HBM_GBPS")]
    pkg = os.path.dirname(os.path.abspath(mx.__file__))
    readers = []
    for dirpath, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(dirpath, f)).read()
                readers += [os.path.join(dirpath, f) for o in options
                            if o in text]
    assert readers == []


# ----------------------------------------------------------------------
# ProgramInventory through a real fit
# ----------------------------------------------------------------------
def test_inventory_register_analyze_dump(tmp_path):
    tel.enable()
    mod = _fit()
    tel.disable()
    grp = mod._exec_group
    name = grp._program_names["train_step"]
    inv = tel.inventory()
    assert name in inv.names()
    a = inv.analyze(name)
    assert a["flops"] > 0 and a["bytes_accessed"] > 0
    assert a["kind"] == "train_step" and not a["analytic"]
    # argument/donation audit fields
    assert a["n_args"] > 0 and a["argument_bytes"] > 0
    assert "donated" in a
    # the fused step carries an analytic optimizer account: read w/g +
    # write w + read/write momentum = 5 * 4 bytes * n_params
    opt = inv.analyze(grp._program_names["optimizer_update"])
    n_par = sum(int(np.prod(b.shape))
                for b in grp._param_dict.values())
    assert opt["analytic"] and opt["flops"] == 4.0 * n_par
    assert opt["bytes_accessed"] == 5.0 * 4 * n_par
    # programs.* gauges published on analysis
    gauges = tel.registry().snapshot()["gauges"]
    assert gauges["programs.%s.flops" % name] == a["flops"]
    # JSON report commits and parses
    out = tmp_path / "programs.json"
    rep = tel.dump_programs(str(out))
    assert rep["format"] == "program-inventory-r1"
    disk = json.loads(out.read_text())
    assert disk["n_programs"] == rep["n_programs"] >= 2
    kinds = {p["kind"] for p in disk["programs"]}
    assert {"train_step", "optimizer_update"} <= kinds


def test_eval_program_registers_too():
    X, y = _data()
    tel.enable()
    mod = _fit(eval_data=NDArrayIter(X, y, batch_size=16))
    tel.disable()
    names = mod._exec_group._program_names
    assert "train_step" in names
    # the padded-eval / score program registered alongside
    assert any(k.startswith("fwd_eval") for k in names), names


def test_eval_fit_no_per_epoch_recompile():
    """Regression (found BY the introspection gate): fit passed its
    validation metric to score() as a string, so every epoch's eval
    created a fresh metric object — fresh device-tally token — and
    compiled a brand-new fwd_eval_stat program: one hidden XLA compile
    per epoch, post-warmup. Fixed by materializing validation_metric
    once per fit; a multi-epoch eval fit now retraces ZERO times after
    the warmup boundary."""
    X, y = _data()
    before = tel.registry().counter("compile.post_warmup_retraces").value
    total_before = tel.registry().counter("compile.retraces").value
    tel.enable()
    _fit(epochs=3, eval_data=NDArrayIter(X, y, batch_size=16))
    tel.disable()
    assert tel.registry().counter("compile.post_warmup_retraces").value \
        == before
    # one train-step trace + ONE eval-stat trace for the whole fit
    # (was one eval trace per epoch)
    assert tel.registry().counter("compile.retraces").value \
        - total_before == 2


_RECORD_KEYS = {"step", "epoch", "nbatch", "loop", "host_wait_ms",
                "dispatch_ms", "metric_cb_ms", "checkpoint_ms",
                "batch_group", "recompile", "total_ms", "ts"}
_UTILISATION = ("mfu", "achieved_hbm_gbps", "achieved_tflops", "hbm_util",
                "bound_by")


@pytest.mark.parametrize("batch_group", [1, 2])
def test_fit_step_records_are_host_clocks_only(batch_group):
    """A 3-epoch fit with telemetry on: every step record is exactly
    its host clocks, no utilisation gauge is published, the retrace
    counter does not move after the warm-up boundary, and the step
    program still analyses to flops and bytes for whoever holds a peak
    table and a device time (``benchmark/``)."""
    before = tel.registry().counter("compile.post_warmup_retraces").value
    tel.enable()
    mod = _fit(epochs=3, batch_group=batch_group)
    tel.disable()
    assert tel.registry().counter("compile.post_warmup_retraces").value \
        == before
    recs = tel.timeline().records()
    assert {r["epoch"] for r in recs} == {0, 1, 2}
    assert all(set(r) == _RECORD_KEYS for r in recs), \
        [sorted(set(r) ^ _RECORD_KEYS) for r in recs
         if set(r) != _RECORD_KEYS][:1]
    assert all(r["batch_group"] == batch_group for r in recs)
    gauges = tel.registry().snapshot()["gauges"]
    assert not [g for g in gauges
                if g.startswith("train.") and g.split(".")[-1]
                in _UTILISATION], sorted(gauges)
    names = mod._exec_group._program_names
    kind = "train_step_grouped" if batch_group > 1 else "train_step"
    a = tel.inventory().analyze(names[kind])
    assert a["flops"] > 0 and a["bytes_accessed"] > 0


def test_introspection_zero_perturbation_bitwise(tmp_path):
    plain = _params_bytes(_fit())
    tel.enable()
    mod = _fit()
    tel.dump_programs(str(tmp_path / "programs.json"))
    tel.disable()
    assert _params_bytes(mod) == plain


def test_inventory_analytic_entry_and_capacity():
    inv = ProgramInventory(registry=tel.registry(), capacity=3)
    for i in range(5):
        inv.register("p%d" % i, kind="k", flops=1.0, bytes_accessed=2.0)
    assert len(inv) == 3 and "p0" not in inv.names()
    a = inv.analyze("p4")
    assert a["analytic"] and a["flops"] == 1.0 and a["n_dev"] == 1
    assert inv.analyze("nope") is None


# ----------------------------------------------------------------------
# FlightRecorder
# ----------------------------------------------------------------------
def test_flight_recorder_dump_atomic(tmp_path):
    fr = tel.FlightRecorder(capacity=8)
    assert fr.dump("nothing armed") is None      # unarmed: no-op
    fr.arm(str(tmp_path / "bb"))
    fr.set_state(rank=0, dp_width=8)
    for i in range(12):
        fr.note("tick", i=i)
    path = fr.dump("unit test")
    assert path and os.path.exists(path)
    pm = json.loads(open(path).read())
    assert pm["format"] == "flight-recorder-r1"
    assert pm["reason"] == "unit test"
    assert pm["state"] == {"rank": 0, "dp_width": 8}
    assert len(pm["events"]) == 8               # bounded ring
    assert pm["events"][-1]["i"] == 11
    assert "dist" in pm["metrics"] and "compile" in pm["metrics"]
    # no staging residue after a clean commit
    assert not [f for f in os.listdir(str(tmp_path / "bb"))
                if ".tmp-" in f]
    assert fr.pop_last_dump() == path and fr.pop_last_dump() is None


def test_flight_recorder_crash_mid_dump_leaves_only_tmp(tmp_path,
                                                        monkeypatch):
    fr = tel.FlightRecorder().arm(str(tmp_path / "bb"))

    def boom(src, dst):
        raise OSError("simulated crash at commit")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        fr.dump("crash mid dump")
    monkeypatch.undo()
    files = os.listdir(str(tmp_path / "bb"))
    assert files and all(".tmp-" in f for f in files)
    # the staged tmp is complete valid JSON — only the COMMIT failed
    staged = json.loads(
        open(os.path.join(str(tmp_path / "bb"), files[0])).read())
    assert staged["reason"] == "crash mid dump"
    assert fr.last_dump_path is None            # never recorded as done


def test_fit_crash_dumps_postmortem(tmp_path):
    """An unhandled exception escaping fit commits a postmortem whose
    last step record is the step that was in flight (the record is
    written even though the callback raised)."""
    tel.enable()
    tel.flight_recorder().arm(str(tmp_path / "bb"))

    def bomb(param):
        if param.epoch == 1 and param.nbatch == 2:
            raise RuntimeError("injected crash")

    with pytest.raises(RuntimeError, match="injected crash"):
        _fit(epochs=3, batch_end_callback=bomb)
    tel.disable()
    path = tel.flight_recorder().pop_last_dump()
    assert path and os.path.exists(path)
    pm = json.loads(open(path).read())
    assert "RuntimeError" in pm["reason"]
    last = pm["steps"][-1]
    assert last["epoch"] == 1 and last["nbatch"] == 2


def test_fit_crash_unarmed_leaves_nothing(tmp_path):
    def bomb(param):
        raise RuntimeError("no recorder")

    with pytest.raises(RuntimeError):
        _fit(epochs=1, batch_end_callback=bomb)
    assert tel.flight_recorder().pop_last_dump() is None


def test_install_chains_excepthook_and_sigterm(tmp_path):
    import signal
    fr = tel.FlightRecorder().arm(str(tmp_path / "bb"))
    seen = []
    old_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.append(("hook", a[0].__name__))
    prev_sig = signal.signal(signal.SIGTERM,
                             lambda s, f: seen.append(("sig", s)))
    try:
        fr.install()
        assert sys.excepthook != seen  # replaced
        sys.excepthook(RuntimeError, RuntimeError("x"), None)
        fr._on_sigterm(signal.SIGTERM, None)
        fr.uninstall()
        # chained to the previous handlers, dumped twice
        assert ("hook", "RuntimeError") in seen
        assert ("sig", signal.SIGTERM) in seen
        dumps = os.listdir(str(tmp_path / "bb"))
        assert len(dumps) == 2
        reasons = sorted(json.loads(open(os.path.join(
            str(tmp_path / "bb"), f)).read())["reason"] for f in dumps)
        assert reasons[0] == "SIGTERM" and "unhandled" in reasons[1]
        # uninstall restored our stand-ins
        assert sys.excepthook.__name__ == "<lambda>"
    finally:
        sys.excepthook = old_hook
        signal.signal(signal.SIGTERM, prev_sig)


def test_sigterm_ignored_stays_ignored(tmp_path):
    """A process that deliberately SIG_IGNs SIGTERM keeps ignoring it
    through the recorder: dump, then DON'T re-deliver with SIG_DFL."""
    import signal
    fr = tel.FlightRecorder().arm(str(tmp_path / "bb"))
    prev = signal.signal(signal.SIGTERM, signal.SIG_IGN)
    try:
        fr.install(excepthook=False)
        fr._on_sigterm(signal.SIGTERM, None)   # must not kill us
        assert os.listdir(str(tmp_path / "bb"))   # dumped
        fr.uninstall()
        assert signal.getsignal(signal.SIGTERM) is signal.SIG_IGN
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_install_not_torn_down_by_second_owner(tmp_path):
    """ElasticTrainer brackets fit with install/uninstall, but it must
    not uninstall hooks someone else (the MXNET_TELEMETRY_BLACKBOX
    autostart) installed first — `installed` is the guard."""
    fr = tel.FlightRecorder().arm(str(tmp_path / "bb"))
    old_hook = sys.excepthook
    try:
        fr.install(sigterm=False)
        assert fr.installed
        # second owner's bracket: sees installed, skips both calls
        installed_here = not fr.installed
        assert not installed_here
        if installed_here:
            fr.uninstall()
        assert fr.installed and sys.excepthook == fr._on_excepthook
        fr.uninstall()
        assert sys.excepthook is old_hook
    finally:
        sys.excepthook = old_hook


# ----------------------------------------------------------------------
# rank/process_count export labels
# ----------------------------------------------------------------------
class _FakeRuntime:
    rank = 1
    size = 4


def test_prometheus_and_jsonl_rank_labels(tmp_path):
    from mxnet_tpu.dist import runtime as rt
    reg = tel.MetricsRegistry()
    reg.counter("a.b").add(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)

    # single-process: byte-identical to the unlabeled format (pinned)
    plain = tel.render_prometheus(reg)
    assert "rank=" not in plain and "process_count=" not in plain
    assert "mxtpu_a_b 2.0" in plain

    prev = rt.active_runtime()
    rt._install_runtime(_FakeRuntime())
    try:
        labeled = tel.render_prometheus(reg)
        assert 'mxtpu_a_b{rank="1",process_count="4"} 2.0' in labeled
        assert 'mxtpu_g{rank="1",process_count="4"} 1.5' in labeled
        assert 'mxtpu_h_bucket{le="1.0",rank="1",process_count="4"} 1' \
            in labeled
        assert 'mxtpu_h_count{rank="1",process_count="4"} 1' in labeled
        sink = tel.JsonlSink(str(tmp_path / "out.jsonl"))
        sink.write("step", {"step": 0})
        sink.close()
        line = json.loads(open(str(tmp_path / "out.jsonl")).read())
        assert line["rank"] == 1 and line["process_count"] == 4
    finally:
        rt._install_runtime(prev)
    sink = tel.JsonlSink(str(tmp_path / "out2.jsonl"))
    sink.write("step", {"step": 0})
    sink.close()
    line = json.loads(open(str(tmp_path / "out2.jsonl")).read())
    assert "rank" not in line and "process_count" not in line


# ----------------------------------------------------------------------
# straggler gauge (virtual-host harness)
# ----------------------------------------------------------------------
def test_virtual_feed_straggler_gauge():
    from mxnet_tpu import dist
    cluster = dist.VirtualCluster(4)
    X, y = _data(n=64)
    X8 = np.repeat(X, 2, axis=0)[:64]
    it = NDArrayIter(X8[:, :6], y, batch_size=32,
                     label_name="softmax_label")
    feed = cluster.feed(it)
    feed.next()
    clocks = feed.host_clocks_ms()
    assert len(clocks) == 4 and all(c >= 0 for c in clocks)
    ratio = tel.registry().snapshot()["gauges"]["dist.straggler_ratio"]
    assert ratio >= 1.0
    assert feed.straggler_ratio() >= 1.0


# ----------------------------------------------------------------------
# serving: bucket programs in the inventory, no utilisation gauge
# ----------------------------------------------------------------------
def test_serving_bucket_programs_in_inventory_and_rows_bitwise():
    from mxnet_tpu.serving import Predictor
    X, y = _data()
    mod = _fit(epochs=1)
    tel.enable()
    pred = Predictor(mod, max_batch_size=8)
    pred.warmup()
    served = pred.predict(X[:3, :6])
    tel.disable()
    # a 3-row request runs bucket 4: its program is registered and
    # analyses to non-zero flops and bytes
    grp = pred._modules[4]._exec_group
    a = tel.inventory().analyze(grp._program_names["fwd_eval"])
    assert a["flops"] > 0 and a["bytes_accessed"] > 0
    gauges = pred._stats.scope.snapshot()["gauges"]
    assert not [g for g in gauges if g.split(".")[-1] in _UTILISATION], \
        sorted(gauges)
    # served rows bitwise vs Module.predict with telemetry on
    np.testing.assert_array_equal(
        served,
        mod.predict(NDArrayIter(X[:4, :6], None, batch_size=4),
                    num_batch=1).asnumpy()[:3])
