"""The device's idle time by the phase of `fit` (`benchmark/idle_by_phase.py`,
`metrics/idle_*.py`), Python's collector (`metrics/py_gc_ms_per_step.py`)
and the decode stage's own wall (`metrics/input_decode_wall_ms_per_step.py`):

* the manifest's nine entries follow the parent's, in order, and nothing
  the manifest had is changed;
* the readers on a hand-made report and hand-made idle intervals: the
  innermost span wins, a span the table does not name takes its
  parent's phase, everything under `fit.epoch_end` is its own, the
  collector wins over all, the phases tile the idle time inside the
  root span; nothing where there is no trace, no intervals (the parent),
  dropped intervals, a busy window edge or a root span whose start lies
  more than 1 ms off the window's;
* a small `fit` under a profiler session on the CPU: with its whole
  root span taken as idle, the readers agree with the trace's own `mx.*`
  spans put through `trace_reduce.attribute_gaps`, and with the
  report's self times.
"""
import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, idle_by_phase, trace_reduce  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

PARENT = "2fdffada6e709b40de1ed9b8281f6b2f09a4e784"
FED = "resnet50-b256.fit-recordio"
IDLE = ("fit_next", "exec_stage", "exec_launch", "fit_update",
        "fit_epoch_end", "fit_other")
READERS = {"idle_%s_ms_per_step" % p: p for p in IDLE}
READERS["idle_py_gc_ms_per_step"] = "py.gc"
LAYER = {"fit_next": "input", "exec_stage": "executor group",
         "exec_launch": "executor group", "py.gc": "host interpreter"}
MS = 1_000_000                 # a millisecond in nanoseconds
OFF = 1_792_000_000_000_000_000    # the report's clock less the trace's
STEPS = 2


def _entry(name, source, layer, workloads=None):
    e = {"name": name, "unit": "ms/step", "better": "lower",
         "source": source, "layer": layer, "moves": "img_per_s"}
    if workloads:
        e["workloads"] = workloads
    return e


NINE = [_entry(n, "device_trace", LAYER.get(p, "training loop"))
        for n, p in READERS.items()] + [
    _entry("py_gc_ms_per_step", "program_counter", "host interpreter"),
    _entry("input_decode_wall_ms_per_step", "program_counter", "input",
           [FED])]


def test_the_manifests_nine_entries_follow_the_parents():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    names = [m["name"] for m in now["per_layer"]]
    at = names.index(NINE[0]["name"])
    assert now["per_layer"][at:at + len(NINE)] == NINE
    for m in NINE:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))

    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args,
                              capture_output=True, text=True)
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    was = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    assert at == len(was["per_layer"])
    assert now["per_layer"][:at] == was["per_layer"]
    for key in was:
        if key != "per_layer":
            assert now[key] == was[key], key


# ------------------------------------------------------------ hand-made
# trace clock, ns: the window is [1 ms, 101 ms]; the root span opens
# 20 ns after it and closes at its end (the anchor maps it there)
LO, HI = 1 * MS, 101 * MS
SPANS = [
    ("fit", LO + 20, HI),
    ("fit.epoch", 2 * MS, 90 * MS),
    ("fit.next", 2 * MS, 20 * MS),
    ("data.stage", 2.5 * MS, 2.8 * MS),     # unnamed: fit.next's
    ("fit.forward_backward", 20 * MS, 30 * MS),
    ("exec.stage", 21 * MS, 29 * MS),
    ("fit.update", 30 * MS, 50 * MS),
    ("exec.launch", 40 * MS, 50 * MS),
    ("fit.metric", 50 * MS, 52 * MS),
    ("fit.next", 52 * MS, 60 * MS),
    ("fit.forward_backward", 60 * MS, 62 * MS),
    ("exec.stage", 60 * MS, 61 * MS),
    ("fit.update", 62 * MS, 70 * MS),
    ("exec.launch", 63 * MS, 69 * MS),
    ("fit.metric", 70 * MS, 72 * MS),
    ("fit.next", 72 * MS, 90 * MS),
    ("fit.epoch_end", 90 * MS, 100 * MS),
    ("fit.epoch_end_callback", 92 * MS, 95 * MS),
    ("exec.launch", 93 * MS, 94 * MS),       # the epoch end's
    ("py.gc", 45 * MS, 47 * MS),             # inside exec.launch
]
IDLE_NS = [(LO, 3 * MS), (25 * MS, 35 * MS), (44 * MS, 48 * MS),
           (51 * MS, 53 * MS), (89 * MS, HI)]
WANT_MS = {          # idle milliseconds of the window, by phase
    "fit_next": 3.0,                  # 2-3, 52-53, 89-90
    "exec_stage": 4.0,                # 25-29
    "fit_update": 5.0,                # 30-35
    "exec_launch": 2.0,               # 44-45, 47-48
    "py.gc": 2.0,                     # 45-47
    "fit_epoch_end": 10.0,            # 90-100, its launch included
    # 1 ms less 20 ns before the epoch, the forward-backward's self
    # time 29-30, the metric 51-52, the root's own 100-101
    "fit_other": (MS - 20 + MS + MS + MS) / MS,
}


def _report(spans=SPANS, **kw):
    rep = {"steps": STEPS, "epochs": 1, "wall_ns": HI - LO - 20,
           "start_ns": OFF + LO + 20, "end_ns": OFF + HI,
           "counters": {}, "spans": {},
           "intervals": [(n, OFF + int(s), OFF + int(e))
                         for n, s, e in spans],
           "intervals_dropped": 0}
    rep.update(kw)
    return rep


def _run(idle=IDLE_NS, window_s=(HI - LO) * 1e-9):
    return {"steps": STEPS,
            "trace": {"window_s": window_s, "device": {"idle": idle}}}


@pytest.fixture
def report(monkeypatch):
    rep = _report()
    monkeypatch.setattr(telemetry, "last_fit", lambda: rep)
    return rep


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_window(report, name):
    got = harness.load_reader(ROOT, name)(_run())
    assert got == pytest.approx(WANT_MS[READERS[name]] / STEPS, rel=1e-12)


def test_the_phases_tile_the_idle_time_inside_the_root(report):
    got = idle_by_phase.split(_run())
    assert set(got) == set(WANT_MS)
    inside = trace_reduce.total(trace_reduce.clip(IDLE_NS, LO + 20, HI))
    assert sum(got.values()) == pytest.approx(inside * 1e-9, rel=1e-12)
    assert sum(WANT_MS.values()) * MS == pytest.approx(inside, rel=1e-12)


def test_the_innermost_span_wins_whatever_the_order(monkeypatch):
    """The report lists intervals as they closed, children first."""
    rep = _report(spans=sorted(SPANS, key=lambda s: s[2]))
    monkeypatch.setattr(telemetry, "last_fit", lambda: rep)
    got = idle_by_phase.split(_run())
    assert got == pytest.approx({k: v / 1e3 for k, v in WANT_MS.items()})


def test_a_root_half_a_millisecond_off_still_reads(monkeypatch):
    rep = _report(start_ns=OFF + LO + MS // 2)
    monkeypatch.setattr(telemetry, "last_fit", lambda: rep)
    assert idle_by_phase.split(_run()) is not None


@pytest.mark.parametrize("why,run,report", [
    ("no trace: a CPU rehearsal", {"steps": STEPS, "trace": None}, {}),
    ("the root's start 1.5 ms off the window's", _run(),
     {"start_ns": OFF + LO - 3 * MS // 2}),
    ("the window's start busy", _run(idle=IDLE_NS[1:]), {}),
    ("the window's end busy", _run(idle=IDLE_NS[:-1]), {}),
    ("the idle extent 2 us short of the window", _run(
        window_s=(HI - LO + 2000) * 1e-9), {}),
    ("intervals dropped", _run(), {"intervals_dropped": 1}),
    ("no idle interval at all", _run(idle=[]), {}),
])
def test_readers_give_nothing_where_the_anchor_fails(monkeypatch, why, run,
                                                     report):
    rep = _report(**report)
    monkeypatch.setattr(telemetry, "last_fit", lambda: rep)
    for name in READERS:
        assert harness.load_reader(ROOT, name)(run) is None, why


def test_readers_give_nothing_on_the_parent(monkeypatch):
    """A report without intervals (the parent's), or no report at all:
    the line leaves the metrics out and nothing raises."""
    rep = _report()
    for key in ("intervals", "intervals_dropped", "start_ns", "end_ns"):
        del rep[key]
    monkeypatch.setattr(telemetry, "last_fit", lambda: rep)
    for name in READERS:
        assert harness.load_reader(ROOT, name)(_run()) is None
    monkeypatch.delattr(telemetry, "last_fit")
    for name in list(READERS) + ["py_gc_ms_per_step",
                                 "input_decode_wall_ms_per_step"]:
        assert harness.load_reader(ROOT, name)(_run()) is None


def test_a_report_not_the_windows_is_an_error(report):
    with pytest.raises(RuntimeError, match="not the window's"):
        idle_by_phase.split(dict(_run(), steps=STEPS + 1))


@pytest.mark.parametrize("name,counter,value,want", [
    ("py_gc_ms_per_step", "py.gc_ns", STEPS * 3_500_000, 3.5),
    ("py_gc_ms_per_step", None, None, 0.0),      # no pass in the window
    ("input_decode_wall_ms_per_step", "input.decode_wall_ns",
     STEPS * 120_000_000, 120.0),
    ("input_decode_wall_ms_per_step", None, None, None),   # resident
])
def test_counter_readers(monkeypatch, name, counter, value, want):
    rep = _report(counters={} if counter is None else {counter: value})
    monkeypatch.setattr(telemetry, "last_fit", lambda: rep)
    got = harness.load_reader(ROOT, name)({"steps": STEPS, "trace": None})
    assert got == (None if want is None else pytest.approx(want))


# ------------------------------------------------------ a traced fit
def test_a_traced_fit_agrees_with_its_trace_and_its_report(tmp_path):
    """A small `fit` under a profiler session on the CPU, whose whole
    root span (and 5 us on each side) is taken as the device's idle
    time: each phase matches the direct reduction of the trace's `mx.*`
    spans through `attribute_gaps`, outermost first, to 0.1 ms a step,
    and the report's own self times to 1 us a step."""
    import jax
    import mxnet_tpu as mx
    import mxnet_tpu.symbol as sym
    from mxnet_tpu.io import NDArrayIter
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=10, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(3)
    X = rng.rand(64, 6).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.float32)
    mod = mx.mod.Module(net, context=[mx.cpu(0)])
    gc.disable()          # no collector's pass: the trace has no such span
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        mod.fit(NDArrayIter(X, y, batch_size=16), num_epoch=2,
                optimizer_params={"learning_rate": 0.1})
    finally:
        jax.profiler.stop_trace()
        gc.enable()
    rep = telemetry.last_fit()
    steps = rep["steps"]
    trace = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)),
                              span_prefix="mx.")
    (root,) = [(s, e) for n, s, e in trace["spans"] if n == "mx.fit"]
    idle = [(root[0] - 5000, root[1] + 5000)]
    got = {p: harness.load_reader(ROOT, n)(
        {"steps": steps, "trace": {"window_s": (idle[0][1] - idle[0][0])
                                   * 1e-9, "device": {"idle": idle}}})
        for n, p in READERS.items()}
    order = ["mx.fit", "mx.fit.epoch", "mx.fit.epoch_end", "mx.fit.next",
             "mx.fit.forward_backward", "mx.fit.update", "mx.fit.metric",
             "mx.exec.stage", "mx.exec.launch"]
    assert {n for n, _s, _e in trace["spans"]} == set(order)
    spans = sorted(trace["spans"], key=lambda s: order.index(s[0]))
    direct = trace_reduce.attribute_gaps(idle, spans, "outside")
    want = {"fit_next": direct["mx.fit.next"],
            "exec_stage": direct["mx.exec.stage"],
            "exec_launch": direct["mx.exec.launch"],
            "fit_update": direct["mx.fit.update"],
            "fit_epoch_end": direct["mx.fit.epoch_end"],
            "fit_other": sum(direct[n] for n in (
                "mx.fit", "mx.fit.epoch", "mx.fit.forward_backward",
                "mx.fit.metric")),
            "py.gc": 0.0}
    for phase, seconds in want.items():
        assert got[phase] == pytest.approx(1e3 * seconds / steps, abs=0.1), \
            phase
    own = {"fit_next": "fit.next", "exec_stage": "exec.stage",
           "exec_launch": "exec.launch", "fit_update": "fit.update",
           "fit_epoch_end": "fit.epoch_end"}
    for phase, span in own.items():
        assert got[phase] == pytest.approx(
            1e-6 * rep["spans"][span]["self_ns"] / steps, abs=1e-3), phase
    assert sum(got.values()) == pytest.approx(
        1e-6 * rep["wall_ns"] / steps, rel=1e-9)
