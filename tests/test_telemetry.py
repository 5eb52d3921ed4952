"""mxnet_tpu.telemetry — unified metrics, tracing, and step-timeline
observability.

Pins the subsystem's hard contracts: the registry is exact under
concurrent writers, histograms bucket like Prometheus, the JSONL and
Prometheus exporters round-trip the registry, spans merge into the
profiler's Chrome trace as complete (``"ph": "X"``) events with real
thread ids, ``fit`` writes one StepTimeline record per step (per group
with ``batch_group=K``) with ZERO numeric perturbation (bitwise-equal
params, ci.sh-gated too), the CompileWatch attributes every XLA
retrace and stays at 0 post-warmup for a steady loop, disabled mode is
a no-op, and the retrofitted ServingStats/PipelineStats keep their
exact snapshot surface while living in the shared registry.
"""
import collections
import json
import logging
import os
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu import telemetry as tel
from mxnet_tpu.io import NDArrayIter


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Every test starts disabled with a fresh timeline/trace ring and
    leaves no sink/server/active-pipeline behind."""
    tel.disable()
    tel.timeline().clear()
    tel.clear_trace()
    yield
    tel.disable()
    tel.timeline().clear()
    tel.clear_trace()
    tel.set_active_pipeline(None)


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


def _fit(mod_net, X, y, seed=11, **kw):
    mx.random.seed(seed)
    mod = mx.mod.Module(mod_net, context=[mx.cpu(0)])
    it = NDArrayIter(X, y, batch_size=16, shuffle=False)
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Uniform(0.07), **kw)
    return mod


def _own(counters):
    """A report's counters but the collector's (`py.gc_*`), which a pass
    of the oldest generation adds to whatever fit it falls in."""
    return {k: v for k, v in counters.items() if not k.startswith("py.gc_")}


def _params_bytes(mod):
    arg, aux = mod.get_params()
    return [np.ascontiguousarray(arg[k].asnumpy()).tobytes()
            for k in sorted(arg)] + \
           [np.ascontiguousarray(aux[k].asnumpy()).tobytes()
            for k in sorted(aux or {})]


# ----------------------------------------------------------------------
# MetricsRegistry
# ----------------------------------------------------------------------
def test_registry_concurrent_writers():
    """Counters and histograms stay exact under racing writer threads
    (each instrument carries its own lock)."""
    reg = tel.MetricsRegistry()
    shared = reg.counter("t.shared")
    hist = reg.histogram("t.lat_ms", buckets=(1.0, 10.0))
    n_threads, n_iter = 8, 400

    def work(i):
        mine = reg.counter("t.worker.%d" % i)
        for k in range(n_iter):
            shared.add()
            mine.add(2)
            hist.observe(float(k % 20))
            reg.gauge("t.g").set(i)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["t.shared"] == n_threads * n_iter
    for i in range(n_threads):
        assert snap["counters"]["t.worker.%d" % i] == 2 * n_iter
    h = snap["histograms"]["t.lat_ms"]
    assert h["count"] == n_threads * n_iter
    assert sum(h["counts"]) == h["count"]


def test_histogram_bucketing():
    """Values land in the first bucket with upper bound >= v; one
    implicit +Inf bucket catches the overflow; sum/count track."""
    reg = tel.MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 1.0, 2.0, 5.0, 7.5, 100.0, 1e6):
        h.observe(v)
    v = h.value
    assert v["buckets"] == [1.0, 5.0, 10.0]
    # <=1: {0.5, 1.0}; (1,5]: {2.0, 5.0}; (5,10]: {7.5}; +Inf: 2
    assert v["counts"] == [2, 2, 1, 2]
    assert v["count"] == 7 and v["sum"] == pytest.approx(1000116.0)


def test_registry_types_and_tree():
    reg = tel.MetricsRegistry()
    reg.counter("a.b.c").add(3)
    reg.gauge("a.g").set_fn(lambda: 42)
    assert reg.tree()["a"]["b"]["c"] == 3
    assert reg.tree()["a"]["g"] == 42
    with pytest.raises(TypeError):
        reg.gauge("a.b.c")  # registered as a counter
    s0, s1 = reg.unique_scope("fam"), reg.unique_scope("fam")
    assert s0.prefix != s1.prefix  # per-instance namespaces never clash
    s0.counter("x").add()
    assert s0.snapshot()["counters"]["x"] == 1


def test_jsonl_export_roundtrip(tmp_path):
    """flush_metrics appends ONE wall-clock-stamped line whose payload
    round-trips the registry snapshot."""
    path = str(tmp_path / "events.jsonl")
    tel.enable(jsonl=path)
    tel.registry().counter("t.jsonl_probe").add(7)
    tel.flush_metrics("unit test")
    tel.log_event("custom", {"k": 1})
    tel.disable()
    lines = [json.loads(line) for line in open(path)]
    assert [ln["kind"] for ln in lines] == ["metrics", "custom"]
    assert all("ts" in ln for ln in lines)
    assert lines[0]["metrics"]["counters"]["t.jsonl_probe"] == 7
    assert lines[0]["reason"] == "unit test"
    assert lines[1]["k"] == 1


def test_prometheus_render_and_endpoint():
    """The renderer speaks Prometheus text (typed, sanitized names,
    cumulative histogram buckets) and the stdlib endpoint serves it."""
    import urllib.request
    reg = tel.MetricsRegistry()
    reg.counter("serving.0.requests").add(5)
    reg.gauge("q.depth").set(3)
    h = reg.histogram("lat.ms", buckets=(1.0, 10.0))
    for v in (0.5, 2.0, 99.0):
        h.observe(v)
    text = tel.render_prometheus(reg)
    assert "# TYPE mxtpu_serving_0_requests counter" in text
    assert "mxtpu_serving_0_requests 5.0" in text
    assert "mxtpu_q_depth 3.0" in text
    # cumulative: le=1 -> 1, le=10 -> 2, +Inf -> 3
    assert 'mxtpu_lat_ms_bucket{le="1.0"} 1' in text
    assert 'mxtpu_lat_ms_bucket{le="10.0"} 2' in text
    assert 'mxtpu_lat_ms_bucket{le="+Inf"} 3' in text
    assert "mxtpu_lat_ms_count 3" in text
    with tel.MetricsServer(reg, port=0) as srv:
        with urllib.request.urlopen(srv.url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.read().decode() == tel.render_prometheus(reg)
        health = srv.url.replace("/metrics", "/healthz")
        with urllib.request.urlopen(health, timeout=10) as resp:
            assert resp.read() == b"ok\n"


# ----------------------------------------------------------------------
# Span tracing + profiler merge
# ----------------------------------------------------------------------
def test_span_nesting_merges_into_chrome_trace(tmp_path):
    """Nested spans from two threads land in dump_profile's Chrome
    trace as complete events with REAL thread ids, child intervals
    contained in their parents (the old unpaired B/E-with-tid=pid
    events are gone)."""
    from mxnet_tpu import profiler as prof
    tel.enable()

    def nest(tag):
        with tel.span("outer_%s" % tag):
            with tel.span("inner_%s" % tag, depth=1):
                x = sum(range(2000))
        return x

    t = threading.Thread(target=nest, args=("bg",))
    t.start()
    nest("fg")
    t.join()

    out = tmp_path / "trace.json"
    prof.profiler_set_config(mode="symbolic", filename=str(out))
    prof.profiler_set_state("run")
    prof.profiler_set_state("stop")
    prof.dump_profile()
    trace = json.load(open(out))
    events = {e["name"]: e for e in trace["traceEvents"]}
    for name in ("outer_fg", "inner_fg", "outer_bg", "inner_bg"):
        assert events[name]["ph"] == "X" and "dur" in events[name], \
            events.get(name)
    assert not any(e.get("ph") in ("B", "E")
                   for e in trace["traceEvents"])
    # real thread ids: the two outer spans ran on different threads
    assert events["outer_fg"]["tid"] != events["outer_bg"]["tid"]
    for tag in ("fg", "bg"):
        o, i = events["outer_" + tag], events["inner_" + tag]
        assert i["tid"] == o["tid"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert events["inner_fg"]["args"] == {"depth": 1}


# ----------------------------------------------------------------------
# StepTimeline through fit
# ----------------------------------------------------------------------
def test_step_timeline_short_fit():
    """One record per train step with the documented fields; the first
    step (the train-program compile) carries recompile=True, steady
    steps False; slowest() ranks by total_ms; to_jsonl round-trips."""
    X, y = _data()
    tel.enable()
    _fit(_mlp(), X, y)
    recs = tel.timeline().records()
    assert len(recs) == 2 * (len(X) // 16)   # 2 epochs x 4 steps
    for r in recs:
        for f in ("step", "epoch", "nbatch", "host_wait_ms", "dispatch_ms",
                  "metric_cb_ms", "checkpoint_ms", "batch_group",
                  "recompile", "total_ms", "ts"):
            assert f in r, (f, r)
        assert r["batch_group"] == 1
        assert r["total_ms"] >= r["dispatch_ms"]
    assert [r["step"] for r in recs] == \
        [recs[0]["step"] + i for i in range(len(recs))]
    assert recs[0]["recompile"] is True
    assert not any(r["recompile"] for r in recs[1:])
    slowest = tel.timeline().slowest(3)
    assert slowest[0]["total_ms"] == max(r["total_ms"] for r in recs)
    # steady-state contract: warmup boundary after epoch 0, then silence
    assert tel.compile_watch().post_warmup_count == 0


def test_step_timeline_to_jsonl(tmp_path):
    X, y = _data()
    tel.enable()
    _fit(_mlp(), X, y)
    path = str(tmp_path / "steps.jsonl")
    n = tel.timeline().to_jsonl(path)
    lines = [json.loads(line) for line in open(path)]
    assert n == len(lines) == len(tel.timeline())
    assert all(ln["kind"] == "step" for ln in lines)


def test_step_timeline_grouped_and_prefetch():
    """batch_group=K: one record per GROUP with the true group size;
    prefetch_to_device: host-wait comes from the loader's ring and the
    active-pipeline registration clears when fit returns."""
    X, y = _data()
    tel.enable()
    _fit(_mlp(), X, y, batch_group=2)
    recs = tel.timeline().records()
    assert len(recs) == 2 * 2          # 4 steps/epoch in groups of 2
    assert all(r["batch_group"] == 2 for r in recs)
    assert tel.compile_watch().post_warmup_count == 0

    tel.timeline().clear()
    _fit(_mlp(), X, y, prefetch_to_device=2)
    recs = tel.timeline().records()
    assert len(recs) == 2 * 4
    assert all(r["host_wait_ms"] >= 0.0 for r in recs)
    assert tel.active_pipeline() is None   # cleared on fit exit


def test_fit_streams_step_jsonl(tmp_path):
    """With a sink configured, fit writes one "step" line per step as
    it happens (the ci.sh telemetry gate's contract) plus per-epoch
    metrics flushes; the epoch-end callback cost lands as its own
    "checkpoint" event (the step lines streamed before the fold) AND
    folds into the epoch's last timeline record."""
    X, y = _data()
    tel.enable(jsonl=str(tmp_path / "run.jsonl"))
    _fit(_mlp(), X, y, epoch_end_callback=lambda *a: None)
    tel.disable()
    lines = [json.loads(line) for line in open(tmp_path / "run.jsonl")]
    steps = [ln for ln in lines if ln["kind"] == "step"]
    assert len(steps) == 2 * 4
    assert {ln["epoch"] for ln in steps} == {0, 1}
    assert sum(1 for ln in lines if ln["kind"] == "metrics") == 2
    ck = [ln for ln in lines if ln["kind"] == "checkpoint"]
    assert [c["epoch"] for c in ck] == [0, 1]
    assert all(c["checkpoint_ms"] >= 0 for c in ck)
    last_of_epoch0 = [r for r in tel.timeline().records()
                      if r["epoch"] == 0][-1]
    assert last_of_epoch0["checkpoint_ms"] >= 0


# ----------------------------------------------------------------------
# CompileWatch
# ----------------------------------------------------------------------
def test_compile_watch_catches_shape_unstable_eval(caplog):
    """A deliberately shape-unstable eval retraces; the watch counts
    it, attributes call site + input shapes, and warns once past the
    warmup boundary."""
    X, y = _data()
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0)])
    mod.bind(data_shapes=[("data", (16, 6))], for_training=False)
    mod.init_params(initializer=mx.init.Uniform(0.07))
    watch = tel.CompileWatch(scope=tel.MetricsRegistry().scope("compile"))
    assert watch.attach(mod)
    assert watch.attach(mod)   # idempotent re-attach

    from mxnet_tpu.io import DataBatch

    def run(rows):
        # forward is lazy on the fused path: reading the outputs is
        # what traces+launches the program
        mod.forward(DataBatch([mx.nd.array(X[:rows])], None),
                    is_train=False)
        return mod.get_outputs()[0].asnumpy()

    run(16)
    warm = watch.count
    assert warm >= 1
    run(16)
    assert watch.count == warm      # cached program: no retrace
    watch.mark_warmup_done()
    with caplog.at_level(logging.WARNING, "mxnet_tpu.telemetry"):
        mod.reshape(data_shapes=[("data", (32, 6))])   # shape drift
        run(32)
    assert watch.count > warm
    assert watch.post_warmup_count >= 1
    ev = [e for e in watch.events() if e["post_warmup"]][-1]
    assert ev["shapes"].get("data") == (32, 6)
    assert "test_telemetry.py" in ev["site"]
    assert any("retrace AFTER the warmup boundary" in r.getMessage()
               for r in caplog.records)
    # abstract shape inference (jax.eval_shape over the wrapped body)
    # is NOT a compile: output_shapes queries must not count/warn
    n = watch.count
    mod._exec_group._out_structs()
    assert watch.count == n


# ----------------------------------------------------------------------
# Disabled mode + zero perturbation
# ----------------------------------------------------------------------
def test_disabled_mode_is_noop():
    """Disabled means: no ring events, no timeline records, no sink
    writes.  The spans still run (they reach the profiler and the fit
    report), so `last_fit()` is filled all the same."""
    assert not tel.enabled()
    with tel.span("x") as sp:
        pass
    assert sp.ns > 0
    assert tel.trace_events() == []
    tel.log_event("step", {"a": 1})        # no sink: swallowed
    tel.flush_metrics()
    assert tel.jsonl_sink() is None
    X, y = _data()
    _fit(_mlp(), X, y)
    assert len(tel.timeline()) == 0        # fit recorded nothing
    assert tel.trace_events() == []
    rep = tel.last_fit()
    assert rep["steps"] == 2 * (len(X) // 16) and rep["epochs"] == 2
    assert rep["spans"]["fit.update"]["count"] == rep["steps"]


def test_zero_perturbation_bitwise_params():
    """Telemetry-on training is bitwise identical to telemetry-off
    (host clocks only — no readback, no RNG touch)."""
    X, y = _data()
    ref = _params_bytes(_fit(_mlp(), X, y, seed=23))
    tel.enable()
    on = _params_bytes(_fit(_mlp(), X, y, seed=23))
    tel.disable()
    assert ref == on


# ----------------------------------------------------------------------
# Stats views over the shared registry (snapshot-API compatibility)
# ----------------------------------------------------------------------
def test_serving_stats_snapshot_compat():
    s = mx.serving.ServingStats(latency_window=8)
    s.note_request(3)
    s.note_compile()
    s.note_batch(4, 3)
    s.note_batch(8, 5, warmup=True)
    s.note_completed(2.0)
    s.note_completed(4.0)
    s.note_reject()
    s.note_timeout()
    s.note_error()
    s.set_queue_probe(lambda: 6)
    snap = s.snapshot()
    assert set(snap) == {
        "requests", "completed", "rejected", "timeouts", "errors",
        "batches", "warmup_batches", "batch_fill", "compiles",
        "compile_tracking", "bucket_hits", "latency_ms", "queue_depth",
        "cache_hits", "cache_misses", "sheds", "warmup_ms",
        "worker_restarts"}
    assert snap["cache_hits"] == 0 and snap["cache_misses"] == 0
    assert snap["sheds"] == 0 and snap["warmup_ms"] == {}
    assert snap["worker_restarts"] == 0
    assert snap["requests"] == 3 and snap["completed"] == 2
    assert snap["batches"] == 1 and snap["warmup_batches"] == 1
    assert snap["batch_fill"] == 0.75 and snap["bucket_hits"] == {4: 1}
    assert snap["compiles"] == 1 and snap["queue_depth"] == 6
    assert snap["latency_ms"]["p50"] in (2.0, 4.0)
    assert snap["latency_ms"]["count"] == 2
    # ... and the same numbers are visible through the SHARED registry
    reg_view = s.scope.snapshot()
    assert reg_view["counters"]["requests"] == 3
    assert reg_view["counters"]["bucket_hits.4"] == 1
    assert reg_view["gauges"]["queue_depth"] == 6
    assert reg_view["histograms"]["latency_ms"]["count"] == 2


def test_pipeline_stats_snapshot_compat():
    p = mx.data.PipelineStats(ring_depth=3)
    p.note_staged(16, 0.002)
    p.note_ring(2)
    p.note_ring_full()
    p.note_delivered(16, 0.001)
    snap = p.snapshot()
    assert set(snap) == {
        "batches_delivered", "images_delivered", "host_wait_ms",
        "host_wait_ms_per_step", "stage_ms", "stager_img_per_sec",
        "ring_depth", "ring_occupancy", "ring_high_water",
        "ring_full_waits",
        # staged-transport provenance (docs/api/data.md field table)
        "staged_bytes", "staged_bytes_per_batch", "staged_dtype",
        "augment_placement",
        # dataset-cache provenance (PR 15: the sharded-cache tier wire
        # bench and the watchdog both read)
        "cache_tier", "cache_shard_bytes", "cache_global_rows"}
    assert snap["batches_delivered"] == 1
    assert snap["images_delivered"] == 16
    assert snap["host_wait_ms"] == pytest.approx(1.0)
    assert snap["ring_depth"] == 3 and snap["ring_high_water"] == 2
    assert snap["ring_full_waits"] == 1
    reg_view = p.scope.snapshot()
    assert reg_view["counters"]["images_delivered"] == 16
    p.reset()
    assert p.snapshot()["batches_delivered"] == 0
    assert p.snapshot()["ring_depth"] == 3    # config survives reset


def test_loader_close_releases_registry_scope():
    """A DeviceLoader that created its own stats retires their
    registry scope on close (fit-per-call workloads must not grow the
    registry unboundedly); the stats OBJECT stays readable."""
    from mxnet_tpu.data import DeviceLoader
    X, y = _data()
    loader = DeviceLoader(NDArrayIter(X, y, batch_size=16), depth=2)
    prefix = loader.pipeline_stats.scope.prefix
    loader.next()
    assert tel.registry().snapshot(prefix=prefix)["counters"]
    loader.close()
    empty = tel.registry().snapshot(prefix=prefix)
    assert not empty["counters"] and not empty["gauges"]
    # the detached stats object keeps answering post-mortem queries
    assert loader.pipeline_stats.snapshot()["batches_delivered"] == 1


def test_checkpoint_records_duration_and_bytes(tmp_path):
    before = tel.registry().snapshot()["counters"]
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / "ckpt"))
    arrays = {"arg:w": np.arange(32, dtype=np.float32)}
    mgr.save(0, arrays, optimizer_state=b"\x01" * 10, async_save=False)
    ckpt = mgr.restore()
    after = tel.registry().snapshot()["counters"]

    def delta(name):
        return after.get("checkpoint.%s" % name, 0) - \
            before.get("checkpoint.%s" % name, 0)

    assert delta("saves") == 1 and delta("restores") == 1
    assert delta("bytes_written") == 32 * 4 + 10
    assert delta("bytes_read") == 32 * 4 + 10
    assert delta("save_ms") > 0 and delta("restore_ms") > 0
    assert np.array_equal(ckpt.params["arg:w"], arrays["arg:w"])


# ----------------------------------------------------------------------
# Spans on the profiler's clock, and the fit report
# ----------------------------------------------------------------------
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_SPANS = ("fit.forward_backward", "exec.stage", "fit.update",
              "exec.launch", "fit.metric")
PARENT = {"fit.epoch": "fit", "fit.epoch_end": "fit",
          "fit.next": "fit.epoch", "fit.forward_backward": "fit.epoch",
          "fit.update": "fit.epoch", "fit.metric": "fit.epoch",
          "exec.stage": "fit.forward_backward", "exec.launch": "fit.update"}


def _traced(tmp_path, work):
    """Run `work()` under a `jax.profiler` session; returns the host
    plane's `mx.*` events as (name, start_ns, end_ns), prefix cut."""
    import jax
    sys.path.insert(0, ROOT)
    from benchmark import trace_reduce
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)),
                              span_prefix="mx.")
    return [(n[3:], s, e) for n, s, e in trace["spans"]]


@pytest.mark.parametrize("batch_group", [None, 2])
def test_fit_spans_lie_in_the_profiler_trace(tmp_path, batch_group):
    """With telemetry disabled, a `fit` under anyone's profiler session
    leaves its phases on the host plane as `mx.*` events: each inside
    its parent's interval, one of each step-level span per launch, one
    `fit.next` per pull.  `fit(batch_group=2)` gives the same names."""
    X, y = _data()
    spans = _traced(tmp_path, lambda: _fit(_mlp(), X, y,
                                           batch_group=batch_group))
    assert not tel.enabled()
    by_name = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((s, e))
    steps, epochs = 2 * (len(X) // 16), 2
    launches = steps // (batch_group or 1)
    assert set(by_name) == set(PARENT) | {"fit"}, sorted(by_name)
    assert len(by_name["fit"]) == 1
    assert len(by_name["fit.epoch"]) == len(by_name["fit.epoch_end"]) \
        == epochs
    assert len(by_name["fit.next"]) == steps + epochs
    for name in STEP_SPANS:
        assert len(by_name[name]) == launches, name
    for name, parent in PARENT.items():
        for s, e in by_name[name]:
            assert any(ps <= s and e <= pe for ps, pe in by_name[parent]), \
                (name, parent)
    rep = tel.last_fit()
    assert rep["steps"] == steps and rep["epochs"] == epochs
    assert {n: r["parent"] for n, r in rep["spans"].items()
            if n != "fit"} == PARENT


def test_score_opens_exec_stage_without_a_fit(tmp_path):
    """The executor group's spans are opened whoever calls: `score`
    stages under `score.forward`, and no `fit` span is above it."""
    X, y = _data()
    mod = _fit(_mlp(), X, y)
    before = tel.last_fit()
    it = NDArrayIter(X, y, batch_size=16, shuffle=False)
    spans = _traced(tmp_path, lambda: mod.score(
        it, "acc", batch_end_callback=lambda param: None))
    names = [n for n, _s, _e in spans]
    assert names.count("exec.stage") == len(X) // 16
    assert "score" in names and not any(n.startswith("fit") for n in names)
    (score,) = [(s, e) for n, s, e in spans if n == "score"]
    for n, s, e in spans:
        assert score[0] <= s and e <= score[1], n
    assert tel.last_fit() == before        # `score` is no `fit`


def test_last_fit_report_adds_up():
    """Counts equal the steps; the self times of all spans add up to
    the root's total (so children + self = parent at every level); the
    root's total is the wall time of the call."""
    import time
    X, y = _data()
    mx.random.seed(5)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0)])
    it = NDArrayIter(X, y, batch_size=16, shuffle=False)
    t0 = time.perf_counter_ns()
    mod.fit(it, num_epoch=3, optimizer_params={"learning_rate": 0.1})
    wall = time.perf_counter_ns() - t0
    rep = tel.last_fit()
    spans = rep["spans"]
    steps = 3 * (len(X) // 16)
    assert rep["steps"] == steps and rep["epochs"] == 3
    for name in STEP_SPANS:
        assert spans[name]["count"] == steps, name
    assert spans["fit.next"]["count"] == steps + 3
    assert spans["fit"]["count"] == 1 and spans["fit"]["parent"] is None
    assert rep["wall_ns"] == spans["fit"]["total_ns"]
    assert abs(rep["wall_ns"] - wall) <= 0.01 * wall
    assert sum(r["self_ns"] for r in spans.values()) == rep["wall_ns"]
    for name, row in spans.items():
        kids = sum(r["total_ns"] for r in spans.values()
                   if r["parent"] == name)
        assert kids + row["self_ns"] == row["total_ns"], name
        assert 0 < row["max_ns"] <= row["total_ns"]
        assert 0 <= row["max_step"] <= steps
    # the first step compiled: it is the longest launch
    assert spans["exec.launch"]["max_step"] == 0


def test_last_fit_is_the_latest_and_this_threads():
    """The next `fit` replaces the report; a span opened on another
    thread while a `fit` runs reaches the ring, not the report."""
    X, y = _data()
    _fit(_mlp(), X, y)
    first = tel.last_fit()
    assert first["steps"] == 8
    tel.enable()
    done = threading.Event()

    def elsewhere(param):
        if param.nbatch == 0 and not done.is_set():
            def work():
                with tel.span("elsewhere"):
                    tel.count("input.h2d_bytes", 7)
            t = threading.Thread(target=work)
            t.start()
            t.join(10)
            done.set()

    mx.random.seed(11)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0)])
    mod.fit(NDArrayIter(X, y, batch_size=16, shuffle=False), num_epoch=1,
            batch_end_callback=elsewhere,
            optimizer_params={"learning_rate": 0.1})
    second = tel.last_fit()
    assert done.is_set()
    assert second["steps"] == 4 and second["epochs"] == 1
    assert "elsewhere" not in second["spans"] and not _own(second["counters"])
    assert "elsewhere" in {e["name"] for e in tel.trace_events()}
    assert tel.last_fit() == second and second is not tel.last_fit()


def _jpeg_rec(tmp_path, n=10, hw=(12, 12)):
    import io
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


@pytest.mark.parametrize("device_augment", [False, "defer"])
def test_image_record_iter_stages_under_fit_next(tmp_path, device_augment):
    """`ImageRecordIter` clocks decode, assemble and put on its
    producer's thread and hands each batch's durations over with it:
    `next()` credits them to the report of the `fit` that took the
    batch, once a batch taken, as rows that ran off the thread (no
    children of `fit.next`, whose time is now the wait, and no part of
    the self times that tile the call).  The report counts the host
    bytes handed to `jax.device_put`: the float32 batch and its labels
    from the iterator; with the u8 wire (`device_augment="defer"`) a
    quarter of the batch, handed over in the executor group's staging."""
    batch, shape = 5, (3, 12, 12)
    it = mx.io.ImageRecordIter(
        path_imgrec=_jpeg_rec(tmp_path), data_shape=shape,
        batch_size=batch, rand_mirror=True, preprocess_threads=2,
        device_augment=device_augment)
    net = sym.Flatten(sym.Variable("data"))
    net = sym.FullyConnected(net, num_hidden=3, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(0)])
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.01})
    rep = tel.last_fit()
    steps = 4
    assert rep["steps"] == steps
    for name in ("input.decode", "input.assemble", "input.put"):
        row = rep["spans"][name]
        assert row["count"] == steps, name
        assert row["parent"] == tel.OFF_THREAD
        assert 0 < row["max_ns"] <= row["total_ns"] == row["self_ns"]
    on_thread = [r for r in rep["spans"].values()
                 if r["parent"] != tel.OFF_THREAD]
    assert sum(r["self_ns"] for r in on_thread) == rep["wall_ns"]
    assert not any(r["parent"] == "fit.next" for r in rep["spans"].values())
    assert rep["spans"]["fit.next"]["self_ns"] \
        == rep["spans"]["fit.next"]["total_ns"]
    pixels, labels = batch * 3 * 12 * 12, batch * 4
    if device_augment == "defer":
        mirror_draws = batch              # one uint8 a row
        per_step = pixels + mirror_draws + labels
    else:
        per_step = 4 * pixels + labels
    counters = _own(rep["counters"])
    assert counters.pop("input.ready", 0) + counters.pop("input.waited", 0) \
        == steps
    # the decode stage's own wall, first task's start to last task's
    # end, a batch: at most the producer's whole time over the batches
    wall = counters.pop("input.decode_wall_ns")
    first, last = rep["start_ns"], rep["end_ns"]
    assert 0 < wall <= steps * (last - first)
    assert counters == {"input.h2d_bytes": per_step * steps}


def test_report_shape_is_the_same_enabled_and_disabled():
    """Enabling telemetry adds the ring, the timeline and the sinks;
    the report has the same spans with the same counts, and training is
    bitwise the same."""
    X, y = _data()
    off = _fit(_mlp(), X, y, seed=29)
    rep_off = tel.last_fit()
    tel.enable()
    on = _fit(_mlp(), X, y, seed=29)
    rep_on = tel.last_fit()
    tel.disable()
    assert _params_bytes(off) == _params_bytes(on)
    assert set(rep_on) == set(rep_off)
    assert {n: (r["count"], r["parent"]) for n, r in rep_on["spans"].items()} \
        == {n: (r["count"], r["parent"])
            for n, r in rep_off["spans"].items()}
    recs = tel.timeline().records()
    assert len(recs) == rep_on["steps"]
    # the timeline's clocks are the spans' own reads
    assert abs(sum(r["host_wait_ms"] for r in recs) * 1e6
               - rep_on["spans"]["fit.next"]["total_ns"]) \
        <= rep_on["spans"]["fit.next"]["max_ns"] + 1e3 * len(recs)


def test_symbol_node_names_reach_the_lowered_program():
    """`executor._run_op` evaluates every node under
    `jax.named_scope(node.name)`: the step program's HLO metadata names
    the symbol node an instruction came from."""
    X, y = _data(16)
    mod = _fit(_mlp(), X, y)
    fn, skeleton = mod._exec_group._last_step
    text = fn.lower(*skeleton).as_text(debug_info=True)
    for node in ("fc1", "fc2", "softmax"):
        # forward and backward of the node: `.../jvp(fc1)/dot_general`,
        # `.../transpose(jvp(fc1))/dot_general`
        assert "/jvp(%s)/" % node in text, node
    assert "/transpose(jvp(fc1))/" in text


# ----------------------------------------------------------------------
# The report's intervals and the collector's passes
# ----------------------------------------------------------------------
GC = "py.gc"


def _profile_start_ns(trace_dir):
    """The session's start on the host's real-time clock: the `Task
    Environment` plane's stat, from which every event counts."""
    sys.path.insert(0, ROOT)
    from benchmark import trace_reduce
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_reduce.find_xplane(str(trace_dir)))
    (start,) = [v for p in data.planes if p.name == "Task Environment"
                for k, v in p.stats if k == "profile_start_time"]
    return int(start)


def test_fit_intervals_lie_on_the_profilers_clock(tmp_path):
    """Each span of `fit`'s thread leaves `(name, start_ns, end_ns)` in
    the report on `time.time_ns()`'s clock, which the profiler's trace
    keeps shifted by the session's start: every `mx.fit.update` event,
    moved by that start, lies within 100 us of its interval."""
    X, y = _data()
    spans = _traced(tmp_path, lambda: _fit(_mlp(), X, y))
    start = _profile_start_ns(tmp_path)
    rep = tel.last_fit()
    mine = [(s, e) for n, s, e in rep["intervals"] if n == "fit.update"]
    theirs = [(s + start, e + start) for n, s, e in spans
              if n == "fit.update"]
    assert len(mine) == len(theirs) == rep["steps"] == 8
    for (s, e), (ts, te) in zip(mine, sorted(theirs)):
        assert abs(s - ts) <= 100e3 and abs(e - te) <= 100e3


def test_the_report_keeps_its_threads_intervals():
    """One interval per span closed on `fit`'s thread, in the order
    they closed, each inside the root's; the root's is `start_ns` and
    `end_ns`; a span credited from another thread leaves none."""
    X, y = _data()
    _fit(_mlp(), X, y)
    rep = tel.last_fit()
    names = [n for n, _s, _e in rep["intervals"] if n != GC]
    assert collections.Counter(names) == {
        n: r["count"] for n, r in rep["spans"].items()}
    assert rep["intervals_dropped"] == 0
    (root,) = [iv for iv in rep["intervals"] if iv[0] == "fit"]
    assert root == ("fit", rep["start_ns"], rep["end_ns"])
    assert rep["end_ns"] - rep["start_ns"] == rep["wall_ns"]
    ends = [e for n, _s, e in rep["intervals"] if n != GC]
    assert ends == sorted(ends)
    for n, s, e in rep["intervals"]:
        assert rep["start_ns"] <= s <= e <= rep["end_ns"], n

    report = tel.FitReport()
    tel.tracing._tls.report, outer = report, tel.tracing._tls.report
    try:
        tel.credit("input.decode", 5)
    finally:
        tel.tracing._tls.report = outer
    assert report.intervals == [] and report.as_dict()["spans"][
        "input.decode"]["total_ns"] == 5


def test_intervals_past_the_bound_are_counted_as_dropped(monkeypatch):
    monkeypatch.setattr(tel.tracing, "_INTERVALS", 10)
    X, y = _data()
    _fit(_mlp(), X, y)
    rep = tel.last_fit()
    assert len(rep["intervals"]) == 10
    assert rep["intervals_dropped"] == \
        sum(r["count"] for r in rep["spans"].values()) - 10 > 0


def test_a_clock_that_steps_back_gives_no_negative_span(monkeypatch):
    """The real-time clock may be stepped: a span whose end reads
    before its start lasts 0."""
    reads = iter([2_000_000, 1_000_000])
    monkeypatch.setattr(tel.tracing.time, "time_ns", lambda: next(reads))
    with tel.span("stepped") as sp:
        pass
    assert sp.ns == 0 and sp.start_ns == 2_000_000


def test_collector_passes_land_in_the_open_fit(monkeypatch):
    """A pass of the oldest generation while a `fit` is open, on its
    thread or on another, is a `py.gc` interval of its report and a
    pass of `py.gc_ns` / `py.gc_passes`, and no span row: the self
    times still tile the call.  One after the fit, like the harness's
    own after the window, lands nowhere."""
    import gc
    X, y = _data()

    def collect(param):
        if param.nbatch == 1:
            gc.collect()
            t = threading.Thread(target=gc.collect)
            t.start()
            t.join(10)

    _fit(_mlp(), X, y, batch_end_callback=collect)
    rep = tel.last_fit()
    gcs = [(s, e) for n, s, e in rep["intervals"] if n == GC]
    assert len(gcs) == rep["counters"]["py.gc_passes"] >= 4   # 2 epochs
    assert rep["counters"]["py.gc_ns"] == sum(e - s for s, e in gcs) > 0
    assert GC not in rep["spans"]
    assert sum(r["self_ns"] for r in rep["spans"].values()) \
        == rep["wall_ns"]
    gc.collect()
    assert tel.last_fit() == rep


def test_a_young_generations_pass_records_nothing():
    import gc
    report = tel.FitReport()
    outer = tel.tracing._open_fit
    tel.tracing._open_fit = report
    try:
        gc.collect(0)
        gc.collect(1)
        assert report.intervals == [] and report.counters == {}
        gc.collect(2)
        assert [n for n, _s, _e in report.intervals] == [GC]
        assert report.counters["py.gc_passes"] == 1
    finally:
        tel.tracing._open_fit = outer
