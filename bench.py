"""Benchmark: ResNet-50 training throughput through the Module API.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The north-star path (BASELINE.md, reference module/base_module.py:368-519):
``mx.mod.Module`` bound on every visible device, one batch per step through
``forward_backward`` + ``update``. On this framework that runs the fused
MeshExecutorGroup — forward+backward+psum as one mesh-sharded XLA program,
optimizer as one donated whole-tree update (module/mesh_executor_group.py).

Baseline: the reference's published ResNet-50 training throughput at batch 32
on its best single GPU — 181.53 img/s on P100 (docs/how_to/perf.md:179-189).
vs_baseline = ours / 181.53.

MFU accounting: ResNet-50 ≈ 3.8 GFLOPs/image forward at 224²; training
(fwd + bwd) ≈ 3×. peak_tflops from the device kind (bf16 systolic peak).
xla_* metrics come from the compiled program's own cost analysis; ResNet
training is HBM-bound on single chips (see PERF.md), so
hbm_util (= xla bytes-accessed / time vs peak HBM BW) is the roofline
figure of merit, not MFU.

Timing barrier: the clock stops on a data-dependent 4-byte fetch — a
tiny jitted sum of a post-step parameter, converted to a Python float —
so a window cannot end before the device finished its last step.

Refuses to measure anywhere but on a TPU, unless ``JAX_PLATFORMS=cpu``
asks for the CPU by name (the contract smoke test does); a section that
raises is recorded as a ``*_error`` field and makes the process exit
non-zero after the JSON line.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time

BASELINE_IMG_S = 181.53  # P100, reference perf.md
FLOPS_PER_IMG_TRAIN = 3.8e9 * 3

def _peaks(device_kind, n_dev):
    """n_dev-scaled (peak TFLOP/s, peak HBM GB/s). The per-chip table
    lives in mxnet_tpu.telemetry.introspect (ONE copy shared with the
    live roofline gauges, so bench and the gauges agree on peaks)."""
    from mxnet_tpu.telemetry.introspect import device_peaks
    tf, bw = device_peaks(device_kind)
    return (tf * n_dev if tf else None, bw * n_dev if bw else None)


class _DedupeLogFilter(object):
    """Drop repeated identical WARNING+ records, and drop the module
    re-entry advisories entirely.  The bench drives fit/bind in timed
    windows — re-binding an already-driven module IS the methodology —
    and each driver rep used to print its own "Already binded"/
    "optimizer already initialized" pair through the root logger
    (the JSON tail drowned in them; the in-library once-per-process
    dedupe cannot reach across the driver's repeat runs, so the bench
    drops them outright).  Other warnings print one line per
    distinct message; INFO and below pass untouched (progress lines
    legitimately repeat), which also bounds the seen set."""

    # advisories that are expected bench behavior, not signal
    _DROP = ("Already binded, ignoring bind()",
             "optimizer already initialized, ignoring")

    def __init__(self):
        self._seen = set()

    def filter(self, record):
        import logging
        if record.levelno < logging.WARNING:
            return True
        msg = record.getMessage()
        if any(d in msg for d in self._DROP):
            return False
        key = (record.levelno, msg)
        if key in self._seen:
            return False
        self._seen.add(key)
        return True


def _emit(value, extra=None):
    rec = {"metric": "resnet50_train_throughput", "value": round(value, 2),
           "unit": "images/sec", "vs_baseline": round(value / BASELINE_IMG_S,
                                                      3)}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)


def _watchdog(seconds):
    def fire(signum, frame):
        # no metric line: a backend that never came up measured nothing
        sys.stderr.write("bench.py: timeout initializing device backend\n")
        os._exit(2)

    signal.signal(signal.SIGALRM, fire)
    signal.alarm(seconds)


def _cached_feed(rec_path, step_batch, img, n, mode):
    """Cached feed measurement for one route, in this process (the one
    that holds the chip).  Decode fills the RAM cache untimed; the
    timed region feeds n batches and stops the clock only after one
    data-dependent readback, so the rate includes device completion.

    mode selects the route:
    * ``host`` — host assemble + f32 NCHW transfer;
    * ``dev`` — uint8-NHWC transfer + a per-batch on-chip augment
      program;
    * ``devcache`` — the HBM-resident dataset cache
      (mxnet_tpu.data.CachedDataset over ImageRecordIter
      (device_augment="defer")): epoch 1 fills the device cache
      untimed, then every timed batch is a device-side gather (the
      only transfer is a (B,) int32 index array) + the same
      in-program augment stage fit compiles into the train step —
      zero image bytes from the host."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.image import ImageRecordIter

    if mode == "devcache":
        it = ImageRecordIter(
            rec_path, data_shape=(3, img, img), batch_size=step_batch,
            shuffle=False, device_augment="defer", cache_decoded=True,
            label_name="softmax_label")
        spec = it.device_augment_spec["data"]
        from mxnet_tpu.data import CachedDataset
        cds = CachedDataset(it)

        def next_batch():
            try:
                return next(cds)
            except StopIteration:
                cds.reset()
                return next(cds)

        # the augment program the train step would run, folded into the
        # accumulating probe: u8 gather output -> cast/normalize ->
        # scalar tap (is_train False = deterministic variant; the
        # timed rate includes the in-program augment work)
        def acc_body(d, s):
            return s + spec.apply(d, None, None,
                                  train=False).ravel()[0]

        acc_fn = jax.jit(acc_body)
        # drain the capture epoch (fills the device cache) untimed
        while True:
            try:
                next(cds)
            except StopIteration:
                break
        cds.reset()
        b = next_batch()   # first gathered batch: compiles gather+acc
        acc = acc_fn(b.data[0], jnp.float32(0.0))
        t0 = time.time()
        for _ in range(n):
            acc = acc_fn(next_batch().data[0], acc)
        float(acc)  # the window's one readback, inside the timed region
        rate = n * step_batch / (time.time() - t0)
        info = cds.cache_info()
        cds.close()     # frees the HBM-resident cache for later sections
        it.pool.shutdown(wait=False)
        return {
            "pipeline_device_cached_img_per_sec": round(rate, 2),
            "io_cache_placement": info["placement"],
            "io_cache_bytes": info["bytes"],
            # per-step host->device bytes in cached mode: the index array
            "io_device_cached_staged_bytes_per_step": step_batch * 4}

    dev_aug = mode == "dev"
    it = ImageRecordIter(
        rec_path, data_shape=(3, img, img), batch_size=step_batch,
        shuffle=True, device_augment=dev_aug, cache_decoded=True,
        label_name="softmax_label")
    try:
        def next_batch():
            try:
                return next(it)
            except StopIteration:
                it.reset()
                return next(it)

        acc_fn = jax.jit(
            lambda d, s: s + d.ravel()[0].astype(jnp.float32))
        # sacrificial slot: fills the cache and compiles augment + acc
        acc = acc_fn(next_batch().data[0]._read(), jnp.float32(0.0))
        t0 = time.time()
        for _ in range(n):
            acc = acc_fn(next_batch().data[0]._read(), acc)
        float(acc)  # the window's one readback, INSIDE the timed region
        rate = n * step_batch / (time.time() - t0)
    finally:
        it.pool.shutdown(wait=False)
    key = ("pipeline_cached_u8_img_per_sec" if dev_aug
           else "pipeline_cached_f32_img_per_sec")
    # staged bytes/step attribution for the streaming routes: u8 NHWC
    # vs f32 NCHW is exactly the 4x the device-augment path exists for
    nbytes = step_batch * img * img * 3 * (1 if dev_aug else 4)
    return {key: round(rate, 2),
            ("io_staged_bytes_per_step_u8" if dev_aug else
             "io_staged_bytes_per_step_f32"): nbytes}


def _section(extra, failed, name, fn, *args):
    """Run one benchmark section. A crash never blocks the headline:
    it becomes a ``<name>_error`` field — and is remembered, so main()
    exits non-zero after the JSON line instead of passing for a run."""
    try:
        extra.update(fn(*args))
    except Exception as e:  # noqa: BLE001 - boundary: record and go on
        import traceback
        traceback.print_exc()
        extra[name + "_error"] = str(e)[:160]
        failed.append(name)


def main():
    _watchdog(int(os.environ.get("BENCH_INIT_TIMEOUT", "600")))

    import logging
    logging.getLogger().addFilter(_DedupeLogFilter())

    import numpy as np
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    signal.alarm(0)
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        # a chip that was not found must not become a CPU number under
        # a device metric's name; only an explicit JAX_PLATFORMS=cpu
        # (the contract smoke test) may run this on the CPU
        sys.exit("bench.py: JAX found platform %r, not a TPU — refusing "
                 "to measure (set JAX_PLATFORMS=cpu to run the CPU "
                 "contract smoke)" % platform)

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.serving import enable_persistent_compile_cache
    enable_persistent_compile_cache()

    n_dev = len(devices)
    # bs128/chip: best measured true throughput (PERF.md batch sweep)
    per_dev_batch = int(os.environ.get("BENCH_BATCH", "128"))
    batch = per_dev_batch * n_dev
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    img = 224

    # bfloat16 compute on TPU (MXU-native; params stay f32), f32 elsewhere
    dtype_env = os.environ.get("BENCH_DTYPE",
                               "bfloat16" if platform == "tpu" else "float32")
    compute_dtype = None if dtype_env == "float32" else dtype_env

    net = models.get_symbol("resnet-50", num_classes=1000)
    ctxs = [mx.Context("tpu", i) for i in range(n_dev)]
    mod = mx.mod.Module(net, context=ctxs, compute_dtype=compute_dtype)
    mod.bind(data_shapes=[("data", (batch, 3, img, img))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4,
                                         "rescale_grad": 1.0 / batch})
    from mxnet_tpu.module.mesh_executor_group import MeshExecutorGroup
    fused = isinstance(mod._exec_group, MeshExecutorGroup)

    # device-resident synthetic batches (input-pipeline throughput is its own
    # benchmark — bench_io.py), pre-sharded so staging is a no-op device_put
    rng = np.random.RandomState(0)
    n_bufs = 2
    batches = []
    sharding = mod._exec_group._batch_sharding if fused else None
    for _ in range(n_bufs):
        X = rng.rand(batch, 3, img, img).astype(np.float32)
        y = rng.randint(0, 1000, batch).astype(np.float32)
        if sharding is not None:
            Xd = mx.nd.NDArray(jax.device_put(X, sharding), ctx=ctxs[0])
            yd = mx.nd.NDArray(jax.device_put(y, sharding), ctx=ctxs[0])
        else:
            Xd, yd = mx.nd.array(X, ctx=ctxs[0]), mx.nd.array(y, ctx=ctxs[0])
        batches.append(DataBatch(data=[Xd], label=[yd]))

    def step(i):
        b = batches[i % n_bufs]
        mod.forward_backward(b)
        mod.update()

    # sections that crashed: recorded as *_error fields, and the reason
    # main() exits non-zero after the JSON line
    failed = []
    # readback-free pipeline feed rate: runs before the first barrier,
    # so no device->host fetch has happened yet in this process when
    # the host pipeline's feed rate is taken
    pipe_recs = pipe_tmp = None
    pipe_extra = {}
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        # never let a pipeline failure block the headline measurement,
        # and never let a clean-phase failure drop the fed-phase metrics
        # (the rec files survive for _bench_pipeline below)
        try:
            pipe_tmp, pipe_recs = _make_rec_files(mx, img, batch)
        except Exception as e:  # noqa: BLE001 - section boundary
            pipe_extra = {"pipeline_rec_error": str(e)[:120]}
            failed.append("pipeline_rec")
        if pipe_recs is not None:
            _section(pipe_extra, failed, "pipeline_clean",
                     _bench_pipeline_clean, mx, pipe_recs, batch, steps,
                     img, failed)

    barrier = _make_barrier(mod, fused)

    # compile + warmup (incl. the barrier program itself)
    for i in range(3):
        step(i)
    barrier()

    # Two-window slope measurement.  The window-ending readback has a
    # fixed cost that a single window charges to its steps.  Timing two
    # window lengths and differencing cancels the fixed cost exactly —
    # the slope IS the steady-state step time; min-of-reps suppresses
    # the fixed cost's variance.  window_fixed_cost_ms reports what was
    # cancelled (how large it is on the current chip path is ROADMAP
    # S1's question).  Raw single-window numbers are still emitted.
    steps_short = max(3, steps // 5)

    def _window(n):
        t0 = time.time()
        for i in range(n):
            step(i)
        barrier()
        return time.time() - t0

    # matched rep counts: min-of-k samples a lower fixed cost as k
    # grows, so unequal counts would leave a residual bias in the slope.
    # Every rep is recorded so the artifact carries its own spread —
    # a PERF claim must quote the artifact band, not a best interactive
    # run. One shared implementation: bench_timing.py.
    from bench_timing import two_window_slope
    sl = two_window_slope(_window, steps, steps_short, reps=3)
    dt, n_slope, timing = sl["dt"], sl["n_slope"], sl["timing"]
    t_long = min(sl["longs"])

    img_per_sec = n_slope * batch / dt
    achieved_tflops = img_per_sec * FLOPS_PER_IMG_TRAIN / 1e12
    peak_tf, peak_bw = _peaks(devices[0].device_kind, n_dev)
    extra = {"platform": platform, "devices": n_dev, "batch": batch,
             "steps": steps, "dtype": dtype_env, "path": "module",
             "fused_group": fused,
             "ms_per_step": round(dt * 1000 / n_slope, 2),
             "timing": timing,
             "raw_window_img_per_sec": round(steps * batch / t_long, 2),
             "achieved_tflops": round(achieved_tflops, 2),
             "device_kind": devices[0].device_kind}
    if timing == "two_window_slope":
        extra["window_fixed_cost_ms"] = round(sl["fixed_cost_s"] * 1000, 1)
        extra["window_reps_s"] = {
            "long": [round(t, 3) for t in sl["longs"]],
            "short": [round(t, 3) for t in sl["shorts"]]}
        # pairwise slope band: rate from every (long, short) rep pair —
        # the honest min/median/max of what this harness can claim
        pair_rates = [n_slope * batch / d for d in sl["pair_dts"]]
        pair_rates.sort()
        if pair_rates:
            mid = pair_rates[len(pair_rates) // 2]
            extra["img_per_sec_band"] = {
                "min": round(pair_rates[0], 1),
                "median": round(mid, 1),
                "max": round(pair_rates[-1], 1)}
    if peak_tf:
        extra["peak_tflops"] = peak_tf
        extra["mfu"] = round(achieved_tflops / peak_tf, 4)
    _section(extra, failed, "xla_cost", _xla_cost, mod, fused,
             dt / n_slope, peak_bw, n_dev)

    if os.environ.get("BENCH_HANDWRITTEN", "1") != "0":
        # independent roofline witness: framework-free NHWC ResNet-50
        # step in the same harness/barrier (PERF.md "Independent witness")
        def _handwritten():
            import bench_handwritten
            return {
                "handwritten_img_per_sec": round(
                    bench_handwritten.measure(
                        batch=per_dev_batch, steps=steps,
                        compute_dtype=dtype_env), 2),
                # the witness runs on ONE device at the per-device
                # batch; compare against the headline / n_dev on
                # multi-chip runs
                "handwritten_scope": "single_chip_bs%d" % per_dev_batch}

        _section(extra, failed, "handwritten", _handwritten)

    if os.environ.get("BENCH_FIT", "1") != "0":
        # north-star path: throughput via the REAL Module.fit loop with a
        # live eval metric. The device-side metric tally
        # makes per-batch update_metric free; the per-epoch drain (one
        # readback, data-dependent on every step program) is the honest
        # completion barrier for each epoch.
        _section(extra, failed, "fit", _bench_fit, mx, mod, batches,
                 batch, img_per_sec, steps)

    if os.environ.get("BENCH_TELEMETRY", "1") != "0":
        # telemetry overhead: the SAME fit windows with recording off
        # vs on (step timeline + compile watch + JSONL streaming) —
        # pins the <2% zero-perturbation overhead contract
        # (docs/api/telemetry.md). Off in the CPU contract smoke (its
        # fresh metric tally token is one more full resnet-50 compile).
        _section(extra, failed, "telemetry", _bench_telemetry, mx, mod,
                 batches, batch, img_per_sec, steps)

    if fused and os.environ.get("BENCH_GROUPED", "1") != "0":
        # iterations-per-loop: the same fit loop with batch_group=K —
        # K steps per launch through the scanned train-step program
        _section(extra, failed, "grouped", _bench_grouped, mx, mod,
                 batches, batch, img_per_sec, steps)

    if fused and os.environ.get("BENCH_PREFETCH", "1") != "0":
        # async device-feed pipeline: the SAME host-fed fit loop with
        # and without the DeviceLoader ring (mxnet_tpu.data) — the
        # delta is exactly what overlapping host assembly + transfer
        # with the step buys. Off in the CPU
        # contract smoke (a fresh metric tally token means one more
        # full resnet-50 train-step compile).
        _section(extra, failed, "prefetch", _bench_prefetch, mx, mod,
                 batch, steps, img_per_sec)

    if fused and os.environ.get("BENCH_PRECISION", "1") != "0":
        # opt-in precision modes (mxnet_tpu.precision): the same raw
        # step loop under BENCH_PRECISION_MODE (default "combined":
        # bf16 optimizer state + dots_saveable remat) vs the headline
        # f32 run — throughput ratio AND the analyze_compiled byte
        # account, so the recorded delta attributes the win to bytes.
        # Off in the CPU contract smoke (another full resnet-50
        # train-step compile).
        _section(extra, failed, "precision", _bench_precision, mx, net,
                 ctxs, batch, img, steps, img_per_sec,
                 extra.get("xla_bytes_per_step_gb"), n_dev,
                 compute_dtype)

    if os.environ.get("BENCH_SERVE", "1") != "0":
        # online serving: bucketed Predictor + DynamicBatcher under
        # concurrent mixed-size requests (docs/api/serving.md) — the
        # production-shaped small-request load the training-side
        # numbers cannot show. Off in the CPU contract smoke (every
        # bucket is another full resnet-50 eval compile).
        _section(extra, failed, "serve", _bench_serve, mx, mod, batch,
                 n_dev)

    if os.environ.get("BENCH_DECODE", "1") != "0":
        # continuous-batching decode: the slot-structured step engine
        # under concurrent streaming clients vs the sequential
        # per-request baseline (docs/api/serving.md "Decode engine").
        # Cheap (bench-sized char-LM), but off in the CPU contract
        # smoke with the other serving sections.
        _section(extra, failed, "decode", _bench_decode, n_dev)

    extra.update(pipe_extra)
    if pipe_recs is not None:
        _section(extra, failed, "pipeline", _bench_pipeline, mx, mod,
                 pipe_recs, batch, steps, img, img_per_sec, barrier,
                 failed)
        import shutil
        shutil.rmtree(pipe_tmp, ignore_errors=True)
        extra.update(_pipeline_verdict(extra))

    if os.environ.get("BENCH_SHARDED_CACHE", "1") != "0":
        # pod-sharded dataset cache (mxnet_tpu.data.ShardedCachedDataset):
        # per-tier gather feed rates over the local devices partitioned
        # into virtual hosts — the per-batch transfer on the hbm tier is
        # a (B,) int32 index; the host tier pays the staged rows back.
        # Off in the CPU contract smoke (its own gather/augment compiles
        # would eat the tier-1 budget).
        _section(extra, failed, "sharded_cache", _bench_sharded_cache,
                 mx, batch, extra)

    if os.environ.get("BENCH_AUTOPILOT", "1") != "0":
        # fleet autopilot (mxnet_tpu.autopilot, docs/api/autopilot.md):
        # replica spin-up latency through the persistent executable
        # cache vs a cold JIT spin-up (the scale-out an SLO breach
        # triggers), and peer-memory checkpoint assembly vs the disk
        # restore of the same step (the elastic goodput win). Cheap
        # enough (one tiny MLP) to stay on in the CPU contract smoke.
        _section(extra, failed, "autopilot", _bench_autopilot, mx)

    if os.environ.get("BENCH_SCENARIOS", "0") != "0":
        # pinned-workload scenario matrix (mxnet_tpu.scenarios,
        # docs/api/scenarios.md): per-scenario training throughput
        # through the same fit path the contract gate runs. Opt-in
        # (BENCH_SCENARIOS=1) — the matrix trains every registered
        # long-tail workload and is far too heavy for the CPU
        # contract smoke.
        _section(extra, failed, "scenarios", _bench_scenarios)

    if os.environ.get("BENCH_GATEWAY", "0") != "0":
        # network serving plane (mxnet_tpu.gateway,
        # docs/api/gateway.md): the same predict rows and decode
        # streams measured above, but through the HTTP front door —
        # gateway_overhead_pct is the per-request tax of the wire
        # (JSON + socket + routing) over the in-process Predictor,
        # and gateway_ttft_ms percentiles are CLIENT-observed first
        # token latencies (what a caller actually waits, not the
        # engine's internal ring). Opt-in (BENCH_GATEWAY=1) — the
        # loopback HTTP load is meaningless in the contract smoke.
        _section(extra, failed, "gateway", _bench_gateway, mx)
    _emit(img_per_sec, extra)
    if failed:
        sys.exit("bench.py: section(s) raised: %s" % ", ".join(failed))


class _DeviceBatchIter(object):
    """Minimal DataIter over pre-staged device-resident batches: fit's
    input-pipeline cost is measured separately (pipeline_* fields), so
    the fit benchmark isolates the LOOP itself — step + metric + epoch
    bookkeeping — exactly like the synthetic headline does for the step."""

    def __init__(self, batches, provide_data, provide_label, n_batches):
        self._batches = batches
        self._n = n_batches
        self._i = 0
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= self._n:
            raise StopIteration
        b = self._batches[self._i % len(self._batches)]
        self._i += 1
        return b

    next = __next__

    def reset(self):
        self._i = 0


def _fit_window_slope(run, ep_batches, batch, step_img_per_sec, prefix,
                      plaus):
    """Two fit-call windows of different epoch counts, differenced —
    the ONE implementation of the fit-loop slope (plain fit AND grouped
    fit consume it, so the methodology/guards cannot drift between the
    two metrics).  Emits ``<prefix>_img_per_sec`` + band + ``_vs_step``
    when the slope is sane, else a ``<prefix>_error`` that names
    degeneracy vs implausibility.  The plausibility guard exists
    because a slope from noise-dominated near-equal windows once
    recorded 11.8x (a round-5 run, pre-token-fix recompiles);
    ``plaus`` is the allowed ratio over the raw step
    rate.  Returns (fields, ok)."""
    from bench_timing import two_window_slope
    sl = two_window_slope(run, 4, 2, reps=2)
    out = {prefix + "_reps_s": {
        "long": [round(t, 3) for t in sl["longs"]],
        "short": [round(t, 3) for t in sl["shorts"]]}}
    rate = sl["n_slope"] * ep_batches * batch / sl["dt"] \
        if sl["dt"] > 0 else 0.0
    ok = sl["timing"] == "two_window_slope" and \
        (step_img_per_sec <= 0 or rate <= plaus * step_img_per_sec)
    if ok:
        out[prefix + "_img_per_sec"] = round(rate, 2)
        pair = sorted(sl["n_slope"] * ep_batches * batch / d
                      for d in sl["pair_dts"])
        if pair:
            out[prefix + "_img_per_sec_band"] = {
                "min": round(pair[0], 1),
                "median": round(pair[len(pair) // 2], 1),
                "max": round(pair[-1], 1)}
        if step_img_per_sec > 0:
            out[prefix + "_vs_step"] = round(rate / step_img_per_sec, 3)
    else:
        out[prefix + "_error"] = "degenerate %s windows: %r vs %r" % (
            prefix, sl["longs"], sl["shorts"])
        if step_img_per_sec > 0 and rate > plaus * step_img_per_sec:
            out[prefix + "_error"] = (
                "implausible %s slope %.0f img/s vs step %.0f — "
                "windows %r vs %r" % (prefix, rate, step_img_per_sec,
                                      sl["longs"], sl["shorts"]))
    return out, ok


def _bench_fit(mx, mod, batches, batch, step_img_per_sec, steps):
    """Module.fit(eval_metric='acc') throughput via two fit() calls of
    different epoch counts, differenced (two-window slope over whole
    epochs). Every per-epoch cost fit really pays — the device-tally
    drain readback, metric reset, iterator reset — is inside the
    window; compile/session warmup cancels in the difference."""
    # 12*steps (240 at the default 20) still UNDERSTATES real epochs —
    # ImageNet at this rate is ~10,000 steps/epoch — so the per-epoch
    # drain cost this measures is an upper bound on the true one
    ep_batches = int(os.environ.get("BENCH_FIT_EPOCH_BATCHES",
                                    str(max(4, steps * 12))))
    it = _DeviceBatchIter(batches, mod.data_shapes, mod.label_shapes,
                          ep_batches)
    metric = mx.metric.Accuracy()

    def run(n_epochs):
        t0 = time.time()
        # bind/init/init_optimizer are no-ops on the already-driven
        # module; fit reuses the compiled one-program step
        mod.fit(it, eval_metric=metric, num_epoch=n_epochs)
        return time.time() - t0

    run(1)  # warm the fit path (metric program recompile)
    # plausibility: fit cannot beat the raw step rate
    fields, ok = _fit_window_slope(run, ep_batches, batch,
                                   step_img_per_sec, "fit", plaus=1.2)
    out = {"fit_epoch_batches": ep_batches}
    out.update(fields)
    if ok:
        grp = mod._exec_group
        out["fit_device_metric"] = getattr(grp, "_metric_live",
                                           None) is metric
        out["fit_train_acc"] = round(float(metric.get()[1]), 4)
    return out


def _bench_telemetry(mx, mod, batches, batch, step_img_per_sec, steps):
    """Telemetry recording overhead on the REAL fit loop: the same
    two-fit-windows slope, once with telemetry disabled and once with
    the full recording path live (StepTimeline records, CompileWatch
    wrappers, one JSONL step line per step to a temp file).
    ``telemetry_overhead_pct`` is the throughput the recording costs —
    the subsystem's <2% contract; ``telemetry_post_warmup_retraces``
    must be 0 (fit declares the warmup boundary after its first
    epoch)."""
    import tempfile

    from mxnet_tpu import telemetry as tel

    ep_batches = int(os.environ.get("BENCH_FIT_EPOCH_BATCHES",
                                    str(max(4, steps * 12))))
    it = _DeviceBatchIter(batches, mod.data_shapes, mod.label_shapes,
                          ep_batches)
    # ONE metric for both windows: each new metric object is a new
    # device-tally token, i.e. another full train-step compile
    metric = mx.metric.Accuracy()

    def run(n_epochs):
        t0 = time.time()
        mod.fit(it, eval_metric=metric, num_epoch=n_epochs)
        return time.time() - t0

    # snapshot operator telemetry (MXNET_TELEMETRY autostart) so this
    # stage's off-window toggling doesn't tear down their sink/server
    # for the rest of the bench run
    was_enabled = tel.enabled()
    prev_sink = tel.jsonl_sink()
    prev_sink_path = prev_sink.path if prev_sink is not None else None
    prev_server = tel.metrics_server()
    prev_port = prev_server.port if prev_server is not None else None
    tel.disable()
    try:
        run(1)  # warm this metric's train-step program
        off_fields, off_ok = _fit_window_slope(
            run, ep_batches, batch, step_img_per_sec, "telemetry_off",
            plaus=1.2)

        tmp = tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False)
        tmp.close()
        tel.enable(jsonl=tmp.name)
        try:
            run(1)  # warm the recording path (watch attach, sink open)
            on_fields, on_ok = _fit_window_slope(
                run, ep_batches, batch, step_img_per_sec, "telemetry_on",
                plaus=1.2)
            out = {"telemetry_post_warmup_retraces":
                   tel.compile_watch().post_warmup_count,
                   "telemetry_step_records": len(tel.timeline())}
        finally:
            tel.disable()
            os.unlink(tmp.name)
    finally:
        if was_enabled:
            tel.enable(jsonl=prev_sink_path, port=prev_port)
    out.update(off_fields)
    out.update(on_fields)
    if off_ok and on_ok:
        off_r = off_fields["telemetry_off_img_per_sec"]
        on_r = on_fields["telemetry_on_img_per_sec"]
        out["telemetry_overhead_pct"] = round(
            100.0 * (off_r - on_r) / off_r, 2)
    return out


def _bench_grouped(mx, mod, batches, batch, step_img_per_sec, steps):
    """Module.fit(batch_group=K) throughput — K whole train steps per
    XLA launch via the scanned grouped program.  Same two-fit-windows
    slope discipline as _bench_fit; device-resident batches isolate the
    LOOP+LAUNCH amortization (the transfer-side amortization is
    pipeline_grouped_img_per_sec).  With ~5 ms launch overhead on
    ~47 ms steps (PERF.md) the expected gain is modest here and large
    on the fed pipeline, where each group also saves (K-1) fixed
    ~110 ms transfer costs."""
    group_k = int(os.environ.get("BENCH_GROUP", "4"))
    ep_batches = int(os.environ.get("BENCH_FIT_EPOCH_BATCHES",
                                    str(max(4, steps * 12))))
    it = _DeviceBatchIter(batches, mod.data_shapes, mod.label_shapes,
                          ep_batches)
    metric = mx.metric.Accuracy()

    def run(n_epochs):
        t0 = time.time()
        mod.fit(it, eval_metric=metric, num_epoch=n_epochs,
                batch_group=group_k)
        return time.time() - t0

    run(1)  # warm (grouped-program compile)
    if not mod.grouped_train_engaged():
        return {"grouped_error": "grouped program did not engage "
                                 "(fit fell back to per-batch)"}
    # plausibility at 1.3x (vs fit's 1.2x): grouping legitimately saves
    # fixed per-step overheads, so it may modestly beat the step rate
    fields, _ok = _fit_window_slope(run, ep_batches, batch,
                                    step_img_per_sec, "grouped",
                                    plaus=1.3)
    out = {"grouped_batch_group": group_k,
           "grouped_epoch_batches": ep_batches}
    out.update(fields)
    return out


def _bench_prefetch(mx, mod, batch, steps, step_img_per_sec):
    """Device-feed pipeline throughput (mxnet_tpu.data.DeviceLoader):
    two host-FED fit windows — plain (every batch's device_put on the
    step's critical path) vs prefetched (a background stager keeps a
    depth-2 ring of batches already resident, transfers overlapped
    with compute).  Same two-fit-windows slope discipline as
    _bench_fit.  ``prefetch_vs_plain`` is the overlap win;
    ``host_wait_ms_per_step`` (from PipelineStats) says how much of
    the input path the ring could NOT hide — on a balanced pipeline
    it approaches 0 while the plain loop pays the full transfer."""
    import numpy as np

    from mxnet_tpu.data import DeviceLoader
    from mxnet_tpu.io import DataBatch

    shape = dict(mod.data_shapes)["data"]
    rng = np.random.RandomState(7)
    host_batches = []
    for _ in range(2):
        X = rng.rand(*shape).astype(np.float32)
        yv = rng.randint(0, 1000, shape[0]).astype(np.float32)
        host_batches.append(DataBatch(data=[mx.nd.array(X)],
                                      label=[mx.nd.array(yv)]))
    ep_batches = int(os.environ.get("BENCH_FIT_EPOCH_BATCHES",
                                    str(max(4, steps * 12))))
    depth = int(os.environ.get("BENCH_PREFETCH_DEPTH", "2"))
    # ONE metric for both windows: each new metric object is a new
    # device-tally token, i.e. another full train-step compile
    metric = mx.metric.Accuracy()

    def make_iter():
        return _DeviceBatchIter(host_batches, mod.data_shapes,
                                mod.label_shapes, ep_batches)

    def run_plain(n_epochs):
        t0 = time.time()
        mod.fit(make_iter(), eval_metric=metric, num_epoch=n_epochs)
        return time.time() - t0

    out = {"prefetch_depth": depth,
           "prefetch_epoch_batches": ep_batches}
    run_plain(1)  # warm the host-fed path (+ this metric's program)
    plain_fields, plain_ok = _fit_window_slope(
        run_plain, ep_batches, batch, step_img_per_sec,
        "prefetch_plain", plaus=1.2)

    # loader created only AFTER the plain windows: its stager starts
    # transferring immediately, which would contend with (and inflate)
    # the plain measurement
    loader = DeviceLoader(make_iter(), module=mod, depth=depth)

    def run_pre(n_epochs):
        t0 = time.time()
        mod.fit(loader, eval_metric=metric, num_epoch=n_epochs)
        return time.time() - t0

    try:
        run_pre(1)  # warm the ring (stager start, first transfers)
        pre_fields, pre_ok = _fit_window_slope(
            run_pre, ep_batches, batch, step_img_per_sec, "prefetch",
            plaus=1.2)
    finally:
        snap = loader.pipeline_stats.snapshot()
        loader.close()
    out.update(plain_fields)
    out.update(pre_fields)
    out["host_wait_ms_per_step"] = snap["host_wait_ms_per_step"]
    out["prefetch_ring_high_water"] = snap["ring_high_water"]
    if pre_ok and plain_ok and \
            plain_fields.get("prefetch_plain_img_per_sec"):
        out["prefetch_vs_plain"] = round(
            pre_fields["prefetch_img_per_sec"]
            / plain_fields["prefetch_plain_img_per_sec"], 3)
    return out


def _bench_precision(mx, net, ctxs, batch, img, steps, f32_img_per_sec,
                     f32_gb_per_step, n_dev, compute_dtype):
    """Precision-mode window (mxnet_tpu.precision): a SECOND module on
    the same symbol under ``BENCH_PRECISION_MODE`` (default "combined"
    = bf16 optimizer state + dots_saveable remat), driven by the same
    raw step loop and two-window slope as the headline number, plus the
    shared ``analyze_compiled`` byte account of its one-program train
    step.  ``precision_gb_vs_f32`` attributes the throughput delta to
    bytes: <1.0 means the mode genuinely ships fewer bytes per step.
    NOTE the byte realization is platform-dependent — bf16 state
    streams shrink everywhere, but remat's temp-buffer win exists only
    where XLA buffer assignment honors checkpoint boundaries (TPU/GPU,
    not CPU), and a bf16 compute cast on XLA:CPU ADDS cast traffic
    around f32 convs (docs/how_to/perf.md byte-count levers)."""
    import time

    import jax
    import numpy as np

    from bench_timing import two_window_slope
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.telemetry.introspect import analyze_compiled

    mode = os.environ.get("BENCH_PRECISION_MODE", "combined")
    pmod = mx.mod.Module(net, context=ctxs, compute_dtype=compute_dtype,
                         precision=mode)
    pmod.bind(data_shapes=[("data", (batch, 3, img, img))],
              label_shapes=[("softmax_label", (batch,))])
    pmod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                           factor_type="in", magnitude=2))
    pmod.init_optimizer(optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9, "wd": 1e-4,
                                          "rescale_grad": 1.0 / batch})

    rng = np.random.RandomState(0)
    sharding = pmod._exec_group._batch_sharding
    batches = []
    for _ in range(2):
        X = rng.rand(batch, 3, img, img).astype(np.float32)
        y = rng.randint(0, 1000, batch).astype(np.float32)
        batches.append(DataBatch(
            data=[mx.nd.NDArray(jax.device_put(X, sharding), ctx=ctxs[0])],
            label=[mx.nd.NDArray(jax.device_put(y, sharding),
                                 ctx=ctxs[0])]))

    def step(i):
        pmod.forward_backward(batches[i % 2])
        pmod.update()

    barrier = _make_barrier(pmod, True)
    for i in range(3):
        step(i)
    barrier()

    def _window(n):
        t0 = time.time()
        for i in range(n):
            step(i)
        barrier()
        return time.time() - t0

    steps_short = max(3, steps // 5)
    sl = two_window_slope(_window, steps, steps_short, reps=3)
    rate = sl["n_slope"] * batch / sl["dt"]
    out = {"precision_mode": mode,
           "precision_img_per_sec": round(rate, 2)}
    if f32_img_per_sec:
        out["precision_vs_f32"] = round(rate / f32_img_per_sec, 3)

    comp = compiled_step(pmod._exec_group)
    if comp is not None:
        a = analyze_compiled(comp)
        gb = a["bytes_accessed"] * n_dev / 1e9
        out["precision_gb_per_step"] = round(gb, 3)
        out["precision_argument_gb"] = round(
            a.get("argument_bytes", 0) * n_dev / 1e9, 3)
        out["precision_temp_gb"] = round(
            a.get("temp_bytes", 0) * n_dev / 1e9, 3)
        if f32_gb_per_step:
            out["precision_gb_vs_f32"] = round(gb / f32_gb_per_step, 3)
    return out


def _bench_serve(mx, mod, batch, n_dev):
    """Online-serving load through mxnet_tpu.serving: a Predictor
    (shape-bucketed program cache, params snapshotted from the trained
    bench module) fronted by a DynamicBatcher, fired at by concurrent
    client threads with mixed-size requests for a fixed wall window.

    serve_qps counts completed requests/s; latency percentiles and the
    batch-fill ratio come from the shared ServingStats snapshot, so the
    artifact records how full the coalesced launches actually ran. The
    post-warmup compile count is emitted too — it must be 0 (the
    serving contract) and a nonzero value in an artifact is a red flag
    on its own."""
    import threading

    import numpy as np

    from mxnet_tpu.serving import DynamicBatcher, Predictor, QueueFull

    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", "5"))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    # serving requests are small; cap the ladder well below the train
    # batch so warmup stays a handful of eval compiles
    serve_max = int(os.environ.get("BENCH_SERVE_MAX_BATCH",
                                   str(min(batch, 8 * n_dev))))
    # replica warm start (docs/api/serving.md "Persistent compile
    # cache"): the first replica compiles the ladder and commits each
    # bucket's executable; a second replica (fresh Predictor — fresh
    # jit objects, nothing trace-cached) warms from the same directory
    # by deserializing. cold/warm wall times are the recorded win.
    import shutil
    import tempfile
    # a cold/warm fixture for the AOT executable store, not a compile
    # cache location: it must start empty so the first warmup is cold
    cache_root = tempfile.mkdtemp(prefix="bench_serve_cache_")
    try:
        pred = Predictor(mod, max_batch_size=serve_max)
        t_cold = time.time()
        pred.warmup(cache_dir=cache_root)
        cold_s = time.time() - t_cold
        warm_pred = Predictor(mod, max_batch_size=serve_max)
        t_warm = time.time()
        warm_pred.warmup(cache_dir=cache_root)
        warm_s = time.time() - t_warm
        warm_all_deserialized = all(
            r["source"] == "deserialized"
            for r in warm_pred.warmup_report().values())
        warm_pred.release()
    finally:
        shutil.rmtree(cache_root, ignore_errors=True)
    compiles0 = pred.stats()["compiles"]

    shape = dict(mod.data_shapes)["data"]
    rng = np.random.RandomState(3)
    sizes = sorted({1, 2, 3, max(1, serve_max // 4),
                    max(1, serve_max // 2)})
    pool = [rng.rand(n, *shape[1:]).astype(np.float32) for n in sizes]
    batcher = DynamicBatcher(pred, max_queue=4 * clients,
                             max_wait_ms=2.0)
    stop_at = time.time() + seconds
    done_lock = threading.Lock()
    done = [0]

    def client(i):
        k = i
        while time.time() < stop_at:
            x = pool[k % len(pool)]
            k += 1
            try:
                batcher.predict(x, timeout=120)
            except QueueFull:
                time.sleep(0.002)
                continue
            with done_lock:
                done[0] += 1

    t0 = time.time()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batcher.shutdown(drain=True)
    elapsed = time.time() - t0
    s = pred.stats()
    lat = s["latency_ms"]
    return {
        "serve_qps": round(done[0] / elapsed, 2),
        "serve_latency_ms_p50": (round(lat["p50"], 3)
                                 if lat["p50"] is not None else None),
        "serve_latency_ms_p99": (round(lat["p99"], 3)
                                 if lat["p99"] is not None else None),
        "serve_batch_fill": s["batch_fill"],
        "serve_requests": s["completed"],
        "serve_clients": clients,
        "serve_buckets": pred.buckets,
        "serve_rejected": s["rejected"],
        "serve_post_warmup_compiles": s["compiles"] - compiles0,
        "serve_cold_start_s": round(cold_s, 3),
        "serve_warm_start_s": round(warm_s, 3),
        "serve_warm_vs_cold": (round(cold_s / warm_s, 2)
                               if warm_s > 0 else None),
        "serve_warm_all_deserialized": warm_all_deserialized,
    }


def _bench_decode(n_dev):
    """Continuous-batching decode load through
    mxnet_tpu.serving.decode (docs/api/serving.md "Decode engine"): a
    bench-sized char-LM decoded by concurrent streaming clients
    through the slot-structured engine, against the sequential
    per-request baseline on the same warmed program family.

    decode_tokens_per_sec is the continuous engine's aggregate over
    device-busy wall; TTFT percentiles come from the engine's own
    ring; decode_slot_occupancy is the mean active-slot fraction per
    step (the continuous-batching win is roughly occupancy /
    (1/slots))."""
    import numpy as np

    from mxnet_tpu.serving.decode import DecodeEngine, LSTMCharLM

    slots = int(os.environ.get("BENCH_DECODE_SLOTS", "8"))
    n_req = int(os.environ.get("BENCH_DECODE_REQUESTS",
                               str(3 * slots)))
    max_new = int(os.environ.get("BENCH_DECODE_MAX_NEW", "64"))
    model = LSTMCharLM(vocab_size=64, num_hidden=64, num_embed=32)
    params = model.init_params(seed=7)
    rng = np.random.RandomState(7)
    prompts = [list(map(int, rng.randint(0, 64, size=int(
        rng.randint(2, 17))))) for _ in range(n_req)]

    eng = DecodeEngine(model, params, slots=slots, max_prefill_len=16,
                       start=False)
    eng.warmup()
    reqs = [eng.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(prompts)]
    t0 = time.time()
    eng.start()
    for r in reqs:
        r.result(timeout=600)
    wall = time.time() - t0
    eng.shutdown(drain=True)
    cont = eng.stats()["decode"]
    eng.release()

    seq = DecodeEngine(model, params, slots=slots, max_prefill_len=16)
    seq.warmup()
    for i, p in enumerate(prompts):
        seq.generate(p, max_new_tokens=max_new, seed=i, timeout=600)
    seq.shutdown(drain=True)
    seq_tps = seq.stats()["decode"]["tokens_per_sec"]
    seq.release()

    # weight-only int8 vs bf16 on the same load: the decode step
    # re-reads every weight byte per token, so the memory-bound claim
    # needs BOTH witnesses — the analyze_compiled argument-bytes shrink
    # AND the tokens/sec ratio (precision.quant, docs/api/precision.md)
    def _mode_run(mode):
        e = DecodeEngine(model, params, slots=slots, max_prefill_len=16,
                         start=False, precision=mode)
        e.warmup()
        rs = [e.submit(p, max_new_tokens=max_new, seed=i)
              for i, p in enumerate(prompts)]
        e.start()
        for r in rs:
            r.result(timeout=600)
        e.shutdown(drain=True)
        d = e.stats()["decode"]
        out = {"tokens_per_sec": d["tokens_per_sec"],
               "weight_bytes": d["weight_bytes"],
               "step_argument_bytes": e.step_argument_bytes()}
        e.release()
        return out

    bf16 = _mode_run("bf16")
    int8 = _mode_run("int8_weight")

    return {
        "decode_weight_bytes_per_token": int8["weight_bytes"],
        "decode_weight_bytes_per_token_bf16": bf16["weight_bytes"],
        "decode_step_argument_bytes_int8": int8["step_argument_bytes"],
        "decode_step_argument_bytes_bf16": bf16["step_argument_bytes"],
        "decode_int8_tokens_per_sec": int8["tokens_per_sec"],
        "decode_bf16_tokens_per_sec": bf16["tokens_per_sec"],
        "decode_quant_speedup": (
            round(int8["tokens_per_sec"] / bf16["tokens_per_sec"], 2)
            if int8["tokens_per_sec"] and bf16["tokens_per_sec"]
            else None),
        "decode_tokens_per_sec": cont["tokens_per_sec"],
        "decode_sequential_tokens_per_sec": seq_tps,
        "decode_speedup": (round(cont["tokens_per_sec"] / seq_tps, 2)
                           if cont["tokens_per_sec"] and seq_tps
                           else None),
        "decode_ttft_ms_p50": (round(cont["ttft_ms"]["p50"], 3)
                               if cont["ttft_ms"]["p50"] is not None
                               else None),
        "decode_ttft_ms_p99": (round(cont["ttft_ms"]["p99"], 3)
                               if cont["ttft_ms"]["p99"] is not None
                               else None),
        "decode_slot_occupancy": cont["avg_occupancy"],
        "decode_slots": slots,
        "decode_requests": n_req,
        "decode_tokens": cont["tokens"],
        "decode_wall_s": round(wall, 3),
    }


def _make_rec_files(mx, img, step_batch):
    """Write the synthetic .rec files (raw-npy and jpeg payloads) used by
    both pipeline measurements. Returns (tmpdir, {fmt: path})."""
    import tempfile

    import numpy as np

    n_images = max(int(os.environ.get("BENCH_IO_IMAGES", "512")),
                   2 * step_batch)
    rng = np.random.RandomState(1)
    tmp = tempfile.mkdtemp(prefix="bench_io_")
    recs = {"_n_images": n_images}
    try:
        for fmt in ("npy", "jpg"):
            path = os.path.join(tmp, "train_%s.rec" % fmt)
            writer = mx.recordio.MXRecordIO(path, "w")
            for i in range(n_images):
                arr = (rng.rand(img, img, 3) * 255).astype(np.uint8)
                writer.write(mx.recordio.pack_img(
                    mx.recordio.IRHeader(0, float(i % 1000), i, 0), arr,
                    img_fmt="." + fmt))
            writer.close()
            rdr = mx.recordio.MXRecordIO(path, "r")
            _, payload = mx.recordio.unpack(rdr.read())
            rdr.close()
            if fmt == "jpg" and payload[:6] == b"\x93NUMPY":
                recs["_jpeg_skipped"] = "no jpeg encoder on host"
                continue
            recs[fmt] = path
    except Exception:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return tmp, recs


def _io_iter_opts():
    threads = int(os.environ.get("BENCH_IO_THREADS", str(
        min(16, (os.cpu_count() or 1) * 4))))
    procs = int(os.environ.get(
        "BENCH_IO_PROCS", str((os.cpu_count() or 1)
                              if (os.cpu_count() or 1) >= 4 else 0)))
    # the streaming measurements default to the host-assemble route;
    # BENCH_IO_DEVICE_AUG=1 takes the uint8 transfer + on-chip
    # normalize route (which of the two feeds faster on the current
    # chip path is ROADMAP S2's question)
    dev_aug = os.environ.get("BENCH_IO_DEVICE_AUG", "0") != "0"
    return threads, procs, dev_aug


def _bench_sharded_cache(mx, step_batch, seen_extra=None):
    """Pod-sharded dataset cache feed rates, one field per tier.

    Builds a synthetic u8 epoch over the local devices partitioned
    into virtual hosts (the CPU-CI harness IS the measurement rig —
    on a real pod the same class rides
    ``make_array_from_process_local_data`` per process) and times the
    epoch->=2 serve path for each tier:

    * ``sharded_cache_hbm_img_per_sec`` — the dp-sharded device cache,
      jitted global gather, (B,) int32 index per batch;
    * ``sharded_cache_host_img_per_sec`` — the spill tier: rows
      gathered host-side and staged per batch;
    * ``sharded_cache_single_img_per_sec`` — the single-shard
      (CachedDataset-equivalent) device gather, for the N-way
      comparison.

    Also records ``io_cache_tier``/``io_cache_shard_bytes``/
    ``io_cache_global_rows``/``io_cache_n_shards`` from the resolved
    hbm run, and fills ``pipeline_device_cached_img_per_sec`` from the
    single-shard rate when the fed-pipeline stage did not record one
    (tagged ``io_cache_source`` so the two methodologies are never
    conflated)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import dist
    from mxnet_tpu.data import CachedDataset, ShardedCachedDataset

    n_dev = len(jax.devices())
    n_hosts = next((h for h in (4, 2, 1)
                    if h <= n_dev and n_dev % h == 0), 1)
    side = int(os.environ.get("BENCH_SHARDED_CACHE_SIDE", "64"))
    rows = 8 * step_batch
    rng = np.random.RandomState(0)
    Xu8 = rng.randint(0, 256, (rows, side, side, 3)).astype(np.uint8)
    y = rng.randint(0, 1000, rows).astype(np.float32)

    def make_iter():
        return mx.io.NDArrayIter(Xu8, y, batch_size=step_batch,
                                 label_name="softmax_label")

    def _val(a):
        return a._read() if hasattr(a, "_read") else a

    def feed_rate(ds, n=16):
        while True:                 # capture epoch, untimed
            try:
                next(ds)
            except StopIteration:
                break
        ds.reset()
        acc_fn = jax.jit(
            lambda d, s: s + d.ravel()[0].astype(jnp.float32))

        def next_batch():
            try:
                return next(ds)
            except StopIteration:
                ds.reset()
                return next(ds)

        acc = acc_fn(_val(next_batch().data[0]), jnp.float32(0.0))
        t0 = time.time()
        for _ in range(n):
            acc = acc_fn(_val(next_batch().data[0]), acc)
        float(acc)                  # completion-ordering readback
        return n * step_batch / (time.time() - t0)

    out = {"io_cache_rows_shape": [rows, side, side, 3]}
    cluster = dist.VirtualCluster(n_hosts) if n_hosts > 1 else None

    hbm = ShardedCachedDataset(make_iter(), cluster=cluster, tier="hbm")
    out["sharded_cache_hbm_img_per_sec"] = round(feed_rate(hbm), 2)
    info = hbm.cache_info()
    out.update({"io_cache_tier": info["tier"],
                "io_cache_shard_bytes": info["shard_bytes"],
                "io_cache_global_rows": info["rows"],
                "io_cache_n_shards": info["num_shards"]})
    hbm.close()

    host = ShardedCachedDataset(make_iter(), cluster=cluster,
                                tier="host")
    out["sharded_cache_host_img_per_sec"] = round(feed_rate(host), 2)
    host.close()

    single = CachedDataset(make_iter())
    rate1 = round(feed_rate(single), 2)
    single_info = single.cache_info()
    out["sharded_cache_single_img_per_sec"] = rate1
    single.close()
    if not (seen_extra or {}).get("pipeline_device_cached_img_per_sec"):
        out["pipeline_device_cached_img_per_sec"] = rate1
        out["io_cache_source"] = "sharded_cache_stage"
        out["io_cache_placement"] = single_info["placement"]
        out["io_cache_bytes"] = single_info["bytes"]
    return out


def _bench_autopilot(mx):
    """Autopilot actuator latencies (docs/api/autopilot.md): the
    scale-out spin-up a breach triggers — cold (fresh JIT of every
    bucket) vs warm (deserialized from the persistent executable
    cache, the ReplicaPool path) — and the elastic resume restore —
    peer host-memory assembly (PeerCheckpointStore) vs the manager's
    disk restore of the same step.

    ``autopilot_spinup_warm_over_cold`` and
    ``peer_over_disk_restore`` are the two speedups the autopilot's
    zero-recompile / zero-reread claims buy."""
    import shutil
    import tempfile

    import numpy as np

    from mxnet_tpu.autopilot import PeerCheckpointStore, ReplicaPool
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.serving import Predictor

    dim = 16
    rng = np.random.RandomState(0)
    X = rng.rand(64, dim).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.float32)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mx.random.seed(7)
    mod = mx.mod.Module(net, context=[mx.cpu()])
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=8), num_epoch=1,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1})

    tmp = tempfile.mkdtemp(prefix="bench_autopilot_")
    out = {}
    try:
        mgr = CheckpointManager(os.path.join(tmp, "ckpt"))
        mod.save_checkpoint(None, 1, manager=mgr, async_save=False)
        shapes = [("data", (8, dim))]

        def factory():
            return Predictor.load(mgr, 1, data_shapes=shapes)

        # cold: every bucket is a fresh XLA compile
        with ReplicaPool(factory, min_replicas=1, max_replicas=1,
                         cache_dir=None) as cold:
            out["autopilot_spinup_cold_ms"] = round(
                cold.spinup_reports[0]["spinup_ms"], 3)

        # warm: the cache a real pool's first replica committed
        cache_dir = os.path.join(tmp, "exec_cache")
        seed_pred = factory()
        seed_pred.warmup(cache_dir=cache_dir)
        seed_pred.release()
        with ReplicaPool(factory, min_replicas=1, max_replicas=1,
                         cache_dir=cache_dir) as warm:
            out["autopilot_spinup_warm_ms"] = round(
                warm.spinup_reports[0]["spinup_ms"], 3)
        out["autopilot_spinup_warm_over_cold"] = round(
            out["autopilot_spinup_cold_ms"] /
            max(out["autopilot_spinup_warm_ms"], 1e-9), 2)

        # peer-memory assembly vs the disk restore of the same step
        arrays = mod._checkpoint_arrays()
        opt = mod._optimizer_state_bytes()
        mgr.save(2, arrays, optimizer_state=opt, extra={"epoch": 1},
                 async_save=False)
        store = PeerCheckpointStore(2)
        store.capture(2, arrays, optimizer_state=opt,
                      extra={"epoch": 1})
        t0 = time.perf_counter()
        peer_ck = store.restore(2)
        out["peer_restore_ms"] = round(
            (time.perf_counter() - t0) * 1000.0, 3)
        t0 = time.perf_counter()
        disk_ck = mgr.restore(2)
        out["disk_restore_ms"] = round(
            (time.perf_counter() - t0) * 1000.0, 3)
        out["peer_over_disk_restore"] = round(
            out["disk_restore_ms"] /
            max(out["peer_restore_ms"], 1e-9), 2)
        assert all(np.array_equal(np.asarray(peer_ck.params[k]),
                                  np.asarray(disk_ck.params[k]))
                   for k in disk_ck.params)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_scenarios():
    """Per-scenario training throughput (docs/api/scenarios.md): each
    registered pinned workload fit twice through the matrix runner's
    seeded fit — the first pass warms every program (trace + XLA
    compile land in the executable caches), the second is the timed
    steady-state run, so the rows measure training rate, not compile.

    Emits ``scenario_<name>_rows_per_sec`` for every scenario and
    additionally ``scenario_<name>_tokens_per_sec`` where the batch
    is token-shaped (2-D integer data: the LM workloads). Honors the
    MXNET_SCENARIOS / MXNET_SCENARIO_FILTER selection knobs."""
    import numpy as np

    from mxnet_tpu.scenarios import registry
    from mxnet_tpu.scenarios.runner import _run_fit, _seed_all

    out = {}
    for sc in (registry.get(n) for n in registry.selected_names()):
        kw = dict(sc.fit_kwargs() if callable(sc.fit_kwargs)
                  else sc.fit_kwargs)
        epochs = int(kw.get("num_epoch", 1))
        # count one epoch's rows on a throwaway data instance (the
        # iterators are stateful; the timed fit gets its own)
        _seed_all(sc.seed)
        mod = sc.make_module()
        data = sc.make_data(mod)
        rows, tok_len = 0, None
        for batch in data:
            d0 = batch.data[0]
            arr = np.asarray(d0.asnumpy() if hasattr(d0, "asnumpy")
                             else d0)
            rows += arr.shape[0]
            integral = np.issubdtype(arr.dtype, np.integer) \
                or bool(np.all(arr == np.round(arr)))
            if arr.ndim == 2 and arr.shape[1] > 1 and integral:
                tok_len = arr.shape[1]
        _run_fit(sc)                      # warmup: trace + compile
        t0 = time.perf_counter()
        _run_fit(sc)                      # steady state
        dt = max(time.perf_counter() - t0, 1e-9)
        rps = rows * epochs / dt
        out["scenario_%s_rows_per_sec" % sc.name] = round(rps, 1)
        if tok_len:
            out["scenario_%s_tokens_per_sec" % sc.name] = round(
                rps * tok_len, 1)
    return out


def _bench_gateway(mx):
    """Network serving plane load (docs/api/gateway.md): the warmed
    Predictor and DecodeEngine from the serving benches, fronted by a
    loopback GatewayServer and driven through GatewayClient.

    gateway_overhead_pct is the per-request HTTP tax over the
    in-process Predictor on identical rows (JSON encode/decode +
    socket + routing + admission — the price of the wire, not the
    model). gateway_ttft_ms percentiles are CLIENT-observed: wall
    from generate() call to the first streamed token crossing the
    socket, which is the number an SLO on the front door actually
    binds (the engine-internal TTFT ring can't see the flush path)."""
    import numpy as np

    from mxnet_tpu.gateway import GatewayClient, GatewayServer
    from mxnet_tpu.serving import Predictor
    from mxnet_tpu.serving.decode import DecodeEngine, LSTMCharLM

    n_pred = int(os.environ.get("BENCH_GATEWAY_PREDICTS", "32"))
    n_gen = int(os.environ.get("BENCH_GATEWAY_GENERATES", "8"))
    max_new = int(os.environ.get("BENCH_GATEWAY_MAX_NEW", "32"))
    rows_per = 8

    def _mlp():
        net = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
        net = mx.sym.Activation(net, act_type="relu", name="relu1")
        net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    rng = np.random.RandomState(11)
    X = rng.rand(64, 16).astype(np.float32)
    y = rng.randint(0, 10, 64).astype(np.float32)
    mx.random.seed(11)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu()])
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=8), num_epoch=1,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    pred = Predictor(mod, max_batch_size=rows_per)
    pred.warmup()

    model = LSTMCharLM(vocab_size=64, num_hidden=64, num_embed=32)
    params = model.init_params(seed=11)
    prompts = [list(map(int, rng.randint(0, 64, size=int(
        rng.randint(2, 17))))) for _ in range(n_gen)]
    eng = DecodeEngine(model, params, slots=4, max_prefill_len=16,
                       start=False)
    eng.warmup()
    eng.start()

    out = {}
    try:
        with GatewayServer(predict_backend=pred,
                           decode_backend=eng) as gw:
            cli = GatewayClient("127.0.0.1", gw.port, timeout=120)
            batch = X[:rows_per]
            cli.predict(batch)                  # warm the socket path
            pred.predict(batch)

            t0 = time.perf_counter()
            for _ in range(n_pred):
                pred.predict(batch)
            inproc_s = (time.perf_counter() - t0) / n_pred

            t0 = time.perf_counter()
            for _ in range(n_pred):
                cli.predict(batch)
            http_s = (time.perf_counter() - t0) / n_pred
            out["gateway_predict_rows_per_sec"] = round(
                rows_per / http_s, 1)
            out["gateway_overhead_pct"] = round(
                (http_s - inproc_s) / inproc_s * 100.0, 1) \
                if inproc_s > 0 else None

            ttfts, tokens, t0 = [], 0, time.perf_counter()
            for i, p in enumerate(prompts):
                ts = time.perf_counter()
                first = True
                for _tok in cli.generate(p, max_new_tokens=max_new,
                                         seed=i):
                    if first:
                        ttfts.append(
                            (time.perf_counter() - ts) * 1000.0)
                        first = False
                    tokens += 1
            wall = max(time.perf_counter() - t0, 1e-9)
            out["gateway_decode_tokens_per_sec"] = round(
                tokens / wall, 1)
            ttfts.sort()
            out["gateway_ttft_ms_p50"] = round(
                ttfts[len(ttfts) // 2], 3) if ttfts else None
            out["gateway_ttft_ms_p99"] = round(
                ttfts[min(len(ttfts) - 1,
                          int(len(ttfts) * 0.99))], 3) \
                if ttfts else None
            out["gateway_predicts"] = n_pred
            out["gateway_generates"] = n_gen
    finally:
        eng.shutdown(drain=True)
        eng.release()
        pred.release()
    return out


def _bench_pipeline_clean(mx, recs, step_batch, steps, img, failed):
    """Decode -> (device_augment) -> host->device feed rate with no
    readback until the single window-ending barrier (a device-side
    accumulator over every batch makes that one readback order against
    all of them). ``failed`` collects the sub-measurements that raised
    (main() exits non-zero on any)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.image import ImageRecordIter

    threads, procs, dev_aug = _io_iter_opts()
    out = {"io_threads": threads, "io_processes": procs,
           "io_device_augment": dev_aug,
           # wire-format attribution for the streaming measurements
           # below: where the augment stage runs and what dtype
           # actually crosses from host to device per staged batch
           "io_augment_placement": "device" if dev_aug else "host",
           "io_staged_dtype": "uint8" if dev_aug else "float32",
           "io_staged_bytes_per_step": step_batch * img * img * 3
           * (1 if dev_aug else 4),
           "io_host_cores": os.cpu_count() or 1,
           "io_images": recs["_n_images"]}
    if "_jpeg_skipped" in recs:
        out["pipeline_jpeg_skipped"] = recs["_jpeg_skipped"]
    fmt = "jpg" if "jpg" in recs else "npy"
    if fmt not in recs:
        return out
    n = max(4, min(steps, recs["_n_images"] // step_batch))

    # streaming decode feed (per-epoch decode on this host's cores);
    # first, so that no readback precedes it in this process
    it = ImageRecordIter(
        recs[fmt], data_shape=(3, img, img), batch_size=step_batch,
        shuffle=True, preprocess_threads=threads,
        preprocess_processes=procs, device_augment=dev_aug,
        label_name="softmax_label")
    try:
        def next_batch():
            try:
                return next(it)
            except StopIteration:
                it.reset()
                return next(it)

        acc_fn = jax.jit(lambda d, s: s + d.ravel()[0].astype(jnp.float32))
        b = next_batch()  # compile prep + acc
        acc = acc_fn(b.data[0]._read(), jnp.float32(0.0))
        t0 = time.time()
        for _ in range(n):
            acc = acc_fn(next_batch().data[0]._read(), acc)
        float(acc)  # ONE readback — orders against all batches, and the
        # clock stops only after it: the rate includes completion
        out["pipeline_clean_%s_img_per_sec" % fmt] = round(
            n * step_batch / (time.time() - t0), 2)
    finally:
        it.pool.shutdown(wait=False)

    # RAM-cached decoded-uint8 feed: decode once (outside the timed
    # window), then every batch is gather + uint8 transfer (+ one
    # on-chip augment program) — the feed rate a host sustains once
    # decode is no longer per-epoch work.  Runs in THIS process: the
    # chip belongs to it, and a child that wanted the chip could not
    # have it.  Each mode ends its timed region after its own
    # data-dependent readback, so the number includes device completion.
    for mode in ("host", "dev", "devcache"):
        _section(out, failed, "pipeline_cached_%s" % mode, _cached_feed,
                 recs[fmt], step_batch, img, n, mode)

    # decode-farm scaling curve (host-only, no device involvement):
    # images/sec of the bare decode stage at 1..K workers.  On a 1-core
    # host this is flat — the curve IS the evidence for what feeds
    # scale with on real hosts.
    cores = os.cpu_count() or 1
    curve = {}
    n_dec = min(recs["_n_images"], 2 * step_batch)
    for nw in sorted({1, 2, min(4, max(1, cores)), cores}):
        itd = ImageRecordIter(
            recs[fmt], data_shape=(3, img, img), batch_size=step_batch,
            shuffle=False, preprocess_threads=nw,
            label_name="softmax_label")
        try:
            # decode stage ONLY (no assembly, no device transfer —
            # that is measured separately above)
            list(itd.pool.map(itd._decode_one, range(min(8, n_dec))))
            t0 = time.time()
            list(itd.pool.map(itd._decode_one, range(n_dec)))
            curve["t%d" % nw] = round(n_dec / (time.time() - t0), 1)
        finally:
            itd.pool.shutdown(wait=False)
    out["io_decode_scaling"] = curve

    # host-only stage rates for the cached mode (no device): the uint8
    # gather and the full host assemble (normalize/mirror/HWC->CHW in
    # the native OpenMP loop).  Together with the decode curve these
    # bound every pipeline stage above the host->device copy.
    def _host_stages():
        import numpy as np
        itg = ImageRecordIter(
            recs[fmt], data_shape=(3, img, img), batch_size=step_batch,
            shuffle=True, cache_decoded=True, preprocess_threads=threads,
            label_name="softmax_label")
        next(itg)  # fill cache
        cache, _cl = itg._cache
        rngi = np.random.RandomState(0)
        from mxnet_tpu import runtime as rt
        mean = np.zeros(3, np.float32)
        std = np.ones(3, np.float32)
        nb = 8
        # fresh random indices per draw: a repeated index set goes
        # LLC-resident after the first gather and overstates the rate
        idxs = [rngi.randint(0, cache.shape[0], size=step_batch)
                for _ in range(nb)]
        t0 = time.time()
        for ix in idxs:
            cache[ix]
        gather = round(nb * step_batch / (time.time() - t0), 1)
        t0 = time.time()
        for ix in idxs:
            rt.assemble_batch(cache[ix], mean=mean, std=std, mirror=None)
        return {"io_gather_u8_img_per_sec": gather,
                "io_assemble_host_img_per_sec": round(
                    nb * step_batch / (time.time() - t0), 1)}

    _section(out, failed, "io_host_stage", _host_stages)
    return out


def _make_barrier(mod, fused):
    """Data-dependent completion barrier: jitted 4-byte reduction of a
    post-step parameter fetched to host (module docstring) — the fetch
    cannot return before the step that produced the parameter ran."""
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda a: jnp.sum(a.astype(jnp.float32)))
    if fused:
        eg = mod._exec_group
        name = sorted(eg._param_dict)[0]

        def barrier():
            return float(tiny(eg._param_dict[name]._read()))
    else:
        def barrier():
            return float(tiny(mod.get_outputs()[0]._read()))
    return barrier


def compiled_step(eg):
    """The jax ``Compiled`` for the group's train-step program: the
    one-program ``_last_step`` (fwd+bwd+optimizer) when present, else
    the ``fwd_bwd`` jit lowered on the live param/aux buffers.  Shared
    protocol for _xla_cost here and tools/hlo_byte_audit.py — keep the
    two consumers on this one helper so a change to the group's jit
    bookkeeping cannot silently split their numbers."""
    import numpy as np
    step = getattr(eg, "_last_step", None)
    if step is not None:
        fn, structs = step
        return fn.lower(*structs).compile()
    fn = eg._jits.get("fwd_bwd")
    if fn is None:
        return None
    params = {n: b._read() for n, b in eg._param_dict.items()}
    aux = {n: b._read() for n, b in eg._aux_dict.items()}
    rngk = np.zeros((2,), np.uint32)
    return fn.lower(params, aux, eg._last[0], rngk).compile()


def _xla_cost(mod, fused, sec_per_step, peak_bw, n_dev):
    """XLA's own cost analysis of the train-step programs: true flops and
    bytes-accessed, plus the HBM roofline utilization they imply.

    cost_analysis() reports the PER-DEVICE partitioned module; scale by
    n_dev to compare against the n_dev-scaled peaks. The optimizer-update
    program's traffic (read w/g/m + write w/m on f32 for sgd-momentum) is
    added analytically — it's a separate jit keyed deep in the optimizer.
    """
    out = {}
    if not fused:
        return out
    import numpy as np
    from mxnet_tpu.telemetry.introspect import analyze_compiled
    eg = mod._exec_group
    upd_fl = upd_by = 0.0
    if getattr(eg, "_last_step", None) is None:
        # separate optimizer-update program: account its traffic
        # analytically (read w/g/m + write w/m on f32 sgd-momentum)
        n_par = sum(int(np.prod(b.shape))
                    for b in eg._param_dict.values())
        upd_by = 5.0 * 4 * n_par
        upd_fl = 4.0 * n_par
    comp = compiled_step(eg)
    if comp is None:
        return out
    # ONE shared extraction rule (telemetry.introspect) — the live
    # roofline gauges and these offline fields read the same
    # numbers, so the two can never drift (ci.sh introspection gate)
    ca = analyze_compiled(comp)
    fl = ca["flops"] * n_dev
    by = ca["bytes_accessed"] * n_dev
    out["xla_flops_per_step_tf"] = round((fl + upd_fl) / 1e12, 3)
    out["xla_bytes_per_step_gb"] = round((by + upd_by) / 1e9, 3)
    if sec_per_step > 0:
        out["xla_achieved_tflops"] = round(
            (fl + upd_fl) / sec_per_step / 1e12, 2)
        if peak_bw:
            out["hbm_util"] = round(
                (by + upd_by) / sec_per_step / 1e9 / peak_bw, 4)
            out["bound_by"] = ("hbm" if out.get("hbm_util", 0) > 0.5
                               else "other")
    return out


def _bench_pipeline(mx, mod, recs, step_batch, steps, img, synthetic_img_s,
                    barrier, failed):
    """Input-pipeline-fed training throughput (SURVEY §7 hard part f):
    the SAME Module.fit-style step fed from ImageRecordIter, vs the
    synthetic number. Runs AFTER the synthetic phase, i.e. after
    device->host readbacks have happened in this process
    (_bench_pipeline_clean takes the feed rate before any).

    Two storage formats: raw .npy (decode is a buffer view — measures
    the pipeline machinery) and jpeg (adds real decode — the host-CPU
    ceiling on few-core hosts).
    """
    from mxnet_tpu.image import ImageRecordIter

    threads, procs, dev_aug = _io_iter_opts()
    n_images = recs["_n_images"]
    group_k = int(os.environ.get("BENCH_GROUP", "4"))
    out = {}
    # NOTE: no PrefetchingIter wrapper here — on few-core hosts the
    # extra producer thread contends with the decode pool and the
    # transfer-serialization thread for the GIL and *lowers*
    # throughput; on many-core hosts wrap it back (tests cover it).
    for fmt, key in (("npy", "pipeline_img_per_sec"),
                     ("jpg", "pipeline_jpeg_img_per_sec")):
        if fmt not in recs:
            continue
        it = ImageRecordIter(
            recs[fmt], data_shape=(3, img, img), batch_size=step_batch,
            shuffle=True, preprocess_threads=threads,
            preprocess_processes=procs, device_augment=dev_aug,
            label_name="softmax_label")

        def next_batch():
            try:
                return next(it)
            except StopIteration:
                it.reset()
                return next(it)

        # iterator-only throughput (decode+assemble ceiling of the host)
        for _ in range(2):
            next_batch()
        t0 = time.time()
        io_batches = max(4, min(steps, n_images // step_batch))
        for _ in range(io_batches):
            next_batch()
        out["iter_only_%s_img_per_sec" % fmt] = round(
            io_batches * step_batch / (time.time() - t0), 2)

        for _ in range(2):  # warmup (staging path)
            b = next_batch()
            mod.forward_backward(b)
            mod.update()
        barrier()
        # ONE barrier for the whole window: a per-step barrier would
        # be a device->host readback per step, serializing host and
        # device
        t0 = time.time()
        for _ in range(steps):
            b = next_batch()
            mod.forward_backward(b)
            mod.update()
        barrier()
        out[key] = round(steps * step_batch / (time.time() - t0), 2)

        if fmt == "npy" and group_k > 1 and \
                os.environ.get("BENCH_GROUPED", "1") != "0" and \
                getattr(mod._exec_group, "fused", False):
            # grouped fed window: K iterator batches -> ONE stacked
            # host block -> ONE device_put -> ONE scanned K-step
            # program.  Each group pays the fixed per-transfer and
            # per-launch cost once instead of K times — the
            # amortization the iterations-per-loop path exists for.
            def _grouped_window():
                n_groups = max(2, steps // group_k)
                run_group, gstate = _grouped_pipeline_step(
                    mod, group_k, next_batch)
                run_group()  # compile/warm the grouped program
                barrier()
                t0 = time.time()
                for _ in range(n_groups):
                    run_group()
                barrier()
                rate = round(
                    n_groups * group_k * step_batch / (time.time() - t0),
                    2)
                if gstate["fallbacks"]:
                    # a declined group trained per batch — the window
                    # no longer measures the grouped program
                    raise RuntimeError(
                        "%d/%d groups fell back to per-batch steps"
                        % (gstate["fallbacks"], n_groups + 1))
                return {"pipeline_grouped_img_per_sec": rate,
                        "pipeline_grouped_batch_group": group_k}

            _section(out, failed, "pipeline_grouped", _grouped_window)
        it.pool.shutdown(wait=False)

    if "pipeline_img_per_sec" in out:
        out["pipeline_vs_synthetic"] = round(
            out["pipeline_img_per_sec"] / synthetic_img_s, 3)
        out["pipeline_vs_iter_only"] = round(
            out["pipeline_img_per_sec"]
            / out["iter_only_npy_img_per_sec"], 3)
    return out


def _grouped_pipeline_step(mod, group_k, next_batch):
    """One fed grouped step: pull K batches, train them as one staged
    block through Module._grouped_stage/_grouped_update (falling back
    per batch if the grouped program declines, so the window still
    measures training).
    Returns (run_group, state); ``state["fallbacks"]`` counts declined
    groups — a nonzero count means the recorded rate did NOT exercise
    the grouped program and must be flagged, not reported as grouped."""
    state = {"fallbacks": 0}

    def run_group():
        group = [next_batch() for _ in range(group_k)]
        staged = mod._grouped_stage(group)
        if staged is None or not mod._grouped_update(staged):
            state["fallbacks"] += 1
            for b in group:
                mod.forward_backward(b)
                mod.update()

    return run_group, state


def _pipeline_verdict(extra):
    """Name the binding constraint from the merged pipeline metrics."""
    fed = extra.get("pipeline_jpeg_img_per_sec",
                    extra.get("pipeline_img_per_sec"))
    if fed is None:
        return {}
    clean = extra.get("pipeline_clean_jpg_img_per_sec",
                      extra.get("pipeline_clean_npy_img_per_sec", 0))
    if extra.get("pipeline_vs_synthetic", 0) >= 0.9:
        return {"pipeline_bound_by": "balanced"}
    if clean > 2 * fed:
        # the host pipeline alone feeds at twice the fed-training rate:
        # decode is not what holds the fed step back
        return {"pipeline_bound_by": "staging_or_step"}
    return {"pipeline_bound_by": "host_cpu_decode"}


if __name__ == "__main__":
    main()
