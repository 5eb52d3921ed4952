"""One run of one cell: set-up, the measured window, the check.

The window is one call of `Module.fit` on a module that set-up has
already driven through its first steps; everything the run reports
comes from the host clock around that call, from the profiler's trace
of it, or from counts made here.  Nothing of the program is read but
the entry points a user calls, the optimizer's state and the outputs
of a step.
"""
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import check, peaks, trace_reduce, traffic, weights

EXIT_NO_CHIP, EXIT_REHEARSAL = 2, 3
CHECK_STEPS = 3          # the reference follows the first three steps
TRACE_SECONDS = 10       # a traced window is cut to about this long
STEP_MODULE = "train_step"   # the fused step's program, as the trace names it


def log(msg):
    print("[bench] " + msg, file=sys.stderr, flush=True)


def load_cell(root, workload):
    """(cell, configuration, traffic mix, metric entries, limits)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (has: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load(os.path.join(root, "benchmark", "traffic",
                                    cell["traffic"] + ".json"))
    limits_path = os.path.join(root, "benchmark", "limits",
                               workload + ".json")
    with open(limits_path) as f:
        limits = json.load(f)["limits"]
    return cell, cfg, mix, manifest, limits


def tiny(cfg, mix):
    """The rehearsal's sizes: each file names its own."""
    cfg, mix = dict(cfg), dict(mix)
    cfg.update(cfg.get("rehearsal", {}))
    mix.update(mix.get("rehearsal", {}))
    return cfg, mix


def family_of(cfg):
    """(plain reference, counts) of the configuration's family: the
    modules `benchmark/reference/<family>.py` and
    `benchmark/counts/<family>.py`, found by the name in its file."""
    return (importlib.import_module("benchmark.reference." + cfg["family"]),
            importlib.import_module("benchmark.counts." + cfg["family"]))


def build_symbol(cfg):
    """The program's symbol through an entry a user calls: `symbol` is
    a name for `models.get_symbol`; `symbol_call` names any other
    builder of the program by its dotted path, with its arguments."""
    if "symbol_call" in cfg:
        module, name = cfg["symbol_call"]["function"].rsplit(".", 1)
        return getattr(importlib.import_module(module), name)(
            **cfg["symbol_call"]["arguments"])
    from mxnet_tpu import models
    return models.get_symbol(
        cfg["symbol"], num_classes=cfg["classes"],
        image_shape=",".join(str(d) for d in cfg["image"]))


def set_compile_cache(root):
    """JAX's persistent cache: where the environment says, else at a
    fixed path inside the checkout.  Every program is kept, however
    quickly it compiled, so a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCount:
    """Programs lowered since `start()`: jax reports each lowering,
    whether or not the persistent cache then spares the compile."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.n += 1


class FirstSteps:
    """`fit`'s batch-end callback during warm-up: keeps what the check
    compares of the first steps, as the program left it."""

    def __init__(self, mod, lr, decays):
        self.mod, self.lr, self.decays = mod, lr, decays
        self.loss, self.momentum, self.params, self.stats = [], None, None, None

    def __call__(self, param):
        step = param.nbatch
        if step >= CHECK_STEPS:
            return
        probs = np.asarray(self.mod.get_outputs()[0].asnumpy(), np.float64)
        labels = param.locals["data_batch"].label[0].asnumpy().astype(int)
        self.loss.append(float(-np.mean(np.log(
            np.maximum(probs[np.arange(len(labels)), labels], 1e-300)))))
        if step == 0:
            opt, upd = self.mod._optimizer, self.mod._updater
            self.momentum = {opt.idx2name[k]: s.asnumpy()
                             for k, s in upd.states.items() if s is not None}
        if step == CHECK_STEPS - 1:
            args, aux = self.mod.get_params()
            self.params = {k: v.asnumpy() for k, v in args.items()}
            self.stats = {k: v.asnumpy() for k, v in aux.items()}

    def readings(self, args0, aux0, opt):
        """What `check.compare` takes, from the kept state.  The first
        gradient as the optimizer got it is worked out from the
        momentum after one step: m1 = -lr * (g + wd * w0)."""
        def norm(a):
            return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))
        grad = {}
        for k, m in self.momentum.items():
            wd = opt["wd"] if self.decays(k) else 0.0
            grad[k] = norm(-m.astype(np.float64) / self.lr - wd * args0[k])
        return {
            "loss": self.loss,
            "grad_norm": grad,
            "param_change": {k: norm(self.params[k].astype(np.float64)
                                     - args0[k]) for k in args0},
            "stat_change": {k: norm(self.stats[k].astype(np.float64)
                                    - aux0[k]) for k in aux0},
        }


def load_reader(root, name):
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_record(devices, chips):
    """The devices as JAX reports them, and the peak on the fullest.

    On the TPU the allocator counts two disjoint things: buffers
    (`peak_bytes_in_use`: parameters, batches, outputs) and what is
    reserved for the compiled programs' temporaries
    (`peak_bytes_reserved`: the saved activations of a step live
    there).  Both occupy the chip's memory, so the peak is their sum."""
    parts = (0, 0)
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        parts = max(parts, (int(stats.get("peak_bytes_in_use", 0)),
                            int(stats.get("peak_bytes_reserved", 0))), key=sum)
    log("fullest device's peak: %.2f GB in buffers, %.2f GB reserved for "
        "programs" % (parts[0] / 1e9, parts[1] / 1e9))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": sum(parts)}


def reduce_trace(trace_dir, chips, steps):
    """The traced window on the busiest device, the mean busy time over
    the chips used, and the breakdown.  The step program has to have
    run `steps` times inside the window, under its name."""
    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    windows = [(s, e) for n, s, e in trace["spans"] if n == "bench.window"]
    if not windows or not trace["devices"]:
        raise RuntimeError(
            "the trace holds %d device plane(s) and %d bench.window "
            "span(s): nothing to reduce" % (len(trace["devices"]),
                                            len(windows)))
    window = windows[-1]
    red = trace_reduce.reduce_window(trace, window, step_module=STEP_MODULE,
                                     steps=steps)
    used = sorted(red["devices"])[:chips]
    busiest = max(used, key=lambda d: red["devices"][d]["busy_s"])
    dev = red["devices"][busiest]
    nexts = [s for s in trace["spans"] if s[0] == "bench.next"]
    by_host = trace_reduce.attribute_gaps(dev["idle"], nexts,
                                          "elsewhere in fit")
    ops = trace["devices"][busiest]["ops"]
    # all idle time by what the host was doing, then the longest gaps,
    # each under the side that holds most of it
    covered = trace_reduce.union((a, b) for _n, a, b in nexts)
    gap_rows = [["all gaps: inside the iterator's next()",
                 by_host.get("bench.next", 0.0)],
                ["all gaps: elsewhere in fit", by_host["elsewhere in fit"]]]
    for s, e in sorted(dev["idle"], key=lambda g: g[0] - g[1])[:8]:
        outside = trace_reduce.total(trace_reduce.subtract([(s, e)], covered))
        gap_rows.append(["iterator next()" if outside < (e - s) / 2
                         else "elsewhere in fit", (e - s) * 1e-9])
    return {
        "window_s": red["window_s"],
        "busy_s": sum(red["devices"][d]["busy_s"] for d in used) / len(used),
        "device": dev,
        "idle_in_next_s": by_host.get("bench.next", 0.0),
        "idle_elsewhere_s": by_host["elsewhere in fit"],
        "breakdown": {
            "device_ops": [[n, s] for n, s in trace_reduce.time_by_name(
                ops, *window)[:10]],
            "idle_gaps": gap_rows,
        },
    }


def run(root, workload, seed, seconds, trace, t_start, cfg_mix=None,
        require_chip=True):
    """Run one cell once; returns (exit code, result or None).

    `cfg_mix` replaces the files' configuration and mix, and
    `require_chip=False` skips the look for a chip: both are for the
    tests, which drive the rest of a run on the CPU."""
    cell, cfg, mix, manifest, limits = load_cell(root, workload)
    chips = cell["chips"]
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = False
    if require_chip:
        if platform != "tpu":
            if os.environ.get("JAX_PLATFORMS") != "cpu":
                log("JAX found platform %r, not a TPU: refusing to "
                    "measure (JAX_PLATFORMS=cpu rehearses at a tiny "
                    "shape)" % platform)
                return EXIT_NO_CHIP, None
            rehearsal = True
            cfg, mix = tiny(cfg, mix)
        if len(devices) < chips:
            log("the cell asks for %d chip(s), JAX shows %d"
                % (chips, len(devices)))
            return EXIT_NO_CHIP, None
    if cfg_mix is not None:
        cfg, mix = cfg_mix
    log("platform: %s, device_kind: %s, devices: %d of %d, host cores: %s"
        % (platform, devices[0].device_kind, chips, len(devices),
           os.cpu_count()))
    set_compile_cache(root)
    compiles = CompileCount()

    import mxnet_tpu as mx
    log("%.1f s: imports done, devices seen" % (time.perf_counter() - t_start))
    reference, counts = family_of(cfg)
    arch, opt = reference.arch_of(cfg), cfg["optimizer"]
    batch = cfg["per_chip_batch"] * chips
    workdir = tempfile.mkdtemp(prefix="bench_")
    try:
        # ------------------------------------------------------ set-up
        mx.random.seed(int(seed) % (2 ** 31))
        mod = mx.mod.Module(build_symbol(cfg),
                            context=[mx.tpu(i) for i in range(chips)],
                            compute_dtype=cfg["compute_dtype"])
        arg_shapes, aux_shapes = reference.param_shapes(arch)
        args, aux = weights.make(seed, arg_shapes, aux_shapes)
        args0 = {k: np.asarray(v, np.float64) for k, v in args.items()}
        aux0 = {k: np.asarray(v, np.float64) for k, v in aux.items()}
        log("%.1f s: weights made" % (time.perf_counter() - t_start))
        feed = traffic.make_feed(mix, cfg, seed, chips, workdir)
        log("%.1f s: feed made" % (time.perf_counter() - t_start))
        metric = mx.metric.Accuracy()   # ONE object: a new one is a
        # new tally token, and so a new compile of the whole step
        fit_kwargs = dict(
            eval_metric=metric, optimizer=opt["name"], kvstore="local",
            optimizer_params={"learning_rate": opt["learning_rate"],
                              "momentum": opt["momentum"], "wd": opt["wd"]})
        first = FirstSteps(mod, opt["learning_rate"], reference.decays)
        feed.cut, feed.keep_first = CHECK_STEPS + 1, CHECK_STEPS
        mod.fit(feed, num_epoch=1, batch_end_callback=first,
                arg_params={k: mx.nd.NDArray(v) for k, v in args.items()},
                aux_params={k: mx.nd.NDArray(v) for k, v in aux.items()},
                **fit_kwargs)
        log("%.1f s: first steps driven" % (time.perf_counter() - t_start))
        have = {k: tuple(v.shape) for k, v in mod.get_params()[0].items()}
        if have != {k: tuple(v) for k, v in arg_shapes.items()}:
            raise RuntimeError("the symbol's parameters are not those "
                               "of the configuration's reference")
        program = first.readings(args0, aux0, opt)
        kept, feed.keep_first = feed.kept, 0
        # the rate, for the number of epochs: a second short fit, which
        # also passes every program of an epoch's end once more
        feed.cut = mix["warm_steps"]
        t0 = time.perf_counter()
        mod.fit(feed, num_epoch=1, **fit_kwargs)
        step_s = (time.perf_counter() - t0) / mix["warm_steps"]
        feed.cut = None
        epoch_steps = mix.get("steps_per_epoch") or mix["records"] // batch
        target = min(seconds, TRACE_SECONDS) if trace else seconds
        epochs = max(1, round(target / (epoch_steps * step_s)))
        feed.clear_clocks()
        trace_dir = os.path.join(workdir, "trace")
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles_before = compiles.n
        setup_s = time.perf_counter() - t_start
        log("set-up %.1f s; warm-up step %.1f ms; window: %d epoch(s) of "
            "%d steps" % (setup_s, step_s * 1e3, epochs, epoch_steps))

        # ------------------------------------------------------ window
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            mod.fit(feed, num_epoch=epochs, **fit_kwargs)
        wall_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        steps = feed.steps
        window_compiles = compiles.n - compiles_before
        log("window: %d steps in %.3f s; epochs ended at %s s"
            % (steps, wall_s, " ".join("%.3f" % (t - t0)
                                       for t in feed.epoch_ends)))
        device = device_record(devices, chips)

        # ------------------------------------------------ after the window
        del mod, first, metric, fit_kwargs
        feed._source = None
        gc.collect()
        jax.clear_caches()
        # the CPU backend writes no device plane: a rehearsal reads none
        traced = reduce_trace(trace_dir, chips, steps) \
            if trace and platform == "tpu" else None

        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.array(devices[:chips]), ("dp",))
        rows = NamedSharding(mesh, PartitionSpec("dp"))
        whole = NamedSharding(mesh, PartitionSpec())
        ref_batches, input_gap = traffic.reference_batches(
            mix, cfg, seed, chips, kept, rows)
        t0 = time.perf_counter()
        # its own weights from the seed: the program's step has donated
        # the buffers it was handed
        args, aux = weights.make(seed, arg_shapes, aux_shapes, whole)
        ref = reference.follow(
            args, aux, ref_batches[:CHECK_STEPS], arch,
            {k: opt[k] for k in ("learning_rate", "momentum", "wd")},
            sharding=rows)
        log("reference: %d steps in %.1f s"
            % (CHECK_STEPS, time.perf_counter() - t0))
        numbers = check.compare(program, ref, reference.products(arch))
        if input_gap is not None:
            numbers["input_gap"] = (input_gap, "delivered rows")
        numbers["window_compiles"] = (window_compiles, "the window")
        correct, rows_out = check.judge(
            numbers, dict(limits, window_compiles=0))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    # ---------------------------------------------------------- result
    expected = epochs * epoch_steps
    metrics = {}
    if not trace:
        metrics["img_per_s"] = {"value": steps * batch / wall_s,
                                "unit": "img/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        if traced is not None:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
        seen = {
            "steps": steps, "wall_s": wall_s, "chips": chips,
            "per_chip_batch": cfg["per_chip_batch"], "arch": arch,
            "counts": counts,
            "compute_dtype": cfg["compute_dtype"],
            "peaks": peaks.peaks_of(devices[0].device_kind)
            if platform == "tpu" else None,
            "input_wait_s": feed.wait_s, "window_compiles": window_compiles,
            "peak_bytes": device["memory_peak_bytes"], "trace": traced,
        }
        for m in manifest["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            value = load_reader(root, m["name"])(seen)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct and steps == expected),
              "attempted": expected, "failed": expected - steps,
              "metrics": metrics, "device": device}
    if traced is not None:
        result["breakdown"] = traced["breakdown"]
    result["check"] = {k: {"value": r["value"], "limit": r["limit"]}
                       for k, r in rows_out.items()}
    for k, r in rows_out.items():
        log("check %-18s %-12s limit %-8s (%s)"
            % (k, "%.4g" % r["value"] if r["value"] is not None else "none",
               r["limit"], r["where"]))
    log("correct: %s" % result["correct"])
    if rehearsal:
        log("rehearsal on platform: cpu at a tiny shape: no device was "
            "measured and no result is printed")
        return EXIT_REHEARSAL, None
    return 0, result
