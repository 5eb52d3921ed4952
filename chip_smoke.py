#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three hot paths once, in THIS process (it holds the chip; it
starts no child), through the entry points a user would call, at the full
width of the one large model the repo supports:

* **train** — ResNet-50, 1000 classes, 224x224, 128 images per chip,
  bf16 compute over f32 master params, SGD with momentum, through
  ``Module(context=[mx.tpu(i) ...]).fit`` fed by ``ImageRecordIter`` over
  a ``.rec`` written here from a seed (the calls
  ``example/image-classification/train_imagenet.py`` makes), then
  ``Module.score``, an async ``CheckpointManager`` save with buffer
  donation on, and ``fit(resume_from=)`` into a fresh module;
* **serve** — ``Predictor(mod, max_batch_size=32)`` behind a
  ``DynamicBatcher``, mixed request sizes, rows against
  ``Module.predict``; a second Predictor warms from the first one's
  executable cache;
* **decode** — ``DecodeEngine`` over the scenario catalog's transformer
  LM.  That model is TOY-SIZED (2 blocks, 32 wide): the leg proves the
  prefill ladder and the step program compile and repeat on a TPU, not
  that decode is fast.

Uses every chip ``jax.devices()`` shows (dp1 on one chip, dp4 with global
batch 512 on a four-chip host).  Times are SMOKE TIMINGS — set-up
(compile) and run seconds of a handful of steps — never speeds.

Exit code 0 only on a TPU with every leg green; the last stdout line is
then ``{"ok": true, "device": {...}}``.  Without an accelerator it exits
2 and prints no result.  With ``JAX_PLATFORMS=cpu`` set explicitly it
REHEARSES the same code at a tiny shape, says that it checked no device,
prints no result and exits 3 (1 if a leg failed).
"""
import json
import logging
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

EXIT_LEG_FAILED, EXIT_NO_CHIP, EXIT_REHEARSAL = 1, 2, 3

# full width on the chip; depth of the run (steps, requests, tokens) is
# what is cut.  10 steps an epoch: BatchNorm's moving statistics
# (momentum 0.9) need a few dozen updates before the eval-mode forward
# the serve leg compares is well conditioned.
REAL = dict(per_chip=128, img=224, classes=1000, steps=10, serve_max=32,
            requests=(1, 3, 8, 17, 32, 5), new_tokens=24)
# rehearsal: same code, shapes a CPU compiles in minutes
TINY = dict(per_chip=4, img=64, classes=1000, steps=2, serve_max=4,
            requests=(1, 3, 4, 2), new_tokens=8)

# bf16 keeps 8 mantissa bits (ulp 2**-8).  Predictor buckets and
# Module.predict run the same bf16 math at different batch sizes, so XLA
# may tile and accumulate differently and flip a rounding here and
# there.  Conv, ReLU and eval-mode BatchNorm are homogeneous, so such a
# flip stays a few ulps RELATIVE to the activations all the way to the
# logits, whatever their scale — and after thirty steps the eval-mode
# logits of this net are large (BatchNorm's moving statistics have not
# converged).  So rows are compared as log-probabilities, against a
# tolerance of SERVE_ULPS bf16 ulps of the row's own logit range (never
# less than a range of 1).  A narrower format than stated (fp8: ulp
# 2**-3, 32x coarser) would not fit.
SERVE_ULPS = 16


def _serve_error(out, want):
    """Worst |delta log p| of ``out`` against ``want``, per row, in
    units of the tolerance (<= 1 passes)."""
    lo, lw = (np.log(np.maximum(p, 1e-30)) for p in (out, want))
    logit_range = np.maximum(lw.max(axis=1) - lw.min(axis=1), 1.0)
    return float((np.abs(lo - lw).max(axis=1)
                  / (SERVE_ULPS * 2.0 ** -8 * logit_range)).max())


def check(cond, what_hid_the_device):
    """Every assertion names the thing that would have hidden the
    device (or the fault) had the smoke not looked."""
    if not cond:
        raise AssertionError(what_hid_the_device)


def _log(leg, msg):
    print("[%s] %s" % (leg, msg), flush=True)


def _write_rec(path, n_records, img, classes, seed):
    """A labeled-JPEG .rec from a seed: a small set of distinct images
    (colour blob + noise), each its own class, repeated to
    ``n_records``."""
    import io as pyio

    from PIL import Image

    from mxnet_tpu import recordio
    rng = np.random.RandomState(seed)
    payloads = []
    for i in range(min(n_records, 64)):
        base = np.zeros((img, img, 3), np.uint8)
        base[..., i % 3] = 60 + 37 * ((i // 3) % 5)
        noise = rng.randint(0, 60, (img, img, 3)).astype(np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(base + noise).save(buf, format="JPEG")
        payloads.append(buf.getvalue())
    rec = recordio.MXRecordIO(path, "w")
    for i in range(n_records):
        rec.write(recordio.pack(
            recordio.IRHeader(0, float(i % len(payloads) % classes), i, 0),
            payloads[i % len(payloads)]))
    rec.close()


class _EpochCost(logging.Handler):
    """fit's own epoch clock: it stops after the epoch's metric drain,
    a readback that depends on every step of the epoch."""

    def __init__(self):
        super().__init__()
        self.cost = {}

    def emit(self, record):
        if record.msg == "Epoch[%d] Time cost=%.3f":
            self.cost[record.args[0]] = record.args[1]


def _on_devices(arr, devices, what):
    """``arr`` (a jax array) lives on exactly ``devices``."""
    got = arr.sharding.device_set
    check(got == set(devices),
          "%s lives on %s, not on the %d device(s) JAX reported — a "
          "context resolved to another backend (Context.jax_device maps "
          "tpu(i) onto jax.devices()[i] whatever the platform)"
          % (what, sorted(str(d) for d in got), len(devices)))


# ---------------------------------------------------------------- train
def leg_train(env):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.module.mesh_executor_group import MeshExecutorGroup

    sz, devices, work = env["sizes"], env["devices"], env["work"]
    n = len(devices)
    batch = sz["per_chip"] * n
    img = sz["img"]
    shape = (3, img, img)

    t0 = time.time()
    rec_path = os.path.join(work, "train.rec")
    _write_rec(rec_path, sz["steps"] * batch, img, sz["classes"], seed=0)
    _log("train", "wrote %d-record .rec in %.1fs (smoke timing)"
         % (sz["steps"] * batch, time.time() - t0))

    def make_iter():
        # the iterator train_imagenet.py builds (shuffle off: the smoke
        # wants the same stream on every run)
        return mx.io.ImageRecordIter(
            path_imgrec=rec_path, data_shape=shape, batch_size=batch,
            shuffle=False, rand_mirror=True,
            mean_r=123.68, mean_g=116.28, mean_b=103.53,
            preprocess_threads=4, label_name="softmax_label")

    def make_module():
        return mx.mod.Module(
            models.get_symbol("resnet-50", num_classes=sz["classes"],
                              image_shape="3,%d,%d" % (img, img)),
            context=[mx.tpu(i) for i in range(n)],
            compute_dtype="bfloat16")

    fit_kwargs = dict(
        eval_metric="acc", optimizer="sgd", kvstore="local",
        # small and flat: thirty steps without warm-up must stay near
        # the initialisation, where BatchNorm's moving statistics track
        # the batch statistics and the eval-mode forward the serve leg
        # compares keeps unsaturated probabilities
        optimizer_params={"learning_rate": 0.01,
                          "momentum": 0.9, "wd": 1e-4,
                          "rescale_grad": 1.0 / batch},
        initializer=mx.initializer.Xavier(
            rnd_type="gaussian", factor_type="in", magnitude=2))

    mx.random.seed(0)
    mx.telemetry.enable()
    watch = mx.telemetry.compile_watch()
    manager = mx.checkpoint.CheckpointManager(os.path.join(work, "ckpt"))
    clock = _EpochCost()
    logging.getLogger().addHandler(clock)
    it = make_iter()
    mod = make_module()
    mod.fit(it, num_epoch=2,
            epoch_end_callback=mx.callback.module_checkpoint(
                mod, save_optimizer_states=True, manager=manager),
            **fit_kwargs)
    grp = mod._exec_group
    check(isinstance(grp, MeshExecutorGroup),
          "the bind took the classic per-executor group (%s), not the "
          "fused MeshExecutorGroup: Module._fused_eligible refused it, "
          "and the classic path neither donates nor shards"
          % type(grp).__name__)
    check(grp._platform == env["platform"],
          "MeshExecutorGroup compiled for %r, JAX's default backend is "
          "%r" % (grp._platform, env["platform"]))
    donating = grp._platform != "cpu"
    check(donating or env["rehearsal"],
          "buffer donation is off: the group sees platform 'cpu'")

    # --- where things live ------------------------------------------
    name = sorted(grp._param_dict)[0]
    param = grp._param_dict[name]._read()
    staged = grp._last[0]["data"]
    _on_devices(param, devices, "parameter %r" % name)
    _on_devices(staged, devices, "the staged batch")
    check(len(param.addressable_shards) == n and all(
        s.data.shape == param.shape for s in param.addressable_shards),
        "parameter %r is not replicated whole on each of %d devices"
        % (name, n))
    rows = sorted(s.data.shape[0] for s in staged.addressable_shards)
    check(rows == [batch // n] * n and
          len({s.device for s in staged.addressable_shards}) == n,
          "the batch is not split %d ways over distinct devices "
          "(per-device rows %r): everything sits on one device"
          % (n, rows))
    for d in devices:
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats and "bytes_in_use" in stats:
            check(stats["bytes_in_use"] > 0,
                  "%s holds no bytes: its share of the mesh is empty"
                  % d)
    check(watch.post_warmup_count == 0,
          "compile.post_warmup_retraces = %d after fit's first epoch: "
          "%r" % (watch.post_warmup_count, watch.events()[-3:]))

    # --- a batch made under the default context ----------------------
    # ImageRecordIter's nd.array puts onto jax's CPU backend; staging
    # must take it up from host memory (no client-to-client copy), and
    # say so in the fit's report
    rows = np.random.RandomState(1).rand(batch, *shape).astype(np.float32)
    landed = grp._stage(mx.io.DataBatch(data=[mx.nd.array(rows)],
                                        label=None))["data"]
    _on_devices(landed, devices, "a staged mx.nd.array batch")
    check(np.asarray(landed).tobytes() == rows.tobytes(),
          "a batch made with mx.nd.array under the default context did "
          "not land on the mesh bit for bit")
    counters = mx.telemetry.last_fit()["counters"]
    routed = counters.get("exec.stage_host_routed_bytes", 0)
    want = 0 if env["rehearsal"] else counters["input.h2d_bytes"]
    check(routed == want,
          "fit's report counts %d bytes staged from host memory, wanted "
          "%d (on the chip every byte the iterator handed over, on one "
          "backend none): staging copied the batches client to client"
          % (routed, want))

    # --- one scored batch: finite loss -----------------------------
    t_fit = time.time()
    scores = dict(mod.score(it, mx.metric.create(["acc", "ce"]),
                            num_batch=1))
    check(np.isfinite(scores["cross-entropy"]),
          "Module.score cross-entropy is %r" % scores["cross-entropy"])
    t_score = time.time() - t_fit
    it.reset()

    # --- checkpoints taken with donation on ------------------------
    manager.wait_until_finished()
    check(manager.all_steps() == [0, 1],
          "CheckpointManager committed steps %r, wanted [0, 1]"
          % manager.all_steps())
    live_args, live_aux = mod.get_params()
    ck0 = mx.checkpoint.split_params(manager.restore(0).params)[0]
    ck1_args, ck1_aux = mx.checkpoint.split_params(
        manager.restore(1).params)
    for k, v in live_args.items():
        check(np.array_equal(ck1_args[k], v.asnumpy()),
              "async checkpoint of %r differs from the live parameter: "
              "the snapshot raced a donated buffer" % k)
    for k, v in live_aux.items():
        check(np.array_equal(ck1_aux[k], v.asnumpy()),
              "async checkpoint of aux %r differs from the live value"
              % k)
    check(all(np.isfinite(v).all() for v in ck1_args.values()),
          "a trained parameter is not finite")
    moved = max(float(np.abs(ck1_args[k] - ck0[k]).max())
                for k in ck0)
    check(moved > 0, "no parameter moved between the epoch-0 and "
          "epoch-1 checkpoints: the update never reached the params")

    setup_s, run_s = clock.cost[0], clock.cost[1]
    _log("train", "fit: epoch 0 with set-up (compile) %.1fs, epoch 1 "
         "(%d steps, fed by ImageRecordIter) %.2fs; score 1 batch with "
         "set-up %.1fs (smoke timings, not speeds)"
         % (setup_s, sz["steps"], run_s, t_score))
    _log("train", "acc=%.4f ce=%.4f max|dparam|=%.3g donation=%s "
         "post_warmup_retraces=0"
         % (scores["accuracy"], scores["cross-entropy"], moved,
            "on" if donating else "off (cpu rehearsal)"))

    # --- resume into a fresh module: one more epoch ------------------
    del mod, grp, param, staged, live_args, live_aux
    seen = []
    t0 = time.time()
    mod2 = make_module()
    mod2.fit(it, num_epoch=3, resume_from=manager,
             batch_end_callback=lambda p: seen.append((p.epoch,
                                                       p.nbatch)),
             epoch_end_callback=mx.callback.module_checkpoint(
                 mod2, save_optimizer_states=True, manager=manager),
             **fit_kwargs)
    manager.wait_until_finished()
    check(seen and {e for e, _ in seen} == {2},
          "fit(resume_from=) trained epochs %r, wanted only epoch 2: "
          "the checkpoint was not picked up"
          % sorted({e for e, _ in seen}))
    check(manager.latest() == 2, "no checkpoint after the resumed epoch")
    args2 = mod2.get_params()[0]
    check(all(np.isfinite(v.asnumpy()).all() for v in args2.values()),
          "a parameter is not finite after the resumed epoch")
    check(mod2._optimizer.num_update == 3 * sz["steps"],
          "the optimizer's update clock reads %d after the resumed "
          "epoch, wanted %d: the checkpoint's optimizer state did not "
          "land" % (mod2._optimizer.num_update, 3 * sz["steps"]))
    check(watch.post_warmup_count == 0,
          "compile.post_warmup_retraces = %d after the resumed fit"
          % watch.post_warmup_count)
    _log("train", "resume: fresh module, restore + set-up + %d steps "
         "%.1fs, of which epoch 2 %.2fs (smoke timings); steps "
         "committed %r" % (len(seen), time.time() - t0, clock.cost[2],
                           manager.all_steps()))
    logging.getLogger().removeHandler(clock)
    it.pool.shutdown(wait=False)
    env["module"] = mod2
    return {"setup_s": round(setup_s, 1), "run_s": round(run_s, 2),
            "n_dev": n, "batch": batch, "donation": donating}


# ---------------------------------------------------------------- serve
def leg_serve(env):
    import mxnet_tpu as mx
    from mxnet_tpu.serving import DynamicBatcher, Predictor

    check("module" in env, "no trained module: the train leg failed")
    mod, sz = env["module"], env["sizes"]
    batch = dict(mod.data_shapes)["data"][0]
    rng = np.random.RandomState(3)
    X = rng.uniform(-120.0, 130.0,
                    (batch, 3, sz["img"], sz["img"])).astype(np.float32)

    t0 = time.time()
    ref = mod.predict(mx.io.NDArrayIter(X, None, batch_size=batch)) \
        .asnumpy()
    t_ref = time.time() - t0
    check(ref.shape == (batch, sz["classes"]) and np.isfinite(ref).all(),
          "Module.predict gave shape %r / non-finite rows" % (ref.shape,))

    # the executable store is a cold/warm fixture, so it starts empty:
    # a temporary directory is right here (the jax compile cache is not
    # placed like this)
    aot = os.path.join(env["work"], "aot_serve")
    pred = Predictor(mod, max_batch_size=sz["serve_max"])
    t0 = time.time()
    pred.warmup(cache_dir=aot)
    t_cold = time.time() - t0
    compiles0 = pred.stats()["compiles"]

    bitwise, worst, agree, rows = True, 0.0, 0, 0
    t0 = time.time()
    with DynamicBatcher(pred, max_wait_ms=2.0) as batcher:
        futures = []
        for i, n in enumerate(sz["requests"]):
            lo = (5 * i) % (batch - n + 1)
            futures.append((lo, n, batcher.submit(X[lo:lo + n])))
        for lo, n, fut in futures:
            out = fut.result(timeout=600)
            want = ref[lo:lo + n]
            check(out.shape == want.shape and np.isfinite(out).all(),
                  "request of %d rows came back %r / non-finite"
                  % (n, out.shape))
            bitwise &= bool(np.array_equal(out, want))
            worst = max(worst, _serve_error(out, want))
            agree += int((out.argmax(axis=1) == want.argmax(axis=1)).sum())
            rows += n
    t_run = time.time() - t0
    check(worst <= 1.0,
          "served rows leave Module.predict by %.2fx the bf16 tolerance "
          "(%d ulps of the row's logit range); top-1 agrees on %d/%d "
          "rows" % (worst, SERVE_ULPS, agree, rows))
    check(pred.stats()["compiles"] == compiles0,
          "serving compiled %d program(s) after warmup"
          % (pred.stats()["compiles"] - compiles0))

    t0 = time.time()
    warm = Predictor(mod, max_batch_size=sz["serve_max"])
    warm.warmup(cache_dir=aot)
    t_warm = time.time() - t0
    sources = {b: r["source"] for b, r in warm.warmup_report().items()}
    check(set(sources.values()) == {"deserialized"},
          "the second Predictor's warmup_report() is %r, wanted every "
          "bucket 'deserialized'" % sources)
    n0 = sz["requests"][0]
    again = warm.predict(X[:n0])
    check(np.array_equal(again, pred.predict(X[:n0])),
          "the warm replica's rows differ from the cold replica's")
    warm.release()
    pred.release()
    _log("serve", "buckets %r: cold warmup %.1fs, warm (deserialized) "
         "%.1fs, Module.predict reference %.1fs, %d requests %.2fs "
         "(smoke timings, not speeds)"
         % (pred.buckets, t_cold, t_warm, t_ref, len(sz["requests"]),
            t_run))
    _log("serve", "rows vs Module.predict: worst %.3f of the bf16 "
         "tolerance (%d ulps of the logit range), top-1 agrees on %d/%d "
         "rows; bitwise contract held: %s (reported for ROADMAP D9, not "
         "gated)" % (worst, SERVE_ULPS, agree, rows,
                     "yes" if bitwise else "no"))
    return {"setup_s": round(t_cold, 1), "run_s": round(t_run, 2),
            "warm_s": round(t_warm, 1), "bitwise": bitwise}


# --------------------------------------------------------------- decode
def leg_decode(env):
    from mxnet_tpu.scenarios.catalog import _TF
    from mxnet_tpu.serving.decode import DecodeEngine, TransformerLM

    sz = env["sizes"]
    model = TransformerLM(_TF["V"], num_embed=_TF["D"],
                          num_heads=_TF["H"], window=_TF["T"],
                          num_blocks=_TF["BLOCKS"])
    params = model.init_params(seed=5)
    rng = np.random.RandomState(7)
    # one prompt inside a bucket, one on a boundary, one past the top
    # bucket (the chunked prefill path)
    prompts = [[int(t) for t in rng.randint(0, _TF["V"], size=k)]
               for k in (3, 8, 21)]
    aot = os.path.join(env["work"], "aot_decode")   # cold/warm fixture

    def run():
        # temperature > 0: the counter-hash sampler is in the program
        eng = DecodeEngine(model, params, slots=4,
                           max_prefill_len=_TF["T"], temperature=0.8,
                           start=False)
        check(eng._device == env["devices"][0],
              "DecodeEngine placed its params on %s, not on %s"
              % (eng._device, env["devices"][0]))
        t0 = time.time()
        report = eng.warmup(cache_dir=aot)
        t_setup = time.time() - t0
        try:
            reqs = [eng.submit(p, max_new_tokens=sz["new_tokens"],
                               seed=i) for i, p in enumerate(prompts)]
            t0 = time.time()
            eng.start()
            streams = [r.result(timeout=600) for r in reqs]
            t_run = time.time() - t0
        finally:
            eng.shutdown(drain=True)
            eng.release()
        return streams, {k: v["source"] for k, v in report.items()}, \
            t_setup, t_run

    first, src1, setup1, run1 = run()
    second, src2, setup2, run2 = run()
    for s in first:
        check(len(s) == sz["new_tokens"] and
              all(0 <= t < _TF["V"] for t in s),
              "a stream is %r: wrong length or a token outside the "
              "vocabulary" % (s,))
    check(first == second,
          "two runs of the same three requests gave different streams")
    check(set(src2.values()) == {"deserialized"},
          "the second engine's warmup sources are %r, wanted every "
          "program 'deserialized' (one-device executable loaded in a "
          "process that sees %d devices)" % (src2, len(env["devices"])))
    _log("decode", "TOY-SIZED model (%s): %d programs; run 1 set-up "
         "%.1fs (%s) + %d tokens %.2fs; run 2 set-up %.1fs "
         "(deserialized) + %.2fs (smoke timings, not speeds)"
         % (model.signature(), len(src1), setup1,
            "/".join(sorted(set(src1.values()))),
            3 * sz["new_tokens"], run1, setup2, run2))
    return {"setup_s": round(setup1, 1), "run_s": round(run1, 2),
            "warm_s": round(setup2, 1)}


LEGS = (("train", leg_train), ("serve", leg_serve), ("decode", leg_decode))


def peaks_known(kind):
    """Whether the benchmark's peak table (``benchmark/peaks.py``, the
    only one in the repository) has a row for this ``device_kind``.
    ``benchmark/run.py`` refuses a chip it has no published peaks for;
    the smoke says so first, in the command a new chip sees first."""
    from benchmark.peaks import peaks_of
    try:
        peaks = peaks_of(kind)
    except KeyError as e:
        print("FAILED peaks: %s" % e.args[0], flush=True)
        return False
    print("chip_smoke: benchmark/peaks.py has %r: %.0f bf16 TFLOP/s, "
          "%.0f GB/s (%s)" % (kind, peaks["bf16_flops_per_s"] / 1e12,
                              peaks["hbm_bytes_per_s"] / 1e9,
                              peaks["source"]), flush=True)
    return True


def main():
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    import jax
    import jaxlib
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - the package may be absent on cpu
        libtpu = "absent"
    print("chip_smoke: platform=%s device_kind=%r count=%d jax=%s "
          "jaxlib=%s libtpu=%s python=%s"
          % (platform, kind, len(devices), jax.__version__,
             jaxlib.__version__, libtpu, sys.version.split()[0]),
          flush=True)
    if platform != "tpu" and not rehearsal:
        print("chip_smoke: JAX found no TPU (platform %r) and "
              "JAX_PLATFORMS=cpu was not asked for — refusing to run: "
              "a smoke that passes on a silent CPU proves nothing"
              % platform, file=sys.stderr)
        return EXIT_NO_CHIP
    if rehearsal:
        print("chip_smoke: REHEARSAL — JAX_PLATFORMS=cpu was set "
              "explicitly; tiny shapes, checks NO device, prints no "
              "result", flush=True)

    logging.basicConfig(level=logging.INFO)
    from mxnet_tpu.serving import enable_persistent_compile_cache
    print("chip_smoke: jax compile cache at %s"
          % enable_persistent_compile_cache(), flush=True)

    failed = []
    if not rehearsal and not peaks_known(kind):
        failed.append("peaks")

    work = tempfile.mkdtemp(prefix="chip_smoke_")   # data, not caches
    env = {"sizes": TINY if rehearsal else REAL, "devices": devices,
           "platform": platform, "rehearsal": rehearsal, "work": work}
    results = {}
    try:
        for name, leg in LEGS:
            t0 = time.time()
            try:
                results[name] = leg(env)
                _log(name, "OK in %.1fs" % (time.time() - t0))
            except Exception:  # noqa: BLE001 - report, run the next leg
                traceback.print_exc()
                print("FAILED %s after %.1fs"
                      % (name, time.time() - t0), flush=True)
                failed.append(name)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    print("chip_smoke: legs %s" % json.dumps(results, sort_keys=True),
          flush=True)
    if failed:
        print("chip_smoke: FAILED %s" % ", ".join(failed), flush=True)
        return EXIT_LEG_FAILED
    if rehearsal:
        print("chip_smoke: rehearsal passed on the CPU; no device was "
              "checked", flush=True)
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
