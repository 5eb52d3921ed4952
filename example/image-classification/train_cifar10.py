"""Train CIFAR-10 (reference example/image-classification/
train_cifar10.py) with ``--gpus`` swapped for ``--tpus``.

Uses a real CIFAR-10 python-pickle batch directory when ``--data-dir``
has one, else a synthetic CIFAR-shaped dataset (no network egress).
Like the reference, images are center-cropped to 28x28 — the zoo's
cifar depth tables key on height<=28 (symbols/resnet.py:124).
"""
import argparse
import logging
import os
import pickle
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import models


def load_cifar_dir(data_dir):
    """cifar-10-batches-py layout (data_batch_1..5 + test_batch)."""
    def _load(names):
        xs, ys = [], []
        for n in names:
            with open(os.path.join(data_dir, n), "rb") as f:
                d = pickle.load(f, encoding="bytes")
            xs.append(d[b"data"].reshape(-1, 3, 32, 32)[:, :, 2:30, 2:30])
            ys.append(np.array(d[b"labels"]))
        return (np.concatenate(xs).astype(np.float32) / 255.0,
                np.concatenate(ys).astype(np.float32))
    train = _load(["data_batch_%d" % i for i in range(1, 6)])
    test = _load(["test_batch"])
    return train, test


def synthetic_cifar(rng, n=4096):
    protos = rng.rand(10, 3, 7, 7).astype(np.float32)
    y = rng.randint(0, 10, n)
    up = np.kron(protos[y], np.ones((1, 1, 4, 4), np.float32))
    X = up + 0.25 * rng.rand(n, 3, 28, 28).astype(np.float32)
    return X, y.astype(np.float32)


def params_digest(mod):
    """sha256 over every final param/aux array (sorted by name): the
    CI bit-identity gates compare these digests, a stronger pin than
    comparing accuracies."""
    import hashlib
    h = hashlib.sha256()
    arg_params, aux_params = mod.get_params()
    for name in sorted(arg_params):
        h.update(name.encode())
        h.update(np.ascontiguousarray(arg_params[name].asnumpy())
                 .tobytes())
    for name in sorted(aux_params or {}):
        h.update(name.encode())
        h.update(np.ascontiguousarray(aux_params[name].asnumpy())
                 .tobytes())
    return h.hexdigest()


def serve_smoke(mod, val, Xte, batch_size):
    """The CI serving gate: an in-process Predictor + DynamicBatcher
    over the just-trained module. Concurrent client threads fire
    mixed-size requests; every client's rows must come back BITWISE
    equal to ``Module.predict`` on the same inputs, and after
    ``warmup()`` sustained traffic must trigger ZERO further XLA
    compiles (the steady-state serving contract)."""
    import threading

    from mxnet_tpu.serving import DynamicBatcher, Predictor

    ref = mod.predict(val).asnumpy()
    pred = Predictor(mod, max_batch_size=min(batch_size, 32))
    pred.warmup()
    frozen = pred.stats()["compiles"]
    srv = DynamicBatcher(pred, max_queue=256, max_wait_ms=2)
    errs = []

    def client(i):
        rng = np.random.RandomState(100 + i)
        for _ in range(8):
            n = int(rng.randint(1, 9))
            lo = int(rng.randint(0, len(ref) - n))
            try:
                out = srv.predict(Xte[lo:lo + n], timeout=300)
            except Exception as e:  # noqa: BLE001 — gate must report
                errs.append("client %d: %r" % (i, e))
                return
            if not np.array_equal(out, ref[lo:lo + n]):
                errs.append("client %d: served rows != Module.predict"
                            % i)
                return

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    srv.shutdown(drain=True)
    stats = pred.stats()
    assert not errs, errs[:3]
    assert stats["completed"] == 8 * 8, (
        "gate verified only %d of %d requests" % (stats["completed"],
                                                  8 * 8))
    assert stats["compiles"] == frozen, (
        "serving recompiled under traffic: %d compiles after warmup's %d"
        % (stats["compiles"], frozen))
    logging.info(
        "serving smoke: %d requests ok, buckets %s, fill %.2f, "
        "p50 %.1f ms, compiles frozen at %d",
        stats["completed"], pred.buckets, stats["batch_fill"],
        stats["latency_ms"]["p50"], frozen)


def main():
    parser = argparse.ArgumentParser(description="train cifar10")
    parser.add_argument("--network", default="resnet-20",
                        help="model zoo name (resnet-N, resnext-N, vgg, "
                             "alexnet, inception-bn, ...)")
    parser.add_argument("--data-dir", default="cifar10/")
    parser.add_argument("--tpus", "--gpus", dest="tpus", default=None)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--model-prefix", default=None)
    parser.add_argument("--min-accuracy", type=float, default=None,
                        help="exit nonzero if final validation accuracy "
                             "lands below this (the CI convergence gate, "
                             "reference Jenkinsfile test_score stage)")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="durable async checkpoints: commit one "
                             "atomic step entry per epoch into this "
                             "directory (mxnet_tpu.checkpoint"
                             ".CheckpointManager)")
    parser.add_argument("--resume", action="store_true",
                        help="resume params/optimizer/RNG from the "
                             "latest committed step in --checkpoint-dir "
                             "(no-op when the directory is empty)")
    parser.add_argument("--seed", type=int, default=None,
                        help="seed numpy + mxnet RNG (fixes the shuffle "
                             "order so a resumed run retraces the "
                             "uninterrupted one)")
    parser.add_argument("--exit-after-epoch", type=int, default=None,
                        help="hard-exit (code 66) once this many epochs "
                             "committed — the CI crash/resume gate's "
                             "simulated preemption")
    parser.add_argument("--acc-out", default=None,
                        help="write the final validation accuracy to "
                             "this file (CI resume gate comparison)")
    parser.add_argument("--batch-group", type=int, default=None,
                        help="train K batches per XLA launch through "
                             "the grouped (iterations-per-loop) train "
                             "step — one staged transfer and one "
                             "scanned program per K batches; numerics "
                             "match per-batch training exactly")
    parser.add_argument("--prefetch-device", type=int, default=None,
                        help="train through the async device-feed "
                             "pipeline (mxnet_tpu.data.DeviceLoader): "
                             "keep a ring of N batches already "
                             "resident on device so host assembly, "
                             "transfer, and the step overlap; trained "
                             "params are bit-identical to the plain "
                             "path (the CI device-feed gate)")
    parser.add_argument("--params-digest-out", default=None,
                        help="write a sha256 over the final params + "
                             "aux arrays to this file (CI bit-"
                             "identity gates)")
    parser.add_argument("--telemetry-jsonl", default=None,
                        help="enable mxnet_tpu.telemetry and stream one "
                             "JSON line per train step (plus per-epoch "
                             "metrics snapshots) into this file; "
                             "training stays bit-identical to the "
                             "telemetry-off path (the CI telemetry "
                             "gate)")
    parser.add_argument("--telemetry-port", type=int, default=None,
                        help="serve the telemetry registry as a "
                             "Prometheus /metrics endpoint on this "
                             "port for the run's lifetime (0 picks a "
                             "free port)")
    parser.add_argument("--program-report", default=None,
                        help="enable telemetry's program introspection "
                             "and write the compiled-program inventory "
                             "(XLA FLOPs/bytes per program, argument/"
                             "donation audit) as JSON after training; "
                             "asserts in-process that the step AND "
                             "optimizer programs report nonzero "
                             "flops/bytes (the CI introspection "
                             "gate)")
    parser.add_argument("--health-report", default=None,
                        help="enable telemetry's regression watchdog "
                             "(armed by fit at the warmup boundary, "
                             "self-calibrated from the first post-"
                             "warmup window) and write its "
                             "health_report() JSON here after "
                             "training; asserts in-process that the "
                             "watchdog armed, calibrated, and reports "
                             "HEALTHY — zero incidents on a clean run "
                             "(the CI health gate, mirroring "
                             "--program-report)")
    parser.add_argument("--device-augment", action="store_true",
                        help="feed the u8 device-side input path: the "
                             "iterator ships uint8 NHWC wire batches "
                             "(4x fewer bytes than f32 NCHW) and "
                             "random-crop/flip/normalize compile INTO "
                             "the train-step program (mxnet_tpu.data"
                             ".DeviceAugment); deterministic draws "
                             "keyed (seed, epoch, batch)")
    parser.add_argument("--augment-placement", default="device",
                        choices=["device", "host"],
                        help="where the augment stage runs: 'device' "
                             "(in-program, the u8 wire path) or "
                             "'host' (the numpy reference "
                             "DeviceAugment.apply_host on the same "
                             "draws — the CI gate pins both to bit-"
                             "identical trained params)")
    parser.add_argument("--cache-dataset", action="store_true",
                        help="HBM-resident dataset cache (mxnet_tpu"
                             ".data.CachedDataset): epoch 1 streams "
                             "and captures the decoded u8 epoch, "
                             "epochs >= 2 are served by device-side "
                             "gather — zero image bytes over the "
                             "transport, bit-identical params to "
                             "streaming (implies the u8 augment "
                             "pipeline)")
    parser.add_argument("--precision", default=None,
                        help="precision mode name (mxnet_tpu.precision "
                             "MODES: f32, bf16, bf16_opt, combined, ...) "
                             "— byte-count levers with per-mode "
                             "reproducibility contracts")
    parser.add_argument("--opt-state-dtype", default=None,
                        help="optimizer-state storage dtype (float32 or "
                             "bfloat16); composes into an ad-hoc "
                             "PrecisionPolicy with --remat when "
                             "--precision is not given")
    parser.add_argument("--remat", default=None,
                        help="remat policy for the train step (none, "
                             "full, dots_saveable, offload_bn_stats)")
    parser.add_argument("--fault-plan", default=None,
                        help="arm a seeded mxnet_tpu.faults.FaultPlan "
                             "for the run (grammar string, JSON list, "
                             "or @file — docs/api/faults.md); after "
                             "training the script asserts every "
                             "deterministic rule actually fired and "
                             "logs the incident transcript. Transient "
                             "faults heal through the shared retry "
                             "helper, so the trained params stay "
                             "bitwise identical to a fault-free run "
                             "(the ci.sh chaos-smoke gate compares "
                             "digests)")
    parser.add_argument("--guardian", action="store_true",
                        help="arm the training guardian "
                             "(mxnet_tpu.guardian): device-resident "
                             "numeric-health sentinels on the train "
                             "step, epoch-boundary polling, and "
                             "rollback-and-skip recovery for NaN / "
                             "loss-spike / SDC verdicts. Shares the "
                             "--checkpoint-dir manager when given "
                             "(recommended — rollback can then "
                             "truncate a poisoned trajectory), else "
                             "uses a run-local directory. With a "
                             "--fault-plan carrying numeric rules "
                             "(module.step / guardian.sdc sites) the "
                             "script asserts the guardian actually "
                             "rolled back")
    parser.add_argument("--serve-smoke", action="store_true",
                        help="after training, serve the model through "
                             "an in-process mxnet_tpu.serving stack "
                             "(Predictor + DynamicBatcher) under "
                             "concurrent client threads and assert "
                             "bitwise parity with Module.predict plus "
                             "zero post-warmup XLA compiles (the CI "
                             "serving gate)")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.serving.enable_persistent_compile_cache()   # before the first compile
    telemetry_on = (args.telemetry_jsonl or args.telemetry_port is not None
                    or args.program_report or args.health_report)
    if telemetry_on:
        server = mx.telemetry.enable(jsonl=args.telemetry_jsonl,
                                     port=args.telemetry_port)
        if server is not None:
            logging.info("telemetry: Prometheus endpoint at %s",
                         server.url)
    if args.seed is not None:
        np.random.seed(args.seed)
        mx.random.seed(args.seed)
    fault_plan = None
    if args.fault_plan:
        fault_plan = mx.faults.arm(args.fault_plan,
                                   seed=args.seed or 0)
        logging.info("fault plan armed (seed %d): %s", fault_plan.seed,
                     "; ".join(r.describe()
                               for r in fault_plan.rules))

    ctx = [mx.tpu(int(i)) for i in args.tpus.split(",")] if args.tpus \
        else [mx.cpu()]

    batch_dir = os.path.join(args.data_dir, "cifar-10-batches-py")
    if os.path.exists(batch_dir):
        (Xtr, ytr), (Xte, yte) = load_cifar_dir(batch_dir)
    else:
        logging.warning("CIFAR batches not found in %s; synthetic data",
                        args.data_dir)
        rng = np.random.RandomState(0)
        Xtr, ytr = synthetic_cifar(rng)
        Xte, yte = Xtr[:512], ytr[:512]

    net = models.get_symbol(args.network, num_classes=10,
                            image_shape=(3, 28, 28))
    precision = args.precision
    if precision is None and (args.opt_state_dtype or args.remat):
        precision = mx.precision.PrecisionPolicy(
            opt_state_dtype=args.opt_state_dtype, remat=args.remat)
    elif precision is not None and (args.opt_state_dtype or args.remat):
        parser.error("--precision is a complete mode; do not combine it "
                     "with --opt-state-dtype/--remat")
    mod = mx.mod.Module(net, context=ctx, precision=precision)
    if precision is not None:
        logging.info("precision mode: %s (%r)", mod.precision_mode,
                     mod._precision.describe())

    u8_pipeline = args.device_augment or args.cache_dataset
    if u8_pipeline:
        from mxnet_tpu.data import (CachedDataset, DeviceAugment,
                                    DeviceAugmentIter)

        def to_u8(x):
            # f32 NCHW in [0, ~1] -> uint8 NHWC wire layout
            return (np.clip(x, 0.0, 1.0) * 255.0).round() \
                .astype(np.uint8).transpose(0, 2, 3, 1)

        # pad-2 random crop + random mirror, normalize back to the f32
        # [0, 1] range the plain path trains on (scale=1/255); draws
        # are a pure function of (seed, epoch, batch index), so the
        # device and host placements see the SAME stream
        spec = DeviceAugment(shape=(3, 28, 28), rand_crop=True,
                             rand_mirror=True, pad=2, mean=0.0,
                             std=1.0, scale=1.0 / 255.0,
                             seed=args.seed or 0)
        train_src = mx.io.NDArrayIter(to_u8(Xtr), ytr,
                                      batch_size=args.batch_size,
                                      shuffle=True)
        if args.cache_dataset:
            train = CachedDataset(
                train_src, augment=spec, module=mod,
                augment_placement=args.augment_placement)
        else:
            train = DeviceAugmentIter(train_src, spec,
                                      placement=args.augment_placement)
        # eval variant: both placements score the identical
        # deterministic center-cropped stream
        val = DeviceAugmentIter(
            mx.io.NDArrayIter(to_u8(Xte), yte,
                              batch_size=args.batch_size),
            spec, placement=args.augment_placement, train=False)
    else:
        train = mx.io.NDArrayIter(Xtr, ytr, batch_size=args.batch_size,
                                  shuffle=True)
        val = mx.io.NDArrayIter(Xte, yte, batch_size=args.batch_size)

    callbacks = []
    if args.model_prefix:
        callbacks.append(mx.callback.do_checkpoint(args.model_prefix))
    manager = None
    if args.checkpoint_dir:
        manager = mx.checkpoint.CheckpointManager(args.checkpoint_dir,
                                                  keep=3)
        callbacks.append(mx.callback.module_checkpoint(
            mod, save_optimizer_states=True, manager=manager))
    if args.exit_after_epoch is not None:
        assert manager is not None, "--exit-after-epoch needs " \
            "--checkpoint-dir (it simulates preemption after the commit)"

        def _preempt(iter_no, sym=None, arg=None, aux=None):
            if iter_no + 1 >= args.exit_after_epoch:
                manager.wait_until_finished()
                logging.info("simulated preemption after epoch %d",
                             iter_no)
                os._exit(66)

        callbacks.append(_preempt)
    guard = None
    if args.guardian:
        import tempfile
        guard = mx.guardian.Guardian(
            manager if manager is not None
            else tempfile.mkdtemp(prefix="cifar_guardian_"))
        logging.info("guardian armed: window=%d threshold=%g "
                     "max_rollbacks=%d sdc_period=%d",
                     guard.spike_window, guard.spike_threshold,
                     guard.max_rollbacks, guard.sdc_probe_period)
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            kvstore=args.kv_store,
            initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                              "wd": 1e-4},
            batch_end_callback=mx.callback.Speedometer(args.batch_size,
                                                       20),
            epoch_end_callback=callbacks or None,
            resume_from=manager if args.resume else None,
            batch_group=args.batch_group,
            prefetch_to_device=args.prefetch_device,
            guardian=guard)
    if manager is not None:
        manager.wait_until_finished()
    if telemetry_on:
        # the steady-state contract: after fit's first epoch declared
        # the warmup boundary, the train loop must never retrace
        post = mx.telemetry.compile_watch().post_warmup_count
        assert post == 0, (
            "train loop retraced %d time(s) after the warmup boundary: %r"
            % (post, mx.telemetry.compile_watch().events()))
        tl = mx.telemetry.timeline()
        logging.info("telemetry: %d step records; slowest: %r",
                     len(tl), tl.slowest(1))
        mx.telemetry.flush_metrics("train_cifar10 end")
    if args.program_report:
        report = mx.telemetry.dump_programs(args.program_report)
        by_kind = {}
        for prog in report["programs"]:
            if prog.get("flops") and prog.get("bytes_accessed"):
                by_kind.setdefault(prog["kind"], []).append(prog["name"])
        assert any(k in by_kind for k in ("train_step",
                                          "train_step_grouped")), (
            "program report lacks an analyzed train-step program "
            "with nonzero flops/bytes: %r" % (by_kind,))
        assert "optimizer_update" in by_kind, (
            "program report lacks the optimizer-update account: %r"
            % (by_kind,))
        logging.info("program report: %d programs -> %s",
                     report["n_programs"], args.program_report)
    if args.health_report:
        # the judgment-layer contract: fit armed the watchdog at the
        # warmup boundary, the first post-warmup window calibrated the
        # baseline, and a clean run produced ZERO incidents
        rep = mx.telemetry.health_report()
        assert rep["armed"], "watchdog never armed (fit arms it at " \
            "the warmup boundary when telemetry is on)"
        if args.num_epochs > 1:
            assert rep["calibrated"], \
                "watchdog never calibrated a baseline: %r" % (rep,)
        assert rep["healthy"], (
            "clean training run produced health incidents: %r"
            % (rep["incidents"],))
        mx.telemetry.export.atomic_json_dump(args.health_report, rep)
        logging.info("health report: armed=%s healthy=%s polls=%d -> %s",
                     rep["armed"], rep["healthy"], rep["polls"],
                     args.health_report)
    if guard is not None:
        st = guard.stats()
        logging.info(
            "guardian: rollbacks=%d skipped=%r sdc_checks=%d "
            "sdc_mismatches=%d", st["rollbacks"], st["skipped"],
            st["sdc_checks"], st["sdc_mismatches"])
        numeric_rules = [r.describe() for r in
                         (fault_plan.rules if fault_plan else [])
                         if r.site in ("module.step", "guardian.sdc")]
        if numeric_rules:
            # the robustness contract: a planned numeric fault MUST
            # have been healed by rollback-and-skip, and training must
            # have reached the end anyway (which reaching this line
            # proves)
            assert st["rollbacks"] >= 1, (
                "numeric fault(s) %r were planned but the guardian "
                "never rolled back" % (numeric_rules,))
    if fault_plan is not None:
        # the chaos contract: a plan whose deterministic rules never
        # fired silently missed its targets — that is a gate failure,
        # not a pass; and every firing must be in the transcript
        unfired = fault_plan.unfired()
        assert not unfired, (
            "fault plan rules never fired (workload missed their "
            "trigger coordinates): %r" % (unfired,))
        incidents = fault_plan.incidents()
        logging.info("fault plan: %d incident(s) injected and "
                     "recovered: %s", len(incidents),
                     ", ".join("%s(%s)" % (i["site"], i["kind"])
                               for i in incidents))
        mx.faults.disarm()
    trained = mod._optimizer is not None and mod._optimizer.num_update > 0
    if args.batch_group and args.batch_group > 1 and trained:
        # the CI equivalence gate must FAIL, not trivially pass, if the
        # grouped path silently fell back to per-batch training (a
        # fallback would make both gate runs identical per-batch runs).
        # Gated on `trained`: a resume already at num_epochs runs zero
        # batches — nothing engaged because nothing trained.
        assert mod.grouped_train_engaged(), (
            "--batch-group %d requested but the grouped train program "
            "never engaged (fit fell back to per-batch training)"
            % args.batch_group)
    if u8_pipeline and trained:
        if args.augment_placement == "device":
            # structural contract: the augment stage really compiled
            # into the step program (u8 wire batches, not a silent
            # host fallback)
            assert getattr(mod._exec_group, "_device_augment", None), (
                "--device-augment requested but the bound program has "
                "no in-program augment stage")
            assert any(np.dtype(getattr(d, "dtype", np.float32))
                       == np.uint8 for d in train.provide_data), (
                "u8 pipeline requested but no uint8 wire input in %r"
                % (train.provide_data,))
        if args.cache_dataset and args.num_epochs > 1:
            info = train.cache_info()
            assert info["built_epoch"] is not None, (
                "--cache-dataset ran %d epochs but never built the "
                "cache: %r" % (args.num_epochs, info))
            logging.info("dataset cache: %s, %d rows, %.1f MB, built "
                         "after epoch %d", info["placement"],
                         info["rows"], info["bytes"] / (1 << 20),
                         info["built_epoch"])
    if args.params_digest_out:
        # digest BEFORE scoring: scoring must not (and does not)
        # change params, but the gate pins the trained state itself
        digest = params_digest(mod)
        with open(args.params_digest_out, "w") as f:
            f.write(digest + "\n")
        logging.info("params digest: %s", digest)
    score = mod.score(val, "acc")
    print("final validation:", score)
    if args.serve_smoke:
        serve_smoke(mod, val, Xte, args.batch_size)
    if args.acc_out:
        with open(args.acc_out, "w") as f:
            f.write("%.6f\n" % dict(score)["accuracy"])
    if args.min_accuracy is not None:
        acc = dict(score)["accuracy"]
        assert acc >= args.min_accuracy, (
            "convergence regression: accuracy %.3f < required %.3f"
            % (acc, args.min_accuracy))


if __name__ == "__main__":
    main()
