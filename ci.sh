#!/usr/bin/env bash
# One-command CI gate mirroring the reference Jenkinsfile stages
# (Sanity lint :31-41 -> Unit tests :207-258 -> Integration): lint,
# full test suite, multi-chip dryrun. Nonzero exit on any gate. Runs
# pure-CPU (the suite's conftest provisions an 8-device virtual mesh).
set -u
cd "$(dirname "$0")"
FAILED=0

stage() {
    echo
    echo "=== CI stage: $1 ==="
}

stage "lint (tools/lint.py)"
python tools/lint.py || FAILED=1

stage "unit + integration suite (pytest tests/)"
python -m pytest tests/ -q || FAILED=1

stage "convergence gate (train_cifar10 to fixed accuracy)"
# reference Jenkinsfile integration stage (test_score.py): train a small
# resnet on the CIFAR-shaped set and FAIL on accuracy regression
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 5 --batch-size 128 \
    --min-accuracy 0.95 || FAILED=1

stage "checkpoint resume gate (preempt after epoch 1, resume from latest())"
# durable-checkpoint contract (docs/api/checkpoint.md): a run killed
# after a committed epoch and resumed with fit(resume_from=manager)
# must land on the same final accuracy as the uninterrupted run —
# params, optimizer momentum, BN stats and RNG all come back
CKPT_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 3 --batch-size 128 --seed 7 \
    --acc-out "$CKPT_TMP/acc_straight.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 3 --batch-size 128 --seed 7 \
    --checkpoint-dir "$CKPT_TMP/ckpt" --exit-after-epoch 1
rc=$?
if [ "$rc" -ne 66 ]; then
    echo "expected simulated preemption exit 66, got $rc"
    FAILED=1
fi
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 3 --batch-size 128 --seed 7 \
    --checkpoint-dir "$CKPT_TMP/ckpt" --resume \
    --acc-out "$CKPT_TMP/acc_resumed.txt" || FAILED=1
python - "$CKPT_TMP/acc_straight.txt" "$CKPT_TMP/acc_resumed.txt" <<'PY' || FAILED=1
import sys
a, b = (float(open(p).read()) for p in sys.argv[1:3])
assert abs(a - b) <= 1e-3, \
    "resumed accuracy %.4f != uninterrupted %.4f" % (b, a)
print("resume gate: uninterrupted %.4f vs resumed %.4f" % (a, b))
PY
rm -rf "$CKPT_TMP"

stage "batch-group gate (grouped K-step training == per-batch, 1 epoch)"
# iterations-per-loop contract (docs/how_to/perf.md "batch_group"): the
# scanned K-step train program is bit-identical to per-batch training,
# so a seeded 1-epoch run must land on the same accuracy either way
BG_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --acc-out "$BG_TMP/acc_plain.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --batch-group 4 --acc-out "$BG_TMP/acc_grouped.txt" || FAILED=1
python - "$BG_TMP/acc_plain.txt" "$BG_TMP/acc_grouped.txt" <<'PY' || FAILED=1
import sys
a, b = (float(open(p).read()) for p in sys.argv[1:3])
assert abs(a - b) <= 1e-3, \
    "batch_group accuracy %.4f != per-batch %.4f" % (b, a)
print("batch-group gate: per-batch %.4f vs grouped %.4f" % (a, b))
PY
rm -rf "$BG_TMP"

stage "device-feed gate (prefetch_to_device == plain, bit-identical params)"
# async device-feed contract (docs/api/data.md): training through the
# DeviceLoader ring — background mesh-aware staging, host/transfer/step
# overlapped — must land on BIT-IDENTICAL final params to the plain
# path (compared by sha256 digest, stronger than an accuracy check)
PF_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --acc-out "$PF_TMP/acc_plain.txt" \
    --params-digest-out "$PF_TMP/digest_plain.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --prefetch-device 2 \
    --params-digest-out "$PF_TMP/digest_prefetch.txt" || FAILED=1
python - "$PF_TMP/digest_plain.txt" "$PF_TMP/digest_prefetch.txt" <<'PY' || FAILED=1
import sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "prefetch-device params digest %s != plain %s" % (b, a)
print("device-feed gate: bit-identical params (sha256 %s...)" % a[:16])
PY

stage "precision gate (bf16 opt-state + remat: reproducible digest + accuracy vs f32)"
# precision-mode contract (docs/api/precision.md): a mode is allowed to
# CHANGE numerics vs f32, but must be exactly reproducible WITHIN the
# mode — two seeded runs under bf16 optimizer state + dots_saveable
# remat must land on the SAME sha256 params digest — and its final
# accuracy must stay within the pinned tolerance of the f32 reference
# (reusing the device-feed gate's plain run as the reference).
PM_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --opt-state-dtype bf16 --remat dots_saveable \
    --acc-out "$PM_TMP/acc_precision.txt" \
    --params-digest-out "$PM_TMP/digest_a.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --opt-state-dtype bf16 --remat dots_saveable \
    --params-digest-out "$PM_TMP/digest_b.txt" || FAILED=1
python - "$PM_TMP/digest_a.txt" "$PM_TMP/digest_b.txt" \
    "$PM_TMP/acc_precision.txt" "$PF_TMP/acc_plain.txt" <<'PY' || FAILED=1
import sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "precision-mode params digest not reproducible: %s != %s" % (a, b)
pa, pf = (float(open(p).read()) for p in sys.argv[3:5])
assert abs(pa - pf) <= 0.02, \
    "precision-mode accuracy %.4f drifted >0.02 from f32 %.4f" % (pa, pf)
print("precision gate: within-mode digest reproducible (sha256 %s...), "
      "accuracy %.4f vs f32 %.4f" % (a[:16], pa, pf))
PY
rm -rf "$PM_TMP"

stage "device-augment gate (u8 wire + device augment + HBM cache == host reference)"
# fed-input contract (docs/api/data.md "Device-side augmentation"):
# training through the u8 device path — uint8 NHWC wire batches, the
# augment compiled as a device program (random pad-crop + mirror +
# normalize, draws keyed (seed, epoch, batch)), and the HBM-resident
# dataset cache serving epoch >= 2 by device gather — must land on a
# BIT-IDENTICAL params digest vs the numpy host-reference augment
# path (DeviceAugment.apply_host) on the same stream.  The telemetry
# run also asserts ZERO post-warmup retraces in-script, so the cache
# handover at epoch 2 provably compiles nothing new.
DA_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 2 --batch-size 128 --seed 7 \
    --device-augment --cache-dataset \
    --telemetry-jsonl "$DA_TMP/steps.jsonl" \
    --params-digest-out "$DA_TMP/digest_device.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 2 --batch-size 128 --seed 7 \
    --device-augment --augment-placement host \
    --params-digest-out "$DA_TMP/digest_hostref.txt" || FAILED=1
python - "$DA_TMP/digest_device.txt" "$DA_TMP/digest_hostref.txt" <<'PY' || FAILED=1
import sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "device-augment+cache params digest %s != host-reference %s" % (a, b)
print("device-augment gate: bit-identical params (sha256 %s...)" % a[:16])
PY
rm -rf "$DA_TMP"

stage "telemetry gate (telemetry-on fit == plain, bit-identical params + step JSONL)"
# observability contract (docs/api/telemetry.md): a fit with the full
# telemetry recording path live — step timeline, compile watch, one
# JSONL line per step — must train to BIT-IDENTICAL params (sha256
# digest) and leave a parseable event log with one step record per
# train step (and zero post-warmup retraces, asserted in-script).
# Reuses the device-feed gate's plain-path digest (identical command)
# rather than retraining the same baseline a third time.
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --telemetry-jsonl "$PF_TMP/steps.jsonl" \
    --params-digest-out "$PF_TMP/digest_telemetry.txt" || FAILED=1
python - "$PF_TMP/digest_plain.txt" "$PF_TMP/digest_telemetry.txt" \
    "$PF_TMP/steps.jsonl" <<'PY' || FAILED=1
import json, sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "telemetry-on params digest %s != plain %s" % (b, a)
lines = [json.loads(l) for l in open(sys.argv[3])]   # every line parses
steps = [l for l in lines if l["kind"] == "step"]
# one record per train step: the synthetic set is 4096 rows at batch
# 128 -> 32 steps/epoch x 1 epoch; pin via the records' own coordinates
per_epoch = {}
for s in steps:
    per_epoch.setdefault(s["epoch"], set()).add(s["nbatch"])
assert per_epoch and all(
    batches == set(range(max(batches) + 1)) and len(batches) >= 32
    for batches in per_epoch.values()), \
    "step records are not 1:1 with train steps: %r" % (
        {e: len(b) for e, b in per_epoch.items()})
assert any(l["kind"] == "metrics" for l in lines), "no metrics flush"
print("telemetry gate: bit-identical params (sha256 %s...), %d step "
      "records across %d epoch(s), %d JSONL lines"
      % (a[:16], len(steps), len(per_epoch), len(lines)))
PY
rm -rf "$PF_TMP"

stage "introspection + health gate (program report + watchdog + bitwise params)"
# program-introspection contract (docs/api/telemetry.md "Program
# introspection") plus the judgment layer (same doc, "Regression
# watchdog"): a 2-epoch fit with the inventory + the regression
# watchdog live must (a) train to BIT-IDENTICAL params vs
# telemetry-off, (b) emit a program report with nonzero XLA
# flops/bytes for the step AND optimizer programs, (c) write
# post-warmup step JSONL lines — with zero post-warmup retraces
# (asserted in-script) — and (d) arm the watchdog at the warmup
# boundary, self-calibrate a baseline, and report HEALTHY (zero
# health incidents on the clean run).
IN_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 2 --batch-size 128 --seed 7 \
    --params-digest-out "$IN_TMP/digest_plain.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 2 --batch-size 128 --seed 7 \
    --program-report "$IN_TMP/programs.json" \
    --telemetry-jsonl "$IN_TMP/steps.jsonl" \
    --health-report "$IN_TMP/health.json" \
    --params-digest-out "$IN_TMP/digest_introspect.txt" || FAILED=1
python - "$IN_TMP/digest_plain.txt" "$IN_TMP/digest_introspect.txt" \
    "$IN_TMP/programs.json" "$IN_TMP/steps.jsonl" \
    "$IN_TMP/health.json" <<'PY' || FAILED=1
import json, sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "introspection-on params digest %s != plain %s" % (b, a)
rep = json.load(open(sys.argv[3]))
kinds = {}
for p in rep["programs"]:
    if p.get("flops") and p.get("bytes_accessed"):
        kinds.setdefault(p["kind"], []).append(p["name"])
assert "train_step" in kinds, "no analyzed train_step: %r" % kinds
assert "optimizer_update" in kinds, "no optimizer account: %r" % kinds
steps = [json.loads(l) for l in open(sys.argv[4])
         if json.loads(l).get("kind") == "step"]
post = [s for s in steps if s["epoch"] >= 1]
assert post, "no post-warmup step lines"
health = json.load(open(sys.argv[5]))
assert health["armed"] and health["calibrated"], health
assert health["healthy"] and health["incidents"] == [], \
    "clean run produced health incidents: %r" % health["incidents"]
assert health["baseline"] and "step_total_ms" in health["baseline"], \
    "watchdog baseline missing step_total_ms: %r" % health["baseline"]
print("introspection+health gate: bit-identical params (sha256 "
      "%s...), %d programs (%s), %d post-warmup steps, watchdog "
      "armed+healthy (baseline step %.1f ms)"
      % (a[:16], rep["n_programs"], ",".join(sorted(kinds)), len(post),
         health["baseline"]["step_total_ms"]))
PY
rm -rf "$IN_TMP"

stage "serving SLO gate (burn-rate scope populated, no breach, request traces)"
# judgment-layer serving contract (docs/api/serving.md "Request
# traces" + docs/api/telemetry.md "Serving SLOs"): the demo serves a
# concurrent mixed-size load through DynamicBatcher(slo=...) with
# request tracing live — the slo.* gauge scope must be populated on
# the Prometheus scrape with NO breach on the healthy smoke workload,
# every request must carry a phase-decomposed trace, and the usual
# parity + frozen-compile serving asserts still hold (all in-script).
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/serve_cifar10.py \
    --num-epochs 1 --clients 4 --requests 8 --slo-report || FAILED=1

stage "serving smoke gate (Predictor parity + frozen compiles under traffic)"
# online-serving contract (docs/api/serving.md): train 1 epoch, stand up
# an in-process Predictor + DynamicBatcher, fire concurrent mixed-size
# requests from client threads — served rows must be bitwise equal to
# Module.predict and warmup() must leave ZERO further XLA compiles
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --serve-smoke || FAILED=1

stage "serving warm-start gate (persistent compile cache, two processes)"
# replica warm-start contract (docs/api/serving.md "Persistent compile
# cache"): two separate serving processes share one executable-cache
# directory off one committed checkpoint. The first cold-starts
# (compiles the bucket ladder, commits each entry atomically); the
# second must WARM-start — every bucket deserialized, zero warmup XLA
# compiles under CompileWatch (--expect-warm asserts both in-script) —
# and both must serve bit-identical responses (sha256 over a fixed
# serial request sweep).
WS_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --checkpoint-dir "$WS_TMP/ckpt" --exit-after-epoch 1
rc=$?
if [ "$rc" -ne 66 ]; then
    echo "expected simulated preemption exit 66, got $rc"
    FAILED=1
fi
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/serve_cifar10.py \
    --checkpoint-dir "$WS_TMP/ckpt" --clients 4 --requests 8 \
    --max-batch-size 16 --cache-dir "$WS_TMP/cache" \
    --digest-out "$WS_TMP/digest_cold.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/serve_cifar10.py \
    --checkpoint-dir "$WS_TMP/ckpt" --clients 4 --requests 8 \
    --max-batch-size 16 --cache-dir "$WS_TMP/cache" \
    --digest-out "$WS_TMP/digest_warm.txt" --expect-warm || FAILED=1
python - "$WS_TMP/digest_cold.txt" "$WS_TMP/digest_warm.txt" <<'PY' || FAILED=1
import sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "warm-replica response digest %s != cold %s" % (b, a)
print("warm-start gate: bit-identical responses (sha256 %s...)" % a[:16])
PY
rm -rf "$WS_TMP"

stage "multi-chip dryrun (8 virtual devices)"
python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)" \
    || FAILED=1

stage "multi-host dryrun (4 virtual hosts, elastic resume gate)"
# mxnet_tpu.dist contract (docs/api/dist.md): the per-host
# slice/stage/assemble path trains BITWISE identically to a plain fit
# with zero post-warmup retraces, and a dp=8 -> worker-loss -> dp=4
# elastic resume is bitwise equal to a continuous dp=4 run from the
# same committed checkpoint (params, optimizer state, num_update).
# Emits MULTIHOST_r01.json (mesh spec, per-process shard shapes,
# barrier/heartbeat clocks, elastic-resume transcript).
python -c "from __graft_entry__ import dryrun_multihost; dryrun_multihost(8, 4)" \
    || FAILED=1

stage "sharded-cache dryrun (pod-sharded HBM dataset cache gate)"
# pod-sharded cache contract (docs/api/data.md "Pod-sharded cache"):
# a dp=4 virtual-host fit through ShardedCachedDataset — each host
# capturing only its shard_rows block, epochs >= 2 served by the
# jitted gather over the P('dp') cache pytree — must train BITWISE
# equal to the single-host CachedDataset fit AND the streaming fit
# with zero post-warmup retraces; each host's cache bytes must be
# 1/4 of the single-host capture; the global shuffle order must be
# dp-width-stable (two shard widths draw the identical order and
# train to identical params); and one shard forced onto the host
# spill tier must stay bit-identical. Emits SHARDCACHE_r01.json.
python -c "from __graft_entry__ import dryrun_sharded_cache; dryrun_sharded_cache(8, 4)" \
    || FAILED=1

stage "chaos-soak gate (seeded FaultPlan over train + elastic resume + serve)"
# fault-injection contract (docs/api/faults.md): one seeded FaultPlan —
# transient transform/commit faults, a straggler delay, a planned
# worker loss (dp=8 -> dp=4 elastic resume), a serving device
# slowdown, a queue flood, a batcher worker death, and a poisoned
# executable-cache entry — must (a) recover to the bitwise-identical
# params digest of the fault-free continuous reference, (b) leave
# EXACTLY the planned incidents in the plan transcript / FlightRecorder
# / health scopes, (c) perform zero post-warmup retraces, (d) serve
# bitwise-correct rows after every serving fault, and (e) keep the
# decode plane's non-abandoned streams bitwise across a per-step
# slowdown, a decode-scheduler crash, and a mid-stream client
# abandon. Emits CHAOS_r01.json.
python -c "from __graft_entry__ import dryrun_chaos; dryrun_chaos(8, 4)" \
    || FAILED=1

stage "decode gate (continuous-batching slot engine: bitwise streams + tps win)"
# continuous-batching decode contract (docs/api/serving.md "Decode
# engine"): a seeded multi-client run through the slot-structured
# DecodeEngine must (a) emit token streams bitwise equal to the same
# requests decoded ALONE through a sequential per-request engine,
# (b) beat the sequential baseline on aggregate decode tokens/sec,
# (c) perform zero post-warmup retraces across slot join/retire
# churn, (d) warm a second replica from the persistent executable
# cache with zero XLA compiles (state init + prefill buckets + step),
# (e) carry a phase-decomposed TTFT trace per request and populate
# the slo.decode.ttft / slo.decode.per_token gauges on a live scrape,
# and (f) keep the padded prefill bucket ladder bitwise vs the
# exact-length forward. Emits DECODE_r01.json.
python -c "from __graft_entry__ import dryrun_decode; dryrun_decode(1)" \
    || FAILED=1

stage "quant gate (weight-only int8 decode + calibrated int8 serving)"
# native low-bit compute contract (docs/api/precision.md "Quantized
# serving modes"): (a) the int8_weight decode step program's
# analyze_compiled argument bytes shrink vs bf16 and f32 (the byte
# witness), (b) decode streams are deterministic per (params, prompt,
# seed) under quantized weights — across a warm replica deserialized
# from the executable cache with zero XLA compiles — and the prefill
# bucket ladder stays bitwise, (c) an f32 engine warming from the
# same cache directory adopts nothing (mode + quant tag key
# separation), (d) a calibration pass populates the quant.calib.*
# histograms and the resulting int8_serve Predictor matches the f32
# reference within MXNET_QUANT_TOLERANCE, (e) a cross-mode checkpoint
# restore is refused, (f) zero post-warmup retraces. Emits
# QUANT_r01.json.
python -c "from __graft_entry__ import dryrun_quant; dryrun_quant(1)" \
    || FAILED=1

stage "chaos-soak numeric stage (training guardian heals NaN + loss spike)"
# guardian contract (docs/api/guardian.md): a seeded plan poisons one
# mid-train batch with NaN and spikes a later one; the device-resident
# health sentinel detects both at the epoch boundary and rollback-and-
# skip must (a) finish with params bitwise-equal to a clean guarded
# run trained on the same stream with the two batches excluded,
# (b) leave exactly the planned incidents + one guardian_rollback
# flight event per heal, (c) perform zero post-warmup retraces, and
# (d) keep the SDC parity probe silent throughout. Emits CHAOS_r02.json.
python -c "from __graft_entry__ import dryrun_chaos_numeric; dryrun_chaos_numeric(8)" \
    || FAILED=1

stage "autopilot gate (telemetry-to-action loop closes, warm + bitwise)"
# fleet-autopilot contract (docs/api/autopilot.md): (a) an injected
# slo.* burn-rate breach scales the ReplicaPool out through the
# persistent executable cache — every bucket deserialized, zero XLA
# compiles, rows bitwise the first replica's; (b) cooldown hysteresis
# holds, then sustained idle scales back in; (c) a NaN-poisoned
# committed generation is admitted as a canary, fails the finite
# probe, rolls back and is NEVER promoted, while the clean generation
# is — the protected stable route stays bitwise-clean throughout;
# (d) an elastic dp-shrink (non-ring-adjacent deaths) resumes from
# the PeerCheckpointStore's host memory, bitwise vs the disk restore
# AND the disk-resumed control run's final params; (e) zero
# post-warmup retraces across all the serving-plane churn; (f) the
# armed fault plan (blinded poll + failed spin-up) fires exactly its
# planned incidents and every transcribed decision replays through
# the pure kernel; (g) autopilot-off serves bitwise-identical rows.
# Emits AUTOPILOT_r01.json.
python -c "from __graft_entry__ import dryrun_autopilot; dryrun_autopilot(8)" \
    || FAILED=1

stage "scenario matrix (pinned example/ long-tail workloads, full contract set)"
# pinned-workload scenario contract (docs/api/scenarios.md): every
# registered mxnet_tpu.scenarios scenario — the example/ long tail
# (transformer-lm decode serving, bucketing LSTM, NCE embeddings, toy
# SSD) plus the u8-cache CNN and pod-sharded-cache MLP — runs through
# the REAL Module.fit / serving stack and must hold its full contract
# set: (a) bitwise repeat-run params digest, (b) zero post-warmup
# retraces across the whole scenario, (c) accuracy floor met,
# (d) declared telemetry gauges present, (e) kill/resume landing
# bitwise on the straight run, (f) serving parity (Predictor rows /
# DecodeEngine streams) where declared, and (g) the seeded chaos
# sweep firing every planned fault, healing every incident, and
# keeping the trained params bitwise-equal to the fault-free run.
# Emits SCENARIO_r01.json.
python -c "from __graft_entry__ import dryrun_scenarios; dryrun_scenarios(8)" \
    || FAILED=1

stage "network serving plane (gateway: HTTP parity, drain, chaos re-route)"
# the mxnet_tpu.gateway contract (docs/api/gateway.md): the serving
# stack's guarantees must survive the wire — (a) /v1/predict rows
# through GatewayClient are bitwise-equal to the in-process Predictor
# (float32 survives the JSON round trip exactly); (b) the raw chunked
# /v1/generate body is byte-identical to the same-seed in-process
# DecodeEngine stream; (c) a replica warmed from the persistent
# executable cache serves HTTP traffic with zero XLA compiles;
# (d) an armed gateway.accept flood answers 429 + Retry-After for
# exactly its budget, then the same request recovers bitwise;
# (e) /readyz flips 503 the moment drain starts yet the in-flight
# stream runs to completion; (f) the chaos seam sweep heals — accept
# flood by client retry, transient stream fault and a replica KILLED
# mid-stream by deterministic affinity re-route with the replayed
# prefix skipped, every healed stream exactly equal to the fault-free
# reference; (g) zero post-warmup retraces across all of the above.
# Emits GATEWAY_r01.json.
python -c "from __graft_entry__ import dryrun_gateway; dryrun_gateway(1)" \
    || FAILED=1

stage "chaos smoke (train_cifar10 --fault-plan: healed faults keep the digest)"
# the smoke-sized spelling tests/test_examples.py shares: transient
# staging faults healed by the shared bounded-backoff retry must leave
# the trained params digest bitwise identical to the fault-free run
CH_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --prefetch-device 2 \
    --params-digest-out "$CH_TMP/digest_plain.txt" || FAILED=1
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=1 \
    timeout 420 python example/image-classification/train_cifar10.py \
    --network resnet-8 --num-epochs 1 --batch-size 128 --seed 7 \
    --prefetch-device 2 \
    --fault-plan "data.device_put:transient@nth=5;data.stager:transient@nth=9" \
    --params-digest-out "$CH_TMP/digest_chaos.txt" || FAILED=1
python - "$CH_TMP/digest_plain.txt" "$CH_TMP/digest_chaos.txt" <<'PY' || FAILED=1
import sys
a, b = (open(p).read().strip() for p in sys.argv[1:3])
assert a and a == b, \
    "faulted-run params digest %s != fault-free %s" % (b, a)
print("chaos smoke: bit-identical params under injected transient "
      "faults (sha256 %s...)" % a[:16])
PY
rm -rf "$CH_TMP"

echo
if [ "$FAILED" -ne 0 ]; then
    echo "CI: FAILED"
    exit 1
fi
echo "CI: all gates passed"
