"""bench.py is the driver's scoring gate — a syntax error or API drift
inside it would only surface in the end-of-round TPU run. This smoke
test executes it end to end on the CPU backend with tiny dimensions and
validates the one-line JSON contract."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke_env(**over):
    sys.path.insert(0, ROOT)
    from __graft_entry__ import virtual_cpu_env  # the one clean-env home
    env = virtual_cpu_env(1)
    # BENCH_GROUPED=0 / BENCH_HANDWRITTEN=0: each of those stages
    # builds and compiles ANOTHER full resnet-50 train program — pure
    # compile time (100s+ each on this backend) inside the tier-1
    # suite budget, where every second pushes later tests past the
    # 870s cutoff.  The grouped path is pinned by
    # tests/test_module_grouped.py; on the TPU run drift there shows as
    # a recorded *_error field and a non-zero exit after the JSON line.
    # BENCH_SERVE=0 for the same reason: Predictor warmup compiles one
    # resnet-50 eval program per batch bucket (tests/test_serving.py
    # pins the serving contracts on a small net instead).
    # BENCH_PREFETCH=0 likewise: its fresh metric tally token is one
    # more full train-step compile (tests/test_data_pipeline.py pins
    # the device-feed contracts on a small net)
    # BENCH_TELEMETRY=0 for the same reason as BENCH_PREFETCH: its
    # fresh metric tally token is one more full train-step compile
    # (tests/test_telemetry.py pins the telemetry contracts on a
    # small net)
    # BENCH_PRECISION=0 likewise: the precision-mode window is a
    # SECOND full resnet-50 train-step compile (tests/test_precision.py
    # pins every mode contract on a small net)
    # BENCH_SHARDED_CACHE=0 likewise: the sharded-cache tier sweep
    # compiles its own gather programs (tests/test_sharded_cache.py
    # pins the tier contracts on a small net)
    env.update(BENCH_BATCH="4", BENCH_STEPS="2", BENCH_PIPELINE="0",
               BENCH_DTYPE="float32", BENCH_FIT_EPOCH_BATCHES="3",
               BENCH_GROUPED="0", BENCH_HANDWRITTEN="0",
               BENCH_SERVE="0", BENCH_PREFETCH="0", BENCH_TELEMETRY="0",
               BENCH_PRECISION="0", BENCH_SHARDED_CACHE="0")
    env.update(over)
    return env


def test_bench_emits_contract_json():
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                          capture_output=True, text=True, timeout=1200,
                          env=_smoke_env(), cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    assert rec["metric"] == "resnet50_train_throughput"
    assert rec["value"] > 0
    assert rec["path"] == "module" and rec["fused_group"] is True
    # the north-star fit loop must be measured on the device-metric path
    # (tiny CPU windows are noisy: an implausible slope may be flagged
    # instead of recorded — that is the guard working, not a failure)
    assert rec.get("fit_img_per_sec", 0) > 0 or "fit_error" in rec, rec
    if rec.get("fit_img_per_sec"):
        assert rec.get("fit_device_metric") is True, rec


@pytest.mark.slow   # one more full resnet-50 train-step compile
def test_section_that_raises_exits_nonzero_after_the_json_line():
    code = ("import bench\n"
            "def boom(mx):\n"
            "    raise RuntimeError('forced')\n"
            "bench._bench_autopilot = boom\n"
            "bench.main()\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=1200,
                          env=_smoke_env(BENCH_FIT="0", BENCH_DECODE="0"),
                          cwd=ROOT)
    assert proc.returncode != 0
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["autopilot_error"] == "forced" and rec["value"] > 0
    assert "section(s) raised: autopilot" in proc.stderr


@pytest.mark.slow   # a fresh interpreter that waits for the TPU probe
def test_bench_refuses_a_cpu_nobody_asked_for():
    """No TPU and no explicit JAX_PLATFORMS=cpu: no metric line, exit
    non-zero (the BENCH_r06 failure — a CPU number under a device
    metric's name — made impossible)."""
    env = _smoke_env()
    del env["JAX_PLATFORMS"]
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "refusing to measure" in proc.stderr
    assert "resnet50_train_throughput" not in proc.stdout
