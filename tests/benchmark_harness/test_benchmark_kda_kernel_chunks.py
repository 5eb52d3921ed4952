"""`kda_kernel_chunks_per_step`
(`benchmark/metrics/kda_kernel_chunks_per_step.py`): the chunks of a
step whose decayed inner products the delta rules' own kernels made.

* the manifest's entry is the last of `per_layer`, names the Kimi cell
  and nothing the manifest had is changed;
* the reader on hand-made reports: the counter `kda.kernel_chunks` a
  step, 0 where the op fell back to XLA's form, nothing where the
  program counts no such thing (the parent) or keeps no report, an
  error where the report is not the window's;
* one rehearsal-size traced run of the Kimi cell on the CPU, where no
  kernel engages, prints 0 beside `kda_chunks_per_step` and says so.
"""
import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, traffic  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

NAME, STEPS = "kda_kernel_chunks_per_step", 50
CONFIG = "kimi-linear-ep32-l5"
CELL = CONFIG + ".fit-tokens-resident"
PARENT = "d92efaa31371ce3b4744d1ba9eef72cb8c949b90"


def _report(counters):
    return {"steps": STEPS, "epochs": 1, "wall_ns": 10 ** 10,
            "counters": counters, "spans": {}}


def test_the_manifests_entry_is_appended_and_nothing_else_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    assert now["per_layer"][-1] == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "img_per_s", "workloads": [CELL]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       NAME + ".py"))

    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args,
                              capture_output=True, text=True)
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    was = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    assert now["per_layer"][:-1] == was["per_layer"]
    for key in was:
        if key != "per_layer":
            assert now[key] == was[key], key
    changed = git("diff", "--name-status", PARENT, "--", "benchmark",
                  "tests/benchmark_harness").stdout.split("\n")
    assert [line for line in changed if line and not line.startswith("A")] \
        == []


@pytest.mark.parametrize("counters,want", [
    ({"kda.chunks": STEPS * 512, "kda.kernel_chunks": STEPS * 512}, 512.0),
    ({"kda.chunks": STEPS * 512, "kda.kernel_chunks": 0}, 0.0),  # fell back
    ({"kda.chunks": STEPS * 512}, None),       # the parent: no such counter
    ({"ssm.chunks": STEPS * 256}, None),       # a cell with no delta rule
    ({}, None),
])
def test_reader_on_a_hand_made_report(monkeypatch, counters, want):
    read = harness.load_reader(ROOT, NAME)
    monkeypatch.setattr(telemetry, "last_fit", lambda: _report(counters))
    got = read({"steps": STEPS})
    assert got is None if want is None else got == pytest.approx(want)
    with pytest.raises(RuntimeError, match="not the window's"):
        read({"steps": STEPS + 1})


def test_reader_gives_nothing_where_the_program_keeps_no_report(monkeypatch):
    monkeypatch.delattr(telemetry, "last_fit")
    assert harness.load_reader(ROOT, NAME)({"steps": STEPS}) is None


def test_traced_rehearsal_on_the_cpu_reads_nought_and_says_so():
    """Off the TPU the op takes XLA's form: the line carries the metric
    at 0 beside the chunks it went through, which is how a reader tells
    a silent fall to the old path from kernels that stopped helping."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfg = json.load(f)
    mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic",
                                    "fit-tokens-resident.json"))
    code, result = harness.run(ROOT, CELL, 3500000011, 0.3, True,
                               time.perf_counter(),
                               cfg_mix=harness.tiny(cfg, mix),
                               require_chip=False)
    assert code == 0
    assert result["metrics"][NAME] == {"value": 0, "unit": "count"}
    assert result["metrics"]["kda_chunks_per_step"]["value"] == 24
    report = telemetry.last_fit()
    assert report["counters"]["kda.kernel_chunks"] == 0
    assert report["counters"]["kda.chunks"] == 24 * report["steps"]
