"""`input_ready_pct` (`benchmark/metrics/input_ready_pct.py`) and the fed
cell's account of an iterator that runs ahead of `fit`:

* the reader on hand-made reports: the share of `next()` calls that
  found a batch waiting, nothing where the program counts neither or
  keeps no report, an error where the report is not the window's;
* one rehearsal-size traced run of the fed cell is `correct`, prints
  the new metric beside all four `input_*` metrics, and the stages it
  credits are rows that ran off `fit`'s thread: the six phase metrics
  still tile the window's wall time without them.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from test_benchmark_correct import CELLS, CFG, RECORDIO  # noqa: E402

NAME, STEPS = "input_ready_pct", 50
INPUTS = ("input_decode_ms_per_step", "input_assemble_ms_per_step",
          "input_put_ms_per_step", "input_h2d_mb_per_step")
TILE = ("fit_next_ms_per_step", "fit_forward_backward_ms_per_step",
        "fit_update_ms_per_step", "fit_metric_ms_per_step",
        "fit_epoch_end_ms_per_step", "fit_self_ms_per_step")


def _report(counters):
    return {"steps": STEPS, "epochs": 2, "wall_ns": 10 ** 10,
            "counters": counters, "spans": {}}


def test_the_manifests_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "input",
                     "moves": "img_per_s", "workloads": [CELLS[1]]}
    assert manifest["per_layer"][-1] is entry      # added at the end


@pytest.mark.parametrize("counters,want", [
    ({"input.ready": 30, "input.waited": 20}, 60.0),
    ({"input.ready": 7, "input.waited": 43, "input.h2d_bytes": 1}, 14.0),
    ({"input.waited": STEPS}, 0.0),
    ({"input.ready": STEPS}, 100.0),
    ({"input.h2d_bytes": 5}, None),        # a program from before this one
    ({}, None),                            # a cell with no ImageRecordIter
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


def test_traced_rehearsal_of_the_fed_cell(monkeypatch):
    monkeypatch.setenv("MXNET_BN_EXACT_STATS", "1")
    code, result = harness.run(ROOT, CELLS[1], 11, 0.3, True,
                               time.perf_counter(),
                               cfg_mix=(dict(CFG), dict(RECORDIO)),
                               require_chip=False)
    assert code == 0 and result["correct"] is True
    assert result["check"]["input_gap"]["value"] == 0
    assert result["check"]["window_compiles"]["value"] == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert all(got.get(n) is not None and got[n] > 0 for n in INPUTS), got
    assert 0.0 <= got[NAME] <= 100.0
    assert result["metrics"][NAME]["unit"] == "%"
    report = telemetry.last_fit()
    steps = report["steps"]
    assert steps == result["attempted"]
    counters = report["counters"]
    assert counters.get("input.ready", 0) + counters.get("input.waited", 0) \
        == steps
    assert got[NAME] == pytest.approx(
        100.0 * counters.get("input.ready", 0) / steps)
    batch = CFG["per_chip_batch"]
    c, h, w = CFG["image"]
    assert got["input_h2d_mb_per_step"] == pytest.approx(
        (batch * c * h * w * 4 + batch * 4) / 1e6)
    # the wait is the program's `fit.next` and the benchmark's own clock
    assert got["fit_next_ms_per_step"] == pytest.approx(
        got["input_wait_ms_per_step"], rel=0.10)
    # the stages ran on the producer's thread, a batch each: rows of
    # their own, and the phases tile the call without them
    for stage in ("input.decode", "input.assemble", "input.put"):
        row = report["spans"][stage]
        assert row["count"] == steps
        assert row["parent"] == telemetry.OFF_THREAD
    tiled_s = sum(got[n] for n in TILE) * steps * 1e-3
    assert tiled_s == pytest.approx(report["wall_ns"] * 1e-9, rel=1e-9)
