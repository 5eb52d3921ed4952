"""The readers of the program's own fit report (`benchmark/fit_report.py`,
`metrics/fit_*.py`, `metrics/exec_*.py`, `metrics/input_*.py`):

* each reader on a hand-made report: its value, nothing where the
  program keeps no report, and an error where the report is not the
  window's;
* one rehearsal-size traced run of each cell prints every new metric of
  that cell, the program's `fit.next` agrees with the benchmark's own
  clock around the iterator, and the six metrics that tile `fit` add up
  to the window's wall time.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import fit_report, harness  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from test_benchmark_correct import (CELLS, CFG, RECORDIO,  # noqa: E402
                                    RESIDENT)

STEPS = 50


def _row(total_ns, self_ns=None, parent="fit.epoch"):
    return {"count": STEPS, "total_ns": total_ns,
            "self_ns": total_ns if self_ns is None else self_ns,
            "max_ns": total_ns // STEPS, "max_step": 0, "parent": parent}


REPORT = {
    "steps": STEPS, "epochs": 2, "wall_ns": 31_000_000_000,
    "counters": {"input.h2d_bytes": STEPS * 154_141_696},
    "spans": {
        "fit": _row(31_000_000_000, 40_000_000, None),
        "fit.epoch": _row(30_000_000_000, 10_000_000, "fit"),
        "fit.epoch_end": _row(960_000_000, parent="fit"),
        "fit.next": _row(8_000_000_000, 100_000_000),
        "input.decode": _row(6_000_000_000, parent="fit.next"),
        "input.assemble": _row(1_500_000_000, parent="fit.next"),
        "input.put": _row(400_000_000, parent="fit.next"),
        "fit.forward_backward": _row(20_000_000_000, 50_000_000),
        "exec.stage": _row(19_950_000_000, parent="fit.forward_backward"),
        "fit.update": _row(1_900_000_000, 1_700_000_000),
        "exec.launch": _row(200_000_000, parent="fit.update"),
        "fit.metric": _row(90_000_000),
    }}
EXPECTED = {
    "fit_next_ms_per_step": 160.0,
    "input_decode_ms_per_step": 120.0,
    "input_assemble_ms_per_step": 30.0,
    "input_put_ms_per_step": 8.0,
    "input_h2d_mb_per_step": 154.141696,
    "fit_forward_backward_ms_per_step": 400.0,
    "exec_stage_ms_per_step": 399.0,
    "fit_update_ms_per_step": 38.0,
    "exec_launch_ms_per_step": 4.0,
    "fit_metric_ms_per_step": 1.8,
    "fit_epoch_end_ms_per_step": 19.2,
    "fit_self_ms_per_step": 1.0,
}
TILE = ("fit_next_ms_per_step", "fit_forward_backward_ms_per_step",
        "fit_update_ms_per_step", "fit_metric_ms_per_step",
        "fit_epoch_end_ms_per_step", "fit_self_ms_per_step")
INPUT_ONLY = {n for n in EXPECTED if n.startswith("input_")}


@pytest.fixture
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_table_above_is_the_manifests(manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]
               if m["name"] in EXPECTED}
    assert set(entries) == set(EXPECTED)
    assert {m["name"] for m in manifest["per_layer"]
            if m["source"] == "program_span"} == \
        set(EXPECTED) - {"input_h2d_mb_per_step"}
    for name, m in entries.items():
        assert m["moves"] == "img_per_s" and m["better"] == "lower"
        assert m.get("workloads") == ([CELLS[1]] if name in INPUT_ONLY
                                      else None), name


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_report(monkeypatch, name):
    read = harness.load_reader(ROOT, name)
    monkeypatch.setattr(telemetry, "last_fit", lambda: REPORT)
    assert read({"steps": STEPS}) == pytest.approx(EXPECTED[name], rel=1e-12)
    # not the window's report: an error, not another reading
    with pytest.raises(RuntimeError, match="not the window's"):
        read({"steps": STEPS + 1})
    monkeypatch.setattr(telemetry, "last_fit", lambda: None)
    with pytest.raises(RuntimeError, match="not the window's"):
        read({"steps": STEPS})


def test_readers_give_nothing_where_the_program_keeps_no_report(monkeypatch):
    """A parent commit from before the report: the line leaves the
    metrics out, and nothing raises."""
    monkeypatch.delattr(telemetry, "last_fit")
    for name in EXPECTED:
        assert harness.load_reader(ROOT, name)({"steps": STEPS}) is None
    assert fit_report.window_report({"steps": STEPS}) is None


def test_a_span_the_window_never_opened_reads_nothing(monkeypatch):
    resident = dict(REPORT, counters={}, spans={
        n: r for n, r in REPORT["spans"].items()
        if not n.startswith("input.")})
    monkeypatch.setattr(telemetry, "last_fit", lambda: resident)
    for name in INPUT_ONLY:
        assert harness.load_reader(ROOT, name)({"steps": STEPS}) is None


def test_hand_made_report_tiles_its_wall_time():
    assert sum(EXPECTED[n] for n in TILE) * STEPS * 1e6 == \
        pytest.approx(REPORT["wall_ns"], rel=1e-12)


@pytest.mark.parametrize("cell,mix", [(CELLS[0], RESIDENT),
                                      (CELLS[1], RECORDIO)])
def test_traced_rehearsal_prints_the_report_metrics(monkeypatch, cell, mix):
    monkeypatch.setenv("MXNET_BN_EXACT_STATS", "1")
    seen, load_reader = {}, harness.load_reader

    def spy(root, name):
        read = load_reader(root, name)

        def wrapped(run):
            seen.update(wall_s=run["wall_s"], steps=run["steps"])
            return read(run)
        return wrapped
    monkeypatch.setattr(harness, "load_reader", spy)
    code, result = harness.run(ROOT, cell, 7, 0.3, True, time.perf_counter(),
                               cfg_mix=(dict(CFG), dict(mix)),
                               require_chip=False)
    assert code == 0 and result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    fed = cell == CELLS[1]
    assert set(EXPECTED) & set(got) == \
        (set(EXPECTED) if fed else set(EXPECTED) - INPUT_ONLY)
    assert all(got[n] >= 0 for n in EXPECTED if n in got)
    report = telemetry.last_fit()
    steps = report["steps"]
    assert steps == result["attempted"] == seen["steps"]
    # the six that tile the call add up to the report's root, and to
    # the harness's own clock around the call
    tiled_s = sum(got[n] for n in TILE) * steps * 1e-3
    assert tiled_s == pytest.approx(report["wall_ns"] * 1e-9, rel=1e-9)
    assert tiled_s == pytest.approx(seen["wall_s"], rel=0.02)
    if fed:
        assert got["fit_next_ms_per_step"] == pytest.approx(
            got["input_wait_ms_per_step"], rel=0.10)
        batch = CFG["per_chip_batch"]
        c, h, w = CFG["image"]
        assert got["input_h2d_mb_per_step"] == pytest.approx(
            (batch * c * h * w * 4 + batch * 4) / 1e6)
        stages = sum(got["input_%s_ms_per_step" % s]
                     for s in ("decode", "assemble", "put"))
        assert stages <= got["fit_next_ms_per_step"]
    else:
        # device-resident batches: nothing goes up from the host
        assert "input.h2d_bytes" not in report["counters"]
    assert got["exec_stage_ms_per_step"] <= \
        got["fit_forward_backward_ms_per_step"]
    assert got["exec_launch_ms_per_step"] <= got["fit_update_ms_per_step"]
