"""`remat_kept_gib` (`benchmark/metrics/remat_kept_gib.py`): what the
token cell's segmented backward pass is handed in place of making it
again.

* the reader on hand-made reports: the counter `remat.kept_bytes` a
  step in GiB, 0 where the policy keeps nothing, nothing where the
  program counts none (a parent from before the counter, a cell that
  does not recompute) or keeps no report, an error where the report is
  not the window's;
* one rehearsal-size traced run of the token cell is `correct` and
  prints the metric: the bytes, by shape, of the products and attention
  outputs its wrapped segments name.
"""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, traffic  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

NAME, STEPS = "remat_kept_gib", 50
CELL = "trinity-mini-ep8-l5.fit-tokens-resident"


def _report(counters):
    return {"steps": STEPS, "epochs": 2, "wall_ns": 10 ** 10,
            "counters": counters, "spans": {}}


def test_the_manifests_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "GiB", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "img_per_s", "workloads": [CELL]}


@pytest.mark.parametrize("counters,want", [
    ({"remat.kept_bytes": STEPS * 2 ** 30}, 1.0),
    ({"remat.kept_bytes": STEPS * 1766850560, "moe.dropped": 0},
     1766850560 / 2 ** 30),
    ({"remat.kept_bytes": 0}, 0.0),        # a policy that keeps nothing
    ({"moe.held_pairs": 5}, None),         # a program from before this one
    ({}, None),                            # a cell that does not recompute
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


def test_traced_rehearsal_of_the_token_cell():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-ep8-l5.json")) as f:
        cfg = json.load(f)
    mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic",
                                    "fit-tokens-resident.json"))
    cfg, mix = harness.tiny(cfg, mix)
    # float32 on the CPU: the limits are the chip's, and at this size
    # bfloat16 routes a few tokens unlike the float32 reference
    cfg["compute_dtype"] = "float32"
    code, result = harness.run(ROOT, CELL, 3100000011, 0.3, True,
                               time.perf_counter(), cfg_mix=(cfg, mix),
                               require_chip=False)
    assert code == 0 and result["correct"] is True
    assert result["check"]["window_compiles"]["value"] == 0
    assert result["metrics"][NAME]["unit"] == "GiB"
    got = result["metrics"][NAME]["value"]
    # by shape, in the activation type: q, k, v, gate and o of every
    # layer, the dense layer's and the shared experts' gate, up and
    # down, and (off the TPU: no log-sum-exp) attention's output; the
    # head's logits lie in the last segment, which is not wrapped
    a = cfg["symbol_call"]["arguments"]
    layers, rows = len(a["layer_types"]), cfg["per_chip_batch"]
    q = a["num_attention_heads"] * a["head_dim"]
    kv = a["num_key_value_heads"] * a["head_dim"]
    hidden, dense = a["hidden_size"], a["num_dense_layers"]
    columns = layers * (q + kv + kv + q + hidden) \
        + dense * (2 * a["intermediate_size"] + hidden) \
        + (layers - dense) * (2 * a["moe_intermediate_size"] + hidden) \
        + layers * q
    assert got == pytest.approx(rows * columns * 4 / 2 ** 30, rel=1e-12)
    report = telemetry.last_fit()
    assert report["counters"]["remat.kept_bytes"] \
        == report["steps"] * rows * columns * 4
    assert report["steps"] == result["attempted"]
