"""`kda_carry_kernel_chunks_per_step`
(`benchmark/metrics/kda_carry_kernel_chunks_per_step.py`): the chunks
of a step that the delta rules' carry went through in its own kernel.

* the manifest's entry is appended: every entry before it is the
  parent's, in order, and nothing else the manifest had is changed;
* the reader on hand-made reports: the counter `kda.carry_kernel_chunks`
  a step, 0 where the carry took the scan, nothing where the program
  counts no such thing (the parent) or keeps no report, an error where
  the report is not the window's.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

NAME, STEPS = "kda_carry_kernel_chunks_per_step", 50
CELL = "kimi-linear-ep32-l5.fit-tokens-resident"
PARENT = "c1b5b58386a53858d6558372bdd87b56c43f3d4b"
ENTRY = {"name": NAME, "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "kernels",
         "moves": "img_per_s", "workloads": [CELL]}


def _report(counters):
    return {"steps": STEPS, "epochs": 1, "wall_ns": 10 ** 10,
            "counters": counters, "spans": {}}


def test_the_manifests_entry_is_appended_and_nothing_before_it_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    names = [m["name"] for m in now["per_layer"]]
    at = names.index(NAME)
    assert now["per_layer"][at] == ENTRY
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       NAME + ".py"))

    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args,
                              capture_output=True, text=True)
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    was = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    assert at == len(was["per_layer"])
    assert now["per_layer"][:at] == was["per_layer"]
    for key in was:
        if key != "per_layer":
            assert now[key] == was[key], key


@pytest.mark.parametrize("counters,want", [
    ({"kda.chunks": STEPS * 512, "kda.carry_kernel_chunks": STEPS * 512},
     512.0),
    ({"kda.chunks": STEPS * 512, "kda.carry_kernel_chunks": 0}, 0.0),
    ({"kda.chunks": STEPS * 512, "kda.kernel_chunks": STEPS * 512},
     None),                                   # the parent: no such counter
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
