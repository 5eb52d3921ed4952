"""BENCHMARK.json against the files it names and the contract's shapes."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _exists(*parts):
    return os.path.isfile(os.path.join(ROOT, *parts))


def test_keys_and_paths(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark_harness"]
    assert _exists(manifest["command"][1])
    assert manifest["command"][1].startswith("benchmark/")
    assert 1 <= manifest["run_seconds"] <= 51


def test_every_named_thing_has_its_file(manifest):
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/") and _exists(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    configs = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert w["config"] in configs
        assert w["name"] == w["config"] + "." + w["traffic"]
        assert _exists("benchmark", "traffic", w["traffic"] + ".json")
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            feed = json.load(f)["feed"]
        assert NAME.match(feed) and _exists("benchmark", "traffic",
                                            feed + ".py")
        assert _exists("benchmark", "limits", w["name"] + ".json")
    for m in manifest["per_layer"]:
        assert _exists("benchmark", "metrics", m["name"] + ".py")
    assert {c["name"] for c in manifest["configs"]} == \
        {w["config"] for w in manifest["workloads"]}


def test_every_feed_kind_is_a_file_found_by_name():
    import sys
    sys.path.insert(0, ROOT)
    from benchmark import traffic
    kinds = [f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark",
                                                     "traffic"))
             if f.endswith(".py")]
    assert {"recordio", "resident"} <= set(kinds)
    for feed in kinds:
        mod = traffic.kind(feed)
        for offered in ("make_feed", "reference_batches", "own_batches"):
            assert callable(getattr(mod, offered)), (feed, offered)
    with pytest.raises(ValueError, match="no file"):
        traffic.kind("no-such-feed")


def test_nothing_rides_along_unused(manifest):
    """Every file under configs/, traffic/*.json, limits/ and metrics/
    belongs to an entry of the manifest."""
    def names(sub, ext):
        return {f[:-len(ext)] for f in os.listdir(
            os.path.join(ROOT, "benchmark", sub)) if f.endswith(ext)}
    assert names("configs", ".json") == {c["name"] for c in manifest["configs"]}
    assert names("traffic", ".json") == {w["traffic"]
                                         for w in manifest["workloads"]}
    assert names("limits", ".json") == {w["name"]
                                        for w in manifest["workloads"]}
    assert names("metrics", ".py") == {m["name"]
                                       for m in manifest["per_layer"]}


def test_names_units_and_lengths(manifest):
    entries = manifest["configs"] + manifest["workloads"] \
        + manifest["end_to_end"] + manifest["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "source", "layer"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in manifest["workloads"]]
    assert len(cells) == len(set(cells))
    assert len(json.dumps(manifest)) < 64 * 1024


def test_end_to_end_and_what_moves_it(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert set(e2e) == {"img_per_s", "setup_s"}
    assert e2e["setup_s"]["bound"] <= 0.1
    assert 0.01 <= e2e["img_per_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] == "img_per_s"
        assert set(m.get("workloads", [])) <= cells
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert any("mfu" in m["name"].split("_") for m in manifest["per_layer"])


def test_four_chip_cells(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_limits_files(manifest):
    for w in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "limits",
                               w["name"] + ".json")) as f:
            limits = json.load(f)["limits"]
        assert limits, w["name"]
        # the first gradient, the parameters' change and BatchNorm's
        # moving statistics each carry a limit in every cell
        assert {"grad_median_gap", "change_median_gap",
                "stat_median_gap"} <= set(limits), w["name"]
        for name, limit in limits.items():
            assert NAME.match(name) and limit >= 0
