"""The afmoe family and the cell of PR 27 under the harness, at a size
a test run can hold:

* the program through `Module.fit` against `reference/afmoe.py` under
  the harness's own `check.compare` (float32 on the CPU, so routing is
  the reference's and the gaps are rounding), and the same in bfloat16;
* the control (the reference in fp8), half the rows, and a selection
  bias that is no longer written back come out not correct;
* the counts against the reference's shapes and against XLA;
* the new cell rehearses (`run.py` exits 3) and no file the benchmark
  had is changed.
"""
import json
import math
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check, harness, traffic, weights  # noqa: E402
from benchmark.counts import afmoe as counts  # noqa: E402
from benchmark.reference import afmoe as reference  # noqa: E402

CELL = "trinity-mini-ep8-l5.fit-tokens-resident"
PARENT = "8796c49fb3bf6d6a41c0b3ac32d3d27e07d435ec"


def _files():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-ep8-l5.json")) as f:
        cfg = json.load(f)
    mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic",
                                    "fit-tokens-resident.json"))
    return cfg, mix


def _tiny(compute_dtype):
    cfg, mix = harness.tiny(*_files())
    cfg["compute_dtype"] = compute_dtype
    return cfg, mix


def _limits(cell=CELL):
    with open(os.path.join(ROOT, "benchmark", "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def _run(cell, cfg_mix, seed=7):
    code, result = harness.run(ROOT, cell, seed, 0.3, False,
                               time.perf_counter(), cfg_mix=cfg_mix,
                               require_chip=False)
    assert code == 0
    return result


def test_the_file_is_the_published_config_cut_as_it_says():
    cfg, _mix = _files()
    # every width as published (catalog: arcee-ai/Trinity-Mini config.json)
    published = {"head_dim": 128, "hidden_size": 2048,
                 "intermediate_size": 6144, "moe_intermediate_size": 1024,
                 "num_attention_heads": 32, "num_key_value_heads": 4,
                 "num_experts_per_tok": 8, "num_shared_experts": 1,
                 "sliding_window": 2048, "rope_theta": 10000,
                 "route_scale": 2.826, "load_balance_coeff": 0.001,
                 "rms_norm_eps": 1e-05, "score_func": "sigmoid",
                 "route_norm": True, "num_experts_published": 128,
                 "vocab_size_published": 200192}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    assert cfg["num_experts"] == cfg["experts_held"][1] == 16
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["layer_types"][-4:] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 6
    # the builder's arguments say the same as the keys the reference reads
    for group in (cfg, cfg["rehearsal"]):
        args = group["symbol_call"]["arguments"]
        for key in reference.ARCH_KEYS:
            ours = {"num_experts_published": "num_experts"}.get(key, key)
            want = group.get(key, cfg[key])
            assert args[ours] == want, (key, args[ours], want)


def test_counts_follow_the_reference_and_xla():
    cfg, _mix = _files()
    arch = reference.arch_of(cfg)
    shapes, aux = reference.param_shapes(arch)
    assert counts.n_parameters(arch) == sum(
        math.prod(s) for s in shapes.values())
    assert 700e6 < counts.n_parameters(arch) < 712e6
    assert set(aux) == {"l%d_moe_router_bias" % i for i in (1, 2, 3, 4)}
    assert counts.mean_keys(4096, 2048) == pytest.approx(1536.25)
    assert counts.mean_keys(4096, 0) == pytest.approx(2048.5)
    assert counts.held_pairs_per_step(arch, 8192) == 8192 * 8 * 16 / 128 * 4
    assert 16.5e12 < 8192 * counts.train_flops_per_image(arch) < 17.5e12
    # with every token choosing every expert held, a token meets every
    # weight of a product once: the counts are then the reference's
    # shapes plus attention's two products under their masks
    every = dict(arch, num_experts_per_tok=arch["num_experts_published"])
    weights_met = sum(math.prod(shapes[k]) for k in reference.products(arch))
    attention = sum(
        2 * counts.mean_keys(4096, 2048 if kind == "sliding_attention"
                             else 0) * 32 * 128
        for kind in arch["layer_types"])
    assert counts.forward_macs_per_token(every) == weights_met + attention
    # at even routing a token meets 8 * 16 / 128 = one expert's weights
    one_expert = 3 * 2048 * 1024
    assert counts.forward_macs_per_token(every) \
        - counts.forward_macs_per_token(arch) == 4 * 15 * one_expert


def test_reference_agrees_with_the_program_in_float32():
    result = _run(CELL, _tiny(None))
    got = {k: v["value"] for k, v in result["check"].items()}
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert got[name] < 1e-5, (name, got[name])
    for name in ("grad_gap", "change_gap", "grad_median_gap",
                 "change_median_gap", "stat_gap"):
        assert got[name] < 2e-3, (name, got[name])
    assert got["window_compiles"] == 0
    assert result["correct"] is True, got
    assert result["failed"] == 0 and result["attempted"] > 0


def test_program_in_bfloat16_stays_near_the_reference():
    """The stated compute type at the rehearsal's size.  The cell's
    limits were read at 8,192 tokens over 128 experts and do not hold
    32 tokens over 8 (there the loads sit within a token of their mean,
    so one rounded score flips the bias rule's sign, and a leaf has a
    few hundred values): here the numbers only have to be rounding."""
    result = _run(CELL, _tiny("bfloat16"))
    got = {k: v["value"] for k, v in result["check"].items()}
    for name, most in (("loss1_gap", 5e-3), ("loss3_gap", 0.1),
                       ("grad_median_gap", 0.02), ("change_median_gap", 0.05),
                       ("grad_gap", 0.2), ("change_gap", 0.3)):
        assert got[name] < most, (name, got[name])
    assert abs(got["grad_shrink"]) < 0.01
    assert got["window_compiles"] == 0


def _reference_readings(arith, rows=None, seed=11):
    cfg, mix = _tiny(None)
    arch = reference.arch_of(cfg)
    args, aux = weights.make(seed, *reference.param_shapes(arch))
    batches = traffic.own_batches(mix, cfg, seed, 1, harness.CHECK_STEPS)
    if rows is not None:
        batches = [(x[:rows], y[:rows]) for x, y in batches]
    opt = {k: cfg["optimizer"][k] for k in ("learning_rate", "momentum", "wd")}
    return reference.follow(args, aux, batches, arch, opt, arith=arith)


@pytest.fixture(scope="module")
def readings():
    return {"f32": _reference_readings(reference.Exact),
            "fp8": _reference_readings(check.Fp8),
            "half": _reference_readings(reference.Exact, rows=16)}


def _judged(got, want):
    cfg, _ = _tiny(None)
    numbers = check.compare(got, want,
                            reference.products(reference.arch_of(cfg)))
    numbers["window_compiles"] = (0, "")
    return check.judge(numbers, dict(_limits(), window_compiles=0))


def test_control_is_not_correct(readings):
    ok, rows = _judged(readings["fp8"], readings["f32"])
    assert not ok, rows
    same, rows = _judged(readings["f32"], readings["f32"])
    assert same, rows


def test_half_the_rows_are_not_correct(readings):
    ok, rows = _judged(readings["half"], readings["f32"])
    assert not ok, rows


def test_a_selection_bias_not_written_back_is_not_correct(monkeypatch):
    from mxnet_tpu.module.mesh_executor_group import MeshExecutorGroup
    monkeypatch.setattr(MeshExecutorGroup, "_write_aux",
                        lambda self, new_aux: None)
    result = _run(CELL, _tiny(None))
    assert result["correct"] is False
    assert result["check"]["stat_median_gap"]["value"] > 0.9


def test_traced_line_carries_the_cells_counters():
    """The readers of a `--trace 1` run, on the CPU (no device plane,
    so the trace's own metrics stay out): what the expert layers
    counted, a step."""
    code, result = harness.run(ROOT, CELL, 7, 0.3, True, time.perf_counter(),
                               cfg_mix=_tiny(None), require_chip=False)
    assert code == 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # 4 expert layers x 32 tokens x 2 choices, 4 of 8 experts held
    assert 0 < got["moe_held_pairs_per_step"] < 256
    assert got["moe_load_max_over_mean"] >= 1
    assert got["moe_dropped_pairs_per_step"] == 0


def test_new_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4700000077", "--seconds", "1",
         "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == harness.EXIT_REHEARSAL, done.stderr[-2000:]
    assert "[bench] correct:" in done.stderr    # the check ran to its end
    assert done.stdout.strip() == ""        # a rehearsal prints no result


def test_no_file_the_benchmark_had_is_changed():
    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args,
                              capture_output=True, text=True)
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    changed = git("diff", "--name-only", "--diff-filter=MDRT", PARENT, "--",
                  "benchmark", "tests/benchmark_harness").stdout.split()
    assert changed == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    was = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert now[key] == was[key]
    for key in ("configs", "workloads", "per_layer"):
        assert now[key][:len(was[key])] == was[key]
