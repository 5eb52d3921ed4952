"""The nemotron_h family and the cell of PR 32 under the harness, at a
size a test run can hold:

* the file is the published config cut as it says (every width the
  catalog row's);
* the counts against the reference's shapes and against XLA's own
  operation count;
* the program through `Module.fit` (the chunked scan) against
  `reference/nemotron_h.py` (the recurrence token by token) under the
  harness's own `check.compare`, in float32 and in bfloat16;
* the control (the reference in fp8), half the rows, and a program
  whose scan carries nothing from chunk to chunk come out not correct
  under the cell's limits;
* the traced line carries the scan's counters, the cell rehearses
  (`run.py` exits 3) and no file the benchmark had is changed.
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
from benchmark.counts import nemotron_h as counts  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402

CONFIG = "nemotron3-nano-ep16-l9"
CELL = CONFIG + ".fit-tokens-resident"
PARENT = "8de30a4dcfb951ec315c79020f2bb345c144774c"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


def _files():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfg = json.load(f)
    mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic",
                                    "fit-tokens-resident.json"))
    return cfg, mix


def _tiny(compute_dtype):
    cfg, mix = harness.tiny(*_files())
    cfg["compute_dtype"] = compute_dtype
    return cfg, mix


def _limits():
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        return json.load(f)["limits"]


def _run(cfg_mix, seed=7, trace=False):
    code, result = harness.run(ROOT, CELL, seed, 0.3, trace,
                               time.perf_counter(), cfg_mix=cfg_mix,
                               require_chip=False)
    assert code == 0
    return result


# what the model's config.json publishes (nvidia/NVIDIA-Nemotron-3-Nano-
# 30B-A3B-BF16), for where the catalog is not at hand
PUBLISHED = {
    "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
    "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "intermediate_size": 1856,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
    "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "layer_norm_epsilon": 1e-05, "mlp_hidden_act": "relu2",
    "mamba_hidden_act": "silu", "use_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "tie_word_embeddings": False, "model_type": "nemotron_h"}


def test_the_file_is_the_published_config_cut_as_it_says():
    cfg, _mix = _files()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"].split(" ")[0])
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert cfg["hybrid_override_pattern_published"] \
            == row["config"]["hybrid_override_pattern"]
        assert cfg["n_routed_experts_published"] \
            == row["config"]["n_routed_experts"]
        assert cfg["vocab_size_published"] == row["config"]["vocab_size"]
    assert cfg["reduced"] == REDUCED == list(cfg["reduced_why"])
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] == 8
    assert cfg["n_routed_experts_published"] == 128
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 131072
    # one whole period: published layers 35-43
    assert cfg["hybrid_override_pattern"] == "MEMEMEM*E" \
        == cfg["hybrid_override_pattern_published"][35:44]
    assert cfg["num_hidden_layers"] == 9
    assert len(cfg["hybrid_override_pattern_published"]) == 52
    assert "16 chips share each layer" in cfg["deployment"]
    for item in ("no_positions_in_attention", "bias_rule", "no_dt_clamp",
                 "learning_rate", "seq_len", "scan_float32_parts",
                 "initial_per_head_parameters"):
        assert len(cfg["assumed"][item]) > 40, item
    assert cfg["seq_len"] == cfg["per_chip_batch"] == 8192
    # the rehearsal: at least three chunks a sequence and two sequences
    tiny = cfg["rehearsal"]
    assert tiny["seq_len"] >= 3 * tiny["chunk_size"]
    assert tiny["per_chip_batch"] >= 2 * tiny["seq_len"]
    # the builder's arguments say the same as the keys the reference reads
    for group in (cfg, tiny):
        args = group["symbol_call"]["arguments"]
        for key in reference.ARCH_KEYS:
            ours = {"n_routed_experts_published": "n_routed_experts"}.get(
                key, key)
            want = group.get(key, cfg[key])
            assert args[ours] == want, (key, args[ours], want)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == CONFIG]
    assert entry[0]["reduced"] == REDUCED
    assert cfg["source"].startswith(entry[0]["source"])


def test_counts_follow_the_reference():
    cfg, _mix = _files()
    arch = reference.arch_of(cfg)
    shapes, aux = reference.param_shapes(arch)
    assert counts.n_parameters(arch) == sum(
        math.prod(s) for s in shapes.values()) == 666962944
    assert set(aux) == {"l%d_moe_router_bias" % i for i in (1, 3, 5, 8)}
    assert counts.held_pairs_per_step(arch, 8192) == 4 * 3072
    # ISSUE 32's figures: MFLOP a token forward by layer, TFLOP a step
    flop = lambda kind: 2e-6 * sum(  # noqa: E731
        p[1] for p in counts.layer_products(arch, kind))
    assert flop("M") == pytest.approx(80.2, abs=0.05)
    assert flop("*") == pytest.approx(113.9, abs=0.05)
    assert flop("E") == pytest.approx(48.1, abs=0.05)
    assert 2e-6 * counts.scan_macs(arch) == pytest.approx(2.76, abs=0.005)
    step = 8192 * counts.train_flops_per_image(arch)
    assert step == pytest.approx(17.57e12, rel=1e-3)
    assert counts.scan_flops(arch, 8192) == 3 * 2 * 8192 * counts.scan_macs(arch)
    # one scan's least bytes: x, dt, B, C three times, y twice, bfloat16
    assert counts.scan_least_bytes(arch, 8192, "bfloat16") \
        == 8192 * (3 * (4096 + 64 + 2048) + 2 * 4096) * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    bounds = counts.step_bounds(arch, 8192, "bfloat16", peaks)
    assert bounds["bound"] == "operations"
    assert bounds["ops_s"] == pytest.approx(0.0892, abs=2e-4)
    # with every token choosing every expert held, a token meets every
    # weight of a product once: the counts are then the reference's
    # shapes plus attention's two products and the scans' four
    every = dict(arch, num_experts_per_tok=arch["n_routed_experts_published"])
    weights_met = sum(math.prod(shapes[k]) for k in reference.products(arch))
    inner = 2 * 4096.5 * 32 * 128 + 4 * counts.scan_macs(arch)
    assert counts.forward_macs_per_token(every) == weights_met + inner


def test_counts_stay_under_what_xla_counts():
    """XLA's own count of the program's forward pass at the rehearsal's
    size lies over the shape-derived count and within a stated band of
    it, 1.0 to 2.5 times: XLA counts the scan's and attention's
    products whole and not their causal halves, the expert layer over
    its worst-case rows (2 pairs a token here against 1 at even
    routing), and the elementwise work, which at a width of 32 is as
    large as the products."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import executor
    cfg, _mix = _tiny(None)
    arch = reference.arch_of(cfg)
    rows = cfg["per_chip_batch"]
    sym = harness.build_symbol(cfg)
    shapes, _, aux_shapes = sym.infer_shape(data=(rows,),
                                            softmax_label=(rows,))
    ev, _ = executor._build_eval(sym)
    fn = jax.jit(lambda a, x: ev(a, x, jax.random.PRNGKey(0), True)[0][0])
    cost = fn.lower([jnp.zeros(s, jnp.float32) for s in shapes],
                    [jnp.zeros(s, jnp.float32) for s in aux_shapes]
                    ).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = 2 * rows * counts.forward_macs_per_token(arch)
    assert 1.0 <= cost["flops"] / ours <= 2.5, cost["flops"] / ours


def test_reference_agrees_with_the_program_in_float32():
    """Two algorithms, one answer: the chunked scan over three chunks
    and two sequences against the recurrence, values and gradients."""
    result = _run(_tiny(None))
    got = {k: v["value"] for k, v in result["check"].items()}
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert got[name] < 1e-5, (name, got[name])
    for name in ("grad_gap", "change_gap", "grad_median_gap",
                 "change_median_gap", "stat_gap"):
        assert got[name] < 1e-3, (name, got[name])
    assert got["window_compiles"] == 0
    assert result["correct"] is True, got
    assert result["failed"] == 0 and result["attempted"] > 0


def test_program_in_bfloat16_stays_near_the_reference():
    """The stated compute type at the rehearsal's size.  The cell's
    limits were read at 8,192 tokens and 2,688 wide and do not hold 48
    tokens 32 wide (a leaf has a few hundred values, the per-head
    parameters four): here the numbers only have to be rounding."""
    result = _run(_tiny("bfloat16"))
    got = {k: v["value"] for k, v in result["check"].items()}
    for name, most in (("loss1_gap", 5e-3), ("loss3_gap", 0.1),
                       ("grad_median_gap", 0.03), ("change_median_gap", 0.06),
                       ("grad_gap", 0.5), ("change_gap", 0.8)):
        assert got[name] < most, (name, got[name])
    assert abs(got["grad_shrink"]) < 0.02
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
            "half": _reference_readings(reference.Exact, rows=24),
            "short": _reference_readings(reference.Exact, rows=12)}


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


@pytest.mark.parametrize("which", ["half", "short"])
def test_rows_left_out_are_not_correct(readings, which):
    """Half the rows (one sequence of the two), and rows that are no
    whole sequence (the cell's own half batch is half of its one)."""
    ok, rows = _judged(readings[which], readings["f32"])
    assert not ok, rows


def test_a_carry_left_out_of_the_program_is_not_correct(monkeypatch):
    """The planted fault of this family: a scan whose chunks each start
    from nothing, held against the reference's recurrence."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm
    monkeypatch.setattr(ssm, "_carry",
                        lambda own, decay: jnp.zeros_like(own))
    result = _run(_tiny(None), seed=13)
    got = {k: v["value"] for k, v in result["check"].items()}
    assert result["correct"] is False, got
    limits = _limits()
    assert any(got[k] > 3 * limits[k] for k in ("grad_median_gap",
                                                "change_median_gap")), got


def test_traced_line_carries_the_scans_counters():
    """The readers of a `--trace 1` run, on the CPU (no device plane,
    so the trace's own metrics stay out): what the scans counted, a
    step."""
    result = _run(_tiny(None), trace=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # 4 Mamba-2 layers x 2 sequences x 3 chunks; states of 4 x 8 x 16
    assert got["ssm_chunks_per_step"] == 24
    assert got["ssm_carried_state_mib_per_step"] \
        == 24 * 4 * 8 * 16 * 4 / 2 ** 20
    assert got["window_compiles"] == 0


def test_new_readers_read_nothing_of_a_program_without_the_scan(monkeypatch):
    """Laid over the parent, whose report has no `ssm.*` counter, the
    readers return nothing and do not raise."""
    from mxnet_tpu import telemetry
    monkeypatch.setattr(telemetry, "last_fit", lambda: {
        "steps": 3, "spans": {}, "counters": {"moe.held_pairs": 9.0}})
    for name in ("ssm_chunks_per_step", "ssm_carried_state_mib_per_step"):
        assert harness.load_reader(ROOT, name)({"steps": 3}) is None
    monkeypatch.setattr(telemetry, "last_fit", lambda: {
        "steps": 3, "spans": {}, "counters": {"ssm.chunks": 768.0,
                                              "ssm.carried_bytes": 3 * 2.0 ** 29}})
    assert harness.load_reader(ROOT, "ssm_chunks_per_step")({"steps": 3}) == 256
    assert harness.load_reader(ROOT, "ssm_carried_state_mib_per_step")(
        {"steps": 3}) == 512


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


def test_the_manifest_gains_one_configuration_one_cell_two_metrics():
    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args,
                              capture_output=True, text=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    cell = [w for w in now["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == CONFIG
    assert cell[0]["traffic"] == "fit-tokens-resident"
    for name in ("ssm_chunks_per_step", "ssm_carried_state_mib_per_step"):
        entry = [m for m in now["per_layer"] if m["name"] == name][0]
        assert CELL in entry["workloads"]
        assert (entry["layer"], entry["moves"], entry["source"]) \
            == ("kernels", "img_per_s", "program_counter")
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
    for entry in now["configs"] + now["workloads"]:
        assert len(entry["why"]) <= 200
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history with the parent commit here")
    was = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert now[key] == was[key]
    for key, more in (("configs", 1), ("workloads", 1), ("per_layer", 2)):
        assert now[key][:len(was[key])] == was[key]
        assert len(now[key]) >= len(was[key]) + more
