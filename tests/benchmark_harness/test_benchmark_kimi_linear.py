"""The kimi_linear family and the cell of PR 34 under the harness, at a
size a test run can hold:

* the file is the published config cut as it says (every width the
  catalog row's);
* the counts against the reference's shapes and against XLA's own
  operation count;
* the program through `Module.fit` (the chunked delta rule, the
  library's or the blockwise attention) against
  `reference/kimi_linear.py` (the recurrence token by token, the plain
  softmax) under the harness's own `check.compare`, in float32 and in
  bfloat16;
* the control (the reference in fp8), half the rows, and programs with
  a planted fault (a carry that passes nothing on, beta taken as 0, the
  keys' shared columns left out) come out not correct under the cell's
  limits;
* the traced line carries the three counters, the cell rehearses
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
from benchmark.counts import kimi_linear as counts  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402

CONFIG = "kimi-linear-ep32-l5"
CELL = CONFIG + ".fit-tokens-resident"
PARENT = "7c31a0226f97708c47275d9e03cad27032fe28dc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
METRICS = ("kda_chunks_per_step", "kda_carried_state_mib_per_step",
           "mla_expanded_kv_mib_per_step")


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


# what the model's config.json publishes (moonshotai/Kimi-Linear-48B-A3B-
# Instruct), for where the catalog is not at hand
PUBLISHED = {
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "q_lora_rank": None, "num_attention_heads": 32,
    "num_key_value_heads": 32, "head_dim": 72, "mla_use_nope": True,
    "moe_intermediate_size": 1024, "num_experts_per_token": 8,
    "num_shared_experts": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "routed_scaling_factor": 2.446,
    "num_expert_group": 1, "topk_group": 1, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
    "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": False, "model_type": "kimi_linear"}
PUBLISHED_LINEAR = {"head_dim": 128, "num_heads": 32,
                    "short_conv_kernel_size": 4}


def test_the_file_is_the_published_config_cut_as_it_says():
    cfg, _mix = _files()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    linear, whole = cfg["linear_attn_config"], \
        cfg["linear_attn_config_published"]
    for key, value in PUBLISHED_LINEAR.items():
        assert linear[key] == whole[key] == value, key
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"].split(" ")[0])
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert whole == row["config"]["linear_attn_config"]
        assert cfg["num_experts_published"] == row["config"]["num_experts"]
        assert cfg["vocab_size_published"] == row["config"]["vocab_size"]
        assert cfg["num_hidden_layers_published"] \
            == row["config"]["num_hidden_layers"]
    assert cfg["reduced"] == REDUCED == list(cfg["reduced_why"])
    assert cfg["num_experts"] == cfg["experts_held"][1] == 8
    assert cfg["num_experts_published"] == 256
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 163840
    # published layers 1 and 5-8: the leading dense layer and one whole
    # period, renumbered 1-5
    assert cfg["num_hidden_layers"] == 5
    assert cfg["num_hidden_layers_published"] == 27
    kept = [1, 5, 6, 7, 8]
    assert [i + 1 for i, n in enumerate(kept) if n in whole["kda_layers"]] \
        == linear["kda_layers"] == [1, 2, 3, 4]
    assert [i + 1 for i, n in enumerate(kept)
            if n in whole["full_attn_layers"]] \
        == linear["full_attn_layers"] == [5]
    assert sorted(whole["kda_layers"] + whole["full_attn_layers"]) \
        == list(range(1, 28))
    assert "32 chips share each layer" in cfg["deployment"]
    for item in ("no_positions_at_all", "short_conv_no_bias",
                 "low_rank_gates_no_bias", "A_log_and_dt_bias",
                 "q_scale_and_l2_norm", "output_norm", "chunk_size",
                 "bias_rule", "learning_rate", "seq_len",
                 "delta_rule_float32_parts", "initial_per_head_parameters"):
        assert len(cfg["assumed"][item]) > 40, item
    assert cfg["seq_len"] == cfg["per_chip_batch"] == 8192
    assert cfg["chunk_size"] == 64
    # the rehearsal: at least three chunks a sequence and two sequences
    tiny = cfg["rehearsal"]
    assert tiny["seq_len"] >= 3 * tiny["chunk_size"]
    assert tiny["per_chip_batch"] >= 2 * tiny["seq_len"]
    # the builder's arguments say the same as the keys the reference reads
    for group in (cfg, dict(cfg, **tiny)):
        args = group["symbol_call"]["arguments"]
        for key, want in reference.arch_of(group).items():
            ours = {"num_experts_published": "num_experts"}.get(key, key)
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
        math.prod(s) for s in shapes.values()) == 602433408
    assert set(aux) == {"l%d_moe_router_bias" % i for i in (2, 3, 4, 5)}
    assert counts.held_pairs_per_step(arch, 8192) == 4 * 2048
    # ISSUE 34's figures: M multiply-adds a token forward by layer
    macs = lambda mixer, dense: 1e-6 * sum(  # noqa: E731
        p[1] for p in counts.layer_products(arch, mixer, dense))
    assert macs("kda", True) == pytest.approx(105.4, abs=0.05)
    assert macs("kda", False) == pytest.approx(51.1, abs=0.05)
    assert macs("mla", False) == pytest.approx(80.5, abs=0.05)
    assert 1e-6 * counts.kda_macs(arch) == pytest.approx(2.226, abs=0.001)
    assert 1e-6 * counts.mla_attention_macs(arch) \
        == pytest.approx(41.95, abs=0.005)
    step = 8192 * counts.train_flops_per_image(arch)
    assert step == pytest.approx(18.99e12, rel=1e-3)
    assert counts.kda_flops(arch, 8192) == 3 * 2 * 8192 * counts.kda_macs(arch)
    assert counts.mla_attention_flops(arch, 8192) \
        == 3 * 2 * 8192 * 4096.5 * 32 * (192 + 128)
    # one delta rule's least bytes: q, k, v, gate, beta three times, o
    # twice, bfloat16
    assert counts.kda_least_bytes(arch, 8192, "bfloat16") \
        == 8192 * (3 * (4 * 4096 + 32) + 2 * 4096) * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    bounds = counts.step_bounds(arch, 8192, "bfloat16", peaks)
    assert bounds["bound"] == "operations"
    assert bounds["ops_s"] == pytest.approx(0.0964, abs=2e-4)
    # with every token choosing every expert held, a token meets every
    # weight of a product once: the counts are then the reference's
    # shapes plus attention's two products and the delta rules' own
    every = dict(arch, num_experts_per_token=arch["num_experts_published"])
    weights_met = sum(math.prod(shapes[k]) for k in reference.products(arch))
    inner = counts.mla_attention_macs(arch) + 4 * counts.kda_macs(arch)
    assert counts.forward_macs_per_token(every) == weights_met + inner


def test_counts_stay_under_what_xla_counts():
    """XLA's own count of the program's forward pass at the rehearsal's
    size lies over the shape-derived count and within a stated band of
    it, 1.0 to 3.5 times: XLA counts the delta rule's and attention's
    products whole and not their causal halves, the solve and the
    running sums as it lowers them, the expert layer over its
    worst-case rows (2 pairs a token here against 1 at even routing),
    and the elementwise work, which at a width of 32 is as large as the
    products."""
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
    assert 1.0 <= cost["flops"] / ours <= 3.5, cost["flops"] / ours


def test_reference_agrees_with_the_program_in_float32():
    """Two algorithms, one answer: the chunked delta rule over three
    chunks and two sequences against the recurrence, blockwise
    attention against the plain softmax, values and gradients."""
    result = _run(_tiny(None))
    got = {k: v["value"] for k, v in result["check"].items()}
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert got[name] < 1e-4, (name, got[name])
    for name in ("grad_gap", "grad_median_gap", "change_median_gap",
                 "stat_gap"):
        assert got[name] < 1e-3, (name, got[name])
    # the worst leaf after three steps: a token that changes expert on a
    # difference of rounding moves it (48 tokens here)
    assert got["change_gap"] < 0.05, got["change_gap"]
    assert got["window_compiles"] == 0
    assert result["correct"] is True, got
    assert result["failed"] == 0 and result["attempted"] > 0


def test_program_in_bfloat16_stays_near_the_reference():
    """The stated compute type at the rehearsal's size.  The cell's
    limits were read at 8,192 tokens and 2,304 wide and do not hold 48
    tokens 32 wide (a leaf has a few hundred values, the per-head
    parameters four): here the numbers only have to be rounding.  At
    this size they swing with the seed (seeds 7 and 11-16:
    `grad_median_gap` 0.04-0.14, `change_median_gap` 0.05-0.15, where an
    expert's choice flips after the first update); seed 12 reads in
    their middle."""
    result = _run(_tiny("bfloat16"), seed=12)
    got = {k: v["value"] for k, v in result["check"].items()}
    for name, most in (("loss1_gap", 0.03), ("loss3_gap", 0.1),
                       ("grad_median_gap", 0.1), ("change_median_gap", 0.15)):
        assert got[name] < most, (name, got[name])
    assert abs(got["grad_shrink"]) < 0.08
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


def _no_carry(monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm
    real = ssm._delta_carry

    def cut(w, u, k_end, decay):
        s_in, _ = real(w, u, k_end, decay)
        return jnp.zeros_like(s_in), u      # every chunk starts from nothing
    monkeypatch.setattr(ssm, "_delta_carry", cut)


def _beta_zero(monkeypatch):
    """The correction left out: S_t = Diag(alpha_t) S_{t-1} + beta_t k_t
    v_t^T, a state that only decays and sums and is never told what it
    already holds.  In the chunked form: no Akk in the system (the solve
    hands back its right-hand side) and no W S_0 taken off U."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm
    products, carry = ssm._decayed_products, ssm._delta_carry
    monkeypatch.setattr(ssm, "_decayed_products", lambda rows, k, G: [
        jnp.zeros_like(a) if i == 0 else a
        for i, a in enumerate(products(rows, k, G))])
    monkeypatch.setattr(ssm, "_delta_carry", lambda w, u, k_end, decay:
                        carry(jnp.zeros_like(w), u, k_end, decay))


def _no_shared_key(monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu import registry
    op = registry.get_op("LatentExpand")
    real = op.fcompute
    monkeypatch.setattr(
        op, "fcompute", lambda attrs, ins, octx: real(
            attrs, [ins[0], jnp.zeros_like(ins[1])], octx))


@pytest.mark.parametrize("plant", [_no_carry, _beta_zero, _no_shared_key],
                         ids=["no_carry", "beta_zero", "no_shared_key"])
def test_a_planted_fault_in_the_program_is_not_correct(plant, monkeypatch):
    """The planted faults of this family, each held against the
    reference: a delta rule whose chunks each start from nothing; one
    whose correction is left out (the state only decays and sums); keys
    whose 64 shared columns are left out of the attention."""
    plant(monkeypatch)
    result = _run(_tiny(None), seed=13)
    got = {k: v["value"] for k, v in result["check"].items()}
    assert result["correct"] is False, got
    limits = _limits()
    assert any(got[k] > 3 * limits[k] for k in ("grad_median_gap",
                                                "change_median_gap")), got


def test_traced_line_carries_the_three_counters():
    """The readers of a `--trace 1` run, on the CPU (no device plane,
    so the trace's own metrics stay out): what the ops counted, a
    step."""
    result = _run(_tiny(None), trace=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # 4 delta-rule layers x 2 sequences x 3 chunks; states of 4 x 8 x 8
    assert got["kda_chunks_per_step"] == 24
    assert got["kda_carried_state_mib_per_step"] \
        == 24 * 4 * 8 * 8 * 4 / 2 ** 20
    # 48 rows x 4 heads x (8 + 4 + 8) float32
    assert got["mla_expanded_kv_mib_per_step"] == 48 * 4 * 20 * 4 / 2 ** 20
    assert got["window_compiles"] == 0


def test_new_readers_read_nothing_of_a_program_without_the_ops(monkeypatch):
    """Laid over the parent, whose report has no `kda.*` or `mla.*`
    counter, the readers return nothing and do not raise."""
    from mxnet_tpu import telemetry
    monkeypatch.setattr(telemetry, "last_fit", lambda: {
        "steps": 3, "spans": {}, "counters": {"moe.held_pairs": 9.0,
                                              "ssm.chunks": 768.0}})
    for name in METRICS:
        assert harness.load_reader(ROOT, name)({"steps": 3}) is None
    monkeypatch.setattr(telemetry, "last_fit", lambda: {
        "steps": 3, "spans": {}, "counters": {
            "kda.chunks": 3 * 512.0, "kda.carried_bytes": 3 * 2.0 ** 30,
            "mla.expanded_kv_bytes": 3 * 160 * 2.0 ** 20}})
    read = lambda name: harness.load_reader(ROOT, name)(  # noqa: E731
        {"steps": 3})
    assert read("kda_chunks_per_step") == 512
    assert read("kda_carried_state_mib_per_step") == 1024
    assert read("mla_expanded_kv_mib_per_step") == 160


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


def test_the_manifest_gains_one_configuration_one_cell_three_metrics():
    def git(*args):
        return subprocess.run(("git", "-C", ROOT) + args,
                              capture_output=True, text=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)
    cell = [w for w in now["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == CONFIG
    assert cell[0]["traffic"] == "fit-tokens-resident"
    for name in METRICS:
        entry = [m for m in now["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL]
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
    for key, more in (("configs", 1), ("workloads", 1), ("per_layer", 3)):
        assert now[key][:len(was[key])] == was[key]
        assert len(now[key]) >= len(was[key]) + more
    # and every file the benchmark had is as it was
    changed = git("diff", "--name-status", PARENT, "--", "benchmark",
                  "tests/benchmark_harness").stdout.split("\n")
    assert [line for line in changed if line and not line.startswith("A")] \
        == []
