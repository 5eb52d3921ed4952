"""tools/trace_by_node.py: device time of a trace by symbol node."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import trace_by_node  # noqa: E402
from benchmark import trace_reduce  # noqa: E402


def test_device_time_by_symbol_node_reads_the_traces_hlo(tmp_path):
    """The op_name of every instruction from the HLO proto a trace
    stores (read by hand: no generated protobuf classes here), nested
    events counted once, groups by the innermost node."""

    def f(x, w):
        with jax.named_scope("l3_attn"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("head"):
            return jnp.sum(y @ w.T)
    g = jax.jit(jax.grad(f, 1))
    x = w = jnp.ones((32, 32))
    g(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x, w).block_until_ready()
    jax.profiler.stop_trace()
    with open(trace_reduce.find_xplane(str(tmp_path)), "rb") as fh:
        protos = trace_by_node.hlo_protos(fh.read())
    ours = [v for k, v in protos.items() if k.startswith("jit_f(")]
    assert len(ours) == 1
    names = trace_by_node.op_names(ours[0])
    groups = {trace_by_node.group_of(v) for v in names.values() if v}
    assert {"attention", "lm_head"} <= groups
    assert any("transpose(jvp(l3_attn))" in v for v in names.values())
    # the arithmetic, on events that nest as a conditional's do
    assert trace_by_node.group_of("jit(s)/transpose(jvp(l2_moe))/while/mul") \
        == "moe"
    assert trace_by_node.group_of("jit(s)/l1_q_rope/mul") == trace_by_node.REST
    events = [("%cond.1 = f32[] conditional(...)", 0, 100),
              ("%fusion.2 = f32[] fusion(...)", 10, 40),
              ("%fusion.3 = f32[] fusion(...)", 50, 60),
              ("%copy.4 = f32[] copy(...)", 100, 130)]
    by, unnamed, by_instruction = trace_by_node.split_by_group(
        events, {"cond.1": "jit(s)/l1_moe/cond", "fusion.2": "jit(s)/l1_moe/a",
                 "fusion.3": "jit(s)/jvp(l0_attn)/b", "copy.4": ""})
    assert by == {("moe", "forward"): pytest.approx(90e-9),
                  ("attention", "forward"): pytest.approx(10e-9),
                  (trace_by_node.REST, "forward"): pytest.approx(30e-9)}
    assert unnamed == pytest.approx(30e-9)
    assert by_instruction["cond.1"][:3] == [pytest.approx(60e-9), 1,
                                            "jit(s)/l1_moe/cond"]
    # the whole trace, on the CPU: no device plane, nothing to reduce
    assert trace_by_node.reduce_by_group(
        trace_reduce.find_xplane(str(tmp_path))) is None


STEP = "jit(train_step)/"
BACK = STEP + "transpose(jvp(jvp()))/checkpoint/"


@pytest.mark.parametrize("op_name,group,which", [
    # a Mamba-2 layer's nodes as the remat step of models/nemotron_h.py
    # spells them (read from a CPU trace of the tiny model)
    (STEP + "jvp(l0_conv)/mx.ssm.conv/mul", "conv", "forward"),
    (BACK + "l0_conv/mx.ssm.conv/reduce_sum", "conv", "backward"),
    (BACK + "rematted_computation/l2_conv_silu/logistic", "conv",
     "made again"),
    (STEP + "jvp(l0_ssd)/mx.ssm.intra/bcgrqs,bcsgrp->bcqgrp/dot_general",
     "scan.intra", "forward"),
    (BACK + "l6_ssd/mx.ssm.carry/while/body/closed_call/mul", "scan.carry",
     "backward"),
    (BACK + "rematted_computation/l0_ssd/jit(softplus)/log1p", "scan",
     "made again"),
    (BACK + "l4_in/dot_general", "mamba_proj", "backward"),
    (STEP + "jvp(l4_out)/dot_general", "mamba_proj", "forward"),
    (BACK + "l0_ssm_norm/mx.ssm.norm/rsqrt", "ssm_norm", "backward"),
    (STEP + "jvp(l1_shared_up)/dot_general", "shared_ffn", "forward"),
    (BACK + "l7_q/dot_general", "attn_proj", "backward"),
    # the innermost node wins; a slice of the projection is no group
    (STEP + "jvp(l0_in)/jvp(l0_conv)/mx.ssm.conv/add", "conv", "forward"),
    (STEP + "jvp(l0_xbc)/slice", trace_by_node.REST, "forward"),
    # the update names neither a node nor a pass
    (STEP + "sgd_update/mul", trace_by_node.REST, "forward"),
])
def test_mamba_nodes_and_the_pass_from_the_path(op_name, group, which):
    assert trace_by_node.group_of(op_name) == group
    assert trace_by_node.pass_of(op_name) == which


@pytest.mark.parametrize("op_name,group,which", [
    # a delta-rule and a latent-attention layer's nodes as the remat
    # step of models/kimi_linear.py spells them
    (STEP + "jvp(l1_kda)/mx.kda.intra/...rc,...ic->...ri/dot_general",
     "kda.intra", "forward"),
    # Akk and Aqk by the op's own kernels (PR 35): ONE custom-call a
    # pass, named by the scope it was called under, as a traced chip run
    # of the cell spells them
    (STEP + "jvp(l1_kda)/mx.kda.intra/pallas_call", "kda.intra", "forward"),
    (BACK + "rematted_computation/l1_kda/mx.kda.intra/pallas_call",
     "kda.intra", "made again"),
    (BACK + "l1_kda/mx.kda.intra/pallas_call", "kda.intra", "backward"),
    (BACK + "l2_kda/mx.kda.solve/triangular_solve", "kda.solve", "backward"),
    # the solve's own products, its backward function's scope
    # entered again inside the node's
    (STEP + "jvp(l2_kda)/mx.kda.solve/dot_general", "kda.solve", "forward"),
    (BACK + "rematted_computation/l2_kda/mx.kda.solve/dot_general",
     "kda.solve", "made again"),
    (BACK + "l2_kda/mx.kda.solve/mx.kda.solve/...ji,...jc->...ic/dot_general",
     "kda.solve", "backward"),
    (BACK + "rematted_computation/l3_kda/mx.kda.carry/while/body/add",
     "kda.carry", "made again"),
    # the carry's kernel, forward and in its own backward pass, and the
    # batched delta made again
    (STEP + "jvp(l1_kda)/mx.kda.carry/pallas_call", "kda.carry", "forward"),
    (BACK + "l1_kda/mx.kda.carry/mx.kda.carry/pallas_call", "kda.carry",
     "backward"),
    (BACK + "rematted_computation/l1_kda/mx.kda.carry/"
     "bnhqd,bnhde->bnhqe/dot_general", "kda.carry", "made again"),
    (STEP + "jvp(l4_kda)/mx.kda.gate/jit(softplus)/log1p", "kda.gate",
     "forward"),
    (STEP + "jvp(l4_kda)/mx.kda.inter/bnhri,bnhie->bnhre/dot_general",
     "kda.inter", "forward"),
    (BACK + "l1_kda/mx.kda.norm/rsqrt", "kda.norm", "backward"),
    (BACK + "l1_kda/convert_element_type", "kda", "backward"),
    (STEP + "jvp(l2_kda_q)/dot_general", "kda_proj", "forward"),
    (BACK + "l2_kda_f_down/dot_general", "kda_proj", "backward"),
    (BACK + "l2_kda_g_up/dot_general", "kda_proj", "backward"),
    (BACK + "l2_kda_beta/dot_general", "kda_proj", "backward"),
    (STEP + "jvp(l3_kda_o)/dot_general", "kda_proj", "forward"),
    (BACK + "l3_kda_k_conv/mx.ssm.conv/reduce_sum", "conv", "backward"),
    (BACK + "rematted_computation/l3_kda_v_conv_silu/logistic", "conv",
     "made again"),
    (BACK + "l4_kda_norm/rsqrt", "ssm_norm", "backward"),
    (STEP + "jvp(l4_kda_norm_gate)/logistic", "ssm_norm", "forward"),
    (STEP + "jvp(l5_mla)/splash_attention/pallas_call", "mla", "forward"),
    (STEP + "jvp(l5_mla_kv)/mx.mla.expand/concatenate", "mla.expand",
     "forward"),
    (BACK + "l5_mla_kv_up/dot_general", "mla_proj", "backward"),
    (STEP + "jvp(l5_mla_q)/dot_general", "mla_proj", "forward"),
    (BACK + "l5_mla_o/dot_general", "mla_proj", "backward"),
    # the latent's norm and its slices are no group, as other norms
    (STEP + "jvp(l5_mla_kv_norm)/rsqrt", trace_by_node.REST, "forward"),
    (STEP + "jvp(l5_mla_shared_key)/slice", trace_by_node.REST, "forward"),
    # and the older nodes read as they did
    (BACK + "l7_q/dot_general", "attn_proj", "backward"),
    (BACK + "l0_ssd/mx.ssm.carry/while/body/mul", "scan.carry", "backward"),
])
def test_delta_rule_and_latent_attention_nodes(op_name, group, which):
    assert trace_by_node.group_of(op_name) == group
    assert trace_by_node.pass_of(op_name) == which


def test_a_kernels_custom_call_counts_under_its_scope_by_pass():
    """The delta rule's kernels on the device's line of a trace: one
    `custom-call` an instruction, named after the scope (the compiler
    numbers it), its time under `kda.intra` in the pass its `op_name`
    shows."""
    text = ("%%mx.kda.intra.%d = (f32[262144,64]{1,0:T(8,128)}, "
            "f32[262144,64]{1,0:T(8,128)}) custom-call(f32[262144,128]{1,0} "
            "%%bitcast.1), custom_call_target=\"tpu_custom_call\"")
    names = {"mx.kda.intra.3": STEP + "jvp(l1_kda)/mx.kda.intra/pallas_call",
             "mx.kda.intra.9": BACK + "rematted_computation/l1_kda/"
                               "mx.kda.intra/pallas_call",
             "mx.kda.intra.23": BACK + "l1_kda/mx.kda.intra/pallas_call"}
    events = [(text % 3, 0, 2200), (text % 9, 3000, 5100),
              (text % 23, 6000, 8650), (text % 23, 9000, 11650)]
    by_group, unnamed, by_instruction = trace_by_node.split_by_group(
        events, names)
    assert unnamed == 0
    assert by_group == {
        ("kda.intra", "forward"): pytest.approx(2200e-9),
        ("kda.intra", "made again"): pytest.approx(2100e-9),
        ("kda.intra", "backward"): pytest.approx(5300e-9)}
    assert by_instruction["mx.kda.intra.23"][1] == 2


def test_the_report_has_a_row_a_group_and_a_column_a_pass():
    got = {"by_group": {("scan.intra", "forward"): 0.01,
                        ("scan", "backward"): 0.02,
                        ("conv", "made again"): 0.003,
                        (trace_by_node.REST, "forward"): 0.05},
           "unnamed_s": 0.01, "instructions_named": 5, "instructions": 9,
           "by_instruction": {
               "fusion.1": [0.02, 20, "a/l0_conv/b", "%fusion.1 = f32[] x"],
               "fusion.2": [0.01, 10, "", "%fusion.2 = f32[] y"]}}
    lines = trace_by_node.report(got, 10, top=1).split("\n")
    assert lines[0].split() == ["ms", "a", "step", "forward", "made", "again",
                                "backward", "all"]
    assert [ln.split()[0] for ln in lines[1:6]] == [
        "conv", "scan", "scan.intra", "rest", "sum"]
    assert lines[1].split()[1:] == ["0.000", "0.300", "0.000", "0.300"]
    assert lines[5].split()[1:] == ["6.000", "0.300", "2.000", "8.300"]
    assert "fusion.1" in lines[7] and "2.0 runs" in lines[7]
    assert not any("fusion.2" in ln for ln in lines)
