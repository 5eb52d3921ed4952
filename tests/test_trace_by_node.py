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
    by, unnamed = trace_by_node.split_by_group(
        events, {"cond.1": "jit(s)/l1_moe/cond", "fusion.2": "jit(s)/l1_moe/a",
                 "fusion.3": "jit(s)/jvp(l0_attn)/b", "copy.4": ""})
    assert by == {"moe": pytest.approx(90e-9), "attention": pytest.approx(10e-9),
                  trace_by_node.REST: pytest.approx(30e-9)}
    assert unnamed == pytest.approx(30e-9)
    # the whole trace, on the CPU: no device plane, nothing to reduce
    assert trace_by_node.reduce_by_group(
        trace_reduce.find_xplane(str(tmp_path))) is None
