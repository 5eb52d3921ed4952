"""The yardstick's arithmetic: the trace reduction on a recorded trace,
the shape-derived counts against XLA and against every tensor of a
pass, and the table of peaks."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, trace_reduce  # noqa: E402
from benchmark.counts import resnet as counts  # noqa: E402
from benchmark.reference import resnet as reference  # noqa: E402

# the configuration on file, and the paper's 152-layer column at 128 a
# chip: the counts are functions of the shapes, at any depth
CONFIGS = ["resnet50-b256", "resnet152-b128"]
DEEPER = {"resnet152-b128": {"units": [3, 8, 36, 3], "per_chip_batch": 128}}
# the recorded trace: 20 jitted 2048^3 matmuls on the CPU backend
# (profile_matmul_xplane/, copied), whose executor line stands in for a
# device's "XLA Ops" line
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "matmul.xplane.pb")
FIXTURE_LINE = "tf_XLAPjRtCpuClient/6033312393225914528"


def _arch(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "resnet50-b256.json")) as f:
        cfg = dict(json.load(f), **DEEPER.get(name, {}))
    return {k: cfg[k] for k in ("units", "filters", "classes", "image")}, cfg


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(FIXTURE, device_plane=re.compile(r"^/host:CPU$"),
                             ops_line=FIXTURE_LINE)


def test_trace_reduction_on_the_recorded_trace(recorded):
    ops = recorded["devices"]["/host:CPU"]["ops"]
    assert len(ops) == 99
    lo = min(s for _n, s, _e in ops)
    hi = max(e for _n, _s, e in ops)
    # worked out by hand from the file: first event starts at
    # 107,899,980 ns, the last ends at 177,257,029 ns; the 20
    # dot_general events hold 62,452,453 ns and the 20 waits after them
    # 3,146 ns more; every other event lies inside one of those
    assert (lo, hi) == (107899980.0, 177257029.0)
    red = trace_reduce.reduce_window(recorded, (lo, hi))
    dev = red["devices"]["/host:CPU"]
    assert red["window_s"] == pytest.approx(0.069357049, rel=1e-9)
    assert dev["busy_s"] == pytest.approx(0.062455599, rel=1e-9)
    assert 1 - dev["busy_s"] / red["window_s"] == pytest.approx(0.099506, abs=1e-6)
    assert trace_reduce.total(dev["idle"]) == pytest.approx(69357049 - 62455599)
    by_name = dict(trace_reduce.time_by_name(ops, lo, hi))
    assert by_name["dot_general.1"] == pytest.approx(0.062452453, rel=1e-9)
    assert by_name["end: dot_general.1"] == pytest.approx(23299e-9, rel=1e-9)
    # half the window holds about half the work
    half = trace_reduce.reduce_window(recorded, (lo, (lo + hi) / 2))
    assert 0.4 < half["devices"]["/host:CPU"]["busy_s"] / dev["busy_s"] < 0.6


def test_step_program_under_another_name_is_an_error():
    """The step's seconds are read from the program of that name, as
    often as the window stepped, or not at all."""
    step = [("jit_train_step(123)", 10 * i, 10 * i + 8) for i in range(5)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": [("fusion.1", s + 1, e - 1) for _n, s, e in step],
        "modules": step + [("jit_tally(9)", 50, 52)]}}, "spans": []}
    red = trace_reduce.reduce_window(trace, (0, 60), "train_step", steps=5)
    dev = red["devices"]["/device:TPU:0"]
    assert dev["step_module_runs"] == 5
    assert dev["step_module_s"] == pytest.approx(30e-9)
    with pytest.raises(RuntimeError, match="0 run"):
        trace_reduce.reduce_window(trace, (0, 60), "fused_update", steps=5)
    with pytest.raises(RuntimeError, match="5 run.* 6 expected"):
        trace_reduce.reduce_window(trace, (0, 60), "train_step", steps=6)
    with pytest.raises(RuntimeError, match="4 run"):
        trace_reduce.reduce_window(trace, (0, 45), "train_step", steps=5)
    whole = trace_reduce.reduce_window(trace, (0, 60))
    assert whole["devices"]["/device:TPU:0"]["step_module_s"] == \
        pytest.approx(30e-9)


def test_op_label():
    text = ("%fusion.387 = (f32[3]{0:T(128)S(1)}, f32[3]{0:T(128)S(1)}) "
            "fusion(f32[3]{0:T(128)} %aux__bn_data_moving_mean__.1, "
            "f32[256,3,224,224]{0,3,2,1:T(8,128)} %inputs__data__.1), "
            "kind=kLoop, calls=%fused_computation.474")
    assert trace_reduce.op_label(text) == "fusion.387 kLoop f32[3]"
    assert trace_reduce.op_label("dot_general.1") == "dot_general.1"
    assert trace_reduce.COLLECTIVE.search(trace_reduce.op_label(
        "%all-reduce-done.5 = f32[64]{0} all-reduce-done(%all-reduce-start.5)"))


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert trace_reduce.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert trace_reduce.gaps(u, 1, 6) == [(3, 5)]
    ops = [("fusion.1", 0, 4), ("all-reduce.2", 3, 6), ("all-reduce-start", 8, 9)]
    assert trace_reduce.exposed_collective_ns(ops, 0, 10) == 3
    idle = [(0, 4), (6, 10)]
    by = trace_reduce.attribute_gaps(idle, [("bench.next", 1, 3), ("bench.next", 7, 12)], "rest")
    assert by == {"bench.next": pytest.approx(5e-9), "rest": pytest.approx(3e-9)}


@pytest.mark.parametrize("name", CONFIGS)
def test_operation_count_agrees_with_xla(name):
    """XLA's flops of the forward pass at batch 1 (sound; its bytes are
    not) against 2 x multiply-adds.  Both leave out products against
    the zero padding.  XLA also counts BatchNorm, ReLU and pooling,
    which the count leaves out on purpose: XLA may read up to 3 % more,
    and never less."""
    import jax
    import jax.numpy as jnp
    arch, _cfg = _arch(name)
    args, _aux = reference.param_shapes(arch)
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in args.items()}
    x = jax.ShapeDtypeStruct((1,) + tuple(arch["image"]), jnp.float32)
    lowered = jax.jit(lambda p, x: reference.forward(p, x, arch, scan=False)[0]
                      ).lower(params, x)
    xla = lowered.cost_analysis()["flops"]
    mine = 2 * counts.forward_macs_per_image(arch)
    assert mine <= xla <= 1.03 * mine, (mine, xla)
    assert counts.train_flops_per_image(arch) == 3 * mine
    # the paper's counts: 3.8 and 11.3 G multiply-adds (within 10 %: the
    # reference script strides the 3x3, the paper the first 1x1, and
    # the paper counts the padding)
    paper = {"resnet50-b256": 3.8e9, "resnet152-b128": 11.3e9}[name]
    assert abs(counts.forward_macs_per_image(arch) / paper - 1) < 0.1
    n_params = sum(int(jnp.prod(jnp.array(s))) for s in args.values())
    assert counts.n_parameters(arch) == n_params


@pytest.mark.parametrize("name", CONFIGS)
def test_byte_count_is_a_lower_bound(name):
    """Every tensor a forward pass makes, written once and read once in
    each direction, plus the state: the least bytes must not pass it."""
    import jax
    import jax.numpy as jnp
    arch, cfg = _arch(name)
    batch = cfg["per_chip_batch"]
    args, _aux = reference.param_shapes(arch)
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in args.items()}
    x = jax.ShapeDtypeStruct((batch,) + tuple(arch["image"]), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, x: reference.forward(p, x, arch, scan=False)[0])(params, x)

    def walk(jp):
        n = 0
        for eqn in jp.eqns:
            inner = [v for v in eqn.params.values() if hasattr(v, "eqns")
                     or hasattr(getattr(v, "jaxpr", None), "eqns")]
            if inner:
                n += sum(walk(getattr(v, "jaxpr", v)) for v in inner)
            else:
                n += sum(int(jnp.prod(jnp.array(o.aval.shape)))
                         for o in eqn.outvars if o.aval.shape)
        return n

    every_tensor = walk(jaxpr.jaxpr)
    act = counts.BYTES[cfg["compute_dtype"]]
    n_params = counts.n_parameters(arch)
    everything = every_tensor * act * 4 + n_params * 4 * 6 \
        + batch * 3 * 224 * 224 * 4
    least = counts.train_least_bytes(arch, batch, cfg["compute_dtype"])
    assert 0 < least <= everything
    # and a step cannot be all state: the saved outputs dominate
    assert least > 10 * n_params * 4 * 5


def test_peaks_table():
    v5e = peaks.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")


def test_bounds_say_which_binds():
    arch, cfg = _arch("resnet50-b256")
    b = counts.step_bounds(arch, 256, "bfloat16", peaks.peaks_of("TPU v5 lite"))
    assert b["bound"] == ("operations" if b["ops_s"] >= b["bytes_s"] else "bytes")
    assert 0.02 < b["ops_s"] < 0.04 and 0.01 < b["bytes_s"] < 0.04
