"""Mesh-aware parallel layer ops — the Module-reachable surface for
expert parallelism and sequence parallelism (VERDICT r3 #5; new design
per SURVEY §2.3, no reference counterpart: the reference scales MoE/
long-context by hand-written device placement, this framework by
sharding annotations).

Both ops read :func:`registry.current_mesh` at trace time (set by
MeshExecutorGroup around its evaluator closures):

* ``MoE`` — one router, two layers.  With ``experts_held`` one chip's
  share of an expert-parallel layer: top-k over all the experts, the
  experts held (gated or not, ``expert_act``) computed by grouped
  matrix products over the pairs sorted by expert, no token dropped.
  Without it the Switch-style
  top-1 router + capacity-bucketed expert FFN in
  the GSPMD formulation: dispatch/combine are einsums over an
  expert-major buffer whose expert dim carries a sharding constraint on
  the ``ep`` mesh axis, and the expert weights arrive ``ep``-sharded via
  ``Module(param_sharding=...)`` rules — XLA inserts the all-to-alls.
  Routing math is GLOBAL (same tokens, same cumsum order) regardless of
  the mesh, so the sharded program is numerically the 1-device program.
* ``RingAttention`` — blockwise ring attention over the ``sp`` axis
  (parallel/ring_attention.py): GSPMD cannot express the ppermute ring
  schedule, so the op drops into ``shard_map`` for the staged region;
  without an ``sp`` axis it runs the exact single-device attention the
  ring is equality-tested against.
"""
from __future__ import annotations

from ..registry import register, current_mesh, count as count_op
from ..parallel.expert_parallel import (top1_routing, moe_ffn_block, route,
                                        held_experts_ffn, expert_load,
                                        balance_bias)
from ..parallel.ring_attention import ring_attention, local_attention


def _jnp():
    import jax.numpy as jnp
    return jnp


def _held(attrs):
    """(first, count) of the experts this layer holds, or None for the
    capacity-bucketed layer that holds them all."""
    held = attrs.get("experts_held")
    if held is None:
        return None
    first, count = (int(v) for v in held)
    E = int(attrs["num_experts"])
    if not (0 <= first and count >= 1 and first + count <= E):
        raise ValueError("MoE: experts_held=%r is no run of the %d experts"
                         % (held, E))
    return first, count


def _moe_args(attrs):
    if _held(attrs) is None:
        return ("data", "gate_weight", "expert1_weight", "expert1_bias",
                "expert2_weight", "expert2_bias")
    gate = ("experts_gate_weight",) if attrs.get("gated", True) else ()
    return ("data", "router_weight") + gate + (
        "experts_up_weight", "experts_down_weight")


def _moe_aux(attrs):
    return () if _held(attrs) is None else ("router_bias",)


_MOE_COUNTERS = ("moe.held_pairs", "moe.load_max", "moe.load_mean",
                 "moe.dropped")


def _moe_counters(attrs):
    return () if _held(attrs) is None else _MOE_COUNTERS


def _moe_infer(attrs, in_shapes, aux):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None, aux
    E = int(attrs["num_experts"])
    f = int(attrs["hidden_size"])
    d = data[-1]
    held = _held(attrs)
    if held is not None:
        n = held[1]
        in_shapes[1] = (E, d)
        in_shapes[2:-1] = [(n * f, d)] * (len(in_shapes) - 3)   # gate?, up
        in_shapes[-1] = (n * d, f)
        return in_shapes, [tuple(data), ()], [(E,)]
    in_shapes[1] = (d, E)
    in_shapes[2] = (E, d, f)
    in_shapes[3] = (E, f)
    in_shapes[4] = (E, f, d)
    in_shapes[5] = (E, d)
    return in_shapes, [tuple(data), ()], aux


@register("MoE", arg_names=_moe_args, aux_names=_moe_aux,
          attr_types={"num_experts": int, "hidden_size": int,
                      "capacity_factor": float, "num_experts_per_tok": int,
                      "experts_held": tuple, "score_func": str,
                      "route_norm": bool, "route_scale": float,
                      "load_balance_coeff": float, "expert_act": str,
                      "gated": bool},
          required_attrs=("num_experts", "hidden_size"),
          infer_shape=_moe_infer, num_outputs=2,
          out_names=("output", "aux_loss"), counters=_moe_counters)
def _moe(attrs, ins, octx):
    """Mixture-of-experts block, two layers on one router
    (``parallel.expert_parallel.route``).

    Without ``experts_held``: the Switch layer, top-1 of softmax scores
    into capacity buckets (tokens over capacity dropped), ReLU experts
    with biases, ep-shardable.  Outputs: the routed expert output (same
    shape as data) and the scalar load-balance aux loss (add it into
    the objective via MakeLoss).

    With ``experts_held=(first, count)``: one chip's share of an
    expert-parallel layer.  The router scores all ``num_experts``
    (``score_func``), every token chooses ``num_experts_per_tok`` of
    them by score plus the selection bias (auxiliary state
    ``router_bias``, moved after each training step by the sign rule,
    ``load_balance_coeff``), and the output is the part of the weighted
    sum that the experts held here give; no token is dropped under any
    imbalance.  An expert is ``(act(x.Wgate) * (x.Wup)).Wdown`` with
    ``expert_act`` "silu" (the default) or "relu2", or with
    ``gated=False`` the two matrices ``act(x.Wup).Wdown`` (no
    ``experts_gate_weight`` then).  Expert weights are stacked on rows:
    gate and up (count * hidden_size, d), down (count * d, hidden_size).
    The second output is 0."""
    held = _held(attrs)
    if held is not None:
        return _moe_held(attrs, ins, octx, *held)
    import math

    jnp = _jnp()
    x, wg, w1, b1, w2, b2 = ins
    E = int(attrs["num_experts"])
    cf = float(attrs.get("capacity_factor", 1.25))

    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    cap = max(1, int(math.ceil(T * cf / E)))

    f32 = jnp.float32
    logits = xt.astype(f32) @ wg.astype(f32)
    dispatch, combine, aux = top1_routing(logits, cap)

    # expert-major buffer (E, C, d); constrain its expert dim onto the
    # 'ep' axis when one exists — GSPMD turns the einsums around it into
    # the dispatch/collect all-to-alls
    sendbuf = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    sendbuf = _constrain_leading_ep(sendbuf)
    expert_out = moe_ffn_block(sendbuf, w1.astype(x.dtype),
                               b1.astype(x.dtype), w2.astype(x.dtype),
                               b2.astype(x.dtype))
    expert_out = _constrain_leading_ep(expert_out)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return [y.reshape(lead + (d,)), aux.astype(f32)]


def _moe_held(attrs, ins, octx, first, count):
    jnp = _jnp()
    x, wr, *experts, bias = ins
    wg, wu, wd = experts if len(experts) == 3 else (None,) + tuple(experts)
    E, f = int(attrs["num_experts"]), int(attrs["hidden_size"])
    k = int(attrs.get("num_experts_per_tok", 1))
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    f32 = jnp.float32
    logits = jnp.dot(xt, wr.astype(xt.dtype).T, preferred_element_type=f32)
    _scores, chosen, weights = route(
        logits, k=k, score_func=str(attrs.get("score_func", "softmax")),
        bias=bias, route_norm=bool(attrs.get("route_norm", False)),
        route_scale=float(attrs.get("route_scale", 1.0)))
    def as_kn(w, out):
        """rows (count * out, in) -> (count, in, out), activation type"""
        return w.astype(xt.dtype).reshape(count, out, -1).transpose(0, 2, 1)

    y, sizes, dropped = held_experts_ffn(
        xt, chosen, weights, None if wg is None else as_kn(wg, f),
        as_kn(wu, f), as_kn(wd, d), first,
        act=str(attrs.get("expert_act", "silu")))
    load = expert_load(chosen, E)
    here = load[first:first + count]
    count_op("moe.held_pairs", jnp.sum(sizes))
    count_op("moe.load_max", jnp.max(here))
    count_op("moe.load_mean", jnp.mean(here))
    count_op("moe.dropped", dropped)
    coeff = float(attrs.get("load_balance_coeff", 0.0))
    new_bias = balance_bias(bias, load, coeff) \
        if octx.is_train and coeff else bias
    return [y.reshape(lead + (d,)), jnp.zeros((), f32), new_bias]


def _constrain_leading_ep(t):
    mesh = current_mesh()
    if mesh is None or "ep" not in mesh.axis_names:
        return t
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*(("ep",) + (None,) * (t.ndim - 1)))
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))


def _ring_infer(attrs, in_shapes, aux):
    q = in_shapes[0]
    if q is None:
        return in_shapes, None, aux
    in_shapes[1] = tuple(q)
    in_shapes[2] = tuple(q)
    return in_shapes, [tuple(q)], aux


@register("RingAttention", arg_names=("query", "key", "value"),
          attr_types={"causal": bool, "scale": float},
          infer_shape=_ring_infer)
def _ring_attention_op(attrs, ins, octx):
    """Sequence-parallel self-attention over (B, H, T, D) inputs.

    With an 'sp' mesh axis the sequence dim is ring-scheduled over it
    (shard_map + ppermute); otherwise exact single-device attention —
    the ring's tests pin the two equal up to the blockwise
    log-sum-exp accumulation."""
    q, k, v = ins
    causal = bool(attrs.get("causal", False))
    scale = attrs.get("scale")
    scale = float(scale) if scale is not None else None

    mesh = current_mesh()
    if mesh is None or "sp" not in mesh.axis_names:
        return [local_attention(q, k, v, causal=causal, scale=scale)]

    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = axes["sp"]
    if q.shape[2] % sp:
        raise ValueError(
            "RingAttention: sequence length %d not divisible by the "
            "sp axis (%d)" % (q.shape[2], sp))
    bdim = "dp" if "dp" in mesh.axis_names else None
    spec = P(bdim, None, "sp", None)
    fn = shard_map(
        partial(ring_attention, axis_name="sp", causal=causal,
                scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return [fn(q, k, v)]
