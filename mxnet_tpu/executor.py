"""Executor — whole-graph XLA compilation.

TPU-native replacement for GraphExecutor (src/executor/graph_executor.cc).
Where the reference builds a gradient graph (nnvm::pass::Gradient), plans
memory (PlanMemory) and pushes cached per-op engine blocks (RunOps,
graph_executor.cc:780-830), this executor lowers the *entire* symbol —
forward, and fused forward+backward — into single jitted XLA programs:

* bulk-exec segments (InitOpSegs, :686-735) == the whole graph, always;
* PlanMemory/DetectInplaceAddTo == XLA buffer assignment in HBM;
* the Gradient pass + per-op backward kernels == one ``jax.vjp`` over the
  traced graph (custom-vjp loss ops reproduce reference loss gradients);
* `forward(is_train=True)` is *deferred*: the computation runs when either
  `backward()` fires (one fused fwd+bwd XLA program) or an output is read
  (forward-only program). Output NDArrays carry a ``force`` thunk so eager
  reads stay correct — preserving the async-engine illusion with zero
  double-compute in the train loop.

grad_req semantics ('write'/'add'/'null') follow graph_executor.cc:87
AggregateGradient; aux states (BatchNorm moving stats) are written back
after each run, replacing FMutateInputs.
"""
from __future__ import annotations

from functools import partial

import numpy as onp

from .base import MXNetError
from . import random as _random
from .registry import OpContext

__all__ = ["Executor"]


def _run_op(n, get, put, rng, is_train, aux_sink=None):
    """Execute one op node: rng split, fcompute, output + aux write-back.
    Shared by the plain and segmented evaluators so their semantics
    (dropout streams, BN stat updates) can never diverge."""
    import jax
    ins = [get(id(s), oi) for (s, oi) in n.inputs]
    sub = None
    if n.op.needs_rng:
        rng, sub = jax.random.split(rng)
    octx = OpContext(is_train=is_train, rng=sub)
    # trace-time only: the node's name becomes the `op_name` metadata of
    # every HLO instruction it lowers to, so a profile's fusions say
    # which symbol node they came from
    with jax.named_scope(n.name):
        res = n.op.fcompute(n.attrs, ins, octx)
    n_out = n.op.num_outputs(n.attrs)
    for oi in range(n_out):
        put(id(n), oi, res[oi])
    if aux_sink is not None and n.op.list_auxiliary_states(n.attrs):
        n_args = len(n.op.list_arguments(n.attrs))
        for (src, _), newv in zip(n.inputs[n_args:], res[n_out:]):
            aux_sink(id(src), jax.lax.stop_gradient(newv))
    return rng, res, n_out


def fuse_bn_relu(symbol):
    """Graph pass: collapse BatchNorm→Activation(relu) pairs into one
    BatchNorm node carrying ``_fused_relu=True``.

    TPU-first rationale: the pair is the hottest pattern in conv nets,
    and fusing it routes training through the hand-VJP BatchNorm core
    (ops/nn.py _bn_train_core_make) with the ReLU mask recomputed
    in-register during the backward — the post-activation tensor is
    never re-read (or saved) by the backward at all.  On an HBM-bound
    ResNet step this removes whole activation sweeps.

    Fusion applies only when the Activation is the *sole* consumer of
    the BatchNorm output (otherwise the pre-ReLU value is needed) and
    the BatchNorm does not expose mean/var (`output_mean_var`).  The
    rewrite builds new nodes; the input symbol is never mutated.  The
    fused node takes the Activation's name, so head/loss wiring and
    debug output names stay stable; the BatchNorm's parameter and aux
    Variables (gamma/beta/moving stats) are reused unchanged, so
    arg/aux lists and checkpoints are unaffected.
    """
    from .symbol import Symbol, _Node

    order = symbol._topo()
    n_cons = {}
    for nd in order:
        for (s, oi) in nd.inputs:
            key = (id(s), oi)
            n_cons[key] = n_cons.get(key, 0) + 1
    for (h, oi) in symbol._heads:
        key = (id(h), oi)
        n_cons[key] = n_cons.get(key, 0) + 1

    new_of = {}   # id(old node) -> new node
    fused_away = set()   # id(BatchNorm nodes absorbed into a fused node)

    def resolve(nd):
        return new_of.get(id(nd), nd)

    changed = False
    for nd in order:
        if nd.op is None:
            continue
        if (nd.op.name == "Activation"
                and nd.attrs.get("act_type", "relu") == "relu"
                and len(nd.inputs) == 1 and nd.inputs[0][1] == 0):
            src = nd.inputs[0][0]
            if (src.op is not None and src.op.name == "BatchNorm"
                    and id(src) not in fused_away
                    and n_cons.get((id(src), 0), 0) == 1
                    and not src.attrs.get("output_mean_var", False)
                    # never move a node across a placement boundary: the
                    # fused node carries the Activation's ctx_group, so
                    # the pair must agree (pipeline stages are split on
                    # per-node ctx_group — _split_pipeline_stages)
                    and src._attr_dict.get("ctx_group")
                    == nd._attr_dict.get("ctx_group")):
                b = resolve(src)
                fused = _Node(
                    b.op, nd.name,
                    attrs=dict(b.attrs, _fused_relu=True),
                    inputs=[(resolve(s), oi) for (s, oi) in b.inputs],
                    attr_dict=dict(nd._attr_dict),
                    auto_named=nd.auto_named)
                new_of[id(nd)] = fused
                fused_away.add(id(src))
                changed = True
                continue
        new_inputs = [(resolve(s), oi) for (s, oi) in nd.inputs]
        if any(a is not b for (a, _), (b, _) in zip(new_inputs, nd.inputs)):
            new_of[id(nd)] = _Node(
                nd.op, nd.name, attrs=nd.attrs, inputs=new_inputs,
                is_aux=nd.is_aux, attr_dict=nd._attr_dict,
                auto_named=nd.auto_named)
    if not changed:
        return symbol
    return Symbol([(resolve(h), oi) for (h, oi) in symbol._heads])


def _build_eval(symbol):
    """Compile the symbol's DAG into a pure function
    (arg_vals, aux_vals, rng, is_train) -> (outs, new_aux)."""
    order = symbol._topo()
    arg_nodes = [n for n in order if n.op is None and not n.is_aux]
    aux_nodes = [n for n in order if n.op is None and n.is_aux]
    op_nodes = [n for n in order if n.op is not None]
    heads = symbol._heads
    needs_rng = any(n.op.needs_rng for n in op_nodes)

    def eval_fn(arg_vals, aux_vals, rng, is_train, tap=None):
        env = {}
        for n, v in zip(arg_nodes, arg_vals):
            env[(id(n), 0)] = v
        for n, v in zip(aux_nodes, aux_vals):
            env[(id(n), 0)] = v
        aux_out = {id(n): v for n, v in zip(aux_nodes, aux_vals)}
        aux_ids = {id(n) for n in aux_nodes}

        def sink(aid, v):
            if aid in aux_ids:
                aux_out[aid] = v

        for n in op_nodes:
            rng, res, n_out = _run_op(
                n, lambda i, oi: env[(i, oi)],
                lambda i, oi, v: env.__setitem__((i, oi), v), rng,
                is_train, aux_sink=sink)
            if tap is not None:
                if n_out == 1:
                    tap("%s_output" % n.name, res[0])
                else:
                    for oi in range(n_out):
                        tap("%s_output%d" % (n.name, oi), res[oi])
        outs = tuple(env[(id(n), oi)] for (n, oi) in heads)
        new_aux = tuple(aux_out[id(n)] for n in aux_nodes)
        return outs, new_aux

    return eval_fn, needs_rng


def _build_eval_segmented(symbol, remat="full", n_segments=None):
    """Like :func:`_build_eval`, but the op sequence is split into
    ~sqrt(N) contiguous segments, each wrapped in ``jax.checkpoint``.

    A SINGLE checkpoint around the whole forward saves nothing (the
    backward's recompute re-materializes every activation at the same
    peak); the sqrt-N segment schedule keeps only segment-boundary
    values live plus one segment's internals — the classic
    O(sqrt(N))-memory rematerialization the reference's memonger tool
    approximates by graph re-planning (example/memcost).

    A segment's backward pass re-runs what is cheaper to make than to
    hold and is handed the rest: the ops name what is dear
    (``precision.policy.keep``: ``FullyConnected``'s output, attention's
    output and log-sum-exp, BatchNorm's batch statistics) and the policy
    says which names it keeps.  remat="full" keeps the products and
    attention and makes norms, rotations, casts and activations again
    (``Convolution`` is not named: a convolutional net's activations
    are its convolutions' outputs); "dots" keeps what
    ``jax.checkpoint_policies.dots_saveable`` sees (matmul/conv
    outputs) and attention, which on a TPU is a Pallas call that it
    cannot see; "bn_stats" additionally the per-channel BatchNorm
    statistics, so the replays never redo the stat sweeps; a callable
    passes straight through as the jax checkpoint policy
    (``jax.checkpoint_policies.nothing_saveable``: a segment keeps
    nothing, the strict sqrt-N schedule).

    The LAST segment is not wrapped: its backward pass follows its
    forward pass at once, so a checkpoint there frees no byte at the
    peak and runs the segment (the classifier's product) a second time.

    Training-mode only, no tap support (the monitor path uses the
    per-node evaluator).
    """
    import math

    order = symbol._topo()
    arg_nodes = [n for n in order if n.op is None and not n.is_aux]
    aux_nodes = [n for n in order if n.op is None and n.is_aux]
    op_nodes = [n for n in order if n.op is not None]
    heads = symbol._heads
    needs_rng = any(n.op.needs_rng for n in op_nodes)
    aux_ids = {id(n) for n in aux_nodes}

    n_ops = len(op_nodes)
    if n_ops == 0:
        # variable-only symbol: nothing to checkpoint (range() below would
        # get a zero step) — the plain evaluator is already optimal
        return _build_eval(symbol)
    if n_segments is None:
        n_segments = max(1, int(math.ceil(math.sqrt(n_ops))))
    seg_size = int(math.ceil(n_ops / float(n_segments)))
    segments = [op_nodes[i:i + seg_size]
                for i in range(0, n_ops, seg_size)]

    # liveness, computed ONCE at build time: per segment, the slots it
    # consumes from before it and the products needed later (or heads)
    head_slots = {(id(n), oi) for (n, oi) in heads}
    produced_in = {}
    consumed_in = {}  # slot -> set of segment indices that read it
    for si, seg in enumerate(segments):
        for n in seg:
            for oi in range(n.op.num_outputs(n.attrs)):
                produced_in[(id(n), oi)] = si
            for (src, oi) in n.inputs:
                consumed_in.setdefault((id(src), oi), set()).add(si)

    seg_plan = []  # (seg, in_slots, out_slots, aux_updates)
    for si, seg in enumerate(segments):
        in_slots, seen = [], set()
        for n in seg:
            for (src, oi) in n.inputs:
                slot = (id(src), oi)
                if produced_in.get(slot, -1) != si and slot not in seen:
                    seen.add(slot)
                    in_slots.append(slot)
        out_slots, aux_updates = [], []
        for n in seg:
            for oi in range(n.op.num_outputs(n.attrs)):
                slot = (id(n), oi)
                later = consumed_in.get(slot, set())
                if any(sj > si for sj in later) or slot in head_slots:
                    out_slots.append(slot)
            if n.op.list_auxiliary_states(n.attrs):
                n_args = len(n.op.list_arguments(n.attrs))
                for (src, _) in n.inputs[n_args:]:
                    if id(src) in aux_ids:
                        aux_updates.append(id(src))
        seg_plan.append((seg, tuple(in_slots), tuple(out_slots),
                         tuple(aux_updates)))

    # policy object resolved ONCE at build time (mxnet_tpu.precision
    # owns the name -> jax.checkpoint_policies mapping)
    from .precision.policy import (keeping, remat_checkpoint_policy,
                                   remat_kept_names)
    from .registry import count, counting
    _ckpt_policy = remat_checkpoint_policy(remat)
    _kept_names = remat_kept_names(remat)

    arg_slots = [(id(n), 0) for n in arg_nodes]

    def eval_fn(arg_vals, aux_vals, rng, is_train, tap=None,
                arg_dtypes=None, kept=None):
        """``arg_dtypes``: per argument a type to cast it to where a
        segment reads it, or None.  A cast made up front would keep a
        second copy of every parameter alive from the first segment to
        the last backward one; made inside the segments the copies are
        a segment's temporaries (and recomputed with it).

        ``kept``: a dict that takes, per name the policy keeps, the
        bytes of the values so named inside the wrapped segments
        (Python ints by shape, known when this traces): what the
        backward pass is handed beside the segments' arguments."""
        import jax

        assert tap is None, "segmented remat has no monitor taps"
        policy = _ckpt_policy
        cast_to = {s: dt for s, dt in zip(arg_slots, arg_dtypes or ())
                   if dt is not None}
        env = {}
        for n, v in zip(arg_nodes, arg_vals):
            env[(id(n), 0)] = v
        for n, v in zip(aux_nodes, aux_vals):
            env[(id(n), 0)] = v
        aux_out = {id(n): v for n, v in zip(aux_nodes, aux_vals)}

        for si, (seg, in_slots, out_slots, aux_updates) in \
                enumerate(seg_plan):

            def seg_fn(in_vals, rng_in, _seg=seg, _in=in_slots,
                       _out=out_slots):
                local = {s: v.astype(cast_to[s]) if s in cast_to else v
                         for s, v in zip(_in, in_vals)}
                upd = []

                def sink(aid, v):
                    if aid in aux_ids:
                        upd.append(v)

                r = rng_in
                # what the segment's ops count leaves it as an output,
                # like everything else traced under the checkpoint
                with counting() as counted:
                    for n in _seg:
                        r, _, _ = _run_op(
                            n, lambda i, oi: local[(i, oi)],
                            lambda i, oi, v: local.__setitem__((i, oi), v),
                            r, is_train, aux_sink=sink)
                return (tuple(local[s] for s in _out), tuple(upd), r,
                        counted)

            in_vals = tuple(env[s] for s in in_slots)
            if si == len(seg_plan) - 1:
                # the last segment's backward pass follows its forward
                # pass at once: the same values are live either way
                outs, upd, rng, counted = seg_fn(in_vals, rng)
            else:
                with keeping(kept, _kept_names):
                    outs, upd, rng, counted = jax.checkpoint(
                        seg_fn, policy=policy)(in_vals, rng)
            for name, v in counted.items():
                count(name, v)
            for slot, v in zip(out_slots, outs):
                env[slot] = v
            for aid, v in zip(aux_updates, upd):
                aux_out[aid] = v

        out_vals = tuple(env[(id(n), oi)] for (n, oi) in heads)
        new_aux = tuple(aux_out[id(n)] for n in aux_nodes)
        return out_vals, new_aux

    return eval_fn, needs_rng


def _split_pipeline_stages(symbol, n_stages):
    """Classify the symbol's op nodes into preamble / ``n_stages``
    pipeline stages / postamble from ``ctx_group="stage<i>"`` attrs
    (the reference's user-facing placement surface, AttrScope ->
    PlaceDevice, graph_executor.cc:318 — here mapped to GPipe stages).

    Contract (checked, with precise errors):
      * tagged ops form stages 0..n_stages-1; dataflow between tags is
        non-decreasing;
      * untagged ops reachable INTO stages are preamble, ops depending
        on the last stage are postamble; an untagged op between interior
        stages is an error;
      * exactly ONE tensor crosses each stage boundary, same shape at
        every boundary;
      * stages are structurally identical (same op types/attrs in the
        same order) so one stage body can run under ``lax.switch``-free
        weight-stationary scheduling with stacked per-stage params;
      * no aux states (BatchNorm) inside stages.
    Returns (pre_nodes, stage_nodes: list[list], post_nodes,
    carry_slots, side_slots, stage_param_slots).
    """
    import re

    order = symbol._topo()
    op_nodes = [n for n in order if n.op is not None]
    tag_of = {}
    for n in op_nodes:
        g = n._attr_dict.get("ctx_group")
        if g is not None:
            m = re.match(r"stage(\d+)$", g)
            if m:
                tag_of[id(n)] = int(m.group(1))
    if not tag_of:
        raise MXNetError("pipeline: no ctx_group='stage<i>' attrs found")
    found = sorted(set(tag_of.values()))
    if found != list(range(n_stages)):
        raise MXNetError(
            "pipeline: mesh pp axis is %d but symbol tags stages %s"
            % (n_stages, found))

    # transitive "depends on a tagged op of stage s" classification
    max_dep = {}  # node id -> highest stage it depends on (-1 none)
    for n in order:
        d = tag_of.get(id(n), -1)
        for (src, _) in (n.inputs or []):
            d = max(d, max_dep.get(id(src), -1))
        max_dep[id(n)] = d

    pre, post = [], []
    stage_nodes = [[] for _ in range(n_stages)]
    for n in op_nodes:
        s = tag_of.get(id(n))
        if s is not None:
            dep = max(max_dep.get(id(src), -1) for (src, _) in n.inputs)
            if dep > s:
                raise MXNetError(
                    "pipeline: op %s tagged stage%d consumes stage%d "
                    "output — dataflow must be stage-monotone"
                    % (n.name, s, dep))
            stage_nodes[s].append(n)
        elif max_dep[id(n)] == -1:
            pre.append(n)
        elif max_dep[id(n)] == n_stages - 1:
            post.append(n)
        else:
            raise MXNetError(
                "pipeline: untagged op %s depends on interior stage%d — "
                "tag it or move it out of the pipelined region"
                % (n.name, max_dep[id(n)]))

    produced_by = {}
    for s, seg in enumerate(stage_nodes):
        for n in seg:
            for oi in range(n.op.num_outputs(n.attrs)):
                produced_by[(id(n), oi)] = s

    # carry slot per boundary: the single stage-(i-1) product stage i reads
    carry_slots = []
    for s in range(n_stages):
        if s == 0:
            continue
        crossing = {slot for n in stage_nodes[s] for slot in
                    ((id(src), oi) for (src, oi) in n.inputs)
                    if produced_by.get(slot) == s - 1}
        if len(crossing) != 1:
            id2name = {id(n2): n2.name for seg2 in stage_nodes
                       for n2 in seg2}
            raise MXNetError(
                "pipeline: %d tensors cross the stage%d->stage%d "
                "boundary; exactly one must (crossing outputs of ops %s)"
                % (len(crossing), s - 1, s,
                   sorted(id2name.get(i, "?") for (i, _) in crossing)))
        carry_slots.append(next(iter(crossing)))
    # final carry: the single last-stage product the postamble reads
    last_out = {slot for n in post for slot in
                ((id(src), oi) for (src, oi) in n.inputs)
                if produced_by.get(slot) == n_stages - 1}
    for (hn, hoi) in symbol._heads:
        if produced_by.get((id(hn), hoi)) is not None:
            if produced_by[(id(hn), hoi)] != n_stages - 1:
                raise MXNetError("pipeline: output taken from an "
                                 "interior stage")
            last_out.add((id(hn), hoi))
    if len(last_out) != 1:
        raise MXNetError(
            "pipeline: the last stage must hand exactly one tensor to "
            "the postamble (got %d)" % len(last_out))
    carry_slots.append(next(iter(last_out)))
    # postamble must not peek inside interior stages
    for n in post:
        for (src, oi) in n.inputs:
            p = produced_by.get((id(src), oi))
            if p is not None and p != n_stages - 1:
                raise MXNetError(
                    "pipeline: postamble op %s reads stage%d internals"
                    % (n.name, p))

    # structural identity + positional input classification
    ref_seg = stage_nodes[0]
    for s, seg in enumerate(stage_nodes[1:], 1):
        if len(seg) != len(ref_seg):
            raise MXNetError(
                "pipeline: stage%d has %d ops, stage0 has %d — stages "
                "must be structurally identical" % (s, len(seg),
                                                    len(ref_seg)))
        for a, b in zip(ref_seg, seg):
            if a.op.name != b.op.name or a.attrs != b.attrs:
                raise MXNetError(
                    "pipeline: stage%d op %s (%s) does not match stage0 "
                    "op %s (%s)" % (s, b.name, b.op.name, a.name,
                                    a.op.name))

    if n_stages < 2:
        raise MXNetError("pipeline: needs a pp axis of size >= 2")

    # which stages consume each Variable (param-vs-shared classification)
    var_stages = {}
    for n in pre + post:
        for (src, _) in n.inputs:
            if src.op is None:
                var_stages.setdefault(id(src), set()).add("outside")
    for s, seg in enumerate(stage_nodes):
        for n in seg:
            for (src, _) in n.inputs:
                if src.op is None:
                    var_stages.setdefault(id(src), set()).add(s)

    # positional input classification per stage:
    # ("internal", j, oi) | ("carry",) | ("param", k) | ("side", k)
    stage_param_slots = [[] for _ in range(n_stages)]
    sides_of = [[] for _ in range(n_stages)]
    kinds_of = [[] for _ in range(n_stages)]
    for s, seg in enumerate(stage_nodes):
        local_pos = {}
        for j, n in enumerate(seg):
            for oi in range(n.op.num_outputs(n.attrs)):
                local_pos[(id(n), oi)] = (j, oi)
        seen_p, seen_s = {}, {}
        for n in seg:
            for (src, oi) in n.inputs:
                slot = (id(src), oi)
                if slot in local_pos:
                    kinds_of[s].append(("internal",) + local_pos[slot])
                elif produced_by.get(slot) is not None:
                    kinds_of[s].append(("carry",))  # single, checked above
                elif src.op is None and src.is_aux:
                    raise MXNetError(
                        "pipeline: aux state %s used inside stage%d — "
                        "BatchNorm-style ops cannot be pipelined"
                        % (src.name, s))
                elif src.op is None and var_stages[id(src)] == {s}:
                    # consumed by exactly this stage -> its private param
                    if slot not in seen_p:
                        seen_p[slot] = len(stage_param_slots[s])
                        stage_param_slots[s].append(slot)
                    kinds_of[s].append(("param", seen_p[slot]))
                else:
                    # preamble product or a Variable shared across stages
                    # (e.g. a causal mask): a broadcast side input
                    if slot not in seen_s:
                        seen_s[slot] = len(sides_of[s])
                        sides_of[s].append(slot)
                    kinds_of[s].append(("side", seen_s[slot]))

    # stages 1..K-1 must wire identically; stage0's carry positions hold
    # the pipeline input x0 (a preamble product / arg), classified side
    ref = kinds_of[1]
    for s in range(2, n_stages):
        if kinds_of[s] != ref:
            raise MXNetError(
                "pipeline: stage%d wires its inputs differently from "
                "stage1 — stages must be structurally identical" % s)
    carry_pos = [i for i, k in enumerate(ref) if k == ("carry",)]
    if not carry_pos:
        raise MXNetError("pipeline: stages do not consume the carry")
    k0 = list(kinds_of[0])
    # stage0's carry positions name the pipeline input x0. Two legal
    # shapes: a preamble product / shared Variable (classified "side"),
    # or a bare data Variable read only by stage0 — no preamble op —
    # which the scan above classified as a stage-private "param".
    x0_slots = set()
    for i in carry_pos:
        if k0[i][0] == "side":
            x0_slots.add(sides_of[0][k0[i][1]])
        elif k0[i][0] == "param":
            x0_slots.add(stage_param_slots[0][k0[i][1]])
        else:
            x0_slots.add(None)
    if len(x0_slots) != 1 or None in x0_slots:
        raise MXNetError(
            "pipeline: stage0 must read one preamble/arg tensor at the "
            "positions where later stages read the carry")
    x0_slot = next(iter(x0_slots))

    # re-key stage0: x0 becomes the carry; drop it from whichever slot
    # list (sides or stage params) it was classified into
    def rekey(slots, tag):
        x0_idx = slots.index(x0_slot)
        kept = [sl for sl in slots if sl != x0_slot]
        remap = {i: kept.index(sl) for i, sl in enumerate(slots)
                 if sl != x0_slot}
        new_k0 = [("carry",) if k[0] == tag and k[1] == x0_idx else
                  ((tag, remap[k[1]]) if k[0] == tag else k)
                  for k in k0]
        return kept, new_k0

    if x0_slot in sides_of[0]:
        sides0, k0 = rekey(sides_of[0], "side")
    else:
        stage_param_slots[0], k0 = rekey(stage_param_slots[0], "param")
        sides0 = list(sides_of[0])
    if k0 != ref:
        raise MXNetError(
            "pipeline: stage0 wires its inputs differently from stage1")
    # shared side inputs must be the SAME source slots for every stage
    for s in range(2, n_stages):
        if sides_of[s] != sides_of[1]:
            raise MXNetError(
                "pipeline: stage%d consumes different shared inputs "
                "than stage1" % s)
    if sides0 != sides_of[1]:
        raise MXNetError(
            "pipeline: stage0 consumes different shared inputs than "
            "stage1")

    # the outgoing carry must sit at the same local position in every
    # stage (one stage body serves all pp ranks, weight-stationary)
    out_pos = None
    for s, seg in enumerate(stage_nodes):
        local_pos = {}
        for j, n in enumerate(seg):
            for oi in range(n.op.num_outputs(n.attrs)):
                local_pos[(id(n), oi)] = (j, oi)
        p = local_pos.get(carry_slots[s])
        if p is None:
            raise MXNetError(
                "pipeline: stage%d does not produce its carry" % s)
        if out_pos is None:
            out_pos = p
        elif p != out_pos:
            raise MXNetError(
                "pipeline: stage%d emits its carry from a different op "
                "position than stage0" % s)

    return {"pre": pre, "stages": stage_nodes, "post": post,
            "carry_slots": carry_slots, "x0_slot": x0_slot,
            "side_slots": sides_of[1], "kinds": ref, "out_pos": out_pos,
            "stage_param_slots": stage_param_slots}


def _build_eval_pipelined(symbol, mesh, n_microbatch, pp_axis="pp",
                          dp_axis="dp"):
    """Like :func:`_build_eval`, but the symbol's ``ctx_group="stage<i>"``
    region runs as a GPipe pipeline over the mesh's ``pp`` axis.

    One fused program: preamble ops execute under GSPMD as usual; the
    staged region becomes a ``shard_map`` over the full mesh running the
    GPipe schedule (``lax.scan`` of compute + ``lax.ppermute`` ring hops,
    parallel/pipeline_parallel.py design) with each pp rank holding its
    stage's parameters (stacked leading stage axis, sharded on 'pp');
    the postamble (loss head) runs on the re-assembled sequence output.
    ``jax.vjp`` differentiates straight through the schedule, so the
    enclosing fused fwd+bwd/train-step machinery is unchanged.

    Microbatching splits the global batch B into ``n_microbatch`` chunks
    along axis 0 (B % (n_microbatch * dp) == 0); pipeline bubble is the
    standard (S-1)/(M+S-1). Stage bodies must be batch-size-polymorphic
    (Reshape with -1, no BatchNorm inside stages — checked).
    """
    order = symbol._topo()
    arg_nodes = [n for n in order if n.op is None and not n.is_aux]
    aux_nodes = [n for n in order if n.op is None and n.is_aux]
    op_nodes = [n for n in order if n.op is not None]
    heads = symbol._heads
    needs_rng = any(n.op.needs_rng for n in op_nodes)
    aux_ids = {id(n) for n in aux_nodes}

    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[pp_axis]
    plan = _split_pipeline_stages(symbol, n_stages)
    pre, stages, post = plan["pre"], plan["stages"], plan["post"]
    body_seg = stages[1]  # canonical stage (kinds computed against it)
    final_slot = plan["carry_slots"][-1]
    out_pos = plan["out_pos"]

    # per-op resolver table for the shared stage body
    kinds_by, it = [], iter(plan["kinds"])
    for n in body_seg:
        kinds_by.append([next(it) for _ in n.inputs])

    def stage_body(param_vals, x, side_vals, key, is_train):
        import jax
        local = {}
        for j, n in enumerate(body_seg):
            ins = []
            for kk in kinds_by[j]:
                if kk[0] == "internal":
                    ins.append(local[(kk[1], kk[2])])
                elif kk[0] == "carry":
                    ins.append(x)
                elif kk[0] == "param":
                    ins.append(param_vals[kk[1]])
                else:
                    ins.append(side_vals[kk[1]])
            sub = None
            if n.op.needs_rng:
                key, sub = jax.random.split(key)
            res = n.op.fcompute(n.attrs, ins, OpContext(is_train=is_train,
                                                        rng=sub))
            for oi in range(n.op.num_outputs(n.attrs)):
                local[(j, oi)] = res[oi]
        return local[out_pos], key

    def eval_fn(arg_vals, aux_vals, rng, is_train, tap=None):
        import jax
        import jax.numpy as jnp
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        assert tap is None, "pipelined eval has no monitor taps"
        env = {}
        for n, v in zip(arg_nodes, arg_vals):
            env[(id(n), 0)] = v
        for n, v in zip(aux_nodes, aux_vals):
            env[(id(n), 0)] = v
        aux_out = {id(n): v for n, v in zip(aux_nodes, aux_vals)}

        def sink(aid, v):
            if aid in aux_ids:
                aux_out[aid] = v

        def get(i, oi):
            return env[(i, oi)]

        def put(i, oi, v):
            env[(i, oi)] = v

        for n in pre:
            rng, _, _ = _run_op(n, get, put, rng, is_train, aux_sink=sink)

        x0 = env[plan["x0_slot"]]
        sides = tuple(env[s] for s in plan["side_slots"])
        stacked = tuple(
            jnp.stack([env[plan["stage_param_slots"][s][k]]
                       for s in range(n_stages)])
            for k in range(len(plan["stage_param_slots"][0])))
        B, M = x0.shape[0], n_microbatch
        if B % M:
            raise MXNetError(
                "pipeline: batch %d not divisible by %d microbatches"
                % (B, M))
        x_mb = x0.reshape((M, B // M) + x0.shape[1:])
        if needs_rng:
            rng, pipe_key = jax.random.split(rng)
        else:
            pipe_key = jnp.zeros((2,), jnp.uint32)

        def sched(stacked_l, x_l, sides_l, key):
            S = lax.axis_size(pp_axis)
            idx = lax.axis_index(pp_axis)
            params_l = tuple(p[0] for p in stacked_l)
            Ml = x_l.shape[0]
            zero = jnp.zeros_like(x_l[0])
            perm = [(i, (i + 1) % S) for i in range(S)]

            # distinct rng stream per (tick, pp rank, dp shard): without
            # the rank folds, structurally-identical stages would draw
            # byte-identical dropout masks at every tick
            kbase = jax.random.fold_in(key, idx)
            if dp_axis in mesh.axis_names:
                kbase = jax.random.fold_in(kbase,
                                           lax.axis_index(dp_axis))

            def tick(state, t):
                inject = x_l[jnp.minimum(t, Ml - 1)]
                cur = jnp.where(idx == 0, inject, state)
                y, _ = stage_body(params_l, cur, sides_l,
                                  jax.random.fold_in(kbase, t), is_train)
                out = jnp.where(idx == S - 1, y, jnp.zeros_like(y))
                return lax.ppermute(y, pp_axis, perm), out

            _, ys = lax.scan(tick, zero, jnp.arange(Ml + S - 1))
            # only the last stage wrote non-zeros; psum replicates
            return lax.psum(ys[S - 1:], pp_axis)

        y_mb = shard_map(
            sched, mesh=mesh,
            in_specs=(tuple(P(pp_axis) for _ in stacked),
                      P(None, dp_axis), tuple(P() for _ in sides), P()),
            out_specs=P(None, dp_axis), check_vma=False)(
                stacked, x_mb, sides, pipe_key)
        env[final_slot] = y_mb.reshape((B,) + y_mb.shape[2:])

        for n in post:
            rng, _, _ = _run_op(n, get, put, rng, is_train, aux_sink=sink)

        outs = tuple(env[(id(n), oi)] for (n, oi) in heads)
        new_aux = tuple(aux_out[id(n)] for n in aux_nodes)
        return outs, new_aux

    # names of stage-private parameters: these get stacked with a leading
    # stage axis sharded on 'pp' inside shard_map, so caller-supplied
    # param_sharding rules cannot apply to them (MeshExecutorGroup checks)
    id2name = {id(n): n.name for n in arg_nodes}
    stage_param_names = {id2name[sid]
                         for slots in plan["stage_param_slots"]
                         for (sid, _oi) in slots}
    return eval_fn, needs_rng, stage_param_names


class Executor:
    """Runnable binding of a Symbol to argument/gradient/aux NDArrays."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        import jax

        self._symbol = symbol
        self._ctx = ctx
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self.arg_names = arg_names
        self.aux_names = aux_names

        self.arg_arrays = self._normalize(args, arg_names, "args")
        self.aux_arrays = self._normalize(aux_states or [], aux_names,
                                          "aux_states")
        self.arg_dict = dict(zip(arg_names, self.arg_arrays))
        self.aux_dict = dict(zip(aux_names, self.aux_arrays))

        # gradient buffers + per-arg request
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in arg_names}
        if args_grad is None:
            self.grad_arrays = [None] * len(arg_names)
            for n in arg_names:
                self._grad_req[n] = "null"
        elif isinstance(args_grad, dict):
            self.grad_arrays = [args_grad.get(n) for n in arg_names]
            for n in arg_names:
                if args_grad.get(n) is None:
                    self._grad_req[n] = "null"
        else:
            self.grad_arrays = list(args_grad)
            while len(self.grad_arrays) < len(arg_names):
                self.grad_arrays.append(None)
        self.grad_dict = dict(zip(arg_names, self.grad_arrays))
        self._diff_names = [n for n in arg_names
                            if self._grad_req.get(n, "null") != "null"
                            and self.grad_dict.get(n) is not None]

        self._eval_fn, self._needs_rng = _build_eval(symbol)

        # jitted programs (compiled lazily on first use, cached thereafter —
        # the "compile once via simple_bind, reuse every batch" contract)
        self._jit_fwd = {
            True: jax.jit(partial(self._eval_fn, is_train=True)),
            False: jax.jit(partial(self._eval_fn, is_train=False)),
        }
        self._jit_grad = jax.jit(self._grad_step)

        # allocate persistent output buffers from abstract evaluation
        arg_structs = [jax.ShapeDtypeStruct(a.shape, onp.dtype(a.dtype))
                       for a in self.arg_arrays]
        aux_structs = [jax.ShapeDtypeStruct(a.shape, onp.dtype(a.dtype))
                       for a in self.aux_arrays]
        rng_struct = jax.ShapeDtypeStruct((2,), onp.uint32)
        out_structs, _ = jax.eval_shape(partial(self._eval_fn, is_train=False),
                                        arg_structs, aux_structs, rng_struct)
        from . import ndarray as nd
        self._out_arrays = [nd.zeros(s.shape, ctx=ctx, dtype=s.dtype)
                            for s in out_structs]
        self.outputs = self._out_arrays
        self.output_dict = dict(zip(symbol.list_outputs(), self._out_arrays))

        self._pending = None     # (is_train, arg_vals, aux_vals, rng)
        self._last_run = None    # captured values of the last forward
        self._monitor_callback = None

    # ------------------------------------------------------------------
    def _normalize(self, arrays, names, what):
        from .ndarray import NDArray
        if isinstance(arrays, dict):
            missing = [n for n in names if n not in arrays]
            if missing:
                raise MXNetError("missing %s: %s" % (what, missing))
            return [arrays[n] for n in names]
        arrays = list(arrays)
        if len(arrays) != len(names):
            raise MXNetError("%s length %d != expected %d"
                             % (what, len(arrays), len(names)))
        return arrays

    # ------------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Schedule a forward pass; returns the output NDArrays (lazy).

        Mirrors Executor::Forward / MXExecutorForward: copies any kwargs into
        the bound input arrays first (the reference requires explicit copy;
        we keep the convenience from executor.py:86)."""
        if kwargs:
            for k, v in kwargs.items():
                if k not in self.arg_dict:
                    raise MXNetError("unknown input %s" % k)
                from .ndarray import NDArray
                if isinstance(v, NDArray):
                    v.copyto(self.arg_dict[k])
                else:
                    self.arg_dict[k][:] = v

        arg_vals = [a._read() for a in self.arg_arrays]
        aux_vals = [a._read() for a in self.aux_arrays]
        rng = _random.next_key() if self._needs_rng else \
            onp.zeros((2,), onp.uint32)
        self._pending = (bool(is_train), arg_vals, aux_vals, rng)
        self._last_run = self._pending
        if self._monitor_active():
            # execute-with-taps: run the per-node interpreter eagerly and
            # feed every op output to the monitor callback — the reference
            # copies each output to ExecuteMonCallback
            # (graph_executor.cc:760-778)
            self._pending = None
            cb = self._monitor_callback
            from . import ndarray as nd

            def tap(name, val):
                cb(name, nd.NDArray(val, ctx=self._ctx, writable=False))

            outs, new_aux = self._eval_fn(arg_vals, aux_vals, rng,
                                          bool(is_train), tap=tap)
            self._write_results(outs, new_aux, bool(is_train))
            return self.outputs
        force = self._materialize_forward
        for o in self._out_arrays:
            o._chunk.force = force
        return self.outputs

    def _monitor_active(self):
        cb = self._monitor_callback
        if cb is None:
            return False
        owner = getattr(cb, "__self__", None)
        # Monitor gates taps by interval via its ``activated`` flag; plain
        # callables tap every batch
        return getattr(owner, "activated", True) is not False

    def _materialize_forward(self):
        if self._pending is None:
            return
        is_train, arg_vals, aux_vals, rng = self._pending
        self._pending = None
        outs, new_aux = self._jit_fwd[is_train](arg_vals, aux_vals, rng)
        self._write_results(outs, new_aux, is_train)

    def _write_results(self, outs, new_aux, is_train):
        for o, v in zip(self._out_arrays, outs):
            o._chunk.force = None
            o._chunk.arr = v
        if is_train:
            for a, v in zip(self.aux_arrays, new_aux):
                a._write(v)

    # ------------------------------------------------------------------
    def _grad_step(self, arg_vals, aux_vals, rng, head_grads):
        import jax
        names = self.arg_names
        diff_idx = [i for i, n in enumerate(names) if n in self._diff_names]
        diff_vals = tuple(arg_vals[i] for i in diff_idx)

        def f(diff):
            merged = list(arg_vals)
            for i, v in zip(diff_idx, diff):
                merged[i] = v
            outs, new_aux = self._eval_fn(merged, aux_vals, rng, True)
            return outs, new_aux

        outs, vjp_fn, new_aux = jax.vjp(f, diff_vals, has_aux=True)
        (grads,) = vjp_fn(tuple(head_grads))
        return outs, new_aux, grads

    def backward(self, out_grads=None):
        """Fused forward+backward XLA program; writes gradients honoring
        grad_req write/add (Executor::Backward, graph_executor.cc:45)."""
        import jax.numpy as jnp
        if self._last_run is None:
            raise MXNetError("backward() called before forward()")
        is_train, arg_vals, aux_vals, rng = self._last_run
        self._pending = None
        if out_grads is None:
            heads = [jnp.ones(o.shape, o.dtype) for o in self._out_arrays]
        else:
            from .ndarray import NDArray
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            heads = [g._read() if isinstance(g, NDArray) else jnp.asarray(g)
                     for g in out_grads]
        outs, new_aux, grads = self._jit_grad(arg_vals, aux_vals, rng, heads)
        self._write_results(outs, new_aux, is_train=True)
        for name, g in zip(self._diff_names, grads):
            buf = self.grad_dict[name]
            if self._grad_req[name] == "add":
                buf._write(buf._read() + g)
            else:
                buf._write(g)

    # ------------------------------------------------------------------
    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to resized arrays (executor.py:287).

        Matches the reference's flag semantics: an arg whose shape changes
        without being named in kwargs requires ``partial_shaping``; growing
        an array beyond its current element count requires
        ``allow_up_sizing`` (same-or-smaller reshapes share memory)."""
        from . import ndarray as nd
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("Insufficient argument shapes provided.")

        def _resize(name, new_shape, arr, specified):
            new_shape = tuple(new_shape)
            if tuple(arr.shape) == new_shape:
                return arr
            if not (partial_shaping or specified):
                raise MXNetError(
                    "Shape of unspecified array %s changed. This can cause "
                    "the new executor to not share parameters with the old "
                    "one. Set partial_shaping=True if intended." % name)
            if int(onp.prod(new_shape)) > arr.size:
                if not allow_up_sizing:
                    raise MXNetError(
                        "New shape of %s larger than original; set "
                        "allow_up_sizing=True to allocate a new array."
                        % name)
                return nd.empty(new_shape, ctx=arr.context, dtype=arr.dtype)
            if int(onp.prod(new_shape)) == arr.size:
                return arr.reshape(new_shape)
            # shrink: the reference keeps a prefix view of the old buffer
            # (executor.py:287 arr.reshape); values are preserved here via a
            # prefix copy (jax arrays are immutable, so no aliased view)
            prefix = arr._read().ravel()[:int(onp.prod(new_shape))]
            return nd.NDArray(prefix.reshape(new_shape), ctx=arr.context)

        new_args, grads = {}, None
        if any(g is not None for g in self.grad_arrays):
            grads = {}
        for name, new_shape, arr in zip(self.arg_names, arg_shapes,
                                        self.arg_arrays):
            new_args[name] = _resize(name, new_shape, arr, name in kwargs)
            g = self.grad_dict.get(name)
            if g is not None:
                grads[name] = _resize("grad of " + name, new_shape, g,
                                      name in kwargs)
        new_aux = {}
        for name, new_shape, arr in zip(self.aux_names, aux_shapes,
                                        self.aux_arrays):
            new_aux[name] = _resize(name, new_shape, arr, True)
        return Executor(self._symbol, self._ctx, new_args, grads,
                        self._grad_req, new_aux)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise ValueError("Found name \"%s\" not in arguments" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    arr.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise ValueError("Found name \"%s\" not in aux" % name)

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def debug_str(self):
        lines = ["Symbol outputs: %s" % ", ".join(self._symbol.list_outputs())]
        for n in self._symbol._topo():
            if n.op is not None:
                lines.append("Op:%s, Name=%s" % (n.op.name, n.name))
        lines.append("Memory planning: delegated to XLA buffer assignment")
        return "\n".join(lines)
