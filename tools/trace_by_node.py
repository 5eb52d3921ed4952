#!/usr/bin/env python3
"""Device time of a traced run by symbol node.

    python3 tools/trace_by_node.py <trace dir> [--steps N]
        [--window bench.window] [--program train_step] [--top N]

The executor runs every node of the symbol under
`jax.named_scope(node.name)`, so each HLO instruction of the step
carries the node in its `op_name` metadata
(`jit(train_step)/.../l0_attn/...`, and `transpose(jvp(l0_attn))` in
the backward pass).  The device trace's `XLA Ops` line has one event per
executed instruction, named by its HLO text and with no metadata; the
trace keeps each program's HLO proto beside them (plane
`/host:metadata`, stat `Hlo Proto` of the program's entry).  This tool
reads the instructions' `op_name` from that proto and sums the events'
own time (an instruction's time less that of the instructions nested
in it: a conditional's branch, a loop's body) by the group of nodes
they name and by the pass the path shows (forward, a checkpointed
segment made again, backward), for the device that was busy longest;
what names no group is the remainder, printed with the rest, never
dropped.  A Pallas kernel is one instruction (a `custom-call` whose
`op_name` ends in `<scope>/pallas_call`): it counts under the node and
the scope it was called under, in the pass its path shows, as the
instructions it replaced did (`l1_kda/mx.kda.intra/pallas_call` under
`kda.intra`).  `--top N` lists the N instructions that took longest, each
with the end of its `op_name`.

The trace is any `jax.profiler.trace(dir)` around `Module.fit`;
`--window` names a `TraceAnnotation` that brackets the steps to count
(none: the whole trace).  The benchmark's harness removes its trace
before anything can read it by node (PERF.md section 7), so the traced
run is one's own.
"""
import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import trace_reduce  # noqa: E402

GROUPS = (
    # (group, regex on a node name): first match wins
    ("attention", re.compile(r"^l\d+_attn$")),
    ("attn_proj", re.compile(r"^l\d+_(q|k|v|o|gate)$")),
    ("moe", re.compile(r"^l\d+_moe$")),
    ("shared_ffn", re.compile(r"^l\d+_(shared|mlp)_\w+$")),
    ("lm_head", re.compile(r"^(head|softmax)$")),
    # a Mamba-2 layer (models/nemotron_h.py): the convolution with its
    # silu, the scan, the two projections, the gated norm; a delta-rule
    # layer's (models/kimi_linear.py) three convolutions and its norm
    # per head with that norm's sigmoid gate beside them
    ("conv", re.compile(r"^l\d+_(kda_[qkv]_)?conv(_silu)?$")),
    ("scan", re.compile(r"^l\d+_ssd$")),
    ("mamba_proj", re.compile(r"^l\d+_(in|out)$")),
    ("ssm_norm", re.compile(r"^l\d+_(ssm_norm|kda_norm(_gate)?)$")),
    # a delta-rule layer: the recurrence and its nine projections; a
    # latent-attention layer: the kernels with the latent's expansion,
    # and its four projections
    ("kda", re.compile(r"^l\d+_kda$")),
    ("kda_proj", re.compile(r"^l\d+_kda_(q|k|v|o|beta|[fg]_(down|up))$")),
    ("mla", re.compile(r"^l\d+_mla(_kv)?$")),
    ("mla_proj", re.compile(r"^l\d+_mla_(q|kv_down|kv_up|o)$")),
)
REST = "rest"
# groups split further by the scope inside the node: ops/ssm.py's
# `mx.ssm.<scope>` and `mx.kda.<scope>`, ops/transformer.py's
# `mx.mla.<scope>`
SPLIT_BY_SCOPE = ("scan", "kda", "mla")
_SCOPE = re.compile(r"^mx\.(?:ssm|kda|mla)\.(\w+)$")
PASSES = ("forward", "made again", "backward")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "hlo proto"
_WORD = re.compile(r"[A-Za-z0-9_.\-]+")
_INSTRUCTION = re.compile(r"^%?([^\s=]+) = ")


def group_of(op_name):
    """The group of the innermost symbol node an `op_name` path names,
    or REST.  A path element is a node's name as it stands, or wrapped
    by autodiff: `jvp(l0_attn)`, `transpose(jvp(l0_attn))`.  A scan's
    instructions are split further by the scope inside the node:
    `scan.intra` under `l0_ssd/mx.ssm.intra`, `scan` outside them."""
    scope = ""
    for part in reversed(op_name.split("/")):
        for word in _WORD.findall(part):
            m = _SCOPE.match(word)
            if m and not scope:
                scope = "." + m.group(1)
            for group, pattern in GROUPS:
                if pattern.match(word):
                    return group + scope if group in SPLIT_BY_SCOPE \
                        else group
    return REST


def pass_of(op_name):
    """Which pass of the step an `op_name` path lies in: a segment
    under `jax.checkpoint` run a second time
    (`.../checkpoint/rematted_computation/l0_conv/...`) is "made
    again", any other path under `transpose(...)` "backward", and the
    rest "forward": `jvp(l0_conv)`, and what names no pass at all (the
    update, the casts before the first layer)."""
    forward, made_again, backward = PASSES
    if "rematted_computation" in op_name:
        return made_again
    return backward if "transpose(" in op_name else forward


# ------------------------------------------------- protobuf, by hand
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one message's wire format: an int for a
    varint, bytes otherwise.  The few messages read here
    (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto) need no
    generated classes, which this installation does not have."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError("wire type %d" % wire)
        yield field, value


def hlo_protos(xplane_bytes):
    """{program name as the trace spells it: serialized HloProto} from
    the metadata plane.  XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 (map: value = 2), .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .bytes_value = 6; XStatMetadata.name = 2."""
    out = {}
    for field, plane in _fields(xplane_bytes):
        if field != 1:
            continue
        name, stat_names, events = None, {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1)] = bytes(meta.get(2, b"")).decode()
            elif f == 4:
                events.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in events:
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            program, proto = None, None
            for f, v in _fields(meta):
                if f == 2:
                    program = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1), "").lower() \
                            == HLO_STAT and 6 in stat:
                        proto = stat[6]
            if program is not None and proto is not None:
                out[program] = proto
    return out


def op_names(hlo_proto):
    """{instruction name: op_name} over every computation of a program.
    HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2."""
    out = {}
    module = dict(_fields(hlo_proto)).get(1, b"")
    for f, computation in _fields(module):
        if f != 3:
            continue
        for g, instruction in _fields(computation):
            if g != 2:
                continue
            name, op_name = None, ""
            for h, v in _fields(instruction):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    for k, w in _fields(v):
                        if k == 2:
                            op_name = bytes(w).decode()
            if name is not None:
                out[name] = op_name
    return out


# ------------------------------------------------------------ reduction
def own_times(events):
    """[(name, own ns)] of events (name, start, end) that nest on one
    line: an event's duration less its direct children's."""
    out, stack = [], []         # stack: [name, end, own]
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, own) for name, _e, own in stack)
    return out


def split_by_group(events, names):
    """Seconds by (group, pass) of events (HLO text, start, end);
    `names` maps an instruction to its op_name.  An instruction the
    program's HLO does not know, or one with no op_name, goes to REST,
    forward; their time is also returned as `unnamed`.  Third, by
    instruction: {name: [seconds, runs, op_name, HLO text]}."""
    by_group, unnamed, by_instruction = {}, 0.0, {}
    for text, own in own_times(events):
        m = _INSTRUCTION.match(text)
        instruction = m.group(1) if m else text
        op_name = names.get(instruction, "")
        key = (group_of(op_name), pass_of(op_name))
        if not op_name:
            unnamed += own * 1e-9
        by_group[key] = by_group.get(key, 0.0) + own * 1e-9
        rec = by_instruction.setdefault(instruction, [0.0, 0, op_name, text])
        rec[0] += own * 1e-9
        rec[1] += 1
    return by_group, unnamed, by_instruction


def reduce_by_group(path, window_name=None, step_module="train_step"):
    """For the device that was busy longest inside the last
    `window_name` span (None: the whole trace): seconds by (group,
    pass) of the instructions that ran inside programs named like
    `step_module`, and by instruction."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    names = {}
    for program, proto in hlo_protos(raw).items():
        if re.search(step_module, program):
            names.update(op_names(proto))
    data = ProfileData.from_serialized_xspace(raw)
    del raw
    window = (float("-inf"), float("inf")) if window_name is None else None
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE or window_name is None:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == window_name:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
    best = None
    for plane in data.planes:
        if window is None or not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lo, hi = window
        modules, ops = [], []
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                modules = [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if re.search(step_module, e.name)
                           and e.start_ns >= lo
                           and e.start_ns + e.duration_ns <= hi]
            elif line.name == trace_reduce.OPS_LINE:
                ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
        inside = trace_reduce.union(modules)
        kept, j = [], 0
        for ev in sorted(ops, key=lambda t: t[1]):
            while j < len(inside) and inside[j][1] <= ev[1]:
                j += 1
            if j < len(inside) and ev[1] >= inside[j][0]:
                kept.append(ev)
        by_group, unnamed, by_instruction = split_by_group(kept, names)
        got = {"by_group": by_group, "unnamed_s": unnamed,
               "by_instruction": by_instruction,
               "ops_s": sum(by_group.values()),
               "instructions_named": sum(1 for v in names.values() if v),
               "instructions": len(names)}
        if best is None or got["ops_s"] > best["ops_s"]:
            best = got
    return best


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--steps", type=int, default=1,
                    help="steps inside the window: times are per step")
    ap.add_argument("--window", default=None)
    ap.add_argument("--program", default="train_step")
    ap.add_argument("--top", type=int, default=0,
                    help="list the N instructions that took longest")
    args = ap.parse_args(argv)
    got = reduce_by_group(trace_reduce.find_xplane(args.trace_dir),
                          args.window, args.program)
    if got is None:
        sys.exit("no device plane, or no span %r, in the trace" % args.window)
    print(report(got, args.steps, args.top))


def report(got, steps, top=0):
    """The table main() prints: ms a step, a row a group (a split
    group's parts after it), a column a pass."""
    by, ms = got["by_group"], 1e3 / steps
    groups = [g for g, _p in GROUPS] + [REST]
    rows = sorted({g for g, _p in by},
                  key=lambda g: (groups.index(g.split(".")[0]), g))
    lines = ["%-14s" % "ms a step" + "".join("%12s" % p for p in PASSES)
             + "%12s" % "all"]
    for label, summed in [(g, [g]) for g in rows] + [("sum", rows)]:
        cells = [sum(by.get((g, p), 0.0) for g in summed) for p in PASSES]
        lines.append("%-14s" % label + "".join(
            "%12.3f" % (ms * c) for c in cells + [sum(cells)]))
    lines.append("instructions with no node: %.3f ms; %d of the program's %d "
                 "instructions carry an op_name" % (
                     ms * got["unnamed_s"], got["instructions_named"],
                     got["instructions"]))
    longest = sorted(got["by_instruction"].items(), key=lambda kv: -kv[1][0])
    for name, (s, runs, op_name, text) in longest[:top]:
        lines.append("%9.3f ms %5.1f runs  %s  ...%s\n%21s%s" % (
            ms * s, runs / steps, name, op_name[-110:], "", text[:200]))
    return "\n".join(lines)


if __name__ == "__main__":
    main()
