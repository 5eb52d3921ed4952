"""Per-instruction byte audit of a compiled XLA program.

The roofline work (PERF.md) established ResNet-50 training here is
HBM-bound at ~50 GB/step (XLA cost model's "bytes accessed").  This tool
answers *where those bytes go*: it parses the post-optimization HLO of
the train-step program and charges every entry-computation instruction
its operand + output buffer sizes — the traffic that actually crosses
HBM at fusion boundaries — then ranks instructions and aggregates by
category (convolution / loop fusion / reduce / copy / ...) and by the
source op recorded in HLO metadata.

Usage (real TPU):
    python tools/hlo_byte_audit.py [--batch 128] [--top 40]

The byte model: fusion internals live in registers/VMEM; only a
fusion's external operands and outputs touch HBM.  That is the same
model XLA's own cost analysis uses for "bytes accessed", so the totals
here reconcile with ``analyze_compiled``'s (within the cost model's
double-count of shared operands).
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0, "s4": 1, "u4": 1,
    "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def shape_bytes(type_str):
    """Bytes of an HLO type string; tuples sum their elements."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_META_RE = re.compile(r'op_name="([^"]*)"')


def _split_instr(ln):
    """Split one HLO instruction line into (name, type_str, opcode, rest)
    or None.  Bracket-aware: type strings carry layout/memory-space
    annotations like f32[128,1000]{1,0:T(8,128)S(1)} and tuple types
    contain spaces, so a regex over char classes is not enough."""
    s = ln.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    eq = s.find(" = ")
    if eq < 0 or not s.startswith("%"):
        return None
    name = s[1:eq]
    rhs = s[eq + 3:]
    # type token: ends at the first space at bracket depth 0
    depth = 0
    i = 0
    for i, c in enumerate(rhs):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
    else:
        return None
    type_str, tail = rhs[:i], rhs[i + 1:]
    p = tail.find("(")
    if p < 0:
        return None
    opcode = tail[:p].strip()
    if not re.fullmatch(r"[\w\-]+", opcode or ""):
        return None
    return name, type_str, opcode, tail[p + 1:]

# instructions that are layout/book-keeping, not HBM traffic
_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
         "after-all", "partition-id", "replica-id", "iota"}


def parse_entry(hlo_text):
    """Yield (name, out_bytes, opcode, operand_names, op_name_meta) for
    each instruction of the ENTRY computation."""
    lines = hlo_text.splitlines()
    in_entry = False
    for ln in lines:
        if ln.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and ln.startswith("}"):
            break
        if not in_entry:
            continue
        m = _split_instr(ln)
        if m is None:
            continue
        name, type_str, opcode, rest = m
        # operands: names inside the top-level call parens, before any
        # attribute list (", kind=", ", calls=", ", metadata=")
        depth, i = 1, 0
        while i < len(rest) and depth > 0:
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
            i += 1
        opstr = rest[:i - 1] if depth == 0 else rest
        operands = _OPERAND_RE.findall(opstr)
        meta = _META_RE.search(ln)
        yield (name, shape_bytes(type_str), opcode, operands,
               meta.group(1) if meta else "")


def audit(hlo_text):
    """Return (rows, total_bytes): rows = [(bytes, name, opcode, meta)]."""
    defs = {}
    instrs = []
    for name, out_b, opcode, operands, meta in parse_entry(hlo_text):
        defs[name] = out_b
        instrs.append((name, out_b, opcode, operands, meta))
    rows = []
    for name, out_b, opcode, operands, meta in instrs:
        if opcode in _FREE:
            continue
        in_b = sum(defs.get(o, 0) for o in operands)
        rows.append((out_b + in_b, name, opcode, meta))
    rows.sort(reverse=True)
    return rows, sum(r[0] for r in rows)


def _fmt_gb(b):
    return "%8.3f" % (b / 1e9)


def report(rows, total, top=40, out=sys.stdout):
    w = out.write
    w("total bytes accessed (entry instrs): %s GB\n" % _fmt_gb(total).strip())
    by_cat = collections.Counter()
    by_src = collections.Counter()
    for b, _n, opcode, meta in rows:
        by_cat[opcode] += b
        # collapse jax scopes: keep the trailing "op[:sub]" segments
        src = "/".join(meta.split("/")[-2:]) if meta else "(none)"
        by_src[src] += b
    w("\n== by opcode ==\n")
    for k, v in by_cat.most_common():
        w("  %s GB  %5.1f%%  %s\n" % (_fmt_gb(v), 100.0 * v / total, k))
    w("\n== top source ops (HLO metadata) ==\n")
    for k, v in by_src.most_common(25):
        w("  %s GB  %5.1f%%  %s\n" % (_fmt_gb(v), 100.0 * v / total, k))
    w("\n== top instructions ==\n")
    for b, name, opcode, meta in rows[:top]:
        w("  %s GB  %-14s %-28s %s\n"
          % (_fmt_gb(b), opcode, name[:28], meta[-90:]))


def compiled_train_step(batch=128, img=224, num_classes=1000,
                        compute_dtype="bfloat16", network="resnet-50"):
    """Build the train-step program through Module and return the jax
    `Compiled` for its one-program fwd+bwd+update step."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.io import DataBatch
    import jax

    net = models.get_symbol(network, num_classes=num_classes)
    ctxs = [mx.Context("tpu", i) for i in range(len(jax.devices()))]
    mod = mx.mod.Module(net, context=ctxs, compute_dtype=compute_dtype)
    mod.bind(data_shapes=[("data", (batch, 3, img, img))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 1e-4,
                                         "rescale_grad": 1.0 / batch})
    rng = np.random.RandomState(0)
    X = rng.rand(batch, 3, img, img).astype(np.float32)
    y = rng.randint(0, num_classes, batch).astype(np.float32)
    eg = mod._exec_group
    sharding = eg._batch_sharding
    Xd = mx.nd.NDArray(jax.device_put(X, sharding), ctx=ctxs[0])
    yd = mx.nd.NDArray(jax.device_put(y, sharding), ctx=ctxs[0])
    b = DataBatch(data=[Xd], label=[yd])
    mod.forward_backward(b)
    mod.update()
    fn, structs = eg._last_step
    return fn.lower(*structs).compile()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--network", default="resnet-50")
    ap.add_argument("--dump", help="also write full optimized HLO here")
    args = ap.parse_args(argv)
    comp = compiled_train_step(batch=args.batch, network=args.network)
    txt = comp.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(txt)
    rows, total = audit(txt)
    report(rows, total, top=args.top)


if __name__ == "__main__":
    main()
