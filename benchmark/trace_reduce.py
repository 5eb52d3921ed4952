"""From a profiler trace (`.xplane.pb`) to the numbers metrics read.

A trace is planes (one per device, one for the host), each with lines,
each with events (name, start, duration; nanoseconds).  Everything
here works on plain lists of `(name, start_ns, end_ns)`, so the
arithmetic is checked on a recorded trace without a chip
(`tests/benchmark_harness`).

On a TPU the device planes are named `/device:TPU:<n>`; their line
`XLA Ops` holds one event per executed HLO operation and `XLA Modules`
one per executed program.  Host spans written with
`jax.profiler.TraceAnnotation` land on the thread lines of `/host:CPU`.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


# ------------------------------------------------------------ intervals
def union(intervals):
    """Merge `(start, end)` pairs into disjoint, sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of disjoint sorted `a` that no interval of disjoint
    sorted `b` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of `[lo, hi]` given disjoint sorted `busy`."""
    return subtract([(lo, hi)], busy)


# -------------------------------------------------------------- reading
_SHAPE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")
_KIND = re.compile(r"kind=(\w+)")


def op_label(name):
    """A short name for an event of the ops line.  The TPU's trace
    names an operation by its whole HLO text,
    `%fusion.3 = bf16[256,64,56,56]{...} fusion(...), kind=kLoop, ...`;
    that becomes `fusion.3 kLoop bf16[256,64,56,56]`."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    parts = [op.lstrip("%")]
    kind = _KIND.search(rest)
    if kind:
        parts.append(kind.group(1))
    shape = _SHAPE.search(rest)
    if shape:
        parts.append(shape.group(0))
    return " ".join(parts)


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path, device_plane=DEVICE_PLANE, ops_line=OPS_LINE,
         modules_line=MODULES_LINE, host_plane=HOST_PLANE,
         span_prefix="bench."):
    """{"devices": {plane name: {"ops": [...], "modules": [...]}},
        "spans": [...]} with events as (name, start_ns, end_ns).

    `spans` are the host events whose name starts with `span_prefix`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = device_plane.match(plane.name)
        if m:
            dev = devices.setdefault(plane.name,
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {ops_line: "ops", modules_line: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key].extend(
                    (op_label(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name == host_plane:
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(span_prefix))
    return {"devices": devices, "spans": spans}


# ------------------------------------------------------------ reduction
def busy_intervals(ops, lo, hi):
    return clip(union((s, e) for _n, s, e in ops), lo, hi)


def time_by_name(ops, lo, hi):
    """Seconds per operation name inside `[lo, hi]`, longest first."""
    acc = {}
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])


def exposed_collective_ns(ops, lo, hi):
    """Nanoseconds of `[lo, hi]` in which a collective operation runs
    on the device and nothing else does."""
    coll = clip(union((s, e) for n, s, e in ops if COLLECTIVE.search(n)),
                lo, hi)
    rest = clip(union((s, e) for n, s, e in ops
                      if not COLLECTIVE.search(n)), lo, hi)
    return total(subtract(coll, rest))


def attribute_gaps(idle, spans, default):
    """Split idle intervals by what the host was doing: seconds under
    each span name, and under `default` where no span covers.  A later
    span in `spans` wins over an earlier one where they overlap, so
    list the outermost first."""
    by, left = {}, list(idle)
    for name in reversed(list(dict.fromkeys(n for n, _s, _e in spans))):
        cover = union((s, e) for n, s, e in spans if n == name)
        rest = subtract(left, cover)
        by[name] = (total(left) - total(rest)) * 1e-9
        left = rest
    by[default] = by.get(default, 0.0) + total(left) * 1e-9
    return by


def reduce_window(trace, window, step_module=None, steps=None):
    """The numbers of one traced window.

    `window` is `(lo_ns, hi_ns)`.  Per device: busy seconds, idle
    intervals, exposed collective seconds, and the seconds in which an
    operation ran inside the program `step_module` (a regex on the
    names of `modules`).  A device on which no program of that name
    ran, or on which it ran another number of times than `steps`, is an
    error: under a new name the same step must not be read as
    something else.  With `step_module=None` the step's seconds are the
    device's busy seconds, whatever ran."""
    lo, hi = window
    out = {"window_s": (hi - lo) * 1e-9, "devices": {}}
    for plane, dev in sorted(trace["devices"].items()):
        busy = busy_intervals(dev["ops"], lo, hi)
        stepping, runs = busy, None
        if step_module is not None:
            mods = [(s, e) for n, s, e in dev["modules"]
                    if re.search(step_module, n)]
            runs = sum(1 for s, e in mods if s >= lo and e <= hi)
            if not mods or (steps is not None and runs != steps):
                raise RuntimeError(
                    "%s: %d run(s) of a program named like %r lie inside "
                    "the window, %s expected; programs seen: %s"
                    % (plane, runs, step_module,
                       "some" if steps is None else steps,
                       sorted({n for n, _s, _e in dev["modules"]})[:8]))
            stepping = subtract(clip(union(mods), lo, hi),
                                gaps(busy, lo, hi))
        out["devices"][plane] = {
            "busy_s": total(busy) * 1e-9,
            "idle": gaps(busy, lo, hi),
            "exposed_collective_s":
                exposed_collective_ns(dev["ops"], lo, hi) * 1e-9,
            "step_module_s": total(stepping) * 1e-9,
            "step_module_runs": runs,
        }
    return out
