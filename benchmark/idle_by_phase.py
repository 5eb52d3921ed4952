"""The device's idle time put down to the phase of `fit` the host was in.

The program keeps, in `telemetry.last_fit()`, the interval of every
span that closed on `fit`'s thread and of every pass of Python's
collector (`py.gc`), on the host's real-time clock (`time.time_ns()`).
The profiler's trace keeps its events on the same clock shifted by an
offset the program cannot see.  The window anchors it: the harness
wraps exactly one `fit` in `bench.window`, the root span `fit` opens
first and closes last inside it, and the device is idle at both edges
(the fit before it drained, this one drains at its last epoch's end).
So the first idle interval begins at the window's start, the last ends
at its end, and the root span's end lies on the window's end.

Each idle interval is then split by the innermost span open on `fit`'s
thread (`PHASE`; a span the table does not name takes its parent's
phase, and everything inside `fit.epoch_end` is the epoch end's), and
a collector's pass wins over any phase: it holds the interpreter.  The
phases tile the idle time inside the root span.  The arithmetic is
`trace_reduce`'s; nothing is read but the run's trace and the report.

None, and the line leaves the metrics out, where there is no trace (a
CPU rehearsal), where the program keeps no intervals (a parent from
before them) or dropped some, and where the anchor does not hold.
"""
from benchmark import fit_report, trace_reduce

GC = "py.gc"
OTHER = "fit_other"   # fit.metric, the self time of fit.forward_backward,
#                       fit.epoch and fit
PHASE = {"fit.next": "fit_next", "exec.stage": "exec_stage",
         "exec.launch": "exec_launch", "fit.update": "fit_update",
         "fit.epoch_end": "fit_epoch_end", "fit.metric": OTHER,
         "fit.forward_backward": OTHER, "fit.epoch": OTHER, "fit": OTHER}
WHOLE = "fit_epoch_end"   # its child spans are its own
SPAN_TOL_NS = 1e3     # the idle intervals' extent against the window
EDGE_TOL_NS = 1e6     # the root span's start against the window's


def keyed_intervals(intervals, shift):
    """The report's span intervals moved onto the trace's clock by
    `shift`, each under `(depth, phase)`, outermost first, then the
    collector's passes under `GC`: the order in which
    `trace_reduce.attribute_gaps` lets the later win."""
    spans = sorted((s, -e, n) for n, s, e in intervals if n != GC)
    stack, out = [], []       # stack: (end, phase) of the open spans
    for s, neg_e, name in spans:
        while stack and stack[-1][0] <= s:
            stack.pop()
        outer = stack[-1][1] if stack else OTHER
        phase = outer if outer == WHOLE else PHASE.get(name, outer)
        out.append(((len(stack), phase), s + shift, -neg_e + shift))
        stack.append((-neg_e, phase))
    out.sort(key=lambda iv: iv[0][0])
    out.extend((GC, s + shift, e + shift)
               for n, s, e in intervals if n == GC)
    return out


def split(run):
    """{phase: idle seconds} of the window, over `PHASE`'s phases and
    `GC`; None as the module docstring says."""
    if run["trace"] is None:
        return None
    report = fit_report.window_report(run)
    if report is None or "intervals" not in report \
            or report["intervals_dropped"]:
        return None
    idle = run["trace"]["device"]["idle"]
    if not idle:
        return None
    lo, hi = idle[0][0], idle[-1][1]
    if abs(hi - lo - run["trace"]["window_s"] * 1e9) > SPAN_TOL_NS:
        return None            # a window edge is busy
    shift = round(hi) - report["end_ns"]     # integers: no rounding
    if abs(report["start_ns"] + shift - lo) > EDGE_TOL_NS:
        return None
    by = trace_reduce.attribute_gaps(
        idle, keyed_intervals(report["intervals"], shift), None)
    out = {}
    for key, seconds in by.items():
        if key is not None:    # None: outside the root span
            phase = key if key == GC else key[1]
            out[phase] = out.get(phase, 0.0) + seconds
    return out


def ms_per_step(run, phase):
    """Idle milliseconds a step under `phase`; None as above."""
    by = split(run)
    if by is None:
        return None
    return 1e3 * by.get(phase, 0.0) / run["steps"]
