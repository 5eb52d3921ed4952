"""The program's own account of the window: `telemetry.last_fit()`.

`Module.fit` clocks its phases itself, each where the work happens
(`mxnet_tpu/telemetry/tracing.py`), and keeps the report of the
process's last finished `fit`.  The window is that `fit`, so the
readers `metrics/fit_*.py`, `metrics/exec_*.py` and `metrics/input_*.py`
take their numbers from it.  No device plane is needed: a CPU rehearsal
reads them too.

A program without the report (a parent commit from before it) gives
None, and the line leaves those metrics out.  A report that is not the
window's (another number of steps) is an error, as a trace without the
step program's runs is one.
"""


def window_report(run):
    """The report of the window's `fit`, or None where the program
    keeps none."""
    from mxnet_tpu import telemetry
    last_fit = getattr(telemetry, "last_fit", None)
    if last_fit is None:
        return None
    report = last_fit()
    if report is None or report["steps"] != run["steps"]:
        raise RuntimeError(
            "the program's last fit report counts %s step(s), the window "
            "%d: it is not the window's"
            % (None if report is None else report["steps"], run["steps"]))
    return report


def span_ms_per_step(run, *names, part="total_ns"):
    """Host milliseconds a step under the spans `names` together
    (`part="self_ns"`: outside their child spans); None where the
    program keeps no report or opened none of them."""
    report = window_report(run)
    if report is None:
        return None
    rows = [report["spans"][n] for n in names if n in report["spans"]]
    if not rows:
        return None
    return 1e-6 * sum(r[part] for r in rows) / run["steps"]


def counter_per_step(run, name):
    """A counter of the report over the steps; None as above."""
    report = window_report(run)
    if report is None or name not in report["counters"]:
        return None
    return report["counters"][name] / run["steps"]
