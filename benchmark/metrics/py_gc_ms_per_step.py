"""host interpreter: host milliseconds a step in passes of Python's
collector over its oldest generation while the window's `fit` was open,
on any thread (the program's counter `py.gc_ns`).  A program that keeps
the report's intervals has the collector's hook, so a window with no
such pass reads 0; no reading where the program counts none (a parent
from before the hook)."""
from benchmark import fit_report


def read(run):
    report = fit_report.window_report(run)
    if report is None or "intervals" not in report:
        return None
    return report["counters"].get("py.gc_ns", 0) / run["steps"] / 1e6
