"""input layer: host milliseconds a step that `fit` waited in `next()` of
its iterator, from the program's own span around the call (the pull
that ends an epoch counts)."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "fit.next")
