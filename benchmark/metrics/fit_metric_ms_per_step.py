"""training loop: host milliseconds a step for `update_metric`, the
monitor and the batch-end callbacks."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "fit.metric")
