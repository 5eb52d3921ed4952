"""training loop: host milliseconds a step inside `update()`: on the
fused path per-parameter bookkeeping, then the launch."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "fit.update")
