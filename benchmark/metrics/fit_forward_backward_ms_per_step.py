"""training loop: host milliseconds a step inside `forward_backward`: on
the fused path staging the batch and leaving the step pending."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "fit.forward_backward")
