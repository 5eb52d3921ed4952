"""training loop: host milliseconds a step of `fit` that no phase's span
covers: the self time of `fit` and of `fit.epoch`.  Large means a phase
is missing its span."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "fit", "fit.epoch",
                                       part="self_ns")
