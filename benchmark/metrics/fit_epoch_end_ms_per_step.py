"""training loop: host milliseconds a step between two epochs: the
metric drain (it waits for every step), parameter sync, callbacks,
eval and the iterator's reset, over all steps."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "fit.epoch_end")
