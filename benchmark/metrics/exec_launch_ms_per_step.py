"""executor group: host milliseconds a step inside the call that
enqueues the step program."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "exec.launch")
