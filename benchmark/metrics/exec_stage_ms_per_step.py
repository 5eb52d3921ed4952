"""executor group: host milliseconds a step placing the batch on the
mesh (`MeshExecutorGroup._stage`, `stage_stacked`)."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "exec.stage")
