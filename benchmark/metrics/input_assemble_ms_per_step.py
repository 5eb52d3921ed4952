"""input layer: host milliseconds a step `ImageRecordIter.next()` spent
stacking, mirroring and normalising the decoded batch."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "input.assemble")
