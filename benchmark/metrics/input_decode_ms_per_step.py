"""input layer: host milliseconds a step `ImageRecordIter.next()` waited
for its pool to read, decode and crop the batch's records."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "input.decode")
