"""input layer: host milliseconds a step inside the calls of
`ImageRecordIter.next()` that hand host memory to `jax.device_put`
(the calls, not the transfers' completion)."""
from benchmark import fit_report


def read(run):
    return fit_report.span_ms_per_step(run, "input.put")
