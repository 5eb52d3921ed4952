"""input layer: megabytes of host memory a step handed to
`jax.device_put` on the input path (the iterator's puts, and the
executor group's staging of values that are not on the device yet)."""
from benchmark import fit_report


def read(run):
    value = fit_report.counter_per_step(run, "input.h2d_bytes")
    return None if value is None else value / 1e6
