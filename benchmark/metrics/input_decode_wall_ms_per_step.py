"""input layer: the decode stage's own wall milliseconds a batch, from
its first pool task's start to its last task's end (the program's
counter `input.decode_wall_ns`, counted when `fit` takes the batch)."""
from benchmark import fit_report


def read(run):
    value = fit_report.counter_per_step(run, "input.decode_wall_ns")
    return None if value is None else value / 1e6
