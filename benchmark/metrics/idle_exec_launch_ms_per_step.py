"""executor group: device-idle milliseconds a step while the innermost
span open on `fit`'s thread was `exec.launch`: the call that enqueues
the step program (`benchmark/idle_by_phase.py`)."""
from benchmark import idle_by_phase


def read(run):
    return idle_by_phase.ms_per_step(run, "exec_launch")
