"""training loop: device-idle milliseconds a step inside `fit.update`
and outside `exec.launch`: the update's per-parameter host bookkeeping
(`benchmark/idle_by_phase.py`)."""
from benchmark import idle_by_phase


def read(run):
    return idle_by_phase.ms_per_step(run, "fit_update")
