"""training loop: device-idle milliseconds a step anywhere else inside
`fit`: `fit.metric`, and the self time of `fit.forward_backward`,
`fit.epoch` and `fit` (`benchmark/idle_by_phase.py`)."""
from benchmark import idle_by_phase


def read(run):
    return idle_by_phase.ms_per_step(run, "fit_other")
