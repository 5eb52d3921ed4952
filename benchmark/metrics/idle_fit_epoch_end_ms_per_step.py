"""training loop: device-idle milliseconds a step inside
`fit.epoch_end` and its child spans: the metric drain, callbacks, eval
and the iterator's reset (`benchmark/idle_by_phase.py`)."""
from benchmark import idle_by_phase


def read(run):
    return idle_by_phase.ms_per_step(run, "fit_epoch_end")
