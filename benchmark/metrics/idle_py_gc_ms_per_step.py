"""host interpreter: device-idle milliseconds a step while a pass of
Python's collector over its oldest generation ran, whatever phase
`fit` was in: the pass holds the interpreter
(`benchmark/idle_by_phase.py`)."""
from benchmark import idle_by_phase


def read(run):
    return idle_by_phase.ms_per_step(run, idle_by_phase.GC)
