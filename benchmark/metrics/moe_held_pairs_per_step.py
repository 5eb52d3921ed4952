"""kernels: token-expert pairs a step that the experts held computed,
over all expert layers (the program's counter `moe.held_pairs`, tallied
on the device and read once an epoch).  `counts/afmoe.py` counts the
routed experts' operations at even routing, `held_pairs_per_step`
there: this says how far a run was from it."""
from benchmark import fit_report


def read(run):
    return fit_report.counter_per_step(run, "moe.held_pairs")
