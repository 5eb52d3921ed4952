"""kernels: token-expert pairs a step that the experts held should have
computed and whose row the layer's fold did not read back, over all
expert layers (the program's counter `moe.dropped`, counted from the
indices its gathers use): 0, or tokens were dropped."""
from benchmark import fit_report


def read(run):
    return fit_report.counter_per_step(run, "moe.dropped")
