"""kernels: the busiest held expert's load over the mean load of the
experts held, averaged over steps and expert layers (the program's
counters `moe.load_max` and `moe.load_mean`): 1 is even routing."""
from benchmark import fit_report


def read(run):
    top = fit_report.counter_per_step(run, "moe.load_max")
    mean = fit_report.counter_per_step(run, "moe.load_mean")
    if top is None or not mean:
        return None
    return top / mean
