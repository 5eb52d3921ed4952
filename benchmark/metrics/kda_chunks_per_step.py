"""kernels: chunks the gated delta rules of a step went through, over
all delta-rule layers and sequences (the program's counter
`kda.chunks`, tallied on the device and read once an epoch): sequences
x seq_len / chunk x layers, so it moves only when the program chunks
otherwise.  No reading where the program has no such op."""
from benchmark import fit_report


def read(run):
    return fit_report.counter_per_step(run, "kda.chunks")
