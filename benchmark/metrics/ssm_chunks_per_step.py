"""kernels: chunks the selective scans of a step went through, over
all state-space layers and sequences (the program's counter
`ssm.chunks`, tallied on the device and read once an epoch): sequences
x seq_len / chunk x layers, so it moves only when the program chunks
otherwise.  No reading where the program has no such op."""
from benchmark import fit_report


def read(run):
    return fit_report.counter_per_step(run, "ssm.chunks")
