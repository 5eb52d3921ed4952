"""kernels: chunks of a step whose decayed inner products (Akk, Aqk)
the delta rules' own kernels made, over all delta-rule layers and
sequences (the program's counter `kda.kernel_chunks`, tallied on the
device and read once an epoch): `kda_chunks_per_step` when the kernels
engage, 0 when the op fell back to XLA's form of the same arithmetic
(another backend, operands that are not float32, a head of no whole
lanes, a chunk the kernels do not take).  No reading where the program
has no such counter."""
from benchmark import fit_report


def read(run):
    return fit_report.counter_per_step(run, "kda.kernel_chunks")
