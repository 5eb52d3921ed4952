"""kernels: the chunk-boundary states a step's gated delta rules carry
from chunk to chunk, in MiB (the program's counter `kda.carried_bytes`,
by shape: chunks x heads x head_dim x head_dim x 4 bytes a layer): what
a delta rule holds between its chunks and its backward pass reads
again.  No reading where the program has no such op."""
from benchmark import fit_report


def read(run):
    carried = fit_report.counter_per_step(run, "kda.carried_bytes")
    return None if carried is None else carried / 2 ** 20
