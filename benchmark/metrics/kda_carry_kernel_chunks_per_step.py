"""kernels: chunks of a step that the delta rules' carry went through
in its own kernel, the state held in fast memory, over all delta-rule
layers and sequences (the program's counter `kda.carry_kernel_chunks`,
tallied on the device and read once an epoch): `kda_chunks_per_step`
when the kernel engages, 0 when the carry took `lax.scan` (another
backend, a head of no whole lanes, a chunk the kernel does not take).
No reading where the program has no such counter."""
from benchmark import fit_report


def read(run):
    return fit_report.counter_per_step(run, "kda.carry_kernel_chunks")
