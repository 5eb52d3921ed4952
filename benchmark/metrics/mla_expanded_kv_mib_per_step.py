"""kernels: the per-head keys and values a step's latent attention
makes from the latent, in MiB (the program's counter
`mla.expanded_kv_bytes`, by shape: rows x heads x (key + value width) x
the activation type's bytes a layer, forward): what a kernel that reads
the latent itself would not write.  No reading where the program has no
such op."""
from benchmark import fit_report


def read(run):
    made = fit_report.counter_per_step(run, "mla.expanded_kv_bytes")
    return None if made is None else made / 2 ** 20
