"""kernels: the bytes a step's segmented backward pass is handed in
place of making them again, in GiB (the program's counter
`remat.kept_bytes`: the values its ops named and its remat policy keeps
inside the checkpointed segments, by shape).  0 where the policy keeps
nothing; no reading where the program does not recompute or keeps no
such count."""
from benchmark import fit_report


def read(run):
    kept = fit_report.counter_per_step(run, "remat.kept_bytes")
    return None if kept is None else kept / 2 ** 30
