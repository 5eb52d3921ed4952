"""kernels: the least time the chip could take for the step (the
larger of shape-derived operations over peak FLOP/s and least bytes
over peak bytes/s) over the time the step program ran on the device."""


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    took = run["trace"]["device"]["step_module_s"] / run["steps"]
    if took <= 0:
        return None
    b = run["counts"].step_bounds(run["arch"], run["per_chip_batch"],
                                  run["compute_dtype"], run["peaks"])
    return 100.0 * max(b["ops_s"], b["bytes_s"]) / took
