"""whole step: shape-derived forward and backward operations of the
window's steps over the traced wall time, the chips and the peak."""


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    flops = run["counts"].train_flops_per_image(run["arch"]) \
        * run["per_chip_batch"] * run["chips"] * run["steps"]
    return 100.0 * flops / (run["trace"]["window_s"] * run["chips"]
                            * run["peaks"]["bf16_flops_per_s"])
