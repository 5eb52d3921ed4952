"""executor group: milliseconds a step in which an operation of the
fused step program ran on the busiest device."""


def read(run):
    if run["trace"] is None:
        return None
    return 1e3 * run["trace"]["device"]["step_module_s"] / run["steps"]
