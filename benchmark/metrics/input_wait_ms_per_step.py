"""input layer: host milliseconds a step waited inside the iterator's
`next()`, from the benchmark's own clock around it."""


def read(run):
    return 1e3 * run["input_wait_s"] / run["steps"]
