"""device: share of the traced window in which no operation ran on the
busiest device."""


def read(run):
    if run["trace"] is None:
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["device"]["busy_s"] / t["window_s"])
