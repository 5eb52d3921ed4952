"""training loop: device-idle milliseconds a step that fall outside
the iterator's `next()`: what `fit` itself (stage, dispatch, metric,
epoch end) costs the device."""


def read(run):
    if run["trace"] is None:
        return None
    return 1e3 * run["trace"]["idle_elsewhere_s"] / run["steps"]
