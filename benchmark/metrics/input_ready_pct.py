"""input layer: of the window's `next()` calls on `ImageRecordIter`,
the share that found a finished batch waiting (the program's counters
`input.ready` and `input.waited`): 100 where the iterator always runs
ahead of `fit`, 0 where `fit` waits for every batch."""
from benchmark import fit_report


def read(run):
    ready = fit_report.counter_per_step(run, "input.ready")
    waited = fit_report.counter_per_step(run, "input.waited")
    if ready is None and waited is None:
        return None
    ready, waited = ready or 0.0, waited or 0.0
    return 100.0 * ready / (ready + waited)
