"""The one traffic generator: a mix is a data file, this reads it.

`benchmark/traffic/<mix>.json` says how the training loop is fed.  Its
`feed` names the kind of source, and each kind is a file of its own
beside the mixes, `benchmark/traffic/<feed>.py`, found by that name:

    {"feed": "resident", "distinct_batches": 8, "steps_per_epoch": 100}
        batches made on the device from the seed and handed out in
        turn: input costs nothing (`traffic/resident.py`);
    {"feed": "recordio", "records": 6400, "distinct_images": 256,
     "jpeg_quality": 90, "iterator": {...ImageRecordIter arguments...}}
        a `.rec` of labelled JPEGs written from the seed and read by
        the program's own `ImageRecordIter` (`traffic/recordio.py`).

A kind offers `make_feed(mix, cfg, seed, chips, workdir)`,
`reference_batches(mix, cfg, seed, chips, kept, sharding)` and
`own_batches(mix, cfg, seed, chips, steps, sharding)`; a new kind is a
new file and edits none.  Every source comes back inside a `Feed`, the
iterator `fit` sees: it times every `next()` on the host clock, marks
it in the profiler's trace, can end an epoch early (warm-up), and
keeps what it delivered in the first steps for the check.
"""
import importlib.util
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
_KINDS = {}


def load(path):
    with open(path) as f:
        return json.load(f)


def kind(feed):
    """The module `benchmark/traffic/<feed>.py`, loaded once."""
    if feed not in _KINDS:
        path = os.path.join(HERE, "traffic", feed + ".py")
        if not os.path.isfile(path):
            raise ValueError("traffic feed %r: no file %s" % (feed, path))
        spec = importlib.util.spec_from_file_location(
            "benchmark_traffic_" + feed.replace("-", "_").replace(".", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _KINDS[feed] = mod
    return _KINDS[feed]


def make_feed(mix, cfg, seed, chips, workdir):
    """The cell's `Feed`; what it needs on disk goes under `workdir`."""
    return kind(mix["feed"]).make_feed(mix, cfg, seed, chips, workdir)


def reference_batches(mix, cfg, seed, chips, kept, sharding=None):
    """The rows of the first steps as the reference takes them, and the
    widest gap between a delivered value and the reference's own (None
    where the rows are the benchmark's own).  `kept` is what the `Feed`
    kept of those steps."""
    return kind(mix["feed"]).reference_batches(mix, cfg, seed, chips, kept,
                                               sharding)


def own_batches(mix, cfg, seed, chips, steps, sharding=None):
    """The first batches of a seed made without the program, for the
    control's readings (`control.py`)."""
    return kind(mix["feed"]).own_batches(mix, cfg, seed, chips, steps,
                                         sharding)


# ----------------------------------------------------------------- feed
class Feed:
    """The iterator handed to `fit`, around the cell's own source."""

    def __init__(self, source, provide_data, provide_label, steps_per_epoch,
                 keep_rows):
        self._source = source
        self.provide_data = provide_data
        self.provide_label = provide_label
        self.steps_per_epoch = steps_per_epoch   # None: the source's own
        self.cut = None           # an earlier end of the epoch (warm-up)
        self.keep_first = 0       # deliveries to keep for the check
        self.keep_rows = keep_rows    # with their rows, or only counted
        self.kept = []
        self.wait_s = 0.0
        self.steps = 0
        self.epoch_ends = []      # host clock at each epoch's reset
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        end = self.steps_per_epoch if self.cut is None else self.cut
        if end is not None and self._i >= end:
            raise StopIteration
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.next"):
            batch = next(self._source)
        self.wait_s += time.perf_counter() - t0
        self._i += 1
        self.steps += 1
        if len(self.kept) < self.keep_first:
            self.kept.append((batch.data[0].asnumpy(),
                              batch.label[0].asnumpy())
                             if self.keep_rows else None)
        return batch

    next = __next__

    def reset(self):
        self.epoch_ends.append(time.perf_counter())
        self._i = 0
        self._source.reset()

    def clear_clocks(self):
        self.wait_s, self.steps, self.epoch_ends = 0.0, 0, []
