"""`ImageRecordIter` runs ahead of its caller: a thread of its own makes
the coming batches.  What a caller can observe must not depend on how
far ahead it was: the stream is the serial iterator's, bit for bit,
after resets too; errors arrive in their turn; the thread goes when the
iterator does.

Every case runs under a limit of its own (`limited`), so a producer
that hangs fails one test and not the suite's clock.
"""
import faulthandler
import functools
import gc
import io
import random
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio, runtime, sym
from mxnet_tpu import telemetry as tel

BATCH, SHAPE, RECORDS = 8, (3, 32, 32), 20      # 3 batches, the last padded
PRODUCER = "ImageRecordIter-producer"


def limited(seconds):
    """Run the test's body on a thread and fail if it is still running
    after `seconds`."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            box = {}

            def body():
                try:
                    fn(*args, **kwargs)
                except BaseException as err:   # handed to pytest's thread
                    box["err"] = err

            t = threading.Thread(target=body, daemon=True)
            t.start()
            t.join(seconds)
            if t.is_alive():
                faulthandler.dump_traceback(file=sys.stderr)
                pytest.fail("still running after %d s" % seconds)
            if "err" in box:
                raise box["err"]
        return run
    return wrap


@pytest.fixture(scope="module")
def rec(tmp_path_factory):
    from PIL import Image
    path = str(tmp_path_factory.mktemp("prefetch") / "twenty.rec")
    out = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(RECORDS):
        buf = io.BytesIO()
        Image.fromarray(rng.randint(0, 255, (40, 40, 3), dtype=np.uint8)) \
            .save(buf, format="JPEG", quality=90)
        out.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                buf.getvalue()))
    out.close()
    return path


def _kw(rec, **more):
    kw = dict(path_imgrec=rec, data_shape=SHAPE, batch_size=BATCH,
              shuffle=True, rand_mirror=True, mean_r=10.0, std_b=2.0,
              scale=0.5, seed=3, preprocess_threads=2)
    kw.update(more)
    return kw


MODES = {"host": {}, "device": {"device_augment": True},
         "defer": {"device_augment": "defer"},
         "cached": {"cache_decoded": True}}


class Serial:
    """The iterator as it was before it ran ahead: every stage when the
    batch is asked for, on the calling thread, from a position and
    draws of its own.  The stage functions are those of an
    `ImageRecordIter` that is never iterated (and so starts no
    thread)."""

    def __init__(self, **kw):
        self.it = mx.io.ImageRecordIter(**kw)
        self.rng = random.Random(kw["seed"])
        self.epoch = self.cur = self.batch_seq = 0

    def reset(self):
        self.epoch, self.cur, self.batch_seq = self.epoch + 1, 0, 0

    def set_epoch(self, epoch):
        self.epoch, self.batch_seq = int(epoch), 0

    def next(self):
        it = self.it
        seq = it._order(self.epoch)
        if self.cur >= len(seq):
            raise StopIteration
        idxs = seq[self.cur:self.cur + BATCH]
        self.cur += BATCH
        pad = BATCH - len(idxs)
        idxs = idxs + seq[:pad]
        decoded = [it._decode_one(i) for i in idxs]
        imgs = np.stack([d[0] for d in decoded])
        labels = np.stack([d[1] for d in decoded])[:, 0].astype(np.float32)
        if it._defer:
            spec = it._aug_spec
            params = spec.draw("data", self.epoch, self.batch_seq, BATCH)
            self.batch_seq += 1
            data = [imgs] + [params[d.name]
                             for d in spec.param_descs("data", BATCH)]
            return data, labels, pad
        mirror = np.array([self.rng.random() < 0.5 for _ in idxs], np.uint8)
        if it.device_augment:
            return [np.asarray(it._device_preprocess(imgs, mirror))], \
                labels, pad
        return [runtime.assemble_batch(imgs, mean=it.mean,
                                       std=it.std / it.scale,
                                       mirror=mirror)], labels, pad


def _same(batch, want):
    data, labels, pad = want
    assert batch.pad == pad
    assert len(batch.data) == len(data)
    for got, ref in zip(batch.data, data):
        got = got.asnumpy() if hasattr(got, "asnumpy") else np.asarray(got)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()
    assert batch.label[0].asnumpy().tobytes() == labels.tobytes()


def _ready(it):
    return sum(s.done for s in it._ahead.slots)


def _let_run_ahead(it, n=2):
    end = time.monotonic() + 20
    while _ready(it) < n:
        assert time.monotonic() < end, "the producer made nothing"
        time.sleep(0.002)


def _hold_back(it, seconds=0.02):
    """Slow decodes: when `next()` returns, no later batch is ready."""
    decode = it._decode_one

    def slow(idx):
        time.sleep(seconds)
        return decode(idx)
    it._decode_one = slow


@pytest.mark.parametrize("mode", sorted(MODES))
@limited(120)
def test_stream_is_the_serial_iterators(rec, mode):
    """Two epochs and the first batch of a third, shuffled and
    mirrored: every batch equals the one the stages give when called
    one after the other, bitwise, and each epoch ends where it did."""
    it = mx.io.ImageRecordIter(**_kw(rec, **MODES[mode]))
    ref = Serial(**_kw(rec, **MODES[mode]))
    for epoch in range(2):
        for _ in range(3):
            _same(it.next(), ref.next())
        assert it.epoch_coord == ref.epoch == epoch
        for _ in range(2):                  # the end is said again
            with pytest.raises(StopIteration):
                it.next()
        it.reset()
        ref.reset()
    _same(it.next(), ref.next())
    it.close()


@pytest.mark.parametrize("ahead", ["held back", "run ahead"])
@pytest.mark.parametrize("after", [1, 2, 3])
@pytest.mark.parametrize("mode", ["host", "defer"])
@limited(120)
def test_reset_gives_the_same_continuation(rec, mode, after, ahead):
    """A `reset()` after 1, 2 or 3 batches of an epoch: the next epoch
    and the one after it are the serial iterator's, whether the
    producer had batches waiting or none."""
    it = mx.io.ImageRecordIter(**_kw(rec, **MODES[mode]))
    ref = Serial(**_kw(rec, **MODES[mode]))
    if ahead == "held back":
        _hold_back(it)
    for _ in range(after):
        _same(it.next(), ref.next())
    if ahead == "run ahead":
        _let_run_ahead(it)
    else:
        assert _ready(it) == 0
    it.reset()
    ref.reset()
    for _ in range(2):
        for _ in range(3):
            _same(it.next(), ref.next())
        with pytest.raises(StopIteration):
            it.next()
        it.reset()
        ref.reset()
    it.close()


@pytest.mark.parametrize("ahead", ["held back", "run ahead"])
@pytest.mark.parametrize("mode", ["host", "defer"])
@limited(120)
def test_set_epoch_gives_the_same_continuation(rec, mode, ahead):
    """`set_epoch(k)` in the middle of an epoch pins another order and
    other draws from the next batch on, and keeps the position."""
    it = mx.io.ImageRecordIter(**_kw(rec, **MODES[mode]))
    ref = Serial(**_kw(rec, **MODES[mode]))
    if ahead == "held back":
        _hold_back(it)
    _same(it.next(), ref.next())
    if ahead == "run ahead":
        _let_run_ahead(it)
    it.set_epoch(5)
    ref.set_epoch(5)
    for _ in range(2):
        _same(it.next(), ref.next())
    with pytest.raises(StopIteration):
        it.next()
    it.reset()
    ref.reset()
    assert it.epoch_coord == 6
    it.set_epoch(6)             # fit pins what reset() has just set
    ref.set_epoch(6)
    for _ in range(3):
        _same(it.next(), ref.next())
    it.close()


@pytest.mark.parametrize("mode", ["host", "defer"])
@limited(240)
def test_a_drawn_sequence_of_calls_under_a_short_switch_interval(rec, mode):
    """Eighty calls drawn from a seed (`next`, `reset`, `set_epoch`,
    now and then a pause that lets the producer fill its slots), with
    the interpreter switching threads every 10 us: the consumer, the
    producer and two pool threads interleave wherever they can, and
    every batch is still the serial iterator's."""
    it = mx.io.ImageRecordIter(**_kw(rec, **MODES[mode]))
    ref = Serial(**_kw(rec, **MODES[mode]))
    draw = random.Random(17)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(80):
            call = draw.choice(["next"] * 6 + ["reset", "set_epoch", "pause"])
            if call == "next":
                try:
                    want = ref.next()
                except StopIteration:
                    with pytest.raises(StopIteration):
                        it.next()
                    it.reset()
                    ref.reset()
                    continue
                _same(it.next(), want)
            elif call == "reset":
                it.reset()
                ref.reset()
            elif call == "set_epoch":
                epoch = draw.randrange(8)
                it.set_epoch(epoch)
                ref.set_epoch(epoch)
            else:
                time.sleep(draw.random() * 0.03)
            assert it.epoch_coord == ref.epoch and it.cur == ref.cur
    finally:
        sys.setswitchinterval(was)
        it.close()


@limited(120)
def test_reset_at_an_epochs_end_adopts_what_is_ready(rec):
    """The producer goes on into the next epoch; `reset()` there, and a
    `set_epoch` of the coordinate it has set, take over what waits and
    throw nothing away."""
    it = mx.io.ImageRecordIter(**_kw(rec))
    ref = Serial(**_kw(rec))
    for _ in range(3):
        _same(it.next(), ref.next())
    _let_run_ahead(it)
    waiting = list(it._ahead.slots)
    assert waiting[0].before == (1, 0, 0)
    gen = it._ahead.gen
    it.reset()
    it.set_epoch(1)
    ref.reset()
    assert it._ahead.gen == gen
    assert list(it._ahead.slots)[:len(waiting)] == waiting
    first = it.next()
    assert first is waiting[0].batch
    _same(first, ref.next())
    it.close()


@limited(120)
def test_an_error_in_a_decode_reaches_next(rec):
    """A decode that raises: `next()` raises it for that batch, in its
    turn, and the batch after it comes as it would have."""
    it = mx.io.ImageRecordIter(**_kw(rec, shuffle=False, rand_mirror=False))
    decode = it._decode_one

    def faulty(idx):
        if idx == BATCH + 1:
            raise ValueError("record %d is broken" % idx)
        return decode(idx)
    it._decode_one = faulty
    first = it.next()
    np.testing.assert_array_equal(first.label[0].asnumpy(), np.arange(BATCH))
    with pytest.raises(ValueError, match="record 9 is broken"):
        it.next()
    third = it.next()
    assert third.pad == 3 * BATCH - RECORDS
    np.testing.assert_array_equal(third.label[0].asnumpy()[:4],
                                  np.arange(2 * BATCH, RECORDS))
    it.close()


def _producers():
    return [t for t in threading.enumerate() if t.name == PRODUCER]


@limited(120)
def test_the_thread_starts_at_the_first_next_and_goes_with_the_iterator(rec):
    """An iterator that is built, reset and dropped starts no thread;
    `close()` joins the one `next()` started and the iterator is closed
    for good; a dropped iterator leaves none behind."""
    before = len(_producers())
    it = mx.io.ImageRecordIter(**_kw(rec))
    it.reset()
    it.set_epoch(0)
    assert it._ahead.thread is None and len(_producers()) == before
    it.next()
    thread = it._ahead.thread
    assert thread.daemon and thread.is_alive()
    it.close()
    assert not thread.is_alive()
    it.close()                              # idempotent
    with pytest.raises(mx.base.MXNetError, match="closed"):
        it.next()
    dropped = mx.io.ImageRecordIter(**_kw(rec))
    dropped.next()
    _let_run_ahead(dropped)
    thread = dropped._ahead.thread
    del dropped
    gc.collect()
    thread.join(20)
    assert not thread.is_alive()
    assert len(_producers()) == before


@limited(120)
def test_batches_a_caller_holds_are_not_rewritten(rec):
    """Every batch lies in memory of its own: two batches held while
    the third and an epoch more are made still read as they did."""
    it = mx.io.ImageRecordIter(**_kw(rec))
    a, b = it.next(), it.next()
    was = [a.data[0].asnumpy().copy(), b.data[0].asnumpy().copy()]
    it.next()
    it.reset()
    list(it)
    _let_run_ahead(it)
    assert a.data[0].asnumpy().tobytes() == was[0].tobytes()
    assert b.data[0].asnumpy().tobytes() == was[1].tobytes()
    assert was[0].tobytes() != was[1].tobytes()
    it.close()


@limited(300)
def test_every_next_of_a_fit_found_its_batch_or_waited(rec):
    """`input.ready + input.waited` is the number of batches `fit`
    took; the stages' spans and the bytes are credited once a batch
    taken, and what was made ahead and never taken is not counted."""
    it = mx.io.ImageRecordIter(**_kw(rec))
    net = sym.Flatten(sym.Variable("data"))
    net = sym.FullyConnected(net, num_hidden=RECORDS, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(0)])
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.01})
    rep = tel.last_fit()
    steps = 6
    assert rep["steps"] == steps
    counters = rep["counters"]
    assert counters.get("input.ready", 0) + counters.get("input.waited", 0) \
        == steps
    assert counters["input.h2d_bytes"] == steps * 4 * (
        BATCH * 3 * 32 * 32 + BATCH)
    for name in ("input.decode", "input.assemble", "input.put"):
        assert rep["spans"][name]["count"] == steps
    it.close()
