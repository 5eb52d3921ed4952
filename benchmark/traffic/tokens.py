"""Feed kind `tokens`: batches of token ids made on the device from the
seed and handed out in turn, so that input costs nothing.

A batch's rows are tokens: `per_chip_batch * chips` of them, cut into
sequences of the configuration's `seq_len`.  Each sequence is drawn
`seq_len + 1` long; `data` is its first `seq_len` ids and
`softmax_label` the ids one place on, the next token.  Ids are Zipf
with the mix's `zipf_exponent` over the configuration's `vocab_size`
(the rows of the vocabulary held), rank to id by a permutation made
from the seed: a few ids are frequent and most are rare, as in text, so
the routing of an expert layer is uneven.  Both arrays are float32
whole numbers, the way mxnet carries ids.

A mix of this kind takes `distinct_batches`, `steps_per_epoch`,
`warm_steps` and `zipf_exponent`.
"""
import functools

import numpy as np

from benchmark.traffic import Feed


@functools.lru_cache(maxsize=None)
def _batch_maker(rows, seq_len, vocab, exponent, sharding):
    import jax
    import jax.numpy as jnp

    def build(key, index):
        ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
        cdf = jnp.cumsum(ranks ** -exponent)
        cdf = cdf / cdf[-1]
        to_id = jax.random.permutation(jax.random.fold_in(key, 0), vocab)
        u = jax.random.uniform(jax.random.fold_in(key, 1 + index),
                               (rows // seq_len, seq_len + 1), jnp.float32)
        rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        ids = to_id[rank].astype(jnp.float32)
        return ids[:, :-1].reshape(rows), ids[:, 1:].reshape(rows)

    out = None if sharding is None else (sharding, sharding)
    return jax.jit(build, out_shardings=out)


def token_batch(seed, index, rows, cfg, mix, sharding=None):
    """Batch `index` of a seed: (ids, next ids), each (rows,)."""
    from benchmark.weights import key_of
    return _batch_maker(rows, cfg["seq_len"], cfg["vocab_size"],
                        float(mix["zipf_exponent"]), sharding)(
        key_of(seed, 1), index)


class _Turn:
    def __init__(self, batches):
        self._batches, self._i = batches, 0

    def __next__(self):
        b = self._batches[self._i % len(self._batches)]
        self._i += 1
        return b

    def reset(self):
        pass            # the turn goes on: every step another batch


def make_feed(mix, cfg, seed, chips, workdir):
    import jax
    import mxnet_tpu as mx
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from mxnet_tpu.io import DataBatch, DataDesc

    rows = cfg["per_chip_batch"] * chips
    mesh = Mesh(np.array(jax.devices()[:chips]), ("dp",))
    sharding = NamedSharding(mesh, PartitionSpec("dp"))
    batches = []
    for i in range(mix["distinct_batches"]):
        x, y = token_batch(seed, i, rows, cfg, mix, sharding)
        batches.append(DataBatch(
            data=[mx.nd.NDArray(x, ctx=mx.tpu(0))],
            label=[mx.nd.NDArray(y, ctx=mx.tpu(0))]))
    return Feed(_Turn(batches), [DataDesc("data", (rows,))],
                [DataDesc("softmax_label", (rows,))],
                mix["steps_per_epoch"], keep_rows=False)


def reference_batches(mix, cfg, seed, chips, kept, sharding=None):
    """Made anew from the seed: the benchmark's own rows, so there is
    no delivery to hold them against."""
    rows = cfg["per_chip_batch"] * chips
    made = [token_batch(seed, i, rows, cfg, mix, sharding)
            for i in range(len(kept))]
    return made, None


def own_batches(mix, cfg, seed, chips, steps, sharding=None):
    rows = cfg["per_chip_batch"] * chips
    return [token_batch(seed, i, rows, cfg, mix, sharding)
            for i in range(steps)]
