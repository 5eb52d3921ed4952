"""Feed kind `resident`: batches made on the device from the seed and
handed out in turn, so that input costs nothing.

A mix of this kind takes `distinct_batches`, `steps_per_epoch` and
`warm_steps`.
"""
import functools

import numpy as np

from benchmark.traffic import Feed


@functools.lru_cache(maxsize=None)
def _batch_maker(batch, image, classes, sharding):
    import jax
    import jax.numpy as jnp

    def build(key, index):
        kx, ky = jax.random.split(jax.random.fold_in(key, index))
        x = jax.random.normal(kx, (batch,) + image, jnp.float32)
        y = jax.random.randint(ky, (batch,), 0, classes).astype(jnp.float32)
        return x, y

    out = None if sharding is None else (sharding, sharding)
    return jax.jit(build, out_shardings=out)


def resident_batch(seed, index, batch, image, classes, sharding=None):
    """Batch `index` of a seed: rows that all differ, float32 images
    and whole-number labels (as float32, the way mxnet carries them)."""
    from benchmark.weights import key_of
    return _batch_maker(batch, tuple(image), classes, sharding)(
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

    image = tuple(cfg["image"])
    batch = cfg["per_chip_batch"] * chips
    mesh = Mesh(np.array(jax.devices()[:chips]), ("dp",))
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    batches = []
    for i in range(mix["distinct_batches"]):
        x, y = resident_batch(seed, i, batch, image, cfg["classes"], rows)
        batches.append(DataBatch(
            data=[mx.nd.NDArray(x, ctx=mx.tpu(0))],
            label=[mx.nd.NDArray(y, ctx=mx.tpu(0))]))
    return Feed(_Turn(batches),
                [DataDesc("data", (batch,) + image)],
                [DataDesc("softmax_label", (batch,))],
                mix["steps_per_epoch"], keep_rows=False)


def reference_batches(mix, cfg, seed, chips, kept, sharding=None):
    """Made anew from the seed: the benchmark's own rows, so there is
    no delivery to hold them against."""
    batch = cfg["per_chip_batch"] * chips
    made = [resident_batch(seed, i, batch, tuple(cfg["image"]),
                           cfg["classes"], sharding)
            for i in range(len(kept))]
    return made, None


def own_batches(mix, cfg, seed, chips, steps, sharding=None):
    return reference_batches(mix, cfg, seed, chips, [None] * steps,
                             sharding)[0]
