"""Allreduce bandwidth benchmark (reference tools/bandwidth/measure.py —
numbers in tools/bandwidth/README.md:30-57: 11.1 GB/s/gpu on a 2-GPU P2P
box).

Measures the KVStore push+pull path and the raw XLA psum over the device
mesh — the TPU-native replacement where gradients ride ICI instead of
staged pinned-memory copies.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def measure_kvstore(kv_type, size_mb, repeat, num_arrays):
    import mxnet_tpu as mx
    from mxnet_tpu import ndarray as nd
    kv = mx.kvstore.create(kv_type)
    n = int(size_mb * 1024 * 1024 / 4 / num_arrays)
    arrays = [nd.ones((n,)) for _ in range(num_arrays)]
    for i, a in enumerate(arrays):
        kv.init(i, a)
    outs = [nd.empty((n,)) for _ in range(num_arrays)]
    # warmup
    for i, a in enumerate(arrays):
        kv.push(i, a)
        kv.pull(i, out=outs[i])
    nd.waitall()
    tic = time.time()
    for _ in range(repeat):
        for i, a in enumerate(arrays):
            kv.push(i, a)
            kv.pull(i, out=outs[i])
        nd.waitall()
    dt = time.time() - tic
    total_gb = size_mb / 1024 * repeat * 2  # push + pull
    return total_gb / dt


def measure_psum(size_mb, repeat):
    """Raw XLA all-reduce over all visible devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from functools import partial

    devs = jax.devices()
    if len(devs) < 2:
        return None
    mesh = Mesh(np.array(devs), ("d",))
    n = int(size_mb * 1024 * 1024 / 4)
    x = jax.device_put(
        jnp.ones((len(devs), n // len(devs))),
        jax.sharding.NamedSharding(mesh, P("d")))

    @jax.jit
    def allreduce(x):
        from jax import shard_map

        def f(s):
            return jax.lax.psum(s, "d")

        return shard_map(f, mesh=mesh, in_specs=P("d"),
                         out_specs=P("d"))(x)

    allreduce(x).block_until_ready()
    tic = time.time()
    for _ in range(repeat):
        out = allreduce(x)
    out.block_until_ready()
    dt = time.time() - tic
    return size_mb / 1024 * repeat / dt


def main():
    parser = argparse.ArgumentParser(description="measure allreduce bandwidth")
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--size-mb", type=float, default=256,
                        help="total payload (resnet-200 weights = 258 MB)")
    parser.add_argument("--num-arrays", type=int, default=100)
    parser.add_argument("--repeat", type=int, default=5)
    args = parser.parse_args()
    bw = measure_kvstore(args.kv_store, args.size_mb, args.repeat,
                         args.num_arrays)
    print("kvstore %s: %.2f GB/s" % (args.kv_store, bw))
    psum_bw = measure_psum(args.size_mb, args.repeat)
    if psum_bw:
        print("xla psum over mesh: %.2f GB/s" % psum_bw)


if __name__ == "__main__":
    main()
