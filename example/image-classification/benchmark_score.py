"""Inference throughput benchmark (reference example/image-classification/
benchmark_score.py; numbers table docs/how_to/perf.md:116-148)."""
import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import models


def score(network, dev, batch_size, num_batches, batch_group=1,
          compute_dtype=None):
    if network == "inception-v3":
        data_shape = (batch_size, 3, 299, 299)
    else:
        data_shape = (batch_size, 3, 224, 224)
    sym = models.get_symbol(network, num_classes=1000)

    # bf16 activations on TPU (MXU-native + half the HBM bytes), like
    # the training bench — an f32 eval program moves 15.9 GB/batch vs
    # 7.7 GB and scores ~2.4x slower (measured round 5). NB: gate on
    # the JAX platform — Context.device_type says 'gpu' for mx.tpu()
    # (reference device-code compat)
    if compute_dtype is None and dev.jax_device().platform == "tpu":
        compute_dtype = "bfloat16"
    mod = mx.mod.Module(sym, context=dev,
                        label_names=["softmax_label"],
                        compute_dtype=compute_dtype)
    mod.bind(for_training=False, inputs_need_grad=False,
             data_shapes=[("data", data_shape)], label_shapes=None)
    mod.init_params(initializer=mx.init.Xavier(magnitude=2.0))
    import jax
    from mxnet_tpu.io import DataBatch
    X = np.random.rand(*data_shape).astype(np.float32)
    eg = mod._exec_group
    if getattr(eg, "fused", False):
        # device-resident batch: scoring measures the model, not staging
        batch = DataBatch([mx.nd.NDArray(
            jax.device_put(X, eg._batch_sharding))], [])
    else:
        batch = DataBatch([mx.nd.array(X)], [])

    grouped = batch_group > 1 and getattr(eg, "fused", False)
    if grouped:
        # persistent multi-batch scoring: one launch scans batch_group
        # batches (mesh_executor_group "fwd_eval_stacked") — amortizes
        # the per-launch overhead that dominates small-batch scoring
        from jax.sharding import NamedSharding, PartitionSpec as P
        st = NamedSharding(eg.mesh, P(*((None,) + eg._batch_sharding.spec)))
        Xg = jax.device_put(
            np.broadcast_to(X, (batch_group,) + X.shape).copy(), st)
        assert num_batches % batch_group == 0, \
            "num_batches must be a multiple of batch_group"

        def dispatch():
            return eg.score_stacked({"data": Xg})[0]
    else:
        def dispatch():
            # the fused group defers forward until outputs are read;
            # _read() materializes (async dispatch) WITHOUT waiting for
            # completion — a second forward() before this would
            # supersede the batch
            mod.forward(batch, is_train=False)
            return mod.get_outputs()[0]._read()

    # warm up (compile)
    for _ in range(2):
        out = dispatch()
    jax.block_until_ready(out)
    launches = num_batches // batch_group if grouped else num_batches

    tic = time.time()
    for _ in range(launches):
        out = dispatch()
    # single-queue device: the last forward completes after all others;
    # block_until_ready is a completion barrier
    jax.block_until_ready(out)
    dt = time.time() - tic
    eff_batch = batch_size * (batch_group if grouped else 1)
    return launches * eff_batch / dt, (batch_group if grouped else 1)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--networks", default="resnet-50")
    parser.add_argument("--tpus", "--gpus", dest="tpus", default=None)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-batches", type=int, default=10)
    parser.add_argument("--batch-group", type=int, default=1,
                        help="batches scored per XLA launch (fused path)")
    parser.add_argument("--dtype", default=None,
                        help="compute dtype (default: bfloat16 on TPU)")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    dev = mx.tpu(0) if args.tpus is not None else mx.cpu()
    for net in args.networks.split(","):
        speed, eff_group = score(net, dev, args.batch_size,
                                 args.num_batches, args.batch_group,
                                 compute_dtype=args.dtype)
        logging.info("network: %s, batch %d, group %d: %.1f images/sec",
                     net, args.batch_size, eff_group, speed)
