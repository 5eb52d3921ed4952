"""Train an ImageNet-class network from RecordIO packs (reference
example/image-classification/train_imagenet.py, ``--gpus`` swapped for
``--tpus``).

Points at real ``.rec`` packs via ``--data-train``/``--data-val``
(tools/im2rec.py builds them); without packs it synthesizes a tiny
labeled-JPEG rec so the entry point runs end to end with no egress.
``--network`` takes any zoo name including the ``-bf16``
reduced-precision variants; ``--dtype bfloat16`` independently selects
the Module-level mixed-precision path (compute in bf16, params f32) —
the TPU-native equivalent of the reference's fp16 flag.
"""
import argparse
import logging
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models, recordio


def synth_rec(path, n, img, classes, rng):
    """Labeled JPEG rec: each class is a distinct color blob + noise."""
    from PIL import Image
    import io as pyio

    rec = recordio.MXRecordIO(path, "w")
    for i in range(n):
        cls = i % classes
        base = np.zeros((img, img, 3), np.uint8)
        base[..., cls % 3] = 60 + 37 * (cls // 3)
        noise = rng.randint(0, 60, (img, img, 3)).astype(np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(base + noise).save(buf, format="JPEG")
        rec.write(recordio.pack(
            recordio.IRHeader(0, float(cls), i, 0), buf.getvalue()))
    rec.close()


def main():
    parser = argparse.ArgumentParser(description="train imagenet")
    parser.add_argument("--network", default="resnet-50")
    parser.add_argument("--data-train", default=None)
    parser.add_argument("--data-val", default=None)
    parser.add_argument("--image-shape", default="3,224,224")
    parser.add_argument("--tpus", "--gpus", dest="tpus", default=None)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--num-epochs", type=int, default=1)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--mom", type=float, default=0.9)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--dtype", default=None,
                        choices=[None, "bfloat16", "float32"])
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--model-prefix", default=None)
    parser.add_argument("--synthetic-images", type=int, default=256,
                        help="rec size when --data-train is absent")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.serving.enable_persistent_compile_cache()   # before the first compile

    shape = tuple(int(x) for x in args.image_shape.split(","))
    tmp = None
    if args.data_train is None:
        tmp = tempfile.mkdtemp(prefix="imagenet_synth_")
        args.data_train = os.path.join(tmp, "train.rec")
        rng = np.random.RandomState(0)
        n_cls = min(args.num_classes, 8)
        args.num_classes = n_cls
        synth_rec(args.data_train, args.synthetic_images, shape[1],
                  n_cls, rng)
        logging.info("no --data-train: synthesized %d-image rec at %s",
                     args.synthetic_images, args.data_train)

    it = mx.io.ImageRecordIter(
        path_imgrec=args.data_train, data_shape=shape,
        batch_size=args.batch_size, shuffle=True, rand_mirror=True,
        mean_r=123.68, mean_g=116.28, mean_b=103.53,
        preprocess_threads=4, label_name="softmax_label")
    val = None
    if args.data_val:
        val = mx.io.ImageRecordIter(
            path_imgrec=args.data_val, data_shape=shape,
            batch_size=args.batch_size,
            mean_r=123.68, mean_g=116.28, mean_b=103.53,
            label_name="softmax_label")

    if args.tpus:
        ctxs = [mx.Context("tpu", int(i)) for i in args.tpus.split(",")]
    else:
        n = mx.context.num_devices() or 1
        ctxs = [mx.Context("tpu", i) for i in range(n)]

    net = models.get_symbol(args.network, num_classes=args.num_classes,
                            image_shape=args.image_shape)
    mod = mx.mod.Module(net, context=ctxs,
                        compute_dtype=args.dtype)
    metric = mx.metric.Accuracy()
    cbs = [mx.callback.Speedometer(args.batch_size, 10)]
    epoch_cb = (mx.callback.do_checkpoint(args.model_prefix)
                if args.model_prefix else None)
    mod.fit(it, eval_data=val, num_epoch=args.num_epochs,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr,
                              "momentum": args.mom, "wd": args.wd,
                              "rescale_grad": 1.0 / args.batch_size},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
            eval_metric=metric, kvstore=args.kv_store,
            batch_end_callback=cbs, epoch_end_callback=epoch_cb)
    logging.info("final train accuracy: %.3f", metric.get()[1])
    print("TRAIN_IMAGENET_DONE")


if __name__ == "__main__":
    main()
