"""Feed kind `recordio`: a `.rec` of labelled JPEGs written from the
seed and read by the program's own `ImageRecordIter`.

A mix of this kind takes `records`, `distinct_images`, `jpeg_quality`,
`warm_steps` and `iterator`, the arguments of `mx.io.ImageRecordIter`
beside the path, the shape, the batch and the seed.
"""
import io
import os

import numpy as np

from benchmark.traffic import Feed


def source_images(seed, n, image):
    """`n` distinct uint8 HWC images: smooth colour fields with mild
    noise, so that a JPEG of one costs what a photograph's does."""
    _c, h, w = image
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    out = []
    for _ in range(n):
        img = np.empty((h, w, 3), np.float32)
        for ch in range(3):
            fy, fx = rng.uniform(0.5, 4.0, 2) * 2 * np.pi
            py, px = rng.uniform(0, 2 * np.pi, 2)
            img[..., ch] = rng.uniform(60, 190) + rng.uniform(20, 60) * (
                np.sin(fy * yy / h + py) * np.cos(fx * xx / w + px))
        img += rng.normal(0, 6.0, img.shape)
        out.append(np.clip(img, 0, 255).astype(np.uint8))
    return out


def jpeg_payloads(images, quality):
    from PIL import Image
    out = []
    for img in images:
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=quality)
        out.append(buf.getvalue())
    return out


def write_rec(path, payloads, records):
    """Record i holds image i mod n, labelled with that number."""
    from mxnet_tpu import recordio
    rec = recordio.MXRecordIO(path, "w")
    try:
        for i in range(records):
            k = i % len(payloads)
            rec.write(recordio.pack(recordio.IRHeader(0, float(k), i, 0),
                                    payloads[k]))
    finally:
        rec.close()


def make_feed(mix, cfg, seed, chips, workdir):
    import mxnet_tpu as mx
    image = tuple(cfg["image"])
    path = os.path.join(workdir, "train.rec")
    write_rec(path, jpeg_payloads(
        source_images(seed, mix["distinct_images"], image),
        mix["jpeg_quality"]), mix["records"])
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=image,
        batch_size=cfg["per_chip_batch"] * chips,
        seed=int(seed) % (2 ** 31), **mix["iterator"])
    return Feed(it, it.provide_data, it.provide_label, None, keep_rows=True)


def reference_batches(mix, cfg, seed, chips, kept, sharding=None):
    """Each delivered row's label names its source image; the reference
    decodes that image itself (PIL), subtracts the mean, and takes it
    mirrored where the delivery is nearer the mirror image.  Order and
    mirroring are the program's free draws; the pixels are not."""
    from PIL import Image
    it = mix["iterator"]
    mean = np.array([it.get("mean_r", 0.0), it.get("mean_g", 0.0),
                     it.get("mean_b", 0.0)], np.float32)
    decoded = [np.asarray(Image.open(io.BytesIO(p)).convert("RGB"))
               .astype(np.float32) - mean for p in jpeg_payloads(
                   source_images(seed, mix["distinct_images"],
                                 tuple(cfg["image"])),
                   mix["jpeg_quality"])]
    plain = [d.transpose(2, 0, 1) for d in decoded]
    out, gap = [], 0.0
    for kx, ky in kept:
        rows = np.empty_like(kx)
        for r, label in enumerate(ky):
            src = plain[int(label)]
            flip = src[:, :, ::-1]
            d_src = float(np.abs(kx[r] - src).max())
            d_flip = float(np.abs(kx[r] - flip).max())
            rows[r] = src if d_src <= d_flip else flip
            gap = max(gap, min(d_src, d_flip))
        out.append((rows, ky.astype(np.float32)))
    return out, gap


def own_batches(mix, cfg, seed, chips, steps, sharding=None):
    """The reference's own decode of the source images, in an order and
    a mirroring of its own drawn from the seed."""
    batch = cfg["per_chip_batch"] * chips
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    kept = []
    for _ in range(steps):
        labels = rng.randint(0, mix["distinct_images"], batch)
        marker = np.full((batch,) + tuple(cfg["image"]), np.inf, np.float32)
        kept.append((marker, labels.astype(np.float32)))
    rows, _gap = reference_batches(mix, cfg, seed, chips, kept)
    out = []
    for x, y in rows:
        flip = rng.rand(batch) < 0.5
        x[flip] = x[flip][:, :, :, ::-1]
        out.append((x, y))
    return out
