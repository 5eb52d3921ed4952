"""Operations and least bytes of one training step, from shapes alone.

Both are lower bounds of what any schedule of the step must do, so a
share of a peak worked out from them cannot pass 100 %:

* operations: 2 per multiply-add of every convolution (products
  against the zero padding left out) and of the classifier, times 3:
  the forward product and the two backward products, with respect to
  the input and to the weight.  Nothing that a schedule recomputes is
  counted, and BatchNorm, ReLU, pooling and the update are left out.
* bytes: each parameter read and written once, its momentum read and
  written once, its gradient written once (float32); the input batch
  read once (float32); the output of every convolution and of the
  classifier written once in the forward pass and read once in the
  backward pass, and its gradient written once and read once (in the
  compute type).  What a schedule could fuse away (normalised and
  rectified copies, sums of residual branches) is left out.

XLA's `cost_analysis` is not used: its byte estimate counts re-reads
and read 1.20 of the chip's peak on this very step (PERF.md).
"""
import math

BYTES = {"float32": 4, "bfloat16": 2}


def layers(arch):
    """Every product of the net: (name, output elements per image,
    multiply-adds per image, weight elements)."""
    c, h, w = arch["image"]
    out = []

    def taps(size, k, stride, pad):
        """(outputs, products) along one axis; a product against the
        zero padding is not one the algorithm needs."""
        n_out = (size + 2 * pad - k) // stride + 1
        return n_out, sum(1 for o in range(n_out) for t in range(k)
                          if 0 <= o * stride - pad + t < size)

    def conv(name, cin, cout, k, stride, pad, hw):
        ho, th = taps(hw[0], k, stride, pad)
        wo, tw = taps(hw[1], k, stride, pad)
        out.append((name, cout * ho * wo, cout * cin * th * tw,
                    cout * cin * k * k))
        return ho, wo

    f0 = arch["filters"][0]
    hw = conv("conv0", c, f0, 7, 2, 3, (h, w))
    hw = ((hw[0] + 2 - 3) // 2 + 1, (hw[1] + 2 - 3) // 2 + 1)   # max pool
    prev = f0
    for i, n in enumerate(arch["units"]):
        width = arch["filters"][i + 1]
        for j in range(n):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            stride = (1 if i == 0 else 2) if j == 0 else 1
            conv(name + "_conv1", prev, width // 4, 1, 1, 0, hw)
            hw_out = conv(name + "_conv2", width // 4, width // 4, 3,
                          stride, 1, hw)
            conv(name + "_conv3", width // 4, width, 1, 1, 0, hw_out)
            if j == 0:
                conv(name + "_sc", prev, width, 1, stride, 0, hw)
            hw, prev = hw_out, width
    out.append(("fc1", arch["classes"], arch["classes"] * prev,
                arch["classes"] * prev))
    return out


def n_parameters(arch):
    """Every trained element: products' weights, BatchNorm's gamma and
    beta, the classifier's bias."""
    n = sum(l[3] for l in layers(arch)) + arch["classes"]
    bn_channels = arch["image"][0] + arch["filters"][0] + arch["filters"][-1]
    prev = arch["filters"][0]
    for i, units in enumerate(arch["units"]):
        width = arch["filters"][i + 1]
        for j in range(units):
            bn_channels += prev + 2 * (width // 4)
            prev = width
    return n + 2 * bn_channels


def forward_macs_per_image(arch):
    return sum(l[2] for l in layers(arch))


def train_flops_per_image(arch):
    """Forward + backward operations a training step requires."""
    return 3 * 2 * forward_macs_per_image(arch)


def train_least_bytes(arch, batch, compute_dtype):
    """Least HBM bytes of one step on one chip at `batch` rows there."""
    act = BYTES[compute_dtype]
    params = n_parameters(arch)
    state = params * 4 * 5          # w r+w, momentum r+w, gradient w
    inputs = batch * math.prod(arch["image"]) * 4
    saved = batch * sum(l[1] for l in layers(arch)) * act * 4
    return state + inputs + saved


def step_bounds(arch, batch, compute_dtype, peaks):
    """Least seconds one chip needs for a step of `batch` rows: by
    operations, by bytes, and which of the two binds."""
    t_ops = batch * train_flops_per_image(arch) / peaks["bf16_flops_per_s"]
    t_bytes = train_least_bytes(arch, batch, compute_dtype) \
        / peaks["hbm_bytes_per_s"]
    return {"ops_s": t_ops, "bytes_s": t_bytes,
            "bound": "operations" if t_ops >= t_bytes else "bytes"}
