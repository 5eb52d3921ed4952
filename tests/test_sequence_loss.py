"""CTCLoss / Correlation / rtc-Pallas tests."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd


def _ctc_ref(logits, labels, blank=0):
    """Brute-force CTC loss by enumerating alignments (tiny T only)."""
    import itertools
    T, C = logits.shape
    mx_ = logits.max(-1, keepdims=True)
    lp = logits - np.log(
        np.exp(logits - mx_).sum(-1, keepdims=True)) - mx_
    target = [l for l in labels if l > 0]

    def collapse(path):
        out = []
        prev = None
        for p in path:
            if p != prev and p != blank:
                out.append(p)
            prev = p
        return out

    total = -np.inf
    for path in itertools.product(range(C), repeat=T):
        if collapse(path) == target:
            s = sum(lp[t, path[t]] for t in range(T))
            total = np.logaddexp(total, s)
    return -total


def test_ctc_loss_vs_bruteforce():
    rng = np.random.RandomState(0)
    T, N, C, L = 4, 2, 3, 2
    data = rng.randn(T, N, C).astype(np.float32)
    labels = np.array([[1, 2], [2, 0]], dtype=np.float32)
    loss = nd.CTCLoss(nd.array(data), nd.array(labels)).asnumpy()
    for n in range(N):
        ref = _ctc_ref(data[:, n], labels[n].astype(int))
        assert abs(loss[n] - ref) < 1e-3, (n, loss[n], ref)


def test_ctc_loss_gradient_flows():
    from mxnet_tpu import symbol as sym
    data = sym.Variable("data")
    label = sym.Variable("label")
    loss = sym.MakeLoss(sym.CTCLoss(data, label, name="ctc"))
    e = loss.simple_bind(mx.cpu(), data=(5, 2, 4), label=(2, 2))
    e.arg_dict["data"][:] = np.random.randn(5, 2, 4)
    e.arg_dict["label"][:] = np.array([[1, 2], [3, 0]])
    e.forward(is_train=True)
    e.backward()
    g = e.grad_dict["data"].asnumpy()
    assert np.abs(g).sum() > 0 and not np.isnan(g).any()


def test_correlation():
    rng = np.random.RandomState(0)
    d1 = rng.randn(1, 4, 6, 6).astype(np.float32)
    d2 = rng.randn(1, 4, 6, 6).astype(np.float32)
    out = nd.Correlation(nd.array(d1), nd.array(d2), max_displacement=1)
    assert out.shape == (1, 9, 6, 6)
    # center displacement (dy=dx=0) == mean over channels of product
    center = out.asnumpy()[0, 4]
    np.testing.assert_allclose(center, (d1[0] * d2[0]).mean(axis=0),
                               rtol=1e-5)


def test_rtc_pallas_kernel():
    x = nd.array(np.random.rand(8, 128).astype(np.float32))
    y = nd.array(np.random.rand(8, 128).astype(np.float32))
    z = nd.zeros((8, 128))
    rtc = mx.rtc.Rtc("axpy", [("x", x), ("y", y)], [("z", z)],
                     "z_ref[...] = x_ref[...] * 2.0 + y_ref[...]")
    rtc.push([x, y], [z], interpret=True)   # CPU test: Pallas interpreter
    np.testing.assert_allclose(z.asnumpy(), x.asnumpy() * 2 + y.asnumpy(),
                               rtol=1e-6)


def test_pallas_kernel_class():
    from mxnet_tpu.rtc import PallasKernel

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] ** 2

    pk = PallasKernel(kern)
    x = nd.array(np.random.rand(4, 128).astype(np.float32))
    (out,) = pk([x], [(4, 128)], interpret=True)
    np.testing.assert_allclose(out.asnumpy(), x.asnumpy() ** 2, rtol=1e-6)
