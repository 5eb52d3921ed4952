"""Framework-free JAX ResNet-50 train step — the independent perf witness.

VERDICT r2 #4: the claim "the Module-path step runs at ~100% of its HBM
roofline" was adjudicated only by XLA's own cost model. This module is
the independent cross-check: a minimal hand-rolled NHWC ResNet-50
(bottleneck [3,4,6,3], v1 heads — same conv shapes as
``models.get_symbol("resnet-50")``), bf16 compute / f32 params, softmax
cross-entropy, SGD+momentum, the WHOLE step one donated jitted program.
No Symbol, no Module, no optimizer registry — if this beats the
framework number, the framework is leaving throughput on the table; if
it matches, the roofline claim becomes a measurement.

``bench.py`` runs :func:`measure` in the same harness with the same
data-dependent barrier and reports ``handwritten_img_per_sec`` next to
the Module-path headline (PERF.md records both).
"""
from __future__ import annotations

import time

import numpy as np

STAGES = (3, 4, 6, 3)
FILTERS = (256, 512, 1024, 2048)


def _conv(x, w, stride=1, compute_dtype=None):
    import jax.lax as lax
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        w = w.astype(compute_dtype)
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding="SAME" if w.shape[0] > 1 else "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


_BNR_CORE = None


def _bnr_core():
    """Hand-VJP fused BatchNorm(+ReLU) core, NHWC — the same
    minimal-HBM-traffic schedule the framework's ops/nn.py
    _bn_train_core uses (independent implementation, same math):
    centered one-pass f32 statistics in the forward; a backward that
    reads (dout, x) twice total, recomputing x_hat and the ReLU mask
    in-register.  This keeps the witness honest: it must carry the same
    algorithm the Module path runs, or the 'framework overhead ~ 0'
    cross-check compares different programs."""
    global _BNR_CORE
    if _BNR_CORE is not None:
        return _BNR_CORE
    import jax
    import jax.numpy as jnp
    from functools import partial

    def _fwd(x, gamma, beta, c, eps, relu):
        f32 = jnp.float32
        xf = x.astype(f32)
        n = x.shape[0] * x.shape[1] * x.shape[2]
        xc = xf - c
        m1 = jnp.sum(xc, axis=(0, 1, 2)) / n
        m2 = jnp.sum(xc * xc, axis=(0, 1, 2)) / n
        mean = c + m1
        var = jnp.maximum(m2 - m1 * m1, 0.0)
        rstd = jax.lax.rsqrt(var + eps)
        scale = gamma * rstd
        shift = beta - mean * scale
        y = xf * scale + shift
        if relu:
            y = jnp.maximum(y, 0.0)
        return ((y.astype(x.dtype), mean, var),
                (x, gamma, beta, mean, rstd, c))

    def _bwd(eps, relu, res, cots):
        dout = cots[0]
        x, gamma, beta, mean, rstd, c = res
        n = x.shape[0] * x.shape[1] * x.shape[2]
        xf = x.astype(jnp.float32)
        xhat = (xf - mean) * rstd
        du = dout.astype(jnp.float32)
        if relu:
            scale = gamma * rstd
            shift = beta - mean * scale
            du = jnp.where(xf * scale + shift > 0, du, 0.0)
        dbeta = jnp.sum(du, axis=(0, 1, 2))
        dgamma = jnp.sum(du * xhat, axis=(0, 1, 2))
        dx = (du - dbeta / n - xhat * (dgamma / n)) * (gamma * rstd)
        return (dx.astype(x.dtype), dgamma, dbeta, jnp.zeros_like(c))

    @partial(jax.custom_vjp, nondiff_argnums=(4, 5))
    def core(x, gamma, beta, c, eps, relu):
        return _fwd(x, gamma, beta, c, eps, relu)[0]

    core.defvjp(_fwd, _bwd)
    _BNR_CORE = core
    return core


def _bn(x, p, training, momentum=0.9, eps=2e-5, relu=False):
    """BatchNorm with f32 statistics (bf16 EMA increments underflow);
    train mode runs the hand-VJP fused core, optionally with ReLU."""
    import jax
    import jax.numpy as jnp
    gamma, beta, mean, var = p
    if training:
        c = jax.lax.stop_gradient(mean)
        y, m, v = _bnr_core()(x, gamma, beta, c, eps, relu)
        m = jax.lax.stop_gradient(m)
        v = jax.lax.stop_gradient(v)
        new_mean = momentum * mean + (1 - momentum) * m
        new_var = momentum * var + (1 - momentum) * v
        return y, (gamma, beta, new_mean, new_var)
    xf = x.astype(jnp.float32)
    y = (xf - mean) * (gamma / jnp.sqrt(var + eps)) + beta
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), (gamma, beta, mean, var)


def _bottleneck(x, blk, stride, training, cdt):
    import jax.numpy as jnp
    y, bn1 = _bn(_conv(x, blk["w1"], 1, cdt), blk["bn1"], training,
                 relu=True)
    y, bn2 = _bn(_conv(y, blk["w2"], stride, cdt), blk["bn2"], training,
                 relu=True)
    y, bn3 = _bn(_conv(y, blk["w3"], 1, cdt), blk["bn3"], training)
    if "wproj" in blk:
        sc, bnp = _bn(_conv(x, blk["wproj"], stride, cdt), blk["bnp"],
                      training)
        new = {"bn1": bn1, "bn2": bn2, "bn3": bn3, "bnp": bnp}
    else:
        sc, new = x, {"bn1": bn1, "bn2": bn2, "bn3": bn3}
    return jnp.maximum(y + sc, 0), new


def forward(params, x, training, cdt):
    """NHWC ResNet-50 -> logits; returns (logits_f32, updated bn stats)."""
    import jax.lax as lax
    import jax.numpy as jnp

    new_stats = {}
    y = lax.conv_general_dilated(
        x.astype(cdt or x.dtype), params["stem_w"].astype(cdt or x.dtype),
        window_strides=(2, 2), padding=[(3, 3), (3, 3)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y, new_stats["stem_bn"] = _bn(y, params["stem_bn"], training,
                                  relu=True)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for si, n_blocks in enumerate(STAGES):
        for bi in range(n_blocks):
            key = "s%db%d" % (si, bi)
            stride = 2 if (bi == 0 and si > 0) else 1
            y, new_stats[key] = _bottleneck(y, params[key], stride,
                                            training, cdt)
    y = jnp.mean(y.astype(jnp.float32), axis=(1, 2))
    logits = y @ params["fc_w"] + params["fc_b"]
    return logits, new_stats


def init_params(rng, cdt=None):
    def he(shape):
        fan_in = int(np.prod(shape[:-1]))
        return (rng.randn(*shape) * np.sqrt(2.0 / fan_in)).astype(
            np.float32)

    def bn(c):
        return (np.ones(c, np.float32), np.zeros(c, np.float32),
                np.zeros(c, np.float32), np.ones(c, np.float32))

    params = {"stem_w": he((7, 7, 3, 64)), "stem_bn": bn(64),
              "fc_w": he((2048, 1000)).astype(np.float32),
              "fc_b": np.zeros(1000, np.float32)}
    c_in = 64
    for si, n_blocks in enumerate(STAGES):
        c_out = FILTERS[si]
        c_mid = c_out // 4
        for bi in range(n_blocks):
            blk = {"w1": he((1, 1, c_in, c_mid)), "bn1": bn(c_mid),
                   "w2": he((3, 3, c_mid, c_mid)), "bn2": bn(c_mid),
                   "w3": he((1, 1, c_mid, c_out)), "bn3": bn(c_out)}
            if c_in != c_out or (bi == 0 and si > 0):
                blk["wproj"] = he((1, 1, c_in, c_out))
                blk["bnp"] = bn(c_out)
            params["s%db%d" % (si, bi)] = blk
            c_in = c_out
    return params


def make_train_step(cdt, batch, lr=0.1, mom=0.9, wd=1e-4):
    import jax
    import jax.numpy as jnp

    def loss_fn(params, x, y):
        logits, new_stats = forward(params, x, True, cdt)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(
            logp, y[:, None].astype(jnp.int32), axis=1))
        return loss, new_stats

    def is_running_stat(path):
        # bn tuples are (gamma, beta, mean, var): the running stats
        # (tuple indices 2, 3) are not optimized — they're written back
        # from the batch statistics by _merge_stats
        in_bn = any(getattr(k, "key", None) in
                    ("bn1", "bn2", "bn3", "bnp", "stem_bn") for k in path)
        return in_bn and getattr(path[-1], "idx", 0) >= 2

    def train_step(params, moms, x, y):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x, y)

        def upd(path, p, g, m):
            if is_running_stat(path):
                return p, m
            g = g + wd * p
            m2 = mom * m + g
            return p - lr * m2, m2

        flat_p, tree = jax.tree_util.tree_flatten_with_path(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(moms)
        new_p, new_m = [], []
        for (path, p), g, m in zip(flat_p, flat_g, flat_m):
            pn, mn = upd(path, p, g, m)
            new_p.append(pn)
            new_m.append(mn)
        params = jax.tree_util.tree_unflatten(tree, new_p)
        moms = jax.tree_util.tree_unflatten(tree, new_m)
        # write back the batch-updated bn running stats (not optimized)
        params = _merge_stats(params, new_stats)
        return params, moms, loss

    return jax.jit(train_step, donate_argnums=(0, 1))


def _merge_stats(params, new_stats):
    out = dict(params)
    for key, st in new_stats.items():
        if key == "stem_bn":
            g, b, _, _ = out["stem_bn"]
            out["stem_bn"] = (g, b, st[2], st[3])
        else:
            blk = dict(out[key])
            for bn_name, bn_new in st.items():
                g, b, _, _ = blk[bn_name]
                blk[bn_name] = (g, b, bn_new[2], bn_new[3])
            out[key] = blk
    return out


def measure(batch=128, steps=20, compute_dtype="bfloat16", img=224):
    """Time the handwritten step with the data-dependent barrier.
    Returns images/sec."""
    import jax
    import jax.numpy as jnp

    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else None
    rng = np.random.RandomState(0)
    params = init_params(rng)
    moms = jax.tree_util.tree_map(lambda p: np.zeros_like(p), params)
    X = rng.rand(batch, img, img, 3).astype(np.float32)
    y = rng.randint(0, 1000, batch).astype(np.float32)
    step = make_train_step(cdt, batch)

    params = jax.device_put(params)
    moms = jax.device_put(moms)
    Xd, yd = jax.device_put(X), jax.device_put(y)

    tiny = jax.jit(lambda a: jnp.sum(a.astype(jnp.float32)))

    def barrier():
        return float(tiny(params["fc_b"]))

    for _ in range(3):
        params, moms, loss = step(params, moms, Xd, yd)
    barrier()

    # two-window slope, mirroring bench.py: differencing two window
    # lengths cancels the window-ending readback's fixed cost, so the
    # slope is the steady-state step time
    def _window(n):
        nonlocal params, moms
        t0 = time.time()
        for _ in range(n):
            params, moms, loss = step(params, moms, Xd, yd)
        barrier()
        return time.time() - t0

    from bench_timing import two_window_slope
    sl = two_window_slope(_window, steps, max(3, steps // 5), reps=3)
    return sl["n_slope"] * batch / sl["dt"]


if __name__ == "__main__":
    import json
    ips = measure()
    print(json.dumps({"handwritten_img_per_sec": round(ips, 2)}))
