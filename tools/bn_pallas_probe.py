"""Probe: Pallas one-pass BatchNorm(+ReLU) backward vs the jnp hand-VJP.

Round-3 VERDICT #1 asked for the ~42 GB/step ResNet-50 floor to be either
broken or "proved with a kernel rather than a cost model".  The jnp
hand-VJP backward (ops/nn.py `_bn_train_core_make`) is streaming-optimal
at 5 HBM sweeps of the activation: pass 1 reads (dout, x) for both
reductions, pass 2 reads (dout, x) again and writes dx — the re-read is
forced because dx depends on the *global* per-channel sums.  The only
schedule below 5 sweeps is VMEM residency: hold a channel-group's
(N, k*HW) slab on-chip across BOTH phases, so the data is read once and
dx written once (~3 sweeps + f32 per-channel rows ≈ 3.1 sweeps).

This probe measures that kernel (`bn_bwd_onepass`) against the jnp
backward on the ResNet-50 bs128 shapes, on the real chip, with the
dependent-chain slope timing discipline from PERF.md.  The kernel is
deliberately NOT mounted in the framework: the measured verdict
(PERF.md "Round-4 Pallas counter-witness") is that pallas block-DMA on
this chip tops out 2-3x below XLA's in-context bandwidth, so the
residency schedule loses despite its byte cut.  The probe stays
runnable for hardware where that ratio flips.

Layout trick: NCHW viewed as (N, C*HW) — free reshape — and gridded over
channel groups of k = 128/gcd(HW,128) channels, so every block is
(N, k*HW) with k*HW % 128 == 0 (legal, full-sublane).  Per-channel
segment sums and broadcasts inside a mixed-channel block ride the MXU
via a tiny (k*HW, k) block-diagonal selector.  dbeta/dgamma leave the
kernel through an (8, 128)-padded VMEM tile per group.

Run:  python tools/bn_pallas_probe.py [--steps 30]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from functools import partial

import numpy as onp

# the cost-analysis extraction rule is shared with the runtime
# (mxnet_tpu.telemetry.introspect) — make the package importable when
# the probe runs from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _shapes():
    # ResNet-50 bs128 stage shapes (N, C, H, W) + the stem
    return [
        (128, 64, 112, 112),
        (128, 64, 56, 56),
        (128, 256, 56, 56),
        (128, 512, 28, 28),
        (128, 1024, 14, 14),
        (128, 2048, 7, 7),
    ]


def group_k(hw):
    """Channels per block so the lane dim k*HW is 128-divisible."""
    return 128 // math.gcd(hw, 128)


def make_selector(k, hw, dtype):
    """(k*HW, k) block-diagonal ones: column c selects channel c's lanes."""
    import jax.numpy as jnp
    s = onp.zeros((k * hw, k), onp.float32)
    for c in range(k):
        s[c * hw:(c + 1) * hw, c] = 1.0
    return jnp.asarray(s, dtype)


def bn_bwd_onepass(du, x, rstd, mean, scale, shift, relu):
    """One-pass BN(+ReLU) backward: returns (dx, dbeta, dgamma).

    du, x: (N, C, H, W) activation dtype.  rstd/mean/scale/shift: (C,)
    f32 with scale = g*rstd, shift = beta - mean*scale (the forward's
    exact pre-activation affine, so the recomputed ReLU mask matches).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, C, H, W = x.shape
    HW = H * W
    k = group_k(HW)
    if C % k or N % 8:
        raise ValueError("unsupported shape for onepass bwd: %s" % (x.shape,))
    khw = k * HW
    n_count = N * HW  # reduction count per channel
    f32 = jnp.float32

    x2 = x.reshape(N, C * HW)
    du2 = du.reshape(N, C * HW)
    # rows are (1, C*HW): Mosaic's remote compile rejects 1-D blocked
    # inputs here, but (1, khw) blocks of a (1, C*HW) array are legal
    # (last dim full, first dim equals the array dim)
    rep = lambda v: jnp.repeat(v.astype(f32), HW,
                               total_repeat_length=C * HW)[None, :]
    a_row = rep(rstd)                   # xhat = x*a - b
    b_row = rep(mean * rstd)
    sc_row = rep(scale)
    sh_row = rep(shift)
    S = make_selector(k, HW, f32)

    def kernel(x_ref, du_ref, a_ref, b_ref, sc_ref, sh_ref, s_ref,
               dx_ref, db_ref, dg_ref):
        xf = x_ref[...].astype(f32)
        duf = du_ref[...].astype(f32)
        a = a_ref[...]
        b = b_ref[...]
        sc = sc_ref[...]
        xhat = xf * a - b
        if relu:
            y = xf * sc + sh_ref[...]
            duf = jnp.where(y > 0, duf, 0.0)
        col_db = jnp.sum(duf, axis=0, keepdims=True)          # (1, kHW)
        col_dg = jnp.sum(duf * xhat, axis=0, keepdims=True)
        sel = s_ref[...]
        db = jnp.dot(col_db, sel, preferred_element_type=f32)  # (1, k)
        dg = jnp.dot(col_dg, sel, preferred_element_type=f32)
        # broadcast (1,k) back to (1,kHW) lanes: contract with S's dim 1
        dims = (((1,), (1,)), ((), ()))
        db_row = jax.lax.dot_general(db, sel, dims,
                                     preferred_element_type=f32)
        dg_row = jax.lax.dot_general(dg, sel, dims,
                                     preferred_element_type=f32)
        inv_n = 1.0 / n_count
        dx = (duf - db_row * inv_n - xhat * (dg_row * inv_n)) * sc
        dx_ref[...] = dx.astype(dx_ref.dtype)
        pad = ((0, 0), (0, 128 - k))
        db_ref[0] = jnp.concatenate(
            [jnp.pad(db, pad), jnp.zeros((7, 128), f32)], axis=0)
        dg_ref[0] = jnp.concatenate(
            [jnp.pad(dg, pad), jnp.zeros((7, 128), f32)], axis=0)

    grid = (C // k,)
    dx2, db3, dg3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, khw), lambda i: (0, i)),
            pl.BlockSpec((N, khw), lambda i: (0, i)),
            pl.BlockSpec((1, khw), lambda i: (0, i)),
            pl.BlockSpec((1, khw), lambda i: (0, i)),
            pl.BlockSpec((1, khw), lambda i: (0, i)),
            pl.BlockSpec((1, khw), lambda i: (0, i)),
            pl.BlockSpec((khw, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((N, khw), lambda i: (0, i)),
            pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, C * HW), x.dtype),
            jax.ShapeDtypeStruct((C // k, 8, 128), f32),
            jax.ShapeDtypeStruct((C // k, 8, 128), f32),
        ],
        # the default 16MB scoped-vmem cap rejects the 112² blocks; the
        # v5e has headroom (the 12.8MB-block copy probe compiled fine
        # at a raised cap)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(x2, du2, a_row, b_row, sc_row, sh_row, S)
    dx = dx2.reshape(N, C, H, W)
    dbeta = db3[:, 0, :k].reshape(C)
    dgamma = dg3[:, 0, :k].reshape(C)
    return dx, dbeta, dgamma


def bn_bwd_jnp(du, x, rstd, mean, scale, shift, relu):
    """The framework's current jnp hand-VJP backward (ops/nn.py _bwd),
    restated standalone with the same math."""
    import jax.numpy as jnp
    f32 = jnp.float32
    axes = (0, 2, 3)
    n = x.shape[0] * x.shape[2] * x.shape[3]
    bshape = (1, -1, 1, 1)
    xf = x.astype(f32)
    xhat = (xf - mean.reshape(bshape)) * rstd.reshape(bshape)
    duf = du.astype(f32)
    if relu:
        y = xf * scale.reshape(bshape) + shift.reshape(bshape)
        duf = jnp.where(y > 0, duf, 0.0)
    dbeta = jnp.sum(duf, axis=axes)
    dgamma = jnp.sum(duf * xhat, axis=axes)
    dx = (duf - (dbeta / n).reshape(bshape)
          - xhat * (dgamma / n).reshape(bshape)) * scale.reshape(bshape)
    return dx.astype(x.dtype), dbeta, dgamma


def run_shape(shape, steps, relu=True, dtype="bfloat16"):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    N, C, H, W = shape
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, shape, f32).astype(dtype)
    du = (jax.random.normal(k2, shape, f32) * 0.1).astype(dtype)
    mean = jax.random.normal(k3, (C,), f32) * 0.1
    rstd = jnp.ones((C,), f32) * 1.3
    gamma = jnp.ones((C,), f32) * 0.9
    beta = jnp.zeros((C,), f32) + 0.05
    scale = gamma * rstd
    shift = beta - mean * scale

    res = {"shape": list(shape), "k": group_k(H * W)}

    fns = {}
    for name, fn in (("jnp", bn_bwd_jnp), ("pallas", bn_bwd_onepass)):
        jfn = jax.jit(partial(fn, relu=relu))
        try:
            out = jfn(du, x, rstd, mean, scale, shift)
            jax.block_until_ready(out)
        except Exception as e:  # noqa: BLE001 - probe records failures
            res[name + "_error"] = str(e)[:300]
            continue
        fns[name] = jfn
        comp = jfn.lower(du, x, rstd, mean, scale, shift).compile()
        # shared extraction rule (telemetry.introspect)
        from mxnet_tpu.telemetry.introspect import analyze_compiled
        by = analyze_compiled(comp)["bytes_accessed"]
        if by:
            res[name + "_gb"] = round(by / 1e9, 3)

    if "jnp" in fns and "pallas" in fns:
        o_j = fns["jnp"](du, x, rstd, mean, scale, shift)
        o_p = fns["pallas"](du, x, rstd, mean, scale, shift)
        dxj, dxp = onp.asarray(o_j[0], onp.float32), onp.asarray(
            o_p[0], onp.float32)
        den = max(1e-6, float(onp.max(onp.abs(dxj))))
        res["dx_rel_err"] = float(onp.max(onp.abs(dxj - dxp)) / den)
        for i, nm in ((1, "dbeta"), (2, "dgamma")):
            aj, ap = onp.asarray(o_j[i]), onp.asarray(o_p[i])
            res[nm + "_rel_err"] = float(
                onp.max(onp.abs(aj - ap)) / max(1e-6, onp.max(onp.abs(aj))))

    # timing: dependent chain (previous dx IS the next du — no blend, so
    # no extra traffic and no fusion-barrier asymmetry between paths),
    # two chain lengths differenced: a single window would charge the
    # window-ending readback's fixed cost to kernels whose true cost is
    # ~1ms.
    tiny = jax.jit(lambda a: jnp.sum(a.astype(f32)))
    L1, L2 = max(4, steps // 4), steps

    def _mk_chain(jfn, length):
        def chain(du0, xx):
            def body(carry, _):
                dx, db, dg = jfn(carry, xx, rstd, mean, scale, shift)
                return dx.astype(du0.dtype), db[0]
            return jax.lax.scan(body, du0, None, length=length)
        return jax.jit(chain)

    for name, jfn in fns.items():
        c1, c2 = _mk_chain(jfn, L1), _mk_chain(jfn, L2)

        def _run(cj):
            t0 = time.time()
            outc = cj(du, x)
            float(tiny(outc[0]))
            return time.time() - t0

        _run(c1), _run(c2)  # warm/compile both
        t1 = min(_run(c1) for _ in range(3))
        t2 = min(_run(c2) for _ in range(3))
        dt = (t2 - t1) / (L2 - L1) if L2 > L1 else 0.0
        if dt <= 0:
            dt = t2 / L2
        res[name + "_ms"] = round(dt * 1e3, 3)
        bytes_min = N * C * H * W * (2 if dtype == "bfloat16" else 4)
        res[name + "_eff_gbps"] = round(
            res.get(name + "_gb", 0.0) / dt, 1) if name + "_gb" in res else 0
        res[name + "_sweeps_equiv"] = round(dt * 819e9 / bytes_min, 2)
    if "jnp_ms" in res and "pallas_ms" in res:
        res["speedup"] = round(res["jnp_ms"] / res["pallas_ms"], 3)
    return res


def copy_sweep(nblocks_list=(1, 4, 16)):
    """Pure-copy Pallas kernel (zero compute) over column blocks of a
    (128, 256*3136) bf16 array — measures the block-DMA bandwidth
    ceiling of pallas_call on this chip.  This is the decisive number:
    if a COPY cannot beat ~1/2.4 of the XLA-in-context bandwidth, no
    residency kernel built on the same DMA path can win back its
    2-sweep saving (PERF.md "Round-4 Pallas counter-witness")."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    N, CHW = 128, 256 * 3136
    A = N * CHW * 2
    x0 = jax.random.normal(jax.random.PRNGKey(0), (N, CHW), f32) \
        .astype(jnp.bfloat16)
    tiny = jax.jit(lambda a: jnp.sum(a.astype(f32)))

    def slope_time(call, L1=8, L2=40):
        def mk(L):
            def chain(x):
                def body(c, _):
                    return call(c), 0
                out, _ = jax.lax.scan(body, x, None, length=L)
                return out
            return jax.jit(chain)
        c1, c2 = mk(L1), mk(L2)

        def run(cj):
            t0 = time.time()
            out = cj(x0)
            float(tiny(out[0]))
            return time.time() - t0
        run(c1), run(c2)
        t1 = min(run(c1) for _ in range(3))
        t2 = min(run(c2) for _ in range(3))
        return (t2 - t1) / (L2 - L1)

    def k_copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    for nb in nblocks_list:
        blk = nb * 6272
        call = pl.pallas_call(
            k_copy, grid=(CHW // blk,),
            in_specs=[pl.BlockSpec((N, blk), lambda i: (0, i))],
            out_specs=pl.BlockSpec((N, blk), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((N, CHW), jnp.bfloat16),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=110 * 1024 * 1024))
        dt = slope_time(call)
        print(json.dumps({"block_mb": round(N * blk * 2 / 1e6, 1),
                          "ms": round(dt * 1e3, 3),
                          "copy_gbps": round(2 * A / dt / 1e9, 1)}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--no-relu", action="store_true")
    ap.add_argument("--shape", type=int, default=-1,
                    help="index into the shape list (default -1 = all)")
    ap.add_argument("--copy-sweep", action="store_true",
                    help="measure the pallas block-DMA bandwidth ceiling "
                         "instead of the backward kernels")
    args = ap.parse_args()
    import jax
    print(json.dumps({"device": str(jax.devices()[0])}))
    if args.copy_sweep:
        copy_sweep()
        return
    shapes = _shapes() if args.shape < 0 else [_shapes()[args.shape]]
    for shape in shapes:
        r = run_shape(shape, args.steps, relu=not args.no_relu)
        print(json.dumps(r))


if __name__ == "__main__":
    main()
