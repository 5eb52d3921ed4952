"""Plain reference: pre-activation bottleneck ResNet, trained with SGD.

The net `example/image-classification/symbols/resnet.py` builds for
depth >= 50 (He et al., arXiv:1512.03385 Table 1 for the widths and unit
counts; the BN -> ReLU -> conv unit order of arXiv:1603.05027, which is
what that script writes), softmax cross-entropy, train-mode BatchNorm
with its moving statistics, and mxnet's SGD with momentum:

    g <- mean gradient + wd * w      (wd on *_weight and *_gamma only)
    m <- momentum * m - lr * g
    w <- w + m

Straight `jax.numpy` / `lax.conv_general_dilated`, float32, matmul
precision `highest`.  It imports nothing of the program and is given
nothing the program made.  Departures from a line-by-line transcription,
both to make it fit and compile on one chip:

* the units of a stage that share a shape (all but the first) run as
  one `lax.scan` over their stacked parameters;
* every unit is wrapped in `jax.checkpoint`, so the backward pass keeps
  only unit boundaries and recomputes inside a unit (BatchNorm ties the
  rows of a batch together, so "in blocks" means layer blocks here).

`arith` is the hook of the control: `arith.operand` is applied to both
operands of every convolution and of the classifier's matmul, and
`arith.result` to their results and to each unit's sum, the tensors the
program holds in its compute type (forward, and through a custom
gradient the cotangents that come back).  BatchNorm's statistics and
the update stay in float32, as the program's do.  `Exact` changes
nothing.
"""
import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

class Exact:
    """The reference's own arithmetic: float32 throughout."""
    operand = staticmethod(lambda t: t)
    result = staticmethod(lambda t: t)


BN_EPS = 2e-5
BN_MOMENTUM = 0.9
HIGHEST = lax.Precision.HIGHEST


# --------------------------------------------------------------- shapes
def arch_of(cfg):
    """What of a configuration's file shapes the net."""
    return {k: cfg[k] for k in ("units", "filters", "classes", "image")}


def unit_names(arch):
    """[(unit name, in channels, out channels, stride, dim_match)]."""
    out, prev = [], arch["filters"][0]
    for i, n in enumerate(arch["units"]):
        width = arch["filters"][i + 1]
        for j in range(n):
            out.append(("stage%d_unit%d" % (i + 1, j + 1), prev, width,
                        (1 if i == 0 else 2) if j == 0 else 1, j > 0))
            prev = width
    return out


def param_shapes(arch):
    """(parameters, BatchNorm moving statistics): name -> shape, by the
    names the reference's symbol script gives them."""
    c_in = arch["image"][0]
    args, aux = {}, {}

    def bn(name, c):
        args[name + "_gamma"] = (c,)
        args[name + "_beta"] = (c,)
        aux[name + "_moving_mean"] = (c,)
        aux[name + "_moving_var"] = (c,)

    bn("bn_data", c_in)
    f0 = arch["filters"][0]
    args["conv0_weight"] = (f0, c_in, 7, 7)
    bn("bn0", f0)
    for name, cin, cout, _stride, match in unit_names(arch):
        mid = cout // 4
        bn(name + "_bn1", cin)
        args[name + "_conv1_weight"] = (mid, cin, 1, 1)
        bn(name + "_bn2", mid)
        args[name + "_conv2_weight"] = (mid, mid, 3, 3)
        bn(name + "_bn3", mid)
        args[name + "_conv3_weight"] = (cout, mid, 1, 1)
        if not match:
            args[name + "_sc_weight"] = (cout, cin, 1, 1)
    last = arch["filters"][-1]
    bn("bn1", last)
    args["fc1_weight"] = (arch["classes"], last)
    args["fc1_bias"] = (arch["classes"],)
    return args, aux


def products(arch):
    """The leaves that are an operand of a convolution or of the
    classifier's matmul: those the compute type touches directly."""
    return {k for k, shape in param_shapes(arch)[0].items() if len(shape) > 1}


# -------------------------------------------------------------- forward
def _bn(x, gamma, beta, fix_gamma=False):
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]),
                   axis=(0, 2, 3))
    scale = lax.rsqrt(var + BN_EPS)
    if not fix_gamma:
        scale = scale * gamma
    y = (x - mean[None, :, None, None]) * scale[None, :, None, None] \
        + beta[None, :, None, None]
    return y, (lax.stop_gradient(mean), lax.stop_gradient(var))


def _conv(x, w, stride, pad, arith):
    return arith.result(lax.conv_general_dilated(
        arith.operand(x), arith.operand(w), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST))


def _unit(x, p, stride, match, arith):
    """One bottleneck unit; `p` holds its leaves by their short names.
    Returns the output and the six batch statistics, bn1..bn3."""
    y, s1 = _bn(x, p["bn1_gamma"], p["bn1_beta"])
    a1 = jax.nn.relu(y)
    y = _conv(a1, p["conv1_weight"], 1, 0, arith)
    y, s2 = _bn(y, p["bn2_gamma"], p["bn2_beta"])
    y = _conv(jax.nn.relu(y), p["conv2_weight"], stride, 1, arith)
    y, s3 = _bn(y, p["bn3_gamma"], p["bn3_beta"])
    y = _conv(jax.nn.relu(y), p["conv3_weight"], 1, 0, arith)
    short = x if match else _conv(a1, p["sc_weight"], stride, 0, arith)
    return arith.result(y + short), (s1, s2, s3)


def _short(params, unit):
    n = len(unit) + 1
    return {k[n:]: v for k, v in params.items() if k.startswith(unit + "_")}


def forward(params, x, arch, arith=Exact, scan=True):
    """Logits and the batch statistics of every BatchNorm, by name.
    `scan=False` writes every unit out (for counting what a pass holds)."""
    stats = {}
    x, stats["bn_data"] = _bn(x, None, params["bn_data_beta"],
                              fix_gamma=True)
    x = _conv(x, params["conv0_weight"], 2, 3, arith)
    x, stats["bn0"] = _bn(x, params["bn0_gamma"], params["bn0_beta"])
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    units = unit_names(arch)
    i = 0
    while i < len(units):
        name, _cin, _cout, stride, match = units[i]
        if not match or not scan:
            x, (s1, s2, s3) = jax.checkpoint(functools.partial(
                _unit, stride=stride, match=match, arith=arith))(
                    x, _short(params, name))
            for k, s in (("_bn1", s1), ("_bn2", s2), ("_bn3", s3)):
                stats[name + k] = s
            i += 1
            continue
        # the rest of the stage: one shape, one scan
        j = i
        while j < len(units) and units[j][4]:
            j += 1
        names = [u[0] for u in units[i:j]]
        trees = [_short(params, n) for n in names]
        stacked = {k: jnp.stack([t[k] for t in trees]) for k in trees[0]}

        @jax.checkpoint
        def body(h, p):
            return _unit(h, p, 1, True, arith)

        x, per_unit = lax.scan(body, x, stacked)
        for u, n in enumerate(names):
            for k, s in zip(("_bn1", "_bn2", "_bn3"), per_unit):
                stats[n + k] = (s[0][u], s[1][u])
        i = j
    x, stats["bn1"] = _bn(x, params["bn1_gamma"], params["bn1_beta"])
    x = jnp.mean(jax.nn.relu(x), axis=(2, 3))
    logits = arith.result(lax.dot_general(
        arith.operand(x), arith.operand(params["fc1_weight"]),
        (((1,), (1,)), ((), ())), precision=HIGHEST))
    return logits + params["fc1_bias"], stats


def loss_fn(params, x, labels, arch, arith):
    logits, stats = forward(params, x, arch, arith)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return -jnp.mean(picked), stats


# ------------------------------------------------------------------ SGD
def decays(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_step(params, aux, mom, x, labels, arch, opt, arith):
    """One step.  Returns (loss, norms of the mean gradient by leaf,
    new params, new moving statistics, new momentum)."""
    (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, x, labels, arch, arith)
    new_aux = {}
    for name, (mean, var) in stats.items():
        for k, s in (("_moving_mean", mean), ("_moving_var", var)):
            new_aux[name + k] = aux[name + k] * BN_MOMENTUM \
                + s * (1.0 - BN_MOMENTUM)
    new_p, new_m = {}, {}
    for k, w in params.items():
        g = grads[k] + (opt["wd"] if decays(k) else 0.0) * w
        new_m[k] = opt["momentum"] * mom[k] - opt["learning_rate"] * g
        new_p[k] = w + new_m[k]
    return loss, _leaf_norms(grads), new_p, new_aux, new_m


@functools.lru_cache(maxsize=None)
def _jitted_step(arch_json, opt_json, arith):
    """One jitted step per (net, optimizer, arithmetic) and process: a
    second seed in the same process compiles nothing."""
    return jax.jit(functools.partial(
        train_step, arch=json.loads(arch_json), opt=json.loads(opt_json),
        arith=arith), donate_argnums=(0, 1, 2))


def release():
    """Forget the jitted steps, so that their programs can be freed."""
    _jitted_step.cache_clear()


_diff = jax.jit(lambda a, b: _leaf_norms({k: a[k] - b[k] for k in a}))
_copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
_zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))


def follow(params, aux, batches, arch, opt, arith=Exact, sharding=None):
    """Drive the reference from `params`/`aux` through `batches`
    (a list of (x, labels)) and return what the check compares:
    each step's loss, the first gradient's norm by leaf, and the norm
    of the change of every parameter and moving statistic."""
    step = _jitted_step(json.dumps(arch, sort_keys=True),
                        json.dumps(opt, sort_keys=True), arith)
    p, a, m = _copy(params), _copy(aux), _zeros(params)
    losses, grad_norms = [], None
    for x, y in batches:
        if sharding is not None:
            x, y = jax.device_put(x, sharding), jax.device_put(y, sharding)
        loss, norms, p, a, m = step(p, a, m, x, y)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
    return {
        "loss": losses,
        "grad_norm": grad_norms,
        "param_change": {k: float(v) for k, v in _diff(p, params).items()},
        "stat_change": {k: float(v) for k, v in _diff(a, aux).items()},
    }
