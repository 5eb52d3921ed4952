"""Plain reference: the afmoe decoder (Arcee Trinity family), trained
with SGD on next-token cross-entropy.

Residual stream `h` of width `hidden_size`; a batch's rows are tokens,
cut into sequences of `seq_len`:

    a  = RMSNorm_in(h)
    q, k, v, g = a.Wq, a.Wk, a.Wv, a.Wg
    q, k = RMSNorm per head (one learned scale of head_dim for all heads)
    sliding layers: q, k = RoPE(q, k); full layers: no positions at all
    P  = softmax(q.k^T / sqrt(head_dim) + mask)   causal; sliding layers
         also drop keys with i - j >= sliding_window; the query heads of
         a group share a key-value head
    o  = (P.v) * sigmoid(g);  h = h + RMSNorm_post_attn(o.Wo)
    m  = RMSNorm_pre_mlp(h)
    dense layer:  f = (silu(m.Wgate) * (m.Wup)).Wdown
    expert layer: s = sigmoid(m.Wr); S = top-k of (s + b);
                  w_e = route_scale * s_e / sum_{e' in S} s_e'
                  f = shared(m) + sum_{e in S, e held} w_e * expert_e(m)
    h  = h + RMSNorm_post_mlp(f)
    input: embedding row * sqrt(hidden_size); output: RMSNorm_final, head
    after each step, not by gradient:
                  b_e += coeff * sign(mean(load) - load_e), load over all E

Straight `jax.numpy`, float32, matmul precision `highest`.  It imports
nothing of the program and is given nothing the program made.  It is
given the same `experts_held` as the program and leaves out the same
terms: what the experts on other chips would add.

Departures from the published description (config.json of
arcee-ai/Trinity-Mini and, for what no key settles, the family's public
modelling code as remembered; the configuration's file lists these
under `assumed`):

* the sigmoid gate `Wg` on the attention output, no positions on full
  layers, a norm per head on q and k, four norms a block with the two
  `post` ones on the branch, the embedding's sqrt(hidden_size): assumed;
* the rule that moves `b` is the published rule of loss-free balancing
  (arXiv:2412.19437, 2.1.2) with the config's `load_balance_coeff`;
* every expert held runs over every token and the tokens that did not
  choose it get weight 0: the same sum as sorting tokens to experts,
  written the plain way;
* each layer, each sequence's attention of one key-value group, each
  expert and the head run under `jax.checkpoint`, so that the backward
  pass fits one chip: nothing of the mathematics changes.  (The expert
  layers as one `lax.scan` over stacked parameters, as
  reference/resnet.py runs a stage's units, compiled no faster and
  took 9.8 GB of temporaries where this takes 4.0: not done.)

`arith` is the hook of the control: `arith.operand` is applied to both
operands of every matrix product (the embedding's rows too) and
`arith.result` to the tensors the program holds in its compute type:
products' results, norms' outputs, each residual sum.  Router scores,
the softmax of attention and of the loss, every norm's statistics and
the update stay in float32, as the program's do.  `Exact` changes
nothing.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax


class Exact:
    """The reference's own arithmetic: float32 throughout."""
    operand = staticmethod(lambda t: t)
    result = staticmethod(lambda t: t)


HIGHEST = lax.Precision.HIGHEST
ARCH_KEYS = ("vocab_size", "seq_len", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "intermediate_size",
             "moe_intermediate_size", "num_experts_published",
             "num_experts_per_tok", "experts_held", "num_shared_experts",
             "num_dense_layers", "layer_types", "sliding_window",
             "rope_theta", "rms_norm_eps", "score_func", "route_norm",
             "route_scale", "load_balance_coeff")


# --------------------------------------------------------------- shapes
def arch_of(cfg):
    """What of a configuration's file shapes the net."""
    return {k: cfg[k] for k in ARCH_KEYS}


def param_shapes(arch):
    """(parameters, auxiliary state): name -> shape, by the names the
    program's builder (`mxnet_tpu/models/afmoe.py`) gives them.  Every
    matrix is (out, in); the experts held are stacked on rows."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    nq, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    f, fe = arch["intermediate_size"], arch["moe_intermediate_size"]
    held = arch["experts_held"][1]
    args, aux = {"embed_weight": (arch["vocab_size"], d)}, {}
    for i in range(len(arch["layer_types"])):
        p = "l%d_" % i
        args[p + "in_norm_gamma"] = (d,)
        args[p + "q_weight"] = (nq * hd, d)
        args[p + "k_weight"] = (nkv * hd, d)
        args[p + "v_weight"] = (nkv * hd, d)
        args[p + "gate_weight"] = (nq * hd, d)
        args[p + "q_norm_gamma"] = (hd,)
        args[p + "k_norm_gamma"] = (hd,)
        args[p + "o_weight"] = (d, nq * hd)
        for n in ("post_attn", "pre_mlp", "post_mlp"):
            args[p + n + "_norm_gamma"] = (d,)
        if i < arch["num_dense_layers"]:
            args[p + "mlp_gate_weight"] = (f, d)
            args[p + "mlp_up_weight"] = (f, d)
            args[p + "mlp_down_weight"] = (d, f)
            continue
        args[p + "moe_router_weight"] = (arch["num_experts_published"], d)
        args[p + "moe_experts_gate_weight"] = (held * fe, d)
        args[p + "moe_experts_up_weight"] = (held * fe, d)
        args[p + "moe_experts_down_weight"] = (held * d, fe)
        aux[p + "moe_router_bias"] = (arch["num_experts_published"],)
        if arch["num_shared_experts"]:
            fs = fe * arch["num_shared_experts"]
            args[p + "shared_gate_weight"] = (fs, d)
            args[p + "shared_up_weight"] = (fs, d)
            args[p + "shared_down_weight"] = (d, fs)
    args["final_norm_gamma"] = (d,)
    args["head_weight"] = (arch["vocab_size"], d)
    return args, aux


def products(arch):
    """The leaves that are an operand of a matrix product."""
    return {k for k, shape in param_shapes(arch)[0].items()
            if len(shape) > 1 and k != "embed_weight"}


# -------------------------------------------------------------- forward
def _linear(x, w, arith):
    """x (rows, in) times w (out, in), transposed."""
    return arith.result(lax.dot_general(
        arith.operand(x), arith.operand(w), (((1,), (1,)), ((), ())),
        precision=HIGHEST))


def _rms_norm(x, gamma, eps, arith):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return arith.result(x * lax.rsqrt(ms + eps) * gamma)


def _rope(x, theta):
    """x: (sequences, T, heads, head_dim); the two halves of a head are
    the rotation's pairs."""
    T, D = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attend(q, k, v, window, arith):
    """One sequence, one key-value head: q (R, T, D), k and v (T, D)."""
    T, D = k.shape
    s = jnp.einsum("rqd,kd->rqk", arith.operand(q), arith.operand(k),
                   precision=HIGHEST) / math.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = j <= i
    if window:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    return arith.result(jnp.einsum(
        "rqk,kd->rqd", arith.operand(p), arith.operand(v),
        precision=HIGHEST))


def _attention(p, a, arch, sliding, arith):
    T, hd = arch["seq_len"], arch["head_dim"]
    nq, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    eps = arch["rms_norm_eps"]
    B = a.shape[0] // T
    q = _linear(a, p["q_weight"], arith).reshape(B, T, nq, hd)
    k = _linear(a, p["k_weight"], arith).reshape(B, T, nkv, hd)
    v = _linear(a, p["v_weight"], arith).reshape(B, T, nkv, hd)
    g = _linear(a, p["gate_weight"], arith)
    q = _rms_norm(q, p["q_norm_gamma"], eps, arith)
    k = _rms_norm(k, p["k_norm_gamma"], eps, arith)
    if sliding:
        q = arith.result(_rope(q, arch["rope_theta"]))
        k = arith.result(_rope(k, arch["rope_theta"]))
    # (sequence, key-value head) pairs in turn, each under a checkpoint
    qg = q.reshape(B, T, nkv, nq // nkv, hd).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(B * nkv, nq // nkv, T, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * nkv, T, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * nkv, T, hd)
    one = jax.checkpoint(functools.partial(
        _attend, window=arch["sliding_window"] if sliding else 0,
        arith=arith))
    o = lax.map(lambda t: one(*t), (qg, kg, vg))
    o = o.reshape(B, nkv, nq // nkv, T, hd).transpose(0, 3, 1, 2, 4)
    o = arith.result(o.reshape(B * T, nq * hd) * jax.nn.sigmoid(g))
    return _linear(o, p["o_weight"], arith)


def _gated_mlp(x, w_gate, w_up, w_down, arith):
    act = arith.result(jax.nn.silu(_linear(x, w_gate, arith))
                       * _linear(x, w_up, arith))
    return _linear(act, w_down, arith)


def _route(m, w_router, bias, arch, arith):
    """(chosen (rows, k), weights (rows, k)) over ALL experts, scores in
    float32."""
    logits = lax.dot_general(arith.operand(m), arith.operand(w_router),
                             (((1,), (1,)), ((), ())), precision=HIGHEST)
    if arch["score_func"] == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    _, chosen = lax.top_k(s + lax.stop_gradient(bias)[None, :],
                          arch["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if arch["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * arch["route_scale"]


def _experts(p, m, bias, arch, arith):
    """The routed part that the experts held give, and the load of
    every expert (tokens that chose it)."""
    first, held = arch["experts_held"]
    d, fe = arch["hidden_size"], arch["moe_intermediate_size"]
    chosen, w = _route(m, p["moe_router_weight"], bias, arch, arith)
    # weight of each held expert for each token, 0 where not chosen
    ids = first + jnp.arange(held)
    w_tok = jnp.sum(jnp.where(chosen[None] == ids[:, None, None],
                              w[None], 0.0), axis=-1)        # (held, rows)
    stacks = (p["moe_experts_gate_weight"].reshape(held, fe, d),
              p["moe_experts_up_weight"].reshape(held, fe, d),
              p["moe_experts_down_weight"].reshape(held, d, fe))

    @jax.checkpoint
    def one(acc, t):
        wg, wu, wd, wt = t
        return acc + wt[:, None] * _gated_mlp(m, wg, wu, wd, arith), None

    out, _ = lax.scan(one, jnp.zeros_like(m), stacks + (w_tok,))
    load = jnp.zeros((arch["num_experts_published"],), jnp.float32).at[
        chosen.reshape(-1)].add(1.0)
    return out, lax.stop_gradient(load)


def _short(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _layer(h, p, bias, arch, i, arith):
    eps = arch["rms_norm_eps"]
    sliding = arch["layer_types"][i] == "sliding_attention"
    a = _rms_norm(h, p["in_norm_gamma"], eps, arith)
    o = _attention(p, a, arch, sliding, arith)
    h = arith.result(h + _rms_norm(o, p["post_attn_norm_gamma"], eps, arith))
    m = _rms_norm(h, p["pre_mlp_norm_gamma"], eps, arith)
    if i < arch["num_dense_layers"]:
        f, load = _gated_mlp(m, p["mlp_gate_weight"], p["mlp_up_weight"],
                             p["mlp_down_weight"], arith), None
    else:
        f, load = _experts(p, m, bias, arch, arith)
        if arch["num_shared_experts"]:
            f = arith.result(f + _gated_mlp(
                m, p["shared_gate_weight"], p["shared_up_weight"],
                p["shared_down_weight"], arith))
    h = arith.result(h + _rms_norm(f, p["post_mlp_norm_gamma"], eps, arith))
    return h, load


def trunk(params, aux, ids, arch, arith=Exact):
    """The residual stream after the last layer, and every expert
    layer's load; each layer under a checkpoint."""
    d = arch["hidden_size"]
    h = arith.result(arith.operand(params["embed_weight"])[ids]
                     * math.sqrt(d))
    loads = {}
    for i in range(len(arch["layer_types"])):
        prefix = "l%d_" % i
        bias = aux.get(prefix + "moe_router_bias")
        h, load = jax.checkpoint(functools.partial(
            _layer, arch=arch, i=i, arith=arith))(
                h, _short(params, prefix), bias)
        if load is not None:
            loads[prefix + "moe_router_bias"] = load
    return h, loads


def _head(h, gamma, w_head, arch, arith):
    return _linear(_rms_norm(h, gamma, arch["rms_norm_eps"], arith),
                   w_head, arith)


def forward(params, aux, ids, arch, arith=Exact):
    """Logits (rows, vocabulary held) and every expert layer's load."""
    h, loads = trunk(params, aux, ids, arch, arith)
    return _head(h, params["final_norm_gamma"], params["head_weight"],
                 arch, arith), loads


def loss_fn(params, aux, ids, labels, arch, arith):
    h, loads = trunk(params, aux, ids, arch, arith)

    @jax.checkpoint
    def head_loss(h, gamma, w_head):
        logits = _head(h, gamma, w_head, arch, arith)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    return head_loss(h, params["final_norm_gamma"],
                     params["head_weight"]), loads


# ------------------------------------------------------------------ SGD
def decays(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_step(params, aux, mom, x, labels, arch, opt, arith):
    """One step.  Returns (loss, norms of the mean gradient by leaf,
    new params, new selection biases, new momentum)."""
    ids, labels = x.astype(jnp.int32), labels.astype(jnp.int32)
    (loss, loads), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, aux, ids, labels, arch, arith)
    coeff = arch["load_balance_coeff"]
    new_aux = {k: aux[k] + coeff * jnp.sign(jnp.mean(load) - load)
               for k, load in loads.items()}
    new_p, new_m = {}, {}
    for k, w in params.items():
        g = grads[k] + (opt["wd"] if decays(k) else 0.0) * w
        new_m[k] = opt["momentum"] * mom[k] - opt["learning_rate"] * g
        new_p[k] = w + new_m[k]
    return loss, _leaf_norms(grads), new_p, new_aux, new_m


@functools.lru_cache(maxsize=None)
def _jitted_step(arch_json, opt_json, arith):
    """One jitted step per (net, optimizer, arithmetic) and process."""
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
    """Drive the reference from `params`/`aux` through `batches` (a list
    of (ids, next ids), rows a whole number of sequences) and return
    what the check compares: each step's loss, the first gradient's
    norm by leaf, and the norm of the change of every parameter and of
    every selection bias."""
    with jax.default_matmul_precision("highest"):
        step = _jitted_step(json.dumps(arch, sort_keys=True),
                            json.dumps(opt, sort_keys=True), arith)
        # every step takes its state committed to the placement the
        # caller's parameters have, its own outputs too: an input that
        # is placed another way (or not committed at all, as fresh
        # zeros are) compiles the step a second time
        spot = jax.tree_util.tree_map(lambda v: v.sharding, (params, aux))
        placed = (spot[0], spot[1], spot[0])
        p, a, m = jax.device_put(
            (_copy(params), _copy(aux), _zeros(params)), placed)
        losses, grad_norms = [], None
        for x, y in batches:
            if sharding is not None:
                x, y = jax.device_put(x, sharding), jax.device_put(y, sharding)
            loss, norms, p, a, m = step(p, a, m, x, y)
            p, a, m = jax.device_put((p, a, m), placed)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(v) for k, v in norms.items()}
    return {
        "loss": losses,
        "grad_norm": grad_norms,
        "param_change": {k: float(v) for k, v in _diff(p, params).items()},
        "stat_change": {k: float(v) for k, v in _diff(a, aux).items()},
    }
