"""Plain reference: the nemotron_h decoder (NVIDIA Nemotron-H /
Nemotron-3 family), trained with SGD on next-token cross-entropy.

Residual stream `h` of width `hidden_size`; a batch's rows are tokens,
cut into sequences of `seq_len`.  Every layer is ONE mixer, by its
letter in `hybrid_override_pattern`:

    h = h + mixer(RMSNorm(h))

    M, Mamba-2 (H heads of P, G groups, state N, d_inner = H.P):
       z, xBC, dt = a.W_in                 widths d_inner | d_inner+2GN | H
       xBC_t = silu(b + sum_j w[:, j] * xBC_{t-3+j})   zero before the
               sequence's first token, never across sequences
       x, B, C = xBC                       (H, P) | (G, N) | (G, N)
       dt = softplus(dt + dt_bias);  A = -exp(A_log)       per head
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S_0 = 0, per head,
       y_t = S_t C_t + D x_t                           head h: group h // (H/G)
       y = y * silu(z); RMSNorm over each group of d_inner / G channels
       under a learned scale of d_inner; out = y.W_out
    *, attention: P = softmax(q.k^T / sqrt(head_dim) + causal mask), the
       query heads of a group sharing a key-value head; o = (P.v).Wo
    E, experts: s = sigmoid(a.Wr); S = top-k of (s + b);
       w_e = scale * s_e / sum_{e' in S} s_e'
       f = shared(a) + sum_{e in S, e held} w_e * expert_e(a)
       expert(a) = relu(a.Wup)^2 .Wdown
    input: the embedding's row; output: RMSNorm_final, an untied head
    after each step, not by gradient:
       b_e += coeff * sign(mean(load) - load_e), load over all experts

Straight `jax.numpy`, float32, matmul precision `highest`.  It imports
nothing of the program and is given nothing the program made.  **The
Mamba-2 layer is the recurrence as written, token by token**: the
program computes it chunked (the state-space dual form), so the two
sides share no algorithm.  It is given the same `experts_held` and the
same slice of the vocabulary as the program and leaves out the same
terms: what the experts on other chips would add.

Departures from the published description (config.json of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16; the configuration's file
lists what no key settles under `assumed`) are comments where they
happen.  The pieces that are the same mathematics in every decoder
(a product, RMSNorm, SGD's leaf norms) are `reference/afmoe.py`'s.

`arith` is the hook of the control, as there: `arith.operand` on both
operands of every matrix product, and on the scan's `x`, `B`, `C`
(operands of the products the program's chunked form is made of);
`arith.result` on what the program holds in its compute type.  Time
steps, decays, the state, router scores, softmaxes, norms' statistics
and the update stay in float32, as the program's do.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.afmoe import (  # noqa: F401  (Exact, decays: offered)
    HIGHEST, Exact, _copy, _diff, _leaf_norms, _linear, _rms_norm, _short,
    _zeros, decays)

ARCH_KEYS = ("vocab_size", "seq_len", "hidden_size",
             "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
             "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "moe_intermediate_size", "moe_shared_expert_intermediate_size",
             "n_routed_experts_published", "num_experts_per_tok",
             "experts_held", "n_shared_experts", "norm_topk_prob",
             "routed_scaling_factor", "load_balance_coeff",
             "layer_norm_epsilon")
SCAN_BLOCK = 128    # tokens between two states the backward pass holds


# --------------------------------------------------------------- shapes
def arch_of(cfg):
    """What of a configuration's file shapes the net (`chunk_size` only
    the counts: nothing here is chunked)."""
    return {k: cfg[k] for k in ARCH_KEYS}


def _seq_len(rows, arch):
    """Rows that are no whole number of sequences are one shorter
    sequence (the control's half batch is the first half of one: under
    a causal model its tokens see what they saw in the whole)."""
    return arch["seq_len"] if rows % arch["seq_len"] == 0 else rows


def _mamba_widths(arch):
    d_inner = arch["mamba_num_heads"] * arch["mamba_head_dim"]
    return d_inner, arch["n_groups"] * arch["ssm_state_size"]


def param_shapes(arch):
    """(parameters, auxiliary state): name -> shape, by the names the
    program's builder (`mxnet_tpu/models/nemotron_h.py`) gives them.
    Every matrix is (out, in); the experts held are stacked on rows."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    nq, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    H = arch["mamba_num_heads"]
    d_inner, gn = _mamba_widths(arch)
    fe, E = arch["moe_intermediate_size"], arch["n_routed_experts_published"]
    held = arch["experts_held"][1]
    args, aux = {"embed_weight": (arch["vocab_size"], d)}, {}
    for i, kind in enumerate(arch["hybrid_override_pattern"]):
        p = "l%d_" % i
        args[p + "norm_gamma"] = (d,)
        if kind == "M":
            args[p + "in_weight"] = (2 * d_inner + 2 * gn + H, d)
            args[p + "conv_weight"] = (d_inner + 2 * gn, arch["conv_kernel"])
            args[p + "conv_bias"] = (d_inner + 2 * gn,)
            args[p + "A_log_weight"] = (H,)
            args[p + "dt_bias"] = (H,)
            args[p + "D_gamma"] = (H,)
            args[p + "ssm_norm_gamma"] = (d_inner,)
            args[p + "out_weight"] = (d, d_inner)
        elif kind == "*":
            args[p + "q_weight"] = (nq * hd, d)
            args[p + "k_weight"] = (nkv * hd, d)
            args[p + "v_weight"] = (nkv * hd, d)
            args[p + "o_weight"] = (d, nq * hd)
        elif kind == "E":
            args[p + "moe_router_weight"] = (E, d)
            args[p + "moe_experts_up_weight"] = (held * fe, d)
            args[p + "moe_experts_down_weight"] = (held * d, fe)
            aux[p + "moe_router_bias"] = (E,)
            if arch["n_shared_experts"]:
                fs = arch["moe_shared_expert_intermediate_size"] \
                    * arch["n_shared_experts"]
                args[p + "shared_up_weight"] = (fs, d)
                args[p + "shared_down_weight"] = (d, fs)
        else:
            raise ValueError("layer %d: %r is none of M, *, E" % (i, kind))
    args["final_norm_gamma"] = (d,)
    args["head_weight"] = (arch["vocab_size"], d)
    return args, aux


def products(arch):
    """The leaves that are an operand of a matrix product (the
    depthwise convolution's taps are none)."""
    return {k for k, shape in param_shapes(arch)[0].items()
            if len(shape) > 1 and k != "embed_weight"
            and not k.endswith("conv_weight")}


# -------------------------------------------------------------- Mamba-2
def _scan_tokens(x, dt, B, C, A, D):
    """The recurrence of one group's heads, token by token.  x
    (S, T, R, P); dt (S, T, R) positive; B, C (S, T, N), shared by the
    group's R heads; A, D (R,).  Tokens in blocks of `SCAN_BLOCK`, each
    block under a checkpoint, so that the backward pass holds one state
    a block and a block's own while it replays it: nothing of the
    mathematics changes."""
    S, T, R, P = x.shape
    N = B.shape[-1]
    block = next(b for b in range(min(SCAN_BLOCK, T), 0, -1) if T % b == 0)

    def token(state, t):
        x_t, dt_t, b_t, c_t = t             # (S, R, P) (S, R) (S, N) (S, N)
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1) \
            + D[:, None] * x_t

    @jax.checkpoint
    def run_block(state, ts):
        return lax.scan(token, state, ts)

    def blocks(t):      # (S, T, ...) -> (T / block, block, S, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((T // block, block) + t.shape[1:])

    _, y = lax.scan(run_block, jnp.zeros((S, R, P, N), jnp.float32),
                    (blocks(x), blocks(dt), blocks(B), blocks(C)))
    return jnp.moveaxis(y.reshape((T,) + y.shape[2:]), 0, 1)


def _mamba_group(t, S, T, P, eps, arith):
    """What one group of heads makes of its channels: the convolution
    over them, the scan of its heads, the gate and the norm over the
    group.  z (rows, R.P); xbc (rows, R.P + 2N) = the group's x, B, C."""
    z, xbc, dt, w, b, a_log, dt_bias, D, gamma = t
    K, R = w.shape[1], a_log.shape[0]
    # the causal depthwise convolution, a tap at a time
    xp = jnp.pad(xbc.reshape(S, T, -1), ((0, 0), (K - 1, 0), (0, 0)))
    conv = b + sum(w[:, j] * xp[:, j:j + T] for j in range(K))
    xbc = arith.operand(arith.result(jax.nn.silu(conv)))
    N = (xbc.shape[-1] - R * P) // 2
    x, B, C = jnp.split(xbc, [R * P, R * P + N], axis=2)
    # no clamp on dt: the config carries no time_step_limit, whose
    # default is (0, inf); time_step_min/max/floor shape only the
    # published start of dt_bias (assumed)
    dt = jax.nn.softplus(dt.reshape(S, T, R) + dt_bias)
    y = arith.result(_scan_tokens(x.reshape(S, T, R, P), dt, B, C,
                                  -jnp.exp(a_log), D))
    y = y.reshape(S * T, R * P) * jax.nn.silu(z)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return arith.result(y * gamma)


def _mamba(p, a, arch, arith):
    """The groups of heads in turn, each under a checkpoint (a group's
    heads share its B and C, the norm is over a group's channels, the
    convolution is a channel's own: no group reads another's), so that
    the backward pass fits beside the gradients: nothing of the
    mathematics changes."""
    T = _seq_len(a.shape[0], arch)
    H, P = arch["mamba_num_heads"], arch["mamba_head_dim"]
    G, N = arch["n_groups"], arch["ssm_state_size"]
    d_inner, gn = _mamba_widths(arch)
    rows = a.shape[0]
    proj = _linear(a, p["in_weight"], arith)

    def by_group(t, widths):
        """Channels (..., [x | B | C]) -> (G, ..., a group's x, B, C)."""
        x, B, C = jnp.split(t, [d_inner, d_inner + gn], axis=-1)
        parts = [v.reshape(v.shape[:-1] + (G, w))
                 for v, w in zip((x, B, C), widths)]
        return jnp.moveaxis(jnp.concatenate(parts, axis=-1), -2, 0)

    widths = (d_inner // G, N, N)
    heads = lambda v: v.reshape(G, H // G)  # noqa: E731
    stacks = (
        jnp.moveaxis(proj[:, :d_inner].reshape(rows, G, -1), 1, 0),
        by_group(proj[:, d_inner:2 * d_inner + 2 * gn], widths),
        jnp.moveaxis(proj[:, 2 * d_inner + 2 * gn:].reshape(rows, G, -1),
                     1, 0),
        by_group(p["conv_weight"].T, widths).transpose(0, 2, 1),
        by_group(p["conv_bias"], widths),
        heads(p["A_log_weight"]), heads(p["dt_bias"]), heads(p["D_gamma"]),
        p["ssm_norm_gamma"].reshape(G, -1))
    one = jax.checkpoint(functools.partial(
        _mamba_group, S=rows // T, T=T, P=P,
        eps=arch["layer_norm_epsilon"], arith=arith))
    y = jnp.moveaxis(lax.map(one, stacks), 0, 1).reshape(rows, d_inner)
    return _linear(y, p["out_weight"], arith)


# ------------------------------------------------------------ attention
def _attend(q, k, v, arith):
    """One sequence, one query head: q, k, v (T, D), causal."""
    T, D = k.shape
    s = jnp.einsum("qd,kd->qk", arith.operand(q), arith.operand(k),
                   precision=HIGHEST) / math.sqrt(D)
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return arith.result(jnp.einsum("qk,kd->qd", arith.operand(p),
                                   arith.operand(v), precision=HIGHEST))


def _attention(p, a, arch, arith):
    """No positions at all: the model's public modelling code applies
    no rotation in its attention layers (arXiv:2504.03624); the
    config's rope_theta and partial_rotary_factor are unread
    (assumed)."""
    T, hd = _seq_len(a.shape[0], arch), arch["head_dim"]
    nq, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    S = a.shape[0] // T

    def heads(w, n):        # (S * nkv, n / nkv, T, hd)
        t = _linear(a, w, arith).reshape(S, T, nkv, n // nkv, hd)
        return t.transpose(0, 2, 3, 1, 4).reshape(S * nkv, n // nkv, T, hd)

    # (sequence, key-value head) pairs in turn and the query heads of
    # each in turn, every one under a checkpoint: one head's T x T
    # scores at a time
    one = jax.checkpoint(functools.partial(_attend, arith=arith))
    o = lax.map(
        lambda g: lax.map(lambda q: one(q, g[1][0], g[2][0]), g[0]),
        (heads(p["q_weight"], nq), heads(p["k_weight"], nkv),
         heads(p["v_weight"], nkv)))
    o = o.reshape(S, nkv, nq // nkv, T, hd).transpose(0, 3, 1, 2, 4)
    return _linear(o.reshape(S * T, nq * hd), p["o_weight"], arith)


# -------------------------------------------------------------- experts
def _relu2_mlp(x, w_up, w_down, arith):
    up = _linear(x, w_up, arith)
    return _linear(arith.result(jnp.square(jnp.maximum(up, 0.0))), w_down,
                   arith)


def _route(m, w_router, bias, arch, arith):
    """(chosen (rows, k), weights (rows, k)) over ALL experts, scores in
    float32.  n_group = topk_group = 1: no limit by groups of experts."""
    logits = lax.dot_general(arith.operand(m), arith.operand(w_router),
                             (((1,), (1,)), ((), ())), precision=HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(s + lax.stop_gradient(bias)[None, :],
                          arch["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * arch["routed_scaling_factor"]


def _experts(p, m, bias, arch, arith):
    """shared(m) + the routed part that the experts held give, and the
    load of every expert (tokens that chose it).  Every expert held
    runs over every token and the tokens that did not choose it get
    weight 0: the same sum as sorting tokens to experts, written the
    plain way."""
    first, held = arch["experts_held"]
    d, fe = arch["hidden_size"], arch["moe_intermediate_size"]
    chosen, w = _route(m, p["moe_router_weight"], bias, arch, arith)
    ids = first + jnp.arange(held)
    w_tok = jnp.sum(jnp.where(chosen[None] == ids[:, None, None],
                              w[None], 0.0), axis=-1)        # (held, rows)

    @jax.checkpoint
    def one(wu, wd, wt):
        return wt[:, None] * _relu2_mlp(m, wu, wd, arith)

    # the running sum stays outside the checkpoint, which would hold
    # it once an expert for the backward pass
    out, _ = lax.scan(
        lambda acc, t: (acc + one(*t), None), jnp.zeros_like(m),
        (p["moe_experts_up_weight"].reshape(held, fe, d),
         p["moe_experts_down_weight"].reshape(held, d, fe), w_tok))
    if arch["n_shared_experts"]:
        out = arith.result(out + _relu2_mlp(
            m, p["shared_up_weight"], p["shared_down_weight"], arith))
    load = jnp.zeros((arch["n_routed_experts_published"],), jnp.float32).at[
        chosen.reshape(-1)].add(1.0)
    return out, lax.stop_gradient(load)


# ---------------------------------------------------------------- trunk
def _layer(h, p, bias, arch, kind, arith):
    a = _rms_norm(h, p["norm_gamma"], arch["layer_norm_epsilon"], arith)
    load = None
    if kind == "M":
        f = _mamba(p, a, arch, arith)
    elif kind == "*":
        f = _attention(p, a, arch, arith)
    else:
        f, load = _experts(p, a, bias, arch, arith)
    return arith.result(h + f), load


def trunk(params, aux, ids, arch, arith=Exact):
    """The residual stream after the last layer, and every expert
    layer's load; each layer under a checkpoint.  No embedding
    multiplier."""
    h = arith.result(arith.operand(params["embed_weight"])[ids])
    loads = {}
    for i, kind in enumerate(arch["hybrid_override_pattern"]):
        prefix = "l%d_" % i
        bias = aux.get(prefix + "moe_router_bias")
        h, load = jax.checkpoint(functools.partial(
            _layer, arch=arch, kind=kind, arith=arith))(
                h, _short(params, prefix), bias)
        if load is not None:
            loads[prefix + "moe_router_bias"] = load
    return h, loads


def _head(h, gamma, w_head, arch, arith):
    return _linear(_rms_norm(h, gamma, arch["layer_norm_epsilon"], arith),
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
def train_step(params, aux, mom, x, labels, arch, opt, arith):
    """One step.  Returns (loss, norms of the mean gradient by leaf,
    new params, new selection biases, new momentum).  The rate of the
    bias rule is no config key: 0.001 (arXiv:2412.19437 section 2.1.2;
    assumed)."""
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
        # caller's parameters have, its own outputs too (an input placed
        # another way compiles the step a second time)
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
