"""Plain reference: the kimi_linear decoder (Moonshot Kimi-Linear
family), trained with SGD on next-token cross-entropy.

Residual stream `h` of width `hidden_size`; a batch's rows are tokens,
cut into sequences of `seq_len`.  Layers are numbered from 1, as the
published `linear_attn_config` numbers them.  Every layer:

    h = h + mixer(RMSNorm_in(h));  h = h + ffn(RMSNorm_pre_mlp(h))

    KDA mixer (layers in kda_layers), H heads of d, a = the normed h:
       q, k, v = silu(conv(a.Wq)), silu(conv(a.Wk)), silu(conv(a.Wv))
                 conv_t = sum_j w[:, j] * x_{t-3+j}, depthwise, zero before
                 the sequence's first token, never across sequences, no bias
       q = q / sqrt(sum_head q^2 + 1e-6) * d^-1/2
       k = k / sqrt(sum_head k^2 + 1e-6)
       g_t = -exp(A_log[h]) * softplus((a.Wf_down).Wf_up + dt_bias)   <= 0
       beta_t = sigmoid(a.Wbeta)                               one a head
       S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
             S_0 = 0, per head, d x d
       o_t = S_t^T q_t
       out = (RMSNorm over each head's d, one scale of d for all heads)(o)
             * sigmoid((a.Wg_down).Wg_up);  mixer = out.Wo
    MLA mixer (layers in full_attn_layers), H heads, NO position signal:
       q = a.Wq                          per head nope + rope wide
       [c | kr] = a.Wdkv                 kv_lora_rank | rope
       [kn_h | v_h] = RMSNorm(c).Wukv    per head nope | v
       k_h = [kn_h | kr]                 kr shared by all heads
       P = softmax(q_h.k_h^T / sqrt(nope + rope) + causal mask)
       mixer = concat_h(P.v_h).Wo
    ffn, the first first_k_dense_replace layers:
       (silu(m.Wgate) * (m.Wup)).Wdown
    ffn, the others: s = sigmoid(m.Wr); S = top-k of (s + b);
       w_e = scale * s_e / sum_{e' in S} s_e'
       f = shared(m) + sum_{e in S, e held} w_e * expert_e(m), gated SiLU
    input: the embedding's row; output: RMSNorm_final, an untied head
    after each step, not by gradient:
       b_e += coeff * sign(mean(load) - load_e), load over all experts

Straight `jax.numpy`, float32, matmul precision `highest`.  It imports
nothing of the program and is given nothing the program made.  **The
delta rule is the recurrence as written, token by token**: the program
computes it chunked (a triangular solve a chunk and a carry of matrix
products), so the two sides share no algorithm.  It is given the same
`experts_held` and the same slice of the vocabulary as the program and
leaves out the same terms: what the experts on other chips would add.

Departures from the published description (config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct and arXiv:2510.26692; the
configuration's file lists what no key settles under `assumed`) are
comments where they happen.  The pieces that are the same mathematics
in every decoder (a product, RMSNorm, the gated feed-forward, the
router and the experts held, SGD's leaf norms) are
`reference/afmoe.py`'s.  To fit one chip beside its own state the
token loop runs in blocks under `jax.checkpoint`, attention a head at
a time, each layer and the head under a checkpoint: nothing of the
mathematics changes.

`arith` is the hook of the control, as there: `arith.operand` on both
operands of every matrix product, and on the delta rule's `v`
(operand of the products the program's chunked form is made of);
`arith.result` on what the program holds in its compute type.  Gates,
decays, the normalised q and k, the state, router scores, softmaxes,
norms' statistics and the update stay in float32, as the program's do.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import afmoe
from benchmark.reference.afmoe import (  # noqa: F401  (Exact, decays: offered)
    HIGHEST, Exact, _copy, _diff, _gated_mlp, _leaf_norms, _linear,
    _rms_norm, _short, _zeros, decays)

# keys of the file's top level, then those of its linear_attn_config
ARCH_KEYS = ("vocab_size", "seq_len", "hidden_size", "num_hidden_layers",
             "chunk_size", "num_attention_heads", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "intermediate_size", "first_k_dense_replace",
             "moe_intermediate_size", "num_experts_published",
             "num_experts_per_token", "experts_held", "num_shared_experts",
             "moe_renormalize", "routed_scaling_factor",
             "load_balance_coeff", "rms_norm_eps")
LINEAR_KEYS = {"kda_layers": "kda_layers",
               "full_attn_layers": "full_attn_layers",
               "kda_num_heads": "num_heads", "kda_head_dim": "head_dim",
               "short_conv_kernel_size": "short_conv_kernel_size"}
TOKEN_BLOCK = 128   # tokens between two states the backward pass holds
KDA_GROUPS = 4      # groups of heads the delta-rule layer runs in turn
L2_EPS = 1e-6


# --------------------------------------------------------------- shapes
def arch_of(cfg):
    """What of a configuration's file shapes the net (`chunk_size` only
    the counts: nothing here is chunked)."""
    arch = {k: cfg[k] for k in ARCH_KEYS}
    arch.update({k: cfg["linear_attn_config"][theirs]
                 for k, theirs in LINEAR_KEYS.items()})
    return arch


def _seq_len(rows, arch):
    """Rows that are no whole number of sequences are one shorter
    sequence (the control's half batch is the first half of one: under
    a causal model its tokens see what they saw in the whole)."""
    return arch["seq_len"] if rows % arch["seq_len"] == 0 else rows


def _layers(arch):
    """[(number from 1, "kda" or "mla", dense feed-forward or not)]."""
    return [(i, "kda" if i in arch["kda_layers"] else "mla",
             i <= arch["first_k_dense_replace"])
            for i in range(1, arch["num_hidden_layers"] + 1)]


def param_shapes(arch):
    """(parameters, auxiliary state): name -> shape, by the names the
    program's builder (`mxnet_tpu/models/kimi_linear.py`) gives them.
    Every matrix is (out, in); the experts held are stacked on rows."""
    d, E = arch["hidden_size"], arch["num_experts_published"]
    H, dh = arch["kda_num_heads"], arch["kda_head_dim"]
    nh, lora = arch["num_attention_heads"], arch["kv_lora_rank"]
    nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    vd, f, fe = arch["v_head_dim"], arch["intermediate_size"], \
        arch["moe_intermediate_size"]
    held = arch["experts_held"][1]
    args, aux = {"embed_weight": (arch["vocab_size"], d)}, {}
    for i, mixer, dense in _layers(arch):
        p = "l%d_" % i
        args[p + "in_norm_gamma"] = (d,)
        if mixer == "kda":
            for n in "qkv":
                args[p + "kda_%s_weight" % n] = (H * dh, d)
                args[p + "kda_%s_conv_weight" % n] = (
                    H * dh, arch["short_conv_kernel_size"])
            for n in "fg":
                args[p + "kda_%s_down_weight" % n] = (dh, d)
                args[p + "kda_%s_up_weight" % n] = (H * dh, dh)
            args[p + "kda_beta_weight"] = (H, d)
            args[p + "A_log_weight"] = (H,)
            args[p + "dt_bias"] = (H * dh,)
            args[p + "kda_norm_gamma"] = (dh,)
            args[p + "kda_o_weight"] = (d, H * dh)
        else:
            args[p + "mla_q_weight"] = (nh * (nope + rope), d)
            args[p + "mla_kv_down_weight"] = (lora + rope, d)
            args[p + "mla_kv_norm_gamma"] = (lora,)
            args[p + "mla_kv_up_weight"] = (nh * (nope + vd), lora)
            args[p + "mla_o_weight"] = (d, nh * vd)
        args[p + "pre_mlp_norm_gamma"] = (d,)
        if dense:
            args[p + "mlp_gate_weight"] = (f, d)
            args[p + "mlp_up_weight"] = (f, d)
            args[p + "mlp_down_weight"] = (d, f)
            continue
        args[p + "moe_router_weight"] = (E, d)
        args[p + "moe_experts_gate_weight"] = (held * fe, d)
        args[p + "moe_experts_up_weight"] = (held * fe, d)
        args[p + "moe_experts_down_weight"] = (held * d, fe)
        aux[p + "moe_router_bias"] = (E,)
        if arch["num_shared_experts"]:
            fs = fe * arch["num_shared_experts"]
            args[p + "shared_gate_weight"] = (fs, d)
            args[p + "shared_up_weight"] = (fs, d)
            args[p + "shared_down_weight"] = (d, fs)
    args["final_norm_gamma"] = (d,)
    args["head_weight"] = (arch["vocab_size"], d)
    return args, aux


def products(arch):
    """The leaves that are an operand of a matrix product (the
    depthwise convolutions' taps are none)."""
    return {k for k, shape in param_shapes(arch)[0].items()
            if len(shape) > 1 and k != "embed_weight"
            and not k.endswith("conv_weight")}


# ------------------------------------------------------------------ KDA
def _short_conv(x, w, S, T, arith):
    """silu of the causal depthwise convolution of x (rows, C) with
    taps w (C, K), a tap at a time; no bias (assumed: the family's
    public modelling code).  Returns (S, T, C)."""
    K = w.shape[1]
    xp = jnp.pad(x.reshape(S, T, -1), ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(w[:, j] * xp[:, j:j + T] for j in range(K))
    return arith.result(jax.nn.silu(conv))


def _delta_tokens(q, k, v, g, beta):
    """The recurrence token by token.  q, k, g (S, T, H, d); v
    (S, T, H, dv); beta (S, T, H).  Tokens in blocks of `TOKEN_BLOCK`,
    each block under a checkpoint, so that the backward pass holds one
    state a block and a block's own while it replays it."""
    S, T, H, d = k.shape
    block = next(b for b in range(min(TOKEN_BLOCK, T), 0, -1) if T % b == 0)

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t   # (S, H, d) .. (S, H, dv) .. (S, H)
        state = jnp.exp(g_t)[..., None] * state
        # what the decayed state already answers for this key
        seen = jnp.einsum("shde,shd->she", state, k_t, precision=HIGHEST)
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("shde,shd->she", state, q_t,
                                 precision=HIGHEST)

    @jax.checkpoint
    def run_block(state, ts):
        return lax.scan(token, state, ts)

    def blocks(t):      # (S, T, ...) -> (T / block, block, S, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((T // block, block) + t.shape[1:])

    _, o = lax.scan(run_block,
                    jnp.zeros((S, H, d, v.shape[-1]), jnp.float32),
                    tuple(blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def _kda_heads(t, a, f_down, g_down, gamma, S, T, d, eps, arith):
    """What a group of R heads makes of the layer's input `a`: its rows
    of the five projections, the three short convolutions, the norms of
    q and k, the decay, the recurrence, the norm of each head's output
    and its gate.  Wq, Wk, Wv (R.d, hidden); the low-rank gates' second
    matrices (R.d, d) on `f_down`, `g_down` (rows, d); Wbeta
    (R, hidden); the taps (R.d, K) each; A_log (R,), dt_bias (R.d,);
    gamma (d,)."""
    wq, wk, wv, wf_up, wg_up, wbeta, cq, ck, cv, a_log, dt_bias = t
    R = a_log.shape[0]

    def heads(x):
        return x.reshape(S, T, R, -1)

    def unit(x):    # length 1 over each head (assumed: eps 1e-6 inside)
        return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)

    q, k, v = (heads(_short_conv(_linear(a, w, arith), c, S, T, arith))
               for w, c in ((wq, cq), (wk, ck), (wv, cv)))
    # q scaled by d^-1/2 (assumed)
    q, k = unit(q) * d ** -0.5, unit(k)
    # one A_log a head, one dt_bias a channel, no other bias (assumed)
    g = -jnp.exp(a_log)[:, None] * heads(jax.nn.softplus(
        _linear(f_down, wf_up, arith) + dt_bias))
    beta = jax.nn.sigmoid(_linear(a, wbeta, arith)).reshape(S, T, R)
    o = arith.result(_delta_tokens(q, k, arith.operand(v), g, beta))
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) \
        * gamma
    return arith.result(o.reshape(S * T, R * d)
                        * jax.nn.sigmoid(_linear(g_down, wg_up, arith)))


def _kda(p, a, arch, arith):
    """The heads in `KDA_GROUPS` groups in turn, each with its rows of
    the projections and each under a checkpoint (a head reads no other:
    a row of a projection is a channel's own, the convolutions too, the
    norms a head's), so that the backward pass fits beside the
    gradients: nothing of the mathematics changes."""
    T = _seq_len(a.shape[0], arch)
    S, rows = a.shape[0] // T, a.shape[0]
    H, d = arch["kda_num_heads"], arch["kda_head_dim"]
    G = math.gcd(KDA_GROUPS, H)
    names = ["kda_%s_weight" % n for n in "qkv"] \
        + ["kda_f_up_weight", "kda_g_up_weight", "kda_beta_weight"] \
        + ["kda_%s_conv_weight" % n for n in "qkv"] \
        + ["A_log_weight", "dt_bias"]
    # (H.w, ...) -> (G, (H/G).w, ...): a group's heads are neighbours
    stacks = tuple(p[n].reshape((G, -1) + p[n].shape[1:]) for n in names)
    one = jax.checkpoint(functools.partial(
        _kda_heads, S=S, T=T, d=d, eps=arch["rms_norm_eps"], arith=arith))
    f_down = _linear(a, p["kda_f_down_weight"], arith)
    g_down = _linear(a, p["kda_g_down_weight"], arith)
    o = lax.map(lambda t: one(t, a, f_down, g_down, p["kda_norm_gamma"]),
                stacks)
    return _linear(jnp.moveaxis(o, 0, 1).reshape(rows, H * d),
                   p["kda_o_weight"], arith)


# ------------------------------------------------------------------ MLA
def _attend(q, k, v, arith):
    """One sequence, one head: q, k (T, D), v (T, Dv), causal."""
    T, D = k.shape
    s = jnp.einsum("qd,kd->qk", arith.operand(q), arith.operand(k),
                   precision=HIGHEST) / math.sqrt(D)
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return arith.result(jnp.einsum("qk,kd->qd", arith.operand(p),
                                   arith.operand(v), precision=HIGHEST))


def _mla(p, a, arch, arith):
    """No rotation and no other position signal (mla_use_nope); the 64
    columns of a key that all heads share come straight from the
    down-projection, un-normed; the latent's 512 are normed."""
    T = _seq_len(a.shape[0], arch)
    S, rows = a.shape[0] // T, a.shape[0]
    nh, lora = arch["num_attention_heads"], arch["kv_lora_rank"]
    nope, vd = arch["qk_nope_head_dim"], arch["v_head_dim"]

    def heads(t):       # (rows, nh * w) -> (S * nh, T, w)
        t = t.reshape(S, T, nh, -1).transpose(0, 2, 1, 3)
        return t.reshape((S * nh, T) + t.shape[3:])

    q = heads(_linear(a, p["mla_q_weight"], arith))
    down = _linear(a, p["mla_kv_down_weight"], arith)
    latent = _rms_norm(down[:, :lora], p["mla_kv_norm_gamma"],
                       arch["rms_norm_eps"], arith)
    up = _linear(latent, p["mla_kv_up_weight"], arith) \
        .reshape(rows, nh, nope + vd)
    shared = jnp.broadcast_to(down[:, None, lora:],
                              (rows, nh, down.shape[1] - lora))
    k = heads(jnp.concatenate([up[..., :nope], shared], -1)
              .reshape(rows, -1))
    v = heads(up[..., nope:].reshape(rows, -1))
    # (sequence, head) pairs in turn, each under a checkpoint: one
    # head's T x T scores at a time
    one = jax.checkpoint(functools.partial(_attend, arith=arith))
    o = lax.map(lambda t: one(*t), (q, k, v))
    o = o.reshape(S, nh, T, vd).transpose(0, 2, 1, 3).reshape(rows, nh * vd)
    return _linear(o, p["mla_o_weight"], arith)


# -------------------------------------------------------------- experts
def _experts_arch(arch):
    """The keys `reference/afmoe.py`'s router and experts read."""
    return {"experts_held": arch["experts_held"],
            "hidden_size": arch["hidden_size"],
            "moe_intermediate_size": arch["moe_intermediate_size"],
            "num_experts_published": arch["num_experts_published"],
            "num_experts_per_tok": arch["num_experts_per_token"],
            # moe_router_activation_func sigmoid; num_expert_group =
            # topk_group = 1: no limit by groups of experts
            "score_func": "sigmoid", "route_norm": arch["moe_renormalize"],
            "route_scale": arch["routed_scaling_factor"]}


# ---------------------------------------------------------------- trunk
def _layer(h, p, bias, arch, mixer, dense, arith):
    eps = arch["rms_norm_eps"]
    a = _rms_norm(h, p["in_norm_gamma"], eps, arith)
    h = arith.result(h + (_kda if mixer == "kda" else _mla)(
        p, a, arch, arith))
    m = _rms_norm(h, p["pre_mlp_norm_gamma"], eps, arith)
    if dense:
        f, load = _gated_mlp(m, p["mlp_gate_weight"], p["mlp_up_weight"],
                             p["mlp_down_weight"], arith), None
    else:
        f, load = afmoe._experts(p, m, bias, _experts_arch(arch), arith)
        if arch["num_shared_experts"]:
            f = arith.result(f + _gated_mlp(
                m, p["shared_gate_weight"], p["shared_up_weight"],
                p["shared_down_weight"], arith))
    return arith.result(h + f), load


def trunk(params, aux, ids, arch, arith=Exact):
    """The residual stream after the last layer, and every expert
    layer's load; each layer under a checkpoint.  No embedding
    multiplier."""
    h = arith.result(arith.operand(params["embed_weight"])[ids])
    loads = {}
    for i, mixer, dense in _layers(arch):
        prefix = "l%d_" % i
        bias = aux.get(prefix + "moe_router_bias")
        h, load = jax.checkpoint(functools.partial(
            _layer, arch=arch, mixer=mixer, dense=dense, arith=arith))(
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
