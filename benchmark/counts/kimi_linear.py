"""Operations and least bytes of one kimi_linear training step, from
shapes alone.  A row of the batch is a token, so what the harness calls
"an image" is a token here.

Both are lower bounds of what any schedule of the step must do, so a
share of a peak worked out from them cannot pass 100 %:

* operations: 2 per multiply-add of every matrix product, times 3 (the
  forward product and the two backward products).  Latent attention is
  counted under its causal mask: a query sees (T + 1) / 2 keys, never
  T, keys `qk_nope_head_dim + qk_rope_head_dim` wide and values
  `v_head_dim`.  The routed experts are counted at even routing: every
  token gives `num_experts_per_token * held / num_experts` pairs to
  the experts held (the program's counter `moe.held_pairs` says what a
  run really computed).  The gated delta rule is counted as the
  products of its chunked form (`kda_macs`): the decayed inner products
  Akk and Aqk under their causal halves ((Q - 1) / 2 and (Q + 1) / 2 of
  a chunk's Q columns a row), the unit-triangular solve as forward
  substitution ((Q - 1) / 2 rows of d + dv a row), and the four
  products with the state and with the corrected values whole.  The
  embedding's lookup, the convolutions, norms, gates, softplus,
  exponentials, the running sums, softmaxes, the sort by expert and the
  update are left out, and so is everything a schedule recomputes.
* bytes: each parameter read and written once, its momentum read and
  written once, its gradient written once (float32); the ids and labels
  read once; the output of every matrix product and of every delta rule
  written once in the forward pass and read once in the backward pass,
  and its gradient written and read once (compute type; attention's
  scores and the delta rule's inner products, solved systems and states
  never touch memory and are not counted); the probabilities written
  once (float32).

`kda_flops`, `kda_least_bytes` and `mla_attention_flops` give one
mixer's own work, so that the roofline of whatever computes it (XLA's
fusions and the library's attention kernel today, kernels of the
repository's own later) reads the same numerator.
"""
BYTES = {"float32": 4, "bfloat16": 2}


def _kda_width(arch):
    return arch["kda_num_heads"] * arch["kda_head_dim"]


def kda_macs(arch):
    """Multiply-adds of one delta-rule layer's recurrence for one
    token, forward (values as wide as keys)."""
    d = arch["kda_head_dim"]
    Q = min(arch["chunk_size"], arch["seq_len"])
    below, upto = (Q - 1) / 2.0, (Q + 1) / 2.0
    head = d * below + d * upto         # Akk, Aqk
    head += below * 2 * d               # the solve for [W | U]
    head += 3 * d * d                   # W S, K^T delta, Q S
    head += upto * d                    # Aqk delta
    return arch["kda_num_heads"] * head


def kda_flops(arch, tokens):
    """Forward + backward operations of one delta rule over `tokens`."""
    return 3 * 2 * kda_macs(arch) * tokens


def kda_least_bytes(arch, tokens, compute_dtype):
    """Least HBM bytes of one delta rule, forward and backward: q, k,
    v, the gate and beta read in each pass and their gradients written,
    o written and its gradient read."""
    inputs = 4 * _kda_width(arch) + arch["kda_num_heads"]
    return tokens * (3 * inputs + 2 * _kda_width(arch)) * BYTES[compute_dtype]


def mla_attention_macs(arch):
    """Multiply-adds of latent attention's two products for one token,
    forward, under the causal mask."""
    keys = (arch["seq_len"] + 1) / 2.0
    return keys * arch["num_attention_heads"] * (
        arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
        + arch["v_head_dim"])


def mla_attention_flops(arch, tokens):
    """Forward + backward operations of one latent-attention layer's
    scores and values over `tokens`."""
    return 3 * 2 * mla_attention_macs(arch) * tokens


def _layers(arch):
    return [(i, "kda" if i in arch["kda_layers"] else "mla",
             i <= arch["first_k_dense_replace"])
            for i in range(1, arch["num_hidden_layers"] + 1)]


def layer_products(arch, mixer, dense):
    """Matrix products of a layer for one token: [(name, multiply-adds,
    output elements, weight elements)]; attention's scores and the
    delta rule's inner products have no output in memory."""
    d = arch["hidden_size"]

    def product(name, n_in, n_out):
        return (name, n_in * n_out, n_out, n_in * n_out)

    if mixer == "kda":
        wide, dh = _kda_width(arch), arch["kda_head_dim"]
        out = [product("kda_" + n, d, wide) for n in "qkv"]
        for n in "fg":
            out += [product("kda_%s_down" % n, d, dh),
                    product("kda_%s_up" % n, dh, wide)]
        out += [product("kda_beta", d, arch["kda_num_heads"]),
                ("kda", kda_macs(arch), wide, 0),
                product("kda_o", wide, d)]
    else:
        nh, lora = arch["num_attention_heads"], arch["kv_lora_rank"]
        nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
        vd = arch["v_head_dim"]
        keys = (arch["seq_len"] + 1) / 2.0
        out = [product("mla_q", d, nh * (nope + rope)),
               product("mla_kv_down", d, lora + rope),
               product("mla_kv_up", lora, nh * (nope + vd)),
               ("mla_scores", keys * nh * (nope + rope), 0, 0),
               ("mla_values", keys * nh * vd, nh * vd, 0),
               product("mla_o", nh * vd, d)]

    def gated(name, width, share, weights):
        out.extend([(name + "_gate", share * d * width, share * width,
                     weights * d * width),
                    (name + "_up", share * d * width, share * width,
                     weights * d * width),
                    (name + "_down", share * width * d, share * d,
                     weights * width * d)])

    if dense:
        gated("mlp", arch["intermediate_size"], 1, 1)
        return out
    fe, E = arch["moe_intermediate_size"], arch["num_experts_published"]
    held = arch["experts_held"][1]
    out.append(product("moe_router", d, E))
    # pairs a token gives the experts held, at even routing
    gated("moe_experts", fe, arch["num_experts_per_token"] * held / float(E),
          held)
    if arch["num_shared_experts"]:
        gated("shared", fe * arch["num_shared_experts"], 1, 1)
    return out


def products(arch):
    out = []
    for i, mixer, dense in _layers(arch):
        out.extend(("l%d_%s" % (i, n), m, o, w)
                   for n, m, o, w in layer_products(arch, mixer, dense))
    d, V = arch["hidden_size"], arch["vocab_size"]
    out.append(("head", d * V, V, d * V))
    return out


def n_parameters(arch):
    """Every trained element: products' weights, the embedding, the
    norms' scales, the short convolutions' taps, the delta rules'
    per-head and per-channel parameters."""
    d = arch["hidden_size"]
    layers = _layers(arch)
    n_kda = sum(1 for _i, mixer, _d in layers if mixer == "kda")
    kda = 3 * _kda_width(arch) * arch["short_conv_kernel_size"] \
        + arch["kda_num_heads"] + _kda_width(arch) + arch["kda_head_dim"]
    return sum(p[3] for p in products(arch)) + arch["vocab_size"] * d \
        + (2 * len(layers) + 1) * d + n_kda * kda \
        + (len(layers) - n_kda) * arch["kv_lora_rank"]


def forward_macs_per_token(arch):
    return sum(p[1] for p in products(arch))


def train_flops_per_image(arch):
    """Forward + backward operations a training step requires for one
    row of the batch: a token."""
    return 3 * 2 * forward_macs_per_token(arch)


def held_pairs_per_step(arch, tokens):
    """Token-expert pairs the experts held compute in a step at even
    routing, over all expert layers."""
    expert_layers = sum(1 for _i, _m, dense in _layers(arch) if not dense)
    return tokens * arch["num_experts_per_token"] * arch["experts_held"][1] \
        / float(arch["num_experts_published"]) * expert_layers


def train_least_bytes(arch, batch, compute_dtype):
    """Least HBM bytes of one step on one chip at `batch` tokens there."""
    act = BYTES[compute_dtype]
    state = n_parameters(arch) * 4 * 5      # w r+w, momentum r+w, grad w
    inputs = batch * 2 * 4
    saved = batch * sum(p[2] for p in products(arch)) * act * 4
    probs = batch * arch["vocab_size"] * 4
    return state + inputs + saved + probs


def step_bounds(arch, batch, compute_dtype, peaks):
    """Least seconds one chip needs for a step of `batch` tokens: by
    operations, by bytes, and which of the two binds."""
    t_ops = batch * train_flops_per_image(arch) / peaks["bf16_flops_per_s"]
    t_bytes = train_least_bytes(arch, batch, compute_dtype) \
        / peaks["hbm_bytes_per_s"]
    return {"ops_s": t_ops, "bytes_s": t_bytes,
            "bound": "operations" if t_ops >= t_bytes else "bytes"}
