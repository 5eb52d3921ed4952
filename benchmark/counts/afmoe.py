"""Operations and least bytes of one afmoe training step, from shapes
alone.  A row of the batch is a token, so what the harness calls "an
image" is a token here.

Both are lower bounds of what any schedule of the step must do, so a
share of a peak worked out from them cannot pass 100 %:

* operations: 2 per multiply-add of every matrix product, times 3 (the
  forward product and the two backward products).  Attention is counted
  under its masks: a query sees `mean_keys` keys (causal: (T + 1) / 2;
  with a window W < T the older keys are dropped as well), never T.
  The routed experts are counted at even routing: every token gives
  `num_experts_per_tok * held / num_experts` pairs to the experts held
  (the program's counter `moe.held_pairs` says what a run really
  computed).  The embedding's lookup, norms, rotations, softmaxes, the
  sort by expert and the update are left out, and so is everything a
  schedule recomputes.
* bytes: each parameter read and written once, its momentum read and
  written once, its gradient written once (float32); the ids and labels
  read once; the output of every matrix product written once in the
  forward pass and read once in the backward pass, and its gradient
  written and read once (compute type; attention's scores never touch
  memory and are not counted); the probabilities written once
  (float32).
"""
BYTES = {"float32": 4, "bfloat16": 2}


def mean_keys(seq_len, window):
    """Keys a query sees, averaged over the positions of a sequence:
    causal, and with `window` > 0 no key `window` or more back."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    ramp = window * (window + 1) / 2.0          # positions 0 .. window-1
    return (ramp + (seq_len - window) * window) / seq_len


def layer_products(arch, i):
    """Matrix products of layer `i` for one token: [(name, multiply-adds,
    output elements, weight elements)]; attention's two products have
    no weights and their scores no output in memory."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    nq, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    fe = arch["moe_intermediate_size"]
    sliding = arch["layer_types"][i] == "sliding_attention"
    keys = mean_keys(arch["seq_len"],
                     arch["sliding_window"] if sliding else 0)
    out = [("q", d * nq * hd, nq * hd, d * nq * hd),
           ("k", d * nkv * hd, nkv * hd, d * nkv * hd),
           ("v", d * nkv * hd, nkv * hd, d * nkv * hd),
           ("gate", d * nq * hd, nq * hd, d * nq * hd),
           ("scores", keys * nq * hd, 0, 0),
           ("values", keys * nq * hd, nq * hd, 0),
           ("o", nq * hd * d, d, nq * hd * d)]

    def gated(name, width, share, weights):
        out.extend([(name + "_gate", share * d * width, share * width,
                     weights * d * width),
                    (name + "_up", share * d * width, share * width,
                     weights * d * width),
                    (name + "_down", share * width * d, share * d,
                     weights * width * d)])

    if i < arch["num_dense_layers"]:
        gated("mlp", arch["intermediate_size"], 1, 1)
        return out
    held = arch["experts_held"][1]
    E = arch["num_experts_published"]
    out.append(("router", d * E, E, d * E))
    # pairs a token gives the experts held, at even routing
    share = arch["num_experts_per_tok"] * held / float(E)
    gated("experts", fe, share, held)
    if arch["num_shared_experts"]:
        gated("shared", fe * arch["num_shared_experts"], 1, 1)
    return out


def products(arch):
    out = []
    for i in range(len(arch["layer_types"])):
        out.extend(("l%d_%s" % (i, n), m, o, w)
                   for n, m, o, w in layer_products(arch, i))
    d, V = arch["hidden_size"], arch["vocab_size"]
    out.append(("head", d * V, V, d * V))
    return out


def n_parameters(arch):
    """Every trained element: products' weights, the embedding, the
    norms' scales."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    layers = len(arch["layer_types"])
    norms = layers * (4 * d + 2 * hd) + d
    return sum(p[3] for p in products(arch)) \
        + arch["vocab_size"] * d + norms


def forward_macs_per_token(arch):
    return sum(p[1] for p in products(arch))


def train_flops_per_image(arch):
    """Forward + backward operations a training step requires for one
    row of the batch: a token."""
    return 3 * 2 * forward_macs_per_token(arch)


def held_pairs_per_step(arch, tokens):
    """Token-expert pairs the experts held compute in a step at even
    routing, over all expert layers."""
    expert_layers = len(arch["layer_types"]) - arch["num_dense_layers"]
    return tokens * arch["num_experts_per_tok"] * arch["experts_held"][1] \
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
