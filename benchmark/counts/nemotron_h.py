"""Operations and least bytes of one nemotron_h training step, from
shapes alone.  A row of the batch is a token, so what the harness calls
"an image" is a token here.

Both are lower bounds of what any schedule of the step must do, so a
share of a peak worked out from them cannot pass 100 %:

* operations: 2 per multiply-add of every matrix product, times 3 (the
  forward product and the two backward products).  Attention is counted
  under its causal mask: a query sees (T + 1) / 2 keys, never T.  The
  routed experts are counted at even routing: every token gives
  `num_experts_per_tok * held / n_routed_experts` pairs to the experts
  held (the program's counter `moe.held_pairs` says what a run really
  computed).  The selective scan is counted as the four products of its
  chunked form (`scan_macs`): C.B^T and the intra-chunk product under
  their causal half, (Q + 1) / 2 of a chunk's Q columns a row, the
  chunk states and the inter-chunk product whole.  The embedding's
  lookup, the convolution, norms, gates, softplus, exponentials,
  softmaxes, the sort by expert and the update are left out, and so is
  everything a schedule recomputes.
* bytes: each parameter read and written once, its momentum read and
  written once, its gradient written once (float32); the ids and labels
  read once; the output of every matrix product and of every scan
  written once in the forward pass and read once in the backward pass,
  and its gradient written and read once (compute type; attention's
  scores and the scan's decay matrices and states never touch memory
  and are not counted); the probabilities written once (float32).

`scan_flops` and `scan_least_bytes` give one scan's own work, so that
the roofline of whatever computes it (XLA's fusions today, a kernel
later) reads the same numerator.
"""
BYTES = {"float32": 4, "bfloat16": 2}


def _mamba_widths(arch):
    d_inner = arch["mamba_num_heads"] * arch["mamba_head_dim"]
    return d_inner, arch["n_groups"] * arch["ssm_state_size"]


def scan_macs(arch):
    """Multiply-adds of one scan for one token, forward."""
    d_inner, gn = _mamba_widths(arch)
    half = (min(arch["chunk_size"], arch["seq_len"]) + 1) / 2.0
    N = arch["ssm_state_size"]
    return gn * half + d_inner * half + 2 * d_inner * N


def scan_flops(arch, tokens):
    """Forward + backward operations of one scan over `tokens`."""
    return 3 * 2 * scan_macs(arch) * tokens


def scan_least_bytes(arch, tokens, compute_dtype):
    """Least HBM bytes of one scan, forward and backward: x, dt, B, C
    read in each pass and their gradients written, y written and its
    gradient read."""
    d_inner, gn = _mamba_widths(arch)
    inputs = d_inner + arch["mamba_num_heads"] + 2 * gn
    return tokens * (3 * inputs + 2 * d_inner) * BYTES[compute_dtype]


def layer_products(arch, kind):
    """Matrix products of a layer of `kind` for one token: [(name,
    multiply-adds, output elements, weight elements)]; attention's
    scores and the scan's inner products have no output in memory."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    nq, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    if kind == "M":
        d_inner, gn = _mamba_widths(arch)
        wide = 2 * d_inner + 2 * gn + arch["mamba_num_heads"]
        return [("in", d * wide, wide, d * wide),
                ("scan", scan_macs(arch), d_inner, 0),
                ("out", d_inner * d, d, d_inner * d)]
    if kind == "*":
        keys = (arch["seq_len"] + 1) / 2.0
        return [("q", d * nq * hd, nq * hd, d * nq * hd),
                ("k", d * nkv * hd, nkv * hd, d * nkv * hd),
                ("v", d * nkv * hd, nkv * hd, d * nkv * hd),
                ("scores", keys * nq * hd, 0, 0),
                ("values", keys * nq * hd, nq * hd, 0),
                ("o", nq * hd * d, d, nq * hd * d)]
    fe, E = arch["moe_intermediate_size"], arch["n_routed_experts_published"]
    held = arch["experts_held"][1]
    # pairs a token gives the experts held, at even routing
    share = arch["num_experts_per_tok"] * held / float(E)
    out = [("router", d * E, E, d * E),
           ("experts_up", share * d * fe, share * fe, held * d * fe),
           ("experts_down", share * fe * d, share * d, held * fe * d)]
    if arch["n_shared_experts"]:
        fs = arch["moe_shared_expert_intermediate_size"] \
            * arch["n_shared_experts"]
        out += [("shared_up", d * fs, fs, d * fs),
                ("shared_down", fs * d, d, fs * d)]
    return out


def products(arch):
    out = []
    for i, kind in enumerate(arch["hybrid_override_pattern"]):
        out.extend(("l%d_%s" % (i, n), m, o, w)
                   for n, m, o, w in layer_products(arch, kind))
    d, V = arch["hidden_size"], arch["vocab_size"]
    out.append(("head", d * V, V, d * V))
    return out


def n_parameters(arch):
    """Every trained element: products' weights, the embedding, the
    norms' scales, the convolutions' taps and biases, the scans'
    per-head parameters."""
    d = arch["hidden_size"]
    d_inner, gn = _mamba_widths(arch)
    pattern = arch["hybrid_override_pattern"]
    mamba = (d_inner + 2 * gn) * (arch["conv_kernel"] + 1) \
        + 3 * arch["mamba_num_heads"] + d_inner
    return sum(p[3] for p in products(arch)) + arch["vocab_size"] * d \
        + (len(pattern) + 1) * d + pattern.count("M") * mamba


def forward_macs_per_token(arch):
    return sum(p[1] for p in products(arch))


def train_flops_per_image(arch):
    """Forward + backward operations a training step requires for one
    row of the batch: a token."""
    return 3 * 2 * forward_macs_per_token(arch)


def held_pairs_per_step(arch, tokens):
    """Token-expert pairs the experts held compute in a step at even
    routing, over all expert layers."""
    return tokens * arch["num_experts_per_tok"] * arch["experts_held"][1] \
        / float(arch["n_routed_experts_published"]) \
        * arch["hybrid_override_pattern"].count("E")


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
