"""nemotron_h decoder (NVIDIA Nemotron-H / Nemotron-3 family): a stack
whose layers are each ONE mixer, chosen by a pattern string, trained on
next-token cross-entropy.

A batch's rows are tokens: ``data`` and ``softmax_label`` are
``(sequences * seq_len,)`` ids, and every activation is
``(rows, width)``.  Every layer, residual stream ``h``::

    h = h + mixer(RMSNorm(h))

with the mixer of its letter in ``hybrid_override_pattern``:

``M`` Mamba-2::

    z, xBC, dt = a.W_in                    widths d_inner | d_inner + 2GN | H
    xBC = silu(causal depthwise conv(xBC)) kernel conv_kernel, with bias
    x, B, C = xBC                          d_inner | G*N | G*N
    y = SSD(x, dt, B, C; A_log, dt_bias, D)     (``sym.SSD``)
    out = GatedRMSNorm(y, z; G groups).W_out

``*`` attention: causal grouped-query attention with no positions at
all and no gate, ``o = attention(a.Wq, a.Wk, a.Wv).Wo``.

``E`` experts: ``shared(a) + routed(a)``; an expert is the ungated
``relu(a.Wup)^2 .Wdown``; routing is top-k of sigmoid scores plus a
selection bias, weights normalised and scaled (``sym.MoE``).

No embedding multiplier; after the last layer RMSNorm and an untied
head.  ``experts_held=(first, count)`` gives the symbol one chip's
share of an expert-parallel deployment, ``vocab_size`` the rows of the
vocabulary held here, as in :mod:`.afmoe`.

The scan's per-head parameters are variables named for the
initialisers' suffix rules: ``<layer>_A_log_weight`` (drawn like a
weight), ``<layer>_dt_bias`` (zero), ``<layer>_D_gamma`` (one).
"""
from .. import symbol as sym


def _linear(x, width, name):
    return sym.FullyConnected(x, num_hidden=width, no_bias=True, name=name)


def _relu2_mlp(x, width, hidden_size, name):
    """relu(x.Wup)^2 .Wdown"""
    up = sym.Activation(_linear(x, width, name + "_up"), act_type="relu2",
                        name=name + "_relu2")
    return _linear(up, hidden_size, name + "_down")


def get_symbol(vocab_size, seq_len, hidden_size=2688,
               hybrid_override_pattern="MEMEMEM*E",
               mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
               ssm_state_size=128, conv_kernel=4, chunk_size=128,
               num_attention_heads=32, num_key_value_heads=2, head_dim=128,
               moe_intermediate_size=1856,
               moe_shared_expert_intermediate_size=3712,
               n_routed_experts=128, num_experts_per_tok=6,
               experts_held=None, n_shared_experts=1,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               load_balance_coeff=1e-3, layer_norm_epsilon=1e-5,
               remat=None, **kwargs):
    """The training symbol; one layer a letter of
    ``hybrid_override_pattern``.  ``remat`` names the step's
    recomputation policy for a Module built without one
    (``Module(remat=...)`` wins)."""
    if experts_held is None:
        experts_held = (0, n_routed_experts)
    eps = layer_norm_epsilon
    d_inner = mamba_num_heads * mamba_head_dim
    gn = n_groups * ssm_state_size

    def cut(x, lo, hi, name):
        return sym.slice_axis(x, axis=1, begin=lo, end=hi, name=name)

    def mamba(a, p):
        proj = _linear(a, 2 * d_inner + 2 * gn + mamba_num_heads, p + "_in")
        z = cut(proj, 0, d_inner, p + "_z")
        xbc = cut(proj, d_inner, 2 * d_inner + 2 * gn, p + "_xbc")
        dt = cut(proj, 2 * d_inner + 2 * gn,
                 2 * d_inner + 2 * gn + mamba_num_heads, p + "_dt")
        xbc = sym.Activation(
            sym.CausalConv1D(xbc, kernel=conv_kernel, seq_len=seq_len,
                             name=p + "_conv"),
            act_type="silu", name=p + "_conv_silu")
        y = sym.SSD(
            cut(xbc, 0, d_inner, p + "_x"), dt,
            cut(xbc, d_inner, d_inner + gn, p + "_b"),
            cut(xbc, d_inner + gn, d_inner + 2 * gn, p + "_c"),
            sym.Variable(p + "_A_log_weight"), sym.Variable(p + "_dt_bias"),
            sym.Variable(p + "_D_gamma"),
            heads=mamba_num_heads, head_dim=mamba_head_dim, groups=n_groups,
            state=ssm_state_size, chunk=chunk_size, seq_len=seq_len,
            name=p + "_ssd")
        y = sym.GatedRMSNorm(y, z, eps=eps, groups=n_groups,
                             name=p + "_ssm_norm")
        return _linear(y, hidden_size, p + "_out")

    def attention(a, p):
        o = sym.GroupedQueryAttention(
            _linear(a, num_attention_heads * head_dim, p + "_q"),
            _linear(a, num_key_value_heads * head_dim, p + "_k"),
            _linear(a, num_key_value_heads * head_dim, p + "_v"),
            num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
            head_dim=head_dim, seq_len=seq_len, window=0, gated=False,
            name=p + "_attn")
        return _linear(o, hidden_size, p + "_o")

    def experts(a, p):
        f = sym.MoE(a, num_experts=n_routed_experts,
                    hidden_size=moe_intermediate_size,
                    num_experts_per_tok=num_experts_per_tok,
                    experts_held=tuple(experts_held), score_func="sigmoid",
                    route_norm=norm_topk_prob,
                    route_scale=routed_scaling_factor,
                    load_balance_coeff=load_balance_coeff,
                    expert_act="relu2", gated=False, name=p + "_moe")[0]
        if n_shared_experts:
            f = f + _relu2_mlp(
                a, moe_shared_expert_intermediate_size * n_shared_experts,
                hidden_size, p + "_shared")
        return f

    mixers = {"M": mamba, "*": attention, "E": experts}
    h = sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                      output_dim=hidden_size, name="embed")
    for i, kind in enumerate(hybrid_override_pattern):
        if kind not in mixers:
            raise ValueError("nemotron_h: layer %d of pattern %r is none "
                             "of M, *, E" % (i, hybrid_override_pattern))
        p = "l%d" % i
        h = h + mixers[kind](sym.RMSNorm(h, eps=eps, name=p + "_norm"), p)
    logits = _linear(sym.RMSNorm(h, eps=eps, name="final_norm"), vocab_size,
                     "head")
    net = sym.SoftmaxOutput(logits, sym.Variable("softmax_label"),
                            name="softmax")
    if remat is not None:
        net._set_attr(__remat__=str(remat))
    return net
