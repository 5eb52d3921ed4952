"""kimi_linear decoder (Moonshot Kimi-Linear family): a stack whose
layers mix by a gated delta rule (Kimi Delta Attention, KDA) or by
latent attention without any position signal (MLA), each followed by a
feed-forward, dense in the leading layers and sparse experts after;
trained on next-token cross-entropy.

A batch's rows are tokens: ``data`` and ``softmax_label`` are
``(sequences * seq_len,)`` ids, and every activation is
``(rows, width)``.  Every layer, residual stream ``h``::

    h = h + mixer(RMSNorm(h));  h = h + ffn(RMSNorm(h))

Layers are numbered from 1, as the published ``linear_attn_config``
numbers them.  The mixer of a layer in ``kda_layers``, H heads of d::

    q, k, v = silu(conv(a.Wq)), silu(conv(a.Wk)), silu(conv(a.Wv))
              causal depthwise convolutions of kernel 4, no bias
    o = GatedDeltaRule(q, k, v, (a.Wf_down).Wf_up, a.Wbeta; A_log, dt_bias)
    out = (RMSNorm per head(o) * sigmoid((a.Wg_down).Wg_up)).Wo

(``sym.GatedDeltaRule`` normalises q and k, scales q and makes the
decay and beta from the raw gate and the raw beta.)  The mixer of a
layer in ``full_attn_layers``, H heads, no rotation::

    q = a.Wq                            H x (nope + rope)
    c, kr = a.Wdkv                      kv_lora_rank | rope
    k_h, v_h = [RMSNorm(c).Wukv | kr]   per head nope | v, kr shared
    out = attention(q, k, v).Wo         keys nope + rope wide, values v

Feed-forwards: the first ``first_k_dense_replace`` layers
``(silu(a.Wgate) * a.Wup).Wdown``; the others ``shared(a) +
routed(a)``, routing top-k of sigmoid scores plus a selection bias,
weights renormalised and scaled (``sym.MoE``).

No embedding multiplier; after the last layer RMSNorm and an untied
head.  ``experts_held=(first, count)`` gives the symbol one chip's
share of an expert-parallel deployment, ``vocab_size`` the rows of the
vocabulary held here, as in :mod:`.afmoe`.

The delta rule's per-head parameters are variables named for the
initialisers' suffix rules: ``<layer>_A_log_weight`` (drawn like a
weight) and ``<layer>_dt_bias`` (zero).
"""
from .. import symbol as sym
from .afmoe import _gated_mlp, _linear


def get_symbol(vocab_size, seq_len, hidden_size=2304, num_hidden_layers=27,
               kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26),
               full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
               kda_num_heads=32, kda_head_dim=128, short_conv_kernel_size=4,
               chunk_size=64, num_attention_heads=32, kv_lora_rank=512,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
               intermediate_size=9216, first_k_dense_replace=1,
               moe_intermediate_size=1024, num_experts=256,
               num_experts_per_token=8, experts_held=None,
               num_shared_experts=1, moe_renormalize=True,
               routed_scaling_factor=2.446, load_balance_coeff=1e-3,
               rms_norm_eps=1e-5, remat=None, **kwargs):
    """The training symbol of ``num_hidden_layers`` layers; layer i
    (from 1) mixes by the list that names it.  ``remat`` names the
    step's recomputation policy for a Module built without one
    (``Module(remat=...)`` wins)."""
    if experts_held is None:
        experts_held = (0, num_experts)
    eps = rms_norm_eps
    kda_width = kda_num_heads * kda_head_dim
    key_dim = qk_nope_head_dim + qk_rope_head_dim

    def kda(a, p):
        def short_conv(x, name):
            return sym.Activation(
                sym.CausalConv1D(x, kernel=short_conv_kernel_size,
                                 seq_len=seq_len, no_bias=True,
                                 name=name + "_conv"),
                act_type="silu", name=name + "_conv_silu")

        def low_rank(name):
            return _linear(_linear(a, kda_head_dim, name + "_down"),
                           kda_width, name + "_up")

        q, k, v = (short_conv(_linear(a, kda_width, p + "_kda_" + n),
                              p + "_kda_" + n) for n in "qkv")
        o = sym.GatedDeltaRule(
            q, k, v, low_rank(p + "_kda_f"),
            _linear(a, kda_num_heads, p + "_kda_beta"),
            sym.Variable(p + "_A_log_weight"), sym.Variable(p + "_dt_bias"),
            heads=kda_num_heads, head_dim=kda_head_dim, chunk=chunk_size,
            seq_len=seq_len, name=p + "_kda")
        o = sym.RMSNorm(o, eps=eps, width=kda_head_dim,
                        name=p + "_kda_norm") \
            * sym.Activation(low_rank(p + "_kda_g"), act_type="sigmoid",
                             name=p + "_kda_norm_gate")
        return _linear(o, hidden_size, p + "_kda_o")

    def mla(a, p):
        down = _linear(a, kv_lora_rank + qk_rope_head_dim,
                       p + "_mla_kv_down")
        latent = sym.RMSNorm(
            sym.slice_axis(down, axis=1, begin=0, end=kv_lora_rank,
                           name=p + "_mla_latent"),
            eps=eps, name=p + "_mla_kv_norm")
        shared_key = sym.slice_axis(
            down, axis=1, begin=kv_lora_rank,
            end=kv_lora_rank + qk_rope_head_dim, name=p + "_mla_shared_key")
        kv = sym.LatentExpand(
            _linear(latent, num_attention_heads
                    * (qk_nope_head_dim + v_head_dim), p + "_mla_kv_up"),
            shared_key, num_heads=num_attention_heads,
            key_dim=qk_nope_head_dim, value_dim=v_head_dim,
            name=p + "_mla_kv")
        o = sym.GroupedQueryAttention(
            _linear(a, num_attention_heads * key_dim, p + "_mla_q"),
            kv[0], kv[1], num_heads=num_attention_heads,
            num_kv_heads=num_attention_heads, head_dim=key_dim,
            v_head_dim=v_head_dim, seq_len=seq_len, window=0, gated=False,
            name=p + "_mla")
        return _linear(o, hidden_size, p + "_mla_o")

    def experts(m, p):
        f = sym.MoE(m, num_experts=num_experts,
                    hidden_size=moe_intermediate_size,
                    num_experts_per_tok=num_experts_per_token,
                    experts_held=tuple(experts_held), score_func="sigmoid",
                    route_norm=moe_renormalize,
                    route_scale=routed_scaling_factor,
                    load_balance_coeff=load_balance_coeff,
                    name=p + "_moe")[0]
        if num_shared_experts:
            f = f + _gated_mlp(m, moe_intermediate_size * num_shared_experts,
                               hidden_size, p + "_shared")
        return f

    h = sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                      output_dim=hidden_size, name="embed")
    for i in range(1, num_hidden_layers + 1):
        if (i in kda_layers) == (i in full_attn_layers):
            raise ValueError("kimi_linear: layer %d is in both or in "
                             "neither of kda_layers %r and "
                             "full_attn_layers %r"
                             % (i, tuple(kda_layers),
                                tuple(full_attn_layers)))
        p = "l%d" % i
        mixer = kda if i in kda_layers else mla
        h = h + mixer(sym.RMSNorm(h, eps=eps, name=p + "_in_norm"), p)
        m = sym.RMSNorm(h, eps=eps, name=p + "_pre_mlp_norm")
        if i <= first_k_dense_replace:
            h = h + _gated_mlp(m, intermediate_size, hidden_size, p + "_mlp")
        else:
            h = h + experts(m, p)
    logits = _linear(sym.RMSNorm(h, eps=eps, name="final_norm"), vocab_size,
                     "head")
    net = sym.SoftmaxOutput(logits, sym.Variable("softmax_label"),
                            name="softmax")
    if remat is not None:
        net._set_attr(__remat__=str(remat))
    return net
