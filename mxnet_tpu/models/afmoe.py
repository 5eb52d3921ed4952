"""afmoe decoder (Arcee Trinity family): gated window and full
attention, a leading dense layer, then expert layers with a shared
expert and top-k sigmoid routing; trained on next-token cross-entropy.

A batch's rows are tokens: ``data`` and ``softmax_label`` are
``(sequences * seq_len,)`` ids, and every activation is
``(rows, width)``.  One block, residual stream ``h``::

    a = RMSNorm(h);  q, k, v, g = a.Wq, a.Wk, a.Wv, a.Wg
    q, k = RMSNorm per head;  sliding layers: q, k = RoPE(q, k)
    o = attention(q, k, v) * sigmoid(g);  h = h + RMSNorm(o.Wo)
    m = RMSNorm(h);  f = dense MLP(m)  or  shared(m) + routed experts(m)
    h = h + RMSNorm(f)

``experts_held=(first, count)`` gives the symbol one chip's share of an
expert-parallel deployment: the router still scores all
``num_experts``, the layer computes the experts it holds (``sym.MoE``).
``vocab_size`` is likewise the rows of the vocabulary held here.
"""
import math

from .. import symbol as sym


def _linear(x, width, name):
    return sym.FullyConnected(x, num_hidden=width, no_bias=True, name=name)


def _gated_mlp(x, width, hidden_size, name):
    """(silu(x.Wgate) * (x.Wup)).Wdown"""
    gate = sym.Activation(_linear(x, width, name + "_gate"),
                          act_type="silu", name=name + "_silu")
    return _linear(gate * _linear(x, width, name + "_up"), hidden_size,
                   name + "_down")


def get_symbol(vocab_size, seq_len, hidden_size=2048,
               num_attention_heads=32, num_key_value_heads=4, head_dim=128,
               intermediate_size=6144, moe_intermediate_size=1024,
               num_experts=128, num_experts_per_tok=8, experts_held=None,
               num_shared_experts=1, num_dense_layers=1,
               layer_types=("sliding_attention", "sliding_attention",
                            "sliding_attention", "full_attention"),
               sliding_window=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
               score_func="sigmoid", route_norm=True, route_scale=2.826,
               load_balance_coeff=1e-3, remat=None, **kwargs):
    """The training symbol; ``layer_types`` lists every layer, the
    first ``num_dense_layers`` of them dense.  ``remat`` names the
    step's recomputation policy for a Module built without one
    (``Module(remat=...)`` wins)."""
    if experts_held is None:
        experts_held = (0, num_experts)
    eps = rms_norm_eps

    def norm(x, name, width=None):
        kw = {} if width is None else {"width": width}
        return sym.RMSNorm(x, eps=eps, name=name, **kw)

    h = sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                      output_dim=hidden_size, name="embed")
    h = h * math.sqrt(hidden_size)
    for i, kind in enumerate(layer_types):
        p = "l%d" % i
        a = norm(h, p + "_in_norm")
        q = norm(_linear(a, num_attention_heads * head_dim, p + "_q"),
                 p + "_q_norm", head_dim)
        k = norm(_linear(a, num_key_value_heads * head_dim, p + "_k"),
                 p + "_k_norm", head_dim)
        v = _linear(a, num_key_value_heads * head_dim, p + "_v")
        g = _linear(a, num_attention_heads * head_dim, p + "_gate")
        sliding = kind == "sliding_attention"
        if sliding:     # full layers carry no positions at all
            rope = dict(head_dim=head_dim, seq_len=seq_len, theta=rope_theta)
            q = sym.RoPE(q, name=p + "_q_rope", **rope)
            k = sym.RoPE(k, name=p + "_k_rope", **rope)
        o = sym.GroupedQueryAttention(
            q, k, v, g, num_heads=num_attention_heads,
            num_kv_heads=num_key_value_heads, head_dim=head_dim,
            seq_len=seq_len, window=sliding_window if sliding else 0,
            gated=True, name=p + "_attn")
        h = h + norm(_linear(o, hidden_size, p + "_o"), p + "_post_attn_norm")
        m = norm(h, p + "_pre_mlp_norm")
        if i < num_dense_layers:
            f = _gated_mlp(m, intermediate_size, hidden_size, p + "_mlp")
        else:
            f = sym.MoE(m, num_experts=num_experts,
                        hidden_size=moe_intermediate_size,
                        num_experts_per_tok=num_experts_per_tok,
                        experts_held=tuple(experts_held),
                        score_func=score_func, route_norm=route_norm,
                        route_scale=route_scale,
                        load_balance_coeff=load_balance_coeff,
                        name=p + "_moe")[0]
            if num_shared_experts:
                f = f + _gated_mlp(
                    m, moe_intermediate_size * num_shared_experts,
                    hidden_size, p + "_shared")
        h = h + norm(f, p + "_post_mlp_norm")
    logits = _linear(norm(h, "final_norm"), vocab_size, "head")
    net = sym.SoftmaxOutput(logits, sym.Variable("softmax_label"),
                            name="softmax")
    if remat is not None:
        net._set_attr(__remat__=str(remat))
    return net
