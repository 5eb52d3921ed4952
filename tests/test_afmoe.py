"""The decoder ops of models/afmoe.py at a size the CPU holds: the one
blockwise attention (window, positions, kernel path under the Pallas
interpreter), the top-k expert layer that is told which experts it
holds (shares add up, no token dropped), and the model through
``Module.fit`` with its counters."""
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.transformer import attention
from mxnet_tpu.parallel import expert_parallel as ep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# ------------------------------------------------------------ attention
def _dense_attention(q, k, v, causal, window):
    """T x T scores, the plain way."""
    H, G = q.shape[1], k.shape[1]
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    keep = jnp.ones((q.shape[2], k.shape[2]), bool)
    if causal:
        keep = keep & (j <= i)
    if window:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(B, H, G, T, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, H, T, D)),
            jax.random.normal(ks[1], (B, G, T, D)),
            jax.random.normal(ks[2], (B, G, T, D)))


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (True, 700), (False, 0)])
def test_blockwise_attention_is_dense_attention(causal, window):
    """Query blocks of 512 against the keys their mask leaves: values
    and gradients of the T x T form; the window shorter than the
    sequence, and longer (then it is plain causal)."""
    q, k, v = _qkv(2, 4, 2, 640, 16)
    got = attention(q, k, v, causal=causal, window=window)
    want = _dense_attention(q, k, v, causal, window if window < 640 else 0)
    np.testing.assert_allclose(got, want, atol=2e-5)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a)))
    g_got = jax.grad(loss(lambda *a: attention(
        *a, causal=causal, window=window)), (0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(lambda *a: _dense_attention(
        *a, causal, window if window < 640 else 0)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-4)


@pytest.mark.parametrize("window", [0, 100])
def test_kernel_path_is_the_blockwise_path(window, monkeypatch):
    """The TPU's splash-attention kernel under the Pallas interpreter:
    same values and gradients as the blockwise path."""
    from mxnet_tpu.ops import transformer
    q, k, v = _qkv(1, 4, 2, 256, 128, seed=3)

    def both(fn):
        want = fn()
        monkeypatch.setattr(transformer, "_INTERPRET", True)
        got = fn()
        monkeypatch.setattr(transformer, "_INTERPRET", False)
        return got, want
    got, want = both(lambda: attention(q, k, v, causal=True, window=window))
    assert not np.array_equal(got, want)        # two paths
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(*both(lambda: jax.grad(lambda *a: jnp.sum(jnp.square(
            attention(*a, causal=True, window=window))), (0, 1, 2))(q, k, v))):
        np.testing.assert_allclose(a, b, atol=5e-4)


def test_kernel_path_traces_more_than_once(monkeypatch):
    """Two programs that hold the kernel: it keeps nothing of the
    first trace (a cached kernel would leak its mask tables)."""
    from mxnet_tpu.ops import transformer
    monkeypatch.setattr(transformer, "_INTERPRET", True)
    q, k, v = _qkv(1, 2, 1, 128, 128, seed=4)
    f = lambda s: jax.jit(lambda *a: s * attention(  # noqa: E731
        *a, causal=True))
    np.testing.assert_allclose(f(2.0)(q, k, v), 2 * f(1.0)(q, k, v),
                               rtol=1e-6)


def test_local_attention_is_the_same_code():
    from mxnet_tpu.parallel.ring_attention import local_attention
    q, k, v = _qkv(1, 2, 2, 48, 8, seed=5)
    np.testing.assert_allclose(local_attention(q, k, v, causal=True),
                               _dense_attention(q, k, v, True, 0),
                               atol=2e-5)


def _gqa_symbol(T, window, rope):
    q, k, v, g = (mx.sym.Variable(n) for n in "qkvg")
    if rope:
        q = mx.sym.RoPE(q, head_dim=8, seq_len=T, theta=100.0)
        k = mx.sym.RoPE(k, head_dim=8, seq_len=T, theta=100.0)
    return mx.sym.GroupedQueryAttention(
        q, k, v, g, num_heads=4, num_kv_heads=2, head_dim=8, seq_len=T,
        window=window, gated=True)


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("rope", [False, True])
def test_attention_op_sliding_and_full_with_and_without_positions(window,
                                                                  rope):
    """The op on rows cut into sequences: against dense attention with
    the rotation and the gate written out."""
    T, B, H, G, D = 12, 2, 4, 2, 8
    rs = np.random.RandomState(1)
    vals = {"q": rs.randn(B * T, H * D), "k": rs.randn(B * T, G * D),
            "v": rs.randn(B * T, G * D), "g": rs.randn(B * T, H * D)}
    vals = {n: a.astype(np.float32) for n, a in vals.items()}
    ex = _gqa_symbol(T, window, rope).bind(
        mx.cpu(), {n: mx.nd.array(a) for n, a in vals.items()})
    got = ex.forward()[0].asnumpy()

    def heads(a, n):
        return jnp.asarray(a).reshape(B, T, n, D).transpose(0, 2, 1, 3)

    def rotate(x):
        if not rope:
            return x
        inv = 100.0 ** (-np.arange(0, D, 2) / D)
        ang = np.arange(T)[:, None] * inv[None, :]
        a, b = x[..., :D // 2], x[..., D // 2:]
        return jnp.concatenate([a * np.cos(ang) - b * np.sin(ang),
                                b * np.cos(ang) + a * np.sin(ang)], -1)

    o = _dense_attention(rotate(heads(vals["q"], H)),
                         rotate(heads(vals["k"], G)), heads(vals["v"], G),
                         True, window)
    want = o.transpose(0, 2, 1, 3).reshape(B * T, H * D) \
        * jax.nn.sigmoid(vals["g"])
    np.testing.assert_allclose(got, want, atol=2e-5)
    if rope and not window:
        # positions matter: without the rotation the result differs
        plain = _gqa_symbol(T, window, False).bind(
            mx.cpu(), {n: mx.nd.array(a) for n, a in vals.items()})
        assert np.abs(plain.forward()[0].asnumpy() - got).max() > 1e-3


def test_rms_norm_per_head_and_silu():
    x = np.random.RandomState(2).randn(6, 16).astype(np.float32)
    gamma = np.linspace(0.5, 1.5, 4).astype(np.float32)
    y = mx.sym.RMSNorm(mx.sym.Variable("x"), mx.sym.Variable("g"),
                       width=4, eps=1e-5).bind(
        mx.cpu(), {"x": mx.nd.array(x), "g": mx.nd.array(gamma)}
    ).forward()[0].asnumpy()
    xh = x.reshape(6, 4, 4)
    want = xh / np.sqrt((xh ** 2).mean(-1, keepdims=True) + 1e-5) * gamma
    np.testing.assert_allclose(y, want.reshape(6, 16), atol=1e-5)
    s = mx.sym.Activation(mx.sym.Variable("x"), act_type="silu").bind(
        mx.cpu(), {"x": mx.nd.array(x)}).forward()[0].asnumpy()
    np.testing.assert_allclose(s, x / (1 + np.exp(-x)), atol=1e-6)


# ----------------------------------------------------------------- MoE
E, K, D_MODEL, F_EXP = 8, 3, 16, 24


def _moe_weights(seed=0):
    rs = np.random.RandomState(seed)
    return {"router": rs.randn(E, D_MODEL).astype(np.float32),
            "gate": rs.randn(E, F_EXP, D_MODEL).astype(np.float32) * 0.3,
            "up": rs.randn(E, F_EXP, D_MODEL).astype(np.float32) * 0.3,
            "down": rs.randn(E, D_MODEL, F_EXP).astype(np.float32) * 0.3}


def _uncut_layer(x, w, bias, k=K):
    """The whole routed layer the plain way: every expert over every
    token, weight 0 where not chosen (as benchmark/reference/afmoe.py
    writes one share of it)."""
    s = jax.nn.sigmoid(x @ w["router"].T)
    _, chosen = jax.lax.top_k(s + bias[None, :], k)
    wt = jnp.take_along_axis(s, chosen, -1)
    wt = 2.5 * wt / jnp.sum(wt, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(E):
        w_tok = jnp.sum(jnp.where(chosen == e, wt, 0.0), -1)
        h = jax.nn.silu(x @ w["gate"][e].T) * (x @ w["up"][e].T)
        out = out + w_tok[:, None] * (h @ w["down"][e].T)
    return out


def _share(x, w, bias, first, count, k=K, name="moe"):
    """One chip's share through sym.MoE; returns (output, new bias)."""
    sym = mx.sym.MoE(mx.sym.Variable("data"), num_experts=E,
                     hidden_size=F_EXP, num_experts_per_tok=k,
                     experts_held=(first, count), score_func="sigmoid",
                     route_norm=True, route_scale=2.5,
                     load_balance_coeff=0.01, name=name)
    sl = slice(first, first + count)
    args = {"data": x, name + "_router_weight": w["router"],
            name + "_experts_gate_weight": w["gate"][sl].reshape(-1, D_MODEL),
            name + "_experts_up_weight": w["up"][sl].reshape(-1, D_MODEL),
            name + "_experts_down_weight": w["down"][sl].reshape(-1, F_EXP)}
    ex = sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in args.items()},
                  aux_states={name + "_router_bias": mx.nd.array(bias)})
    out = ex.forward(is_train=True)[0].asnumpy()
    return out, ex.aux_dict[name + "_router_bias"].asnumpy()


def test_the_shares_add_up():
    """8 experts held 2 a share over 4 shares: the four routed parts
    equal the uncut layer, to float32 rounding; and every share moves
    the selection bias alike (the rule sees all experts' load)."""
    x = np.random.RandomState(4).randn(40, D_MODEL).astype(np.float32)
    w = _moe_weights()
    bias = np.linspace(-0.05, 0.05, E).astype(np.float32)
    parts = [_share(x, w, bias, first, 2) for first in (0, 2, 4, 6)]
    total = sum(p[0] for p in parts)
    want = np.asarray(_uncut_layer(jnp.asarray(x), w, jnp.asarray(bias)))
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    for _out, new_bias in parts[1:]:
        np.testing.assert_array_equal(new_bias, parts[0][1])
    moved = parts[0][1] - bias
    assert np.all(np.isclose(np.abs(moved), 0.01, atol=1e-6)
                  | (moved == 0))
    assert np.any(moved > 0) and np.any(moved < 0)


def test_every_token_to_one_expert_is_not_dropped():
    """A bias that sends every token to expert 5 first: its share
    computes all of them (Switch would drop what passes the capacity)."""
    x = np.random.RandomState(6).randn(64, D_MODEL).astype(np.float32)
    w = _moe_weights(1)
    bias = np.zeros(E, np.float32)
    bias[5] = 10.0
    scores, chosen, weights = ep.route(
        jnp.asarray(x) @ w["router"].T, k=1, score_func="sigmoid",
        bias=jnp.asarray(bias), route_norm=True, route_scale=2.5)
    assert np.all(np.asarray(chosen) == 5)
    got, _ = _share(x, w, bias, 4, 2, k=1)
    want = np.asarray(_uncut_layer(jnp.asarray(x), w, jnp.asarray(bias), k=1))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and the held pairs are all 64: nothing left out
    xs = jnp.asarray(x)
    kn = lambda a: jnp.asarray(a[4:6]).transpose(0, 2, 1)  # noqa: E731
    _y, sizes, dropped = ep.held_experts_ffn(
        xs, chosen, weights, kn(w["gate"]), kn(w["up"]), kn(w["down"]), 4)
    assert list(np.asarray(sizes)) == [0, 64]
    assert int(dropped) == 0


def test_dropped_counts_the_pairs_the_fold_does_not_read_back(monkeypatch):
    """`moe.dropped` is counted from the indices the gathers use, not
    from the routing: with rows for half the worst case and every token
    choosing two experts held here, half the pairs find no row."""
    x = jnp.asarray(np.random.RandomState(7).randn(32, D_MODEL), jnp.float32)
    w = _moe_weights(2)
    bias = np.zeros(E, np.float32)
    bias[[4, 5]] = 10.0
    _s, chosen, weights = ep.route(x @ w["router"].T, k=2,
                                   score_func="sigmoid",
                                   bias=jnp.asarray(bias))
    kn = lambda a: jnp.asarray(a[4:6]).transpose(0, 2, 1)  # noqa: E731
    run = lambda: ep.held_experts_ffn(  # noqa: E731
        x, chosen, weights, kn(w["gate"]), kn(w["up"]), kn(w["down"]), 4)
    y_all, sizes, dropped = run()
    assert int(jnp.sum(sizes)) == 64 and int(dropped) == 0
    monkeypatch.setattr(ep, "_worst_case_rows", lambda T, k, held: T)
    y_half, _sizes, dropped = run()
    assert int(dropped) == 32
    assert not np.allclose(y_half, y_all, atol=1e-3)


def test_rows_and_back_are_each_others_transpose():
    """`take` and `fold` (gathers both ways) against the scatter-add
    they stand in for, values and gradients, with pairs that are not
    held and rows no pair sits in."""
    rs = np.random.RandomState(8)
    T, k, d, held = 12, 3, 5, 2
    chosen = jnp.asarray(rs.randint(0, 6, (T, k)))
    here = (chosen >= 1) & (chosen < 1 + held)
    key = jnp.where(here, chosen - 1, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rows = T * min(k, held)
    pos = jnp.minimum(jnp.argsort(order).reshape(T, k), rows - 1)
    token = order[:rows] // k
    live = (jnp.arange(rows) < jnp.sum(here))[:, None]
    take, fold = ep._rows_and_back()
    x = jnp.asarray(rs.randn(T, d).astype(np.float32))
    w = jnp.asarray(rs.rand(T, k).astype(np.float32)) * here
    mix = jnp.asarray(rs.randn(rows, d).astype(np.float32))

    def ours(x, w):
        return fold(jnp.where(live, take(x, token, pos, here * 1.0), 0) * mix,
                    token, pos, w)

    def plain(x, w):
        r = jnp.where(live, x[token], 0) * mix
        w_row = w.reshape(-1)[order[:rows]]
        return jnp.zeros((T, d)).at[token].add(r * w_row[:, None])

    np.testing.assert_allclose(ours(x, w), plain(x, w), rtol=1e-5, atol=1e-6)
    g = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), (0, 1))(x, w)  # noqa: E731
    for a, b in zip(g(ours), g(plain)):
        np.testing.assert_allclose(a * 1.0, b, rtol=1e-5, atol=1e-6)


def test_grouped_matmul_kernel_path_is_the_plain_path(monkeypatch):
    """The TPU's grouped-matmul kernel under the Pallas interpreter:
    values and both gradients of `lax.ragged_dot`, rows past the groups
    zero."""
    rs = np.random.RandomState(9)
    x = jnp.asarray(rs.randn(256, 128).astype(np.float32))
    w = jnp.asarray(rs.randn(3, 128, 128).astype(np.float32))
    sizes = jnp.asarray([100, 0, 90], jnp.int32)

    def both(fn):
        plain = fn()
        monkeypatch.setattr(ep, "_INTERPRET", True)
        kernel = fn()
        monkeypatch.setattr(ep, "_INTERPRET", False)
        return kernel, plain
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    calls, real = [], megablox.gmm
    monkeypatch.setattr(megablox, "gmm",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    a, b = both(lambda: ep._grouped_matmul(x, w, sizes))
    assert len(calls) == 1                      # two paths
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
    assert float(jnp.max(jnp.abs(a[190:]))) == 0.0
    for ga, gb in zip(*both(lambda: jax.grad(lambda x, w: jnp.sum(jnp.square(
            ep._grouped_matmul(x, w, sizes))), (0, 1))(x, w))):
        np.testing.assert_allclose(ga, gb, rtol=1e-3, atol=5e-2)


def test_switch_layer_is_the_router_at_k1_with_a_capacity():
    """top1_routing is `route` at k = 1 plus capacity buckets."""
    logits = jnp.asarray(np.random.RandomState(3).randn(16, 4), jnp.float32)
    dispatch, combine, aux = ep.top1_routing(logits, capacity=3)
    probs, chosen, gate = ep.route(logits, k=1)
    assert dispatch.shape == (16, 4, 3)
    kept = np.asarray(dispatch.sum((1, 2)))
    assert set(kept) <= {0.0, 1.0} and kept.sum() < 16   # some dropped
    np.testing.assert_allclose(
        np.asarray(combine.sum((1, 2))), kept * np.asarray(gate[:, 0]),
        rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(dispatch.sum(2).argmax(1))[kept > 0],
        np.asarray(chosen[:, 0])[kept > 0])
    assert float(aux) > 0


# --------------------------------------------------------------- model
TINY = dict(vocab_size=64, seq_len=16, hidden_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, intermediate_size=48,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            experts_held=(2, 4), sliding_window=8,
            layer_types=["sliding_attention", "sliding_attention",
                         "full_attention"])


def _fit(remat, compute_dtype=None, epochs=2, net=None):
    """`remat`: a name goes the model's way (`get_symbol(remat=)`); a
    jax policy callable, or any for a `net` of the caller's, the
    Module's."""
    from mxnet_tpu import models
    mx.random.seed(5)
    by_module = remat if callable(remat) or net is not None else None
    if net is None:
        net = models.get_symbol(
            "afmoe", remat=None if by_module else remat, **TINY)
    rs = np.random.RandomState(0)
    x = rs.randint(0, 64, (128,)).astype(np.float32)
    y = rs.randint(0, 64, (128,)).astype(np.float32)
    mod = mx.mod.Module(net, context=[mx.cpu(0)], compute_dtype=compute_dtype,
                        remat=by_module)
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=32), num_epoch=epochs,
            optimizer="sgd", eval_metric="acc",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier())
    return mod


def test_model_is_found_by_name_and_trains_with_its_counters():
    from mxnet_tpu import telemetry
    mod = _fit("full")
    assert mod._exec_group.remat == "full"      # the symbol named it
    counters = telemetry.last_fit()["counters"]
    # 8 steps x 2 expert layers x 32 tokens x 2 choices: the experts
    # held (4 of 8) get their share of the 1,024 pairs, none dropped
    assert counters["moe.dropped"] == 0
    assert 300 < counters["moe.held_pairs"] < 724
    assert counters["moe.load_max"] >= counters["moe.load_mean"] > 0
    aux = mod.get_params()[1]
    assert sorted(aux) == ["l1_moe_router_bias", "l2_moe_router_bias"]
    for b in aux.values():      # written back by the step, 8 times
        assert np.abs(b.asnumpy()).max() > 0
        assert np.abs(b.asnumpy()).max() <= 0.008 + 1e-6


@pytest.mark.parametrize("remat", [
    "full", "dots", jax.checkpoint_policies.nothing_saveable],
    ids=["full", "dots", "strict"])
def test_remat_changes_no_number(remat):
    """What a segment keeps is the value it would have made again: the
    last step's probabilities and the parameters after an epoch are
    those of the step that recomputes nothing."""
    a, b = _fit(None, epochs=1), _fit(remat, epochs=1)
    np.testing.assert_allclose(a.get_outputs()[0].asnumpy(),
                               b.get_outputs()[0].asnumpy(),
                               rtol=2e-4, atol=2e-6)
    pa, pb = a.get_params()[0], b.get_params()[0]
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-4, atol=2e-6)


def test_fit_counts_what_the_segments_keep_once_a_step():
    """`remat.kept_bytes` of the fit report: every step adds the bytes
    of the products and attention outputs named inside the wrapped
    segments (by shape); a module that does not recompute counts none."""
    from mxnet_tpu import telemetry
    rows, steps, f32 = 32, 4, 4
    # three layers of q, k, v, gate, o; the dense layer's gate, up,
    # down; two shared experts' (the head lies in the last segment)
    products = 3 * (32 + 16 + 16 + 32 + 32) + (48 + 48 + 32) \
        + 2 * (16 + 16 + 32)
    attention = 3 * (2 * 4 * 16 * 8)
    mod = _fit("full", epochs=1)
    assert mod._exec_group._remat_kept_bytes \
        == (rows * products + attention) * f32
    assert telemetry.last_fit()["counters"]["remat.kept_bytes"] \
        == steps * (rows * products + attention) * f32
    _fit(jax.checkpoint_policies.nothing_saveable, epochs=1)
    assert telemetry.last_fit()["counters"]["remat.kept_bytes"] == 0
    _fit(None, epochs=1)
    assert "remat.kept_bytes" not in telemetry.last_fit()["counters"]


def test_the_last_segment_is_not_wrapped_and_its_counters_arrive():
    """Four op nodes make two segments and the expert layer lies in the
    second, which runs outside any checkpoint: what it counts still
    reaches the fit report."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.executor import _build_eval_segmented
    h = mx.sym.Embedding(mx.sym.Variable("data"), input_dim=64,
                         output_dim=32, name="embed")
    h = mx.sym.RMSNorm(h, name="norm")
    h = mx.sym.MoE(h, num_experts=8, hidden_size=16, num_experts_per_tok=2,
                   experts_held=(2, 4), score_func="sigmoid",
                   name="moe")[0]
    net = mx.sym.SoftmaxOutput(h, mx.sym.Variable("softmax_label"),
                               name="softmax")
    assert [n.name for n in net._topo() if n.op is not None][2:] \
        == ["moe", "softmax"]
    shapes, _, aux_shapes = net.infer_shape(data=(32,), softmax_label=(32,))
    args = [jnp.zeros(s, jnp.float32) + 0.1 for s in shapes]
    auxs = [jnp.zeros(s, jnp.float32) for s in aux_shapes]
    ev, _ = _build_eval_segmented(net, "full")
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda v: jnp.sum(ev(
        v, auxs, jax.random.PRNGKey(0), True)[0][0])))(args))
    assert jaxpr.count("remat") + jaxpr.count("checkpoint") == 1

    _fit("full", epochs=1, net=net)
    counters = telemetry.last_fit()["counters"]
    assert 0 < counters["moe.held_pairs"] <= 4 * 32 * 2
    assert counters["moe.dropped"] == 0
    assert counters["remat.kept_bytes"] == 0    # embed, norm: nothing dear


def test_ids_survive_the_compute_type():
    """`data` indexes the embedding: it is not cast to bfloat16 (ids
    above 256 would land on their neighbours)."""
    from mxnet_tpu.module.mesh_executor_group import _index_inputs
    from mxnet_tpu import models
    net = models.get_symbol("afmoe", **TINY)
    assert _index_inputs(net) == {"data"}
    mod = _fit("full", compute_dtype="bfloat16", epochs=1)
    assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()


def test_the_fused_step_keeps_no_gradients_and_says_so():
    """One step program: it hands no gradients back.  Read between
    backward() and update() they come from the plain fwd_bwd program;
    read after the fused update they raise."""
    mod = _fit("full", epochs=1)
    grad = mod._exec_group._grad_dict["head_weight"]
    with pytest.raises(mx.base.MXNetError, match="not kept"):
        grad.asnumpy()
    batch = mx.io.DataBatch(data=[mx.nd.array(np.arange(32.0) % 64)],
                            label=[mx.nd.array(np.arange(32.0) % 64)])
    mod.forward_backward(batch)
    assert np.abs(grad.asnumpy()).max() > 0
    mod.update()
    assert np.abs(grad.asnumpy()).max() > 0     # the plain path keeps them
    mod.forward_backward(batch)
    mod.update()                                # fused again
    with pytest.raises(mx.base.MXNetError, match="not kept"):
        grad.asnumpy()


def test_outputs_in_flight_are_bounded_by_bytes(monkeypatch):
    """A step whose outputs would pass the byte limit within the
    client's run-ahead waits for the oldest step in flight; small
    outputs are not even tracked."""
    from mxnet_tpu.module import mesh_executor_group as meg
    mod = _fit("full", epochs=1)
    grp = mod._exec_group
    assert not grp._inflight_outs           # 32 x 64 x 4 B a step: far under
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    monkeypatch.setattr(meg, "STEP_OUTPUT_BYTES_IN_FLIGHT", 3 * 32 * 64 * 4)
    outs = [(jnp.zeros((32, 64), jnp.float32),) for _ in range(5)]
    for o in outs:
        grp._bound_outputs_in_flight(o)
    # 3 steps' worth may be in flight: the 4th and 5th each waited for
    # the then oldest
    assert [w[0] is o[0] for w, o in zip(waited, outs)] == [True, True]
    assert len(grp._inflight_outs) == 3
