"""The state-space ops of models/nemotron_h.py at a size the CPU holds:
the chunked selective scan against the recurrence token by token, the
causal depthwise convolution and the gated norm over groups against
plain loops, ungated relu2 experts through the one ``held_experts_ffn``
(the gated-SiLU path unchanged), the sixteen shares of an expert layer
adding up, and the model through ``Module.fit`` with its counters."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import ssm
from mxnet_tpu.parallel import expert_parallel as ep

H, P, G, N, Q = 4, 4, 2, 8, 8           # heads, head_dim, groups, state, chunk


# ----------------------------------------------------------------- scan
def _scan_inputs(S, T, seed=0):
    """Inputs of one scan, the per-head parameters drawn as the model's
    published start: dt = softplus(dt + dt_bias) in 0.001-0.1, A = 1..H,
    D = 1, so that state crosses many chunks."""
    rs = np.random.RandomState(seed)
    rows = S * T
    dt0 = np.exp(rs.uniform(math.log(0.001), math.log(0.1), H))
    vals = {"x": rs.randn(rows, H * P), "dt": 0.1 * rs.randn(rows, H),
            "B": rs.randn(rows, G * N), "C": rs.randn(rows, G * N),
            "A_log": np.log(np.arange(1.0, H + 1)),
            "dt_bias": np.log(np.expm1(dt0)),       # softplus's inverse
            "D": np.ones(H)}
    return {k: jnp.asarray(v, jnp.float32) for k, v in vals.items()}


def _token_by_token(v, S, T):
    """The recurrence as written: S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    (x) B_t, y_t = S_t C_t + D x_t, a Python loop over tokens, a zero
    state at each sequence's start; head h reads group h // (H / G)."""
    x = v["x"].reshape(S, T, H, P)
    dt = jax.nn.softplus(v["dt"] + v["dt_bias"]).reshape(S, T, H)
    B = jnp.repeat(v["B"].reshape(S, T, G, N), H // G, axis=2)
    C = jnp.repeat(v["C"].reshape(S, T, G, N), H // G, axis=2)
    A = -jnp.exp(v["A_log"])
    state, ys = jnp.zeros((S, H, P, N)), []
    for t in range(T):
        state = jnp.exp(dt[:, t] * A)[..., None, None] * state \
            + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, :, None, :]
        ys.append(jnp.einsum("shpn,shn->shp", state, C[:, t])
                  + v["D"][:, None] * x[:, t])
    return jnp.stack(ys, axis=1).reshape(S * T, H * P)


def _ssd_symbol(T, chunk=Q):
    names = ("x", "dt", "B", "C", "A_log", "dt_bias", "D")
    return names, mx.sym.SSD(*(mx.sym.Variable(n) for n in names), heads=H,
                             head_dim=P, groups=G, state=N, chunk=chunk,
                             seq_len=T, name="ssd")


def _ssd_through_the_symbol(v, T, head_grad=None):
    """(output, gradients of every input) of sym.SSD bound on the CPU."""
    names, net = _ssd_symbol(T)
    grads = {n: mx.nd.zeros(v[n].shape) for n in names}
    ex = net.bind(mx.cpu(), {n: mx.nd.array(np.asarray(v[n])) for n in names},
                  args_grad=grads)
    out = ex.forward(is_train=True)[0].asnumpy()
    if head_grad is not None:
        ex.backward([mx.nd.array(np.asarray(head_grad))])
    return out, {n: g.asnumpy() for n, g in grads.items()}


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("chunks", [1, 5])
def test_chunked_scan_is_the_recurrence(S, chunks):
    """Values and the gradient of every input, at a sequence of one
    chunk and of five, one sequence and three."""
    T = chunks * Q
    v = _scan_inputs(S, T, seed=S + chunks)
    want = _token_by_token(v, S, T)
    weight = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                         jnp.float32)
    got, g_got = _ssd_through_the_symbol(v, T, head_grad=weight)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    g_want = jax.grad(lambda v: jnp.sum(weight * _token_by_token(v, S, T)))(v)
    for name in v:
        scale = float(jnp.max(jnp.abs(g_want[name]))) + 1e-6
        np.testing.assert_allclose(g_got[name] / scale, g_want[name] / scale,
                                   atol=3e-5, err_msg=name)
    if chunks > 1:
        # the slow heads' state really crosses the chunks: without the
        # carry the later chunks read otherwise
        old = ssm._carry
        ssm._carry = lambda own, decay: jnp.zeros_like(own)
        try:
            cut, _ = _ssd_through_the_symbol(v, T)
        finally:
            ssm._carry = old
        np.testing.assert_allclose(cut[:Q], got[:Q], rtol=1e-6, atol=1e-6)
        assert np.abs(cut[Q:T] - got[Q:T]).max() > 1e-2


def test_a_sequence_sees_nothing_of_the_one_before():
    """Conv and state start from nothing at a sequence's first token:
    the second sequence of a batch reads what it reads alone."""
    T = 3 * Q
    v = _scan_inputs(2, T, seed=4)
    both, _ = _ssd_through_the_symbol(v, T)
    per_row = {k: a[T:] if a.ndim == 2 else a for k, a in v.items()}
    alone, _ = _ssd_through_the_symbol(per_row, T)
    np.testing.assert_allclose(both[T:], alone, rtol=1e-6, atol=1e-6)
    x = np.random.RandomState(5).randn(2 * T, 6).astype(np.float32)
    w = np.random.RandomState(6).randn(6, 4).astype(np.float32)
    conv = lambda a: mx.sym.CausalConv1D(  # noqa: E731
        mx.sym.Variable("x"), mx.sym.Variable("w"), mx.sym.Variable("b"),
        kernel=4, seq_len=T).bind(
            mx.cpu(), {"x": mx.nd.array(a), "w": mx.nd.array(w),
                       "b": mx.nd.zeros((6,))}).forward()[0].asnumpy()
    np.testing.assert_allclose(conv(x)[T:], conv(x[T:]), rtol=1e-6, atol=1e-6)
    assert np.abs(conv(x)[T:T + 3] - conv(np.roll(x, 1, 0))[T:T + 3]).max() > 0


def test_scan_refuses_rows_that_are_no_whole_sequences_or_chunks():
    v = _scan_inputs(1, 12)
    with pytest.raises(Exception, match="whole number"):
        _ssd_through_the_symbol(v, 8)       # 12 rows, sequences of 8
    with pytest.raises(Exception, match="whole number"):
        _ssd_through_the_symbol(v, 12)      # one sequence, chunks of 8


@pytest.mark.parametrize("S,T", [(2, 7), (1, 3)])
def test_causal_conv_is_the_plain_loop(S, T):
    """Two sequences longer than the kernel, and one shorter than it."""
    C, K = 5, 4
    rs = np.random.RandomState(1)
    x = rs.randn(S * T, C).astype(np.float32)
    w = rs.randn(C, K).astype(np.float32)
    b = rs.randn(C).astype(np.float32)
    args = {"data": mx.nd.array(x), "conv_weight": mx.nd.array(w),
            "conv_bias": mx.nd.array(b)}
    net = mx.sym.CausalConv1D(mx.sym.Variable("data"), kernel=K, seq_len=T,
                              name="conv")
    assert net.list_arguments() == list(args)
    grads = {"data": mx.nd.zeros(x.shape)}
    ex = net.bind(mx.cpu(), args, args_grad=grads)
    got = ex.forward(is_train=True)[0].asnumpy()
    want = np.zeros_like(x)
    xs = x.reshape(S, T, C)
    for s in range(S):
        for t in range(T):
            acc = b.copy()
            for j in range(K):
                if t - (K - 1) + j >= 0:
                    acc += w[:, j] * xs[s, t - (K - 1) + j]
            want[s * T + t] = acc
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # its transpose looks forward only, and stays inside the sequence
    ex.backward([mx.nd.array(np.ones_like(x))])
    g = grads["data"].asnumpy().reshape(S, T, C)
    np.testing.assert_allclose(
        g[:, 0], np.broadcast_to(w[:, K - min(T, K):].sum(1), (S, C)),
        rtol=1e-5)
    np.testing.assert_allclose(g[:, -1], np.broadcast_to(w[:, -1], (S, C)),
                               rtol=1e-5)


def _plain_conv(x, w, b, S, T, K):
    """The op's formula as jax would differentiate it, all float32."""
    xp = jnp.pad(x.reshape(S, T, -1), ((0, 0), (K - 1, 0), (0, 0)))
    return (sum(w[:, j] * xp[:, j:j + T] for j in range(K)) + b) \
        .reshape(x.shape)


def _conv_through_the_symbol(x, w, b, dy, T, K):
    """(output, gradients of data, weight, bias) of sym.CausalConv1D
    bound on the CPU, every array in x's type."""
    args = {"data": x, "conv_weight": w, "conv_bias": b}
    nd = lambda a: mx.nd.array(np.asarray(a, np.float32)) \
        .astype(str(x.dtype))                                  # noqa: E731
    grads = {k: nd(jnp.zeros(a.shape)) for k, a in args.items()}
    ex = mx.sym.CausalConv1D(
        mx.sym.Variable("data"), kernel=K, seq_len=T, name="conv").bind(
            mx.cpu(), {k: nd(a) for k, a in args.items()}, args_grad=grads)
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward([nd(dy)])
    return out, [grads[k].asnumpy() for k in args]


def _conv_inputs(S, T, K, dtype, C=5, seed=7):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(*shape), jnp.float32).astype(dtype)
            for shape in ((S * T, C), (C, K), (C,), (S * T, C))]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S,T,K", [(2, 7, 4), (1, 3, 4), (2, 7, 2), (1, 1, 2)])
def test_causal_conv_gradients_are_jaxs_of_the_plain_formula(S, T, K, dtype):
    """The backward pass the op writes out (the mirrored convolution,
    one pass of sums for the taps and the bias) against `jax.vjp` of
    the formula in float32: two sequences longer than the kernel and
    one shorter than it, a kernel of 2 and of 4."""
    x, w, b, dy = _conv_inputs(S, T, K, dtype)
    out, got = _conv_through_the_symbol(x, w, b, dy, T, K)
    f32 = [a.astype(jnp.float32) for a in (x, w, b)]
    want_out, back = jax.vjp(lambda *a: _plain_conv(*a, S, T, K), *f32)
    want = back(dy.astype(jnp.float32))
    # float32: the same products summed in another order.  bfloat16: the
    # operands are exact in float32 and every sum is float32, so each
    # gradient is the float32 value rounded ONCE to 8 bits of mantissa,
    # half a unit in the last place (2**-9 of the value), and a sum
    # reordered in float32 may land across a rounding edge: one unit
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=2.0 ** -8, atol=1e-6)
    np.testing.assert_allclose(out.astype(np.float32), want_out, **tol)
    for name, g, ref in zip(("data", "weight", "bias"), got, want):
        assert g.dtype == out.dtype and g.shape == ref.shape
        np.testing.assert_allclose(g.astype(np.float32), ref, err_msg=name,
                                   **tol)


def test_causal_conv_backward_stays_inside_a_sequence():
    """Backward the convolution looks ahead, and no further than its
    own sequence's last row: the second sequence's gradient is the same
    to the bit whatever the first one's cotangent."""
    S, T, K = 2, 5, 4
    x, w, b, dy = _conv_inputs(S, T, K, "float32", seed=8)
    other = dy.at[:T].set(3.0 * dy[:T] + 1.0)
    (dx, _, _), (dx_other, _, _) = (
        _conv_through_the_symbol(x, w, b, d, T, K)[1] for d in (dy, other))
    np.testing.assert_array_equal(dx[T:], dx_other[T:])
    assert np.abs(dx[:T] - dx_other[:T]).min() > 0
    # and a row's gradient reads the K - 1 rows after it: change the
    # sequence's last cotangent and exactly its last K rows move
    last = dy.at[T - 1].add(1.0)
    moved = np.abs(_conv_through_the_symbol(x, w, b, last, T, K)[1][0]
                   - dx).max(axis=1) > 0
    assert moved.tolist() == [False] * (T - K) + [True] * K + [False] * T


def _avals(jaxpr):
    """Every value a jaxpr makes, the jaxprs in its equations included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda p: hasattr(p, "eqns") or hasattr(p, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _avals(sub)


def test_causal_conv_backward_makes_no_float32_copy_of_the_padded_input():
    """bfloat16 operands stay bfloat16 in memory: the pad and the
    shifted slices come before the upcast, so neither pass holds a
    float32 array of the padded shape (S, T + K - 1, C); the float32
    values are (S, T, C) views that XLA fuses into the sums."""
    from mxnet_tpu.registry import OpContext, get_op
    S, T, K, C = 2, 16, 4, 8
    x, w, b, dy = _conv_inputs(S, T, K, "bfloat16", C=C)
    conv = lambda *ins: get_op("CausalConv1D").fcompute(   # noqa: E731
        {"kernel": K, "seq_len": T}, list(ins), OpContext(True))[0]
    back = jax.make_jaxpr(lambda x, w, b, dy: jax.vjp(conv, x, w, b)[1](dy))(
        x, w, b, dy)
    assert [v.aval.dtype for v in back.jaxpr.outvars] == [jnp.bfloat16] * 3
    made = [(a.shape, str(a.dtype)) for a in _avals(back.jaxpr)
            if hasattr(a, "shape")]
    assert ((S, T + K - 1, C), "bfloat16") in made      # x's pad, and dy's
    assert ((S, T, C), "float32") in made
    assert not [m for m in made if m[0] == (S, T + K - 1, C)
                and m[1] != "bfloat16"]


def test_gated_norm_over_groups_and_relu2():
    rs = np.random.RandomState(2)
    x, z = (rs.randn(6, 16).astype(np.float32) for _ in range(2))
    gamma = np.linspace(0.5, 1.5, 16).astype(np.float32)
    net = mx.sym.GatedRMSNorm(mx.sym.Variable("x"), mx.sym.Variable("z"),
                              groups=4, eps=1e-5, name="n")
    assert net.list_arguments() == ["x", "z", "n_gamma"]
    assert net.infer_shape(x=(6, 16))[0] == [(6, 16), (6, 16), (16,)]
    y = net.bind(mx.cpu(), {"x": mx.nd.array(x), "z": mx.nd.array(z),
                            "n_gamma": mx.nd.array(gamma)}
                 ).forward()[0].asnumpy()
    g = (x * z / (1 + np.exp(-z))).reshape(6, 4, 4)
    want = (g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(6, 16) * gamma
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    # a scale of the full width: sym.RMSNorm(width=) learns a run's
    r = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu2").bind(
        mx.cpu(), {"x": mx.nd.array(x)}).forward()[0].asnumpy()
    np.testing.assert_allclose(r, np.maximum(x, 0) ** 2, rtol=1e-6)


# -------------------------------------------------------------- experts
E, K, D_MODEL, F_EXP = 16, 3, 16, 24


def _moe_weights(seed=0):
    rs = np.random.RandomState(seed)
    return {"router": rs.randn(E, D_MODEL).astype(np.float32),
            "gate": rs.randn(E, F_EXP, D_MODEL).astype(np.float32) * 0.3,
            "up": rs.randn(E, F_EXP, D_MODEL).astype(np.float32) * 0.3,
            "down": rs.randn(E, D_MODEL, F_EXP).astype(np.float32) * 0.3,
            "shared_up": 0.3 * rs.randn(2 * F_EXP, D_MODEL).astype(np.float32),
            "shared_down": 0.3 * rs.randn(D_MODEL, 2 * F_EXP).astype(np.float32)}


def _relu2(t):
    return jnp.square(jnp.maximum(t, 0))


def _uncut_layer(x, w, bias):
    """The whole layer the plain way: the shared expert, and every
    routed expert over every token with weight 0 where not chosen."""
    s = jax.nn.sigmoid(x @ w["router"].T)
    _, chosen = jax.lax.top_k(s + bias[None, :], K)
    wt = jnp.take_along_axis(s, chosen, -1)
    wt = 2.5 * wt / jnp.sum(wt, -1, keepdims=True)
    out = _relu2(x @ w["shared_up"].T) @ w["shared_down"].T
    for e in range(E):
        w_tok = jnp.sum(jnp.where(chosen == e, wt, 0.0), -1)
        out = out + w_tok[:, None] * (_relu2(x @ w["up"][e].T)
                                      @ w["down"][e].T)
    return out


def _share(x, w, bias, first, count):
    """One chip's routed share through sym.MoE with ungated relu2
    experts."""
    net = mx.sym.MoE(mx.sym.Variable("data"), num_experts=E,
                     hidden_size=F_EXP, num_experts_per_tok=K,
                     experts_held=(first, count), score_func="sigmoid",
                     route_norm=True, route_scale=2.5, expert_act="relu2",
                     gated=False, name="moe")
    assert net.list_arguments() == [
        "data", "moe_router_weight", "moe_experts_up_weight",
        "moe_experts_down_weight"]
    sl = slice(first, first + count)
    args = {"data": x, "moe_router_weight": w["router"],
            "moe_experts_up_weight": w["up"][sl].reshape(-1, D_MODEL),
            "moe_experts_down_weight": w["down"][sl].reshape(-1, F_EXP)}
    ex = net.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in args.items()},
                  aux_states={"moe_router_bias": mx.nd.array(bias)})
    return ex.forward(is_train=True)[0].asnumpy()


def test_the_sixteen_shares_add_up():
    """16 experts, one a share, 16 shares: the routed parts and the
    shared expert, counted once (every chip computes it alike), equal
    the uncut layer to float32 rounding."""
    x = np.random.RandomState(4).randn(48, D_MODEL).astype(np.float32)
    w = _moe_weights()
    bias = np.linspace(-0.05, 0.05, E).astype(np.float32)
    routed = sum(_share(x, w, bias, first, 1) for first in range(16))
    shared = np.asarray(_relu2(x @ w["shared_up"].T) @ w["shared_down"].T)
    want = np.asarray(_uncut_layer(jnp.asarray(x), w, jnp.asarray(bias)))
    np.testing.assert_allclose(routed + shared, want, rtol=3e-5, atol=3e-5)
    assert np.abs(routed).max() > 0.1       # the shares are not nothing


def _pairs(seed=7, T=32):
    x = jnp.asarray(np.random.RandomState(seed).randn(T, D_MODEL),
                    jnp.float32)
    w = _moe_weights(seed)
    _s, chosen, weights = ep.route(x @ w["router"].T, k=K,
                                   score_func="sigmoid", route_norm=True,
                                   route_scale=2.5)
    kn = lambda a: jnp.asarray(a[4:10]).transpose(0, 2, 1)  # noqa: E731
    return x, w, chosen, weights, kn


def test_relu2_experts_through_the_one_function_are_the_dense_loop():
    x, w, chosen, weights, kn = _pairs()
    y, sizes, dropped = ep.held_experts_ffn(
        x, chosen, weights, None, kn(w["up"]), kn(w["down"]), 4, act="relu2")
    assert int(dropped) == 0 and int(jnp.sum(sizes)) > 0
    want = jnp.zeros_like(x)
    for e in range(4, 10):
        w_tok = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        want = want + w_tok[:, None] * (_relu2(x @ w["up"][e].T)
                                        @ w["down"][e].T)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="act must be"):
        ep.held_experts_ffn(x, chosen, weights, None, kn(w["up"]),
                            kn(w["down"]), 4, act="gelu")


def test_gated_silu_path_is_unchanged_to_the_bit():
    """The body the Trinity step ran before the gate became optional,
    written out from the same pieces: the one function gives its bits,
    values and gradients."""
    x, w, chosen, weights, kn = _pairs(seed=8)
    held, first, T = 6, 4, x.shape[0]

    def before(x, wg, wu, wd):
        local = chosen - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        pos = jnp.argsort(order).astype(jnp.int32).reshape(T, K)
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        rows = ep._worst_case_rows(T, K, held)
        token = order[:rows] // K
        pos = jnp.minimum(pos, rows - 1)
        take, fold = ep._rows_and_back()
        mine = here.astype(jnp.float32)
        xs = take(x, token, pos, mine)
        h = ep._grouped_matmul(xs, wg, sizes)
        u = ep._grouped_matmul(xs, wu, sizes)
        act = (jax.nn.silu(h.astype(jnp.float32))
               * u.astype(jnp.float32)).astype(x.dtype)
        out = ep._grouped_matmul(act, wd, sizes)
        return fold(out, token, pos,
                    weights.astype(jnp.float32) * mine).astype(x.dtype)

    def now(x, wg, wu, wd):
        return ep.held_experts_ffn(x, chosen, weights, wg, wu, wd, first)[0]

    args = (x, kn(w["gate"]), kn(w["up"]), kn(w["down"]))
    np.testing.assert_array_equal(now(*args), before(*args))
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    for a, b in zip(jax.grad(loss(now), (0, 1, 2, 3))(*args),
                    jax.grad(loss(before), (0, 1, 2, 3))(*args)):
        np.testing.assert_array_equal(a, b)


def test_widths_that_are_no_whole_lanes_reach_the_kernel_padded(monkeypatch):
    """An expert 1,856 wide is no multiple of the kernel's 128 lanes:
    the grouped product pads the widths with zeros and runs the kernel
    (here under the Pallas interpreter) to the values and gradients of
    `lax.ragged_dot`."""
    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.randn(256, 192).astype(np.float32))
    w = jnp.asarray(rs.randn(3, 192, 320).astype(np.float32))
    sizes = jnp.asarray([70, 0, 130], jnp.int32)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    calls, real = [], megablox.gmm
    monkeypatch.setattr(megablox, "gmm", lambda *a, **kw: calls.append(
        (a[0].shape, a[1].shape, a[4])) or real(*a, **kw))

    def both(fn):
        plain = fn()
        monkeypatch.setattr(ep, "_INTERPRET", True)
        kernel = fn()
        monkeypatch.setattr(ep, "_INTERPRET", False)
        return kernel, plain
    a, b = both(lambda: ep._grouped_matmul(x, w, sizes))
    # 192 -> 256 and 320 -> 384, one tile of 128 that divides both
    assert calls == [((256, 256), (3, 256, 384), (256, 128, 128))]
    assert a.shape == (256, 320)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
    assert float(jnp.max(jnp.abs(a[200:]))) == 0.0
    for ga, gb in zip(*both(lambda: jax.grad(lambda x, w: jnp.sum(jnp.square(
            ep._grouped_matmul(x, w, sizes))), (0, 1))(x, w))):
        assert ga.shape == gb.shape
        np.testing.assert_allclose(ga, gb, rtol=1e-3, atol=5e-2)


# ---------------------------------------------------------------- model
TINY = dict(vocab_size=64, seq_len=24, hidden_size=32,
            hybrid_override_pattern="MEM*E", mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
            chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, moe_intermediate_size=16,
            moe_shared_expert_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, experts_held=(2, 4))
ROWS, STEPS = 48, 4                     # two sequences a step


def _fit(remat, compute_dtype=None):
    from mxnet_tpu import models
    mx.random.seed(5)
    net = models.get_symbol("nemotron_h", remat=remat, **TINY)
    rs = np.random.RandomState(0)
    x = rs.randint(0, 64, (ROWS * STEPS,)).astype(np.float32)
    y = rs.randint(0, 64, (ROWS * STEPS,)).astype(np.float32)
    mod = mx.mod.Module(net, context=[mx.cpu(0)], compute_dtype=compute_dtype)
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=ROWS), num_epoch=1,
            optimizer="sgd", eval_metric="acc",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier())
    return mod


def test_model_is_found_by_name_and_trains_with_its_counters():
    from mxnet_tpu import telemetry
    mod = _fit("full")
    assert mod._exec_group.remat == "full"      # the symbol named it
    counters = telemetry.last_fit()["counters"]
    # 4 steps x 2 Mamba-2 layers x 2 sequences x 3 chunks, and the
    # float32 states at their starts: heads x head_dim x state each
    assert counters["ssm.chunks"] == STEPS * 2 * 2 * 3
    assert counters["ssm.carried_bytes"] == STEPS * 2 * 2 * 3 * 4 * 8 * 16 * 4
    # 2 expert layers x 48 tokens x 2 choices a step, 4 of 8 held
    assert counters["moe.dropped"] == 0
    assert 0 < counters["moe.held_pairs"] < STEPS * 2 * ROWS * 2
    args, aux = mod.get_params()
    assert sorted(aux) == ["l1_moe_router_bias", "l4_moe_router_bias"]
    assert args["l0_A_log_weight"].shape == (4,)
    assert args["l0_D_gamma"].asnumpy().std() > 0       # it trains
    assert "l1_moe_experts_gate_weight" not in args     # ungated experts
    assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()
    assert _fit("full", "bfloat16").get_outputs()[0].asnumpy().std() > 0


def test_remat_changes_no_number_and_counts_the_scan_once_a_step():
    """What a segment keeps is the value it would have made again; the
    scan's output (rows x d_inner) and its chunk-boundary states are
    among the bytes `remat.kept_bytes` counts, once for every step."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.precision import policy
    a = _fit(None)
    assert "remat.kept_bytes" not in telemetry.last_fit()["counters"]
    b = _fit("full")
    kept = telemetry.last_fit()["counters"]["remat.kept_bytes"]
    np.testing.assert_allclose(a.get_outputs()[0].asnumpy(),
                               b.get_outputs()[0].asnumpy(),
                               rtol=2e-4, atol=2e-6)
    pa, pb = a.get_params()[0], b.get_params()[0]
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-4, atol=2e-6, err_msg=k)
    scan = 2 * (ROWS * 32 + 2 * 3 * 4 * 8 * 16) * 4     # y and the states
    assert policy.SCAN in policy.remat_kept_names("full")
    assert policy.SCAN not in policy.remat_kept_names("dots")
    assert kept % STEPS == 0 and kept // STEPS > scan
    # and without the scan's name the count falls by just those bytes
    names = policy._KEPT_NAMES["full"]
    policy._KEPT_NAMES["full"] = tuple(n for n in names if n != policy.SCAN)
    try:
        _fit("full")
    finally:
        policy._KEPT_NAMES["full"] = names
    assert telemetry.last_fit()["counters"]["remat.kept_bytes"] \
        == kept - STEPS * scan


def test_pattern_of_other_letters_is_refused():
    from mxnet_tpu import models
    with pytest.raises(ValueError, match="none of M"):
        models.get_symbol("nemotron_h", **dict(
            TINY, hybrid_override_pattern="M-E"))
