"""The ops of models/kimi_linear.py at a size the CPU holds: the chunked
gated delta rule against the recurrence token by token, attention with
keys of one width and values of another (and a key width that is no
whole number of the kernel's lanes), the latent's expansion, the output
norm as the builder composes it, the causal convolution without a bias
with its default unchanged, the thirty-two shares of an expert layer
adding up, and the model through ``Module.fit`` with its counters."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import ssm
from mxnet_tpu import registry
from mxnet_tpu.registry import OpContext, get_op

H, D, Q = 2, 8, 8                       # heads, head_dim, chunk
NAMES = ("q", "k", "v", "gate", "beta", "A_log", "dt_bias")


# ----------------------------------------------------------- delta rule
def _delta_inputs(S, T, seed=0, a=(0.05, 1.0), gate_shift=-2.0, H=H, D=D):
    """Inputs of one delta rule; A = exp(A_log) spread over `a` and the
    gate's mean at `gate_shift`, so that some channels forget within a
    chunk and others carry across several."""
    rs = np.random.RandomState(seed)
    rows = S * T
    vals = {"q": rs.randn(rows, H * D), "k": rs.randn(rows, H * D),
            "v": rs.randn(rows, H * D),
            "gate": gate_shift + rs.randn(rows, H * D),
            "beta": rs.randn(rows, H),
            "A_log": np.log(np.linspace(a[0], a[1], H)),
            "dt_bias": 0.1 * rs.randn(H * D)}
    return {k: jnp.asarray(v, jnp.float32) for k, v in vals.items()}


def _gates(v, S, T):
    """(q, k normalised and q scaled; g; beta) as the op makes them."""
    H = v["A_log"].shape[0]
    D = v["dt_bias"].shape[0] // H

    def unit(t):
        t = t.reshape(S, T, H, D)
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(v["A_log"])[:, None] * jax.nn.softplus(
        v["gate"] + v["dt_bias"]).reshape(S, T, H, D)
    return (unit(v["q"]) * D ** -0.5, unit(v["k"]), g,
            jax.nn.sigmoid(v["beta"]).reshape(S, T, H))


def _token_by_token(v, S, T):
    """The recurrence as written: S_t = (I - beta k k^T) Diag(alpha)
    S_{t-1} + beta k v^T, o_t = S_t^T q_t, a Python loop over tokens, a
    zero state at each sequence's start."""
    q, k, g, beta = _gates(v, S, T)
    H, D = q.shape[2:]
    val = v["v"].reshape(S, T, H, D)
    state, out = jnp.zeros((S, H, D, D)), []
    for t in range(T):
        step = jnp.eye(D) - beta[:, t, :, None, None] \
            * k[:, t, :, :, None] * k[:, t, :, None, :]
        state = jnp.einsum("shdc,shce->shde", step,
                           jnp.exp(g[:, t])[..., None] * state) \
            + beta[:, t, :, None, None] * k[:, t, :, :, None] \
            * val[:, t, :, None, :]
        out.append(jnp.einsum("shde,shd->she", state, q[:, t]))
    return jnp.stack(out, axis=1).reshape(S * T, H * D)


def _delta(v, T, chunk=Q):
    H = v["A_log"].shape[0]
    return get_op("GatedDeltaRule").fcompute(
        {"heads": H, "head_dim": v["dt_bias"].shape[0] // H, "chunk": chunk,
         "seq_len": T}, [v[n] for n in NAMES], OpContext(True))[0]


def _delta_through_the_symbol(v, T, head_grad=None, chunk=Q):
    """(output, gradients of every input) of sym.GatedDeltaRule bound
    on the CPU."""
    H = v["A_log"].shape[0]
    net = mx.sym.GatedDeltaRule(*(mx.sym.Variable(n) for n in NAMES),
                                heads=H, head_dim=v["dt_bias"].shape[0] // H,
                                chunk=chunk, seq_len=T, name="kda")
    grads = {n: mx.nd.zeros(v[n].shape) for n in NAMES}
    ex = net.bind(mx.cpu(), {n: mx.nd.array(np.asarray(v[n])) for n in NAMES},
                  args_grad=grads)
    out = ex.forward(is_train=True)[0].asnumpy()
    if head_grad is not None:
        ex.backward([mx.nd.array(np.asarray(head_grad))])
    return out, {n: g.asnumpy() for n, g in grads.items()}


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("chunks", [1, 5])
def test_chunked_delta_rule_is_the_recurrence(S, chunks):
    """Values and the gradient of every input, at a sequence of one
    chunk and of five, one sequence and three, float32 to 1e-5."""
    T = chunks * Q
    v = _delta_inputs(S, T, seed=S + chunks)
    want = _token_by_token(v, S, T)
    weight = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                         jnp.float32)
    got, g_got = _delta_through_the_symbol(v, T, head_grad=weight)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    g_want = jax.grad(lambda v: jnp.sum(weight * _token_by_token(v, S, T)))(v)
    for name in v:
        scale = float(jnp.max(jnp.abs(g_want[name]))) + 1e-6
        np.testing.assert_allclose(g_got[name] / scale, g_want[name] / scale,
                                   atol=1e-5, err_msg=name)


@pytest.mark.parametrize("T,chunk", [(128, 64), (96, 32), (40, 20)])
def test_a_chunk_is_halved_down_to_blocks_computed_pair_by_pair(T, chunk):
    """The cell's chunk of 64 (two halvings, blocks of 16), one of 32
    (one) and one of 20 (one, blocks of 10): the decayed inner products
    by matrix products below the diagonal are the recurrence's."""
    v = _delta_inputs(2, T, seed=T)
    want = _token_by_token(v, 2, T)
    np.testing.assert_allclose(_delta(v, T, chunk), want, rtol=2e-5,
                               atol=2e-5)
    g_want = jax.grad(lambda v: jnp.sum(jnp.sin(_token_by_token(v, 2, T))))(v)
    g_got = jax.grad(lambda v: jnp.sum(jnp.sin(_delta(v, T, chunk))))(v)
    for name in v:
        scale = float(jnp.max(jnp.abs(g_want[name]))) + 1e-6
        np.testing.assert_allclose(g_got[name] / scale, g_want[name] / scale,
                                   atol=2e-5, err_msg=name)


def test_state_crosses_the_chunks(monkeypatch):
    """The planted fault of the benchmark's control: a carry that passes
    nothing on leaves the first chunk as it was and moves the later
    ones."""
    T = 5 * Q
    v = _delta_inputs(1, T, seed=2)
    good = _delta(v, T)
    real = ssm._delta_carry

    def cut(w, u, k_end, decay):
        s_in, _ = real(w, u, k_end, decay)
        return jnp.zeros_like(s_in), u
    monkeypatch.setattr(ssm, "_delta_carry", cut)
    bad = _delta(v, T)
    np.testing.assert_allclose(bad[:Q], good[:Q], rtol=1e-6, atol=1e-6)
    assert np.abs(bad[Q:] - good[Q:]).max() > 1e-2


def test_without_the_correction_the_state_only_decays_and_sums(monkeypatch):
    """The other planted fault, "beta taken as 0" in the step's matrix:
    with no Akk in the chunk's system and no W S_0 taken off U, the
    same chunked code computes S_t = Diag(alpha_t) S_{t-1} + beta_t k_t
    v_t^T, which is not the delta rule."""
    S, T = 2, 5 * Q
    v = _delta_inputs(S, T, seed=2)
    good = _delta(v, T)
    products, carry = ssm._decayed_products, ssm._delta_carry
    monkeypatch.setattr(ssm, "_decayed_products", lambda rows, k, G: [
        jnp.zeros_like(a) if i == 0 else a
        for i, a in enumerate(products(rows, k, G))])
    monkeypatch.setattr(ssm, "_delta_carry", lambda w, u, k_end, decay:
                        carry(jnp.zeros_like(w), u, k_end, decay))
    q, k, g, beta = _gates(v, S, T)
    val = v["v"].reshape(S, T, H, D)
    state, out = jnp.zeros((S, H, D, D)), []
    for t in range(T):
        state = jnp.exp(g[:, t])[..., None] * state \
            + (beta[:, t, :, None] * k[:, t])[..., None] \
            * val[:, t, :, None, :]
        out.append(jnp.einsum("shde,shd->she", state, q[:, t]))
    want = jnp.stack(out, axis=1).reshape(S * T, H * D)
    np.testing.assert_allclose(_delta(v, T), want, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(want) - np.asarray(good)).max() > 1e-2


def test_a_sequence_sees_nothing_of_the_one_before():
    T = 3 * Q
    v = _delta_inputs(2, T, seed=4)
    both, _ = _delta_through_the_symbol(v, T)
    second = {k: a[T:] if a.ndim == 2 else a for k, a in v.items()}
    alone, _ = _delta_through_the_symbol(second, T)
    np.testing.assert_allclose(both[T:], alone, rtol=1e-6, atol=1e-6)


def test_delta_rule_refuses_rows_that_are_no_whole_sequences_or_chunks():
    v = _delta_inputs(1, 12)
    with pytest.raises(Exception, match="whole number"):
        _delta_through_the_symbol(v, 8)         # 12 rows, sequences of 8
    with pytest.raises(Exception, match="whole number"):
        _delta_through_the_symbol(v, 12)        # one sequence, chunks of 8


@pytest.mark.parametrize("chunk,heads,dim,kernels", [
    (8, H, D, False), (64, H, D, False), (64, 1, 128, True)])
def test_a_strong_decay_gives_no_inf_or_nan_in_either_pass(
        monkeypatch, chunk, heads, dim, kernels):
    """g of -30 a step and more: over a chunk of 64 the running sum
    passes -1,900, and exp of its negation is far past float32; no
    exponent the chunked form takes is positive, on XLA's path and
    (a head of 128, under the Pallas interpreter) on the kernels'."""
    monkeypatch.setattr(ssm, "_INTERPRET", kernels)
    T = 128
    v = _delta_inputs(2, T, seed=3, H=heads, D=dim)
    with registry.counting() as counted:
        _delta(v, T, chunk)
    assert float(counted["kda.kernel_chunks"]) == (2 * T // chunk) * kernels
    v["A_log"] = jnp.full((heads,), math.log(30.0))
    v["gate"] = 2.0 + jnp.abs(v["gate"])            # softplus(.) > 1
    _, _, g, _ = _gates(v, 2, T)
    assert float(jnp.max(g)) < -30.0
    out = _delta(v, T, chunk)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out, _token_by_token(v, 2, T), rtol=1e-5,
                               atol=1e-6)
    grads = jax.grad(lambda v: jnp.sum(jnp.sin(_delta(v, T, chunk))))(v)
    for name, g in grads.items():
        assert bool(jnp.isfinite(g).all()), name


# ------------------------------------------------------------ the solve
def _xla_solve(system, rhs):
    return jax.lax.linalg.triangular_solve(system, rhs, left_side=True,
                                           lower=True, unit_diagonal=True)


def _systems(chunk, width, seed):
    """Chunk systems as the delta rule builds them, I + tril(beta Akk,
    -1), from keys of length 1 and decays, with noise above the
    diagonal and on it (neither solve reads there); and a right-hand
    side (2, 3, chunk, width)."""
    rs = np.random.RandomState(seed)
    k = rs.randn(2, 3, chunk, 16)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    G = np.cumsum(-0.1 * np.abs(rs.randn(2, 3, chunk, 16)), axis=2)
    akk, = ssm._decayed_products([jnp.asarray(k, jnp.float32)],
                                 jnp.asarray(k, jnp.float32),
                                 jnp.asarray(G, jnp.float32))
    beta = 1 / (1 + np.exp(-rs.randn(2, 3, chunk, 1)))
    system = np.tril(beta * np.asarray(akk), -1) \
        + np.triu(rs.randn(2, 3, chunk, chunk), 1) + 3 * np.eye(chunk)
    return (jnp.asarray(system, jnp.float32),
            jnp.asarray(rs.randn(2, 3, chunk, width), jnp.float32))


def _float64_solve(system, rhs):
    unit = np.tril(np.asarray(system, np.float64), -1) \
        + np.eye(system.shape[-1])
    return np.linalg.solve(unit, np.asarray(rhs, np.float64))


@pytest.mark.parametrize("width", [256, 24])
@pytest.mark.parametrize("chunk", [64, 32, 20, 16, 8])
def test_the_solve_by_halving_is_xlas_and_float64s(chunk, width):
    """The inverse by halving (64: 16 -> 32 -> 64; 32; 20: 10 -> 20;
    16 and 8 by substitution alone) times the right-hand side, against
    XLA's triangular solve and float64, and both gradients against
    jax's gradient of XLA's solve; float32 to 1e-5 of the largest
    entry."""
    system, rhs = _systems(chunk, width, seed=chunk + width)
    got = ssm._unit_lower_solve(system, rhs)
    for want in (_xla_solve(system, rhs), _float64_solve(system, rhs)):
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * float(np.abs(want).max()))
    weight = jnp.asarray(np.random.RandomState(1).randn(*rhs.shape),
                         jnp.float32)
    g_got = jax.grad(lambda s, b: jnp.sum(weight * ssm._unit_lower_solve(
        s, b)), (0, 1))(system, rhs)
    g_want = jax.grad(lambda s, b: jnp.sum(weight * _xla_solve(s, b)),
                      (0, 1))(system, rhs)
    for name, a, b in zip(("system", "rhs"), g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_the_solve_at_the_bound_of_its_entries():
    """beta near 1, one key for every row and no decay: every entry
    below the diagonal is 1, the largest the delta rule can make, and
    the inverse's rows cancel nearly to two entries.  No inf or NaN in
    either pass, and float64's answer to 1e-4."""
    Q = 64
    k = np.ones((1, 1, Q, 128)) / math.sqrt(128)
    akk, = ssm._decayed_products([jnp.asarray(k, jnp.float32)],
                                 jnp.asarray(k, jnp.float32),
                                 jnp.zeros(k.shape, jnp.float32))
    beta = float(jax.nn.sigmoid(12.0))
    system = jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1), beta * akk,
                       0.0) + jnp.eye(Q)
    rhs = jnp.asarray(np.random.RandomState(3).randn(1, 1, Q, 256),
                      jnp.float32)
    got = ssm._unit_lower_solve(system, rhs)
    assert bool(jnp.isfinite(got).all())
    want = _float64_solve(system, rhs)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * float(np.abs(want).max()))
    for g in jax.grad(lambda s, b: jnp.sum(jnp.sin(ssm._unit_lower_solve(
            s, b))), (0, 1))(system, rhs):
        assert bool(jnp.isfinite(g).all())


def test_a_training_step_of_the_delta_rule_lowers_without_a_triangular_solve():
    """The gradient of the op at the cell's chunk of 64 and head of 128,
    lowered on the CPU: no triangular solve in any pass (on the CPU XLA's
    is LAPACK's `trsm`)."""
    def lowered(f, *args):
        return jax.jit(f).lower(*args).as_text()

    def old_path_shows(text):
        return "triangular_solve" in text or "trsm" in text
    s = jnp.eye(64)[None] + jnp.tril(jnp.ones((1, 64, 64)), -1)
    assert old_path_shows(lowered(jax.grad(lambda s: jnp.sum(_xla_solve(
        s, s))), s))
    T = 128
    v = _delta_inputs(1, T, seed=7, H=2, D=128)
    text = lowered(jax.grad(lambda v: jnp.sum(jnp.sin(_delta(v, T, 64)))), v)
    assert not old_path_shows(text)
    assert "dot_general" in text


# ------------------------------------------- the delta rule's kernels
def _chunks_of(cells, chunk, dim, seed=0):
    """k, q and the running sum G of `cells` chunks (cells, chunk, dim)
    float32, with cotangents for Akk and Aqk."""
    rs = np.random.RandomState(seed)
    k, q = (rs.randn(cells, chunk, dim) / math.sqrt(dim) for _ in range(2))
    G = np.cumsum(-0.3 * np.abs(rs.randn(cells, chunk, dim)), axis=1)
    cts = [rs.randn(cells, chunk, chunk) for _ in range(2)]
    return [jnp.asarray(t, jnp.float32) for t in (k, q, G)], \
        [jnp.asarray(t, jnp.float32) for t in cts]


@pytest.mark.parametrize("steps", [1, 2])
@pytest.mark.parametrize("chunk,dim", [(64, 128), (32, 128), (16, 128)])
def test_kernels_under_the_interpreter_are_todays_products(
        monkeypatch, chunk, dim, steps):
    """Akk, Aqk and the three gradients by the two Pallas kernels
    against `_decayed_products` and jax's gradient of it: one chunk (a
    grid step made up with zeros) and a grid step of 128 blocks and one
    more chunk (two steps, several chunks each); float32 to 1e-5 of the
    largest entry."""
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    cells = 1 if steps == 1 else 2048 // chunk + 1
    (k, q, G), cts = _chunks_of(cells, chunk, dim, seed=chunk + steps)
    assert ssm._kernel_fits(k, q, G)

    def old(k, q, G):
        return ssm._decayed_products([k, q], k, G)

    def weighed(f):
        return lambda *a: sum(jnp.sum(c * x) for c, x in zip(cts, f(*a)))
    new = ssm._intra_kernels(chunk, dim, True)
    for want, got in zip(old(k, q, G), new(k, q, G)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * float(jnp.abs(want).max()))
    for name, want, got in zip(
            "kqG", jax.grad(weighed(old), (0, 1, 2))(k, q, G),
            jax.grad(weighed(new), (0, 1, 2))(k, q, G)):
        np.testing.assert_allclose(got, want, rtol=0, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(want).max()))


def test_the_decays_gradient_is_rows_times_theirs_less_keys_times_theirs():
    """What the backward kernel computes, against jax's gradient of a
    block taken pair by pair alone: with the rows and the keys as
    arguments of their own, dG = R * dR - K * dK summed over the two
    pairs."""
    (k, q, G), cts = _chunks_of(3, ssm._DIRECT_ROWS, 24, seed=5)

    def weighed(rk, rq, keys, G):
        return sum(jnp.sum(c * a) for c, a in zip(
            cts, ssm._decayed_products([rk, rq], keys, G)))
    drk, drq, dkeys, dG = jax.grad(weighed, (0, 1, 2, 3))(k, q, k, G)
    np.testing.assert_allclose(dG, k * drk + q * drq - k * dkeys, rtol=0,
                               atol=1e-5 * float(jnp.abs(dG).max()))


@pytest.mark.parametrize("chunk,dim,dtype,fits", [
    (64, 128, "float32", True), (128, 128, "float32", True),
    (16, 128, "float32", True), (20, 128, "float32", False),
    (64, 12, "float32", False), (64, 256, "float32", False),
    (64, 128, "bfloat16", False), (256, 128, "float32", False)])
def test_shapes_the_kernels_take_and_turn_away(monkeypatch, chunk, dim,
                                               dtype, fits):
    """The kernels take float32 operands, a head of 128 and a chunk of
    16, 32, 64 or 128 rows, under the interpreter (on the CPU with no
    interpreter, nothing)."""
    k, q, G = (t.astype(dtype) for t in _chunks_of(2, chunk, dim)[0])
    assert not ssm._kernel_fits(k, q, G)
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    assert ssm._kernel_fits(k, q, G) == fits


@pytest.mark.parametrize("chunk,dim", [(20, 128), (8, 12)])
def test_a_chunk_or_a_head_the_kernels_turn_away_takes_the_old_path(
        monkeypatch, chunk, dim):
    """Chunks of 20 and heads of 12 go through `_decayed_products`,
    and the op counts no chunk as the kernels'; off the TPU and with no
    interpreter so does every shape."""
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    monkeypatch.setattr(ssm, "_intra_kernels", None)    # would raise
    T = 2 * chunk
    v = _delta_inputs(1, T, seed=6, H=1, D=dim)
    with registry.counting() as counted:
        out = _delta(v, T, chunk)
    assert float(counted["kda.kernel_chunks"]) == 0
    assert float(counted["kda.chunks"]) == 2
    np.testing.assert_allclose(out, _token_by_token(v, 1, T), rtol=2e-5,
                               atol=2e-5)
    monkeypatch.setattr(ssm, "_INTERPRET", False)
    v = _delta_inputs(1, 128, seed=6, H=1, D=128)
    with registry.counting() as counted:
        _delta(v, 128, 64)
    assert float(counted["kda.kernel_chunks"]) == 0


def test_the_symbol_on_the_kernel_path_is_the_recurrence(monkeypatch):
    """`sym.GatedDeltaRule` with a head of 128 and chunks of 64 under
    the Pallas interpreter: values and the gradient of every input
    against the recurrence token by token, and every chunk counted as
    the kernels'."""
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    S, T = 2, 128
    v = _delta_inputs(S, T, seed=8, H=2, D=128)
    want = _token_by_token(v, S, T)
    weight = jnp.asarray(np.random.RandomState(9).randn(*want.shape),
                         jnp.float32)
    got, g_got = _delta_through_the_symbol(v, T, head_grad=weight, chunk=64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    g_want = jax.grad(lambda v: jnp.sum(weight * _token_by_token(v, S, T)))(v)
    for name in v:
        scale = float(jnp.max(jnp.abs(g_want[name]))) + 1e-6
        np.testing.assert_allclose(g_got[name] / scale, g_want[name] / scale,
                                   atol=1e-5, err_msg=name)
    with registry.counting() as counted:
        _delta(v, T, 64)
    assert float(counted["kda.kernel_chunks"]) \
        == float(counted["kda.chunks"]) == S * T // 64



# -------------------------------------------- the delta rule's carry
def _scan_carry(w, u, k_end, decay):
    """The carry as a plain `lax.scan` that makes delta in its loop
    (the form before the carry had a backward pass of its own), for
    jax's gradient of it: the oracle of both carriers."""
    f32, cdt = jnp.float32, w.dtype

    def step(state, chunk_c):
        w_c, u_c, k_c, decay_c = chunk_c
        delta = u_c - jnp.einsum("bhqd,bhde->bhqe", w_c, state.astype(cdt),
                                 preferred_element_type=f32)
        new = decay_c[..., None] * state + jnp.einsum(
            "bhqd,bhqe->bhde", k_c, delta.astype(cdt),
            preferred_element_type=f32)
        return new, (state, delta)
    S, _nc, H, _Q, d = w.shape
    _, (s_in, delta) = jax.lax.scan(
        step, jnp.zeros((S, H, d, u.shape[-1]), jnp.float32),
        tuple(t.swapaxes(0, 1) for t in (w, u, k_end, decay)))
    return s_in.swapaxes(0, 1), delta.swapaxes(0, 1)


def _carry_operands(chunk, dtype, seed, S=1, chunks=3, heads=2, dim=128):
    """w, u, k_end, decay of `chunks` chunks as the delta rule hands
    them to its carry, and cotangents for s_in and delta."""
    rs = np.random.RandomState(seed)
    rows = (S, chunks, heads, chunk, dim)
    w, k = (jnp.asarray(rs.randn(*rows) / math.sqrt(dim), dtype)
            for _ in range(2))
    u = jnp.asarray(rs.randn(*rows), jnp.float32)
    decay = jnp.asarray(rs.uniform(0.3, 1.0, rows[:3] + (dim,)),
                        jnp.float32)
    g_s = jnp.asarray(rs.randn(*rows[:3], dim, dim), jnp.float32)
    g_delta = jnp.asarray(rs.randn(*rows), jnp.float32)
    return (w, u, k, decay), (g_s, g_delta)


@pytest.mark.parametrize("chunk", [64, 16])
@pytest.mark.parametrize("cts", ["forward", "both cotangents",
                                 "none for the states", "none for delta"])
def test_the_carry_kernel_under_the_interpreter_is_the_scan(chunk, cts):
    """The carry by its Pallas kernel under the interpreter against the
    scan that carries it elsewhere: forward (s_in and delta), and
    backward, the recurrence run reversed with the cotangent of s_in as
    its extra term and that of delta as its u, each of them zero or
    not.  bfloat16 operands as in the cell: float32 results within
    1e-5 of their largest entry, bfloat16 ones within a rounding
    (2e-3); and in float32 both carriers' gradients with respect to w,
    u, k and decay are jax's gradient of the plain scan within 1e-5."""
    kernel = ssm._delta_carrier(True, True)
    scan = ssm._delta_carrier(False)

    def close(got, want, err_msg=""):
        got, want = (np.asarray(t.astype(jnp.float32)) for t in (got, want))
        tol = 2e-3 if got.dtype != want.dtype else 1e-5
        np.testing.assert_allclose(got, want, rtol=0, err_msg=err_msg,
                                   atol=tol * float(np.abs(want).max()))
    for dtype in (jnp.bfloat16, jnp.float32):
        args, (g_s, g_delta) = _carry_operands(chunk, dtype, seed=chunk)
        if cts == "none for the states":
            g_s = jnp.zeros_like(g_s)
        if cts == "none for delta":
            g_delta = jnp.zeros_like(g_delta)
        if cts == "forward":
            for got, want, oracle, name in zip(
                    kernel(*args), scan(*args), _scan_carry(*args),
                    ("s_in", "delta")):
                close(got, want, name)
                close(got, oracle, name)
            continue
        got = jax.vjp(kernel, *args)[1]((g_s, g_delta))
        want = jax.vjp(scan, *args)[1]((g_s, g_delta))
        for name, a, b in zip(("w", "u", "k", "decay"), got, want):
            assert a.dtype == b.dtype, name
            tol = 2e-3 if a.dtype == jnp.bfloat16 else 1e-5
            np.testing.assert_allclose(
                np.asarray(a.astype(jnp.float32)),
                np.asarray(b.astype(jnp.float32)), rtol=0, err_msg=name,
                atol=tol * float(jnp.abs(b.astype(jnp.float32)).max()))
        if dtype == jnp.float32:
            oracle = jax.vjp(_scan_carry, *args)[1]((g_s, g_delta))
            for name, a, b in zip(("w", "u", "k", "decay"), got, oracle):
                np.testing.assert_allclose(
                    a, b, rtol=0, err_msg=name,
                    atol=1e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("chunk,dim,fits", [
    (64, 128, True), (16, 128, True), (128, 128, True), (32, 256, True),
    (20, 128, False), (8, 128, False), (256, 128, False), (64, 12, False),
    (16, 8, False)])
def test_shapes_the_carry_kernel_takes_and_turns_away(monkeypatch, chunk,
                                                       dim, fits):
    """The carry's kernel takes heads of whole lanes and a chunk of 16
    to 128 rows in whole tiles of 16, under the interpreter (on the
    CPU with no interpreter, nothing); any operand type."""
    (w, u, _, _), _ = _carry_operands(chunk, jnp.bfloat16, 0, chunks=1,
                                      heads=1, dim=dim)
    assert not ssm._carry_kernel_fits(w, u)
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    assert ssm._carry_kernel_fits(w, u) == fits
    assert ssm._carry_kernel_fits(w.astype(jnp.float32), u) == fits


@pytest.mark.parametrize("chunk,dim", [(20, 128), (16, 12), (64, 128)])
def test_the_carry_counts_the_chunks_its_kernel_went_through(
        monkeypatch, chunk, dim):
    """A head that is not 128 wide or a chunk of 20 takes the scan, and
    the op counts no chunk as the carry kernel's; a head of 128 and a
    chunk of 64 count every chunk.  Either way the op is the recurrence
    token by token."""
    monkeypatch.setattr(ssm, "_INTERPRET", True)
    fits = dim == 128 and chunk % 16 == 0
    if not fits:
        monkeypatch.setattr(ssm, "_carry_call", None)   # would raise
    T = 2 * chunk
    v = _delta_inputs(1, T, seed=6, H=1, D=dim)
    with registry.counting() as counted:
        out = _delta(v, T, chunk)
    assert float(counted["kda.chunks"]) == 2
    assert float(counted["kda.carry_kernel_chunks"]) == 2 * fits
    np.testing.assert_allclose(out, _token_by_token(v, 1, T), rtol=2e-5,
                               atol=2e-5)


def test_a_remat_step_runs_the_carry_once_a_pass_and_never_again():
    """The lowered training step of the tiny model under `remat="full"`
    on the CPU, where the scan carries: the carry's loop stands twice a
    delta-rule layer (forward, and backward reversed), not three times
    as when jax differentiated the scan and a segment's backward pass
    ran it again.  The step has no other loop."""
    mod = _fit("full")
    fn, skeleton = mod._exec_group._last_step
    text = fn.lower(*skeleton).as_text()
    assert text.count("stablehlo.while") == 2 * 3


# ------------------------------------------------------------ attention
def _plain_attention(q, k, v, causal):
    """softmax(q k^T / sqrt(D)) v, every score at once; q (B, H, T, D),
    k (B, G, T, D), v (B, G, T, Dv)."""
    R = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, R, axis=1), jnp.repeat(v, R, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        T = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _qkv(B, Hq, G, T, Dk, Dv, seed):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(*s), jnp.float32)
                 for s in ((B, Hq, T, Dk), (B, G, T, Dk), (B, G, T, Dv)))


@pytest.mark.parametrize("Dk,Dv", [(12, 8), (8, 12), (192, 128)])
def test_values_of_another_width_than_the_keys_on_the_blockwise_path(Dk, Dv):
    from mxnet_tpu.ops.transformer import attention
    q, k, v = _qkv(2, 4, 2, 40, Dk, Dv, seed=Dk)
    got = attention(q, k, v, causal=True)
    assert got.shape == (2, 4, 40, Dv)
    np.testing.assert_allclose(got, _plain_attention(q, k, v, True),
                               atol=2e-5)


def test_keys_of_one_and_a_half_lanes_on_the_kernel_path(monkeypatch):
    """192-wide keys and 128-wide values through the library kernel
    (under the Pallas interpreter): the keys reach it with zero columns
    up to 256, which change no score; values and all three gradients
    are the plain softmax's."""
    from mxnet_tpu.ops import transformer
    q, k, v = _qkv(1, 2, 2, 256, 192, 128, seed=6)
    seen, real = [], transformer._splash_kernel

    def spy(*a):
        seen.append(a)
        return real(*a)
    monkeypatch.setattr(transformer, "_splash_kernel", spy)
    monkeypatch.setattr(transformer, "_INTERPRET", True)
    got = transformer.attention(q, k, v, causal=True)
    # one kernel, 256-row blocks; wide queries: keys 512 a block in the
    # backward kernel, which 256 rows are no whole number of
    assert seen == [(256, 1, True, 0, 256, 256)]
    np.testing.assert_allclose(got, _plain_attention(q, k, v, True),
                               atol=2e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.square(f(*a, True)))  # noqa: E731
    g_got = jax.grad(loss(lambda *a: transformer.attention(
        *a[:3], causal=a[3])), (0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(_plain_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-4)


def test_heads_of_whole_lanes_reach_the_kernel_as_before(monkeypatch):
    """128-wide heads (the Trinity and Nemotron programs): no padding
    and the backward kernel's block of keys the forward one's, so the
    kernel is built from the arguments it was built from before."""
    from mxnet_tpu.ops import transformer
    q, k, v = _qkv(1, 2, 1, 128, 128, 128, seed=7)
    seen, real = [], transformer._splash_kernel

    def spy(*a):
        seen.append(a)
        return real(*a)
    monkeypatch.setattr(transformer, "_splash_kernel", spy)
    monkeypatch.setattr(transformer, "_INTERPRET", True)
    pads = []
    monkeypatch.setattr(jnp, "pad", lambda *a, **kw: pads.append(a))
    transformer.attention(q, k, v, causal=True)
    assert seen == [(128, 2, True, 0, 128, 128)] and pads == []


def test_latent_attention_through_the_symbols():
    """sym.LatentExpand and sym.GroupedQueryAttention(v_head_dim=)
    against the formula: k_h = [kn_h | kr], kr shared by all heads."""
    rows, T, nh, nope, rope, vd = 24, 12, 3, 8, 4, 6
    rs = np.random.RandomState(8)
    q = rs.randn(rows, nh * (nope + rope)).astype(np.float32)
    up = rs.randn(rows, nh * (nope + vd)).astype(np.float32)
    kr = rs.randn(rows, rope).astype(np.float32)
    kv = mx.sym.LatentExpand(mx.sym.Variable("up"), mx.sym.Variable("kr"),
                             num_heads=nh, key_dim=nope, value_dim=vd,
                             name="kv")
    assert kv.list_outputs() == ["kv_key", "kv_value"]
    net = mx.sym.GroupedQueryAttention(
        mx.sym.Variable("q"), kv[0], kv[1], num_heads=nh, num_kv_heads=nh,
        head_dim=nope + rope, v_head_dim=vd, seq_len=T, name="mla")
    assert net.infer_shape(q=q.shape, up=up.shape, kr=kr.shape)[1] \
        == [(rows, nh * vd)]
    got = net.bind(mx.cpu(), {"q": mx.nd.array(q), "up": mx.nd.array(up),
                              "kr": mx.nd.array(kr)}).forward()[0].asnumpy()
    per_head = up.reshape(rows, nh, nope + vd)
    k = np.concatenate([per_head[..., :nope],
                        np.broadcast_to(kr[:, None], (rows, nh, rope))], -1)

    def heads(t):
        return jnp.asarray(t.reshape(2, T, nh, -1).transpose(0, 2, 1, 3))
    want = _plain_attention(heads(q), heads(k), heads(per_head[..., nope:]),
                            True)
    np.testing.assert_allclose(
        got, np.asarray(want).transpose(0, 2, 1, 3).reshape(rows, nh * vd),
        atol=2e-5)
    # without the shared columns the scores are others
    cut = _plain_attention(heads(q)[..., :nope], heads(k)[..., :nope],
                           heads(per_head[..., nope:]), True)
    assert np.abs(np.asarray(cut) - np.asarray(want)).max() > 1e-2


# ------------------------------------------ output norm and convolution
def test_kda_output_norm_composed_against_the_plain_formula():
    """KDA's output norm as models/kimi_linear.py composes it: the norm
    per head first, ONE scale of a head's width, then a sigmoid gate."""
    rs = np.random.RandomState(2)
    x, z = (rs.randn(6, 16).astype(np.float32) for _ in range(2))
    g4 = np.linspace(0.5, 1.5, 4).astype(np.float32)
    net = mx.sym.RMSNorm(mx.sym.Variable("x"), eps=1e-5, width=4, name="n") \
        * mx.sym.Activation(mx.sym.Variable("z"), act_type="sigmoid")
    assert net.list_arguments() == ["x", "n_gamma", "z"]
    assert net.infer_shape(x=(6, 16), z=(6, 16))[0] \
        == [(6, 16), (4,), (6, 16)]
    got = net.bind(mx.cpu(), {"x": mx.nd.array(x), "n_gamma": mx.nd.array(g4),
                              "z": mx.nd.array(z)}).forward()[0].asnumpy()
    t = x.reshape(6, 4, 4)
    norm = t / np.sqrt((t ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        got, (norm * g4).reshape(6, 16) / (1 + np.exp(-z)),
        rtol=1e-5, atol=1e-6)


def _conv(attrs, *ins):
    return get_op("CausalConv1D").fcompute(
        dict(attrs, kernel=4, seq_len=7), list(ins), OpContext(True))[0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_conv_without_a_bias(dtype):
    """`no_bias`: two arguments, the plain formula without its bias,
    and the gradients of data and weight those with a bias of nought;
    with a bias the op is what it was, to the bit."""
    rs = np.random.RandomState(5)
    x, w, b, dy = (jnp.asarray(rs.randn(*s), dtype)
                   for s in ((14, 5), (5, 4), (5,), (14, 5)))
    net = mx.sym.CausalConv1D(mx.sym.Variable("data"), kernel=4, seq_len=7,
                              no_bias=True, name="conv")
    assert net.list_arguments() == ["data", "conv_weight"]
    assert net.infer_shape(data=(14, 5))[0] == [(14, 5), (5, 4)]
    assert mx.sym.CausalConv1D(mx.sym.Variable("data"), kernel=4, seq_len=7,
                               name="conv").list_arguments() \
        == ["data", "conv_weight", "conv_bias"]
    zero = jnp.zeros_like(b)
    f32 = lambda t: np.asarray(t, np.float32)           # noqa: E731
    np.testing.assert_array_equal(f32(_conv({"no_bias": True}, x, w)),
                                  f32(_conv({}, x, w, zero)))
    xp = jnp.pad(x.astype(jnp.float32).reshape(2, 7, 5),
                 ((0, 0), (3, 0), (0, 0)))
    plain = sum(w.astype(jnp.float32)[:, j] * xp[:, j:j + 7]
                for j in range(4)).reshape(14, 5)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=2.0 ** -8, atol=1e-6)
    np.testing.assert_allclose(f32(_conv({"no_bias": True}, x, w)), plain,
                               **tol)
    got = jax.vjp(lambda x, w: _conv({"no_bias": True}, x, w), x, w)[1](dy)
    want = jax.vjp(lambda x, w, b: _conv({}, x, w, b), x, w, zero)[1](dy)
    for a, c in zip(got, want[:2]):
        np.testing.assert_array_equal(f32(a), f32(c))
    # with a bias: PR 33's function itself
    direct = ssm._conv_and_back(4, 7, b.dtype)
    np.testing.assert_array_equal(f32(_conv({}, x, w, b)),
                                  f32(direct(x, w, b)))
    for a, c in zip(jax.vjp(lambda *t: _conv({}, *t), x, w, b)[1](dy),
                    jax.vjp(direct, x, w, b)[1](dy)):
        np.testing.assert_array_equal(f32(a), f32(c))


# -------------------------------------------------------------- experts
E, K, D_MODEL, F_EXP = 32, 3, 16, 24


def _moe_weights(seed=0):
    rs = np.random.RandomState(seed)
    draw = lambda *s: (0.3 * rs.randn(*s)).astype(np.float32)  # noqa: E731
    return {"router": rs.randn(E, D_MODEL).astype(np.float32),
            "gate": draw(E, F_EXP, D_MODEL), "up": draw(E, F_EXP, D_MODEL),
            "down": draw(E, D_MODEL, F_EXP),
            "shared_gate": draw(F_EXP, D_MODEL),
            "shared_up": draw(F_EXP, D_MODEL),
            "shared_down": draw(D_MODEL, F_EXP)}


def _gated(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd.T


def _uncut_layer(x, w, bias):
    """The whole layer the plain way: the shared expert, and every
    routed expert over every token with weight 0 where not chosen."""
    s = jax.nn.sigmoid(x @ w["router"].T)
    _, chosen = jax.lax.top_k(s + bias[None, :], K)
    wt = jnp.take_along_axis(s, chosen, -1)
    wt = 2.446 * wt / jnp.sum(wt, -1, keepdims=True)
    out = _gated(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(E):
        w_tok = jnp.sum(jnp.where(chosen == e, wt, 0.0), -1)
        out = out + w_tok[:, None] * _gated(x, w["gate"][e], w["up"][e],
                                            w["down"][e])
    return out


def _share(x, w, bias, first, count):
    """One chip's routed share through sym.MoE as the builder calls it."""
    net = mx.sym.MoE(mx.sym.Variable("data"), num_experts=E,
                     hidden_size=F_EXP, num_experts_per_tok=K,
                     experts_held=(first, count), score_func="sigmoid",
                     route_norm=True, route_scale=2.446, name="moe")
    sl = slice(first, first + count)
    args = {"data": x, "moe_router_weight": w["router"],
            "moe_experts_gate_weight": w["gate"][sl].reshape(-1, D_MODEL),
            "moe_experts_up_weight": w["up"][sl].reshape(-1, D_MODEL),
            "moe_experts_down_weight": w["down"][sl].reshape(-1, F_EXP)}
    ex = net.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in args.items()},
                  aux_states={"moe_router_bias": mx.nd.array(bias)})
    return ex.forward(is_train=True)[0].asnumpy()


def test_the_thirty_two_shares_add_up():
    """32 experts, one a share, 32 shares: the routed parts and the
    shared expert, counted once (every chip computes it alike), equal
    the uncut layer to float32 rounding."""
    x = np.random.RandomState(4).randn(48, D_MODEL).astype(np.float32)
    w = _moe_weights()
    bias = np.linspace(-0.05, 0.05, E).astype(np.float32)
    routed = sum(_share(x, w, bias, first, 1) for first in range(32))
    shared = np.asarray(_gated(x, w["shared_gate"], w["shared_up"],
                               w["shared_down"]))
    want = np.asarray(_uncut_layer(jnp.asarray(x), w, jnp.asarray(bias)))
    np.testing.assert_allclose(routed + shared, want, rtol=3e-5, atol=3e-5)
    assert np.abs(routed).max() > 0.1       # the shares are not nothing


# ---------------------------------------------------------------- model
TINY = dict(vocab_size=64, seq_len=24, hidden_size=32, num_hidden_layers=4,
            kda_layers=(1, 2, 3), full_attn_layers=(4,), kda_num_heads=4,
            kda_head_dim=8, chunk_size=8, num_attention_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
            num_experts=8, num_experts_per_token=2, experts_held=(2, 4))
ROWS, STEPS = 48, 4                     # two sequences a step


def _fit(remat, compute_dtype=None):
    from mxnet_tpu import models
    mx.random.seed(5)
    net = models.get_symbol("kimi_linear", remat=remat, **TINY)
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
    # 4 steps x 3 delta-rule layers x 2 sequences x 3 chunks, and the
    # float32 states at their starts: heads x head_dim x head_dim each
    assert counters["kda.chunks"] == STEPS * 3 * 2 * 3
    assert counters["kda.carried_bytes"] == STEPS * 3 * 2 * 3 * 4 * 8 * 8 * 4
    # one latent-attention layer: rows x heads x (12 + 8) float32
    assert counters["mla.expanded_kv_bytes"] == STEPS * ROWS * 4 * 20 * 4
    # 3 expert layers x 48 tokens x 2 choices a step, 4 of 8 held
    assert counters["moe.dropped"] == 0
    assert 0 < counters["moe.held_pairs"] < STEPS * 3 * ROWS * 2
    args, aux = mod.get_params()
    assert sorted(aux) == ["l%d_moe_router_bias" % i for i in (2, 3, 4)]
    assert args["l1_A_log_weight"].shape == (4,)
    assert args["l1_dt_bias"].shape == (32,)
    assert args["l1_kda_norm_gamma"].shape == (8,)
    assert args["l1_kda_q_conv_weight"].shape == (32, 4)
    assert "l1_kda_q_conv_bias" not in args         # no bias
    assert args["l1_dt_bias"].asnumpy().std() > 0   # it trains
    assert args["l4_mla_kv_up_weight"].shape == (4 * 16, 16)
    assert "l1_mlp_gate_weight" in args and "l2_mlp_gate_weight" not in args
    assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()
    assert _fit("full", "bfloat16").get_outputs()[0].asnumpy().std() > 0


def test_remat_changes_no_number_and_keeps_the_delta_rule():
    """What a segment keeps is the value it would have made again; the
    delta rule's output and its chunk-boundary states are among the
    bytes `remat.kept_bytes` counts, under the name the selective scan
    has."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.precision import policy
    a = _fit(None)
    b = _fit("full")
    kept = telemetry.last_fit()["counters"]["remat.kept_bytes"]
    pa, pb = a.get_params()[0], b.get_params()[0]
    for k in pa:
        # four steps on: a sum made again in another order is amplified
        # by the solves and the router's choices
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-3, atol=2e-4, err_msg=k)
    names = policy._KEPT_NAMES["full"]
    policy._KEPT_NAMES["full"] = tuple(n for n in names if n != policy.SCAN)
    try:
        _fit("full")
    finally:
        policy._KEPT_NAMES["full"] = names
    without = telemetry.last_fit()["counters"]["remat.kept_bytes"]
    # by layer inside a wrapped segment: y (rows x 32) and the states
    # (2 sequences x 3 chunks x 4 heads x 8 x 8), float32
    one = (ROWS * 32 + 2 * 3 * 4 * 8 * 8) * 4
    assert (kept - without) % (STEPS * one) == 0
    assert 1 <= (kept - without) // (STEPS * one) <= 3


def test_a_layer_in_both_lists_or_in_neither_is_refused():
    from mxnet_tpu import models
    with pytest.raises(ValueError, match="both or in neither"):
        models.get_symbol("kimi_linear", **dict(TINY, kda_layers=(1, 2)))
    with pytest.raises(ValueError, match="both or in neither"):
        models.get_symbol("kimi_linear", **dict(TINY,
                                                full_attn_layers=(3, 4)))
