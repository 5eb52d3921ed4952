"""State-space layer ops: a causal depthwise convolution along the
sequence, the Mamba-2 selective scan and the gated delta rule in their
chunked forms, and the gated norm over groups of channels that follows
the scan.

Rows are tokens, as in :mod:`.transformer`: every op takes
``(rows, width)`` activations with ``rows = sequences * seq_len``, and
nothing crosses from one sequence into the next.  Time steps, decays
and the carried state are float32 whatever the activation type; the
matrix products take the activation type's operands and sum in float32.

The scan, per head, with state ``S`` (head_dim x state) from 0::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t . C_t + D * x_t

is computed a chunk of ``chunk`` tokens at a time (the state-space
dual form): inside a chunk as products over all chunks at once, and
between chunks by ONE short ``lax.scan`` over the chunk states, so a
sequence of 8,192 costs 64 sequential steps, not 8,192.  jax
differentiates it through that form.

The gated delta rule (Kimi Delta Attention), per head, with state ``S``
(head_dim x head_dim) from 0, a decay per channel and a correction of
rank one each step::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

multiplies its state by a MATRIX each step, so its chunked form
(:func:`gated_delta_chunked`) has a unit-triangular solve inside each
chunk and a carry of two matrix products a chunk.  The solve makes each
chunk's inverse by halving down to blocks of ``_DIRECT_ROWS`` rows,
solved by substitution with the chunks across the lanes and merged by
products, and multiplies; its backward pass reads that inverse and
inverts nothing (:func:`_unit_lower_solver`).  The decayed inner
products of a chunk's rows and keys, and their gradient, are on a TPU
two Pallas kernels of this module (``_intra_forward_kernel``,
``_intra_backward_kernel``) and elsewhere ``_decayed_products``: one
arithmetic, two carriers.  So is the carry (``_delta_steps``, the same
recurrence run forward and, as its own backward pass, reversed): on a
TPU ``_carry_forward_kernel`` and ``_carry_backward_kernel`` hold the
state in fast memory across the chunks.
"""
from __future__ import annotations

import functools

from ..precision.policy import SCAN, keep
from ..registry import register, count as count_op


def _sequences(name, rows, seq_len):
    if rows % seq_len:
        raise ValueError("%s: %d rows are no whole number of sequences "
                         "of %d" % (name, rows, seq_len))
    return rows // seq_len


# ---------------------------------------------------------------------------
# causal depthwise convolution
# ---------------------------------------------------------------------------
def _conv_args(attrs):
    return ("data", "weight") if attrs.get("no_bias", False) \
        else ("data", "weight", "bias")


def _conv_infer(attrs, in_shapes, aux):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None, aux
    in_shapes[1:] = [(data[-1], int(attrs["kernel"])), (data[-1],)][
        :len(in_shapes) - 1]
    return in_shapes, [tuple(data)], aux


def _conv_and_back(K, T, bias_dtype):
    """conv(x, w, b) over x (S * T, C), and its backward pass:

        dx[t]    = sum_j w[:, j] * dy[t + (K-1) - j]
        dw[:, j] = sum_{s,t} dy[s, t] * x[s, t - (K-1) + j]
        db       = sum_{s,t} dy[s, t]

    the mirrored convolution (zeros past a sequence's last row) and one
    pass of reductions.  Left to jax, the transpose of the forward sum
    writes the K products ``w[:, j] * dy`` to memory in float32 before
    it shifts and adds them.  Operands stay in their own type in
    memory; every product and sum is float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def shifted(a, before, after):
        """a (S, T, C) as K float32 views of T rows, the j-th j rows
        further down the zero-padded sequence: upcast behind the pad
        and the slice, so no float32 copy of the padded array."""
        ap = jnp.pad(a, ((0, 0), (before, after), (0, 0)))
        return [ap[:, j:j + T].astype(f32) for j in range(K)]

    @jax.custom_vjp
    def conv(x, w, b):
        with jax.named_scope("mx.ssm.conv"):
            w = w.astype(f32)
            xs = shifted(x.reshape(-1, T, x.shape[-1]), K - 1, 0)
            y = sum(w[:, j] * xs[j] for j in range(K)) + b.astype(f32)
            return y.reshape(x.shape).astype(x.dtype)

    def conv_fwd(x, w, b):
        return conv(x, w, b), (x, w)

    def conv_bwd(res, dy):
        x, w = res
        with jax.named_scope("mx.ssm.conv"):
            w32 = w.astype(f32)
            dy = dy.reshape(-1, T, dy.shape[-1])
            dys = shifted(dy, 0, K - 1)
            dx = sum(w32[:, j] * dys[K - 1 - j] for j in range(K))
            dy32 = dy.astype(f32)
            xs = shifted(x.reshape(dy.shape), K - 1, 0)
            dw = jnp.stack([jnp.sum(dy32 * xs[j], axis=(0, 1))
                            for j in range(K)], axis=1)
            db = jnp.sum(dy32, axis=(0, 1))
            return (dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype),
                    db.astype(bias_dtype))

    conv.defvjp(conv_fwd, conv_bwd)
    return conv


@register("CausalConv1D", arg_names=_conv_args,
          attr_types={"kernel": int, "seq_len": int, "no_bias": bool},
          required_attrs=("kernel", "seq_len"), infer_shape=_conv_infer)
def _causal_conv1d(attrs, ins, octx):
    """Depthwise convolution along each sequence of ``seq_len`` rows,
    looking back only: ``y[t] = bias + sum_j weight[:, j] *
    x[t - (kernel - 1) + j]``, with zeros before a sequence's first
    row.  data (rows, channels), weight (channels, kernel), bias
    (channels,); with ``no_bias`` there is no bias and no such
    argument.  Summed in float32.  Its gradient is written out, not
    left to jax: data's is the mirrored convolution of the output's,
    weight's and bias's one pass of float32 sums over the rows."""
    import jax.numpy as jnp
    x, w = ins[:2]
    K, T = int(attrs["kernel"]), int(attrs["seq_len"])
    _sequences("CausalConv1D", x.shape[0], T)
    if attrs.get("no_bias", False):
        # the same two passes with a bias of nought, whose gradient
        # nobody reads
        b = jnp.zeros((x.shape[-1],), w.dtype)
    else:
        b = ins[2]
    return [_conv_and_back(K, T, b.dtype)(x, w, b)]


# ---------------------------------------------------------------------------
# the chunked selective scan
# ---------------------------------------------------------------------------
def _carry(own, decay):
    """The state at each chunk's start, from the chunks' own states
    (S, chunks, G, R, P, N) and their whole decays (S, chunks, G, R):
    ``S_in[0] = 0, S_in[c+1] = decay[c] * S_in[c] + own[c]``."""
    import jax
    import jax.numpy as jnp

    def step(state, chunk_c):
        own_c, decay_c = chunk_c
        return decay_c[..., None, None] * state + own_c, state
    _, s_in = jax.lax.scan(step, jnp.zeros_like(own[:, 0]),
                           (own.swapaxes(0, 1), decay.swapaxes(0, 1)))
    return s_in.swapaxes(0, 1)


def ssd_chunked(x, dt, B, C, A, D, chunk):
    """The recurrence of the module's head, chunk by chunk.

    x (S, T, G, R, P) in the activation type: heads as G groups of R,
    head_dim P; dt (S, T, G, R) float32, already positive; B, C
    (S, T, G, N): a group's heads share them; A, D (G, R) float32.
    T is a whole number of chunks.  Returns (y like x, float32; the
    states at the chunks' starts (S, chunks, G, R, P, N), float32).

    With l_t the running sum of dt * A inside a chunk (never positive,
    so no decay below passes 1)::

        Y_intra = ((C B^T) * L) (dt * X)       L_ts = exp(l_t - l_s), s <= t
        own state  = sum_s exp(l_Q - l_s) dt_s x_s (x) B_s
        S_in[c+1]  = exp(l_Q[c]) S_in[c] + own state[c]     (the carry)
        Y_inter_t  = exp(l_t) S_in C_t
    """
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    S, T, G, R, P = x.shape
    N, Q = B.shape[-1], chunk
    nc = T // Q
    cdt = x.dtype
    xs = x.reshape(S, nc, Q, G, R, P).astype(f32)
    dts = dt.reshape(S, nc, Q, G, R)
    Bs, Cs = B.reshape(S, nc, Q, G, N), C.reshape(S, nc, Q, G, N)
    la = jnp.cumsum(dts * A, axis=2)                     # l: (S, nc, Q, G, R)
    last = la[:, :, -1:]                                 # l_Q

    with jax.named_scope("mx.ssm.intra"):
        cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cs, Bs,
                        preferred_element_type=f32)
        lt = la.transpose(0, 1, 3, 4, 2)                 # (S, nc, G, R, Q)
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        # masked before the exponential: above the diagonal l_t - l_s
        # is positive and may overflow
        decay = jnp.exp(jnp.where(causal, lt[..., :, None] - lt[..., None, :],
                                  -jnp.inf))
        m = (cb[:, :, :, None] * decay).astype(cdt)      # (S, nc, G, R, Q, Q)
        y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", m,
                       (xs * dts[..., None]).astype(cdt),
                       preferred_element_type=f32)
    with jax.named_scope("mx.ssm.states"):
        to_end = (dts * jnp.exp(last - la))[..., None]
        own = jnp.einsum("bcsgrp,bcsgn->bcgrpn", (xs * to_end).astype(cdt),
                         Bs, preferred_element_type=f32)
    with jax.named_scope("mx.ssm.carry"):
        # the chunk-boundary states: what the backward pass of the
        # carry and of the product below reads
        s_in = keep(_carry(own, jnp.exp(last[:, :, 0])), SCAN)
    with jax.named_scope("mx.ssm.inter"):
        y = y + jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cs, s_in.astype(cdt),
                           preferred_element_type=f32) \
            * jnp.exp(la)[..., None]
    y = y + D[..., None] * xs
    return y.reshape(S, T, G, R, P), s_in


def _ssd_infer(attrs, in_shapes, aux):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None, aux
    H, GN = int(attrs["heads"]), int(attrs["groups"]) * int(attrs["state"])
    rows = data[0]
    in_shapes[1:] = [(rows, H), (rows, GN), (rows, GN), (H,), (H,), (H,)]
    return in_shapes, [tuple(data)], aux


@register("SSD", arg_names=("data", "dt", "B", "C", "A_log", "dt_bias", "D"),
          attr_types={"heads": int, "head_dim": int, "groups": int,
                      "state": int, "chunk": int, "seq_len": int},
          required_attrs=("heads", "head_dim", "groups", "state", "seq_len"),
          infer_shape=_ssd_infer,
          counters=("ssm.chunks", "ssm.carried_bytes"))
def _ssd(attrs, ins, octx):
    """The Mamba-2 selective scan over rows cut into sequences of
    ``seq_len``.  data (rows, heads * head_dim); dt (rows, heads);
    B and C (rows, groups * state), head h reading group
    h // (heads / groups); per head A_log, dt_bias, D.  With
    ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, per head
    and from a zero state at each sequence's start:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t C_t + D x_t``.  Computed ``chunk`` tokens at a time
    (default 128; ``seq_len`` a whole number of them) with one carry of
    ``seq_len / chunk`` steps between the chunks; time steps, decays and
    states in float32.  Counts ``ssm.chunks`` and ``ssm.carried_bytes``
    (the chunk-boundary states the carry passes)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    x, dt, B, C, A_log, dt_bias, D = ins
    H, P = int(attrs["heads"]), int(attrs["head_dim"])
    G, N = int(attrs["groups"]), int(attrs["state"])
    T = int(attrs["seq_len"])
    Q = min(int(attrs.get("chunk", 128)), T)
    S = _sequences("SSD", x.shape[0], T)
    if T % Q or H % G:
        raise ValueError("SSD: seq_len %d is no whole number of chunks of "
                         "%d, or %d heads of groups of %d" % (T, Q, H, G))
    R = H // G
    A = -jnp.exp(A_log.astype(f32)).reshape(G, R)
    dtv = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    y, s_in = ssd_chunked(
        x.reshape(S, T, G, R, P), dtv.reshape(S, T, G, R),
        B.reshape(S, T, G, N), C.reshape(S, T, G, N), A,
        D.astype(f32).reshape(G, R), Q)
    count_op("ssm.chunks", S * (T // Q))
    count_op("ssm.carried_bytes", s_in.size * s_in.dtype.itemsize)
    # dear to make again: a segment's backward pass is handed it
    return [keep(y.reshape(x.shape).astype(x.dtype), SCAN)]


# ---------------------------------------------------------------------------
# the chunked gated delta rule
# ---------------------------------------------------------------------------
# Below this many rows a block of decayed inner products is computed
# pair by pair (every exponent taken on its own); above it a block is
# halved and the half below the diagonal is one matrix product.
_DIRECT_ROWS = 16
# added to the sum of squares under the root of q's and k's lengths
_L2_EPS = 1e-6


def _decayed_products(rows, k, G):
    """``A[n][r, i] = sum_c rows_n[r, c] k[i, c] exp(G[r, c] - G[i, c])``
    for ``i <= r`` and 0 above the diagonal, for each array of
    ``rows``.  rows_n, k, G (..., Q, d) float32; G is a running sum of
    decays that are never positive, so ``G[r] - G[i] <= 0`` wherever it
    is taken: no exponent here is ever positive, however strong the
    decay.

    A block of Q rows is cut in two.  Its half below the diagonal
    (rows of the second half against keys of the first) is factored
    around the last row of the first half, ``G_m``:
    ``exp(G_r - G_i) = exp(G_r - G_m) exp(G_m - G_i)`` with both
    exponents <= 0, so it is ONE matrix product of decayed rows with
    decayed keys; the two halves on the diagonal are blocks of their
    own.  At ``_DIRECT_ROWS`` rows or fewer a block is computed pair by
    pair, masked before the exponential."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST      # float32 operands, as stated
    Q = k.shape[-2]
    if Q > _DIRECT_ROWS and Q % 2 == 0:
        m = Q // 2
        ref = G[..., m - 1:m, :]
        k_dec = k[..., :m, :] * jnp.exp(ref - G[..., :m, :])
        r_dec = jnp.exp(G[..., m:, :] - ref)
        below = [jnp.einsum("...rc,...ic->...ri", r[..., m:, :] * r_dec,
                            k_dec, precision=hi) for r in rows]
        first = _decayed_products([r[..., :m, :] for r in rows],
                                  k[..., :m, :], G[..., :m, :])
        second = _decayed_products([r[..., m:, :] for r in rows],
                                   k[..., m:, :], G[..., m:, :])
        return [jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([b, c], axis=-1)], axis=-2)
            for a, b, c in zip(first, below, second)]
    seen = jnp.tril(jnp.ones((Q, Q), bool))[..., None]
    decay = jnp.exp(jnp.where(seen, G[..., :, None, :] - G[..., None, :, :],
                              -jnp.inf))
    return [jnp.sum(r[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
            for r in rows]


# True runs the TPU kernels off the TPU, under the Pallas interpreter:
# the tests patch it, nothing else sets it
_INTERPRET = False
# a register's lanes; a grid step of the kernels takes that many blocks
# of ``_DIRECT_ROWS`` tokens, one to a lane
_LANES = 128
# rows between the j-th and the (j + 1)-th tokens' planes of a scratch
# that is read back a block at a time: 128 and a register more, so that
# the strided read splits in two and not in eight
_PLANE = 136
# chunks a turn of the kernels' loop over chunks takes: the products of
# one are too short to fill the matrix unit's pipeline
_CHUNKS = 4
# heads a grid step of the carry's kernel takes: their states (64 KiB a
# head of 128 by 128) stay in fast memory from chunk to chunk
_CARRY_HEADS = 16


def _transposed(x):
    """x (128, w) as (w, 128) by way of a square of 128: a width short
    of it is made up with zeros first."""
    import jax.numpy as jnp
    w = x.shape[1]
    if w < _LANES:
        x = jnp.concatenate(
            [x, jnp.zeros((_LANES, _LANES - w), x.dtype)], axis=1)
    return x.T[:w]


def _untransposed(xt):
    """The way back: xt (w, 128) as (128, w)."""
    import jax.numpy as jnp
    w = xt.shape[0]
    if w < _LANES:
        xt = jnp.concatenate(
            [xt, jnp.zeros((_LANES - w, _LANES), xt.dtype)], axis=0)
    return xt.T[:, :w]


def _second_halves(x, size):
    """The rows of x (Q, w) that lie in the second half of their block
    of ``size``, in order: (Q / 2, w)."""
    import jax.numpy as jnp
    return jnp.concatenate([x[s + size // 2:s + size]
                            for s in range(0, x.shape[0], size)], axis=0)


def _to_second_halves(x, size):
    """The way back: rows (Q / 2, w) to their places in (Q, w), zeros
    in the first halves."""
    import jax.numpy as jnp
    m = size // 2
    nought = jnp.zeros((m, x.shape[1]), x.dtype)
    return jnp.concatenate(
        [t for s in range(0, x.shape[0], m) for t in (nought, x[s:s + m])],
        axis=0)


def _halves(g, size):
    """For the blocks of ``size`` rows of g (Q, d), each cut in two:
    the decay of every row of a first half to its block's cut, and from
    the cut to every row of a second half, (Q, d) each and 0 in the
    other half.  The cut is the first half's last row, so no exponent
    is positive."""
    import jax
    import jax.numpy as jnp
    m = size // 2
    cut = jnp.concatenate([
        jnp.broadcast_to(g[s + m - 1:s + m], (size, g.shape[1]))
        for s in range(0, g.shape[0], size)], axis=0)
    second = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) % size >= m
    e = jnp.exp(jnp.where(second, g - cut, cut - g))
    return jnp.where(second, 0.0, e), jnp.where(second, e, 0.0)


def _level(k, q, g, size):
    """A chunk's operands at one level of halving: the two decays of
    ``_halves``, the decayed keys (Q, d), and the second halves'
    decayed rows, k's over q's (Q, d)."""
    import jax.numpy as jnp
    first, second = _halves(g, size)
    rows = jnp.concatenate([_second_halves(k * second, size),
                            _second_halves(q * second, size)], axis=0)
    return first, second, k * first, rows


def _same_block(Q, size):
    """(Q, Q) mask over the rows ``_second_halves`` picks of k's and of
    q's (Q / 2 each) against a chunk's Q keys: row and key in the same
    block of ``size``."""
    import jax
    import jax.numpy as jnp
    r = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) % (Q // 2)
    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return r // (size // 2) == i // size


def _own_columns(p, Q):
    """A block's pairs p (_DIRECT_ROWS keys, 128 blocks) at their place
    among the block's chunk's Q columns, 0 elsewhere: (Q, 128)."""
    import jax
    import jax.numpy as jnp
    B = _DIRECT_ROWS
    in_chunk = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) % (Q // B)
    return jnp.concatenate([jnp.where(in_chunk == b, p, 0.0)
                            for b in range(Q // B)], axis=0)


def _from_own_columns(wide, Q):
    """The way back: (Q, 128) to (_DIRECT_ROWS, 128)."""
    import jax
    import jax.numpy as jnp
    B = _DIRECT_ROWS
    in_chunk = jax.lax.broadcasted_iota(jnp.int32, (B, _LANES), 1) % (Q // B)
    own = wide[:B]
    for b in range(1, Q // B):
        own = jnp.where(in_chunk == b, wide[b * B:(b + 1) * B], own)
    return own


def _to_lanes(refs, planes):
    """Each of ``refs`` (2048, d) into its scratch of ``planes``
    (_DIRECT_ROWS, d, 128): the j-th tokens of all 128 blocks, channels
    down and a block to a lane."""
    from jax.experimental import pallas as pl
    for j in range(_DIRECT_ROWS):
        for ref, plane in zip(refs, planes):
            plane[j] = _transposed(
                ref[pl.ds(j, _LANES, stride=_DIRECT_ROWS), :])


def _blocks_rows(ref, n, Q):
    """Rows of chunk n, (Q, w), from a scratch that holds the j-th rows
    of all 128 blocks at ``ref[_PLANE * j:_PLANE * j + 128]``."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    B = _DIRECT_ROWS
    return jnp.concatenate([
        ref[pl.ds(n * (Q // B) + b, B, stride=_PLANE), :]
        for b in range(Q // B)], axis=0)


def _intra_forward_kernel(Q, k_ref, q_ref, g_ref, akk_ref, aqk_ref,
                          kt_ref, qt_ref, gt_ref, pkk_ref, pqk_ref,
                          okk_ref, oqk_ref):
    """``_decayed_products([k, q], k, G)`` for 128 blocks of
    ``_DIRECT_ROWS`` tokens (whole chunks of Q): the refs hold
    (2048, d) and (2048, Q); the last seven are scratch.  The same
    arithmetic as the function above, carried otherwise.

    The blocks on the diagonal, pair by pair: the j-th tokens of all
    128 blocks lie a block to a lane with the channels down
    (``kt_ref[j]`` (d, 128)), so a row j and a key i <= j of every
    block meet register by register with no shift, no exponent is
    taken above the diagonal, and the sum over the channels is a sum
    of registers.

    The halves below the diagonal, a chunk and a level of halving at a
    time: one product of the second halves' decayed rows (k's over
    q's) with the chunk's decayed keys, masked to the blocks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    B = _DIRECT_ROWS
    d = k_ref.shape[1]

    _to_lanes((k_ref, q_ref, g_ref), (kt_ref, qt_ref, gt_ref))
    pkk_ref[...] = jnp.zeros(pkk_ref.shape, f32)
    pqk_ref[...] = jnp.zeros(pqk_ref.shape, f32)

    def pair(n, carry):
        # ONE loop over rows and keys, the pairs above the diagonal
        # passed over: sixteen loops of their own cost the trace sixteen
        # bodies
        j, i = n // B, n % B

        @pl.when(i <= j)        # so the exponent is never positive
        def _():
            skk = sqk = jnp.zeros((8, _LANES), f32)
            for c in range(0, d, 8):        # a register of 8 channels
                ke = kt_ref[i, c:c + 8] * jnp.exp(
                    gt_ref[j, c:c + 8] - gt_ref[i, c:c + 8])
                skk = skk + kt_ref[j, c:c + 8] * ke
                sqk = sqk + qt_ref[j, c:c + 8] * ke
            pkk_ref[j, pl.ds(i, 1), :] = jnp.sum(skk, axis=0, keepdims=True)
            pqk_ref[j, pl.ds(i, 1), :] = jnp.sum(sqk, axis=0, keepdims=True)
        return carry
    jax.lax.fori_loop(0, B * B, pair, 0)
    for j in range(B):
        plane = pl.ds(_PLANE * j, _LANES)
        okk_ref[plane, :] = _untransposed(_own_columns(pkk_ref[j], Q))
        oqk_ref[plane, :] = _untransposed(_own_columns(pqk_ref[j], Q))

    def chunks(turn, carry):
        for n in [turn * _CHUNKS + i for i in range(_CHUNKS)]:
            at = pl.ds(pl.multiple_of(n * Q, Q), Q)
            k, q, g = k_ref[at, :], q_ref[at, :], g_ref[at, :]
            akk, aqk = _blocks_rows(okk_ref, n, Q), _blocks_rows(oqk_ref, n, Q)
            size = Q
            while size > B:
                _, _, keys, rows = _level(k, q, g, size)
                p = jax.lax.dot_general(
                    rows, keys, (((1,), (1,)), ((), ())), precision=hi,
                    preferred_element_type=f32)
                if size < Q:
                    p = jnp.where(_same_block(Q, size), p, 0.0)
                akk = akk + _to_second_halves(p[:Q // 2], size)
                aqk = aqk + _to_second_halves(p[Q // 2:], size)
                size //= 2
            akk_ref[at, :] = akk
            aqk_ref[at, :] = aqk
        return carry
    jax.lax.fori_loop(0, B * _LANES // Q // _CHUNKS, chunks, 0)


def _intra_backward_kernel(Q, k_ref, q_ref, g_ref, dakk_ref, daqk_ref,
                           dk_ref, dq_ref, dg_ref, kt_ref, qt_ref, gt_ref,
                           ckk_ref, cqk_ref, rk_ref, rq_ref, key_ref,
                           ork_ref, orq_ref, okey_ref):
    """The backward pass of the kernel above from its inputs and the two
    cotangents, the decays made again (the last eleven refs are
    scratch).  For a pair
    ``A[r, i] = sum_c R[r, c] K[i, c] E[r, i, c]``::

        dR[r, c] = sum_i dA[r, i] K[i, c] E[r, i, c]
        dK[i, c] = sum_r dA[r, i] R[r, c] E[r, i, c]
        dG = R * dR - K * dK

    summed over the two pairs (k's rows and q's rows against k's keys);
    the factored halves the same through their one product, whose
    operands are the decayed rows and keys."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    B = _DIRECT_ROWS
    d = k_ref.shape[1]

    _to_lanes((k_ref, q_ref, g_ref), (kt_ref, qt_ref, gt_ref))
    for j in range(B):
        every = pl.ds(j, _LANES, stride=B)      # the j-th of each block
        ckk_ref[j] = _from_own_columns(_transposed(dakk_ref[every, :]), Q)
        cqk_ref[j] = _from_own_columns(_transposed(daqk_ref[every, :]), Q)

    def channels(n, carry):
        # a register of 8 channels at a time, every pair inside it: the
        # keys' sums stay in registers
        c = pl.ds(pl.multiple_of(n * 8, 8), 8)
        key = [jnp.zeros((8, _LANES), f32)] * B
        for j in range(B):
            k_j, q_j, g_j = kt_ref[j, c], qt_ref[j, c], gt_ref[j, c]
            rk = rq = jnp.zeros((8, _LANES), f32)
            for i in range(j + 1):
                ckk = ckk_ref[j, i:i + 1, :]
                cqk = cqk_ref[j, i:i + 1, :]
                # i <= j: the exponent is never positive
                e = jnp.exp(g_j - gt_ref[i, c])
                ke = kt_ref[i, c] * e
                rk, rq = rk + ckk * ke, rq + cqk * ke
                key[i] = key[i] + (ckk * k_j + cqk * q_j) * e
            rk_ref[j, c], rq_ref[j, c] = rk, rq
        for i in range(B):
            key_ref[i, c] = key[i]
        return carry
    jax.lax.fori_loop(0, d // 8, channels, 0)
    for j in range(B):
        plane = pl.ds(_PLANE * j, _LANES)
        ork_ref[plane, :] = _untransposed(rk_ref[j])
        orq_ref[plane, :] = _untransposed(rq_ref[j])
        okey_ref[plane, :] = _untransposed(key_ref[j])

    def chunks(turn, carry):
        for n in [turn * _CHUNKS + i for i in range(_CHUNKS)]:
            at = pl.ds(pl.multiple_of(n * Q, Q), Q)
            k, q, g = k_ref[at, :], q_ref[at, :], g_ref[at, :]
            dakk, daqk = dakk_ref[at, :], daqk_ref[at, :]
            # k's gradient as rows, q's, and k's as keys
            drk, drq, dkey = (_blocks_rows(ork_ref, n, Q),
                              _blocks_rows(orq_ref, n, Q),
                              _blocks_rows(okey_ref, n, Q))
            size = Q
            while size > B:
                first, second, keys, rows = _level(k, q, g, size)
                dp = jnp.concatenate([_second_halves(dakk, size),
                                      _second_halves(daqk, size)], axis=0)
                if size < Q:
                    dp = jnp.where(_same_block(Q, size), dp, 0.0)
                d_rows = jax.lax.dot_general(
                    dp, keys, (((1,), (0,)), ((), ())), precision=hi,
                    preferred_element_type=f32)
                d_keys = jax.lax.dot_general(
                    dp, rows, (((0,), (0,)), ((), ())), precision=hi,
                    preferred_element_type=f32)
                drk = drk + second * _to_second_halves(d_rows[:Q // 2], size)
                drq = drq + second * _to_second_halves(d_rows[Q // 2:], size)
                dkey = dkey + first * d_keys
                size //= 2
            dk_ref[at, :] = drk + dkey
            dq_ref[at, :] = drq
            dg_ref[at, :] = k * drk + q * drq - k * dkey
        return carry
    jax.lax.fori_loop(0, B * _LANES // Q // _CHUNKS, chunks, 0)


def _intra_call(kernel, Q, ins, widths, scratch, interpret):
    """``kernel`` over arrays (tokens, width) cut into grid steps of 128
    blocks of ``_DIRECT_ROWS`` tokens; outputs (tokens, w) float32 for w
    in ``widths``, scratch float32 of the shapes in ``scratch``.
    Tokens short of a whole step are made up with zeros, which give
    zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    step = _DIRECT_ROWS * _LANES
    tokens = ins[0].shape[0]
    short = -tokens % step
    if short:
        ins = [jnp.pad(t, ((0, short), (0, 0))) for t in ins]

    def block(width):
        return pl.BlockSpec((step, width), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(kernel, Q),
        out_shape=[jax.ShapeDtypeStruct((tokens + short, w), jnp.float32)
                   for w in widths],
        grid=((tokens + short) // step,),
        in_specs=[block(t.shape[1]) for t in ins],
        out_specs=[block(w) for w in widths],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret)(*ins)
    return [o[:tokens] for o in outs] if short else outs


def _kernel_fits(k, q, G):
    """Whether Akk and Aqk of these chunks go through the kernels: on a
    TPU (or under the interpreter, for the tests), float32 operands, a
    head of a register's 128 lanes (the kernels read the j-th rows of
    128 blocks in one strided access, which the compiler takes of
    arrays 128 wide only) and a chunk of 16, 32, 64 or 128 rows (a
    block of ``_DIRECT_ROWS`` halved back from it, and a whole number
    of chunks to 128 blocks)."""
    import jax
    import jax.numpy as jnp
    Q, d = k.shape[-2:]
    return ((jax.default_backend() == "tpu" or _INTERPRET)
            and all(t.dtype == jnp.float32 for t in (k, q, G))
            and d == _LANES and Q in (_DIRECT_ROWS << n for n in range(4)))


@functools.lru_cache(maxsize=None)
def _intra_kernels(Q, d, interpret=False):
    """(k, q, G) (..., Q, d) -> (Akk, Aqk) (..., Q, Q) by the two
    kernels, under the scope the function above runs under (a
    ``custom_vjp``'s backward function loses the scope unless it enters
    it again).  One object a shape for the whole process, and its two
    calls under ``jax.jit(inline=True)``: a kernel's ten thousand
    instructions are traced once, not once a layer and pass (which cost
    a step of four layers a minute and a half of set-up on every run),
    while each call still lowers where it stands, under its own node's
    and pass's name."""
    import jax
    B = _DIRECT_ROWS
    lanes, pairs = [(B, d, _LANES)] * 3, [(B, B, _LANES)] * 2

    @functools.partial(jax.jit, inline=True)
    def made(k, q, G):
        flat = [t.reshape(-1, d) for t in (k, q, G)]
        return tuple(a.reshape(k.shape[:-1] + (Q,)) for a in _intra_call(
            _intra_forward_kernel, Q, flat, (Q, Q),
            lanes + pairs + [(B * _PLANE, Q)] * 2, interpret))

    @functools.partial(jax.jit, inline=True)
    def given(k, q, G, dakk, daqk):
        flat = [t.reshape(-1, d) for t in (k, q, G)] \
            + [t.reshape(-1, Q) for t in (dakk, daqk)]
        return tuple(t.reshape(k.shape) for t in _intra_call(
            _intra_backward_kernel, Q, flat, (d, d, d),
            lanes + pairs + lanes + [(B * _PLANE, d)] * 3, interpret))

    @jax.custom_vjp
    def products(k, q, G):
        with jax.named_scope("mx.kda.intra"):
            return made(k, q, G)

    def forward(k, q, G):
        return products(k, q, G), (k, q, G)

    def backward(res, cts):
        with jax.named_scope("mx.kda.intra"):
            return given(*res, *cts)

    products.defvjp(forward, backward)
    return products


def _unit_lower_inverse(L):
    """The inverses of unit lower-triangular systems L (n, n, ...),
    read below the diagonal only, a system to each place of the
    trailing axes (so that the systems lie across the lanes, and a row
    of one is a row of every one).

    A system of more than ``_DIRECT_ROWS`` rows, and an even count, is
    halved: its two halves on the diagonal are inverted at once as
    systems of their own, and merged by
    ``T21 = -T22 L21 T11`` under ``T11`` and ``T22``.  At
    ``_DIRECT_ROWS`` rows or fewer, or an odd count, forward
    substitution: row i of the inverse is ``e_i`` less the rows above
    it weighed by ``L[i]``.  Every product is of float32 elements."""
    import jax.numpy as jnp
    n = L.shape[0]
    if n > _DIRECT_ROWS and n % 2 == 0:
        m = n // 2
        both = _unit_lower_inverse(jnp.stack([L[:m, :m], L[m:, m:]], axis=2))
        t11, t22 = both[:, :, 0], both[:, :, 1]
        t22_l21 = jnp.sum(t22[:, :, None] * L[m:, :m][None], axis=1)
        t21 = -jnp.sum(t22_l21[:, :, None] * t11[None], axis=1)
        return jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=1),
            jnp.concatenate([t21, t22], axis=1)], axis=0)
    eye = jnp.eye(n, dtype=L.dtype).reshape((n, n) + (1,) * (L.ndim - 2))
    t = jnp.broadcast_to(eye[:1], (1, n) + L.shape[2:])
    for i in range(1, n):
        row = eye[i] - jnp.sum(L[i, :i, None] * t, axis=0)
        t = jnp.concatenate([t, row[None]], axis=0)
    return t


@functools.lru_cache(maxsize=None)
def _unit_lower_solver():
    """``X = T rhs`` with ``T`` the inverse of the unit lower-triangular
    ``system`` (..., n, n), read below the diagonal only; rhs
    (..., n, w).  ``T`` is made by ``_unit_lower_inverse`` with the
    systems moved across the lanes, and moved back for the product.
    The backward pass reads ``T`` and ``X`` and inverts nothing::

        g_rhs    = T^T g
        g_system = -tril(g_rhs X^T, -1)

    The products are float32 at ``HIGHEST``.  The backward function
    enters the solve's scope again: a ``custom_vjp``'s backward function
    loses the scope it was called under."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def inverse_and_product(system, rhs):
        lanes = jnp.moveaxis(system, (-2, -1), (0, 1))
        # the barrier keeps XLA from laying the inverse out as the product
        # reads it, which puts a system's 16 and 32 columns across 128
        # lanes (a v5e: 3.3 ms where 2.0 do for 4,096 systems of 64)
        t = jax.lax.optimization_barrier(_unit_lower_inverse(lanes))
        t = jnp.moveaxis(t, (0, 1), (-2, -1))
        return t, jnp.matmul(t, rhs, precision=hi)

    @jax.custom_vjp
    def solve(system, rhs):
        return inverse_and_product(system, rhs)[1]

    def forward(system, rhs):
        t, x = inverse_and_product(system, rhs)
        return x, (t, x)

    def backward(res, g):
        t, x = res
        with jax.named_scope("mx.kda.solve"):
            g_rhs = jnp.einsum("...ji,...jc->...ic", t, g, precision=hi)
            g_system = jnp.einsum("...ic,...jc->...ij", g_rhs, x,
                                  precision=hi)
            n = t.shape[-1]
            below = jnp.tril(jnp.ones((n, n), bool), -1)
            return jnp.where(below, -g_system, 0.0), g_rhs

    solve.defvjp(forward, backward)
    return solve


def _unit_lower_solve(system, rhs):
    """``system^-1 rhs`` for unit lower-triangular systems (..., n, n),
    read below the diagonal only, and rhs (..., n, w); float32."""
    return _unit_lower_solver()(system, rhs)


def _delta_steps(w, u, k, decay, extra=None, reverse=False, sign=1,
                 want_x=True):
    """The carry's recurrence by ``lax.scan``, a chunk a step, in the
    chunks' order or (``reverse``) against it; R float32 from zero::

        x[c] = u[c] - sign * w[c] bf16(R)
        R   <- Diag(decay[c]) R + sign * k[c]^T bf16(x[c]) + extra[c]

    w, k (S, chunks, H, Q, d) in the activation type (bf16 above);
    u (S, chunks, H, Q, dv) and extra (S, chunks, H, d, dv) float32,
    extra None for zero; decay (S, chunks, H, d) float32.  Returns R at
    each chunk's start (before its step) and x, at the chunk's place;
    x None where not ``want_x``.  Both products take the activation
    type's operands and sum in float32.  This is the carry off the TPU
    and at any shape ``_carry_kernel_fits`` turns away, and the tests'
    oracle for the carry's kernels."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    cdt = w.dtype

    def step(state, chunk_c):
        w_c, u_c, k_c, decay_c, extra_c = chunk_c
        p = jnp.einsum("bhqd,bhde->bhqe", w_c, state.astype(cdt),
                       preferred_element_type=f32)
        x = u_c - p if sign > 0 else u_c + p
        p = jnp.einsum("bhqd,bhqe->bhde", k_c, x.astype(cdt),
                       preferred_element_type=f32)
        new = decay_c[..., None] * state + (p if sign > 0 else -p)
        if extra_c is not None:
            new = new + extra_c
        return new, (state, x) if want_x else state
    S, _nc, H, _Q, d = w.shape
    _, out = jax.lax.scan(
        step, jnp.zeros((S, H, d, u.shape[-1]), f32),
        tuple(None if t is None else t.swapaxes(0, 1)
              for t in (w, u, k, decay, extra)), reverse=reverse)
    r, x = out if want_x else (out, None)
    return r.swapaxes(0, 1), None if x is None else x.swapaxes(0, 1)


def _carry_forward_kernel(heads, w_ref, u_ref, k_ref, decay_ref, s_ref,
                          state_ref):
    """One grid step of the carry: ``heads`` heads of one chunk, the
    recurrence of ``_delta_steps`` with the state of those heads in
    ``state_ref`` (fast memory, heads x d x dv float32) from chunk to
    chunk, zero at the chunk axis's first step; the step writes the
    state it started from, ``s_in``.  The decays come as (d, heads), a
    head's a column, so that a row of the state is scaled by its
    channel's decay with no transpose."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    cdt = w_ref.dtype
    tn = (((0,), (0,)), ((), ()))           # a^T with b

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, f32)
    decay = decay_ref[...]
    for j in range(heads):
        state = state_ref[j]
        s_ref[j] = state
        x = u_ref[j] - jnp.dot(w_ref[j], state.astype(cdt),
                               preferred_element_type=f32)
        p = jax.lax.dot_general(k_ref[j], x.astype(cdt), tn,
                                preferred_element_type=f32)
        state_ref[j] = decay[:, j:j + 1] * state + p


def _carry_backward_kernel(heads, w_ref, u_ref, k_ref, decay_ref, s_ref,
                           gd_ref, gs_ref, gw_ref, gu_ref, gk_ref,
                           gdecay_ref, state_ref):
    """The forward kernel's backward pass, a grid step of it: the
    recurrence run against the chunks' order with ``w' = -k``,
    ``u' = g_delta``, ``k' = -w`` and ``extra = g_s`` (gd_ref and
    gs_ref, the cotangents of delta and of s_in), so that the state it
    starts a chunk with is ``H``, the cotangent of the state after the
    chunk, and its x is ``g_u = k H + g_delta``; the chunk's other
    gradients are taken while both are in fast memory::

        g_w     = -bf16(g_u) bf16(s_in)^T
        g_k     = bf16(u - w bf16(s_in)) bf16(H)^T
        g_decay = sum over the values' axis of s_in * H

    g_decay is written as the decays come, (d, heads)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    cdt = w_ref.dtype
    nt = (((1,), (1,)), ((), ()))           # a product with b^T
    tn = (((0,), (0,)), ((), ()))           # a^T with b

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, f32)
    decay = decay_ref[...]
    sums = []
    for j in range(heads):
        h = state_ref[j]
        hb = h.astype(cdt)
        gu = gd_ref[j] + jnp.dot(k_ref[j], hb, preferred_element_type=f32)
        gu_ref[j] = gu
        gub = gu.astype(cdt)
        s = s_ref[j]
        sb = s.astype(cdt)
        gw_ref[j] = (-jax.lax.dot_general(
            gub, sb, nt, preferred_element_type=f32)).astype(gw_ref.dtype)
        delta = u_ref[j] - jnp.dot(w_ref[j], sb, preferred_element_type=f32)
        gk_ref[j] = jax.lax.dot_general(
            delta.astype(cdt), hb, nt,
            preferred_element_type=f32).astype(gk_ref.dtype)
        sums.append(jnp.sum(s * h, axis=1, keepdims=True))
        p = jax.lax.dot_general(w_ref[j], gub, tn,
                                preferred_element_type=f32)
        state_ref[j] = decay[:, j:j + 1] * h - p + gs_ref[j]
    gdecay_ref[...] = jnp.concatenate(sums, axis=1)


def _carry_call(w, u, k, decay, grads=None, interpret=False):
    """``_carry_forward_kernel`` over a grid of (sequence, block of
    ``_CARRY_HEADS`` heads, chunk), the chunk axis walked in order, or
    ``_carry_backward_kernel`` with it walked backwards where ``grads``
    (s_in, g_delta, g_s) asks for the backward pass.  Each grid step
    reads one chunk's operands and writes its results.  Forward: s_in;
    backward: (g_w, g_u, g_k, g_decay)."""
    import math
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    S, nc, H, Q, d = w.shape
    dv = u.shape[-1]
    hb = math.gcd(H, _CARRY_HEADS)
    backward = grads is not None

    def at(s, h, i):
        return (s, nc - 1 - i if backward else i, h, 0, 0)

    def rows(width):
        return pl.BlockSpec((None, None, hb, Q, width), at)
    states = pl.BlockSpec((None, None, hb, d, dv), at)
    # (S, nc, H / hb, d, hb): a head's decays a column of its block
    cols = pl.BlockSpec((None, None, None, d, hb), at)
    ins = [w, u, k, decay.reshape(S, nc, H // hb, hb, d).swapaxes(-1, -2)]
    specs = [rows(d), rows(dv), rows(d), cols]
    if backward:
        ins += list(grads)
        specs += [states, rows(dv), states]
        out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype)
                     for t in (w, u, k)] \
            + [jax.ShapeDtypeStruct((S, nc, H // hb, d, hb), f32)]
        out_specs = [rows(d), rows(dv), rows(d), cols]
    else:
        out_shape = jax.ShapeDtypeStruct((S, nc, H, d, dv), f32)
        out_specs = states
    out = pl.pallas_call(
        functools.partial(_carry_backward_kernel if backward
                          else _carry_forward_kernel, hb),
        out_shape=out_shape, grid=(S, H // hb, nc), in_specs=specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((hb, d, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret)(*ins)
    if not backward:
        return out
    gw, gu, gk, gdecay = out
    return gw, gu, gk, gdecay.swapaxes(-1, -2).reshape(decay.shape)


def _carry_kernel_fits(w, u):
    """Whether the carry goes through its kernels: on a TPU (or
    under the interpreter, for the tests), a head of whole registers'
    lanes for keys and values alike, and a chunk of 16 to 128 rows in
    whole tiles of bfloat16 (16 rows)."""
    import jax
    Q, d = w.shape[-2:]
    return ((jax.default_backend() == "tpu" or _INTERPRET)
            and d % _LANES == 0 and u.shape[-1] % _LANES == 0
            and Q % 16 == 0 and 16 <= Q <= 128)


@functools.lru_cache(maxsize=None)
def _delta_carrier(kernel, interpret=False):
    """``(w, u, k_end, decay) -> (s_in, delta)`` under ``jax.custom_vjp``:
    the states by ``_carry_call`` where ``kernel``, else by
    ``_delta_steps``, and ``delta = u - w bf16(s_in)`` from them for
    all chunks at once.  It keeps its inputs and s_in, which it names
    for the remat policies where it makes it: a segment's backward pass
    makes only the batched delta again, and runs no carry.

    The backward pass, given the cotangents of delta and of s_in, is
    the recurrence run against the chunks' order with ``w' = -k``,
    ``u' = g_delta``, ``k' = -w`` and ``extra = g_s``: the state it
    hands back at chunk c is ``H``, the cotangent of the state after
    the chunk, and its x is ``g_u = k H + g_delta``; then::

        g_w     = -bf16(g_u) bf16(s_in)^T
        g_k     = bf16(delta) bf16(H)^T
        g_decay = sum over the values' axis of s_in * H

    for all chunks at once on the scan, a chunk at a time inside the
    reversed kernel.  One object a carrier, its two passes under
    ``jax.jit(inline=True)`` (traced once a shape); the backward
    function enters the carry's scope again."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    f32 = jnp.float32

    def delta_of(w, u, s_in):
        return u - jnp.einsum("bnhqd,bnhde->bnhqe", w, s_in.astype(w.dtype),
                              preferred_element_type=f32)

    @functools.partial(jax.jit, inline=True)
    def made(w, u, k, decay):
        if kernel:
            return _carry_call(w, u, k, decay, interpret=interpret)
        return _delta_steps(w, u, k, decay, want_x=False)[0]

    @functools.partial(jax.jit, inline=True)
    def given(w, u, k, decay, s_in, g_delta, g_s):
        if kernel:
            return _carry_call(w, u, k, decay, (s_in, g_delta, g_s),
                               interpret=interpret)
        h, gu = _delta_steps(k, g_delta, w, decay, g_s, reverse=True,
                             sign=-1)
        cdt = w.dtype
        gw = -jnp.einsum("bnhqe,bnhde->bnhqd", gu.astype(cdt),
                         s_in.astype(cdt), preferred_element_type=f32)
        gk = jnp.einsum("bnhqe,bnhde->bnhqd", delta_of(w, u, s_in).astype(
            cdt), h.astype(cdt), preferred_element_type=f32)
        return (gw.astype(w.dtype), gu, gk.astype(k.dtype),
                jnp.sum(s_in * h, axis=-1))

    @jax.custom_vjp
    def carry(w, u, k, decay):
        s_in = made(w, u, k, decay)
        return s_in, delta_of(w, u, s_in)

    def forward(w, u, k, decay):
        s_in = checkpoint_name(made(w, u, k, decay), SCAN)
        return (s_in, delta_of(w, u, s_in)), (w, u, k, decay, s_in)

    def backward(res, cts):
        g_s, g_delta = cts
        with jax.named_scope("mx.kda.carry"):
            return given(*res, g_delta, g_s)

    carry.defvjp(forward, backward)
    return carry


def _delta_carry(w, u, k_end, decay):
    """The state at each chunk's start and each chunk's corrected
    values, from the chunks' solved W (S, chunks, H, Q, d), U
    (S, chunks, H, Q, dv), keys decayed to the chunk's end and the
    chunk's whole decay (S, chunks, H, d)::

        delta[c] = U[c] - W[c] S[c]
        S[c + 1] = Diag(decay[c]) S[c] + k_end[c]^T delta[c]     S[0] = 0

    two matrix products a step: by ``_carry_forward_kernel``, the state
    in fast memory, where ``_carry_kernel_fits``, else by ``lax.scan``;
    delta from the states afterwards, for all chunks at once.  The state
    is float32; the products take W's type's operands and sum in
    float32."""
    return _delta_carrier(_carry_kernel_fits(w, u), _INTERPRET)(
        w, u, k_end, decay)


def gated_delta_chunked(q, k, v, g, beta, chunk):
    """The gated delta rule of the module's head, chunk by chunk.

    q, k (S, T, H, d) float32 (normalised, q scaled); v (S, T, H, dv)
    in the activation type; g (S, T, H, d) float32, the log of the
    decay, never positive; beta (S, T, H) float32.  T is a whole number
    of chunks.  Returns (o (S, T, H, dv) float32; the states at the
    chunks' starts (S, chunks, H, d, dv), float32; the chunks whose Akk
    and Aqk the kernels made: all where ``_kernel_fits``, else 0; the
    chunks the carry's kernel went through: all where
    ``_carry_kernel_fits``, else 0).

    With ``G_r`` the running sum of g inside a chunk and ``S_0`` the
    state at its start::

        Akk[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])    i < r
        Aqk[r, i] likewise with q_r                             i <= r
        (I + tril(diag(beta) Akk, -1)) [W | U] = diag(beta) [K exp(G) | V]
        O   = (Q exp(G)) S_0 + Aqk (U - W S_0)
        S_Q = Diag(exp(G_Q)) S_0 + (K exp(G_Q - G))^T (U - W S_0)

    Running sums, decays, Akk, Aqk, the solve and the state are
    float32; the four products with the state and with ``U - W S_0``
    take the activation type's operands and sum in float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    S, T, H, d = k.shape
    Q, nc, cdt = chunk, T // chunk, v.dtype

    def chunks(t):      # (S, T, H, w) -> (S, chunks, H, Q, w)
        return t.reshape(S, nc, Q, H, t.shape[-1]).transpose(0, 1, 3, 2, 4)

    qs, ks, vs = chunks(q), chunks(k), chunks(v).astype(f32)
    bs = chunks(beta[..., None])                         # (S, nc, H, Q, 1)
    with jax.named_scope("mx.kda.gate"):
        # the running sum as a product with a triangle of ones
        # (`cumsum` over an inner axis is a window reduction on a TPU)
        G = jnp.einsum("rq,bnhqc->bnhrc", jnp.tril(jnp.ones((Q, Q), f32)),
                       chunks(g), precision=hi)
        last = G[..., -1:, :]
    kernels = _kernel_fits(ks, qs, G)
    if kernels:
        akk, aqk = _intra_kernels(Q, d, _INTERPRET)(ks, qs, G)
    else:
        with jax.named_scope("mx.kda.intra"):
            akk, aqk = _decayed_products([ks, qs], ks, G)
    with jax.named_scope("mx.kda.solve"):
        below = jnp.tril(jnp.ones((Q, Q), bool), -1)
        system = jnp.where(below, bs * akk, 0.0) + jnp.eye(Q, dtype=f32)
        wu = _unit_lower_solve(
            system, bs * jnp.concatenate([ks * jnp.exp(G), vs], axis=-1))
        w, u = wu[..., :d].astype(cdt), wu[..., d:]
    with jax.named_scope("mx.kda.carry"):
        # the chunk-boundary states: what the backward pass of the
        # carry and of the products below reads
        carried = _carry_kernel_fits(w, u)
        s_in, delta = _delta_carry(
            w, u, (ks * jnp.exp(last - G)).astype(cdt),
            jnp.exp(last[..., 0, :]))
        s_in = keep(s_in, SCAN)
    with jax.named_scope("mx.kda.inter"):
        o = jnp.einsum("bnhqd,bnhde->bnhqe", (qs * jnp.exp(G)).astype(cdt),
                       s_in.astype(cdt), preferred_element_type=f32) \
            + jnp.einsum("bnhri,bnhie->bnhre", aqk.astype(cdt),
                         delta.astype(cdt), preferred_element_type=f32)
    return (o.transpose(0, 1, 3, 2, 4).reshape(S, T, H, -1), s_in,
            S * nc if kernels else 0, S * nc if carried else 0)


def _delta_infer(attrs, in_shapes, aux):
    q = in_shapes[0]
    if q is None:
        return in_shapes, None, aux
    H = int(attrs["heads"])
    rows, width = q[0], H * int(attrs["head_dim"])
    in_shapes[:] = [(rows, width)] * 4 + [(rows, H), (H,), (width,)]
    return in_shapes, [(rows, width)], aux


@register("GatedDeltaRule",
          arg_names=("query", "key", "value", "gate", "beta", "A_log",
                     "dt_bias"),
          attr_types={"heads": int, "head_dim": int, "chunk": int,
                      "seq_len": int},
          required_attrs=("heads", "head_dim", "seq_len"),
          infer_shape=_delta_infer,
          counters=("kda.chunks", "kda.carried_bytes", "kda.kernel_chunks",
                    "kda.carry_kernel_chunks"))
def _gated_delta_rule(attrs, ins, octx):
    """The gated delta rule (Kimi Delta Attention) over rows cut into
    sequences of ``seq_len``.  query, key, value and gate
    (rows, heads * head_dim); beta (rows, heads); A_log (heads,),
    dt_bias (heads * head_dim,).  With q and k normalised to length 1
    over each head (``x / sqrt(sum x^2 + 1e-6)``), q scaled by
    ``head_dim ** -0.5``, ``g = -exp(A_log) * softplus(gate + dt_bias)``,
    ``alpha = exp(g)`` and ``beta = sigmoid(beta)``, per head and from a
    zero state at each sequence's start:
    ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
    + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``.  Computed ``chunk``
    tokens at a time (default 64; ``seq_len`` a whole number of them)
    with one unit-triangular solve a chunk and one carry of
    ``seq_len / chunk`` steps between the chunks; gates, decays, the
    solve and the state in float32.  The solve inverts each chunk's
    system by halving it down to blocks of at most 16 rows, solved by
    substitution and merged by products, then multiplies; its backward
    pass takes two products with that inverse and inverts nothing.
    On a TPU the carry is a Pallas kernel whose grid walks the chunks
    with a block of heads' state held in fast memory, and elsewhere (or
    at a head that is not whole lanes wide, or a chunk that is not 16
    to 128 rows in tiles of 16) a ``lax.scan`` of the same arithmetic.
    Its backward pass is the same recurrence run against the chunks'
    order (a second kernel on a TPU), which hands back the state's
    cotangent chunk by chunk and the chunk's gradients with it; it
    reads the boundary states the forward pass kept, so no pass makes
    the carry again.  Counts ``kda.chunks``,
    ``kda.carried_bytes`` (the chunk-boundary states the carry passes),
    ``kda.kernel_chunks`` (the chunks whose decayed inner products
    the kernels made: all of them or none) and
    ``kda.carry_kernel_chunks`` (the chunks the carry's kernel went
    through: all of them or none)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    q, k, v, gate, beta, A_log, dt_bias = ins
    H, D = int(attrs["heads"]), int(attrs["head_dim"])
    T = int(attrs["seq_len"])
    Q = min(int(attrs.get("chunk", 64)), T)
    S = _sequences("GatedDeltaRule", q.shape[0], T)
    if T % Q:
        raise ValueError("GatedDeltaRule: seq_len %d is no whole number of "
                         "chunks of %d" % (T, Q))

    def heads(t):
        return t.reshape(S, T, H, -1)

    with jax.named_scope("mx.kda.norm"):
        def unit(t):
            t = heads(t).astype(f32)
            return t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), axis=-1, keepdims=True) + _L2_EPS)
        qn, kn = unit(q) * D ** -0.5, unit(k)
    with jax.named_scope("mx.kda.gate"):
        g = -jnp.exp(A_log.astype(f32))[:, None] * heads(
            jax.nn.softplus(gate.astype(f32) + dt_bias.astype(f32)))
        b = jax.nn.sigmoid(beta.astype(f32)).reshape(S, T, H)
    o, s_in, by_kernel, carried = gated_delta_chunked(
        qn, kn, heads(v), g, b, Q)
    count_op("kda.chunks", S * (T // Q))
    count_op("kda.kernel_chunks", by_kernel)
    count_op("kda.carry_kernel_chunks", carried)
    count_op("kda.carried_bytes", s_in.size * s_in.dtype.itemsize)
    # dear to make again: a segment's backward pass is handed it
    return [keep(o.reshape(v.shape).astype(v.dtype), SCAN)]


# ---------------------------------------------------------------------------
# the gated norm after the scan
# ---------------------------------------------------------------------------
def _gated_norm_infer(attrs, in_shapes, aux):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None, aux
    in_shapes[1:] = [tuple(data), (data[-1],)]
    return in_shapes, [tuple(data)], aux


@register("GatedRMSNorm", arg_names=("data", "gate", "gamma"),
          attr_types={"eps": float, "groups": int},
          infer_shape=_gated_norm_infer)
def _gated_rms_norm(attrs, ins, octx):
    """RMSNorm of ``data * silu(gate)`` over each of ``groups`` equal
    runs of the last axis, under a learned scale of the full width
    (``sym.RMSNorm(width=)`` learns one scale of a run's width).
    Gate and mean square in float32."""
    import jax
    import jax.numpy as jnp
    x, gate, gamma = ins
    eps = float(attrs.get("eps", 1e-5))
    groups = int(attrs.get("groups", 1))
    with jax.named_scope("mx.ssm.norm"):
        x32 = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        x32 = x32.reshape(x.shape[:-1] + (groups, -1))
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = (x32 * jax.lax.rsqrt(ms + eps)).reshape(x.shape) \
            * gamma.astype(jnp.float32)
        return [y.astype(x.dtype)]
