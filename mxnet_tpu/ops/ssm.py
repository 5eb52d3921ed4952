"""State-space layer ops: a causal depthwise convolution along the
sequence, the Mamba-2 selective scan in its chunked form, and the gated
norm over groups of channels that follows it.

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
"""
from __future__ import annotations

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
def _conv_infer(attrs, in_shapes, aux):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None, aux
    in_shapes[1:] = [(data[-1], int(attrs["kernel"])), (data[-1],)]
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


@register("CausalConv1D", arg_names=("data", "weight", "bias"),
          attr_types={"kernel": int, "seq_len": int},
          required_attrs=("kernel", "seq_len"), infer_shape=_conv_infer)
def _causal_conv1d(attrs, ins, octx):
    """Depthwise convolution along each sequence of ``seq_len`` rows,
    looking back only: ``y[t] = bias + sum_j weight[:, j] *
    x[t - (kernel - 1) + j]``, with zeros before a sequence's first
    row.  data (rows, channels), weight (channels, kernel), bias
    (channels,).  Summed in float32.  Its gradient is written out, not
    left to jax: data's is the mirrored convolution of the output's,
    weight's and bias's one pass of float32 sums over the rows."""
    x, w, b = ins
    K, T = int(attrs["kernel"]), int(attrs["seq_len"])
    _sequences("CausalConv1D", x.shape[0], T)
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
