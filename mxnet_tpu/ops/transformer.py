"""Decoder-block ops: RMSNorm, RoPE and one causal grouped-query
attention, computed blockwise.

The block's rows are tokens: every op here takes ``(rows, width)``
activations, with ``rows = sequences * seq_len`` where positions
matter.  Statistics (a norm's mean square, the softmax's running
maximum and sum, the rotation's angles) are float32 whatever the
activation type; outputs come back in the activation type.

:func:`attention` is the one attention of the package:
``sym.GroupedQueryAttention`` calls it, and so does the single-device
path of ``sym.RingAttention`` (``parallel.ring_attention.local_attention``).
It never holds the ``T x T`` scores: on a TPU it is the library's
splash-attention kernel (``jax.experimental.pallas.ops.tpu``), whose
backward pass recomputes the scores block by block; elsewhere, and for
shapes the kernel does not take, a query block at a time under
``jax.checkpoint`` against the keys its mask leaves.
"""
from __future__ import annotations

import functools
import math

from ..precision.policy import ATTENTION, keep, note_kept
from ..registry import register, count as count_op


def _jnp():
    import jax.numpy as jnp
    return jnp


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _key_range(q_lo, q_hi, n_keys, causal, window):
    """The keys a block of queries [q_lo, q_hi) can see, as [lo, hi)."""
    hi = min(q_hi, n_keys) if causal else n_keys
    lo = max(0, q_lo - window + 1) if window else 0
    return lo, hi


def _attend_block(q, k, v, q_lo, k_lo, causal, window, scale):
    """One block of queries against its keys, scores in float32.
    q: (B, G, R, tq, D); k, v: (B, G, tk, D)."""
    import jax
    jnp = _jnp()
    f32 = jnp.float32
    s = jnp.einsum("bgrqd,bgkd->bgrqk", q, k,
                   preferred_element_type=f32) * scale
    if causal or window:
        i = q_lo + jnp.arange(q.shape[3])[:, None]
        j = k_lo + jnp.arange(k.shape[2])[None, :]
        keep = jnp.ones(i.shape[:1] + j.shape[1:], bool)
        if causal:
            keep = keep & (j <= i)
        if window:
            keep = keep & (i - j < window)
        s = jnp.where(keep, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(v.dtype), v,
                      preferred_element_type=f32).astype(q.dtype)


def _blockwise(q, k, v, causal, window, scale, block):
    """Query blocks in turn, each against the keys its mask leaves and
    each under ``jax.checkpoint``: the backward pass recomputes a
    block's scores and keeps none.  The result carries the name the
    kernel path gives its own, so a remat policy keeps the same set on
    both paths."""
    import jax
    jnp = _jnp()
    B, H, T, D = q.shape
    G, S = k.shape[1], k.shape[2]
    qg = q.reshape(B, G, H // G, T, D)
    outs = []
    for q_lo in range(0, T, block):
        q_hi = min(T, q_lo + block)
        k_lo, k_hi = _key_range(q_lo, q_hi, S, causal, window)
        fn = jax.checkpoint(functools.partial(
            _attend_block, q_lo=q_lo, k_lo=k_lo, causal=causal,
            window=window, scale=scale))
        outs.append(fn(qg[:, :, :, q_lo:q_hi], k[:, :, k_lo:k_hi],
                       v[:, :, k_lo:k_hi]))
    return keep(jnp.concatenate(outs, axis=3).reshape(B, H, T, v.shape[-1]),
                ATTENTION)


# True runs the TPU kernel off the TPU, under the Pallas interpreter:
# the tests patch it, nothing else sets it
_INTERPRET = False


def _splash_kernel(T, R, causal, window, block, block_dkv=None):
    """The library kernel for one key-value head and its R query heads
    (multi-query form).  Built anew in every trace: it holds its mask's
    block tables as arrays of the trace that made it.  ``block_dkv``
    (default ``block``) is the backward kernel's block of keys: it
    writes the queries' gradient once a block of keys, T / block_dkv
    partial copies."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    if window:
        mask = sm.LocalMask((T, T), (window - 1, 0 if causal else None), 0)
    elif causal:
        mask = sm.CausalMask((T, T))
    else:
        mask = sm.FullMask((T, T))
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block_dkv or block,
        block_kv_dkv_compute=block, use_fused_bwd_kernel=True)
    # the kernel names its output and its log-sum-exp inside its
    # custom_vjp: a remat policy that keeps the name hands them to the
    # backward kernels and the forward one is not run again
    return sk.make_splash_mqa(sm.MultiHeadMask([mask] * R),
                              block_sizes=sizes, head_shards=1,
                              q_seq_shards=1,
                              residual_checkpoint_name=ATTENTION,
                              interpret=_INTERPRET)


def _whole_lanes(t):
    """t with zero columns up to the next whole number of the kernel's
    128 lanes: a zero column of a query or of a key adds nothing to a
    score."""
    short = -t.shape[-1] % 128
    if not short:
        return t
    return _jnp().pad(t, [(0, 0)] * (t.ndim - 1) + [(0, short)])


def _splash(q, k, v, causal, window, scale, block):
    import jax
    B, H, T, D = q.shape
    G = k.shape[1]
    qg = _whole_lanes((q * scale).astype(q.dtype)
                      .reshape(B, G, H // G, T, D))
    # queries wider than 128 lanes: as many fewer partial copies of
    # their gradient, so that they take what a 128-wide head's do
    block_dkv = block * (qg.shape[-1] // 128)
    kernel = _splash_kernel(T, H // G, causal, window, block,
                            block_dkv if T % block_dkv == 0 else block)
    with jax.named_scope("splash_attention"):
        out = jax.vmap(jax.vmap(kernel))(qg, _whole_lanes(k), v)
    # what the kernel named: its output and a float32 log-sum-exp a query
    note_kept(ATTENTION, out.size * out.dtype.itemsize + B * H * T * 4)
    return out.reshape(B, H, T, v.shape[-1]).astype(q.dtype)


def attention(q, k, v, causal=False, window=0, scale=None):
    """Softmax attention of q (B, H, T, D) over k (B, G, S, D) and
    v (B, G, S, Dv) with H a multiple of G (H // G query heads share a
    key-value head); the values may be of another width than the keys,
    and the result is (B, H, T, Dv).

    ``causal`` drops keys after the query; ``window`` (0: none) also
    drops keys ``window`` or more positions before it.  On a TPU, where
    the shapes allow, the library kernel (a key width that is no whole
    number of its 128 lanes reaches it with zero columns added);
    elsewhere the blockwise path.
    """
    import jax
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    window = int(window or 0)
    if window >= max(T, S):
        window = 0
    block = next((b for b in (512, 256, 128) if T % b == 0), None)
    fits = block is not None and T == S and v.shape[-1] % 128 == 0
    if fits and (jax.default_backend() == "tpu" or _INTERPRET):
        return _splash(q, k, v, causal, window, scale, block)
    return _blockwise(q, k, v, causal, window, scale, block or 512)


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------
def _rms_infer(attrs, in_shapes, aux):
    data = in_shapes[0]
    if data is None:
        return in_shapes, None, aux
    in_shapes[1] = (int(attrs.get("width") or data[-1]),)
    return in_shapes, [tuple(data)], aux


@register("RMSNorm", arg_names=("data", "gamma"),
          attr_types={"eps": float, "width": int}, infer_shape=_rms_infer)
def _rms_norm(attrs, ins, octx):
    """y = x / sqrt(mean(x^2) + eps) * gamma over the last axis, or,
    with ``width``, over each run of ``width`` values of it (a norm per
    head with one learned scale for all heads).  The mean square is
    taken in float32."""
    import jax
    jnp = _jnp()
    x, gamma = ins
    eps = float(attrs.get("eps", 1e-5))
    width = int(attrs.get("width") or x.shape[-1])
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, width))
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
    return [y.reshape(x.shape).astype(x.dtype)]


@register("RoPE", attr_types={"head_dim": int, "seq_len": int,
                               "theta": float},
          required_attrs=("head_dim", "seq_len"))
def _rope(attrs, ins, octx):
    """Rotary position embedding over whole heads (the two halves of a
    head are the rotation's pairs).  data: (rows, heads * head_dim);
    a row's position is its index within its sequence of ``seq_len``."""
    jnp = _jnp()
    x = ins[0]
    D, T = int(attrs["head_dim"]), int(attrs["seq_len"])
    theta = float(attrs.get("theta", 10000.0))
    rows = x.shape[0]
    if rows % T:
        raise ValueError("RoPE: %d rows are no whole number of sequences "
                         "of %d" % (rows, T))
    f32 = jnp.float32
    pos = (jnp.arange(rows) % T).astype(f32)
    inv = theta ** (-jnp.arange(0, D, 2, dtype=f32) / D)
    ang = pos[:, None] * inv[None, :]                      # (rows, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(f32).reshape(rows, -1, D)
    a, b = x32[..., :D // 2], x32[..., D // 2:]
    y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return [y.reshape(x.shape).astype(x.dtype)]


def _gqa_args(attrs):
    return ("query", "key", "value", "gate") if attrs.get("gated", False) \
        else ("query", "key", "value")


def _gqa_infer(attrs, in_shapes, aux):
    q = in_shapes[0]
    if q is None:
        return in_shapes, None, aux
    out = tuple(q)
    if attrs.get("v_head_dim"):
        out = (q[0], int(attrs["num_heads"]) * int(attrs["v_head_dim"]))
    return in_shapes, [out], aux


@register("GroupedQueryAttention", arg_names=_gqa_args,
          attr_types={"num_heads": int, "num_kv_heads": int,
                      "head_dim": int, "v_head_dim": int, "seq_len": int,
                      "window": int, "causal": bool, "gated": bool,
                      "scale": float},
          required_attrs=("num_heads", "num_kv_heads", "head_dim",
                          "seq_len"),
          infer_shape=_gqa_infer)
def _grouped_query_attention(attrs, ins, octx):
    """Causal attention of rows cut into sequences of ``seq_len``:
    query (rows, num_heads * head_dim), key
    (rows, num_kv_heads * head_dim), value
    (rows, num_kv_heads * v_head_dim); ``v_head_dim`` defaults to
    ``head_dim``, and the output is (rows, num_heads * v_head_dim).
    ``window`` > 0 also drops keys that many or more positions back;
    with ``gated`` the output is multiplied by sigmoid(gate), gate
    shaped like the output."""
    import jax
    jnp = _jnp()
    H, G = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    D, T = int(attrs["head_dim"]), int(attrs["seq_len"])
    Dv = int(attrs.get("v_head_dim") or D)
    q, k, v = ins[:3]
    rows = q.shape[0]
    if rows % T:
        raise ValueError("GroupedQueryAttention: %d rows are no whole "
                         "number of sequences of %d" % (rows, T))

    def heads(t, n, width=D):
        return t.reshape(rows // T, T, n, width).transpose(0, 2, 1, 3)

    o = attention(heads(q, H), heads(k, G), heads(v, G, Dv),
                  causal=bool(attrs.get("causal", True)),
                  window=int(attrs.get("window", 0) or 0),
                  scale=attrs.get("scale"))
    o = o.transpose(0, 2, 1, 3).reshape(rows, H * Dv)
    if attrs.get("gated", False):
        gate = ins[3].astype(jnp.float32)
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(q.dtype)
    return [o]


def _latent_infer(attrs, in_shapes, aux):
    data, shared = in_shapes
    if data is None or shared is None:
        return in_shapes, None, aux
    H = int(attrs["num_heads"])
    kd, vd = int(attrs["key_dim"]), int(attrs["value_dim"])
    in_shapes[0] = (data[0], H * (kd + vd))
    return in_shapes, [(data[0], H * (kd + shared[-1])),
                       (data[0], H * vd)], aux


@register("LatentExpand", arg_names=("data", "shared_key"),
          attr_types={"num_heads": int, "key_dim": int, "value_dim": int},
          required_attrs=("num_heads", "key_dim", "value_dim"),
          infer_shape=_latent_infer, num_outputs=2,
          out_names=("key", "value"), counters=("mla.expanded_kv_bytes",))
def _latent_expand(attrs, ins, octx):
    """The per-head keys and values of latent attention from the
    latent's up-projection and the part of a key all heads share.
    data (rows, num_heads * (key_dim + value_dim)), a head's
    ``key_dim`` values of its key followed by its ``value_dim`` of its
    value; shared_key (rows, R).  Outputs key
    (rows, num_heads * (key_dim + R)), every head's own part followed
    by the shared one, and value (rows, num_heads * value_dim): what
    ``sym.GroupedQueryAttention(head_dim=key_dim + R,
    v_head_dim=value_dim)`` takes.  Counts ``mla.expanded_kv_bytes``,
    the bytes of both: what a kernel that reads the latent itself would
    not write."""
    import jax
    jnp = _jnp()
    data, shared = ins
    H = int(attrs["num_heads"])
    kd, vd = int(attrs["key_dim"]), int(attrs["value_dim"])
    rows = data.shape[0]
    with jax.named_scope("mx.mla.expand"):
        per_head = data.reshape(rows, H, kd + vd)
        key = jnp.concatenate(
            [per_head[..., :kd],
             jnp.broadcast_to(shared[:, None, :].astype(data.dtype),
                              (rows, H, shared.shape[-1]))], axis=-1)
        value = per_head[..., kd:]
    key, value = key.reshape(rows, -1), value.reshape(rows, -1)
    count_op("mla.expanded_kv_bytes",
             (key.size + value.size) * key.dtype.itemsize)
    return [key, value]
