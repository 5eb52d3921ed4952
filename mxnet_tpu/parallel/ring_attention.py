"""Ring attention — sequence/context parallelism over ICI.

The reference has no long-context story beyond bucketing + BPTT unrolling
(SURVEY.md §5 "Long-context"); this is the TPU-native replacement: shard the
sequence axis over mesh devices, keep Q local, and rotate K/V blocks around
the ring with ``lax.ppermute`` while accumulating flash-style online softmax
(running max + denominator), so attention over a sequence of length S costs
O(S/dev) memory per chip and the K/V transfers ride the ICI ring concurrently
with compute.

``ring_attention`` is the shard_map-able core; ``ring_self_attention`` wraps
it over a Mesh axis for direct use.
"""
from __future__ import annotations

from functools import partial

__all__ = ["ring_attention", "ring_self_attention", "local_attention"]


def _block_attn(jnp, q, k, v, mask, m_prev, l_prev, o_prev, scale):
    """One block of streaming-softmax attention accumulation.

    q: (B, H, Tq, D); k/v: (B, H, Tk, D); mask broadcastable (Tq, Tk).
    Carries the flash-attention running statistics (m, l, o).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    l_corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * l_corr + jnp.sum(p, axis=-1)
    o_new = o_prev * l_corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Blockwise ring attention inside shard_map.

    q, k, v: local shards (B, H, T_local, D), sequence sharded over
    ``axis_name``. Returns the local output shard (B, H, T_local, D).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_dev = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    q32 = q.astype(jnp.float32)

    def mask_for(kv_idx):
        if not causal:
            return None
        q_pos = my_idx * T + jnp.arange(T)[:, None]
        k_pos = kv_idx * T + jnp.arange(T)[None, :]
        return q_pos >= k_pos

    def body(step, carry):
        m, l, o, kc, vc = carry
        kv_idx = (my_idx - step) % n_dev
        m, l, o = _block_attn(jnp, q32, kc.astype(jnp.float32),
                              vc.astype(jnp.float32), mask_for(kv_idx),
                              m, l, o, scale)
        # rotate k/v one hop around the ring (overlaps with next compute)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return m, l, o, kc, vc

    m0 = jnp.full((B, H, T), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    carry = (m0, l0, o0, k, v)
    for step in range(n_dev):  # static unroll: n_dev is a compile-time const
        carry = body(step, carry)
    m, l, o, _, _ = carry
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def local_attention(q, k, v, causal=False, scale=None):
    """Single-device attention over (B, H, T, D): the package's one
    blockwise attention (``ops.transformer.attention``), here with as
    many key-value heads as query heads and no window."""
    from ..ops.transformer import attention
    return attention(q, k, v, causal=causal, scale=scale)


def ring_self_attention(mesh, axis="sp"):
    """Build a jitted ring-attention fn over ``mesh``'s sequence axis.

    Inputs (B, H, S, D) arrive sequence-sharded on ``axis``; output has the
    same sharding. Usage::

        attn = ring_self_attention(mesh)
        out = attn(q, k, v, causal=True)
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    spec = P(None, None, axis, None)

    def build(causal):
        fn = shard_map(
            partial(ring_attention, axis_name=axis, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)
        return jax.jit(fn)

    cache = {}

    def call(q, k, v, causal=False):
        if causal not in cache:
            cache[causal] = build(causal)
        return cache[causal](q, k, v)

    return call
