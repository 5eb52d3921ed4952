"""Expert parallelism (Mixture-of-Experts) over a mesh axis — TPU-native.

One router for every MoE layer (:func:`route`: top-k of softmax or
sigmoid scores plus a selection bias, :func:`balance_bias` its sign
rule) and two ways to run the experts.  :func:`held_experts_ffn` is one
chip's share of an expert-parallel layer (gated experts of three
matrices, or ungated ones of two, under a named activation): the
pairs whose expert is held here, sorted by expert, as grouped matrix
products, with no capacity and no dropped token (``sym.MoE`` with
``experts_held``).  The rest of the file is the Switch-Transformer-style
top-1 layer (absent from the reference, SURVEY.md §2.3), designed for
the ICI fabric:

* tokens live batch-sharded on the 'ep' axis; experts are sharded over the
  same axis (each device owns E/n_ep experts);
* routing builds a STATIC-shape capacity-bucketed dispatch tensor (no
  dynamic shapes — XLA/MXU friendly), tokens over capacity are dropped and
  routed around by the residual connection as in Switch;
* dispatch and return are each ONE ``lax.all_to_all`` — the canonical MoE
  collective pattern riding ICI;
* expert FFNs run as a single batched einsum over the local expert dim so
  the MXU sees one large matmul, not a per-expert loop.

``moe_dispatch_combine`` is the shard_map-level core; ``MoELayer`` wraps
param creation + jit.
"""
from __future__ import annotations

__all__ = ["route", "expert_load", "balance_bias", "top1_routing",
           "held_experts_ffn", "moe_dispatch_combine", "moe_ffn_block",
           "MoELayer"]


def route(logits, k=1, score_func="softmax", bias=None, route_norm=False,
          route_scale=1.0):
    """The router of every MoE layer here: scores from ``logits`` (T, E)
    in float32 (``softmax`` over the experts, or ``sigmoid`` of each),
    the ``k`` experts a token chooses (the largest of scores + ``bias``;
    the bias steers the choice and never the weight), and the weight
    of each choice: its score, over the sum of the chosen scores with
    ``route_norm``, times ``route_scale``.

    Returns (scores (T, E), chosen (T, k) int32, weights (T, k))."""
    import jax
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    if score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError("score_func must be 'softmax' or 'sigmoid' "
                         "(got %r)" % (score_func,))
    steer = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))[None, :]
    _, chosen = jax.lax.top_k(steer, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if route_norm:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-20)
    return scores, chosen.astype(jnp.int32), weights * route_scale


def expert_load(chosen, n_experts):
    """Tokens that chose each expert: (E,) float32.  Counted by
    comparison, not by scatter: a scatter of 65,536 ones runs one
    element at a time on the TPU."""
    import jax.numpy as jnp
    hit = chosen.reshape(-1)[:, None] == jnp.arange(n_experts)[None, :]
    return jnp.sum(hit, axis=0, dtype=jnp.float32)


def balance_bias(bias, load, coeff):
    """The selection bias after a step, by the sign rule of loss-free
    balancing: up for an expert under the mean load, down for one over
    it, by ``coeff``.  No gradient reaches it."""
    import jax.numpy as jnp
    return bias + coeff * jnp.sign(jnp.mean(load) - load)


def top1_routing(gate_logits, capacity):
    """Top-1 router with static capacity buckets: :func:`route` at
    k = 1 with softmax scores, then each token's place in its expert's
    bucket (tokens over capacity are dropped, as in Switch).

    gate_logits: (T, E). Returns (dispatch (T, E, C) one-hot, combine
    (T, E, C) prob-weighted, aux_loss scalar — the Switch load-balance loss).
    """
    import jax
    import jax.numpy as jnp

    T, E = gate_logits.shape
    dtype = gate_logits.dtype
    probs, chosen, gate = route(gate_logits, k=1)
    probs, gate = probs.astype(dtype), gate[:, 0].astype(dtype)
    mask = jax.nn.one_hot(chosen[:, 0], E, dtype=dtype)      # (T, E)
    # position of each token within its expert's capacity bucket
    pos = (jnp.cumsum(mask, axis=0) - 1.0) * mask            # (T, E)
    keep = mask * (pos < capacity)
    pos_idx = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)  # (T,)
    pos_hot = jax.nn.one_hot(pos_idx, capacity, dtype=dtype)  # (T, C)
    dispatch = keep[:, :, None] * pos_hot[:, None, :]        # (T, E, C)
    combine = dispatch * gate[:, None, None]
    # load-balance aux loss: E * sum_e frac_tokens_e * mean_prob_e
    frac = jnp.mean(mask, axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return dispatch, combine, aux


# True runs the TPU kernel off the TPU, under the Pallas interpreter:
# the tests patch it, nothing else sets it
_INTERPRET = False


def _grouped_matmul(x, w, group_sizes):
    """Rows of x (M, K), sorted by group, each times its group's matrix
    of w (G, K, N); rows past the groups' total give zeros.  On a TPU
    the library's grouped-matmul kernel (megablox: only the row tiles a
    group covers are computed), elsewhere ``lax.ragged_dot``.  The
    kernel's tiles are whole lanes of 128: a width that is none (an
    expert 1,856 wide) is padded with zeros up to one, which adds
    nothing to a product; XLA:TPU's ``ragged_dot`` multiplies every row
    by every group's matrix."""
    import math

    import jax
    import jax.numpy as jnp

    M, K = x.shape
    N = w.shape[2]
    valid = (jnp.arange(M) < jnp.sum(group_sizes))[:, None]
    x = jnp.where(valid, x, 0)      # and so its gradient's dead rows
    tile_m = next((t for t in (512, 256, 128) if M % t == 0), None)
    if tile_m is not None and (jax.default_backend() == "tpu" or _INTERPRET):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        tiling = (tile_m, min(K, 1024), min(N, 1024))
        pad_k, pad_n = -K % 128, -N % 128
        if pad_k or pad_n:
            x = jnp.pad(x, ((0, 0), (0, pad_k)))
            w = jnp.pad(w, ((0, 0), (0, pad_k), (0, pad_n)))
            # a padded width only: one tile for both widths, so that it
            # divides them in the backward products too, where they
            # change places
            tile = next(t for t in range(1024, 0, -128)
                        if math.gcd(K + pad_k, N + pad_n) % t == 0)
            tiling = (tile_m, tile, tile)
        with jax.named_scope("grouped_matmul"):
            y = megablox.gmm(x, w, group_sizes, x.dtype, tiling, None, None,
                             False, _INTERPRET)
        if pad_n:
            y = y[:, :N]
    else:
        y = jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
    return jnp.where(valid, y, 0)


def _rows_and_back():
    """The two moves between tokens (T, d) and sorted pair rows (M, d),
    each the other's transpose and both gathers in both directions (a
    scatter of rows runs a row at a time on the TPU, and its time
    would follow the routing):

    take(x, token, pos, mine): rows[r] = x[token[r]]
    fold(rows, token, pos, cw): y[t] = sum_j cw[t, j] * rows[pos[t, j]]

    ``token`` (M,) is each row's token, ``pos`` (T, k) each pair's row,
    ``mine`` / ``cw`` (T, k) which pairs count, and with what weight."""
    import jax
    import jax.numpy as jnp

    def gathered(rows, pos):
        return rows[pos.reshape(-1)].reshape(pos.shape + rows.shape[1:])

    @jax.custom_vjp
    def fold(rows, token, pos, cw):
        return jnp.einsum("tk,tkd->td", cw, gathered(rows, pos),
                          preferred_element_type=jnp.float32)

    def fold_fwd(rows, token, pos, cw):
        return fold(rows, token, pos, cw), (rows, token, pos, cw)

    def fold_bwd(res, dy):
        rows, token, pos, cw = res
        k = pos.shape[1]
        d_cw = jnp.einsum("td,tkd->tk", dy, gathered(rows, pos),
                          preferred_element_type=jnp.float32)
        # row r holds pair (token[r], j): its weight is cw[token[r], j]
        # for the j with pos[token[r], j] == r
        at = pos[token] == jnp.arange(rows.shape[0])[:, None]   # (M, k)
        w_row = jnp.sum(jnp.where(at, cw[token], 0.0), axis=1)
        d_rows = (dy[token] * w_row[:, None]).astype(rows.dtype)
        return d_rows, None, None, d_cw.astype(cw.dtype)

    fold.defvjp(fold_fwd, fold_bwd)

    @jax.custom_vjp
    def take(x, token, pos, mine):
        return x[token]

    def take_fwd(x, token, pos, mine):
        return x[token], (token, pos, mine)

    def take_bwd(res, d_rows):
        token, pos, mine = res
        return (fold(d_rows, token, pos, mine).astype(d_rows.dtype),
                None, None, None)

    take.defvjp(take_fwd, take_bwd)
    return take, fold


def _worst_case_rows(T, k, held):
    """Rows that hold every pair under any routing: every token
    choosing only experts held here."""
    return T * min(k, held)


def held_experts_ffn(x, chosen, weights, w_gate, w_up, w_down, first,
                     act="silu"):
    """What the experts held here add to a layer's output: for every
    token-expert pair whose expert is one of ``first .. first + held``,
    weight * expert(token), summed by token.  No pair is dropped under
    any imbalance.

    x: (T, d) tokens; chosen, weights: (T, k) from :func:`route` over
    ALL experts; w_gate, w_up: (held, d, f), w_down: (held, f, d), the
    experts held: ``(act(x.w_gate) * (x.w_up)).w_down``, or with
    ``w_gate`` None the ungated ``act(x.w_up).w_down``; ``act`` is
    ``"silu"`` or ``"relu2"`` (relu(x)^2).
    The pairs are sorted by expert, the held
    ones first, into T * min(k, held) rows: the worst case, every token
    choosing only experts held here.  The grouped matrix products
    compute only the row tiles a pair sits in, so their work follows
    the load; everything else (two gathers each way, the gate) runs
    over all the rows whatever the routing, so that a step takes the
    same time under even and uneven routing but for those products.

    Returns (y (T, d), group_sizes (held,) int32: pairs per expert,
    dropped: the pairs held here that the fold did not read back from
    a computed row of their own, counted from the indices the gathers
    use; 0 unless the rows are too few or the sort is wrong)."""
    import jax
    import jax.numpy as jnp

    if act not in ("silu", "relu2"):
        raise ValueError("held_experts_ffn: act must be 'silu' or 'relu2' "
                         "(got %r)" % (act,))
    activate = jax.nn.silu if act == "silu" else \
        (lambda t: jnp.square(jnp.maximum(t, 0)))
    T, d = x.shape
    k = chosen.shape[1]
    held = w_up.shape[0]
    local = chosen - first                                   # (T, k)
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # held pairs first
    pos = jnp.argsort(order).astype(jnp.int32).reshape(T, k)  # a pair's row
    group_sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                          dtype=jnp.int32)
    rows = _worst_case_rows(T, k, held)
    token = order[:rows] // k
    has_row = pos < rows
    pos = jnp.minimum(pos, rows - 1)        # pairs not held: any row, x 0
    folded = here & has_row & (pos < jnp.sum(group_sizes)) \
        & (token[pos] == jnp.arange(T)[:, None])
    dropped = jnp.sum(here) - jnp.sum(folded)
    take, fold = _rows_and_back()
    mine = here.astype(jnp.float32)

    xs = take(x, token, pos, mine)
    h = None if w_gate is None else _grouped_matmul(xs, w_gate, group_sizes)
    u = _grouped_matmul(xs, w_up, group_sizes).astype(jnp.float32)
    mid = activate(u) if h is None else activate(h.astype(jnp.float32)) * u
    out = _grouped_matmul(mid.astype(x.dtype), w_down, group_sizes)
    y = fold(out, token, pos, weights.astype(jnp.float32) * mine)
    return y.astype(x.dtype), group_sizes, dropped


def moe_dispatch_combine(x, wg, expert_fn, axis_name, capacity_factor=1.25):
    """Full MoE layer body inside shard_map.

    x: (T_local, d) local token shard; wg: (d, E) router weights
    (replicated); expert_fn(expert_inputs (E_local, Cap_total, d)) ->
    same-shape outputs using the LOCAL experts.
    Returns (y (T_local, d), aux_loss).
    """
    import jax.numpy as jnp
    from jax import lax

    n_ep = lax.axis_size(axis_name)
    T, d = x.shape
    logits = x @ wg                                   # (T, E)
    E = logits.shape[-1]
    assert E % n_ep == 0, "n_experts must divide the ep axis"
    cap = max(1, int(T * capacity_factor / E))
    dispatch, combine, aux = top1_routing(logits, cap)

    # (T,E,C) x (T,d) -> (E, C, d) expert-major send buffer
    sendbuf = jnp.einsum("tec,td->ecd", dispatch, x)
    # scatter expert dim over devices / gather capacity from all peers:
    # (E, C, d) -> (E_local, n_ep*C, d)
    recvbuf = lax.all_to_all(sendbuf, axis_name, split_axis=0,
                             concat_axis=1, tiled=True)
    expert_out = expert_fn(recvbuf)                   # (E_local, n_ep*C, d)
    # inverse all_to_all: back to token owners, (E, C, d)
    retbuf = lax.all_to_all(expert_out, axis_name, split_axis=1,
                            concat_axis=0, tiled=True)
    y = jnp.einsum("tec,ecd->td", combine, retbuf)
    aux = lax.pmean(aux, axis_name)
    return y, aux


def moe_ffn_block(expert_inputs, w1, b1, w2, b2):
    """Batched two-layer FFN over the local expert dim: one big einsum per
    matmul so every expert's tokens hit the MXU together.

    expert_inputs: (E_local, Cap, d); w1: (E_local, d, ff); w2: (E_local,
    ff, d)."""
    import jax.numpy as jnp
    h = jnp.einsum("ecd,edf->ecf", expert_inputs, w1) + b1[:, None, :]
    h = jnp.maximum(h, 0)
    return jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]


class MoELayer:
    """Jitted MoE layer over ``mesh``'s ep axis.

    Token batch (B, d) arrives sharded on 'ep'; expert weights (E, d, ff)
    arrive sharded on their expert dim; router weights replicated.
    """

    def __init__(self, mesh, n_experts, d_model, d_ff, axis="ep",
                 capacity_factor=1.25):
        self.mesh = mesh
        self.axis = axis
        self.E = n_experts
        self.d = d_model
        self.ff = d_ff
        self.capacity_factor = capacity_factor
        self._fn = None

    def init_params(self, rng):
        import numpy as onp
        r = onp.random.RandomState(rng)
        s = 1.0 / onp.sqrt(self.d)
        return {
            "gate": (r.randn(self.d, self.E) * s).astype(onp.float32),
            "w1": (r.randn(self.E, self.d, self.ff) * s).astype(onp.float32),
            "b1": onp.zeros((self.E, self.ff), onp.float32),
            "w2": (r.randn(self.E, self.ff, self.d) *
                   (1.0 / onp.sqrt(self.ff))).astype(onp.float32),
            "b2": onp.zeros((self.E, self.d), onp.float32),
        }

    def _build(self):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        ax = self.axis

        def body(x, p):
            def expert_fn(inp):
                return moe_ffn_block(inp, p["w1"], p["b1"], p["w2"],
                                     p["b2"])
            return moe_dispatch_combine(
                x, p["gate"], expert_fn, ax,
                capacity_factor=self.capacity_factor)

        specs = {"gate": P(), "w1": P(ax), "b1": P(ax), "w2": P(ax),
                 "b2": P(ax)}
        self._fn = jax.jit(shard_map(
            body, mesh=self.mesh, in_specs=(P(ax), specs),
            out_specs=(P(ax), P()), check_vma=False))

    def __call__(self, x, params):
        """x: (B, d) global batch; returns (y, aux_loss)."""
        if self._fn is None:
            self._build()
        return self._fn(x, params)
