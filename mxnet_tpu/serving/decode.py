"""Continuous-batching decode engine: slot-structured step-wise serving
for sequence models.

The Predictor/DynamicBatcher stack serves *one-shot* fixed-shape
requests; an autoregressive LM is served as a *decode loop* — per-step
launches over a batch in which sequences join and retire mid-flight.
:class:`DecodeEngine` is that serving shape, built from three
disciplines the stack already proved:

* **bucketed-by-length prefill** — the prompt runs through one program
  per power-of-two length bucket (the Predictor bucket-ladder idiom:
  pad up, mask, slice back). A per-row length mask makes padding a
  pure ``where`` select, so the bucketed prefill is BITWISE equal to a
  whole-sequence forward at the exact length (:meth:`prefill_parity`);
  oversized prompts chunk through the top bucket carrying slot state.
* **slot-structured decode state** — the recurrent state (the RNN
  h/c, a transformer's KV rows) lives as ONE device-resident,
  slot-indexed pytree. Prefill writes rows with a jitted
  ``state.at[idx].set(rows, mode="drop")`` scatter and resumed chunks
  read them back with a gather — the ``(B,)`` int32-index discipline
  of ``data.ShardedCachedDataset``. The per-step transfer is the
  ``(slots,)`` token/mask vectors; the state NEVER round-trips to the
  host.
* **continuous batching** — between steps the scheduler admits queued
  sequences into free slots and retires finished ones, then launches
  ONE fixed-shape decode program regardless of occupancy. Inactive
  rows are carried through an active-mask ``where``, so occupancy
  churn never changes a program shape and never retraces
  (``CompileWatch`` counts stay frozen after :meth:`warmup`). Because
  rows are computed independently and masking is an exact select, the
  token stream of a request decoded at occupancy N is bitwise equal
  to the same request decoded alone — the property the
  ``dryrun_decode`` gate pins while showing aggregate tokens/sec
  strictly above the sequential baseline.

Per-sequence SLOs ride the existing judgment layer: time-to-first-token
and per-token latency are :class:`~mxnet_tpu.telemetry.SLOTracker`
objectives (``slo.<name>.ttft.*`` / ``slo.<name>.per_token.*`` gauges);
``shed_on_breach=True`` turns a TTFT breach into admission shed
(:class:`TenantShed`) at submit. Request traces use the decode phase
set (queue-wait / prefill / decode / resolve,
:data:`~mxnet_tpu.serving.stats.DECODE_TRACE_PHASES`) in the shared
request-trace ring, and counters publish under a ``decode.<i>.*``
registry scope.

The prefill/step/state-init program family is cacheable through the
PR-11 persistent executable cache: ``warmup(cache_dir=...)`` AOT
compiles + commits entries keyed by (params digest, precision mode,
bucket, input signature, backend); a second replica deserializes every
program with ZERO XLA compiles and serves bitwise-identical streams.
The engine runs under a named :class:`~mxnet_tpu.precision
.PrecisionPolicy` (the mode name is part of every cache key).

Fault seams (armed via :mod:`mxnet_tpu.faults`):
``serving.decode_worker`` (check — scheduler loop; a crash restarts the
loop, slots and device state survive), ``serving.decode_step`` (check —
per-step launch; ``delay`` = device slowdown), and
``serving.decode_abandon`` (fires — a mid-stream client abandon: the
oldest active request retires with :class:`RequestAbandoned`).

Quick start::

    from mxnet_tpu.serving.decode import DecodeEngine, LSTMCharLM

    model = LSTMCharLM(vocab_size=32, num_hidden=32, num_embed=16)
    eng = DecodeEngine(model, model.init_params(seed=0), slots=4)
    eng.warmup()                       # compile the program family
    reqs = [eng.submit(prompt, max_new_tokens=16) for prompt in prompts]
    streams = [r.result(timeout=60) for r in reqs]
    eng.shutdown(drain=True)

Env knobs: ``MXNET_SERVE_DECODE_SLOTS`` (default slot count),
``MXNET_SERVE_DECODE_MAX_STEPS`` (per-request generation cap),
``MXNET_SERVE_DECODE_TTFT_SLO_MS`` / ``MXNET_SERVE_DECODE_TOKEN_SLO_MS``
(default SLO objectives) — docs/how_to/env_var.md.
"""
from __future__ import annotations

import collections
import hashlib
import logging
import os
import threading
import time

import numpy as onp

from .. import faults as _faults
from .. import telemetry
from ..base import MXNetError
from ..precision import resolve as _resolve_precision
from .errors import (QueueFull, RequestAbandoned, RequestTimeout,
                     ServerClosed, TenantShed, WorkerCrashed)
from .stats import DECODE_TRACE_PHASES, ServingStats

__all__ = ["DecodeModel", "LSTMCharLM", "DecodeRequest", "DecodeEngine"]

logger = logging.getLogger("mxnet_tpu.serving")

# prefill programs run a fixed tiny row batch: row 0 is the admitted
# request, the rest are masked padding (lengths 0, slot index = slots →
# the scatter drops them). Starting at 2 keeps the matmuls off the
# batch-1 gemv lowering the Predictor ladder documents as the one
# shape whose codegen can differ bitwise.
PREFILL_ROWS = 2


def _env_int(name, default):
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _env_float(name, default):
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


# ---------------------------------------------------------------------------
# model interface
# ---------------------------------------------------------------------------
class DecodeModel(object):
    """A pure-functional autoregressive model the engine can serve.

    Subclasses define ``vocab_size``, :meth:`state_struct` (the
    per-sequence recurrent-state rows) and :meth:`step` (one token of
    batched forward math, row-independent). :meth:`prefill` — a
    length-masked ``lax.scan`` over :meth:`step` — comes for free and
    is what makes padded prefill bitwise: padded positions update
    state through an exact ``where`` select, and each row's logits are
    captured at its own final real position.
    """

    vocab_size = None

    def state_struct(self):
        """``{name: (per_row_shape, dtype_str)}`` for the recurrent
        state — the engine allocates each leaf as ``(slots,) + shape``."""
        raise NotImplementedError

    def step(self, params, tokens, state):
        """One decode step: ``(params, (B,) int32 tokens, state rows)
        -> (new state rows, (B, vocab) logits)``. Must be row-wise
        independent (row r's outputs depend only on row r's inputs)."""
        raise NotImplementedError

    def signature(self):
        """Canonical config string — the executable-cache input
        signature component."""
        raise NotImplementedError

    def params_digest(self, params):
        """Content digest of (config, param names, param bytes) — the
        executable-cache identity; two processes holding bitwise-equal
        params agree on it."""
        h = hashlib.sha256(self.signature().encode())
        for k in sorted(params):
            h.update(k.encode())
            h.update(onp.ascontiguousarray(onp.asarray(params[k])).tobytes())
        return h.hexdigest()

    def prefill(self, params, tokens, lengths, state0):
        """Whole-prompt forward: ``tokens (B, L) int32``, per-row real
        ``lengths (B,) int32``, initial state rows ``state0``. Returns
        ``(state rows at each row's position length-1, logits at that
        position)``. Positions ``t >= lengths[b]`` are exact no-ops for
        row ``b``."""
        import jax
        import jax.numpy as jnp
        B, L = tokens.shape
        logits0 = jnp.zeros((B, int(self.vocab_size)), jnp.float32)

        def body(carry, xs):
            state, logits = carry
            t, tok = xs
            new_state, new_logits = self.step(params, tok, state)
            keep = t < lengths
            state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(
                    keep.reshape((B,) + (1,) * (n.ndim - 1)), n, o),
                new_state, state)
            logits = jnp.where((t == lengths - 1)[:, None],
                               new_logits.astype(logits.dtype), logits)
            return (state, logits), None

        (state, logits), _ = jax.lax.scan(
            body, (state0, logits0),
            (jnp.arange(L, dtype=jnp.int32), jnp.transpose(tokens)))
        return state, logits


class LSTMCharLM(DecodeModel):
    """The `example/rnn` char-LM as a functional decode model.

    The step math mirrors :class:`mxnet_tpu.rnn.LSTMCell` exactly
    (gate order [i, f, g, o], ``FullyConnected`` = ``x @ W.T + b``),
    so :meth:`from_params` adopts parameters trained through
    ``Module.fit`` on the unfused ``lstm_l<i>_`` symbol graph
    (``example/rnn/decode_lm.py``) verbatim: ``embed_weight``,
    ``lstm_l<i>_{i2h,h2h}_{weight,bias}``, ``pred_{weight,bias}``.
    """

    def __init__(self, vocab_size, num_hidden=64, num_embed=32,
                 num_layers=1):
        self.vocab_size = int(vocab_size)
        self.num_hidden = int(num_hidden)
        self.num_embed = int(num_embed)
        self.num_layers = int(num_layers)

    def signature(self):
        return ("lstm_char_lm:vocab=%d;embed=%d;hidden=%d;layers=%d"
                % (self.vocab_size, self.num_embed, self.num_hidden,
                   self.num_layers))

    def state_struct(self):
        shape = (self.num_layers, self.num_hidden)
        return {"h": (shape, "float32"), "c": (shape, "float32")}

    def param_shapes(self):
        """``{name: shape}`` of the full parameter set (init +
        from_params validation)."""
        V, E, H = self.vocab_size, self.num_embed, self.num_hidden
        shapes = {"embed_weight": (V, E),
                  "pred_weight": (V, H), "pred_bias": (V,)}
        for l in range(self.num_layers):
            in_dim = E if l == 0 else H
            shapes["lstm_l%d_i2h_weight" % l] = (4 * H, in_dim)
            shapes["lstm_l%d_i2h_bias" % l] = (4 * H,)
            shapes["lstm_l%d_h2h_weight" % l] = (4 * H, H)
            shapes["lstm_l%d_h2h_bias" % l] = (4 * H,)
        return shapes

    def init_params(self, seed=0, scale=0.1):
        """Deterministic random parameters (tests / dryruns that need
        no training)."""
        rng = onp.random.RandomState(int(seed))
        return {k: (rng.rand(*s) * 2 - 1).astype(onp.float32) * scale
                for k, s in sorted(self.param_shapes().items())}

    @classmethod
    def from_params(cls, params, num_layers=None):
        """Adopt a fit-trained parameter dict (numpy or NDArray
        values) from the unfused char-LM graph; the config is inferred
        from the shapes."""
        arrs = {k: (v.asnumpy() if hasattr(v, "asnumpy") else
                    onp.asarray(v))
                for k, v in params.items()}
        if num_layers is None:
            num_layers = len([k for k in arrs
                              if k.endswith("_i2h_weight")])
        V, E = arrs["embed_weight"].shape
        H = arrs["lstm_l0_h2h_weight"].shape[1]
        model = cls(V, num_hidden=H, num_embed=E, num_layers=num_layers)
        want = model.param_shapes()
        got = {k: tuple(v.shape) for k, v in arrs.items()
               if k in want}
        bad = [k for k in want if got.get(k) != want[k]]
        if bad:
            raise MXNetError(
                "LSTMCharLM.from_params: missing/mismatched params %s "
                "(want %s)" % (bad, {k: want[k] for k in bad}))
        model._adopted = {k: arrs[k] for k in want}
        return model

    def step(self, params, tokens, state):
        import jax
        import jax.numpy as jnp
        x = jnp.take(params["embed_weight"], tokens, axis=0)
        h_all, c_all = state["h"], state["c"]
        hs, cs = [], []
        for l in range(self.num_layers):
            gates = (x @ params["lstm_l%d_i2h_weight" % l].T
                     + params["lstm_l%d_i2h_bias" % l]
                     + h_all[:, l] @ params["lstm_l%d_h2h_weight" % l].T
                     + params["lstm_l%d_h2h_bias" % l])
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = (jax.nn.sigmoid(f) * c_all[:, l]
                 + jax.nn.sigmoid(i) * jnp.tanh(g))
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            hs.append(h)
            cs.append(c)
            x = h
        logits = x @ params["pred_weight"].T + params["pred_bias"]
        return ({"h": jnp.stack(hs, axis=1), "c": jnp.stack(cs, axis=1)},
                logits)


class TransformerLM(DecodeModel):
    """The `example/transformer-lm` causal decoder as a functional
    decode model (the scenario matrix's transformer serving customer).

    Recurrent state is the sliding token window of the training
    length: each step writes the incoming token at its row's position
    (shifting left once the window fills) and re-runs the full causal
    forward over the window — the identical math the training symbol
    graph computes (``FullyConnected`` = ``x @ W.T + b``, softmax over
    ``scores + causal_mask``), so :meth:`from_params` adopts
    fit-trained parameters (``embed_weight``, ``pos_embed``,
    ``blk<i>_{att_{q,k,v,o},mlp_{fc1,fc2}}_{weight,bias}``,
    ``head_{weight,bias}``) verbatim.  The ``causal_mask`` constant is
    synthesized internally (``triu(-1e9)``, the LMInit rule), never
    read from the checkpoint — a mask must not ride the weight-quant
    path.  Positions beyond a row's real length hold zeros; the causal
    mask keeps them out of every attended position, so the garbage is
    unreachable.
    """

    def __init__(self, vocab_size, num_embed, num_heads, window,
                 num_blocks):
        self.vocab_size = int(vocab_size)
        self.num_embed = int(num_embed)
        self.num_heads = int(num_heads)
        self.window = int(window)
        self.num_blocks = int(num_blocks)
        if self.num_embed % self.num_heads:
            raise MXNetError(
                "TransformerLM: num_embed %d not divisible by "
                "num_heads %d" % (self.num_embed, self.num_heads))
        self._mask = onp.triu(
            onp.full((self.window, self.window), -1e9, onp.float32),
            k=1)

    def signature(self):
        return ("transformer_lm:vocab=%d;embed=%d;heads=%d;window=%d;"
                "blocks=%d" % (self.vocab_size, self.num_embed,
                               self.num_heads, self.window,
                               self.num_blocks))

    def state_struct(self):
        return {"ctx": ((self.window,), "int32"),
                "len": ((), "int32")}

    def param_shapes(self):
        V, D, T = self.vocab_size, self.num_embed, self.window
        shapes = {"embed_weight": (V, D), "pos_embed": (1, T, D),
                  "head_weight": (V, D), "head_bias": (V,)}
        for i in range(self.num_blocks):
            for p in ("att_q", "att_k", "att_v", "att_o"):
                shapes["blk%d_%s_weight" % (i, p)] = (D, D)
                shapes["blk%d_%s_bias" % (i, p)] = (D,)
            shapes["blk%d_mlp_fc1_weight" % i] = (4 * D, D)
            shapes["blk%d_mlp_fc1_bias" % i] = (4 * D,)
            shapes["blk%d_mlp_fc2_weight" % i] = (D, 4 * D)
            shapes["blk%d_mlp_fc2_bias" % i] = (D,)
        return shapes

    def init_params(self, seed=0, scale=0.1):
        """Deterministic random parameters (tests that need no
        training)."""
        rng = onp.random.RandomState(int(seed))
        return {k: (rng.rand(*s) * 2 - 1).astype(onp.float32) * scale
                for k, s in sorted(self.param_shapes().items())}

    @classmethod
    def from_params(cls, params, num_heads):
        """Adopt a fit-trained parameter dict (numpy or NDArray
        values) from the transformer-lm symbol graph; everything but
        the head count is inferred from the shapes."""
        arrs = {k: (v.asnumpy() if hasattr(v, "asnumpy") else
                    onp.asarray(v))
                for k, v in params.items()}
        V, D = arrs["embed_weight"].shape
        T = arrs["pos_embed"].shape[1]
        blocks = len([k for k in arrs
                      if k.startswith("blk") and
                      k.endswith("_att_q_weight")])
        model = cls(V, num_embed=D, num_heads=num_heads, window=T,
                    num_blocks=blocks)
        want = model.param_shapes()
        got = {k: tuple(v.shape) for k, v in arrs.items() if k in want}
        bad = [k for k in want if got.get(k) != want[k]]
        if bad:
            raise MXNetError(
                "TransformerLM.from_params: missing/mismatched params "
                "%s (want %s)" % (bad, {k: want[k] for k in bad}))
        model._adopted = {k: arrs[k] for k in want}
        return model

    def _block(self, jnp, params, x, i):
        """One decoder block over the window: causal multi-head
        attention + MLP, both residual — mirrors the training graph's
        ``attention()``/``mlp()`` builders shape for shape."""
        B, T, D = x.shape
        H = self.num_heads
        DH = D // H

        def proj(name, inp):
            return inp @ params["blk%d_%s_weight" % (i, name)].T \
                + params["blk%d_%s_bias" % (i, name)]

        def heads(p):
            # (B, T, D) -> (B, H, T, DH)
            return jnp.transpose(p.reshape(B, T, H, DH), (0, 2, 1, 3))

        q, k, v = (heads(proj(n, x))
                   for n in ("att_q", "att_k", "att_v"))
        scores = (q @ jnp.swapaxes(k, -1, -2)) \
            * onp.float32(DH ** -0.5)
        scores = scores + jnp.asarray(self._mask)[None, None]
        att = jax_softmax(jnp, scores)
        ctx = att @ v                               # (B, H, T, DH)
        ctx = jnp.transpose(ctx, (0, 2, 1, 3)).reshape(B, T, D)
        x = x + proj("att_o", ctx)
        h = x @ params["blk%d_mlp_fc1_weight" % i].T \
            + params["blk%d_mlp_fc1_bias" % i]
        h = jnp.maximum(h, 0.0)
        return x + (h @ params["blk%d_mlp_fc2_weight" % i].T
                    + params["blk%d_mlp_fc2_bias" % i])

    def step(self, params, tokens, state):
        import jax.numpy as jnp
        T = self.window
        ctx, ln = state["ctx"], state["len"]        # (B, T), (B,)
        B = ctx.shape[0]
        full = ln >= T
        # window full: slide left one and write at T-1; else append
        ctx = jnp.where(full[:, None], jnp.roll(ctx, -1, axis=1), ctx)
        pos = jnp.where(full, T - 1, ln).astype(jnp.int32)
        ctx = ctx.at[jnp.arange(B), pos].set(
            tokens.astype(jnp.int32))
        x = jnp.take(params["embed_weight"], ctx, axis=0) \
            + params["pos_embed"][0]
        for i in range(self.num_blocks):
            x = self._block(jnp, params, x, i)
        h = x[jnp.arange(B), pos]                   # (B, D)
        logits = h @ params["head_weight"].T + params["head_bias"]
        return ({"ctx": ctx,
                 "len": jnp.minimum(ln + 1, T).astype(jnp.int32)},
                logits)


def jax_softmax(jnp, scores):
    """Max-subtracted softmax over the last axis — the same lowering
    ``mx.sym.softmax`` compiles to, kept as one shared helper so the
    decode model and any future functional graph agree bit for bit."""
    z = scores - scores.max(axis=-1, keepdims=True)
    e = jnp.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# request future
# ---------------------------------------------------------------------------
class DecodeRequest(object):
    """One submitted sequence: a future over its generated token
    stream. Thread-safe; resolved exactly once (tokens or an
    exception) — engine shutdown and abandonment both resolve it, a
    future never hangs."""

    def __init__(self, req_id, prompt, max_new_tokens, seed,
                 timeout_ms=None):
        self.id = req_id
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.seed = int(seed) & 0xFFFFFFFF
        self.timeout_ms = (None if timeout_ms is None
                           else float(timeout_ms))
        self._lock = threading.Lock()
        self._emitted = []
        self._done = threading.Event()
        self._exc = None
        self._cancel = False
        self.outcome = None   # "ok" | "abandoned" | "error" | "timeout"
        self.slot = None
        self.bucket = None          # top prefill length bucket used
        self.t_submit = time.time()
        self.deadline = (None if self.timeout_ms is None
                         else self.t_submit + self.timeout_ms / 1000.0)
        self.t_admit = None
        self.t_first = None         # first token emitted (TTFT point)
        self.t_done = None

    # -- engine side ----------------------------------------------------
    def _append(self, tok):
        with self._lock:
            self._emitted.append(int(tok))

    def _resolve(self, outcome, exc=None):
        with self._lock:
            if self._done.is_set():
                return
            self.outcome = outcome
            self._exc = exc
        self._done.set()

    # -- client side ----------------------------------------------------
    def tokens(self):
        """The tokens emitted so far (a snapshot — readable while the
        request streams, and after abandonment)."""
        with self._lock:
            return list(self._emitted)

    def cancel(self):
        """Client abandons the stream: the engine retires the slot at
        the next step boundary and the future resolves with
        :class:`RequestAbandoned`."""
        self._cancel = True

    def done(self):
        return self._done.is_set()

    @property
    def ttft_ms(self):
        if self.t_first is None:
            return None
        return (self.t_first - self.t_submit) * 1000.0

    def result(self, timeout=None):
        """Block for the full stream. Raises the resolution error
        (:class:`RequestAbandoned`, :class:`WorkerCrashed`,
        :class:`ServerClosed`) if the request did not complete."""
        if not self._done.wait(timeout):
            raise TimeoutError("decode request %s still streaming "
                               "after %.1fs" % (self.id, timeout or 0))
        if self._exc is not None:
            raise self._exc
        return self.tokens()


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
class DecodeEngine(object):
    """Continuous-batching decode scheduler over one slot-structured
    device state (module docstring).

    Parameters
    ----------
    model : DecodeModel
    params : dict
        Host parameters (numpy / NDArray values). Placed on device
        once, cast per the precision policy; never re-staged per step.
    slots : int
        Concurrent sequences (``MXNET_SERVE_DECODE_SLOTS`` default).
    max_prefill_len : int
        Top of the power-of-two prefill length-bucket ladder; longer
        prompts chunk through the top bucket carrying slot state.
    temperature : float
        0.0 = greedy argmax (the bitwise-gate path); > 0 samples via a
        deterministic counter-hash gumbel keyed by (request seed,
        step) — same request, same stream, at any occupancy.
    eos_id : int or None
        Token id that retires a sequence early.
    precision : str / PrecisionPolicy / None
        Named precision mode (``mxnet_tpu.precision.resolve``); the
        mode name keys every cache entry.
    ttft_slo_ms / token_slo_ms : float
        p95 objectives for the two SLO trackers (env defaults
        ``MXNET_SERVE_DECODE_TTFT_SLO_MS`` /
        ``MXNET_SERVE_DECODE_TOKEN_SLO_MS``; 0 disables that tracker).
    shed_on_breach : bool
        Shed new submits (:class:`TenantShed`) while the TTFT
        objective is in multi-window burn-rate breach.
    start : bool
        Spawn the scheduler thread now; ``start=False`` lets tests
        queue a full arrival transcript first (deterministic
        join/retire order), then call :meth:`start`.
    """

    def __init__(self, model, params, slots=None, max_prefill_len=32,
                 temperature=0.0, eos_id=None, precision=None,
                 max_queue=256, ttft_slo_ms=None, token_slo_ms=None,
                 shed_on_breach=False, name="decode", start=True,
                 seed=0):
        import jax
        import jax.numpy as jnp
        self._model = model
        self._name = str(name)
        self._slots = int(slots if slots is not None else
                          _env_int("MXNET_SERVE_DECODE_SLOTS", 8))
        if self._slots < 1:
            raise MXNetError("DecodeEngine needs slots >= 1")
        self._max_steps = _env_int("MXNET_SERVE_DECODE_MAX_STEPS", 256)
        self._temperature = float(temperature)
        self._eos_id = None if eos_id is None else int(eos_id)
        # resolve(None) = the implicit f32 baseline (returns None);
        # the engine always runs under a NAMED policy — the mode name
        # keys every executable-cache entry
        self._policy = _resolve_precision(precision) \
            or _resolve_precision("f32")
        self._seed = int(seed)
        self._max_queue = int(max_queue)
        self._shed_on_breach = bool(shed_on_breach)
        self._max_restarts = _env_int(
            "MXNET_SERVE_MAX_WORKER_RESTARTS", 100)

        if getattr(model, "_adopted", None) is not None and params is None:
            params = model._adopted
        host = {k: (v.asnumpy() if hasattr(v, "asnumpy")
                    else onp.asarray(v))
                for k, v in params.items()}
        self._digest = model.params_digest(host)
        cdt = jnp.dtype(self._policy.compute_dtype or "float32")
        self._compute_dtype = cdt
        self._weight_quant = getattr(self._policy, "weight_quant", None)
        if self._weight_quant == "int8":
            # weight-only int8 (precision.quant): params live on device
            # as per-channel int8 + f32 scales; the step program
            # dequantizes IN-PROGRAM, so its arguments — re-read every
            # token on the memory-bound decode path — shrink ~4x
            # (step_argument_bytes is the witness)
            from ..precision import quant as _quant
            self._dparams = {
                k: jax.device_put(
                    jnp.asarray(v).astype(cdt)
                    if (not _quant.is_quantized(v)
                        and onp.issubdtype(v.dtype, onp.floating))
                    else v)
                for k, v in _quant.quantize_params(host).items()}
        else:
            self._dparams = {
                k: jax.device_put(
                    jnp.asarray(v).astype(cdt)
                    if onp.issubdtype(v.dtype, onp.floating)
                    else jnp.asarray(v))
                for k, v in host.items()}
        # where the bare device_put above landed: jax's default device
        (self._device,) = jax.tree_util.tree_leaves(
            self._dparams)[0].devices()

        # power-of-two length-bucket ladder (Predictor idiom)
        top = max(4, int(max_prefill_len))
        b, buckets = 4, []
        while True:
            buckets.append(b)
            if b >= top:
                break
            b *= 2
        self._buckets = buckets

        self._stats = ServingStats(
            scope=telemetry.registry().unique_scope("decode"),
            phases=DECODE_TRACE_PHASES)
        self._g_occupancy = self._stats.scope.gauge("occupancy")
        self._c_steps = self._stats.scope.counter("steps")
        self._c_tokens = self._stats.scope.counter("tokens")
        self._c_prefills = self._stats.scope.counter("prefill_launches")
        self._c_abandoned = self._stats.scope.counter("abandoned")
        self._h_ttft = self._stats.scope.histogram("ttft_ms")

        from ..telemetry.slo import SLOTracker
        if ttft_slo_ms is None:
            ttft_slo_ms = _env_float(
                "MXNET_SERVE_DECODE_TTFT_SLO_MS", 500.0)
        if token_slo_ms is None:
            token_slo_ms = _env_float(
                "MXNET_SERVE_DECODE_TOKEN_SLO_MS", 100.0)
        self.slo_ttft = (SLOTracker(name="%s.ttft" % self._name,
                                    p95_ms=float(ttft_slo_ms))
                         if ttft_slo_ms else None)
        self.slo_token = (SLOTracker(name="%s.per_token" % self._name,
                                     p95_ms=float(token_slo_ms))
                          if token_slo_ms else None)

        # slot tables (touched only by the scheduler thread)
        n = self._slots
        self._slot_req = [None] * n
        self._active = onp.zeros((n,), onp.bool_)
        self._cur_tok = onp.zeros((n,), onp.int32)
        self._steps_in = onp.zeros((n,), onp.int32)
        self._seeds = onp.zeros((n,), onp.uint32)

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue = collections.deque()
        self._closed = False
        self._drain = True
        self._restarts = 0
        self._n_steps = 0
        self._n_tokens = 0
        self._occ_sum = 0.0
        self._busy_s = 0.0
        self._ttft_ring = collections.deque(maxlen=4096)
        self._transcript = []
        self._warmed = False
        self._warmup_report = {}
        self._thread = None

        self._build_programs()
        if start:
            self.start()

    # -- program family --------------------------------------------------
    def _count_trace(self, site, **shapes):
        """Runs INSIDE each traced body — exactly once per XLA trace
        (the Predictor._instrument discipline): the serving compile
        counter plus the process CompileWatch streams (warmup vs
        steady attribution, post-warmup retrace warnings)."""
        self._stats.note_compile()
        telemetry.compile_watch().note_trace("decode.%s" % site, shapes)

    def _state_zeros(self, batch):
        import jax.numpy as jnp
        out = {}
        for k, (shape, dt) in sorted(self._model.state_struct().items()):
            dt = jnp.dtype(dt)
            if jnp.issubdtype(dt, jnp.floating):
                dt = self._compute_dtype
            out[k] = jnp.zeros((batch,) + tuple(shape), dt)
        return out

    def _select(self, logits, steps, seeds):
        """Next-token rule, shared by prefill (first token) and decode
        step — greedy argmax, or a deterministic counter-hash gumbel
        keyed by (seed, step) when temperature > 0. uint32 arithmetic
        only (x64 stays off)."""
        import jax.numpy as jnp
        if self._temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        V = logits.shape[-1]
        ctr = (seeds[:, None].astype(jnp.uint32)
               ^ (steps[:, None].astype(jnp.uint32)
                  * jnp.uint32(0x9E3779B9)))
        ctr = ctr + jnp.arange(V, dtype=jnp.uint32)[None, :] \
            * jnp.uint32(0x85EBCA77)
        x = ctr
        for mult in (0x7FEB352D, 0x846CA68B):
            x = x ^ (x >> jnp.uint32(16))
            x = x * jnp.uint32(mult)
        x = x ^ (x >> jnp.uint32(16))
        u = (x >> jnp.uint32(8)).astype(jnp.float32) \
            * onp.float32(1.0 / (1 << 24))
        u = jnp.clip(u, 1e-7, 1.0 - 1e-7)
        g = -jnp.log(-jnp.log(u))
        scaled = logits.astype(jnp.float32) \
            / onp.float32(self._temperature)
        return jnp.argmax(scaled + g, axis=-1).astype(jnp.int32)

    def _dense_params(self, params):
        """The dense param view a program body consumes: in-program
        per-channel dequant under weight-only int8 (the executable's
        ARGUMENTS stay int8 — that is the bytes win), identity
        otherwise.  Bitwise-deterministic per (q, s), so quantized
        decode streams and the prefill-parity reference agree exactly."""
        if self._weight_quant != "int8":
            return params
        import jax.numpy as jnp
        from ..precision import quant as _quant
        return _quant.dequant_params(jnp, params, self._compute_dtype)

    def _build_programs(self):
        import jax
        import jax.numpy as jnp
        model, slots, pb = self._model, self._slots, PREFILL_ROWS
        tree = jax.tree_util.tree_map
        dense = self._dense_params

        def init_fn():
            self._count_trace("state_init", slots=(slots,))
            return self._state_zeros(slots)

        def step_fn(params, state, tokens, active, steps, seeds):
            self._count_trace("step", tokens=(slots,))
            rows, logits = model.step(dense(params), tokens, state)
            nxt = self._select(logits, steps, seeds)
            bmask = lambda ref: active.reshape(  # noqa: E731
                (slots,) + (1,) * (ref.ndim - 1))
            state = tree(lambda n, o: jnp.where(bmask(n), n, o),
                         rows, state)
            nxt = jnp.where(active, nxt, tokens)
            return state, nxt

        def make_prefill(L):
            def prefill_fn(params, state, tokens, lengths, idx,
                           resume, seeds):
                self._count_trace("prefill_%d" % L, tokens=(pb, L))
                clip = jnp.clip(idx, 0, slots - 1)
                rows0 = tree(
                    lambda s: jnp.where(
                        resume.reshape((pb,) + (1,) * (s.ndim - 1)),
                        jnp.take(s, clip, axis=0),
                        jnp.zeros((pb,) + s.shape[1:], s.dtype)),
                    state)
                rows, logits = model.prefill(dense(params), tokens,
                                             lengths, rows0)
                # OOB index == slots → dropped: the padding rows (and
                # non-final chunks of co-padded rows) never land
                state = tree(
                    lambda s, r: s.at[idx].set(r.astype(s.dtype),
                                               mode="drop"),
                    state, rows)
                first = self._select(
                    logits, jnp.zeros((pb,), jnp.int32), seeds)
                return state, logits, first
            return prefill_fn

        self._init_jit = jax.jit(init_fn)
        self._step_jit = jax.jit(step_fn)
        self._prefill_jits = {L: jax.jit(make_prefill(L))
                              for L in self._buckets}
        self._init_exec = None
        self._step_exec = None
        self._prefill_execs = {}
        self._ref_jits = {}
        self._state = None

    # -- launches --------------------------------------------------------
    def _launch_init(self):
        fn = self._init_exec or self._init_jit
        return fn()

    def _launch_step(self, state, tokens, active, steps, seeds):
        fn = self._step_exec or self._step_jit
        return fn(self._dparams, state, tokens, active, steps, seeds)

    def _launch_prefill(self, L, state, tokens, lengths, idx, resume,
                        seeds):
        fn = self._prefill_execs.get(L) or self._prefill_jits[L]
        return fn(self._dparams, state, tokens, lengths, idx, resume,
                  seeds)

    # -- bucket ladder ---------------------------------------------------
    @property
    def buckets(self):
        return list(self._buckets)

    @property
    def slots(self):
        return self._slots

    @property
    def params_digest(self):
        return self._digest

    def bucket_for(self, n):
        """Smallest length bucket that fits ``n`` prompt tokens (the
        top bucket for oversized prompts — those chunk)."""
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    # -- weight-bytes accounting (the memory-bound decode roofline) ------
    def weight_bytes(self):
        """Stored bytes of the device-resident param tree — what the
        decode step re-reads per token.  Under ``int8_weight`` this is
        the int8 payloads + f32 scale vectors (~4x under the f32
        tree)."""
        import jax
        return int(sum(x.size * onp.dtype(x.dtype).itemsize
                       for x in jax.tree_util.tree_leaves(
                           self._dparams)))

    def step_argument_bytes(self):
        """``analyze_compiled`` argument bytes of the decode STEP
        program — the byte witness the quant mode must shrink (the
        arguments are dominated by the weights every token re-reads).
        Uses the warmed executable when present, else an AOT compile
        outside the retrace counters."""
        from ..telemetry import analyze_compiled
        compiled = self._step_exec
        if compiled is None:
            with telemetry.compile_watch().suppressed():
                for name, _b, jit_fn, args, _i in self._program_specs():
                    if name == "step":
                        compiled = jit_fn.lower(*args).compile()
                        break
        return int(analyze_compiled(compiled).get("argument_bytes", 0))

    # -- warmup / executable cache --------------------------------------
    def _program_specs(self):
        """(name, bucket, jit, abstract_args, install) for the whole
        cacheable decode program family."""
        import jax
        tree = jax.tree_util.tree_map
        sds = lambda t: tree(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        p_s = sds(self._dparams)
        state_s = sds(self._state_zeros(self._slots))
        n, pb = self._slots, PREFILL_ROWS
        i32 = onp.dtype("int32")
        specs = [
            ("state_init", 0, self._init_jit, (),
             lambda c: setattr(self, "_init_exec", c)),
            ("step", 1, self._step_jit,
             (p_s, state_s,
              jax.ShapeDtypeStruct((n,), i32),
              jax.ShapeDtypeStruct((n,), onp.dtype("bool")),
              jax.ShapeDtypeStruct((n,), i32),
              jax.ShapeDtypeStruct((n,), onp.dtype("uint32"))),
             lambda c: setattr(self, "_step_exec", c)),
        ]
        for L in self._buckets:
            specs.append((
                "prefill_%d" % L, L, self._prefill_jits[L],
                (p_s, state_s,
                 jax.ShapeDtypeStruct((pb, L), i32),
                 jax.ShapeDtypeStruct((pb,), i32),
                 jax.ShapeDtypeStruct((pb,), i32),
                 jax.ShapeDtypeStruct((pb,), onp.dtype("bool")),
                 jax.ShapeDtypeStruct((pb,), onp.dtype("uint32"))),
                (lambda c, _L=L:
                 self._prefill_execs.__setitem__(_L, c))))
        return specs

    def _program_key(self, name, bucket):
        from . import cache as _cache
        backend = _cache.backend_signature(
            mesh_axes=None, n_dev=1,
            device_kind=self._device.device_kind,
            platform=self._device.platform)
        input_sig = ("decode.%s:model=%s;slots=%d;pb=%d;temp=%g"
                     % (name, self._model.signature(), self._slots,
                        PREFILL_ROWS, self._temperature))
        if self._weight_quant:
            # quantized storage changes the program's argument layout
            # (int8 payloads + scale vectors): the quant scheme rides
            # the input signature so a wide replica can never adopt a
            # narrow executable (belt to the precision-mode suspender)
            input_sig += ";wq=%s" % self._weight_quant
        return _cache.cache_key(self._digest, self._policy.name,
                                bucket, input_sig, backend)

    def warmup(self, cache_dir=None):
        """AOT-compile (or deserialize) the full program family —
        state init, every prefill bucket, the decode step — BEFORE
        traffic; afterwards steady-state serving performs zero XLA
        compiles regardless of slot join/retire churn
        (``stats()['compiles']`` stays frozen, ``CompileWatch`` counts
        nothing post-warmup).

        ``cache_dir`` activates the persistent executable cache with
        the Predictor key discipline — (params digest, precision mode,
        bucket, input signature, backend) — extended to the decode
        family via per-program input signatures. A warm replica
        deserializes every program with zero compiles and serves
        bitwise-identical token streams (the ``dryrun_decode`` gate).
        Defaults to ``$MXNET_COMPILE_CACHE_DIR/aot`` when set."""
        from . import cache as _cache
        if cache_dir is None:
            root = os.environ.get("MXNET_COMPILE_CACHE_DIR")
            cache_dir = os.path.join(root, "aot") if root else None
        else:
            cache_dir = os.path.join(str(cache_dir), "aot")
        store = _cache.ExecutableCache(cache_dir) if cache_dir else None
        watch = telemetry.compile_watch()
        report = {}
        with watch.warmup_scope():
            for name, bucket, jit_fn, args, install in \
                    self._program_specs():
                t0 = time.perf_counter()
                source = self._warm_program(
                    name, bucket, jit_fn, args, install, store, watch)
                ms = (time.perf_counter() - t0) * 1000.0
                self._stats.note_warmup_bucket(
                    bucket, ms, source if store else None)
                report[name] = {"warmup_ms": round(ms, 3),
                                "source": source}
            if self._state is None:
                self._state = self._launch_init()
        self._warmed = True
        self._warmup_report = report
        return report

    def _warm_program(self, name, bucket, jit_fn, abstract_args,
                      install, store, watch):
        """Load-or-compile one program (the Predictor ``_warm_bucket``
        discipline): deserialize the crc-verified entry, else AOT
        compile and commit it; either way the compiled executable is
        INSTALLED so the request path never touches a jit wrapper."""
        from . import cache as _cache
        key = self._program_key(name, bucket)
        loaded, source = None, "compiled"
        if store is not None:
            try:
                payload, in_tree, out_tree = store.load(key)
                from jax.experimental import serialize_executable as _se
                # the engine's one device, not the backend-wide default
                loaded = _se.deserialize_and_load(
                    payload, in_tree, out_tree,
                    backend=self._device.client,
                    execution_devices=[self._device])
                source = "deserialized"
            except _cache.CacheMiss as e:
                log = logger.info if e.reason == "absent" \
                    else logger.warning
                log("decode program %s: executable cache %s — falling "
                    "back to a fresh compile (%s)",
                    name, e.reason, getattr(e, "detail", "") or "")
            except Exception as e:  # noqa: BLE001
                logger.warning(
                    "decode program %s: cached executable failed to "
                    "deserialize (%s) — falling back to a fresh "
                    "compile", name, e)
        if loaded is None:
            compiled = jit_fn.lower(*abstract_args).compile()
            if store is not None:
                try:
                    from jax.experimental import \
                        serialize_executable as _se
                    payload, in_tree, out_tree = _se.serialize(compiled)
                    store.store(key, payload, in_tree, out_tree)
                except Exception as e:  # noqa: BLE001 - best-effort
                    logger.warning(
                        "decode program %s: could not persist the "
                        "compiled executable (%s) — the next replica "
                        "will recompile", name, e)
            loaded = compiled
        install(loaded)
        if store is not None:
            if source == "deserialized":
                watch.note_cache_hit()
            else:
                watch.note_cache_miss()
        return source if store else "jit"

    def warmup_report(self):
        """Per-program outcome of the last :meth:`warmup` —
        ``{name: {"warmup_ms", "source"}}`` with source
        ``"deserialized"`` / ``"compiled"`` / ``"jit"``."""
        return {k: dict(v) for k, v in self._warmup_report.items()}

    # -- prefill parity ---------------------------------------------------
    def prefill_parity(self, prompt):
        """Bitwise witness for the bucket ladder: the padded-bucket
        prefill's final-position logits for ``prompt`` equal a
        reference whole-sequence forward at the EXACT length (no
        padding, no masking in effect). Uses scratch state — never
        touches live slots. Returns True on bitwise equality."""
        import jax
        import jax.numpy as jnp
        prompt = [int(t) for t in prompt]
        watch = telemetry.compile_watch()
        with watch.suppressed():
            scratch = self._launch_init()
            _, _, logits = self._run_prefill_chunks(
                scratch, 0, prompt, 0)
            L = len(prompt)
            ref_jit = self._ref_jits.get(L)
            if ref_jit is None:
                model, pb = self._model, PREFILL_ROWS

                def ref_fn(params, tokens, lengths):
                    rows0 = self._state_zeros(pb)
                    _, lg = model.prefill(self._dense_params(params),
                                          tokens, lengths, rows0)
                    return lg
                ref_jit = self._ref_jits[L] = jax.jit(ref_fn)
            toks = onp.zeros((PREFILL_ROWS, L), onp.int32)
            toks[0, :] = prompt
            lengths = onp.array([L, 0], onp.int32)
            ref = ref_jit(self._dparams, jnp.asarray(toks),
                          jnp.asarray(lengths))
        return bool(onp.array_equal(onp.asarray(ref)[0],
                                    onp.asarray(logits)[0]))

    # -- submission -------------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, seed=0,
               timeout_ms=None):
        """Queue one sequence; returns its :class:`DecodeRequest`
        future. ``max_new_tokens`` is clamped to
        ``MXNET_SERVE_DECODE_MAX_STEPS``. Raises :class:`ServerClosed`
        after shutdown, :class:`QueueFull` at capacity, and
        :class:`TenantShed` when ``shed_on_breach`` and the TTFT
        objective is in breach.

        ``timeout_ms`` is a per-request admission deadline (the
        ``DynamicBatcher.submit(timeout_ms=)`` contract, applied to
        the TTFT phase): a request still queued past its deadline
        fails its future with :class:`RequestTimeout` instead of
        prefilling, and the miss lands in the TTFT SLO tracker as a
        timeout — how the gateway propagates a client's
        ``X-Deadline-Ms`` into the decode plane."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("decode prompt must be non-empty")
        if any(t < 0 or t >= self._model.vocab_size for t in prompt):
            raise MXNetError("prompt token out of range [0, %d)"
                             % self._model.vocab_size)
        if self._closed:
            raise ServerClosed("decode engine is shut down")
        if (self._shed_on_breach and self.slo_ttft is not None
                and self.slo_ttft.breached_cached()):
            self._stats.note_shed()
            self.slo_ttft.record(outcome="reject")
            raise TenantShed(
                "decode TTFT objective in multi-window breach — "
                "request shed at admission")
        with self._cond:
            if self._closed:
                raise ServerClosed("decode engine is shut down")
            if len(self._queue) >= self._max_queue:
                self._stats.note_reject()
                if self.slo_ttft is not None:
                    self.slo_ttft.record(outcome="reject")
                raise QueueFull("decode queue at capacity (%d)"
                                % self._max_queue)
            req = DecodeRequest(
                self._stats.new_request_id(), prompt,
                min(int(max_new_tokens), self._max_steps), seed,
                timeout_ms=timeout_ms)
            self._queue.append(req)
            self._stats.note_request()
            self._cond.notify_all()
        return req

    def generate(self, prompt, max_new_tokens=32, seed=0, timeout=None):
        """Blocking convenience: :meth:`submit` + ``result()``."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           seed=seed).result(timeout=timeout)

    # -- scheduler --------------------------------------------------------
    def start(self):
        """Start the scheduler thread (no-op when running)."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._thread = threading.Thread(
            target=self._loop, name="mxtpu-decode", daemon=True)
        self._thread.start()
        return self

    def _any_active(self):
        return bool(self._active.any())

    def _loop(self):
        while True:
            with self._cond:
                while (not self._closed and not self._queue
                       and not self._any_active()
                       and not any(r is not None and r._cancel
                                   for r in self._slot_req)):
                    self._cond.wait(0.05)
                no_drain = self._closed and not self._drain
                done = (self._closed and not self._queue
                        and not self._any_active())
            if no_drain:
                self._fail_pending(ServerClosed(
                    "decode engine shut down without drain"))
                return
            if done:
                return
            try:
                self._tick()
            except Exception as e:  # noqa: BLE001 - supervised loop
                if not self._on_crash(e):
                    return

    def _tick(self):
        if self._state is None:
            # lazy so an un-warmed engine still works; after warmup()
            # this ran from the installed state_init executable already
            self._state = self._launch_init()
        if _faults.armed():
            _faults.check("serving.decode_worker", step=self._n_steps)
        self._admit_pending()
        if _faults.armed() and _faults.fires("serving.decode_abandon",
                                             step=self._n_steps):
            self._abandon_oldest()
        for s in range(self._slots):
            req = self._slot_req[s]
            if req is not None and req._cancel:
                self._retire(s, "abandoned", RequestAbandoned(
                    "decode request %s cancelled by the client after "
                    "%d tokens" % (req.id, len(req.tokens()))))
        if not self._any_active():
            return
        if _faults.armed():
            _faults.check("serving.decode_step", step=self._n_steps)
        t0 = time.perf_counter()
        n_active = int(self._active.sum())
        state, nxt = self._launch_step(
            self._state, self._cur_tok, self._active, self._steps_in,
            self._seeds)
        nxt_host = onp.asarray(nxt)
        self._state = state
        dt = time.perf_counter() - t0
        self._busy_s += dt
        self._n_steps += 1
        self._c_steps.add()
        self._occ_sum += n_active / float(self._slots)
        self._g_occupancy.set(round(n_active / float(self._slots), 4))
        self._stats.note_batch(self._slots, n_active)
        self._cur_tok = nxt_host.astype(onp.int32)
        for s in range(self._slots):
            if not self._active[s]:
                continue
            self._steps_in[s] += 1
            self._emit(s, int(nxt_host[s]))

    def _admit_pending(self):
        while True:
            with self._cond:
                if not self._queue:
                    return
                free = [s for s in range(self._slots)
                        if self._slot_req[s] is None]
                if not free:
                    return
                req = self._queue.popleft()
            if req._cancel:
                req._resolve("abandoned", RequestAbandoned(
                    "decode request %s cancelled while queued"
                    % req.id))
                self._c_abandoned.add()
                continue
            if req.deadline is not None and time.time() > req.deadline:
                age_ms = (time.time() - req.t_submit) * 1000.0
                req._resolve("timeout", RequestTimeout(
                    "decode request %s expired after %.0f ms in queue "
                    "(deadline %.0f ms)"
                    % (req.id, age_ms, req.timeout_ms)))
                self._stats.note_timeout(age_ms)
                if self.slo_ttft is not None:
                    self.slo_ttft.record(age_ms, "timeout")
                if telemetry.enabled():
                    self._stats.note_trace(
                        req.id, rows=1, bucket=0,
                        phases={"queue_wait_ms": age_ms,
                                "prefill_ms": 0.0, "decode_ms": 0.0,
                                "resolve_ms": 0.0},
                        outcome="timeout", ts_end=time.time())
                continue
            try:
                self._admit(free[0], req)
            except BaseException as e:
                req._resolve("error", WorkerCrashed(
                    "decode scheduler crashed while prefilling "
                    "request %s" % req.id))
                self._stats.note_error()
                raise

    def _admit(self, slot, req):
        req.t_admit = time.time()
        req.slot = slot
        self._state, first_tok, _ = self._run_prefill_chunks(
            self._state, slot, req.prompt, req.seed, req=req)
        self._slot_req[slot] = req
        self._active[slot] = True
        self._cur_tok[slot] = first_tok
        self._steps_in[slot] = 1
        self._seeds[slot] = onp.uint32(req.seed)
        self._transcript.append(
            ("admit", req.id, slot, self._n_steps))
        req.t_first = time.time()
        ttft = req.ttft_ms
        self._ttft_ring.append(ttft)
        self._h_ttft.observe(ttft)
        if self.slo_ttft is not None:
            self.slo_ttft.record(ttft, "ok")
        self._emit(slot, first_tok)

    def _run_prefill_chunks(self, state, slot, prompt, seed, req=None):
        """Run one prompt through the bucket ladder into ``slot`` of
        ``state``: each chunk pads to its bucket, non-first chunks
        gather the slot row back (``resume``) so state is continuous;
        returns (state, first generated token, final-chunk logits)."""
        top = self._buckets[-1]
        pos, resume = 0, False
        first_tok, logits = 0, None
        pb = PREFILL_ROWS
        seeds = onp.zeros((pb,), onp.uint32)
        seeds[0] = onp.uint32(seed)
        while pos < len(prompt):
            chunk = prompt[pos:pos + top]
            L = self.bucket_for(len(chunk))
            toks = onp.zeros((pb, L), onp.int32)
            toks[0, :len(chunk)] = chunk
            lengths = onp.zeros((pb,), onp.int32)
            lengths[0] = len(chunk)
            idx = onp.full((pb,), self._slots, onp.int32)
            idx[0] = slot
            res = onp.zeros((pb,), onp.bool_)
            res[0] = resume
            state, logits, first = self._launch_prefill(
                L, state, toks, lengths, idx, res, seeds)
            self._c_prefills.add()
            self._stats.scope.counter(
                "prefill_bucket_hits.%d" % L).add()
            if req is not None:
                req.bucket = L
            pos += len(chunk)
            resume = True
            first_tok = int(onp.asarray(first)[0])
        return state, first_tok, logits

    def _emit(self, slot, tok):
        req = self._slot_req[slot]
        req._append(tok)
        self._n_tokens += 1
        self._c_tokens.add()
        if ((self._eos_id is not None and tok == self._eos_id)
                or len(req.tokens()) >= req.max_new_tokens):
            self._retire(slot, "ok")

    def _retire(self, slot, outcome, exc=None):
        req = self._slot_req[slot]
        req.t_done = time.time()
        n_tok = len(req.tokens())
        decode_ms = (req.t_done - req.t_first) * 1000.0 \
            if req.t_first else 0.0
        if outcome == "ok":
            self._stats.note_completed(
                (req.t_done - req.t_submit) * 1000.0)
            if self.slo_token is not None and n_tok > 1:
                self.slo_token.record(decode_ms / (n_tok - 1), "ok")
        elif outcome == "abandoned":
            self._c_abandoned.add()
            if self.slo_token is not None:
                self.slo_token.record(decode_ms or None, "error")
        else:
            self._stats.note_error()
            if self.slo_token is not None:
                self.slo_token.record(decode_ms or None, "error")
        if telemetry.enabled():
            qw = ((req.t_admit - req.t_submit) * 1000.0
                  if req.t_admit else 0.0)
            pf = ((req.t_first - req.t_admit) * 1000.0
                  if req.t_first and req.t_admit else 0.0)
            self._stats.note_trace(
                req.id, rows=1, bucket=req.bucket or 0,
                phases={"queue_wait_ms": qw, "prefill_ms": pf,
                        "decode_ms": decode_ms, "resolve_ms": 0.0},
                outcome=outcome, ts_end=req.t_done)
        self._transcript.append(
            ("retire", req.id, slot, n_tok, outcome, self._n_steps))
        self._slot_req[slot] = None
        self._active[slot] = False
        req._resolve(outcome, exc)
        with self._cond:
            self._cond.notify_all()

    def _abandon_oldest(self):
        """The ``serving.decode_abandon`` seam body: the oldest active
        request's client walks away mid-stream."""
        oldest, t = None, None
        for s in range(self._slots):
            req = self._slot_req[s]
            if req is not None and (t is None or req.t_admit < t):
                oldest, t = s, req.t_admit
        if oldest is not None:
            req = self._slot_req[oldest]
            self._retire(oldest, "abandoned", RequestAbandoned(
                "decode request %s abandoned mid-stream (injected "
                "client disconnect) after %d tokens"
                % (req.id, len(req.tokens()))))

    def _fail_pending(self, exc):
        """Resolve every queued + active request with ``exc`` (the
        no-drain shutdown / restart-storm path — futures never hang)."""
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
        for req in queued:
            req._resolve("error", exc)
            self._stats.note_error()
        for s in range(self._slots):
            if self._slot_req[s] is not None:
                self._retire(s, "error", exc)

    def _on_crash(self, e):
        """Supervised restart (the DynamicBatcher worker discipline).
        Unlike the one-shot batcher, in-flight decode sequences
        SURVIVE a scheduler crash — the slot state is device-resident
        and the loop resumes stepping it. Returns False when the
        restart budget is exhausted (everything failed loudly)."""
        self._restarts += 1
        self._stats.note_worker_restart()
        logger.warning(
            "decode scheduler crashed (restart %d/%d): %s — slot "
            "state is device-resident, in-flight sequences resume",
            self._restarts, self._max_restarts, e, exc_info=True)
        if self._restarts > self._max_restarts:
            crash = WorkerCrashed(
                "decode scheduler exceeded %d restarts"
                % self._max_restarts)
            crash.__cause__ = e
            with self._cond:
                self._closed = True
            self._fail_pending(crash)
            return False
        return True

    # -- lifecycle --------------------------------------------------------
    def shutdown(self, drain=True, timeout=None):
        """Stop the engine. ``drain=True`` finishes every queued and
        in-flight sequence first; ``drain=False`` resolves them all
        with :class:`ServerClosed` immediately. Either way no future
        is left hanging (pinned by tests/test_serving_decode.py)."""
        with self._cond:
            self._closed = True
            self._drain = bool(drain)
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        if not drain:
            # belt-and-braces for a never-started engine
            self._fail_pending(ServerClosed(
                "decode engine shut down without drain"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=True)
        return False

    def release(self):
        """Drop the ``decode.<i>`` registry scope (long-lived
        multi-tenant processes discarding an engine)."""
        self._stats.release()

    # -- reading ----------------------------------------------------------
    def transcript(self):
        """The slot lifecycle transcript — ``("admit", req_id, slot,
        step)`` and ``("retire", req_id, slot, n_tokens, outcome,
        step)`` tuples in order. With a fixed arrival transcript
        (``start=False``, submit, :meth:`start`) it is a pure function
        of (seed, arrival order) — the determinism contract."""
        return list(self._transcript)

    def request_traces(self):
        return self._stats.request_traces()

    def stats(self):
        """The ServingStats snapshot plus a ``decode`` section:
        steps, tokens, tokens_per_sec (over device-busy wall),
        avg_occupancy, TTFT percentiles, abandon count."""
        s = self._stats.snapshot()
        ttfts = sorted(self._ttft_ring)
        s["decode"] = {
            "slots": self._slots,
            "buckets": list(self._buckets),
            "steps": int(self._n_steps),
            "tokens": int(self._n_tokens),
            "tokens_per_sec": round(
                self._n_tokens / self._busy_s, 2)
            if self._busy_s > 0 else None,
            "avg_occupancy": round(
                self._occ_sum / self._n_steps, 4)
            if self._n_steps else None,
            "abandoned": int(self._c_abandoned.value),
            "ttft_ms": {
                "count": len(ttfts),
                "p50": ServingStats._pct(ttfts, 50),
                "p99": ServingStats._pct(ttfts, 99),
            },
            "precision_mode": self._policy.name,
            "weight_quant": self._weight_quant,
            "weight_bytes": self.weight_bytes(),
        }
        return s
