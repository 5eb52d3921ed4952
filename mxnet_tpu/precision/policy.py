"""Precision policies — declarative, opt-in byte-count levers.

The ResNet-50 train step takes three times its roofline's least time
(PERF.md §5) and the compiled program moves more bytes than the
algorithm needs: one lever is shipping fewer bytes through it.  A
:class:`PrecisionPolicy` names one point in that trade space and the
Module/Updater/executor stack applies it at the existing seams:

* ``opt_state_dtype="bfloat16"`` — optimizer state (momentum, Adam
  moments) is STORED as bf16 leaves while parameters stay f32 masters;
  the fused per-param apply upcasts to f32, computes, and rounds back
  on the way out (:func:`wrap_fused_apply`).  For sgd-momentum this
  halves 2 of the 5 param-sized streams the analytic optimizer account
  tracks (``3p + 2s`` rule, telemetry.introspect).
* ``compute_dtype="bfloat16"`` — the existing fwd/bwd activation cast
  seam (``MeshExecutorGroup`` ``compute_dtype``), named so a mode can
  carry it.
* ``remat=...`` — a named ``jax.checkpoint`` policy for the segmented
  rematerialization evaluator: ``"none"``, ``"full"`` (a segment's
  backward pass is handed ``FullyConnected``'s, attention's, the
  selective scan's and the gated delta rule's outputs and makes the
  rest again),
  ``"dots_saveable"`` (keep matmul/conv outputs, and attention's),
  ``"offload_bn_stats"`` (dots_saveable + keep the tagged per-channel
  BatchNorm statistics), or a raw jax policy callable
  (``jax.checkpoint_policies.nothing_saveable``: a segment keeps
  nothing).  Trades recompute FLOPs for activation bytes.
* ``act_cast="int8"|"fp8"`` (EXPERIMENTAL, ``MXNET_PRECISION_EXPERIMENTAL=1``)
  — fake-quantized low-bit casts at the input seam, with device-side
  dynamic loss scaling for the narrow backward.
* ``weight_quant="int8"`` — parameters STORED as per-channel symmetric
  int8 with f32 scales and dequantized inside the compiled program
  (:mod:`mxnet_tpu.precision.quant`): ~4x fewer weight bytes per decode
  token on the memory-bound serving path.  Serving-only.
* ``narrow_math="int8"|"fp8"`` — the dot/conv call sites emit NATIVE
  narrow GEMMs (int8xint8->int32 / e4m3 operands via
  ``preferred_element_type``) instead of fake-quantized wide math, with
  static per-layer activation scales from a calibration pass
  (:class:`mxnet_tpu.precision.quant.CalibrationTable`).  Serving-only.

Every mode carries the same contract the rest of the repo lives by:
exact WITHIN-mode reproducibility (same mode + seed -> bit-identical
params, zero post-warmup retraces), an accuracy gate vs the f32
reference (ci.sh precision gate), and an introspection witness — the
``programs.*`` bytes resolve AFTER the policy is applied, so
``analyze_compiled`` proves the bytes actually dropped.
"""
from __future__ import annotations

import contextlib
import contextvars
import os

from ..base import MXNetError

__all__ = ["PrecisionPolicy", "MODES", "resolve", "register_mode",
           "mode_name", "canon_dtype", "canon_remat", "state_np_dtype",
           "wrap_fused_apply", "fake_cast", "remat_checkpoint_policy",
           "remat_kept_names", "keep", "keeping", "note_kept",
           "loss_scale_config"]


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------
def canon_dtype(d, field="dtype"):
    """Canonical storage-dtype spelling: ``None`` (= f32 / follow the
    param), or ``"bfloat16"``.  Accepts the common aliases."""
    if d is None:
        return None
    s = str(d).lower()
    if s in ("f32", "fp32", "float32"):
        return None
    if s in ("bf16", "bfloat16"):
        return "bfloat16"
    raise MXNetError(
        "precision %s must be None/'float32' or 'bfloat16' (got %r)"
        % (field, d))


def canon_remat(r):
    """Canonical remat-policy name: ``None`` (no remat), ``"full"``,
    ``"dots"`` (jax dots_saveable), ``"bn_stats"`` (dots_saveable +
    saved BatchNorm statistics), or a raw jax checkpoint-policy
    callable passed through."""
    if r is None or callable(r):
        return r
    s = str(r).lower()
    if s == "none":
        return None
    if s == "full":
        return "full"
    if s in ("dots", "dots_saveable"):
        return "dots"
    if s in ("bn_stats", "offload_bn_stats"):
        return "bn_stats"
    raise MXNetError(
        "remat policy must be one of 'none', 'full', 'dots_saveable', "
        "'offload_bn_stats' or a jax checkpoint-policy callable "
        "(got %r)" % (r,))


def state_np_dtype(name, weight_dtype):
    """The numpy dtype optimizer-state zeros are allocated with for a
    canonical ``state_dtype`` spelling (``None`` follows the weight)."""
    if name is None:
        return weight_dtype
    if name == "bfloat16":
        import jax.numpy as jnp
        return jnp.bfloat16
    import numpy as onp
    return onp.dtype(name)


# ---------------------------------------------------------------------------
# the policy object + named-mode registry
# ---------------------------------------------------------------------------
class PrecisionPolicy(object):
    """One named point in the precision trade space (module docstring).

    All fields default to the f32 baseline; a policy with every field
    at its default is a no-op and binds byte-identical programs to a
    module constructed without one (pinned by tests)."""

    __slots__ = ("name", "compute_dtype", "opt_state_dtype", "remat",
                 "act_cast", "weight_quant", "narrow_math", "calibration",
                 "loss_scale", "loss_scale_window", "experimental")

    def __init__(self, name=None, compute_dtype=None, opt_state_dtype=None,
                 remat=None, act_cast=None, weight_quant=None,
                 narrow_math=None, calibration=None, loss_scale=None,
                 loss_scale_window=None, experimental=False):
        self.compute_dtype = canon_dtype(compute_dtype, "compute_dtype")
        self.opt_state_dtype = canon_dtype(opt_state_dtype,
                                           "opt_state_dtype")
        self.remat = canon_remat(remat)
        if act_cast not in (None, "int8", "fp8"):
            raise MXNetError("act_cast must be None, 'int8' or 'fp8' "
                             "(got %r)" % (act_cast,))
        self.act_cast = act_cast
        if weight_quant not in (None, "int8"):
            raise MXNetError("weight_quant must be None or 'int8' "
                             "(got %r)" % (weight_quant,))
        self.weight_quant = weight_quant
        if narrow_math not in (None, "int8", "fp8"):
            raise MXNetError("narrow_math must be None, 'int8' or 'fp8' "
                             "(got %r)" % (narrow_math,))
        self.narrow_math = narrow_math
        # a CalibrationTable (precision.quant) or None; NOT part of the
        # mode name — the same int8_serve mode serves any calibration,
        # but the table digest goes into describe()/cache keys so two
        # calibrations never share a compiled program
        self.calibration = calibration
        # None means "the env/default at BIND time" — the registry's
        # named modes are built at import, so resolving the
        # MXNET_PRECISION_LOSS_SCALE/SCALE_WINDOW knobs here would
        # freeze them before the user's environment is read
        # (loss_scale_config resolves them lazily instead)
        self.loss_scale = None if loss_scale is None else float(loss_scale)
        self.loss_scale_window = None if loss_scale_window is None \
            else int(loss_scale_window)
        self.experimental = bool(experimental)
        self.name = str(name) if name else self._auto_name()

    def _auto_name(self):
        """Deterministic name from the canonical fields, so an ad-hoc
        policy recorded into a checkpoint manifest matches the policy a
        resume run builds from the same flags."""
        parts = []
        if self.compute_dtype:
            parts.append("compute=%s" % self.compute_dtype)
        if self.opt_state_dtype:
            parts.append("opt=%s" % self.opt_state_dtype)
        if self.remat is not None:
            parts.append("remat=%s" % (self.remat if not
                                       callable(self.remat) else "custom"))
        if self.act_cast:
            parts.append("act=%s" % self.act_cast)
        if self.weight_quant:
            parts.append("wq=%s" % self.weight_quant)
        if self.narrow_math:
            parts.append("nm=%s" % self.narrow_math)
        # loss-scale fields change numerics (the scaler engages and its
        # doubling schedule differs per window), so a scale-only policy
        # must NOT collide with the "f32" baseline name — the manifest
        # adoption and serving-refusal checks compare by name
        if self.loss_scale is not None:
            parts.append("ls=%g" % self.loss_scale)
        if self.loss_scale_window is not None:
            parts.append("lsw=%d" % self.loss_scale_window)
        if not parts:
            return "f32"
        return "custom(%s)" % ",".join(parts)

    def is_default(self):
        """True when this policy changes nothing vs the f32 baseline."""
        return (self.compute_dtype is None and self.opt_state_dtype is None
                and self.remat is None and self.act_cast is None
                and self.weight_quant is None and self.narrow_math is None
                and self.loss_scale is None)

    def serving_only(self):
        """True when the policy only makes sense for inference programs
        (quantized weight storage / native narrow GEMMs have no gradient
        story); ``Module.bind(for_training=True)`` refuses these."""
        return self.weight_quant is not None or self.narrow_math is not None

    def describe(self):
        return {"name": self.name,
                "compute_dtype": self.compute_dtype or "float32",
                "opt_state_dtype": self.opt_state_dtype or "float32",
                "remat": ("custom" if callable(self.remat)
                          else (self.remat or "none")),
                "act_cast": self.act_cast,
                "weight_quant": self.weight_quant,
                "narrow_math": self.narrow_math,
                "calibration_digest": (None if self.calibration is None
                                       else self.calibration.digest()),
                "loss_scale": self.loss_scale,
                "loss_scale_window": self.loss_scale_window,
                "experimental": self.experimental}

    def __repr__(self):
        return "PrecisionPolicy(%r)" % (self.describe(),)


MODES = {
    # the reference point: byte-identical programs to no policy at all
    "f32": PrecisionPolicy("f32"),
    # activations/grads in bf16 through the existing compute_dtype seam
    "bf16": PrecisionPolicy("bf16", compute_dtype="bfloat16"),
    # optimizer state stored bf16, f32 master params + f32 update math
    "bf16_opt": PrecisionPolicy("bf16_opt", opt_state_dtype="bfloat16"),
    # THE default combined HBM lever (ROADMAP item 2): bf16 opt-state +
    # dots_saveable remat — fewer state bytes, fewer activation bytes,
    # f32 compute numerics family
    "combined": PrecisionPolicy("combined", opt_state_dtype="bfloat16",
                                remat="dots_saveable"),
    # experimental narrow modes (MXNET_PRECISION_EXPERIMENTAL=1):
    # fake-quantized input casts + dynamic loss scaling on device
    "int8_act": PrecisionPolicy("int8_act", compute_dtype="bfloat16",
                                act_cast="int8", experimental=True),
    "fp8": PrecisionPolicy("fp8", compute_dtype="bfloat16",
                           act_cast="fp8", experimental=True),
    # weight-only int8: params STORED as per-channel-symmetric int8 +
    # f32 scales, dequantized inside the compiled program — 4x fewer
    # weight bytes on the memory-bound decode path, f32 compute, no
    # gradient story (serving-only)
    "int8_weight": PrecisionPolicy("int8_weight", weight_quant="int8"),
    # calibrated int8 serving: real int8 activation math through the
    # native dot seam, with static per-layer scales from a
    # CalibrationTable (tolerance-gated vs the f32 reference)
    "int8_serve": PrecisionPolicy("int8_serve", act_cast="int8",
                                  narrow_math="int8"),
    # native fp8 GEMMs (e4m3 operands + preferred_element_type) — the
    # numerics family is backend-dependent, so it stays experimental
    "fp8_native": PrecisionPolicy("fp8_native", compute_dtype="bfloat16",
                                  act_cast="fp8", narrow_math="fp8",
                                  experimental=True),
}


def register_mode(policy):
    """Register a custom named mode (overwrites an existing name)."""
    assert isinstance(policy, PrecisionPolicy)
    MODES[policy.name] = policy
    return policy


def resolve(spec=None):
    """Resolve a mode name / :class:`PrecisionPolicy` / None into a
    policy (or None = the implicit f32 baseline).  ``None`` consults
    ``MXNET_PRECISION_MODE`` so a deployment can flip the default
    without code changes; experimental modes additionally require
    ``MXNET_PRECISION_EXPERIMENTAL=1``."""
    if spec is None:
        spec = os.environ.get("MXNET_PRECISION_MODE") or None
        if spec is None:
            return None
    if isinstance(spec, PrecisionPolicy):
        pol = spec
    else:
        pol = MODES.get(str(spec))
        if pol is None:
            raise MXNetError(
                "unknown precision mode %r; known modes: %s (or pass a "
                "PrecisionPolicy)" % (spec, sorted(MODES)))
    if pol.experimental and os.environ.get(
            "MXNET_PRECISION_EXPERIMENTAL", "0") != "1":
        raise MXNetError(
            "precision mode %r is experimental; set "
            "MXNET_PRECISION_EXPERIMENTAL=1 to opt in" % pol.name)
    return pol


def mode_name(policy):
    """The recorded mode name for a resolved policy (None -> 'f32') —
    THE one spelling checkpoint manifests and the serving-side check
    compare."""
    return "f32" if policy is None else policy.name


# ---------------------------------------------------------------------------
# the applying pieces
# ---------------------------------------------------------------------------
def wrap_fused_apply(fa, state_dtype):
    """Wrap an optimizer's pure per-param apply so narrow-stored state
    computes in f32 master math: state leaves upcast to f32 at entry,
    the new state rounds back to ``state_dtype`` on the way out.  The
    param update consumes the UNROUNDED f32 state (standard mixed-
    precision practice); between steps the state lives — and round-trips
    through checkpoints — at the storage dtype, which is what makes
    within-mode resume bit-exact."""
    def _cast(t, dt):
        if t is None:
            return None
        if isinstance(t, (tuple, list)):
            return tuple(_cast(x, dt) for x in t)
        return t.astype(dt)

    def wrapped(jnp, p, g, s, lr, wd):
        new_p, new_s = fa(jnp, p, g, _cast(s, jnp.float32), lr, wd)
        return new_p, _cast(new_s, state_dtype)

    return wrapped


def fake_cast(jnp, v, kind):
    """The experimental low-bit input cast: a value-level round trip
    through the narrow format (fake quantization), so the program's
    numerics see the precision loss while the surrounding compute stays
    in the compute dtype.  ``int8``: symmetric per-tensor scale to the
    [-127, 127] grid; ``fp8``: e4m3 round trip."""
    if kind == "fp8":
        import ml_dtypes
        return v.astype(ml_dtypes.float8_e4m3fn).astype(v.dtype)
    if kind == "int8":
        f32 = jnp.float32
        vf = v.astype(f32)
        amax = jnp.max(jnp.abs(vf))
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(vf / scale), -127.0, 127.0)
        return (q * scale).astype(v.dtype)
    raise MXNetError("unknown act_cast %r" % (kind,))


# ---------------------------------------------------------------------------
# remat: what a segment's backward pass is handed instead of re-running
# ---------------------------------------------------------------------------
# The names an op gives a value that is dear to make again (:func:`keep`)
# and, per canonical remat spec, the names its policy keeps.  One table:
# the ops import the names, the segmented evaluator the rest.
PRODUCT = "product"        # FullyConnected's output, in the activation type
ATTENTION = "attention"    # attention's output (and the kernel's log-sum-exp)
BN_STATS = "bn_stats"      # BatchNorm's per-channel batch statistics
SCAN = "scan"              # a selective scan's or a delta rule's output
#                            and chunk-boundary states

_KEPT_NAMES = {"full": (PRODUCT, ATTENTION, SCAN),
               "dots": (ATTENTION,),
               "bn_stats": (BN_STATS, ATTENTION)}

_KEPT_BAG = contextvars.ContextVar("mxnet_tpu_remat_kept",
                                   default=(None, ()))


def note_kept(name, nbytes):
    """Tell the evaluator that collects (:func:`keeping`) that a value
    of ``nbytes`` was named ``name``; nothing where none collects, or
    not that name."""
    bag, names = _KEPT_BAG.get()
    if name in names:
        bag[name] = bag.get(name, 0) + int(nbytes)


def keep(value, name):
    """Name ``value`` for the remat policies (``checkpoint_name``): a
    segment's backward pass is handed a value whose name its policy
    keeps, and makes every other again.  Outside a checkpoint the name
    is the identity and the program is unchanged."""
    from jax.ad_checkpoint import checkpoint_name
    note_kept(name, value.size * value.dtype.itemsize)
    return checkpoint_name(value, name)


@contextlib.contextmanager
def keeping(bag, names):
    """While the body traces, add to ``bag`` (``{name: bytes}``, Python
    ints by shape) the bytes of the values given one of ``names`` in
    it; a ``bag`` of None collects nothing."""
    tok = _KEPT_BAG.set((bag, names if bag is not None else ()))
    try:
        yield
    finally:
        _KEPT_BAG.reset(tok)


def remat_kept_names(remat):
    """The names whose values a canonical remat spec keeps inside a
    segment; none for a callable, which decides for itself."""
    if callable(remat):
        return ()
    if remat not in _KEPT_NAMES:
        raise MXNetError("unknown remat policy %r" % (remat,))
    return _KEPT_NAMES[remat]


def remat_checkpoint_policy(remat):
    """The ``jax.checkpoint`` policy object for a canonical remat spec
    (:func:`canon_remat` output).  Every named spec keeps the values
    named in :func:`remat_kept_names`: ``"full"`` those alone (the
    matrix products', attention's, the selective scan's and the gated
    delta rule's outputs; norms, rotations, casts and activations are
    made again), ``"dots"`` and ``"bn_stats"`` beside what
    ``dots_saveable`` sees (attention is named because on a TPU it is a
    Pallas call, no ``dot_general``).  A callable passes
    through: ``jax.checkpoint_policies.nothing_saveable`` is the strict
    "a segment keeps nothing"."""
    import jax
    if callable(remat):
        return remat
    by_name = jax.checkpoint_policies.save_only_these_names(
        *remat_kept_names(remat))
    if remat == "full":
        return by_name
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_saveable, by_name)


def loss_scale_config(policy):
    """Dynamic-loss-scale configuration for a policy, or None when the
    policy does not scale.  The scale lives ON DEVICE as a (scale f32,
    good-steps i32) pair carried through the fused step program: grads
    found non-finite skip the update and halve the scale; after
    ``window`` consecutive finite steps the scale doubles (clamped to
    [1, 2^24]) — no readback on the step path.

    Policy fields left at None resolve HERE, at bind time, from
    ``MXNET_PRECISION_LOSS_SCALE`` (default 2^15) and
    ``MXNET_PRECISION_SCALE_WINDOW`` (default 2000) — never at import,
    so setting the knobs after ``import mxnet_tpu`` still works for
    the registry's named modes."""
    if policy is None or (policy.loss_scale is None
                          and policy.act_cast is None):
        return None
    init = policy.loss_scale if policy.loss_scale is not None else \
        float(os.environ.get("MXNET_PRECISION_LOSS_SCALE",
                             str(2.0 ** 15)))
    window = policy.loss_scale_window \
        if policy.loss_scale_window is not None else \
        int(os.environ.get("MXNET_PRECISION_SCALE_WINDOW", "2000"))
    return {"init": float(init), "window": int(window),
            "scale_max": 2.0 ** 24, "scale_min": 1.0}
