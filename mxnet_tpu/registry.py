"""Operator registry — the single registration point per op.

TPU-native redesign of the reference's *two* registration regimes (NNVM
``FCompute`` stateless ops + legacy stateful ``OperatorProperty``,
include/mxnet/op_attr_types.h:33-63 and include/mxnet/operator.h): here every
op is one record with

* ``fcompute(attrs, inputs, octx) -> [jnp outputs]`` — a pure JAX function
  (jnp/lax/pallas).  Gradients come from whole-graph ``jax.vjp`` so no per-op
  backward registration exists; ops with non-standard gradients (losses whose
  backward ignores head grads, e.g. SoftmaxOutput) wrap themselves in
  ``jax.custom_vjp`` inside their fcompute.
* shape/type inference: by default derived automatically with
  ``jax.eval_shape`` over fcompute; layer ops that must infer *parameter*
  shapes from data (FullyConnected's weight etc.) register a custom
  ``infer_shape`` with the reference's bidirectional-fill contract
  (returns (in_shapes, out_shapes, aux_shapes)).
* aux state (BatchNorm moving stats): declared via ``aux_names``; fcompute
  receives aux arrays appended to inputs and returns aux updates appended to
  outputs (the executor writes them back, replacing FMutateInputs).
* randomness: ``needs_rng`` ops receive a JAX PRNG key in ``octx.rng``
  (replaces the per-ctx kRandom resource, include/mxnet/resource.h:18-24).
"""
from __future__ import annotations

import ast
import re

import numpy as onp

from .base import MXNetError

__all__ = ["OpDef", "OpContext", "register", "get_op", "list_ops", "parse_attrs"]

_OP_REGISTRY = {}


class OpContext:
    """Per-invocation context handed to fcompute.

    Replaces the reference OpContext (include/mxnet/op_attr_types.h) —
    is_train flag + RunContext/Resources — with is_train + a PRNG key.
    """

    __slots__ = ("is_train", "rng")

    def __init__(self, is_train=False, rng=None):
        self.is_train = is_train
        self.rng = rng


class OpDef:
    """One registered operator."""

    def __init__(self, name, fcompute, arg_names=("data",), out_names=("output",),
                 aux_names=(), attr_types=None, infer_shape=None,
                 needs_rng=False, variable_args=None, num_outputs=None,
                 alias=(), backward_ignores_head_grads=False,
                 required_attrs=(), counters=()):
        self.name = name
        self.fcompute = fcompute
        # arg_names may be a callable(attrs) -> names for ops whose input
        # list depends on attrs (no_bias, prelu's gamma, ...), mirroring
        # OperatorProperty::ListArguments(param).
        self.arg_names = arg_names if callable(arg_names) else tuple(arg_names)
        self.out_names = tuple(out_names)
        # like arg_names, a callable(attrs) -> names where the states an
        # op keeps depend on its attributes (MoE's selection bias)
        self.aux_names = aux_names if callable(aux_names) \
            else tuple(aux_names)
        self.attr_types = attr_types or {}
        self._infer_shape = infer_shape
        self.needs_rng = needs_rng
        # attr key holding the (variable) number of inputs, e.g. Concat's
        # ``num_args`` (key_var_num_args in the reference registry).
        self.variable_args = variable_args
        self._num_outputs = num_outputs  # int, or callable(attrs)->int
        self.alias = tuple(alias)
        self.backward_ignores_head_grads = backward_ignores_head_grads
        # attrs with no usable default (dmlc::Parameter's .set_default-less
        # fields report "required" through GetAtomicSymbolInfo)
        self.required_attrs = tuple(required_attrs)
        # names this op may hand to :func:`count` while it traces; a
        # callable(attrs) -> names where they depend on the attributes
        self._counters = counters

    def list_counters(self, attrs=None):
        if callable(self._counters):
            return list(self._counters(attrs or {}))
        return list(self._counters)

    # -- arity -------------------------------------------------------------
    def list_arguments(self, attrs=None):
        if self.variable_args is not None:
            n = int((attrs or {}).get(self.variable_args, 1))
            return ["arg%d" % i for i in range(n)]
        if callable(self.arg_names):
            return list(self.arg_names(attrs or {}))
        return list(self.arg_names)

    def list_outputs(self, attrs=None):
        n = self.num_outputs(attrs)
        if n == len(self.out_names):
            return list(self.out_names)
        return ["%s%d" % (self.out_names[0], i) for i in range(n)]

    def list_auxiliary_states(self, attrs=None):
        if callable(self.aux_names):
            return list(self.aux_names(attrs or {}))
        return list(self.aux_names)

    def num_inputs(self, attrs=None):
        return len(self.list_arguments(attrs))

    def num_outputs(self, attrs=None):
        n = self._num_outputs
        if n is None:
            return len(self.out_names)
        if callable(n):
            return n(attrs or {})
        return n

    # -- inference ---------------------------------------------------------
    def infer_shape(self, attrs, in_shapes, aux_shapes=None):
        """Return (in_shapes, out_shapes, aux_shapes), filling unknowns.

        Mirrors OperatorProperty::InferShape's bidirectional contract
        (include/mxnet/operator.h); defaults to forward-only inference via
        jax.eval_shape when every input shape is known.
        """
        if self._infer_shape is not None:
            return self._infer_shape(attrs, list(in_shapes),
                                     list(aux_shapes or []))
        if any(s is None for s in in_shapes):
            return list(in_shapes), None, list(aux_shapes or [])
        out_shapes = [s.shape for s in self.abstract_eval(
            attrs, [_ShapeOnly(s) for s in in_shapes])]
        return list(in_shapes), out_shapes, list(aux_shapes or [])

    def abstract_eval(self, attrs, in_avals, is_train=False):
        """jax.eval_shape over fcompute; returns list of ShapeDtypeStruct."""
        import jax

        structs = [jax.ShapeDtypeStruct(a.shape, getattr(a, "dtype", onp.float32))
                   for a in in_avals]
        octx = OpContext(is_train=is_train,
                         rng=jax.ShapeDtypeStruct((2,), onp.uint32)
                         if self.needs_rng else None)

        def f(*xs):
            outs = self.fcompute(attrs, list(xs), octx)
            return tuple(outs)

        return list(jax.eval_shape(f, *structs))

    def __repr__(self):
        return "OpDef(%s)" % self.name


class _ShapeOnly:
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype=onp.float32):
        self.shape = tuple(shape)
        self.dtype = dtype


def f32_precision(x):
    """Matmul/conv precision for mxnet float32 semantics on TPU.

    XLA:TPU lowers f32 contractions to bf16xbf16 passes by default
    (~1e-2 relative error); the reference's f32 ops compute true f32 on
    GPU, so f32 inputs here request 'highest' (float32 accumulation).
    bf16/other dtypes keep the default fast path — the bench's
    compute_dtype="bfloat16" route is unaffected. Verified by
    tools/check_consistency_tpu.py (cpu<->tpu oracle).
    """
    import numpy as _np
    return "highest" if _np.dtype(x.dtype) == _np.float32 else None


def register(name, **kwargs):
    """Decorator: register ``fcompute`` under ``name`` (+ aliases)."""

    def _reg(fcompute):
        op = OpDef(name, fcompute, **kwargs)
        _OP_REGISTRY[name] = op
        for a in op.alias:
            _OP_REGISTRY[a] = op
        return fcompute

    return _reg


def get_op(name):
    try:
        return _OP_REGISTRY[name]
    except KeyError:
        raise MXNetError("Operator %s is not registered" % name)


def list_ops():
    return sorted(_OP_REGISTRY)


# ---------------------------------------------------------------------------
# attr parsing — replaces dmlc::Parameter string reflection
# ---------------------------------------------------------------------------
_TUPLE_RE = re.compile(r"^\(.*\)$|^\[.*\]$")


def _parse_value(v, ty=None):
    if ty is not None and not isinstance(v, str):
        if ty is bool:
            return bool(v)
        if ty in (int, float):
            return ty(v)
        if ty is tuple and isinstance(v, (list, tuple)):
            return tuple(v)
        if ty is str:
            return str(v)
        return v
    if not isinstance(v, str):
        return v
    s = v.strip()
    if ty is str:
        return s
    low = s.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    try:
        val = ast.literal_eval(s)
        if isinstance(val, list):
            val = tuple(val)
        if ty is not None and ty is not tuple and not isinstance(val, tuple):
            try:
                val = ty(val)
            except (TypeError, ValueError):
                pass
        return val
    except (ValueError, SyntaxError):
        return s


def parse_attrs(op, attrs):
    """Parse raw attrs (possibly all-string, from JSON) to typed python."""
    out = {}
    for k, v in attrs.items():
        out[k] = _parse_value(v, op.attr_types.get(k))
    return out


# ---------------------------------------------------------------------------
# current device mesh — how mesh-aware ops (MoE, RingAttention) learn the
# sharding context they trace under.  MeshExecutorGroup wraps its
# evaluator closures in use_mesh(mesh), so the contextvar is set exactly
# while the op fcomputes trace (and harmlessly during execution); the
# classic per-device executor leaves it None and the ops take their
# single-device paths.  Thread-local by contextvar semantics, so
# concurrently-bound groups on different threads cannot cross-talk.
# ---------------------------------------------------------------------------
import contextlib as _contextlib
import contextvars as _contextvars

_CURRENT_MESH = _contextvars.ContextVar("mxnet_tpu_current_mesh",
                                        default=None)


def current_mesh():
    """The Mesh the enclosing evaluator traces under, or None."""
    return _CURRENT_MESH.get()


@_contextlib.contextmanager
def use_mesh(mesh):
    tok = _CURRENT_MESH.set(mesh)
    try:
        yield
    finally:
        _CURRENT_MESH.reset(tok)


# ---------------------------------------------------------------------------
# op counters — how an op reports what it counted in a step (MoE: pairs
# computed, load) without a readback: while an evaluator traces under
# counting(), count(name, value) adds a traced scalar to the open bag;
# the evaluator returns the bag with its outputs, the train step folds
# it into a device tally, and fit reads that once an epoch into the
# report's counters (telemetry.last_fit()).
# ---------------------------------------------------------------------------
_COUNTER_BAG = _contextvars.ContextVar("mxnet_tpu_counter_bag", default=None)


def count(name, value):
    """Add a traced scalar to the named counter of the open bag;
    nothing where no evaluator collects."""
    bag = _COUNTER_BAG.get()
    if bag is not None:
        import jax.numpy as jnp
        value = jnp.asarray(value, jnp.float32)
        bag[name] = bag[name] + value if name in bag else value


@_contextlib.contextmanager
def counting():
    """Collect :func:`count` calls made while the body traces; yields
    the bag ``{name: traced f32 scalar}``."""
    bag = {}
    tok = _COUNTER_BAG.set(bag)
    try:
        yield bag
    finally:
        _COUNTER_BAG.reset(tok)
