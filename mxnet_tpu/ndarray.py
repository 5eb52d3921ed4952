"""NDArray — imperative tensor with engine-ordered mutation semantics.

TPU-native redesign of the reference NDArray (include/mxnet/ndarray.h:58,
src/ndarray/ndarray.cc). The reference pairs every NDArray with an engine Var
and pushes each mutation as an async engine op; buffers are mutable and
``Slice/At/Reshape`` alias memory (ndarray.h:286-346). JAX arrays are
immutable and async-by-construction, so here:

* a ``_Chunk`` (ndarray.h:376-432's Chunk) holds the *current* jax.Array;
  mutation swaps the chunk's array (a versioned buffer). Ordering hazards the
  engine resolved by Var scheduling are resolved by value semantics.
* views (``Slice``/``At``/``Reshape``) keep a reference to the parent chunk
  plus an axis-0 window and a view shape; writes through a view apply
  ``.at[start:stop].set`` on the parent, so reference aliasing behaviour is
  preserved observably.
* ``wait_to_read`` == ``block_until_ready`` (Engine::WaitForVar); dispatch is
  already async under JAX so there is nothing to schedule host-side.

Every registered operator (registry.py) is exposed as a function in this
module (the reference auto-generates these from the C API op list,
python/mxnet/ndarray.py _init_ndarray_module).
"""
from __future__ import annotations

import sys

import numpy as onp

from .base import MXNetError, numeric_types
from .context import Context, cpu, current_context
from . import registry as _registry
from . import engine as _engine
from . import random as _random

__all__ = ["NDArray", "array", "zeros", "ones", "empty", "full", "arange",
           "concatenate", "load", "save", "waitall", "imdecode", "onehot_encode"]

_DEFAULT_DTYPE = onp.float32
# _init_ndarray_module exposes ops at module level; an op is named "slice",
# so keep a handle on the builtin for internal use.
_py_slice = slice


def _jnp():
    import jax.numpy as jnp
    return jnp


class _Chunk:
    """Holds the current device buffer + its context (ndarray.h Chunk).

    ``force`` is an optional thunk installed by a pending (lazy) executor:
    reading the chunk first materializes the deferred computation — this is
    how forward+backward fuse into one XLA program while `exec.outputs`
    stays eagerly readable (the engine-Var WaitToRead contract).
    """

    __slots__ = ("arr", "ctx", "force")

    def __init__(self, arr, ctx):
        self.arr = arr
        self.ctx = ctx
        self.force = None


class NDArray:
    """Multi-dimensional, mutable-by-swap array on a device context."""

    __slots__ = ("_chunk", "_start", "_stop", "_vshape", "writable")

    def __init__(self, data=None, ctx=None, _chunk=None, _start=None,
                 _stop=None, _vshape=None, writable=True):
        if _chunk is not None:
            self._chunk = _chunk
        else:
            ctx = ctx or current_context()
            self._chunk = _Chunk(data, ctx)
        self._start = _start
        self._stop = _stop
        self._vshape = tuple(_vshape) if _vshape is not None else None
        self.writable = writable

    # ------------------------------------------------------------------ io
    def _read(self):
        """Current jnp value of this (possibly view) array."""
        if self._chunk.force is not None:
            f, self._chunk.force = self._chunk.force, None
            f()
        arr = self._chunk.arr
        if self._start is not None:
            arr = arr[self._start:self._stop]
        if self._vshape is not None and tuple(arr.shape) != self._vshape:
            arr = arr.reshape(self._vshape)
        return arr

    def _write(self, new):
        """Replace this array's contents with jnp value ``new``."""
        if not self.writable:
            raise MXNetError("trying to write to a readonly NDArray")
        chunk = self._chunk
        if chunk.force is not None:
            if self._start is None and self._vshape is None:
                chunk.force = None  # full overwrite supersedes pending value
            else:
                f, chunk.force = chunk.force, None
                f()
        if self._start is None and self._vshape is None:
            chunk.arr = new
            return
        if self._start is None:
            chunk.arr = new.reshape(chunk.arr.shape)
            return
        seg_shape = (self._stop - self._start,) + tuple(chunk.arr.shape[1:])
        chunk.arr = chunk.arr.at[self._start:self._stop].set(
            new.reshape(seg_shape))

    # ------------------------------------------------------------- basics
    @property
    def shape(self):
        if self._vshape is not None:
            return self._vshape
        if self._start is not None:
            return (self._stop - self._start,) + tuple(self._chunk.arr.shape[1:])
        return tuple(self._chunk.arr.shape)

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        sz = 1
        for s in self.shape:
            sz *= s
        return sz

    @property
    def dtype(self):
        return onp.dtype(self._chunk.arr.dtype).type

    @property
    def context(self):
        return self._chunk.ctx

    ctx = context

    @property
    def handle(self):  # compat: opaque handle
        return self._chunk

    @property
    def T(self):
        if self.ndim <= 1:
            return self
        return transpose(self)

    def __repr__(self):
        shape_info = "x".join(str(x) for x in self.shape)
        return "<%s %s @%s>" % (type(self).__name__, shape_info, self.context)

    def __len__(self):
        return self.shape[0]

    # ------------------------------------------------------------ convert
    def asnumpy(self):
        """Copy to host numpy array (blocking read, = WaitToRead + copy)."""
        return onp.asarray(self._read())

    def __array__(self, dtype=None, copy=None):
        # numpy protocol: without this, onp.asarray(nd) walks __getitem__
        # row by row — one jitted slice per element. asnumpy() is already
        # a fresh host copy, so copy=False is satisfiable (NumPy 2 kwarg).
        a = self.asnumpy()
        return a.astype(dtype, copy=False) if dtype is not None else a

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def astype(self, dtype):
        res = empty(self.shape, ctx=self.context, dtype=dtype)
        self.copyto(res)
        return res

    def wait_to_read(self):
        """Block until this array's value is computed (WaitForVar)."""
        if self._chunk.force is not None:
            f, self._chunk.force = self._chunk.force, None
            f()
        try:
            self._chunk.arr.block_until_ready()
        except AttributeError:  # pragma: no cover - non-jax backing
            pass

    wait_to_write = wait_to_read

    # -------------------------------------------------------------- copy
    def copyto(self, other):
        """Copy into another NDArray or to a new array on a Context."""
        import jax
        if isinstance(other, NDArray):
            if other._chunk is self._chunk and other._start == self._start:
                return other
            val = self._read()
            if other.context != self.context:
                val = jax.device_put(val, other.context.jax_device())
            if onp.dtype(val.dtype) != onp.dtype(other.dtype):
                val = val.astype(other.dtype)
            if tuple(val.shape) != other.shape:
                raise ValueError("array shape do not match the target %s vs %s"
                                 % (val.shape, other.shape))
            other._write(val)
            return other
        if isinstance(other, Context):
            arr = jax.device_put(self._read(), other.jax_device())
            return NDArray(arr, ctx=other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def copy(self):
        return self.copyto(self.context)

    def as_in_context(self, context):
        if self.context == context:
            return self
        return self.copyto(context)

    # ------------------------------------------------------------- views
    def slice(self, start, stop):
        """Zero-copy axis-0 slice sharing this array's chunk (ndarray.h:286)."""
        start, stop, _ = _py_slice(start, stop).indices(self.shape[0])
        base = self._start or 0
        sub_shape = (stop - start,) + tuple(self.shape[1:])
        return NDArray(_chunk=self._chunk, _start=base + start,
                       _stop=base + stop,
                       _vshape=sub_shape if self._vshape is not None else None,
                       writable=self.writable)

    def at(self, idx):
        """View of row ``idx`` with the leading axis removed (ndarray.h At)."""
        if idx < 0:
            idx += self.shape[0]
        base = self._start or 0
        return NDArray(_chunk=self._chunk, _start=base + idx,
                       _stop=base + idx + 1, _vshape=tuple(self.shape[1:]),
                       writable=self.writable)

    def reshape(self, shape, **kwargs):
        """Shape-changing view sharing storage (ndarray.h Reshape)."""
        if isinstance(shape, int):
            shape = (shape,) + tuple(kwargs.pop("__rest", ()))
        shape = tuple(shape)
        if -1 in shape:
            known = 1
            for s in shape:
                if s != -1:
                    known *= s
            shape = tuple(self.size // known if s == -1 else s for s in shape)
        sz = 1
        for s in shape:
            sz *= s
        if sz != self.size:
            raise ValueError("new shape %s has different size from current %s"
                             % (shape, self.shape))
        return NDArray(_chunk=self._chunk, _start=self._start, _stop=self._stop,
                       _vshape=shape, writable=self.writable)

    # --------------------------------------------------------- item access
    def __getitem__(self, key):
        if isinstance(key, int):
            return self.at(key)
        if isinstance(key, _py_slice):
            if key.step is not None and key.step != 1:
                raise ValueError("NDArray only supports continuous slicing on axis 0")
            return self.slice(key.start, key.stop)
        raise ValueError("NDArray only supports int/slice as index")

    def __setitem__(self, key, value):
        view = self[key] if not (isinstance(key, _py_slice) and key.start is None
                                 and key.stop is None and key.step is None) else self
        if isinstance(value, NDArray):
            value.copyto(view)
        elif isinstance(value, numeric_types):
            # fill on the array's OWN device — jnp.full would land on the
            # default accelerator and silently migrate a cpu-ctx array
            # (then one jitted step over mixed devices fails to compile)
            view._sync_copyfrom(onp.full(view.shape, value,
                                         dtype=view.dtype))
        elif isinstance(value, (onp.ndarray, onp.generic, list, tuple)):
            view._sync_copyfrom(onp.asarray(value))
        else:
            raise TypeError("type %s not supported" % str(type(value)))

    def _sync_copyfrom(self, source_array):
        import jax
        src = onp.asarray(source_array, dtype=self.dtype)
        if src.shape != self.shape:
            try:
                src = src.reshape(self.shape)
            except ValueError:
                raise ValueError("Shape inconsistent: expected %s, got %s"
                                 % (str(self.shape), str(src.shape)))
        self._write(jax.device_put(src, self.context.jax_device()))

    # ---------------------------------------------------------- operators
    def __add__(self, other):
        return _binary(self, other, "broadcast_add", "_plus_scalar")

    def __radd__(self, other):
        return self.__add__(other)

    def __iadd__(self, other):
        return _binary(self, other, "broadcast_add", "_plus_scalar", out=self)

    def __sub__(self, other):
        return _binary(self, other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return _binary(self, other, None, "_rminus_scalar")

    def __isub__(self, other):
        return _binary(self, other, "broadcast_sub", "_minus_scalar", out=self)

    def __mul__(self, other):
        return _binary(self, other, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, other):
        return self.__mul__(other)

    def __imul__(self, other):
        return _binary(self, other, "broadcast_mul", "_mul_scalar", out=self)

    def __div__(self, other):
        return _binary(self, other, "broadcast_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, other):
        return _binary(self, other, None, "_rdiv_scalar")

    __rtruediv__ = __rdiv__

    def __idiv__(self, other):
        return _binary(self, other, "broadcast_div", "_div_scalar", out=self)

    __itruediv__ = __idiv__

    def __mod__(self, other):
        return _binary(self, other, "broadcast_mod", "_mod_scalar")

    def __pow__(self, other):
        return _binary(self, other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return _binary(self, other, None, "_rpower_scalar")

    def __neg__(self):
        return _binary(self, -1.0, None, "_mul_scalar")

    def __eq__(self, other):
        if isinstance(other, (NDArray,) + numeric_types):
            return _binary(self, other, "broadcast_equal", "_equal_scalar")
        return NotImplemented

    def __ne__(self, other):
        if isinstance(other, (NDArray,) + numeric_types):
            return _binary(self, other, "broadcast_not_equal",
                           "_not_equal_scalar")
        return NotImplemented

    def __gt__(self, other):
        return _binary(self, other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return _binary(self, other, "broadcast_greater_equal",
                       "_greater_equal_scalar")

    def __lt__(self, other):
        return _binary(self, other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return _binary(self, other, "broadcast_lesser_equal",
                       "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple "
                         "elements is ambiguous.")

    __nonzero__ = __bool__

    # convenience reductions mirroring generated methods
    def sum(self, *args, **kwargs):
        return sum(self, *args, **kwargs)

    def max(self, *args, **kwargs):
        return max(self, *args, **kwargs)

    def min(self, *args, **kwargs):
        return min(self, *args, **kwargs)

    def mean(self, *args, **kwargs):
        return mean(self, *args, **kwargs)

    def argmax(self, *args, **kwargs):
        return argmax(self, *args, **kwargs)

    def transpose(self, *args, **kwargs):
        return transpose(self, *args, **kwargs)

    def flatten(self):
        return flatten(self)


def _binary(lhs, rhs, nd_op, scalar_op, out=None):
    if isinstance(rhs, NDArray):
        if nd_op is None:
            raise MXNetError("operation not supported between NDArrays")
        return invoke(_registry.get_op(nd_op), [lhs, rhs], {}, out=out)
    if isinstance(rhs, numeric_types):
        return invoke(_registry.get_op(scalar_op), [lhs],
                      {"scalar": float(rhs)}, out=out)
    raise TypeError("type %s not supported" % str(type(rhs)))


# ---------------------------------------------------------------------------
# imperative invoke — the MXImperativeInvoke path (src/c_api/c_api_ndarray.cc)
# ---------------------------------------------------------------------------
def invoke(op, inputs, raw_attrs, out=None, ctx=None):
    """Run a registered op on NDArrays eagerly.

    Mirrors MXImperativeInvoke (c_api_ndarray.cc:123-310): infer shapes/types
    (implicit in jnp), set dependencies (implicit in JAX async dispatch),
    execute, record on the autograd tape when training. Ops with aux state
    mutate the trailing aux inputs in place (FMutateInputs).
    """
    from . import autograd as _autograd

    attrs = _registry.parse_attrs(op, raw_attrs)
    if op.variable_args is not None and op.variable_args not in attrs:
        attrs[op.variable_args] = len(inputs)

    n_aux = len(op.list_auxiliary_states(attrs))
    vals = [x._read() for x in inputs]
    octx = _registry.OpContext(
        is_train=_autograd.is_training(),
        rng=_random.next_key() if op.needs_rng else None)
    # pin input-free ops (zeros/full/random fills) to the op's context:
    # they would otherwise land on the process default device — silently
    # migrating "cpu" arrays onto the accelerator (every host-side fill
    # would become a host->device transfer). Ops WITH
    # inputs follow their committed inputs already; skip the config
    # context manager on that hot path.
    out_first = (next((o for o in out if o is not None), None)
                 if isinstance(out, (list, tuple))
                 else out)
    in_ctx = ctx or (inputs[0].context if inputs
                     else out_first.context if out_first is not None
                     else current_context())
    if inputs:
        results = op.fcompute(attrs, vals, octx)
    else:
        import jax
        with jax.default_device(in_ctx.jax_device()):
            results = op.fcompute(attrs, vals, octx)
    n_out = op.num_outputs(attrs)
    outs, aux_updates = list(results[:n_out]), list(results[n_out:])

    # write back mutated aux states (BatchNorm moving stats etc.)
    if n_aux and aux_updates:
        for nda, new in zip(inputs[-n_aux:], aux_updates):
            nda._write(new)

    out_list = out if isinstance(out, (list, tuple)) else (
        [out] if out is not None else None)
    wrapped = []
    for i, o in enumerate(outs):
        if out_list is not None and i < len(out_list) and out_list[i] is not None:
            tgt = out_list[i]
            tgt._write(o.astype(tgt.dtype) if onp.dtype(o.dtype) != onp.dtype(tgt.dtype) else o)
            wrapped.append(tgt)
        else:
            wrapped.append(NDArray(o, ctx=in_ctx))

    if _autograd.is_recording():
        _autograd.record_op(op, attrs, list(inputs), wrapped, octx)

    if _engine.is_naive():
        for w in wrapped:
            w.wait_to_read()
    return wrapped[0] if len(wrapped) == 1 else wrapped


def _make_op_func(op):
    def fn(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        ctx = kwargs.pop("ctx", None)
        inputs = [a for a in args if isinstance(a, NDArray)]
        # None kwargs mean "default" — the reference's generated wrappers
        # drop them before the C call (they would stringify to "None")
        attrs = {k: v for k, v in kwargs.items()
                 if v is not None and not isinstance(v, NDArray)}
        named_in = {k: v for k, v in kwargs.items() if isinstance(v, NDArray)}
        if named_in:
            order = op.list_arguments(attrs) + \
                op.list_auxiliary_states(attrs)
            for nm in order:
                if nm in named_in:
                    inputs.append(named_in.pop(nm))
            inputs.extend(named_in.values())
        scalars = [a for a in args if not isinstance(a, NDArray)]
        if scalars and "scalar" in getattr(op, "attr_types", {}) and "scalar" not in attrs:
            attrs["scalar"] = scalars[0]
        return invoke(op, inputs, attrs, out=out, ctx=ctx)

    fn.__name__ = op.name
    fn.__doc__ = (op.fcompute.__doc__ or "") + "\n\n(op: %s)" % op.name
    return fn


def _init_ndarray_module():
    """Expose every registered op as a module-level function (mirrors
    python/mxnet/ndarray.py _init_ndarray_module)."""
    mod = sys.modules[__name__]
    for name in _registry.list_ops():
        op = _registry.get_op(name)
        # python-level creation helpers (zeros/ones/arange/...) take
        # precedence over the raw attr-style op wrappers
        if hasattr(mod, name):
            continue
        setattr(mod, name, _make_op_func(op))


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------
def empty(shape, ctx=None, dtype=_DEFAULT_DTYPE):
    """Allocate an NDArray without defined contents (mx.nd.empty).

    Contract note: XLA's functional buffer model has no "uninitialized
    allocation" — every device buffer is produced by a computation, and
    jnp.empty is itself zeros. The zero-fill executes on device at HBM
    bandwidth and typically fuses away when the buffer is first written,
    so unlike the reference (ndarray.cc empty alloc) there is no separate
    fill pass to save.
    """
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=_DEFAULT_DTYPE):
    import jax
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    arr = jax.device_put(onp.zeros(shape, dtype=dtype), ctx.jax_device())
    return NDArray(arr, ctx=ctx)


def ones(shape, ctx=None, dtype=_DEFAULT_DTYPE):
    import jax
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    arr = jax.device_put(onp.ones(shape, dtype=dtype), ctx.jax_device())
    return NDArray(arr, ctx=ctx)


def full(shape, val, ctx=None, dtype=_DEFAULT_DTYPE):
    arr = zeros(shape, ctx=ctx, dtype=dtype)
    arr[:] = val
    return arr


def array(source_array, ctx=None, dtype=_DEFAULT_DTYPE):
    """Create an NDArray from any array-like (defaults to float32, as the
    reference does: python/mxnet/ndarray.py array())."""
    import jax
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy().astype(dtype)
    else:
        src = onp.asarray(source_array, dtype=dtype)
    return NDArray(jax.device_put(src, ctx.jax_device()), ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=_DEFAULT_DTYPE):
    if stop is None:
        start, stop = 0, start
    vals = onp.arange(start, stop, step, dtype=dtype)
    if repeat != 1:
        vals = onp.repeat(vals, repeat)
    return array(vals, ctx=ctx, dtype=dtype)


def concatenate(arrays, axis=0, always_copy=True):
    if not arrays:
        raise ValueError("arrays must not be empty")
    import jax
    jnp = _jnp()
    # inputs may live on different devices (multi-ctx executor outputs);
    # stage onto the first array's device like the reference's CPU gather
    parts = [a._read() for a in arrays]
    dev = getattr(parts[0], "devices", lambda: None)()
    if dev:
        target = next(iter(dev))
        parts = [p if getattr(p, "devices", lambda: {target})() == {target}
                 else jax.device_put(p, target) for p in parts]
    res = jnp.concatenate(parts, axis=axis)
    return NDArray(res, ctx=arrays[0].context)


def onehot_encode(indices, out):
    """One-hot into ``out`` (mx.nd.onehot_encode compatibility)."""
    jnp = _jnp()
    depth = out.shape[1]
    idx = indices._read().astype("int32")
    out._write(jnp.squeeze(
        (idx[:, None] == jnp.arange(depth)[None, :]).astype(out.dtype)))
    return out


def imdecode(str_img, **kwargs):
    from .io_util import imdecode as _imdecode
    return _imdecode(str_img, **kwargs)



def _copyto(src, out):
    """Legacy NDArray function (src/ndarray/ndarray.cc MXNET_REGISTER_NDARRAY_FUN
    _copyto): copy ``src`` into ``out``, possibly across devices."""
    return src.copyto(out)


def _set_value(src_scalar, out):
    """Fill ``out`` with a scalar (ndarray.cc _set_value)."""
    jnp = _jnp()
    out._write(jnp.full(out.shape, float(src_scalar), out.dtype))
    return out


def _onehot_encode(indices, out):
    return onehot_encode(indices, out)


def choose_element_0index(lhs, rhs, out=None):
    """out[i] = lhs[i, rhs[i]] (ndarray.cc:765)."""
    from .registry import get_op
    return invoke(get_op("choose_element_0index"), [lhs, rhs], {}, out=out)


def fill_element_0index(lhs, mhs, rhs, out=None):
    """lhs with lhs[i, rhs[i]] = mhs[i] (ndarray.cc:771)."""
    from .registry import get_op
    return invoke(get_op("fill_element_0index"), [lhs, mhs, rhs], {}, out=out)


def _broadcast(src, axis, size, out=None):
    """Broadcast ``src`` along ``axis`` to ``size`` (ndarray.cc:860)."""
    jnp = _jnp()
    x = src._read()
    res = jnp.broadcast_to(
        x, x.shape[:int(axis)] + (int(size),) + x.shape[int(axis) + 1:])
    if out is not None:
        out._write(res)
        return out
    return NDArray(res, ctx=src.context)


def _imdecode(mean, index, x0, y0, x1, y1, n_channels, size, str_img, out=None):
    """Legacy positional imdecode (ndarray.cc _imdecode)."""
    from .io_util import imdecode as _dec
    return _dec(str_img, clip_rect=(x0, y0, x1, y1), out=out, index=index,
                channels=n_channels, mean=mean)


# ---------------------------------------------------------------------------
# OpenCV-backed host image ops (plugin/opencv/cv_api.cc _cvimdecode/
# _cvimresize/_cvcopyMakeBorder). Host-side work, imperative only.
# ---------------------------------------------------------------------------
def _cvimdecode(buf, flag=1, to_rgb=True):
    """Decode a JPEG/PNG byte buffer into an HWC uint8 NDArray.
    ``flag`` follows cv::imread: 0 = grayscale (h,w), nonzero = color."""
    from .image import imdecode as _dec
    import numpy as _np
    img = _dec(buf if isinstance(buf, (bytes, bytearray)) else
               buf.asnumpy().astype("uint8").tobytes(), to_rgb=to_rgb)
    if flag == 0 and img.ndim == 3:
        # ITU-R BT.601 luma — what cv::IMREAD_GRAYSCALE computes
        w = _np.array([0.299, 0.587, 0.114] if to_rgb
                      else [0.114, 0.587, 0.299], _np.float32)
        img = (img.astype(_np.float32) @ w).round().astype(img.dtype)
    return array(img, dtype=img.dtype)


def _cvimresize(src, w, h, interp=1):
    """Resize an HWC image NDArray (plugin/opencv cv_api.cc). ``interp``
    follows cv2 enums (0=nearest, 1=linear, ...) when cv2 is present; the
    PIL fallback maps 0 to nearest and anything else to bilinear."""
    import numpy as _np
    img = src.asnumpy()
    try:
        import cv2
        out = cv2.resize(img, (int(w), int(h)), interpolation=int(interp))
    except ImportError:
        from PIL import Image
        mode = Image.NEAREST if int(interp) == 0 else Image.BILINEAR
        out = _np.asarray(Image.fromarray(img.astype(_np.uint8)).resize(
            (int(w), int(h)), mode)).astype(img.dtype)
    return array(out, dtype=out.dtype)


def _cvcopyMakeBorder(src, top, bot, left, right, type=0, value=0.0):  # noqa: A002
    """Pad an HWC image (plugin/opencv cv_api.cc). ``type`` follows cv2
    border enums: 0 = constant fill; others fall back to edge replicate."""
    import numpy as _np
    img = src.asnumpy()
    if int(type) == 0:
        out = _np.full((img.shape[0] + top + bot, img.shape[1] + left + right)
                       + img.shape[2:], value, dtype=img.dtype)
        out[top:top + img.shape[0], left:left + img.shape[1]] = img
    else:
        pad = [(top, bot), (left, right)] + [(0, 0)] * (img.ndim - 2)
        out = _np.pad(img, pad, mode="edge")
    return array(out, dtype=out.dtype)

# ---------------------------------------------------------------------------
# serialization — NDArray::Save/Load (ndarray.h:360-371); we use the npz
# container (documented own format, not binary-compatible with the reference)
# ---------------------------------------------------------------------------
def save(fname, data):
    """Save a list or str->NDArray dict of NDArrays to file.

    The write is crash-atomic: content goes to ``fname + ".tmp"``, is
    fsynced, then renamed over ``fname`` (``os.replace``). A preemption
    mid-write leaves the previous file intact plus at most a stray
    ``.tmp`` that :func:`load` refuses to read.
    """
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        fmt, arrs = "dict", {k: v.asnumpy() for k, v in data.items()}
    elif isinstance(data, (list, tuple)):
        fmt = "list"
        arrs = {"arr_%d" % i: v.asnumpy() for i, v in enumerate(data)}
    else:
        raise ValueError("data needs to either be a NDArray, dict or list")
    from .checkpoint.serialize import atomic_write_stream
    # savez streams into the tmp handle (which also stops numpy
    # appending ".npz"); atomic_write_stream does the fsync + rename
    atomic_write_stream(
        fname, lambda f: onp.savez(f, __mx_format__=fmt, **arrs))


def load(fname):
    """Load NDArrays saved by ``save`` — returns list or dict like the
    reference's MXNDArrayLoad. ``.tmp`` files (an interrupted
    :func:`save` that never committed) are rejected."""
    if str(fname).endswith(".tmp"):
        raise MXNetError(
            "refusing to load %r: .tmp files are uncommitted partial "
            "writes left by an interrupted save" % (fname,))
    with onp.load(fname, allow_pickle=False) as npz:
        fmt = str(npz["__mx_format__"]) if "__mx_format__" in npz else "dict"
        items = {k: npz[k] for k in npz.files if k != "__mx_format__"}
        if fmt == "list":
            return [array(items["arr_%d" % i], dtype=items["arr_%d" % i].dtype)
                    for i in range(len(items))]
        return {k: array(v, dtype=v.dtype) for k, v in items.items()}


def waitall():
    _engine.waitall()


# Register all operators and expose them at module level immediately, so
# ``from mxnet_tpu.ndarray import sgd_update`` works without package-level
# ordering constraints.
from . import ops as _ops  # noqa: E402,F401
_init_ndarray_module()
