"""Runtime kernel compilation — Pallas instead of NVRTC.

The reference's ``mx.rtc`` compiles CUDA C source at runtime
(include/mxnet/mxrtc.h:26, python/mxnet/rtc.py:91). The TPU-native
equivalent is runtime Pallas: users provide a python kernel body operating
on ``pl.Ref``s (VMEM tiles) — as python source text (API-compatible with
rtc.Rtc's (name, inputs, outputs, body) signature) or a callable — and it
is JIT-compiled for TPU via ``pl.pallas_call`` on first push.
"""
from __future__ import annotations

import textwrap

import numpy as onp

from .base import MXNetError

__all__ = ["Rtc", "PallasKernel"]


class PallasKernel(object):
    """Compile + run a user Pallas kernel.

    kernel_fn(*refs): standard Pallas kernel taking input Refs then output
    Refs; use jnp ops on ``ref[...]``. The kernel compiles for the
    device (Mosaic on TPU); a caller that wants the Pallas interpreter —
    a CPU test — passes ``interpret=True``. It is never inferred from
    the platform: a chip that was not found must fail, not interpret.
    """

    def __init__(self, kernel_fn, name="rtc_kernel"):
        self.kernel_fn = kernel_fn
        self.name = name
        self._compiled = {}

    def __call__(self, inputs, out_shapes, out_dtypes=None, interpret=False):
        import jax
        from jax.experimental import pallas as pl
        import jax.numpy as jnp

        vals = [x._read() if hasattr(x, "_read") else jnp.asarray(x)
                for x in inputs]
        if out_dtypes is None:
            out_dtypes = [vals[0].dtype] * len(out_shapes)
        key = (bool(interpret),) + tuple((tuple(v.shape), str(v.dtype)) for v in vals) + \
            tuple((tuple(s), str(d)) for s, d in zip(out_shapes, out_dtypes))
        if key not in self._compiled:
            out_struct = [jax.ShapeDtypeStruct(tuple(s), d)
                          for s, d in zip(out_shapes, out_dtypes)]
            call = pl.pallas_call(self.kernel_fn, out_shape=out_struct,
                                  interpret=interpret)
            self._compiled[key] = jax.jit(call)
        outs = self._compiled[key](*vals)
        from .ndarray import NDArray
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return [NDArray(o) for o in outs]


class Rtc(object):
    """Source-text API mirroring python/mxnet/rtc.py Rtc(name, inputs,
    outputs, kernel). The kernel body is python/Pallas source; input and
    output names bind to Refs in order.

    Example::

        rtc = mx.rtc.Rtc('axpy', [('x', x), ('y', y)], [('z', z)],
                         "z_ref[...] = x_ref[...] * 2.0 + y_ref[...]")
        rtc.push([x, y], [z], (1,1,1), (1,1,1))
    """

    def __init__(self, name, inputs, outputs, kernel):
        self.name = name
        self.input_names = [n for n, _ in inputs]
        self.output_names = [n for n, _ in outputs]
        args = ", ".join(["%s_ref" % n for n in self.input_names]
                         + ["%s_ref" % n for n in self.output_names])
        src = "def _kernel(%s):\n%s\n" % (
            args, textwrap.indent(textwrap.dedent(kernel), "    "))
        scope = {}
        try:
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            scope.update({"jnp": jnp, "pl": pl})
            exec(src, scope)  # noqa: S102 - explicit runtime compilation API
        except SyntaxError as e:
            raise MXNetError("invalid rtc kernel source: %s" % e)
        self._pk = PallasKernel(scope["_kernel"], name=name)

    def push(self, inputs, outputs, grid_dims=None, block_dims=None,
             interpret=False):
        """Run the kernel; grid/block dims accepted for API compat (Pallas
        grids come from BlockSpecs; simple elementwise kernels need none).
        ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
        out_shapes = [tuple(o.shape) for o in outputs]
        out_dtypes = [onp.dtype(o.dtype) for o in outputs]
        results = self._pk(inputs, out_shapes, out_dtypes,
                           interpret=interpret)
        for o, r in zip(outputs, results):
            r.copyto(o)
        return outputs
