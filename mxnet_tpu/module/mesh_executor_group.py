"""MeshExecutorGroup — the fused, mesh-sharded Module execution path.

TPU-native replacement for the reference's DataParallelExecutorGroup
(python/mxnet/module/executor_group.py:77-231): instead of slicing the batch
across N per-device executors and reducing gradients through KVStore staging
buffers (src/kvstore/comm.h), the whole forward+backward is ONE jitted XLA
program over a ``jax.sharding.Mesh`` with a single 'dp' axis:

* inputs are sharded on the batch axis (``PartitionSpec('dp')``);
* parameters/aux are replicated; requesting *replicated* gradient outputs
  makes the GSPMD partitioner insert the cross-device all-reduce (psum over
  ICI) exactly where the reference staged through pinned merge buffers;
* BatchNorm statistics are computed over the global batch (the partitioner
  reduces across shards) — matching single-device numerics, which the
  reference's per-device-slice BN does not;
* the optimizer update stays in ``Module.update`` -> ``Updater.update_multi``
  (one jitted whole-tree call, buffers donated on accelerators), preserving
  every lr-scheduler/wd-mult semantic of optimizer.py.

The group implements the same surface Module drives on
DataParallelExecutorGroup, so ``Module.fit`` (base_module.py:368-519 in the
reference) runs unchanged on top of it.
"""
from __future__ import annotations

import logging

import numpy as onp

import collections
import itertools

from .. import ndarray as nd
from .. import random as _random
from ..base import MXNetError
from ..executor import _build_eval, _build_eval_segmented

# monotonic tokens for optimizer instances (train_step jit cache keys)
_STEP_TOKENS = itertools.count()

# Output bytes of train steps that may be in flight at once.  The host
# runs ahead of the device (32 launches on the TPU client) and every
# launch owns its outputs from the moment it is queued: a step whose
# outputs are a language model's probabilities (0.8 GB) would hold 26 GB
# that way.  Past this many bytes the launch waits for the oldest step
# in flight; an image classifier's outputs (1 MB a step) never reach it.
STEP_OUTPUT_BYTES_IN_FLIGHT = 1 << 30
STEPS_IN_FLIGHT = 32        # the TPU client's own run-ahead (PERF.md)
# Parameters packed into one device array for one readback (get_params)
PACK_BYTES = 256 << 20


def _index_inputs(symbol):
    """Names of the variables that reach an Embedding as its indices,
    directly or through reshapes."""
    names = set()
    for n in symbol._topo():
        if n.op is None or n.op.name != "Embedding":
            continue
        src = n.inputs[0][0]
        while src.op is not None and src.op.name in ("Reshape", "Flatten",
                                                     "BlockGrad"):
            src = src.inputs[0][0]
        if src.op is None:
            names.add(src.name)
    return names


def _grads_not_kept():
    raise MXNetError(
        "this step's gradients were not kept: update() ran forward, "
        "backward and the optimizer as one program, which hands no "
        "gradients back (they would be a second copy of every "
        "parameter); read them between backward() and update(), or "
        "install a monitor")


def _tally_add(jnp, stat, labels, outs, acc):
    """Fold one batch's metric statistic into a (sums f32, counts i32)
    device tally — shared by the train step and the eval program.
    Counts ride int32: an f32 tally would stop counting at 2^24."""
    rows = stat(jnp, labels, outs)
    if isinstance(rows, tuple):
        rows = [rows]
    sums, counts = acc
    sums = sums + jnp.stack([jnp.asarray(s, jnp.float32)
                             for s, _ in rows])
    counts = counts + jnp.stack([jnp.asarray(c, jnp.int32)
                                 for _, c in rows])
    return sums, counts


def _tree_where(jnp, pred, new, old):
    """Per-leaf select over an optimizer-state tree (None passes
    through) — the skipped-step selection of the dynamic loss scaler."""
    if new is None:
        return None
    if isinstance(new, (tuple, list)):
        return tuple(_tree_where(jnp, pred, a, b)
                     for a, b in zip(new, old))
    return jnp.where(pred, new, old)


def _grads_finite(jnp, grads):
    """Scalar bool: every gradient leaf is finite (the loss-scaler's
    overflow probe, computed on device inside the step program)."""
    finite = jnp.asarray(True)
    for g in grads.values():
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
    return finite


def _ls_update(jnp, cfg, scale, good, finite):
    """The dynamic loss-scale transition (standard AMP rule, on
    device): overflow halves the scale and zeroes the growth counter;
    ``window`` consecutive finite steps double it, clamped to
    [scale_min, scale_max]."""
    grew = (good + 1) >= cfg["window"]
    up = jnp.minimum(scale * 2.0, cfg["scale_max"])
    down = jnp.maximum(scale * 0.5, cfg["scale_min"])
    new_scale = jnp.where(finite, jnp.where(grew, up, scale), down)
    new_good = jnp.where(finite, jnp.where(grew, 0, good + 1),
                         0).astype(good.dtype)
    return new_scale, new_good


def _ls_step(jnp, cfg, ls, finite):
    """One device loss-scale transition over the threaded
    ``(scale f32, good i32, skips i32)`` triple: the AMP rule on
    (scale, good) plus a skipped-update count — the witness the
    ``precision.scale_skips`` telemetry satellite polls off-path
    alongside :meth:`MeshExecutorGroup.loss_scale`."""
    scale, good, skips = ls
    new_scale, new_good = _ls_update(jnp, cfg, scale, good, finite)
    new_skips = skips + jnp.where(finite, 0, 1).astype(skips.dtype)
    return new_scale, new_good, new_skips


# guardian health-word flag bits (mxnet_tpu.guardian reads these):
HEALTH_LOSS_NONFINITE = 1
HEALTH_GRAD_NONFINITE = 2
HEALTH_PARAM_NONFINITE = 4
HEALTH_SDC_MISMATCH = 8


def _health_update(jnp, cfg, health, inputs, outs, grads, new_params,
                   grad_names, label_names):
    """Fold one step's numeric-health observation into the threaded
    guardian word ``(flags i32, first_bad i32, count i32, ring f32)``
    — pure reads of values the step already computed, so the params
    math is untouched. ``flags`` accumulates the sentinel bitmask
    (loss/grad/param non-finite), ``first_bad`` pins the step ordinal
    (within the polling window, i.e. since the last ``health_reset``)
    of the FIRST bad observation, ``count`` counts steps, and ``ring``
    is a rolling per-step loss-scalar window the host-side spike judge
    reads at the epoch/commit boundary. Zero step-path readbacks: the
    word lives on device and is polled off-path."""
    flags, first_bad, count, ring = health
    loss_fin = jnp.all(jnp.isfinite(outs[0].astype(jnp.float32)))
    grad_fin = _grads_finite(jnp, grads)
    par_fin = jnp.asarray(True)
    for n in grad_names:
        par_fin = jnp.logical_and(
            par_fin, jnp.all(jnp.isfinite(new_params[n])))
    bad = (jnp.where(loss_fin, 0, HEALTH_LOSS_NONFINITE)
           | jnp.where(grad_fin, 0, HEALTH_GRAD_NONFINITE)
           | jnp.where(par_fin, 0,
                       HEALTH_PARAM_NONFINITE)).astype(jnp.int32)
    new_flags = flags | bad
    first_bad = jnp.where((flags == 0) & (new_flags != 0), count,
                          first_bad)
    stat = cfg.get("stat")
    if stat is not None:
        # the guardian's loss-like scalar: the spike metric's fused
        # statistic over this batch (sum/count of its first slot —
        # for the default cross-entropy stat, the batch's mean loss).
        # A stat that cannot trace over this model's label/output
        # shapes (e.g. the default "ce" stat against a non-softmax
        # head) must NOT take the train step down: degrade to the
        # coarse output-mean scalar the no-stat path uses and record
        # the downgrade so the guardian's judge knows its ring is
        # coarse (this runs at trace time, so the fallback costs
        # nothing per step).
        try:
            rows = stat(jnp, [inputs[n] for n in label_names], outs)
            if isinstance(rows, tuple):
                rows = [rows]
            s, c = rows[0]
            scalar = jnp.asarray(s, jnp.float32) / jnp.maximum(
                jnp.asarray(c, jnp.float32), 1.0)
        except Exception as exc:  # noqa: BLE001 - any trace failure
            cfg["stat_degraded"] = "%s: %s" % (type(exc).__name__, exc)
            logging.getLogger("mxnet_tpu.guardian").warning(
                "guardian spike metric cannot trace over this model's "
                "label/output shapes (%s); falling back to the coarse "
                "output-mean loss scalar", cfg["stat_degraded"])
            stat = None
    if stat is None:
        # no labels / no fusable spike metric: finiteness sentinels
        # still work; the ring carries a coarse output mean (the spike
        # judge is only as meaningful as this scalar — documented)
        scalar = jnp.mean(outs[0].astype(jnp.float32))
    ring = ring.at[count % int(cfg["window"])].set(scalar)
    return new_flags, first_bad, count + 1, ring


def _sdc_fold(jnp, a_params, b_params, health, grad_names):
    """Fold an SDC parity-probe verdict into the health word: compare
    the two launches' updated params BITWISE (integer bitcast — a NaN
    payload must compare equal to itself) and set the SDC flag on any
    mismatch. Under the repo's bitwise-determinism contracts two
    launches of the same program on the same inputs are byte-equal,
    so a mismatch is a true hardware/silent-corruption signal."""
    from jax import lax
    flags, first_bad, count, ring = health
    neq = jnp.asarray(False)
    for n in grad_names:
        ai = lax.bitcast_convert_type(a_params[n], jnp.int32)
        bi = lax.bitcast_convert_type(b_params[n], jnp.int32)
        neq = jnp.logical_or(neq, jnp.any(ai != bi))
    new_flags = flags | jnp.where(neq, HEALTH_SDC_MISMATCH,
                                  0).astype(jnp.int32)
    # the probed step already counted (its health update ran inside
    # the launch): the offending ordinal is count - 1
    first_bad = jnp.where((flags == 0) & (new_flags != 0),
                          jnp.maximum(count - 1, 0), first_bad)
    return new_flags, first_bad, count, ring


def _compiler_options():
    """TPU compiler options for the step programs, from
    ``MXNET_XLA_COMPILER_OPTIONS`` ("key=value,key=value").

    jit's ``compiler_options`` hands TPU compiler flags to exactly the
    step programs — the per-program tuning knob (e.g.
    ``xla_tpu_scoped_vmem_limit_kib=65536``). Reference counterpart:
    the MXNET_* engine tuning env family."""
    import os
    raw = os.environ.get("MXNET_XLA_COMPILER_OPTIONS", "")
    if not raw:
        return None
    out = {}
    for part in raw.split(","):
        if "=" in part:
            key, val = part.split("=", 1)
            out[key.strip()] = val.strip()
        elif part.strip():
            # a typo'd tuning flag must not silently no-op — the whole
            # point of the knob is measurable effect
            logging.warning(
                "MXNET_XLA_COMPILER_OPTIONS: ignoring segment %r "
                "(expected key=value, comma-separated)", part.strip())
    return out or None

__all__ = ["MeshExecutorGroup"]


class MeshExecutorGroup(object):
    """One donated, mesh-sharded program instead of N Python executors."""

    fused = True

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", compute_dtype=None, remat=None,
                 mesh_axes=None, param_sharding=None,
                 pipeline_microbatches=None, device_augment=None,
                 precision=None):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        assert shared_group is None or shared_group.fused
        assert not inputs_need_grad
        # graph fusion: BatchNorm→ReLU pairs collapse into the hand-VJP
        # BN core (HBM-traffic win, executor.fuse_bn_relu).  arg/aux
        # lists and head wiring are invariant under the rewrite.  The
        # monitor path is unaffected: this group rejects monitors.
        from ..executor import fuse_bn_relu
        symbol = fuse_bn_relu(symbol)
        self.symbol = symbol
        self.contexts = contexts
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.for_training = for_training
        self.inputs_need_grad = False
        self.logger = logger
        self.fixed_param_names = fixed_param_names or []
        # resolved PrecisionPolicy (mxnet_tpu.precision) or None; the
        # compute_dtype/remat fields arrive already folded in by Module,
        # the group consumes the policy for the input-seam act casts,
        # the device-side loss scaler, and introspection provenance
        self._precision = precision
        self.compute_dtype = compute_dtype
        if remat is not None and not callable(remat) and \
                remat not in ("full", "dots", "bn_stats"):
            raise ValueError(
                "remat must be None, 'full', 'dots', 'bn_stats' or a jax "
                "checkpoint-policy callable (got %r)" % (remat,))
        self.remat = remat
        # device-side dynamic loss scale state (narrow experimental
        # modes): a (scale f32, good-steps i32) pair threaded through
        # the fused step program — see precision.loss_scale_config
        from ..precision.policy import loss_scale_config
        self._ls_cfg = loss_scale_config(precision)
        self._ls_state = None
        # guardian numeric-health sentinel (mxnet_tpu.guardian): when
        # armed via enable_health(), a (flags, first_bad, count, ring)
        # device word rides the train-step programs exactly like the
        # loss-scale pair above — unarmed, every seam below is one
        # attribute branch and the programs are byte-identical
        self._health_cfg = None
        self._health_state = None
        self._probe_count = 0
        # what the symbol's ops count in a step (registry.count): a
        # device tally of one f32 per name rides the train step like
        # the metric's, read once an epoch (read_op_counters)
        self._counter_names = sorted({
            c for n in symbol._topo() if n.op is not None
            for c in n.op.list_counters(n.attrs)})
        self._counter_acc = None
        # inputs that index an Embedding keep their type: ids do not
        # survive a cast to the compute type
        self._index_inputs = _index_inputs(symbol)
        # outputs of steps launched and maybe not yet run, oldest first
        self._inflight_outs = collections.deque()
        self._grad_names = [n for n in param_names
                            if n not in self.fixed_param_names] \
            if for_training and grad_req == "write" else []

        devices = [c.jax_device() for c in contexts]
        # multi-host: when the job spans processes (jax.distributed up)
        # and the bind covers all local devices with a plain dp mesh,
        # widen the mesh to EVERY process's devices — the global SPMD
        # program whose dp axis spans hosts (mxnet_tpu.dist; SNIPPETS.md
        # "8 chips to a pod without changing application code"). Batch
        # staging then assembles per-process local shards
        # (dist.staging.stage_sharded). MXNET_DIST_GLOBAL_MESH=0 opts
        # out (each process then trains its own replica, the degraded
        # pre-PR-6 behavior).
        import os as _os
        import jax as _jax_probe
        if (_jax_probe.process_count() > 1 and mesh_axes is None
                and _os.environ.get("MXNET_DIST_GLOBAL_MESH", "1") != "0"
                and set(devices) == set(_jax_probe.local_devices())):
            devices = list(_jax_probe.devices())
        # N-axis named mesh (default: one 'dp' axis over all devices).
        # GSPMD turns per-param PartitionSpecs over these axes into sliced
        # matmuls + collectives — the TP/MP story lives entirely in the
        # sharding annotations, not in the evaluator.
        if mesh_axes is None:
            mesh_axes = {"dp": len(devices)}
        self.mesh_axes = dict(mesh_axes)
        import math as _math
        if _math.prod(self.mesh_axes.values()) != len(devices):
            raise MXNetError(
                "mesh_axes %r needs %d devices, bind got %d contexts"
                % (self.mesh_axes, _math.prod(self.mesh_axes.values()),
                   len(devices)))
        shape = tuple(self.mesh_axes.values())
        self.mesh = Mesh(onp.array(devices).reshape(shape),
                         tuple(self.mesh_axes))
        self._repl = NamedSharding(self.mesh, P())
        self._batch_sharding = NamedSharding(self.mesh, P("dp"))
        self._platform = devices[0].platform
        self._device_kind = getattr(devices[0], "device_kind",
                                    self._platform)
        # program-introspection identity: this group's programs publish
        # into the process ProgramInventory under "<owner>.<kind>"
        # (serving overrides the owner per bucket before warmup)
        self._inventory_owner = "mod%d" % next(_STEP_TOKENS)

        # per-param NamedSharding from first-match rules
        # (parallel.tensor_parallel.shard_params_for_tp rule format)
        self._param_rules = list(param_sharding or [])
        axis_names = set(self.mesh_axes)

        def spec_for(name):
            for pat, s in self._param_rules:
                if pat in name:
                    for ax in s:
                        if ax is not None and ax not in axis_names:
                            raise MXNetError(
                                "param_sharding rule %r names mesh axis %r "
                                "but mesh_axes is %r" % (pat, ax,
                                                         self.mesh_axes))
                    return P(*s)
            return P()

        self._param_shardings = {
            n: NamedSharding(self.mesh, spec_for(n)) for n in param_names}

        # mesh-aware ops (MoE / RingAttention) read the current mesh at
        # trace time; wrapping the evaluator closures pins it for every
        # jit/vjp trace this group triggers (registry.use_mesh)
        def _with_mesh(fn):
            if fn is None:
                return None
            from ..registry import use_mesh

            def wrapped(*a, **k):
                with use_mesh(self.mesh):
                    return fn(*a, **k)
            return wrapped

        self._eval_fn, self._needs_rng = _build_eval(symbol)
        self._eval_fn = _with_mesh(self._eval_fn)
        if self.remat:
            # sqrt-N segmented checkpoints (training only): a single
            # checkpoint around the whole forward saves no memory
            self._remat_eval_fn, _ = _build_eval_segmented(
                symbol, remat=self.remat)
            self._remat_eval_fn = _with_mesh(self._remat_eval_fn)
        else:
            self._remat_eval_fn = None
        self._remat_kept_bytes = 0
        self.pipeline_microbatches = pipeline_microbatches
        if pipeline_microbatches:
            if "pp" not in self.mesh_axes:
                raise MXNetError(
                    "pipeline_microbatches needs a 'pp' mesh axis "
                    "(mesh_axes=%r)" % (self.mesh_axes,))
            if self.remat:
                raise MXNetError(
                    "pipeline_microbatches and remat cannot be combined "
                    "(checkpoint the stage body instead)")
            from ..executor import _build_eval_pipelined
            self._pipe_eval_fn, _, stage_pnames = _build_eval_pipelined(
                symbol, self.mesh, pipeline_microbatches)
            self._pipe_eval_fn = _with_mesh(self._pipe_eval_fn)
            # stage params are stacked and sharded on 'pp' inside the
            # shard_map schedule — a param_sharding rule resolving one to
            # a non-replicated spec would be silently dropped, so reject
            # it loudly instead (first-match semantics, like spec_for)
            hit = sorted(n for n in stage_pnames
                         if any(ax is not None for ax in spec_for(n)))
            if hit:
                raise MXNetError(
                    "param_sharding resolves pipeline-stage parameter(s) "
                    "%s to a non-replicated spec: stage parameters are "
                    "stacked on the 'pp' axis and cannot take a "
                    "tensor-parallel sharding — scope the rule to "
                    "preamble/postamble parameters" % (hit,))
        else:
            self._pipe_eval_fn = None
        self._jits = {}
        self._pending = None     # (inputs dict of device arrays, is_train)
        self._outputs_from = None  # "fwd" | "bwd"
        # device-side metric tally (enable_device_metric): the fused train
        # step accumulates (sum, count) rows on device; metric.get() drains
        # them with ONE readback instead of one per batch
        self._metric_stat = None
        self._metric_live = None
        self._metric_acc = None
        self._metric_step_done = False
        # device-side input augmentation (mxnet_tpu.data.DeviceAugment):
        # {data input name: spec}.  The wire batch stages as uint8 NHWC
        # (4x fewer bytes than f32 NCHW) plus tiny per-row parameter
        # arrays; pad/crop/mirror/normalize/transpose run as their OWN
        # compiled device program at staging (_augment_jit below) and
        # the host never touches a float pixel.
        self._device_augment = dict(device_augment or {})

        self.bind_exec(data_shapes, label_shapes)

        # parameter / grad / aux buffers: replicated global jax arrays
        # wrapped as NDArrays so Module + Updater.update_multi drive them
        # unchanged.  ctx is display-only; placement is the mesh sharding.
        arg_shapes, _, aux_shapes = symbol.infer_shape(**self._input_shapes)
        shape_of = dict(zip(self.arg_names, arg_shapes))
        self._shape_of = shape_of
        # non-param args the batch may not provide (e.g. labels at predict
        # time) are bound as zeros, like the classic group's pre-allocated
        # input arrays
        self._nonparam_names = [n for n in self.arg_names
                                if n not in param_names]
        ctx0 = contexts[0]

        def zeros_with(shape, sharding):
            # the staging rule handles the multi-host case (device_put
            # cannot place onto another process's devices; each process
            # allocates and contributes only its LOCAL block)
            from ..dist.staging import stage_zeros
            return nd.NDArray(stage_zeros(shape, sharding), ctx=ctx0)

        def lazy_zeros(shape, sharding, dtype=onp.float32):
            """Zeros that take no memory until they are read: the
            gradients of a module that trains under fit() and the
            outputs before the first forward are written before anyone
            reads them, and at a language model's size (2.8 GB of
            gradients, 0.8 GB of probabilities) idle copies cost the
            step its room."""
            import jax
            from ..dist.staging import stage_zeros
            arr = nd.NDArray(jax.ShapeDtypeStruct(tuple(shape),
                                                  onp.dtype(dtype)),
                             ctx=ctx0)
            chunk = arr._chunk

            def fill():
                chunk.arr = stage_zeros(shape, sharding, dtype)
            chunk.force = fill
            return arr

        p_sh = self._param_shardings
        if shared_group is not None:
            # shared_module semantics (executor_group.py:560-585): share the
            # parameter/grad/aux buffers with the parent module — trivially
            # memory-shared here since params are name-keyed device dicts
            shared_group._shared_out = True  # parent must not rebind away
            assert shared_group.mesh_axes == self.mesh_axes, \
                "shared_module must be bound on the same mesh_axes"
            # non-learned state args (__lr_mult__ 0, e.g. an RNN cell's
            # zero begin_state) are shaped by the BATCH, so a shared
            # bind at a different batch size (a Predictor bucket, a
            # reshaped shared module) legitimately disagrees with the
            # parent's buffer — such args get their own zero buffers;
            # a shape mismatch on a LEARNED param is still a hard error
            attrs = symbol.attr_dict()
            fresh = set()
            for n in param_names:
                src = shared_group._param_dict[n]
                if tuple(src.shape) != tuple(shape_of[n]):
                    lr = (attrs.get(n) or {}).get("__lr_mult__")
                    if lr is not None and float(lr) == 0.0:
                        fresh.add(n)
                    else:
                        raise MXNetError(
                            "shared_module bind: learned param %r has "
                            "shape %r in the parent but %r here — a "
                            "shared module must agree on every learned "
                            "param shape" % (n, tuple(src.shape),
                                             tuple(shape_of[n])))
            self.param_arrays = [[zeros_with(shape_of[n], p_sh[n])]
                                 if n in fresh else
                                 [shared_group._param_dict[n]]
                                 for n in param_names]
            self._param_dict = dict(shared_group._param_dict)
            for n, b in zip(param_names, self.param_arrays):
                if n in fresh:
                    self._param_dict[n] = b[0]
            self.grad_arrays = [[shared_group._grad_dict[n]]
                                if n in self._grad_names
                                and n not in fresh
                                and n in shared_group._grad_dict else
                                ([lazy_zeros(shape_of[n], p_sh[n])]
                                 if n in self._grad_names else None)
                                for n in param_names]
            self._grad_dict = {n: b[0] for n, b in zip(param_names,
                                                       self.grad_arrays)
                               if b is not None}
            self.aux_arrays = shared_group.aux_arrays
            self._aux_dict = shared_group._aux_dict
        else:
            self.param_arrays = [[zeros_with(shape_of[n], p_sh[n])]
                                 for n in param_names]
            self._param_dict = {n: b[0] for n, b in zip(param_names,
                                                        self.param_arrays)}
            # gradients shard exactly like their params: GSPMD reduces them
            # over 'dp' only, and a tp-sharded weight keeps a tp-sharded
            # grad — no gather ever materializes the full tensor
            self.grad_arrays = [[lazy_zeros(shape_of[n], p_sh[n])]
                                if n in self._grad_names else None
                                for n in param_names]
            self._grad_dict = {n: b[0] for n, b in zip(param_names,
                                                       self.grad_arrays)
                               if b is not None}
            self.aux_arrays = [[zeros_with(s, self._repl)]
                               for s in aux_shapes]
            self._aux_dict = {n: b[0] for n, b in zip(self.aux_names,
                                                      self.aux_arrays)}

        # persistent output NDArrays (lazy force thunk, like Executor)
        out_structs = self._out_structs()
        self._out_arrays = [
            lazy_zeros(s.shape, jax.sharding.SingleDeviceSharding(
                ctx0.jax_device()), s.dtype) for s in out_structs]

    # ------------------------------------------------------------------
    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        assert shared_group is None
        self.batch_size = data_shapes[0][1][0]
        n_dp = self.mesh_axes["dp"] if hasattr(self, "mesh_axes") else \
            len(self.contexts)
        if self.batch_size % n_dp:
            raise MXNetError(
                "fused mesh path needs batch_size %% dp_axis == 0 "
                "(got %d %% %d)" % (self.batch_size, n_dp))
        mb = getattr(self, "pipeline_microbatches", None)
        if mb and self.batch_size % (n_dp * mb):
            raise MXNetError(
                "pipelined fit needs batch_size %% (dp * microbatches) "
                "== 0 (got %d %% (%d * %d))"
                % (self.batch_size, n_dp, mb))
        self.data_shapes = [(x[0], tuple(x[1])) for x in data_shapes]
        self.label_shapes = [(x[0], tuple(x[1])) for x in label_shapes] \
            if label_shapes else None
        self._input_shapes = dict(self.data_shapes)
        # device-augmented inputs: the symbol's shape world sees the
        # MODEL view (B, C, H, W) f32; the wire view (uint8 NHWC block
        # + crop/mirror parameter arrays) exists only in staging and in
        # run_fwd's first stage.  data_shapes keeps the wire entries —
        # _stage zips them against batch.data — while _input_shapes
        # drives infer_shape.
        for name, aug in getattr(self, "_device_augment", {}).items():
            if name not in self._input_shapes:
                raise MXNetError(
                    "device_augment names input %r but the bind "
                    "provides %r" % (name, list(self._input_shapes)))
            for d in aug.param_descs(name, self.batch_size):
                self._input_shapes.pop(d.name, None)
            self._input_shapes[name] = aug.model_shape(self.batch_size)
        if self.label_shapes:
            self._input_shapes.update(dict(self.label_shapes))
        self.input_names = list(self._input_shapes)
        self._label_names = [x[0] for x in (self.label_shapes or [])]
        # per-output shardings: only outputs that actually carry the batch
        # dimension shard on 'dp'; scalars (losses) and batch-free outputs
        # (e.g. MultiBoxPrior anchors, batch dim 1) stay replicated.
        # Recomputed on every (re)bind since it depends on batch size.
        _, out_shapes, _ = self.symbol.infer_shape(**self._input_shapes)
        self._out_shardings = tuple(
            self._batch_sharding
            if len(s) >= 1 and s[0] == self.batch_size else self._repl
            for s in out_shapes)
        self._jits = {}  # shardings changed; recompile
        # introspection bookkeeping resets with the jits: stale aval
        # skeletons from the previous bind must not be re-analyzed
        self._program_notes = set()
        self._program_names = {}

    def _out_structs(self):
        import jax
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(
            **self._input_shapes)
        args = [jax.ShapeDtypeStruct(tuple(s), onp.float32)
                for s in arg_shapes]
        auxs = [jax.ShapeDtypeStruct(tuple(s), onp.float32)
                for s in aux_shapes]
        rng = jax.ShapeDtypeStruct((2,), onp.uint32)
        outs, _ = jax.eval_shape(
            lambda a, x, r: self._eval_fn(a, x, r, False), args, auxs, rng)
        return outs

    # ------------------------------------------------------------------
    # jitted programs (cached per (kind, input-shape) — recompiles on a
    # batch-size change exactly like simple_bind reshaping)
    def _get_jit(self, kind):
        key = kind
        if key in self._jits:
            return self._jits[key]
        import jax

        # optional TPU compiler options (MXNET_XLA_COMPILER_OPTIONS)
        copts = _compiler_options()
        if copts:
            import functools
            jax_jit = functools.partial(jax.jit, compiler_options=copts)
        else:
            jax_jit = jax.jit

        cdt = self.compute_dtype
        pol = self._precision
        act_cast = getattr(pol, "act_cast", None) if pol is not None \
            else None
        ls_cfg = self._ls_cfg
        label_names = set(self._label_names)
        grad_names = list(self._grad_names)

        index_inputs = self._index_inputs

        def cast(name, v):
            if cdt is not None and name not in label_names \
                    and name not in index_inputs:
                return v.astype(cdt)
            return v

        def cast_input(name, v):
            v = cast(name, v)
            if act_cast is not None and name not in label_names:
                # experimental low-bit input seam
                # (mxnet_tpu.precision.fake_cast): value-level round
                # trip through int8/fp8 so eval and train forwards see
                # the identical quantization
                import jax.numpy as jnp
                from ..precision.policy import fake_cast
                v = fake_cast(jnp, v, act_cast)
            return v

        def run_fwd(params, aux, inputs, rng, is_train, counts=None):
            """`counts`: a dict that takes what the ops counted
            (registry.count) as traced scalars; None collects none."""
            if counts is not None:
                from ..registry import counting
                with counting() as bag:
                    res = run_fwd(params, aux, inputs, rng, is_train)
                counts.update(bag)
                return res
            if not is_train:
                # narrow-math GEMM seam (precision.quant): entered
                # INSIDE the traced body so every (re)trace resolves
                # the mode — calibration collect, native int8/fp8, or
                # (the common case) a no-op passthrough that leaves the
                # program byte-identical
                from ..precision.quant import trace_gemm_scope
                with trace_gemm_scope(pol):
                    return run_fwd_body(params, aux, inputs, rng,
                                        is_train)
            return run_fwd_body(params, aux, inputs, rng, is_train)

        def run_fwd_body(params, aux, inputs, rng, is_train):
            if self.remat and is_train and self._pipe_eval_fn is None:
                # rematerialization trades HBM for recompute in backward
                # (the reference's external memonger tool). sqrt-N
                # contiguous segments each under jax.checkpoint: segment
                # boundaries stay live through backward, and inside a
                # segment what its policy keeps by name ("full": the
                # matrix products' and attention's outputs; "dots":
                # matmul/conv outputs and attention's).
                # parameters reach the segments as they are held and are
                # cast where a segment reads them (executor.py)
                vals = [params[n] if n in params else
                        cast_input(n, inputs[n]) for n in self.arg_names]
                auxv = [aux[n] for n in self.aux_names]
                kept = {}
                outs, new_aux = self._remat_eval_fn(
                    vals, auxv, rng, True,
                    arg_dtypes=[cdt if n in params else None
                                for n in self.arg_names], kept=kept)
                # known by shape while this traces; every step that
                # runs the program counts it (_count_remat_kept)
                self._remat_kept_bytes = sum(kept.values())
                return outs, dict(zip(self.aux_names, new_aux))
            vals = [cast(n, params[n]) if n in params else
                    cast_input(n, inputs[n]) for n in self.arg_names]
            # aux (BN moving stats) stay f32: BatchNorm's fcompute runs its
            # statistics math in f32 and casts its output to the activation
            # dtype, so mixed-precision dtype agreement is the op's job
            auxv = [aux[n] for n in self.aux_names]
            if self._pipe_eval_fn is not None:
                # GPipe schedule over the 'pp' axis inside this same
                # program (shard_map scan; see _build_eval_pipelined)
                outs, new_aux = self._pipe_eval_fn(vals, auxv, rng,
                                                   is_train)
                return outs, dict(zip(self.aux_names, new_aux))
            outs, new_aux = self._eval_fn(vals, auxv, rng, is_train)
            return outs, dict(zip(self.aux_names, new_aux))

        repl, batch = self._repl, self._batch_sharding
        psh = self._param_shardings            # dict pytree over params
        gsh = {n: psh[n] for n in grad_names}  # grads shard like params

        def fwd_bwd_math(params, aux, inputs, rng, heads=None,
                         scale=None, counts=None):
            def f(p):
                seen = None if counts is None else {}
                outs, new_aux = run_fwd(p, aux, inputs, rng, True, seen)
                return tuple(outs), (new_aux, seen)

            outs, vjp_fn, (new_aux, seen) = jax.vjp(f, params,
                                                    has_aux=True)
            if counts is not None:
                counts.update(seen)
            import jax.numpy as jnp
            hs = tuple(h.astype(o.dtype) for h, o in zip(heads, outs)) \
                if heads is not None else \
                tuple(jnp.ones_like(o) for o in outs)
            if scale is not None:
                # dynamic loss scaling (narrow modes): scale the head
                # cotangents so the low-precision backward stays above
                # the underflow floor, unscale the f32 grads after
                hs = tuple(h * scale.astype(h.dtype) for h in hs)
            (grads,) = vjp_fn(hs)
            grads = {n: grads[n].astype(params[n].dtype)
                     for n in grad_names}
            if scale is not None:
                inv = 1.0 / scale
                grads = {n: g * inv for n, g in grads.items()}
            outs = tuple(o.astype(onp.float32) for o in outs)
            return outs, new_aux, grads

        if kind in ("fwd_train", "fwd_eval"):
            is_train = kind == "fwd_train"

            def fwd(params, aux, inputs, rng):
                outs, new_aux = run_fwd(params, aux, inputs, rng, is_train)
                outs = tuple(o.astype(onp.float32) for o in outs)
                return outs, new_aux

            fn = jax_jit(fwd, in_shardings=(psh, repl, batch, None),
                         out_shardings=(self._out_shardings, repl))
        elif kind == "fwd_eval_stacked":
            # persistent multi-batch scoring: K batches stacked on a
            # leading axis, ONE program launch scans them — amortizes
            # the per-launch overhead that dominates small-batch scoring
            # (PERF.md: ~5 ms/launch vs ~7 ms ideal bs32 batch time).
            # The reference's analogue is benchmark_score's tight loop
            # over per-batch Forward (docs/how_to/perf.md:116-148).
            st_batch = self._stacked_sharding(self._batch_sharding)
            st_outs = tuple(self._stacked_sharding(s)
                            for s in self._out_shardings)

            def fwd_stacked(params, aux, inputs, rng):
                def body(rng_c, inp):
                    if self._needs_rng:
                        # fresh key per scanned batch, like the
                        # per-batch path's one next_key() per forward
                        rng_c, sub = jax.random.split(rng_c)
                    else:
                        sub = rng_c
                    outs, _ = run_fwd(params, aux, inp, sub, False)
                    return rng_c, tuple(o.astype(onp.float32)
                                        for o in outs)

                _, outs = jax.lax.scan(body, rng, inputs)
                return outs

            fn = jax_jit(fwd_stacked,
                         in_shardings=(psh, repl, st_batch, None),
                         out_shardings=st_outs)
        elif kind.startswith("fwd_eval_stat:"):
            # evaluation with the metric tallied ON DEVICE: forward +
            # statistic + donated accumulate as one program per batch,
            # zero readbacks until the caller drains (score_device)
            estat = self._escore_stat
            elabels = list(self._label_names)

            def fwd_eval_stat(params, aux, inputs, rng, acc):
                import jax.numpy as jnp
                outs, _new_aux = run_fwd(params, aux, inputs, rng, False)
                outs = tuple(o.astype(onp.float32) for o in outs)
                return _tally_add(jnp, estat,
                                  [inputs[n] for n in elabels], outs, acc)

            fn = jax_jit(
                fwd_eval_stat,
                in_shardings=(psh, repl, batch, None, (repl, repl)),
                out_shardings=(repl, repl),
                donate_argnums=(4,) if self._platform != "cpu" else ())
        elif kind.startswith("train_step:"):
            # whole train step — fwd+bwd+optimizer — as ONE XLA program:
            # one launch per step and the update fuses into the
            # bandwidth-bound backward. fa is the optimizer's pure
            # per-param apply; params/states donate for in-place HBM.
            fa = self._step_fa
            # ':m<token>' kinds fold the metric statistic into the same
            # program: macc rides along as a donated (n_slots, 2) tally,
            # so a real fit(eval_metric=...) loop costs zero extra
            # launches and zero per-batch readbacks (VERDICT r4 #1).
            # ':h<token>' kinds thread the guardian health word the
            # same way; a ':probe' suffix compiles the NON-donating
            # variant the SDC parity probe launches twice.
            mstat = self._metric_stat if ":m" in kind else None
            mlabels = list(self._label_names)
            hcfg = self._health_cfg if ":h" in kind else None
            probe = kind.endswith(":probe")

            def step_math(params, aux, states, inputs, rng, lrs, wds,
                          ls=None, counts=None):
                import jax.numpy as jnp
                if ls is None:
                    outs, new_aux, grads = fwd_bwd_math(
                        params, aux, inputs, rng, counts=counts)
                    finite = None
                else:
                    # dynamic loss scaling rides the step: scaled heads,
                    # unscaled grads, an on-device finite probe deciding
                    # whether this step's update applies at all
                    scale = ls[0]
                    outs, new_aux, grads = fwd_bwd_math(
                        params, aux, inputs, rng, scale=scale,
                        counts=counts)
                    finite = _grads_finite(jnp, grads)
                new_params = dict(params)
                new_states = []
                for k, n in enumerate(grad_names):
                    p, s = fa(jnp, params[n], grads[n], states[k],
                              lrs[k], wds[k])
                    if finite is not None:
                        # overflow: skip the whole update (params AND
                        # state), the standard AMP skipped-step rule
                        p = jnp.where(finite, p, params[n])
                        s = _tree_where(jnp, finite, s, states[k])
                    new_params[n] = p
                    new_states.append(s)
                if ls is None:
                    return (outs, new_aux, grads, new_params,
                            tuple(new_states))
                new_ls = _ls_step(jnp, ls_cfg, ls, finite)
                return (outs, new_aux, grads, new_params,
                        tuple(new_states), new_ls)

            # optional trailing args (metric tally / loss-scale triple /
            # guardian health word) COMPOSE: each is threaded in and out
            # with its own sharding by one generic wrapper instead of a
            # 2^3 variant matrix. Order is fixed — macc, ls, health —
            # so the metric tally keeps its historical argnum 7
            # donation slot.
            extra_names, extra_sh = [], []
            if mstat is not None:
                extra_names.append("macc")
                extra_sh.append((repl, repl))
            counter_names = self._counter_names
            if counter_names:
                extra_names.append("counters")
                extra_sh.append(repl)
            if ls_cfg is not None:
                extra_names.append("ls")
                extra_sh.append((repl, repl, repl))
            if hcfg is not None:
                extra_names.append("health")
                extra_sh.append((repl, repl, repl, repl))
            grad_names_t = tuple(grad_names)

            def train_step(params, aux, states, inputs, rng, lrs, wds,
                           *extras):
                import jax.numpy as jnp
                ex = dict(zip(extra_names, extras))
                ls = ex.get("ls")
                counts = {} if counter_names else None
                sm = step_math(params, aux, states, inputs, rng, lrs,
                               wds, ls, counts)
                if ls is None:
                    outs, new_aux, grads, new_params, new_states = sm
                    new_ls = None
                else:
                    (outs, new_aux, grads, new_params, new_states,
                     new_ls) = sm
                # the gradients stay temporaries of the update: handed
                # back they are a second copy of every parameter, held
                # from step to step (read them between backward() and
                # update(), which runs the plain fwd_bwd program)
                res = [outs, new_aux, {}, new_params, new_states]
                if mstat is not None:
                    res.append(_tally_add(
                        jnp, mstat, [inputs[n] for n in mlabels], outs,
                        ex["macc"]))
                if counter_names:
                    res.append({n: ex["counters"][n] + counts[n]
                                if n in counts else ex["counters"][n]
                                for n in counter_names})
                if new_ls is not None:
                    res.append(new_ls)
                if hcfg is not None:
                    res.append(_health_update(
                        jnp, hcfg, ex["health"], inputs, outs, grads,
                        new_params, grad_names_t, mlabels))
                return tuple(res)

            # no donation on cpu: device_put is zero-copy there, so user-
            # visible host arrays can alias the param buffers (the classic
            # update path gates donation the same way). The probe
            # variant never donates: the SDC parity probe launches it
            # TWICE from the same argument buffers.
            donate = (0, 2) if self._platform != "cpu" and not probe \
                else ()
            base_in = (psh, repl, None, batch, None, None, None)
            base_out = (self._out_shardings, repl, {}, psh, None)
            if donate and mstat is not None:
                donate = donate + (7,)   # macc is always the first extra
            fn = jax_jit(
                train_step,
                # states: committed per-leaf in step_update (momentum
                # etc. shard like their param); None = follow the arg
                in_shardings=base_in + tuple(extra_sh),
                out_shardings=base_out + tuple(extra_sh),
                donate_argnums=donate)
        elif kind.startswith("train_step_grouped:"):
            # K train steps as ONE XLA program (TPUEstimator's
            # iterations_per_loop, reconstructed): lax.scan of the same
            # step math over a (K, batch, ...) staged block.  One launch
            # and ONE host->device transfer cover K steps — the fixed
            # per-transfer and per-launch costs amortize K-fold, with zero
            # readbacks inside the group (metric rides the device tally,
            # the lr schedule rides a precomputed (K, n_params) row per
            # step — see step_update_grouped).
            fa = self._step_fa
            mstat = self._metric_stat if ":m" in kind else None
            mlabels = list(self._label_names)
            hcfg = self._health_cfg if ":h" in kind else None
            probe = kind.endswith(":probe")
            out_structs = self._out_structs()
            grad_names_t = tuple(grad_names)

            def grouped_math(params, aux, states, inputs, rng, lrs, wds,
                             macc, ls=None, health=None):
                import jax.numpy as jnp
                K = lrs.shape[0]
                if self._needs_rng:
                    # independent per-step keys (the per-batch path draws
                    # one host next_key() per step; rng-free nets are
                    # bit-identical either way, rng ops draw their own
                    # streams like the pipelined schedule documents)
                    subs = jax.random.split(rng, K)
                else:
                    subs = jnp.broadcast_to(rng, (K,) + rng.shape)

                def body(carry, xs):
                    params, aux, states, _outs, macc, ls, health = carry
                    inp, lr_row, sub = xs
                    if ls is None:
                        outs, aux, grads = fwd_bwd_math(params, aux, inp,
                                                        sub)
                        finite = None
                    else:
                        # the loss-scale state rides the scan carry: each
                        # scanned step sees the scale its predecessors
                        # left, exactly as K sequential steps would
                        scale = ls[0]
                        outs, aux, grads = fwd_bwd_math(
                            params, aux, inp, sub, scale=scale)
                        finite = _grads_finite(jnp, grads)
                    new_params = dict(params)
                    new_states = []
                    for k, n in enumerate(grad_names):
                        p, s = fa(jnp, params[n], grads[n], states[k],
                                  lr_row[k], wds[k])
                        if finite is not None:
                            p = jnp.where(finite, p, params[n])
                            s = _tree_where(jnp, finite, s, states[k])
                        new_params[n] = p
                        new_states.append(s)
                    if ls is not None:
                        ls = _ls_step(jnp, ls_cfg, ls, finite)
                    if mstat is not None:
                        macc = _tally_add(jnp, mstat,
                                          [inp[n] for n in mlabels], outs,
                                          macc)
                    if health is not None:
                        # the guardian word rides the same carry
                        # discipline as the loss-scale triple: each
                        # scanned step observes and counts like K
                        # sequential per-batch steps would
                        health = _health_update(
                            jnp, hcfg, health, inp, outs, grads,
                            new_params, grad_names_t, mlabels)
                    return (new_params, aux, tuple(new_states), outs,
                            macc, ls, health), None

                # the last step's outs ride the carry; gradients stay
                # inside the body, as in the per-batch step
                zero_outs = tuple(jnp.zeros(s.shape, jnp.float32)
                                  for s in out_structs)
                carry = (params, aux, states, zero_outs, macc, ls, health)
                # rolled loop, never unrolled: XLA:CPU runs while-loop
                # bodies on a slow path (8-30x per-step on conv nets),
                # but unrolling lets XLA fuse ACROSS steps and the
                # reassociated reductions break the bitwise match with
                # K sequential per-batch programs (measured on the CPU
                # mesh).  Exactness is the contract; the rolled loop
                # also keeps compile time and program size
                # K-independent on accelerators, where loop bodies run
                # at full speed anyway.
                (params, aux, states, outs, macc, ls, health), _ = \
                    jax.lax.scan(body, carry, (inputs, lrs, subs))
                return outs, aux, params, states, macc, ls, health

            # same composable-extras wrapper as the per-batch step
            # (macc, ls, health in fixed order)
            extra_names, extra_sh = [], []
            if mstat is not None:
                extra_names.append("macc")
                extra_sh.append((repl, repl))
            if ls_cfg is not None:
                extra_names.append("ls")
                extra_sh.append((repl, repl, repl))
            if hcfg is not None:
                extra_names.append("health")
                extra_sh.append((repl, repl, repl, repl))

            def train_grouped(params, aux, states, inputs, rng, lrs,
                              wds, *extras):
                import jax.numpy as jnp
                ex = dict(zip(extra_names, extras))
                macc = ex.get("macc")
                if macc is None:
                    macc = (jnp.zeros((0,), jnp.float32),
                            jnp.zeros((0,), jnp.int32))
                (outs, new_aux, new_params, new_states, new_macc,
                 new_ls, new_health) = grouped_math(
                    params, aux, states, inputs, rng, lrs, wds, macc,
                    ex.get("ls"), ex.get("health"))
                res = [outs, new_aux, {}, new_params, new_states]
                if mstat is not None:
                    res.append(new_macc)
                if new_ls is not None:
                    res.append(new_ls)
                if hcfg is not None:
                    res.append(new_health)
                return tuple(res)

            st_batch = self._stacked_sharding()
            donate = (0, 2) if self._platform != "cpu" and not probe \
                else ()
            base_in = (psh, repl, None, st_batch, None, None, None)
            base_out = (self._out_shardings, repl, {}, psh, None)
            if donate and mstat is not None:
                donate = donate + (7,)
            fn = jax_jit(
                train_grouped,
                in_shardings=base_in + tuple(extra_sh),
                out_shardings=base_out + tuple(extra_sh),
                donate_argnums=donate)
        else:  # fused forward+backward, grads all-reduced to replicated
            with_heads = kind == "fwd_bwd_heads"

            def fwd_bwd(params, aux, inputs, rng, heads=None):
                return fwd_bwd_math(params, aux, inputs, rng,
                                    heads if with_heads else None)

            in_sh = (psh, repl, batch, None) + (
                (self._out_shardings,) if with_heads else ())
            fn = jax_jit(fwd_bwd, in_shardings=in_sh,
                         out_shardings=(self._out_shardings, repl, gsh))

        self._jits[key] = fn
        return fn

    # -- program introspection -----------------------------------------
    def _note_program(self, kind, fn, args, extra=None):
        """Register this program with the process ProgramInventory
        (telemetry.introspect) — once per jit kind per (re)bind.
        Stores the call's aval skeleton so the inventory can later
        re-acquire the ``Compiled`` through the jit trace cache
        (analysis is lazy, off the step path, and runs under
        CompileWatch suppression). Cost here: one set lookup per call,
        one tree_map on the first."""
        if kind in self._program_notes:
            return
        self._program_notes.add(kind)
        try:
            from .. import telemetry
            avals = telemetry.aval_skeleton(args)
            base = kind.split(":")[0]
            meta = {"batch_size": self.batch_size,
                    "mesh_axes": dict(self.mesh_axes)}
            if extra:
                meta.update(extra)
            self._program_names[base] = telemetry.inventory().register(
                "%s.%s" % (self._inventory_owner, base),
                fn=fn, args_avals=avals, kind=base,
                n_dev=int(self.mesh.devices.size),
                device_kind=self._device_kind, meta=meta)
        except Exception:  # noqa: BLE001 - introspection never breaks a step
            pass

    def _note_optimizer_analytic(self, states, triples):
        """Register the optimizer-update traffic the FUSED train step
        folds in, as an analytic inventory entry: read
        w/g + write w on f32 plus a read+write of every state leaf —
        5 * 4 * n_params for f32 sgd-momentum. State leaves are
        accounted at their STORAGE dtype: a bf16 opt-state mode
        (mxnet_tpu.precision) halves the two state streams and this
        analytic entry is exactly the witness that records it."""
        if "optimizer_update" in self._program_notes:
            return
        self._program_notes.add("optimizer_update")
        try:
            from .. import telemetry

            def leaves(t):
                if t is None:
                    return 0
                if isinstance(t, (tuple, list)):
                    return sum(leaves(s) for s in t)
                return int(onp.prod(t.shape)) if hasattr(t, "shape") else 0

            def leaf_bytes(t):
                if t is None:
                    return 0
                if isinstance(t, (tuple, list)):
                    return sum(leaf_bytes(s) for s in t)
                if not hasattr(t, "shape"):
                    return 0
                itemsize = onp.dtype(t.dtype).itemsize \
                    if hasattr(t, "dtype") else 4
                return int(onp.prod(t.shape)) * int(itemsize)

            n_par = sum(int(onp.prod(self._param_dict[n].shape))
                        for _k, n in triples)
            n_state = sum(leaves(s) for s in states)
            state_bytes = sum(leaf_bytes(s) for s in states)
            self._program_names["optimizer_update"] = \
                telemetry.inventory().register(
                    "%s.optimizer_update" % self._inventory_owner,
                    kind="optimizer_update",
                    flops=4.0 * n_par,
                    bytes_accessed=4.0 * 3 * n_par + 2.0 * state_bytes,
                    device_kind=self._device_kind,
                    meta={"fused_into": "%s.train_step"
                          % self._inventory_owner,
                          "n_params": n_par, "n_state": n_state,
                          "state_bytes": state_bytes,
                          "precision_mode": self.precision_mode_name()})
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------------------------
    def set_params(self, arg_params, aux_params):
        # device_put straight from the source buffer (host OR device):
        # an .asnumpy() here would be a device->host readback per param —
        # ~260 blocking D2H round trips per ResNet-50 init
        import jax
        for n, buf in self._param_dict.items():
            if n in arg_params:
                buf._write(jax.device_put(arg_params[n]._read(),
                                          self._param_shardings[n]))
        for n, buf in self._aux_dict.items():
            if aux_params and n in aux_params:
                buf._write(jax.device_put(aux_params[n]._read(),
                                          self._repl))

    def get_params(self, arg_params, aux_params):
        """Sync host mirrors from device with packed readbacks (one up
        to PACK_BYTES of parameters).

        ResNet-50 has ~270 param/aux buffers — per-buffer fetches (the
        reference's copyto-per-array, executor_group.py get_params)
        would be ~270 blocking device->host round trips per call. One
        jitted concat of the raveled f32 buffers makes it a single
        fetch; slices are then split back on host.
        """
        import jax
        import jax.numpy as jnp

        items = [(arg_params[n], buf) for n, buf in self._param_dict.items()
                 if n in arg_params]
        if aux_params is not None:
            items += [(aux_params[n], buf)
                      for n, buf in self._aux_dict.items()
                      if n in aux_params]
        if not items:
            return
        fn = self._jits.get("pack_params")
        if fn is None:
            repl = self._repl

            def pack(arrs):
                # constrain every input to replicated BEFORE the ravel:
                # concatenating mixed partially-replicated arrays makes
                # the SPMD partitioner emit a dp-axis SUM instead of a
                # replication (observed on XLA:CPU, dp=2 doubles every
                # param), which silently corrupted sharded-module
                # get_params/save_params
                return jnp.concatenate(
                    [jax.lax.with_sharding_constraint(a, repl)
                     .ravel().astype(jnp.float32) for a in arrs])

            fn = self._jits["pack_params"] = jax.jit(
                pack, out_shardings=self._repl)
        # one packed fetch per PACK_BYTES of parameters: the pack is a
        # second copy on the device for as long as its fetch takes, and
        # one of a whole language model (2.8 GB) does not fit beside a
        # step in flight.  ResNet-50's 102 MB stay one fetch.
        groups, size = [[]], 0
        for item in items:
            nbytes = 4 * (int(onp.prod(item[1].shape)) if item[1].shape
                          else 1)
            if groups[-1] and size + nbytes > PACK_BYTES:
                groups.append([])
                size = 0
            groups[-1].append(item)
            size += nbytes
        for group in groups:
            flat = onp.asarray(fn([buf._read() for _, buf in group]))
            off = 0
            for tgt, buf in group:
                size = int(onp.prod(buf.shape)) if buf.shape else 1
                tgt._write(flat[off:off + size].reshape(buf.shape)
                           .astype(tgt.dtype, copy=False))
                off += size

    # ------------------------------------------------------------------
    # device-side input augmentation (mxnet_tpu.data.DeviceAugment)
    #
    # The augment runs as its OWN compiled device program at staging
    # time, consuming the staged uint8 NHWC wire block + the tiny
    # per-row parameter arrays and emitting the f32 NCHW model batch.
    # Deliberately NOT fused into the train-step program: a different
    # preamble changes how XLA compiles the whole step (layout/fusion
    # choices shift the model's reduction rounding), which would break
    # the bitwise host-reference parity contract.  Standalone, the
    # augment is pure elementwise/gather work — no reductions — so its
    # output bytes equal DeviceAugment.apply_host exactly for ANY
    # batch shape, and the train-step program stays byte-identical to
    # one fed pre-augmented f32 batches.  The wire still carries u8
    # (the 4x transfer win); the cost is one extra launch per staged
    # batch, amortized K-fold by grouped staging.
    def _augment_jit(self, name, aug, train, grouped):
        key = ("augment", name, bool(train), bool(grouped))
        if key in self._jits:
            return self._jits[key]
        import jax

        out_sh = self._stacked_sharding() if grouped \
            else self._batch_sharding

        def fn(x, crop, mirror):
            if not grouped:
                return aug.apply(x, crop, mirror, train=train)
            # (K, B, ...) block: flatten the group axis, augment, and
            # restore — elementwise ops, so the bytes match K per-batch
            # launches exactly
            k, b = x.shape[0], x.shape[1]
            flat = aug.apply(
                x.reshape((k * b,) + tuple(x.shape[2:])),
                None if crop is None else
                crop.reshape((k * b,) + tuple(crop.shape[2:])),
                None if mirror is None else mirror.reshape((k * b,)),
                train=train)
            return flat.reshape((k, b) + tuple(flat.shape[1:]))

        jitted = jax.jit(fn, out_shardings=out_sh,
                         static_argnames=())
        self._jits[key] = jitted
        return jitted

    def _apply_device_augment(self, inputs, is_train, grouped=False):
        """Replace each augmented input's staged wire block (+ param
        arrays, which are POPPED) with the augment program's f32 model
        batch.  Already-model-view inputs (a classic f32 eval iterator
        on an augment-bound module) pass through untouched."""
        if not self._device_augment:
            return inputs
        from ..data.augment import crop_input_name, mirror_input_name
        lead = 2 if grouped else 1
        for name, aug in self._device_augment.items():
            v = inputs.get(name)
            if v is None:
                continue
            crop = inputs.pop(crop_input_name(name), None)
            mirror = inputs.pop(mirror_input_name(name), None)
            if tuple(v.shape[lead:]) != aug.wire_shape:
                continue    # already the model view
            fn = self._augment_jit(name, aug, is_train, grouped)
            inputs[name] = fn(v, crop, mirror)
        return inputs

    def _stage(self, batch, is_train=False):
        """Shard the host batch onto the mesh ('dp' on axis 0).

        Every input rides THE staging rule
        (:func:`mxnet_tpu.dist.staging.stage_sharded`): an array on
        another backend than the mesh's (``nd.array`` under the default
        context puts onto jax's CPU backend) goes up from its host
        view, as a numpy value would; single-process the rest is
        ``jax.device_put`` (arrays resident on the MESH's backend —
        the DeviceLoader / virtual-host feed — pass through bitwise);
        multi-process it assembles this process's local rows — a
        ``ShardedDataIter`` slice, or this process's block of a
        replicated global batch — into the global array with
        ``make_array_from_process_local_data``, so the compiled global
        program runs unchanged across hosts.

        ``input.h2d_bytes`` counts here only what no producer can have
        counted, a value that is not a ``jax.Array`` yet; an
        off-backend array's bytes are counted by staging itself in
        ``exec.stage_host_routed_bytes``."""
        import jax
        from .. import telemetry
        from ..dist.staging import stage_sharded, stage_zeros

        def put(arr):
            val = arr._read() if hasattr(arr, "_read") else arr
            if not isinstance(val, jax.Array):
                telemetry.count("input.h2d_bytes", val.nbytes)
            return stage_sharded(
                val, self._batch_sharding,
                (self.batch_size,) + tuple(val.shape[1:]))

        with telemetry.span("exec.stage"):
            inputs = {}
            data_names = [x[0] for x in self.data_shapes]
            for name, arr in zip(data_names, batch.data):
                inputs[name] = put(arr)
            if self.label_shapes and batch.label:
                for name, arr in zip(self._label_names, batch.label):
                    if arr is not None:
                        inputs[name] = put(arr)
            inputs = self._apply_device_augment(inputs, is_train)
            bs = next(iter(inputs.values())).shape[0]
            for name in self._nonparam_names:
                if name not in inputs:
                    inputs[name] = stage_zeros(
                        (bs,) + tuple(self._shape_of[name][1:]),
                        self._batch_sharding)
        return inputs

    def _stacked_sharding(self, sharding=None):
        """Lift a per-batch NamedSharding to its (K, ...) stacked form:
        the leading group axis replicates, inner axes keep their spec.
        Default: the batch input sharding (group axis + 'dp' batch)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if sharding is None:
            sharding = self._batch_sharding
        return NamedSharding(self.mesh, P(*((None,) + sharding.spec)))

    def stage_stacked(self, stacked_data, is_train=True):
        """Place a dict of name -> (K, batch, ...) blocks (host or
        device, NDArray or raw) onto the mesh — ONE ``device_put`` per
        block — and zero-fill bound inputs the block does not provide
        (labels at predict time), like the per-batch ``_stage``.

        The shared staging step of every K-batches-per-launch program:
        stacked scoring (``score_stacked``) and the grouped train step
        (``step_update_grouped``) both ride it. Blocks route through
        the same :func:`~mxnet_tpu.dist.staging.stage_sharded` rule as
        per-batch staging (off-backend blocks from their host view;
        single-process: plain ``device_put``; multi-process:
        per-process ``(K, B/R, ...)`` blocks assemble into the global
        ``(K, B, ...)`` array).  ``input.h2d_bytes`` is counted
        where the block was stacked (``_stack_batch_arrays``), which
        still sees which of its batches came in as numpy."""
        from .. import telemetry
        from ..dist.staging import stage_sharded, stage_zeros
        st_batch = self._stacked_sharding()
        with telemetry.span("exec.stage"):
            inputs = {}
            K = None
            for name, arr in stacked_data.items():
                arr = arr._read() if isinstance(arr, nd.NDArray) else arr
                K = arr.shape[0]
                inputs[name] = stage_sharded(
                    arr, st_batch,
                    (K, self.batch_size) + tuple(arr.shape[2:]))
            inputs = self._apply_device_augment(inputs, is_train,
                                                grouped=True)
            bs = next(iter(inputs.values())).shape[1]
            for name in self._nonparam_names:
                if name not in inputs:
                    inputs[name] = stage_zeros(
                        (K, bs) + tuple(self._shape_of[name][1:]),
                        st_batch)
        return inputs

    def score_stacked(self, stacked_data):
        """Score K batches in ONE launch (see "fwd_eval_stacked").

        ``stacked_data``: dict data_name -> (K, B, ...) array (host or
        device). Returns a tuple of stacked (K, ...) output jax arrays.
        """
        self._materialize_backward()
        inputs = self.stage_stacked(stacked_data, is_train=False)
        fn = self._get_jit("fwd_eval_stacked")
        params = {n: b._read() for n, b in self._param_dict.items()}
        aux = {n: b._read() for n, b in self._aux_dict.items()}
        rng = _random.next_key() if self._needs_rng else \
            onp.zeros((2,), onp.uint32)
        self._note_program("fwd_eval_stacked", fn,
                           (params, aux, inputs, rng))
        return fn(params, aux, inputs, rng)

    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self.for_training
        # a still-deferred backward (one-program step awaiting update())
        # must run before its inputs are superseded — dropping it would
        # lose that batch's grads and BN-EMA side effects
        self._materialize_backward()
        inputs = self._stage(data_batch, is_train=bool(is_train))
        rng = _random.next_key() if self._needs_rng else \
            onp.zeros((2,), onp.uint32)
        self._pending = (inputs, bool(is_train), rng)
        self._last = self._pending
        self._last_aux = None
        self._outputs_from = None
        force = self._materialize_forward
        for o in self._out_arrays:
            o._chunk.force = force

    def _materialize_forward(self):
        if self._pending is None:
            return
        inputs, is_train, rng = self._pending
        self._pending = None
        fn = self._get_jit("fwd_train" if is_train else "fwd_eval")
        params = {n: b._read() for n, b in self._param_dict.items()}
        aux = {n: b._read() for n, b in self._aux_dict.items()}
        # snapshot pre-forward aux so a later backward() re-runs from the
        # same moving statistics (no double BN-EMA update)
        self._last_aux = aux
        self._note_program("fwd_train" if is_train else "fwd_eval", fn,
                           (params, aux, inputs, rng))
        outs, new_aux = fn(params, aux, inputs, rng)
        self._write_outs(outs)
        if is_train:
            self._write_aux(new_aux)
        self._outputs_from = "fwd"

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True"
        if self._outputs_from == "bwd":
            return  # fused fwd+bwd already ran for this forward
        if getattr(self, "_last", None) is None:
            raise MXNetError("backward() called before forward()")
        inputs, _, rng = self._last
        self._pending = None
        if out_grads is None and getattr(self, "_step_enabled", False):
            # defer: if update() follows (the fit loop), the whole step —
            # fwd+bwd+optimizer — runs as ONE XLA program (step_update).
            # Reading outputs or grads first falls back to plain fwd_bwd.
            self._pending_bwd = (inputs, rng)
            force = self._materialize_backward
            for o in self._out_arrays:
                o._chunk.force = force
            for g in self._grad_dict.values():
                g._chunk.force = force
            self._outputs_from = "bwd"
            return
        self._run_fwd_bwd(inputs, rng, out_grads)

    def _run_fwd_bwd(self, inputs, rng, out_grads=None):
        params = {n: b._read() for n, b in self._param_dict.items()}
        aux = self._last_aux if getattr(self, "_last_aux", None) is not None \
            else {n: b._read() for n, b in self._aux_dict.items()}
        if out_grads is None:
            fn = self._get_jit("fwd_bwd")
            self._note_program("fwd_bwd", fn, (params, aux, inputs, rng))
            outs, new_aux, grads = fn(params, aux, inputs, rng)
        else:
            import jax
            if isinstance(out_grads, nd.NDArray):
                out_grads = [out_grads]
            # each head is placed with ITS output's sharding (replicated
            # outputs, e.g. anchors/losses, can't take the batch spec)
            heads = tuple(jax.device_put(
                g._read() if isinstance(g, nd.NDArray) else onp.asarray(g),
                sh) for g, sh in zip(out_grads, self._out_shardings))
            fn = self._get_jit("fwd_bwd_heads")
            outs, new_aux, grads = fn(params, aux, inputs, rng, heads)
        self._count_remat_kept()
        self._write_outs(outs)
        self._write_aux(new_aux)
        for n, g in grads.items():
            self._grad_dict[n]._write(g)
        self._outputs_from = "bwd"

    def _count_remat_kept(self, steps=1):
        """After a program that ran ``steps`` remat steps: count into
        the fit report the bytes the segments' backward passes were
        handed in place of making them again (the named values the
        policy keeps, by shape; known since the program traced)."""
        if self._remat_eval_fn is not None:
            from .. import telemetry
            telemetry.count("remat.kept_bytes",
                            steps * self._remat_kept_bytes)

    def _materialize_backward(self):
        """Early outputs/grads read while a one-program step was pending:
        run the plain fwd+bwd now (params are still pre-update)."""
        pend = getattr(self, "_pending_bwd", None)
        if pend is None:
            return
        self._pending_bwd = None
        for g in self._grad_dict.values():
            g._chunk.force = None
        inputs, rng = pend
        self._run_fwd_bwd(inputs, rng)

    def precision_mode_name(self):
        """Recorded precision-mode name for this group ('f32' when no
        policy is bound) — the spelling checkpoint manifests and the
        serving-side mode check compare."""
        from ..precision.policy import mode_name
        return mode_name(self._precision)

    def _ls_current(self):
        """The device-resident (scale, good-steps, skipped-updates)
        loss-scale triple, lazily initialized from the policy's config
        (None when the policy does not scale). Lives across steps; the
        step programs return its successor."""
        if self._ls_cfg is None:
            return None
        if self._ls_state is None:
            import jax
            self._ls_state = (
                jax.device_put(onp.float32(self._ls_cfg["init"]),
                               self._repl),
                jax.device_put(onp.int32(0), self._repl),
                jax.device_put(onp.int32(0), self._repl))
        return self._ls_state

    def loss_scale(self):
        """Current dynamic loss scale as a host float (None when the
        policy does not scale). Well-defined from bind onward: before
        the first step the configured init is reported (without forcing
        device-state allocation). Forces a device readback once the
        state exists — monitoring only, never on the step path."""
        if self._ls_cfg is None:
            return None
        if self._ls_state is None:
            return float(self._ls_cfg["init"])
        return float(self._ls_state[0])

    def scale_skips(self):
        """Total loss-scaler skipped updates (non-finite-grad steps
        whose param/state update was suppressed) as a host int, or
        None when the policy does not scale. Same off-path readback
        discipline as :meth:`loss_scale` — fit polls it at the epoch
        boundary into the ``precision.scale_skips`` gauge so a
        pathological skip storm is visible to the watchdog."""
        if self._ls_cfg is None:
            return None
        if self._ls_state is None:
            return 0
        return int(self._ls_state[2])

    # -- guardian numeric-health sentinel (mxnet_tpu.guardian) ---------
    def enable_health(self, window=32, stat_metric=None, probe_period=0):
        """Arm the device-resident health word: subsequent train-step
        programs thread a ``(flags, first_bad, count, loss-ring)``
        carry (the loss-scale pair's discipline — zero step-path
        readbacks, polled off-path via :meth:`health_poll`).
        ``stat_metric`` (an EvalMetric with a fused statistic, e.g.
        CrossEntropy) defines the ring's per-step loss scalar; None
        falls back to the first output's mean. ``probe_period=N`` also
        runs every N-th step twice through a non-donating program and
        compares the updated params bitwise on device (the SDC parity
        probe). Must be armed before the step programs compile (fit
        arms at its entry, inside the warmup window)."""
        stat = None
        token = 0
        if stat_metric is not None and self._label_names:
            stat = stat_metric.fused_stat()
            if stat is not None:
                # metric-token protocol (enable_device_metric): the
                # SAME metric object re-arms onto the SAME compiled
                # program instead of retracing
                token = getattr(stat_metric, "_mxtpu_tally_token", None)
                if token is None:
                    token = stat_metric._mxtpu_tally_token = \
                        next(_STEP_TOKENS)
        self._health_cfg = {"window": int(window), "stat": stat,
                            "probe_period": int(probe_period or 0),
                            "token": int(token)}
        self._health_state = None
        self._probe_count = 0

    def disable_health(self):
        self._health_cfg = None
        self._health_state = None

    def _health_kind_tag(self):
        """The jit-cache tag an armed health word adds to a step
        program's kind (window + stat identity — the program's shape
        depends on both)."""
        cfg = self._health_cfg
        if cfg is None:
            return ""
        return ":h%d.%d" % (cfg["window"], cfg["token"])

    def _health_current(self):
        """The device health word, lazily (re)initialized: flags 0,
        first_bad -1, count 0, ring NaN-filled."""
        if self._health_cfg is None:
            return None
        if self._health_state is None:
            import jax
            w = self._health_cfg["window"]
            self._health_state = (
                jax.device_put(onp.int32(0), self._repl),
                jax.device_put(onp.int32(-1), self._repl),
                jax.device_put(onp.int32(0), self._repl),
                jax.device_put(onp.full((w,), onp.nan, onp.float32),
                               self._repl))
        return self._health_state

    def health_poll(self):
        """Read the health word back to host (OFF the step path — the
        guardian calls this at the epoch/commit boundary only).
        Returns ``{"flags", "first_bad", "count", "ring"}`` or None
        when unarmed / no step has run."""
        if self._health_cfg is None or self._health_state is None:
            return None
        flags, first_bad, count, ring = self._health_state
        return {"flags": int(flags), "first_bad": int(first_bad),
                "count": int(count),
                "ring": onp.asarray(ring, onp.float32)}

    def health_reset(self):
        """Zero the health word (guardian epoch-boundary bracket):
        the next step re-initializes it, so ``count`` is the executed-
        step ordinal within the polling window."""
        self._health_state = None

    def _step_extras(self):
        """The optional trailing step-program arguments in their fixed
        order — metric tally, loss-scale triple, health word — lazily
        initializing each (the one arg-assembly rule the per-batch and
        grouped launches share)."""
        import jax
        extras = ()
        if self._metric_stat is not None:
            if self._metric_acc is None:
                self._metric_acc = (
                    jax.device_put(onp.zeros(self._metric_slots,
                                             onp.float32), self._repl),
                    jax.device_put(onp.zeros(self._metric_slots,
                                             onp.int32), self._repl))
            extras += (self._metric_acc,)
        if self._counter_names:
            if self._counter_acc is None:
                self._counter_acc = {
                    n: jax.device_put(onp.zeros((), onp.float32),
                                      self._repl)
                    for n in self._counter_names}
            extras += (self._counter_acc,)
        ls = self._ls_current()
        if ls is not None:
            extras += (ls,)
        health = self._health_current()
        if health is not None:
            extras += (health,)
        return extras

    def _commit_step_extras(self, out):
        """Unpack one step program's outputs: commit the trailing
        extras (tally / loss scale / health word) back into their
        device-state slots and return the fixed five-tuple."""
        idx = 5
        if self._metric_stat is not None:
            self._metric_acc = out[idx]
            self._metric_step_done = True
            idx += 1
        if self._counter_names:
            self._counter_acc = out[idx]
            idx += 1
        if self._ls_cfg is not None:
            self._ls_state = out[idx]
            idx += 1
        if self._health_cfg is not None:
            self._health_state = out[idx]
            idx += 1
        return out[0], out[1], out[2], out[3], out[4]

    def _launch_step_program(self, kind, fn, args):
        """Launch a train-step program — or, on an SDC-probe step,
        launch the non-donating variant TWICE on the identical
        arguments and fold the bitwise params comparison into the
        health word. Two separate launches (not one program computing
        the step twice): XLA would CSE a duplicated pure computation
        back into one, which is exactly what a parity probe must not
        let happen."""
        hcfg = self._health_cfg
        if not hcfg or not hcfg.get("probe_period"):
            return fn(*args)
        n = self._probe_count
        self._probe_count += 1
        if n % int(hcfg["probe_period"]):
            return fn(*args)
        from .. import faults as _faults
        from .. import telemetry
        fnp = self._get_jit(kind + ":probe")
        out1 = fnp(*args)
        args2 = args
        if _faults.armed():
            # guardian.sdc seam (kind=value): perturb the second
            # launch's host lr row by the injected relative delta — a
            # deterministic way to make the parity compare fail, so
            # the whole detect->rollback chain downstream is the real
            # one (a real SDC needs real flaky silicon)
            delta = _faults.value("guardian.sdc", None, probe=n)
            if delta is not None:
                args2 = args[:5] + (args[5] * (1.0 + float(delta)),) \
                    + args[6:]
        out2 = fnp(*args2)
        telemetry.registry().scope("guardian").counter(
            "sdc_checks").add()
        health = self._sdc_fold_jit()(out1[3], out2[3], out1[-1])
        return out1[:-1] + (health,)

    def _grads_stay_in_the_step(self):
        """After a one-program step: the gradient arrays hold nothing,
        and say so when read."""
        import jax
        for g in self._grad_dict.values():
            g._chunk.arr = jax.ShapeDtypeStruct(g.shape, g.dtype)
            g._chunk.force = _grads_not_kept

    def _bound_outputs_in_flight(self, outs):
        """Hold the host back while the outputs of the steps in flight
        pass STEP_OUTPUT_BYTES_IN_FLIGHT: wait for the oldest of them.
        One step is always let through.  Steps whose outputs could not
        reach the limit within the client's run-ahead are not tracked
        at all."""
        nbytes = sum(o.nbytes for o in outs)
        if nbytes * STEPS_IN_FLIGHT <= STEP_OUTPUT_BYTES_IN_FLIGHT:
            return
        import jax
        q = self._inflight_outs
        q.append((outs, nbytes))
        while len(q) > 1 and sum(b for _o, b in q) > \
                STEP_OUTPUT_BYTES_IN_FLIGHT:
            jax.block_until_ready(q.popleft()[0])

    def read_op_counters(self):
        """What the symbol's ops counted in the train steps since the
        last call, {name: float}, read back in one transfer; the tally
        starts again from nought."""
        acc, self._counter_acc = self._counter_acc, None
        if acc is None:
            return {}
        import jax
        return {n: float(v) for n, v in jax.device_get(acc).items()}

    def _sdc_fold_jit(self):
        """The tiny device comparator folding an SDC probe verdict
        into the health word (cached like every other program)."""
        fn = self._jits.get("sdc_fold")
        if fn is None:
            import jax
            import jax.numpy as jnp
            grad_names = tuple(self._grad_names)

            def fold(a_params, b_params, health):
                return _sdc_fold(jnp, a_params, b_params, health,
                                 grad_names)

            fn = self._jits["sdc_fold"] = jax.jit(
                fold, out_shardings=(self._repl,) * 4)
        return fn

    def step_update(self, updater, num_device=1):
        """Run the pending fwd+bwd AND the optimizer as one XLA program.

        Returns False (caller must use the classic update path) when no
        step is pending or the optimizer has no pure fused apply. The
        updater's state dict / update counters are maintained exactly as
        Updater.update_multi would (same (index*num_device) state keys).
        """
        pend = getattr(self, "_pending_bwd", None)
        if pend is None:
            return False
        opt = updater.optimizer
        fa = updater.fused_apply_or_none()
        if fa is None:
            return False
        import jax
        import numpy as np

        inputs, rng = pend
        # state keys follow _update_params: index over param_names of the
        # grads-bearing params, times num_device (one block here)
        triples = []
        for index, n in enumerate(self.param_names):
            if n in self._grad_dict:
                triples.append((index * num_device, n))
        ws = {}
        states, lrs, wds = [], [], []
        for key, n in triples:
            w = self._param_dict[n]
            if key not in updater.states:
                updater.states[key] = opt.create_state(key, w)
            opt._update_count(key)
            get_lr = getattr(opt, "_fused_lr", opt._get_lr)
            lrs.append(get_lr(key))
            wds.append(opt._get_wd(key))
            ws[n] = w._read()
            states.append(updater.read_state_tree(key, ws[n]))
        self._pending_bwd = None
        for g in self._grad_dict.values():
            g._chunk.force = None

        self._step_fa = fa
        # per-instance token, NOT id(): ids are reused after GC, and the
        # fa closure bakes trace-time hypers (momentum, betas) into the
        # compiled program — a recycled id would silently reuse them
        token = getattr(opt, "_mxtpu_step_token", None)
        if token is None:
            token = opt._mxtpu_step_token = next(_STEP_TOKENS)
        kind = "train_step:%s:%d" % (type(opt).__name__, token)
        if self._metric_stat is not None:
            kind += ":m%d" % self._metric_token
        kind += self._health_kind_tag()
        fn = self._get_jit(kind)
        params = {n: b._read() for n, b in self._param_dict.items()}
        # pre-forward aux snapshot (same contract as _run_fwd_bwd): if the
        # forward already materialized, _aux_dict holds post-EMA stats —
        # re-running from them would apply the BN EMA twice
        aux = self._last_aux if getattr(self, "_last_aux", None) is not None \
            else {n: b._read() for n, b in self._aux_dict.items()}
        args = (params, aux, tuple(states), inputs, rng,
                np.asarray(lrs, np.float32), np.asarray(wds, np.float32))
        args = args + self._step_extras()
        # aval skeleton for diagnostics (cost analysis) — the real
        # buffers are donated below and unusable afterwards
        from .. import telemetry
        self._last_step = (fn, telemetry.aval_skeleton(args))
        self._note_program(kind, fn, args)
        self._note_optimizer_analytic(states, triples)
        with telemetry.span("exec.launch"):
            out = self._launch_step_program(kind, fn, args)
            self._bound_outputs_in_flight(out[0])
        self._count_remat_kept()
        outs, new_aux, _no_grads, new_params, new_states = \
            self._commit_step_extras(out)
        self._write_outs(outs)
        self._write_aux(new_aux)
        self._grads_stay_in_the_step()
        for n, p in new_params.items():
            self._param_dict[n]._write(p)
        for (key, n), ns in zip(triples, new_states):
            updater.write_state_tree(key, ns)
        self._outputs_from = "bwd"
        return True

    def step_update_grouped(self, updater, inputs, num_device=1):
        """Run K whole train steps — fwd+bwd+optimizer (+metric tally) —
        as ONE XLA program over a ``(K, batch, ...)`` stacked block.

        ``inputs``: what :meth:`stage_stacked` returned for the block —
        dict input name -> (K, batch, ...) array on the mesh, staged
        with ONE ``device_put`` per input, so the fixed per-transfer
        cost is paid once per K steps instead of once per step (``fit``
        stages in its ``fit.forward_backward`` phase and launches in
        ``fit.update``, like a per-batch step).  The lr-scheduler clock
        advances K times on the HOST before launch — each scanned step
        consumes its own true-``num_update`` lr row, so schedules that change
        mid-group (and Adam's per-step bias correction) match K
        sequential steps exactly.  Updater states / counters end up
        exactly as K ``step_update`` calls would leave them.

        Returns False (caller must run per-batch steps) when the fused
        one-program step is not available for this optimizer."""
        if not getattr(self, "_step_enabled", False) or \
                not self.for_training:
            return False
        opt = updater.optimizer
        fa = updater.fused_apply_or_none()
        if fa is None:
            return False
        import numpy as np
        from .. import telemetry

        # a still-deferred per-batch step must run before its params are
        # superseded (same contract as forward())
        self._materialize_backward()
        K = next(iter(inputs.values())).shape[0]

        triples = []
        for index, n in enumerate(self.param_names):
            if n in self._grad_dict:
                triples.append((index * num_device, n))
        ws = {}
        for key, n in triples:
            w = self._param_dict[n]
            if key not in updater.states:
                updater.states[key] = opt.create_state(key, w)
            ws[n] = w._read()
        # per-STEP lr rows: the scheduler (and Adam's t-dependent fused
        # lr) is consulted at every one of the K update counts, exactly
        # as K sequential step_update calls would
        get_lr = getattr(opt, "_fused_lr", opt._get_lr)
        lr_rows = []
        for _ in range(K):
            row = []
            for key, _n in triples:
                opt._update_count(key)
                row.append(get_lr(key))
            lr_rows.append(row)
        lrs = np.asarray(lr_rows, np.float32)
        wds = np.asarray([opt._get_wd(key) for key, _n in triples],
                         np.float32)
        states = [updater.read_state_tree(key, ws[n])
                  for key, n in triples]

        self._step_fa = fa
        token = getattr(opt, "_mxtpu_step_token", None)
        if token is None:
            token = opt._mxtpu_step_token = next(_STEP_TOKENS)
        kind = "train_step_grouped:%s:%d" % (type(opt).__name__, token)
        if self._metric_stat is not None:
            kind += ":m%d" % self._metric_token
        kind += self._health_kind_tag()
        fn = self._get_jit(kind)
        params = {n: b._read() for n, b in self._param_dict.items()}
        aux = {n: b._read() for n, b in self._aux_dict.items()}
        rng = _random.next_key() if self._needs_rng else \
            onp.zeros((2,), onp.uint32)
        args = (params, aux, tuple(states), inputs, rng, lrs, wds)
        args = args + self._step_extras()
        self._note_program(kind, fn, args, extra={"batch_group": K})
        self._note_optimizer_analytic(states, triples)
        with telemetry.span("exec.launch"):
            out = self._launch_step_program(kind, fn, args)
        self._count_remat_kept(K)
        outs, new_aux, _no_grads, new_params, new_states = \
            self._commit_step_extras(out)
        self._write_outs(outs)
        self._write_aux(new_aux)
        self._grads_stay_in_the_step()
        for n, p in new_params.items():
            self._param_dict[n]._write(p)
        for (key, n), ns in zip(triples, new_states):
            updater.write_state_tree(key, ns)
        self._last_aux = None
        self._outputs_from = "bwd"
        return True

    def _write_outs(self, outs):
        for o, v in zip(self._out_arrays, outs):
            o._chunk.force = None
            o._chunk.arr = v

    def _write_aux(self, new_aux):
        for n, v in new_aux.items():
            self._aux_dict[n]._write(v)

    # ------------------------------------------------------------------
    def get_outputs(self, merge_multi_context=True):
        for o in self._out_arrays:
            o._read()  # materialize any pending forward
        if merge_multi_context:
            return list(self._out_arrays)
        return [[o] for o in self._out_arrays]

    def get_input_grads(self, merge_multi_context=True):
        raise MXNetError("inputs_need_grad is not supported on the fused "
                         "mesh path; set MXNET_MODULE_FUSED=0")

    # -- device-side metric tally --------------------------------------
    def enable_device_metric(self, eval_metric):
        """Fold ``eval_metric``'s statistic into the one-program train step.

        TPU-first redesign of the reference's per-batch metric feed
        (executor_group.py:510 + base_module.py fit loop): there every
        batch pays an ``asnumpy`` device->host readback, which blocks
        the host on the device every step. Here the jitted step accumulates
        ``(sum, count)`` rows in a donated device tally; ``get()`` drains
        it with one readback at epoch end / Speedometer tick. Installed by
        ``Module.fit`` only — raw-loop users keep exact host semantics.
        Returns True when installed (metric decomposable + fused step on).
        """
        # always clear first: a non-fusable metric must not leave a
        # previous fit's tally live absorbing this fit's statistics
        self.disable_device_metric()
        if not getattr(self, "_step_enabled", False) or \
                not self.for_training or not self._label_names:
            return False
        stat = eval_metric.fused_stat()
        if stat is None:
            return False
        self._metric_stat = stat
        self._metric_slots = getattr(stat, "n_slots", 1)
        self._metric_live = eval_metric
        # per-metric-instance token (same protocol as the optimizer's
        # _mxtpu_step_token): re-fitting with the SAME metric object must
        # reuse the compiled train-step program, not retrace it. The stat
        # closure bakes the metric's config (top_k, pred_index, ...), so
        # mutating a metric between fits requires a fresh metric object.
        token = getattr(eval_metric, "_mxtpu_tally_token", None)
        if token is None:
            token = eval_metric._mxtpu_tally_token = next(_STEP_TOKENS)
        self._metric_token = token
        self._metric_step_done = False
        self._metric_acc = None  # zeroed lazily at the next step
        eval_metric._bind_device_tally(self._read_metric_tally,
                                       self._zero_metric_tally)
        return True

    def disable_device_metric(self):
        """Detach any live tally (new fit with a host-only metric, or
        MXNET_DEVICE_METRIC=0): drain-pending state is folded by the old
        metric's next get(); new steps stop accumulating."""
        if self._metric_live is not None:
            self._metric_live._drain_device()
            self._metric_live._unbind_device_tally()
        self._metric_stat = None
        self._metric_live = None
        self._metric_acc = None
        self._metric_step_done = False

    def score_device(self, eval_data, eval_metric, num_batch=None):
        """Evaluate with the metric tallied on device (one launch per
        batch, ONE readback at the end) — the eval-side twin of
        ``enable_device_metric``. Uses its own accumulator, so a live
        fit tally on a DIFFERENT metric object is untouched; passing
        the fit metric itself behaves like the host loop does (score
        resets the metric — mid-epoch train statistics are consumed on
        either path). Returns ``(name_value_pairs, batches_seen)``, or
        ``None`` when the metric is not fusable (caller falls back to
        the host loop)."""
        stat = eval_metric.fused_stat()
        if stat is None or not self._label_names:
            return None
        import jax

        self._materialize_backward()
        token = getattr(eval_metric, "_mxtpu_tally_token", None)
        if token is None:
            token = eval_metric._mxtpu_tally_token = next(_STEP_TOKENS)
        self._escore_stat = stat
        fn = self._get_jit("fwd_eval_stat:m%d" % token)
        slots = getattr(stat, "n_slots", 1)
        acc = (jax.device_put(onp.zeros(slots, onp.float32), self._repl),
               jax.device_put(onp.zeros(slots, onp.int32), self._repl))
        params = {n: b._read() for n, b in self._param_dict.items()}
        aux = {n: b._read() for n, b in self._aux_dict.items()}
        seen = 0
        host_tally = None
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            if not batch.label or all(lb is None for lb in batch.label):
                # the host loop raises in check_label_shapes; scoring
                # against _stage's zero-filled labels would be a silent
                # wrong answer
                raise MXNetError(
                    "score() needs labels; batch %d has none" % nbatch)
            rows = batch.data[0].shape[0]
            if 0 < rows < self.batch_size:
                # epoch tail: pad to the bound shape and run the PLAIN
                # eval program (shared with the predict path) instead of
                # tracing a remainder-shape tally program; the real
                # rows' statistic folds on host (the donated device
                # accumulate cannot mask padded rows)
                host_tally = self._tail_stat_host(batch, rows, stat,
                                                  host_tally)
                seen = nbatch + 1
                continue
            inputs = self._stage(batch)
            rng = _random.next_key() if self._needs_rng else \
                onp.zeros((2,), onp.uint32)
            self._note_program("fwd_eval_stat:m%d" % token, fn,
                               (params, aux, inputs, rng, acc))
            acc = fn(params, aux, inputs, rng, acc)
            seen = nbatch + 1
        eval_metric.reset()
        packed = self._pack_tally_pair(*acc)
        if host_tally is not None:
            packed[:, 0] += host_tally[0]
            packed[:, 1] += host_tally[1]
        eval_metric._fold_tally(packed)
        return eval_metric.get_name_value(), seen

    def _tail_stat_host(self, batch, rows, stat, host_tally):
        """Score one smaller-than-bound tail batch without a new
        compile: zero-pad inputs to the bound batch shape, run the
        cached ``fwd_eval`` program, slice the real rows, and fold the
        metric statistic into a host-side (sums, counts) pair that the
        caller adds to the device tally at drain time."""
        import jax.numpy as jnp
        from ..io import DataBatch
        from .base_module import pad_batch_rows
        data = [nd.NDArray(pad_batch_rows(d, self.batch_size))
                for d in batch.data]
        label = [None if lb is None else
                 nd.NDArray(pad_batch_rows(lb, self.batch_size))
                 for lb in batch.label]
        inputs = self._stage(DataBatch(data=data, label=label))
        fn = self._get_jit("fwd_eval")
        params = {n: b._read() for n, b in self._param_dict.items()}
        aux = {n: b._read() for n, b in self._aux_dict.items()}
        rng = _random.next_key() if self._needs_rng else \
            onp.zeros((2,), onp.uint32)
        outs, _ = fn(params, aux, inputs, rng)
        sliced = tuple(o[:rows] if o.ndim >= 1 and
                       o.shape[0] == self.batch_size else o for o in outs)
        labels = [inputs[n][:rows] for n in self._label_names]
        slots = getattr(stat, "n_slots", 1)
        sums, counts = _tally_add(
            jnp, stat, labels, sliced,
            (jnp.zeros((slots,), jnp.float32),
             jnp.zeros((slots,), jnp.int32)))
        pair = (onp.asarray(sums, onp.float64),
                onp.asarray(counts, onp.float64))
        if host_tally is None:
            return pair
        return (host_tally[0] + pair[0], host_tally[1] + pair[1])

    def _pack_tally_pair(self, sums, counts):
        """Read a (sums f32, counts i32) device tally as numpy (n, 2).

        ONE fused readback: separate fetches would cost two blocking
        round trips per drain. The pack rides in the
        INTEGER domain — small i32 counts bitcast to f32 are denormals,
        which the TPU vector unit flushes to zero (observed: a fit's
        num_inst read back as 0); f32 sums bitcast to i32 are plain
        bits and survive. Host side un-bitcasts the sum column."""
        import jax
        import jax.numpy as jnp
        fn = self._jits.get("pack_tally")
        if fn is None:
            from jax import lax

            def pack_tally(s, c):
                return jnp.stack(
                    [lax.bitcast_convert_type(s, jnp.int32), c], axis=1)

            fn = self._jits["pack_tally"] = jax.jit(
                pack_tally, out_shardings=self._repl)
        packed = onp.asarray(fn(sums, counts))
        out = onp.empty((packed.shape[0], 2), onp.float64)
        out[:, 0] = packed[:, 0].copy().view(onp.float32)
        out[:, 1] = packed[:, 1]
        return out

    def _read_metric_tally(self):
        if self._metric_acc is None:
            return onp.zeros((self._metric_slots, 2), onp.float64)
        return self._pack_tally_pair(*self._metric_acc)

    def _zero_metric_tally(self):
        self._metric_acc = None

    def update_metric(self, eval_metric, labels):
        if eval_metric is self._metric_live and self._metric_step_done:
            # this batch's statistic was accumulated on device inside the
            # fused train step — nothing to do host-side
            self._metric_step_done = False
            return
        eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        raise MXNetError("monitor requires the per-executor path; "
                         "Module re-binds automatically")
