"""Module — symbol + contexts + params + optimizer
(python/mxnet/module/module.py:708).
"""
from __future__ import annotations

import logging
import os

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..initializer import Uniform, InitDesc
from ..model import _create_kvstore, _initialize_kvstore, _update_params, \
    _update_params_on_kvstore, load_checkpoint, save_checkpoint
from .base_module import BaseModule, stack_group_inputs
from .executor_group import DataParallelExecutorGroup
from .mesh_executor_group import MeshExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    """Trainable module over a Symbol (module.py Module).

    When the bound contexts form one device mesh (and no feature forces the
    per-executor path), ``bind`` builds a fused :class:`MeshExecutorGroup` —
    one mesh-sharded XLA program per step — instead of N Python executors.
    ``compute_dtype`` selects mixed precision there (bfloat16 on TPU; params
    stay float32 master copies). ``MXNET_MODULE_FUSED=0`` forces the classic
    per-executor group.

    ``remat="full"`` (or ``MXNET_BACKWARD_DO_MIRROR=1``, matching the
    reference's graph_executor.cc:210-223 mirror switch) trains through the
    sqrt-N segmented-checkpoint evaluator (``executor._build_eval_segmented``):
    segment boundaries are held, and a segment's backward pass makes again
    what is cheap (norms, rotations, casts, activations) and is handed what
    is dear, as the reference's switch never recomputed ``FullyConnected``:
    the matrix products' outputs and attention's (the ops name them,
    ``precision.policy.keep``; ``Convolution`` is not named, since a
    convolutional net's activations are its convolutions' outputs). The
    trade, read on a v5e on a five-layer sparse decoder at 8,192 tokens a
    step (PERF.md section 6, PR 31): against a step that keeps nothing
    inside a segment, 1.65 GiB kept, peak 10.03 -> 11.23 GiB, the step
    345.8 -> 320.2 ms; without remat that step needs 18.7 GiB. The
    reduction is realized by XLA:TPU/GPU buffer assignment — a Module left
    on the default cpu() context compiles for XLA:CPU, which schedules
    through checkpoint boundaries and only shows the recompute, not the
    memory win.
    ``remat="dots"`` keeps what ``checkpoint_policies.dots_saveable`` sees
    (matmul/conv outputs) and attention's output — useful for
    transformer-style nets where elementwise chains dominate between
    matmuls; on conv nets it saves nothing.
    ``remat=jax.checkpoint_policies.nothing_saveable`` (any jax checkpoint
    policy callable passes through) is the strict schedule, a segment keeps
    nothing: for a step that fits so and not with its products kept.

    ``mesh_axes`` + ``param_sharding`` make tensor/model parallelism
    user-reachable through ``fit`` (the TPU-native upgrade of the
    reference's user-reachable ctx_group placement,
    graph_executor.cc:318):

    * ``mesh_axes={"dp": 2, "tp": 4}`` factorizes the bound contexts into
      a named device mesh (dict order = mesh order; sizes must multiply
      to the context count; a "dp" axis is required and carries the
      batch).
    * ``param_sharding=[(pattern, spec), ...]`` shards parameters over
      mesh axes: first substring match wins, ``spec`` is a
      PartitionSpec-style tuple over the param's dims, e.g. Megatron
      column-parallel ``("fc1_weight", ("tp", None))`` / row-parallel
      ``("fc2_weight", (None, "tp"))`` for mxnet's (out, in) weight
      layout (rules as in ``parallel.tensor_parallel
      .shard_params_for_tp``). Unmatched params replicate.

    The partitioner (GSPMD) then slices every matmul/conv touching a
    sharded param and inserts the Megatron collectives (one psum per
    column->row pair) automatically — the whole train step stays ONE XLA
    program, gradients and optimizer states shard like their params, and
    checkpoints still see full (gathered) arrays.

    ``pipeline_microbatches=M`` (with a ``"pp"`` axis in ``mesh_axes``)
    runs the symbol's ``ctx_group="stage<i>"`` region — the reference's
    ctx_group surface — as a GPipe pipeline: each pp rank holds its
    stage's params and the schedule is a ``lax.scan`` of stage compute +
    ``ppermute`` ring hops inside the same fused program
    (``executor._build_eval_pipelined``). Stages must be structurally
    identical repeated blocks (single carry tensor between stages,
    batch-polymorphic reshapes, no BatchNorm inside stages — violations
    raise with precise messages); preamble (embedding) and postamble
    (head/loss) run outside the pipeline under GSPMD. Numerics are
    microbatch-exact vs the unpipelined run for rng-free stages (ops
    with rng, e.g. Dropout, draw independent per-tick/rank streams
    instead of reproducing the unpipelined mask sequence); the bubble
    is the standard (S-1)/(M+S-1).
    """

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 compute_dtype=None, remat=None, mesh_axes=None,
                 param_sharding=None, pipeline_microbatches=None,
                 device_augment=None, precision=None, _allow_fused=True):
        super().__init__(logger=logger)
        # precision mode (mxnet_tpu.precision): a mode name ("combined",
        # "bf16_opt", ...) or PrecisionPolicy; None consults
        # MXNET_PRECISION_MODE. The policy FOLDS into the existing
        # compute_dtype/remat seams (explicit kwargs win over the
        # policy's fields so old call sites keep their meaning) and
        # additionally drives the optimizer-state storage dtype, the
        # experimental act casts + loss scaler, and the recorded mode
        # name checkpoints/serving compare.
        from .. import precision as _precision_mod
        self._precision = _precision_mod.resolve(precision)
        if self._precision is not None:
            pol = self._precision
            if compute_dtype is None:
                compute_dtype = pol.compute_dtype
            if remat is None:
                remat = pol.remat
        self._compute_dtype = compute_dtype
        # {data name: mxnet_tpu.data.DeviceAugment} — in-program input
        # augmentation (u8 wire batches).  Usually adopted from the
        # train iterator's device_augment_spec by fit(); settable here
        # for manual bind flows.
        self._device_augment = dict(device_augment or {})
        if mesh_axes is not None:
            mesh_axes = dict(mesh_axes)
            if "dp" not in mesh_axes:
                raise ValueError(
                    "mesh_axes must include a 'dp' (batch) axis; use "
                    "{'dp': 1, ...} for pure model parallelism")
        self._mesh_axes = mesh_axes
        self._param_sharding = list(param_sharding or [])
        self._pipeline_microbatches = pipeline_microbatches
        if remat is None:
            # a symbol may name the recomputation it was sized for
            # (models.afmoe: a step that does not fit without one)
            remat = symbol.attr("__remat__")
        if remat is None and os.environ.get(
                "MXNET_BACKWARD_DO_MIRROR", "0") == "1":
            # the reference's activation-recompute switch
            # (docs/how_to/env_var.md:64-66, graph_executor.cc:210-223)
            remat = "full"
        if remat is not None and not callable(remat):
            from ..base import MXNetError
            from ..precision.policy import canon_remat
            try:
                remat = canon_remat(remat)  # accepts the docs' long names
            except MXNetError:
                raise ValueError(
                    "remat must be None, 'full', 'dots'/'dots_saveable', "
                    "'bn_stats'/'offload_bn_stats' or a jax checkpoint-"
                    "policy callable (got %r)" % (remat,))
        self._remat = remat
        self._allow_fused = _allow_fused
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._kvstore_arg = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._shared_from_fused = False

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._eval_pad_extra = 0

    @staticmethod
    def load(prefix, epoch=None, load_optimizer_states=False, **kwargs):
        """Create from a checkpoint (module.py:97).

        ``prefix`` may be the legacy file prefix (with ``epoch``
        required), or a :class:`mxnet_tpu.checkpoint.CheckpointManager`
        (or its directory path) — then ``epoch`` selects a committed
        step, default the latest, and the symbol comes from the entry's
        manifest."""
        from ..checkpoint import CheckpointManager
        from ..checkpoint.manager import is_checkpoint_dir
        # a string routes to the manager path only when it actually
        # holds committed step entries (or no epoch was given, which the
        # legacy path cannot mean) — a legacy prefix colliding with an
        # unrelated directory name keeps loading its prefix files
        if isinstance(prefix, CheckpointManager) or (
                isinstance(prefix, str) and os.path.isdir(prefix) and
                (epoch is None or is_checkpoint_dir(prefix))):
            return Module._load_from_manager(prefix, epoch,
                                             load_optimizer_states,
                                             **kwargs)
        assert epoch is not None, \
            "epoch is required when loading from a legacy prefix"
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    @staticmethod
    def _load_from_manager(manager, step=None, load_optimizer_states=False,
                           **kwargs):
        """Rebuild a Module from a durable checkpoint entry. The entry is
        self-describing (symbol json rides in the manifest ``extra``);
        sharded saves re-assemble to global host arrays here, so the new
        Module may bind onto any device count / mesh layout."""
        from .. import symbol as sym_mod
        from ..base import MXNetError
        from ..checkpoint import CheckpointManager, split_params
        if not isinstance(manager, CheckpointManager):
            manager = CheckpointManager(manager)
        ckpt = manager.restore(step)
        sym_json = ckpt.extra.get("symbol")
        if sym_json is None:
            raise MXNetError(
                "checkpoint step %d in %s carries no symbol — it was not "
                "saved by Module.save_checkpoint(manager=...)"
                % (ckpt.step, manager.directory))
        arg_np, aux_np = split_params(ckpt.params)
        saved_mode = str(ckpt.extra.get("precision_mode", "f32"))
        if "precision" not in kwargs and saved_mode != "f32":
            # adopt the entry's recorded precision mode so the restored
            # module (and its optimizer-state dtypes) continue under the
            # numerics family the checkpoint was trained in; an explicit
            # precision= kwarg wins (the Updater still refuses a state-
            # dtype mismatch when optimizer states load)
            kwargs["precision"] = Module._policy_from_manifest(
                saved_mode, ckpt.extra.get("precision"))
        mod = Module(symbol=sym_mod.load_json(sym_json), **kwargs)
        mod._ckpt_precision_mode = saved_mode
        # recorded structural identity: the Predictor cross-checks it
        # against the digest it recomputes from the restored params, so
        # a post-load param swap cannot silently adopt a stale serving
        # executable-cache entry (None for pre-digest checkpoints)
        mod._ckpt_params_digest = ckpt.extra.get("params_digest")
        if mod.precision_mode != saved_mode:
            logging.warning(
                "checkpoint step %d was saved under precision mode %r "
                "but the restored module runs %r — serving this module "
                "will be refused (Predictor precision check)",
                ckpt.step, saved_mode, mod.precision_mode)
        mod._arg_params = {k: nd.array(v, dtype=v.dtype)
                           for k, v in arg_np.items()}
        mod._aux_params = {k: nd.array(v, dtype=v.dtype)
                           for k, v in aux_np.items()}
        mod.params_initialized = True
        if load_optimizer_states:
            if ckpt.optimizer_state is None:
                raise MXNetError(
                    "checkpoint step %d in %s has no optimizer state "
                    "(save with save_optimizer_states=True)"
                    % (ckpt.step, manager.directory))
            mod._preload_opt_states = ckpt.optimizer_state
        return mod

    @staticmethod
    def _policy_from_manifest(mode, desc):
        """Reconstruct a PrecisionPolicy from a checkpoint manifest's
        recorded mode name + describe() dict. Named registry modes
        resolve directly; ad-hoc policies rebuild from their canonical
        fields (a custom remat CALLABLE cannot ride a manifest — pass
        ``precision=`` explicitly to restore such a run)."""
        from .. import precision as _precision_mod
        from ..base import MXNetError
        desc = dict(desc or {})
        pol = _precision_mod.MODES.get(mode)
        if pol is not None:
            # a name hit alone is not provenance: register_mode()
            # overwrites names and built-in modes can evolve, so the
            # registry policy must still mean what the checkpoint
            # recorded — on disagreement the RECORDED fields win (the
            # numerics family the params were actually trained in)
            if not desc or pol.describe() == desc:
                return pol
            logging.warning(
                "checkpoint precision mode %r no longer matches the "
                "registered mode's fields; restoring the policy the "
                "checkpoint recorded (%r)", mode, desc)
        if desc.get("remat") == "custom":
            raise MXNetError(
                "checkpoint was saved under an ad-hoc precision policy "
                "with a custom remat callable (%r); callables cannot be "
                "reconstructed from the manifest — pass the policy via "
                "precision= when loading" % mode)

        def _field(key):
            v = desc.get(key)
            return None if v in (None, "float32", "none") else v

        return _precision_mod.PrecisionPolicy(
            name=mode, compute_dtype=_field("compute_dtype"),
            opt_state_dtype=_field("opt_state_dtype"),
            remat=_field("remat"), act_cast=desc.get("act_cast"),
            weight_quant=desc.get("weight_quant"),
            narrow_math=desc.get("narrow_math"),
            loss_scale=desc.get("loss_scale"),
            loss_scale_window=desc.get("loss_scale_window"),
            experimental=bool(desc.get("experimental")))

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        manager=None, async_save=True, extra=None):
        """Save symbol + params (+ optimizer states) (module.py:135-156).

        With ``manager=`` (a :class:`mxnet_tpu.checkpoint
        .CheckpointManager`) the save goes to a durable step entry
        instead of prefix files: atomic commit, async by default (the
        next train step overlaps the disk write), per-shard files for
        mesh-sharded parameters (no full gather), symbol + epoch + RNG
        in the manifest so ``fit(resume_from=manager)`` restores
        everything. ``epoch`` becomes the step number; ``prefix`` is
        ignored on this path and may be None. ``extra=`` merges caller
        metadata into the manifest — step-granular entries
        (``mxnet_tpu.dist.ElasticTrainer``) record their exact resume
        coordinates (``epoch``/``nbatch``/``num_update``) this way."""
        if manager is not None:
            return self._save_to_manager(manager, epoch,
                                         save_optimizer_states, async_save,
                                         extra)
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        self.logger.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            self.logger.info('Saved optimizer state to "%s"', state_name)

    def _save_to_manager(self, manager, step, save_optimizer_states,
                         async_save, extra=None):
        arrays = self._checkpoint_arrays()
        opt_state = None
        if save_optimizer_states:
            assert self.optimizer_initialized
            opt_state = self._optimizer_state_bytes()
        from ..checkpoint import params_digest
        merged = {"epoch": int(step), "symbol": self._symbol.tojson(),
                  # the entry's precision provenance: restores adopt the
                  # mode, serving refuses a mismatch (docs/api/precision.md)
                  "precision_mode": self.precision_mode,
                  # structural identity (symbol + param shapes/dtypes):
                  # the serving executable cache keys AOT entries by
                  # this same digest, so an operator can match a cache
                  # directory to a checkpoint without loading either
                  "params_digest": params_digest(self._symbol.tojson(),
                                                 arrays)}
        if self._precision is not None:
            merged["precision"] = self._precision.describe()
        if extra:
            merged.update(extra)
        manager.save(step, arrays, optimizer_state=opt_state, extra=merged,
                     async_save=async_save)
        self.logger.info('Staged checkpoint step %d into "%s"%s', step,
                         manager.directory,
                         " (async)" if async_save else "")
        return step

    def _checkpoint_arrays(self):
        """Packed ``arg:``/``aux:`` name -> checkpointable array for the
        manager path. The fused mesh group hands over its device-resident
        (possibly sharded) buffers directly — the manager snapshots one
        host copy per unique local shard, never a full gather; classic
        groups go through the host mirrors."""
        from ..checkpoint import pack_params
        assert self.binded and self.params_initialized
        grp = self._exec_group
        if getattr(grp, "fused", False):
            return pack_params(grp._param_dict, grp._aux_dict)
        return pack_params(*self.get_params())

    def _optimizer_state_bytes(self):
        if self._update_on_kvstore:
            assert self._kvstore._updater is not None, \
                "Cannot snapshot states for distributed training"
            return self._kvstore._updater.get_states()
        return self._updater.get_states()

    # ------------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outputs = self._exec_group.get_outputs()
        return list(zip(self._output_names, [o.shape for o in outputs]))

    # ------------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """Allocate + initialize parameters (module.py:227)."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(arr[0].shape, dtype=arr[0].dtype)
                for name, arr in zip(self._param_names,
                                     self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(arr[0].shape, dtype=arr[0].dtype)
                for name, arr in zip(self._aux_names,
                                     self._exec_group.aux_arrays)}

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(name, arr)
            else:
                initializer(name, arr)

        attrs = self._symbol.attr_dict()
        for name, arr in sorted(self._arg_params.items()):
            desc = InitDesc(name, attrs.get(name, None))
            _impl(desc, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            desc = InitDesc(name, attrs.get(name, None))
            _impl(desc, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind executors (module.py:323-415)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self._warn_once("rebind", "Already binded, ignoring bind()")
            return

        if for_training and self._precision is not None and \
                self._precision.serving_only():
            # quantized weight storage / native narrow GEMMs have no
            # gradient story — they exist for inference programs only
            raise ValueError(
                "precision=%r is a serving-only mode (weight_quant/"
                "narrow_math); bind with for_training=False or train "
                "under a training mode and quantize post-training"
                % self._precision.name)
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [x if isinstance(x, tuple) else tuple(x)
                             for x in data_shapes]
        self._data_shapes = [(x[0], tuple(x[1])) for x in data_shapes]
        if label_shapes is not None and len(label_shapes) > 0:
            self._label_shapes = [(x[0], tuple(x[1])) for x in label_shapes]
        else:
            self._label_shapes = None

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        shared_is_fused = shared_group is not None and \
            getattr(shared_group, "fused", False)
        self._shared_from_fused = shared_is_fused
        if self._fused_eligible(shared_group, inputs_need_grad, grad_req):
            self._exec_group = MeshExecutorGroup(
                self._symbol, self._context, self._work_load_list,
                self._data_shapes, self._label_shapes, self._param_names,
                for_training, inputs_need_grad, shared_group, self.logger,
                self._fixed_param_names, grad_req,
                compute_dtype=self._compute_dtype, remat=self._remat,
                mesh_axes=self._mesh_axes,
                param_sharding=self._param_sharding,
                pipeline_microbatches=self._pipeline_microbatches,
                device_augment=self._device_augment,
                precision=self._precision)
        elif self._precision is not None and \
                not self._precision.is_default():
            # precision modes exist only on the one-program mesh path
            # (opt-state dtype + act casts + loss scaler all live in the
            # fused step program); a silent classic fallback would train
            # a plain f32 model under a mode name that promises otherwise
            raise ValueError(
                "precision=%r requires the fused mesh path, but this "
                "bind is not fused-eligible (check MXNET_MODULE_FUSED, "
                "batch divisibility by the dp axis, grad_req='write', "
                "uniform work_load_list, distinct same-platform devices)"
                % self._precision.name)
        elif self._device_augment:
            # the u8 wire layout + in-program augment stage exist only
            # in the one-program mesh path; a silent classic fallback
            # would hand the symbol uint8 NHWC blocks it cannot consume
            raise ValueError(
                "device_augment requires the fused mesh path, but this "
                "bind is not fused-eligible (check MXNET_MODULE_FUSED, "
                "batch divisibility by the dp axis, grad_req='write', "
                "uniform work_load_list, distinct same-platform "
                "devices)")
        elif shared_is_fused:
            raise ValueError(
                "shared_module uses the fused mesh group but this bind is "
                "not fused-eligible; bind the shared module with "
                "MXNET_MODULE_FUSED=0 to share classic executors")
        elif self._mesh_axes is not None or self._param_sharding or \
                self._pipeline_microbatches:
            # sharded model parallelism exists only as the one-program mesh
            # path; a silent fallback would train an unsharded model
            raise ValueError(
                "mesh_axes/param_sharding/pipeline_microbatches require "
                "the fused mesh path, but this bind is not fused-eligible "
                "(check MXNET_MODULE_FUSED, batch divisibility by the dp "
                "axis, grad_req='write', uniform work_load_list, distinct "
                "same-platform devices)")
        else:
            if self._remat is not None:
                self.logger.warning(
                    "remat=%r is only supported on the fused mesh path; "
                    "this bind fell back to per-executor groups and will "
                    "NOT rematerialize", self._remat)
            self._exec_group = DataParallelExecutorGroup(
                self._symbol, self._context, self._work_load_list,
                self._data_shapes, self._label_shapes, self._param_names,
                for_training, inputs_need_grad, shared_group, self.logger,
                self._fixed_param_names, grad_req)
        self._total_exec_bytes = 0

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)

    @property
    def precision_mode(self):
        """Recorded precision-mode name ('f32' when no policy) — THE
        spelling checkpoint manifests carry and serving compares."""
        from ..precision.policy import mode_name
        return mode_name(self._precision)

    @property
    def _opt_state_dtype(self):
        return None if self._precision is None \
            else self._precision.opt_state_dtype

    def _fused_eligible(self, shared_group, inputs_need_grad, grad_req):
        """Use the mesh-fused group when the bind maps onto one device mesh
        and nothing requires per-executor machinery."""
        import os
        if not self._allow_fused or \
                os.environ.get("MXNET_MODULE_FUSED", "1") == "0":
            return False
        if shared_group is not None and \
                not getattr(shared_group, "fused", False):
            return False
        if inputs_need_grad:
            return False
        if grad_req != "write":
            return False
        # the batch shards over the 'dp' axis only (model axes replicate
        # or slice params, not the batch)
        dp_size = (self._mesh_axes or {}).get("dp", len(self._context))
        if self._data_shapes[0][1][0] % dp_size:
            return False
        # the fused mesh shards the batch evenly; a deliberate non-uniform
        # workload split needs the classic sliced group
        if len(set(self._work_load_list)) != 1:
            return False
        try:
            devs = [c.jax_device() for c in self._context]
        except ValueError as e:
            # a context JAX has no device for: say so, because the
            # classic group this bind falls to hides which device ran
            self.logger.warning(
                "bind is not fused-eligible: %s", e)
            return False
        return (len(set(devs)) == len(devs)
                and len({d.platform for d in devs}) == 1)

    @property
    def _num_update_blocks(self):
        """Per-param device-block count seen by the optimizer machinery:
        the fused group exposes ONE replicated block regardless of mesh
        size; the classic group one block per context."""
        return 1 if getattr(self._exec_group, "fused", False) \
            else len(self._context)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._eval_pad_extra = 0

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind with new batch shapes, keeping parameters (module.py)."""
        assert self.binded
        self._data_shapes = [(x[0], tuple(x[1])) for x in data_shapes]
        if label_shapes is not None:
            self._label_shapes = [(x[0], tuple(x[1])) for x in label_shapes]
        else:
            self._label_shapes = None
        if getattr(self._exec_group, "fused", False) and \
                self._data_shapes[0][1][0] % \
                (self._mesh_axes or {}).get("dp", len(self._context)):
            # new batch doesn't divide the mesh: fall back to the classic
            # sliced group, keeping parameters
            self._fallback_to_classic("reshape to a batch size that does "
                                      "not divide the device mesh")
            # _fallback_to_classic already re-set the parameters
        else:
            self._exec_group.bind_exec(self._data_shapes, self._label_shapes,
                                       reshape=True)
            if self.params_initialized:
                self._exec_group.set_params(self._arg_params,
                                            self._aux_params)

    def _fallback_to_classic(self, reason):
        """Swap the fused mesh group for the classic per-executor group,
        keeping parameters and re-wiring the optimizer for per-device
        update blocks."""
        from ..base import MXNetError
        if getattr(self._exec_group, "_shared_out", False) or \
                getattr(self, "_shared_from_fused", False):
            raise MXNetError(
                "cannot fall back from the fused mesh group (%s) while "
                "parameters are shared with another module; bind all "
                "modules with MXNET_MODULE_FUSED=0 instead" % reason)
        if self._mesh_axes is not None or self._param_sharding or \
                self._pipeline_microbatches or self._device_augment:
            raise MXNetError(
                "cannot fall back from the fused mesh group (%s): "
                "mesh_axes/param_sharding/pipeline_microbatches/"
                "device_augment have no classic-path equivalent"
                % reason)
        if self._precision is not None and not self._precision.is_default():
            raise MXNetError(
                "cannot fall back from the fused mesh group (%s): "
                "precision=%r has no classic-path equivalent"
                % (reason, self._precision.name))
        if self._params_dirty:
            self._sync_params_from_devices()
        if self._compute_dtype is not None:
            self.logger.warning(
                "%s: falling back to per-executor groups; compute_dtype=%s "
                "only applies on the fused path, execution continues in "
                "float32", reason, self._compute_dtype)
        if self._remat is not None:
            self.logger.warning(
                "%s: falling back to per-executor groups; remat=%r only "
                "applies on the fused path", reason, self._remat)
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            self.for_training, self.inputs_need_grad, None, self.logger,
            self._fixed_param_names, "write")
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)
        if self.optimizer_initialized:
            # per-param update keys change from 1 block to N; re-wire the
            # optimizer (momentum state restarts) and fix idx2name so
            # lr_mult/wd_mult attribute lookups keep resolving
            self.logger.warning(
                "%s: optimizer re-initialized for per-executor update "
                "blocks; optimizer state was reset", reason)
            self.optimizer_initialized = False
            self.init_optimizer(self._kvstore_arg, self._optimizer,
                                force_init=True)
            # re-key idx2name from the FINAL update placement decision
            # (init_optimizer may flip update_on_kvstore now that the
            # block count changed): kvstore updates use plain param
            # indices, local updates stripe index*n_blocks+block
            if self._optimizer is not None:
                if self._update_on_kvstore:
                    idx2name = dict(enumerate(self._param_names))
                else:
                    n_blocks = self._num_update_blocks
                    idx2name = {
                        i * n_blocks + k: n
                        for i, n in enumerate(self._param_names)
                        for k in range(n_blocks)}
                self._optimizer.idx2name = idx2name

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Create kvstore + optimizer (module.py:432-502)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self._warn_once("reinit_optimizer",
                            "optimizer already initialized, ignoring...")
            return
        self._kvstore_arg = kvstore

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, self._num_update_blocks, self._arg_params)

        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group.param_names))
            else:
                n_blocks = self._num_update_blocks
                for k in range(n_blocks):
                    idx2name.update(
                        {i * n_blocks + k: n for i, n in
                         enumerate(self._exec_group.param_names)})
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            if "state_dtype" not in optimizer_params and \
                    self._opt_state_dtype is not None:
                # the precision policy's optimizer-state storage dtype
                # (bf16 moments, f32 master params + f32 update math)
                optimizer_params["state_dtype"] = self._opt_state_dtype
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            want = self._opt_state_dtype
            have = getattr(optimizer, "state_dtype", None)
            if want is not None and have is None:
                optimizer.state_dtype = want
            elif want is not None and have != want:
                from ..base import MXNetError
                raise MXNetError(
                    "optimizer instance carries state_dtype=%r but the "
                    "module's precision mode %r wants %r — drop one of "
                    "the two settings" % (have, self.precision_mode, want))

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
            if getattr(self._exec_group, "fused", False) and not kvstore:
                # one-program train step: backward defers so update() can
                # run fwd+bwd+optimizer as a single XLA launch
                # (mesh_executor_group.step_update)
                self._exec_group._step_enabled = True

        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore_arg = shared_module._kvstore_arg
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        if getattr(self._exec_group, "fused", False) and \
                not self._update_on_kvstore and self._kvstore is None:
            # keep the one-program train step across bucket switches
            # (BucketingModule borrows the master bucket's optimizer)
            self._exec_group._step_enabled = True
        self.optimizer_initialized = True

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._eval_pad_extra = 0
        train = self.for_training if is_train is None else bool(is_train)
        if not train and getattr(self._exec_group, "fused", False):
            data_batch = self._pad_eval_tail(data_batch)
        self._exec_group.forward(data_batch, is_train)

    def _pad_eval_tail(self, batch):
        """An eval batch with fewer rows than the bound batch size runs
        padded to the bound shape through the SAME compiled program,
        instead of tracing+compiling a second XLA program for the
        remainder shape (the epoch-tail recompile; same pad-and-slice
        trick as the serving bucketer — shared ``pad_batch_rows``
        helper).  Rows are independent in an ``is_train=False``
        forward, so the real rows are bit-identical either way; the
        extra rows are sliced off in ``_unpadded_outputs`` /
        ``update_metric`` via ``_eval_pad_extra``.  Raw-loop callers
        that read outputs should slice ``[:n]`` themselves (the
        existing contract for padded batches)."""
        from .base_module import pad_batch_rows
        from ..io import DataBatch
        target = self._exec_group.batch_size
        rows = batch.data[0].shape[0] if batch.data else 0
        if rows == 0 or rows >= target:
            return batch
        # only the batch dim may shrink: any other mismatch is a true
        # reshape and keeps the existing behavior
        for (_name, shape), arr in zip(self._data_shapes, batch.data):
            if tuple(arr.shape[1:]) != tuple(shape[1:]):
                return batch
        data = [nd.NDArray(pad_batch_rows(d, target)) for d in batch.data]
        label = None
        if batch.label:
            label = [None if lb is None else
                     nd.NDArray(pad_batch_rows(lb, target))
                     for lb in batch.label]
        self._eval_pad_extra = target - rows
        return DataBatch(data=data, label=label, pad=batch.pad,
                         index=batch.index)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply the optimizer (module.py update; dispatch logic
        model.py:88-116)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._update_on_kvstore:
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      self._exec_group.grad_arrays,
                                      self._kvstore)
        else:
            fused = getattr(self._exec_group, "fused", False)
            if fused and self._kvstore is None and \
                    self._exec_group.step_update(
                        self._updater,
                        num_device=self._num_update_blocks):
                return  # ran fwd+bwd+optimizer as one XLA program
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=self._num_update_blocks,
                           kvstore=self._kvstore,
                           donate=fused and
                           self._exec_group._platform != "cpu")

    def grouped_train_engaged(self):
        """True when a grouped (``fit(batch_group=K)``) train program
        has actually compiled and run on this module — the supported
        engagement probe for benches and CI gates, so they need not
        reach into the executor group's jit-cache key format."""
        grp = self._exec_group
        return any(isinstance(k, str) and
                   k.startswith("train_step_grouped")
                   for k in (getattr(grp, "_jits", None) or {}))

    def _fit_grouped_ready(self, eval_metric):
        """fit(batch_group=K) needs the whole group to run device-side:
        the one-program train step (fused group + fusable optimizer,
        local updates) and the metric riding the device tally — there
        are no per-batch host outputs inside a scanned group to update
        a host metric from."""
        grp = self._exec_group
        if not getattr(grp, "fused", False) or \
                not getattr(grp, "_step_enabled", False):
            return False
        if self._updater is None or \
                self._updater.fused_apply_or_none() is None:
            return False
        return grp._metric_live is eval_metric

    def _grouped_stage(self, batches):
        """Assemble K iterator batches into one stacked block per input
        and stage it on the mesh (``MeshExecutorGroup.stage_stacked``).
        Host batches stack into one contiguous block (ONE
        ``device_put`` per input); device-resident batches stack on
        device — neither path pays a readback."""
        grp = self._exec_group
        if not getattr(grp, "fused", False):
            return None
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        stacked = self._staged_group_block(batches)
        if stacked is None:
            stacked = stack_group_inputs(
                batches, [d[0] for d in grp.data_shapes],
                getattr(grp, "_label_names", []), grp._batch_sharding)
        return grp.stage_stacked(stacked)

    def _grouped_update(self, staged):
        """Run the staged block's K steps as ONE scanned train-step
        program (the iterations-per-loop pattern; see
        ``MeshExecutorGroup.step_update_grouped``)."""
        # grouped steps bypass forward(); a stale eval-tail pad marker
        # would make update_metric slice-and-host-update instead of
        # consuming the device tally's step-done flag
        self._eval_pad_extra = 0
        if not self._exec_group.step_update_grouped(
                self._updater, staged, num_device=self._num_update_blocks):
            return False
        self._params_dirty = True
        return True

    @staticmethod
    def _staged_group_block(batches):
        """If every batch in the group is a view onto ONE DeviceLoader-
        staged ``(K, B, ...)`` block covering exactly this group (in
        order), return that block's already-staged input dict — the
        scanned program consumes it directly (``stage_stacked``'s
        ``device_put`` no-ops on resident arrays), skipping the
        re-stack a generic group would pay.  Any mismatch (manual
        loader with a different K, mixed sources) returns None and the
        generic on-device stacking path handles it."""
        block = getattr(batches[0], "_staged_block", None)
        if block is None or \
                getattr(batches[0], "_staged_size", -1) != len(batches):
            return None
        for j, b in enumerate(batches):
            if getattr(b, "_staged_block", None) is not block or \
                    getattr(b, "_staged_index", -1) != j:
                return None
        return block

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        extra = getattr(self, "_eval_pad_extra", 0)
        if extra:
            # tail-padded eval forward (_pad_eval_tail): the metric must
            # see only the real rows — the padded rows are zeros, not
            # data.  ``labels`` from the score loop are the ORIGINAL
            # (unpadded) arrays; slice only when a caller passed padded
            # ones.
            keep = self._exec_group.batch_size - extra
            outs = [o[0:keep] for o in self.get_outputs()]
            labels = [lb if lb is None or lb.shape[0] <= keep
                      else lb[0:keep] for lb in (labels or [])]
            eval_metric.update(labels, outs)
            return
        self._exec_group.update_metric(eval_metric, labels)

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate; on the fused mesh path with a decomposable metric the
        tally rides the device (one launch per batch, ONE readback —
        the host loop's per-batch ``asnumpy`` blocks on the device every
        batch). Per-batch callbacks need the running host value, so
        their presence keeps the reference loop."""
        import os
        grp = self._exec_group
        if batch_end_callback is None and getattr(grp, "fused", False) \
                and os.environ.get("MXNET_DEVICE_METRIC", "1") != "0":
            assert self.binded and self.params_initialized
            from .. import metric as metric_mod
            eval_metric = metric_mod.create(eval_metric)
            if reset:
                eval_data.reset()
            from .. import telemetry
            with telemetry.span("score.device", epoch=epoch) as s_score:
                result = grp.score_device(eval_data, eval_metric,
                                          num_batch)
            if result is not None:
                pairs, seen = result
                if telemetry.enabled() and seen:
                    # one eval record for the whole device-tallied pass
                    # (batch_group = batches covered, mirroring the
                    # grouped train records) so eval regressions reach
                    # the health watchdog on this path too
                    rec = telemetry.timeline().record(
                        epoch, seen - 1,
                        dispatch_ms=s_score.ns * 1e-6,
                        batch_group=seen, loop="eval")
                    telemetry.log_event("eval_step", rec)
                self._fire(score_end_callback, epoch, seen, eval_metric,
                           locals())
                return pairs
            reset = False  # already rewound; device path declined
        return super().score(eval_data, eval_metric, num_batch=num_batch,
                             batch_end_callback=batch_end_callback,
                             score_end_callback=score_end_callback,
                             reset=reset, epoch=epoch)

    def _install_device_metric(self, eval_metric):
        import os
        grp = self._exec_group
        if not getattr(grp, "fused", False):
            return
        if os.environ.get("MXNET_DEVICE_METRIC", "1") == "0":
            grp.disable_device_metric()
            return
        grp.enable_device_metric(eval_metric)

    def _read_op_counters(self):
        grp = self._exec_group
        return grp.read_op_counters() if getattr(grp, "fused", False) \
            else {}

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def _epoch_end_params(self):
        if getattr(self._exec_group, "fused", False):
            # one packed readback; no re-upload — the mesh params ARE the
            # training state, set_params would just round-trip them
            return self.get_params()
        return super()._epoch_end_params()

    def _epoch_end_sync(self, need_params):
        if getattr(self._exec_group, "fused", False):
            # device params are the single authority: host mirrors stay
            # lazy (get_params materializes on demand) unless a callback
            # needs them NOW — saves a packed readback of every
            # parameter per epoch
            self._params_dirty = True
            return self._epoch_end_params() if need_params else None
        return super()._epoch_end_sync(need_params)

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """Restore optimizer states from a ``.states`` file or, on the
        manager checkpoint path, from the raw state bytes directly."""
        assert self.optimizer_initialized
        if isinstance(fname, (bytes, bytearray)):
            states = bytes(fname)
            if self._update_on_kvstore:
                self._kvstore._updater.set_states(states)
            else:
                self._updater.set_states(states)
            return
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        """Install a Monitor; the fused mesh group has no per-op boundaries
        (the whole step is one XLA program), so re-bind onto the classic
        per-executor group where the tapped interpreter runs."""
        assert self.binded
        if getattr(self._exec_group, "fused", False):
            self._fallback_to_classic("install_monitor needs per-op taps")
        self._exec_group.install_monitor(mon)
