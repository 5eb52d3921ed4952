"""mxnet_tpu.data — the async device-feed pipeline.

The round-5 fed benchmark found the end-to-end fed rate at a few
percent of the device step's rate: the HOST input path — decode, batch
assembly, and above all the host->device transfer — sat on the step's
critical path (measured before PR 1; not measured on the current chip
path).  The reference hides decode behind
``dmlc::ThreadedIter`` double buffering (``PrefetcherIter``,
iter_prefetcher.h:129; our ``io.PrefetchingIter`` reproduces it as a
host thread), but a TPU-native stack has a third stage to hide: the
transfer itself.  This package overlaps all three:

* :class:`TransformIter` — N ordered decode/augment workers over any
  ``DataIter`` with deterministic per-batch seeding and in-order
  reassembly.  Worker count is a pure throughput knob: the delivered
  batch stream is bitwise identical at 1/2/4 workers.
* :class:`DeviceLoader` — a bounded ring (depth 2-3) of batches
  ALREADY resident on device: a background stager dispatches
  mesh-aware ``jax.device_put`` (per-device shards placed directly,
  no host concat; ``(K, B, ...)`` blocks through the executor group's
  ``stage_stacked`` for ``fit(batch_group=K)``) for batch i+1/i+2
  while the step for batch i runs.
* :class:`PipelineStats` — host-wait ms per step, ring occupancy,
  staged bytes/dtype, and stager throughput, so "input-bound" is a
  measured number in the training log, not a guess.
* :class:`DeviceAugment` / :class:`DeviceAugmentIter` — the u8 wire
  path: uint8 NHWC batches (4x fewer transported bytes than f32
  NCHW) with random crop/flip/normalize compiled as a DEVICE program
  at staging, draws keyed ``(seed, epoch, batch)`` — bitwise
  host-reference parity, replayable across resume.
* :class:`CachedDataset` — the HBM-resident dataset cache: epoch 1
  streams + captures the decoded u8 epoch, epochs >= 2 are served by
  device-side gather (a ``(B,)`` index array is the whole per-batch
  transfer), bit-identical to streaming and budget-gated with a
  graceful host fallback.
* :class:`ShardedCachedDataset` — the pod-sharded spelling: each host
  captures only its ``shard_rows`` block, the cache is one global
  ``P('dp')``-sharded pytree (N x the dataset budget per pod, zero
  duplicated bytes), spill tiers (HBM -> pinned host -> recordio
  re-decode) resolve per shard under one budget knob, and the
  per-epoch global shuffle is a pure function of ``(seed, epoch)``
  (:func:`global_shuffle_order`) — dp-width-stable across elastic
  resume.

Batches delivered through the pipeline are BITWISE identical to plain
iteration, so ``Module.fit(prefetch_to_device=2)`` trains to
bit-equal parameters (pinned by tests/test_data_pipeline.py and the
ci.sh gate).

Quick start::

    from mxnet_tpu.data import DeviceLoader, TransformIter

    it = TransformIter(host_iter, transform=augment, num_workers=4)
    mod.fit(it, num_epoch=..., prefetch_to_device=2)   # or, manually:
    with DeviceLoader(it, module=mod, depth=2) as loader:
        for batch in loader:
            ...
    print(loader.pipeline_stats.snapshot())

See docs/api/data.md for semantics and the stats field reference.
"""
from __future__ import annotations

from .augment import DeviceAugment, DeviceAugmentIter, fold_seed
from .cached import CachedDataset, global_shuffle_order
from .loader import DeviceLoader
from .sharded_cache import ShardedCachedDataset, cache_row_of_pos
from .stats import PipelineStats
from .transform import TransformIter

__all__ = ["DeviceLoader", "TransformIter", "PipelineStats",
           "DeviceAugment", "DeviceAugmentIter", "CachedDataset",
           "ShardedCachedDataset", "global_shuffle_order",
           "cache_row_of_pos", "fold_seed"]
