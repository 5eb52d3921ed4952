"""Multi-host staging — host rows to global mesh-sharded arrays.

THE batch-staging rule of the whole stack
(``MeshExecutorGroup._stage`` / ``stage_stacked``, the DeviceLoader's
stager and the grouped stacker route every input through
:func:`stage_sharded` / :func:`host_view`):

* an array on ANOTHER backend than the sharding's devices (a batch an
  iterator made with ``nd.array`` under the default context on a TPU
  machine lives on jax's CPU backend) is first replaced by its host
  view (:func:`host_view`: ``onp.asarray``, no copy) and then staged as
  the numpy value it now is. "Device-resident" means *on the mesh's
  backend*: a ``device_put`` of the array itself would copy between two
  PJRT clients, out of one and then into the other, inside the
  dispatch (397 ms for 154 MB against one direct put, PERF.md PR 26);
* single process — ``jax.device_put(value, sharding)``, the path every
  existing program compiled against (arrays already on the mesh's
  backend pass through untouched, which is how the DeviceLoader ring
  and the virtual-host feed keep fit's own staging a no-op);
* multi process — each process holds only its LOCAL slice of the
  global batch (a :class:`~mxnet_tpu.dist.ShardedDataIter` shard), and
  the global array is assembled with
  ``jax.make_array_from_process_local_data`` — the GSPMD pattern from
  SNIPPETS.md: the program is written against the global shape, each
  process contributes the shards it can address, no host ever
  materializes the whole batch. A process that was handed the FULL
  global value (replicated synthetic source) has its local block cut
  out first, so both feeding styles land on the same assembly call.

:func:`assemble_host_slices` is the single-process twin used by the
virtual-host harness (:class:`~mxnet_tpu.dist.VirtualCluster`): given
every simulated host's slice, it places each DEVICE's piece straight
from its host's slice and assembles the global array with
``jax.make_array_from_single_device_arrays`` — the same
shards-to-global assembly the multi-process path performs, minus the
processes. No host-side concat happens on either path.
"""
from __future__ import annotations

__all__ = ["stage_sharded", "stage_zeros", "assemble_host_slices",
           "local_block", "host_view"]


def local_block(sharding, global_shape):
    """This process's contiguous block (a tuple of per-dim slices) of a
    sharded global array — what a replicated global value must be cut
    to before ``make_array_from_process_local_data``. Computed from the
    sharding's addressable shard indices, so it is correct for any
    process->device order the mesh encodes and for blocks on any axis
    (per-batch rows on axis 0, grouped ``(K, B, ...)`` blocks on
    axis 1).

    Raises when the addressable shards do NOT tile one contiguous
    block (a mesh whose sharded-axis device order interleaves
    processes): the covering range would silently include rows owned
    by other processes — the same not-host-major condition
    :func:`assemble_host_slices` rejects."""
    global_shape = tuple(global_shape)
    amap = sharding.addressable_devices_indices_map(global_shape)
    bounds = []
    boxes = set()
    for idx in amap.values():
        box = []
        for d, extent in enumerate(global_shape):
            s0, s1, _ = idx[d].indices(extent)
            box.append((s0, s1))
        boxes.add(tuple(box))
    for d in range(len(global_shape)):
        bounds.append((min(b[d][0] for b in boxes),
                       max(b[d][1] for b in boxes)))
    # distinct shard boxes are disjoint (one owner per element of a
    # sharded axis; a replicated sharding is ONE distinct box), so the
    # block is contiguous iff their volumes sum to the covering volume
    covered = sum(_vol(b) for b in boxes)
    total = _vol(bounds)
    if covered != total:
        raise ValueError(
            "this process's shards cover %d elements but their bounding "
            "block holds %d — the mesh's sharded-axis device order is "
            "not process-contiguous (not host-major), so a local block "
            "cannot be cut" % (covered, total))
    return tuple(slice(a, b) for a, b in bounds)


def _vol(box):
    v = 1
    for a, b in box:
        v *= max(0, b - a)
    return v


def _array_platform(arr):
    """The backend a fully addressable ``jax.Array`` lives on."""
    return next(iter(arr.devices())).platform


def host_view(val, sharding):
    """``val`` as staging onto ``sharding`` should take it: a fully
    addressable ``jax.Array`` on another backend than the sharding's
    devices comes back as its host view (``onp.asarray``: on the CPU
    backend a read-only, zero-copy view of the client's buffer, which
    keeps the array alive), counted in ``exec.stage_host_routed_bytes``;
    anything else (a numpy value, an array on the sharding's own
    backend, a global array this process addresses only in part) comes
    back as the object it is. THE one backend comparison: every staging
    and stacking site decides "on the device already" here."""
    import jax
    if not isinstance(val, jax.Array) or not val.is_fully_addressable or \
            _array_platform(val) == next(iter(sharding.device_set)).platform:
        return val
    import numpy as onp
    from .. import telemetry
    host = onp.asarray(val)
    telemetry.count("exec.stage_host_routed_bytes", host.nbytes)
    return host


def stage_sharded(value, sharding, global_shape=None):
    """Place ``value`` (NDArray / numpy / jax array) onto ``sharding``.

    ``global_shape`` is the GLOBAL shape of the array being staged;
    None means ``value`` already has it. See module docstring for the
    off-backend rule and the single- vs multi-process behavior. Batch
    axes may differ from the global shape only in multi-process mode
    (the local-slice case) — single-process callers staging odd shapes
    (eval tails, bucketing) keep plain ``device_put`` semantics.
    """
    import jax
    val = value._read() if hasattr(value, "_read") else value
    val = host_view(val, sharding)
    if jax.process_count() == 1:
        return jax.device_put(val, sharding)
    gshape = tuple(global_shape) if global_shape is not None \
        else tuple(val.shape)
    if isinstance(val, jax.Array) and tuple(val.shape) == gshape and \
            not val.is_fully_addressable:
        return val  # already a staged global array
    if tuple(val.shape) == gshape:
        # replicated global value on every process: cut our block so
        # the assembly below sees exactly this process's shard. A fully
        # replicated sharding keeps the whole value (block == extent).
        block = local_block(sharding, gshape)
        if any(sl.indices(n) != (0, n, 1)
               for sl, n in zip(block, gshape)):
            val = val[block]
    return jax.make_array_from_process_local_data(sharding, val, gshape)


def stage_zeros(global_shape, sharding, dtype=None):
    """A zero-filled global array on ``sharding`` that only ever
    allocates this process's LOCAL block host-side — the buffer-creation
    twin of :func:`stage_sharded` (a full ``onp.zeros(global_shape)``
    per process would materialize the whole model on every host, the
    exact cost the local-shards assembly exists to avoid)."""
    import jax
    import numpy as onp
    dtype = onp.float32 if dtype is None else dtype
    global_shape = tuple(global_shape)
    if jax.process_count() == 1:
        return jax.device_put(onp.zeros(global_shape, dtype), sharding)
    block = local_block(sharding, global_shape)
    local = onp.zeros([sl.stop - sl.start for sl in block], dtype)
    return jax.make_array_from_process_local_data(sharding, local,
                                                  global_shape)


def assemble_host_slices(sharding, global_shape, host_slices,
                         host_of_device):
    """Assemble a global array from per-virtual-host row slices.

    ``host_slices`` maps host rank -> that host's contiguous row block
    (host order = row order, the ShardedDataIter rule);
    ``host_of_device`` maps a jax device -> its host rank. Each
    device's piece is sliced from ITS host's block and placed with one
    per-device ``device_put`` — the per-process placement of the real
    multi-host path, driven from one process.
    """
    import jax
    global_shape = tuple(global_shape)
    n_hosts = len(host_slices)
    assert global_shape[0] % n_hosts == 0, \
        "global rows %d not divisible by %d hosts" % (global_shape[0],
                                                      n_hosts)
    rows_per_host = global_shape[0] // n_hosts
    pieces = []
    for dev, idx in sharding.addressable_devices_indices_map(
            global_shape).items():
        r0, r1, _ = idx[0].indices(global_shape[0])
        host = host_of_device[dev]
        if r1 - 1 >= (host + 1) * rows_per_host or r0 < host * rows_per_host:
            raise ValueError(
                "device %s shard rows [%d,%d) cross its host %d block — "
                "the mesh is not host-major over the batch axis"
                % (dev, r0, r1, host))
        block = host_slices[host]
        local = block[r0 - host * rows_per_host:r1 - host * rows_per_host]
        rest = tuple(sl for sl in idx[1:])
        if rest:
            local = local[(slice(None),) + rest]
        pieces.append(jax.device_put(local, dev))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, pieces)
