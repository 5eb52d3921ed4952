"""Cluster bootstrap — ``jax.distributed.initialize`` from env.

Replaces the ps-lite + dmlc-tracker bring-up (tools/launch.py spawns
workers/servers with ``DMLC_*`` env; kvstore_dist.h connects each to
the scheduler). A job is launched the same way — every process gets
coordinator address + world size + its id — but the variables may come
from either vocabulary:

=======================  ==========================  ==================
meaning                  reference (``DMLC_*``)      JAX coordination
=======================  ==========================  ==================
coordinator host         ``DMLC_PS_ROOT_URI``        ``JAX_COORDINATOR_ADDRESS``
coordinator port         ``DMLC_PS_ROOT_PORT``       (part of the address)
world size               ``DMLC_NUM_WORKER``         ``JAX_NUM_PROCESSES``
process id               ``DMLC_WORKER_ID``          ``JAX_PROCESS_ID``
=======================  ==========================  ==================

so reference launch scripts (``tools/launch.py -n 4 python train.py``)
keep working unchanged.

``initialize()`` adds what a real fleet needs over the bare call:
bounded retry with exponential backoff on coordinator connect (workers
race the coordinator process to the port), a rendezvous barrier with
timeout once the backend is up (so no rank starts compiling against a
half-formed world), and process metadata published into the telemetry
registry (``dist.rank`` / ``dist.world_size`` / device counts,
``dist.bootstrap_ms``).
"""
from __future__ import annotations

import os
import time

__all__ = ["initialize", "init_from_env", "coordination_env"]


def coordination_env(env=None):
    """Resolve the coordination settings from the environment.

    Returns ``{"coordinator_address", "num_processes", "process_id",
    "heartbeat_timeout", "source"}`` where ``source`` names which
    vocabulary supplied them (``"jax"``, ``"dmlc"``, or ``"none"``).
    JAX-native variables win when both are set (they are the more
    specific spelling)."""
    env = os.environ if env is None else env
    if env.get("JAX_COORDINATOR_ADDRESS") or env.get("JAX_NUM_PROCESSES"):
        return {
            "coordinator_address": env.get("JAX_COORDINATOR_ADDRESS"),
            "num_processes": int(env.get("JAX_NUM_PROCESSES", "1")),
            "process_id": int(env.get("JAX_PROCESS_ID", "0")),
            "heartbeat_timeout": int(
                env.get("MXNET_KVSTORE_HEARTBEAT_TIMEOUT", "100")),
            "source": "jax",
        }
    n_worker = int(env.get("DMLC_NUM_WORKER", "1"))
    if n_worker > 1:
        coord = env.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        port = env.get("DMLC_PS_ROOT_PORT", "9091")
        return {
            "coordinator_address": "%s:%s" % (coord, port),
            "num_processes": n_worker,
            "process_id": int(env.get("DMLC_WORKER_ID", "0")),
            "heartbeat_timeout": int(
                env.get("MXNET_KVSTORE_HEARTBEAT_TIMEOUT", "100")),
            "source": "dmlc",
        }
    return {"coordinator_address": None, "num_processes": 1,
            "process_id": 0, "heartbeat_timeout": 100, "source": "none"}


def _connect(kwargs, heartbeat):
    """One jax.distributed.initialize attempt (the seam the connect-
    retry tests replace)."""
    import jax
    jax.distributed.initialize(heartbeat_timeout_seconds=heartbeat,
                               **kwargs)


def initialize(coordinator_address=None, num_processes=None,
               process_id=None, heartbeat_timeout=None,
               connect_retries=None, connect_backoff_s=None,
               barrier_timeout=None):
    """Join (or stand up) the multi-host job and return the runtime.

    Arguments default from the environment (:func:`coordination_env`;
    retry knobs from ``MXNET_DIST_CONNECT_RETRIES`` /
    ``MXNET_DIST_CONNECT_BACKOFF`` / ``MXNET_DIST_BARRIER_TIMEOUT``).
    Single-process (``num_processes`` <= 1) is a cheap no-op that still
    publishes process metadata — safe to call unconditionally, which is
    how ``import mxnet_tpu`` calls it.

    The connect retries with exponential backoff: worker processes race
    the coordinator to its port, and a coordinator restart (elastic
    resume) leaves a window where connects fail. The attempt count and
    backoff are BOUNDED — a job that cannot form its world must die
    loudly, not hang in a connect loop forever.

    ``MXNET_KVSTORE_ELASTIC=1`` flips jax recoverability on (where the
    toolchain has it) so survivors keep running when a peer dies —
    letting :func:`DistRuntime.num_dead_nodes` report the death instead
    of the default die-together policy. Maps the reference's ps-lite
    elastic-training knob.
    """
    from .runtime import DistRuntime, get_runtime
    resolved = coordination_env()
    if coordinator_address is None:
        coordinator_address = resolved["coordinator_address"]
    if num_processes is None:
        num_processes = resolved["num_processes"]
    if process_id is None:
        process_id = resolved["process_id"]
    if heartbeat_timeout is None:
        heartbeat_timeout = resolved["heartbeat_timeout"]
    if connect_retries is None:
        connect_retries = int(os.environ.get(
            "MXNET_DIST_CONNECT_RETRIES", "5"))
    if connect_backoff_s is None:
        connect_backoff_s = float(os.environ.get(
            "MXNET_DIST_CONNECT_BACKOFF", "0.5"))
    if barrier_timeout is None:
        barrier_timeout = float(os.environ.get(
            "MXNET_DIST_BARRIER_TIMEOUT", "300"))

    if num_processes <= 1:
        return get_runtime()

    import jax
    # elastic mode: survivors keep running when a peer dies. Set via
    # jax.config (an env var would be ignored if jax imported first).
    if os.environ.get("MXNET_KVSTORE_ELASTIC", "0") == "1":
        jax.config.update("jax_enable_recoverability", True)

    from jax._src import distributed as _dstate
    # NOTE: probe the coordination client, NOT jax.process_count() — the
    # latter initializes the XLA backend, after which initialize() is
    # rejected
    t0 = time.perf_counter()
    if _dstate.global_state.client is None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=int(num_processes),
                      process_id=int(process_id))

        from .. import faults as _faults

        def attempt():
            if _faults.armed():
                # coordinator connect-flap seam: a transient fault here
                # is exactly a worker racing a restarting coordinator
                _faults.check("dist.connect",
                              address=str(coordinator_address))
            _connect(kwargs, int(heartbeat_timeout))
        import logging
        try:
            # THE shared bounded-backoff idiom (faults.retry) — jitter
            # pinned to 0 so the documented connect schedule
            # (backoff * 2^k) is exact
            _faults.retry(
                attempt, retries=int(connect_retries),
                backoff_s=float(connect_backoff_s),
                max_backoff_s=float("inf"),   # the documented schedule
                jitter=0.0,                   # is uncapped backoff*2^k
                retry_on=(RuntimeError, ConnectionError,
                          _faults.TransientFault),
                site="dist.connect",
                logger=logging.getLogger(__name__))
        except (RuntimeError, ConnectionError,
                _faults.TransientFault) as exc:
            raise RuntimeError(
                "could not join coordinator %s after %d attempts"
                % (coordinator_address, int(connect_retries) + 1)) \
                from exc

    # install as THE process singleton before the rendezvous: its
    # _barrier_n counter owns the coordination-service barrier ids, so
    # a later get_runtime() must hand back this same instance (a fresh
    # one would restart at 0 and reuse consumed ids)
    from .runtime import _install_runtime
    runtime = _install_runtime(DistRuntime())
    # rendezvous: no rank proceeds (and starts compiling the global
    # program) until every rank reached here — bounded, so a peer that
    # died during ITS bootstrap fails the job instead of deadlocking it
    runtime.barrier(timeout=barrier_timeout)
    from .. import telemetry
    telemetry.registry().scope("dist").counter("bootstrap_ms").add(
        (time.perf_counter() - t0) * 1000.0)
    return runtime


def init_from_env():
    """Import-time hook: initialize jax.distributed iff the environment
    declares a multi-process job (launch.py / JAX coordination
    contract). Cheap no-op otherwise — it must not touch jax at all on
    a single-process import."""
    resolved = coordination_env()
    if resolved["num_processes"] <= 1:
        return
    initialize(coordinator_address=resolved["coordinator_address"],
               num_processes=resolved["num_processes"],
               process_id=resolved["process_id"],
               heartbeat_timeout=resolved["heartbeat_timeout"])
