"""Multi-host (process-spanning mesh) support for the fused path.

Reference: the multi-machine training loop rides kvstore ``dist_sync`` —
each worker pushes per-key gradients to parameter servers, which
aggregate exactly ``num_workers`` pushes before workers pull
(``src/kvstore/kvstore_dist.h:192-238``,
``kvstore_dist_server.h:164-199``).  TPU-native design (SURVEY §5.8):
there are no servers and no per-key pushes — ``ShardedTrainer``'s single
jitted step runs as the SAME XLA program on every process over a
process-spanning ``jax.sharding.Mesh``, and GSPMD places the gradient
psum on the cross-process fabric (ICI within a slice, DCN across
slices) wherever the ``data`` axis spans hosts.  The multi-controller
model keeps the hot loop identical to single-host; these helpers cover
the seams jit does not:

* joining the runtime (``ensure_initialized`` — the reference's
  ``InitPSEnv`` from DMLC_* env, ``include/mxnet/kvstore.h:162``);
* staging per-process host shards into global arrays
  (``stage_local`` — the role of the worker-side send slicing,
  ``kvstore_dist.h:273-314``);
* gathering process-sharded state back to every host for rank-0
  checkpoint writes (``gather_to_host``).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError

__all__ = ["ensure_initialized", "spans_processes", "stage_local",
           "scale_local_shape", "gather_to_host", "process_barrier",
           "world_size"]


def world_size():
    """Process count of the running job (1 single-process).

    Elastic contract (docs/api/reshard.md): this is the CURRENT world —
    after a rank leave/join restart, ``tools/launch.py --elastic``
    relaunches every worker with the new ``MXNET_TPU_NUM_PROCESSES``,
    :func:`ensure_initialized` joins the resized ``jax.distributed``
    job under the same ``MXNET_TPU_INIT_TIMEOUT``/``_RETRIES`` bounds,
    and checkpoint loaders compare this value against the manifest's
    saved world to emit ``rank_join``/``rank_leave`` events."""
    import jax
    try:
        return int(jax.process_count())
    except (RuntimeError, ValueError):
        return 1


def _distributed_initialized():
    """True when this process already joined a jax.distributed job."""
    import jax
    return bool(jax.distributed.is_initialized())


def ensure_initialized():
    """Join the ``jax.distributed`` job described by the MXNET_TPU_*
    env (set by ``tools/launch.py``); no-op for single-process jobs or
    when the runtime is already up.  Must run before the XLA backend is
    touched — the first eagerly-executed primitive binds it, after
    which joining is impossible.

    Resilience: the join is bounded by ``MXNET_TPU_INIT_TIMEOUT``
    seconds (0/unset = the runtime's own timeout); transient connect
    failures are retried with exponential backoff up to
    ``MXNET_TPU_INIT_RETRIES`` times (default 2) — a coordinator that
    is still binding its port when a fast rank arrives no longer kills
    the whole job.  A TIMED-OUT join is terminal (see the retry_call
    below).  The ``multihost.init`` fault seam (resilience.py) fires
    inside the retried attempt."""
    import jax
    from .. import config
    from .. import resilience

    nproc = config.get_int("MXNET_TPU_NUM_PROCESSES")
    need_init = bool(nproc and nproc > 1
                     and not _distributed_initialized())
    coordinator = config.get("MXNET_TPU_COORDINATOR")
    if need_init and not coordinator:
        # a config error never heals — fail fast OUTSIDE the retry (a
        # silent localhost default would make every rank wait on its
        # own unbound port)
        raise MXNetError(
            "MXNET_TPU_NUM_PROCESSES=%d but MXNET_TPU_COORDINATOR is "
            "unset; launch via tools/launch.py or export the "
            "coordinator address" % nproc)
    import inspect
    kwargs = {}
    accepted = inspect.signature(jax.distributed.initialize).parameters
    hb = config.get_int("MXNET_TPU_HEARTBEAT_TIMEOUT")
    if hb and "heartbeat_timeout_seconds" in accepted:
        # failure detection: a dead peer is declared failed after this
        # many seconds without heartbeats (the reference's ps-lite
        # heartbeat role, kvstore_dist.h:159-169); default 100 s.
        # Older jax has no such kwarg — the env is then only consumed
        # by the launch.py watchdog.
        kwargs["heartbeat_timeout_seconds"] = hb
    timeout = config.get_int("MXNET_TPU_INIT_TIMEOUT")
    if timeout and "initialization_timeout" in accepted:
        kwargs["initialization_timeout"] = timeout

    def attempt():
        resilience.fault_point("multihost.init")
        if not need_init or _distributed_initialized():
            return
        resilience.with_timeout(
            lambda: jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=nproc,
                process_id=config.get_int("MXNET_TPU_PROCESS_ID", 0),
                **kwargs),
            timeout or None,
            name="jax.distributed.initialize(%s)" % coordinator)

    # a TIMED-OUT initialize is terminal, not retried: its daemon
    # thread is still inside the coordinator handshake, and a second
    # concurrent initialize from this process could double-register the
    # rank.  Transient pre-connect failures (coordinator still binding
    # its port) are the retryable class.
    resilience.retry_call(
        attempt,
        retries=config.get_int("MXNET_TPU_INIT_RETRIES", "2"),
        exceptions=(resilience.FaultInjected, RuntimeError,
                    ConnectionError, OSError),
        no_retry=(resilience.TimeoutError,),
        base_delay=0.2, max_delay=5.0,
        name="multihost.ensure_initialized")


def spans_processes(mesh):
    """True when the mesh's devices live in more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def stage_local(sharding, local, global_shape=None):
    """Build a global array on a process-spanning mesh from this
    process's host data.

    ``local`` is either the full global value (identical on every
    process — parameters, optimizer slots) or this process's contiguous
    shard of a process-sharded dimension (batches).  ``global_shape``
    defaults to ``local.shape`` (the full-value case)."""
    import jax
    local = np.asarray(local)
    return jax.make_array_from_process_local_data(
        sharding, local, tuple(global_shape or local.shape))


def scale_local_shape(sharding, local_shape):
    """Global shape implied by a per-process local shard under a
    NamedSharding: every dimension sharded over process-spanning mesh
    axes scales by the number of distinct processes along those axes
    (so partial tail batches keep working — the global batch dim follows
    the local one instead of the configured full size)."""
    mesh, spec = sharding.mesh, sharding.spec
    gshape = list(local_shape)
    for d, axes in enumerate(spec):
        if d >= len(gshape) or axes is None:
            continue
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        sub = mesh.devices[tuple(
            slice(None) if name in axes else 0
            for name in mesh.axis_names)]
        gshape[d] *= len({dev.process_index for dev in np.ravel(sub)})
    return tuple(gshape)


def gather_to_host(arr):
    """Numpy copy of a global array, identical on every process.

    Fully-addressable and fully-replicated arrays read out locally;
    process-sharded state (e.g. tensor-parallel weights on a
    process-spanning 'model' axis) is all-gathered — every process must
    call this (it is a collective in that case)."""
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    if arr.is_fully_replicated:
        return np.asarray(arr.addressable_data(0))
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


# first sync_global_devices compiles its collective program; that call's
# wall time must not land in the wait histogram (see attempt() below)
_barrier_state = {"warm": False}


def process_barrier(name="mxnet_tpu_multihost"):
    """Block until every process reaches this point (checkpoint
    write/read ordering across ranks).

    Resilience: with ``MXNET_TPU_BARRIER_TIMEOUT`` set (seconds), the
    sync is bounded: a TIMEOUT is terminal and raises
    :class:`~mxnet_tpu.base.MXNetError` naming the barrier — the
    dead-rank detector for rendezvous points, instead of an unbounded
    hang against a preempted peer.  (A timed-out collective is NOT
    retried: the hung attempt's thread is still parked inside it, and
    re-entering the same barrier from a second thread of this process
    would corrupt the rendezvous.)  Transient pre-collective failures —
    including the ``multihost.barrier`` fault seam — are retried up to
    ``MXNET_TPU_BARRIER_RETRIES`` times (default 1) with backoff.
    0/unset keeps the previous wait-forever behavior."""
    import jax
    from .. import config
    from .. import resilience

    timeout = config.get_int("MXNET_TPU_BARRIER_TIMEOUT") or None

    def attempt():
        resilience.fault_point("multihost.barrier")
        if jax.process_count() > 1:
            import time as _time
            from jax.experimental import multihost_utils
            t0 = _time.perf_counter()
            resilience.with_timeout(
                lambda: multihost_utils.sync_global_devices(name),
                timeout, name="process_barrier(%r)" % name)
            # the barrier IS a collective wait: how long this rank
            # stalled for its slowest peer (straggler attribution,
            # telemetry.distview) — except the process's FIRST barrier,
            # whose duration is dominated by the sync program's XLA
            # compile, not peer wait (same warm-up rule as distview's
            # timestamp barrier)
            if _barrier_state["warm"]:
                from ..telemetry.registry import histogram
                histogram("mxtpu_collective_wait_seconds").observe(
                    _time.perf_counter() - t0)
            else:
                _barrier_state["warm"] = True

    resilience.retry_call(
        attempt,
        retries=config.get_int("MXNET_TPU_BARRIER_RETRIES", "1"),
        exceptions=(resilience.FaultInjected,),
        base_delay=0.1, max_delay=2.0,
        name="process_barrier(%r)" % name)
