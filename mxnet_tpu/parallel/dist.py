"""Multi-process collectives (replaces ps-lite, reference
`src/kvstore/kvstore_dist.h`).

Workers are `jax.distributed` processes; gradient sync is an allreduce over
all processes' devices instead of push/pull against parameter servers. Roles
(scheduler/server) disappear — every process is a worker, rank =
`jax.process_index()` (reference `KVStore::get_rank`, kvstore.h:326).

Allreduce design (device-side): one device per process forms a global
1-D mesh; each process contributes its local value as one shard of a
global array, and a jitted ``sum`` over the process axis with a replicated
output sharding makes XLA emit the all-reduce over ICI/DCN — no host
staging, no O(P x bytes) gather (the reference's server sharding +
`MXNET_KVSTORE_BIGARRAY_BOUND` splitting, kvstore_dist.h:151-173, solved
the same scaling problem for the PS transport; XLA's collective handles
chunking internally). ``allreduce_nds`` batches MANY keys into ONE
dispatch — the analog of the reference's engine-bulked ZPush round.
"""
from __future__ import annotations

import os

import numpy as np
import jax

from .. import telemetry

__all__ = ["init", "shutdown", "allreduce_nd", "allreduce_nds",
           "broadcast_nd", "barrier", "rank", "size", "start_heartbeat",
           "stop_heartbeat", "num_dead_nodes"]

_initialized = False
_PMESH = None
_AR_JIT = {}
_HB_THREAD = None
_HB_STOP = None
_HB_PREFIX = "mxnet_tpu_hb"


def init(coordinator_address=None, num_processes=None, process_id=None,
         recoverable=None):
    """Initialise multi-process JAX (reference `InitPSEnv`, kvstore.h:254;
    env vars DMLC_* are honored for launcher compatibility).

    recoverable (or MXNET_RECOVERABLE=1): register THIS process as a
    recoverable cluster member — its crash is reported through the
    heartbeat/`get_num_dead_node` protocol instead of the coordination
    service broadcasting a fatal error that aborts every healthy peer
    (the reference's ps-lite likewise keeps workers up when a peer dies
    and surfaces it via the scheduler's heartbeat bookkeeping, van.cc).
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("MX_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("DMLC_NUM_WORKER", "0")) or None
    if process_id is None and "DMLC_WORKER_ID" in os.environ:
        process_id = int(os.environ["DMLC_WORKER_ID"])
    if recoverable is None:
        recoverable = os.environ.get("MXNET_RECOVERABLE", "0") == "1"
    if coordinator_address:
        if process_id is not None:
            # stamp telemetry host id BEFORE the attach: the retry/chaos
            # events fired while connecting must carry the real rank
            # (jax's own process id is not known until the attach lands)
            telemetry.set_host_id(process_id)
        # coordinator attach is the classic transient: workers race the
        # coordinator process coming up, and a preempted coordinator
        # returns timeouts for a while before recovering — retry with
        # bounded backoff instead of dying on the first connect
        from . import retry as _retry
        from .. import chaos

        def _attach():
            chaos.maybe_timeout("dist.init")
            try:
                if recoverable:
                    # the runtime client's `recoverable` flag, through
                    # the config state jax.distributed reads it from
                    jax.config.update("jax_enable_recoverability", True)
                jax.distributed.initialize(coordinator_address,
                                           num_processes, process_id)
            except Exception:
                # a failed connect leaves jax's global state partially
                # initialized (client/service assigned BEFORE connect),
                # and a second initialize would then raise 'should only
                # be called once' — clear it so the retry really retries
                _clear_jax_distributed_state()
                raise

        with telemetry.span("dist.init", coordinator=coordinator_address,
                            process_id=process_id):
            _retry.retry_call(
                _attach, policy=_retry.RetryPolicy.from_env(
                    "MXNET_INIT", max_attempts=4, base_delay=0.5,
                    max_delay=10.0),
                retry_on=_retry.timeout_like,  # config errors fail fast
                describe="jax.distributed.initialize")
        telemetry.counter("dist_init_total",
                          help="successful coordinator attaches").inc()
    _initialized = True
    # liveness protocol on by default for multi-process runs (reference
    # ps-lite heartbeats are always on, van.cc); cheap: one tiny KV write
    # per interval
    if jax.process_count() > 1:
        start_heartbeat(float(os.environ.get(
            "MXNET_HEARTBEAT_INTERVAL", "5")))


def _clear_jax_distributed_state():
    """Best-effort reset of jax's distributed global state so a failed or
    torn-down attach doesn't poison the next ``initialize`` call."""
    try:
        from jax._src import distributed as _jd
        state = _jd.global_state
    except Exception as exc:  # pragma: no cover - internal layout moved
        telemetry.swallowed("dist.clear_state", exc)
        return
    for attr in ("client", "service", "preemption_sync_manager"):
        obj = getattr(state, attr, None)
        if obj is not None:
            try:
                obj.shutdown()
            except Exception as exc:  # half-dead client: clearing wins
                telemetry.swallowed("dist.clear_state.shutdown", exc)
            try:
                setattr(state, attr, None)
            except Exception as exc:  # pragma: no cover
                telemetry.swallowed("dist.clear_state.setattr", exc)


def shutdown():
    """Tear down multi-process state so :func:`init` can attach again —
    the elastic restart path (reference analog: a ps-lite worker
    re-registering with the scheduler after a restart). Stops the
    heartbeat writer, disconnects from the coordinator, and drops every
    cache keyed on the old device set (process mesh, jitted collectives,
    data-parallel meshes) so the rebuilt cluster gets fresh ones."""
    global _initialized, _PMESH
    stop_heartbeat()
    try:
        jax.distributed.shutdown()
    except Exception as exc:  # not initialized / coordinator already gone
        telemetry.swallowed("dist.shutdown", exc)
    _clear_jax_distributed_state()  # a half-failed shutdown must not
    _initialized = False            # block the next initialize
    _PMESH = None
    _AR_JIT.clear()
    from . import mesh as _mesh
    _mesh._DP_MESHES.clear()
    _mesh._NAMED_MESHES.clear()


def rank():
    return jax.process_index()


def size():
    return jax.process_count()


def _proc_mesh():
    """Global 1-D mesh with ONE device per process (process order)."""
    global _PMESH
    if _PMESH is None:
        from jax.sharding import Mesh
        per_proc = {}
        for d in jax.devices():
            per_proc.setdefault(d.process_index, d)
        devs = [per_proc[i] for i in sorted(per_proc)]
        _PMESH = Mesh(np.array(devs), ("p",))
    return _PMESH


def allreduce_nds(nds):
    """Sum a LIST of NDArrays across processes in ONE jitted dispatch
    (BSP dist_sync semantics, device-side collective)."""
    if jax.process_count() == 1 or not nds:
        return nds
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..ndarray.ndarray import NDArray

    mesh = _proc_mesh()
    nproc = jax.process_count()
    my_dev = mesh.devices.flat[jax.process_index()]
    in_shard = NamedSharding(mesh, P("p"))
    out_shard = NamedSharding(mesh, P())

    globals_in = []
    for nd in nds:
        local = jax.device_put(jnp.asarray(nd._data)[None], my_dev)
        g = jax.make_array_from_single_device_arrays(
            (nproc,) + tuple(nd.shape), in_shard, [local])
        globals_in.append(g)

    key = tuple((tuple(nd.shape), str(nd.dtype)) for nd in nds)
    fn = _AR_JIT.get(key)
    if fn is None:
        from ..compiled import donate_argnums_for
        # the gathered inputs are consumed by the reduction: donate them
        # where the backend supports it (policy point strips CPU)
        donate = donate_argnums_for(None, tuple(range(len(nds))))
        fn = jax.jit(lambda *gs: tuple(jnp.sum(g, axis=0) for g in gs),
                     out_shardings=out_shard, donate_argnums=donate)
        _AR_JIT[key] = fn
    outs = fn(*globals_in)

    results = []
    for nd, out in zip(nds, outs):
        val = out.addressable_data(0)
        dev = nd.context.jax_device() if hasattr(nd.context, "jax_device") \
            else None
        if dev is not None and val.devices() != {dev}:
            val = jax.device_put(val, dev)
        results.append(NDArray(val, ctx=nd.context))
    return results


def allgather_arrays(arrs):
    """All-gather a LIST of per-process jnp arrays in ONE dispatch: each
    process contributes its local array; every process receives the
    stacked ``(P, ...)`` result. This is the compressed-gradient wire
    (reference kvstore_dist.h:379: quantized codes are what crosses the
    network, 2-bit codes = 1/16 the dense f32 bytes per direction) —
    ONLY the given arrays' bytes ride the collective."""
    if jax.process_count() == 1 or not arrs:
        return [a[None] for a in arrs]
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _proc_mesh()
    nproc = jax.process_count()
    my_dev = mesh.devices.flat[jax.process_index()]
    in_shard = NamedSharding(mesh, P("p"))
    out_shard = NamedSharding(mesh, P())
    globals_in = []
    for a in arrs:
        local = jax.device_put(jnp.asarray(a)[None], my_dev)
        g = jax.make_array_from_single_device_arrays(
            (nproc,) + tuple(a.shape), in_shard, [local])
        globals_in.append(g)
    key = ("ag",) + tuple((tuple(a.shape), str(a.dtype)) for a in arrs)
    fn = _AR_JIT.get(key)
    if fn is None:
        fn = jax.jit(lambda *gs: gs, out_shardings=out_shard)
        _AR_JIT[key] = fn
    outs = fn(*globals_in)
    return [o.addressable_data(0) for o in outs]


def allreduce_nd(nd):
    """Sum an NDArray across processes (single-key allreduce_nds)."""
    if jax.process_count() == 1:
        return nd
    return allreduce_nds([nd])[0]


def broadcast_nd(nd):
    """Replicate rank 0's NDArray value to every process (reference dist
    kvstore init semantics: only rank 0's payload seeds the server).
    Init-time only; the hot path is allreduce_nds."""
    if jax.process_count() == 1:
        return nd
    from jax.experimental import multihost_utils
    from ..ndarray.ndarray import NDArray
    out = multihost_utils.broadcast_one_to_all(np.asarray(nd._data))
    # commit to the source's device: a host-numpy payload would silently
    # re-commit to the default device at first use
    val = np.asarray(out)
    if hasattr(nd.context, "jax_device"):
        val = jax.device_put(val, nd.context.jax_device())
    return NDArray(val, ctx=nd.context)


def barrier():
    from .. import chaos
    chaos.maybe_timeout("barrier")  # armed chaos applies at any size
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("mxnet_tpu.kvstore.barrier")


def host_barrier(name, timeout_s=60.0):
    """Barrier over the coordination service itself — pure host-side, so
    it works even where device collectives are unavailable (multiprocess
    CPU test clusters) and never dispatches to the accelerator. Used for
    control-plane gates like the elastic checkpoint commit. ``name`` must
    be unique per use within one coordinator's lifetime."""
    from .. import chaos
    chaos.maybe_timeout("host_barrier")
    if jax.process_count() == 1:
        return
    client = _coordinator_client()
    if client is None:
        # multi-process but no coordination client: the gate CANNOT be
        # provided, and callers (the elastic commit) rely on it for
        # correctness — fail loudly instead of silently passing
        raise RuntimeError(
            "host_barrier(%r): no coordination-service client available "
            "in a %d-process run; cannot synchronize hosts"
            % (name, jax.process_count()))
    client.wait_at_barrier(name, int(timeout_s * 1000))


# ---------------------------------------------------------------------------
# Liveness / failure detection (reference kvstore.h:338 get_num_dead_node,
# backed by ps-lite heartbeats between nodes and the scheduler, van.cc).
# Here each process heartbeats a timestamp into the jax.distributed
# coordinator's key-value store; any process can count peers whose beat is
# older than a timeout.
# ---------------------------------------------------------------------------

def _coordinator_client():
    try:
        from jax._src import distributed
        return distributed.global_state.client
    except Exception as exc:  # pragma: no cover
        telemetry.swallowed("dist.coordinator_client", exc)
        return None


def start_heartbeat(interval=5.0):
    """Background thread writing this process's liveness timestamp to the
    coordinator KV store every ``interval`` seconds. No-op single-process
    or when no coordinator is attached."""
    global _HB_THREAD, _HB_STOP
    client = _coordinator_client()
    if client is None or _HB_THREAD is not None:
        return False
    import threading
    import time as _time

    stop_evt = threading.Event()  # captured by THIS thread: a stop/start
    _HB_STOP = stop_evt           # pair must not hand the old thread the
    me = jax.process_index()      # new thread's event (it would never stop)

    from .. import chaos

    def beat():
        last = None
        while True:
            extra = chaos.heartbeat_extra_delay()
            if extra:  # injected network stall: the beat arrives late
                _time.sleep(extra)
            now = _time.time()
            if last is not None:
                # liveness-gap series: in a healthy run this sits at
                # ~interval; chaos stalls and coordinator hiccups show
                # up as p99 outliers long before a peer is declared dead
                telemetry.histogram(
                    "heartbeat_gap_seconds",
                    help="gap between successive liveness writes"
                ).observe(now - last)
            last = now
            try:
                client.key_value_set("%s/%d" % (_HB_PREFIX, me),
                                     repr(now), allow_overwrite=True)
            except Exception as exc:  # pragma: no cover - coord. gone
                telemetry.swallowed("dist.heartbeat_write", exc)
                return
            if stop_evt.wait(interval):
                return

    _HB_THREAD = threading.Thread(target=beat, daemon=True,
                                  name="mxnet_tpu-heartbeat")
    _HB_THREAD.start()
    return True


def stop_heartbeat():
    """Stop the liveness writer and WAIT for it: after return, no further
    heartbeat reaches the coordinator (so a stopped node goes stale and
    num_dead_nodes counts it). Returns True on a clean stop (or when no
    writer was running); False — with a warning — if the thread failed to
    exit within 30s and was leaked (e.g. a KV write wedged on a dead
    coordinator), in which case a stray late beat may still land."""
    global _HB_THREAD, _HB_STOP
    thread, _HB_THREAD = _HB_THREAD, None
    if _HB_STOP is not None:
        _HB_STOP.set()
    _HB_STOP = None
    if thread is not None:
        thread.join(timeout=30)
        if thread.is_alive():
            import logging
            logging.warning(
                "heartbeat writer did not stop within 30s; leaking the "
                "thread (a late beat may still reach the coordinator)")
            return False
    return True


def num_dead_nodes(timeout=60):
    """Count processes whose heartbeat is older than ``timeout`` seconds
    (or missing entirely). Returns 0 when not distributed or when no peer
    ever started heartbeating (no liveness protocol in play)."""
    from .. import chaos
    chaos.maybe_timeout("num_dead_nodes")  # armed chaos applies at any size
    return _num_dead_nodes_nochaos(timeout)


def _num_dead_nodes_nochaos(timeout):
    """num_dead_nodes without the chaos poll — for background monitors
    (the elastic watchdog) whose own polling would otherwise race the
    main thread for armed triggers and break chaos determinism."""
    dead = _count_stale_peers(timeout)
    telemetry.gauge("dist_dead_nodes",
                    help="peers with stale/missing heartbeats at the "
                         "last liveness poll").set(dead)
    return dead


def _count_stale_peers(timeout):
    client = _coordinator_client()
    if client is None or jax.process_count() == 1:
        return 0
    import time as _time
    try:
        entries = client.key_value_dir_get(_HB_PREFIX)
    except Exception as exc:  # no beats written yet / coordinator gone
        telemetry.swallowed("dist.heartbeat_read", exc)
        return 0
    if not entries:
        return 0
    now = _time.time()
    seen = {}
    for k, v in entries:
        try:
            seen[int(str(k).rsplit("/", 1)[-1])] = float(str(v))
        except ValueError:  # pragma: no cover
            continue
    if not seen:
        return 0
    # a peer with NO key yet may simply still be starting up: only count
    # missing peers once the cluster has been beating for > timeout
    # (earliest observed beat as the cluster-age proxy)
    cluster_old_enough = now - min(seen.values()) > timeout
    dead = 0
    for pid in range(jax.process_count()):
        t = seen.get(pid)
        if t is None:
            dead += 1 if cluster_old_enough else 0
        elif now - t > timeout:
            dead += 1
    return dead
