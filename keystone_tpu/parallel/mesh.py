"""Device-mesh management for the TPU-native execution substrate.

The reference runs every distributed operation through a ``SparkContext``
over cluster executors. Here the substrate is a `jax.sharding.Mesh`: data
parallelism shards the example/batch dimension over the ``data`` axis, and
the feature-block / model dimension may be sharded over a ``model`` axis
(see SURVEY.md section 2.14 for the strategy mapping).

A single process-global mesh plays the role of the reference's implicit
global SparkContext (``pipelines/*`` apps construct one ``sc`` per run).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.timeline import record_span

DATA_AXIS = "data"
MODEL_AXIS = "model"

_lock = threading.Lock()
_global_mesh: Optional[Mesh] = None


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    data: Optional[int] = None,
    model: int = 1,
) -> Mesh:
    """Build a ('data', 'model') mesh over the given (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if data is None:
        data = n // model
    if data * model != n:
        raise ValueError(f"mesh shape {data}x{model} != {n} devices")
    arr = np.asarray(devices).reshape(data, model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _global_mesh
    with _lock:
        _global_mesh = mesh


def get_mesh() -> Mesh:
    """The process-global mesh, lazily built over all visible devices.

    ``KEYSTONE_MESH_MODEL=k`` sizes the ``model`` axis of the lazily
    built default mesh (CLUSTER.md environment contract).
    """
    global _global_mesh
    with _lock:
        if _global_mesh is None:
            raw = os.environ.get("KEYSTONE_MESH_MODEL") or "1"
            try:
                model = int(raw)
            except ValueError:
                raise ValueError(
                    f"KEYSTONE_MESH_MODEL must be an integer, got {raw!r}"
                ) from None
            _global_mesh = make_mesh(model=model)
        return _global_mesh


@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    """Temporarily replace the global mesh (tests, multi-mesh programs)."""
    global _global_mesh
    with _lock:
        prev = _global_mesh
        _global_mesh = mesh
    try:
        yield mesh
    finally:
        with _lock:
            _global_mesh = prev


def num_data_shards(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape[DATA_AXIS]


def replication_factor(mesh: Optional[Mesh] = None) -> int:
    """How many replicas of a ``P('data')``-sharded batch the mesh
    holds: the product of the non-data axis sizes. Each replica is its
    own host->device transfer, so wire-byte accounting (the streaming
    ``h2d_bytes`` counter and the static planner's wire model) scales by
    this factor while the LOGICAL array footprint does not."""
    mesh = mesh or get_mesh()
    rep = 1
    for name, size in dict(mesh.shape).items():
        if name != DATA_AXIS:
            rep *= int(size)
    return rep


def batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Sharding for a batch-major array: rows split over the data axis."""
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P())


def replicated_zeros(mesh: Mesh, shapes):
    """f32 zero buffers explicitly replicated on ``mesh``
    (``NamedSharding(mesh, P())`` rather than the default
    SingleDeviceSharding). The sharding KIND matters: jax's jit cache
    keys on input shardings, so a streamed-accumulate carry seeded as
    single-device recompiles its update program on chunk 2 when the
    mesh-sharded chunk-1 output arrives — a replicated init keeps the
    carry's sharding stable from call 1 (the compile observatory's fit
    fence flagged exactly this in the Gram and moments carries)."""
    import jax.numpy as jnp

    sh = NamedSharding(mesh, P())
    return [jax.device_put(jnp.zeros(s, jnp.float32), sh) for s in shapes]


#: shared per-shard H2D staging pool (lazy; every staging site —
#: streaming prefetch, resident ArrayDataset construction — fans shard
#: puts through ONE small pool: staging is transfer-bound, not
#: cpu-bound, so a handful of lanes saturates the host link)
_H2D_POOL: Optional[ThreadPoolExecutor] = None
_H2D_POOL_LOCK = threading.Lock()


def h2d_workers() -> int:
    """Configured staging-lane count (``KEYSTONE_H2D_THREADS``, default
    4; ``<=1`` disables per-shard staging). Raises a clear ValueError on
    a malformed value — callers that later run on a background thread
    (``StreamingDataset.__init__``) validate EAGERLY through this, so a
    bad knob fails at construction, not as an opaque mid-fit
    ``_SourceError`` from the prefetch thread (the KEYSTONE_MESH_MODEL
    convention)."""
    env = os.environ.get("KEYSTONE_H2D_THREADS")
    if not env:
        return 4
    try:
        return int(env)
    except ValueError:
        raise ValueError(
            f"KEYSTONE_H2D_THREADS must be an integer, got {env!r}"
        ) from None


#: set by the exit teardown: no pool may be (re)built while the
#: interpreter is shutting down — a producer mid-``_stage`` at exit
#: would otherwise lazily rebuild a fresh non-daemon pool whose
#: teardown already ran
_H2D_EXITING = False


def h2d_pool() -> Optional[ThreadPoolExecutor]:
    """The shared staging pool, or None when per-shard staging is
    disabled (``KEYSTONE_H2D_THREADS=1`` / ``0`` forces the single
    whole-array ``device_put``) or the interpreter is exiting."""
    workers = h2d_workers()
    if workers <= 1 or _H2D_EXITING:
        return None
    global _H2D_POOL
    with _H2D_POOL_LOCK:
        if _H2D_POOL is None and not _H2D_EXITING:
            _H2D_POOL = ThreadPoolExecutor(
                workers, thread_name_prefix="keystone-h2d")
        return _H2D_POOL


def shutdown_h2d_pool(wait: bool = False) -> None:
    """Tear down the shared staging pool (idempotent; the next
    ``h2d_pool()`` call builds a fresh one). The interpreter-exit path
    goes through :func:`_shutdown_h2d_pool_at_exit` instead, which also
    blocks rebuilds."""
    global _H2D_POOL
    with _H2D_POOL_LOCK:
        pool, _H2D_POOL = _H2D_POOL, None
    if pool is not None:
        pool.shutdown(wait=wait, cancel_futures=True)


def _shutdown_h2d_pool_at_exit() -> None:
    """Exit teardown: the pool's workers are NON-daemon threads, and
    without an explicit shutdown an exit under an active stream leaks
    them into the interpreter's thread join — a prefetch producer
    racing new ``device_put`` submissions against teardown used to spew
    'cannot schedule new futures' / join warnings (pinned by the
    subprocess test in tests/test_concurrency_sched.py)."""
    global _H2D_EXITING
    _H2D_EXITING = True
    shutdown_h2d_pool()


# Registered at IMPORT time, not first pool build: threading's private
# ``_register_atexit`` callbacks run in REVERSE registration order
# (before non-daemon threads are joined — exactly the window the pool
# must die in; plain ``atexit`` is the fallback for interpreters
# without the hook). streaming.py imports this module before
# registering its stream-stop teardown, so at exit the stream stops
# run FIRST, then this pool shutdown — stops-before-pool is the
# invariant that keeps producers from racing teardown.
import atexit  # noqa: E402

getattr(threading, "_register_atexit", atexit.register)(
    _shutdown_h2d_pool_at_exit)


def shard_put(arr, sharding: NamedSharding, pool=None):
    """Host array -> sharded device array via PER-DEVICE shard puts.

    The whole-array ``jax.device_put(arr, sharding)`` serializes the
    host->device copies of every shard behind one call; staging each
    device's row slice from a thread ``pool`` overlaps the host-side
    slicing + transfer of shard *k+1* with the in-flight transfer of
    shard *k* (``jax.device_put`` is thread-safe and per-device
    transfers are independent DMA streams). Slices are numpy VIEWS — no
    host copy is made per shard — and the shards reassemble with
    ``jax.make_array_from_single_device_arrays`` (replicated axes get
    the same slice put to each replica, exactly what
    ``devices_indices_map`` prescribes).

    With ``pool=None`` or a single addressable device this is exactly
    ``jax.device_put(arr, sharding)``.
    """
    if pool is None:
        return jax.device_put(arr, sharding)
    try:
        dev_map = sharding.addressable_devices_indices_map(arr.shape)
    except Exception:
        return jax.device_put(arr, sharding)
    if len(dev_map) <= 1:
        return jax.device_put(arr, sharding)

    def put_shard(slice_, dev):
        # one flight-recorder span per shard put, on the pool worker
        # thread — the H2D staging lanes in the Perfetto export. The
        # put is async; the span covers dispatch + host-side slicing,
        # which is what the lane occupancy shows (transfer completion
        # is the device's business).
        t0 = time.perf_counter()
        out = jax.device_put(slice_, dev)
        record_span("h2d", "h2d", t0, time.perf_counter() - t0,
                    args={"nbytes": int(getattr(slice_, "nbytes", 0)),
                          "device": str(dev)})
        return out

    futures = [pool.submit(put_shard, arr[idx], dev)
               for dev, idx in dev_map.items()]
    shards = [f.result() for f in futures]
    return jax.make_array_from_single_device_arrays(
        arr.shape, sharding, shards)


def local_mesh(model: int = 1) -> Mesh:
    """A ('data', 'model') mesh over THIS process's addressable devices.

    The multi-host streamed-ingest path
    (:mod:`keystone_tpu.parallel.distributed`) is
    shard-local-accumulate / cross-host-reduce-at-finalize: each host
    stages only its own chunks, so the stream's mesh must contain only
    devices this host can ``device_put`` to. A mesh over the GLOBAL
    ``jax.devices()`` view (what :func:`get_mesh` lazily builds once
    ``jax.distributed`` is live) would make every staging call try to
    feed remote devices. Single-process, this is exactly the default
    mesh."""
    import jax

    return make_mesh(jax.local_devices(), model=model)


def world_data_mesh(model: int = 1) -> Mesh:
    """A ('data', 'model') mesh over EVERY process's devices — the
    world mesh the sharded apply (:mod:`keystone_tpu.parallel.
    spmd_apply`) runs on: batch rows and resident weight rows both
    shard over the global ``data`` axis, so one logical model serves
    from N hosts' HBM. Single-process this is the default mesh over
    all visible devices; under a live ``jax.distributed`` world the
    data axis spans hosts (cross-host gathers over DCN/gloo). Device
    order is jax's global enumeration — process-major — so each host's
    row shards are contiguous in the global batch."""
    import jax

    return make_mesh(jax.devices(), model=model)


def initialize_distributed(coordinator_address=None, num_processes=None,
                           process_id=None):
    """Multi-host initialization (the DCN scale-out entry point): wires
    jax.distributed so ``jax.devices()`` spans all hosts and meshes built
    from it run cross-host collectives over DCN, intra-slice ones over
    ICI. No-op when already initialized or single-host args are absent.

    The reference's analogue is Spark cluster attach
    (``bin/run-pipeline.sh`` spark-submit); here every host runs the same
    program (SPMD) and the mesh spans the pod.

    On the CPU backend (the dryrun harness, CI) cross-process
    collectives ride gloo, which is this JAX's default
    ``jax_cpu_collectives_implementation``.
    """
    import jax

    if jax.distributed.is_initialized():
        return
    if coordinator_address is None:
        jax.distributed.initialize()  # env-driven (TPU pods)
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    if jax.process_count() > 1:
        # every resilience event now carries which HOST it fired on
        # (announcement keeps the event funnel itself device-free)
        from ..resilience.events import set_process_dimension

        set_process_dimension(jax.process_index())
