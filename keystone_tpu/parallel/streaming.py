"""Streaming chunked execution: double-buffered host->device ingest.

The reference framework never materializes a whole featurized dataset:
Spark streams partitions through narrow stages and the solvers reduce
per-partition Gram/cross products (SURVEY.md section 3.2). The TPU port
lost that property — ``ArrayDataset`` requires the full dataset
device-resident before any fit. This module restores it:

* :class:`StreamingDataset` — yields fixed-shape, zero-padded, masked
  :class:`~keystone_tpu.parallel.dataset.ArrayDataset` chunks from a
  host source (item iterables, pre-chunked decode pools like
  ``loaders.image_loader_utils.iter_decoded_chunks``, resident numpy).
  A background prefetch thread stages (pad + ``device_put``) the next
  chunks behind a bounded queue (``prefetch_depth``, default 2 — a
  double buffer), so chunk *i+1* decodes/uploads while chunk *i*
  computes. Every chunk is padded to the SAME ``chunk_size`` rows, so
  per-chunk transformer programs compile once per chain structure
  (PERFORMANCE.md rules 5-6) and the second epoch compiles nothing.

* the **accumulate/finalize protocol** — a streamable estimator
  implements ``accumulate(carry, chunk[, labels_chunk]) -> carry`` and
  ``finalize(carry) -> Transformer``; :func:`fit_streaming` drives the
  chunk loop. LeastSquares/BlockLS accumulate Gram + cross products via
  the fused ``ops.pallas_kernels.gram_cross`` streaming kernel,
  StandardScaler accumulates moments — a fit never holds the full
  featurized matrix in HBM, so datasets larger than HBM fit out-of-core
  (device residency is bounded by ``device_nbytes(stream)``: the
  prefetch buffer plus one working chunk).

* **dtype on the wire** — ``wire_dtype`` narrows each host chunk before
  the transfer (uint8 image chunks stay uint8 across PCIe/ICI — 4x
  fewer wire bytes than the f32 the math eventually wants) and a fused
  on-device cast, prepended to the per-chunk transform chain by the
  chunk executor, restores the compute dtype (``compute_dtype``, default
  = the source's native dtype) before any consumer sees the chunk. The
  residency ledger and ``hbm_budget`` asserts account for the post-cast
  working copy, so narrowing the wire never hides HBM cost.

* **parallel per-shard staging** — chunks reach the mesh as per-device
  row-slice ``device_put``\\ s fanned out over a small thread pool
  (:func:`~keystone_tpu.parallel.mesh.shard_put`), so the host-side
  slicing + H2D of shard *k+1* overlaps the transfer of shard *k*;
  full-size chunks skip the host pad copy entirely (only ragged tails
  pad). ``KEYSTONE_H2D_THREADS=1`` forces the single whole-chunk put.

Observability: consuming a stream feeds the process metrics
(``streaming.ingest_stall_s`` histogram — time the device-side consumer
waited on ingest; ``streaming.prefetch_occupancy`` gauge;
``streaming.chunks_total`` counter; ``streaming.h2d_bytes`` counter —
actual bytes shipped host->device, post wire-narrowing) and, when a
:class:`~keystone_tpu.observability.PipelineTrace` is active, per-chunk
trace entries with ingest-stall attribution plus stage-lane occupancy
(``stage_lanes`` / ``stage_s`` / ``h2d_bytes``).

Resilience (:mod:`keystone_tpu.resilience`): chunk staging retries
transient failures under a :class:`RetryPolicy`; a producer watchdog
(``stall_timeout_s``) converts a hung source into a clear
:class:`IngestTimeoutError` instead of an indefinite consumer block;
and :func:`fit_streaming` checkpoints its (cursor, carry, quarantine)
state every ``checkpoint_every`` chunks so a killed multi-hour fit
resumes bit-comparably instead of restarting.
"""
from __future__ import annotations

import atexit
import os
import queue
import sys
import threading
import time
import weakref
from typing import Any, Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..observability.metrics import MetricsRegistry
from ..observability.postmortem import attach_postmortem, dump_postmortem
from ..observability.timeline import record_span
from ..observability.trace import current_trace
from ..utils.guarded import TracedLock, TracedSemaphore, guarded_by
from ..observability.numerics import (
    HealthMonitor,
    SketchTracker,
    check_fitted,
    numerics_active,
    record_numerics_event,
)
from ..resilience.events import record_event
from ..resilience.faults import corrupt, inject
from ..resilience.retry import (
    IngestTimeoutError,
    RetryPolicy,
    default_retry_policy,
)
from .dataset import ArrayDataset, Dataset, HostDataset, _pad_to, device_nbytes
from .mesh import (
    DATA_AXIS,
    batch_sharding,
    get_mesh,
    h2d_pool as _h2d_pool,
    h2d_workers,
    num_data_shards,
    replicated_sharding,
    replication_factor,
    shard_put,
)


def _dtype_policy(value: Any) -> Any:
    """Normalize a wire/compute dtype policy: None, a single dtype
    (np.dtype) applied to EVERY chunk leaf, or a pytree of
    dtype-or-None matching the chunk structure (mixed trees — narrow
    the image leaf, leave the integer-label leaf untouched). Pytree
    policies are structure-validated lazily at first stage."""
    if value is None:
        return None
    try:
        return np.dtype(value)
    except TypeError:
        return value  # pytree policy


def _policy_name(policy: Any) -> Optional[str]:
    """Stable printable identity of a dtype policy (spec/fingerprint)."""
    if policy is None:
        return None
    if isinstance(policy, np.dtype):
        return policy.name
    return repr(jax.tree_util.tree_map(
        lambda d: None if d is None else np.dtype(d).name, policy,
        is_leaf=lambda x: x is None))


def _policy_leaves(policy: Any, treedef: Any, n: int) -> List:
    """Per-chunk-leaf dtype targets for a normalized policy."""
    if policy is None:
        return [None] * n
    if isinstance(policy, np.dtype):
        return [policy] * n
    leaves, td = jax.tree_util.tree_flatten(
        policy, is_leaf=lambda x: x is None)
    if td != treedef:
        raise ValueError(
            "wire/compute dtype policy structure does not match the "
            f"chunk structure: policy {td}, chunk {treedef}. Pass a "
            "single dtype to apply it to every leaf, or a pytree of "
            "dtype-or-None mirroring the chunk pytree.")
    return [None if l is None else np.dtype(l) for l in leaves]

_DONE = object()

#: (treedef, target dtypes) -> jitted wire->compute cast program: the
#: cast depends only on chunk STRUCTURE and dtypes, so every stream of
#: the same shape family (each refit builds a fresh StreamingDataset)
#: shares one compiled program — a per-instance memo would recompile
#: the cast on every refit, breaking the zero-recompile second epoch.
#: Bounded LRU, same discipline as the dataset/transformer jit memos.
from ..utils.lru import LruMemo  # noqa: E402

_CAST_JIT_CACHE = LruMemo()
# guards the miss path: LruMemo's get/put are individually locked, but
# get->build->put is a check-then-act — two prefetch threads racing the
# same key would each build a DISTINCT jit wrapper, and jax's trace
# cache keys on the function object, so the loser recompiles the cast
# on every chunk (found by the guarded-by review sweep; pinned in
# test_concurrency_sched.py)
_CAST_BUILD_LOCK = TracedLock("stream.cast_build")


def _cast_program(treedef, casts: Tuple) -> Callable:
    key = ("wire_cast", treedef, tuple(dt.name for dt in casts))
    fn = _CAST_JIT_CACHE.get(key)
    if fn is None:
        with _CAST_BUILD_LOCK:
            fn = _CAST_JIT_CACHE.get(key)
            if fn is None:
                from ..observability.compilelog import watch_jit

                cast_tree = jax.tree_util.tree_unflatten(
                    treedef, list(casts))
                # observed site: the memo stores the WATCHED wrapper,
                # so a cast that recompiles per chunk (the pre-PR-5
                # per-instance-memo bug) shows up as classified
                # compile records, not silent wall time
                fn = watch_jit(jax.jit(lambda data: jax.tree_util.tree_map(
                    lambda x, t: x.astype(t), data, cast_tree)),
                    name="wire_cast")
                _CAST_JIT_CACHE.put(key, fn)
    return fn


#: stop events of every live ``chunks()`` iteration, set at interpreter
#: exit so prefetch producers stop BEFORE the H2D pool tears down —
#: a daemon producer mid-``device_put`` at exit otherwise races pool
#: shutdown into join warnings (or, with an unlucky schedule, a hang).
#: WeakSet: a finished iteration's event is garbage, not a leak.
_LIVE_STREAM_STOPS: "weakref.WeakSet" = weakref.WeakSet()


def _shutdown_live_streams() -> None:
    live = list(_LIVE_STREAM_STOPS)
    for stop in live:
        stop.set()
    if live:
        # exit under an ACTIVE stream: flush the flight recorder +
        # metrics to a post-mortem before the H2D pool teardown runs
        # (this callback is registered after mesh's pool shutdown, so
        # threading._register_atexit's reverse order runs it FIRST) —
        # a driver-killed or ctrl-C'd fit still leaves its timeline
        dump_postmortem("exit_under_active_stream",
                        {"live_streams": len(live)})


# threading._register_atexit callbacks run at threading shutdown,
# BEFORE non-daemon threads (the H2D pool's workers) are joined —
# plain atexit would run too late to matter. Fall back gracefully on
# interpreters without the private hook.
_register_teardown = getattr(threading, "_register_atexit", atexit.register)
_register_teardown(_shutdown_live_streams)


class _SourceError:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class _IterLedger:
    """One active ``chunks()`` iteration's contribution to the shared
    residency (so concurrent iterations — e.g. a data stream and a
    labels view derived from the same root — compose instead of
    clobbering each other's accounting)."""

    __slots__ = ("buffered", "working")

    def __init__(self) -> None:
        self.buffered = 0.0
        self.working = 0.0


@guarded_by("_lock", "buffered", "working", "chunk_nbytes", "peak")
class _Residency:
    """Thread-safe device-residency ledger for one prefetch pipeline:
    bytes staged in the queue + working chunks, with a peak high-water
    mark. One instance is shared by a root stream and all its derived
    (mapped) views; each live ``chunks()`` iteration tracks its own
    contribution through an :class:`_IterLedger`, and closing an
    iteration removes exactly that contribution — never another
    iteration's. The producer/consumer lock is a TracedLock: its
    contention is observable and the schedule harness interleaves at it
    (the PR 3 ledger-close race's regression schedule)."""

    __slots__ = ("_lock", "buffered", "working", "chunk_nbytes", "peak")

    def __init__(self) -> None:
        self._lock = TracedLock("stream.residency")
        self.buffered = 0.0
        self.working = 0.0
        self.chunk_nbytes = 0.0
        self.peak = 0.0

    def stage(self, it: _IterLedger, nbytes: float) -> None:
        with self._lock:
            self.chunk_nbytes = nbytes
            it.buffered += nbytes
            self.buffered += nbytes
            self.peak = max(self.peak, self.buffered + self.working)

    def hand_off(self, it: _IterLedger, staged_nbytes: float,
                 work_nbytes: float, transient: float = 0.0) -> None:
        """One chunk leaves the buffer and becomes the working chunk.
        ``staged_nbytes`` is the wire-dtype footprint removed from the
        buffer; ``work_nbytes`` the (possibly post-cast, wider) working
        footprint; ``transient`` charges the brief co-existence of the
        wire copy and the cast output against the peak."""
        with self._lock:
            self.buffered -= staged_nbytes
            it.buffered -= staged_nbytes
            # this iteration's previous working chunk is released;
            # other iterations' working chunks stay counted
            self.working += work_nbytes - it.working
            it.working = work_nbytes
            self.peak = max(self.peak,
                            self.buffered + self.working + transient)

    def close(self, it: _IterLedger) -> None:
        """Remove one finished iteration's residual contribution (its
        still-buffered chunks and working chunk)."""
        with self._lock:
            self.buffered -= it.buffered
            self.working -= it.working
            it.buffered = 0.0
            it.working = 0.0

    def live(self) -> float:
        with self._lock:
            return self.buffered + self.working


class StreamingDataset(Dataset):
    """Chunked, prefetched view of a host data source.

    ``chunk_source`` is a CALLABLE returning a fresh iterator of host
    chunks (so the stream is re-iterable: multi-pass estimators and
    repeated epochs re-open the source); each host chunk is a pytree of
    numpy-like arrays sharing a leading dim of at most ``chunk_size``
    rows. Chunks are padded with zero rows to exactly ``chunk_size``
    (rounded up to a shard multiple), staged to the mesh on a background
    thread, and yielded as masked :class:`ArrayDataset`\\ s whose ``n``
    is the chunk's true row count — the zero-pad invariant linear
    reductions rely on holds per chunk.

    ``n`` (the total item count) may be known or unknown (None); the
    static analyzer carries either through ``DatasetSpec``.

    Dtype on the wire: ``wire_dtype`` (default None = ship each leaf in
    its source dtype) narrows host chunks before the transfer — a uint8
    wire moves 1/4 the bytes of an f32 one, and for decoded images
    (integral values in [0, 255]) the narrowing is lossless.
    ``compute_dtype`` (default None = restore each leaf's pre-wire
    source dtype) is what consumers see: the chunk executor prepends ONE
    fused on-device cast to the transform chain, compiled once per
    chunk-structure family. Either may be a single dtype — applied to
    EVERY leaf, so only safe when all leaves share a value range — or a
    pytree of dtype-or-None mirroring the chunk structure, for mixed
    trees where e.g. the image leaf narrows and the label leaf must not
    (``wire_dtype={"x": np.uint8, "y": None}``). The residency ledger
    and ``hbm_budget`` asserts charge the post-cast working copy, never
    just the narrow wire bytes.
    """

    def __init__(self, chunk_source: Callable[[], Iterator[Any]],
                 chunk_size: int, n: Optional[int] = None,
                 mesh: Optional[Mesh] = None, prefetch_depth: int = 2,
                 tag: Optional[str] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 stall_timeout_s: Optional[float] = None,
                 quarantine: Any = None,
                 wire_dtype: Any = None,
                 compute_dtype: Any = None,
                 _transforms: Tuple[Callable, ...] = ()):
        if not callable(chunk_source):
            raise TypeError(
                "chunk_source must be a callable returning a fresh chunk "
                "iterator (one-shot generators cannot support re-iteration "
                "— wrap the construction in a function)")
        if prefetch_depth < 1:
            raise ValueError("prefetch_depth must be >= 1")
        self.mesh = mesh or get_mesh()
        if jax.process_count() > 1 and any(
                d.process_index != jax.process_index()
                for d in self.mesh.devices.flat):
            # multi-host ingest is shard-local: every chunk this host
            # stages must land on devices this host owns. A global mesh
            # here means the caller skipped the distributed recipe.
            raise ValueError(
                "StreamingDataset mesh contains devices owned by other "
                "processes: multi-host streamed ingest is shard-local — "
                "each host stages only its own chunks onto its own "
                "devices and fit_streaming tree-reduces the carries at "
                "finalize. Build the stream under "
                "parallel.mesh.local_mesh() (see CLUSTER.md 'Elastic "
                "resume').")
        # every chunk pads to one fixed shape: a shard-divisible row
        # count means ONE compiled program per chain serves all chunks
        self.chunk_size = _round_up(int(chunk_size),
                                    num_data_shards(self.mesh))
        self.n = None if n is None else int(n)
        self.prefetch_depth = int(prefetch_depth)
        self.tag = tag
        # device staging retries transient failures (one try/except per
        # chunk when healthy — the <2% resilience-overhead budget);
        # stall_timeout_s arms the producer watchdog: None = wait
        # forever, like a plain queue (dead producers still raise)
        self.retry_policy = retry_policy or default_retry_policy()
        self.stall_timeout_s = (None if stall_timeout_s is None
                                else float(stall_timeout_s))
        #: the corrupt-record quarantine the source feeds, when it has
        #: one (``stream_tar_images`` wires its decode pool here) —
        #: carried through ``map``/``map_chunks`` derivations so a
        #: featurized view still exposes the ingest accounting
        self.quarantine = quarantine
        #: wire/compute dtype policy: None, a single np.dtype applied
        #: to EVERY leaf (only safe when all leaves share a value
        #: range, e.g. single-array chunks), or a pytree of
        #: dtype-or-None mirroring the chunk structure for mixed trees
        #: (narrow the image leaf, leave integer labels untouched)
        self.wire_dtype = _dtype_policy(wire_dtype)
        self.compute_dtype = _dtype_policy(compute_dtype)
        # eager knob validation: the staging pool is first touched on
        # the prefetch thread, where a malformed env var would surface
        # as an opaque mid-fit source error
        h2d_workers()
        self._chunk_source = chunk_source
        self._transforms = tuple(_transforms)
        # device-residency accounting (the out-of-core budget evidence):
        # bytes sitting in the prefetch queue plus the working chunk.
        # SHARED between a root stream and every map/map_chunks
        # derivation of it — only one prefetch pipeline runs, and the
        # budget must be readable from whichever handle the caller kept.
        self._residency = _Residency()

    # -- derivation --------------------------------------------------------
    def _derive(self, transform: Callable[[ArrayDataset], ArrayDataset],
                tag: Optional[str] = None) -> "StreamingDataset":
        out = StreamingDataset(
            self._chunk_source, self.chunk_size, n=self.n, mesh=self.mesh,
            prefetch_depth=self.prefetch_depth, tag=tag or self.tag,
            retry_policy=self.retry_policy,
            stall_timeout_s=self.stall_timeout_s,
            quarantine=self.quarantine,
            wire_dtype=self.wire_dtype,
            compute_dtype=self.compute_dtype,
            _transforms=self._transforms + (transform,))
        out._residency = self._residency  # shared budget accounting
        # the static plan follows the shared ledger: a derived view's
        # residency IS the root's prefetch pipeline
        out.__dict__["_plan_geometry"] = self.plan_geometry
        if getattr(self, "process_sharded", False):
            # a featurized view of a shard-local source is still
            # shard-local (the analyzer reports the flag; n stays a
            # per-host share)
            out.process_sharded = True
        return out

    def map(self, fn: Callable[[Any], Any]) -> "StreamingDataset":
        """Per-item device transform, applied chunk-wise (lazy: nothing
        runs until the stream is consumed)."""
        return self._derive(lambda ad: ad.map(fn))

    def map_chunks(
        self, fn: Callable[[ArrayDataset], ArrayDataset]
    ) -> "StreamingDataset":
        """Chunk-level transform (an ``ArrayDataset -> ArrayDataset``
        function, e.g. a transformer's ``apply_dataset``), lazy."""
        return self._derive(fn)

    def __len__(self) -> int:
        if self.n is None:
            raise TypeError(
                "StreamingDataset length is unknown (n=None); consume the "
                "stream or construct with an explicit n")
        return self.n

    # -- staging -----------------------------------------------------------
    def _stage(self, raw: Any) -> Tuple[ArrayDataset, dict]:
        """Stage one host chunk onto the mesh (runs on the prefetch
        thread; jax device transfers are thread-safe and async, so the
        upload overlaps the consumer's compute):

        * leaves are narrowed to ``wire_dtype`` on the host when set —
          the only host copy a full-size, native-dtype chunk pays is
          ZERO (no wire cast, no pad: only ragged tails pad);
        * each leaf goes up as per-device shard slices fanned over the
          shared staging pool (:func:`~..mesh.shard_put`), so shard
          *k+1*'s host slice + H2D overlaps shard *k*'s transfer.

        Returns ``(chunk, meta)`` where ``meta`` carries the wire bytes
        actually shipped (``h2d_bytes``), the post-cast working
        footprint (``work_nbytes``), the staging lane count/wall, and
        the device-cast spec the consumer applies (None when the wire
        dtype already IS the compute dtype). Transient staging failures
        retry under the stream's :class:`RetryPolicy` (the
        ``ingest.stage`` fault-injection site lives inside the
        attempt)."""
        # value-corruption fault site (kind="corrupt" FaultSpecs): the
        # numerics-gate tests poison exactly one chunk's data here to
        # prove the NaN tripwire names the right chunk — a no-op (one
        # global read) without an active FaultPlan
        raw = corrupt("ingest.stage", raw, context=self.tag or "stream")
        leaves, treedef = jax.tree_util.tree_flatten(raw)
        if not leaves:
            raise ValueError("empty chunk from source")
        rows = int(np.shape(leaves[0])[0])
        if rows > self.chunk_size:
            raise ValueError(
                f"source chunk has {rows} rows > chunk_size "
                f"{self.chunk_size}")

        def put() -> Tuple[ArrayDataset, dict]:
            inject("ingest.stage", context=self.tag or "stream")
            sh = batch_sharding(self.mesh)
            pool = _h2d_pool()
            t0 = time.perf_counter()
            staged: List[Any] = []
            casts: List[np.dtype] = []
            # bytes that actually cross the host->device link: a
            # P('data') batch replicates each row shard across the
            # non-data mesh axes, so every replica is its own transfer
            replication = replication_factor(self.mesh)
            h2d_bytes = 0.0
            work_nbytes = 0.0
            needs_cast = False
            wire_targets = _policy_leaves(self.wire_dtype, treedef,
                                          len(leaves))
            compute_targets = _policy_leaves(self.compute_dtype, treedef,
                                             len(leaves))
            for x, wire, compute in zip(leaves, wire_targets,
                                        compute_targets):
                arr = np.asarray(x)
                source = arr.dtype
                if wire is not None and source != wire:
                    # narrow on host: the wire carries wire bytes
                    arr = arr.astype(wire)
                target = compute if compute is not None else source
                if arr.shape[0] != self.chunk_size:
                    # ragged tail: pad to the one shared chunk shape.
                    # The explicit guard (rather than _pad_to's own
                    # no-op short-circuit) keeps the full-chunk
                    # zero-copy invariant ASSERTABLE — the regression
                    # test monkeypatches _pad_to to prove full chunks
                    # never reach it.
                    arr = _pad_to(arr, self.chunk_size)
                h2d_bytes += float(arr.nbytes) * replication
                work_nbytes += float(arr.size * np.dtype(target).itemsize)
                needs_cast = needs_cast or target != arr.dtype
                staged.append(shard_put(arr, sh, pool))
                casts.append(np.dtype(target))
            lanes = 1
            if pool is not None:
                try:
                    # actual staging concurrency: shard puts in flight
                    # are bounded by BOTH the pool and the shard count
                    lanes = max(1, min(h2d_workers(),
                                       len(sh.addressable_devices)))
                except Exception:
                    lanes = 1
            data = jax.tree_util.tree_unflatten(treedef, staged)
            meta = {
                "h2d_bytes": h2d_bytes,
                "work_nbytes": work_nbytes,
                "stage_lanes": lanes,
                "stage_s": time.perf_counter() - t0,
                "cast": (treedef, tuple(casts)) if needs_cast else None,
            }
            return (ArrayDataset(data, rows, self.mesh,
                                 _already_sharded=True), meta)

        return self.retry_policy.call(put, site="ingest.stage")

    def _device_cast(self, ad: ArrayDataset, cast_spec: Tuple) -> ArrayDataset:
        """The fused wire->compute cast the chunk executor prepends to
        the transform chain: one GLOBALLY memoized program per chunk
        structure/dtype family (``_cast_program``), so refits on fresh
        streams of the same shape compile nothing."""
        treedef, casts = cast_spec
        fn = _cast_program(treedef, casts)
        return ArrayDataset(fn(ad.data), ad.n, self.mesh,
                            _already_sharded=True)

    def chunks(self) -> Iterator[ArrayDataset]:
        """Iterate device chunks with background prefetch. Each call
        re-opens the source (a fresh epoch); breaking out of the loop
        stops the producer thread."""
        reg = MetricsRegistry.get_or_create()
        # the queue itself is unbounded; SLOTS is the bound, acquired
        # BEFORE staging so at most prefetch_depth chunks are ever
        # staged-or-queued at once. Gating the queue alone would let the
        # producer stage chunk depth+1 while blocked on a full queue,
        # putting (depth + 2) chunks live against the documented
        # (depth + 1)-chunk budget (review finding, reproduced).
        q: queue.Queue = queue.Queue()
        slots = TracedSemaphore("stream.slots", self.prefetch_depth)
        stop = threading.Event()
        # interpreter-exit teardown: _shutdown_live_streams sets this
        # before the H2D pool is torn down, so an active producer exits
        # its slot wait instead of racing pool shutdown
        _LIVE_STREAM_STOPS.add(stop)
        it_ledger = _IterLedger()

        def acquire_slot() -> bool:
            while not stop.is_set():
                if slots.acquire(timeout=0.05):
                    return True
            return False

        def produce():
            try:
                produced = 0
                for raw in self._chunk_source():
                    # named fault site for producer hangs/stalls; abort
                    # wakes a "hang" injection when the consumer leaves
                    inject("ingest.produce", context=self.tag or "stream",
                           abort=stop.is_set)
                    if not acquire_slot():
                        return
                    t_stage = time.perf_counter()
                    ad, meta = self._stage(raw)
                    # the prefetch lane of the flight-recorder timeline:
                    # one span per chunk on this producer thread, so
                    # ingest-vs-compute overlap is visually inspectable
                    # in the Perfetto export
                    record_span(f"stage:{self.tag or 'stream'}", "ingest",
                                t_stage, time.perf_counter() - t_stage,
                                args={"chunk": produced,
                                      "h2d_bytes": meta["h2d_bytes"]})
                    produced += 1
                    nbytes = device_nbytes(ad)
                    reg.counter("streaming.h2d_bytes").inc(
                        meta["h2d_bytes"])
                    self._residency.stage(it_ledger, nbytes)
                    q.put((ad, nbytes, meta))
                q.put(_DONE)
            except BaseException as exc:  # surfaced on the consumer side
                q.put(_SourceError(exc))
            finally:
                if stop.is_set():
                    # the consumer is gone (early exit) — it may have
                    # closed the ledger while this thread was still
                    # inside _stage() (its bounded join timed out), so
                    # remove whatever this iteration still holds;
                    # close() is idempotent over an already-zeroed
                    # ledger, so racing the consumer's close is safe
                    self._residency.close(it_ledger)

        producer = threading.Thread(
            target=produce, name="keystone-stream-prefetch", daemon=True)
        producer.start()
        seen = 0
        rows_seen = 0
        complete = False
        trace = current_trace()
        def get_with_watchdog(t0: float):
            """Heartbeat loop around ``q.get``: wakes once a second to
            notice a dead producer thread (nothing more is coming —
            raise instead of blocking forever) and, when
            ``stall_timeout_s`` is set, enforces the ingest deadline.
            Zero-cost while chunks flow: the timeout only matters when
            the consumer is already starved."""
            deadline = (None if self.stall_timeout_s is None
                        else t0 + self.stall_timeout_s)
            while True:
                wait = 1.0
                if deadline is not None:
                    wait = min(wait, max(deadline - time.perf_counter(),
                                         0.01))
                try:
                    return q.get(timeout=wait)
                except queue.Empty:
                    starved_s = time.perf_counter() - t0
                    if not producer.is_alive() and q.empty():
                        record_event("watchdog_trip",
                                     source=self.tag or "stream",
                                     reason="producer_died", chunk=seen)
                        # the post-mortem carries the flight recorder's
                        # last spans + the metrics snapshot — what the
                        # producer was doing when it died, not just
                        # that it did
                        raise attach_postmortem(IngestTimeoutError(
                            f"stream {self.tag or '<untagged>'}: the "
                            f"producer thread died without completing "
                            f"the stream (after chunk {seen})"),
                            "ingest_timeout",
                            {"source": self.tag or "stream",
                             "reason": "producer_died", "chunk": seen})
                    if (deadline is not None
                            and time.perf_counter() >= deadline):
                        record_event("watchdog_trip",
                                     source=self.tag or "stream",
                                     reason="stall_deadline", chunk=seen,
                                     stall_s=starved_s)
                        raise attach_postmortem(IngestTimeoutError(
                            f"stream {self.tag or '<untagged>'}: no "
                            f"chunk from the producer in "
                            f"{starved_s:.1f}s (stall_timeout_s="
                            f"{self.stall_timeout_s:g}, after chunk "
                            f"{seen}; producer thread alive) — hung "
                            "source? Raise stall_timeout_s if the "
                            "source is legitimately this slow."),
                            "ingest_timeout",
                            {"source": self.tag or "stream",
                             "reason": "stall_deadline", "chunk": seen,
                             "stall_s": starved_s})

        try:
            while True:
                t0 = time.perf_counter()
                item = get_with_watchdog(t0)
                stall = time.perf_counter() - t0
                if item is _DONE:
                    complete = True
                    break
                if isinstance(item, _SourceError):
                    raise item.exc
                ad, nbytes, meta = item
                occupancy = q.qsize()
                cast_spec = meta["cast"]
                # working footprint is the POST-cast copy; during the
                # cast the wire copy transiently co-exists with it
                self._residency.hand_off(
                    it_ledger, nbytes, meta["work_nbytes"],
                    transient=nbytes if cast_spec is not None else 0.0)
                # the chunk left the buffer: free its staging slot so
                # the producer can stage the next one while this chunk
                # computes — steady state is depth staged + 1 working
                slots.release()
                reg.histogram("streaming.ingest_stall_s").observe(stall)
                reg.gauge("streaming.prefetch_occupancy").set(occupancy)
                reg.counter("streaming.chunks_total").inc()
                # the sampler scrapes residency as a gauge; the stall
                # span is the consumer-side lane of the flight timeline
                reg.gauge("streaming.resident_bytes").set(
                    self._residency.live())
                record_span(f"stall:{self.tag or 'stream'}", "ingest",
                            t0, stall, args={"chunk": seen})
                if trace is not None:
                    trace.record_chunk({
                        "source": self.tag or "stream",
                        "chunk": seen,
                        "n": ad.n,
                        "padded_n": ad.padded_n,
                        "nbytes": meta["work_nbytes"],
                        "h2d_bytes": meta["h2d_bytes"],
                        "stage_lanes": meta["stage_lanes"],
                        "stage_s": meta["stage_s"],
                        "ingest_stall_s": stall,
                        "prefetch_occupancy": occupancy,
                    })
                out = ad
                chunk_rows = ad.n
                if cast_spec is not None:
                    # fused on-device cast to the compute dtype,
                    # prepended to the transform chain; drop the wire
                    # copy's reference so it frees as soon as the cast
                    # completes (the ledger charges it only transiently)
                    out = self._device_cast(out, cast_spec)
                    ad = item = None
                for f in self._transforms:
                    out = f(out)
                yield out
                seen += 1
                rows_seen += chunk_rows
        finally:
            stop.set()
            # join BEFORE closing the ledger: a producer mid-_stage()
            # at early exit would otherwise call stage() after the
            # close and permanently inflate the shared residency (the
            # next epoch's budget assert would then trip spuriously);
            # close() removes only THIS iteration's contribution, so a
            # concurrently running sibling iteration stays accounted.
            # A generator still suspended at interpreter exit is
            # finalized after `threading` has cleared its globals
            # (join() then raises TypeError under Python 3.12); by then
            # _shutdown_live_streams has stopped the daemon producer
            # and there is no next epoch to account for
            if not sys.is_finalizing():
                producer.join(timeout=5.0)
            self._residency.close(it_ledger)
            _LIVE_STREAM_STOPS.discard(stop)
        if complete and self.n is None:
            self.n = rows_seen  # a full pass pins the unknown length

    def __iter__(self) -> Iterator[ArrayDataset]:
        return self.chunks()

    def buffered_nbytes(self) -> float:
        """Current device residency of this stream: chunks staged in the
        prefetch buffer plus the working chunk handed to the consumer.
        ``parallel.dataset.device_nbytes`` reports this for streams, so
        the out-of-core HBM bound is assertable from the outside."""
        return self._residency.live()

    def chunk_nbytes(self) -> float:
        """Footprint of one STAGED chunk at its wire width. With no
        wire narrowing the budget unit is simply ``budget >=
        (prefetch_depth + 1) * chunk_nbytes``; with a narrow wire the
        working chunk is cast wider on device, so size budgets as
        ``depth * chunk_nbytes + (compute_itemsize / wire_itemsize) *
        chunk_nbytes`` plus one transient wire chunk during the cast
        (e.g. u8 wire -> f32 compute: ``depth * w + 4w + w``)."""
        return self._residency.chunk_nbytes

    @property
    def peak_device_nbytes(self) -> float:
        """High-water mark of the stream's device residency (shared
        across a root stream and its derived views)."""
        return self._residency.peak

    # -- static HBM planning (analysis.resources) --------------------------
    def plan_geometry(self):
        """Static chunk geometry
        (:class:`~keystone_tpu.analysis.resources.StreamGeometry`) when
        the source's element can be described without consuming the
        stream, else None. Derived (mapped) views delegate to their
        ROOT: the residency ledger is shared, so the plan must describe
        the one real prefetch pipeline regardless of which handle the
        caller kept."""
        root_fn = self.__dict__.get("_plan_geometry")
        if root_fn is not None:
            return root_fn()
        probe = getattr(self, "_element_probe", None)
        if probe is None:
            return None
        el = probe()
        from ..analysis.spec import element_has_unknown

        if el is None or element_has_unknown(el):
            return None
        leaves, treedef = jax.tree_util.tree_flatten(el)
        try:
            wire_t = _policy_leaves(self.wire_dtype, treedef, len(leaves))
            comp_t = _policy_leaves(self.compute_dtype, treedef,
                                    len(leaves))
        except ValueError:
            return None  # structure mismatch raises at stage time
        wire_row = work_row = 0.0
        cast = False
        for s, wire, comp in zip(leaves, wire_t, comp_t):
            size = float(np.prod(s.shape)) if s.shape else 1.0
            source = np.dtype(s.dtype)
            wd = wire if wire is not None else source
            cd = comp if comp is not None else source
            wire_row += size * np.dtype(wd).itemsize
            work_row += size * np.dtype(cd).itemsize
            cast = cast or np.dtype(cd) != np.dtype(wd)
        from ..analysis.resources import StreamGeometry

        return StreamGeometry(
            chunk_rows=self.chunk_size, prefetch_depth=self.prefetch_depth,
            wire_row_nbytes=wire_row, work_row_nbytes=work_row, cast=cast)

    def static_plan_nbytes(self) -> Optional[float]:
        """Device-free residency bound for one live iteration of this
        stream — ``prefetch_depth`` staged wire-width chunks + one
        post-cast working chunk + one transient wire chunk during the
        cast — charging exactly what the runtime ``_Residency`` ledger
        charges, so ``peak_device_nbytes`` can never exceed it.
        ``fit_streaming`` checks ``hbm_budget`` against this BEFORE the
        first chunk is staged (budgets are checked twice), and the
        active trace records it next to the measured peak."""
        geom = self.plan_geometry()
        return None if geom is None else geom.plan_nbytes()

    # -- element spec (static analysis) ------------------------------------
    def element(self) -> Optional[Any]:
        """Per-item element spec (``jax.ShapeDtypeStruct`` pytree) if it
        can be described without consuming the stream, else None. Known
        exactly for numpy/item-backed sources (their first item is
        inspectable); chunked opaque sources return None -> the analyzer
        carries an Unknown element but still knows it is a stream. The
        spec describes what CONSUMERS see: with an explicit
        ``compute_dtype`` the leaves report that dtype (the wire dtype
        rides separately in ``DatasetSpec.wire_dtype`` so the
        dtype-narrowing lint never false-fires on a deliberately
        narrow wire)."""
        probe = getattr(self, "_element_probe", None)
        if probe is None:
            return None
        el = probe()
        if el is None or self.compute_dtype is None:
            return el

        def recast(s, dt):
            if dt is None or not isinstance(s, jax.ShapeDtypeStruct):
                return s
            return jax.ShapeDtypeStruct(tuple(s.shape), np.dtype(dt))

        if isinstance(self.compute_dtype, np.dtype):
            return jax.tree_util.tree_map(
                lambda s: recast(s, self.compute_dtype), el)
        # pytree policy: per-leaf targets mirror the element tree
        el_leaves, el_td = jax.tree_util.tree_flatten(el)
        p_leaves, p_td = jax.tree_util.tree_flatten(
            self.compute_dtype, is_leaf=lambda x: x is None)
        if el_td != p_td:
            return el  # mismatch resolves (or raises) at stage time
        return jax.tree_util.tree_unflatten(
            el_td, [recast(s, d) for s, d in zip(el_leaves, p_leaves)])

    def wire_dtype_name(self) -> Optional[str]:
        """Canonical printable identity of the explicit wire dtype
        policy (None when the wire carries the source's native dtypes)
        — folded into ``DatasetSpec`` and the resume fingerprint."""
        return _policy_name(self.wire_dtype)

    def compute_dtype_name(self) -> Optional[str]:
        """Printable identity of the compute dtype policy (resume
        fingerprint)."""
        return _policy_name(self.compute_dtype)

    # -- materialization ---------------------------------------------------
    def materialize(self) -> ArrayDataset:
        """Collect every chunk to one resident ArrayDataset (parity
        tests, small streams). Defeats the purpose for big data — the
        point of streaming is never doing this."""
        parts: List[Any] = []
        n = 0
        for chunk in self.chunks():
            parts.append(chunk.numpy())
            n += chunk.n
        if not parts:
            raise ValueError("empty stream")
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *parts)
        return ArrayDataset(stacked, n, self.mesh, tag=self.tag)

    def collect(self) -> List[Any]:
        return self.materialize().collect()

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_chunks(factory: Callable[[], Iterator[Any]], chunk_size: int,
                    n: Optional[int] = None, **kw) -> "StreamingDataset":
        """Stream pre-stacked host chunks from ``factory()`` (e.g. the
        tar decode pool via ``loaders.image_loader_utils``)."""
        return StreamingDataset(factory, chunk_size, n=n, **kw)

    @staticmethod
    def from_items(items: Optional[Sequence[Any]] = None, *,
                   source: Optional[Callable[[], Iterable[Any]]] = None,
                   chunk_size: int = 256, **kw) -> "StreamingDataset":
        """Stream per-item pytrees (a sequence, or ``source=`` callable
        yielding items), stacked into chunks of ``chunk_size``."""
        if (items is None) == (source is None):
            raise TypeError("pass exactly one of items or source=")
        if source is None:
            seq = list(items)
            source = lambda: iter(seq)  # noqa: E731
            kw.setdefault("n", len(seq))

        def chunked():
            buf: List[Any] = []
            for it in source():
                buf.append(it)
                if len(buf) == chunk_size:
                    yield jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs), *buf)
                    buf = []
            if buf:
                yield jax.tree_util.tree_map(lambda *xs: np.stack(xs), *buf)

        out = StreamingDataset(chunked, chunk_size, **kw)
        if items is not None and seq:
            from ..analysis.spec import struct_of

            out._element_probe = lambda: struct_of(seq[0])
        return out

    @staticmethod
    def from_numpy(array: Any, chunk_size: int, mesh: Optional[Mesh] = None,
                   **kw) -> "StreamingDataset":
        """Chunk a resident host pytree (the parity/testing path, and
        the honest way to bound HBM when host RAM holds what HBM
        cannot)."""
        leaves = jax.tree_util.tree_leaves(array)
        if not leaves:
            raise ValueError("empty pytree")
        total = int(np.shape(leaves[0])[0])

        def chunked():
            for lo in range(0, total, chunk_size):
                yield jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[lo:lo + chunk_size], array)

        out = StreamingDataset(chunked, chunk_size, n=total, mesh=mesh, **kw)
        out._element_probe = lambda: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                tuple(np.shape(x)[1:]), np.asarray(x).dtype), array)
        return out

    @staticmethod
    def from_host_dataset(ds: HostDataset, chunk_size: int,
                          **kw) -> "StreamingDataset":
        return StreamingDataset.from_items(
            [np.asarray(x) for x in ds.items], chunk_size=chunk_size, **kw)


# -- accumulate/finalize protocol ------------------------------------------

def is_streamable(estimator: Any) -> bool:
    """True when ``estimator`` implements the streaming fit protocol:
    ``accumulate(carry, chunk[, labels_chunk])`` + ``finalize(carry)``."""
    return callable(getattr(estimator, "accumulate", None)) and callable(
        getattr(estimator, "finalize", None))


def _non_streamable_error(estimator: Any) -> TypeError:
    label = getattr(estimator, "label", None)
    name = label() if callable(label) else type(estimator).__name__
    return TypeError(
        f"estimator {name!r} cannot fit a StreamingDataset: it does not "
        "implement the streaming protocol (accumulate(carry, chunk[, "
        "labels]) / finalize(carry)). Materialize the stream first "
        "(StreamingDataset.materialize()) if it fits in HBM, or use a "
        "streamable estimator (LeastSquares family, StandardScaler). "
        "`python -m keystone_tpu check` flags this statically as "
        "'non-streamable-fit'. README 'Streaming ingest' / 'Resilience' "
        "document the streaming fit and checkpoint/resume API.")


def _paired_chunks(data: StreamingDataset,
                   labels: Any) -> Iterator[Tuple[ArrayDataset,
                                                  Optional[ArrayDataset]]]:
    """Yield (data_chunk, labels_chunk) with IDENTICAL padded shapes.

    ``labels`` may be None (plain estimators), an aligned
    StreamingDataset (chunk row counts must match), or a resident
    dataset/array sliced by running offset (labels are k-wide — tiny
    next to the streamed features, so residency is fine).
    """
    if labels is None:
        for chunk in data.chunks():
            yield chunk, None
        return
    if isinstance(labels, StreamingDataset):
        data_it, labels_it = data.chunks(), labels.chunks()
        for chunk in data_it:
            try:
                lchunk = next(labels_it)
            except StopIteration:
                raise ValueError(
                    "labels stream ended before the data stream")
            if lchunk.n != chunk.n:
                raise ValueError(
                    f"misaligned streams: data chunk has {chunk.n} rows, "
                    f"labels chunk has {lchunk.n}")
            yield chunk, lchunk
        # the mirrored check: leftover label chunks mean the pairs were
        # row-shifted — silently truncating would fit a wrong model
        try:
            next(labels_it)
        except StopIteration:
            return
        raise ValueError("misaligned streams: labels stream has more "
                         "rows than the data stream")
    # resident labels: slice rows to follow the stream
    from .dataset import to_numpy

    host = to_numpy(labels)
    sh = batch_sharding(data.mesh)
    off = 0
    for chunk in data.chunks():
        rows = host[off:off + chunk.n]
        if rows.shape[0] != chunk.n:
            raise ValueError(
                f"labels exhausted at row {off}: stream yielded more "
                f"rows than len(labels)={host.shape[0]}")
        off += chunk.n
        padded = jax.device_put(_pad_to(rows, chunk.padded_n), sh)
        yield chunk, ArrayDataset(
            padded, chunk.n, data.mesh, _already_sharded=True)
    if off != host.shape[0]:
        raise ValueError(
            f"misaligned labels: the data stream yielded {off} rows but "
            f"len(labels)={host.shape[0]} — refusing to silently "
            "truncate. If the stream shrank because corrupt records "
            "were quarantined (check stream.quarantine.summary()), drop "
            "the matching label rows first with "
            "resilience.quarantine.drop_quarantined_rows(labels, "
            "record_keys, stream.quarantine); otherwise pair the stream "
            "with labels derived from the same decode pass")


def _restore_carry(host_carry: Any, mesh: Mesh) -> Any:
    """Put a checkpoint's host-side carry back EXACTLY where a live
    carry sits: array leaves replicated on the chunk mesh (the same
    ``NamedSharding(mesh, P())`` the zero inits use), 0-d leaves back
    to host scalars. jax's jit cache keys on input shardings, so a
    resumed fit whose first accumulate saw a raw numpy carry would
    compile a SECOND program — one unexpected compile under the warmup
    fence, on every resume (the same placement discipline
    ``SketchTracker.restore`` already applies to the drift counts)."""
    sh = replicated_sharding(mesh)

    def put(leaf):
        arr = np.asarray(leaf)
        if arr.ndim == 0 and np.issubdtype(arr.dtype, np.integer):
            # the host int (n) the driver loop reads — live carries
            # keep it a Python int. A 0-d FLOAT leaf stays a device
            # array: collapsing it to a weak-typed Python float would
            # change the resumed accumulate's jit signature (and its
            # promotion semantics), exactly the miss this helper
            # prevents.
            return arr.item()
        return jax.device_put(arr, sh)

    return jax.tree_util.tree_map(put, host_carry)


# ONE copy program per carry structure (jit re-specializes per leaf
# shapes/dtypes, so the module-level handle is safe to share): jnp.copy,
# NOT ``x + 0`` — adding zero flips -0.0 to +0.0 and a snapshot that is
# not BIT-identical with the carry it cuts breaks the kill-and-resume
# bit-identity contract in the last ulp.
_copy_carry_leaves = jax.jit(lambda leaves: [jnp.copy(leaf) for leaf in leaves])


def _snapshot_carry_async(carry: Any):
    """Start copying the live carry to host WITHOUT blocking the
    stream. The jitted copy enqueues AFTER the round's accumulates
    (per-device execution order is dispatch order) and BEFORE the next
    round's accumulates can donate the buffers — so the copy is a
    consistent cut at the quiesced round boundary even with donation
    on — then ``copy_to_host_async`` starts the D2H transfer behind
    the next round's compute. Host leaves (the Python int cursor
    ``n``) pass through untouched. Materialize the returned handle
    with :func:`_materialize_snapshot` one boundary later."""
    if carry is None:
        return None
    leaves, treedef = jax.tree_util.tree_flatten(carry)
    device_ix = [i for i, leaf in enumerate(leaves)
                 if isinstance(leaf, jax.Array)]
    copies = (_copy_carry_leaves([leaves[i] for i in device_ix])
              if device_ix else [])
    for cp in copies:
        cp.copy_to_host_async()
    return (treedef, leaves, device_ix, copies)


def _materialize_snapshot(snap: Any) -> Any:
    """Land a :func:`_snapshot_carry_async` handle on host. Called one
    round boundary after the cut, when the async copy has drained
    behind the interleaved compute — so the ``np.asarray`` here blocks
    on (almost) nothing."""
    if snap is None:
        return None
    treedef, leaves, device_ix, copies = snap
    out = list(leaves)
    for i, cp in zip(device_ix, copies):
        out[i] = np.asarray(cp)
    return jax.tree_util.tree_unflatten(treedef, out)


def fit_streaming(estimator: Any, data: StreamingDataset,
                  labels: Any = None, hbm_budget: Optional[float] = None,
                  checkpoint_dir: Optional[str] = None,
                  checkpoint_every: Optional[int] = None,
                  quarantine: Any = None):
    """Drive a streamable estimator over a chunked dataset: one
    ``accumulate`` per chunk, then ``finalize`` — the featurized matrix
    never exists on device, only the carry (Gram/cross/moments) and the
    bounded prefetch buffer do.

    ``hbm_budget`` (bytes), when given, asserts after every chunk that
    the stream's device residency (prefetch buffer + working chunk) has
    stayed within ``budget``: the out-of-core guarantee, checkable.

    Checkpoint/resume (:mod:`keystone_tpu.resilience`): with
    ``checkpoint_dir`` set, every ``checkpoint_every`` chunks (default
    16) the (chunk cursor, estimator carry, quarantine state, config
    fingerprint) is snapshotted atomically. A later call with the same
    configuration resumes from the snapshot — already-accumulated
    chunks are re-ingested but NOT re-accumulated, so the resumed
    weights are bit-comparable with an uninterrupted run. A snapshot
    from a DIFFERENT configuration (estimator params, chunk size,
    labels kind) raises ``CheckpointMismatchError`` instead of silently
    resuming wrong state; the snapshot is cleared after a successful
    finalize.

    ``quarantine`` (a :class:`~keystone_tpu.resilience.Quarantine`,
    usually the one wired into the stream's decode pool) rides the
    checkpoint so a resumed fit keeps its corrupt-record accounting.

    Donated carries (``utils.donation``): on TPU/GPU the accumulate
    jits donate the carry buffers, so the loop below must never touch a
    carry after passing it back in — it reassigns immediately, and the
    checkpoint save copies the carry to HOST (``np.asarray``) before
    the next accumulate donates it, which is what keeps kill-and-resume
    bit-identical with donation on.

    **Elastic multi-host mode** (engaged automatically under a live
    ``jax.distributed`` world, :mod:`keystone_tpu.parallel.distributed`):
    ``data`` is this host's SHARD-LOCAL stream on a
    :func:`~keystone_tpu.parallel.mesh.local_mesh` (each host decodes
    and stages only its own shards), hosts meet every
    ``checkpoint_every`` chunks in a fixed-shape coordination round —
    same round count on every host, coordinated snapshots written as
    per-host sidecars folded by host 0 into ONE world snapshot in the
    (shared) ``checkpoint_dir`` — and at finalize the carries
    tree-reduce across hosts so every host solves the same merged
    carry into bit-identical weights. A killed world relaunched at the
    SAME size resumes each host from its recorded cursor
    (bit-identical with the uninterrupted run); a different world size
    raises ``CheckpointMismatchError``. CLUSTER.md "Elastic resume"
    is the runbook.
    """
    if not is_streamable(estimator):
        raise _non_streamable_error(estimator)
    if checkpoint_every is not None and checkpoint_dir is None:
        raise ValueError("checkpoint_every requires checkpoint_dir")
    # budgets are checked twice (PERFORMANCE.md): the static plan —
    # depth staged wire chunks + one post-cast working chunk + the cast
    # transient, exactly what the ledger will charge — rejects a
    # config that cannot fit BEFORE any chunk is decoded or staged;
    # the per-chunk runtime assert below stays as the ground truth for
    # opaque sources the plan cannot describe
    plan_fn = getattr(data, "static_plan_nbytes", None)
    static_plan = plan_fn() if callable(plan_fn) else None
    if (static_plan is not None and hbm_budget is not None
            and static_plan > hbm_budget):
        raise attach_postmortem(MemoryError(
            f"streamed fit would exceed its HBM budget before any chunk "
            f"is staged: static plan {static_plan:.0f} B (prefetch_depth "
            f"x staged chunk + working chunk + cast transient) > "
            f"{hbm_budget:.0f} B — shrink chunk_size or prefetch_depth "
            "(PERFORMANCE.md 'plan HBM statically'; `python -m "
            "keystone_tpu check --budget` predicts this device-free)"),
            "hbm_budget",
            {"source": data.tag or "stream", "phase": "static_plan",
             "static_plan_nbytes": static_plan, "hbm_budget": hbm_budget})
    if quarantine is None:
        # a stream built by a quarantining loader carries its own
        # (stream_tar_images); use it so checkpoints keep the accounting
        quarantine = getattr(data, "quarantine", None)
    tag = data.tag or "stream"
    # elastic multi-host mode (parallel.distributed): under a live
    # jax.distributed world each host accumulates its SHARD-LOCAL
    # stream and the hosts meet at round boundaries — coordinated
    # checkpoints, same round count everywhere, carries tree-reduced
    # at finalize (CLUSTER.md "Elastic resume")
    world = None
    from .distributed import is_distributed

    if is_distributed():
        from .distributed import WorldCoordinator

        world = WorldCoordinator(tag=tag)
    ckpt = None
    fingerprint = None
    start_chunk = 0
    carry = None
    numerics_state = None
    if checkpoint_dir is not None:
        from ..resilience.stream_checkpoint import (
            StreamCheckpoint,
            fit_fingerprint,
        )

        checkpoint_every = (16 if checkpoint_every is None
                            else int(checkpoint_every))
        if checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        fingerprint = fit_fingerprint(estimator, data, labels)
        ckpt = StreamCheckpoint(checkpoint_dir)
        snap = (ckpt.load(fingerprint) if world is None
                else ckpt.load_world(fingerprint, world.pid, world.nproc))
        if snap is not None:
            start_chunk = int(snap["cursor"])
            carry = (None if snap["carry"] is None
                     else _restore_carry(snap["carry"], data.mesh))
            if quarantine is not None and snap.get("quarantine"):
                quarantine.restore(snap["quarantine"])
            numerics_state = snap.get("numerics")
    takes_labels = labels is not None
    chunks_seen = 0
    idx = -1
    reg = MetricsRegistry.get_or_create()
    # the numerics plane (observability/numerics.py): one fused health
    # word per chunk (deferred D2H, tripwire on non-finite) and the
    # drift-baseline feature sketch, both riding the accumulate pass —
    # no extra data pass, and their programs compile during chunk 1,
    # before the fit fence arms. KEYSTONE_NUMERICS=0 disables both.
    monitor = HealthMonitor(tag) if numerics_active() else None
    sketch = SketchTracker(source=tag) if numerics_active() else None
    if sketch is not None and numerics_state is not None:
        # resume: the restored sketch makes kill-and-resume baselines
        # bit-identical with an uninterrupted fit (replayed chunks are
        # skipped below, exactly like the carry)
        sketch.restore(numerics_state, data.mesh)
    from ..observability.compilelog import compile_observatory, is_device_oom

    obs = compile_observatory()
    fence_armed = False

    def accumulate_one(chunk, lchunk):
        """Fold one chunk into the carry: the shared per-chunk body of
        the single-process loop and the distributed round loop."""
        nonlocal carry, chunks_seen
        t_acc = time.perf_counter()
        try:
            if takes_labels:
                carry = estimator.accumulate(carry, chunk, lchunk)
            else:
                carry = estimator.accumulate(carry, chunk)
        except Exception as exc:
            if is_device_oom(exc):
                # the allocator failed mid-accumulate: the dump must
                # say WHICH executables' argument/output/temp bytes
                # held HBM, so resolve per-executable
                # memory_analysis tables into it (AOT, no execution)
                raise attach_postmortem(
                    exc, "device_oom",
                    {"source": tag, "phase": "accumulate",
                     "chunk": idx},
                    capture_executables=True)
            raise
        # the compute lane of a streamed fit's flight timeline (host
        # wall of the accumulate dispatch — jax async work continues
        # past it, which is exactly the overlap the lanes show)
        record_span(f"accumulate:{tag}", "compute", t_acc,
                    time.perf_counter() - t_acc, args={"chunk": idx})
        if monitor is not None:
            # one small device reduction per chunk; the host pull
            # is deferred `monitor.defer` chunks so it never stalls
            # the ingest/compute overlap. Raises NumericsError
            # (with a post-mortem) on a non-finite chunk. The mask
            # keeps a zero-padded ragged tail out of the series'
            # min/mean/var.
            monitor.observe(idx, chunk.data,
                            None if lchunk is None else lchunk.data,
                            mask=chunk.mask)
        if sketch is not None:
            sketch.update(chunk)
        reg.gauge("streaming.carry_bytes").set(sum(
            float(getattr(leaf, "nbytes", 0) or 0)
            for leaf in jax.tree_util.tree_leaves(carry)))
        chunks_seen += 1
        if hbm_budget is not None:
            resident = data.buffered_nbytes()
            if resident > hbm_budget:
                raise attach_postmortem(MemoryError(
                    f"streamed fit exceeded its HBM budget: "
                    f"{resident:.0f} B resident > {hbm_budget:.0f} B "
                    f"(chunk {chunks_seen}; shrink chunk_size or "
                    "prefetch_depth)"),
                    "hbm_budget",
                    {"source": tag, "phase": "runtime",
                     "resident_nbytes": resident,
                     "hbm_budget": hbm_budget, "chunk": chunks_seen},
                    capture_executables=True)

    def snapshot_states():
        if monitor is not None:
            # drain pending health words first: a snapshot must
            # never capture a carry poisoned by a chunk whose
            # word was still in flight (the save syncs the
            # carry to host anyway, so this adds no new bubble)
            monitor.flush()
        return (None if quarantine is None else quarantine.state(),
                None if sketch is None else sketch.state())

    try:
        if world is None:
            for chunk, lchunk in _paired_chunks(data, labels):
                idx += 1
                if idx < start_chunk:
                    continue  # resume replay: already folded in
                accumulate_one(chunk, lchunk)
                if ckpt is not None and (idx + 1) % checkpoint_every == 0:
                    q_state, n_state = snapshot_states()
                    ckpt.save(fingerprint, idx + 1, carry, q_state,
                              numerics=n_state)
                if chunks_seen == 1 and not fence_armed:
                    # per-chunk compile fence: every later chunk shares
                    # this chunk's padded shape, so steady state must
                    # compile NOTHING (the PR 3 zero-recompile
                    # invariant, asserted dynamically) — any compile
                    # recorded from here to the last chunk is
                    # classified unexpected, named with its signature
                    # delta
                    obs.arm_fence(f"fit_streaming:{tag}")
                    fence_armed = True
        else:
            # the distributed OVERLAPPED round loop: every host folds
            # up to round_len shard-local chunks, DISPATCHES its round
            # collective (step_begin — JAX async dispatch; the gloo
            # exchange proceeds on backend threads), and only awaits
            # the PREVIOUS round (step_await) — so round k's
            # coordination hides behind round k+1's accumulates. The
            # SPMD contract still holds by construction: the awaited
            # state sequence is identical on every host, so every host
            # runs the same round count and breaks at the same
            # boundary (a host whose shard exhausts early keeps
            # stepping with done=1 until all_done).
            #
            # Checkpoints coalesce into the round exchange — zero
            # extra collectives. At each boundary a host cuts an ASYNC
            # host copy of its carry (a quiesced-boundary cut: the
            # copy enqueues before the next round's accumulates can
            # donate the buffers), writes the sidecar one boundary
            # LATER (the copy has drained behind the compute), and
            # reports the durably-written cursor in the NEXT round's
            # payload. Host 0 merges the world snapshot only after
            # AWAITING a round in which every host reported a sidecar:
            # the allgather itself is the happens-before the old
            # ckpt-sidecars/ckpt-world barrier pair provided. A
            # sidecar may trail its host's live cursor by one round;
            # resume re-accumulates that round's chunks — the normal
            # replay path, still bit-identical.
            if checkpoint_every is not None:
                round_len = int(checkpoint_every)
            else:
                raw_len = os.environ.get("KEYSTONE_COORD_ROUND_LEN", "16")
                try:
                    round_len = int(raw_len)
                except ValueError:
                    raise ValueError(
                        "KEYSTONE_COORD_ROUND_LEN must be an integer "
                        "(chunks folded per coordination round), got "
                        f"{raw_len!r} — see CLUSTER.md 'Sizing the "
                        "coordination round'")
                if round_len < 1:
                    raise ValueError(
                        f"KEYSTONE_COORD_ROUND_LEN must be >= 1, got "
                        f"{round_len}")
            chunk_iter = _paired_chunks(data, labels)
            local_done = False
            last_saved_cursor = -1    # this host's last DURABLE sidecar
            last_merged_saved = None  # host 0: frontier at last merge
            pending = None            # dispatched-but-unawaited round
            pending_snap = None       # (cursor, async copy, q/n states)
            final_state = None
            while True:
                in_round = 0
                while in_round < round_len and not local_done:
                    try:
                        chunk, lchunk = next(chunk_iter)
                    except StopIteration:
                        local_done = True
                        break
                    idx += 1
                    if idx < start_chunk:
                        continue  # resume replay: already folded in
                    accumulate_one(chunk, lchunk)
                    in_round += 1
                # lagged sidecar write: the copy cut at the LAST
                # boundary drained behind this round's compute, and it
                # lands durably (atomic rename) BEFORE the dispatch
                # below reports its cursor to the world
                if pending_snap is not None:
                    snap_cursor, snap, q_state, n_state = pending_snap
                    ckpt.save_host(fingerprint, world.pid, snap_cursor,
                                   _materialize_snapshot(snap), q_state,
                                   numerics=n_state)
                    last_saved_cursor = snap_cursor
                    pending_snap = None
                new_pending = world.step_begin(
                    cursor=idx + 1, done=local_done,
                    has_carry=carry is not None,
                    saved_cursor=last_saved_cursor)
                # cut this boundary's snapshot (the copy rides the
                # same per-device queue, so it still precedes any
                # donation by the next round's accumulates) — only
                # when this host advanced since its last cut, so a
                # done host stops re-pickling unchanged state while
                # straggling peers keep working
                if ckpt is not None and idx + 1 != last_saved_cursor:
                    q_state, n_state = snapshot_states()
                    pending_snap = (idx + 1, _snapshot_carry_async(carry),
                                    q_state, n_state)
                if not fence_armed and chunks_seen >= 1:
                    # the distributed fence arms after the FIRST
                    # boundary's dispatch: by then the per-chunk
                    # programs, the fixed-shape round gather, and the
                    # carry-copy program have all compiled, so every
                    # later round — dispatch, await, snapshot cut —
                    # must compile nothing: the PR 9 invariant, held
                    # across process boundaries AND across the
                    # dispatch/await split (overlap adds zero compiles)
                    obs.arm_fence(f"fit_streaming:{tag}")
                    fence_armed = True
                if pending is not None:
                    state = world.step_await(pending)
                    # host 0's barrier-free merge: every saved_cursor
                    # in an AWAITED round was durable before its host
                    # dispatched that round, so the sidecars all exist
                    # — merge whenever the world's sidecar frontier
                    # moved (atomic sidecar renames mean a concurrent
                    # writer can only make a slice NEWER, never torn)
                    if (ckpt is not None and world.pid == 0
                            and min(state.saved_cursors) >= 0
                            and state.saved_cursors != last_merged_saved):
                        ckpt.merge_hosts(world.nproc)
                        last_merged_saved = state.saved_cursors
                    if state.all_done:
                        final_state = state
                        # drain the round dispatched above — every
                        # host observed all_done at the same awaited
                        # boundary, so every host drains the same
                        # final round and no handle is left in flight
                        world.step_await(new_pending)
                        break
                pending = new_pending
            if not all(final_state.carries):
                # an empty peer shard: every host learned it from the
                # same step exchange, so every host raises the SAME
                # error here — one host raising unilaterally would
                # leave its peers wedged in the finalize collective
                empty = [p for p, c in enumerate(final_state.carries)
                         if not c]
                raise ValueError(
                    f"empty stream: host(s) {empty} of {world.nproc} "
                    f"produced no chunks for {tag!r} — every host must "
                    "own at least one chunk (repack the data into >= "
                    "process_count shards, or shrink the world; "
                    "loaders.image_loader_utils.list_archive_paths "
                    "raises the same condition at listing time)")
    finally:
        if fence_armed:
            obs.disarm_fence()
    if monitor is not None:
        # the tail of the deferred window: a NaN born in the last few
        # chunks must trip HERE, before finalize turns it into
        # plausible-looking garbage weights
        monitor.flush()
    if carry is None:
        # world mode already raised the collective empty-shard error
        # above (every host together, from the same step exchange)
        raise ValueError("empty stream: nothing to fit")
    if world is not None:
        # the cross-host tree-reduce (the DriftBaseline.merge() shape,
        # ROADMAP item 2): gather every host's shard-local carry once
        # and fold in process order — Gram/cross/moment carries are
        # additive, so the merged carry equals the one a single host
        # would have accumulated over the whole dataset (to f32
        # rounding), and every host finalizes the SAME merged carry
        # into bit-identical weights. Estimators with non-additive
        # carries provide merge_carries(per_host_carries).
        carry = world.merge_carries(
            carry, reducer=getattr(estimator, "merge_carries", None))
    model = estimator.finalize(carry)
    # finalize-side tripwire: the solver recovery paths guarantee
    # finite weights, so a non-finite fitted array here is always a bug
    # worth a post-mortem (the 'garbage weights at finalize' failure)
    check_fitted(model, tag)
    if sketch is not None:
        baseline = sketch.baseline()
        if baseline is not None:
            if world is not None:
                # per-host sketches fold into one world baseline where
                # bin geometries agree (they were pinned per host from
                # local chunk 1); incompatible hosts are skipped with
                # the shortfall recorded — see
                # WorldCoordinator.merge_baselines
                baseline = world.merge_baselines(baseline)
            try:
                # rides the fitted model into saved-pipeline artifacts:
                # apply-time drift scoring needs the fit-time sketch
                model.numerics_baseline = baseline
            except (AttributeError, TypeError):
                pass  # __slots__ transformer: no attach surface
            record_numerics_event(
                "fit_baseline", source=tag, rows=baseline.rows,
                cols=int(len(baseline.cols)))
    if ckpt is not None:
        if world is not None:
            # all hosts must be past their finalize before the shared
            # snapshot disappears (a host crashing here would otherwise
            # find nothing to resume); host 0 owns the shared files
            world.barrier("finalize-clear")
            if world.pid == 0:
                ckpt.clear()
        else:
            ckpt.clear()
    trace = current_trace()
    if trace is not None:
        # close the plan-vs-measured loop: the static plan rides the
        # trace next to the ledger's measured high-water mark, so every
        # traced streamed fit continuously validates the planner model
        trace.record_streamed_fit({
            "source": data.tag or "stream",
            "chunks": chunks_seen,
            "static_plan_nbytes": static_plan,
            "peak_device_nbytes": float(data.peak_device_nbytes),
            "hbm_budget": hbm_budget,
            "processes": 1 if world is None else world.nproc,
        })
    return model
