"""Dataset: the distributed-collection substrate replacing Spark RDDs.

The reference framework's data model is ``RDD[T]`` — a lazily evaluated,
partitioned collection (SURVEY.md layer 0). The TPU-native equivalent is:

* `ArrayDataset` — a pytree of batch-major `jax.Array`s whose leading
  (example) dimension is sharded over the mesh ``data`` axis. Per-item
  transforms become ``jit(vmap(f))`` over the sharded batch, which is the
  analogue of the reference's per-partition GEMM batching
  (``utils/MatrixUtils.scala:48`` ``rowsToMatrixIter`` + per-partition map).
  Since shard counts must divide the leading dim, the batch is padded with
  zero rows up to a multiple of the shard count; ``n`` records the true
  item count and padded rows are re-zeroed after every map so linear
  reductions (sums, Grams) stay exact.
* `HostDataset` — a plain Python list of items for host-side stages
  (tokenization, ragged features, IO), the analogue of RDDs of JVM objects
  that never touch BLAS.

Laziness lives one level up, in ``workflow.expression`` (as in the
reference's ``workflow/graph/Expression.scala``) — datasets themselves are
eager, like a cached RDD.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.metrics import MetricsRegistry
from ..observability.timeline import flight_span
from .mesh import (
    DATA_AXIS,
    batch_sharding,
    get_mesh,
    h2d_pool,
    num_data_shards,
    shard_put,
)


def _h2d(x: np.ndarray, rows: int, sharding: NamedSharding) -> jax.Array:
    """Host rows -> the device, padded to ``rows``: the ``ingest:h2d``
    span (on the calling thread; the put is asynchronous, so the span is
    the host's part and ``nbytes`` what goes over the link) and the
    ``ingest.h2d_bytes`` counter. Per-device shard slices are fanned
    over the shared staging pool: the host slicing + H2D of shard k+1
    overlaps the transfer of shard k (same discipline as the streaming
    prefetcher's _stage; mesh.shard_put falls back to one device_put
    when the pool is disabled or the mesh has a single data shard). The
    span also says over how many data shards the rows were laid
    (``data_shards``, ``rows_a_shard``)."""
    x = _pad_to(x, rows)
    nbytes = int(x.nbytes)
    shards = num_data_shards(sharding.mesh)
    with flight_span("h2d", "ingest", nbytes=nbytes, data_shards=shards,
                     rows_a_shard=rows // shards):
        out = shard_put(x, sharding, h2d_pool())
    MetricsRegistry.get_or_create().counter("ingest.h2d_bytes").inc(nbytes)
    return out


def _pad_to(x: np.ndarray, rows: int) -> np.ndarray:
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad)


def is_streaming(ds: Any) -> bool:
    """True for chunked streaming datasets (``parallel.streaming``).
    Duck-typed on the chunk API so layers imported BELOW the streaming
    module (this one, ``workflow.transformer``, node rules) share one
    predicate without an import cycle; everything dispatching on
    streams goes through here."""
    return isinstance(ds, Dataset) and hasattr(ds, "map_chunks")


class Dataset:
    """Abstract distributed collection of items."""

    def map(self, fn: Callable[[Any], Any]) -> "Dataset":
        raise NotImplementedError

    def collect(self) -> List[Any]:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError

    def cache(self) -> "Dataset":
        return self


class ArrayDataset(Dataset):
    """Batch-major, mesh-sharded, zero-padded dataset of fixed-shape items.

    ``data`` is a pytree of arrays sharing leading dim ``padded_n``; rows at
    index >= n are zero. All arrays are sharded ``P('data')`` on ``mesh``.
    """

    def __init__(self, data: Any, n: int, mesh: Optional[Mesh] = None,
                 _already_sharded: bool = False, tag: Optional[str] = None):
        self.mesh = mesh or get_mesh()
        self.n = int(n)
        self.tag = tag  # stable identity for cross-session prefix reuse
        if _already_sharded:
            self.data = data
        else:
            self.data = _shard_pytree(data, self.n, self.mesh)

    # -- construction -----------------------------------------------------
    @staticmethod
    def from_numpy(array: Any, mesh: Optional[Mesh] = None,
                   tag: Optional[str] = None) -> "ArrayDataset":
        leaves = jax.tree_util.tree_leaves(array)
        if not leaves:
            raise ValueError("empty pytree")
        n = leaves[0].shape[0]
        return ArrayDataset(array, n, mesh, tag=tag)

    @staticmethod
    def from_items(items: Sequence[Any], mesh: Optional[Mesh] = None) -> "ArrayDataset":
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *items)
        return ArrayDataset.from_numpy(stacked, mesh)

    # -- properties -------------------------------------------------------
    @property
    def padded_n(self) -> int:
        return jax.tree_util.tree_leaves(self.data)[0].shape[0]

    @property
    def mask(self) -> jax.Array:
        """bool[padded_n], True for real rows."""
        return _row_mask(self.padded_n, self.n, self.mesh)

    def __len__(self) -> int:
        return self.n

    # -- transforms -------------------------------------------------------
    def map(self, fn: Callable[[Any], Any]) -> "ArrayDataset":
        """Apply a per-item pure function, batched via vmap under jit.

        ``fn`` must be pure: closure-free functions are traced once per
        input shape and the compiled program is reused across calls, so
        mutated globals would not be observed."""
        out = _masked_vmap(fn, self.data, self.n, self.padded_n, self.mesh)
        return ArrayDataset(out, self.n, self.mesh, _already_sharded=True)

    def map_batch(self, fn: Callable[[Any], Any]) -> "ArrayDataset":
        """Apply a whole-batch function (padded rows included; fn must keep
        leading dim and should preserve zero padding or rely on re-masking)."""
        out = fn(self.data)
        out = _apply_mask(out, self.n, self.mesh)
        return ArrayDataset(out, self.n, self.mesh, _already_sharded=True)

    def zip(self, *others: "ArrayDataset") -> "ArrayDataset":
        """Zip datasets of equal length into a dataset of tuples."""
        for o in others:
            if o.n != self.n:
                raise ValueError("zip requires equal lengths")
        data = (self.data,) + tuple(o.data for o in others)
        pn = max([self.padded_n] + [o.padded_n for o in others])
        data = jax.tree_util.tree_map(
            lambda x: _repad(x, pn, self.mesh), data)
        return ArrayDataset(data, self.n, self.mesh, _already_sharded=True)

    # -- materialization --------------------------------------------------
    def numpy(self) -> Any:
        """Gather to host as a numpy pytree, padding stripped. The host
        stops here until the device has produced ``data``: the
        ``wait:d2h`` span is what tells "device idle while the host
        works" from "device idle while the host already waits for it"."""
        nbytes = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(self.data))
        with flight_span("d2h", "wait", nbytes=nbytes):
            out = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[: self.n], self.data)
        MetricsRegistry.get_or_create().counter("egress.d2h_bytes").inc(nbytes)
        return out

    def collect(self) -> List[Any]:
        arr = self.numpy()
        return [jax.tree_util.tree_map(lambda x: x[i], arr) for i in range(self.n)]


class HostDataset(Dataset):
    """Host-resident list-backed dataset for ragged / non-numeric stages."""

    def __init__(self, items: Iterable[Any], tag: Optional[str] = None):
        self.items = list(items)
        self.tag = tag

    def map(self, fn: Callable[[Any], Any]) -> "HostDataset":
        return HostDataset([fn(x) for x in self.items])

    def collect(self) -> List[Any]:
        return list(self.items)

    def __len__(self) -> int:
        return len(self.items)

    def to_device(self, mesh: Optional[Mesh] = None) -> ArrayDataset:
        return ArrayDataset.from_items(
            [np.asarray(x) for x in self.items], mesh)


def as_dataset(data: Any, mesh: Optional[Mesh] = None) -> Dataset:
    if isinstance(data, Dataset):
        return data
    if isinstance(data, (list, tuple)) and data and not hasattr(data[0], "shape"):
        return HostDataset(data)
    if isinstance(data, (list, tuple)):
        return ArrayDataset.from_items(list(data), mesh)
    return ArrayDataset.from_numpy(data, mesh)


# -- internals ------------------------------------------------------------

def padded_rows(n: int, shards: int) -> int:
    """Rows a resident batch of ``n`` items occupies after padding to a
    shard multiple — the single source of the padding arithmetic, shared
    by the runtime sharder below and the static HBM planner
    (``analysis.resources``), so plans charge exactly the rows the
    device will hold."""
    shards = max(int(shards), 1)
    return max(((int(n) + shards - 1) // shards) * shards, shards)


def _padded_rows(n: int, mesh: Mesh) -> int:
    return padded_rows(n, num_data_shards(mesh))


def bucketed_dataset(data: Any, n: int, bucket_rows: int,
                     mesh: Optional[Mesh] = None) -> ArrayDataset:
    """Stage a host batch of ``n`` items padded to exactly
    ``bucket_rows`` rows (not merely the shard-multiple minimum).

    The serving micro-batcher's pad-to-bucket primitive: every batch in
    a bucket shares ONE padded shape, so one compiled executable per
    bucket serves every request size that lands in it (the compile
    caches key on shapes — per-request shapes would recompile per
    size). The result is a normal :class:`ArrayDataset` with
    ``padded_n == bucket_rows`` and the true ``n``, so the existing
    mask machinery (``mask`` / ``_apply_mask`` re-zeroing after maps)
    treats the extra pad rows exactly like shard pad — linear
    reductions stay exact and ``numpy()``/``collect()`` strip them.
    """
    mesh = mesh or get_mesh()
    shards = num_data_shards(mesh)
    if bucket_rows % shards:
        raise ValueError(
            f"bucket_rows={bucket_rows} must be a multiple of the mesh "
            f"data-shard count ({shards}) — buckets come from a "
            "shard-rounded policy (serving.BucketPolicy)")
    if n > bucket_rows:
        raise ValueError(f"n={n} items do not fit bucket_rows={bucket_rows}")
    sh = batch_sharding(mesh)

    def put(x):
        x = np.asarray(x)
        if x.shape[0] != n:
            raise ValueError(f"leading dim {x.shape[0]} != n={n}")
        return _h2d(x, bucket_rows, sh)

    staged = jax.tree_util.tree_map(put, data)
    return ArrayDataset(staged, n, mesh, _already_sharded=True)


def _shard_pytree(data: Any, n: int, mesh: Mesh) -> Any:
    rows = _padded_rows(n, mesh)
    sh = batch_sharding(mesh)

    def put(x):
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            # already on device: pad + reshard there — round-tripping
            # through np.asarray would drag the whole array over the
            # host link twice
            if x.shape[0] != n:
                raise ValueError(f"leading dim {x.shape[0]} != n={n}")
            if rows != n:
                pad = [(0, rows - n)] + [(0, 0)] * (x.ndim - 1)
                x = jnp.pad(x, pad)  # eager: hits the persistent op cache
            with flight_span("reshard", "ingest", nbytes=int(x.nbytes)):
                return jax.device_put(x, sh)
        x = np.asarray(x)
        if x.shape[0] != n:
            raise ValueError(f"leading dim {x.shape[0]} != n={n}")
        return _h2d(x, rows, sh)

    return jax.tree_util.tree_map(put, data)


def shard_layout(ds: Any) -> dict:
    """``{data_shards, rows_a_shard}`` of a resident dataset, as span
    arguments; nothing for any other value. Read off the mesh and the
    padded row count: no device is asked."""
    if not isinstance(ds, ArrayDataset):
        return {}
    shards = num_data_shards(ds.mesh)
    return {"data_shards": shards, "rows_a_shard": ds.padded_n // shards}


def row_shards(x: jax.Array):
    """``(row shards, bytes on the fullest device)`` of a batch-major
    device array, from its sharding alone: over how many distinct row
    ranges its shards lie (1 for an array held whole, on one device or
    replicated on several) and how many bytes of it the device with the
    longest range holds."""
    ranges = {idx[0].indices(x.shape[0])[:2] for idx in
              x.sharding.devices_indices_map(x.shape).values()}
    row_nbytes = x.dtype.itemsize * int(np.prod(x.shape[1:], dtype=np.int64))
    return len(ranges), max(hi - lo for lo, hi in ranges) * row_nbytes


def _row_mask(padded_n: int, n: int, mesh: Mesh) -> jax.Array:
    mask = np.zeros(padded_n, dtype=bool)
    mask[:n] = True
    return jax.device_put(mask, batch_sharding(mesh))


@jax.jit
def _zero_masked_rows(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.where(
        mask.reshape((-1,) + (1,) * (x.ndim - 1)), x, jnp.zeros((), x.dtype)
    )


def _apply_mask(data: Any, n: int, mesh: Mesh) -> Any:
    leaves = jax.tree_util.tree_leaves(data)
    pn = leaves[0].shape[0]
    if n >= pn:
        return data
    mask = _row_mask(pn, n, mesh)
    return jax.tree_util.tree_map(lambda x: _zero_masked_rows(x, mask), data)


def _repad(x: jax.Array, rows: int, mesh: Mesh) -> jax.Array:
    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jax.device_put(jnp.pad(x, pad), batch_sharding(mesh))


#: fn -> jit(vmap(fn)): repeated maps of the same function (bound methods
#: of live nodes, module-level functions) reuse the compiled program
#: instead of paying a fresh jit wrapper — and a recompile — per call.
#: Closure-capturing functions are NOT cached: a fresh lambda per call
#: would get zero reuse while pinning its captured arrays forever, and
#: re-tracing is what picks up their captured state. Cached functions
#: must therefore be pure in their module globals (they are traced once
#: per input shape). Bounded LRU (ADVICE r2, shared ``utils.lru``
#: protocol): bound-method keys pin their node instances, so unbounded
#: growth leaks host+HBM memory in model-sweep loops.
from ..utils.lru import LruMemo  # noqa: E402

_VMAP_JIT_CACHE = LruMemo()


def clear_vmap_cache() -> None:
    """Drop the fn -> jit(vmap(fn)) memo (long-lived processes; see also
    ``workflow.transformer.clear_jit_cache``)."""
    _VMAP_JIT_CACHE.clear()


def _vmap_cacheable(fn) -> bool:
    """Only functions with a stable, reusable identity enter the cache:
    bound methods of eq_key-hashed operators (equal-config instances
    share one entry) and module-level named functions. Per-call fresh
    objects (lambdas, locals, partials) would accumulate dead entries."""
    inner = getattr(fn, "__func__", fn)  # bound method -> function
    if getattr(inner, "__closure__", None) is not None:
        return False
    self_obj = getattr(fn, "__self__", None)
    if self_obj is not None:
        return hasattr(self_obj, "eq_key")
    qn = getattr(inner, "__qualname__", "<lambda>")
    return "<locals>" not in qn and "<lambda>" not in qn


def _masked_vmap(fn, data, n: int, padded_n: int, mesh: Mesh):
    from ..observability.compilelog import watch_jit

    name = f"vmap:{getattr(fn, '__name__', 'fn')}"
    jfn = None
    if _vmap_cacheable(fn):
        try:
            jfn = _VMAP_JIT_CACHE.get(fn)
            if jfn is None:
                jfn = watch_jit(jax.jit(jax.vmap(fn)), name=name)
                _VMAP_JIT_CACHE.put(fn, jfn)
        except TypeError:  # unhashable fn
            jfn = None
    if jfn is None:
        # uncacheable per-call jit: the compile observatory makes this
        # visible as a fresh first-compile per call — the exact hazard
        # the memo above exists to avoid
        jfn = watch_jit(jax.jit(jax.vmap(fn)), name=name)
    out = jfn(data)
    return _apply_mask(out, n, mesh) if n < padded_n else out


def device_nbytes(value: Any) -> float:
    """Best-effort memory footprint in bytes of a pipeline value, cheap
    enough for the observability hot path: array metadata only — never
    gathers device data to host. ArrayDatasets sum their leaves' nbytes
    (device-resident); HostDatasets extrapolate from a 16-item sample
    (host-resident); other values sum nbytes over their pytree leaves,
    charging a nominal 64 bytes per opaque leaf. Shared by the
    auto-cache profiler's memory accounting and per-node trace records."""
    if isinstance(value, ArrayDataset):
        return float(sum(
            getattr(leaf, "nbytes", 64)
            for leaf in jax.tree_util.tree_leaves(value.data)))
    if isinstance(value, HostDataset):
        items = value.items
        if not items:
            return 0.0
        sample = items[:16]
        per = sum(
            float(getattr(it, "nbytes", 64)) for it in sample) / len(sample)
        return per * len(items)
    if is_streaming(value):
        # StreamingDataset: device residency is the bounded prefetch
        # buffer (wire-dtype bytes) plus the working chunk at its
        # POST-cast width — NOT the logical dataset size. This is the
        # number the out-of-core HBM-budget assertion reads, and why a
        # narrow wire never hides the f32 working copy from budgets.
        return float(value.buffered_nbytes())
    if isinstance(value, Dataset):
        # unknown future subclass: nominal per-item charge — never
        # collect() here, that's the gather this hot path must not do
        return 64.0 * len(value)
    return float(sum(
        getattr(leaf, "nbytes", 64)
        for leaf in jax.tree_util.tree_leaves(value)))


def to_numpy(x: Any, dtype=None) -> np.ndarray:
    """Materialize datasets / lazy pipeline results / arrays as one numpy
    array (the shared coercion for evaluators and host-side fits)."""
    if hasattr(x, "get") and not isinstance(x, Dataset):  # PipelineResult
        x = x.get()
    if isinstance(x, ArrayDataset):
        out = np.asarray(x.numpy())
    elif isinstance(x, Dataset):
        out = np.asarray(x.collect())
    else:
        out = np.asarray(x)
    return out.astype(dtype) if dtype is not None else out


def ensure_array(ds: "Dataset", mesh: Optional[Mesh] = None) -> "ArrayDataset":
    """Promote a host dataset of fixed-shape items to a mesh-sharded
    ArrayDataset (no-op if already one). The implicit host->device
    boundary hit by solvers fed from ragged host pipelines."""
    if isinstance(ds, ArrayDataset):
        return ds
    if isinstance(ds, (np.ndarray, jnp.ndarray)):
        return ArrayDataset.from_numpy(np.asarray(ds), mesh)
    if is_streaming(ds):
        raise TypeError(
            "a StreamingDataset cannot be implicitly promoted to a "
            "device-resident ArrayDataset (that would materialize the "
            "whole stream in HBM — the exact thing streaming exists to "
            "avoid). Fit with a streamable estimator "
            "(parallel.streaming.fit_streaming), or call "
            ".materialize() explicitly if the stream is known to fit.")
    assert isinstance(ds, HostDataset), type(ds)
    return ds.to_device(mesh)


@jax.jit
def argmax_labels(L):
    """Class ids from a one-hot/indicator label matrix, on device."""
    return jnp.argmax(L, axis=1).astype(jnp.int32)


def fetch_to_host(arr) -> np.ndarray:
    """Fetch a (small, metadata-sized) device array to host, working even
    when it spans non-addressable devices in a multi-host mesh."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
    return np.asarray(arr)
