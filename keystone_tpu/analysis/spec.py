"""Abstract values for static pipeline analysis.

The static analogue of ``workflow.expression``: where the executor flows
lazy Dataset/Datum/Transformer expressions through the DAG, the abstract
interpreter (``analysis.interpreter``) flows *specs* — shape/dtype
descriptions in the style of ``jax.ShapeDtypeStruct`` plus the dataset
metadata the cost model needs (item count, sharding, storage density) —
without ever touching a device.

The lattice is deliberately shallow:

* :class:`DatumSpec` — one item: a pytree of ``jax.ShapeDtypeStruct``
  leaves (or :class:`SparseSpec` / :data:`UNKNOWN_ELEMENT` markers).
* :class:`DatasetSpec` — a distributed collection of ``n`` such items.
* :class:`TransformerSpec` — an abstract fitted transformer: what an
  estimator node produces, applied later by a ``DelegatingOperator``.
* :class:`Unknown` — "cannot say"; propagates silently so that host
  stages and unannotated estimators never produce false diagnostics.

``SpecDataset`` is the check-CLI companion: a placeholder ``Dataset``
carrying only a spec, splice-able wherever an app's builder expects
training data, that raises if anything ever tries to *execute* it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..parallel.dataset import ArrayDataset, Dataset, HostDataset


class AbstractValue:
    """Base of the analysis lattice."""


@dataclass(frozen=True)
class Unknown(AbstractValue):
    """Value the analyzer cannot describe (host objects, unannotated
    estimator outputs). Propagates silently: consuming an Unknown yields
    Unknown, never a diagnostic."""

    reason: str = ""

    def __repr__(self) -> str:
        return f"Unknown({self.reason!r})" if self.reason else "Unknown"


@dataclass(frozen=True)
class SparseSpec(AbstractValue):
    """Per-item :class:`~keystone_tpu.nodes.util.sparse.SparseVector`
    element: logical size known, density not."""

    size: Optional[int] = None

    def __repr__(self) -> str:
        return f"SparseSpec(size={self.size})"


@dataclass(frozen=True)
class DatumSpec(AbstractValue):
    """One item: a pytree whose leaves are ``jax.ShapeDtypeStruct``,
    :class:`SparseSpec`, or :class:`Unknown`."""

    element: Any

    def __repr__(self) -> str:
        return f"DatumSpec({format_element(self.element)})"


@dataclass(frozen=True)
class DatasetSpec(AbstractValue):
    """A dataset of ``n`` items shaped like ``element``.

    ``sparsity`` is the *storage* density the cost model consumes:
    1.0 for dense array elements (an ``ArrayDataset`` stores every
    entry), ``None`` when unknown (sparse host items, host objects).

    ``streaming`` marks a chunked (``parallel.streaming``) collection:
    items arrive as bounded device chunks, ``n`` may be unknown (None),
    and only estimators implementing accumulate/finalize can fit on it
    (the ``non-streamable-fit`` lint enforces this statically).

    ``wire_dtype`` (streams only) names the dtype deliberately shipped
    on the host->device wire when it is narrower than the compute dtype
    the ``element`` describes (e.g. ``"uint8"`` for image chunks cast
    back to f32 on device). The element always reports what CONSUMERS
    see post-cast, so narrowness-on-the-wire is visible to tooling
    without ever tripping the ``dtype-narrowing`` lint.

    ``geometry`` (streams only) carries the static chunk geometry
    (:class:`~keystone_tpu.analysis.resources.StreamGeometry`) the HBM
    planner folds into the pipeline plan; None for opaque sources whose
    chunk shape cannot be described without consuming the stream.

    ``sharded`` marks a PROCESS-SHARD-LOCAL stream (built by e.g.
    ``loaders.image_loader_utils.stream_tar_shards``): under a
    multi-host world, ``n`` is THIS host's share of the records, not
    the dataset size, and only the distributed ``fit_streaming`` mode
    (which tree-reduces carries across hosts) fits it correctly — the
    ``non-streamable-fit`` family reports the sharded provenance so a
    diagnostic about a 2-host stream never reads like a single-host
    one.
    """

    element: Any
    n: Optional[int] = None
    host: bool = False
    sparsity: Optional[float] = None
    streaming: bool = False
    wire_dtype: Optional[str] = None
    geometry: Optional[Any] = None
    sharded: bool = False

    def __repr__(self) -> str:
        flag = ", streaming" if self.streaming else ""
        if self.sharded:
            flag += ", sharded"
        if self.wire_dtype is not None:
            flag += f", wire={self.wire_dtype}"
        return (f"DatasetSpec(n={self.n}, "
                f"element={format_element(self.element)}{flag})")


@dataclass(frozen=True)
class TransformerSpec(AbstractValue):
    """Abstract fitted transformer. ``apply_element`` maps an input
    element spec to the fitted transformer's output element spec (what
    the estimator's ``abstract_fit`` promised); None when the estimator
    does not describe its output. ``apply_transient_nbytes`` maps the
    same input element to the fitted apply's per-item device workspace
    (the Pallas-kernel/fallback scratch the HBM planner charges at the
    Delegate node — ``analysis.resources.delegate_resource_effect``);
    None when the estimator declares none."""

    apply_element: Optional[Callable[[Any], Any]] = field(
        default=None, compare=False)
    label: str = "Transformer"
    apply_transient_nbytes: Optional[Callable[[Any], Any]] = field(
        default=None, compare=False)

    def __repr__(self) -> str:
        known = "known" if self.apply_element is not None else "opaque"
        return f"TransformerSpec({self.label}, {known})"


# -- element helpers --------------------------------------------------------

def is_unknown(spec: Any) -> bool:
    return isinstance(spec, Unknown)


def element_has_unknown(element: Any) -> bool:
    leaves = jax.tree_util.tree_leaves(
        element, is_leaf=lambda x: isinstance(x, (Unknown, SparseSpec)))
    return any(isinstance(l, (Unknown, SparseSpec)) for l in leaves)


def dense_sparsity(element: Any) -> Optional[float]:
    """Structural storage density of an element spec: 1.0 when every
    leaf is a dense array struct (an ArrayDataset stores every entry),
    None when any leaf is sparse or opaque (density not static)."""
    return None if element_has_unknown(element) else 1.0


def format_element(element: Any) -> str:
    def fmt(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return f"{np.dtype(leaf.dtype).name}{list(leaf.shape)}"
        return repr(leaf)

    return repr(jax.tree_util.tree_map(
        fmt, element,
        is_leaf=lambda x: isinstance(
            x, (Unknown, SparseSpec, jax.ShapeDtypeStruct))))


def struct_of(value: Any) -> Any:
    """Element spec of a concrete per-item value (host or device)."""
    from ..nodes.util.sparse import SparseVector

    def leaf_spec(v):
        if isinstance(v, SparseVector):
            return SparseSpec(v.size)
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
        if isinstance(v, (bool, int)):
            return jax.ShapeDtypeStruct((), np.int32)
        if isinstance(v, float):
            return jax.ShapeDtypeStruct((), np.float32)
        return Unknown(f"host object {type(v).__name__}")

    return jax.tree_util.tree_map(
        leaf_spec, value,
        is_leaf=lambda v: isinstance(v, SparseVector)
        or (hasattr(v, "shape") and hasattr(v, "dtype")))


def dataset_spec(ds: Dataset) -> AbstractValue:
    """DatasetSpec of a concrete Dataset, touching only metadata (array
    shapes/dtypes, the first host item) — never device buffers."""
    spec = getattr(ds, "_keystone_spec", None)
    if spec is not None:
        return spec
    if isinstance(ds, ArrayDataset):
        element = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype),
            ds.data)
        return DatasetSpec(element, n=ds.n, host=False, sparsity=1.0)
    from ..parallel.streaming import StreamingDataset

    if isinstance(ds, StreamingDataset):
        # exact per-chunk element shape when the source can describe it
        # without being consumed (post-cast: what consumers see); n is
        # known-or-None by construction; a deliberately narrow wire
        # rides separately so it never reads as dtype narrowing
        element = ds.element()
        if element is None:
            element = Unknown("opaque stream source")
        return DatasetSpec(
            element, n=ds.n, host=False,
            sparsity=None if element_has_unknown(element) else 1.0,
            streaming=True, wire_dtype=ds.wire_dtype_name(),
            geometry=ds.plan_geometry(),
            sharded=bool(getattr(ds, "process_sharded", False)))
    from ..parallel.ragged import RaggedDataset

    if isinstance(ds, RaggedDataset):
        # as for a host dataset of items whose sizes differ: the first
        # item stands for all of them
        element = ds.element()
        if element is None:
            element = Unknown("ragged dataset with stages still to apply")
        return DatasetSpec(element, n=len(ds), host=False,
                           sparsity=None if element_has_unknown(element)
                           else 1.0)
    if isinstance(ds, HostDataset):
        items = ds.items
        if not items:
            return DatasetSpec(Unknown("empty host dataset"), n=0, host=True)
        element = struct_of(items[0])
        # dense array elements store every entry -> structural density 1;
        # sparse / opaque host items: density statically unknown
        sparsity = None if element_has_unknown(element) else 1.0
        return DatasetSpec(element, n=len(items), host=True,
                           sparsity=sparsity)
    return Unknown(f"dataset type {type(ds).__name__}")


def datum_spec(value: Any) -> AbstractValue:
    return DatumSpec(struct_of(value))


def value_spec(value: Any) -> AbstractValue:
    """Spec of an already-computed expression value (saved state)."""
    from ..workflow.operators import TransformerOperator

    if isinstance(value, Dataset):
        return dataset_spec(value)
    if isinstance(value, TransformerOperator):
        t = value

        def apply_element(elem, _t=t):
            return abstract_apply_element(_t, elem)

        return TransformerSpec(apply_element, label=t.label())
    return datum_spec(value)


def abstract_apply_element(op, element: Any) -> Any:
    """Shape-propagate one per-item application of a transformer-like
    operator via ``jax.eval_shape`` — abstract by construction, so no
    device buffer is ever allocated. Raises whatever the trace raises
    (shape errors, host-sync ``TracerArrayConversionError``); the
    interpreter classifies those into diagnostics."""
    if element_has_unknown(element):
        return Unknown("input element not fully specified")
    return jax.eval_shape(lambda x: op.single_transform([x]), element)


# -- estimator abstract_fit helpers -----------------------------------------

def element_feature_dim(spec: Any) -> Optional[int]:
    """Per-item feature dimension of a Dataset/Datum spec: last axis of a
    dense vector/matrix element, logical size of a sparse element."""
    element = getattr(spec, "element", spec)
    if isinstance(element, SparseSpec):
        return element.size
    if isinstance(element, jax.ShapeDtypeStruct) and element.shape:
        return int(element.shape[-1])
    return None


def map_last_dim(k: int, dtype: Any = np.float32) -> Callable[[Any], Any]:
    """``abstract_fit`` body for models replacing the feature axis with a
    ``k``-wide output (linear maps, k-means one-hots, GMM posteriors):
    dense ``(..., d) -> (..., k)``, sparse ``-> (k,)`` (solvers densify
    their outputs)."""

    def apply_element(element: Any) -> Any:
        if isinstance(element, SparseSpec):
            return jax.ShapeDtypeStruct((k,), np.dtype(dtype))
        if isinstance(element, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(
                tuple(element.shape[:-1]) + (k,), np.dtype(dtype))
        return Unknown("input element not a vector/matrix")

    return apply_element


def labels_width_fit(dep_specs, dtype: Any = np.float32
                     ) -> Optional[Callable[[Any], Any]]:
    """``abstract_fit`` for (data, labels) label-estimators fitting a
    linear model: output width = the labels' feature dimension. Returns
    None when the labels spec does not resolve."""
    if len(dep_specs) < 2:
        return None
    k = element_feature_dim(dep_specs[1])
    return None if k is None else map_last_dim(k, dtype)


def identity_fit(dep_specs) -> Callable[[Any], Any]:
    """``abstract_fit`` for shape-preserving fitted transformers
    (scalers, whiteners)."""
    return lambda element: element


# -- input-spec coercion ----------------------------------------------------

def as_input_spec(sample: Any, n: Optional[int] = None) -> AbstractValue:
    """Coerce a user-supplied sample description into an AbstractValue.

    Accepts an AbstractValue as-is; a ``jax.ShapeDtypeStruct`` (or pytree
    of them) as the per-item element of a dataset; a concrete Dataset; a
    numpy/jax array interpreted as ONE item (its spec becomes the
    element); or a ``(shape, dtype)`` tuple."""
    if isinstance(sample, AbstractValue):
        return sample
    if isinstance(sample, Dataset):
        return dataset_spec(sample)
    if isinstance(sample, jax.ShapeDtypeStruct):
        return DatasetSpec(sample, n=n, sparsity=1.0)
    if isinstance(sample, tuple) and len(sample) == 2 and isinstance(
            sample[0], (tuple, list)):
        struct = jax.ShapeDtypeStruct(tuple(sample[0]), np.dtype(sample[1]))
        return DatasetSpec(struct, n=n, sparsity=1.0)
    if hasattr(sample, "shape") and hasattr(sample, "dtype"):
        struct = jax.ShapeDtypeStruct(tuple(sample.shape), sample.dtype)
        return DatasetSpec(struct, n=n, sparsity=1.0)
    leaves = jax.tree_util.tree_leaves(sample)
    if leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves):
        return DatasetSpec(sample, n=n, sparsity=1.0)
    raise TypeError(
        f"cannot build an input spec from {type(sample).__name__}; pass a "
        "jax.ShapeDtypeStruct, (shape, dtype), array, Dataset, or spec")


class SpecDataset(Dataset):
    """A Dataset that exists only as a spec: splice-able into pipeline
    builders as training data for static checking (``check`` CLI), but
    guaranteed never to reach a device — executing it raises."""

    def __init__(self, element: Any, n: Optional[int] = None,
                 host: bool = False, sparsity: Optional[float] = None,
                 tag: Optional[str] = None):
        if sparsity is None and not element_has_unknown(element):
            sparsity = 1.0
        self._keystone_spec = DatasetSpec(
            element, n=n, host=host, sparsity=sparsity)
        # a stable tag keeps DatasetOperator.eq_key deterministic for
        # spec-only graphs (no accidental prefix collisions via id())
        self.tag = tag or f"spec:{format_element(element)}:{n}"

    @property
    def spec(self) -> DatasetSpec:
        return self._keystone_spec

    def __len__(self) -> int:
        return self._keystone_spec.n or 0

    def _refuse(self, what: str):
        raise RuntimeError(
            f"SpecDataset cannot be {what}: it is a static-analysis "
            "placeholder (did a check-only pipeline get executed?)")

    def map(self, fn):
        self._refuse("mapped")

    def collect(self):
        self._refuse("collected")


def spec_dataset(shape, dtype=np.float32, n: Optional[int] = None,
                 **kw) -> SpecDataset:
    """Shorthand: ``spec_dataset((784,), np.float32, n=60000)``."""
    return SpecDataset(
        jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype)), n=n, **kw)
