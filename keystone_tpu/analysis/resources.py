"""Static HBM resource planning over the abstract interpretation.

KeystoneML's optimizer works from *static* information — per-node cost
models and a budgeted cache planner over the DAG — and this module
extends the TPU port's abstract interpreter the same way: from the
shape/dtype specs ``analysis.interpreter`` already infers, plus mesh
shard geometry and (for streams) chunk geometry, every node gets a
:class:`ResourceEffect` (output bytes, transient peak, accumulator
carry) and a topo-order liveness planner folds the effects into a
per-pipeline :class:`HbmPlan` — the pipeline's peak device footprint,
known before a single buffer is allocated.

The streaming model mirrors the runtime ``_Residency`` ledger
(``parallel/streaming.py``) charge for charge, so the static plan is an
*upper bound* the measured ``peak_device_nbytes`` can be validated
against (bench emits ``plan_vs_measured``):

* ``prefetch_depth`` staged chunks at their WIRE dtype (the slot-gated
  buffer),
* one working chunk at its POST-cast compute dtype,
* one transient wire-width chunk while the fused on-device cast runs
  (the wire and compute copies briefly co-exist).

Resident datasets charge ``padded_rows(n) * element_nbytes`` (the shard
pad is real HBM); host datasets charge zero device bytes; estimator
nodes charge their accumulator carry (Gram/cross/moments — resident
solves materialize the same Gram workspace) as a transient and their
fitted model as the output that stays live.

Entry points: ``plan_graph`` (used by ``check_graph`` /
``Pipeline.check(sample, hbm_budget=...)``), the ``check --budget``
CLI (exit 2 on a predicted violation), and
``StreamingDataset.static_plan_nbytes()`` (the double-checked budget in
``fit_streaming`` — see PERFORMANCE.md "plan HBM statically").
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from ..workflow.graph_ids import GraphId, NodeId, SinkId, SourceId
from .spec import (
    DatasetSpec,
    DatumSpec,
    SparseSpec,
    TransformerSpec,
    Unknown,
    element_feature_dim,
)


# -- stream geometry ---------------------------------------------------------

@dataclass(frozen=True)
class StreamGeometry:
    """Static chunk geometry of one ``StreamingDataset`` — everything
    the planner needs to reproduce the runtime residency ledger's
    charges without consuming the stream."""

    chunk_rows: int          # padded rows per staged chunk (shard-rounded)
    prefetch_depth: int
    wire_row_nbytes: float   # bytes/row at the wire dtype (as staged)
    work_row_nbytes: float   # bytes/row at the compute dtype (post-cast)
    cast: bool = False       # True when wire dtype != compute dtype
    #: True on specs propagated THROUGH a stream-consuming node: the
    #: residency ledger is shared with the root stream, so a derived
    #: view must not re-charge the same buffer to the plan
    shared: bool = False

    def as_shared(self) -> "StreamGeometry":
        import dataclasses

        return dataclasses.replace(self, shared=True)

    def staged_chunk_nbytes(self) -> float:
        return float(self.chunk_rows) * self.wire_row_nbytes

    def working_chunk_nbytes(self) -> float:
        return float(self.chunk_rows) * self.work_row_nbytes

    def plan_nbytes(self) -> float:
        """Static residency bound for one live iteration of the stream,
        mirroring ``_Residency``: ``depth`` staged wire-width chunks +
        one post-cast working chunk + one transient wire chunk during
        the cast. With no wire narrowing this is the documented
        ``(prefetch_depth + 1) * chunk_nbytes`` budget unit."""
        staged = self.staged_chunk_nbytes()
        transient = staged if self.cast else 0.0
        return (self.prefetch_depth * staged
                + self.working_chunk_nbytes() + transient)


# -- per-node effects --------------------------------------------------------

@dataclass(frozen=True)
class ResourceEffect:
    """One node's static device-memory contribution.

    ``out_nbytes`` stays live until the node's last consumer runs (or
    forever, for sink-held values); ``transient_nbytes`` is charged only
    while the node itself executes (solver workspace, cast co-existence);
    ``carry_nbytes`` is the accumulator a streamed fit keeps resident
    across the whole chunk loop (charged like a transient of the fit
    node, reported separately); ``item_nbytes`` is the per-item
    activation size when the collection size ``n`` is unknown (the apply
    path's unit of residency). ``resolved`` is False when the spec did
    not determine the bytes (Unknown elements, unannotated estimators) —
    the planner charges zero and lists the node as unresolved rather
    than inventing a number."""

    out_nbytes: float = 0.0
    transient_nbytes: float = 0.0
    carry_nbytes: float = 0.0
    item_nbytes: Optional[float] = None
    resolved: bool = True
    note: str = ""


def element_nbytes(element: Any) -> Optional[float]:
    """Bytes of one item described by an element spec, or None when any
    leaf is opaque (Unknown) or sparse (density not static)."""
    import jax
    import numpy as np

    total = 0.0
    for leaf in jax.tree_util.tree_leaves(
            element,
            is_leaf=lambda x: isinstance(
                x, (Unknown, SparseSpec, jax.ShapeDtypeStruct))):
        if not isinstance(leaf, jax.ShapeDtypeStruct):
            return None
        total += float(math.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def padded_rows(n: int, shards: int) -> int:
    """Rows a resident batch of ``n`` items occupies after shard
    padding (re-exported from ``parallel.dataset`` — one source of the
    arithmetic, so the plan charges exactly what the sharder pads)."""
    from ..parallel.dataset import padded_rows as _rows

    return _rows(n, shards)


def spec_effect(spec: Any, data_shards: int) -> ResourceEffect:
    """Default resource derivation from a node's output spec."""
    if isinstance(spec, DatasetSpec):
        if spec.streaming:
            geom = spec.geometry
            if geom is None:
                return ResourceEffect(
                    resolved=False,
                    note="streaming dataset with opaque chunk geometry")
            if geom.shared:
                # a derived view: the prefetch buffer + raw working
                # chunk were already charged at the root stream's node;
                # what is NEW here is one transformed chunk (the ledger
                # does not track it, real HBM does)
                per_item = element_nbytes(spec.element)
                if per_item is None:
                    return ResourceEffect(
                        resolved=False,
                        note="stream view with unsized transformed "
                             "element (buffer charged at the root)")
                return ResourceEffect(
                    out_nbytes=float(geom.chunk_rows) * per_item,
                    note="stream view (buffer charged at the root; "
                         "one transformed chunk here)")
            return ResourceEffect(out_nbytes=geom.plan_nbytes(),
                                  note="stream residency bound")
        per_item = element_nbytes(spec.element)
        if spec.host:
            return ResourceEffect(
                out_nbytes=0.0, item_nbytes=per_item,
                note="host-resident (zero device bytes)")
        if per_item is None:
            return ResourceEffect(resolved=False,
                                  note="element not fully specified")
        if spec.n is None:
            # apply-path collection of unknown size: charge nothing to
            # the fit peak, report the per-item activation instead
            return ResourceEffect(out_nbytes=0.0, item_nbytes=per_item,
                                  note="n unknown (per-item only)")
        return ResourceEffect(
            out_nbytes=float(padded_rows(spec.n, data_shards)) * per_item)
    if isinstance(spec, DatumSpec):
        per = element_nbytes(spec.element)
        if per is None:
            return ResourceEffect(resolved=False,
                                  note="datum element not specified")
        return ResourceEffect(out_nbytes=per, item_nbytes=per)
    if isinstance(spec, TransformerSpec):
        # fitted-model bytes come from the estimator node's own effect;
        # a bare TransformerSpec (saved state) charges nothing
        return ResourceEffect(out_nbytes=0.0, note="transformer")
    return ResourceEffect(resolved=False, note="unknown spec")


# -- estimator annotations (shared size helpers) -----------------------------

def _data_label_dims(dep_specs: Sequence[Any]):
    d = element_feature_dim(dep_specs[0]) if dep_specs else None
    k = (element_feature_dim(dep_specs[1])
         if len(dep_specs) > 1 else None)
    return d, k


def device_memory_bytes(free: bool = False) -> float:
    """Bytes of memory of one device, as the backend reports them
    (``bytes_limit``; less ``bytes_in_use`` with ``free``). The CPU
    backend reports none and is reckoned at a nominal 8 GiB; an
    accelerator that reports none is an error, not an assumption about
    its HBM."""
    import jax

    device = jax.devices()[0]
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        used = stats.get("bytes_in_use", 0) if free else 0
        return float(stats["bytes_limit"] - used)
    if device.platform != "cpu":
        raise RuntimeError(
            f"{device.device_kind} reports no memory_stats()['bytes_limit']"
            "; no planner here will guess its HBM size")
    return 8.0 * (1 << 30)


#: One block of all rows and the centred copy a streamed sweep keeps
#: beside it may take this share of one device's memory together; over
#: it the sweep takes the rows in chunks (``ops.linalg._ChunkedBlock``).
#: The rest is for the rows themselves, the factors and whatever the
#: caller holds, as with ``stream_gather.MAX_GATHER_SHARE``.
MAX_BLOCK_SHARE = 0.5
#: The share of one device's memory a row chunk of one block may take:
#: the sweep holds the chunk, its centred copy and their products
#: beside the block's buffer, three to four chunks in all by the TPU
#: compiler's own count (at 16 GB and 4,096 columns 15,872 rows, under
#: a gigabyte together; a Gram of that many rows keeps the matrix unit
#: busy for 16 ms a call).
ROW_CHUNK_SHARE = 1.0 / 64
#: A chunk is a whole number of row tiles of this many rows (the Gram
#: contracts over them). Where not one fits the share, chunks cannot
#: help and there are none.
ROW_CHUNK_GRANULE = 256


def stream_row_chunk(rows: int, block_width: int,
                     itemsize: int = 4) -> Optional[int]:
    """The rows a streamed block sweep takes at a time where one block
    of all ``rows`` and its centred copy would take more than
    ``MAX_BLOCK_SHARE`` of the device's memory; None where they fit and
    the sweep takes every row at once. From the shapes and
    ``device_memory_bytes()`` alone (the whole, not what is free now:
    two fits of one shape must choose alike). The chunks are as many as
    the share makes them and as even as the granule lets them be, so
    that the last one, which starts where it still fits, shares few
    rows with the one before it."""
    memory = device_memory_bytes()
    row = float(block_width) * itemsize
    if 2.0 * rows * row <= MAX_BLOCK_SHARE * memory:
        return None
    most = (int(ROW_CHUNK_SHARE * memory / row) // ROW_CHUNK_GRANULE
            * ROW_CHUNK_GRANULE)
    if not 0 < most < rows:
        return None
    even = -(-rows // -(-rows // most))
    return -(-even // ROW_CHUNK_GRANULE) * ROW_CHUNK_GRANULE


def gram_carry_nbytes(dep_specs: Sequence[Any]) -> Optional[float]:
    """f32 Gram/cross/sums carry of the least-squares family:
    ``G (d, d) + C (d, k) + sx (d) + sy (k)`` — also the Gram workspace
    a resident normal-equations solve materializes."""
    d, k = _data_label_dims(dep_specs)
    if d is None:
        return None
    k = k or 0
    return 4.0 * (d * d + d * k + d + k)


def linear_model_nbytes(dep_specs: Sequence[Any]) -> Optional[float]:
    """f32 fitted linear model: weights ``(d, k)`` + intercept ``(k,)``
    + feature means ``(d,)``."""
    d, k = _data_label_dims(dep_specs)
    if d is None or k is None:
        return None
    return 4.0 * (d * k + d + k)


def moments_carry_nbytes(dep_specs: Sequence[Any]) -> Optional[float]:
    """Column-moment carry (sums + sums-of-squares) of the scaler."""
    d, _ = _data_label_dims(dep_specs)
    return None if d is None else 2.0 * 4.0 * d


# -- Pallas kernel workspace (PR 13) ----------------------------------------
#
# The kernel program's dispatchers change what the apply path
# materializes in HBM, and the plan should say so: the fused FV kernel
# replaces the (nDesc, K) posterior round trip with two padded (Dp, Kp)
# moment accumulators; dense SIFT keeps its band operators resident.
# The FV helper mirrors its dispatcher's actual decision
# (``use_pallas()`` + the shared fits-vmem predicate), so the charge
# follows the kernel the runtime will really pick.


def fv_apply_transient_nbytes(d: int, k: int,
                              n_desc: Optional[int]) -> Optional[float]:
    """Per-item workspace of the Fisher-vector apply. Fused kernel
    dispatched: the two (Dp, Kp) padded moment accumulators plus the
    padded parameter blocks (q never exists in HBM). Fallback: the
    (nDesc, K) posterior matrix the split form materializes between
    the posterior and moment programs — None when nDesc is unknown
    (the planner lists the node as unresolved rather than inventing
    a number)."""
    from ..ops.pallas_kernels import _LANE, _round_up, fv_fits_vmem, use_pallas

    if use_pallas() and fv_fits_vmem(d, k):
        dp = _round_up(max(d + 1, _LANE), _LANE)
        kp = _round_up(max(k, _LANE), _LANE)
        return 4.0 * (4.0 * dp * kp)
    if n_desc is None:
        return None
    return 4.0 * float(n_desc) * k


def sift_band_operator_nbytes(height: int, width: int, step: int,
                              bin_size: int, num_scales: int,
                              scale_step: int) -> float:
    """Resident band operators of one dense-SIFT config: the per-scale
    smoothing matrices (H, H) + (W, W) and sampling operators
    (NBP*n, L) both axes, charged once per config since the lru caches
    keep them alive."""
    from ..ops.sift import NBP, scale_grid

    total = 0.0
    for scale in range(num_scales):
        ny, nx = scale_grid(height, width, scale, step, bin_size,
                            num_scales, scale_step)
        total += 4.0 * (height * height + width * width
                        + NBP * ny * height + NBP * nx * width)
    return total


def transform_workspace_effect(per_item_fn, data_specs: Sequence[Any],
                               out_spec: Any,
                               data_shards: int) -> Optional[ResourceEffect]:
    """Spec-derived effect of an apply node plus its declared per-item
    device workspace (kernel or fallback scratch): the workspace scales
    with the batch for a resident dataset of known size (every item's
    scratch is live inside the one batched program) and is charged once
    per item otherwise. Returns None — deferring to the derived effect
    — when the workspace does not resolve."""
    import dataclasses

    data = [s for s in data_specs
            if isinstance(s, (DatasetSpec, DatumSpec))]
    if not callable(per_item_fn) or not data:
        return None
    per_item = per_item_fn(data[0].element)
    if per_item is None:
        return None
    if getattr(data[0], "streaming", False):
        # a streamed apply only ever holds one chunk's items live —
        # scaling by the stream's LOGICAL n would invent phantom
        # gigabytes of transient (the plan charges the stream buffer,
        # not the logical size; same principle here)
        geom = getattr(data[0], "geometry", None)
        items = geom.chunk_rows if geom is not None else 1
    else:
        n = getattr(data[0], "n", None)
        items = 1 if n is None else padded_rows(n, data_shards)
    base = spec_effect(out_spec, data_shards)
    return dataclasses.replace(
        base, transient_nbytes=base.transient_nbytes
        + float(per_item) * items,
        note=(base.note + "; " if base.note else "")
        + "apply kernel workspace")


def delegate_resource_effect(dep_specs: Sequence[Any], out_spec: Any,
                             data_shards: int) -> Optional[ResourceEffect]:
    """Effect of a Delegate (fitted-transformer apply) node: the
    spec-derived output charge plus the fitted transformer's declared
    apply workspace (``TransformerSpec.apply_transient_nbytes``, set
    from the estimator's ``abstract_apply_transient`` hook). Returns
    None — deferring to the derived effect — when the transformer
    declares no workspace."""
    t = dep_specs[0] if dep_specs else None
    return transform_workspace_effect(
        getattr(t, "apply_transient_nbytes", None), dep_specs[1:],
        out_spec, data_shards)


def estimator_resource_effect(estimator: Any,
                              dep_specs: Sequence[Any]) -> ResourceEffect:
    """Effect of an estimator node: the fitted model is the output that
    stays live; the accumulator carry (equivalently, the resident
    solver's Gram workspace) is transient across the fit. Estimators
    declare sizes via optional ``carry_nbytes(dep_specs)`` /
    ``fitted_nbytes(dep_specs)`` hooks; undeclared estimators resolve to
    zero bytes but are listed as unresolved."""
    carry_fn = getattr(estimator, "carry_nbytes", None)
    fitted_fn = getattr(estimator, "fitted_nbytes", None)
    carry = carry_fn(dep_specs) if callable(carry_fn) else None
    fitted = fitted_fn(dep_specs) if callable(fitted_fn) else None
    declared = callable(carry_fn) or callable(fitted_fn)
    resolved = declared and not (
        (callable(carry_fn) and carry is None)
        or (callable(fitted_fn) and fitted is None))
    return ResourceEffect(
        out_nbytes=float(fitted or 0.0),
        carry_nbytes=float(carry or 0.0),
        resolved=resolved,
        note=("" if declared
              else "estimator declares no carry/fitted size"))


# -- serving residency (PR 15) ----------------------------------------------
#
# The serving plane admits fitted pipelines under an explicit HBM
# budget; its admission charge is the static-planner arithmetic the
# HbmPlan docstring promises: persistent fitted state plus the widest
# per-item activation times the largest request bucket. Both helpers
# live here so the admission math and the fit-path planning share one
# accounting model (and one review surface).


def fitted_model_nbytes(graph: Any) -> float:
    """Bytes of the fitted parameters a transformer-only pipeline keeps
    resident while served warm: every >0-d array leaf stored on the
    graph's operators (weights, intercepts, scaler moments, codebooks),
    jit-cache attributes excluded. Counted at the STORED width — a
    ``weight_dtype``-quantized mapper stores f32 and narrows on the
    apply path, so this is a deliberate upper bound (the narrow copy
    and the master copy co-exist while the quantized program runs)."""
    import types

    import jax

    def walk(value, seen) -> float:
        total = 0.0
        for leaf in jax.tree_util.tree_leaves(value):
            if getattr(leaf, "ndim", 0) > 0 and hasattr(leaf, "nbytes"):
                total += float(leaf.nbytes)
            elif id(leaf) not in seen and hasattr(leaf, "__dict__") \
                    and not isinstance(leaf, (types.FunctionType,
                                              types.MethodType,
                                              types.ModuleType, type)):
                # opaque config objects (a nested StandardScalerModel
                # riding a mapper) carry fitted arrays the pytree walk
                # cannot see; recurse one attribute level at a time
                seen.add(id(leaf))
                state = {k: v for k, v in vars(leaf).items()
                         if not k.startswith("_jit_")
                         and k != "_eq_key_val"}
                total += walk(state, seen)
        return total

    total = 0.0
    seen: set = set()
    for node in graph.nodes:
        op = graph.get_operator(node)
        attrs = getattr(op, "__dict__", None)
        if not attrs:
            continue
        state = {k: v for k, v in attrs.items()
                 if not k.startswith("_jit_") and k != "_eq_key_val"}
        total += walk(state, seen)
    return total


def sharded_apply_nbytes(graph: Any) -> tuple:
    """``(shardable_nbytes, gather_nbytes)`` for the spmd sharded
    apply (``parallel/spmd_apply.py``): how many of the graph's fitted
    bytes row-shard over the data axis AT REST, and the largest
    transient one in-body ``all_gather`` materializes (the whole
    matrix for ``LinearMapper``, one feature block for
    ``BlockLinearMapper``). Operators opt in via a
    ``sharded_apply_nbytes()`` hook returning that pair; everything
    else stays replicated and is charged in full by the caller."""
    shardable = 0.0
    gather = 0.0
    for node in graph.nodes:
        op = graph.get_operator(node)
        hook = getattr(op, "sharded_apply_nbytes", None)
        if callable(hook):
            s, u = hook()
            shardable += float(s)
            gather = max(gather, float(u))
    return shardable, gather


def serving_residency_nbytes(model_nbytes: float, plan: "HbmPlan",
                             bucket_rows: int, data_shards: int = 1,
                             shardable_nbytes: float = 0.0,
                             gather_nbytes: float = 0.0,
                             ) -> Optional[float]:
    """The admission charge for one served model at its largest request
    bucket: ``model_nbytes + bucket_rows x apply_item_nbytes`` — the
    serving-residency approximation the :class:`HbmPlan` docstring
    documents, now the enforced admission-control arithmetic
    (``serving/residency.py``). Returns None when the plan could not
    size the per-item activation (``apply_item_nbytes == 0`` with
    unresolved nodes): the caller must fall back to a measured probe
    rather than admit on an invented number.

    With ``data_shards > 1`` the charge is PER HOST under the sharded
    apply (``parallel/spmd_apply.py``): the shardable fitted bytes
    (from :func:`sharded_apply_nbytes`) divide across the data axis,
    the rest stays replicated, one ``gather_nbytes`` transient is
    charged for the in-body all_gather, and the activation shrinks to
    this host's row shard of the bucket — verified device-free by
    ``check --budget``."""
    item = float(plan.apply_item_nbytes)
    if item <= 0.0 and plan.unresolved:
        return None
    shards = max(int(data_shards), 1)
    if shards == 1:
        return float(model_nbytes) + float(bucket_rows) * item
    shardable = min(float(shardable_nbytes), float(model_nbytes))
    resident = float(model_nbytes) - shardable + shardable / shards
    shard_rows = -(-int(bucket_rows) // shards)
    return resident + float(gather_nbytes) + float(shard_rows) * item


# -- the plan ----------------------------------------------------------------

@dataclass
class HbmPlan:
    """One pipeline's static HBM plan.

    ``fit_peak_nbytes`` is the liveness peak over the full (fit-path)
    graph: at every topo step, the sum of all still-live outputs plus
    the executing node's transient and carry. ``model_nbytes`` is the
    persistent fitted-state footprint (the apply path's resident cost);
    ``apply_item_nbytes`` the widest per-item activation along the
    unknown-``n`` apply path (serving residency ≈ ``model_nbytes`` +
    batch × ``apply_item_nbytes``). Nodes whose bytes could not be
    derived are charged zero and listed in ``unresolved`` — the plan is
    a bound over what the analyzer can see, never an invention."""

    name: str
    entries: List[Dict[str, Any]] = field(default_factory=list)
    fit_peak_nbytes: float = 0.0
    peak_node: Optional[int] = None
    model_nbytes: float = 0.0
    apply_item_nbytes: float = 0.0
    unresolved: List[str] = field(default_factory=list)

    def over_budget(self, budget: Optional[float]) -> bool:
        return budget is not None and self.fit_peak_nbytes > float(budget)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "fit_peak_nbytes": self.fit_peak_nbytes,
            "peak_node": self.peak_node,
            "model_nbytes": self.model_nbytes,
            "apply_item_nbytes": self.apply_item_nbytes,
            "unresolved": list(self.unresolved),
            "entries": list(self.entries),
        }

    def summary(self) -> str:
        mib = 1 << 20
        lines = [
            f"static HBM plan {self.name!r}: fit peak "
            f"{self.fit_peak_nbytes / mib:.2f} MiB"
            + (f" @ node {self.peak_node}"
               if self.peak_node is not None else "")
            + f", fitted models {self.model_nbytes / mib:.2f} MiB, "
            f"apply {self.apply_item_nbytes / 1024.0:.1f} KiB/item"]
        if self.unresolved:
            lines.append(
                f"  unresolved ({len(self.unresolved)}): "
                + ", ".join(self.unresolved[:6])
                + (" ..." if len(self.unresolved) > 6 else ""))
        return "\n".join(lines)


def plan_graph(analysis: Any, name: str = "graph",
               data_shards: Optional[int] = None) -> HbmPlan:
    """Fold per-node :class:`ResourceEffect`\\ s into an :class:`HbmPlan`
    by liveness over the deterministic topo order (``Graph.linearize``):
    a node's output is charged from its step until its last consumer's
    step (sink-held values stay live to the end), its transient and
    carry only at its own step. Device-free by construction — only
    specs and integer geometry are read."""
    if data_shards is None:
        try:
            from ..parallel.mesh import get_mesh, num_data_shards

            data_shards = num_data_shards(get_mesh())
        except Exception:
            data_shards = 1
    graph = analysis.graph
    order = [g for g in graph.linearize() if not isinstance(g, SinkId)]
    pos = {gid: i for i, gid in enumerate(order)}
    last_use: Dict[GraphId, int] = {}
    for n in graph.nodes:
        for d in graph.get_dependencies(n):
            if d in pos:
                last_use[d] = max(last_use.get(d, -1), pos[n])
    sink_held = {graph.get_sink_dependency(k) for k in graph.sinks}

    plan = HbmPlan(name)
    live: Dict[GraphId, float] = {}
    for i, gid in enumerate(order):
        spec = analysis.value(gid)
        derived = spec_effect(spec, data_shards)
        eff = derived
        label = "Source"
        if isinstance(gid, NodeId):
            op = graph.get_operator(gid)
            label = op.label()
            dep_specs = [analysis.value(d)
                         for d in graph.get_dependencies(gid)]
            override = op.resource_effect(dep_specs, spec,
                                          data_shards=data_shards)
            if override is not None:
                eff = override
        live[gid] = eff.out_nbytes
        step = sum(live.values()) + eff.transient_nbytes + eff.carry_nbytes
        if step > plan.fit_peak_nbytes:
            plan.fit_peak_nbytes = step
            plan.peak_node = gid.id
        if eff.carry_nbytes or (isinstance(gid, NodeId) and isinstance(
                spec, TransformerSpec)):
            plan.model_nbytes += eff.out_nbytes
        if eff.item_nbytes:
            plan.apply_item_nbytes = max(plan.apply_item_nbytes,
                                         eff.item_nbytes)
        if not eff.resolved:
            plan.unresolved.append(f"node {gid.id} [{label}]"
                                   + (f": {eff.note}" if eff.note else ""))
        plan.entries.append({
            "node_id": gid.id,
            "operator": label,
            "out_nbytes": eff.out_nbytes,
            "transient_nbytes": eff.transient_nbytes,
            "carry_nbytes": eff.carry_nbytes,
            "item_nbytes": eff.item_nbytes,
            "live_nbytes": step,
            "resolved": eff.resolved,
            "note": eff.note,
        })
        # release every value whose last consumer just ran
        for d in [d for d in live
                  if d not in sink_held and last_use.get(d, -1) <= i
                  and d is not gid]:
            del live[d]
    return plan


# -- XLA cross-check ---------------------------------------------------------

def xla_verify_plan(analysis: Any,
                    plan: Optional[HbmPlan] = None) -> List[Dict[str, Any]]:
    """Cross-check the static plan against XLA's own memory model:
    every planner-resolved node with a per-item program is
    compiled-WITHOUT-executing on the sample spec (``jit(...).lower(
    element_avals).compile()`` — abstract inputs, no device buffers
    beyond the executable itself) and its ``memory_analysis`` output /
    temp bytes are compared with the plan's per-item charge
    (``plan_vs_xla = planner item bytes / XLA output bytes``; ~1.0
    means the two models agree, large means the planner over-charges,
    small means it UNDER-charges — the dangerous direction). The
    denominator is OUTPUT bytes only: XLA temp scratch (reported per
    row for context) is transient workspace the planner's per-item
    liveness charge deliberately excludes — the fit-path annotation in
    :func:`~..observability.utilization.annotate_trace` is the surface
    that compares output+transient against output+temp.

    Returns one row per plan-resolved node: ``status`` is ``"ok"`` when
    the node compiled and both byte counts resolved, else a named skip
    reason (sources have no per-item program, host stages are not
    jax-traceable) — coverage is reported, never assumed. Compiles are
    swallowed from the compile observatory (verification must not
    count as workload compilation or trip an armed fence)."""
    import jax

    from ..observability.compilelog import (
        _swallow_compiles,
        executable_stats,
    )
    from ..workflow.operators import TransformerOperator
    from .spec import element_has_unknown

    graph = analysis.graph
    # the planner's own per-item charges, by node id: these are what
    # the cross-check must validate (operator resource_effect overrides
    # included), with the raw element size only as a fallback when the
    # caller supplied no plan
    plan_items: Dict[int, float] = {}
    for e in (plan.entries if plan is not None else []):
        if e.get("item_nbytes"):
            plan_items[int(e["node_id"])] = float(e["item_nbytes"])
    rows: List[Dict[str, Any]] = []
    for gid in [g for g in graph.linearize() if not isinstance(g, SinkId)]:
        spec = analysis.value(gid)
        row: Dict[str, Any] = {"node_id": gid.id}
        if not isinstance(gid, NodeId):
            row.update(operator="Source", status="skip:source")
            rows.append(row)
            continue
        op = graph.get_operator(gid)
        row["operator"] = op.label()
        if isinstance(spec, Unknown):
            row["status"] = "skip:unresolved"
            rows.append(row)
            continue
        if not isinstance(op, TransformerOperator):
            row["status"] = "skip:no-per-item-program"
            rows.append(row)
            continue
        dep_specs = [analysis.value(d) for d in graph.get_dependencies(gid)]
        if not dep_specs or not all(
                isinstance(d, (DatasetSpec, DatumSpec)) for d in dep_specs):
            row["status"] = "skip:non-data-input"
            rows.append(row)
            continue
        elements = [d.element for d in dep_specs]
        if any(element_has_unknown(e) for e in elements):
            row["status"] = "skip:input-element-unknown"
            rows.append(row)
            continue
        plan_item = plan_items.get(gid.id) or (
            element_nbytes(spec.element)
            if isinstance(spec, (DatasetSpec, DatumSpec)) else None)
        try:
            with _swallow_compiles():
                compiled = jax.jit(
                    lambda *xs, _op=op: _op.single_transform(list(xs))
                ).lower(*elements).compile()
            stats = executable_stats(compiled) or {}
        except Exception as exc:  # host stage / tracer-hostile program
            row["status"] = f"skip:uncompilable ({type(exc).__name__})"
            rows.append(row)
            continue
        xla_out = stats.get("output_bytes")
        xla_temp = stats.get("temp_bytes")
        row.update(
            plan_item_nbytes=plan_item,
            xla_output_bytes=xla_out,
            xla_temp_bytes=xla_temp,
            xla_flops=stats.get("flops"),
            plan_vs_xla=(round(plan_item / xla_out, 3)
                         if plan_item and xla_out else None),
            status=("ok" if plan_item and xla_out
                    else "skip:bytes-unresolved"),
        )
        rows.append(row)
    return rows


def format_xla_verify(rows: List[Dict[str, Any]], name: str = "") -> str:
    """Human-readable table of :func:`xla_verify_plan` rows."""
    ok = [r for r in rows if r.get("status") == "ok"]
    lines = [f"xla verify {name!r}: {len(ok)}/{len(rows)} nodes "
             "compiled-without-executing and byte-checked"]
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"  node {r['node_id']:>3} "
                         f"[{r.get('operator', '?')}]: {r.get('status')}")
            continue
        lines.append(
            f"  node {r['node_id']:>3} [{r.get('operator', '?')}]: "
            f"plan {r['plan_item_nbytes']:.0f} B/item vs xla out "
            f"{r['xla_output_bytes']:.0f} B (temp "
            f"{(r['xla_temp_bytes'] or 0):.0f} B) -> plan_vs_xla "
            f"{r['plan_vs_xla']}")
    return "\n".join(lines)
