"""Memoized recursive DAG executor.

Mirrors ``workflow/graph/GraphExecutor.scala``: optimizes lazily on first
execution, refuses to execute ids reachable from unconnected sources, and
saves results of saveable nodes (estimator fits, caches) into the global
prefix state table (``GraphExecutor.scala:53-80``).

Observability, two modes. Always on: ``_execute`` wraps every lazy
expression's thunk in a flight-recorder span ``dag:node:<label>#<id>``
(:func:`~keystone_tpu.observability.timeline.flight_span`: a ring write
and a ``jax.profiler.TraceAnnotation``, so any profiler capture carries
pipeline-level operator names), and ``graph`` puts ``dag:optimize``
around the optimizer. Neither blocks on the device nor changes what is
dispatched when, so these spans describe the run users have. Profile
mode: while a :class:`~keystone_tpu.observability.PipelineTrace` is
active the same wrapper also times the node honestly (blocking on
device results before reading the clock, which serialises host and
device), records its output's device-memory footprint and shard count,
runs under ``jax.named_scope`` and a compile context, and checks the
output's numerics. Already-computed expressions (prefix/state cache
hits) are recorded as such. A few always-on :class:`MetricsRegistry`
counters rise per node (``executor.nodes_executed`` / ``memo_hits`` /
``prefix_hits``).
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Optional

from ..observability.compilelog import compile_context
from ..observability.metrics import MetricsRegistry
from ..observability.numerics import check_node_output
from ..observability.timeline import flight_span
from ..observability.trace import NodeRecord, current_trace, metrics_suppressed
from .env import PipelineEnv
from .expression import (
    DatasetExpression,
    DatumExpression,
    Expression,
    TransformerExpression,
)
from .graph import Graph
from .graph_ids import GraphId, NodeId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    DatumOperator,
    EstimatorOperator,
    ExpressionOperator,
    Operator,
)
from .prefix import compute_prefix


def is_saveable(op: Operator) -> bool:
    """Which operators' results enter the global prefix memo (reference
    ``ExtractSaveablePrefixes.scala:8-19``: Cacher or EstimatorOperator)."""
    return isinstance(op, EstimatorOperator) or getattr(op, "saveable", False)


def _expression_kind(expr: Expression) -> str:
    if isinstance(expr, DatasetExpression):
        return "dataset"
    if isinstance(expr, DatumExpression):
        return "datum"
    if isinstance(expr, TransformerExpression):
        return "transformer"
    return "expression"


def _block_on_device(value) -> None:
    """Block until device work backing ``value`` completes, so recorded
    wall times are honest for async-dispatched jax computations. Fitted
    transformers carry their device arrays (solver weights etc.) as
    attributes, so their async fit work is synced too — otherwise the
    solve's cost would be misattributed to the first downstream node
    that forces the weights."""
    import jax

    from ..parallel.dataset import ArrayDataset

    try:
        if isinstance(value, ArrayDataset):
            jax.block_until_ready(value.data)
        elif hasattr(value, "block_until_ready") or isinstance(
                value, (list, tuple, dict)):
            jax.block_until_ready(value)
        else:
            attrs = getattr(value, "__dict__", None)
            if attrs:
                jax.block_until_ready([
                    leaf for leaf in jax.tree_util.tree_leaves(attrs)
                    if hasattr(leaf, "block_until_ready")
                ])
    except (TypeError, ValueError, AttributeError, RuntimeError):
        pass  # host values: nothing to block on


def _measure_output(record: NodeRecord, value) -> None:
    from ..parallel.dataset import ArrayDataset, device_nbytes
    from ..parallel.mesh import num_data_shards

    record.output_bytes = device_nbytes(value)
    if isinstance(value, ArrayDataset):
        record.shards = num_data_shards(value.mesh)


def _traced_thunk(orig, node_id: int, label: str, kind: str):
    """Wrap an expression thunk: a ``dag:node`` span in every run, and
    the blocking measurements while a trace is active. The active trace
    is looked up at *call* time: saved expressions outlive the trace
    under which they were created (they live in ``PipelineEnv.state``),
    and a stale captured trace must not be written to after it exits."""
    scope = f"{label}#{node_id}"

    def run():
        trace = current_trace()
        # flight-recorder span (inclusive wall) and profiler annotation:
        # nested node spans overflow to sub-lanes at export time
        with flight_span(f"node:{scope}", "dag", node_id=node_id, kind=kind):
            if trace is None:
                return orig()
            import jax

            record = NodeRecord(node_id=node_id, operator=label, kind=kind)
            with trace.node_timer(record):
                # compile attribution: any XLA compile dispatched while
                # this node's thunk runs — including app-level jits the
                # observatory does not own — is recorded against
                # "node:<label>#<id>", which is what utilization's
                # annotate_trace joins per-node MFU on
                with compile_context(f"node:{scope}"):
                    with jax.named_scope(scope):
                        value = orig()
                _block_on_device(value)
                _measure_output(record, value)
        # numerics tripwire over the node's float output (AFTER the
        # timer: the health reduction is the plane's cost, not the
        # node's; the executor already blocked on the device result, so
        # the small word pull adds no new sync). Raises NumericsError
        # with a post-mortem naming this node on non-finite values —
        # traced runs only, like every blocking observer here.
        check_node_output(value, scope)
        return value

    run._keystone_traced = True
    return run


class GraphExecutor:
    def __init__(self, graph: Graph, optimize: bool = True):
        self._raw_graph = graph
        self._should_optimize = optimize
        self._optimized: Optional[Graph] = None
        self._cache: Dict[GraphId, Expression] = {}
        self._unexecutables: Optional[FrozenSet[GraphId]] = None

    @property
    def graph(self) -> Graph:
        """The optimized graph (optimization happens once, lazily —
        ``GraphExecutor.scala:19-31``)."""
        if self._optimized is None:
            if self._should_optimize:
                with flight_span("optimize", "dag",
                                 nodes_before=len(self._raw_graph.nodes)
                                 ) as span:
                    self._optimized = (
                        PipelineEnv.get_or_create().optimizer.execute(
                            self._raw_graph))
                    span["nodes_after"] = len(self._optimized.nodes)
            else:
                self._optimized = self._raw_graph
        return self._optimized

    @property
    def raw_graph(self) -> Graph:
        return self._raw_graph

    @property
    def unexecutables(self) -> FrozenSet[GraphId]:
        """Ids whose value depends on an unconnected source
        (``GraphExecutor.scala:39-43``)."""
        if self._unexecutables is None:
            self._unexecutables = self.graph.source_descendants()
        return self._unexecutables

    def execute(self, gid: GraphId) -> Expression:
        return self._execute(gid)

    def _execute(self, gid: GraphId) -> Expression:
        graph = self.graph
        if isinstance(gid, SinkId):
            return self._execute(graph.get_sink_dependency(gid))
        if gid in self.unexecutables:
            raise ValueError(
                f"cannot execute {gid!r}: it depends on an unconnected source"
            )
        # sampled optimizer executions (tracing_disabled) are throwaway:
        # they must not count as real executor activity
        count = not metrics_suppressed()
        metrics = MetricsRegistry.get_or_create() if count else None
        if gid in self._cache:
            if count:
                metrics.counter("executor.memo_hits").inc()
            return self._cache[gid]
        assert isinstance(gid, NodeId), gid
        op = graph.get_operator(gid)
        deps = [self._execute(d) for d in graph.get_dependencies(gid)]
        expr = op.execute(deps)
        if count:
            metrics.counter("executor.nodes_executed").inc()
            if isinstance(op, ExpressionOperator):
                # saved-state substitution (SavedStateLoadRule / prefix
                # memo) — counted traced or not
                metrics.counter("executor.prefix_hits").inc()
        self._instrument(current_trace(), gid, op, expr)
        self._cache[gid] = expr
        if is_saveable(op):
            prefix = compute_prefix(graph, gid)
            if prefix is not None:
                # The expression memoizes itself on first get(), so saving
                # the lazy handle shares the eventual fit/cache result
                # across pipelines (GraphExecutor.scala:66-70).
                PipelineEnv.get_or_create().state[prefix] = expr
        return expr

    @staticmethod
    def _instrument(trace, gid: NodeId, op: Operator, expr: Expression) -> None:
        """Wrap a lazy ``expr``'s thunk (in every run: the wrapper is a
        span and nothing more until a trace is active). Computed
        expressions are recorded in the active trace, if any: constants
        as such, anything else (saved state substituted by
        ``SavedStateLoadRule``, results shared via the prefix memo) as a
        cache hit."""
        if expr.computed:
            if trace is None:
                return
            label = op.label()
            record = NodeRecord(
                node_id=gid.id, operator=label,
                cached=not isinstance(op, (DatasetOperator, DatumOperator)),
                kind=_expression_kind(expr))
            _measure_output(record, expr.get())
            trace.record_node(record)
            return
        if getattr(expr._thunk, "_keystone_traced", False):
            # already wrapped (a saved lazy handle reused across
            # pipelines); the wrapper resolves the active trace itself
            return
        expr._thunk = _traced_thunk(
            expr._thunk, gid.id, op.label(), _expression_kind(expr))
