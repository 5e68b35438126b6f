"""Node-level optimization rule (reference
``workflow/NodeOptimizationRule.scala``).

For every optimizable operator that is not downstream of the pipeline's
runtime source, execute its dependency prefix on *sampled* source
datasets (the analogue of the reference's per-partition sample execution,
``NodeOptimizationRule.scala:337-350``), call the node's ``optimize``
hook with the sample and workload shape, and splice the returned choice
into the graph:

* the chosen operator replaces the optimizable one;
* the choice's prefix transformers are inserted on the fit-path data
  dependency AND on the runtime input of every delegating child — the
  same two-endpoint splice the reference performs on its instruction
  list (``NodeOptimizationRule.scala:82-299``).

Static-first: before sampling, the rule runs the abstract interpreter
(``analysis.interpreter.analyze``) over the graph (once per graph
state — splices invalidate the cached analysis). When the optimizable
node's data (and labels) dependencies resolve to full DatasetSpecs —
known n, element dims, storage density — the node's ``optimize_static``
hook is consulted, and if it returns a choice the sampled execution is
skipped entirely: no data is loaded, no device program runs, and the
PipelineTrace records the decision with ``"provenance": "static"``.
Unresolved shapes (host stages, sparse elements of unknown density)
fall back to the reference's sampling path (``"sampled"``).

The static path's sparsity input is STRUCTURAL (1.0 for dense storage),
not the value-level density a sample would measure; workloads whose
dense-stored data is mostly zeros (and where a Sparsify -> sparse
solver could win) can force the reference behavior with
``NodeOptimizationRule(static_shapes=False)`` or the environment knob
``KEYSTONE_STATIC_NODE_OPT=0``.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from ...parallel.dataset import (
    ArrayDataset,
    Dataset,
    HostDataset,
    is_streaming,
)
from ...parallel.mesh import get_mesh, num_data_shards
from ..graph import Graph
from ..graph_ids import GraphId, NodeId
from ..operators import DatasetOperator, DelegatingOperator
from ..optimizable import (
    NodeChoice,
    OptimizableEstimator,
    OptimizableLabelEstimator,
    OptimizableTransformer,
)
from .rule import Rule

DEFAULT_SAMPLE_SIZE = 96  # reference: samplesPerPartition=3 over many partitions


def _sample_dataset(ds: Dataset, size: int) -> Dataset:
    """Evenly-spread deterministic sample — the analogue of the
    reference's per-partition sampling (samplesPerPartition across all
    partitions), avoiding head bias on ordered datasets."""
    import numpy as np

    if is_streaming(ds):
        # sample from the FIRST chunk only: bounded device/host cost by
        # construction. collect()/len() on a stream would materialize it
        # (or raise on unknown n) — the exact thing streaming forbids.
        # Head bias is acceptable for a ~96-item cost-model sample.
        for chunk in ds.chunks():
            return _sample_dataset(chunk, size)
        raise ValueError("cannot sample an empty stream")
    n = len(ds)
    take = min(size, n)
    idx = np.unique(np.linspace(0, n - 1, take).astype(np.int64))
    if isinstance(ds, ArrayDataset):
        import jax

        data = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[idx], ds.data)
        return ArrayDataset(data, len(idx), ds.mesh)
    items = ds.collect()
    return HostDataset([items[i] for i in idx])


def _dataset_len(ds: Dataset) -> int:
    """len(ds), tolerating unknown-length streams (0 — callers take the
    max over the graph's datasets, and stream-fed optimizable nodes are
    excluded from sampling before this matters)."""
    try:
        return len(ds)
    except TypeError:
        return 0


class NodeOptimizationRule(Rule):
    def __init__(self, sample_size: int = DEFAULT_SAMPLE_SIZE,
                 num_machines: Optional[int] = None,
                 static_shapes: Optional[bool] = None):
        import os

        self.sample_size = sample_size
        self.num_machines = num_machines
        if static_shapes is None:
            static_shapes = os.environ.get(
                "KEYSTONE_STATIC_NODE_OPT", "1") not in ("0", "false", "no")
        self.static_shapes = static_shapes

    # -- sampling ---------------------------------------------------------
    def _execute_sampled(self, graph: Graph, deps: Tuple[GraphId, ...]):
        """Execute dependency ids against a copy of the graph whose source
        datasets are truncated to the sample size. Returns (samples, n)
        where n is the full size of the feeding dataset (node transforms
        are 1:1 per item, as in the reference's numPerPartition count)."""
        from ..executor import GraphExecutor

        relevant = graph.get_ancestors(*deps).union(deps)
        samples = {}
        n = 0
        for node, op in graph.operators.items():
            if isinstance(op, DatasetOperator):
                if node in relevant:
                    n = max(n, _dataset_len(op.dataset))
                samples[node] = DatasetOperator(
                    _sample_dataset(op.dataset, self.sample_size))
        sampled = graph.rewrite(operators=samples)
        from ...observability.trace import tracing_disabled

        executor = GraphExecutor(sampled, optimize=False)
        with tracing_disabled():
            # sampled executions share node ids with the real graph and
            # must not appear as per-node trace records; their cost is
            # logged via the node-choice entry instead
            return [executor.execute(d).get() for d in deps], n

    # -- splicing ---------------------------------------------------------
    @staticmethod
    def _insert_prefix(graph: Graph, dep: GraphId,
                       prefix) -> Tuple[Graph, GraphId]:
        cur = dep
        for t in prefix:
            graph, cur = graph.add_node(t, (cur,))
        return graph, cur

    def _splice_estimator(self, graph: Graph, node: NodeId,
                          choice: NodeChoice) -> Graph:
        deps = graph.get_dependencies(node)
        data_dep, rest = deps[0], deps[1:]
        graph, new_data = self._insert_prefix(graph, data_dep, choice.prefix)
        graph = graph.set_operator(node, choice.node)
        graph = graph.set_dependencies(node, (new_data,) + tuple(rest))
        if not choice.prefix:
            return graph
        # runtime endpoint: delegating children apply the fitted model to
        # live input; that input must pass through the same prefix
        for child in list(graph.get_children(node)):
            if not isinstance(child, NodeId):
                continue
            op = graph.get_operator(child)
            if not isinstance(op, DelegatingOperator):
                continue
            cdeps = graph.get_dependencies(child)
            new_cdeps: List[GraphId] = [cdeps[0]]
            for rt_in in cdeps[1:]:
                graph, wrapped = self._insert_prefix(
                    graph, rt_in, choice.prefix)
                new_cdeps.append(wrapped)
            graph = graph.set_dependencies(child, tuple(new_cdeps))
        return graph

    def _splice_transformer(self, graph: Graph, node: NodeId,
                            choice: NodeChoice) -> Graph:
        deps = graph.get_dependencies(node)
        new_deps = []
        for dep in deps:
            graph, wrapped = self._insert_prefix(graph, dep, choice.prefix)
            new_deps.append(wrapped)
        graph = graph.set_operator(node, choice.node)
        return graph.set_dependencies(node, tuple(new_deps))

    # -- static path ------------------------------------------------------
    @staticmethod
    def _static_choice(analysis, graph: Graph, node: NodeId, op,
                       machines: int) -> Optional[Tuple[NodeChoice, int]]:
        """Resolve the node's choice from statically inferred shapes, or
        None when the analyzer (or the node) declines."""
        from ...analysis.spec import DatasetSpec

        deps = graph.get_dependencies(node)
        data_spec = analysis.value(deps[0]) if deps else None
        if not isinstance(data_spec, DatasetSpec) or data_spec.n is None:
            return None
        n = data_spec.n
        if isinstance(op, OptimizableLabelEstimator):
            if len(deps) < 2:
                return None
            labels_spec = analysis.value(deps[1])
            if not isinstance(labels_spec, DatasetSpec):
                return None
            choice = op.optimize_static(
                data_spec, n, machines, labels_spec=labels_spec)
        else:
            choice = op.optimize_static(data_spec, n, machines)
        return None if choice is None else (choice, n)

    # -- trace hook -------------------------------------------------------
    @staticmethod
    def _record_choice(node: NodeId, op, choice: NodeChoice, n: int,
                       machines: int, wall_s: float,
                       provenance: str) -> None:
        """Log the splice decision to the active trace (the detailed
        per-solver cost table is recorded by the optimizable node itself,
        e.g. ``LeastSquaresEstimator.optimize`` — this entry ties it to a
        graph node, the shape provenance, and the sampling cost)."""
        from ...observability.trace import current_trace

        trace = current_trace()
        if trace is None:
            return
        trace.record_node_choice({
            "node_id": node.id,
            "optimizable": type(op).__name__,
            "chosen": type(choice.node).__name__,
            "prefix": [type(t).__name__ for t in choice.prefix],
            "full_n": n,
            "num_machines": machines,
            "sample_and_optimize_s": wall_s,
            "provenance": provenance,
        })

    @staticmethod
    def _feeds_streaming(graph: Graph, node: NodeId) -> bool:
        """True when any dataset feeding ``node`` is a StreamingDataset:
        the sampled path is off-limits there (executing the prefix on a
        materialized sample is exactly the materialization streaming
        exists to avoid)."""
        deps = graph.get_dependencies(node)
        for a in graph.get_ancestors(*deps).union(deps):
            if not isinstance(a, NodeId) or a not in graph.nodes:
                continue
            op = graph.get_operator(a)
            if isinstance(op, DatasetOperator) and is_streaming(op.dataset):
                return True
        return False

    # -- rule entry -------------------------------------------------------
    def apply(self, graph: Graph) -> Graph:
        import time

        # ids reachable from unconnected (runtime) sources can't be sampled
        downstream = graph.source_descendants()

        machines = self.num_machines or num_data_shards(get_mesh())
        # one abstract interpretation serves every optimizable node on
        # the same graph state; a splice mutates the graph and drops it
        cached_analysis = None
        for node in graph.linearize():
            if not isinstance(node, NodeId) or node not in graph.nodes:
                continue
            op = graph.get_operator(node)
            if node in downstream:
                continue
            if not isinstance(op, (OptimizableLabelEstimator,
                                   OptimizableEstimator,
                                   OptimizableTransformer)):
                continue
            t0 = time.perf_counter()
            static = None
            if self.static_shapes:
                if cached_analysis is None:
                    from ...analysis.interpreter import analyze

                    cached_analysis = analyze(graph)
                static = self._static_choice(
                    cached_analysis, graph, node, op, machines)
            if static is not None:
                choice, n = static
                provenance = "static"
            elif self._feeds_streaming(graph, node):
                # no static shapes AND streamed input: leave the
                # optimizable node in place — a streamable estimator
                # makes its cost-model choice at finalize time from the
                # exact accumulated (n, d, k), and a non-streamable one
                # raises the clear non-streamable-fit error at fit
                continue
            else:
                provenance = "sampled"
                if isinstance(op, OptimizableLabelEstimator):
                    (sample, sample_labels), n = self._execute_sampled(
                        graph, graph.get_dependencies(node)[:2])
                    choice = op.optimize(sample, sample_labels, n, machines)
                else:
                    (sample,), n = self._execute_sampled(
                        graph, graph.get_dependencies(node)[:1])
                    choice = op.optimize(sample, n, machines)
            if isinstance(op, OptimizableTransformer):
                graph = self._splice_transformer(graph, node, choice)
            else:
                graph = self._splice_estimator(graph, node, choice)
            cached_analysis = None  # splice changed the graph
            self._record_choice(node, op, choice, n, machines,
                                time.perf_counter() - t0, provenance)
        return graph
