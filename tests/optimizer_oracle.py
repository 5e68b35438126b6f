"""The optimizer's rules as they were before PR 27, kept as an oracle.

One pair fused an application, one graph copied for every node merged
or removed, a fixed point found by comparing whole graphs: quadratic in
the nodes, and easy to read. ``tests/test_optimizer_linear.py`` holds
``DefaultOptimizer`` to what these produce, node ids included. Nothing
in the package imports this module.
"""
from __future__ import annotations

from typing import Dict, Sequence

from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.graph_ids import GraphId, NodeId
from keystone_tpu.workflow.operators import ExpressionOperator
from keystone_tpu.workflow.optimizer.column_samples import (
    ColumnSamplerMoveRule,
    SiblingSamplerRule,
)
from keystone_tpu.workflow.optimizer.default import DefaultOptimizer
from keystone_tpu.workflow.optimizer.fusion import (
    _fusable,
    fused_gather_transformer,
    fused_transformer,
)
from keystone_tpu.workflow.optimizer.node_rule import NodeOptimizationRule
from keystone_tpu.workflow.optimizer.rule import (
    Batch,
    FixedPoint,
    Once,
    Optimizer,
    Rule,
)
from keystone_tpu.workflow.optimizer.stream_gather import GatherStreamingRule
from keystone_tpu.workflow.prefix import compute_prefix


class OldEquivalentNodeMergeRule(Rule):
    """Merges nodes with equal operators and IDENTICAL dependency lists;
    run to a fixed point so that merges cascade."""

    def apply(self, graph: Graph) -> Graph:
        buckets: list = []  # list of (op, deps, [node ids])
        for n in sorted(graph.nodes, key=lambda g: g.id):
            op = graph.get_operator(n)
            deps = graph.get_dependencies(n)
            for b_op, b_deps, ids in buckets:
                if b_deps == deps and b_op == op:
                    ids.append(n)
                    break
            else:
                buckets.append((op, deps, [n]))
        out = graph
        changed = False
        for _, _, ids in buckets:
            if len(ids) > 1:
                keep, rest = ids[0], ids[1:]
                for r in rest:
                    out = out.replace_dependency(r, keep).remove_node(r)
                changed = True
        return out if changed else graph


class OldUnusedBranchRemovalRule(Rule):
    def apply(self, graph: Graph) -> Graph:
        needed: set = set()
        for k in graph.sinks:
            dep = graph.get_sink_dependency(k)
            needed.add(dep)
            needed |= graph.get_ancestors(dep)
        unused = [n for n in graph.nodes if n not in needed]
        if not unused:
            return graph
        out = graph
        for n in unused:
            out = out.remove_node(n)
        return out


class OldSavedStateLoadRule(Rule):
    def apply(self, graph: Graph) -> Graph:
        state = PipelineEnv.get_or_create().state
        if not state:
            return graph
        out = graph
        changed = False
        memo: Dict[GraphId, object] = {}
        for n in sorted(graph.nodes, key=lambda g: g.id):
            op = graph.get_operator(n)
            if isinstance(op, ExpressionOperator):
                continue
            prefix = compute_prefix(graph, n, memo)
            if prefix is not None and prefix in state:
                out = out.set_operator(n, ExpressionOperator(state[prefix]))
                out = out.set_dependencies(n, ())
                changed = True
        return out if changed else graph


def _consumers_and_sink_deps(graph: Graph):
    consumers: Dict = {}
    for nid, deps in graph.dependencies.items():
        for d in deps:
            consumers.setdefault(d, set()).add(nid)
    return consumers, set(graph.sink_dependencies.values())


class OldMapFusionRule(Rule):
    """Fuses ONE (producer, consumer) pair an application."""

    def apply(self, graph: Graph) -> Graph:
        consumers, sink_deps = _consumers_and_sink_deps(graph)

        for b in sorted(graph.nodes, key=lambda n: n.id):
            deps = graph.get_dependencies(b)
            if len(deps) != 1 or not isinstance(deps[0], NodeId):
                continue
            a = deps[0]
            op_a, op_b = graph.get_operator(a), graph.get_operator(b)
            if not (_fusable(op_a) and _fusable(op_b)):
                continue
            if consumers.get(a, set()) != {b} or a in sink_deps:
                continue  # a's output is needed elsewhere
            fused = fused_transformer([op_a, op_b])
            g = graph.set_operator(b, fused)
            g = g.set_dependencies(b, graph.get_dependencies(a))
            return g.remove_node(a)
        return graph


class OldGatherFusionRule(Rule):
    """Fuses ONE gather with its branches an application."""

    def apply(self, graph: Graph) -> Graph:
        from keystone_tpu.workflow.pipeline import GatherTransformerOperator

        consumers, sink_deps = _consumers_and_sink_deps(graph)

        for gth in sorted(graph.nodes, key=lambda n: n.id):
            if not isinstance(
                    graph.get_operator(gth), GatherTransformerOperator):
                continue
            deps = graph.get_dependencies(gth)
            if not deps or not all(isinstance(d, NodeId) for d in deps):
                continue
            ops = [graph.get_operator(d) for d in deps]
            if not all(_fusable(op) for op in ops):
                continue
            srcs = set()
            ok = True
            for d in set(deps):
                if consumers.get(d, set()) != {gth} or d in sink_deps:
                    ok = False
                    break
                bdeps = graph.get_dependencies(d)
                if len(bdeps) != 1:
                    ok = False
                    break
                srcs.add(bdeps[0])
            if not ok or len(srcs) != 1:
                continue
            g = graph.set_operator(gth, fused_gather_transformer(ops))
            g = g.set_dependencies(gth, (srcs.pop(),))
            for d in set(deps):
                g = g.remove_node(d)
            return g
        return graph


class OldEngine(Optimizer):
    """The engine as it was: a round of a fixed-point batch ends with a
    comparison of whole graphs."""

    def execute(self, graph: Graph) -> Graph:
        for batch in self.batches:
            iters = (1 if isinstance(batch.strategy, Once)
                     else batch.strategy.max_iterations)
            for _ in range(iters):
                before = graph
                for rule in batch.rules:
                    graph = rule.apply(graph)
                if graph == before:
                    break
        return graph


class OracleOptimizer(OldEngine):
    """``DefaultOptimizer``'s batches, in its order, with the old rules.
    The node-level rules are the package's own: PR 27 left them as they
    were. So are the column-sample rules (PR 49), which had no
    predecessor."""

    @property
    def batches(self) -> Sequence[Batch]:
        return [
            Batch("saved-state and pruning", Once(),
                  [OldSavedStateLoadRule(), OldUnusedBranchRemovalRule()]),
            Batch("CSE", FixedPoint(100), [OldEquivalentNodeMergeRule()]),
            Batch("node-level optimization", Once(),
                  [NodeOptimizationRule(), GatherStreamingRule()]),
            Batch("post-splice CSE", FixedPoint(100),
                  [OldEquivalentNodeMergeRule()]),
            Batch("column samples", Once(),
                  [ColumnSamplerMoveRule(), SiblingSamplerRule()]),
            Batch("map fusion", FixedPoint(1000),
                  [OldMapFusionRule(), OldGatherFusionRule()]),
        ]


assert [b.name for b in OracleOptimizer().batches] == [
    b.name for b in DefaultOptimizer().batches]
