"""Core graph rewrite rules.

Mirrors ``workflow/graph/{EquivalentNodeMergeRule, UnusedBranchRemovalRule,
SavedStateLoadRule}.scala``. Each rule does its whole rewrite in one
walk of the graph and builds one new ``Graph`` (``Graph.rewrite`` /
``Graph.induce``), so an application costs O(nodes + edges); a rule that
finds nothing to do returns the graph it was given, which is how the
engine knows a fixed point.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from ..env import PipelineEnv
from ..graph import Graph
from ..graph_ids import GraphId, NodeId
from ..operators import ExpressionOperator, Operator
from ..prefix import compute_prefix
from .rule import Rule


class EquivalentNodeMergeRule(Rule):
    """Common-subexpression elimination: merge nodes whose operators are
    equal and whose dependencies are the same once THEIR duplicates are
    merged (``EquivalentNodeMergeRule.scala:1-48``). One application
    merges all of it: nodes are met dependencies first and bucketed by
    (operator, dependencies as renamed so far), so a merge reaches the
    consumers in the same walk. Of each class the node with the lowest
    id stays."""

    def apply(self, graph: Graph) -> Graph:
        first_met: Dict[GraphId, GraphId] = {}  # duplicate -> its class
        classes: Dict[NodeId, List[NodeId]] = {}
        hashed: Dict[Tuple, NodeId] = {}
        # an operator whose key cannot be hashed is compared with the
        # few others of its kind
        unhashed: List[Tuple[Operator, Tuple, NodeId]] = []
        for n in graph.linearize():
            if not isinstance(n, NodeId):
                continue
            op = graph.operators[n]
            deps = tuple(first_met.get(d, d) for d in graph.dependencies[n])
            try:
                first = hashed.setdefault((op, deps), n)
            except TypeError:
                for b_op, b_deps, first in unhashed:
                    if b_deps == deps and b_op == op:
                        break
                else:
                    first = n
                    unhashed.append((op, deps, n))
            if first is n:
                classes[n] = [n]
            else:
                first_met[n] = first
                classes[first].append(n)
        if not first_met:
            return graph
        rename = {}
        for ids in classes.values():
            keep = min(ids)
            rename.update((n, keep) for n in ids if n is not keep)
        return graph.rewrite(remove=rename.keys(), rename=rename)


class UnusedBranchRemovalRule(Rule):
    """Remove nodes that no sink depends on, transitively
    (``UnusedBranchRemovalRule.scala:8-23``). Sources are kept: a
    pipeline's dangling input is part of its shape."""

    def apply(self, graph: Graph) -> Graph:
        sink_deps = graph.sink_dependencies.values()
        needed = graph.get_ancestors(*sink_deps).union(sink_deps)
        if all(n in needed for n in graph.operators):
            return graph
        return graph.induce(needed | graph.sources)


class SavedStateLoadRule(Rule):
    """Substitute nodes whose logical prefix already has a computed value in
    the global state table with an ExpressionOperator holding that value
    (``SavedStateLoadRule.scala:8-18``)."""

    def apply(self, graph: Graph) -> Graph:
        state = PipelineEnv.get_or_create().state
        if not state:
            return graph
        saved: Dict[NodeId, Operator] = {}
        memo: Dict[GraphId, object] = {}
        for n, op in graph.operators.items():
            if isinstance(op, ExpressionOperator):
                continue
            prefix = compute_prefix(graph, n, memo)
            if prefix is not None and prefix in state:
                saved[n] = ExpressionOperator(state[prefix])
        if not saved:
            return graph
        return graph.rewrite(
            operators=saved, dependencies=dict.fromkeys(saved, ()))
