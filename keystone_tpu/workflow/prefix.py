"""Logical prefix hashing for incremental cross-pipeline state reuse.

Mirrors ``workflow/graph/Prefix.scala:13-30``: a node's Prefix is a
structural hash of its operator together with the prefixes of all its
dependencies. Nodes whose ancestry reaches an unconnected Source have no
prefix (their value depends on unbound input), and neither do nodes
downstream of a single datum: a ``DatumOperator`` is known only by the
``id()`` of an object the state table does not keep alive, so a saved
result would answer for whichever later datum was allocated at the same
address. Prefixes key the global
``PipelineEnv.state`` memo so that re-running a pipeline (or a different
pipeline sharing a fitted prefix) reuses already-computed expressions.

Prefixes are CANONICAL under map/gather fusion: a
``FusedTransformer([a, b, c])`` node contributes exactly the prefix of
the unfused ``a >> b >> c`` chain, and a ``FusedGatherTransformer``
contributes the unfused gather-of-branches prefix. Fitted state is
saved at executor time — on the OPTIMIZED (fused) graph — while
``SavedStateLoadRule`` matches on the next run's RAW (unfused) graph;
without canonicalization the two signatures never meet, so any pipeline
whose pre-estimator chain fuses silently refits every run (the
cache-miss recorded in CHANGES.md PR 1, surfaced statically by the
``fusion-prefix-hazard`` lint in ``analysis/diagnostics.py``). The same
holds for a ``StreamedGatherFit``: it contributes the prefix of its
estimator on the materialised gather of its branches, behind the chain
of cache and scaler nodes the streamed form dropped. An operator that
another rule put in says what it stands for itself
(``Operator.canonical_prefix``: a column sample drawn in front of the
chain it was written behind, ``optimizer/column_samples.py``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from .graph import Graph
from .graph_ids import GraphId, NodeId, SourceId
from .operators import DatumOperator, Operator


def operator_prefix(op: Operator, dep_prefixes: Tuple) -> Tuple:
    """Canonical prefix contribution of one operator given its
    dependencies' prefixes — fused operators expand to the prefix of the
    equivalent unfused subgraph."""
    from .optimizer.fusion import FusedGatherTransformer, FusedTransformer

    if op.canonical_prefix is not None:
        return op.canonical_prefix(dep_prefixes)
    if isinstance(op, FusedTransformer):
        (cur,) = dep_prefixes
        for stage in op.stages:
            cur = operator_prefix(stage, (cur,))
        return cur
    if isinstance(op, FusedGatherTransformer):
        from .pipeline import GatherTransformerOperator

        (p,) = dep_prefixes
        branch_ps = tuple(
            operator_prefix(b, (p,)) for b in op.branches)
        gather = GatherTransformerOperator(len(op.branches))
        return ("prefix", gather._cached_eq_key(), branch_ps)
    from .optimizable import StreamedGatherFit

    if isinstance(op, StreamedGatherFit):
        # the estimator on combine(gather(branches)) of the same rows
        from .pipeline import GatherTransformerOperator

        rows, *rest = dep_prefixes
        gather = GatherTransformerOperator(len(op.branches))
        gathered = ("prefix", gather._cached_eq_key(), tuple(
            operator_prefix(b, (rows,)) for b in op.branches))
        fed = operator_prefix(op.combiner, (gathered,))
        for kind, *ops in op.chain:   # what stood between the two
            if kind == "map":
                fed = operator_prefix(ops[0], (fed,))
            else:   # a transformer fitted on what it is applied to
                fed = operator_prefix(
                    ops[1], (operator_prefix(ops[0], (fed,)), fed))
        return operator_prefix(op.estimator, (fed, *rest))
    return ("prefix", op._cached_eq_key(), tuple(dep_prefixes))


def compute_prefix(
    graph: Graph, gid: GraphId, _memo: Optional[Dict[GraphId, Optional[Tuple]]] = None
) -> Optional[Tuple]:
    """Canonical structural prefix of ``gid`` in ``graph``, or None if it
    depends on an unconnected source or on a single datum."""
    memo: Dict[GraphId, Optional[Tuple]] = _memo if _memo is not None else {}
    if gid in memo:
        return memo[gid]
    if isinstance(gid, SourceId):
        memo[gid] = None
        return None
    assert isinstance(gid, NodeId)
    if isinstance(graph.get_operator(gid), DatumOperator):
        memo[gid] = None
        return None
    memo[gid] = None  # cycle guard; DAGs shouldn't cycle but be safe
    dep_prefixes = []
    for d in graph.get_dependencies(gid):
        p = compute_prefix(graph, d, memo)
        if p is None:
            memo[gid] = None
            return None
        dep_prefixes.append(p)
    result = operator_prefix(graph.get_operator(gid), tuple(dep_prefixes))
    memo[gid] = result
    return result
