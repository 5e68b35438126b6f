"""Column samples drawn where a pass is made anyway.

A ``ColumnSampler`` keeps columns of each item by index, drawn on the
host from its seed, the item's place and the item's true width alone. A
node that maps every column by itself (``Transformer.maps_columns``: a
projection from the left, a cast, a ``Cacher``) changes none of the
three, so *the map of a sample is the sample of the map*, and a
pipeline written ``rows >> project >> cache >> sample`` may be run
``rows >> sample >> project``. Two rules, both read off the graph alone:

* :class:`ColumnSamplerMoveRule` moves a sampler in front of the chain
  of column-wise nodes it reads (delegates of estimators that say their
  transformer will be one, ``EstimatorOperator.fitted_maps_columns``,
  among them). In front of maps always: fewer columns are mapped. In
  front of a ``Cacher`` only where another sampler draws from what the
  whole chain stands on: a cache is there to be read, and leaving it is
  free only where the pass is made anyway. The sampler's node stays,
  as a ``ColumnSampleAhead`` that draws from what the chain stands on
  and applies the chain to the sample itself, fed by the chain's fits:
  its readers are untouched, and whatever else read the chain still
  does. Nodes the sampler alone read go.
* :class:`SiblingSamplerRule` lets the samplers that read one node draw
  in one pass: the first ``ColumnSampler`` among them becomes a
  ``SharedColumnSampler`` that draws for all, and the others take their
  sample from it. A dataset whose items are made when asked for is then
  made once for all of them. A sampler equal to the first takes the
  first's own sample.

Neither changes a draw, and neither adds a node. A graph with no
``ColumnSampler`` is returned as it came after a scan of its operators'
types.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..common import Cacher
from ..graph import Graph
from ..graph_ids import GraphId, NodeId
from ..operators import DelegatingOperator, EstimatorOperator, Operator
from ..transformer import Transformer
from .rule import Rule
from .rules import UnusedBranchRemovalRule


def _data_below(graph: Graph, gid: GraphId) -> Optional[GraphId]:
    """What ``gid`` maps column by column, or None where it is no such
    node."""
    op = graph.operators.get(gid)
    deps = graph.dependencies.get(gid, ())
    if isinstance(op, DelegatingOperator):
        if len(deps) == 2:
            fit = graph.operators.get(deps[0])
            if isinstance(fit, EstimatorOperator) and fit.fitted_maps_columns:
                return deps[1]
        return None
    if isinstance(op, Transformer) and op.maps_columns and len(deps) == 1:
        return deps[0]
    return None


class ColumnSamplerMoveRule(Rule):
    def apply(self, graph: Graph) -> Graph:
        from ...nodes.stats.sampling import ColumnSampleAhead, ColumnSampler

        samplers = [n for n, op in graph.operators.items()
                    if isinstance(op, ColumnSampler)]
        if not samplers:
            return graph
        drawn_from = {graph.dependencies[n][0] for n in samplers}
        ops: Dict[NodeId, Operator] = {}
        deps: Dict[NodeId, Tuple[GraphId, ...]] = {}
        for n in samplers:
            chain: List[NodeId] = []
            cur = graph.dependencies[n][0]
            while (below := _data_below(graph, cur)) is not None:
                chain.append(cur)
                cur = below
            if cur not in drawn_from:
                # no pass is made of what the chain stands on: as far as
                # the first cache, which is there to be read
                caches = [i for i, c in enumerate(chain)
                          if isinstance(graph.operators[c], Cacher)]
                if caches:
                    cur = chain[caches[0]]
                    del chain[caches[0]:]
            if not chain:
                continue
            crossed, fits = [], []
            for c in chain:
                op = graph.operators[c]
                if isinstance(op, DelegatingOperator):
                    crossed.append(None)
                    fits.append(graph.dependencies[c][0])
                else:
                    crossed.append(op)
            ops[n] = ColumnSampleAhead(graph.operators[n], crossed)
            deps[n] = (cur, *fits)
        if not ops:
            return graph
        return UnusedBranchRemovalRule().apply(
            graph.rewrite(operators=ops, dependencies=deps))


class SiblingSamplerRule(Rule):
    def apply(self, graph: Graph) -> Graph:
        from ...nodes.stats.sampling import (
            ColumnSampleAhead, ColumnSampler, SharedColumnSampler)

        # who draws from what: a sampler where the pipeline wrote it,
        # or one that moved there and draws for itself so far
        readers: Dict[GraphId, List[NodeId]] = {}
        for n, op in graph.operators.items():
            if isinstance(op, ColumnSampler) or (
                    isinstance(op, ColumnSampleAhead) and op.index is None):
                readers.setdefault(graph.dependencies[n][0], []).append(n)
        ops: Dict[NodeId, Operator] = {}
        deps: Dict[NodeId, Tuple[GraphId, ...]] = {}
        for siblings in readers.values():
            # the pass is made by a sampler that depends on the rows
            # alone: whatever the others wait for, it never waits for them
            first = next((n for n in siblings if isinstance(
                graph.operators[n], ColumnSampler)), None)
            if first is None or len(siblings) < 2:
                continue
            draws = [graph.operators[first]]
            for n in siblings:
                if n is first:
                    continue
                op = graph.operators[n]
                sampler = getattr(op, "sampler", op)
                # the first's own sample where it draws the same, else
                # a place of its own, which it alone reads
                place = 0 if sampler == draws[0] else len(draws)
                if place:
                    draws.append(sampler)
                ops[n] = ColumnSampleAhead(
                    sampler, getattr(op, "chain", ()), place)
                deps[n] = (first,) + graph.dependencies[n][1:]
            ops[first] = SharedColumnSampler(draws, len(siblings))
        if not ops:
            return graph
        return graph.rewrite(operators=ops, dependencies=deps)
