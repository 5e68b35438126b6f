"""Materialise a gather, or hand its branches to the solver.

``gather(branch_1 .. branch_B) >> VectorCombiner()`` in front of an
estimator makes every branch's output for every row before the estimator
sees one: rows x total width x 4 bytes. KeystoneML's block solver needs
ONE block of features alive at a time, and where a branch is a block the
gather need never be whole. This rule chooses between the two from the
shapes and the device's memory alone, as the node-level optimizer chooses
a solver from n, d and k:

* the estimator says it can fit from raw rows plus these branches
  (``streams_branches``: equal-width blocks of one structure whose
  arrays ride as program arguments);
* the gathered matrix would take more than ``MAX_GATHER_SHARE`` of the
  device's memory (``analysis.resources.device_memory_bytes``).

Then the estimator's node becomes a :class:`~keystone_tpu.workflow.\
optimizable.StreamedGatherFit` fed by the raw rows, and every delegating
child of it (the fitted model applied to the pipeline's input, to test
rows, to the training rows again) is fed what came BEFORE its own copy
of the gather: the fitted transformer makes its blocks itself. The
branch, gather and combiner nodes nothing needs any more are removed.
Anything that does not match exactly is left alone, materialised. A fit
that the state table answers in a later graph carries the mark of how it
was made (``streams_gather`` on its expression), and its delegating
children are fed raw rows there too.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from ..graph import Graph
from ..graph_ids import GraphId, NodeId
from ..operators import (
    DatasetOperator,
    DelegatingOperator,
    ExpressionOperator,
)
from ..optimizable import StreamedGatherFit
from ..transformer import HostTransformer, Transformer
from .rule import Rule
from .rules import UnusedBranchRemovalRule

#: A gathered matrix over this share of the device's memory is not made.
#: The rest is for the rows, a Gram, the factors, the model and the
#: evaluation's copies (the materialised fit of 3.66 GiB of features
#: peaks at 4.43 GiB, ``PERF.md`` section 4).
MAX_GATHER_SHARE = 0.5

Match = Tuple[Transformer, List[Transformer], GraphId]


def gather_of_branches(graph: Graph, gid: GraphId) -> Optional[Match]:
    """``(combiner, branches, upstream)`` when ``gid`` is a concatenating
    combiner over a gather whose every branch is one device transformer
    on one common upstream id; else None. A handful of lookups: every
    fit's optimizer pays this."""
    from ..pipeline import GatherTransformerOperator

    if not isinstance(gid, NodeId):
        return None
    combiner = graph.get_operator(gid)
    if not getattr(combiner, "concatenates_gather", False):
        return None
    (gather,) = graph.get_dependencies(gid)
    if not isinstance(gather, NodeId) or not isinstance(
            graph.get_operator(gather), GatherTransformerOperator):
        return None
    branches: List[Transformer] = []
    upstream = set()
    for dep in graph.get_dependencies(gather):
        if not isinstance(dep, NodeId):
            return None
        op = graph.get_operator(dep)
        deps = graph.get_dependencies(dep)
        if (not isinstance(op, Transformer) or isinstance(op, HostTransformer)
                or len(deps) != 1):
            return None
        branches.append(op)
        upstream.add(deps[0])
    if not branches or len(upstream) != 1:
        return None
    return combiner, branches, upstream.pop()


def _rows_spec(graph: Graph, gid: GraphId):
    """The DatasetSpec of ``gid``: read off a constant dataset, or by
    the abstract interpreter for anything else."""
    if isinstance(gid, NodeId) and isinstance(
            graph.get_operator(gid), DatasetOperator):
        return graph.get_operator(gid).abstract_eval(())
    from ...analysis.interpreter import analyze

    return analyze(graph).value(gid)


def gathered_nbytes(spec, branches: Sequence[Transformer]):
    """``(bytes of the gathered matrix, width of a branch)`` for rows of
    ``spec`` through ``branches`` (all of one structure: one abstract
    evaluation), or None where the shapes do not resolve."""
    import jax
    import numpy as np

    from ...analysis.spec import DatasetSpec, element_has_unknown

    if (not isinstance(spec, DatasetSpec) or spec.n is None
            or element_has_unknown(spec.element)):
        return None
    out = jax.eval_shape(branches[0].apply, spec.element)
    if not hasattr(out, "shape") or len(out.shape) != 1:
        return None
    width = int(out.shape[0])
    return (float(spec.n) * width * len(branches)
            * np.dtype(out.dtype).itemsize, width)


class GatherStreamingRule(Rule):
    def apply(self, graph: Graph) -> Graph:
        alive = graph.nodes
        for node in sorted(alive, key=lambda n: n.id):
            if node not in alive:
                continue
            op = graph.get_operator(node)
            rewritten = None
            if callable(getattr(op, "streams_branches", None)):
                rewritten = self._rewrite(graph, node, op)
            elif isinstance(op, ExpressionOperator):
                # a fit the state table answered (SavedStateLoadRule ran
                # first): one that was made from branches takes raw rows
                made_from = getattr(op.expression, "streams_gather", None)
                if made_from is not None:
                    rewritten = self._feed_raw_rows(
                        graph, self._children(graph, node, *made_from))
            if rewritten is not None:
                graph, alive = rewritten, rewritten.nodes
        return graph

    @staticmethod
    def _children(graph: Graph, node: NodeId, combiner, branches):
        """``(delegating child, what feeds its own copy of the gather)``
        for every delegating child of ``node``; None if one of them is
        fed anything but that gather."""
        found = []
        for child in graph.get_children(node):
            if not isinstance(child, NodeId) or not isinstance(
                    graph.get_operator(child), DelegatingOperator):
                continue
            cdeps = graph.get_dependencies(child)
            theirs = (gather_of_branches(graph, cdeps[1])
                      if len(cdeps) == 2 else None)
            if (theirs is None or theirs[0] != combiner
                    or tuple(theirs[1]) != tuple(branches)):
                return None
            found.append((child, cdeps[0], theirs[2]))
        return found

    @staticmethod
    def _feed_raw_rows(graph: Graph, children) -> Optional[Graph]:
        if not children:
            return None
        return UnusedBranchRemovalRule().apply(graph.rewrite(dependencies={
            child: (fitted, upstream) for child, fitted, upstream in children}))

    def _rewrite(self, graph: Graph, node: NodeId, op) -> Optional[Graph]:
        deps = graph.get_dependencies(node)
        found = gather_of_branches(graph, deps[0]) if deps else None
        if found is None:
            return None
        combiner, branches, rows = found
        children = self._children(graph, node, combiner, branches)
        if children is None:
            return None
        sized = gathered_nbytes(_rows_spec(graph, rows), branches)
        if sized is None:
            return None
        nbytes, width = sized
        if not op.streams_branches(branches, [width] * len(branches)):
            return None
        from ...analysis.resources import device_memory_bytes

        limit = MAX_GATHER_SHARE * device_memory_bytes()
        self._record(node, op, nbytes, limit, len(branches), width)
        if nbytes <= limit:
            return None
        out = graph.set_operator(
            node, StreamedGatherFit(op, combiner, branches))
        out = out.set_dependencies(node, (rows,) + tuple(deps[1:]))
        return self._feed_raw_rows(out, children) or out

    @staticmethod
    def _record(node, op, nbytes, limit, blocks, width) -> None:
        from ...observability.trace import current_trace

        trace = current_trace()
        if trace is not None:
            trace.record_node_choice({
                "node_id": node.id,
                "optimizable": type(op).__name__,
                "chosen": ("StreamedGatherFit" if nbytes > limit
                           else type(op).__name__),
                "gathered_nbytes": nbytes,
                "limit_nbytes": limit,
                "blocks": blocks,
                "block_width": width,
                "provenance": "static",
            })
