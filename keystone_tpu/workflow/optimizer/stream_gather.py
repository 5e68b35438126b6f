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
  arrays ride as program arguments; a narrower last one that can be
  widened with zero columns);
* between the combiner and the estimator stands nothing but ``Cacher``
  nodes, which have nothing to cache once the gather is never whole,
  and fitted transformers whose estimators the solver says it can carry
  into its sweep (``between``: a ``StandardScaler`` works column by
  column, so a block is standardised where it is centred);
* one data shard of the gathered matrix (its bytes over the mesh's
  data shards: it is laid in rows over them) would take more than
  ``MAX_GATHER_SHARE`` of one device's memory
  (``analysis.resources.device_memory_bytes``).

One block of ALL rows is reckoned against the same memory by the same
arithmetic (``analysis.resources.stream_row_chunk``): where it and its
centred copy would not fit, the solver's sweeps take the rows in chunks
of that many, and the choice is recorded beside this one
(``row_chunk``; None: every row at once).

Then the estimator's node becomes a :class:`~keystone_tpu.workflow.\
optimizable.StreamedGatherFit` fed by the raw rows, and every delegating
child of it (the fitted model applied to the pipeline's input, to test
rows, to the training rows again) is fed what came BEFORE its own copy
of the gather: the fitted transformer makes its blocks itself. (The
child applied to the training rows is then fed the one node the fit is
fed, and is answered with the scores the fit's last sweep held:
``DelegatingOperator.execute``.) The
branch, gather, combiner, cache and scaler nodes nothing needs any more
are removed.
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
    EstimatorOperator,
    ExpressionOperator,
)
from ..optimizable import StreamedGatherFit
from ..transformer import HostTransformer, Transformer
from .rule import Rule
from .rules import UnusedBranchRemovalRule

#: A gathered matrix whose data shard is over this share of one device's
#: memory is not made.
#: The rest is for the rows, a Gram, the factors, the model and the
#: evaluation's copies (the materialised fit of 3.66 GiB of features
#: peaks at 4.43 GiB, ``PERF.md`` section 4).
MAX_GATHER_SHARE = 0.5

Match = Tuple[Transformer, List[Transformer], GraphId]
#: What stands between a combiner and an estimator, from the combiner
#: outward: ``("map", Cacher)`` or ``("fit", estimator, delegating
#: operator, node of the estimator, whether it was fitted on what the
#: delegating operator applies it to)``.
Chain = Tuple[tuple, ...]


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


def gather_behind_chain(graph: Graph, gid: GraphId
                        ) -> Optional[Tuple[Chain, Match]]:
    """``(chain, match)`` when ``gid`` is a gather of branches
    (``gather_of_branches``) seen through nothing but ``Cacher`` nodes
    and applications of fitted transformers; else None. Where ``gid`` is
    the combiner itself the chain is empty and this costs what
    ``gather_of_branches`` costs."""
    from ..common import Cacher

    chain: List[tuple] = []
    while True:
        found = gather_of_branches(graph, gid)
        if found is not None:
            return tuple(reversed(chain)), found
        if not isinstance(gid, NodeId):
            return None
        op, deps = graph.get_operator(gid), graph.get_dependencies(gid)
        if isinstance(op, Cacher) and len(deps) == 1:
            chain.append(("map", op))
            gid = deps[0]
        elif (isinstance(op, DelegatingOperator) and len(deps) == 2
              and isinstance(deps[0], NodeId)):
            fit = graph.get_operator(deps[0])
            chain.append(("fit", fit, op, deps[0], isinstance(
                fit, EstimatorOperator) and graph.get_dependencies(
                    deps[0]) == (deps[1],)))
            gid = deps[1]
        else:
            return None


def _same_chain(theirs: Chain, ours: Chain) -> bool:
    """Entry by entry the same: a cache is the same operator; a fitted
    transformer comes from the same NODE where ours says which, and is
    of the same kind where ours is the chain kept on a saved fit (there
    the state table may have answered for that estimator too)."""
    return len(theirs) == len(ours) and all(
        a[0] == b[0] and (a[1] == b[1] if a[0] == "map"
                          else len(b) < 4 or a[3] == b[3])
        for a, b in zip(theirs, ours))


def _rows_spec(graph: Graph, gid: GraphId):
    """The DatasetSpec of ``gid``: read off a constant dataset, or by
    the abstract interpreter for anything else."""
    if isinstance(gid, NodeId) and isinstance(
            graph.get_operator(gid), DatasetOperator):
        return graph.get_operator(gid).abstract_eval(())
    from ...analysis.interpreter import analyze

    return analyze(graph).value(gid)


def gathered_nbytes(spec, branches: Sequence[Transformer]):
    """``(bytes of the gathered matrix, width of every branch)`` for
    rows of ``spec`` through ``branches``, or None where the shapes do
    not resolve. One abstract evaluation for all branches but the last
    (the estimator checks that they are of one structure), and one more
    for a last branch of another structure."""
    import jax
    import numpy as np

    from ...analysis.spec import DatasetSpec, element_has_unknown

    if (not isinstance(spec, DatasetSpec) or spec.n is None
            or element_has_unknown(spec.element)):
        return None
    outs = [jax.eval_shape(branches[0].apply, spec.element)]
    try:
        ragged = branches[-1].struct_key() != branches[0].struct_key()
    except TypeError:
        ragged = False
    if ragged:
        outs.append(jax.eval_shape(branches[-1].apply, spec.element))
    if any(not hasattr(o, "shape") or len(o.shape) != 1 for o in outs):
        return None
    widths = [int(outs[0].shape[0])] * (len(branches) - 1) + [
        int(outs[-1].shape[0])]
    return (float(spec.n) * sum(widths)
            * np.dtype(outs[0].dtype).itemsize, widths)


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
    def _children(graph: Graph, node: NodeId, combiner, branches,
                  chain: Chain = ()):
        """``(delegating child, what feeds its own copy of the gather)``
        for every delegating child of ``node``; None if one of them is
        fed anything but that gather behind that chain."""
        found = []
        for child in graph.get_children(node):
            if not isinstance(child, NodeId) or not isinstance(
                    graph.get_operator(child), DelegatingOperator):
                continue
            cdeps = graph.get_dependencies(child)
            theirs = (gather_behind_chain(graph, cdeps[1])
                      if len(cdeps) == 2 else None)
            if (theirs is None or not _same_chain(theirs[0], chain)
                    or theirs[1][0] != combiner
                    or tuple(theirs[1][1]) != tuple(branches)):
                return None
            found.append((child, cdeps[0], theirs[1][2]))
        return found

    @staticmethod
    def _feed_raw_rows(graph: Graph, children) -> Optional[Graph]:
        if not children:
            return None
        return UnusedBranchRemovalRule().apply(graph.rewrite(dependencies={
            child: (fitted, upstream) for child, fitted, upstream in children}))

    def _rewrite(self, graph: Graph, node: NodeId, op) -> Optional[Graph]:
        deps = graph.get_dependencies(node)
        found = gather_behind_chain(graph, deps[0]) if deps else None
        if found is None:
            return None
        chain, (combiner, branches, rows) = found
        # a transformer in the chain must have been fitted on exactly
        # what it is applied to here: the training rows' own features
        if not all(e[4] for e in chain if e[0] == "fit"):
            return None
        children = self._children(graph, node, combiner, branches, chain)
        if children is None:
            return None
        spec = _rows_spec(graph, rows)
        sized = gathered_nbytes(spec, branches)
        if sized is None:
            return None
        nbytes, widths = sized
        between = [entry[1] for entry in chain if entry[0] == "fit"]
        if not op.streams_branches(branches, widths, between):
            return None
        from ...analysis.resources import (device_memory_bytes,
                                           stream_row_chunk)
        from ...parallel.mesh import num_data_shards

        # the gathered matrix is laid in rows over the mesh's data axis:
        # what one device must hold of it is a shard, not the whole
        shards = num_data_shards()
        limit = MAX_GATHER_SHARE * device_memory_bytes()
        # ... and so is one block of all rows, which the solver's sweeps
        # reckon for themselves from the rows they are handed
        self._record(node, op, nbytes, limit, len(branches), widths[0],
                     shards, stream_row_chunk(-(-spec.n // shards),
                                              widths[0]))
        if nbytes / shards <= limit:
            return None
        out = graph.set_operator(node, StreamedGatherFit(
            op, combiner, branches, tuple(e[:3] for e in chain)))
        out = out.set_dependencies(node, (rows,) + tuple(deps[1:]))
        return self._feed_raw_rows(out, children) or out

    @staticmethod
    def _record(node, op, nbytes, limit, blocks, width, shards,
                row_chunk) -> None:
        from ...observability.trace import current_trace

        trace = current_trace()
        if trace is not None:
            trace.record_node_choice({
                "node_id": node.id,
                "optimizable": type(op).__name__,
                "chosen": ("StreamedGatherFit" if nbytes / shards > limit
                           else type(op).__name__),
                "gathered_nbytes": nbytes,
                "data_shards": shards,
                "limit_nbytes": limit,
                "blocks": blocks,
                "block_width": width,
                "row_chunk": row_chunk,
                "provenance": "static",
            })
