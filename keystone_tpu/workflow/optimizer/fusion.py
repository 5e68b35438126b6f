"""Map-chain fusion: collapse linear chains of per-item device nodes
into ONE jitted stage.

The TPU-native optimization SURVEY.md section 7 calls "staged jit'd
segments": the reference pays nothing for chains of `rdd.map`s (Spark
pipelines narrow transformations within a stage automatically); here
each Transformer node is otherwise a separate `jit(vmap(...))` dispatch.
Fusing a >> b >> c into one jit removes per-node dispatch latency and
lets XLA fuse elementwise work across node boundaries into surrounding
GEMMs — the HBM-bandwidth win.

Runs after fitting too: `FittedPipeline.apply` re-optimizes its
transformer-only graph, so fitted model chains (scaler >> linear model
>> argmax) also fuse. Stages implementing the fitted-param protocol
(``Transformer.apply_params``/``apply_with_params``) thread their
fitted arrays through the fused program as runtime ARGUMENTS, so one
compiled program per chain STRUCTURE serves every refit — fusion and
the content-free compile property compose instead of trading off.

One application of ``MapFusionRule`` fuses every whole chain and one of
``GatherFusionRule`` every fusable gather, each building one new graph
from one table of readers (``Graph.consumers``); the batch's fixed point
takes three rounds (chains, then gathers; the fused gather with what is
around it; a round that finds nothing), whatever the graph's width.

Only nodes with DEFAULT dataset semantics fuse — anything overriding
``apply_dataset`` (whole-batch GEMMs, Windower-style reshapes, host
stages, Cacher materialization points) keeps its node boundary, except
nodes marked ``fusion_safe`` (whose override is an optimized
equivalent of the default per-item map).

Fused chains stream: ``FusedTransformer``/``FusedGatherTransformer``
inherit the default ``apply_dataset``, whose StreamingDataset branch
applies the whole fused program per chunk — one structure-keyed compile
serves every chunk (all chunks share one padded shape) and every refit,
so the ingest-overlapped path pays zero extra compiles
(``tests/test_streaming.py::test_fused_chain_streams_per_chunk``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax

from ..graph import Graph
from ..graph_ids import NodeId
from ..transformer import (
    HostTransformer,
    Transformer,
    config_shim,
    struct_cached_jit,
)
from .rule import Rule


def _stage_key(s: Transformer):
    """Per-stage contribution to a fused program's jit key: the
    content-free struct_key for param-protocol stages (their fitted
    arrays ride as runtime arguments), the full content-bearing eq_key
    for baked stages (whose arrays become program constants, so sharing
    requires identical content)."""
    if s.apply_params() is not None:
        return ("params", s.struct_key())
    return ("baked", s._cached_eq_key())


def _param_batched(node, stages: List[Transformer]):
    """Whole-batch callable for a fused chain/fan-out with every stage's
    fitted params threaded as jit ARGUMENTS: one compiled program per
    chain STRUCTURE serves every refit (the same content-free property
    as ``nodes/learning/linear._affine_apply_batch``, composed through
    fusion). Returns None when any stage key is unhashable (fall back to
    the content-keyed path)."""
    try:
        key = (type(node), tuple(_stage_key(s) for s in stages))
        hash(key)
    except TypeError:
        return None
    plist = node.__dict__.get("_jit_fused_params")
    if plist is None:
        plist = tuple(s.apply_params() for s in stages)
        node.__dict__["_jit_fused_params"] = plist  # _jit_*: unpickled

    is_gather = isinstance(node, FusedGatherTransformer)

    def builder():
        # param stages are captured as array-free config shims so the
        # hot cached program cannot pin the first refit's fitted arrays;
        # baked stages keep the live instance (their key includes the
        # content eq_key, so sharing implies identical arrays anyway)
        bound = [s if s.apply_params() is None else config_shim(s)
                 for s in stages]

        def raw(params, X):
            def item(x):
                if is_gather:
                    return tuple(
                        s.apply_with_params(p, x)
                        for s, p in zip(bound, params))
                for s, p in zip(bound, params):
                    x = s.apply_with_params(p, x)
                return x

            return jax.vmap(item)(X)

        return raw

    fn = struct_cached_jit(key, builder)
    return lambda X: fn(plist, X)


class FusedTransformer(Transformer):
    """Composition of per-item transformers executed in one jit."""

    def __init__(self, stages: List[Transformer]):
        flat: List[Transformer] = []
        for s in stages:
            flat.extend(s.stages if isinstance(s, FusedTransformer) else [s])
        self.stages = flat

    def eq_key(self):
        return (FusedTransformer,
                tuple(s._cached_eq_key() for s in self.stages))

    def apply(self, x):
        for s in self.stages:
            x = s.apply(x)
        return x

    def _batched(self):
        fn = _param_batched(self, self.stages)
        return fn if fn is not None else super()._batched()

    @property
    def keeps_padding(self) -> bool:
        """A chain of stages that all keep padding and carry no fitted
        arrays is mapped over a padded chunk as one program."""
        return all(s.keeps_padding and s.apply_params() is None
                   for s in self.stages)

    def chunk_stage(self):
        """The stages' own forms over padded chunks, one after another
        (one program each: a chunk's extent and mask pass between them
        on the host); None as soon as one stage has none."""
        if self.keeps_padding:
            return super().chunk_stage()
        stages = [s.chunk_stage() for s in self.stages]
        if any(stage is None for stage in stages):
            return None

        def run(chunk):
            for stage in stages:
                chunk = stage(chunk)
            return chunk

        return run

    def label(self) -> str:
        return "Fused[" + " >> ".join(s.label() for s in self.stages) + "]"


#: The optimizer re-runs on every bind of an unfitted pipeline; reusing
#: the same fused instance for the same stage chain keeps its
#: per-instance jit cache warm across binds (a fresh instance per
#: optimize pass would recompile the fused stage every time).
_fusion_cache: Dict[Tuple, Transformer] = {}


def _memoized(fused):
    try:
        return _fusion_cache.setdefault(fused._cached_eq_key(), fused)
    except TypeError:  # unhashable stage key: skip memoization
        return fused


def fused_transformer(stages: List[Transformer]) -> FusedTransformer:
    return _memoized(FusedTransformer(stages))


def _fusable(op) -> bool:
    return (
        isinstance(op, Transformer)
        and not isinstance(op, HostTransformer)
        and (type(op).apply_dataset is Transformer.apply_dataset
             or op.fusion_safe)  # optimized-but-equivalent overrides
        and not getattr(op, "saveable", False)
    )


class FusedGatherTransformer(Transformer):
    """N branches + the gather zip executed in one jit: ``apply(x)``
    returns the per-item tuple of branch outputs that
    ``GatherTransformerOperator`` previously assembled from separately
    dispatched branch nodes."""

    def __init__(self, branches: List[Transformer]):
        self.branches = list(branches)

    def eq_key(self):
        return (FusedGatherTransformer,
                tuple(b._cached_eq_key() for b in self.branches))

    def apply(self, x):
        return tuple(b.apply(x) for b in self.branches)

    def _batched(self):
        fn = _param_batched(self, self.branches)
        return fn if fn is not None else super()._batched()

    def label(self) -> str:
        return ("FusedGather[" +
                ", ".join(b.label() for b in self.branches) + "]")


def fused_gather_transformer(branches: List[Transformer]) -> FusedGatherTransformer:
    return _memoized(FusedGatherTransformer(branches))


def _sole_reader(graph: Graph, gid, reader: NodeId) -> bool:
    """``reader`` is the one thing that reads ``gid``: no other node, no
    sink."""
    return graph.consumers.get(gid) == {reader}


class MapFusionRule(Rule):
    """Fuse every chain of default-semantics transformers into its last
    node, in one application: ``a >> b >> c``, each read by the next and
    by nothing else, becomes ``fused_transformer([a, b, c])`` under
    ``c``'s id, fed what ``a`` was fed."""

    def apply(self, graph: Graph) -> Graph:
        fusable = {n for n, op in graph.operators.items() if _fusable(op)}
        # node -> the one node it reads, where the two can be fused
        reads: Dict[NodeId, NodeId] = {}
        for b, deps in graph.dependencies.items():
            if (b in fusable and len(deps) == 1 and deps[0] in fusable
                    and _sole_reader(graph, deps[0], b)):
                reads[b] = deps[0]
        if not reads:
            return graph
        operators, dependencies = {}, {}
        fused_away = set(reads.values())
        for last in reads:
            if last in fused_away:
                continue  # the chain ends further down
            chain = [last]
            while chain[-1] in reads:
                chain.append(reads[chain[-1]])
            chain.reverse()
            operators[last] = fused_transformer(
                [graph.operators[n] for n in chain])
            dependencies[last] = graph.dependencies[chain[0]]
        return graph.rewrite(operators, dependencies, remove=fused_away)


class GatherFusionRule(Rule):
    """Fuse a Gather node with its fusable single-input branches.

    ``gather(branch_1, ..., branch_N)`` otherwise pays one dispatch per
    branch plus a zip; when every branch is a default-semantics
    transformer hanging off the SAME upstream node, the whole fan-out
    collapses into one jit emitting the per-item tuple directly (MNIST's
    4 FFT branches, TIMIT's 8 cosine branches, ImageNet's
    gather(SIFT, LCS)). MapFusionRule then composes the fused gather
    with the downstream combiner and upstream chain as usual. One
    application fuses every gather that qualifies: two that do share
    no branch, and neither is the other's input.
    """

    def apply(self, graph: Graph) -> Graph:
        from ..pipeline import GatherTransformerOperator

        operators, dependencies, fused_away = {}, {}, set()
        for gth, op in graph.operators.items():
            if not isinstance(op, GatherTransformerOperator):
                continue
            deps = graph.dependencies[gth]
            if not deps or not all(isinstance(d, NodeId) for d in deps):
                continue
            ops = [graph.operators[d] for d in deps]
            if not all(_fusable(op) for op in ops):
                continue
            # every branch must feed only this gather (CSE-merged
            # duplicate branches appear twice in deps — allowed), and
            # all branches must hang off one common upstream input
            branches = set(deps)
            srcs = {graph.dependencies[d] for d in branches}
            if len(srcs) != 1 or not all(
                    _sole_reader(graph, d, gth) for d in branches):
                continue
            (src,) = srcs
            if len(src) != 1:
                continue
            operators[gth] = fused_gather_transformer(ops)
            dependencies[gth] = src
            fused_away |= branches
        if not operators:
            return graph
        return graph.rewrite(operators, dependencies, remove=fused_away)
