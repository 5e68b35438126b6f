"""Profile-driven automatic caching (reference
``workflow/AutoCacheRule.scala``).

The reference's problem: uncached Spark RDDs recompute once per
downstream pass, so it profiles each node at small sample scales,
linearly extrapolates time/memory to full scale, and inserts ``Cacher``
nodes — greedily under a memory budget, or aggressively at every reused
output.

TPU translation: datasets are eager device arrays, so "caching" is a
residency decision — a Cacher pins a result into the cross-pipeline
prefix state (HBM-resident, reused across fits/applies) while uncached
intermediates are free to be dropped. The planning algorithms
(``getRuns`` execution counting with node weights, linear profile
generalization, aggressive + greedy budgeted selection) are ports of the
reference's, with the memory budget defaulting to 75% of free device
memory (reference ``AutoCacheRule.scala:470-482``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset
from ...parallel.mesh import get_mesh, num_data_shards
from ..common import Cacher
from ..graph import Graph
from ..graph_ids import NodeId
from ..operators import (
    DatasetOperator,
    EstimatorOperator,
    ExpressionOperator,
    Operator,
)
from .node_rule import _dataset_len, _sample_dataset
from .rule import Rule


@dataclass
class Profile:
    """Per-node cost measurement (reference ``AutoCacheRule.scala:9-11``;
    rddMem/driverMem collapse to one device-memory figure)."""

    ns: float = 0.0
    mem: float = 0.0

    def __add__(self, other: "Profile") -> "Profile":
        return Profile(self.ns + other.ns, self.mem + other.mem)


@dataclass
class SampleProfile:
    scale: int
    profile: Profile


def node_weight(op: Operator) -> int:
    """Passes an operator makes over its inputs (reference WeightedNode,
    ``AutoCacheRule.scala:20-32``); iterative solvers export ``weight``."""
    return int(getattr(op, "weight", 1))


def _children_with_multiplicity(graph: Graph) -> Dict[NodeId, List[NodeId]]:
    out: Dict[NodeId, List[NodeId]] = {n: [] for n in graph.nodes}
    for n in graph.nodes:
        for dep in graph.get_dependencies(n):
            if isinstance(dep, NodeId):
                out[dep].append(n)
    return out


def get_runs(
    graph: Graph,
    children: Dict[NodeId, List[NodeId]],
    cache: frozenset,
    weights: Dict[NodeId, int],
) -> Dict[NodeId, int]:
    """Estimated execution count per node given a cache set — reverse
    topological accumulation (reference ``AutoCacheRule.scala:46-71``)."""
    runs: Dict[NodeId, int] = {}
    order = [g for g in graph.linearize() if isinstance(g, NodeId)]
    for node in reversed(order):
        kids = children.get(node, [])
        if not kids:
            runs[node] = 1
        else:
            runs[node] = sum(
                weights[c] if c in cache else weights[c] * runs[c]
                for c in kids
            )
    return runs


def init_cache_set(graph: Graph) -> frozenset:
    """Nodes whose results are already effectively cached (reference
    ``AutoCacheRule.scala:76-84``): estimator fits, saved expressions, and
    Cacher applications; raw dataset constants and delegating applies are
    not."""
    cached = set()
    for n in graph.nodes:
        op = graph.get_operator(n)
        if isinstance(op, (EstimatorOperator, ExpressionOperator)):
            cached.add(n)
        elif isinstance(op, Cacher):
            cached.add(n)
    return frozenset(cached)


def _data_outputting(graph: Graph, node: NodeId) -> bool:
    """Only dataset-producing, non-Cacher nodes get Cacher insertions
    (reference ``makeCachedPipeline``, ``AutoCacheRule.scala:388-396``)."""
    op = graph.get_operator(node)
    if isinstance(op, (Cacher, EstimatorOperator, ExpressionOperator)):
        return False
    return True


def generalize_profiles(new_scale: int,
                        samples: Sequence[SampleProfile]) -> Profile:
    """Fit y = a*scale + b (least squares, clamped >= 0) per metric and
    extrapolate (reference ``AutoCacheRule.scala:91-122``)."""

    def model(pairs: List[Tuple[int, float]]) -> float:
        X = np.array([[s, 1.0] for s, _ in pairs])
        y = np.array([v for _, v in pairs])
        coef, *_ = np.linalg.lstsq(X, y, rcond=None)
        coef = np.maximum(coef, 0.0)
        return float(coef[0] * new_scale + coef[1])

    return Profile(
        ns=model([(sp.scale, sp.profile.ns) for sp in samples]),
        mem=model([(sp.scale, sp.profile.mem) for sp in samples]),
    )


def _result_mem(value) -> float:
    # shared with per-node trace records (parallel.dataset.device_nbytes):
    # one memory-accounting definition for planner and observer
    from ...parallel.dataset import device_nbytes

    return device_nbytes(value)


def profile_graph(
    graph: Graph,
    scales: Sequence[int],
    num_trials: int = 1,
) -> Dict[NodeId, Profile]:
    """Execute the non-source-dependent part of the graph on sampled
    datasets at each scale, timing each node and measuring its output
    size, then extrapolate to full scale
    (reference ``profileInstructions``, ``AutoCacheRule.scala:132-361``)."""
    from ..executor import GraphExecutor

    full_n = 0
    for n in graph.nodes:
        op = graph.get_operator(n)
        if isinstance(op, DatasetOperator):
            full_n = max(full_n, _dataset_len(op.dataset))

    shards = num_data_shards(get_mesh())
    samples_by_node: Dict[NodeId, List[SampleProfile]] = {}
    unexec = graph.source_descendants()

    for scale in scales:
        items = int(scale) * shards
        sampled = graph
        for n in graph.nodes:
            op = graph.get_operator(n)
            if isinstance(op, DatasetOperator):
                sampled = sampled.set_operator(
                    n, DatasetOperator(_sample_dataset(op.dataset, items)))
        from ...observability.trace import tracing_disabled

        for _ in range(num_trials):
            executor = GraphExecutor(sampled, optimize=False)
            for node in sampled.linearize():
                if not isinstance(node, NodeId) or node in unexec:
                    continue
                t0 = time.monotonic()
                with tracing_disabled():
                    # sampled profiling runs share node ids with the real
                    # graph; keep them out of the per-node record stream
                    value = executor.execute(node).get()
                if isinstance(value, ArrayDataset):
                    import jax

                    jax.block_until_ready(value.data)
                elapsed = (time.monotonic() - t0) * 1e9
                mem = _result_mem(value)
                samples_by_node.setdefault(node, []).append(
                    SampleProfile(items, Profile(elapsed, mem)))

    return {
        node: generalize_profiles(full_n, sps)
        for node, sps in samples_by_node.items()
    }


def estimate_cached_run_time(
    graph: Graph,
    children: Dict[NodeId, List[NodeId]],
    cached: frozenset,
    profiles: Dict[NodeId, Profile],
) -> float:
    """Total runtime estimate given a cache set
    (reference ``AutoCacheRule.scala:367-381``)."""
    weights = {n: node_weight(graph.get_operator(n)) for n in graph.nodes}
    runs = get_runs(graph, children, cached, weights)
    total = 0.0
    for n in graph.nodes:
        executions = 1 if n in cached else runs[n]
        total += profiles.get(n, Profile()).ns * executions
    return total


def make_cached_graph(graph: Graph, to_cache: frozenset) -> Graph:
    """Insert a Cacher after each selected node, re-pointing its consumers
    (reference ``makeCachedPipeline``, ``AutoCacheRule.scala:386-412``)."""
    for node in sorted(to_cache, key=lambda n: n.id):
        if node not in graph.nodes or not _data_outputting(graph, node):
            continue
        consumers = [
            c for c in graph.nodes
            if node in graph.get_dependencies(c)
        ]
        sink_consumers = [
            s for s in graph.sinks if graph.get_sink_dependency(s) == node
        ]
        graph, cacher_id = graph.add_node(Cacher(), (node,))
        for c in consumers:
            deps = tuple(
                cacher_id if d == node else d
                for d in graph.get_dependencies(c)
            )
            graph = graph.set_dependencies(c, deps)
        for s in sink_consumers:
            graph = graph.set_sink_dependency(s, cacher_id)
    return graph


def greedy_select(initial, candidates_fn, mem_of, objective,
                  budget: float) -> frozenset:
    """The profile-under-budget greedy selection loop (reference
    ``AutoCacheRule.scala:526-549``), decoupled from Cacher insertion so
    one algorithm serves all three residency planners:
    intermediate-result caching here (:meth:`AutoCacheRule._greedy`:
    minimize the estimated pipeline runtime of the cache set), the
    serving plane's multi-model placement/eviction (``serving/plane.py``:
    maximize the retained LRU-with-cost value — observed QPS x recompute
    cost — under the HBM budget), and the fleet placement solver's
    hot-model replication (``serving/placement.py``: maximize the same
    currency into each replica's leftover capacity).

    Starting from ``initial``, repeatedly add the candidate whose
    addition MINIMIZES ``objective(selected | {c})`` while the summed
    ``mem_of`` stays under ``budget``; ``candidates_fn(selected,
    space_left)`` returns the admissible additions for this step (it is
    re-evaluated every step, so run counts / recency may shift as the
    set grows). Returns the selected frozenset."""
    selected = set(initial)

    def used() -> float:
        return sum(mem_of(n) for n in selected)

    while used() < budget:
        cands = candidates_fn(frozenset(selected), budget - used())
        if not cands:
            break
        best = min(cands,
                   key=lambda c: objective(frozenset(selected | {c})))
        selected.add(best)
    return frozenset(selected)


def _device_mem_budget() -> float:
    """75% of free device memory (reference ``AutoCacheRule.scala:480``;
    ``analysis.resources.device_memory_bytes``: nominal on the CPU, an
    error on an accelerator that reports none)."""
    from ...analysis.resources import device_memory_bytes

    return 0.75 * device_memory_bytes(free=True)


class AutoCacheRule(Rule):
    """``strategy`` is "aggressive" or "greedy"
    (reference ``AutoCacheRule.scala:515-523,526-549``)."""

    AGGRESSIVE = "aggressive"
    GREEDY = "greedy"

    def __init__(
        self,
        strategy: str = GREEDY,
        max_mem: Optional[float] = None,
        scales: Sequence[int] = (2, 4),
        num_trials: int = 1,
    ):
        assert strategy in (self.AGGRESSIVE, self.GREEDY)
        self.strategy = strategy
        self.max_mem = max_mem
        self.scales = tuple(scales)
        self.num_trials = num_trials

    # -- strategies -------------------------------------------------------
    def _aggressive(self, graph: Graph) -> Graph:
        from ...observability.trace import current_trace

        children = _children_with_multiplicity(graph)
        weights = {n: node_weight(graph.get_operator(n)) for n in graph.nodes}
        downstream_of_source = graph.source_descendants()
        to_cache = frozenset(
            n for n in graph.nodes
            if sum(weights[c] for c in children[n]
                   if c not in downstream_of_source) > 1
        )
        trace = current_trace()
        if trace is not None:
            trace.record_auto_cache({
                "strategy": self.AGGRESSIVE,
                "selected": sorted(n.id for n in to_cache),
                "selected_operators": {
                    n.id: graph.get_operator(n).label() for n in to_cache},
            })
        return make_cached_graph(graph, to_cache)

    def _greedy(self, graph: Graph) -> Graph:
        profiles = profile_graph(graph, self.scales, self.num_trials)
        children = _children_with_multiplicity(graph)
        weights = {n: node_weight(graph.get_operator(n)) for n in graph.nodes}
        # per-input runtime nodes can never be reused across inputs
        downstream_of_source = graph.source_descendants()
        budget = self.max_mem if self.max_mem is not None else _device_mem_budget()

        def candidates(selected: frozenset, space_left: float):
            # run counts shift as the cache set grows, so they are
            # recomputed per selection step (the original loop's
            # post-add get_runs refresh, folded into the candidate fn)
            runs = get_runs(graph, children, selected, weights)
            return [
                n for n in graph.nodes
                if n not in selected and runs[n] > 1
                and n not in downstream_of_source
                and profiles.get(n, Profile()).mem < space_left
                and _data_outputting(graph, n)
            ]

        cached = set(greedy_select(
            init_cache_set(graph), candidates,
            lambda n: profiles.get(n, Profile()).mem,
            lambda sel: estimate_cached_run_time(
                graph, children, sel, profiles),
            budget))

        def used() -> float:
            return sum(profiles.get(n, Profile()).mem for n in cached)

        to_cache = frozenset(cached - init_cache_set(graph))
        from ...observability.trace import current_trace

        trace = current_trace()
        if trace is not None:
            # the full decision record: what was measured (extrapolated
            # per-node profiles), what was chosen, and under what budget
            # — so "was the cache choice right?" is answerable offline
            trace.record_auto_cache({
                "strategy": self.GREEDY,
                "budget_bytes": float(budget),
                "mem_used_bytes": float(used()),
                "profiles": {
                    n.id: {"ns": p.ns, "mem": p.mem}
                    for n, p in sorted(profiles.items(), key=lambda kv: kv[0].id)
                },
                "profile_scales": list(self.scales),
                "initially_cached": sorted(
                    n.id for n in init_cache_set(graph)),
                "selected": sorted(n.id for n in to_cache),
                "selected_operators": {
                    n.id: graph.get_operator(n).label() for n in to_cache},
                "estimated_uncached_s": estimate_cached_run_time(
                    graph, children, init_cache_set(graph), profiles) / 1e9,
                "estimated_cached_s": estimate_cached_run_time(
                    graph, children, frozenset(cached), profiles) / 1e9,
            })
        return make_cached_graph(graph, to_cache)

    def apply(self, graph: Graph) -> Graph:
        if self.strategy == self.AGGRESSIVE:
            return self._aggressive(graph)
        return self._greedy(graph)
