"""Default optimizer.

Mirrors ``workflow/graph/DefaultOptimizer.scala:5-10`` plus the v1
``workflow/DefaultOptimizer.scala:8-14`` node-level pass: saved-state +
pruning, CSE to fixpoint, cost-model node-level optimization (a solver
from n, d, k; a gather materialised or handed to the solver as branches,
``stream_gather.py``), CSE again, then column samples drawn where a pass
is made anyway (``column_samples.py``: a sampler in front of the
column-wise chain it reads, sibling samplers as one node) and map fusion.
The loading, pruning, CSE and fusion rules each make their whole rewrite
in one walk and one new graph, so a pass costs O(nodes + edges) and a
fixed-point batch is a round that rewrites and a round that finds
nothing (CSE 2, map fusion 3); the node-level rules copy the graph a few
times for each optimizable node they splice.
(The reference's ExtractSaveablePrefixes step is subsumed by the
executor's ``is_saveable`` check — see ``executor.py``.)

Observability: under an active
:class:`~keystone_tpu.observability.PipelineTrace`, every rule
application here is logged with its graph-size delta (engine hook in
``rule.Optimizer.execute``), the node-level pass logs each splice
decision with the cost model's per-solver estimates
(``node_rule`` / ``LeastSquaresEstimator.optimize``), and the
auto-cache batch logs its sampled profiles, selected cache set, and
memory budget (``auto_cache.AutoCacheRule``).
"""
from __future__ import annotations

from typing import Sequence

from .auto_cache import AutoCacheRule
from .column_samples import ColumnSamplerMoveRule, SiblingSamplerRule
from .fusion import GatherFusionRule, MapFusionRule
from .node_rule import NodeOptimizationRule
from .stream_gather import GatherStreamingRule
from .rule import Batch, FixedPoint, Once, Optimizer
from .rules import (
    EquivalentNodeMergeRule,
    SavedStateLoadRule,
    UnusedBranchRemovalRule,
)


class DefaultOptimizer(Optimizer):
    @property
    def batches(self) -> Sequence[Batch]:
        return [
            Batch(
                "saved-state and pruning",
                Once(),
                [SavedStateLoadRule(), UnusedBranchRemovalRule()],
            ),
            Batch("CSE", FixedPoint(100), [EquivalentNodeMergeRule()]),
            Batch("node-level optimization", Once(),
                  [NodeOptimizationRule(), GatherStreamingRule()]),
            Batch("post-splice CSE", FixedPoint(100),
                  [EquivalentNodeMergeRule()]),
            Batch("column samples", Once(),
                  [ColumnSamplerMoveRule(), SiblingSamplerRule()]),
            Batch("map fusion", FixedPoint(1000),
                  [MapFusionRule(), GatherFusionRule()]),
        ]


class AutoCachingOptimizer(Optimizer):
    """DefaultOptimizer plus profile-driven caching (reference
    ``workflow/DefaultOptimizer.scala:19-26``)."""

    def __init__(self, strategy: str = AutoCacheRule.GREEDY,
                 max_mem=None):
        self.strategy = strategy
        self.max_mem = max_mem

    @property
    def batches(self) -> Sequence[Batch]:
        return list(DefaultOptimizer().batches) + [
            Batch("auto-cache", Once(),
                  [AutoCacheRule(self.strategy, self.max_mem)]),
        ]


class NoOpOptimizer(Optimizer):
    """Pass-through optimizer (tests, debugging)."""

    @property
    def batches(self) -> Sequence[Batch]:
        return []
