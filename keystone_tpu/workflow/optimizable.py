"""Node-level optimizable operators (reference
``workflow/OptimizableNodes.scala``).

An optimizable node carries a ``default`` implementation (used when the
optimizer never runs) and an ``optimize(sample..., n, num_machines)``
hook that inspects a data sample plus workload shape and returns a
:class:`NodeChoice` — the implementation the cost model prefers, plus an
optional transformer prefix that must be applied both to the training
data and to the runtime input path (e.g. ``Sparsify`` before a sparse
solver, reference ``LeastSquaresEstimator.scala:36-53``).

``NodeOptimizationRule`` (``optimizer/node_rule.py``) splices choices
into the DAG before execution.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence, Tuple

from ..parallel.dataset import Dataset
from .estimator import Estimator
from .label_estimator import LabelEstimator
from .operators import EstimatorOperator
from .transformer import Transformer


@dataclass
class NodeChoice:
    """The sub-pipeline an optimizable node resolves to: ``prefix``
    transformers feed both the fit path and the runtime path, then
    ``node`` replaces the optimizable operator."""

    node: object
    prefix: Tuple[Transformer, ...] = ()


class OptimizableTransformer(Transformer):
    """A transformer with implementation choices
    (reference ``OptimizableNodes.scala:10-16``)."""

    @property
    def default(self) -> Transformer:
        raise NotImplementedError

    def apply(self, x):
        return self.default.apply(x)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        return self.default.apply_dataset(ds)

    def optimize(self, sample: Dataset, n: int, num_machines: int) -> NodeChoice:
        raise NotImplementedError

    def optimize_static(self, spec, n: int, num_machines: int):
        """Cost-model choice from the static analyzer's input spec
        (``analysis.spec.DatasetSpec``) instead of a sampled execution.
        Return a NodeChoice, or None to fall back to sampling (the
        default: nodes whose cost inputs are not statically derivable)."""
        return None


class OptimizableEstimator(Estimator):
    """An estimator with implementation choices
    (reference ``OptimizableNodes.scala:21-33``)."""

    @property
    def default(self) -> Estimator:
        raise NotImplementedError

    def _fit(self, ds: Dataset) -> Transformer:
        return self.default._fit(ds)

    def optimize(self, sample: Dataset, n: int, num_machines: int) -> NodeChoice:
        raise NotImplementedError

    def optimize_static(self, spec, n: int, num_machines: int):
        """See :meth:`OptimizableTransformer.optimize_static`."""
        return None


class OptimizableLabelEstimator(LabelEstimator):
    """A label estimator with implementation choices
    (reference ``OptimizableNodes.scala:38-46``)."""

    @property
    def default(self) -> LabelEstimator:
        raise NotImplementedError

    def _fit(self, ds: Dataset, labels: Dataset) -> Transformer:
        return self.default._fit(ds, labels)

    def optimize(self, sample: Dataset, sample_labels: Dataset, n: int,
                 num_machines: int) -> NodeChoice:
        raise NotImplementedError

    def optimize_static(self, spec, n: int, num_machines: int,
                        labels_spec=None):
        """See :meth:`OptimizableTransformer.optimize_static`; label
        estimators additionally receive the labels' DatasetSpec."""
        return None


class StreamedGatherFit(EstimatorOperator):
    """What ``GatherStreamingRule`` (``optimizer/stream_gather.py``)
    puts in an estimator's place when the gather that feeds it is too
    wide to materialise: the estimator fits from the RAW rows plus the
    gather's branch featurizers (``fit_branches(rows, labels,
    branches)``) and makes each block of features when it needs it. The
    fitted transformer takes raw rows too. ``chain`` is what stood
    between the combiner and the estimator, from the combiner outward:
    ``("map", Cacher)`` entries, which are dropped, and ``("fit",
    estimator, delegating operator)`` entries, whose estimators the
    solver carries into its sweep (``between``). Its prefix is that of
    the estimator on the materialised gather behind that chain
    (``workflow/prefix.py``), so the state table answers for either
    form."""

    def __init__(self, estimator, combiner: Transformer,
                 branches: Sequence[Transformer], chain: Sequence = ()):
        self.estimator = estimator
        self.combiner = combiner
        self.branches = tuple(branches)
        self.chain = tuple(chain)

    def eq_key(self):
        return (StreamedGatherFit, self.estimator._cached_eq_key(),
                self.combiner._cached_eq_key(),
                tuple(b._cached_eq_key() for b in self.branches),
                tuple(tuple(op._cached_eq_key() for op in entry[1:])
                      for entry in self.chain))

    def fit_datasets(self, inputs):
        return self.fit_transform_datasets(inputs)[0]

    def fit_transform_datasets(self, inputs):
        # the fitted transformer takes the raw rows this node is fed, so
        # what the sweep holds on them is its output on ``inputs[0]``
        between = [e[1] for e in self.chain if e[0] == "fit"]
        return self.estimator.fit_transform_branches(
            inputs[0], inputs[1], self.branches, *([between] if between else []))

    def fit_label(self) -> str:
        # the span every fit of this estimator has, whichever form
        return type(self.estimator).__name__

    def execute(self, deps):
        expr = super().execute(deps)
        # read by GatherStreamingRule where the state table answers for
        # this fit in a later graph: what it yields takes raw rows
        expr.streams_gather = (self.combiner, self.branches, self.chain)
        return expr

    def abstract_fit(self, dep_specs):
        # a raw row of any shape (a vector, an image) in, one score a
        # label column out: the featurizers are inside the fitted model
        import jax
        import numpy as np

        from ..analysis.spec import element_feature_dim

        k = element_feature_dim(dep_specs[1]) if len(dep_specs) > 1 else None
        if k is None:
            return None
        return lambda element: jax.ShapeDtypeStruct((k,), np.float32)

    def resource_effect(self, dep_specs, out_spec, data_shards: int = 1):
        """What the streamed fit keeps on the device: every block's
        factor (and with them the model), and a few blocks of features
        at a time, never the gathered matrix."""
        from ..analysis.resources import ResourceEffect
        from ..analysis.spec import element_feature_dim

        rows = getattr(dep_specs[0], "n", None)
        k = element_feature_dim(dep_specs[1]) if len(dep_specs) > 1 else None
        if rows is None or k is None:
            return ResourceEffect(resolved=False,
                                  note="streamed fit of unsized rows")
        from ..analysis.resources import stream_row_chunk

        blocks, bs = len(self.branches), int(self.estimator.block_size)
        held = -(-rows // max(data_shards, 1))
        chunk = stream_row_chunk(held, bs)
        if chunk is None:
            alive = 3 * 4.0 * held * bs
            what = "three blocks of features alive"
        else:
            # one block of all rows, made and swept a chunk at a time
            alive = 4.0 * bs * (held + 4 * chunk)
            what = (f"one block of features held, swept in "
                    f"{-(-held // chunk)} chunks of {chunk} rows")
        return ResourceEffect(
            out_nbytes=4.0 * blocks * bs * (k + 2),
            carry_nbytes=4.0 * blocks * bs * bs + alive,
            note=f"streamed block solve: {blocks} factors of {bs}^2 and "
                 f"{what}, no gathered matrix")

    def label(self) -> str:
        return f"Streamed[{self.estimator.label()}]"
