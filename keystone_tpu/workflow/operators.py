"""Operator algebra: the untyped execution layer under the typed API.

Mirrors ``workflow/graph/Operator.scala`` — each DAG node holds an
Operator; ``execute`` consumes the dependencies' lazy Expressions and
returns a lazy Expression. Type dispatch between per-datum and batch
execution follows ``Operator.scala:66-100`` (TransformerOperator applies
``batch_transform`` iff any input is a dataset).

Operator equality drives common-subexpression elimination and the prefix
cache (reference ``EquivalentNodeMergeRule.scala``, ``Prefix.scala``): two
operators are equal iff their ``eq_key()`` match. The default key is the
class plus all public, hashable ``__dict__`` entries, so parameterized
nodes written as plain classes get structural equality for free; nodes
holding unhashable state override ``eq_key``.
"""
from __future__ import annotations

import itertools
from typing import Any, Sequence, Tuple

import numpy as np

from ..observability.metrics import MetricsRegistry
from ..observability.timeline import flight_span
from ..observability.trace import metrics_suppressed
from ..parallel.dataset import Dataset, shard_layout
from .expression import (
    DatasetExpression,
    DatumExpression,
    Expression,
    TransformerExpression,
)


def _hashable(v: Any) -> Any:
    """Best-effort conversion of a parameter value to a hashable token."""
    if isinstance(v, np.ndarray):
        return ("ndarray", v.shape, str(v.dtype), v.tobytes())
    if hasattr(v, "shape") and hasattr(v, "dtype"):  # jax.Array
        arr = np.asarray(v)
        return ("array", arr.shape, str(arr.dtype), arr.tobytes())
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    try:
        hash(v)
        return v
    except TypeError:
        return id(v)


def _shared_geometry(dataset_specs):
    """Chunk geometry propagated through a stream-consuming node, marked
    shared: the derived view rides the ROOT stream's residency ledger,
    so the HBM planner must not re-charge the prefetch buffer at this
    node (it charges one transformed chunk instead)."""
    for d in dataset_specs:
        if getattr(d, "streaming", False) and d.geometry is not None:
            return d.geometry.as_shared()
    return None


class Operator:
    """A unit of computation stored at a graph node."""

    #: ``canonical_prefix(dep_prefixes) -> prefix`` on an operator that
    #: an optimizer rule put where the pipeline was written otherwise:
    #: the prefix of what it stands for in the RAW graph, which is the
    #: form the state table is asked in (``workflow/prefix.py``). None:
    #: the operator stands for itself.
    canonical_prefix = None

    def execute(self, deps: Sequence[Expression]) -> Expression:
        raise NotImplementedError

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        """Static analogue of ``execute``: map the dependencies' abstract
        values (``analysis.spec``) to this node's output spec, without
        touching a device. The default declines — the analyzer treats
        that as Unknown and propagates silently (never a diagnostic)."""
        from ..analysis.spec import Unknown

        return Unknown(f"{type(self).__name__} has no abstract_eval")

    def resource_effect(self, dep_specs: Sequence[Any],
                        out_spec: Any, data_shards: int = 1) -> Any:
        """Static resource annotation for the HBM planner
        (``analysis.resources.plan_graph``): return a ``ResourceEffect``
        describing this node's device-memory contribution, or None to
        let the planner derive it from ``out_spec`` (output bytes from
        the dataset/datum element, stream residency from chunk
        geometry). Estimators override to add their accumulator carry
        and fitted-model footprint; Delegate nodes add the fitted
        transformer's declared apply-kernel workspace."""
        return None

    def label(self) -> str:
        return type(self).__name__

    def eq_key(self) -> Tuple:
        items = tuple(
            (k, _hashable(v))
            for k, v in sorted(self.__dict__.items())
            if not k.startswith("_")
        )
        return (type(self),) + items

    def _cached_eq_key(self) -> Tuple:
        # Nodes are logically frozen after construction; caching avoids
        # re-serializing large parameter arrays on every CSE comparison.
        key = self.__dict__.get("_eq_key_val")
        if key is None:
            key = self.eq_key()
            self.__dict__["_eq_key_val"] = key
        return key

    def __eq__(self, other: Any) -> bool:
        return type(self) is type(other) and (
            self._cached_eq_key() == other._cached_eq_key()
        )

    def __hash__(self) -> int:
        return hash(self._cached_eq_key())


#: Session-unique identities for untagged datasets. ``id()`` will not
#: do: the state table outlives the datasets it is keyed by, and a later
#: dataset allocated at a freed one's address was answered with the
#: first one's saved results (a served request got another request's
#: predictions through a ``Cacher``; my chip run, PR 21).
_DATASET_SERIALS = itertools.count()


class DatasetOperator(Operator):
    """A constant dataset (reference ``DatasetOperator``, Operator.scala:25-33)."""

    def __init__(self, dataset: Dataset):
        self.dataset = dataset

    def eq_key(self) -> Tuple:
        # a loader-provided tag (e.g. the source path) gives the dataset a
        # stable identity, so prefixes — and therefore saved fitted state —
        # survive across sessions; untagged data falls back to object
        # identity (session-local reuse only, like the reference's RDDs),
        # as a serial number the object carries from its first use here
        tag = getattr(self.dataset, "tag", None)
        if tag is not None:
            return (DatasetOperator, "tag", tag)
        serial = getattr(self.dataset, "_identity_serial", None)
        if serial is None:
            serial = self.dataset._identity_serial = next(_DATASET_SERIALS)
        return (DatasetOperator, "serial", serial)

    def execute(self, deps: Sequence[Expression]) -> Expression:
        assert not deps
        return DatasetExpression(self.dataset, eager=True)

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        from ..analysis.spec import dataset_spec

        return dataset_spec(self.dataset)

    def label(self) -> str:
        return "Dataset"


class DatumOperator(Operator):
    """A constant single item (reference ``DatumOperator``, Operator.scala:41-52)."""

    def __init__(self, datum: Any):
        self.datum = datum

    def eq_key(self) -> Tuple:
        return (DatumOperator, id(self.datum))

    def execute(self, deps: Sequence[Expression]) -> Expression:
        assert not deps
        return DatumExpression(self.datum, eager=True)

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        from ..analysis.spec import datum_spec

        return datum_spec(self.datum)

    def label(self) -> str:
        return "Datum"


class TransformerOperator(Operator):
    """An operator transforming data, with per-datum and batch paths
    (reference ``TransformerOperator``, Operator.scala:66-100)."""

    def single_transform(self, inputs: Sequence[Any]) -> Any:
        raise NotImplementedError

    def batch_transform(self, inputs: Sequence[Dataset]) -> Dataset:
        raise NotImplementedError

    def execute(self, deps: Sequence[Expression]) -> Expression:
        if any(isinstance(d, DatasetExpression) for d in deps):
            return DatasetExpression(
                lambda: self.batch_transform([d.get() for d in deps])
            )
        return DatumExpression(
            lambda: self.single_transform([d.get() for d in deps])
        )

    # -- static analysis ---------------------------------------------------
    def abstract_single(self, elements: Sequence[Any]) -> Any:
        """Per-item shape propagation mirroring ``single_transform``,
        via ``jax.eval_shape`` (abstract: no device buffers). Raises on
        shape/dtype errors and on host-sync hazards (``np.asarray`` on a
        tracer) — the interpreter classifies those into diagnostics.
        Nodes whose per-item function is not jax-traceable (host
        stages) override this to return Unknown or a bespoke spec."""
        from ..analysis.spec import Unknown, element_has_unknown

        if any(element_has_unknown(e) for e in elements):
            return Unknown("input element not fully specified")
        import jax

        return jax.eval_shape(
            lambda *xs: self.single_transform(list(xs)), *elements)

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        """Type dispatch mirroring ``execute``: dataset in -> dataset
        out (element-wise ``abstract_single``), else datum. Operators
        whose batch path changes the ITEM COUNT (samplers, augmenters)
        must override to adjust ``n``."""
        from ..analysis.spec import (
            DatasetSpec,
            DatumSpec,
            Unknown,
            dense_sparsity,
            is_unknown,
        )

        if any(is_unknown(d) for d in dep_specs):
            return Unknown("unknown input")
        if not all(isinstance(d, (DatasetSpec, DatumSpec))
                   for d in dep_specs):
            return Unknown("non-data input")
        elements = [d.element for d in dep_specs]
        out = self.abstract_single(elements)
        datasets = [d for d in dep_specs if isinstance(d, DatasetSpec)]
        if not datasets:
            return DatumSpec(out)
        ns = [d.n for d in datasets if d.n is not None]
        return DatasetSpec(
            out,
            n=min(ns) if ns else None,  # zip semantics across inputs
            host=all(d.host for d in datasets),
            sparsity=dense_sparsity(out),
            # mapping a stream yields a stream (chunk-wise application)
            streaming=any(d.streaming for d in datasets),
            geometry=_shared_geometry(datasets),
            sharded=any(d.sharded for d in datasets),
        )


class EstimatorOperator(Operator):
    """Fits on datasets, yielding a TransformerOperator
    (reference ``EstimatorOperator.fitRDDs``, Operator.scala:112-125)."""

    #: True where the transformer a fit gives will have ``maps_columns``
    #: (``Transformer``), whatever it is fitted on: said before the fit,
    #: because the optimizer's rules run before anything is fitted and
    #: see only a ``DelegatingOperator`` fed by this estimator's node.
    fitted_maps_columns = False

    def fit_datasets(self, inputs: Sequence[Dataset]) -> TransformerOperator:
        raise NotImplementedError

    def fit_transform_datasets(self, inputs: Sequence[Dataset]):
        """``(fitted transformer, its output on inputs[0] or None)``
        (sklearn's ``fit_transform``). A fit that holds, when it ends,
        what its transformer gives on the rows it was fitted on (a sweep
        that carries its predictions) returns that dataset beside the
        transformer, and a delegating node fed by those very rows is
        answered with it (``DelegatingOperator.execute``). The outputs
        are a product of the fit, not of the transformer: it carries
        them nowhere. None: the transformer is applied, as to any other
        rows."""
        return self.fit_datasets(inputs), None

    def fit_label(self) -> str:
        """Whose fit the ``solve:fit:<name>`` span says this is."""
        return type(self).__name__

    def execute(self, deps: Sequence[Expression]) -> Expression:
        def fit():
            inputs = [d.get() for d in deps]
            # the one span site of every estimator: host time of the fit
            # (dispatch; the device work is the trace's to show), and
            # over how many data shards the rows it is fitted on lie
            with flight_span(f"fit:{self.fit_label()}", "solve",
                             **shard_layout(inputs[0] if inputs else None)):
                fitted, outputs = self.fit_transform_datasets(inputs)
            if outputs is not None:
                expr.fit_outputs[deps[0]] = outputs
            return fitted

        expr = TransformerExpression(fit)
        return expr

    # -- static analysis ---------------------------------------------------
    def resource_effect(self, dep_specs: Sequence[Any],
                        out_spec: Any, data_shards: int = 1) -> Any:
        """Estimator nodes charge their accumulator carry (the Gram /
        cross / moment buffers a streamed fit keeps resident — the same
        workspace a resident normal-equations solve materializes) as a
        transient of the fit step, and the fitted model as the output
        that stays live. Sizes come from the optional
        ``carry_nbytes(dep_specs)`` / ``fitted_nbytes(dep_specs)`` hooks
        concrete estimators declare."""
        from ..analysis.resources import estimator_resource_effect

        return estimator_resource_effect(self, dep_specs)

    def abstract_fit(self, dep_specs: Sequence[Any]):
        """Describe the fitted transformer: return a callable mapping an
        input element spec to the fitted transformer's output element
        spec, or None when this estimator does not declare one (the
        delegating child's output then propagates as Unknown). Estimators
        with statically known output shapes (linear models: d -> k,
        scalers: identity, PCA: d -> dims) override this."""
        return None

    def abstract_apply_transient(self, dep_specs: Sequence[Any]):
        """Describe the fitted apply's per-item device workspace:
        return a callable mapping an input element spec to bytes (or
        None), or None when this estimator declares none. Estimators
        whose fitted apply dispatches a Pallas kernel override this so
        the HBM planner charges the kernel (or fallback) scratch at the
        Delegate node."""
        return None

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        from ..analysis.spec import TransformerSpec

        return TransformerSpec(
            self.abstract_fit(dep_specs), label=self.label(),
            apply_transient_nbytes=self.abstract_apply_transient(dep_specs))


class DelegatingOperator(Operator):
    """Applies a fitted transformer produced upstream: dep 0 is the
    TransformerExpression, the rest are data (reference
    ``DelegatingOperator``, Operator.scala:135-164). Where the fit left
    its transformer's output on the rows it was fitted on
    (``TransformerExpression.fit_outputs``) and this node's one data
    dependency is that very expression, that output is the answer and
    the counter ``executor.fit_outputs_reused`` rises; the node executes
    and counts as any other. Read off the graph and object identity:
    nothing selects it."""

    def execute(self, deps: Sequence[Expression]) -> Expression:
        assert deps, "delegating operator requires a transformer dependency"
        t, data = deps[0], deps[1:]
        assert isinstance(t, TransformerExpression)
        if any(isinstance(d, DatasetExpression) for d in data):
            count = not metrics_suppressed()

            def batch():
                fitted = t.get()
                # fed the one expression the fit consumed (object
                # identity: the same node of the same graph), and the fit
                # left its transformer's output on those rows: that is
                # the answer, and nothing is applied
                held = t.fit_outputs.get(data[0]) if len(data) == 1 else None
                if held is None:
                    return fitted.batch_transform([d.get() for d in data])
                if count:
                    MetricsRegistry.get_or_create().counter(
                        "executor.fit_outputs_reused").inc()
                return held

            return DatasetExpression(batch)
        return DatumExpression(
            lambda: t.get().single_transform([d.get() for d in data])
        )

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        from ..analysis.spec import (
            DatasetSpec,
            DatumSpec,
            TransformerSpec,
            Unknown,
            dense_sparsity,
        )

        if not dep_specs or not isinstance(dep_specs[0], TransformerSpec):
            return Unknown("delegating without a transformer spec")
        t, data = dep_specs[0], dep_specs[1:]
        if t.apply_element is None:
            return Unknown(f"opaque fitted transformer {t.label}")
        if len(data) != 1 or not isinstance(
                data[0], (DatasetSpec, DatumSpec)):
            return Unknown("delegating input not resolvable")
        out = t.apply_element(data[0].element)
        if isinstance(data[0], DatumSpec):
            return DatumSpec(out)
        return DatasetSpec(out, n=data[0].n, host=data[0].host,
                           sparsity=dense_sparsity(out),
                           streaming=data[0].streaming,
                           geometry=_shared_geometry([data[0]]),
                           sharded=data[0].sharded)

    def resource_effect(self, dep_specs: Sequence[Any],
                        out_spec: Any, data_shards: int = 1) -> Any:
        from ..analysis.resources import delegate_resource_effect

        return delegate_resource_effect(dep_specs, out_spec, data_shards)

    def label(self) -> str:
        return "Delegate"


class ExpressionOperator(Operator):
    """Wraps an already-computed Expression (saved state substituted by the
    optimizer; reference ``ExpressionOperator``, Operator.scala:172-177)."""

    def __init__(self, expression: Expression):
        self.expression = expression

    def eq_key(self) -> Tuple:
        return (ExpressionOperator, id(self.expression))

    def execute(self, deps: Sequence[Expression]) -> Expression:
        return self.expression

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        from ..analysis.spec import Unknown, value_spec

        if self.expression.computed:
            return value_spec(self.expression.get())
        return Unknown("saved expression not yet computed")

    def label(self) -> str:
        return "Saved"
