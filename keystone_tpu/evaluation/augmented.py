"""Augmented-example evaluation (reference
``evaluation/AugmentedExamplesEvaluator.scala``).

Test-time augmentation produces several predictions per source example
(e.g. center/corner patches); predictions are grouped by example id and
aggregated — elementwise average, or Borda count (sum of per-patch score
ranks) — before argmax and multiclass evaluation. Grouping and aggregation
happen on the host, every copy at once.
"""
from __future__ import annotations

from typing import Any

import numpy as np

from ..parallel.dataset import ArrayDataset, Dataset, to_numpy
from .multiclass import MulticlassMetrics, evaluate_multiclass

AVERAGE_POLICY = "average"
BORDA_POLICY = "borda"


def _on_host(x: Any) -> np.ndarray:
    if isinstance(x, Dataset) and not isinstance(x, ArrayDataset):
        return np.asarray(x.collect())
    return np.asarray(x if isinstance(x, (list, np.ndarray)) else to_numpy(x))


def vote(names: Any, predicted: Any, actual_labels: Any,
         policy: str = AVERAGE_POLICY):
    """``(aggregated scores [groups, k], labels [groups])``, the groups
    in the order their names first appear, every copy at once. The
    average policy gives the mean of a group's score vectors (reference
    ``AugmentedExamplesEvaluator.scala:17-19``), the Borda policy the
    sum of each copy's ranks of the classes (``:28-35``). Names are
    scalars that ``np.unique`` takes (numbers or strings)."""
    names, preds = _on_host(names), _on_host(predicted)
    labels = _on_host(actual_labels).reshape(-1).astype(np.int64)
    assert names.ndim == 1 and len(names) == len(preds) == len(labels)
    _, first, group = np.unique(names, return_index=True,
                                return_inverse=True)
    group = np.argsort(np.argsort(first))[group]   # by first appearance
    actual = np.empty(len(first), labels.dtype)
    actual[group] = labels
    assert np.array_equal(actual[group], labels), (
        "augmented copies of one example disagree on label")
    preds = np.asarray(preds, np.float64)
    if policy == BORDA_POLICY:
        preds = np.argsort(np.argsort(preds, axis=1), axis=1).astype(
            np.float64)
    total = np.zeros((len(first), preds.shape[1]), np.float64)
    np.add.at(total, group, preds)
    if policy != BORDA_POLICY:
        total /= np.bincount(group)[:, None]
    return total, actual


def evaluate_augmented(
    names: Any,
    predicted: Any,
    actual_labels: Any,
    num_classes: int,
    policy: str = AVERAGE_POLICY,
) -> MulticlassMetrics:
    """Group augmented predictions by example name, aggregate, argmax,
    then standard multiclass evaluation
    (reference ``AugmentedExamplesEvaluator.scala:37-69``). The host
    waits for the device where the predictions are brought over
    (``wait:d2h``); the span ``eval:vote`` is the grouping and the vote
    alone."""
    from ..observability.timeline import flight_span

    names, preds = _on_host(names), _on_host(predicted)
    with flight_span("vote", "eval", rows=len(names)) as span:
        total, actuals = vote(names, preds, actual_labels, policy)
        span["groups"] = len(actuals)
        return evaluate_multiclass(
            np.argmax(total, axis=1), actuals, num_classes)


class AugmentedExamplesEvaluator:
    def evaluate(self, names, predicted, actual_labels, num_classes,
                 policy: str = AVERAGE_POLICY) -> MulticlassMetrics:
        return evaluate_augmented(
            names, predicted, actual_labels, num_classes, policy)
