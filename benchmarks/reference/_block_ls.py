"""Plain block least squares, as KeystoneML's BlockLeastSquaresEstimator
defines it: centre features and +-1 labels by their means, then block
coordinate descent with L2 over feature blocks,

    W_b <- (A_b^T A_b + lam I)^-1 A_b^T (Y - P + A_b W_b),  P += A_b dW_b,

and predict ``(x - mean) W + mean(Y)``. float32 ``jax.numpy`` at
``highest`` matmul precision; one block of features is alive at a time,
so the reference stays far under the program's own peak.

``featurize(rows, b)`` gives block ``b`` of the features of raw rows.
"""
from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np


def fit_and_score(featurize: Callable, num_blocks: int, train_rows, labels,
                  test_rows, num_classes: int, lam: float, num_iter: int = 1):
    """Returns ``(W [d,k], feature_means [d], intercept [k],
    train_scores, test_scores)`` as numpy arrays."""
    with jax.default_matmul_precision("highest"):
        labels = jnp.asarray(labels)
        Y = jnp.where(jnp.arange(num_classes)[None, :] == labels[:, None],
                      1.0, -1.0).astype(jnp.float32)
        y_mean = Y.mean(axis=0)
        Yc = Y - y_mean
        pred = jnp.zeros_like(Yc)
        Ws: List = [None] * num_blocks
        means: List = [None] * num_blocks
        for _ in range(num_iter):
            for b in range(num_blocks):
                A = featurize(train_rows, b)
                means[b] = A.mean(axis=0)
                A = A - means[b]
                G = A.T @ A + lam * jnp.eye(A.shape[1], dtype=A.dtype)
                old = (Ws[b] if Ws[b] is not None else
                       jnp.zeros((A.shape[1], num_classes), A.dtype))
                rhs = A.T @ (Yc - pred + A @ old)
                W = jax.scipy.linalg.cho_solve(
                    jax.scipy.linalg.cho_factor(G, lower=True), rhs)
                pred = pred + A @ (W - old)
                Ws[b] = W
                del A, G
        test_scores = jnp.zeros((test_rows.shape[0], num_classes), jnp.float32)
        for b in range(num_blocks):
            test_scores = test_scores + (
                featurize(test_rows, b) - means[b]) @ Ws[b]
        return (np.asarray(jnp.concatenate(Ws, axis=0)),
                np.asarray(jnp.concatenate(means)), np.asarray(y_mean),
                np.asarray(pred + y_mean), np.asarray(test_scores + y_mean))


def rel_gap(got, want) -> float:
    """Frobenius norm of the difference over the reference's norm."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def error_rate(scores, labels) -> float:
    return float(np.mean(np.argmax(scores, axis=1) != np.asarray(labels)))


def fit_checks(answers, ref, train_labels, test_labels, limits):
    """The numbers compared for a fit cell, each beside its limit.

    * ``weights_gap``: the fitted model (weights, feature means,
      intercept, each scaled to unit norm) against the reference's own
      fit; what a lower solver precision moves first.
    * ``test_scores_gap``: the program's model applied to the reference's
      test features against the reference's scores: the same in
      prediction space, where conditioning amplifies less.
    * ``train_error_gap`` / ``test_error_gap``: the errors the timed fit
      reported against the reference's: featurize, apply and evaluation.
    """
    W, mean, icpt, train_scores, test_scores, test_feats_fn = ref
    parts = [rel_gap(answers["weights"], W),
             rel_gap(answers["feature_means"], mean),
             rel_gap(answers["intercept"], icpt)]
    prog_scores = test_feats_fn(answers)
    values = {
        "weights_gap": max(parts),
        "test_scores_gap": rel_gap(prog_scores, test_scores[:len(prog_scores)]),
        "train_error_gap": abs(answers["train_error"]
                               - error_rate(train_scores, train_labels)),
        "test_error_gap": abs(answers["test_error"]
                              - error_rate(test_scores, test_labels)),
    }
    return [(name, values[name], limits[name]) for name in values]


def blocks_generated_check(made: float, real):
    """``blocks_generated_off``: by how many blocks a fit's count lies
    outside what the configuration's ``real_fit`` states, limit 0. The
    bounds hold a streamed fit to what any such fit must do and no more
    than the form at hand needs: at least a block a pass and a block an
    apply (``blocks_generated_min``), at most one more a block for a
    factor sweep of its own (``blocks_generated_max``)."""
    lo, hi = real["blocks_generated_min"], real["blocks_generated_max"]
    return ("blocks_generated_off",
            float(max(lo - made, made - hi, 0.0)), 0.0)
