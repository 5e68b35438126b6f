"""Plain TimitPipeline: ``num_cosines`` blocks of random cosine features
``cos(x W_i^T + b_i)``, ``W_i = gamma * randn(4096, 440)`` and ``b_i =
2 pi * rand(4096)`` from ``np.random.RandomState(seed + i)``, drawn as
the published ``CosineRandomFeatures`` draws them but from the seed and
not from the program; then block least squares as ``_block_ls`` defines
it (centred blocks, block coordinate descent with L2, ``num_epochs``
sweeps), in float32 ``jax.numpy`` at ``highest``.

A block is made anew for every step, as in ``_block_ls.fit_and_score``;
unlike there, each block's mean and Cholesky factor are kept between
epochs in a plain list (they do not change), so that the reference costs
about one fit and not five Grams a block. Fifty 4,096^2 factors are 3.1
GiB; the program's own peak is read before this runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _block_ls

SCORE_ROWS = 512


def draw(cfg, seed, i):
    rng = np.random.RandomState(seed + i)
    shape = (cfg["num_cosine_features"], cfg["input_dim"])
    if cfg["rf_type"] == "cauchy":
        W = rng.standard_cauchy(shape)
    else:
        W = rng.randn(*shape)
    W = (W * cfg["gamma"]).astype(np.float32)
    b = (rng.rand(shape[0]) * 2 * np.pi).astype(np.float32)
    return jnp.asarray(W), jnp.asarray(b)


def fit_and_score(featurize, num_blocks, train_rows, labels, test_rows,
                  num_classes, lam, num_iter):
    """``_block_ls.fit_and_score`` with the pass-invariant mean and
    factor of a block computed in the first epoch and kept."""
    with jax.default_matmul_precision("highest"):
        labels = jnp.asarray(labels)
        Y = jnp.where(jnp.arange(num_classes)[None, :] == labels[:, None],
                      1.0, -1.0).astype(jnp.float32)
        y_mean = Y.mean(axis=0)
        Yc = Y - y_mean
        pred = jnp.zeros_like(Yc)
        Ws, means, factors = [None] * num_blocks, [], []
        for epoch in range(num_iter):
            for b in range(num_blocks):
                A = featurize(train_rows, b)
                if epoch == 0:
                    means.append(A.mean(axis=0))
                    A = A - means[b]
                    G = A.T @ A + lam * jnp.eye(A.shape[1], dtype=A.dtype)
                    factors.append(jax.scipy.linalg.cho_factor(G, lower=True))
                    del G
                    old = jnp.zeros((A.shape[1], num_classes), A.dtype)
                else:
                    A = A - means[b]
                    old = Ws[b]
                rhs = A.T @ (Yc - pred + A @ old)
                W = jax.scipy.linalg.cho_solve(factors[b], rhs)
                pred = pred + A @ (W - old)
                Ws[b] = W
                del A
        del factors
        test_scores = jnp.zeros((test_rows.shape[0], num_classes), jnp.float32)
        for b in range(num_blocks):
            test_scores = test_scores + (
                featurize(test_rows, b) - means[b]) @ Ws[b]
        return (np.asarray(jnp.concatenate(Ws, axis=0)),
                np.asarray(jnp.concatenate(means)), np.asarray(y_mean),
                np.asarray(pred + y_mean), np.asarray(test_scores + y_mean))


def check(cfg, inputs, answers):
    (train_x, train_y), (test_x, test_y) = inputs["train"], inputs["test"]
    blocks = cfg["num_cosines"]

    def featurize(rows, b):
        W, bias = draw(cfg, inputs["feature_seed"], b)
        return jnp.cos(rows @ W.T + bias)

    train = jnp.asarray(train_x, jnp.float32)
    test = jnp.asarray(test_x, jnp.float32)
    W, mean, icpt, train_scores, test_scores = fit_and_score(
        featurize, blocks, train, train_y, test, cfg["num_classes"],
        cfg["lambda"], cfg["num_epochs"])

    def program_scores(ans):
        rows = test[:SCORE_ROWS]
        width = cfg["num_cosine_features"]
        scores = jnp.zeros((rows.shape[0], cfg["num_classes"]), jnp.float32)
        with jax.default_matmul_precision("highest"):
            for b in range(blocks):
                part = slice(b * width, (b + 1) * width)
                scores = scores + (
                    featurize(rows, b) - ans["feature_means"][part]
                ) @ ans["weights"][part]
            return np.asarray(scores + ans["intercept"])

    checks = _block_ls.fit_checks(
        answers, (W, mean, icpt, train_scores, test_scores, program_scores),
        train_y, test_y, cfg["limits"])
    # exact: the fit took the streamed form and no block's factor was
    # unhealthy; each block was made no more often than the program's
    # form needs and no less than any streamed fit must
    real = cfg["real_fit"]
    checks.append(_block_ls.blocks_generated_check(
        answers["blocks_generated"], real))
    for name in ("stream_fits", "materialised_fits"):
        checks.append((name + "_off", abs(answers[name] - real[name]), 0.0))
    checks.append(("unhealthy_blocks", answers["unhealthy_blocks"], 0.0))
    return checks
