"""The plain references and the operation counts, each against something
simpler still, at tiny size on the CPU. (Reference against the program's
pipeline is ``test_bench_rehearsal``: every rehearsal compares the two.)"""
import numpy as np
import pytest

from benchmarks.harness import load_module


def test_one_block_of_block_least_squares_is_the_ridge_solution():
    import jax.numpy as jnp

    ls = load_module("reference", "_block_ls")
    rng = np.random.default_rng(0)
    X = rng.standard_normal((200, 12)).astype(np.float32)
    y = rng.integers(0, 3, size=200)
    Xt = rng.standard_normal((50, 12)).astype(np.float32)
    W, mean, icpt, train_scores, test_scores = ls.fit_and_score(
        lambda rows, b: jnp.asarray(rows), 1, X, y, Xt, 3, lam=0.5)
    Y = np.where(np.arange(3)[None] == y[:, None], 1.0, -1.0)
    A = X - X.mean(0)
    want = np.linalg.solve(A.T @ A + 0.5 * np.eye(12), A.T @ (Y - Y.mean(0)))
    assert ls.rel_gap(W, want) < 1e-5
    assert ls.rel_gap(test_scores, (Xt - X.mean(0)) @ want + Y.mean(0)) < 1e-5
    assert ls.rel_gap(mean, X.mean(0)) < 1e-6 and ls.rel_gap(icpt, Y.mean(0)) < 1e-6


def test_two_blocks_descend_in_order():
    import jax.numpy as jnp

    ls = load_module("reference", "_block_ls")
    rng = np.random.default_rng(1)
    X = rng.standard_normal((300, 8)).astype(np.float32)
    y = rng.integers(0, 2, size=300)
    W, *_ = ls.fit_and_score(
        lambda rows, b: jnp.asarray(rows)[:, 4 * b:4 * b + 4], 2, X, y,
        X[:5], 2, lam=0.0)
    Y = np.where(np.arange(2)[None] == y[:, None], 1.0, -1.0)
    A, Yc = X - X.mean(0), Y - Y.mean(0)
    w0 = np.linalg.solve(A[:, :4].T @ A[:, :4], A[:, :4].T @ Yc)
    w1 = np.linalg.solve(A[:, 4:].T @ A[:, 4:],
                         A[:, 4:].T @ (Yc - A[:, :4] @ w0))
    assert ls.rel_gap(W, np.concatenate([w0, w1])) < 1e-5


def test_gaps_and_error_rate():
    ls = load_module("reference", "_block_ls")
    assert ls.rel_gap([3.0, 4.0], [3.0, 4.0]) == 0.0
    assert ls.rel_gap([3.0, 5.0], [3.0, 4.0]) == pytest.approx(0.2)
    assert ls.rel_gap([np.nan, 1.0], [1.0, 1.0]) == float("inf")
    assert ls.rel_gap([1.0], [1.0, 2.0]) == float("inf")
    assert ls.error_rate(np.eye(4), [0, 1, 2, 0]) == 0.25


def test_block_solve_counts():
    counts = load_module("counts", "block_solve")
    # MNIST cell: 8 blocks of 2,048 over 60,000 rows, 10 classes
    f = counts.flops(60000, 16384, 2048, 10)
    gram = 8 * 60000 * 2048 * 2049
    assert gram < f < 1.04 * gram
    assert counts.bytes_moved(60000, 16384, 2048, 10) == pytest.approx(
        4 * (60000 * 16384 + 8 * 2 * 60000 * 10))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = counts.roofline_seconds(peaks, 60000, 16384, 2048, 10)
    assert bound == "compute" and seconds == pytest.approx(6 * f / 197e12)
    # two sweeps do the Gram work twice, the factorisations once
    assert counts.flops(100, 8, 4, 2, passes=2) < 2 * counts.flops(100, 8, 4, 2)

