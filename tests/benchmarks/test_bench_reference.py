"""The plain references and the operation counts, each against something
simpler still, at tiny size on the CPU. (Reference against the program's
pipeline is the cells' ``test_bench_rehearsal_<cell>``: every rehearsal
compares the two.)"""
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



# -- how often a streamed fit makes a block: a bound, held by check() -------------

def perfect_timit_program(ref, cfg, inputs):
    """The reference's own fit, handed to its check as the program's."""
    import jax.numpy as jnp

    (train_x, train_y), (test_x, test_y) = inputs["train"], inputs["test"]

    def featurize(rows, b):
        W, bias = ref.draw(cfg, inputs["feature_seed"], b)
        return jnp.cos(rows @ W.T + bias)

    W, mean, icpt, train_scores, test_scores = ref.fit_and_score(
        featurize, cfg["num_cosines"], jnp.asarray(train_x),
        train_y, jnp.asarray(test_x), cfg["num_classes"], cfg["lambda"],
        cfg["num_epochs"])
    return dict(weights=W, feature_means=mean, intercept=icpt,
                train_error=ref._block_ls.error_rate(train_scores, train_y),
                test_error=ref._block_ls.error_rate(test_scores, test_y),
                stream_fits=1.0, materialised_fits=0.0, unhealthy_blocks=0.0)


def perfect_cifar_program(ref, cfg, inputs):
    import jax.numpy as jnp

    (train_px, train_y), (test_px, test_y) = inputs["train"], inputs["test"]
    filters, means = ref.learn_filters(cfg, train_px, inputs["feature_seed"])
    blocks = -(-cfg["num_filters"] // cfg["filters_a_block"])

    def block(rows, b):
        return ref.block_features(cfg, rows, filters, means, b,
                                  one_pass=cfg["conv_one_pass"])

    W, mean, std, icpt, train_scores, test_scores = ref.fit_and_score(
        block, blocks, jnp.asarray(train_px, jnp.float32), train_y,
        jnp.asarray(test_px, jnp.float32), cfg["num_classes"], cfg["lambda"])
    return dict(weights=W, feature_means=mean, feature_inv_stds=1.0 / std,
                intercept=icpt, filters=filters, whitener_means=means,
                block=block, test_scores=test_scores,
                train_error=ref._block_ls.error_rate(train_scores, train_y),
                test_error=ref._block_ls.error_rate(test_scores, test_y),
                stream_fits=1.0, materialised_fits=0.0, unhealthy_blocks=0.0,
                maker="pallas")


@pytest.fixture(scope="module")
def perfect():
    """For each streamed configuration: its file, cut to a few rows and
    narrow blocks but with ``real_fit`` as the timed size states it, the
    seeded inputs, and answers that are the reference's own."""
    from benchmarks.harness import HERE, load_json

    rng = np.random.default_rng(32)
    made = {}
    cfg = load_json(f"{HERE}/configs/timit_50x4096.json")
    cfg.update(num_cosines=3, num_cosine_features=16, input_dim=12,
               num_classes=4)
    inputs = {"train": (rng.standard_normal((96, 12)).astype(np.float32),
                        rng.integers(0, 4, 96)),
              "test": (rng.standard_normal((32, 12)).astype(np.float32),
                       rng.integers(0, 4, 32)), "feature_seed": 7}
    ref = load_module("reference", "timit_50x4096")
    made["timit_50x4096"] = (ref, cfg, inputs,
                             perfect_timit_program(ref, cfg, inputs))
    cfg = load_json(f"{HERE}/configs/cifar_random_patch_10k.json")
    cfg.update(num_filters=13, filters_a_block=8, conv_one_pass=False)
    inputs = {"train": (rng.integers(0, 256, (40, 32, 32, 3)).astype(np.uint8),
                        rng.integers(0, 10, 40)),
              "test": (rng.integers(0, 256, (16, 32, 32, 3)).astype(np.uint8),
                       rng.integers(0, 10, 16)), "feature_seed": 7}
    ref = load_module("reference", "cifar_random_patch_10k")
    made["cifar_random_patch_10k"] = (ref, cfg, inputs,
                                      perfect_cifar_program(ref, cfg, inputs))
    return made


@pytest.mark.parametrize("config,blocks,off", [
    ("timit_50x4096", 299, 1.0),   # a block short of an epoch's
    ("timit_50x4096", 300, 0.0),   # 50 x 5 epochs + 50: the least
    ("timit_50x4096", 350, 0.0),   # 50 x (1 + 5) + 50: the program today
    ("timit_50x4096", 351, 1.0),
    ("cifar_random_patch_10k", 39, 1.0),
    ("cifar_random_patch_10k", 40, 0.0),   # 20 x 1 epoch + 20: the least
    ("cifar_random_patch_10k", 60, 0.0),   # the program today
    ("cifar_random_patch_10k", 80, 0.0),   # a factor sweep and both applies
    ("cifar_random_patch_10k", 81, 1.0),
])
def test_a_fit_that_makes_too_few_or_too_many_blocks_is_not_correct(
        perfect, config, blocks, off):
    ref, cfg, inputs, answers = perfect[config]
    checks = {name: (value, limit) for name, value, limit in ref.check(
        cfg, inputs, dict(answers, blocks_generated=float(blocks)))}
    assert checks["blocks_generated_off"] == (off, 0.0)
    # and nothing else is: the answers are the reference's own
    wrong = {name for name, (value, limit) in checks.items()
             if not value <= limit}
    assert wrong == ({"blocks_generated_off"} if off else set())
