"""Plain MnistRandomFFT: for each of ``num_ffts`` branches multiply the
784 pixels by a seeded +-1 vector, zero-pad to 1,024, take the real part
of the first 512 FFT bins, rectify at 0; concatenate; block least
squares (``_block_ls``). The sign vectors are drawn as the published app
draws them, ``RandomState(seed)`` then one ``randint(0, 2, 784)`` a
branch, from the seed and not from the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _block_ls

SCORE_ROWS = 512


def check(cfg, inputs, answers):
    (train_px, train_y), (test_px, test_y) = inputs["train"], inputs["test"]
    rng = np.random.RandomState(inputs["sign_seed"])
    signs = np.stack([2.0 * rng.randint(0, 2, size=cfg["image_size"]) - 1.0
                      for _ in range(cfg["num_ffts"])]).astype(np.float32)
    per_block = cfg["block_size"] // cfg["features_per_fft"]
    num_blocks = cfg["num_ffts"] // per_block
    pad = cfg["fft_size"] - cfg["image_size"]

    def featurize(rows, b):
        outs = []
        for s in signs[b * per_block:(b + 1) * per_block]:
            xp = jnp.pad(rows * s, ((0, 0), (0, pad)))
            spec = jnp.real(jnp.fft.fft(xp, axis=-1))
            outs.append(jnp.maximum(spec[:, :cfg["features_per_fft"]], 0.0))
        return jnp.concatenate(outs, axis=1)

    train = jnp.asarray(train_px, jnp.float32)
    test = jnp.asarray(test_px, jnp.float32)
    W, mean, icpt, train_scores, test_scores = _block_ls.fit_and_score(
        featurize, num_blocks, train, train_y, test, cfg["num_classes"],
        cfg["lambda"], cfg["num_iter"])

    def program_scores(ans):
        rows = test[:SCORE_ROWS]
        feats = jnp.concatenate(
            [featurize(rows, b) for b in range(num_blocks)], axis=1)
        with jax.default_matmul_precision("highest"):
            return np.asarray((feats - ans["feature_means"]) @ ans["weights"]
                              + ans["intercept"])

    return _block_ls.fit_checks(
        answers, (W, mean, icpt, train_scores, test_scores, program_scores),
        train_y, test_y, cfg["limits"])
