"""Plain MnistRandomFFT at 200 branches, on ONE device and with no mesh:
for each branch multiply the 784 pixels by a seeded +-1 vector, zero-pad
to 1,024, take the real part of the first 512 FFT bins, rectify at 0;
four branches make one block of 2,048 columns (60,000 x 2,048 float32,
0.49 GB), and one block is alive at a time: its Gram, its factor, its
step and the residual's update in the program's block order
(``_block_ls``), then the model applied to the test rows block by block.
So the reference of a 24.6 GB design matrix fits the first chip of the
four, beside nothing: the program's arrays are gone when it runs. The
sign vectors are drawn as the published app draws them,
``RandomState(seed)`` then one ``randint(0, 2, 784)`` a branch, from the
seed and not from the program. float32 ``jax.numpy`` at ``highest``.

Besides what ``mnist_random_fft_32``'s reference compares, two exact
checks of where the timed fits' design matrix lay, from the program's
own account of every fit of the process (``configs/mnist_random_fft_200
.py`` ``FIT_COUNTS``):

* ``shards_off``: the row shards a fit's design matrix lay on against
  the file's ``chips``, worst fit; a fit that was not counted as sharded
  at all counts as off by all of them.
* ``replicated_off``: the bytes of the matrix on the fullest chip over
  a chip's share (the padded rows over ``chips``, times a row's bytes),
  less 1, worst fit, never under 0: a matrix held whole on every chip
  reads ``chips - 1``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _block_ls

SCORE_ROWS = 512


def layout_checks(cfg, fit_counts):
    chips = cfg["chips"]
    width = cfg["num_ffts"] * cfg["features_per_fft"]
    share = -(-cfg["train_rows"] // chips) * width * 4.0
    if not fit_counts:
        return [("shards_off", float(chips), 0.0),
                ("replicated_off", float(chips), 0.0)]
    shards_off = max(
        abs(c["data_shards"] - chips) if c["sharded_fits"] == 1 or chips == 1
        else float(chips) for c in fit_counts)
    replicated_off = max(
        max(c["shard_bytes_max"] / share - 1.0, 0.0) for c in fit_counts)
    return [("shards_off", float(shards_off), 0.0),
            ("replicated_off", float(replicated_off), 0.0)]


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
        """The program's model on the reference's features of the first
        test rows, block by block as the model is."""
        rows = test[:SCORE_ROWS]
        bs = cfg["block_size"]
        scores = jnp.zeros((len(rows), cfg["num_classes"]), jnp.float32)
        with jax.default_matmul_precision("highest"):
            for b in range(num_blocks):
                cols = slice(b * bs, (b + 1) * bs)
                scores = scores + (
                    featurize(rows, b) - ans["feature_means"][cols]
                ) @ ans["weights"][cols]
            return np.asarray(scores + ans["intercept"])

    return _block_ls.fit_checks(
        answers, (W, mean, icpt, train_scores, test_scores, program_scores),
        train_y, test_y, cfg["limits"]) + layout_checks(
            cfg, answers.get("fit_counts"))
