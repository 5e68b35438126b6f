"""A seeded stand-in for the pre-featurised TIMIT frames TimitPipeline
loads: rows of ``label,x0,...,x439`` with 1-indexed labels of 147 phone
classes and 440 real features (11 stacked frames of 40 filter-bank
coefficients in the corpus).

There is no dataset and no network here. A frame is its class's mean plus
a component in a low-rank subspace all classes share (neighbouring
frames and coefficients move together) plus noise of its own in every
coordinate, at about unit variance a coordinate: with the published
``gamma`` = 0.05555 the argument of a random cosine, ``gamma w.x``, is
then of the order of one radian, as the pipeline's authors tuned it to
be. The class means lie a few noise widths apart, so the 147 classes
are learnable, not trivially, and the test error means something
against itself. Every coordinate has noise of its own, so the 440
inputs are full rank.

Values lie on a grid of 1/64 inside +-8: a field is a fixed-width
decimal that reads back to the same float32 exactly, and the file is
written by one table lookup instead of a formatting loop.
"""
from __future__ import annotations

import numpy as np

GRID = 64
LIMIT = 8
#: Standard deviations of the three parts of a coordinate (squares sum to
#: about 1), and the rank of the shared subspace.
MEAN_SD, SHARED_SD, NOISE_SD = 0.22, 0.70, 0.68
SHARED_RANK = 24


def _frames(n: int, rng: np.random.Generator, means: np.ndarray,
            basis: np.ndarray):
    classes, dim = means.shape
    labels = rng.integers(0, classes, size=n)
    x = means[labels]
    x += rng.standard_normal((n, basis.shape[0]), dtype=np.float32) @ basis
    x += NOISE_SD * rng.standard_normal((n, dim), dtype=np.float32)
    steps = np.clip(np.rint(x * GRID), -LIMIT * GRID, LIMIT * GRID - 1)
    return (steps / GRID).astype(np.float32), labels.astype(np.int32)


def make_frames(n_train: int, n_test: int, seed: int, dim: int = 440,
                classes: int = 147):
    """((train_rows f32, train_labels), (test_rows, test_labels)),
    labels 0-indexed."""
    rng = np.random.default_rng(seed)
    means = (MEAN_SD * rng.standard_normal((classes, dim))).astype(np.float32)
    basis = (SHARED_SD / np.sqrt(SHARED_RANK) * rng.standard_normal(
        (SHARED_RANK, dim))).astype(np.float32)
    return (_frames(n_train, rng, means, basis),
            _frames(n_test, rng, means, basis))


_VALUES = np.arange(-LIMIT * GRID, LIMIT * GRID) / GRID
_COMMA = np.array([b"%+.6f," % v for v in _VALUES], dtype="S10")
_NEWLINE = np.array([b"%+.6f\n" % v for v in _VALUES], dtype="S10")
_LABEL = np.array([b"%09d," % i for i in range(1000)], dtype="S10")


def write_csv(path: str, rows: np.ndarray, labels: np.ndarray,
              label_offset: int = 1) -> None:
    n, dim = rows.shape
    index = np.rint(rows * GRID).astype(np.int64) + LIMIT * GRID
    cells = np.empty((n, dim + 1), dtype="S10")
    cells[:, 0] = _LABEL[labels + label_offset]
    cells[:, 1:] = _COMMA[index]
    cells[:, -1] = _NEWLINE[index[:, -1]]
    with open(path, "wb") as f:
        f.write(cells.tobytes())
