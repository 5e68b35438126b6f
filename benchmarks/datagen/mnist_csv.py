"""A seeded stand-in for the MNIST CSVs MnistRandomFFT loads: rows of
``label,p0,...,p783`` with 1-indexed labels and integer pixels 0..255.

There is no dataset and no network here. The images are sparse ink whose density follows a
faint class pattern (coarse, upsampled to 28x28), so the ten classes are
learnable, not trivially, and the test error means something against
itself. Fields are fixed-width
(``007,000,...``): the file is written by one table lookup instead of a
formatting loop, and parses to the same numbers.
"""
from __future__ import annotations

import numpy as np

SIDE = 28
PIXELS = SIDE * SIDE
#: Like MNIST, most pixels are 0 and the rest carry ink of any level; a
#: class shifts the share of inked images per pixel by CLASS_CONTRAST.
#: Every pixel varies on its own, so the 784 inputs are full rank, and
#: their mean is small beside their spread, so no rectified FFT feature
#: is dead: both are needed for the published lambda = 0 block Grams to
#: be positive definite. The contrast puts the test error near a tenth.
INK = 0.2
CLASS_CONTRAST = 0.08


def make_images(n: int, rng: np.random.Generator, ink_share: np.ndarray):
    labels = rng.integers(0, len(ink_share), size=n)
    inked = rng.random((n, PIXELS), dtype=np.float32) < ink_share[labels]
    level = rng.integers(1, 256, size=(n, PIXELS), dtype=np.uint8)
    return np.where(inked, level, 0).astype(np.uint8), labels.astype(np.int32)


def make_templates(classes: int, rng: np.random.Generator) -> np.ndarray:
    """Per class, the share of images in which each pixel carries ink: a
    coarse 7x7 pattern around ``INK``."""
    coarse = rng.uniform(0, 1, size=(classes, 7, 7)) > 0.5
    up = np.kron(coarse, np.ones((4, 4))).astype(np.float32)
    return (INK + CLASS_CONTRAST * (up - 0.5)).reshape(classes, PIXELS)


def make_mnist(n_train: int, n_test: int, seed: int, classes: int = 10):
    """((train_pixels u8, train_labels), (test_pixels, test_labels)),
    labels 0-indexed."""
    rng = np.random.default_rng(seed)
    templates = make_templates(classes, rng)
    return (make_images(n_train, rng, templates),
            make_images(n_test, rng, templates))


_COMMA = np.array([b"%03d," % i for i in range(256)], dtype="S4")
_NEWLINE = np.array([b"%03d\n" % i for i in range(256)], dtype="S4")


def write_csv(path: str, pixels: np.ndarray, labels: np.ndarray,
              label_offset: int = 1) -> None:
    n = len(labels)
    cells = np.empty((n, PIXELS + 1), dtype="S4")
    cells[:, 0] = _COMMA[labels + label_offset]
    cells[:, 1:] = _COMMA[pixels]
    cells[:, -1] = _NEWLINE[pixels[:, -1]]
    with open(path, "wb") as f:
        f.write(cells.tobytes())
