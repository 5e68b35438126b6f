"""A seeded stand-in for the CIFAR-10 binary files RandomPatchCifar
loads: records of one label byte and 3,072 pixel bytes, the R, G and B
planes of a 32 x 32 image each row-major
(``keystone_tpu/loaders/cifar_loader.py``).

There is no dataset and no network here. The images are made as
``loaders/cifar_surrogate.py`` makes its own (the stand-in that read a
test error of 0.19 at 1,024 filters on the chip, PR 21), in bulk instead
of image by image: ten classes in five pairs that SHARE a smooth
low-frequency base, so that raw pixels confuse a pair, and differ in
high-frequency texture, which whitened patch filters pick up; an image
is a 32 x 32 crop of its class's 48 x 48 canvas at a random offset
(training and test rows from disjoint offset ranges, so the test error
needs the shift invariance that convolution and pooling give), with a
random gain and heavy pixel noise, rounded to bytes. Image-like enough
for what the benchmark asks of it: a class is told by texture and not by
single pixels, every pixel has noise of its own (the 108-dimensional
patch covariance the whitener factors is full rank), and the test error
is neither 0 nor chance (0.19 at 10,000 filters and lambda 3,000; my
chip runs, PR 30).
"""
from __future__ import annotations

import numpy as np

SIDE, CHANNELS, CLASSES = 32, 3, 10
CANVAS = 48
RECORD = 1 + SIDE * SIDE * CHANNELS
#: standard deviation of the pixel noise, in byte levels; the share of
#: a class's own texture beside what its pair shares
NOISE_SD = 24.0
OWN_TEXTURE = 0.45


def _neighbours(t):
    return (np.roll(t, 1, 1) + np.roll(t, 1, 2) + np.roll(t, -1, 1)
            + np.roll(t, -1, 2))


def class_canvases(rng: np.random.Generator) -> np.ndarray:
    """``[10, 48, 48, 3]`` in 0..255: a canvas a class."""
    smooth = rng.random((CLASSES // 2, CANVAS, CANVAS, CHANNELS),
                        dtype=np.float32)
    for _ in range(6):
        smooth = (smooth + _neighbours(smooth)) / 5.0

    def texture(count):
        t = rng.random((count, CANVAS, CANVAS, CHANNELS), dtype=np.float32)
        return t - _neighbours(t) / 4.0

    pair = np.arange(CLASSES) // 2
    base = smooth[pair] + 0.9 * (
        texture(CLASSES // 2)[pair] + OWN_TEXTURE * texture(CLASSES))
    return (base - base.min()) / (base.max() - base.min()) * 255.0


def _images(n: int, rng: np.random.Generator, canvases: np.ndarray,
            first_offset: int):
    labels = rng.integers(0, CLASSES, size=n)
    dy = first_offset + rng.integers(0, 8, size=n)
    dx = first_offset + rng.integers(0, 8, size=n)
    span = np.arange(SIDE)
    crops = canvases[labels[:, None, None], (dy[:, None] + span)[:, :, None],
                     (dx[:, None] + span)[:, None, :]]
    gain = (0.7 + 0.6 * rng.random(n, dtype=np.float32))[:, None, None, None]
    noise = rng.standard_normal(crops.shape, dtype=np.float32)
    pixels = np.clip(np.rint(crops * gain + NOISE_SD * noise), 0, 255)
    return pixels.astype(np.uint8), labels.astype(np.int32)


def make_images(n_train: int, n_test: int, seed: int):
    """``((train_pixels u8 [n, 32, 32, 3], train_labels i32), (test ...))``."""
    rng = np.random.default_rng(seed)
    canvases = class_canvases(rng)
    return (_images(n_train, rng, canvases, 0),
            _images(n_test, rng, canvases, 8))


def write_binary(path: str, pixels: np.ndarray, labels: np.ndarray) -> None:
    """CIFAR-10 binary records: the label, then the three colour planes."""
    n = len(labels)
    records = np.empty((n, RECORD), np.uint8)
    records[:, 0] = labels
    records[:, 1:] = pixels.transpose(0, 3, 1, 2).reshape(n, RECORD - 1)
    with open(path, "wb") as f:
        f.write(records.tobytes())
