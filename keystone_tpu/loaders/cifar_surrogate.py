"""Seeded stand-in for CIFAR-10 where the dataset is not on disk (the
sandbox and the chip machine have no network): a discriminative
surrogate at CIFAR shapes, and a writer for the CIFAR-10 binary record
layout ``cifar_loader`` reads. Shared by ``chip_smoke.py`` and the
surrogate's own test.
"""
from __future__ import annotations

import os

import numpy as np

from .cifar_loader import RECORD


def make_surrogate_cifar(n_train, n_test, seed=0):
    """Discriminative surrogate at CIFAR shapes, the honest stand-in
    when the real dataset is absent (zero-egress image); flagged in the
    metric line.

    Built so featurization quality is what the accuracy measures: the
    10 classes come in 5 pairs SHARING a smooth low-frequency base (so
    raw-pixel linear models confuse the pair) and differing in
    high-frequency texture (what whitened random patch filters pick
    up). Images are shifted crops with gain jitter + heavy noise."""
    rng = np.random.RandomState(seed)
    smooth = rng.rand(5, 48, 48, 3).astype(np.float32)
    for _ in range(6):
        smooth = (smooth + np.roll(smooth, 1, 1) + np.roll(smooth, 1, 2)
                  + np.roll(smooth, -1, 1) + np.roll(smooth, -1, 2)) / 5.0
    def sharpen(t):
        return t - (np.roll(t, 1, 1) + np.roll(t, 1, 2)
                    + np.roll(t, -1, 1) + np.roll(t, -1, 2)) / 4.0

    # pair members share MOST of their texture too: only the 0.45-scaled
    # class-specific component separates them, so the task sits in an
    # informative error range (a numerics regression in featurization
    # visibly moves the metric) instead of saturating at 0
    shared = sharpen(rng.rand(5, 48, 48, 3).astype(np.float32))
    own = sharpen(rng.rand(10, 48, 48, 3).astype(np.float32))
    texture = shared[np.arange(10) // 2] + 0.45 * own
    base = smooth[np.arange(10) // 2] + 0.9 * texture
    base = (base - base.min()) / (base.max() - base.min()) * 255.0

    def split(n, r, off):
        # train and test crop from DISJOINT offset ranges, so test
        # accuracy requires the shift-invariance the conv+pool
        # featurizer provides (and raw pixels lack) — not memorization
        # of a finite crop set
        y = r.randint(0, 10, n)
        dx, dy = off + r.randint(0, 8, n), off + r.randint(0, 8, n)
        imgs = np.empty((n, 32, 32, 3), np.float32)
        for i in range(n):
            crop = base[y[i], dy[i]:dy[i] + 32, dx[i]:dx[i] + 32]
            gain = 0.7 + 0.6 * r.rand()
            imgs[i] = np.clip(
                crop * gain + 24.0 * r.randn(32, 32, 3), 0, 255)
        return imgs, y

    tr = split(n_train, np.random.RandomState(seed + 1), 0)
    te = split(n_test, np.random.RandomState(seed + 2), 8)
    return tr, te


def write_cifar_binary(path: str, images: np.ndarray,
                       labels: np.ndarray) -> None:
    """Write ``images`` (n, 32, 32, 3) in [0, 255] and ``labels`` (n,)
    as CIFAR-10 binary records: 1 label byte + 3,072 pixel bytes, the
    R, G and B planes each row-major (``loaders/cifar_loader.py``)."""
    n = len(labels)
    records = np.empty((n, RECORD), np.uint8)
    records[:, 0] = labels
    records[:, 1:] = np.round(images).astype(np.uint8).transpose(
        0, 3, 1, 2).reshape(n, RECORD - 1)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "wb") as f:
        f.write(records.tobytes())
