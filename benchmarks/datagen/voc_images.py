"""A seeded stand-in for what VOCSIFTFisher loads: a tar of JPEGs under
``VOCdevkit/VOC2007/JPEGImages/`` and the labels CSV, in the layout
``keystone_tpu/loaders/voc.py`` reads (a header row; column 1 the
1-based class id, column 4 the quoted file name; a row a label).

There is no dataset and no network here. SIZES (``assumed`` in the
configuration's file: the issue author's memory of VOC 2007): every
image's longer side is ``long_side`` (500); the shorter side is
``common_sides[0]`` (375) for 60% of the images, ``common_sides[1]``
(333) for 25%, and for 15% drawn uniformly from ``short_side_min`` (250)
to ``long_side - 1``; three images in four are landscape. The tail is
what makes shapes differ within a run and between seeds.

CONTENT: 20 classes, each an oriented grating of its own period and
angle (5 periods from 4 to 19 pixels x 4 angles), so that classes differ
in texture at several of dense SIFT's five scales. An image has one to
three labels; its width is cut into as many strips, each filled with
its class's grating at a random phase and a random contrast, over a
smooth background, with a weaker grating of a class it does NOT have
(a distractor) and pixel noise on top. Image-like enough for what the
benchmark asks: descriptors depend on orientation and scale, the
128-dimensional descriptor covariance is full rank (noise in every
pixel), faint labels are missed and distractors mistaken, so the mean
average precision stands clear of chance (about 0.1) and of 1.
"""
from __future__ import annotations

import io
import tarfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CLASSES = 20
PERIODS = (4.0, 6.0, 9.0, 13.0, 19.0)
ANGLES = (0.0, 45.0, 90.0, 135.0)
PREFIX = "VOCdevkit/VOC2007/JPEGImages/"
NOISE_SD = 20.0
WORKERS = 8


def image_sizes(n: int, rng: np.random.Generator, long_side: int,
                common_sides, short_side_min: int) -> np.ndarray:
    """``[n, 2]`` (height, width)."""
    kind = rng.random(n)
    short = np.where(kind < 0.60, common_sides[0], np.where(
        kind < 0.85, common_sides[1],
        rng.integers(short_side_min, long_side, size=n)))
    landscape = rng.random(n) < 0.75
    return np.stack([np.where(landscape, short, long_side),
                     np.where(landscape, long_side, short)], axis=1)


def _grating(cls: int, ys, xs, phase: float) -> np.ndarray:
    period = PERIODS[cls % len(PERIODS)]
    angle = np.deg2rad(ANGLES[cls // len(PERIODS)])
    return np.cos((2.0 * np.pi / period)
                  * (np.cos(angle) * xs + np.sin(angle) * ys) + phase)


def make_image(h: int, w: int, labels, rng: np.random.Generator) -> np.ndarray:
    """``u8 [h, w, 3]``."""
    ys = np.arange(h, dtype=np.float32)[:, None]
    xs = np.arange(w, dtype=np.float32)[None, :]
    # a smooth background: two slow waves
    f = rng.uniform(0.004, 0.02, size=4)
    gray = 128.0 + 30.0 * (np.sin(f[0] * xs + f[1] * ys)
                           + np.cos(f[2] * xs - f[3] * ys))
    cuts = np.linspace(0, w, len(labels) + 1).astype(int)
    for cls, lo, hi in zip(labels, cuts[:-1], cuts[1:]):
        contrast = 45.0 * rng.uniform(0.12, 1.0)
        gray[:, lo:hi] += contrast * _grating(
            cls, ys, xs[:, lo:hi], rng.uniform(0, 2 * np.pi))
    others = np.setdiff1d(np.arange(CLASSES), labels)
    gray += 45.0 * rng.uniform(0.0, 0.3) * _grating(
        int(rng.choice(others)), ys, xs, rng.uniform(0, 2 * np.pi))
    gray += NOISE_SD * rng.standard_normal((h, w), dtype=np.float32)
    tint = rng.uniform(0.85, 1.15, size=3).astype(np.float32)
    return np.clip(np.rint(gray[:, :, None] * tint), 0, 255).astype(np.uint8)


def make_images(n: int, seed: int, part: str, long_side: int = 500,
                common_sides=(375, 333), short_side_min: int = 250):
    """``(images: list of u8 [h, w, 3], labels: list of sorted int lists)``.
    ``part`` (``"train"`` / ``"test"``) draws from its own stream of the
    seed, and image ``i`` of a part from a stream of its own, so the
    images are made side by side on the machine's cores and come out the
    same however many there are."""
    stream = [seed, 0 if part == "train" else 1]
    sizes = image_sizes(n, np.random.default_rng(stream), long_side,
                        common_sides, short_side_min)

    def one(i):
        rng = np.random.default_rng(stream + [1 + i])
        own = sorted(rng.choice(CLASSES, size=int(rng.integers(1, 4)),
                                replace=False).tolist())
        return make_image(int(sizes[i, 0]), int(sizes[i, 1]), own, rng), own

    with ThreadPoolExecutor(WORKERS) as pool:
        made = list(pool.map(one, range(n)))
    return [im for im, _ in made], [own for _, own in made]


def _jpeg(pixels: np.ndarray, quality: int) -> bytes:
    from PIL import Image

    data = io.BytesIO()
    Image.fromarray(pixels).save(data, format="JPEG", quality=quality)
    return data.getvalue()


def write_tar(path: str, images, part: str, quality: int = 90):
    """The JPEGs as one tar; returns the entries' base names."""
    with ThreadPoolExecutor(WORKERS) as pool:
        encoded = list(pool.map(lambda im: _jpeg(im, quality), images))
    names = []
    with tarfile.open(path, "w") as tar:
        for i, data in enumerate(encoded):
            names.append(f"{part}_{i:06d}.jpg")
            info = tarfile.TarInfo(PREFIX + names[-1])
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
    return names


def read_tar(path: str):
    """The tar's JPEGs decoded, in the tar's order: ``u8 [h, w, 3]``
    each. (What the plain reference sees: the files, not the arrays
    they were written from; JPEG is lossy.)"""
    from PIL import Image

    with tarfile.open(path, "r") as tar:
        return [np.asarray(Image.open(tar.extractfile(entry)).convert("RGB"))
                for entry in tar if entry.isfile()]


def write_labels(path: str, names, labels) -> None:
    with open(path, "w") as f:
        f.write("id,class,difficult,truncated,filename\n")
        row = 0
        for name, own in zip(names, labels):
            for cls in own:
                f.write(f'{row},{cls + 1},0,0,"{name}"\n')
                row += 1
