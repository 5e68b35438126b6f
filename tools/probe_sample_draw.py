"""Host probe for the seeded sample every sampling node shares
(``keystone_tpu.nodes.stats.sampling.sample_indices``, ISSUE 43): the
seconds of one draw at ``cifar_refit``'s size, 100,000 of 36,450,000
windows, by NumPy's ``RandomState.choice`` (dense) and by the native
library's ``permutation_head`` (sparse; absent where the library does
not load), which side of ``keystone_tpu.native`` this host runs,
``--sweep``: the same from 1,000 items to the cell's and from a sample
of everything to one of a 512th, which is what says that the native
form needs no threshold, and ``--split`` (needs the TPU): what else
``featurize:learn_filters`` spends a fit, part by part, on 50,000
seeded images.

    chiprun -- python3 tools/probe_sample_draw.py --sweep --split

Host seconds, medians of ``--reps``. Writes
``chiprun_out/probe_sample_draw.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N, SIZE = 36_450_000, 100_000
SWEEP = [(n, n // r) for n in (1000, 1 << 16, 1 << 20, 1 << 22, 1 << 24, N)
         for r in (1, 2, 8, 64, 512)]


def median_s(fn, reps):
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def forms():
    """name -> draw(n, size, seed), unsorted."""
    from keystone_tpu import native

    out = {"dense": lambda n, k, s: np.random.RandomState(s).choice(
        n, k, replace=False)}
    if native.available():
        out["sparse"] = native.permutation_head
    return out


def say(text):
    print(text, flush=True)


def draws(shapes, reps):
    rows = []
    for n, k in shapes:
        row = {"n": n, "size": k}
        for name, draw in forms().items():
            row[name + "_s"] = median_s(lambda: draw(n, k, 43),
                                        reps if n >= 1 << 20 else 50)
        rows.append(row)
        say(json.dumps(row))
    return rows


def split(seed, reps):
    """The parts of ``_learn_filters`` (random_patch_cifar.py), each
    blocked before the next starts; the first repetition compiles."""
    import jax

    from benchmarks.datagen import cifar_images
    from keystone_tpu.nodes.images import core
    from keystone_tpu.nodes.learning.zca import ZCAWhitenerEstimator
    from keystone_tpu.nodes.stats import sampling
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.cifar import random_patch_cifar as app

    (pixels, _), _ = cifar_images.make_images(50_000, 16, seed)
    train = ArrayDataset.from_numpy(pixels.astype(np.float32))
    cfg = app.RandomCifarConfig(num_filters=10_000, seed=seed % 2 ** 32)
    parts = []
    for _ in range(reps + 1):
        took, t = {}, time.perf_counter()

        def lap(name):
            nonlocal t
            took[name] = time.perf_counter() - t
            t = time.perf_counter()

        idx = sampling.sample_indices(train.n * 27 * 27, app.WHITENER_SAMPLES,
                                      cfg.seed)
        lap("draw")
        img, win = np.divmod(idx, 27 * 27)
        starts = np.stack([img, *np.divmod(win, 27)], axis=1)
        data = jax.block_until_ready(core._gather_windows(
            train.data, jax.numpy.asarray(starts, jax.numpy.int32), 6))
        lap("gather")
        mat = np.asarray(app.normalize_rows(data, 10.0))[:len(idx)]
        lap("normalize_download")
        whitener = ZCAWhitenerEstimator(cfg.whitening_epsilon).fit_single(mat)
        lap("zca_fit")
        sampled = sampling.sample_rows(mat, cfg.num_filters, seed=cfg.seed)
        unnorm = (sampled - whitener.means) @ whitener.whitener
        norms = np.sqrt(np.sum(unnorm ** 2, axis=1))
        (unnorm / (norms + 1e-10)[:, None]) @ whitener.whitener.T
        lap("filters")
        t0 = time.perf_counter()
        app.learn_filters(train, cfg)
        took["learn_filters_whole"] = time.perf_counter() - t0
        parts.append(took)
    out = {k: statistics.median(p[k] for p in parts[1:]) for k in parts[0]}
    say("learn_filters by part: " + json.dumps(out))
    return out


def is_tpu(dev) -> bool:
    return dev.platform == "tpu"


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--seed", type=int, default=4300000001)
    p.add_argument("--sweep", action="store_true")
    p.add_argument("--split", action="store_true")
    args = p.parse_args(argv)

    from keystone_tpu import native

    result = {"native": native.status(), "cpus": os.cpu_count()}
    say("native.status(): " + json.dumps(result["native"]))
    result["cell"] = draws([(N, SIZE)], args.reps)[0]
    if args.sweep:
        result["sweep"] = draws(SWEEP, args.reps)
    if args.split:
        import jax

        dev = jax.devices()[0]
        if not is_tpu(dev):
            print(f"probe_sample_draw: --split found {dev.platform!r}, not "
                  "a TPU", file=sys.stderr)
            return 3
        result["device"] = dev.device_kind
        result["split"] = split(args.seed, args.reps)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_sample_draw.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
