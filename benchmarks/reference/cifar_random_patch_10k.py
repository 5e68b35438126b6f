"""Plain RandomPatchCifar (``RandomPatchCifar.scala:21-87``) in float32
``jax.numpy`` at ``highest``, no kernels, nothing of the program:

1. the patch sample: of the ``n x 27 x 27`` windows of the training
   images, in image-major, row-major order, the ``whitener_patches``
   (100,000) that ``np.random.RandomState(seed).choice(total, size,
   replace=False)`` names, sorted; a window flattened ``(dy, dx, c)``;
2. each patch minus its mean, over ``sqrt(variance + 10)`` (unbiased);
3. ZCA: ``W = V diag((s^2 / (n - 1) + eps)^-1/2) V^T`` of the centred
   sample, here from the covariance's eigenvectors in float64 on the
   host (the matrix is 108 x 108);
4. the filter rows: the sample's rows that ``RandomState(seed).choice(
   whitener_patches, num_filters, replace=False)`` names, sorted; each
   centred, whitened, scaled to unit norm and whitened again;
5. features of an image: every window normalised as in 2, minus the
   whitener's means, times the filters; the symmetric rectifier
   ``max(0, x - alpha)``, ``max(0, -x - alpha)``; sum pooling over the
   2 x 2 regions ``[c - 7, min(c + 7, 27))`` at centres 7 and 20;
6. column order: **that of the program's gather, not the source's**:
   ``(block, pool, rectifier half, filter within the block)`` for blocks
   of ``filters_a_block`` (512) filters, where the source's one node
   has ``(pool, half, filter)`` (departure 1: the block solve then
   convolves every filter once a generation; block coordinate descent
   visits the same columns grouped differently, so the weights differ
   from the source's by what one pass of BCD leaves unconverged);
7. ``StandardScaler`` (unbiased deviation, 1 where it is under 1e-12),
   then one pass of block coordinate descent with ``lambda`` over those
   blocks (``_block_ls``'s step), labels +-1, and arg-max.

Departure 2: the images are seeded stand-ins
(``benchmarks/datagen/cifar_images.py``), not CIFAR-10.

``check`` decides ``correct`` in two parts, because the program's
featurizer multiplies at one bfloat16 pass and lies 7.5e-3 from this
file's float32 features (my chip runs, PR 30), a hundred times what the
solver's control moves:

* **features**: the program's own blocks (made by the fitted model's
  block maker) on a seeded sample of training rows, every block,
  against 5 computed AT THE PRECISION THE CONFIGURATION STATES, one
  bfloat16 pass with float32 accumulation: the patch product's two
  operands rounded to bfloat16 (pixels are bytes and come through
  exactly; the filters do not), everything else in float32:
  ``features_gap``. Against the unrounded product the gap reads 7.5e-3
  whether or not the program's output is rounded to bfloat16 as well
  (7.505e-3 against 7.500e-3): that comparison cannot fail its control
  and is not made. (The CPU multiplies float32 exactly whatever is
  asked: the rehearsal states ``conv_one_pass`` false and compares with
  the unrounded product.) And the filter bank and whitener means
  against 1-4: ``filters_gap``;
* **solve**: steps 7 on the PROGRAM's blocks, made as the timed
  programs make them, against the program's weights (``weights_gap``)
  and its scores of the test rows (``test_scores_gap``): the solver
  alone, at the solver's tolerance. At 50,000 rows float32 accumulation,
  which every precision shares, is most of that distance, and the
  program's own lower precision reads only 2.2 to 3.2 times its higher
  one. So the same solve is made a second time with every product at
  THREE bfloat16 passes (``_three_passes``: what ``Precision.HIGH``
  does), and the two distances are compared: ``weights_gap_ratio`` and
  ``test_scores_gap_ratio`` are the distance from the full-precision
  solve over the distance from the three-pass one. A sound program
  stands nearer the first (0.34 to 0.39), a program at ``high`` nearer
  the second (2.0 to 2.2; my chip runs, PR 30): three-pass rounding is
  deterministic, so two solves that both use it round alike. And the
  errors the timed fit reported against the errors of this file's own
  fit on its own features (``train_error_gap``, ``test_error_gap``),
  held to a few rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _block_ls

#: training rows whose features are compared, and the rows a conv call
#: takes at a time (its output is rows x 729 x 512 floats)
FEATURE_ROWS = 256
ROW_BATCH = 1000
SCALER_EPS = 1e-12


def geometry(cfg):
    out = cfg["image_size"] - cfg["patch_size"] + 1
    half = cfg["pool_size"] // 2
    regions = [(c - half, min(c + half, out))
               for c in range(half, out, cfg["pool_stride"])]
    return out, regions


def windows(imgs, size, out):
    """``[n, out, out, size * size * c]``, a window flattened (dy, dx, c)."""
    cols = [imgs[:, dy:dy + out, dx:dx + out, :]
            for dy in range(size) for dx in range(size)]
    return jnp.concatenate(cols, axis=-1)


def normalise(patches, var_constant=10.0):
    d = patches.shape[-1]
    mean = patches.mean(axis=-1, keepdims=True)
    var = ((patches - mean) ** 2).sum(axis=-1, keepdims=True) / (d - 1.0)
    return (patches - mean) / jnp.sqrt(var + var_constant)


def learn_filters(cfg, train_pixels, seed):
    """``(filters [num_filters, 108], whitener means [108])``, float32."""
    size, (out, _) = cfg["patch_size"], geometry(cfg)
    n = len(train_pixels)
    pick = np.random.RandomState(seed).choice(
        n * out * out, size=min(cfg["whitener_patches"], n * out * out),
        replace=False)
    pick.sort()
    img, win = np.divmod(pick, out * out)
    wy, wx = np.divmod(win, out)
    span = np.arange(size)
    sample = train_pixels[img[:, None, None], (wy[:, None] + span)[:, :, None],
                          (wx[:, None] + span)[:, None, :]]
    sample = np.asarray(normalise(jnp.asarray(
        sample.reshape(len(pick), -1), jnp.float32)), np.float64)
    means = sample.mean(axis=0)
    centred = sample - means
    values, vectors = np.linalg.eigh(centred.T @ centred / (len(pick) - 1.0))
    whitener = (vectors * (values + cfg["whitening_epsilon"]) ** -0.5
                ) @ vectors.T
    rows = np.random.RandomState(seed).choice(
        len(pick), size=min(cfg["num_filters"], len(pick)), replace=False)
    rows.sort()
    unit = (sample[rows] - means) @ whitener
    unit /= np.sqrt((unit ** 2).sum(axis=1))[:, None] + 1e-10
    return ((unit @ whitener.T).astype(np.float32), means.astype(np.float32))


def _bf16(x):
    # not a cast there and back, which the TPU compiler may remove
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=(
    "size", "out", "regions", "alpha", "one_pass"))
def _features(imgs, filters, means, size, out, regions, alpha,
              one_pass=False):
    patches = windows(imgs, size, out)
    with jax.default_matmul_precision("highest"):
        if not one_pass:
            conv = (normalise(patches) - means) @ filters.T
        else:
            # the same quantity with the one product a matrix unit does
            # in one bfloat16 pass: operands rounded, products and sums
            # exact in float32; the patch's mean and deviation, the
            # filters' row sums and the whitener's bias in float32
            d = patches.shape[-1]
            mean = patches.mean(axis=-1, keepdims=True)
            var = ((patches - mean) ** 2).sum(axis=-1, keepdims=True) / (
                d - 1.0)
            raw = _bf16(patches) @ _bf16(filters).T
            conv = (raw - mean * filters.sum(axis=1)) / jnp.sqrt(
                var + 10.0) - filters @ means
    both = jnp.concatenate([jnp.maximum(conv - alpha, 0.0),
                            jnp.maximum(-conv - alpha, 0.0)], axis=-1)
    pooled = [both[:, x0:x1, y0:y1].sum(axis=(1, 2))
              for x0, x1 in regions for y0, y1 in regions]
    return jnp.stack(pooled, axis=1).reshape(imgs.shape[0], -1)


def block_features(cfg, imgs, filters, means, block, one_pass=False):
    """Block ``block`` of the features of ``imgs`` (float32 on the
    device); ``one_pass``: the patch product's operands rounded to
    bfloat16."""
    step = cfg["filters_a_block"]
    out, regions = geometry(cfg)
    part = jnp.asarray(filters[block * step:(block + 1) * step])
    made = [_features(imgs[i:i + ROW_BATCH], part, jnp.asarray(means),
                      cfg["patch_size"], out, tuple(regions), cfg["alpha"],
                      one_pass)
            for i in range(0, imgs.shape[0], ROW_BATCH)]
    return made[0] if len(made) == 1 else jnp.concatenate(made)


def _three_passes(a, b):
    """``a @ b`` as a matrix unit multiplies float32 at ``Precision.HIGH``:
    each operand split into a bfloat16 head and a bfloat16 tail, the
    tails' product dropped."""
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi


def fit_and_score(featurize, num_blocks, train_rows, labels, test_rows,
                  num_classes, lam, three_passes=False):
    """Standardise, then one pass of ``_block_ls``'s step over the
    blocks ``featurize(rows, b)`` gives, then the test rows' scores
    block by block. Returns ``(W, means, stds, intercept, train_scores,
    test_scores)``. ``three_passes``: the solver's products as the
    program's lower precision makes them (the control's reference)."""
    mm = _three_passes if three_passes else jnp.matmul
    with jax.default_matmul_precision("highest"):
        labels = jnp.asarray(labels)
        Y = jnp.where(jnp.arange(num_classes)[None, :] == labels[:, None],
                      1.0, -1.0).astype(jnp.float32)
        y_mean = Y.mean(axis=0)
        Yc = Y - y_mean
        pred = jnp.zeros_like(Yc)
        n = train_rows.shape[0]
        Ws, means, stds = [], [], []
        for b in range(num_blocks):
            A = featurize(train_rows, b)
            mean = A.mean(axis=0)
            A = A - mean
            std = jnp.sqrt((A * A).sum(axis=0) / max(n - 1, 1))
            std = jnp.where(jnp.isfinite(std) & (std >= SCALER_EPS), std, 1.0)
            A = A / std
            G = mm(A.T, A) + lam * jnp.eye(A.shape[1], dtype=A.dtype)
            W = jax.scipy.linalg.cho_solve(
                jax.scipy.linalg.cho_factor(G, lower=True),
                mm(A.T, Yc - pred))
            pred = pred + mm(A, W)
            del A, G
            Ws.append(W), means.append(mean), stds.append(std)
        test_scores = jnp.zeros((test_rows.shape[0], num_classes), jnp.float32)
        for b in range(num_blocks):
            test_scores = test_scores + mm(
                (featurize(test_rows, b) - means[b]) / stds[b], Ws[b])
        return (np.asarray(jnp.concatenate(Ws, axis=0)),
                np.asarray(jnp.concatenate(means)),
                np.asarray(jnp.concatenate(stds)), np.asarray(y_mean),
                np.asarray(pred + y_mean), np.asarray(test_scores + y_mean))


def check(cfg, inputs, answers):
    (train_px, train_y), (test_px, test_y) = inputs["train"], inputs["test"]
    limits, real = cfg["limits"], cfg["real_fit"]
    blocks = -(-cfg["num_filters"] // cfg["filters_a_block"])
    classes, lam = cfg["num_classes"], cfg["lambda"]
    train = jnp.asarray(train_px, jnp.float32)
    test = jnp.asarray(test_px, jnp.float32)
    values = {}

    # -- features: filter bank, then the program's blocks on sampled rows
    filters, means = learn_filters(cfg, train_px, inputs["feature_seed"])
    values["filters_gap"] = max(
        _block_ls.rel_gap(answers["filters"], filters),
        _block_ls.rel_gap(answers["whitener_means"], means))
    rows = np.sort(np.random.default_rng(inputs["feature_seed"]).choice(
        len(train_px), size=min(FEATURE_ROWS, len(train_px)), replace=False))
    sample = train[rows]
    # the program's own filters: what is compared here is the featurizer
    own = (np.asarray(answers["filters"]), np.asarray(
        answers["whitener_means"]))
    values["features_gap"] = max(
        _block_ls.rel_gap(answers["block"](sample, b), block_features(
            cfg, sample, *own, b, one_pass=cfg["conv_one_pass"]))
        for b in range(blocks))

    # -- solve: this file's solve on the PROGRAM's blocks, at the stated
    # precision and at the control's
    def solve_gaps(three_passes):
        W, mean, std, icpt, _, test_scores = fit_and_score(
            answers["block"], blocks, train, train_y, test, classes, lam,
            three_passes=three_passes)
        return (max(_block_ls.rel_gap(answers["weights"], W),
                    _block_ls.rel_gap(answers["feature_means"], mean),
                    _block_ls.rel_gap(
                        1.0 / np.asarray(answers["feature_inv_stds"]), std),
                    _block_ls.rel_gap(answers["intercept"], icpt)),
                _block_ls.rel_gap(answers["test_scores"], test_scores))

    full, lower = solve_gaps(False), solve_gaps(True)
    for name, at_full, at_lower in zip(
            ("weights_gap", "test_scores_gap"), full, lower):
        values[name] = at_full
        values[name + "_ratio"] = at_full / max(at_lower, 1e-30)

    # -- the errors, against this file's own fit on its own features
    *_, own_train, own_test = fit_and_score(
        lambda imgs, b: block_features(cfg, imgs, filters, means, b),
        blocks, train, train_y, test, classes, lam)
    values["train_error_gap"] = abs(
        answers["train_error"] - _block_ls.error_rate(own_train, train_y))
    values["test_error_gap"] = abs(
        answers["test_error"] - _block_ls.error_rate(own_test, test_y))
    checks = [(name, values[name], limits[name]) for name in values]

    # exact: the fit took the streamed form with the maker the file
    # states, no block's factor was unhealthy, and each block was made
    # no more often than the program's form needs and no less than any
    # streamed fit must
    for name in ("stream_fits", "materialised_fits"):
        checks.append((name + "_off", abs(answers[name] - real[name]), 0.0))
    checks.append(_block_ls.blocks_generated_check(
        answers["blocks_generated"], real))
    checks.append(("maker_off", 0.0 if answers["maker"] in real["maker"]
                   else 1.0, 0.0))
    checks.append(("unhealthy_blocks", answers["unhealthy_blocks"], 0.0))
    return checks
