"""Plain VOCSIFTFisher, stage by stage, independent of the code under
test: dense SIFT in its direct form, a PCA by the eigendecomposition of
the sample's covariance, EM for a diagonal GMM, Fisher vectors from
posteriors, the two normalisations, block least squares and VOC 2007's
11-point mean average precision.

float64 numpy where a stage is small enough for it (SIFT of the sampled
images, the covariance and its eigenvectors, the projection, the
evaluator); float32 ``jax.numpy`` at ``highest`` where it is not (EM over
a million samples, the Fisher vectors' posteriors, the solve).

``check`` has one part a stage, and each part is fed the PROGRAM's output
of the stage before it, so that it judges its own stage alone: a
descriptor wrong in the seventh digit would otherwise move every later
number. Departures from the source, each because the program under test
makes it and the comparison follows the program:

* the source's dense SIFT is VLFeat's C (``vl_dsift`` with a flat
  window, ``vl_imsmooth_f``); here the same steps are written out:
  Gaussian smoothing at sigma = bin / 6 with a radius of ceil(4 sigma)
  and repeated edges, central differences, eight orientation maps with
  linear interpolation in angle, a triangular window of the bin's width
  in each direction with repeated edges, keypoints every ``step`` pixels
  from the source's lower bound ``max(1 + 2 scales - 3 s, 0)`` with the
  box of 4 bins inside the image, L2 normalise, clamp at 0.2,
  renormalise, zero where the norm over 16 is under 0.005, then
  ``min(512 v, 255)`` NOT rounded to the source's short integers (the
  program keeps the fraction; the PCA that follows is linear);
* ``ColumnSampler`` draws WITHOUT replacement (the source's draws with),
  item ``i`` from ``default_rng((seed, i))``, columns kept in order;
* PCA components carry the source's MATLAB sign convention; the source
  computes them by an SVD of the centred sample, this by ``eigh`` of its
  covariance in float64;
* the GMM is initialised as the program initialises it (k-means++ and
  one Lloyd step, on the device from ``--seed``): the initial parameters
  are taken FROM the program and EM run from them for the program's
  number of adopted steps, because another initialisation is another
  mixture; the source's enceval EM starts from ``random_init(seed=42)``;
* the Fisher vector is the source's Scala form (s0, s1, s2 moments of
  thresholded posteriors), not enceval's.
"""
from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from scipy.ndimage import correlate1d

NBP, NBO = 4, 8
LUMA = (0.2989, 0.5870, 0.1140)   # MATLAB's rgb2gray, the source's GrayScaler


# -- dense SIFT, direct form, float64 -------------------------------------------

def gray_of(image_u8) -> np.ndarray:
    return (np.asarray(image_u8, np.float64) / 255.0) @ np.asarray(LUMA)


def dense_sift(gray: np.ndarray, step=4, bin_size=6, num_scales=5,
               scale_step=0) -> np.ndarray:
    """``[128, descriptors]`` float64, scales one after another, a
    scale's keypoints row by row; dimension ``(by * 4 + bx) * 8 + o``."""
    h, w = gray.shape
    out = []
    for s in range(num_scales):
        b = bin_size + 2 * s
        st = step + s * scale_step
        lo = max(1 + 2 * num_scales - 3 * s, 0)
        sigma = b / 6.0
        radius = int(math.ceil(4.0 * sigma))
        taps = np.exp(-0.5 * (np.arange(-radius, radius + 1) / sigma) ** 2)
        taps /= taps.sum()
        smooth = correlate1d(correlate1d(gray, taps, axis=0, mode="nearest"),
                             taps, axis=1, mode="nearest")
        gy, gx = np.gradient(smooth)
        mag = np.hypot(gx, gy)
        a = (np.arctan2(gy, gx) % (2 * np.pi)) * (NBO / (2 * np.pi))
        low = np.floor(a)
        frac = a - low
        low = low.astype(int) % NBO
        maps = np.stack([mag * ((low == o) * (1.0 - frac)
                                + ((low + 1) % NBO == o) * frac)
                         for o in range(NBO)])
        window = np.maximum(0.0, 1.0 - np.abs(np.arange(-(b - 1), b)) / b)
        binned = correlate1d(correlate1d(maps, window, axis=1, mode="nearest"),
                             window, axis=2, mode="nearest")

        def centres(length):
            first, last = lo + 2 * b, (length - 1) - 2 * b
            return first + st * np.arange(
                max((last - first) // st + 1, 0) if last >= first else 0)

        cy, cx = centres(h), centres(w)
        if not len(cy) or not len(cx):
            continue

        def sampled(values, centre, axis, length):
            """The four bins along one axis: ``values`` at ``centre +
            (bin - 1.5) b``, linear between the two pixels around it."""
            parts = []
            for k in range(NBP):
                at = centre + (k - 1.5) * b
                p = np.floor(at).astype(int)
                f = at - p
                lower = np.take(values, np.clip(p, 0, length - 1), axis=axis)
                upper = np.take(values, np.clip(p + 1, 0, length - 1),
                                axis=axis)
                shape = [1] * values.ndim
                shape[axis] = len(at)
                f = f.reshape(shape)
                parts.append((1.0 - f) * lower + f * upper)
            return np.stack(parts, axis=axis)   # a bin axis before ``axis``

        rows = sampled(binned, cy, 1, h)          # [o, by, ny, W]
        cells = sampled(rows, cx, 3, w)           # [o, by, ny, bx, nx]
        d = cells.transpose(1, 3, 0, 2, 4).reshape(NBP * NBP * NBO, -1)
        norm = np.sqrt((d * d).sum(0))
        d = np.minimum(d / np.maximum(norm, 1e-12), 0.2)
        d = d / np.maximum(np.sqrt((d * d).sum(0)), 1e-12)
        d[:, norm / (NBP * NBP) < 0.005] = 0.0
        out.append(np.minimum(512.0 * d, 255.0))
    return (np.concatenate(out, axis=1) if out
            else np.zeros((NBP * NBP * NBO, 0)))


# -- samples, PCA ------------------------------------------------------------------

def sampled_columns(cols: int, count: int, seed: int, item: int):
    idx = np.random.default_rng((seed, item)).choice(
        cols, size=min(count, cols), replace=False)
    return np.sort(idx)


def columns_as_rows(sample) -> np.ndarray:
    """``[items, d, cols]`` -> ``[items * cols, d]``."""
    sample = np.asarray(sample)
    return sample.transpose(0, 2, 1).reshape(-1, sample.shape[1])


def covariance(rows) -> np.ndarray:
    rows = np.asarray(rows, np.float64)
    centred = rows - rows.mean(0)
    return centred.T @ centred / len(rows)


def pca_basis(rows, dims: int) -> np.ndarray:
    """``[d, dims]``: the covariance's leading eigenvectors, each with
    its largest-magnitude entry positive."""
    _, vecs = np.linalg.eigh(covariance(rows))
    basis = vecs[:, ::-1][:, :dims]
    flip = np.where(basis.max(0) == np.abs(basis).max(0), 1.0, -1.0)
    return basis * flip


def pca_gap(basis, rows) -> float:
    """How far ``basis`` is from spanning the leading eigenvectors of the
    sample's covariance, blind to each column's sign and to a rotation
    among eigenvalues as close as the number read: the largest of each
    column's eigen-residual ``|C v - (v' C v) v|`` over the largest
    eigenvalue, the variance the basis misses over what the best one
    keeps, and the basis's distance from orthonormal."""
    basis = np.asarray(basis, np.float64)
    cov = covariance(rows)
    values = np.linalg.eigvalsh(cov)[::-1]
    image = cov @ basis
    rayleigh = np.sum(basis * image, axis=0)
    residual = np.linalg.norm(image - basis * rayleigh, axis=0) / values[0]
    best = values[:basis.shape[1]].sum()
    missed = abs(best - np.trace(basis.T @ image)) / best
    skew = np.abs(basis.T @ basis - np.eye(basis.shape[1])).max()
    return float(max(residual.max(), missed, skew))


# -- the mixture --------------------------------------------------------------------

def _log_joint(x, means, variances, weights):
    """``log(w_k N(x | mu_k, diag var_k))``, ``[n, k]``; means and
    variances ``[k, d]``."""
    d = x.shape[1]
    mahalanobis = ((x * x) @ (0.5 / variances).T - x @ (means / variances).T
                   + 0.5 * jnp.sum(means * means / variances, axis=1))
    return (-0.5 * d * jnp.log(2 * jnp.pi)
            - 0.5 * jnp.sum(jnp.log(variances), axis=1)
            + jnp.log(weights) - mahalanobis)


def _thresholded_posteriors(log_joint, threshold):
    q = jax.nn.softmax(log_joint, axis=1)
    q = jnp.where(q > threshold, q, 0.0)
    return q / jnp.sum(q, axis=1, keepdims=True)


def em(rows, initial, updates: int, cfg):
    """``updates`` EM steps from ``initial`` (means, variances ``[k, d]``,
    weights): thresholded posteriors, variances floored at
    ``max(small x the column's variance, absolute)``."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(rows, jnp.float32)
        floor = jnp.maximum(
            cfg["gmm_small_variance"] * jnp.var(x, axis=0),
            cfg["gmm_absolute_variance"])
        means, variances, weights = (jnp.asarray(p, jnp.float32)
                                     for p in initial)
        variances = jnp.maximum(variances, floor)

        @jax.jit
        def step(x, floor, means, variances, weights):
            # the sample is an ARGUMENT: closed over, a million rows
            # become a constant of the program and compile for minutes
            q = _thresholded_posteriors(
                _log_joint(x, means, variances, weights),
                cfg["gmm_weight_threshold"])
            mass = jnp.maximum(q.sum(0), 1e-12)
            new = (q.T @ x) / mass[:, None]
            spread = (q.T @ (x * x)) / mass[:, None] - new ** 2
            return new, jnp.maximum(spread, floor), q.sum(0) / x.shape[0]

        for _ in range(updates):
            means, variances, weights = step(x, floor, means, variances,
                                             weights)
        return tuple(np.asarray(p) for p in (means, variances, weights))


def mean_log_likelihood(rows, params) -> float:
    with jax.default_matmul_precision("highest"):
        means, variances, weights = (jnp.asarray(p, jnp.float32)
                                     for p in params)
        return float(jnp.mean(jax.scipy.special.logsumexp(_log_joint(
            jnp.asarray(rows, jnp.float32), means, variances, weights), 1)))


# -- Fisher vectors and their normalisation ----------------------------------------

def fisher_vector(x, params, threshold) -> np.ndarray:
    """``x`` ``[d, n]`` -> ``[d, 2k]``; means and variances ``[k, d]``.
    The descriptors are padded with masked rows to a multiple of 8,192
    and go through ONE program a width: op by op, every image's own
    width compiled a dozen small programs, minutes for 32 images."""
    x = np.asarray(x, np.float32)
    n = x.shape[1]
    padded = np.zeros((-(-max(n, 1) // 8192) * 8192, x.shape[0]), np.float32)
    padded[:n] = x.T
    return np.asarray(_fisher_vector_of_rows(
        padded, np.arange(len(padded)) < n,
        *(jnp.asarray(p, jnp.float32) for p in params), threshold=threshold))


@functools.partial(jax.jit, static_argnames=("threshold",))
def _fisher_vector_of_rows(xt, real, means, variances, weights, threshold):
    with jax.default_matmul_precision("highest"):
        q = _thresholded_posteriors(
            _log_joint(xt, means, variances, weights), threshold)
        q = q * real[:, None].astype(q.dtype)
        n = jnp.sum(real.astype(jnp.float32))
        s0 = q.sum(0) / n                          # [k]
        s1 = (q.T @ xt) / n                        # [k, d]
        s2 = (q.T @ (xt * xt)) / n
        first = (s1 - means * s0[:, None]) / (
            jnp.sqrt(variances) * jnp.sqrt(weights)[:, None])
        second = (s2 - 2 * means * s1 + (means ** 2 - variances)
                  * s0[:, None]) / (variances * jnp.sqrt(2 * weights)[:, None])
        return jnp.concatenate([first.T, second.T], axis=1)


def normalised_row(fv) -> np.ndarray:
    """Column-major vector, L2, signed square root, L2."""
    v = np.asarray(fv, np.float64).T.reshape(-1)
    v = v / max(np.linalg.norm(v), 2.2e-16)
    v = np.sign(v) * np.sqrt(np.abs(v))
    return v / max(np.linalg.norm(v), 2.2e-16)


# -- the solve and the evaluator ----------------------------------------------------

def block_least_squares(train, targets, test, block: int, lam: float):
    """One pass of block coordinate descent over centred feature blocks
    (``_block_ls.fit_and_score`` for a label MATRIX: VOC is multi-label)."""
    with jax.default_matmul_precision("highest"):
        train, test = jnp.asarray(train), jnp.asarray(test)
        targets = jnp.asarray(targets, jnp.float32)
        intercept = targets.mean(0)
        residual = targets - intercept
        scores = jnp.zeros((test.shape[0], targets.shape[1]), jnp.float32)
        weights, means = [], []
        for at in range(0, train.shape[1], block):
            a = train[:, at:at + block]
            means.append(a.mean(0))
            a = a - means[-1]
            gram = a.T @ a + lam * jnp.eye(a.shape[1], dtype=a.dtype)
            w = jax.scipy.linalg.cho_solve(
                jax.scipy.linalg.cho_factor(gram, lower=True),
                a.T @ residual)
            residual = residual - a @ w
            scores = scores + (test[:, at:at + block] - means[-1]) @ w
            weights.append(w)
        return (np.asarray(jnp.concatenate(weights)),
                np.asarray(jnp.concatenate(means)), np.asarray(intercept),
                np.asarray(scores + intercept))


def average_precisions(label_lists, scores, num_classes: int) -> np.ndarray:
    """VOC 2007's 11-point interpolated average precision a class."""
    scores = np.asarray(scores, np.float64)
    truth = np.zeros((len(label_lists), num_classes))
    for i, own in enumerate(label_lists):
        truth[i, list(own)] = 1.0
    out = []
    for c in range(num_classes):
        hits = truth[np.argsort(-scores[:, c], kind="stable"), c]
        tp = np.cumsum(hits)
        recall = tp / max(truth[:, c].sum(), 1.0)
        precision = tp / np.arange(1, len(hits) + 1)
        out.append(np.mean([precision[recall >= t].max(initial=0.0)
                            for t in np.arange(11) / 10.0]))
    return np.asarray(out)


def targets_of(label_lists, num_classes: int) -> np.ndarray:
    y = -np.ones((len(label_lists), num_classes), np.float32)
    for i, own in enumerate(label_lists):
        y[i, list(own)] = 1.0
    return y


# -- the reference's own whole chain ------------------------------------------------

def whole_chain_map(cfg, inputs, initial, updates: int) -> float:
    """Everything from the raw images with the reference's own stages,
    one image's descriptors alive at a time. Minutes at the timed size
    (float64 SIFT of every image on the host); the configuration's file
    says where it runs."""
    seed = inputs["seed"]
    sift = dict(step=cfg["sift_step"], bin_size=cfg["sift_bin_size"],
                num_scales=cfg["sift_num_scales"],
                scale_step=cfg["scale_step"])
    (train, train_labels), (test, test_labels) = (inputs["train"],
                                                  inputs["test"])
    per_pca = max(cfg["num_pca_samples"] // len(train), 1)
    per_gmm = max(cfg["num_gmm_samples"] // len(train), 1)
    described = [dense_sift(gray_of(im), **sift) for im in train]
    basis = pca_basis(np.concatenate(
        [d[:, sampled_columns(d.shape[1], per_pca, seed, i)].T
         for i, d in enumerate(described)]), cfg["desc_dim"])
    reduced = [basis.T @ d for d in described]
    sample = np.concatenate(
        [r[:, sampled_columns(r.shape[1], per_gmm, seed + 1, i)].T
         for i, r in enumerate(reduced)])
    params = em(sample, initial, updates, cfg)
    threshold = cfg["gmm_weight_threshold"]
    rows = np.stack([normalised_row(fisher_vector(r, params, threshold))
                     for r in reduced]).astype(np.float32)
    test_rows = np.stack([normalised_row(fisher_vector(
        basis.T @ dense_sift(gray_of(im), **sift), params, threshold))
        for im in test]).astype(np.float32)
    *_, scores = block_least_squares(
        rows, targets_of(train_labels, cfg["num_classes"]), test_rows,
        cfg["block_size"], cfg["lambda"])
    return float(average_precisions(
        test_labels, scores, cfg["num_classes"]).mean())


# -- the comparison -----------------------------------------------------------------

def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def off(value: float, lo: float, hi: float) -> float:
    """By how much ``value`` lies outside ``[lo, hi]``."""
    return float(max(lo - value, value - hi, 0.0))


def check(cfg, inputs, answers):
    limits, real = cfg["limits"], cfg["real_fit"]
    train_images, train_labels = inputs["train"]
    _, test_labels = inputs["test"]
    classes = cfg["num_classes"]
    sift = dict(step=cfg["sift_step"], bin_size=cfg["sift_bin_size"],
                num_scales=cfg["sift_num_scales"],
                scale_step=cfg["scale_step"])
    threshold = cfg["gmm_weight_threshold"]
    # the program's mixture as [k, d]
    gmm = (answers["gmm"][0].T, answers["gmm"][1].T, answers["gmm"][2])
    values = {}

    # dense SIFT of the sampled images; the projection; their encodings
    sift_gaps, projection_gaps, fv_gaps = [], [], []
    with ThreadPoolExecutor(8) as pool:     # a second an image, in float64
        described = list(pool.map(
            lambda item: dense_sift(gray_of(train_images[item["id"]]),
                                    **sift), answers["sampled"]))
    for item, want in zip(answers["sampled"], described):
        sift_gaps.append(rel_gap(item["descriptors"], want))
        projection_gaps.append(rel_gap(
            item["reduced"], np.asarray(answers["pca_mat"], np.float64).T
            @ np.asarray(item["descriptors"], np.float64)))
        fv_gaps.append(rel_gap(
            answers["train_design"][item["id"]],
            normalised_row(fisher_vector(item["reduced"], gmm, threshold))))
    values["sift_gap"] = max(sift_gaps)
    values["projection_gap"] = max(projection_gaps)
    values["fv_gap"] = max(fv_gaps)

    # the PCA on the program's own sample
    values["pca_gap"] = pca_gap(answers["pca_mat"],
                                columns_as_rows(answers["pca_sample"]))

    # EM from the program's own initialisation, on its own sample
    sample = columns_as_rows(answers["gmm_sample"])
    ours = em(sample, answers["gmm_initial"], answers["gmm_updates"], cfg)
    values["gmm_gap"] = max(rel_gap(p, w) for p, w in zip(gmm, ours))
    want = mean_log_likelihood(sample, ours)
    values["loglik_gap"] = abs(
        mean_log_likelihood(sample, gmm) - want) / abs(want)

    # the solve on the program's own design matrices
    W, means, intercept, scores = block_least_squares(
        answers["train_design"], answers["train_labels"],
        answers["test_design"], cfg["block_size"], cfg["lambda"])
    values["weights_gap"] = max(
        rel_gap(answers["weights"], W),
        rel_gap(answers["feature_means"], means),
        rel_gap(answers["intercept"], intercept))
    # the program's model in prediction space, applied HERE to the
    # program's test design; and the program's own application of it
    with jax.default_matmul_precision("highest"):
        applied = np.asarray(
            (jnp.asarray(answers["test_design"])
             - jnp.asarray(answers["feature_means"]))
            @ jnp.asarray(answers["weights"])
            + jnp.asarray(answers["intercept"]))
    values["test_scores_gap"] = rel_gap(applied, scores)
    values["apply_gap"] = rel_gap(answers["test_scores"], applied)
    values["labels_gap"] = rel_gap(answers["train_labels"],
                                   targets_of(train_labels, classes))

    # the evaluator on the program's own scores; the whole chain
    values["map_gap"] = abs(answers["map"] - float(average_precisions(
        test_labels, answers["test_scores"], classes).mean()))
    if cfg.get("whole_chain"):
        values["test_error_gap"] = abs(answers["map"] - whole_chain_map(
            cfg, inputs, answers["gmm_initial"], answers["gmm_updates"]))
    checks = [(name, value, limits[name]) for name, value in values.items()]

    # what a whole fit does, counted by the program
    n, n_test = len(train_images), len(test_labels)
    counts = {
        "pca_fits_off": abs(answers["pca_fits"] - real["pca_fits"]),
        "gmm_fits_off": abs(answers["gmm_fits"] - real["gmm_fits"]),
        "fv_images_off": abs(answers["fv_images"] - (n + n_test)),
        # every training image between once and three times (the PCA's
        # sample, the GMM's, the encodings), every test image once
        "sift_passes_off": off(answers["sift_images"], n + n_test,
                               real["sift_passes_max"] * n + n_test),
        "em_iterations_off": off(answers["gmm_iterations"], 1,
                                 cfg["gmm_max_iterations"]),
        "maker_off": float(answers["maker"] != real["maker"]),
    }
    return checks + [(name, float(v), 0.0) for name, v in counts.items()]
