"""Plain RandomPatchCifarAugmented (``RandomPatchCifarAugmented.scala:
25-154``) in float32 ``jax.numpy`` at ``highest``, no kernels, nothing
of the program:

1. filters and whitener from the 32 x 32 training images, exactly as
   ``benchmarks/reference/cifar_random_patch_10k.py`` learns them (its
   steps 1 to 4; that file's functions are used, not copied);
2. the training crops, redrawn from the seed by the derivation the
   configuration's file states (``crops``): image ``i``'s key is
   ``fold_in(PRNGKey(seed), i)``, split in two; ``randint(first, (10,),
   0, 9)`` are the rows its ten crops start at and ``randint(second,
   (10,), 0, 9)`` the columns; crop ``10 i + j`` is the 24 x 24 window
   there, mirrored left to right where ``uniform(PRNGKey(seed),
   (500,000,))[10 i + j] < 0.5``; its label is image ``i``'s. Cut out
   of the bytes on the host by NumPy;
3. the test crops: top left, top right, bottom left, bottom right,
   centre, then the same five mirrored: crop ``10 i + j`` of image ``i``;
4. features of a crop: that file's step 5 on a 24 x 24 image, where the
   one pooling region is positions ``[0, 14) x [0, 14)`` of the 19 x 19
   (the other positions are pooled by nothing and are not convolved
   here); column order ``(block of 2,048 filters, rectifier half,
   filter)``, the program's gather's (that file's departure 1);
5. ``StandardScaler``, one pass of block coordinate descent with
   ``lambda`` over the five blocks, labels +-1: that file's step 7 on a
   block held whole (a block and its centred copy are 16 GB), with
   every sum over the rows (the columns' sums, the CENTRED columns'
   squares, Gram, ``A^T (Y - P)``) taken ``ROWS_A_SUM`` rows a term in
   float32 at ``highest`` and carried from term to term in two floats
   (``_two_sum``), and the means, deviations and the solve of ``(G +
   lambda I) W = A^T (Y - P)`` in float64 on the host: the reference's
   own rounding stands far under anything it measures, so a distance
   it reads is the program's. (It is: with float32 sums in chunks of
   16,384 rows and a float32 Cholesky the sound gaps read the same,
   7.7e-5 to 1.7e-4 in the weights; my chip runs, PR 45);
6. a test image's scores are the mean of its ten crops' scores, its
   class their arg-max (``AugmentedExamplesEvaluator``, average policy).

``check`` decides ``correct`` in ``cifar_random_patch_10k``'s parts:

* **crops**: the program's own augmentation nodes on the same images
  against 2 and 3 on sampled rows, as BYTES (``crops_off``: how many
  differ; 0);
* **features**: ``filters_gap``; the program's blocks on sampled
  training crops, every block, against 4 at the precision the
  configuration states (``features_gap``);
* **solve**: 5 on the PROGRAM's blocks, made a chunk of rows at a time
  by the model's own maker as the timed sweep makes them, against the
  program's weights (``weights_gap``) and its scores of the 100,000 test
  crops (``test_scores_gap``), and the same solve with every product at
  three bfloat16 passes for the two ratios (that file says why);
* **vote**: an image's scores as the program's evaluator averaged the
  program's scores of its ten crops, against 6 on the solve's scores
  (``voted_scores_gap``), and the voted test error the timed fit
  reported against the error those choose (``test_error_gap``);
* exact counts: every one of the 500,000 rows entered every block's
  Gram (``rows_solved_off``, counted by the program where it sums them,
  and ``rows_off``, the counter a fit raises), the chunks a block
  (``row_chunks_off``), blocks made, the form of the fit, the maker,
  the factors' health.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _block_ls
from benchmarks.reference import cifar_random_patch_10k as plain

#: training crops whose bytes and features are compared; the rows of a
#: held block made or scored at a time; the rows the device sums in
#: float32 before the sum is carried on in two floats; the crops a
#: convolution takes; the crops NumPy cuts at a time
FEATURE_ROWS = 256
ROWS_A_CHUNK = 16384
ROWS_A_SUM = 1024
CONV_ROWS = 512
HOST_ROWS = 50000
SCALER_EPS = 1e-12


# -- 2 and 3: the crops ------------------------------------------------------------

def train_offsets(cfg, images: int, seed: int):
    """``(rows [images, copies], columns [images, copies], mirrored
    [images * copies])``."""
    copies, room = cfg["crops_a_train_image"], (
        cfg["image_size"] - cfg["crop_size"] + 1)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(images))

    def one(key):
        first, second = jax.random.split(key)
        return (jax.random.randint(first, (copies,), 0, room),
                jax.random.randint(second, (copies,), 0, room))

    xs, ys = jax.vmap(one)(keys)
    mirrored = jax.random.uniform(
        jax.random.PRNGKey(seed), (images * copies,)) < cfg["flip_chance"]
    return np.asarray(xs), np.asarray(ys), np.asarray(mirrored)


def train_crops(cfg, pixels: np.ndarray, seed: int, rows=None) -> np.ndarray:
    """The augmented training rows ``rows`` (all of them: None) as bytes
    ``[rows, crop, crop, 3]``."""
    copies, side = cfg["crops_a_train_image"], cfg["crop_size"]
    xs, ys, mirrored = train_offsets(cfg, len(pixels), seed)
    rows = np.arange(len(pixels) * copies) if rows is None else rows
    span = np.arange(side)
    out = np.empty((len(rows), side, side, pixels.shape[-1]), pixels.dtype)
    for at in range(0, len(rows), HOST_ROWS):     # the index arrays are big
        part = rows[at:at + HOST_ROWS]
        image, copy = np.divmod(part, copies)
        at_x = xs[image, copy][:, None] + span
        at_y = ys[image, copy][:, None] + span
        at_y = np.where(mirrored[part][:, None], at_y[:, ::-1], at_y)
        out[at:at + HOST_ROWS] = pixels[
            image[:, None, None], at_x[:, :, None], at_y[:, None, :]]
    return out


def test_crops(cfg, pixels: np.ndarray) -> np.ndarray:
    """Ten crops an image ``[10 images, crop, crop, 3]``, image-major."""
    side, size = cfg["crop_size"], cfg["image_size"]
    far, mid = size - side, (size - side) // 2
    starts = [(0, 0), (0, far), (far, 0), (far, far), (mid, mid)]
    five = [pixels[:, x:x + side, y:y + side] for x, y in starts]
    ten = five + [crop[:, :, ::-1] for crop in five]
    return np.stack(ten, axis=1).reshape((-1,) + ten[0].shape[1:])


# -- 4: the features -----------------------------------------------------------------

def crop_geometry(cfg):
    """``(positions a side that some region pools, regions)`` of a
    crop."""
    _, regions = plain.geometry({**cfg, "image_size": cfg["crop_size"]})
    return max(hi for _, hi in regions), tuple(regions)


@functools.partial(jax.jit, static_argnames=(
    "side", "size", "out", "regions", "alpha", "one_pass"))
def _crop_features(rows, filters, means, side, size, out, regions, alpha,
                   one_pass):
    # crops arrive as rows of vectors (the device keeps those compact; a
    # 4-D array of them, cut outside a program, is laid out with its 3
    # channels padded to 128 lanes) and are images only in here
    imgs = rows.astype(jnp.float32).reshape(rows.shape[0], side, side, -1)
    return plain._features(imgs, filters, means, size, out, regions, alpha,
                           one_pass)


def block_features(cfg, crops, filters, means, block, one_pass=False):
    """Block ``block`` of the features of ``crops`` (bytes or floats,
    ``[n, crop * crop * 3]`` rows of vectors), float32 on the device."""
    step = cfg["filters_a_block"]
    out, regions = crop_geometry(cfg)
    part = jnp.asarray(filters[block * step:(block + 1) * step])
    made = [_crop_features(
        crops[i:i + CONV_ROWS], part, jnp.asarray(means), cfg["crop_size"],
        cfg["patch_size"], out, regions, cfg["alpha"], one_pass)
        for i in range(0, len(crops), CONV_ROWS)]
    return made[0] if len(made) == 1 else jnp.concatenate(made)


# -- 5: the solve, a block held whole and summed in chunks -------------------------

@functools.partial(jax.jit, donate_argnums=0)
def _put(held, part, at):
    return jax.lax.dynamic_update_slice_in_dim(held, part, at, axis=0)


def held_block(featurize, rows, block, width):
    """Block ``block`` of every row, made ``ROWS_A_CHUNK`` rows at a
    time into one array."""
    held = jnp.zeros((len(rows), width), jnp.float32)
    for at in range(0, len(rows), ROWS_A_CHUNK):
        part = featurize(rows[at:at + ROWS_A_CHUNK], block)
        held = _put(held, part[:, :width], at)
    return held


def _chunks(n):
    return [(at, min(at + ROWS_A_CHUNK, n)) for at in range(0, n, ROWS_A_CHUNK)]


def _two_sum(total, low, term):
    """``total + term`` and what float32 dropped of it, added to
    ``low`` (Knuth's TwoSum): ``total + low`` carries the sum of half a
    thousand terms to about twice float32's digits."""
    new = total + term
    virtual = new - total
    return new, low + ((total - (new - virtual)) + (term - virtual))


def _over_rows(term, *arrays):
    """The sum over all rows of ``term(rows of each array) -> tuple of
    arrays``, ``ROWS_A_SUM`` rows a term, as ``(totals, lows)``: a term
    is summed in float32 by the device, the terms by ``_two_sum``."""
    n = arrays[0].shape[0]
    size = min(ROWS_A_SUM, n)
    whole = n // size

    def part(at, rows):
        return term(*[jax.lax.dynamic_slice_in_dim(a, at, rows)
                      for a in arrays])

    shapes = jax.eval_shape(lambda: part(0, size))
    zeros = tuple(jnp.zeros(x.shape, x.dtype) for x in shapes)

    def add(sums, terms):
        pairs = [_two_sum(t, l, x) for t, l, x in zip(*sums, terms)]
        return tuple(p[0] for p in pairs), tuple(p[1] for p in pairs)

    sums = jax.lax.fori_loop(
        0, whole, lambda i, sums: add(sums, part(i * size, size)),
        (zeros, zeros))
    if n > whole * size:
        sums = add(sums, part(whole * size, n - whole * size))
    return sums


def _as_doubles(sums):
    return [np.asarray(t, np.float64) + np.asarray(l, np.float64)
            for t, l in zip(*sums)]


@jax.jit
def _column_sums(held):
    return _over_rows(lambda part: (part.sum(axis=0),), held)


@jax.jit
def _centred_squares(held, mean):
    return _over_rows(lambda part: (((part - mean) ** 2).sum(axis=0),), held)


def _products(mm):
    @jax.jit
    def products(held, mean, std, residual):
        def term(part, rest):
            A = (part - mean) / std
            return mm(A.T, A), mm(A.T, rest)
        with jax.default_matmul_precision("highest"):
            return _over_rows(term, held, residual)
    return products


def _scores(mm):
    @jax.jit
    def scores(part, mean, std, W):
        with jax.default_matmul_precision("highest"):
            return mm((part - mean) / std, W)
    return scores


_FORMS = {False: (_products(jnp.matmul), _scores(jnp.matmul)),
          True: (_products(plain._three_passes),
                 _scores(plain._three_passes))}


def block_step(held, residual, lam, three_passes=False):
    """One block's step on the block held whole: ``(W, mean, std, A W)``.
    Every sum over the rows (the columns' sums, the CENTRED columns'
    squares, Gram and ``A^T (Y - P)`` of the centred, scaled rows) is
    taken ``ROWS_A_SUM`` rows at a time in float32 on the device and
    carried from term to term by ``_two_sum``; means, deviations and the
    solve of ``(G + lambda I) W = A^T (Y - P)`` are float64 on the host.
    So the reference's own rounding stands far under the program's, and
    what is left of a gap is the program's."""
    products, scores = _FORMS[three_passes]
    n, width = held.shape
    (total,) = _as_doubles(_column_sums(held))
    mean = jnp.asarray(total / n, jnp.float32)
    (squares,) = _as_doubles(_centred_squares(held, mean))
    std = np.sqrt(squares / max(n - 1, 1))
    std = jnp.asarray(np.where(np.isfinite(std) & (std >= SCALER_EPS),
                               std, 1.0), jnp.float32)
    G, rhs = _as_doubles(products(held, mean, std, residual))
    W = jnp.asarray(np.linalg.solve(G + lam * np.eye(width), rhs),
                    jnp.float32)
    moved = jnp.concatenate([scores(held[a:b], mean, std, W)
                             for a, b in _chunks(n)])
    return W, mean, std, moved


def fit_and_score(featurize, widths, train_rows, labels, test_rows,
                  num_classes, lam, forms=(False,)):
    """Standardise, one pass of block coordinate descent over the
    blocks ``featurize(rows, b)`` gives (of ``widths[b]`` columns), the
    test rows' scores block by block; once for each of ``forms``
    (``three_passes`` or not) on ONE generation of every block. Returns
    a list, a form each, of ``(W, means, stds, intercept,
    test_scores)``."""
    labels = jnp.asarray(labels)
    Y = jnp.where(jnp.arange(num_classes)[None, :] == labels[:, None],
                  1.0, -1.0).astype(jnp.float32)
    y_mean = Y.mean(axis=0)
    residual = [Y - y_mean for _ in forms]
    test = [jnp.zeros((len(test_rows), num_classes), jnp.float32)
            for _ in forms]
    parts = [([], [], []) for _ in forms]
    for b, width in enumerate(widths):
        held = held_block(featurize, train_rows, b, width)
        steps = []
        for i, form in enumerate(forms):
            W, mean, std, moved = block_step(held, residual[i], lam, form)
            residual[i] = residual[i] - moved
            for kept, new in zip(parts[i], (W, mean, std)):
                kept.append(new)
            steps.append((W, mean, std))
        del held
        for at, end in _chunks(len(test_rows)):
            part = featurize(test_rows[at:end], b)[:, :width]
            for i, form in enumerate(forms):
                W, mean, std = steps[i]
                test[i] = _put(test[i], test[i][at:end] + _FORMS[form][1](
                    part, mean, std, W), at)
    return [(np.asarray(jnp.concatenate(Ws, axis=0)),
             np.asarray(jnp.concatenate(means)),
             np.asarray(jnp.concatenate(stds)), np.asarray(y_mean),
             np.asarray(scores + y_mean))
            for (Ws, means, stds), scores in zip(parts, test)]


# -- 6: the vote -------------------------------------------------------------------

def voted_scores(scores, copies: int) -> np.ndarray:
    """The means of every ``copies`` consecutive rows' scores, in
    float64."""
    scores = np.asarray(scores, np.float64)
    return scores.reshape(-1, copies, scores.shape[1]).mean(axis=1)


def voted_error(scores, labels, copies: int) -> float:
    """The error of the classes that those means choose."""
    return float(np.mean(np.argmax(voted_scores(scores, copies), axis=1)
                         != np.asarray(labels)))


def check(cfg, inputs, answers):
    (train_px, train_y), (test_px, test_y) = inputs["train"], inputs["test"]
    seed = inputs["feature_seed"]
    limits, real = cfg["limits"], cfg["real_fit"]
    step, classes, lam = cfg["filters_a_block"], cfg["num_classes"], cfg[
        "lambda"]
    blocks = -(-cfg["num_filters"] // step)
    columns = 2 * len(crop_geometry(cfg)[1]) ** 2
    widths = [columns * min(step, cfg["num_filters"] - b * step)
              for b in range(blocks)]
    copies = cfg["crops_a_train_image"]
    values = {}

    # -- crops: the program's nodes against NumPy, as bytes
    train = train_crops(cfg, train_px, seed)
    test = test_crops(cfg, test_px)
    train_labels = np.repeat(np.asarray(train_y), copies)
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.choice(len(train), size=min(FEATURE_ROWS, len(train)),
                              replace=False))
    test_rows = np.sort(rng.choice(len(test), size=min(FEATURE_ROWS,
                                                       len(test)),
                                   replace=False))
    values["crops_off"] = 0.0
    for made, index, want in ((answers["train_crops"], rows, train),
                              (answers["test_crops"], test_rows, test)):
        count, got = made(index)
        values["crops_off"] += abs(count - len(want)) + _bytes_off(
            got, want[index])

    # -- features: filter bank, then the program's blocks on sampled crops
    filters, means = plain.learn_filters(cfg, train_px, seed)
    values["filters_gap"] = max(
        _block_ls.rel_gap(answers["filters"], filters),
        _block_ls.rel_gap(answers["whitener_means"], means))
    own = (np.asarray(answers["filters"]), np.asarray(
        answers["whitener_means"]))
    sample = jnp.asarray(train[rows].reshape(len(rows), -1), jnp.float32)
    values["features_gap"] = max(
        _block_ls.rel_gap(
            answers["block"](sample, b)[:, :widths[b]],
            block_features(cfg, sample, *own, b,
                           one_pass=cfg["conv_one_pass"]))
        for b in range(blocks))

    # -- solve: this file's solve on the PROGRAM's blocks of every crop,
    # at the stated precision and at the control's, one generation
    # as rows of vectors: the device keeps those in rows
    train_dev = jnp.asarray(train.reshape(len(train), -1)).astype(jnp.float32)
    test_dev = jnp.asarray(test.reshape(len(test), -1)).astype(jnp.float32)
    full, lower = fit_and_score(
        answers["block"], widths, train_dev, train_labels, test_dev, classes,
        lam, forms=(False, True))

    def gaps(solved):
        W, mean, std, icpt, test_scores = solved
        return (max(_block_ls.rel_gap(answers["weights"], W),
                    _block_ls.rel_gap(answers["feature_means"], mean),
                    _block_ls.rel_gap(
                        1.0 / np.asarray(answers["feature_inv_stds"]), std),
                    _block_ls.rel_gap(answers["intercept"], icpt)),
                _block_ls.rel_gap(answers["test_scores"], test_scores))

    for name, at_full, at_lower in zip(
            ("weights_gap", "test_scores_gap"), gaps(full), gaps(lower)):
        values[name] = at_full
        values[name + "_ratio"] = at_full / max(at_lower, 1e-30)

    # -- the vote: an image's scores as the program's evaluator averaged
    # them against the means of its ten crops' scores above, and the
    # error the timed fit reported against the error those means give
    copies = cfg["crops_a_test_image"]
    values["voted_scores_gap"] = _block_ls.rel_gap(
        answers["voted_scores"], voted_scores(full[-1], copies))
    values["test_error_gap"] = abs(
        answers["test_error"] - voted_error(full[-1], test_y, copies))
    del full, lower
    checks = [(name, values[name], limits[name]) for name in values]

    # exact: every row entered every Gram, in as many chunks as the file
    # states; the streamed form with the maker the file states; no
    # unhealthy factor; each block made no more often than a streamed
    # fit may and no less than any must
    checks.append(("rows_solved_off", float(np.max(np.abs(
        np.asarray(answers["rows_solved"], np.float64) - len(train)))), 0.0))
    for name in ("stream_fits", "materialised_fits", "row_chunks", "rows"):
        checks.append((name + "_off", abs(answers[name] - real[name]), 0.0))
    checks.append(_block_ls.blocks_generated_check(
        answers["blocks_generated"], real))
    checks.append(("maker_off", 0.0 if answers["maker"] in real["maker"]
                   else 1.0, 0.0))
    checks.append(("unhealthy_blocks", answers["unhealthy_blocks"], 0.0))
    return checks


def _bytes_off(got, want) -> int:
    """How many bytes of the program's crops differ from the
    reference's (every one, where the shapes do)."""
    got = np.asarray(got)
    if got.size != want.size or len(got) != len(want):
        return int(want.size)
    got = got.reshape(want.shape)
    return int(np.sum(got.astype(np.float64) != want.astype(np.float64)))
