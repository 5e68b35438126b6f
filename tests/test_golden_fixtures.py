"""Tests against the reference's own golden fixtures (ported verbatim from
``/root/reference/src/test/resources``), as SURVEY §4 prescribes.

These anchor the implementation to *independent* artifacts rather than
same-author numpy ports:

- ``images/convolved.gantrycrane.csv`` — SciPy-generated convolution golden
  (reference ``ConvolverSuite.scala`` "convolutions should match scipy").
- ``aMat.csv``/``bMat.csv`` (+ ``-1class``/``Shuffled`` variants) — weighted
  least-squares fixtures (reference ``BlockWeightedLeastSquaresSuite.scala``).
- ``images/voc_codebook/{means.csv,variances.csv,priors}`` — the VOC GMM
  codebook (reference ``EncEvalSuite.scala``). Note: the reference's FV-sum
  golden (40.109097) needs ``images/feats.csv``, which is absent from the
  reference checkout itself, so that exact scalar is not reproducible here;
  the codebook still pins loader orientation and the FV feature layout.
"""
import os

import numpy as np
import pytest

RES = os.path.join(os.path.dirname(__file__), "resources")


def _load(name):
    return np.loadtxt(os.path.join(RES, name), delimiter=",", ndmin=2)


# ---------------------------------------------------------------- convolver


def test_convolver_matches_scipy_golden():
    """Reference ConvolverSuite.scala:100-137: convolving gantrycrane.png
    with the ascending 3x3x3 kernel must reproduce the SciPy golden CSV
    ((row, col, value) triplets of output channel 0) exactly.

    The golden is a true convolution (all three axes flipped); the
    Convolver correlates, so the filter row is the flipped kernel —
    the same role ``flipFilters = true`` plays in the reference.
    """
    from PIL import Image

    from keystone_tpu.nodes.images.core import Convolver

    im = np.asarray(
        Image.open(os.path.join(RES, "images", "gantrycrane.png"))
    ).astype(np.float32)
    raw = _load(os.path.join("images", "convolved.gantrycrane.csv"))
    H, W = int(raw[:, 0].max()) + 1, int(raw[:, 1].max()) + 1
    golden = np.zeros((H, W))
    golden[raw[:, 0].astype(int), raw[:, 1].astype(int)] = raw[:, 2]

    k = np.arange(27, dtype=np.float32).reshape(3, 3, 3)  # (dy, dx, c)
    filt = k[::-1, ::-1, ::-1].reshape(1, -1)
    conv = Convolver(filt, im.shape[0], im.shape[1], 3, normalize_patches=False)
    out = np.asarray(conv.apply(im))
    assert out.shape == (H, W, 1)
    np.testing.assert_allclose(out[..., 0], golden, rtol=1e-6, atol=1e-3)


# ------------------------------------------------------- weighted solvers


def _weighted_gradient(X, L, W, b, lam, mw):
    """Gradient of the mixture-weighted objective at (W, b), f64.

    Mirrors BlockWeightedLeastSquaresSuite.computeGradient: example i of
    class c gets weight negWt + mw/n_c on column c and negWt = (1-mw)/n
    elsewhere; grad = X^T ((XW + b - L) .* Wts) + lam * W.
    """
    X = X.astype(np.float64)
    L = L.astype(np.float64)
    n, k = L.shape
    y = np.argmax(L, axis=1)
    counts = np.bincount(y, minlength=k)
    neg = (1.0 - mw) / n
    wts = np.full((n, k), neg)
    wts[np.arange(n), y] = neg + mw / counts[y]
    resid = X @ W + b - L
    return X.T @ (resid * wts) + lam * W


@pytest.fixture(scope="module")
def ab_fixture():
    return _load("aMat.csv"), _load("bMat.csv")


def test_block_weighted_zero_gradient_on_fixture(ab_fixture):
    """BlockWeightedLeastSquaresSuite 'solution should have zero gradient'."""
    from keystone_tpu.nodes.learning.block_weighted import (
        BlockWeightedLeastSquaresEstimator,
    )

    A, B = ab_fixture
    model = BlockWeightedLeastSquaresEstimator(
        block_size=4, num_iter=10, lam=0.1, mixture_weight=0.3
    ).fit_arrays(A.astype(np.float32), B.astype(np.float32))
    g = _weighted_gradient(
        A, B, np.asarray(model.weights, np.float64),
        np.asarray(model.intercept, np.float64), 0.1, 0.3,
    )
    assert np.linalg.norm(g.ravel()) < 1e-2


def test_per_class_matches_block_weighted_on_fixture(ab_fixture):
    """'Per-class solver solution should match BlockWeighted solver'."""
    from keystone_tpu.nodes.learning.block_weighted import (
        BlockWeightedLeastSquaresEstimator,
    )
    from keystone_tpu.nodes.learning.per_class_weighted import (
        PerClassWeightedLeastSquaresEstimator,
    )

    A, B = ab_fixture
    A32, B32 = A.astype(np.float32), B.astype(np.float32)
    wsq = BlockWeightedLeastSquaresEstimator(
        block_size=4, num_iter=5, lam=0.1, mixture_weight=0.3
    ).fit_arrays(A32, B32)
    pcs = PerClassWeightedLeastSquaresEstimator(
        block_size=4, num_iter=5, lam=0.1, mixture_weight=0.3
    ).fit_arrays(A32, B32)
    diff = np.linalg.norm(
        (np.asarray(wsq.weights) - np.asarray(pcs.weights)).ravel()
    )
    assert diff < 1e-4  # reference: 1e-6 in f64; f32 solves here
    assert abs(
        np.linalg.norm(np.asarray(wsq.intercept))
        - np.linalg.norm(np.asarray(pcs.intercept))
    ) < 1e-4


def test_block_weighted_block_size_not_dividing(ab_fixture):
    """'should work with nFeatures not divisible by blockSize' (12 % 5)."""
    from keystone_tpu.nodes.learning.block_weighted import (
        BlockWeightedLeastSquaresEstimator,
    )
    from keystone_tpu.nodes.learning.per_class_weighted import (
        PerClassWeightedLeastSquaresEstimator,
    )

    A, B = ab_fixture
    A32, B32 = A.astype(np.float32), B.astype(np.float32)
    for est_cls in (
        BlockWeightedLeastSquaresEstimator,
        PerClassWeightedLeastSquaresEstimator,
    ):
        model = est_cls(
            block_size=5, num_iter=10, lam=0.1, mixture_weight=0.3
        ).fit_arrays(A32, B32)
        g = _weighted_gradient(
            A, B, np.asarray(model.weights, np.float64),
            np.asarray(model.intercept, np.float64), 0.1, 0.3,
        )
        assert np.linalg.norm(g.ravel()) < 1e-1


def test_block_weighted_one_class_fixture():
    """'should work with 1 class only' — must not crash, finite output."""
    from keystone_tpu.nodes.learning.block_weighted import (
        BlockWeightedLeastSquaresEstimator,
    )

    A = _load("aMat-1class.csv").astype(np.float32)
    B = _load("bMat-1class.csv").astype(np.float32)
    model = BlockWeightedLeastSquaresEstimator(
        block_size=4, num_iter=10, lam=0.1, mixture_weight=0.3
    ).fit_arrays(A, B)
    assert np.isfinite(np.asarray(model.weights)).all()
    assert np.isfinite(np.asarray(model.intercept)).all()


def test_shuffled_fixture_equals_grouped(ab_fixture):
    """'groupByClasses should work correctly': fitting on the shuffled
    fixture must give the same model as on the class-grouped one."""
    from keystone_tpu.nodes.learning.block_weighted import (
        BlockWeightedLeastSquaresEstimator,
    )

    A, B = ab_fixture
    As = _load("aMatShuffled.csv").astype(np.float32)
    Bs = _load("bMatShuffled.csv").astype(np.float32)
    est = BlockWeightedLeastSquaresEstimator(
        block_size=4, num_iter=5, lam=0.1, mixture_weight=0.3
    )
    m_grouped = est.fit_arrays(A.astype(np.float32), B.astype(np.float32))
    m_shuffled = est.fit_arrays(As, Bs)
    np.testing.assert_allclose(
        np.asarray(m_grouped.weights), np.asarray(m_shuffled.weights),
        rtol=1e-4, atol=1e-4,
    )


# ------------------------------------------------------------ voc codebook


def test_voc_codebook_load_and_fisher_vector():
    """EncEvalSuite.scala:17-40: load the VOC GMM codebook (means stored
    (dim, centers) = (80, 256)) and run the Fisher Vector path on it."""
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel

    gmm = GaussianMixtureModel.load(
        os.path.join(RES, "images", "voc_codebook", "means.csv"),
        os.path.join(RES, "images", "voc_codebook", "variances.csv"),
        os.path.join(RES, "images", "voc_codebook", "priors"),
    )
    assert gmm.dim == 80 and gmm.k == 256
    assert abs(gmm.weights.sum() - 1.0) < 1e-3
    assert (gmm.variances > 0).all()

    rng = np.random.RandomState(0)
    descriptors = (
        gmm.means.T[rng.randint(0, 256, 50)]
        + 0.1 * rng.randn(50, 80).astype(np.float32)
    ).astype(np.float32)
    fv = np.asarray(FisherVector(gmm).apply(descriptors.T))  # (D, nDesc) in
    assert fv.shape == (80, 2 * 256)
    assert np.isfinite(fv).all()


def test_gmm_data_fixture_two_cluster_recovery():
    """GaussianMixtureModelSuite.scala 'GMM Two Centers dataset 3': on
    gmm_data.txt with k=2, minClusterSize=1, stopTolerance=0, 30 iters,
    both means are ~(0,0), variances are {(1,25),(25,1)} (one component
    elongated per axis), and weights are ~0.5/0.5 — reference tolerances
    0.5 / 2.0 / 0.05."""
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModelEstimator

    X = np.loadtxt(os.path.join(RES, "gmm_data.txt")).astype(np.float32)
    gmm = GaussianMixtureModelEstimator(
        k=2, min_cluster_size=1, stop_tolerance=0.0, max_iterations=30,
        seed=0,
    ).fit_matrix(X)
    means = gmm.means.T      # (k, d)
    variances = gmm.variances.T
    np.testing.assert_allclose(means, np.zeros((2, 2)), atol=0.5)
    want = np.array([[1.0, 25.0], [25.0, 1.0]])
    ok_order1 = np.allclose(variances, want, atol=2.0)
    ok_order2 = np.allclose(variances, want[::-1], atol=2.0)
    assert ok_order1 or ok_order2, f"variances {variances}"
    np.testing.assert_allclose(gmm.weights, [0.5, 0.5], atol=0.05)


def test_lda_iris_matches_published_eigenvectors():
    """LinearDiscriminantAnalysisSuite.scala:12-37: LDA(2) on standardized
    iris must reproduce the published discriminant directions (Raschka's
    LDA tutorial golden, an implementation-independent anchor), up to sign,
    at 1e-4."""
    from keystone_tpu.nodes.learning.classifiers import (
        LinearDiscriminantAnalysis,
    )
    from keystone_tpu.parallel.dataset import ArrayDataset

    rows = [
        l.strip()
        for l in open(os.path.join(RES, "iris.data"))
        if l.strip()
    ]
    X = np.array([[float(v) for v in r.split(",")[:-1]] for r in rows])
    y = np.array(
        [1 if r.endswith("setosa") else 2 if r.endswith("versicolor") else 3
         for r in rows]
    )
    Xs = (X - X.mean(0)) / X.std(0, ddof=1)
    model = LinearDiscriminantAnalysis(2)._fit(
        ArrayDataset.from_numpy(np.asarray(Xs, np.float32)),
        ArrayDataset.from_numpy(y.astype(np.int32)),
    )
    W = np.asarray(model.weights if hasattr(model, "weights") else model.W)
    W = W / np.linalg.norm(W, axis=0)
    major = np.array([-0.1498, -0.1482, 0.8511, 0.4808])
    minor = np.array([0.0095, 0.3272, -0.5748, 0.75])
    for col, want in ((W[:, 0], major), (W[:, 1], minor)):
        assert (
            np.allclose(col, want, atol=1e-4)
            or np.allclose(-col, want, atol=1e-4)
        ), f"got {col}, want ±{want}"


def test_dense_sift_descriptor_golden_gantrycrane():
    """Descriptor-level SIFT parity on the real gantrycrane.png fixture
    (VERDICT r2 next#4; reference anchor: VLFeatSuite golden tests).

    The golden (tests/resources/sift_golden_gantrycrane.npz, generated
    by tools/make_sift_golden.py — checked in for reproducibility) is an
    independent NumPy/SciPy implementation of the same vl_phow recipe:
    scipy convolve1d smoothing and generic bilinear map_coordinates
    sampling at every bin center, vs the production kernel's XLA convs
    and shared-fractional-offset strided-slice sampling. Asserts
    agreement in quantized units across all three scales, including the
    contrast-threshold zeroing and the min(512 v, 255) quantization."""
    from PIL import Image

    from keystone_tpu.ops.sift import CONTRAST_THRESHOLD, dense_sift

    g = np.load(os.path.join(RES, "sift_golden_gantrycrane.npz"))
    want = g["descriptors"].astype(np.float32)  # (128, N) quantized
    prenorm = g["prenorm"]
    step, bin_size, num_scales, scale_step = (int(v) for v in g["config"])

    rgb = np.asarray(
        Image.open(os.path.join(RES, "images/gantrycrane.png"))
        .convert("RGB"), np.float32) / 255.0
    gray = 0.299 * rgb[..., 0] + 0.587 * rgb[..., 1] + 0.114 * rgb[..., 2]

    got = np.asarray(dense_sift(
        gray, step=step, bin_size=bin_size,
        num_scales=num_scales, scale_step=scale_step))
    assert got.shape == want.shape, (got.shape, want.shape)

    # descriptors sitting within f32 noise of the contrast threshold can
    # legitimately flip between zeroed and kept; exclude the borderline
    solid = np.abs(prenorm - CONTRAST_THRESHOLD) > 1e-4
    assert solid.sum() > 3000  # the exclusion must stay a sliver
    diff = np.abs(got[:, solid] - want[:, solid])
    # f64 golden vs f32 production plus f16 golden storage puts values
    # within ~1 quantized unit; a real algorithm regression (grid shift,
    # window change, norm bug) moves many entries by tens of units
    assert diff.max() <= 2.0, diff.max()
    assert diff.mean() <= 0.15, diff.mean()

    # the contrast path is genuinely exercised: golden zeroes a visible
    # fraction, and the kernel zeroes exactly the same solid columns
    zero_want = (want[:, solid].sum(0) == 0)
    zero_got = (got[:, solid].sum(0) == 0)
    assert zero_want.sum() > 100
    assert np.array_equal(zero_want, zero_got)


@pytest.mark.slow
def test_dense_sift_high_precision_parity():
    """Device-mode parity gate for the shipped Precision.HIGH band
    matmuls (ADVICE medium#2): quantized descriptors at HIGH must stay
    within the golden envelope of a HIGHEST (6-pass, ~f32) reference on
    the same input. On CPU the precision flag is a no-op, so this is
    exact there; on TPU (where tier-2 runs @slow tests on device) it
    pins the "within envelope either way" claim the HIGH default rides
    on."""
    import jax

    from keystone_tpu.ops.sift import dense_sift

    rng = np.random.RandomState(0)
    gray = rng.rand(160, 160).astype(np.float32)
    hi = np.asarray(dense_sift(gray, precision=jax.lax.Precision.HIGH))
    ref = np.asarray(dense_sift(gray, precision=jax.lax.Precision.HIGHEST))
    assert hi.shape == ref.shape
    diff = np.abs(hi - ref)
    assert diff.max() <= 2.0, diff.max()
    assert diff.mean() <= 0.15, diff.mean()
