"""Image node tests vs numpy golden implementations (mirrors
ConvolverSuite / PoolerSuite / WindowerSuite etc.)."""
import numpy as np
import pytest

from keystone_tpu.nodes.images.core import (
    CenterCornerPatcher,
    Convolver,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    RandomPatcher,
    SymmetricRectifier,
    Windower,
)
from keystone_tpu.nodes.learning.zca import ZCAWhitenerEstimator
from keystone_tpu.ops.image_ops import (
    extract_windows,
    filter_bank_convolve,
    normalize_rows,
)
from keystone_tpu.parallel.dataset import ArrayDataset


def rand_images(n=4, h=10, w=10, c=3, seed=0):
    return np.random.RandomState(seed).rand(n, h, w, c).astype(np.float32) * 255


def im2col_patches(img, size):
    """Golden im2col in (dy, dx, c) feature order (the reference's
    makePatches packing)."""
    H, W, C = img.shape
    out = []
    for y in range(H - size + 1):
        for x in range(W - size + 1):
            out.append(img[y : y + size, x : x + size, :].ravel())
    return np.array(out)


def test_extract_windows_matches_im2col():
    img = rand_images(1, 8, 8, 2)[0]
    wins = np.asarray(extract_windows(img, 3, 1))
    flat = wins.reshape(-1, 3 * 3 * 2)
    np.testing.assert_allclose(flat, im2col_patches(img, 3), rtol=1e-6)


def test_normalize_rows_golden():
    rng = np.random.RandomState(0)
    m = rng.rand(5, 12).astype(np.float32)
    out = np.asarray(normalize_rows(m, 10.0))
    means = m.mean(1, keepdims=True)
    var = ((m - means) ** 2).sum(1, keepdims=True) / (m.shape[1] - 1)
    expect = (m - means) / np.sqrt(var + 10.0)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_convolver_matches_im2col_gemm():
    """Conv-based path == materialized patches @ filters (the reference
    algorithm, Convolver.scala:120-190), incl. patch normalization and
    whitener means."""
    rng = np.random.RandomState(1)
    img = rng.rand(10, 10, 3).astype(np.float32)
    K, S, C = 7, 4, 3
    filters = rng.rand(K, S * S * C).astype(np.float32)
    means = rng.rand(S * S * C).astype(np.float32) * 0.1

    out = np.asarray(
        filter_bank_convolve(img, filters, S, C, True, means, 10.0)
    )

    patches = im2col_patches(img, S)
    pn = np.asarray(normalize_rows(patches, 10.0)) - means
    expect = (pn @ filters.T).reshape(10 - S + 1, 10 - S + 1, K)
    np.testing.assert_allclose(out, expect, rtol=2e-3, atol=2e-3)


def test_convolver_no_normalization():
    rng = np.random.RandomState(2)
    img = rng.rand(8, 8, 1).astype(np.float32)
    filters = rng.rand(2, 9).astype(np.float32)
    out = np.asarray(filter_bank_convolve(img, filters, 3, 1, False, None))
    patches = im2col_patches(img, 3)
    expect = (patches @ filters.T).reshape(6, 6, 2)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_symmetric_rectifier():
    img = np.array([[[1.0, -2.0]]], np.float32)
    out = SymmetricRectifier(alpha=0.25)(img[None]).numpy()[0]
    np.testing.assert_allclose(out[0, 0], [0.75, 0.0, 0.0, 1.75])


def test_pooler_cifar_geometry():
    """poolSize=14, stride=13 on 27x27 -> 2x2 pools, regions [0,14) and
    [13,27) (reference Pooler.scala strideStart semantics)."""
    img = np.ones((27, 27, 2), np.float32)
    out = Pooler(13, 14, "identity", "sum")(img[None]).numpy()[0]
    assert out.shape == (2, 2, 2)
    np.testing.assert_allclose(out[0, 0], 14 * 14)
    np.testing.assert_allclose(out[1, 1], 14 * 14)


def test_pooler_sum_golden():
    rng = np.random.RandomState(3)
    img = rng.rand(9, 9, 1).astype(np.float32)
    out = Pooler(4, 4, "identity", "sum")(img[None]).numpy()[0]
    # strideStart=2; xs = 2, 6; region [0,4), [4,8)
    expect00 = img[0:4, 0:4, 0].sum()
    expect11 = img[4:8, 4:8, 0].sum()
    np.testing.assert_allclose(out[0, 0, 0], expect00, rtol=1e-5)
    np.testing.assert_allclose(out[1, 1, 0], expect11, rtol=1e-5)


def test_windower_flatmap_count():
    imgs = rand_images(3, 8, 8, 1)
    ds = ArrayDataset.from_numpy(imgs)
    out = Windower(2, 4)(ds).get()
    npos = ((8 - 4) // 2 + 1) ** 2
    assert len(out) == 3 * npos
    got = out.numpy()
    assert got.shape == (3 * npos, 4, 4, 1)
    # first window of first image is the top-left crop
    np.testing.assert_allclose(got[0], imgs[0][:4, :4, :], rtol=1e-6)


@pytest.mark.parametrize("n,hw,stride,size,take", [
    (13, (32, 32), 1, 6, 500),     # the CIFAR filter-learning geometry
    (5, (20, 17), 2, 4, 10**6),    # ragged image, more asked than exist
    (16, (32, 32), 1, 6, 100),     # n divisible by the 8-device mesh
])
def test_window_sampler_equals_windower_then_sampler(n, hw, stride, size,
                                                     take):
    """Sampling before extraction draws exactly the windows the
    materialize-then-sample chain drew: same seed, same flat order."""
    from keystone_tpu.nodes.images.core import WindowSampler
    from keystone_tpu.nodes.stats.sampling import Sampler

    imgs = rand_images(n, hw[0], hw[1], 3)
    ds = ArrayDataset.from_numpy(imgs)
    chain = (Windower(stride, size) >> ImageVectorizer()
             >> Sampler(take, seed=3))(ds).get()
    fused = WindowSampler(stride, size, take, seed=3)(ds).get()
    assert len(fused) == len(chain) > 0
    np.testing.assert_array_equal(fused.numpy(), chain.numpy())


def test_random_patcher_shapes_and_determinism():
    imgs = rand_images(2, 12, 12, 3)
    ds = ArrayDataset.from_numpy(imgs)
    out1 = RandomPatcher(4, 5, 5, seed=1)(ds).numpy()
    out2 = RandomPatcher(4, 5, 5, seed=1)(ds).numpy()
    assert out1.shape == (8, 5, 5, 3)
    np.testing.assert_array_equal(out1, out2)


def test_center_corner_patcher():
    imgs = rand_images(2, 8, 8, 1)
    ds = ArrayDataset.from_numpy(imgs)
    out = CenterCornerPatcher(4, 4, horizontal_flips=True)(ds).numpy()
    assert out.shape == (20, 4, 4, 1)
    np.testing.assert_allclose(out[0], imgs[0][:4, :4, :], rtol=1e-6)
    # flipped variant
    np.testing.assert_allclose(out[5], imgs[0][:4, :4, ::1][:, ::-1, :], rtol=1e-6)


def test_grayscale_weights():
    img = np.zeros((1, 1, 1, 3), np.float32)
    img[0, 0, 0] = [100, 200, 50]
    out = GrayScaler()(img).numpy()
    expect = 0.2989 * 100 + 0.5870 * 200 + 0.1140 * 50
    np.testing.assert_allclose(out[0, 0, 0, 0], expect, rtol=1e-4)


def test_zca_whitener_decorrelates():
    rng = np.random.RandomState(4)
    base = rng.randn(500, 6).astype(np.float32)
    mix = rng.randn(6, 6).astype(np.float32)
    data = base @ mix
    w = ZCAWhitenerEstimator(eps=1e-5).fit_single(data)
    out = (data - w.means) @ w.whitener
    cov = np.cov(out.T)
    np.testing.assert_allclose(cov, np.eye(6), atol=0.15)
    # whitener is symmetric
    np.testing.assert_allclose(w.whitener, w.whitener.T, atol=1e-4)


def test_grayscale_uint8_promotes():
    """Packed-u8 images: luma weights must not truncate to zero."""
    import numpy as np

    from keystone_tpu.ops.image_ops import to_grayscale

    img = np.full((4, 4, 3), 100, np.uint8)
    out = np.asarray(to_grayscale(img))
    np.testing.assert_allclose(out, 100.0 * 0.9999, rtol=1e-3)
    assert out.dtype == np.float32
