"""Stats/util node tests vs numpy golden implementations (mirrors the
reference's per-node suites)."""
import jax
import numpy as np
import pytest

from keystone_tpu.nodes import stats
from keystone_tpu.nodes.stats import (
    LinearRectifier,
    NormalizeRows,
    PaddedFFT,
    RandomSignNode,
    SignedHellingerMapper,
    StandardScaler,
)
from keystone_tpu.nodes.util import (
    ClassLabelIndicatorsFromIntArrayLabels,
    ClassLabelIndicatorsFromIntLabels,
    MatrixVectorizer,
    MaxClassifier,
    TopKClassifier,
    VectorCombiner,
    VectorSplitter,
)
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.parallel.dataset import ArrayDataset


def test_random_sign_node():
    x = np.arange(6, dtype=np.float32)
    node = RandomSignNode(np.array([1, -1, 1, -1, 1, -1], np.float32))
    out = node(x[None, :]).numpy()
    np.testing.assert_array_equal(out[0], x * np.array([1, -1, 1, -1, 1, -1]))


def test_random_sign_create_seeded():
    a = RandomSignNode.create(100, seed=7)
    b = RandomSignNode.create(100, seed=7)
    np.testing.assert_array_equal(a.signs, b.signs)
    assert set(np.unique(a.signs)) <= {-1.0, 1.0}


def float64_half_spectrum(x):
    """Real part of the first half of the DFT of ``x`` zero-padded to the
    next power of two, in float64 on the host."""
    n = x.shape[-1]
    padded = 1 << (n - 1).bit_length()
    xp = np.pad(np.asarray(x, np.float64), ((0, 0), (0, padded - n)))
    return np.real(np.fft.fft(xp, axis=-1))[:, : padded // 2]


def padded_fft_rows(kind, rows, n, seed=0):
    rng = np.random.RandomState(seed)
    if kind == "unit":
        return rng.randn(rows, n).astype(np.float32)
    # MNIST-shaped: integers 0..255, four pixels in five empty
    ink = rng.rand(rows, n) < 0.2
    return np.where(ink, rng.randint(1, 256, (rows, n)), 0).astype(np.float32)


#: first length whose padded length is past the dense product's threshold
ABOVE = stats.DENSE_MAX_PADDED + 904


@pytest.mark.parametrize("kind", ["unit", "pixels"])
@pytest.mark.parametrize("n", [5, 20, 784, 1000, 1024, ABOVE])
def test_padded_fft_matches_float64_dft(n, kind):
    x = padded_fft_rows(kind, 3, n)
    out = PaddedFFT()(x).numpy()
    expect = float64_half_spectrum(x)
    # next pow2 of 20 = 32 -> first 16 real parts
    assert out.shape == expect.shape == (3, (1 << (n - 1).bit_length()) // 2)
    assert out.dtype == np.float32
    scale = np.abs(expect).max() if kind == "pixels" else 1.0
    np.testing.assert_allclose(out / scale, expect / scale,
                               rtol=2e-5, atol=2e-5)


def test_padded_fft_keeps_a_narrow_dtype():
    import jax.numpy as jnp

    x = jnp.asarray(padded_fft_rows("unit", 2, 20), jnp.bfloat16)
    out = jax.vmap(PaddedFFT().apply)(x)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 16)
    expect = float64_half_spectrum(np.asarray(x, np.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), expect,
                               rtol=2e-2, atol=2e-2 * np.abs(expect).max())


@pytest.mark.parametrize("n", [stats.DENSE_MAX_PADDED - 1096, ABOVE])
def test_padded_fft_paths_agree_across_the_threshold(n, monkeypatch):
    """The same rows through the dense product and through the FFT, at one
    size on either side of the threshold (the threshold is moved; eager
    ``vmap`` so that no cached program answers for the other path)."""
    x = padded_fft_rows("pixels", 4, n, seed=n)
    padded = 1 << (n - 1).bit_length()
    outs, counted = {}, {"dense": 0.0, "fft": 0.0}
    for path, threshold in (("dense", padded), ("fft", padded // 2)):
        monkeypatch.setattr(stats, "DENSE_MAX_PADDED", threshold)
        outs[path] = np.asarray(jax.vmap(PaddedFFT().apply)(x))
        counted[path] += 1.0
        assert padded_fft_counters() == counted
    scale = np.abs(outs["fft"]).max()
    np.testing.assert_allclose(outs["dense"] / scale, outs["fft"] / scale,
                               rtol=0, atol=2e-6)


def padded_fft_counters():
    registry = MetricsRegistry.get_or_create()
    return {path: registry.counter(f"featurize.padded_fft.{path}").value
            for path in ("dense", "fft")}


@pytest.mark.parametrize("n,path", [(784, "dense"), (ABOVE, "fft")])
def test_padded_fft_counts_the_path_when_it_is_traced(n, path):
    """The choice is static, so it is counted where it is made: once a
    trace of ``apply``, nothing when a compiled program runs again."""
    other = {"dense": "fft", "fft": "dense"}[path]
    fn = jax.jit(jax.vmap(PaddedFFT().apply))
    x = padded_fft_rows("pixels", 2, n)
    fn(x)
    assert padded_fft_counters() == {path: 1.0, other: 0.0}
    fn(x + 1.0)
    assert padded_fft_counters() == {path: 1.0, other: 0.0}


def test_padded_fft_builds_its_table_once_per_shape():
    stats._cosine_table.cache_clear()
    for n in (784, 784, 1000):     # 1,000 pads to 1,024 too: its own table
        jax.eval_shape(PaddedFFT().apply,
                       jax.ShapeDtypeStruct((n,), np.float32))
    info = stats._cosine_table.cache_info()
    assert (info.misses, info.hits) == (2, 1)
    table = stats._cosine_table(784, 1024, "float32")
    assert table is stats._cosine_table(784, 1024, "float32")
    assert table.shape == (784, 512) and table.dtype == np.float32
    assert not table.flags.writeable
    # exact where the cosine is: j k a multiple of P / 4
    assert table[0].tolist() == [1.0] * 512 and table[256, 2] == -1.0
    assert table[512, 1] == -1.0 and abs(table[256, 1]) < 1e-15


def test_linear_rectifier():
    x = np.array([[-1.0, 0.5, 2.0]], np.float32)
    out = LinearRectifier(0.0, 0.25)(x).numpy()
    np.testing.assert_allclose(out[0], np.maximum(0.0, x[0] - 0.25))


def test_normalize_rows():
    x = np.array([[3.0, 4.0]], np.float32)
    out = NormalizeRows()(x).numpy()
    np.testing.assert_allclose(out[0], [0.6, 0.8], rtol=1e-6)


def test_signed_hellinger():
    x = np.array([[-4.0, 9.0]], np.float32)
    out = SignedHellingerMapper()(x).numpy()
    np.testing.assert_allclose(out[0], [-2.0, 3.0], rtol=1e-6)


def test_standard_scaler_matches_numpy():
    rng = np.random.RandomState(1)
    x = rng.randn(50, 6).astype(np.float32) * 3 + 1
    model = StandardScaler().fit(x)
    np.testing.assert_allclose(model.mean, x.mean(0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        model.std, x.std(0, ddof=1), rtol=1e-3, atol=1e-4
    )
    out = model(x).numpy()
    np.testing.assert_allclose(out.mean(0), 0, atol=1e-4)
    np.testing.assert_allclose(out.std(0, ddof=1), 1, rtol=1e-3)


def test_standard_scaler_degenerate_column():
    x = np.ones((10, 3), np.float32)
    model = StandardScaler().fit(x)
    np.testing.assert_array_equal(model.std, np.ones(3))


def test_standard_scaler_mean_only():
    x = np.random.RandomState(0).rand(20, 4).astype(np.float32)
    model = StandardScaler(normalize_std_dev=False).fit(x)
    assert model.std is None


def test_class_label_indicators():
    node = ClassLabelIndicatorsFromIntLabels(4)
    out = node(np.array([0, 2, 3], np.int32)).numpy()
    np.testing.assert_array_equal(
        out,
        [[1, -1, -1, -1], [-1, -1, 1, -1], [-1, -1, -1, 1]],
    )


def test_class_label_indicators_array():
    node = ClassLabelIndicatorsFromIntArrayLabels(5)
    # padded multi-labels: -1 = absent
    labels = np.array([[0, 2, -1], [4, -1, -1]], np.int32)
    out = node(labels).numpy()
    np.testing.assert_array_equal(out[0], [1, -1, 1, -1, -1])
    np.testing.assert_array_equal(out[1], [-1, -1, -1, -1, 1])


def test_vector_combiner():
    a = np.ones((4, 2), np.float32)
    b = np.zeros((4, 3), np.float32)
    dsa = ArrayDataset.from_numpy(a)
    z = dsa.zip(ArrayDataset.from_numpy(b))
    out = VectorCombiner().apply_dataset(z).numpy()
    assert out.shape == (4, 5)
    np.testing.assert_array_equal(out[:, :2], a)


def test_max_classifier():
    x = np.array([[0.1, 0.9, 0.2], [1.0, -1.0, 0.0]], np.float32)
    out = MaxClassifier()(x).numpy()
    np.testing.assert_array_equal(out, [1, 0])


def test_topk_classifier():
    x = np.array([[0.1, 0.9, 0.5, -0.2]], np.float32)
    out = TopKClassifier(3)(x).numpy()
    np.testing.assert_array_equal(out[0], [1, 2, 0])


def test_vector_splitter():
    x = np.arange(10, dtype=np.float32)[None, :]
    out = VectorSplitter(4)(x).get()
    parts = out.numpy()
    assert len(parts) == 3
    np.testing.assert_array_equal(parts[0][0], [0, 1, 2, 3])
    np.testing.assert_array_equal(parts[2][0], [8, 9])


def test_matrix_vectorizer_column_major():
    x = np.array([[[1.0, 2.0], [3.0, 4.0]]], np.float32)  # one 2x2 matrix
    out = MatrixVectorizer()(x).numpy()
    np.testing.assert_array_equal(out[0], [1, 3, 2, 4])  # column-major


def test_sparse_vector_coalesces_duplicate_indices():
    # Duplicate indices must sum (matching the padded-COO einsum paths),
    # not last-write-win in todense().
    from keystone_tpu.nodes.util.sparse import SparseVector, sparse_batch

    sv = SparseVector([3, 1, 3, 1, 7], [1.0, 2.0, 4.0, 8.0, 0.5], size=10)
    assert sv.indices.tolist() == [1, 3, 7]
    np.testing.assert_allclose(sv.values, [10.0, 5.0, 0.5])
    dense = sv.todense()
    assert dense[1] == 10.0 and dense[3] == 5.0 and dense[7] == 0.5
    # padded-COO scatter-sum of the batch form must equal todense()
    idx, val, size = sparse_batch([sv])
    scattered = np.zeros(size, dtype=np.float32)
    np.add.at(scattered, idx[0], val[0])
    np.testing.assert_allclose(scattered, dense)
