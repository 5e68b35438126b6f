"""Each stage of VOCSIFTFisher in the system against the plain reference
(``benchmarks/reference/voc_sift_fisher_256.py``) at a small size on the
CPU, seeded: dense SIFT on three image shapes, the column PCA, EM from a
shared initialisation, the Fisher vector and its normalisations, and a
whole fit's model and mean average precision.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.harness import load_module
from keystone_tpu.loaders.image_loader_utils import MultiLabeledImage
from keystone_tpu.parallel.dataset import ArrayDataset, HostDataset

ref = load_module("reference", "voc_sift_fisher_256")
CFG = {"gmm_small_variance": 1e-2, "gmm_absolute_variance": 1e-9,
       "gmm_weight_threshold": 1e-4}


def picture(h, w, seed):
    rng = np.random.default_rng(seed)
    ys, xs = np.mgrid[:h, :w]
    img = 120 + 60 * np.cos(0.7 * xs + 0.3 * ys) + 25 * rng.standard_normal(
        (h, w))
    return np.clip(img[:, :, None] * [1.0, 0.9, 1.1], 0, 255).astype(np.uint8)


@pytest.mark.parametrize("shape", [(75, 100), (100, 66), (96, 96)])
def test_dense_sift_is_the_direct_form(shape):
    from keystone_tpu.nodes.images.core import GrayScaler, PixelScaler
    from keystone_tpu.ops import sift

    img = picture(*shape, seed=shape[0])
    gray = GrayScaler().apply(PixelScaler().apply(jnp.asarray(img)))[..., 0]
    np.testing.assert_allclose(gray, ref.gray_of(img), atol=1e-6)
    got = np.asarray(sift.dense_sift(gray))
    want = ref.dense_sift(ref.gray_of(img))
    assert got.shape == want.shape == (
        128, sift.sift_descriptor_count(*shape))
    assert want.max() > 50 and (want == 0).all(0).sum() < want.shape[1] / 2
    assert ref.rel_gap(got, want) < 2e-5
    # and of the padded chunk, fed the same picture in a larger bucket
    padded = np.zeros((1, 128, 128), np.float32)
    padded[0, :shape[0], :shape[1]] = np.asarray(gray)
    chunk = np.asarray(sift.dense_sift_chunk(
        jnp.asarray(padded), np.array([shape])))[0]
    mask = sift.descriptor_mask(*shape, (128, 128))
    assert chunk.shape == (128, sift.chunk_width(128, 128)) and (
        mask.sum() == want.shape[1])
    assert ref.rel_gap(chunk[:, mask], want) < 2e-5
    assert not chunk[:, ~mask].any()


def test_the_count_of_descriptors_is_the_counts_files():
    from keystone_tpu.ops import sift

    counts = load_module("counts", "dense_sift")
    for h, w in ((375, 500), (333, 500), (500, 281), (64, 96)):
        assert counts.descriptors(h, w) == sift.sift_descriptor_count(h, w)
    assert counts.descriptors(375, 500) == 47213
    assert counts.descriptors(333, 500) == 41286


def sample_of_columns(items=12, d=24, cols=40, seed=0):
    rng = np.random.default_rng(seed)
    mix = rng.standard_normal((d, d)) * np.linspace(3, 0.2, d)
    return (rng.standard_normal((items, cols, d)) @ mix + 5).transpose(
        0, 2, 1).astype(np.float32)


@pytest.mark.parametrize("which", ["local", "distributed"])
def test_the_column_pca_spans_the_covariances_leading_eigenvectors(
        which, mesh8):
    from keystone_tpu.nodes.learning import pca

    sample = sample_of_columns()
    est = {"local": pca.LocalColumnPCAEstimator,
           "distributed": pca.DistributedColumnPCAEstimator}[which](6)
    fitted = est.fit(ArrayDataset.from_numpy(sample))
    rows = ref.columns_as_rows(sample)
    assert ref.pca_gap(fitted.pca_mat, rows) < 1e-5
    want = ref.pca_basis(rows, 6)
    np.testing.assert_allclose(fitted.pca_mat, want, atol=2e-4)
    # blind to a column's sign, not to a wrong column
    flipped = np.array(fitted.pca_mat)
    flipped[:, 2] *= -1
    assert ref.pca_gap(flipped, rows) < 1e-5
    wrong = np.array(fitted.pca_mat)
    wrong[:, 5] = ref.pca_basis(rows, 8)[:, 7]
    assert ref.pca_gap(wrong, rows) > 1e-3
    # the projection keeps zero columns zero, in float32 as the source
    x = sample[0].copy()
    x[:, 7] = 0
    out = np.asarray(fitted.apply(jnp.asarray(x)))
    np.testing.assert_allclose(out, fitted.pca_mat.T @ x, rtol=1e-5, atol=1e-4)
    assert not out[:, 7].any()


def test_em_from_a_shared_initialisation():
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModelEstimator

    rng = np.random.default_rng(3)
    centres = rng.standard_normal((5, 6)) * 6
    X = (centres[rng.integers(0, 5, 4000)]
         + rng.standard_normal((4000, 6)) * rng.uniform(0.5, 2, 6)
         ).astype(np.float32)
    model = GaussianMixtureModelEstimator(5, seed=4).fit_matrix(X)
    assert model.updates >= 2
    ours = ref.em(X, model.initial, model.updates, CFG)
    theirs = (model.means.T, model.variances.T, model.weights)
    assert max(ref.rel_gap(a, b) for a, b in zip(theirs, ours)) < 1e-4
    want = ref.mean_log_likelihood(X, ours)
    assert abs(ref.mean_log_likelihood(X, theirs) - want) < 1e-5 * abs(want)
    # one step fewer is another mixture: the comparison would see it
    fewer = ref.em(X, model.initial, model.updates - 1, CFG)
    assert max(ref.rel_gap(a, b) for a, b in zip(theirs, fewer)) > 1e-4


def test_the_fisher_vector_and_its_normalisations():
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
    from keystone_tpu.nodes.stats import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.nodes.util import MatrixVectorizer

    rng = np.random.default_rng(5)
    d, k, n = 10, 6, 700
    gmm = GaussianMixtureModel(
        rng.standard_normal((d, k)) * 20 + 40, rng.uniform(60, 300, (d, k)),
        rng.dirichlet(np.ones(k) * 5))
    X = (rng.standard_normal((d, n)) * 15 + 40).astype(np.float32)
    got = np.asarray(FisherVector(gmm).apply(jnp.asarray(X)))
    params = (gmm.means.T, gmm.variances.T, gmm.weights)
    want = ref.fisher_vector(X, params, 1e-4)
    assert got.shape == want.shape == (d, 2 * k)
    assert ref.rel_gap(got, want) < 1e-4
    row = got
    for node in (MatrixVectorizer(), NormalizeRows(), SignedHellingerMapper(),
                 NormalizeRows()):
        row = node.apply(jnp.asarray(row))
    assert ref.rel_gap(np.asarray(row), ref.normalised_row(want)) < 1e-4
    assert abs(np.linalg.norm(ref.normalised_row(want)) - 1) < 1e-12


def test_the_evaluator_is_voc_2007s_eleven_points():
    from keystone_tpu.evaluation.mean_average_precision import (
        evaluate_mean_average_precision)

    rng = np.random.default_rng(6)
    scores = rng.standard_normal((60, 20))
    labels = [sorted(rng.choice(20, size=rng.integers(1, 4),
                                replace=False).tolist()) for _ in range(60)]
    want = ref.average_precisions(labels, scores, 20)
    got = evaluate_mean_average_precision(labels, scores, 20)
    np.testing.assert_allclose(got, want, atol=1e-12)
    # a perfect ranking of one class reads 1, a reversed one far less
    truth = np.array([1.0 if 0 in own else 0.0 for own in labels])
    assert ref.average_precisions(labels, truth[:, None] + 0 * scores,
                                  20)[0] == pytest.approx(1.0)
    assert ref.average_precisions(labels, -truth[:, None] + 0 * scores,
                                  20)[0] < 0.5


def test_a_whole_fits_model_and_map_are_the_references(mesh8):
    from keystone_tpu.loaders.voc import NUM_CLASSES
    from keystone_tpu.nodes.images.multilabel import (
        MultiLabeledImageExtractor)
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper
    from keystone_tpu.pipelines.images.voc import voc_sift_fisher as app
    from keystone_tpu.workflow.env import PipelineEnv
    from keystone_tpu.workflow.expression import TransformerExpression

    voc = load_module("datagen", "voc_images")
    sizes = dict(long_side=96, common_sides=(72, 64), short_side_min=64)
    parts = {}
    for part, n in (("train", 24), ("test", 16)):
        images, labels = voc.make_images(n, 77, part, **sizes)
        parts[part] = (HostDataset([MultiLabeledImage(im, own, f"{i}.jpg")
                                    for i, (im, own) in enumerate(
                                        zip(images, labels))]), labels)
    env = PipelineEnv.get_or_create()
    env.clear_state()
    cfg = app.SIFTFisherConfig(
        lam=0.5, desc_dim=16, vocab_size=8, num_pca_samples=2400,
        num_gmm_samples=2400, block_size=64, seed=77)
    built = app.build(cfg, parts["train"][0])
    test_data = MultiLabeledImageExtractor().apply_dataset(parts["test"][0])
    scores = np.asarray(built.predictor(test_data).get().numpy())
    (model,) = [e.get() for e in env.state.values()
                if isinstance(e, TransformerExpression) and e.computed
                and isinstance(e.get(), BlockLinearMapper)]
    train_design = np.asarray(
        built.fisher_featurizer(built.training_data).get().numpy())
    test_design = np.asarray(built.fisher_featurizer(test_data).get().numpy())
    assert train_design.shape == (24, 2 * 16 * 8)
    targets = ref.targets_of(parts["train"][1], NUM_CLASSES)
    np.testing.assert_array_equal(
        np.asarray(built.training_labels.numpy()), targets)
    W, means, intercept, want = ref.block_least_squares(
        train_design, targets, test_design, 64, 0.5)
    assert ref.rel_gap(np.asarray(model.weights), W) < 1e-4
    assert ref.rel_gap(np.asarray(model.feature_means), means) < 1e-5
    assert ref.rel_gap(np.asarray(model.intercept), intercept) < 1e-6
    assert ref.rel_gap(scores, want) < 1e-4
    _, ap = app.run(cfg, *(p[0] for p in parts.values()))
    assert float(np.mean(ap)) == pytest.approx(float(ref.average_precisions(
        parts["test"][1], scores, NUM_CLASSES).mean()), abs=1e-9)
