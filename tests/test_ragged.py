"""Items of different sizes in one dataset (``parallel.ragged``): the
padded, bucketed, batched forms of the VOC featurizers give what the
per-image forms give, shapes mixed; the dataset is lazy, keeps its
order, holds what fits when cached and makes the rest again; and the
whole app over a ``RaggedDataset`` ranks like the app over a
``HostDataset`` of loose images.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.loaders.image_loader_utils import MultiLabeledImage
from keystone_tpu.nodes.images.core import GrayScaler, PixelScaler
from keystone_tpu.nodes.images.extractors import SIFTExtractor
from keystone_tpu.nodes.images.fisher_vector import (
    FisherVector, _fisher_vector, _fisher_vector_chunk,
    fisher_vector_of_sums, fv_moments_split)
from keystone_tpu.nodes.learning import gmm as gmm_mod
from keystone_tpu.nodes.learning.gmm import (
    GaussianMixtureModel, GaussianMixtureModelEstimator)
from keystone_tpu.nodes.learning.pca import BatchPCATransformer
from keystone_tpu.nodes.stats.sampling import ColumnSampler
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.ops import sift
from keystone_tpu.ops.pallas_kernels import fv_moments_pallas
from keystone_tpu.parallel import ragged
from keystone_tpu.parallel.dataset import ArrayDataset, HostDataset
from keystone_tpu.parallel.ragged import RaggedDataset
from keystone_tpu.workflow.common import Cacher

SHAPES = [(75, 100), (67, 100), (100, 75), (90, 100), (75, 100), (52, 100),
          (100, 60), (75, 100), (100, 100), (67, 100)]


def images(n=len(SHAPES), seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, SHAPES[i % len(SHAPES)] + (3,)).astype(dtype)
            for i in range(n)]


def counter(name):
    return MetricsRegistry.get_or_create().counter(name)


@pytest.fixture
def small_buckets(monkeypatch):
    """Sides rounded up to 32 and 8 items a chunk (the test mesh has 8
    data shards): the ten images fall into five buckets."""
    monkeypatch.setattr(ragged, "GRANULE", 32)
    monkeypatch.setattr(ragged, "ITEMS_A_CHUNK", 8)


# -- dense SIFT: a padded chunk against one image at a time ----------------------

def test_a_chunk_of_mixed_sizes_gives_each_images_own_descriptors():
    rng = np.random.default_rng(1)
    shapes = SHAPES[:5]
    padded = np.zeros((len(shapes), 128, 128), np.float32)
    for k, (h, w) in enumerate(shapes):
        padded[k, :h, :w] = rng.random((h, w), dtype=np.float32)
    out = np.asarray(sift.dense_sift_chunk(
        jnp.asarray(padded), np.array(shapes)))
    # every scale's segment filled up to whole tiles of 128 columns
    assert out.shape == (5, 128, sift.chunk_width(128, 128))
    assert out.shape[2] % 128 == 0
    assert out.shape[2] > sift.sift_descriptor_count(128, 128)
    for k, (h, w) in enumerate(shapes):
        own = np.asarray(sift.dense_sift(jnp.asarray(padded[k, :h, :w])))
        mask = sift.descriptor_mask(h, w, (128, 128))
        assert mask.sum() == own.shape[1] == sift.sift_descriptor_count(h, w)
        # values reach 255: 2e-3 is a part in a hundred thousand
        np.testing.assert_allclose(out[k][:, mask], own, atol=2e-3)
        assert not out[k][:, ~mask].any()


@pytest.mark.parametrize("h,w", [(96, 128), (90, 110), (64, 80)])
def test_a_chunk_of_one_is_the_image_alone(h, w):
    """The promise that lets the per-image form fold into the chunk
    form (ROADMAP D5a): one image in a bucket of its own size."""
    img = jnp.asarray(np.random.default_rng(0).random(
        (h, w), dtype=np.float32))
    config = dict(step=4, bin_size=4, num_scales=2, scale_step=1)
    alone = np.asarray(sift.dense_sift(img, **config))
    (chunk,) = np.asarray(sift.dense_sift_chunk(
        img[None], np.array([(h, w)]), **config))
    mask = sift.descriptor_mask(h, w, (h, w), **config)
    assert chunk.shape == (128, sift.chunk_width(h, w, **config))
    assert alone.shape == chunk[:, mask].shape and alone.shape[1] > 0
    np.testing.assert_allclose(chunk[:, mask], alone, atol=2e-3)
    # what fills a scale's segment up to whole tiles: zero, masked out
    assert not mask.all() and not chunk[:, ~mask].any()


def test_an_empty_slot_and_a_full_bucket():
    padded = np.zeros((2, 64, 96), np.float32)
    padded[1] = np.random.default_rng(2).random((64, 96), dtype=np.float32)
    out = np.asarray(sift.dense_sift_chunk(
        jnp.asarray(padded), np.array([(0, 0), (64, 96)])))
    assert not out[0].any()
    full = sift.descriptor_mask(64, 96, (64, 96))
    np.testing.assert_allclose(
        out[1][:, full], np.asarray(sift.dense_sift(jnp.asarray(padded[1]))),
        atol=2e-3)
    assert not out[1][:, ~full].any()
    # a full bucket has every keypoint of every scale, and no pad column
    assert full.sum() == sift.sift_descriptor_count(64, 96)
    for offset, count, padded in sift.chunk_segments(64, 96):
        assert full[offset:offset + count].all()
        assert not full[offset + count:offset + padded].any()
    assert not sift.descriptor_mask(0, 0, (64, 96)).any()


#: (step, bin size, scales, scale step): the source's, and the tests' own
SIFT_CONFIGS = [(4, 6, 5, 0), (4, 4, 2, 1)]
#: bucket -> sizes of images that fall into it (the bucket's own first)
BUCKETS = {(384, 512): [(384, 512), (375, 500), (333, 500), (260, 500)],
           (512, 384): [(512, 384), (500, 375), (500, 281)],
           (128, 128): [(128, 128), (75, 100), (100, 60), (52, 100)],
           (64, 96): [(64, 96), (40, 56), (33, 96)]}


@pytest.mark.parametrize("config", SIFT_CONFIGS, ids=["source", "two_scales"])
@pytest.mark.parametrize("bucket", list(BUCKETS))
def test_a_chunks_scales_stand_in_whole_lane_tiles(bucket, config):
    """The layout (``chunk_segments``) and the mask say the same thing:
    segments of whole tiles, and the mask's true entries, in order, are
    the image's own numbering."""
    segments = sift.chunk_segments(*bucket, *config)
    grids = [sift.scale_grid(*bucket, scale, *config)
             for scale in range(config[2])]
    assert [count for _, count, _ in segments] == [
        ny * nx for ny, nx in grids]
    at = 0
    for offset, count, padded in segments:
        assert offset == at and offset % 128 == 0 and padded % 128 == 0
        assert 0 <= padded - count < 128
        at += padded
    assert at == sift.chunk_width(*bucket, *config)
    assert sum(count for _, count, _ in segments) == (
        sift.sift_descriptor_count(*bucket, *config))
    for h, w in BUCKETS[bucket]:
        mask = sift.descriptor_mask(h, w, bucket, *config)
        assert mask.shape == (at,) and mask.dtype == bool
        assert mask.sum() == sift.sift_descriptor_count(h, w, *config)
        # the image's own order: scale-major, then rows of ITS nx
        own = []
        for scale, ((offset, _, _), (_, nxb)) in enumerate(
                zip(segments, grids)):
            ny, nx = sift.scale_grid(h, w, scale, *config)
            own.extend(offset + iy * nxb + ix
                       for iy in range(ny) for ix in range(nx))
        np.testing.assert_array_equal(np.flatnonzero(mask), own)


def test_the_sampler_draws_the_same_descriptors_from_a_chunk_as_from_the_images():
    """``ColumnSampler`` reaches a chunk's columns through its mask
    alone, so the pad columns between the scales move no draw."""
    rng = np.random.default_rng(7)
    shapes = [(128, 128), (75, 100), (100, 60), (52, 100), (90, 128)]
    grays = [rng.random(s, dtype=np.float32) for s in shapes]
    node, sampler = SIFTExtractor(), ColumnSampler(37, seed=11)
    ds = node.apply_dataset(RaggedDataset.from_items(grays))
    (chunk,) = ds.chunks()
    assert chunk.data.shape[1:] == (128, sift.chunk_width(128, 128))
    assert chunk.mask.shape == (len(chunk.ids), chunk.data.shape[2])
    got = np.asarray(sampler.apply_dataset(ds).numpy())
    whole = np.asarray(chunk.data)
    for i, gray in enumerate(grays):
        alone = np.asarray(node.apply(jnp.asarray(gray)))
        picked = sampler.columns(alone.shape[1], i)
        assert len(picked) == 37 == len(set(picked))
        np.testing.assert_allclose(got[i], alone[:, picked], atol=2e-3)
        # the same COLUMNS, not only close values: the chunk's picked
        # columns are the image's own descriptors of those numbers
        slot = int(np.flatnonzero(chunk.ids == i)[0])
        at = np.flatnonzero(chunk.mask[slot])[picked]
        np.testing.assert_array_equal(got[i], whole[slot][:, at])
    one_by_one = sampler.apply_dataset(HostDataset(
        [np.asarray(node.apply(jnp.asarray(g))) for g in grays])).collect()
    np.testing.assert_allclose(got, np.stack(one_by_one), atol=2e-3)


def test_which_form_a_chunk_took_is_counted_when_it_is_traced():
    before = counter("featurize.sift.einsum").value
    imgs = jnp.zeros((1, 40, 56), jnp.float32)   # a shape no test has traced
    sift.dense_sift_chunk(imgs, np.array([(40, 56)]))
    sift.dense_sift_chunk(imgs, np.array([(40, 50)]))
    assert counter("featurize.sift.einsum").value == before + 1


# -- the dataset ----------------------------------------------------------------------

def test_items_come_back_in_order_at_their_own_sizes(small_buckets):
    items = images()
    ds = RaggedDataset.from_items(items)
    assert len(ds) == len(items)
    shapes = {chunk.data.shape[1:] for chunk, _ in ds.parts}
    assert len(shapes) >= 3 and all(s[0] % 32 == 0 and s[1] % 32 == 0
                                    for s in shapes)
    assert all(chunk.data.shape[0] == 8 for chunk, _ in ds.parts)
    for got, want in zip(ds.collect(), items):
        np.testing.assert_array_equal(got, want)
    assert ds.element().shape == items[0].shape
    assert ds.with_stage(lambda chunk: chunk).element() is None


def test_mapped_nodes_run_when_a_chunk_is_asked_for(small_buckets):
    items = images()
    calls = []
    scaler = PixelScaler()
    stage = scaler.chunk_stage()

    def counted(chunk):
        calls.append(chunk.data.shape)
        return stage(chunk)

    scaler.chunk_stage = lambda: counted
    ds = scaler.apply_dataset(RaggedDataset.from_items(items))
    assert not calls                                   # lazy
    got = GrayScaler().apply_dataset(ds).collect()
    assert len(calls) == len(ds.parts)
    for g, x in zip(got, items):
        want = np.asarray(GrayScaler().apply(PixelScaler().apply(
            jnp.asarray(x))))
        np.testing.assert_allclose(g, want, atol=1e-6)


def test_a_node_without_a_padded_form_gets_the_items_one_by_one(small_buckets):
    from keystone_tpu.workflow.transformer import transformer

    ds = RaggedDataset.from_items(images(4))
    out = transformer(lambda x: x.shape[0] * x.shape[1]).apply_dataset(ds)
    assert isinstance(out, HostDataset)
    assert out.collect() == [h * w for h, w in SHAPES[:4]]


def test_the_cache_holds_what_fits_and_the_rest_is_made_again(
        small_buckets, monkeypatch):
    from keystone_tpu.analysis import resources

    made = []

    def stage(chunk):
        made.append(int(chunk.ids[0]))
        return chunk

    ds = RaggedDataset.from_items(images()).with_stage(stage)
    one = ds.parts[0][0].nbytes()
    # room for about half of the chunks (CACHE_SHARE of what is free)
    monkeypatch.setattr(
        resources, "device_memory_bytes",
        lambda free=False: one * len(ds.parts) / 2 / ragged.CACHE_SHARE + 1)
    cached = Cacher().apply_dataset(ds)
    held = [not stages for _, stages in cached.parts]
    assert 0 < sum(held) < len(held)
    assert len(made) == sum(held) + 1      # the one that no longer fitted
    del made[:]
    first = cached.collect()
    assert len(made) == len(held) - sum(held)          # only the others
    second = cached.collect()
    assert len(made) == 2 * (len(held) - sum(held))
    for a, b, x in zip(first, second, images()):
        np.testing.assert_array_equal(a, x)
        np.testing.assert_array_equal(b, x)
    # with room for everything nothing is made twice
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: 1e12)
    assert all(not s for _, s in Cacher().apply_dataset(ds).parts)


# -- sampler, projection, Fisher vector over padded chunks -----------------------------

def described(items, **kw):
    """(per-image descriptors, the same as a RaggedDataset)."""
    node = SIFTExtractor(**kw)
    grays = [np.asarray(GrayScaler().apply(PixelScaler().apply(
        jnp.asarray(x)))) for x in items]
    loose = [np.asarray(node.apply(jnp.asarray(g))) for g in grays]
    chain = PixelScaler() >> GrayScaler() >> node
    return loose, chain(RaggedDataset.from_items(items)).get()


def test_sift_over_a_ragged_dataset_and_its_counter(small_buckets):
    items = images()
    before = counter("featurize.sift.images").value
    loose, ds = described(items, step=8, num_scales=3)
    assert isinstance(ds, RaggedDataset)
    assert counter("featurize.sift.images").value == before   # lazy
    got = ds.collect()
    assert counter("featurize.sift.images").value == before + len(items)
    for g, want in zip(got, loose):
        assert g.shape == want.shape
        np.testing.assert_allclose(g, want, atol=2e-3)


def test_the_sampler_draws_the_same_columns_in_every_kind_of_dataset(
        small_buckets):
    items = images()
    loose, ds = described(items, step=8, num_scales=3)
    sampler = ColumnSampler(20, seed=5)
    from_ragged = sampler.apply_dataset(ds)
    assert isinstance(from_ragged, ArrayDataset)
    from_host = sampler.apply_dataset(HostDataset(loose)).collect()
    for got, want in zip(np.asarray(from_ragged.numpy()), from_host):
        np.testing.assert_allclose(got, want, atol=2e-3)
    # items of one shape on the device: the same draw again
    same = [loose[0], loose[4], loose[7]]              # three of 75 x 100
    picks = [sampler.columns(same[0].shape[1], i) for i in range(3)]
    batch = sampler.apply_dataset(ArrayDataset.from_numpy(np.stack(same)))
    for got, x, idx in zip(np.asarray(batch.numpy()), same, picks):
        np.testing.assert_array_equal(got, x[:, idx])
    # another seed, another sample; another item, other columns
    assert not np.array_equal(ColumnSampler(20, seed=6).columns(500, 0),
                              sampler.columns(500, 0))
    assert not np.array_equal(sampler.columns(500, 1), sampler.columns(500, 0))
    assert np.array_equal(sampler.columns(500, 1), sampler.columns(500, 1))
    # an item narrower than the sample: widths differ, host dataset
    narrow = ColumnSampler(10 ** 6, seed=5).apply_dataset(ds)
    assert isinstance(narrow, HostDataset)
    assert [x.shape for x in narrow.collect()] == [x.shape for x in loose]


def small_gmm(d, k, seed=3):
    rng = np.random.default_rng(seed)
    return GaussianMixtureModel(
        rng.standard_normal((d, k)) * 20 + 30, rng.uniform(80, 300, (d, k)),
        np.full(k, 1.0 / k))


def test_projection_and_fisher_vector_over_padded_chunks(small_buckets):
    items = images()
    loose, ds = described(items, step=8, num_scales=3)
    basis = np.linalg.qr(np.random.default_rng(4).standard_normal(
        (128, 12)))[0].astype(np.float32)
    pca = BatchPCATransformer(basis)
    reduced = pca.apply_dataset(ds)
    assert isinstance(reduced, RaggedDataset)
    for got, x in zip(reduced.collect(), loose):
        np.testing.assert_allclose(got, basis.T @ x, rtol=1e-4, atol=1e-3)
    fisher = FisherVector(small_gmm(12, 6))
    before = counter("featurize.fv.images").value
    rows = fisher.apply_dataset(reduced)
    assert isinstance(rows, ArrayDataset) and len(rows) == len(items)
    assert counter("featurize.fv.images").value == before + len(items)
    for got, x in zip(np.asarray(rows.numpy()), loose):
        want = np.asarray(fisher.apply(jnp.asarray(basis.T @ x)))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("form", ["split", "pallas"])
def test_masked_columns_count_for_nothing_in_a_fisher_vector(form):
    moments = {"split": fv_moments_split, "pallas": functools.partial(
        fv_moments_pallas, interpret=True)}[form]
    rng = np.random.default_rng(6)
    gmm = small_gmm(10, 5)
    params = FisherVector(gmm).apply_params()
    widths = [300, 180, 0]
    X = np.zeros((3, 10, 320), np.float32)
    mask = np.zeros((3, 320), bool)
    for i, n in enumerate(widths):
        at = np.sort(rng.choice(320, n, replace=False))   # not a leading part
        X[i][:, at] = rng.standard_normal((10, n)) * 15 + 30
        mask[i, at] = True
    got = [np.asarray(fisher_vector_of_sums(
        moments(jnp.asarray(X[i]), *params, threshold=1e-4,
                mask=jnp.asarray(mask[i])), max(n, 1), *params))
        for i, n in enumerate(widths)]
    # the chunk's program, whichever form this platform gives it
    chunk = np.asarray(_fisher_vector_chunk(
        jnp.asarray(X), jnp.asarray(mask), *params, weight_threshold=1e-4))
    for i, n in enumerate(widths[:2]):
        want = np.asarray(_fisher_vector(
            jnp.asarray(X[i][:, mask[i]]), *params, 1e-4))
        np.testing.assert_allclose(got[i], want, rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(chunk[i], want, rtol=2e-3, atol=2e-5)
    assert np.isfinite(got[2]).all()                     # an empty slot
    assert np.isfinite(chunk[2]).all()


# -- the mixture, fitted on the device ------------------------------------------------

def two_blobs(n=3000, seed=7):
    rng = np.random.default_rng(seed)
    part = rng.random(n) < 0.4
    return np.where(part[:, None], rng.standard_normal((n, 3)) * 0.5 + 4,
                    rng.standard_normal((n, 3)) - 2).astype(np.float32)


def test_em_as_one_program_is_the_loop_of_single_steps():
    X = jnp.asarray(two_blobs())
    est = GaussianMixtureModelEstimator(4, max_iterations=30, seed=11)
    model = est.fit_matrix(X)
    assert 1 <= model.iterations <= 30
    assert model.updates in (model.iterations, model.iterations - 1)
    # the loop the program replaced: one step a call, the host deciding
    means, variances, weights = (jnp.asarray(p) for p in model.initial)
    XSq = X * X
    floor = jnp.maximum(1e-2 * (XSq.mean(0) - X.mean(0) ** 2), 1e-9)
    variances = jnp.maximum(variances, floor)
    prev, steps = None, 0
    for _ in range(30):
        *new, cost, unbalanced = gmm_mod._em_iter(
            X, XSq, means, variances, weights, floor, 1e-4, 40.0)
        steps += 1
        if prev is not None and float(cost) - prev < 1e-4 * abs(prev):
            break
        if bool(unbalanced):
            break
        means, variances, weights = new
        prev = float(cost)
    assert steps == model.iterations
    np.testing.assert_allclose(model.means.T, means, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(model.variances.T, variances, rtol=1e-5)
    np.testing.assert_allclose(model.weights, weights, rtol=1e-5)


def test_seeding_follows_the_seed_and_picks_rows_of_the_sample():
    X = jnp.asarray(two_blobs(500))
    a = np.asarray(gmm_mod._kmeans_pp_centres(X, jax.random.PRNGKey(1), k=8))
    b = np.asarray(gmm_mod._kmeans_pp_centres(X, jax.random.PRNGKey(1), k=8))
    c = np.asarray(gmm_mod._kmeans_pp_centres(X, jax.random.PRNGKey(2), k=8))
    rows = {tuple(r) for r in np.asarray(X)}
    assert all(tuple(r) in rows for r in a)
    assert len({tuple(r) for r in a}) == 8               # no centre twice
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # far-apart blobs: both are seeded
    assert (a[:, 0] > 1).any() and (a[:, 0] < 1).any()


def test_a_gmm_fit_counts_itself_and_its_iterations():
    fits, its = (counter("featurize.gmm.fits").value,
                 counter("featurize.gmm.iterations").value)
    model = GaussianMixtureModelEstimator(2, seed=1).fit(
        ArrayDataset.from_numpy(two_blobs(800)))
    assert counter("featurize.gmm.fits").value == fits + 1
    assert counter("featurize.gmm.iterations").value == its + model.iterations


# -- the whole app ---------------------------------------------------------------------

def voc_items(n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for i, x in enumerate(images(n, seed)):
        # a class is a stripe period: something for SIFT to tell apart
        cls = int(rng.integers(0, 3))
        stripes = 60 * np.cos(np.arange(x.shape[1]) * np.pi / (2 + 3 * cls))
        x = np.clip(x * 0.3 + 100 + stripes[None, :, None], 0, 255)
        out.append(MultiLabeledImage(x.astype(np.uint8), [cls], f"im{i}.jpg"))
    return HostDataset(out)


def run_app(monkeypatch, loose, seed=9):
    from keystone_tpu.nodes.images import multilabel
    from keystone_tpu.pipelines.images.voc import voc_sift_fisher as app
    from keystone_tpu.workflow.env import PipelineEnv

    if loose:   # images handed over one by one, as before this dataset
        monkeypatch.setattr(
            multilabel.MultiLabeledImageExtractor, "apply_dataset",
            lambda self, ds: HostDataset([it.image for it in ds.collect()]))
    PipelineEnv.get_or_create().clear_state()
    cfg = app.SIFTFisherConfig(
        lam=0.5, desc_dim=8, vocab_size=3, num_pca_samples=20 * 30,
        num_gmm_samples=20 * 30, block_size=16, seed=seed)
    _, ap = app.run(cfg, voc_items(20, 1), voc_items(12, 2),
                    sift_kwargs=dict(step=8, num_scales=3))
    return ap


def test_the_app_over_padded_chunks_ranks_like_the_app_over_loose_images(
        small_buckets, monkeypatch):
    sift_before = counter("featurize.sift.images").value
    fits = (counter("featurize.pca.fits").value,
            counter("featurize.gmm.fits").value)
    chunks = run_app(monkeypatch, loose=False)
    # PCA's sample and the cache's pass over 20 training images (all
    # held: 8 GiB nominal), one pass over 12 test images
    assert counter("featurize.sift.images").value == sift_before + 52
    assert (counter("featurize.pca.fits").value,
            counter("featurize.gmm.fits").value) == (fits[0] + 1, fits[1] + 1)
    loose = run_app(monkeypatch, loose=True)
    np.testing.assert_allclose(chunks, loose, atol=1e-3)
    assert chunks[:3].mean() > 0.5                      # it learned the stripes


def test_another_seed_is_another_fit_and_compiles_nothing(
        small_buckets, monkeypatch):
    from keystone_tpu.observability.compilelog import compile_observatory
    from keystone_tpu.pipelines.images.voc import voc_sift_fisher as app

    first = run_app(monkeypatch, loose=False, seed=9)
    obs = compile_observatory()
    before = obs.count_total()
    again = run_app(monkeypatch, loose=False, seed=9)
    run_app(monkeypatch, loose=False, seed=10)
    assert obs.count_total() == before
    np.testing.assert_array_equal(first, again)

    def samples(seed):
        cfg = app.SIFTFisherConfig(
            desc_dim=8, vocab_size=3, num_pca_samples=600,
            num_gmm_samples=600, block_size=16, seed=seed)
        parts = app.build(cfg, voc_items(20, 1),
                          sift_kwargs=dict(step=8, num_scales=3))
        return (np.asarray(parts.pca_sample.get().numpy()),
                np.asarray(parts.gmm_sample.get().numpy()))

    nine, nine_again, ten = samples(9), samples(9), samples(10)
    for a, b, c in zip(nine, nine_again, ten):
        np.testing.assert_array_equal(a, b)
        assert a.shape == c.shape and not np.array_equal(a, c)
