"""Column samples drawn where a pass is made anyway (PR 49): what the
nodes that ``workflow/optimizer/column_samples.py`` puts into a graph
compute. Sibling samplers that share a pass give each the sample it
draws alone, bit for bit, from ONE making of the dataset; a sampler
moved in front of a projection gives the projection of its sample; the
VOC app under ``DefaultOptimizer`` draws what it draws under
``NoOpOptimizer``, describes a training image twice where the graph as
written describes it three times, runs as many nodes, and leaves its
fits in the state table under the names the raw graph asks for. The
rules' rewrites themselves: ``tests/test_optimizer_rules.py``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.nodes.learning.pca import BatchPCATransformer
from keystone_tpu.nodes.stats.sampling import (
    ColumnSampleAhead, ColumnSampler, SharedColumnSampler)
from keystone_tpu.observability import PipelineTrace
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.parallel import ragged
from keystone_tpu.parallel.dataset import ArrayDataset, HostDataset
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.expression import (
    DatasetExpression, DatumExpression)
from keystone_tpu.workflow.optimizer.default import (
    DefaultOptimizer, NoOpOptimizer)
from keystone_tpu.workflow.optimizer.rule import Optimizer

from test_ragged import described, images, small_buckets, voc_items  # noqa: F401

SIFT = dict(step=8, num_scales=3)


def counter(name):
    return MetricsRegistry.get_or_create().counter(name).value


def rows(ds):
    """A dataset's items as numpy arrays, whatever its kind."""
    if isinstance(ds, ArrayDataset):
        return list(np.asarray(ds.numpy()))
    return [np.asarray(x) for x in ds.collect()]


def shared_draw(samplers, ds):
    """What the shared node and the nodes it serves give on ``ds``, in
    the samplers' order, through the operators' own ``execute``."""
    first = SharedColumnSampler(samplers, len(samplers)).execute(
        [DatasetExpression(ds, eager=True)])
    return [first.get()] + [
        ColumnSampleAhead(s, (), i).execute([first]).get()
        for i, s in enumerate(samplers) if i]


# -- siblings in one pass ------------------------------------------------------

SIBLINGS = (ColumnSampler(20, seed=5), ColumnSampler(12, seed=6),
            ColumnSampler(20, seed=7))


@pytest.mark.parametrize("kind", ["ragged", "array", "host"])
def test_siblings_of_other_seeds_and_widths_get_the_samples_they_draw_alone(
        kind, small_buckets):
    loose, ds = described(images(), **SIFT)
    if kind == "array":     # items of one shape on the device
        ds = ArrayDataset.from_numpy(np.stack([loose[0], loose[4], loose[7]]))
    elif kind == "host":
        ds = HostDataset(loose)
    alone = [rows(s.apply_dataset(ds)) for s in SIBLINGS]
    passes, served = (counter("featurize.sift.images"),
                      counter("featurize.sample_pass.siblings"))
    together = shared_draw(SIBLINGS, ds)
    if kind == "ragged":    # ONE making of the descriptors for the three
        assert counter("featurize.sift.images") == passes + len(loose)
        assert all(isinstance(t, ArrayDataset) for t in together)
    assert counter("featurize.sample_pass.siblings") == served + 3
    for want, got in zip(alone, together):
        assert len(want) == len(got)
        for a, b in zip(want, rows(got)):
            np.testing.assert_array_equal(a, b)     # bit for bit
    # and they ARE different samples
    assert alone[0][0].shape != alone[1][0].shape
    assert not np.array_equal(alone[0][0], alone[2][0])
    # a sibling takes its sample away: nothing else holds it afterwards
    first = SharedColumnSampler(SIBLINGS, 3).execute(
        [DatasetExpression(ds, eager=True)])
    first.get()
    assert sorted(first.drawn) == [0, 1, 2]
    ColumnSampleAhead(SIBLINGS[1], (), 1).execute([first]).get()
    assert sorted(first.drawn) == [0, 2]


def test_an_item_too_narrow_for_the_widest_sibling_sends_all_down_one_path(
        small_buckets):
    """``_TooFewColumns``: samples of different widths cannot be one
    array, so the descriptors are collected ONCE and every sibling
    draws item by item, as each does alone."""
    loose, ds = described(images(), **SIFT)
    narrowest = min(x.shape[1] for x in loose)
    siblings = (ColumnSampler(10, seed=5),
                ColumnSampler(narrowest + 1, seed=6))
    before = counter("featurize.sift.images")
    alone = [s.apply_dataset(ds) for s in siblings]
    assert isinstance(alone[0], ArrayDataset)       # alone it fits...
    assert isinstance(alone[1], HostDataset)        # ...the wide one not
    # the wide one alone: the chunks up to the narrow item, then all
    lone = counter("featurize.sift.images") - before - len(loose)
    assert len(loose) < lone < 2 * len(loose)
    passes = counter("featurize.sift.images")
    together = shared_draw(siblings, ds)
    assert counter("featurize.sift.images") == passes + lone
    assert all(isinstance(t, HostDataset) for t in together)
    for want, got in zip(alone, together):
        for a, b in zip(rows(want), rows(got)):
            np.testing.assert_allclose(a, b, atol=2e-3)
    assert sorted({x.shape[1] for x in rows(together[1])}) == sorted(
        {min(narrowest + 1, x.shape[1]) for x in loose})


# -- the projection of a sample is the sample of the projection -----------------------

def test_a_sample_drawn_ahead_of_a_projection_is_the_projections_sample(
        small_buckets):
    """Dataset and datum. The tolerance: the projection of a sample is a
    product over other shapes than the projection of a chunk (``[n, 128,
    columns]`` against ``[b, 128, L]``), which the backend may tile, and
    so sum, in another order: rounding of a 128-deep float32 sum at
    HIGHEST, 1e-6 relative to the largest entry."""
    loose, ds = described(images(), **SIFT)
    rng = np.random.default_rng(0)
    project = BatchPCATransformer(rng.standard_normal((128, 8)))
    sampler = ColumnSampler(20, seed=5)
    behind = rows(sampler.apply_dataset(project.apply_dataset(ds)))
    ahead = ColumnSampleAhead(sampler, (project,)).execute(
        [DatasetExpression(ds, eager=True)])
    for want, got in zip(behind, rows(ahead.get())):
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-6 * np.abs(want).max())
    x = jnp.asarray(loose[0])
    one = ColumnSampleAhead(sampler, (project,)).execute(
        [DatumExpression(x, eager=True)])
    assert isinstance(one, DatumExpression)
    want = np.asarray(sampler.apply(project.apply(x)))
    np.testing.assert_allclose(np.asarray(one.get()), want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


# -- the VOC app ---------------------------------------------------------------------

class AsWritten(Optimizer):
    """``DefaultOptimizer`` without this PR's batch: the parent's graph."""

    @property
    def batches(self):
        return [b for b in DefaultOptimizer().batches
                if b.name != "column samples"]


def voc_parts(optimizer, seed=9):
    from keystone_tpu.pipelines.images.voc import voc_sift_fisher as app

    PipelineEnv.get_or_create().set_optimizer(optimizer)
    PipelineEnv.get_or_create().clear_state()
    cfg = app.SIFTFisherConfig(
        lam=0.5, desc_dim=8, vocab_size=3, num_pca_samples=20 * 30,
        num_gmm_samples=20 * 30, block_size=16, seed=seed)
    return app, app.build(cfg, voc_items(20, 1), sift_kwargs=SIFT)


def test_default_optimizer_draws_what_no_optimizer_draws(small_buckets):
    """SIFT -> PCA -> GMM's sample, small. The PCA's sample is the same
    draw from the same descriptors: bit-equal. The GMM's is the
    projection of a sample where it was the sample of a projection: the
    same columns through the same 128-deep products at HIGHEST, summed
    in another tiling at most, 1e-6 relative to the largest entry; and
    the PCA behind it was fitted by the estimator the optimizer chose
    (a local SVD) where no optimizer leaves the distributed default
    (TSQR), so both samples are compared under the SAME fitted basis."""
    _, parts = voc_parts(NoOpOptimizer())
    pca_plain = np.asarray(parts.pca_sample.get().numpy())
    raw_plain = np.asarray(
        (parts.sift_extractor >> ColumnSampler(30, seed=10))(
            parts.training_data).get().numpy())
    _, parts = voc_parts(DefaultOptimizer())
    passes = counter("featurize.sift.images")
    gmm = np.asarray(parts.gmm_sample.get().numpy())
    assert counter("featurize.sift.images") == passes + 20   # ONE pass
    pca = np.asarray(parts.pca_sample.get().numpy())
    np.testing.assert_array_equal(pca, pca_plain)
    # the GMM's sample against the fitted basis applied to the raw draw
    fitted = [e.get() for e in PipelineEnv.get_or_create().state.values()
              if isinstance(e.get(), BatchPCATransformer)]
    assert len(fitted) == 1
    want = np.einsum("dk,ndc->nkc", fitted[0].pca_mat.astype(np.float64),
                     raw_plain.astype(np.float64))
    np.testing.assert_allclose(gmm, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("optimizer,passes", [
    (DefaultOptimizer, 2), (AsWritten, 3)], ids=["default", "as_written"])
def test_with_a_cache_that_holds_nothing_a_training_image_is_described_twice(
        optimizer, passes, small_buckets, monkeypatch):
    """The cache's budget below nothing: every reader of the ``Cacher``
    behind the projection makes its chunks again. As written the PCA's
    sample, the GMM's sample and the encodings are a pass each; with
    both samples drawn in one pass, two. A test image once."""
    monkeypatch.setattr(ragged, "CACHE_SHARE", -1.0)
    app, parts = voc_parts(optimizer())
    before = {k: counter(k) for k in (
        "featurize.sift.images", "featurize.pca.fits", "featurize.gmm.fits",
        "featurize.fv.images", "executor.prefix_hits")}
    test_data, _ = app.images_and_labels(voc_items(12, 2))
    parts.predictor(test_data).get()
    rose = {k: counter(k) - v for k, v in before.items()}
    assert rose == {"featurize.sift.images": passes * 20 + 12,
                    "featurize.pca.fits": 1, "featurize.gmm.fits": 1,
                    "featurize.fv.images": 32, "executor.prefix_hits": 0}


def test_the_rewritten_fit_runs_as_many_nodes_and_ranks_the_same(
        small_buckets):
    """``executor.nodes_executed`` a fit is held to a pair by the
    benchmark and to the parent's count by one of its tests
    (``test_bench_rehearsal_voc_refit.py``: twice 28): the rules add no
    node, and both show in the rule log a ``PipelineTrace`` keeps. And
    the APs are the parent's graph's."""
    from keystone_tpu.evaluation.mean_average_precision import (
        evaluate_mean_average_precision)
    from keystone_tpu.loaders.voc import NUM_CLASSES

    out = {}
    for name, optimizer in (("written", AsWritten()),
                            ("default", DefaultOptimizer())):
        app, parts = voc_parts(optimizer)
        test_data, actuals = app.images_and_labels(voc_items(12, 2))
        nodes = counter("executor.nodes_executed")
        with PipelineTrace("fit") as trace:     # the optimizer's rule log
            scores = parts.predictor(test_data).get()
        logged = [(r["batch"], r["rule"], r["nodes_after"] - r["nodes_before"])
                  for r in trace.optimizer_rules
                  if r["batch"] == "column samples"]
        assert logged == ([] if name == "written" else [
            ("column samples", "ColumnSamplerMoveRule", 0),
            ("column samples", "SiblingSamplerRule", 0)])
        out[name] = (counter("executor.nodes_executed") - nodes,
                     evaluate_mean_average_precision(
                         actuals, scores, NUM_CLASSES))
    assert out["default"][0] == out["written"][0]
    np.testing.assert_allclose(out["default"][1], out["written"][1],
                               atol=1e-3)


def test_a_later_graph_finds_the_fits_under_the_names_it_asks_for(
        small_buckets):
    """State is saved under the OPTIMIZED graph's prefixes and looked up
    under the next RAW graph's. Two column PCAs one behind the other, as
    the app has its PCA and its GMM, the second fitted on a sample the
    rule draws ahead of the first's projection and its cache (estimators
    the node-level rule does not splice: a spliced one is saved under
    the name of what replaced it, and was before this PR): the same
    pipeline composed again fits nothing."""
    from keystone_tpu.nodes.images.core import GrayScaler, PixelScaler
    from keystone_tpu.nodes.images.extractors import SIFTExtractor
    from keystone_tpu.nodes.learning.pca import LocalColumnPCAEstimator
    from keystone_tpu.workflow.common import Cacher

    train = ragged.RaggedDataset.from_items(images())
    sift = PixelScaler() >> GrayScaler() >> Cacher() >> SIFTExtractor(**SIFT)

    def twice_reduced():
        first = (sift >> ColumnSampler(20, seed=5))(train)
        reduced = sift.and_then(
            LocalColumnPCAEstimator(8).with_data(first)) >> Cacher()
        second = (reduced >> ColumnSampler(20, seed=6))(train)
        return reduced.and_then(
            LocalColumnPCAEstimator(4).with_data(second))(train)

    fits, passes = (counter("featurize.pca.fits"),
                    counter("featurize.sift.images"))
    out = np.asarray(twice_reduced().get().collect()[0])
    assert counter("featurize.pca.fits") == fits + 2
    assert counter("featurize.sample_pass.siblings") == 2
    assert counter("featurize.sift.images") == passes + 20  # samples, cache
    again = np.asarray(twice_reduced().get().collect()[0])
    assert counter("featurize.pca.fits") == fits + 2
    assert counter("featurize.sift.images") == passes + 20
    assert counter("executor.prefix_hits") > 0
    np.testing.assert_array_equal(out, again)
