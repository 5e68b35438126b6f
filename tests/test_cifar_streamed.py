"""RandomPatchCifar over a gather of filter blocks (ISSUE 30): the
convolution as the block maker of the streamed block solve, the scaler
carried into the sweep, the narrower last block, several blocks a call,
and the optimizer's rule that chooses all of it from shapes. Small
sizes, CPU: numbers and control flow, no device metric.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import load_module
from keystone_tpu.analysis import resources
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.nodes.images.core import FusedConvRectifyPool
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
    StreamedBlockLinearMapper,
    _block_maker,
    _equal_blocks,
)
from keystone_tpu.nodes.stats import CosineRandomFeatures, StandardScaler
from keystone_tpu.nodes.util import MaxClassifier, VectorCombiner
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.observability.timeline import flight_recorder
from keystone_tpu.ops import linalg, pallas_kernels
from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.parallel.mesh import make_mesh, mesh_scope
from keystone_tpu.pipelines.images.cifar import random_patch_cifar as app
from keystone_tpu.workflow.common import Cacher
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.optimizable import StreamedGatherFit
from keystone_tpu.workflow.pipeline import Pipeline
from keystone_tpu.workflow.transformer import Transformer

ROWS, TEST_ROWS = 160, 48
#: 29 filters in solver blocks of 64 columns = 8 filters: three whole
#: branches and a fourth of 5 filters, 40 columns
FILTERS, BLOCK = 29, 64


def counter(name):
    return MetricsRegistry.get_or_create().counter(name).value


@pytest.fixture
def images():
    made = load_module("datagen", "cifar_images").make_images(
        ROWS, TEST_ROWS, 7)
    return [(px.astype(np.float32), y) for px, y in made]


def datasets(images):
    return [LabeledData(data=ArrayDataset.from_numpy(px),
                        labels=ArrayDataset.from_numpy(y))
            for px, y in images]


def stated_memory(monkeypatch, nbytes):
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: nbytes)


def fit(images, monkeypatch, memory, filters=FILTERS, lam=10.0):
    """The app's public ``run()`` at a small block; returns (pipeline,
    the fitted block model, train error, test error)."""
    stated_memory(monkeypatch, memory)
    PipelineEnv.get_or_create().clear_state()
    pipeline, train_eval, test_eval = app.run(
        app.RandomCifarConfig(num_filters=filters, lam=lam, seed=3,
                              block_size=BLOCK),
        *datasets(images))
    ops = list(pipeline.fit().to_pipeline().graph.operators.values())
    (model,) = [op for op in ops if isinstance(op, BlockLinearMapper)]
    return pipeline, model, ops, train_eval.total_error, test_eval.total_error


def node(filters, seed=0, means=True):
    rng = np.random.RandomState(seed)

    class Whitener:
        pass

    Whitener.means = rng.randn(108).astype(np.float32) / 10
    return FusedConvRectifyPool(
        rng.randn(filters, 108).astype(np.float32) / 10, 32, 6, 3, 13, 14,
        0.25, whitener=Whitener if means else None)


# -- the app: one graph, two forms ---------------------------------------------

def test_the_streamed_fit_equals_the_materialised_fit_of_the_same_graph(
        images, monkeypatch):
    before = {k: counter(k) for k in (
        "solve.stream.fits", "solve.materialised.fits",
        "solve.stream.blocks_generated")}
    _, whole, ops, train_a, test_a = fit(images, monkeypatch, 1e12)
    assert counter("solve.materialised.fits") == before[
        "solve.materialised.fits"] + 1
    assert sum(isinstance(op, FusedConvRectifyPool) for op in ops) == 4
    pipeline, model, ops, train_b, test_b = fit(images, monkeypatch, 1000.0)
    assert counter("solve.stream.fits") == before["solve.stream.fits"] + 1
    # 4 blocks: one epoch, which the factor sweep is since ISSUE 34 (it
    # read ``4 * (1 + 1) + 4`` until then), and the test rows' apply. The
    # training rows' apply makes no block since ISSUE 31 (``+ 4`` more
    # until then): run() evaluates them in the graph that fits, and the
    # sweep's own predictions answer
    assert counter("solve.stream.blocks_generated") == before[
        "solve.stream.blocks_generated"] + 4 * 1 + 4
    assert counter("executor.fit_outputs_reused") == 1
    # the streamed graph has no branch, gather, combiner, cache or scaler
    assert [type(op) for op in ops] == [StreamedBlockLinearMapper,
                                        MaxClassifier]
    assert isinstance(model, StreamedBlockLinearMapper)
    # the same columns in the same order: 29 filters x 8
    assert np.asarray(model.weights).shape == np.asarray(
        whole.weights).shape == (FILTERS * 8, 10)
    gap = np.abs(np.asarray(model.weights) - np.asarray(whole.weights)).max()
    assert gap < 1e-4 * np.abs(np.asarray(whole.weights)).max()
    assert (train_a, test_a) == (train_b, test_b)
    test = datasets(images)[1]
    assert np.array_equal(pipeline(test.data).get().numpy(),
                          pipeline.fit().apply(test.data).get().numpy())


def test_the_padded_last_block_gets_weight_zero_outside_its_columns(
        images, monkeypatch):
    _, model, _, _, _ = fit(images, monkeypatch, 1000.0)
    assert model.Ws.shape == (4, BLOCK, 10)
    padding = np.setdiff1d(np.arange(4 * BLOCK), model.columns)
    assert len(padding) == 4 * BLOCK - FILTERS * 8
    flat = np.asarray(model.Ws).reshape(-1, 10)
    assert np.all(flat[padding] == 0.0)
    assert np.all(np.asarray(model.inv_stds).reshape(-1)[padding] == 1.0)
    assert np.all(np.asarray(model.block_means).reshape(-1)[padding] == 0.0)
    # the real columns of the last block sit at (pool, half, filter < 5)
    last = model.columns[model.columns >= 3 * BLOCK] - 3 * BLOCK
    assert last.tolist() == [g * 8 + k for g in range(8) for k in range(5)]
    assert bool(np.all(np.asarray(model.health[0])))


def test_at_most_one_block_of_filters_the_graph_is_one_node(
        images, monkeypatch):
    _, _, ops, _, _ = fit(images, monkeypatch, 1000.0, filters=8)
    assert sum(isinstance(op, FusedConvRectifyPool) for op in ops) == 1
    assert not any(isinstance(op, (VectorCombiner, StreamedBlockLinearMapper))
                   for op in ops)
    assert app.filters_a_block(app.RandomCifarConfig(block_size=BLOCK)) == 8
    assert app.filters_a_block(app.RandomCifarConfig()) == 512


def test_a_second_fit_is_answered_by_the_state_table_in_either_form(
        images, monkeypatch):
    pipeline, _, _, _, _ = fit(images, monkeypatch, 1000.0)
    fits = counter("solve.stream.fits") + counter("solve.materialised.fits")
    train = datasets(images)[0]
    first = pipeline(train.data).get().numpy()
    again = pipeline.fit().apply(train.data).get().numpy()
    assert np.array_equal(first, again)
    assert counter("solve.stream.fits") + counter(
        "solve.materialised.fits") == fits


def test_learn_filters_leaves_its_span(images, monkeypatch):
    fit(images, monkeypatch, 1000.0)
    spans = [s for s in flight_recorder().spans()
             if (s.cat, s.name) == ("featurize", "learn_filters")]
    assert spans and spans[-1].args["filters"] == FILTERS
    assert spans[-1].args["patches"] == app.WHITENER_SAMPLES


# -- the rule -------------------------------------------------------------------

class Doubler(Transformer):
    def apply(self, x):
        return 2.0 * x


def cosine_pipeline(between, rows, labels, lam=0.1):
    feats = [CosineRandomFeatures.create(12, 16, 0.3, seed=20 + i)
             for i in range(3)]
    featurizer = Pipeline.gather(feats) >> VectorCombiner()
    for stage in between:
        featurizer = (featurizer.and_then(stage, rows)
                      if isinstance(stage, StandardScaler)
                      else featurizer >> stage)
    return featurizer.and_then(
        BlockLeastSquaresEstimator(16, 2, lam), rows, labels)


def rows_and_labels(n=96, seed=2):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 12).astype(np.float32)
    y = np.where(np.arange(3)[None] == rng.randint(0, 3, n)[:, None],
                 1.0, -1.0).astype(np.float32)
    return x, y


@pytest.mark.parametrize("between,streams", [
    ((), True),
    ((Cacher("features"),), True),
    ((Cacher("features"), StandardScaler()), True),
    ((StandardScaler(), Cacher("scaled")), True),
    ((Doubler(),), False),                       # not column-separable
    ((StandardScaler(), StandardScaler()), False),   # one scaler rides
])
def test_the_rule_streams_through_caches_and_one_scaler_only(
        between, streams, monkeypatch):
    stated_memory(monkeypatch, 1000.0)
    x, y = rows_and_labels()
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    before = (counter("solve.stream.fits"), counter("solve.materialised.fits"))
    pipeline = cosine_pipeline(between, rows, labels)
    got = pipeline(rows).get().numpy()
    after = (counter("solve.stream.fits"), counter("solve.materialised.fits"))
    assert (after[0] - before[0], after[1] - before[1]) == (
        (1, 0) if streams else (0, 1))
    # whichever form: the answers of the materialised fit
    stated_memory(monkeypatch, 1e12)
    PipelineEnv.get_or_create().clear_state()
    want = cosine_pipeline(between, rows, labels)(rows).get().numpy()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_standardisation_inside_the_sweep_equals_scaler_then_solver():
    x, y = rows_and_labels(n=128)
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    feats = [CosineRandomFeatures.create(12, 16, 0.3, seed=20 + i)
             for i in range(3)]
    est = BlockLeastSquaresEstimator(16, 2, 0.5)
    model = est.fit_branches(rows, labels, feats, [StandardScaler()])
    gathered = np.concatenate(
        [np.cos(x @ f.W.T + f.b) for f in feats], axis=1)
    scaler = StandardScaler()._fit(ArrayDataset.from_numpy(gathered))
    scaled = scaler.apply_dataset(ArrayDataset.from_numpy(gathered))
    whole = est._fit(scaled, labels)
    np.testing.assert_allclose(np.asarray(model.feature_means), scaler.mean,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(1.0 / np.asarray(model.feature_inv_stds),
                               scaler.std, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(model.weights),
                               np.asarray(whole.weights), rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        model.apply_dataset(rows).numpy(),
        whole.apply_dataset(scaled).numpy(), rtol=2e-4, atol=2e-5)
    # a scaler that only centres adds nothing to what the sweep does
    plain = est.fit_branches(rows, labels, feats,
                             [StandardScaler(normalize_std_dev=False)])
    assert plain.inv_stds is None


def test_the_streamed_prefix_is_the_materialised_estimators(monkeypatch):
    """``StreamedGatherFit`` behind a cache and a scaler contributes the
    prefix of the estimator on the materialised graph."""
    from keystone_tpu.workflow.prefix import compute_prefix

    x, y = rows_and_labels()
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    pipeline = cosine_pipeline((Cacher("f"), StandardScaler()), rows, labels)
    from keystone_tpu.workflow.optimizer.rules import EquivalentNodeMergeRule
    from keystone_tpu.workflow.optimizer.stream_gather import (
        GatherStreamingRule)

    graph = pipeline(rows)._graph
    for _ in range(10):
        graph = EquivalentNodeMergeRule().apply(graph)
    stated_memory(monkeypatch, 1000.0)
    streamed = GatherStreamingRule().apply(graph)
    (fit_node,) = [n for n, op in streamed.operators.items()
                   if isinstance(op, StreamedGatherFit)]
    assert [e[0] for e in streamed.get_operator(fit_node).chain] == [
        "map", "fit"]
    assert compute_prefix(streamed, fit_node) == compute_prefix(
        graph, fit_node)
    # three branches, gather, combiner, cache, scaler and its application
    assert len(streamed.operators) == len(graph.operators) - 8


# -- the maker --------------------------------------------------------------------

def test_a_widened_branch_makes_its_own_columns_and_exact_zeros():
    wide, narrow = node(8, seed=1), node(5, seed=2)
    widened, real, width = narrow.widened_like(wide)
    assert widened.struct_key() == wide.struct_key() and width == 64
    img = np.random.RandomState(4).rand(32, 32, 3).astype(np.float32) * 255
    out = np.asarray(widened.apply(jnp.asarray(img)))
    np.testing.assert_array_equal(out[real], np.asarray(narrow.apply(img)))
    assert np.all(np.delete(out, real) == 0.0)
    assert wide.widened_like(narrow) is None            # narrower, not wider
    assert _equal_blocks([wide, node(8, seed=3)])[1] is None
    branches, columns = _equal_blocks([wide, node(8, seed=3), narrow])
    assert len(columns) == 2 * 64 + 40 and branches[2].filters.shape == (8, 108)
    assert _equal_blocks([narrow, wide, wide]) is None  # only the LAST may


@pytest.mark.parametrize("pallas", [False, True])
def test_the_block_maker_is_the_batch_of_apply(pallas, monkeypatch):
    """Either maker, one block or several a call, against ``apply`` an
    image; the Pallas kernel in interpret mode."""
    monkeypatch.setattr(pallas_kernels, "use_pallas", lambda: pallas)
    monkeypatch.setattr(pallas_kernels, "vmem_budget_bytes",
                        lambda: 96 << 20)     # a v5e's; the CPU has none
    real = pallas_kernels.fused_cifar_featurize_banks
    monkeypatch.setattr(
        pallas_kernels, "fused_cifar_featurize_banks",
        lambda *a, **k: real(*a, interpret=True, **k))
    from keystone_tpu.nodes.images import core

    monkeypatch.setattr(core, "FUSED_ROW_BATCH", 4)
    nodes = [node(8, seed=s) for s in (1, 2)]
    imgs = jnp.asarray(np.random.RandomState(5).rand(
        10, 32, 32, 3).astype(np.float32) * 255)
    want = [np.stack([np.asarray(n.apply(i)) for i in imgs]) for n in nodes]
    maker = _block_maker(nodes[0])
    which = "featurize.conv_block." + ("pallas" if pallas else "xla")
    before = counter(which)
    patches_in_vmem = counter("featurize.conv_patches.vmem")
    got = maker(nodes[0].apply_params(), imgs)
    assert counter(which) == before + 1
    # the kernel builds its patches itself; the composed ops have none
    assert counter("featurize.conv_patches.vmem") == patches_in_vmem + pallas
    from keystone_tpu.observability import names
    assert "featurize.conv_patches.vmem" in names.METRIC_NAMES
    np.testing.assert_allclose(np.asarray(got), want[0], rtol=2e-3, atol=2e-3)
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *[n.apply_params() for n in nodes])
    many = maker.many(stacked, imgs)
    # the composed ops take the rows in whole batches of 4; the kernel
    # takes them as they are
    assert many.shape == (2, 10 if pallas else 12, 64)
    for j in range(2):
        np.testing.assert_allclose(np.asarray(many[j][:10]), want[j],
                                   rtol=2e-3, atol=2e-3)
    # as many blocks a call as keep under the call's bytes, a divisor
    # of their number; from the shapes alone
    monkeypatch.setattr(core, "BANKS_A_CALL_BYTES", int(3.5 * 4 * 10 * 64))
    six = (jnp.zeros((6, 8, 108)), jnp.zeros((6, 108)))
    assert maker.blocks_a_call(10, six) == 3
    assert maker.blocks_a_call(40, six) == 1


def test_several_blocks_a_call_give_the_sweeps_of_one_block_a_call():
    """A maker with ``many`` (two blocks a call) against the same maker
    without: the three programs give the same numbers."""
    x, y = rows_and_labels(n=80)
    feats = [CosineRandomFeatures.create(12, 16, 0.3, seed=30 + i)
             for i in range(4)]
    params = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *[f.apply_params() for f in feats])

    def one(p, rows):
        return jnp.cos(rows @ p[0].T + p[1])

    calls = []

    class Grouped:
        def __call__(self, p, rows):
            return one(p, rows)

        def many(self, p, rows):
            calls.append(p[0].shape[0])
            made = jnp.stack([one((p[0][j], p[1][j]), rows)
                              for j in range(p[0].shape[0])])
            return jnp.pad(made, ((0, 0), (0, 5), (0, 0)))   # padding rows

        def blocks_a_call(self, rows, p):
            return 2

    rows, Y = jnp.asarray(x), jnp.asarray(y - y.mean(0))
    mask = jnp.ones(len(x), bool)
    n, lam = jnp.float32(len(x)), jnp.float32(0.3)
    out = {}
    for name, maker in (("one", one), ("two", Grouped())):
        (means, Ls, oks, _, inv), Ws, pred = linalg.bcd_stream_factor(
            rows, params, maker, Y, mask, n, lam, scale_eps=1e-12)
        Ws, pred = linalg.bcd_stream_epochs(
            rows, params, maker, Y, mask, means, Ls, Ws, pred, num_passes=1,
            inv_stds=inv)
        scores = linalg.block_stream_apply(
            rows, params, maker, means, Ws, jnp.zeros(3), inv_stds=inv)
        # the sweep's carry is the apply's answer: no block made for it
        np.testing.assert_allclose(pred, scores, rtol=1e-5, atol=2e-6)
        out[name] = [np.asarray(a) for a in (means, Ls, inv, Ws, scores)]
        assert bool(np.all(np.asarray(oks)))
    assert calls == [2] * len(calls) and len(calls) >= 3
    for a, b in zip(out["one"], out["two"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_check_command_plans_the_streamed_form_at_the_documented_flags():
    from keystone_tpu.pipelines import resolve_check_app

    # the documented flags are one chip's: on a mesh of several the
    # rule reckons a data shard of the gather against one device
    target = resolve_check_app("cifar.random_patch_10k")()
    with mesh_scope(make_mesh(jax.devices()[:1])):
        report = target.pipeline.check(target.input_spec, name=target.name)
    assert report.ok
    labels = [op.label() for op in report.analysis.graph.operators.values()]
    assert "Streamed[BlockLeastSquaresEstimator]" in labels
    assert not any("FusedConvRectifyPool" in label for label in labels)
    # factors and a few blocks, not rows x 80,000 floats
    assert 2 * 2 ** 30 < report.plan.fit_peak_nbytes < 6 * 2 ** 30
