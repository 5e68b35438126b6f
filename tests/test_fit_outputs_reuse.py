"""A streamed fit answers for the rows it was fitted on (ISSUE 31): the
last sweep's carry handed on beside the model
(``EstimatorOperator.fit_transform_datasets``), and the executor's rule
that answers a delegating node with it when that node is fed the very
expression the fit consumed, and in no other case. Small sizes, CPU:
numbers, counts and object lifetimes, no device metric.
"""
import gc
import pickle
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import load_module
from benchmarks.reference import _block_ls
from keystone_tpu.analysis import resources
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.nodes.images.core import FusedConvRectifyPool
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    StreamedBlockLinearMapper,
    _stream_program,
)
from keystone_tpu.nodes.stats import CosineRandomFeatures, StandardScaler
from keystone_tpu.nodes.util import VectorCombiner
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.ops import linalg
from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.estimator import Estimator
from keystone_tpu.workflow.expression import (
    DatasetExpression,
    TransformerExpression,
)
from keystone_tpu.workflow.operators import DelegatingOperator
from keystone_tpu.workflow.pipeline import Pipeline
from keystone_tpu.workflow.transformer import Transformer

DIM, WIDTH, BLOCKS, CLASSES = 12, 16, 3, 3
#: float32 accumulation in another order, and no more
SCORES_GAP = 1e-5
MADE = "solve.stream.blocks_generated"
REUSED = "executor.fit_outputs_reused"


def counter(name):
    return MetricsRegistry.get_or_create().counter(name).value


def stream_memory(monkeypatch):
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: 1000.0)


def rows_and_labels(n=96, seed=2):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, DIM).astype(np.float32)
    y = np.where(np.arange(CLASSES)[None] == rng.randint(0, CLASSES, n)[:, None],
                 1.0, -1.0).astype(np.float32)
    return x, y


def cosines(seed=20):
    return [CosineRandomFeatures.create(DIM, WIDTH, 0.3, seed=seed + i)
            for i in range(BLOCKS)]


def conv_node(filters, seed):
    rng = np.random.RandomState(seed)

    class Whitener:
        means = rng.randn(108).astype(np.float32) / 10

    return FusedConvRectifyPool(
        rng.randn(filters, 108).astype(np.float32) / 10, 32, 6, 3, 13, 14,
        0.25, whitener=Whitener)


def scores_pipeline(rows, labels, epochs=2, scaler=False, lam=0.1):
    """Gathered cosine blocks, optionally standardised, into the block
    solver; the pipeline's output is the model's scores."""
    featurizer = Pipeline.gather(cosines()) >> VectorCombiner()
    if scaler:
        featurizer = featurizer.and_then(StandardScaler(), rows)
    return featurizer.and_then(
        BlockLeastSquaresEstimator(WIDTH, epochs, lam), rows, labels)


def fitted_model(pipeline):
    (model,) = [op for op in
                pipeline.fit().to_pipeline().graph.operators.values()
                if isinstance(op, StreamedBlockLinearMapper)]
    return model


# -- what the fit hands on ----------------------------------------------------

@pytest.mark.parametrize("n", [96, 93])        # 93: padded, masked rows
@pytest.mark.parametrize("scaler", [False, True])
@pytest.mark.parametrize("epochs", [1, 3])
def test_the_sweeps_scores_are_the_models_on_the_rows_it_was_fitted_on(
        mesh8, epochs, scaler, n):
    x, y = rows_and_labels(n)
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    assert rows.padded_n == 96
    made = counter(MADE)
    model, scores = BlockLeastSquaresEstimator(
        WIDTH, epochs, 0.1).fit_transform_branches(
        rows, labels, cosines(), [StandardScaler()] if scaler else [])
    # a block an epoch (the factor sweep is the first; ``1 + epochs``
    # until ISSUE 34): no block for the scores
    assert counter(MADE) == made + BLOCKS * epochs
    assert (model.inv_stds is not None) == scaler
    assert isinstance(scores, ArrayDataset)
    assert (scores.n, scores.padded_n, scores.mesh) == (n, 96, rows.mesh)
    want = model.apply_dataset(rows)
    assert counter(MADE) == made + BLOCKS * epochs + BLOCKS
    assert _block_ls.rel_gap(scores.numpy(), want.numpy()) < SCORES_GAP
    # padded rows are zero, as a dataset keeps them
    assert np.array_equal(np.asarray(scores.data)[n:],
                          np.asarray(want.data)[n:])
    assert np.all(np.asarray(scores.data)[n:] == 0.0)


def test_a_widened_last_block_answers_for_its_own_columns_only():
    """Two whole conv blocks and a narrower one widened with zero
    filters: the scores of the sweep are the model's, and the fit
    standardises inside the sweep."""
    imgs = np.random.RandomState(5).rand(40, 32, 32, 3).astype(
        np.float32) * 255
    y = rows_and_labels(40)[1]
    rows, labels = ArrayDataset.from_numpy(imgs), ArrayDataset.from_numpy(y)
    feats = [conv_node(8, 1), conv_node(8, 2), conv_node(5, 3)]
    model, scores = BlockLeastSquaresEstimator(
        64, 1, 10.0).fit_transform_branches(
        rows, labels, feats, [StandardScaler()])
    assert model.columns is not None and len(model.columns) == 2 * 64 + 40
    assert _block_ls.rel_gap(
        scores.numpy(), model.apply_dataset(rows).numpy()) < SCORES_GAP


# -- the executor's rule ------------------------------------------------------------

@pytest.mark.parametrize("scaler", [False, True])
@pytest.mark.parametrize("epochs", [1, 3])
def test_the_graph_that_fits_answers_its_training_rows_from_the_fit(
        monkeypatch, epochs, scaler):
    stream_memory(monkeypatch)
    x, y = rows_and_labels()
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    pipeline = scores_pipeline(rows, labels, epochs, scaler)
    made, nodes = counter(MADE), counter("executor.nodes_executed")
    got = pipeline(rows).get()
    assert counter("solve.stream.fits") == 1
    assert counter(REUSED) == 1
    assert counter(MADE) == made + BLOCKS * epochs
    # rows, labels, the fit and the delegating node, which still counts
    assert counter("executor.nodes_executed") == nodes + 4
    assert (got.n, got.mesh) == (rows.n, rows.mesh)
    model = fitted_model(pipeline)
    assert _block_ls.rel_gap(
        got.numpy(), model.apply_dataset(rows).numpy()) < SCORES_GAP


def test_anything_but_those_very_rows_is_applied(monkeypatch):
    """Test rows, another dataset of the same values, a datum, and a
    second graph whose fit the state table answered: each makes its
    blocks, none is counted as reused, and each is the model's own
    answer."""
    stream_memory(monkeypatch)
    x, y = rows_and_labels()
    tx = rows_and_labels(40, seed=9)[0]
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    pipeline = scores_pipeline(rows, labels)
    on_test = pipeline(ArrayDataset.from_numpy(tx)).get().numpy()  # fits here
    model = fitted_model(pipeline)
    assert counter("solve.stream.fits") == 1 and counter(REUSED) == 0

    def applied(bound):
        made = counter(MADE)
        out = bound.get()
        assert counter(MADE) == made + BLOCKS
        assert counter(REUSED) == 0 and counter("solve.stream.fits") == 1
        return out

    assert np.array_equal(on_test, model.apply_dataset(
        ArrayDataset.from_numpy(tx)).numpy())
    twin = ArrayDataset.from_numpy(x)      # the same values, another object
    assert np.array_equal(applied(pipeline(twin)).numpy(),
                          model.apply_dataset(twin).numpy())
    assert np.array_equal(np.asarray(applied(pipeline.apply_datum(x[3]))),
                          np.asarray(model.apply(x[3])))
    # the training rows themselves in a second graph: the state table
    # answers for the fit, the rows are a new expression
    hits = counter("executor.prefix_hits")
    again = applied(pipeline(rows)).numpy()
    assert counter("executor.prefix_hits") == hits + 1
    assert np.array_equal(again, model.apply_dataset(rows).numpy())


def test_a_fitted_pipeline_makes_its_blocks_on_the_training_rows(monkeypatch):
    """``pipeline.fit()`` has no estimator left, so nothing to reuse."""
    stream_memory(monkeypatch)
    x, y = rows_and_labels()
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    pipeline = scores_pipeline(rows, labels, epochs=1)
    fitted = pipeline.fit()
    made = counter(MADE)
    assert made == BLOCKS * 1 and counter(REUSED) == 0   # one epoch
    out = fitted.apply(rows).get().numpy()
    assert counter(MADE) == made + BLOCKS and counter(REUSED) == 0
    # and the graph that fitted gave the same answer from the sweep
    PipelineEnv.get_or_create().clear_state()
    assert _block_ls.rel_gap(
        scores_pipeline(rows, labels, epochs=1)(rows).get().numpy(),
        out) < SCORES_GAP
    assert counter(REUSED) == 1


def test_the_model_carries_neither_scores_nor_rows(monkeypatch):
    """Not into a pickle, and not in memory: with the model and the
    state table's fit alive, the training rows and the sweep's scores go
    when the graph that held them goes."""
    stream_memory(monkeypatch)
    x, y = rows_and_labels()
    rows, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    pipeline = scores_pipeline(rows, labels)
    bound = pipeline(rows)        # holds the executor and its expressions
    scores = bound.get()
    assert counter(REUSED) == 1
    model = fitted_model(pipeline)
    n, k = scores.data.shape
    for holder in (model, pickle.loads(pickle.dumps(model))):
        held = jax.tree_util.tree_leaves(
            {name: v for name, v in vars(holder).items()
             if name != "featurizers"})
        assert not any(isinstance(v, ArrayDataset) for v in held)
        assert not any(getattr(v, "shape", ())[:1] == (n,) for v in held)
    assert len(pickle.dumps(model)) < 4 * (
        BLOCKS * WIDTH * (DIM + CLASSES + 3)) + 4096
    state = PipelineEnv.get_or_create().state
    (saved,) = [e for e in state.values() if e.computed
                and isinstance(e.get(), StreamedBlockLinearMapper)]
    assert saved.get() is model and len(saved.fit_outputs) == 1
    gone = [weakref.ref(rows), weakref.ref(scores), weakref.ref(rows.data)]
    del rows, scores, pipeline, bound
    gc.collect()
    assert [ref() for ref in gone] == [None] * 3
    assert len(saved.fit_outputs) == 0 and saved.get() is model


# -- the protocol, on any estimator ----------------------------------------------

class Shift(Transformer):
    def __init__(self, by):
        self.by = by

    def apply(self, x):
        return x + self.by


class MeanShift(Estimator):
    """Fits ``x - mean``; with ``offers`` it hands on its output on the
    rows it was fitted on, marked (+100) so that a test can tell the two
    answers apart."""

    def __init__(self, offers):
        self.offers = offers

    def _fit(self, ds):
        return Shift(-np.asarray(ds.numpy()).mean(axis=0))

    def fit_transform_datasets(self, inputs):
        fitted = self.fit_datasets(inputs)
        if not self.offers:
            return fitted, None
        return fitted, inputs[0].map_batch(lambda a: a + fitted.by + 100.0)


@pytest.mark.parametrize("offers", [False, True])
def test_any_estimator_may_offer_its_outputs_and_the_default_offers_none(
        offers):
    x = rows_and_labels()[0]
    rows = ArrayDataset.from_numpy(x)
    pipeline = MeanShift(offers).with_data(rows)
    got = pipeline(rows).get().numpy()
    assert counter(REUSED) == float(offers)
    np.testing.assert_allclose(
        got, x - x.mean(0) + (100.0 if offers else 0.0), atol=1e-5)
    # other rows are applied, whatever the fit offered
    other = pipeline(ArrayDataset.from_numpy(x + 1.0)).get().numpy()
    np.testing.assert_allclose(other, x + 1.0 - x.mean(0), atol=1e-5)
    assert counter(REUSED) == float(offers)


def test_a_delegating_node_fed_two_datasets_is_applied():
    """The rule answers a node whose ONE data dependency is what the fit
    consumed; a transformer applied to more is applied."""

    class First(Transformer):
        def batch_transform(self, inputs):
            return inputs[0]

    x = rows_and_labels()[0]
    rows = DatasetExpression(ArrayDataset.from_numpy(x), eager=True)
    fit = TransformerExpression(First)
    fit.fit_outputs[rows] = held = ArrayDataset.from_numpy(x + 100.0)
    assert DelegatingOperator().execute([fit, rows]).get() is held
    assert counter(REUSED) == 1
    assert DelegatingOperator().execute([fit, rows, rows]).get() is rows.get()
    assert counter(REUSED) == 1


def test_nothing_selects_the_behaviour():
    """No constructor flag and no environment variable: the rule reads
    the graph and object identity (``tests/test_tree_consistency.py``
    holds the count of environment knobs for the whole tree)."""
    import inspect

    from keystone_tpu.nodes.learning import linear
    from keystone_tpu.workflow import expression, operators, optimizable

    def parameters(cls):
        return list(inspect.signature(cls).parameters)

    assert parameters(DelegatingOperator) == []
    assert parameters(BlockLeastSquaresEstimator) == [
        "block_size", "num_iter", "lam", "weight_dtype"]
    assert parameters(optimizable.StreamedGatherFit) == [
        "estimator", "combiner", "branches", "chain"]
    assert parameters(
        BlockLeastSquaresEstimator.fit_transform_branches) == [
        "self", "rows", "labels", "branches", "between"]
    for module in (expression, operators, optimizable, linear):
        assert "environ" not in inspect.getsource(module), module.__name__


# -- the apps' graphs ---------------------------------------------------------------

def executor_counts():
    return {k: counter(f"executor.{k}")
            for k in ("nodes_executed", "prefix_hits", "fit_outputs_reused")}


def rose_by(before):
    return {k: v - before[k] for k, v in executor_counts().items()}


def test_the_cifar_app_still_runs_13_nodes_and_meets_the_table_once(
        monkeypatch):
    from keystone_tpu.pipelines.images.cifar import random_patch_cifar as app

    stream_memory(monkeypatch)
    made = load_module("datagen", "cifar_images").make_images(160, 48, 7)
    train, test = [LabeledData(
        data=ArrayDataset.from_numpy(px.astype(np.float32)),
        labels=ArrayDataset.from_numpy(labels)) for px, labels in made]
    before, blocks = executor_counts(), counter(MADE)
    app.run(app.RandomCifarConfig(num_filters=29, lam=10.0, seed=3,
                                  block_size=64), train, test)
    # the delegating node of the training rows executes and counts; its
    # value is the fit's
    assert rose_by(before) == {"nodes_executed": 13, "prefix_hits": 1,
                               "fit_outputs_reused": 1}
    # one epoch, which the factor sweep is, and the test rows' apply
    assert counter(MADE) == blocks + 4 * 1 + 4


def test_the_mnist_app_runs_12_nodes_and_its_fit_offers_nothing():
    from keystone_tpu.pipelines.images.mnist.random_fft import (
        MnistRandomFFTConfig, run)

    mnist_csv = load_module("datagen", "mnist_csv")
    made = mnist_csv.make_mnist(256, 64, 5, 10)
    train, test = [LabeledData(
        data=ArrayDataset.from_numpy(px.astype(np.float32)),
        labels=ArrayDataset.from_numpy(labels)) for px, labels in made]
    before = executor_counts()
    run(MnistRandomFFTConfig(num_ffts=4, block_size=512, lam=0.1, seed=0),
        train=train, test=test)
    assert counter("solve.materialised.fits") == 1
    assert rose_by(before) == {"nodes_executed": 12, "prefix_hits": 1,
                               "fit_outputs_reused": 0}


# -- the program ---------------------------------------------------------------------

def test_the_epoch_sweep_gains_an_output_and_no_work():
    """The lowered ``_stream_epochs`` (the passes after the first, from
    the factor sweep's weights and ``P``) against the sweep that returns
    its weights alone: as many loops and as many block makers (one
    cosine, traced once inside the scan), and the scores ``[n, k]``
    beside the weights."""
    feat = cosines()[0]
    n, more = 64, 2
    args = (jax.ShapeDtypeStruct((n, DIM), jnp.float32),
            (jax.ShapeDtypeStruct((BLOCKS, WIDTH, DIM), jnp.float32),
             jax.ShapeDtypeStruct((BLOCKS, WIDTH), jnp.float32)),
            jax.ShapeDtypeStruct((n, CLASSES), jnp.float32),
            jax.ShapeDtypeStruct((CLASSES,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            jax.ShapeDtypeStruct((BLOCKS, WIDTH), jnp.float32),
            jax.ShapeDtypeStruct((BLOCKS, WIDTH, WIDTH), jnp.float32),
            jax.ShapeDtypeStruct((BLOCKS, WIDTH, CLASSES), jnp.float32),
            jax.ShapeDtypeStruct((n, CLASSES), jnp.float32))
    lowered = _stream_program("epochs", feat, more).lower(*args)
    shapes = [tuple(o.shape) for o in jax.tree_util.tree_leaves(
        lowered.out_info)]
    assert shapes == [(BLOCKS, WIDTH, CLASSES), (n, CLASSES)]

    def weights_alone(rows, params, Y, y_mean, mask, means, Ls, Ws, pred):
        make = lambda p, r: jax.vmap(      # noqa: E731
            lambda x: feat.apply_with_params(p, x))(r)
        Yc = (Y - y_mean) * mask[:, None].astype(Y.dtype)
        return linalg.bcd_stream_epochs(
            rows, params, make, Yc, mask, means, Ls, Ws, pred,
            num_passes=more)[0]

    def ops(text):
        return {op: len(re.findall(rf"stablehlo\.{op}\b", text))
                for op in ("while", "cosine", "cholesky", "triangular_solve")}

    now, before = ops(lowered.as_text()), ops(
        jax.jit(weights_alone).lower(*args).as_text())
    assert now == before and now["cosine"] == 1 and now["while"] >= 2
