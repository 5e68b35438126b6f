"""The streamed block solve (ISSUE 26): block coordinate descent over
feature blocks that are made when the sweep reaches them, the blockwise
apply of what it fits, and the optimizer's choice between materialising
a gather and handing its branches to the solver. Small sizes, seeded
weights, CPU: numbers and control flow, no device metric.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import load_module
from benchmarks.reference import _block_ls
from keystone_tpu.analysis import resources
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.loaders.timit import TimitFeaturesData
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
    StreamedBlockLinearMapper,
    _stream_program,
    stack_branch_params,
)
from keystone_tpu.nodes.stats import CosineRandomFeatures
from keystone_tpu.nodes.util import (
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
    VectorCombiner,
)
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.observability.timeline import flight_recorder
from keystone_tpu.ops import linalg
from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.parallel.mesh import num_data_shards
from keystone_tpu.pipelines.speech.timit import TimitConfig, run
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.pipeline import Pipeline
from test_linear_solvers import _walk_eqns

DIM, WIDTH, BLOCKS, CLASSES = 24, 64, 5, 7
GAMMA = 0.25
#: Streamed core against ``bcd_core`` on the materialised blocks: the same
#: products in the same order, so they differ by how XLA fuses each
#: program and no more (read: 1.3e-7 .. 4.6e-7 over these cases).
CORE_GAP = 1e-6
#: Streamed fit and blockwise apply against the plain reference
#: (``benchmarks/reference/timit_50x4096.py``: float32 ``jax.numpy`` at
#: ``highest``, weights drawn from the seed). Sound readings at this size
#: lie under 5e-6 (weights) and 1e-6 (scores); the three-pass control
#: reads over 1e-4 and 2e-5. The limits lie between.
FIT_LIMITS = {"weights_gap": 3e-5, "test_scores_gap": 6e-6}


def counter(name):
    return MetricsRegistry.get_or_create().counter(name).value


def frames(n_train, n_test, seed=5):
    timit_frames = load_module("datagen", "timit_frames")
    return timit_frames.make_frames(n_train, n_test, seed, DIM, CLASSES)


def branches(seed=11, blocks=BLOCKS, width=WIDTH, dim=DIM):
    return [CosineRandomFeatures.create(dim, width, GAMMA, seed=seed + i)
            for i in range(blocks)]


def make_block(params, rows):
    return jnp.cos(rows @ params[0].T + params[1])


def stream_memory(monkeypatch, nbytes=1000.0):
    """The optimizer reckons against a device of ``nbytes``: every
    gather here is then too wide for it, by shape."""
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: nbytes)


# -- the core against bcd_core on the materialised blocks --------------------

def streamed_core(rows, params, maker, Y, mask, n, lam, epochs,
                  scale_eps=None):
    """The fused sweep and, past one epoch, the passes after it, each
    under ``jit`` as the fit runs them. Returns ``(factors, Ws, P)``."""
    factors, Ws, pred = jax.jit(
        lambda r, p, y, m: linalg.bcd_stream_factor(
            r, p, maker, y, m, n, lam, scale_eps=scale_eps)
    )(rows, params, Y, mask)
    if epochs > 1:
        Ws, pred = jax.jit(
            lambda r, p, y, m, mu, L, W, P, inv: linalg.bcd_stream_epochs(
                r, p, maker, y, m, mu, L, W, P, num_passes=epochs - 1,
                inv_stds=inv)
        )(rows, params, Y, mask, factors[0], factors[1], Ws, pred,
          factors[4] if scale_eps is not None else None)
    return factors, Ws, pred


class TwoACall:
    """``make_block`` with a batch form: two blocks a call, with rows of
    padding under them as an image featurizer's whole batches leave."""

    def __init__(self):
        self.calls = []

    def __call__(self, p, rows):
        return make_block(p, rows)

    def many(self, p, rows):
        self.calls.append(p[0].shape[0])
        made = jnp.stack([make_block((p[0][j], p[1][j]), rows)
                          for j in range(p[0].shape[0])])
        return jnp.pad(made, ((0, 0), (0, 3), (0, 0)))

    def blocks_a_call(self, rows, p):
        return 2


def core_case(n, pad, blocks=BLOCKS):
    (x, labels), _ = frames(n, 8)
    rows = jnp.asarray(np.concatenate([x, np.zeros((pad, DIM), np.float32)]))
    mask = jnp.asarray(np.r_[np.ones(n), np.zeros(pad)] > 0)
    Y = np.where(np.arange(CLASSES)[None] == labels[:, None], 1.0, -1.0)
    Y = np.concatenate([Y - Y.mean(0), np.zeros((pad, CLASSES))]).astype(
        np.float32)
    return rows, mask, jnp.asarray(Y), stack_branch_params(
        branches(blocks=blocks))


def materialised(rows, mask, params, n, scale_eps=None):
    """The centred (and standardised) blocks, whole, and ``1 / std``."""
    m = mask[:, None].astype(jnp.float32)
    blocks, invs = [], []
    for i in range(params[0].shape[0]):
        A = make_block((params[0][i], params[1][i]), rows) * m
        A = (A - A.sum(0) / n) * m
        if scale_eps is not None:
            invs.append(linalg._inv_std(A, n, scale_eps))
            A = A * invs[-1]
        blocks.append(A)
    return blocks, invs


@pytest.mark.parametrize("epochs", [1, 5])
@pytest.mark.parametrize("lam", [0.0, 0.1])
@pytest.mark.parametrize("pad", [0, 13])
def test_streamed_core_equals_bcd_core_on_materialised_blocks(epochs, lam, pad):
    n = 403
    rows, mask, Y, params = core_case(n, pad)
    nf, lam = jnp.float32(n), jnp.float32(lam)
    (means, Ls, oks, _), Ws, pred = streamed_core(
        rows, params, make_block, Y, mask, nf, lam, epochs)
    assert bool(np.all(np.asarray(oks)))

    blocks, _ = materialised(rows, mask, params, n)
    want = np.stack(jax.jit(lambda b, y: linalg.bcd_core(
        b, y, lam, num_passes=epochs))(blocks, Y))
    assert _block_ls.rel_gap(np.asarray(Ws), want) < CORE_GAP
    assert np.asarray(means).shape == (BLOCKS, WIDTH)
    assert np.asarray(Ls).shape == (BLOCKS, WIDTH, WIDTH)
    # the sweep's last carry is the final weights' centred scores on
    # these rows, after any number of epochs, and zero on padded rows
    scores = sum(np.asarray(A) @ W for A, W in zip(blocks, want))
    assert _block_ls.rel_gap(np.asarray(pred), scores) < 1e-5
    assert np.all(np.asarray(pred)[n:] == 0.0)


@pytest.mark.parametrize("epochs", [1, 3])
@pytest.mark.parametrize("case", ["padded", "scaled", "many",
                                  "many_scaled_padded"])
def test_the_fused_sweep_is_the_first_epoch_of_bcd_core(epochs, case):
    """The factor sweep takes the first epoch's step on each block while
    it is alive: with rows of padding, with the blocks standardised
    inside the sweep, and with a maker that makes two blocks a call
    (the inner scan carries ``P`` too), the fused sweep alone (one
    epoch) and the fused sweep plus two more passes are ``bcd_core`` on
    the materialised blocks."""
    n, pad = 403, 13 if "padded" in case else 0
    scale_eps = 1e-12 if "scaled" in case else None
    maker = TwoACall() if "many" in case else make_block
    rows, mask, Y, params = core_case(n, pad, blocks=4)
    nf, lam = jnp.float32(n), jnp.float32(0.05)
    factors, Ws, pred = streamed_core(
        rows, params, maker, Y, mask, nf, lam, epochs, scale_eps)
    assert bool(np.all(np.asarray(factors[2])))
    blocks, invs = materialised(rows, mask, params, n, scale_eps)
    want = np.stack(jax.jit(lambda b, y: linalg.bcd_core(
        b, y, lam, num_passes=epochs))(blocks, Y))
    assert _block_ls.rel_gap(np.asarray(Ws), want) < CORE_GAP
    scores = sum(np.asarray(A) @ W for A, W in zip(blocks, want))
    assert _block_ls.rel_gap(np.asarray(pred), scores) < 1e-5
    assert np.all(np.asarray(pred)[n:] == 0.0)
    assert len(factors) == (5 if scale_eps is not None else 4)
    if scale_eps is not None:
        assert _block_ls.rel_gap(np.asarray(factors[4]),
                                 np.stack(invs)) < CORE_GAP
    if "many" in case:
        # two groups of two a sweep, traced once a program
        assert maker.calls == [2] * (1 if epochs == 1 else 2)


def test_the_first_epochs_step_is_the_epoch_sweeps_step_from_zero():
    """What ``bcd_stream_factor`` leaves after its one epoch is what the
    epoch sweep's own step gives from zero weights and zero ``P`` with
    the same factors: the product it leaves out is a product with
    zero."""
    n = 403
    rows, mask, Y, params = core_case(n, 13, blocks=4)
    nf, lam = jnp.float32(n), jnp.float32(0.05)
    (means, Ls, _, _), Ws, pred = streamed_core(
        rows, params, make_block, Y, mask, nf, lam, 1)
    W0, P0 = jax.jit(lambda r, p, y, m, mu, L: linalg.bcd_stream_epochs(
        r, p, make_block, y, m, mu, L, jnp.zeros_like(Ws),
        jnp.zeros_like(y), num_passes=1))(rows, params, Y, mask, means, Ls)
    assert _block_ls.rel_gap(np.asarray(Ws), np.asarray(W0)) < CORE_GAP
    assert _block_ls.rel_gap(np.asarray(pred), np.asarray(P0)) < CORE_GAP


def degenerate_branches():
    """Two branches; the second makes every feature twice."""
    feats = branches(blocks=2)
    W = np.array(feats[1].W)
    W[WIDTH // 2:] = W[: WIDTH // 2]
    b = np.array(feats[1].b)
    b[WIDTH // 2:] = b[: WIDTH // 2]
    feats[1] = CosineRandomFeatures(W, b)
    return feats


def test_an_unhealthy_block_is_factored_again_with_a_raised_diagonal():
    """Duplicate columns and lambda 0: the first factor of that block has
    a collapsed pivot, ``oks`` says so, and the weights are finite."""
    (x, labels), _ = frames(256, 8)
    feats = degenerate_branches()
    Y = np.where(np.arange(CLASSES)[None] == labels[:, None], 1.0, -1.0
                 ).astype(np.float32)
    model = BlockLeastSquaresEstimator(WIDTH, 2, 0.0).fit_branches(
        ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(Y), feats)
    oks, ratios = (np.asarray(a) for a in model.health)
    assert oks.tolist() == [True, False]
    assert ratios[0] > 1e-3 and not ratios[1] > 1e-3   # collapsed, or NaN
    assert np.all(np.isfinite(np.asarray(model.Ws)))
    assert np.abs(np.asarray(model.Ws)).max() < 1e3
    # what a block's health says survives a pickle
    import pickle

    back = pickle.loads(pickle.dumps(model))
    assert np.asarray(back.health[0]).tolist() == [True, False]


def test_the_two_forms_recover_from_a_singular_block_each_in_its_own_way():
    """Pinned, not hidden: on a block whose Gram is singular at lambda 0
    the materialised form solves through ``clamped_eigh`` and the
    streamed form factors ``G + floor I`` (an ``eigh`` of 4,096 columns
    does not fit the streamed programs, ``ops/linalg.py``). Both are
    sound ridge-like answers, finite and close in prediction, but they
    are NOT the same model to the rounding that healthy blocks agree to
    (``CORE_GAP``): which form a fit took shows in such a model. Read:
    weights 6.7e-4 apart, training scores 2.4e-4."""
    (x, labels), _ = frames(256, 8)
    feats = degenerate_branches()
    Y = np.where(np.arange(CLASSES)[None] == labels[:, None], 1.0, -1.0
                 ).astype(np.float32)
    est = BlockLeastSquaresEstimator(WIDTH, 2, 0.0)
    rows, targets = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(Y)
    streamed = est.fit_branches(rows, targets, feats)
    gathered = ArrayDataset.from_numpy(np.concatenate(
        [np.asarray(make_block((f.W, f.b), jnp.asarray(x))) for f in feats],
        axis=1))
    whole = est.fit(gathered, targets)
    assert not isinstance(whole, StreamedBlockLinearMapper)
    assert np.all(np.isfinite(np.asarray(whole.weights)))
    weights_gap = _block_ls.rel_gap(np.asarray(streamed.weights),
                                    np.asarray(whole.weights))
    scores_gap = _block_ls.rel_gap(
        np.asarray(streamed.apply_dataset(rows).numpy()),
        np.asarray(whole.apply_dataset(gathered).numpy()))
    assert 100 * CORE_GAP < weights_gap < 5e-3
    assert scores_gap < 2e-3


# -- fit and apply against the plain reference --------------------------------

def reference_cfg(epochs, lam, blocks=BLOCKS):
    return {"num_cosines": blocks, "num_cosine_features": WIDTH,
            "input_dim": DIM, "num_classes": CLASSES, "num_epochs": epochs,
            "gamma": GAMMA, "rf_type": "gaussian", "lambda": lam}


def fit_through_the_app(train, test, epochs, lam, seed):
    data = TimitFeaturesData(
        train=LabeledData(ArrayDataset.from_numpy(train[0]),
                          ArrayDataset.from_numpy(train[1])),
        test=LabeledData(ArrayDataset.from_numpy(test[0]),
                         ArrayDataset.from_numpy(test[1])))
    cfg = TimitConfig(num_cosines=BLOCKS, gamma=GAMMA, lam=lam,
                      num_epochs=epochs, seed=seed,
                      num_cosine_features=WIDTH)
    pipeline, test_eval = run(cfg, data=data, num_classes=CLASSES)
    (model,) = [op for op in
                pipeline.fit().to_pipeline().graph.operators.values()
                if isinstance(op, BlockLinearMapper)]
    return pipeline, model, float(test_eval.total_error)


def gaps_against_the_reference(model, test_error, train, test, epochs, lam,
                               seed):
    ref = load_module("reference", "timit_50x4096")
    cfg = reference_cfg(epochs, lam)

    def featurize(rows, b):
        W, bias = ref.draw(cfg, seed, b)
        return jnp.cos(rows @ W.T + bias)

    W, mean, icpt, _, test_scores = ref.fit_and_score(
        featurize, BLOCKS, jnp.asarray(train[0]), train[1],
        jnp.asarray(test[0]), CLASSES, lam, epochs)
    got = np.asarray(model.apply_dataset(
        ArrayDataset.from_numpy(test[0])).numpy())
    return {
        "weights_gap": max(_block_ls.rel_gap(model.weights, W),
                           _block_ls.rel_gap(model.feature_means, mean),
                           _block_ls.rel_gap(model.intercept, icpt)),
        "test_scores_gap": _block_ls.rel_gap(got, test_scores),
        "test_error_gap": abs(test_error - _block_ls.error_rate(
            test_scores, test[1])),
    }


@pytest.mark.parametrize("epochs,lam", [(1, 0.0), (5, 0.0), (5, 0.05)])
@pytest.mark.parametrize("n_train", [512, 509])
def test_streamed_fit_and_blockwise_apply_equal_the_plain_reference(
        mesh8, monkeypatch, epochs, lam, n_train):
    """Through the app, the optimizer and the executor, on eight virtual
    devices: 509 rows leave the last shard ragged, so padded rows are
    masked in every product."""
    stream_memory(monkeypatch)
    train, test = frames(n_train, 96)
    _, model, test_error = fit_through_the_app(train, test, epochs, lam, 31)
    assert isinstance(model, StreamedBlockLinearMapper)
    assert counter("solve.stream.fits") == 1
    assert counter("solve.materialised.fits") == 0
    gaps = gaps_against_the_reference(
        model, test_error, train, test, epochs, lam, 31)
    for name, limit in FIT_LIMITS.items():
        assert gaps[name] < limit, gaps
    assert gaps["test_error_gap"] <= 1 / 96, gaps


def test_the_three_pass_control_fails_the_reference(monkeypatch):
    """The solver's products at three bfloat16 passes (emulated where the
    solver multiplies, as ``tests/benchmarks/rehearsals.py``
    ``THREE_PASSES`` does) come out over the limits the sound fit passes."""
    def split(a):
        hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)

    def gram3(A, preferred=None):
        hi, lo = split(A)
        return hi.T @ hi + hi.T @ lo + lo.T @ hi

    def cross3(A, B, preferred=None):
        ah, al = split(A)
        bh, bl = split(B)
        return ah.T @ bh + ah.T @ bl + al.T @ bh

    stream_memory(monkeypatch)
    # a width no other test uses: the cached programs hold the products
    # they were traced with
    monkeypatch.setattr(linalg, "gram", gram3)
    monkeypatch.setattr(linalg, "cross", cross3)
    monkeypatch.setitem(globals(), "WIDTH", 72)
    train, test = frames(512, 96)
    _, model, test_error = fit_through_the_app(train, test, 5, 0.0, 31)
    gaps = gaps_against_the_reference(
        model, test_error, train, test, 5, 0.0, 31)
    assert gaps["weights_gap"] > FIT_LIMITS["weights_gap"], gaps
    assert gaps["test_scores_gap"] > FIT_LIMITS["test_scores_gap"], gaps


# -- the optimizer's choice ---------------------------------------------------

def predictor(train, labels, feats, epochs=2, lam=0.01):
    indicators = ClassLabelIndicatorsFromIntLabels(CLASSES)(labels)
    return (Pipeline.gather(feats) >> VectorCombiner()).and_then(
        BlockLeastSquaresEstimator(WIDTH, epochs, lam), train, indicators
    ) >> MaxClassifier()


def test_a_gather_that_fits_is_materialised_and_one_that_does_not_is_not(
        monkeypatch):
    (x, y), (tx, _) = frames(256, 64)
    # what ONE device holds of the gathered matrix: a data shard of it
    shard = 256 * BLOCKS * WIDTH * 4 / num_data_shards()
    out = {}
    for name, memory in (("materialised", 2.0 * shard + 8),
                         ("streamed", 2.0 * shard - 8)):
        PipelineEnv.get_or_create().clear_state()
        monkeypatch.setattr(resources, "device_memory_bytes",
                            lambda free=False, m=memory: m)
        before = {k: counter(f"solve.{k}.fits")
                  for k in ("stream", "materialised")}
        pipe = predictor(ArrayDataset.from_numpy(x),
                         ArrayDataset.from_numpy(y), branches())
        out[name] = pipe(ArrayDataset.from_numpy(tx)).numpy()
        took = {k: counter(f"solve.{k}.fits") - before[k] for k in before}
        assert took == {"stream": float(name == "streamed"),
                        "materialised": float(name == "materialised")}
    # the two fits agree to rounding: every prediction is the same
    assert np.array_equal(out["materialised"], out["streamed"])


def test_the_state_table_answers_for_a_streamed_fit(monkeypatch):
    """The prefix of a ``StreamedGatherFit`` is that of its estimator on
    the materialised gather: a second graph over the same training data
    (RAW, before the rule has run) finds the fit, and its delegating
    node is fed raw rows there too."""
    stream_memory(monkeypatch)
    (x, y), (tx, _) = frames(256, 64)
    train, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    pipe = predictor(train, labels, branches())
    first = pipe(ArrayDataset.from_numpy(tx)).numpy()
    hits, made = counter("executor.prefix_hits"), counter(
        "solve.stream.blocks_generated")
    assert counter("solve.stream.fits") == 1
    again = pipe(train).numpy()                 # training rows: no refit
    fitted = pipe.fit()                         # nor here
    assert counter("solve.stream.fits") == 1
    assert counter("executor.prefix_hits") >= hits + 2
    assert counter("solve.stream.blocks_generated") == made + BLOCKS
    assert again.shape == (256,)
    assert np.array_equal(
        fitted.apply(ArrayDataset.from_numpy(tx)).numpy(), first)
    assert int(fitted.apply_datum(tx[3]).get()) == int(first[3])


def test_two_feature_seeds_are_two_fits(monkeypatch):
    stream_memory(monkeypatch)
    (x, y), (tx, _) = frames(256, 64)
    train, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    predictor(train, labels, branches(seed=11))(
        ArrayDataset.from_numpy(tx)).numpy()
    predictor(train, labels, branches(seed=12))(
        ArrayDataset.from_numpy(tx)).numpy()
    assert counter("solve.stream.fits") == 2


def test_branches_that_are_not_blocks_stay_materialised(monkeypatch):
    """Chains in the branches (as MnistRandomFFT has), a width that is
    not the block size, or a model kept narrow: the rule leaves the
    graph alone whatever the device's memory."""
    from keystone_tpu.nodes.stats import LinearRectifier

    stream_memory(monkeypatch)
    (x, y), (tx, _) = frames(128, 32)
    train, labels = ArrayDataset.from_numpy(x), ArrayDataset.from_numpy(y)
    chains = [b >> LinearRectifier(0.0) for b in branches()]
    predictor(train, labels, chains)(ArrayDataset.from_numpy(tx)).numpy()
    indicators = ClassLabelIndicatorsFromIntLabels(CLASSES)(labels)
    wide = (Pipeline.gather(branches()) >> VectorCombiner()).and_then(
        BlockLeastSquaresEstimator(2 * WIDTH, 1, 0.01), train, indicators)
    wide(ArrayDataset.from_numpy(tx)).numpy()
    assert counter("solve.stream.fits") == 0
    assert counter("solve.materialised.fits") == 2


# -- spans and counters ---------------------------------------------------------

def test_a_streamed_fit_leaves_its_spans_and_counts_its_blocks(monkeypatch):
    stream_memory(monkeypatch)
    train, test = frames(256, 64)
    epochs = 3
    fit_through_the_app(train, test, epochs, 0.0, 31)
    # a block is made once an epoch (the factor sweep is the first
    # epoch; ``BLOCKS * (1 + epochs)`` until ISSUE 34), and once more
    # for the test rows' apply
    assert counter("solve.stream.blocks_generated") == (
        BLOCKS * epochs + BLOCKS)
    ring = flight_recorder().spans()
    by_name = {f"{s.cat}:{s.name}": s for s in ring}
    fit = by_name["solve:fit:BlockLeastSquaresEstimator"]
    for name in ("solve:stream:factor", "solve:stream:epochs"):
        span = by_name[name]
        assert span.parent == fit.seq
        assert span.args == {"blocks": BLOCKS, "rows": 256,
                             "block_width": WIDTH, "epochs": epochs,
                             "row_chunks": 1}
    assert by_name["apply:stream"].args == {
        "blocks": BLOCKS, "rows": 64, "block_width": WIDTH, "row_chunks": 1}
    from keystone_tpu.observability import names

    assert {"solve", "apply"} <= names.SPAN_CATEGORIES
    assert {"solve.stream.blocks_generated", "solve.stream.fits",
            "solve.materialised.fits"} <= names.METRIC_NAMES


def test_a_fit_of_one_epoch_is_the_factor_sweep_alone(monkeypatch):
    """``num_iter`` 1: the fused sweep's weights are the model's, no
    ``_stream_epochs`` program is built or dispatched, no
    ``solve:stream:epochs`` span is left, and a block is made once for
    the fit."""
    import importlib

    transformer = importlib.import_module("keystone_tpu.workflow.transformer")
    dispatched = []
    real = transformer.struct_cached_jit

    def watching(key, builder):
        fn = real(key, builder)

        def call(*args):
            dispatched.append(key[0])
            return fn(*args)
        return call

    from keystone_tpu.nodes.learning import linear

    monkeypatch.setattr(linear, "struct_cached_jit", watching)
    stream_memory(monkeypatch)
    train, test = frames(256, 64)
    mark = len(flight_recorder().spans())
    _, model, _ = fit_through_the_app(train, test, 1, 0.05, 31)
    assert isinstance(model, StreamedBlockLinearMapper)
    assert dispatched == ["stream_factor", "stream_apply"]
    assert counter("solve.stream.blocks_generated") == BLOCKS + BLOCKS
    left = {f"{s.cat}:{s.name}" for s in flight_recorder().spans()[mark:]}
    assert "solve:stream:factor" in left
    assert "solve:stream:epochs" not in left
    # and more epochs are one program more
    del dispatched[:]
    PipelineEnv.get_or_create().clear_state()
    fit_through_the_app(train, test, 2, 0.05, 31)
    assert dispatched == ["stream_factor", "stream_epochs", "stream_apply"]


def test_the_fused_sweep_calls_its_maker_once_a_block():
    """The structure of the traced ``_stream_factor``, as PR 29 holds
    ``_block_solve``'s: ONE scan over blocks whose body holds the maker
    once (one cosine), the factor and, under the recovery's two-way
    ``cond``, the raised one, and the first epoch's step (the two
    triangular solves of ``cho_solve``); its outputs are what a fit of
    one epoch hands on: factors, weights, scores. Compiled, it is one
    loop."""
    feat = branches(blocks=1)[0]
    n = 64
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    args = (S((n, DIM), f32),
            (S((BLOCKS, WIDTH, DIM), f32), S((BLOCKS, WIDTH), f32)),
            S((n, CLASSES), f32), S((CLASSES,), f32), S((n,), jnp.bool_),
            S((), f32), S((), f32))
    for more in (0, 4):
        prog = _stream_program("factor", feat, more)
        eqns = list(_walk_eqns(jax.make_jaxpr(prog)(*args).jaxpr))
        names = [eqn.primitive.name for eqn in eqns]
        count = {op: names.count(op) for op in (
            "scan", "while", "cos", "cholesky", "triangular_solve")}
        assert count == {"scan": 1, "while": 0, "cos": 1, "cholesky": 2,
                         "triangular_solve": 2}, count
        assert {len(eqn.params["branches"]) for eqn in eqns
                if eqn.primitive.name == "cond"} == {2}
        lowered = prog.lower(*args)
        shapes = [tuple(o.shape) for o in jax.tree_util.tree_leaves(
            lowered.out_info)]
        assert shapes == [(BLOCKS, WIDTH), (BLOCKS, WIDTH, WIDTH), (BLOCKS,),
                          (BLOCKS,), (BLOCKS, WIDTH, CLASSES), (n, CLASSES)]
        assert lowered.compile().as_text().count(" while(") == 1
    # however many passes follow, the factor sweep is one program
    assert _stream_program("factor", feat, 2) is _stream_program(
        "factor", feat, 4)
    assert _stream_program("factor", feat, 0) is not _stream_program(
        "factor", feat, 4)


def test_a_capture_holds_the_streamed_spans_as_ks_annotations(
        tmp_path, monkeypatch):
    from benchmarks import xplane

    stream_memory(monkeypatch)
    train, test = frames(256, 64)
    fit_through_the_app(train, test, 2, 0.0, 31)   # compiles stay outside
    PipelineEnv.get_or_create().clear_state()
    jax.profiler.start_trace(str(tmp_path))
    try:
        fit_through_the_app(train, test, 2, 0.0, 31)
    finally:
        jax.profiler.stop_trace()
    captured = {n for n, _, _ in xplane.load(
        str(tmp_path), span_prefix="ks:").spans}
    assert {"solve:fit:BlockLeastSquaresEstimator", "solve:stream:factor",
            "solve:stream:epochs", "apply:stream"} <= captured


# -- CosineRandomFeatures: weights as arguments ---------------------------------

def test_branches_drawn_side_by_side_are_what_create_draws_one_by_one():
    """``create_branches`` is fifty ``create`` calls on threads: branch
    ``i`` from ``RandomState(seed + i)``, every call drawn anew (no
    recipe is remembered between pipelines: a sweep over seeds or gamma
    pays what a repeated recipe pays), inside one ``featurize:draw``
    span."""
    mark = len(flight_recorder().spans())
    drawn = CosineRandomFeatures.create_branches(7, DIM, WIDTH, GAMMA, seed=40)
    spans = [s for s in flight_recorder().spans()[mark:]
             if (s.cat, s.name) == ("featurize", "draw")]
    assert len(spans) == 1 and spans[0].args["branches"] == 7
    for i, node in enumerate(drawn):
        one = CosineRandomFeatures.create(DIM, WIDTH, GAMMA, seed=40 + i)
        assert np.array_equal(node.W, one.W) and np.array_equal(node.b, one.b)
        assert node == one and node.W is not one.W
        rng = np.random.RandomState(40 + i)
        assert np.array_equal(
            node.W, (rng.randn(WIDTH, DIM) * GAMMA).astype(np.float32))
    again = CosineRandomFeatures.create_branches(7, DIM, WIDTH, GAMMA, seed=40)
    assert all(a.W is not b.W for a, b in zip(drawn, again))
    assert CosineRandomFeatures.create_branches(0, DIM, WIDTH, GAMMA) == []


def test_two_cosine_nodes_of_one_shape_share_one_compiled_program():
    import importlib

    transformer = importlib.import_module("keystone_tpu.workflow.transformer")
    a = CosineRandomFeatures.create(DIM, WIDTH, GAMMA, seed=1)
    b = CosineRandomFeatures.create(DIM, WIDTH, GAMMA, seed=2)
    assert a.struct_key() == b.struct_key() and a != b
    assert a == CosineRandomFeatures.create(DIM, WIDTH, GAMMA, seed=1)
    explicit = CosineRandomFeatures(np.array(a.W), np.array(a.b))
    assert explicit != CosineRandomFeatures(np.array(b.W), np.array(b.b))
    x = np.random.RandomState(0).randn(16, DIM).astype(np.float32)
    ds = ArrayDataset.from_numpy(x)
    out_a = a.apply_dataset(ds).numpy()
    programs = len(transformer._JIT_CACHE)
    jax.config.update("jax_log_compiles", True)
    try:
        import logging

        records = []
        handler = logging.Handler()
        handler.emit = records.append
        logging.getLogger("jax").addHandler(handler)
        out_b = b.apply_dataset(ds).numpy()
        logging.getLogger("jax").removeHandler(handler)
    finally:
        jax.config.update("jax_log_compiles", False)
    assert len(transformer._JIT_CACHE) == programs
    assert not [r for r in records if "Compiling" in r.getMessage()
                and "param_batched" in r.getMessage()]
    np.testing.assert_allclose(out_a, np.cos(x @ a.W.T + a.b), atol=2e-6)
    np.testing.assert_allclose(out_b, np.cos(x @ b.W.T + b.b), atol=2e-6)


def test_the_streamed_solve_holds_no_weight_constant():
    """At the published width: the compiled HLO of the factor sweep has
    the 4,096 x 440 weights as a parameter and not as a constant."""
    feat = CosineRandomFeatures.create(440, 4096, 0.05555, seed=3)
    prog = _stream_program("factor", feat)
    args = (jax.ShapeDtypeStruct((16, 440), jnp.float32),
            (jax.ShapeDtypeStruct((2, 4096, 440), jnp.float32),
             jax.ShapeDtypeStruct((2, 4096), jnp.float32)),
            jax.ShapeDtypeStruct((16, 147), jnp.float32),
            jax.ShapeDtypeStruct((147,), jnp.float32),
            jax.ShapeDtypeStruct((16,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    text = prog.lower(*args).compile().as_text()
    assert re.search(r"f32\[2,4096,440\]\S* parameter\(", text)
    assert not re.search(r"f32\[(\d+,)?4096,440\]\S* constant\(", text)
    assert not re.search(r"f32\[(\d+,)?440,4096\]\S* constant\(", text)


# -- serving ------------------------------------------------------------------

def test_a_streamed_model_is_admitted_and_answers_blockwise(monkeypatch):
    from keystone_tpu.serving import ServingPlane

    stream_memory(monkeypatch)
    train, test = frames(256, 64)
    pipeline, model, _ = fit_through_the_app(train, test, 2, 0.01, 31)
    fitted = pipeline.fit()
    want = fitted.apply(ArrayDataset.from_numpy(test[0])).numpy()
    plane = ServingPlane(max_batch=16)
    try:
        plane.start()
        plane.admit("timit", fitted, jax.ShapeDtypeStruct((DIM,), np.float32))
        made = counter("solve.stream.blocks_generated")
        got = plane.predict("timit", test[0][:5])
        assert counter("solve.stream.blocks_generated") == made + BLOCKS
        assert np.array_equal(np.asarray(got), want[:5])
    finally:
        plane.close()
