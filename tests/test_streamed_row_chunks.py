"""Rows chunked inside a streamed block (ISSUE 45): the three sweeps of
``ops.linalg`` with their rows in chunks against the same sweeps with a
block of all rows, the arithmetic that derives the chunk from the
device's memory, and RandomPatchCifarAugmented through its public
``run()`` in the three forms one graph can take. Small sizes, CPU:
numbers and control flow, no device metric.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import load_module
from keystone_tpu.analysis import resources
from keystone_tpu.evaluation import augmented
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.nodes.images import core
from keystone_tpu.nodes.learning.linear import (
    BlockLinearMapper,
    StreamedBlockLinearMapper,
)
from keystone_tpu.nodes.util import LabelAugmenter
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.observability.timeline import flight_recorder
from keystone_tpu.ops import linalg
from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.parallel.mesh import make_mesh, mesh_scope
from keystone_tpu.pipelines.images.cifar import (
    random_patch_cifar_augmented as app,
)
from keystone_tpu.workflow.env import PipelineEnv

HELD, ROWS, INPUTS, WIDTH, BLOCKS, CLASSES = 70, 67, 12, 16, 3, 4


def counter(name):
    return MetricsRegistry.get_or_create().counter(name).value


@pytest.fixture(scope="module")
def problem():
    """70 rows held, 67 of them real; three blocks whose features are
    all positive with means far over their deviations, as sums of
    rectified responses are."""
    rng = np.random.default_rng(45)
    rows = jnp.asarray(rng.normal(size=(HELD, INPUTS)), jnp.float32)
    mask = jnp.asarray(np.arange(HELD) < ROWS)
    params = jnp.asarray(rng.normal(size=(BLOCKS, INPUTS, WIDTH)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(HELD, CLASSES)),
                    jnp.float32) * mask[:, None]

    def make_block(params_i, rows):
        return jnp.maximum(rows @ params_i, 0.0) + 50.0

    return rows, params, make_block, Y, mask


def gap(got, want):
    return max(
        float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(
            b, np.float64)) / max(np.linalg.norm(np.asarray(b)), 1e-30))
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)))


# 2 chunks, 3 chunks (the last starts where it still fits and shares 2
# rows with the one before), 5 ragged ones, and a chunk a row short of
# them all
@pytest.mark.parametrize("scale_eps", [None, 1e-12],
                         ids=["centred", "standardised"])
@pytest.mark.parametrize("chunk", [35, 24, 16, 69])
def test_chunked_sweeps_equal_the_whole_block_sweeps(problem, chunk,
                                                     scale_eps):
    rows, params, make_block, Y, mask = problem
    n, lam = float(ROWS), 0.5

    def factor(**chunked):
        return jax.jit(lambda: linalg.bcd_stream_factor(
            rows, params, make_block, Y, mask, n, lam, scale_eps=scale_eps,
            **chunked))()

    whole = factor()
    *parts, counted = factor(row_chunk=chunk, block_width=WIDTH)
    # to float32 rounding: the same arithmetic a row, summed in chunks
    # (a mean 50 deviations up costs the centring 50 roundings of 6e-8)
    assert gap(parts, whole) < 1e-5
    # every real row entered every block's Gram once, and no other
    assert np.array_equal(np.asarray(counted), [ROWS] * BLOCKS)
    (means, Ls, oks, _, *inv_stds), Ws, pred = whole
    assert bool(np.all(oks))
    scale = dict(inv_stds=inv_stds[0]) if inv_stds else {}

    def epochs(**chunked):
        return jax.jit(lambda: linalg.bcd_stream_epochs(
            rows, params, make_block, Y, mask, means, Ls, Ws, pred,
            num_passes=2, **scale, **chunked))()

    assert gap(epochs(row_chunk=chunk), epochs()) < 1e-5

    def apply(**chunked):
        return linalg.block_stream_apply(
            rows, params, make_block, means, Ws, jnp.ones(CLASSES), **scale,
            **chunked)

    assert gap(apply(row_chunk=chunk), apply()) < 1e-6


def test_a_chunk_of_all_rows_is_the_whole_block_program(problem):
    rows, params, make_block, Y, mask = problem
    whole = linalg.bcd_stream_factor(
        rows, params, make_block, Y, mask, float(ROWS), 0.5)
    same = linalg.bcd_stream_factor(
        rows, params, make_block, Y, mask, float(ROWS), 0.5, row_chunk=HELD,
        block_width=WIDTH)
    assert len(same) == len(whole) == 3      # no fourth output: not chunked
    assert gap(same, whole) == 0.0


def test_a_mean_far_over_its_deviation_survives_the_chunked_sums():
    """Columns at 3,000 +- 1: centred first, their Gram is the Gram of
    the deviations; ``sum x x^T - n mean mean^T`` in float32 would be
    rounding noise of 1e7 x 6e-8 a term against entries of the order of
    1."""
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.normal(size=(4096, 8)), jnp.float32)
    params = jnp.eye(8, dtype=jnp.float32)[None]
    Y = rng.normal(size=(4096, 2))
    Y = jnp.asarray(Y - Y.mean(axis=0), jnp.float32)   # as the solver's is
    mask = jnp.ones(4096, bool)
    (_, Ls, *_), Ws, _, counted = linalg.bcd_stream_factor(
        rows, params, lambda p, r: r @ p + 3000.0, Y, mask, 4096.0, 0.0,
        row_chunk=512, block_width=8)
    held = np.asarray(rows + 3000.0, np.float64)    # as float32 holds them
    centred = held - held.mean(0)
    want = np.linalg.solve(centred.T @ centred, centred.T @ np.asarray(
        Y, np.float64))
    assert gap([Ws[0]], [want]) < 1e-4
    assert float(counted[0]) == 4096.0


@pytest.mark.parametrize("memory,rows,width,chunk", [
    # a v5e's 15.75 GiB: the augmented CIFAR cell's block of 500,000 x
    # 4,096 (8.2 GB, 16.4 with its copy) in 32 even chunks; its test
    # crops, TIMIT's and CIFAR's blocks whole, as they always were
    (15.75 * 2 ** 30, 500000, 4096, 15872),
    (15.75 * 2 ** 30, 100000, 4096, None),
    (15.75 * 2 ** 30, 65536, 4096, None),
    (15.75 * 2 ** 30, 50000, 4096, None),
    # the new cell's rehearsal; the rehearsals and tests that state a
    # memory to force a gather to stream: not one granule of rows fits
    # the chunk's share, and there are no chunks
    (4 * 2 ** 20, 8320, 64, 256),
    (4 * 2 ** 20, 4160, 64, 256),
    (8388608, 3072, 256, None),
    (65536, 384, 64, None),
    (1000.0, 256, 16, None),
])
def test_the_row_chunk_follows_from_memory_and_shapes(monkeypatch, memory,
                                                      rows, width, chunk):
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: memory)
    assert resources.stream_row_chunk(rows, width) == chunk
    if chunk is not None:
        assert chunk % resources.ROW_CHUNK_GRANULE == 0 and chunk < rows
        # even: a chunk fewer would not hold the rows
        count = -(-rows // chunk)
        assert (count - 1) * chunk < rows <= count * chunk
        assert 4.0 * chunk * width <= resources.ROW_CHUNK_SHARE * memory


# -- the augmentation's nodes ----------------------------------------------------

def test_random_crops_are_the_windows_their_offsets_name(monkeypatch):
    monkeypatch.setattr(core, "PATCHER_IMAGES_A_STEP", 4)   # 3 steps, ragged
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(10, 32, 32, 3)).astype(np.float32)
    with mesh_scope(make_mesh(jax.devices()[:1])):
        patcher = core.RandomPatcher(10, 24, 24, seed=7)
        crops = patcher(ArrayDataset.from_numpy(images)).numpy()
        flipped = core.RandomFlipper(0.5, seed=7)(
            ArrayDataset.from_numpy(crops)).numpy()
    xs, ys = (np.asarray(a) for a in patcher.offsets(10, 32, 32))
    assert crops.shape == (100, 24, 24, 3)
    assert 0 <= xs.min() and xs.max() <= 8 and len(np.unique(xs)) == 9
    for i in range(10):
        for j in range(10):
            want = images[i, xs[i, j]:xs[i, j] + 24, ys[i, j]:ys[i, j] + 24]
            assert np.array_equal(crops[10 * i + j], want)   # bytes, exact
    # a flip follows the seed and the row's index, whatever the rows
    hit = np.asarray(jax.random.uniform(jax.random.PRNGKey(7), (100,)) < 0.5)
    longer = np.asarray(jax.random.uniform(jax.random.PRNGKey(7), (999,)) < 0.5)
    assert np.array_equal(hit, longer[:100]) and 30 < hit.sum() < 70
    for row in range(100):
        assert np.array_equal(flipped[row], crops[row][:, ::-1] if hit[row]
                              else crops[row])


def test_another_seed_draws_other_crops_and_compiles_nothing():
    """The key is an argument of both programs: a seed that is a
    constant of them misses the persistent compile cache in every
    process that sees it first."""
    from keystone_tpu.observability.compilelog import compile_observatory

    rng = np.random.default_rng(1)
    images = rng.integers(0, 256, size=(6, 32, 32, 3)).astype(np.float32)

    def augmented_rows(seed):
        crops = core.RandomPatcher(10, 24, 24, seed=seed)(
            ArrayDataset.from_numpy(images))
        return core.RandomFlipper(0.5, seed=seed)(crops).numpy()

    with mesh_scope(make_mesh(jax.devices()[:1])):
        first = augmented_rows(2450000101)
        before = compile_observatory().count_total()
        again, other = augmented_rows(2450000101), augmented_rows(2450000102)
        assert compile_observatory().count_total() == before
    assert np.array_equal(first, again)
    assert not np.array_equal(first, other)


def test_labels_are_repeated_on_the_device_and_crops_vectorised():
    labels = np.arange(12, dtype=np.float32).reshape(6, 2)
    before = counter("egress.d2h_bytes")
    out = LabelAugmenter(3).apply_dataset(ArrayDataset.from_numpy(labels))
    assert counter("egress.d2h_bytes") == before      # no trip to the host
    assert out.n == 18
    assert np.array_equal(out.numpy(), np.repeat(labels, 3, axis=0))
    images = np.arange(2 * 4 * 4 * 3, dtype=np.float32).reshape(2, 4, 4, 3)
    rows = core.ImageVectorizer().apply_dataset(
        ArrayDataset.from_numpy(images)).numpy()
    assert np.array_equal(rows, images.reshape(2, -1))


@pytest.mark.parametrize("policy", [augmented.AVERAGE_POLICY,
                                    augmented.BORDA_POLICY])
def test_the_vote_takes_each_name_s_copies_in_order_of_appearance(policy):
    rng = np.random.default_rng(5)
    names = np.repeat(np.array([7, 3, 9, 1]), 5)
    order = rng.permutation(20)
    names, preds = names[order], rng.normal(size=(20, 6))
    classes = {7: 0, 3: 2, 9: 2, 1: 5}
    labels = np.array([classes[int(k)] for k in names])
    seen = list(dict.fromkeys(int(k) for k in names))
    ranks = np.argsort(np.argsort(preds, axis=1), axis=1)
    want = np.stack([
        ranks[names == k].sum(axis=0) if policy == augmented.BORDA_POLICY
        else preds[names == k].mean(axis=0) for k in seen])
    scores, actual = augmented.vote(names, preds, labels, policy)
    np.testing.assert_allclose(scores, want, rtol=1e-12)
    assert list(actual) == [classes[k] for k in seen]
    # lists of Python scalars, as the evaluators' other callers hand over
    metrics = augmented.evaluate_augmented(
        [int(k) for k in names], list(preds), list(labels), 6, policy)
    assert metrics.confusion.sum() == 4
    assert metrics.total_error == np.mean(
        np.argmax(want, axis=1) != np.asarray(actual))
    spans = [s for s in flight_recorder().spans()
             if (s.cat, s.name) == ("eval", "vote")]
    assert spans[-1].args == {"rows": 20, "groups": 4}


# -- the app: one graph, three forms -----------------------------------------------

@pytest.fixture(scope="module")
def images():
    made = load_module("datagen", "cifar_images").make_images(416, 32, 5)
    return [(px.astype(np.float32), y.astype(np.int32)) for px, y in made]


def fit(images, monkeypatch, memory):
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: memory)
    PipelineEnv.get_or_create().clear_state()
    names = ("solve.stream.fits", "solve.materialised.fits",
             "solve.stream.blocks_generated", "solve.stream.row_chunks",
             "solve.stream.rows")
    before = {k: counter(k) for k in names}
    with mesh_scope(make_mesh(jax.devices()[:1])):
        pipeline, test_eval = app.run(
            app.AugmentedConfig(num_filters=80, lam=10.0, seed=3,
                                block_size=64),
            *[LabeledData(data=ArrayDataset.from_numpy(px),
                          labels=ArrayDataset.from_numpy(y))
              for px, y in images])
        ops = pipeline.fit().to_pipeline().graph.operators.values()
        (model,) = [op for op in ops if isinstance(op, BlockLinearMapper)]
        weights = np.asarray(model.weights)
    return (model, weights, test_eval.total_error,
            {k: counter(k) - before[k] for k in names})


def test_the_app_fits_in_chunks_as_it_fits_whole_and_materialised(
        images, monkeypatch):
    """80 filters on 24 x 24 crops: 160 columns, three blocks of 32
    filters (64 columns), the last of 16. 4,160 crops: at 4 MiB their
    gather streams and a block of all rows takes 17 chunks of 256; at
    1.5 MB it streams and no granule of rows fits a chunk's share; at 1
    TB it is materialised."""
    model, chunked, error, rose = fit(images, monkeypatch, 4.0 * 2 ** 20)
    assert isinstance(model, StreamedBlockLinearMapper)
    assert rose == {"solve.stream.fits": 1, "solve.materialised.fits": 0,
                    "solve.stream.blocks_generated": 3 + 3,
                    "solve.stream.row_chunks": 17, "solve.stream.rows": 4160}
    assert np.array_equal(np.asarray(model.rows_solved), [4160.0] * 3)
    assert model.Ws.shape == (3, 64, 10) and chunked.shape == (160, 10)
    by_name = {f"{s.cat}:{s.name}": s for s in flight_recorder().spans()}
    assert by_name["solve:stream:factor"].args["row_chunks"] == 17
    assert by_name["featurize:augment"].args in (
        {"rows": 416, "crops": 4160}, {"rows": 32, "crops": 320})
    assert by_name["eval:vote"].args == {"rows": 320, "groups": 32}

    model, whole, error_whole, rose = fit(images, monkeypatch, 1.5e6)
    assert isinstance(model, StreamedBlockLinearMapper)
    assert model.rows_solved is None and rose["solve.stream.row_chunks"] == 1
    model, materialised, error_mat, rose = fit(images, monkeypatch, 1e12)
    assert not isinstance(model, StreamedBlockLinearMapper)
    assert rose["solve.materialised.fits"] == 1
    assert gap([chunked], [whole]) < 2e-5
    assert gap([chunked], [materialised]) < 2e-5
    assert error == error_whole == error_mat


def test_the_check_command_plans_the_chunked_form_at_the_documented_flags(
        monkeypatch):
    from keystone_tpu.pipelines import resolve_check_app

    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: 15.75 * 2 ** 30)
    target = resolve_check_app("cifar.random_patch_augmented_10k")()
    with mesh_scope(make_mesh(jax.devices()[:1])):
        report = target.pipeline.check(target.input_spec, name=target.name)
    assert report.ok
    ops = list(report.analysis.graph.operators.values())
    (fit,) = [op for op in ops
              if op.label() == "Streamed[BlockLeastSquaresEstimator]"]
    assert len(fit.branches) == 5
    assert not any("FusedConvRectifyPool" in op.label() for op in ops)
    # one block of 500,000 x 4,096 floats, its chunks and five factors:
    # not three blocks (24.6 GB), not rows x 20,000 floats (40 GB)
    assert 8.5e9 < report.plan.fit_peak_nbytes < 14e9
