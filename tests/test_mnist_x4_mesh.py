"""MnistRandomFFT on a 4 x 1 mesh of the CPU's virtual devices (PR 38,
the cell ``mnist_refit_x4``): the app's public ``run()`` at a small size
(8 branches, 1,024-wide blocks, 3,072 + 512 rows) under the default
mesh's shape on four devices, against the plain reference on one device
and against the same fit on a 1 x 1 mesh; where the gathered matrix
lies; what the new counters and span arguments say; the partitioned
solver program's collectives; and the planner that now reckons a data
shard of a gather against one device's memory."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.datagen import mnist_csv
from benchmarks.harness import load_json, load_module
from benchmarks.reference import _block_ls
from keystone_tpu.analysis import resources
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.nodes.learning import linear
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
    StreamedBlockLinearMapper,
    block_solve_allreduce_nbytes,
)
from keystone_tpu.nodes.stats import CosineRandomFeatures
from keystone_tpu.nodes.util import MaxClassifier, VectorCombiner
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.observability.timeline import flight_recorder
from keystone_tpu.ops import linalg
from keystone_tpu.parallel.dataset import (
    ArrayDataset,
    row_shards,
    shard_layout,
)
from keystone_tpu.parallel.mesh import (
    batch_sharding,
    make_mesh,
    mesh_scope,
    replicated_sharding,
)
from keystone_tpu.pipelines.images.mnist import random_fft as app
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.pipeline import Pipeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = load_json(os.path.join(
    ROOT, "benchmarks", "configs", "mnist_random_fft_200.json"))
CFG = {**FILE, **FILE["rehearsal"]}
BRANCHES, BS, TRAIN, TEST, K = 8, 1024, 3072, 512, 10
WIDTH = BRANCHES * 512
NAMES = ("solve.data_shards", "solve.shard_bytes_max")
COUNTERS = ("solve.sharded.fits", "solve.allreduce_bytes",
            "solve.materialised.fits")
#: stated tolerance of a 4 x 1 fit against a 1 x 1 fit of the same rows:
#: the sums over rows are made in another order, nothing else differs
MESH_TOLERANCE = {"weights": 2e-5, "test_scores": 1e-5}


def fit_on(devices, train, test):
    """One whole fit of the app on a mesh of ``devices`` x 1; what it
    produced and what the program said about it."""
    registry = MetricsRegistry.get_or_create()
    before = {n: registry.counter(n).value for n in COUNTERS}
    seen = len(flight_recorder().spans())
    with mesh_scope(make_mesh(jax.devices()[:devices])):
        PipelineEnv.get_or_create().clear_state()
        parts = [LabeledData(
            data=ArrayDataset.from_numpy(px.astype(np.float32)),
            labels=ArrayDataset.from_numpy(y)) for px, y in (train, test)]
        pipeline, train_eval, test_eval = app.run(
            app.MnistRandomFFTConfig(num_ffts=BRANCHES, block_size=BS),
            train=parts[0], test=parts[1])
        model = load_module("configs", "_fitted").linear_model(pipeline)
        features = app.build_featurizer(app.MnistRandomFFTConfig(
            num_ffts=BRANCHES, block_size=BS))(parts[1].data).get()
        scores = (np.asarray(features.data)[:TEST] - model["feature_means"]
                  ) @ model["weights"] + model["intercept"]
    spans = flight_recorder().spans()[seen:]
    return dict(
        model, train_error=float(train_eval.total_error),
        test_error=float(test_eval.total_error), test_scores=scores,
        counters={n: registry.counter(n).value - before[n] for n in COUNTERS},
        gauges={n: registry.gauge(n).value for n in NAMES},
        solve=[s.args for s in spans if (s.cat, s.name) == (
            "solve", "fit:BlockLeastSquaresEstimator")],
        h2d=[s.args for s in spans if (s.cat, s.name) == ("ingest", "h2d")])


@pytest.fixture(scope="module")
def data():
    return mnist_csv.make_mnist(TRAIN, TEST, 38, K)


@pytest.fixture(scope="module")
def fits(data):
    return {devices: fit_on(devices, *data) for devices in (4, 1)}


def test_the_fit_on_four_devices_is_the_fit_on_one(fits):
    four, one = fits[4], fits[1]
    assert _block_ls.rel_gap(four["weights"], one["weights"]) < (
        MESH_TOLERANCE["weights"])
    assert _block_ls.rel_gap(four["test_scores"], one["test_scores"]) < (
        MESH_TOLERANCE["test_scores"])
    assert _block_ls.rel_gap(four["feature_means"], one["feature_means"]) < 1e-6
    assert four["train_error"] == one["train_error"]
    assert four["test_error"] == one["test_error"]


@pytest.mark.parametrize("devices", [4, 1])
def test_the_fit_is_the_plain_references_on_one_device(fits, data, devices):
    got = fits[devices]
    counts = [{"data_shards": got["gauges"]["solve.data_shards"],
               "shard_bytes_max": got["gauges"]["solve.shard_bytes_max"],
               "sharded_fits": got["counters"]["solve.sharded.fits"]}]
    cfg = dict(CFG, chips=devices)
    checks = load_module("reference", "mnist_random_fft_200").check(
        cfg, {"train": data[0], "test": data[1], "sign_seed": 0},
        dict(got, fit_counts=counts))
    assert [name for name, _, _ in checks] == [
        "weights_gap", "test_scores_gap", "train_error_gap",
        "test_error_gap", "shards_off", "replicated_off"]
    assert all(value <= limit for _, value, limit in checks), checks


def test_the_counters_and_span_arguments_say_four_shards(fits):
    four, one = fits[4], fits[1]
    assert four["gauges"] == {
        "solve.data_shards": 4.0,
        "solve.shard_bytes_max": TRAIN / 4 * WIDTH * 4.0}
    assert one["gauges"] == {"solve.data_shards": 1.0,
                             "solve.shard_bytes_max": TRAIN * WIDTH * 4.0}
    by_shapes = 4 * (WIDTH // BS * (BS * BS + BS * K) + WIDTH + K)
    assert four["counters"] == {"solve.sharded.fits": 1.0,
                                "solve.allreduce_bytes": float(by_shapes),
                                "solve.materialised.fits": 1.0}
    # on one shard nothing is reduced between chips and nothing counted
    assert one["counters"] == {"solve.sharded.fits": 0.0,
                               "solve.allreduce_bytes": 0.0,
                               "solve.materialised.fits": 1.0}
    assert four["solve"] == [{"data_shards": 4, "rows_a_shard": TRAIN // 4}]
    assert one["solve"] == [{"data_shards": 1, "rows_a_shard": TRAIN}]
    rows = sorted((a["data_shards"], a["rows_a_shard"], a["nbytes"])
                  for a in four["h2d"])
    assert (4, TRAIN // 4, TRAIN * 784 * 4) in rows
    assert (4, TEST // 4, TEST * 784 * 4) in rows
    assert {a["data_shards"] for a in one["h2d"]} == {1}


def test_the_gathered_matrix_lies_in_four_row_shards(data):
    with mesh_scope(make_mesh(jax.devices()[:4])) as mesh:
        rows = ArrayDataset.from_numpy(data[0][0][:1022].astype(np.float32))
        features = app.build_featurizer(app.MnistRandomFFTConfig(
            num_ffts=BRANCHES, block_size=BS))(rows).get()
        x = features.data
        assert x.shape == (1024, WIDTH) and features.n == 1022   # padded
        assert x.sharding.is_equivalent_to(batch_sharding(mesh), x.ndim)
        held = sorted((s.index[0].start, s.index[0].stop, s.data.nbytes)
                      for s in x.addressable_shards)
        assert held == [(lo, lo + 256, 256 * WIDTH * 4)
                        for lo in (0, 256, 512, 768)]
        assert row_shards(x) == (4, x.nbytes // 4)
        assert shard_layout(features) == {"data_shards": 4,
                                          "rows_a_shard": 256}
        # the padded rows are zero on the last shard, as every map keeps them
        assert not np.asarray(x)[1022:].any()
        # held whole on every chip: one row range, all of its bytes
        whole = jax.device_put(np.asarray(x), replicated_sharding(mesh))
        assert row_shards(whole) == (1, x.nbytes)
    assert shard_layout(np.zeros(3)) == {}


def test_what_a_solve_hands_to_the_reduction_by_shapes():
    # the cell's own: 50 blocks of 2,048, ten 512 x 512 tiles of each Gram
    bounds = [(i, i + 2048) for i in range(0, 102400, 2048)]
    assert linalg.gram_reduced_elems(2048) == 10 * 512 * 512
    assert block_solve_allreduce_nbytes(bounds, 10, 1) == 4 * (
        50 * (10 * 512 * 512 + 2048 * 10) + 102400 + 10) == 528_793_640
    # a narrow block is one einsum, reduced whole; a later pass reduces
    # the cross products alone; a ragged last block counts at its width
    assert linalg.gram_reduced_elems(1024) == 1024 * 1024
    assert linalg.gram_reduced_elems(4096) == 36 * 512 * 512
    assert linalg.gram_reduced_elems(2049) == 2049 * 2049
    ragged = [(0, 1024), (1024, 1536)]
    assert block_solve_allreduce_nbytes(ragged, 3, 2) == 4 * (
        1024 ** 2 + 512 ** 2 + 2 * 1536 * 3 + 1536 + 3)


def test_the_partitioned_solve_reduces_once_a_step_and_gathers_nothing():
    """The one solver program on four devices: an all-reduce inside the
    loop over blocks, and no all-gather: the design matrix is never
    brought together on a chip."""
    mesh = make_mesh(jax.devices()[:4])
    n, d, bs = 256, 4 * 128, 128
    rows, rep = batch_sharding(mesh), replicated_sharding(mesh)
    shape = jax.ShapeDtypeStruct
    bounds = tuple((i, i + bs) for i in range(0, d, bs))
    with mesh_scope(mesh):
        compiled = linear._block_solve_for(mesh).lower(
            shape((n, d), jnp.float32, sharding=rows),
            shape((n, K), jnp.float32, sharding=rows),
            shape((d,), jnp.float32, sharding=rep),
            shape((K,), jnp.float32, sharding=rep),
            shape((n,), jnp.bool_, sharding=rows), 0.0, bounds, 1).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    assert "all-gather" not in text and "all-to-all" not in text
    # each device is handed its quarter of the rows and no more
    assert compiled.input_shardings[0][0].shard_shape((n, d)) == (n // 4, d)


def branches_of(width, count):
    return [CosineRandomFeatures.create(20, width, 0.1, seed=i)
            for i in range(count)]


@pytest.mark.parametrize("devices,form", [(4, "materialised"), (1, "stream")])
def test_the_gather_rule_reckons_a_data_shard_against_one_device(
        monkeypatch, devices, form):
    """256 rows x 4 blocks of 64 columns: 262,144 bytes whole, 65,536 a
    shard of four, against a limit of 100,000 (half of a device of
    200,000 bytes). Held on a 4 x 1 mesh, streamed on a 1 x 1 mesh."""
    rng = np.random.RandomState(3)
    x = rng.randn(256, 20).astype(np.float32)
    y = np.where(np.arange(3)[None] == rng.randint(0, 3, 256)[:, None],
                 1.0, -1.0).astype(np.float32)
    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: 200_000.0)
    counter = MetricsRegistry.get_or_create().counter
    with mesh_scope(make_mesh(jax.devices()[:devices])):
        PipelineEnv.get_or_create().clear_state()
        pipe = (Pipeline.gather(branches_of(64, 4)) >> VectorCombiner()
                ).and_then(BlockLeastSquaresEstimator(64, 1, 0.1),
                           ArrayDataset.from_numpy(x),
                           ArrayDataset.from_numpy(y)) >> MaxClassifier()
        out = pipe(ArrayDataset.from_numpy(x)).numpy()
        (model,) = [op for op in pipe.fit().to_pipeline().graph.operators
                    .values() if isinstance(op, BlockLinearMapper)]
    assert out.shape == (256,)
    assert counter(f"solve.{form}.fits").value == 1
    assert counter("solve.stream.fits").value == float(form == "stream")
    assert isinstance(model, StreamedBlockLinearMapper) == (form == "stream")
