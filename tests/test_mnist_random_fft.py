"""End-to-end MnistRandomFFT on synthetic separable data (the reference's
integration test is the app itself, README.md:15-28)."""
import numpy as np

from keystone_tpu.evaluation.multiclass import evaluate_multiclass
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.pipelines.images.mnist.random_fft import (
    MnistRandomFFTConfig,
    run,
)


CENTERS = np.random.RandomState(42).randn(10, 784).astype(np.float32) * 2.0


def synthetic_mnist(n, seed):
    """Linearly separable 784-dim 10-class blobs (shared class centers)."""
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 10, size=n)
    X = CENTERS[labels] + 0.5 * rng.randn(n, 784).astype(np.float32)
    return LabeledData(
        data=ArrayDataset.from_numpy(X.astype(np.float32)),
        labels=ArrayDataset.from_numpy(labels.astype(np.int32)),
    )


def test_mnist_random_fft_end_to_end():
    train = synthetic_mnist(400, seed=0)
    test = synthetic_mnist(100, seed=1)
    config = MnistRandomFFTConfig(
        num_ffts=2, block_size=512, lam=10.0, seed=0
    )
    pipeline, train_eval, test_eval = run(config, train=train, test=test)
    # Separable blobs through random features must be nearly perfect
    assert train_eval.total_error < 0.05
    assert test_eval.total_error < 0.15


def test_evaluator_exact_values():
    preds = np.array([0, 1, 1, 2, 2, 2])
    actual = np.array([0, 1, 2, 2, 2, 0])
    m = evaluate_multiclass(preds, actual, 3)
    assert m.total == 6
    assert m.confusion[0, 0] == 1 and m.confusion[0, 2] == 1
    assert m.confusion[2, 2] == 2 and m.confusion[2, 1] == 1
    assert abs(m.total_accuracy - 4 / 6) < 1e-9
    p, r, f1 = m.class_metrics(2)
    assert abs(p - 2 / 3) < 1e-9 and abs(r - 2 / 3) < 1e-9


def test_refit_at_the_rehearsal_size_takes_the_dense_path_and_compiles_nothing():
    """The benchmark cell's pipeline at its CPU rehearsal's size (8
    branches, 1,024-wide blocks, 3,072 + 512 MNIST-shaped rows): the first
    fit traces every PaddedFFT branch onto the dense product, and a refit
    on new rows traces and compiles nothing."""
    from benchmarks.datagen import mnist_csv
    from keystone_tpu.observability.compilelog import compile_observatory
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.workflow.env import PipelineEnv

    def datasets(seed):
        return tuple(
            LabeledData(data=ArrayDataset.from_numpy(px.astype(np.float32)),
                        labels=ArrayDataset.from_numpy(labels))
            for px, labels in mnist_csv.make_mnist(3072, 512, seed))

    def traced():
        registry = MetricsRegistry.get_or_create()
        return {path: registry.counter(f"featurize.padded_fft.{path}").value
                for path in ("dense", "fft")}

    # sign vectors no other test draws: baked into the fused program,
    # they make this fit trace it whatever ran before in the process
    config = MnistRandomFFTConfig(num_ffts=8, block_size=1024, seed=250025)
    observatory = compile_observatory()   # the cell's compiles_in_window
    train, test = datasets(1)
    _, first_train, first_test = run(config, train=train, test=test)
    first = traced()
    assert first["dense"] >= 8 and first["fft"] == 0
    assert observatory.count_total() > 0
    PipelineEnv.get_or_create().clear_state()

    compiles = observatory.count_total()
    train, test = datasets(2)
    _, again_train, again_test = run(config, train=train, test=test)
    assert observatory.count_total() == compiles
    assert traced() == first
    # 4,096 features on 3,072 rows all but interpolate: the training
    # rows are learnt, the test rows better than the chance of 0.9
    for fitted, held_out in ((first_train, first_test),
                             (again_train, again_test)):
        assert fitted.total_error < 0.05 and held_out.total_error < 0.8
