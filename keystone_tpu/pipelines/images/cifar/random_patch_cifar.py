"""RandomPatchCifar: the north-star pipeline.

Mirrors reference ``pipelines/images/cifar/RandomPatchCifar.scala:21-87``:
sample patches -> normalize + ZCA-whiten -> random whitened filters ->
Convolver -> SymmetricRectifier -> Pooler(sum) -> vectorize ->
StandardScaler -> BlockLeastSquares(4096, 1, lambda) -> MaxClassifier.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ....evaluation.multiclass import evaluate_multiclass
from ....loaders.cifar_loader import cifar_loader
from ....loaders.csv_loader import LabeledData
from ....nodes.images.core import FusedConvRectifyPool, WindowSampler
from ....nodes.learning import BlockLeastSquaresEstimator
from ....nodes.learning.zca import ZCAWhitener, ZCAWhitenerEstimator
from ....nodes.stats import StandardScaler
from ....nodes.stats.sampling import sample_rows
from ....nodes.util import (
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
    VectorCombiner,
)
from ....observability.timeline import flight_span
from ....ops.image_ops import normalize_rows
from ....workflow.common import Cacher
from ....workflow.pipeline import Pipeline

NUM_CLASSES = 10
IMAGE_SIZE = 32
NUM_CHANNELS = 3
WHITENER_SAMPLES = 100000


@dataclass
class RandomCifarConfig:
    train_location: str = ""
    test_location: str = ""
    num_filters: int = 100
    whitening_epsilon: float = 0.1
    patch_size: int = 6
    patch_steps: int = 1
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    lam: float = 0.0
    seed: int = 0
    #: the solver's block: the source's ``BlockLeastSquaresEstimator(4096,
    #: 1, lambda)``; not a flag of the command line (a caller of ``run()``
    #: may state a smaller one to drive the blockwise paths at a tiny size)
    block_size: int = 4096


def learn_filters(train_images, config: RandomCifarConfig):
    """The imperative filter-learning prefix
    (reference RandomPatchCifar.scala:41-57)."""
    with flight_span("learn_filters", "featurize", patches=WHITENER_SAMPLES,
                     filters=config.num_filters):
        return _learn_filters(train_images, config)


def _learn_filters(train_images, config: RandomCifarConfig):
    patch_extractor = WindowSampler(
        config.patch_steps, config.patch_size, WHITENER_SAMPLES,
        seed=config.seed)
    sample = patch_extractor(train_images).get()
    # normalize ON DEVICE, then download the sampled matrix once for the
    # driver-local ZCA fit (reference collects the sample the same way)
    base_filter_mat = np.asarray(
        normalize_rows(sample.data, 10.0)
    )[: sample.n]
    whitener = ZCAWhitenerEstimator(config.whitening_epsilon).fit_single(
        base_filter_mat
    )
    sampled = sample_rows(base_filter_mat, config.num_filters, seed=config.seed)
    unnorm = (sampled - whitener.means) @ whitener.whitener
    norms = np.sqrt(np.sum(unnorm**2, axis=1))
    filters = (unnorm / (norms + 1e-10)[:, None]) @ whitener.whitener.T
    return filters.astype(np.float32), whitener


def filters_a_block(config: RandomCifarConfig,
                    image_size: int = IMAGE_SIZE) -> int:
    """The filters whose features fill one solver block: a filter makes
    one column a pool and rectifier half, 2 x 2 x 2 = 8 at the default
    geometry, so 512; 1 x 1 x 2 on the augmented app's 24 x 24 crops, so
    2,048."""
    one = FusedConvRectifyPool(
        np.zeros((1, 1), np.float32), image_size, config.patch_size,
        NUM_CHANNELS, config.pool_stride, config.pool_size, config.alpha)
    return max(1, config.block_size // one.columns_a_filter())


def build_pipeline(
    filters: np.ndarray,
    whitener: ZCAWhitener,
    config: RandomCifarConfig,
    train_images,
    train_labels,
):
    return build_scorer(filters, whitener, config, train_images,
                        train_labels) >> MaxClassifier()


def build_scorer(
    filters: np.ndarray,
    whitener: ZCAWhitener,
    config: RandomCifarConfig,
    train_images,
    train_labels,
    image_size: int = IMAGE_SIZE,
):
    """Images of ``image_size`` a side to a score a class: what
    ``build_pipeline`` classifies by, and what RandomPatchCifarAugmented
    averages over an image's crops."""
    # one fused Pallas kernel on TPU (conv/rectify/pool stay in VMEM,
    # ~2x featurization throughput); the node itself composes the plain
    # XLA ops on other backends
    def conv(rows):
        return FusedConvRectifyPool(
            rows, image_size, config.patch_size, NUM_CHANNELS,
            config.pool_stride, config.pool_size, config.alpha,
            whitener=whitener)

    step = filters_a_block(config, image_size)
    if len(filters) <= step:
        featurizer = conv(filters) >> Cacher("features")
    else:
        # one branch a solver block (the last may be narrower): wider
        # than the device holds, the optimizer hands the branches to the
        # solver, which makes each block when its sweep reaches it
        # (workflow/optimizer/stream_gather.py), and a generation of the
        # design matrix convolves every filter once. Columns are those
        # of one node over all filters, branch by branch: (branch, pool,
        # rectifier half, filter), where one node has (pool, half,
        # filter)
        featurizer = Pipeline.gather([
            conv(filters[i:i + step]) for i in range(0, len(filters), step)
        ]) >> VectorCombiner() >> Cacher("features")
    return (
        featurizer.and_then(StandardScaler(), train_images)
        .and_then(
            BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
            train_images,
            train_labels,
        )
    )


def run(config: RandomCifarConfig, train: Optional[LabeledData] = None,
        test: Optional[LabeledData] = None):
    start = time.time()
    if train is None:
        train = cifar_loader(config.train_location)
    if test is None:
        test = cifar_loader(config.test_location)

    train_labels = (
        ClassLabelIndicatorsFromIntLabels(NUM_CLASSES) >> Cacher("labels")
    )(train.labels)

    filters, whitener = learn_filters(train.data, config)
    pipeline = build_pipeline(filters, whitener, config, train.data, train_labels)

    train_eval = evaluate_multiclass(pipeline(train.data), train.labels, NUM_CLASSES)
    test_eval = evaluate_multiclass(pipeline(test.data), test.labels, NUM_CLASSES)
    print(f"Training error is: {train_eval.total_error:.4f}")
    print(f"Test error is: {test_eval.total_error:.4f}")
    print(f"Pipeline took {time.time() - start:.1f} s")
    return pipeline, train_eval, test_eval


def main(argv=None):
    p = argparse.ArgumentParser("RandomPatchCifar")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--numFilters", type=int, default=100)
    p.add_argument("--whiteningEpsilon", type=float, default=0.1)
    p.add_argument("--patchSize", type=int, default=6)
    p.add_argument("--patchSteps", type=int, default=1)
    p.add_argument("--poolSize", type=int, default=14)
    p.add_argument("--poolStride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--lambda", dest="lam", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    a = p.parse_args(argv)
    run(
        RandomCifarConfig(
            train_location=a.trainLocation,
            test_location=a.testLocation,
            num_filters=a.numFilters,
            whitening_epsilon=a.whiteningEpsilon,
            patch_size=a.patchSize,
            patch_steps=a.patchSteps,
            pool_size=a.poolSize,
            pool_stride=a.poolStride,
            alpha=a.alpha,
            lam=a.lam,
            seed=a.seed,
        )
    )


if __name__ == "__main__":
    main()
