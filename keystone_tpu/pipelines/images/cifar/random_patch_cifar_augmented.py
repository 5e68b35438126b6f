"""RandomPatchCifarAugmented (reference
``pipelines/images/cifar/RandomPatchCifarAugmented.scala:25-154``):
RandomPatchCifar plus train-time augmentation (random 24x24 crops +
random horizontal flips, labels repeated to match) and test-time
augmentation (center/corner crops with flips, predictions grouped per
source image by the AugmentedExamplesEvaluator).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ....evaluation.augmented import AVERAGE_POLICY, evaluate_augmented
from ....loaders.cifar_loader import cifar_loader
from ....loaders.csv_loader import LabeledData
from ....nodes.images.core import (
    CenterCornerPatcher,
    ImageVectorizer,
    RandomFlipper,
    RandomPatcher,
)
from ....nodes.util import (
    ClassLabelIndicatorsFromIntLabels,
    LabelAugmenter,
)
from ....observability.timeline import flight_span
from ....workflow.common import Cacher
from .random_patch_cifar import (
    RandomCifarConfig,
    build_scorer,
    learn_filters,
)

NUM_CLASSES = 10
AUGMENT_IMG_SIZE = 24
FLIP_CHANCE = 0.5


@dataclass
class AugmentedConfig(RandomCifarConfig):
    num_random_patches_augment: int = 10
    pool_size: int = 14
    pool_stride: int = 13


def augment_train(config: AugmentedConfig, train: LabeledData):
    """Train-time augmentation (reference :65-77), on the device:
    ``num_random_patches_augment`` random crops an image, each flipped
    with ``FLIP_CHANCE``, and the labels' indicators repeated to match.
    Where a crop starts and whether it is flipped follow the seed and
    the row's index (``RandomPatcher.offsets``; a uniform draw a crop
    from ``PRNGKey(seed)``). A crop is kept as its row-major vector:
    the featurizer takes either, and the solver's sweeps take rows of
    vectors a chunk at a time where they are (``FusedConvRectifyPool.
    _image``)."""
    crops = len(train.data) * config.num_random_patches_augment
    with flight_span("augment", "featurize", rows=len(train.data),
                     crops=crops):
        images = ImageVectorizer().apply_dataset(RandomFlipper(
            FLIP_CHANCE, seed=config.seed).apply_dataset(RandomPatcher(
                config.num_random_patches_augment, AUGMENT_IMG_SIZE,
                AUGMENT_IMG_SIZE, seed=config.seed).apply_dataset(
                    train.data)))
        labels = (
            ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)
            >> LabelAugmenter(config.num_random_patches_augment)
            >> Cacher("labels")
        )(train.labels)
    return images, labels


def augment_test(test: LabeledData):
    """Test-time augmentation (reference :105-125): centre and four
    corners, each also flipped; ``(crops as vectors, id of the image a
    crop is of, its label)``."""
    patcher = CenterCornerPatcher(
        AUGMENT_IMG_SIZE, AUGMENT_IMG_SIZE, horizontal_flips=True)
    copies = patcher.patches_per_image
    with flight_span("augment", "featurize", rows=len(test.data),
                     crops=len(test.data) * copies):
        images = ImageVectorizer().apply_dataset(
            patcher.apply_dataset(test.data))
    ids = np.repeat(np.arange(len(test.data)), copies)
    labels = np.repeat(np.asarray(test.labels.numpy()).ravel(), copies)
    return images, ids, labels


def run(config: AugmentedConfig, train: Optional[LabeledData] = None,
        test: Optional[LabeledData] = None):
    """Returns (pipeline, test_metrics). The pipeline takes crops of
    ``AUGMENT_IMG_SIZE`` a side and gives a score a class; it is
    ``random_patch_cifar``'s (one fused branch a solver block, gathered,
    ``StandardScaler``, the block solver) at that image size, so that
    the optimizer hands its branches to the solver where their gather
    is too wide to hold."""
    start = time.time()
    if train is None:
        train = cifar_loader(config.train_location)
    if test is None:
        test = cifar_loader(config.test_location)

    filters, whitener = learn_filters(train.data, config)
    train_images_aug, train_labels_aug = augment_train(config, train)
    pipeline = build_scorer(
        filters, whitener, config, train_images_aug, train_labels_aug,
        image_size=AUGMENT_IMG_SIZE) >> Cacher()

    test_images_aug, test_ids_aug, test_labels_aug = augment_test(test)
    preds = pipeline(test_images_aug).get()
    # group per source image and average (reference :127-131)
    test_eval = evaluate_augmented(
        test_ids_aug, preds, test_labels_aug, NUM_CLASSES, AVERAGE_POLICY)
    print(f"Test error is: {test_eval.total_error:.4f}")
    print(f"Pipeline took {time.time() - start:.1f} s")
    return pipeline, test_eval


def main(argv=None):
    p = argparse.ArgumentParser("RandomPatchCifarAugmented")
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
    p.add_argument("--numRandomPatchesAugment", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    a = p.parse_args(argv)
    run(AugmentedConfig(
        train_location=a.trainLocation, test_location=a.testLocation,
        num_filters=a.numFilters, whitening_epsilon=a.whiteningEpsilon,
        patch_size=a.patchSize, patch_steps=a.patchSteps,
        pool_size=a.poolSize, pool_stride=a.poolStride, alpha=a.alpha,
        lam=a.lam, num_random_patches_augment=a.numRandomPatchesAugment,
        seed=a.seed))


if __name__ == "__main__":
    main()
