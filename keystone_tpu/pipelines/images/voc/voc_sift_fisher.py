"""VOCSIFTFisher (reference
``pipelines/images/voc/VOCSIFTFisher.scala:29-159``):
PixelScaler -> GrayScaler -> SIFT -> [sampled ColumnPCA] -> [sampled GMM
Fisher vector] -> FloatToDouble -> MatrixVectorizer -> NormalizeRows ->
SignedHellinger -> NormalizeRows -> BlockLeastSquares(4096, 1, lambda) ->
mean-average-precision evaluation over the 20 VOC classes.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ....evaluation.mean_average_precision import (
    evaluate_mean_average_precision,
)
from ....loaders.csv_loader import LabeledData
from ....loaders.voc import NUM_CLASSES, VOCDataPath, VOCLabelPath, voc_loader
from ....nodes.images.core import GrayScaler, PixelScaler
from ....nodes.images.extractors import SIFTExtractor
from ....nodes.images.fisher_vector import FisherVector, GMMFisherVectorEstimator
from ....nodes.images.multilabel import (
    MultiLabeledImageExtractor,
    MultiLabelExtractor,
)
from ....nodes.learning import BlockLeastSquaresEstimator, ColumnPCAEstimator
from ....nodes.learning.gmm import GaussianMixtureModel
from ....nodes.learning.pca import BatchPCATransformer
from ....nodes.stats import NormalizeRows, SignedHellingerMapper
from ....nodes.stats.sampling import ColumnSampler
from ....nodes.util import (
    ClassLabelIndicatorsFromIntArrayLabels,
    FloatToDouble,
    MatrixVectorizer,
)
from ....parallel.dataset import Dataset
from ....workflow.common import Cacher


@dataclass
class SIFTFisherConfig:
    train_location: str = ""
    test_location: str = ""
    label_path: str = ""
    lam: float = 0.5
    desc_dim: int = 80
    vocab_size: int = 256
    scale_step: int = 0
    num_pca_samples: int = 1_000_000
    num_gmm_samples: int = 1_000_000
    block_size: int = 4096
    #: the two column samples and the GMM's initialisation follow it
    seed: int = 42
    # Precomputed-artifact loading (reference VOCSIFTFisher.scala:50-76):
    # when set, the loaded projection / GMM replace their estimators and
    # the fit is skipped.
    pca_file: Optional[str] = None
    gmm_mean_file: Optional[str] = None
    gmm_var_file: Optional[str] = None
    gmm_wts_file: Optional[str] = None


@dataclass
class Parts:
    """The app's pipelines, as ``run`` builds them (lazy: nothing is
    fitted until one is asked for its data)."""

    training_data: Dataset
    training_labels: Dataset
    gray: object              # PixelScaler >> GrayScaler >> Cacher
    sift_extractor: object    # gray >> SIFT
    pca_sample: object        # sampled descriptors, or None (pca_file)
    pca_featurizer: object    # sift >> fitted PCA >> Cacher
    gmm_sample: object        # sampled reduced descriptors, or None
    fisher_featurizer: object  # ... >> Fisher vector >> normalisations
    predictor: object


def images_and_labels(data):
    """``(images, label arrays)`` of what a caller hands over: the
    loader's dataset of ``MultiLabeledImage``, or the two already apart
    (``LabeledData``: images of different sizes as a ``RaggedDataset``,
    labels as rows of class ids padded with -1), as a caller has them
    who holds the images and fits again and again."""
    if isinstance(data, LabeledData):
        return data.data, data.labels
    return (MultiLabeledImageExtractor().apply_dataset(data),
            MultiLabelExtractor().apply_dataset(data))


def build(config: SIFTFisherConfig, train,
          sift_kwargs: Optional[dict] = None) -> Parts:
    training_data, label_ids = images_and_labels(train)
    label_grabber = (
        ClassLabelIndicatorsFromIntArrayLabels(NUM_CLASSES) >> Cacher())
    training_labels = label_grabber(label_ids).get()
    n_train = len(training_data)
    pca_samples_per_image = max(config.num_pca_samples // max(n_train, 1), 1)
    gmm_samples_per_image = max(config.num_gmm_samples // max(n_train, 1), 1)

    sift = SIFTExtractor(scale_step=config.scale_step,
                         **(sift_kwargs or {}))
    gray = PixelScaler() >> GrayScaler() >> Cacher()
    sift_extractor = gray >> sift

    # fit PCA/GMM on sampled branches, or substitute loaded CSV
    # artifacts and skip the fit; the with_data pipeline applies the
    # fitted transformer to the runtime path (the reference's
    # ``pca.fittedTransformer`` composition vs the ``pcaFile``/
    # ``gmmMeanFile`` cases, VOCSIFTFisher.scala:48-76). Both samples
    # and the GMM's initialisation follow ``config.seed``.
    pca_sample = gmm_sample = None
    if config.pca_file is not None:
        pca_featurizer = sift_extractor >> BatchPCATransformer(
            np.loadtxt(config.pca_file, delimiter=",", ndmin=2).T) >> Cacher()
    else:
        pca_sample = (sift_extractor >> ColumnSampler(
            pca_samples_per_image, seed=config.seed))(training_data)
        pca_featurizer = sift_extractor.and_then(
            ColumnPCAEstimator(config.desc_dim).with_data(pca_sample)
        ) >> Cacher()

    if config.gmm_mean_file is not None:
        fisher = pca_featurizer >> FisherVector(GaussianMixtureModel.load(
            config.gmm_mean_file, config.gmm_var_file, config.gmm_wts_file))
    else:
        gmm_sample = (pca_featurizer >> ColumnSampler(
            gmm_samples_per_image, seed=config.seed + 1))(training_data)
        fisher = pca_featurizer.and_then(GMMFisherVectorEstimator(
            config.vocab_size, seed=config.seed).with_data(gmm_sample))
    fisher_featurizer = fisher >> FloatToDouble() >> MatrixVectorizer() \
        >> NormalizeRows() >> SignedHellingerMapper() >> NormalizeRows() \
        >> Cacher()

    predictor = fisher_featurizer.and_then(
        BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
        training_data,
        training_labels,
    )
    return Parts(training_data, training_labels, gray, sift_extractor,
                 pca_sample, pca_featurizer, gmm_sample, fisher_featurizer,
                 predictor)


def run(config: SIFTFisherConfig, train: Optional[Dataset] = None,
        test: Optional[Dataset] = None,
        sift_kwargs: Optional[dict] = None):
    """Returns (pipeline, per-class AP array)."""
    start = time.time()
    if train is None:
        train = voc_loader(
            VOCDataPath(config.train_location, "VOCdevkit/VOC2007/JPEGImages/"),
            VOCLabelPath(config.label_path))
    if test is None:
        test = voc_loader(
            VOCDataPath(config.test_location, "VOCdevkit/VOC2007/JPEGImages/"),
            VOCLabelPath(config.label_path))

    predictor = build(config, train, sift_kwargs).predictor
    test_data, test_actuals = images_and_labels(test)
    predictions = predictor(test_data).get()
    ap = evaluate_mean_average_precision(
        test_actuals, predictions, NUM_CLASSES)
    print(f"TEST APs are: {','.join(str(a) for a in ap)}")
    print(f"TEST MAP is: {float(np.mean(ap))}")
    print(f"Pipeline took {time.time() - start:.1f} s")
    return predictor, ap


def main(argv=None):
    p = argparse.ArgumentParser("VOCSIFTFisher")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--labelPath", required=True)
    p.add_argument("--lambda", dest="lam", type=float, default=0.5)
    p.add_argument("--descDim", type=int, default=80)
    p.add_argument("--vocabSize", type=int, default=256)
    p.add_argument("--scaleStep", type=int, default=0)
    p.add_argument("--numPcaSamples", type=int, default=1_000_000)
    p.add_argument("--numGmmSamples", type=int, default=1_000_000)
    p.add_argument("--seed", type=int, default=42)
    for flag in ("pcaFile", "gmmMeanFile", "gmmVarFile", "gmmWtsFile"):
        p.add_argument("--" + flag, default=None)
    a = p.parse_args(argv)
    run(SIFTFisherConfig(
        a.trainLocation, a.testLocation, a.labelPath, a.lam, a.descDim,
        a.vocabSize, a.scaleStep, a.numPcaSamples, a.numGmmSamples,
        seed=a.seed, pca_file=a.pcaFile, gmm_mean_file=a.gmmMeanFile,
        gmm_var_file=a.gmmVarFile, gmm_wts_file=a.gmmWtsFile))


if __name__ == "__main__":
    main()
