"""TimitPipeline (reference ``pipelines/speech/TimitPipeline.scala:21-148``):
gather(numCosines x CosineRandomFeatures(440 -> 4096, Gaussian or Cauchy))
-> VectorCombiner -> BlockLeastSquares(4096, numEpochs, lambda) ->
MaxClassifier over 147 phone classes.

Which path the optimizer takes (``workflow/optimizer/stream_gather.py``;
from rows x branches x 4,096 x 4 bytes against the device's memory, with
no switch): while the gathered matrix is at most half the device's
memory it is materialised and ``BlockLeastSquaresEstimator`` slices it
into blocks; over that the estimator is handed the raw rows and the
branches, makes each 4,096-wide block when its sweep reaches it, and the
fitted model applies blockwise to raw rows too. At the published 50
branches a row of features is 819 KB, so on a 16 GB chip the gather is
materialised up to 10,485 rows and streamed beyond. The two fits agree
to rounding while every block's Cholesky factor is healthy (the streamed
model's ``health`` says whether); a block that is numerically singular
at ``lam`` 0 is recovered by an eigenvalue clamp in the one and a raised
diagonal in the other, answers about 1e-3 apart, so give such data a
``lam`` over 0.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

from ...evaluation.multiclass import evaluate_multiclass
from ...loaders.timit import (
    NUM_CLASSES,
    TIMIT_DIMENSION,
    TimitFeaturesData,
    timit_features_loader,
)
from ...nodes.learning import BlockLeastSquaresEstimator
from ...nodes.stats import CosineRandomFeatures
from ...nodes.util import (
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
    VectorCombiner,
)
from ...workflow.pipeline import Pipeline

NUM_COSINE_FEATURES = 4096


@dataclass
class TimitConfig:
    train_data_location: str = ""
    train_labels_location: str = ""
    test_data_location: str = ""
    test_labels_location: str = ""
    num_cosines: int = 50
    gamma: float = 0.05555
    rf_type: str = "gaussian"  # or "cauchy"
    lam: float = 0.0
    num_epochs: int = 5
    seed: int = 123
    num_cosine_features: int = NUM_COSINE_FEATURES


def build_featurizer(config: TimitConfig,
                     input_dim: int = TIMIT_DIMENSION) -> Pipeline:
    branches = CosineRandomFeatures.create_branches(
        config.num_cosines,
        input_dim,
        config.num_cosine_features,
        config.gamma,
        w_dist="cauchy" if config.rf_type == "cauchy" else "gaussian",
        b_dist="uniform",
        seed=config.seed,
    )
    return Pipeline.gather(branches) >> VectorCombiner()


def run(config: TimitConfig, data: Optional[TimitFeaturesData] = None,
        num_classes: int = NUM_CLASSES, input_dim: Optional[int] = None):
    """Returns (pipeline, test_metrics)."""
    start = time.time()
    if data is None:
        data = timit_features_loader(
            config.train_data_location, config.train_labels_location,
            config.test_data_location, config.test_labels_location)
    if input_dim is None:
        # TIMIT proper is 440-dim; infer so smaller feature sets also run
        input_dim = int(data.train.data.data.shape[-1])

    labels = ClassLabelIndicatorsFromIntLabels(num_classes)(
        data.train.labels)
    predictor = (
        build_featurizer(config, input_dim).and_then(
            BlockLeastSquaresEstimator(
                config.num_cosine_features, config.num_epochs, config.lam),
            data.train.data,
            labels,
        )
        >> MaxClassifier()
    )

    test_eval = evaluate_multiclass(
        predictor(data.test.data), data.test.labels, num_classes)
    print(f"TEST Error is {100 * test_eval.total_error:.2f}%")
    print(f"Pipeline took {time.time() - start:.1f} s")
    return predictor, test_eval


def main(argv=None):
    p = argparse.ArgumentParser("Timit")
    p.add_argument("--trainDataLocation", required=True)
    p.add_argument("--trainLabelsLocation", required=True)
    p.add_argument("--testDataLocation", required=True)
    p.add_argument("--testLabelsLocation", required=True)
    p.add_argument("--numCosines", type=int, default=50)
    p.add_argument("--gamma", type=float, default=0.05555)
    p.add_argument("--rfType", default="gaussian")
    p.add_argument("--lambda", dest="lam", type=float, default=0.0)
    p.add_argument("--numEpochs", type=int, default=5)
    a = p.parse_args(argv)
    run(TimitConfig(
        a.trainDataLocation, a.trainLabelsLocation, a.testDataLocation,
        a.testLabelsLocation, a.numCosines, a.gamma, a.rfType, a.lam,
        a.numEpochs))


if __name__ == "__main__":
    main()
