"""``one_crop_votes_for_its_image`` for the configuration whose test
images are scored ten crops each: the evaluator's vote
(``evaluation.augmented.vote``) takes an image's FIRST crop's scores for
every one of its crops, so nothing is averaged, and the rest of a run is
driven as it is. The run has to come out not correct by
``voted_scores_gap``: the voted error alone would not say (on images
that every crop classifies alike it does not move)."""
import sys

import numpy as np

import benchmarks.run as harness
from keystone_tpu.evaluation import augmented

averaged = augmented.vote


def first_copy_votes(names, predicted, actual_labels,
                     policy=augmented.AVERAGE_POLICY):
    names, scores = np.asarray(names), np.asarray(predicted)
    seen, first, group = np.unique(names, return_index=True,
                                   return_inverse=True)
    return averaged(names, scores[first][group], actual_labels, policy)


augmented.vote = first_copy_votes
sys.exit(harness.main(sys.argv[1:]))
