"""The accuracy surrogate must stay informative (VERDICT r2 next#2).

The procedurally generated CIFAR stand-in
(loaders.cifar_surrogate.make_surrogate_cifar) is built so that the RandomPatchCifar pipeline's conv+pool featurization
beats the raw-pixel LinearPixels baseline by a wide margin, with BOTH
errors off the 0%/100% rails — a numerics regression anywhere in the
patch-whitening / convolution / pooling / solver path collapses the gap
and fails this test, where a saturated 0.00% metric would hide it
(reference anchor: RandomPatchCifar.scala:59-69 targets the published
~85%-accuracy CIFAR pipeline; this sandbox has no real CIFAR to
report against that bar).
"""
import numpy as np
import pytest


@pytest.mark.slow
def test_randompatch_beats_linear_pixels_on_surrogate():
    from keystone_tpu.loaders.cifar_surrogate import make_surrogate_cifar
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.cifar.random_patch_cifar import (
        RandomCifarConfig,
        run,
    )
    from keystone_tpu.pipelines.images.cifar.linear_pixels import (
        LinearPixelsConfig,
        run as run_linear,
    )

    (tr_x, tr_y), (te_x, te_y) = make_surrogate_cifar(768, 192)
    train = LabeledData(ArrayDataset.from_numpy(tr_x),
                        ArrayDataset.from_numpy(tr_y.astype(np.int32)))
    test = LabeledData(ArrayDataset.from_numpy(te_x),
                       ArrayDataset.from_numpy(te_y.astype(np.int32)))

    _, _, rp_eval = run(RandomCifarConfig(num_filters=48, lam=10.0, seed=0),
                        train=train, test=test)
    _, _, lin_eval = run_linear(LinearPixelsConfig(lam=10.0),
                                train=train, test=test)
    rp_err = float(rp_eval.total_error)
    lin_err = float(lin_eval.total_error)

    # non-saturated: both sit strictly inside the informative band
    assert 0.02 < rp_err < 0.90, rp_err
    assert 0.30 < lin_err < 0.98, lin_err
    # the gap IS the signal: featurization must buy a wide margin
    assert rp_err < lin_err - 0.15, (rp_err, lin_err)
