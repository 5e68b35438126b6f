"""The featurizer's share of its roofline: the least time the chip could
take for the dense-product transforms of one fit's rows
(``counts/dense_dft.py``; compute-bound at these shapes) over
``featurize_dev_ms.refit``, the device time of every program but the
solve. Apply and evaluation are in that time and not in the count, so
they can only lower the share."""
from benchmarks.layers import _common

SHAPE_KEYS = ("train_rows", "test_rows", "image_size", "features_per_fft",
              "num_ffts")


def read(run):
    ms = _common.load_reader("featurize_dev_ms.refit").read(run)
    if not ms or run.peaks is None or any(k not in run.cfg for k in SHAPE_KEYS):
        return None
    cfg = run.cfg
    least, _bound = _common.load_counts("dense_dft").roofline_seconds(
        run.peaks, cfg["train_rows"] + cfg["test_rows"], cfg["image_size"],
        cfg["features_per_fft"], cfg["num_ffts"])
    return 100.0 * 1e3 * least / ms
