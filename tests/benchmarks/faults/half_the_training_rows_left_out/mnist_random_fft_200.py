"""``half_the_training_rows_left_out`` for the configuration of four
chips, which reads its rows through the package's CSV loader as
``mnist_random_fft_32`` does: ``csv_loader.load_csv`` hands back the
first half of every training file and all of a test file. The
rehearsal's environment (four virtual CPU devices) is set before the
program is imported, as ``benchmarks.run`` sets it; the rest of a run is
driven as it is. The run has to come out not correct."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "mnist_random_fft_200.json")) as f:
    os.environ.update(json.load(f)["rehearsal"]["env"])

import numpy as np  # noqa: E402

import benchmarks.run as harness  # noqa: E402
from keystone_tpu.loaders import csv_loader  # noqa: E402

real = csv_loader.load_csv


def half_the_rows(path, dtype=np.float32):   # part of the batch left out
    rows = real(path, dtype)
    return rows if "test" in path else rows[: len(rows) // 2]


csv_loader.load_csv = half_the_rows
sys.exit(harness.main(sys.argv[1:]))
