"""``half_the_training_rows_left_out`` for a configuration that reads its
rows through the package's CSV loader: ``csv_loader.load_csv`` hands
back the first half of every training file and all of a test file, and
the rest of a run is driven as it is (``--rehearse`` skips the harness's
look for a chip). The run has to come out not correct."""
import sys

import numpy as np

import benchmarks.run as harness
from keystone_tpu.loaders import csv_loader

real = csv_loader.load_csv


def half_the_rows(path, dtype=np.float32):   # part of the batch left out
    rows = real(path, dtype)
    return rows if "test" in path else rows[: len(rows) // 2]


csv_loader.load_csv = half_the_rows
sys.exit(harness.main(sys.argv[1:]))
