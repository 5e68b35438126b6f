"""``half_the_training_rows_left_out`` for a configuration that reads a
tar of JPEGs: ``voc_loader`` hands back the first half of the training
images and all of the test images, and the rest of a run is driven as
it is. The run has to come out not correct: the reference's own whole
chain, fitted on every image of the files, ranks the test images
otherwise (``test_error_gap``), and the program's own counts say that
fewer images went through (``fv_images_off``)."""
import importlib
import sys

import benchmarks.run as harness

voc = importlib.import_module("keystone_tpu.loaders.voc")
real = voc.voc_loader


def half_the_images(data_path, labels_path):   # part of the tar left out
    ds = real(data_path, labels_path)
    if "train" in data_path.images_dir_name:
        ds.items = ds.items[:len(ds.items) // 2]
    return ds


voc.voc_loader = half_the_images
sys.exit(harness.main(sys.argv[1:]))
