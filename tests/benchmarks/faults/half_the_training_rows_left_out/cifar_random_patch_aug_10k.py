"""``half_the_training_rows_left_out`` for a configuration that reads
CIFAR binary records: ``cifar_loader.load_cifar_numpy`` hands back the
first half of the training batch and all of the test batch, and the rest
of a run is driven as it is. The run has to come out not correct."""
import importlib
import sys

import benchmarks.run as harness

cifar_loader = importlib.import_module("keystone_tpu.loaders.cifar_loader")
real = cifar_loader.load_cifar_numpy


def half_the_rows(path, packed=False):        # part of the batch left out
    images, labels = real(path, packed)
    keep = len(labels) if "test" in path else len(labels) // 2
    return images[:keep], labels[:keep]


cifar_loader.load_cifar_numpy = half_the_rows
sys.exit(harness.main(sys.argv[1:]))
