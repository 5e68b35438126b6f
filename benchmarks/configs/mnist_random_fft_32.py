"""MnistRandomFFT through the app's public ``run()``: CSVs written from
the seed, loaded by the app's loader, fitted and evaluated by the app.
A traffic mix that fits again and again from memory reads the files once
(``hold``) and hands every fit new datasets of the held rows
(``datasets``)."""
from __future__ import annotations

import os

from benchmarks.datagen import mnist_csv


class Job:
    def __init__(self, cfg, seed, workdir):
        (self.train, self.test) = mnist_csv.make_mnist(
            cfg["train_rows"], cfg["test_rows"], seed, cfg["num_classes"])
        self.cfg, self.seed = cfg, seed
        self.items = cfg["train_rows"] + cfg["test_rows"]
        self.train_path = os.path.join(workdir, "train-mnist.csv")
        self.test_path = os.path.join(workdir, "test-mnist.csv")
        for path, (pixels, labels) in ((self.train_path, self.train),
                                       (self.test_path, self.test)):
            mnist_csv.write_csv(path, pixels, labels, cfg["label_offset"])

    def load(self):
        from keystone_tpu.loaders.csv_loader import csv_labeled_loader

        off = self.cfg["label_offset"]
        return (csv_labeled_loader(self.train_path, label_offset=off),
                csv_labeled_loader(self.test_path, label_offset=off))

    def hold(self):
        """The loader's rows, kept on the host."""
        return [(part.data.numpy(), part.labels.numpy())
                for part in self.load()]

    def datasets(self, held):
        """New datasets of the held rows: host to device, and nothing
        the prefix-state table has met."""
        from keystone_tpu.loaders.csv_loader import LabeledData
        from keystone_tpu.parallel.dataset import ArrayDataset

        return tuple(LabeledData(data=ArrayDataset.from_numpy(rows),
                                 labels=ArrayDataset.from_numpy(labels))
                     for rows, labels in held)

    def fit(self, loaded):
        from keystone_tpu.pipelines.images.mnist.random_fft import (
            MnistRandomFFTConfig, run)

        train, test = loaded
        pipeline, train_eval, test_eval = run(MnistRandomFFTConfig(
            num_ffts=self.cfg["num_ffts"], block_size=self.cfg["block_size"],
            lam=self.cfg["lambda"], seed=self.cfg["sign_seed"]),
            train=train, test=test)
        return {"pipeline": pipeline,
                "train_error": float(train_eval.total_error),
                "test_error": float(test_eval.total_error)}

    def answers(self, outcome):
        """What the last timed fit produced, on the host. ``fit()`` here
        is answered from the prefix-state table the timed fit filled."""
        from benchmarks.configs._fitted import linear_model

        return dict(linear_model(outcome["pipeline"]),
                    train_error=outcome["train_error"],
                    test_error=outcome["test_error"])

    def reference_inputs(self):
        return {"train": self.train, "test": self.test,
                "sign_seed": self.cfg["sign_seed"]}


def prepare(cfg, seed, workdir):
    return Job(cfg, seed, workdir)
