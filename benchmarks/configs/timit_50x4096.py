"""TimitPipeline through the app's public ``run()``: frames written from
the seed as CSVs, read once by the package's CSV loader (``hold``), and
every fit handed new datasets of the held rows (``datasets``). The
feature seed follows ``--seed``: the cosine branches' ``W`` and ``b`` are
program arguments, so another seed compiles nothing.

The configuration's file may state ``device_memory_bytes`` in its
``rehearsal`` block: the CPU rehearsal then reckons the optimizer's
choice (materialise the gather, or hand the branches to the solver)
against that figure, so that the tiny size takes the path the chip takes
at the timed size. A measured run states none and the device's own
figure decides; which path each fit took is counted and compared.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from benchmarks.datagen import timit_frames

#: the program's counters a fit is held to (``real_fit`` in the file)
COUNTERS = {"blocks_generated": "solve.stream.blocks_generated",
            "stream_fits": "solve.stream.fits",
            "materialised_fits": "solve.materialised.fits"}
#: by how much each rose in every fit of this process, oldest first
#: (``layers/blocks_generated.timit.py`` reads the window's)
FIT_COUNTS = []


def block_health(mapper, blocks):
    """``(unhealthy blocks, smallest pivot ratio)`` of a fitted model.
    lambda 0 rests on this check, so a model that cannot say how its
    factors fared (no ``health``) proves nothing: every block counts as
    unhealthy."""
    health = getattr(mapper, "health", None)
    if health is None:
        return float(blocks), float("nan")
    oks, ratios = (np.asarray(part) for part in health)
    return float(np.sum(~oks)), float(np.min(ratios))


class Job:
    def __init__(self, cfg, seed, workdir):
        (self.train, self.test) = timit_frames.make_frames(
            cfg["train_rows"], cfg["test_rows"], seed, cfg["input_dim"],
            cfg["num_classes"])
        self.cfg, self.seed = cfg, seed
        self.items = cfg["train_rows"] + cfg["test_rows"]
        self.train_path = os.path.join(workdir, "train-frames.csv")
        self.test_path = os.path.join(workdir, "test-frames.csv")
        for path, (rows, labels) in ((self.train_path, self.train),
                                     (self.test_path, self.test)):
            timit_frames.write_csv(path, rows, labels, cfg["label_offset"])
        if "device_memory_bytes" in cfg:
            from keystone_tpu.analysis import resources

            stated = float(cfg["device_memory_bytes"])
            resources.device_memory_bytes = lambda free=False: stated

    def load(self):
        from keystone_tpu.loaders import csv_loader

        off = self.cfg["label_offset"]
        return (csv_loader.csv_labeled_loader(self.train_path, label_offset=off),
                csv_loader.csv_labeled_loader(self.test_path, label_offset=off))

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

    def app_config(self):
        from keystone_tpu.pipelines.speech.timit import TimitConfig

        cfg = self.cfg
        return TimitConfig(
            num_cosines=cfg["num_cosines"], gamma=cfg["gamma"],
            rf_type=cfg["rf_type"], lam=cfg["lambda"],
            num_epochs=cfg["num_epochs"], seed=self.seed,
            num_cosine_features=cfg["num_cosine_features"])

    def fit(self, loaded):
        from keystone_tpu.loaders.timit import TimitFeaturesData
        from keystone_tpu.observability.metrics import MetricsRegistry
        from keystone_tpu.pipelines.speech.timit import run

        counter = MetricsRegistry.get_or_create().counter
        before = {k: counter(name).value for k, name in COUNTERS.items()}
        train, test = loaded
        pipeline, test_eval = run(
            self.app_config(), data=TimitFeaturesData(train=train, test=test),
            num_classes=self.cfg["num_classes"])
        out = {"pipeline": pipeline, "train": train,
               "test_error": float(test_eval.total_error)}
        for k, name in COUNTERS.items():
            out[k] = float(counter(name).value - before[k])
        FIT_COUNTS.append({k: out[k] for k in COUNTERS})
        return out

    def answers(self, outcome):
        """What the last timed fit produced, on the host. ``fit()`` here
        is answered from the prefix-state table the timed fit filled;
        the training error is taken here, after the window (the app
        evaluates the test rows only)."""
        from keystone_tpu.evaluation.multiclass import evaluate_multiclass
        from keystone_tpu.nodes.learning.linear import BlockLinearMapper

        fitted = outcome["pipeline"].fit()
        (mapper,) = [op for op in fitted.to_pipeline().graph.operators.values()
                     if isinstance(op, BlockLinearMapper)]
        train = outcome["train"]   # what the fit was handed, not self.train
        train_eval = evaluate_multiclass(
            fitted.apply(train.data), train.labels, self.cfg["num_classes"])
        unhealthy, min_ratio = block_health(mapper, self.cfg["num_cosines"])
        return dict(
            weights=np.asarray(mapper.weights),
            feature_means=np.asarray(mapper.feature_means),
            intercept=np.asarray(mapper.intercept),
            train_error=float(train_eval.total_error),
            test_error=outcome["test_error"], unhealthy_blocks=unhealthy,
            min_pivot_ratio=min_ratio,
            **{k: outcome[k] for k in COUNTERS})

    def reference_inputs(self):
        return {"train": self.train, "test": self.test,
                "feature_seed": self.seed}


def prepare(cfg, seed, workdir):
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator

    if not hasattr(BlockLeastSquaresEstimator, "fit_branches"):
        # a program from before the streamed block solve would try to
        # materialise rows x 204,800 floats; say so at once and exit
        gib = cfg["train_rows"] * cfg["num_cosines"] * cfg[
            "num_cosine_features"] * 4 / 2 ** 30
        print("benchmarks.configs.timit_50x4096: this program has no "
              f"streamed block solve and the gathered matrix is {gib:.1f} "
              "GiB: the configuration cannot run here", file=sys.stderr)
        raise SystemExit(4)
    return Job(cfg, seed, workdir)
