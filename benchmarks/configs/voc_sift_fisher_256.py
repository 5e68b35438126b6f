"""VOCSIFTFisher through the app's public ``run()``: images written from
the seed as a tar of JPEGs and a labels CSV in the layout the package's
``voc_loader`` reads, read once by it (``hold``), and every fit handed
new datasets of the held images (``datasets``). The PCA, the GMM and
both column samples are fitted anew from ``--seed`` inside every fit
(``SIFTFisherConfig.seed``); the projection, the mixture and the model
are program arguments, so another seed compiles nothing that its image
sizes do not ask for, and those are met in the warming fit.

A fit returns numbers only: the fitted pipeline holds the descriptor
cache (gigabytes, sized from what the device has free), and a pipeline
kept from one fit to the next would halve the next one's cache. What
``correct`` compares is taken after the window from ONE MORE whole fit
of the same program on the same images (``answers``; every fit of one
seed computes the same thing, and ``fits_disagree`` holds them to it),
through ``voc_sift_fisher.build``, whose parts ``run()`` itself uses.

``BENCH_FEATURE_CONTROL=one_pass`` in the environment (the file's
``control`` sets it) is the control of the featurizers' gaps; the
program has no such switch, the job degrades what it can reach from
outside: the band products of dense SIFT and every product of the
posteriors, the EM step and the Fisher vector's moments at one bfloat16
pass, the TPU's default for float32 operands.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import sys

import numpy as np

from benchmarks.datagen import voc_images

#: the program's counters a fit is held to (``real_fit`` in the file)
COUNTERS = {"sift_images": "featurize.sift.images",
            "fv_images": "featurize.fv.images",
            "pca_fits": "featurize.pca.fits",
            "gmm_fits": "featurize.gmm.fits",
            "gmm_iterations": "featurize.gmm.iterations"}
MAKERS = {"sift": {"einsum": "featurize.sift.einsum"},
          "fv": {"pallas": "featurize.fv.pallas",
                 "einsum": "featurize.fv.einsum"}}
#: by how much each rose in every fit of this process, oldest first
#: (``layers/sift_passes.voc.py`` reads the window's)
FIT_COUNTS = []
SIZE_KEYS = ("long_side", "common_sides", "short_side_min")


def degrade_features(how):
    if how != "one_pass":
        raise SystemExit(f"BENCH_FEATURE_CONTROL={how!r}: one_pass")
    import jax

    from keystone_tpu.nodes.images import fisher_vector
    from keystone_tpu.nodes.learning import gmm
    from keystone_tpu.ops import sift

    one = jax.lax.Precision.DEFAULT
    sift._PRECISION = gmm._PRECISION = fisher_vector._PRECISION = one


class Job:
    def __init__(self, cfg, seed, workdir):
        self.cfg, self.seed = cfg, seed
        self.items = cfg["train_rows"] + cfg["test_rows"]
        sizes = {k: cfg[k] for k in SIZE_KEYS}
        self.paths, self.labels, names = [], {}, []
        for part in ("train", "test"):
            images, self.labels[part] = voc_images.make_images(
                cfg[part + "_rows"], seed, part, **sizes)
            self.paths.append(os.path.join(workdir, part + ".tar"))
            names.extend(voc_images.write_tar(self.paths[-1], images, part))
        self.paths.append(os.path.join(workdir, "labels.csv"))
        voc_images.write_labels(
            self.paths[-1], names, self.labels["train"] + self.labels["test"])
        if "device_memory_bytes" in cfg:
            from keystone_tpu.analysis import resources

            stated = float(cfg["device_memory_bytes"])
            resources.device_memory_bytes = lambda free=False: stated
        if os.environ.get("BENCH_FEATURE_CONTROL"):
            degrade_features(os.environ["BENCH_FEATURE_CONTROL"])

    def load(self):
        from keystone_tpu.loaders.voc import (VOCDataPath, VOCLabelPath,
                                              voc_loader)

        return tuple(voc_loader(VOCDataPath(path, voc_images.PREFIX),
                                VOCLabelPath(self.paths[2]))
                     for path in self.paths[:2])

    def hold(self):
        """The loader's images and their labels (rows of class ids
        padded with -1, as the package's own extractor makes them), kept
        on the host (``answers`` fits on them once more)."""
        from keystone_tpu.nodes.images.multilabel import MultiLabelExtractor

        self.held = [([item.image for item in part.collect()],
                      MultiLabelExtractor().apply_dataset(part).numpy())
                     for part in self.load()]
        return self.held

    def datasets(self, held):
        """New datasets of the held images: host to device (bucketed by
        size, padded chunks), and nothing the prefix-state table has
        met."""
        from keystone_tpu.loaders.csv_loader import LabeledData
        from keystone_tpu.parallel.dataset import ArrayDataset
        from keystone_tpu.parallel.ragged import RaggedDataset

        return tuple(LabeledData(data=RaggedDataset.from_items(images),
                                 labels=ArrayDataset.from_numpy(labels))
                     for images, labels in held)

    def app_config(self):
        from keystone_tpu.pipelines.images.voc.voc_sift_fisher import (
            SIFTFisherConfig)

        cfg = self.cfg
        return SIFTFisherConfig(
            lam=cfg["lambda"], desc_dim=cfg["desc_dim"],
            vocab_size=cfg["vocab_size"], scale_step=cfg["scale_step"],
            num_pca_samples=cfg["num_pca_samples"],
            num_gmm_samples=cfg["num_gmm_samples"],
            block_size=cfg["block_size"], seed=self.seed)

    def fit(self, loaded):
        from keystone_tpu.observability.metrics import MetricsRegistry
        from keystone_tpu.pipelines.images.voc.voc_sift_fisher import run

        counter = MetricsRegistry.get_or_create().counter
        before = {k: counter(name).value for k, name in COUNTERS.items()}
        train, test = loaded
        _pipeline, ap = run(self.app_config(), train, test)
        out = {"map": float(np.mean(ap))}
        for k, name in COUNTERS.items():
            out[k] = float(counter(name).value - before[k])
        FIT_COUNTS.append({k: out[k] for k in COUNTERS})
        # the fitted pipeline goes, and what it holds on the device with
        # it (the descriptor cache is gigabytes, and a pipeline's graph
        # is cyclic garbage until a collection): inside the timed fit,
        # as a caller pays for it who fits again at once
        del _pipeline, train, test, loaded
        gc.collect()
        return out

    def _one_more_fit(self, config):
        """A whole fit through ``build``: what it leaves on the host,
        its fitted projection, mixture and model, and its cached
        grayscale training images. Everything else it made dies with
        this frame and the state table."""
        from keystone_tpu.nodes.images.fisher_vector import FisherVector
        from keystone_tpu.nodes.learning.linear import BlockLinearMapper
        from keystone_tpu.nodes.learning.pca import BatchPCATransformer
        from keystone_tpu.pipelines.images.voc import voc_sift_fisher as app
        from keystone_tpu.workflow.env import PipelineEnv
        from keystone_tpu.workflow.expression import TransformerExpression

        env = PipelineEnv.get_or_create()
        env.clear_state()
        train, test = self.datasets(
            getattr(self, "held", None) or self.hold())
        parts = app.build(config, train)
        out = dict(
            test_scores=np.asarray(parts.predictor(test.data).get().numpy()),
            train_design=np.asarray(
                parts.fisher_featurizer(parts.training_data).get().numpy()),
            test_design=np.asarray(
                parts.fisher_featurizer(test.data).get().numpy()),
            train_labels=np.asarray(parts.training_labels.numpy()))
        fitted = {type(e.get()): e.get() for e in env.state.values()
                  if isinstance(e, TransformerExpression) and e.computed}
        gray = parts.gray(parts.training_data).get()
        assert not any(stages for _, stages in gray.parts), \
            "the grayscale images are not cached"
        return (out, fitted[BatchPCATransformer], fitted[FisherVector].gmm,
                fitted[BlockLinearMapper], gray)

    def answers(self, outcome):
        """One more whole fit, its parts kept: the fitted projection,
        mixture and model, both design matrices and the test scores.
        Then, with that fit's descriptor cache let go (the device is
        nearly full while it lives), from the fitted pipeline's own
        nodes over its cached grayscale images: both column samples
        again (the same seeds, the same draws) and, for
        ``sampled_images`` training images (whole chunks of every
        bucket, as the timed programs take them, images of sizes not
        yet seen first), the descriptors and the reduced descriptors."""
        from keystone_tpu.loaders.voc import NUM_CLASSES
        from keystone_tpu.nodes.images.extractors import SIFTExtractor
        from keystone_tpu.nodes.stats.sampling import ColumnSampler
        from keystone_tpu.observability.metrics import MetricsRegistry
        from keystone_tpu.workflow.env import PipelineEnv

        config = self.app_config()
        out, pca, gmm, model, gray = self._one_more_fit(config)
        PipelineEnv.get_or_create().clear_state()
        gc.collect()   # the fit's descriptor cache goes; ``gray`` stays

        # the two samples, drawn again as build() draws them
        sift = SIFTExtractor(scale_step=config.scale_step)
        per_image = {k: max(getattr(config, f"num_{k}_samples")
                            // len(gray), 1) for k in ("pca", "gmm")}
        described = sift.apply_dataset(gray)
        out["pca_sample"] = np.asarray(ColumnSampler(
            per_image["pca"], seed=config.seed).apply_dataset(
                described).numpy())
        out["gmm_sample"] = np.asarray(ColumnSampler(
            per_image["gmm"], seed=config.seed + 1).apply_dataset(
                pca.apply_dataset(described)).numpy())

        # whole chunks, two a bucket; of their images, new sizes first
        chunks, per_bucket = [], {}
        for chunk, _ in gray.parts:
            seen = per_bucket.setdefault(chunk.data.shape, [])
            if len(seen) < 2:
                seen.append(len(chunks))
                chunks.append(chunk)
        slots = [(c, int(s)) for c, chunk in enumerate(chunks)
                 for s in np.flatnonzero(chunk.real)]
        met, first, rest = set(), [], []
        for c, s in slots:
            size = tuple(chunks[c].extent[s])
            (rest if size in met else first).append((c, s))
            met.add(size)
        picked = sorted((first + rest)[:self.cfg["sampled_images"]])
        describe, project = sift.chunk_stage(), pca.chunk_stage()
        sampled = []
        for c in sorted({c for c, _ in picked}):
            chunk = describe(chunks[c])
            reduced = np.asarray(project(chunk).data)
            raw = np.asarray(chunk.data)
            for _, s in (p for p in picked if p[0] == c):
                keep = chunk.mask[s]
                sampled.append({"id": int(chunks[c].ids[s]),
                                "bucket": tuple(chunks[c].data.shape[1:3]),
                                "descriptors": raw[s][:, keep],
                                "reduced": reduced[s][:, keep]})
        counter = MetricsRegistry.get_or_create().counter
        ran = {stage: sorted(k for k, name in names.items()
                             if counter(name).value)
               for stage, names in MAKERS.items()}
        return dict(
            out, sampled=sampled, pca_mat=np.asarray(pca.pca_mat),
            gmm=(gmm.means, gmm.variances, gmm.weights),
            gmm_initial=gmm.initial, gmm_updates=gmm.updates,
            weights=np.asarray(model.weights),
            feature_means=np.asarray(model.feature_means),
            intercept=np.asarray(model.intercept),
            num_classes=NUM_CLASSES, maker=ran, **outcome)

    def reference_inputs(self):
        """The files' own images (decoded here, not by the package's
        loader) and the labels they were written with."""
        return {"train": (voc_images.read_tar(self.paths[0]),
                          self.labels["train"]),
                "test": (voc_images.read_tar(self.paths[1]),
                         self.labels["test"]),
                "seed": self.seed}


def prepare(cfg, seed, workdir):
    if importlib.util.find_spec("keystone_tpu.parallel.ragged") is None:
        # a program from before images of different sizes could share a
        # program would compile a program an image size inside the
        # window, image by image; say so at once and exit
        print("benchmarks.configs.voc_sift_fisher_256: this program has no "
              "dataset of items whose sizes differ (keystone_tpu.parallel."
              "ragged): it would run dense SIFT one image and one compile a "
              "size at a time. The configuration cannot run here",
              file=sys.stderr)
        raise SystemExit(4)
    return Job(cfg, seed, workdir)
