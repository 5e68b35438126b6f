"""RandomPatchCifar through the app's public ``run()``: images written
from the seed as CIFAR-10 binary records, read once by the package's
``cifar_loader`` (``hold``), and every fit handed new datasets of the
held rows (``datasets``). Filters and whitener are learned anew from the
seed inside every fit (``RandomCifarConfig.seed`` = ``--seed``): they,
the whitener's means and the scaler's statistics are program arguments,
so another seed compiles nothing.

The configuration's file may state ``device_memory_bytes`` in its
``rehearsal`` block, and a smaller ``block_size`` than the source's
4,096 there: the CPU rehearsal then reckons the optimizer's choice
against that figure and solves in blocks of that width, so that the
tiny size takes the path the chip takes at the timed size. A measured
run states no memory, and the source's block.

``BENCH_FEATURE_CONTROL`` in the environment is the control of the
FEATURES part of ``correct``; the program has no such switch, the job
degrades what it can reach from outside. ``bf16_output`` (the file's
``control`` sets it): every block the program's featurizer makes is
rounded to bfloat16, as a kernel would that wrote its output in
bfloat16. ``bf16_filters``: the filter bank the app learned is rounded
to bfloat16 before the pipeline is built, as a bank kept in bfloat16
would be.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from benchmarks.datagen import cifar_images

#: the program's counters a fit is held to (``real_fit`` in the file)
COUNTERS = {"blocks_generated": "solve.stream.blocks_generated",
            "stream_fits": "solve.stream.fits",
            "materialised_fits": "solve.materialised.fits"}
MAKERS = {"pallas": "featurize.conv_block.pallas",
          "xla": "featurize.conv_block.xla"}
#: by how much each rose in every fit of this process, oldest first
#: (``layers/blocks_generated.cifar.py`` reads the window's)
FIT_COUNTS = []


def degrade_features(how):
    import jax

    def bf16(x):
        # reduce_precision, not a cast there and back: the TPU compiler
        # removes that pair (it allows excess precision)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    if how == "bf16_output":
        from keystone_tpu.nodes.images.core import FusedConvRectifyPool

        own = FusedConvRectifyPool.make_blocks_with_params
        FusedConvRectifyPool.make_blocks_with_params = (
            lambda self, params, imgs: bf16(own(self, params, imgs)))
    elif how == "bf16_filters":
        from keystone_tpu.pipelines.images.cifar import random_patch_cifar

        learn = random_patch_cifar.learn_filters

        def rounded(train_images, config):
            filters, whitener = learn(train_images, config)
            return np.asarray(bf16(filters)), whitener

        random_patch_cifar.learn_filters = rounded
    else:
        raise SystemExit(f"BENCH_FEATURE_CONTROL={how!r}: bf16_output or "
                         "bf16_filters")


class Job:
    def __init__(self, cfg, seed, workdir):
        (self.train, self.test) = cifar_images.make_images(
            cfg["train_rows"], cfg["test_rows"], seed)
        self.cfg, self.seed = cfg, seed
        self.items = cfg["train_rows"] + cfg["test_rows"]
        self.paths = []
        for name, (pixels, labels) in (("train", self.train),
                                       ("test", self.test)):
            self.paths.append(os.path.join(workdir, name + "_batch.bin"))
            cifar_images.write_binary(self.paths[-1], pixels, labels)
        if "device_memory_bytes" in cfg:
            from keystone_tpu.analysis import resources

            stated = float(cfg["device_memory_bytes"])
            resources.device_memory_bytes = lambda free=False: stated
        if os.environ.get("BENCH_FEATURE_CONTROL"):
            degrade_features(os.environ["BENCH_FEATURE_CONTROL"])

    def load(self):
        from keystone_tpu.loaders.cifar_loader import cifar_loader

        return tuple(cifar_loader(path) for path in self.paths)

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
        from keystone_tpu.pipelines.images.cifar.random_patch_cifar import (
            RandomCifarConfig)

        cfg = self.cfg
        return RandomCifarConfig(
            num_filters=cfg["num_filters"], lam=cfg["lambda"],
            whitening_epsilon=cfg["whitening_epsilon"],
            patch_size=cfg["patch_size"], patch_steps=cfg["patch_steps"],
            pool_size=cfg["pool_size"], pool_stride=cfg["pool_stride"],
            alpha=cfg["alpha"], seed=self.seed,
            block_size=cfg["block_size"])

    def fit(self, loaded):
        from keystone_tpu.observability.metrics import MetricsRegistry
        from keystone_tpu.pipelines.images.cifar.random_patch_cifar import run

        counter = MetricsRegistry.get_or_create().counter
        before = {k: counter(name).value for k, name in COUNTERS.items()}
        train, test = loaded
        pipeline, train_eval, test_eval = run(self.app_config(), train, test)
        out = {"pipeline": pipeline, "test": test,
               "train_error": float(train_eval.total_error),
               "test_error": float(test_eval.total_error)}
        for k, name in COUNTERS.items():
            out[k] = float(counter(name).value - before[k])
        FIT_COUNTS.append({k: out[k] for k in COUNTERS})
        return out

    def answers(self, outcome):
        """What the last timed fit produced. ``fit()`` here is answered
        from the prefix-state table the timed fit filled. The model's
        arrays come to the host; its blocks are made on request, by the
        model's own maker, and cut to the gather's own columns."""
        import jax
        import jax.numpy as jnp

        from keystone_tpu.nodes.learning.linear import (
            StreamedBlockLinearMapper, _block_maker)
        from keystone_tpu.observability.metrics import MetricsRegistry

        fitted = outcome["pipeline"].fit()
        (model,) = [op for op in fitted.to_pipeline().graph.operators.values()
                    if isinstance(op, StreamedBlockLinearMapper)]
        bs, blocks = model.block_size, len(model.featurizers)
        columns = (np.arange(blocks * bs) if model.columns is None
                   else np.asarray(model.columns))
        params = model.stream_params()
        maker = _block_maker(model.featurizers[0])
        many = jax.jit(lambda group, rows: maker.many(group, rows))
        made = {}   # the last group made: (rows, first block) -> blocks

        def block(rows, b):
            """Block ``b`` of ``rows`` as the timed programs make it:
            ``blocks_a_call`` blocks a call of the model's own maker."""
            g = maker.blocks_a_call(rows.shape[0], params)
            key = (id(rows), b - b % g)
            if key not in made:
                made.clear()
                made[key] = many(jax.tree_util.tree_map(
                    lambda p: p[key[1]:key[1] + g], params), rows)
            block = made[key][b % g, :rows.shape[0]]
            real = columns[(columns >= b * bs) & (columns < (b + 1) * bs)]
            return block if len(real) == bs else block[:, jnp.asarray(
                real - b * bs)]

        filters = np.concatenate(
            [np.asarray(f.filters) for f in model.featurizers]
        )[:self.cfg["num_filters"]]
        oks, ratios = (np.asarray(part) for part in model.health)
        counter = MetricsRegistry.get_or_create().counter
        ran = sorted(k for k, name in MAKERS.items() if counter(name).value)
        return dict(
            weights=np.asarray(model.weights),
            feature_means=np.asarray(model.feature_means),
            feature_inv_stds=np.asarray(model.feature_inv_stds),
            intercept=np.asarray(model.intercept),
            filters=filters,
            whitener_means=np.asarray(model.featurizers[0].whitener_means),
            block=block,
            test_scores=np.asarray(
                model.apply_dataset(outcome["test"].data).numpy()),
            train_error=outcome["train_error"],
            test_error=outcome["test_error"],
            unhealthy_blocks=float(np.sum(~oks)),
            min_pivot_ratio=float(np.min(ratios)),
            maker="+".join(ran),
            **{k: outcome[k] for k in COUNTERS})

    def reference_inputs(self):
        return {"train": self.train, "test": self.test,
                "feature_seed": self.seed}


def prepare(cfg, seed, workdir):
    from keystone_tpu.nodes.images.core import FusedConvRectifyPool

    if not hasattr(FusedConvRectifyPool, "make_blocks_with_params"):
        # a program from before the convolution could be a block maker
        # would hand the solver one node of rows x 80,000 floats; say so
        # at once and exit
        gib = cfg["train_rows"] * cfg["num_filters"] * 8 * 4 / 2 ** 30
        print("benchmarks.configs.cifar_random_patch_10k: this program "
              "cannot make the convolution's features a block at a time and "
              f"the design matrix is {gib:.1f} GiB: the configuration "
              "cannot run here", file=sys.stderr)
        raise SystemExit(4)
    return Job(cfg, seed, workdir)
