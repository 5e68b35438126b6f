"""RandomPatchCifarAugmented through the app's public ``run()``: images
written from the seed as CIFAR-10 binary records, read once by the
package's ``cifar_loader`` (``hold``), and every fit handed new datasets
of the held 32 x 32 images (``datasets``). Inside every fit: filters and
whitener learned from the seed, ten random 24 x 24 crops an image with
flips made on the device (500,000 rows at the timed size), the streamed
block solve with its rows in chunks, the ten-crop test pass and its
vote. ``AugmentedConfig.seed`` = ``--seed``: filters, offsets and flips
are program arguments, so another seed compiles nothing and does the
same amount of work.

The configuration's file may state ``device_memory_bytes`` in its
``rehearsal`` block, and a smaller ``block_size`` there, as
``cifar_random_patch_10k``'s does: the CPU rehearsal then reckons the
optimizer's choice AND the sweep's row chunk against that figure, so
that the tiny size takes the path the chip takes at the timed size (a
gather handed to the solver, a block of all rows that does not fit).

``BENCH_FEATURE_CONTROL`` is the control of the FEATURES part of
``correct``, as in ``cifar_random_patch_10k``: ``bf16_output`` (the
file's ``control`` sets it) rounds every block the program's featurizer
makes to bfloat16; ``bf16_filters`` rounds the learned filter bank.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from benchmarks.configs import cifar_random_patch_10k as plain

#: the program's counters a fit is held to (``real_fit`` in the file)
COUNTERS = dict(plain.COUNTERS, row_chunks="solve.stream.row_chunks",
                rows="solve.stream.rows")
MAKERS = plain.MAKERS
#: by how much each rose in every fit of this process, oldest first
#: (``layers/blocks_generated.cifar.py`` and ``row_chunks.cifar_aug.py``
#: read the window's)
FIT_COUNTS = []


class Job(plain.Job):
    def __init__(self, cfg, seed, workdir):
        super().__init__(cfg, seed, workdir)   # data, memory, the control
        if os.environ.get("BENCH_FEATURE_CONTROL") == "bf16_filters":
            # the augmented app took ``learn_filters`` from the plain
            # app's module when it was imported: the rounded one, too
            from keystone_tpu.pipelines.images.cifar import (
                random_patch_cifar, random_patch_cifar_augmented)

            random_patch_cifar_augmented.learn_filters = (
                random_patch_cifar.learn_filters)
        self._last = {}    # ``items`` stays the images handed over, not crops

    def app_config(self):
        from keystone_tpu.pipelines.images.cifar.random_patch_cifar_augmented \
            import AugmentedConfig

        cfg = self.cfg
        return AugmentedConfig(
            num_filters=cfg["num_filters"], lam=cfg["lambda"],
            whitening_epsilon=cfg["whitening_epsilon"],
            patch_size=cfg["patch_size"], patch_steps=cfg["patch_steps"],
            pool_size=cfg["pool_size"], pool_stride=cfg["pool_stride"],
            alpha=cfg["alpha"], seed=self.seed,
            block_size=cfg["block_size"],
            num_random_patches_augment=cfg["crops_a_train_image"])

    def fit(self, loaded):
        from keystone_tpu.observability.metrics import MetricsRegistry
        from keystone_tpu.pipelines.images.cifar.random_patch_cifar_augmented \
            import run

        # a fit's pipeline holds its 3.5 GB of training crops, and the
        # driver holds a fit's outcome until the next has answered: the
        # one before this is let go of here, the last is kept whole
        for held in ("pipeline", "train", "test"):
            self._last.pop(held, None)
        counter = MetricsRegistry.get_or_create().counter
        before = {k: counter(name).value for k, name in COUNTERS.items()}
        train, test = loaded
        pipeline, test_eval = run(self.app_config(), train, test)
        out = {"pipeline": pipeline, "train": train, "test": test,
               "test_error": float(test_eval.total_error)}
        for k, name in COUNTERS.items():
            out[k] = float(counter(name).value - before[k])
        FIT_COUNTS.append({k: out[k] for k in COUNTERS})
        self._last = out
        return out

    def answers(self, outcome):
        """What the last timed fit produced. ``fit()`` here is answered
        from the prefix-state table the timed fit filled. The model's
        arrays come to the host; its blocks are made on request, one
        block of the rows handed over a call (as the timed sweep makes
        them, a chunk of rows at a time), and cut to the gather's own
        columns; the crops are the app's own augmentation of the same
        datasets, made again on request (the timed fit keeps none)."""
        import jax
        import jax.numpy as jnp

        from keystone_tpu.nodes.learning.linear import (
            StreamedBlockLinearMapper, _block_maker)
        from keystone_tpu.observability.metrics import MetricsRegistry
        from keystone_tpu.pipelines.images.cifar import (
            random_patch_cifar_augmented as app)

        fitted = outcome["pipeline"].fit()
        (model,) = [op for op in fitted.to_pipeline().graph.operators.values()
                    if isinstance(op, StreamedBlockLinearMapper)]
        # the pipeline holds the timed fit's crops: let it go before the
        # reference needs the room (the closures below keep the images)
        train_set, test_set = outcome["train"], outcome["test"]
        del fitted
        for held in ("pipeline", "train", "test"):
            outcome.pop(held, None)
        self._last = {}
        bs, blocks = model.block_size, len(model.featurizers)
        columns = (np.arange(blocks * bs) if model.columns is None
                   else np.asarray(model.columns))
        params = model.stream_params()
        maker = _block_maker(model.featurizers[0])
        one = jax.jit(lambda params_i, rows: maker(params_i, rows))

        def block(rows, b):
            rows = jnp.asarray(rows, jnp.float32).reshape(len(rows), -1)
            made = one(jax.tree_util.tree_map(lambda p: p[b], params), rows)
            real = columns[(columns >= b * bs) & (columns < (b + 1) * bs)]
            return made if len(real) == bs else made[:, jnp.asarray(
                real - b * bs)]

        def sampled(make):
            """``index -> (rows the app's augmentation makes, those of
            them at index, on the host)``; made on request and let go."""
            def rows_of(index):
                data = make()
                return data.n, np.asarray(
                    jnp.take(data.data, jnp.asarray(index), axis=0))
            return rows_of

        from keystone_tpu.evaluation import augmented

        test_crops, test_ids, test_labels = app.augment_test(test_set)
        test_scores = np.asarray(model.apply_dataset(test_crops).numpy())
        del test_crops
        # an image's scores as the app's evaluator averages its crops'
        voted_scores, _ = augmented.vote(test_ids, test_scores, test_labels)
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
            train_crops=sampled(lambda: app.augment_train(
                self.app_config(), train_set)[0]),
            test_crops=sampled(lambda: app.augment_test(test_set)[0]),
            test_scores=test_scores,
            voted_scores=voted_scores,
            test_error=outcome["test_error"],
            rows_solved=(np.full(blocks, np.inf) if model.rows_solved is None
                         else np.asarray(model.rows_solved)),
            unhealthy_blocks=float(np.sum(~oks)),
            min_pivot_ratio=float(np.min(ratios)),
            maker="+".join(ran),
            **{k: outcome[k] for k in COUNTERS})


def prepare(cfg, seed, workdir):
    from keystone_tpu.nodes.learning.linear import StreamedBlockLinearMapper

    if not hasattr(StreamedBlockLinearMapper, "rows_solved"):
        # a program whose streamed sweeps hold a block of all rows and its
        # centred copy: 16.4 GB at this size. Say so at once and exit
        gib = 2 * (cfg["train_rows"] * cfg["crops_a_train_image"]
                   * cfg["block_size"] * 4) / 2 ** 30
        print("benchmarks.configs.cifar_random_patch_aug_10k: this program "
              "cannot take a streamed block's rows in chunks, and one block "
              f"of all rows with its centred copy is {gib:.1f} GiB: the "
              "configuration cannot run here", file=sys.stderr)
        raise SystemExit(4)
    return Job(cfg, seed, workdir)
