"""Chip probe for RandomPatchCifar's streamed fit (ISSUE 30): what one
4,096-wide block of 512 filters costs to make from 50,000 images with
each of the two makers ``FusedConvRectifyPool.make_blocks_with_params``
can be (the Pallas kernel, the composed XLA ops), how far the two lie
apart, the kernel's call alone on 2,048 images in us an (image, bank)
pair beside the least its product needs (ISSUE 37) and split, by the
same call on one bank, into what it does once an image (the patches it
builds in VMEM and their statistics, ISSUE 42) and once an image and
bank (the product and its epilogue), the same at the augmented app's
geometry (24 x 24 crops, banks of 2,048 filters: ``crop_split``, which
also times the build and the product each alone, ISSUE 46), and what
whole fits
of ``--numFilters 10000 --lambda 3000`` take through the app's public
``run()`` at each of ``--train-rows`` (``--fits 0``: none), with the
device's busy share and the process's peak bytes.

    chiprun --timeout 1800 -- python3 tools/probe_cifar_blocks.py

Times of the makers are the host's, around a blocked call of one
program (warm; medians of 3): nearly all of it is the device's. Needs a
TPU: nothing here is a number a CPU can give. Writes
``chiprun_out/probe_cifar_blocks.json``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRAIN_ROWS, TEST_ROWS = 50000, 10000
FILTERS, LAMBDA = 10000, 3000.0
REPS = 3


def timed(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))   # compile, warm
    walls = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def makers(images, say):
    """One block, both makers: ms, and the gap between their blocks."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.images.core import FusedConvRectifyPool
    from keystone_tpu.ops import pallas_kernels

    rng = np.random.default_rng(3)
    filters = rng.standard_normal((512, 108)).astype(np.float32) / 10.0

    class Whitener:
        means = rng.standard_normal(108).astype(np.float32) / 10.0

    node = FusedConvRectifyPool(filters, 32, 6, 3, 13, 14, 0.25,
                                whitener=Whitener)
    params = node.apply_params()
    own = pallas_kernels.use_pallas
    out, blocks = {}, {}
    for name, on in (("pallas", True), ("xla", False)):
        pallas_kernels.use_pallas = lambda on=on: on
        try:
            make = jax.jit(lambda p, rows: node.make_blocks_with_params(
                jax.tree_util.tree_map(lambda a: a[None], p),
                rows)[0, :rows.shape[0]])
            t0 = time.perf_counter()
            blocks[name] = jax.block_until_ready(make(params, images))
            compile_s = time.perf_counter() - t0
            out[name + "_ms"] = 1e3 * timed(make, params, images)
            say(f"maker {name}: {out[name + '_ms']:.1f} ms a block of "
                f"{images.shape[0]} rows x 4,096 (first call "
                f"{compile_s:.1f} s)")
        finally:
            pallas_kernels.use_pallas = own
    a, b = (np.asarray(blocks[k][:2048], np.float64) for k in ("pallas", "xla"))
    out["pallas_vs_xla_gap"] = float(
        np.linalg.norm(a - b) / np.linalg.norm(b))
    say(f"pallas against xla, first 2,048 rows: {out['pallas_vs_xla_gap']:.3e}")
    return out


def maker_split(images, dev, say):
    """The kernel's call alone on 2,048 images, over as many banks as a
    fit's call holds and over one: a call of ``g`` banks does its
    patches and statistics once an image and its product and epilogue
    ``g`` times, so the two calls give both, in us."""
    import jax
    import jax.numpy as jnp

    from benchmarks.counts import conv_rectify_pool
    from benchmarks.harness import load_peaks
    from keystone_tpu.nodes.images import core
    from keystone_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(5)
    k, blocks = 512, -(-FILTERS // 512)
    node = core.FusedConvRectifyPool(
        np.zeros((k, 108), np.float32), 32, 6, 3, 13, 14, 0.25)
    banks = node.blocks_a_call(
        TRAIN_ROWS, (np.zeros((blocks, k, 108), np.float32),))
    filters = jnp.asarray(
        rng.standard_normal((banks, k, 108)).astype(np.float32) / 10.0)
    means = jnp.asarray(
        rng.standard_normal((banks, 108)).astype(np.float32) / 10.0)
    batch = images[:2048]
    call = jax.jit(lambda x, f, m: pk.fused_cifar_featurize_banks(
        x, f, *node._kernel_statics(), whitener_means=m))
    all_banks = 1e6 * timed(call, batch, filters, means) / batch.shape[0]
    one_bank = 1e6 * timed(call, batch, filters[:1], means[:1]) / batch.shape[0]
    a_bank = (all_banks - one_bank) / max(banks - 1, 1)
    least = conv_rectify_pool.generation_flops(
        1, k, (32 - 6 + 1) ** 2, 108) / load_peaks(
            dev.device_kind)["bf16_flops_per_s"]
    out = {"rows": int(batch.shape[0]), "banks": int(banks),
           "pallas_call_us_a_pair": all_banks / banks,
           "one_bank_call_us_an_image": one_bank,
           "patches_and_statistics_us_an_image": one_bank - a_bank,
           "product_and_epilogue_us_a_pair": a_bank,
           "product_least_us_a_pair": 1e6 * least}
    say(f"{out['rows']} rows, {banks} banks of {k} filters a call: "
        f"{out['pallas_call_us_a_pair']:.3f} us a pair ({all_banks:.3f} an "
        f"image; one bank a call {one_bank:.3f}): patches and statistics "
        f"{out['patches_and_statistics_us_an_image']:.3f} us an image, "
        f"product and epilogue {a_bank:.3f} us a pair, the product's least "
        f"{out['product_least_us_a_pair']:.3f} (benchmarks/peaks.json)")
    return out


CROP_FILTERS, CROP_ROWS = 2048, 16384
CROP_GEOMETRY = (24, 6, 3, 13, 14)    # image, patch, channels, stride, size


def build_alone(rows):
    """A call that does nothing but the kernel's ``_build_patches`` over
    ``_fused_layout``'s windows, a step's crops at a time into the same
    scratch; a row of each crop's matrix leaves, so the call has an
    output."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from keystone_tpu.ops import pallas_kernels as pk

    size, patch, channels, stride, pool = CROP_GEOMETRY
    windows, _, _ = pk._fused_layout(size, patch, stride, pool)
    pp, fp, *_ = pk._fused_geometry(*CROP_GEOMETRY, CROP_FILTERS)
    step = pk.FUSED_IMAGES_A_STEP

    def kernel(img_ref, out_ref, patch_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            patch_ref[...] = jnp.zeros_like(patch_ref)
        jax.lax.fori_loop(0, step, lambda t, _: pk._build_patches(
            img_ref, patch_ref, t, windows, patch, channels), None)
        out_ref[...] = patch_ref[:, 0, :]

    return jax.jit(lambda crops: pl.pallas_call(
        kernel, grid=(rows // step,),
        in_specs=[pl.BlockSpec((step, size, size * channels),
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((step, fp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((step, pp, fp), jnp.float32)],
        name="probe_build_patches",
    )(crops.reshape(rows, size, size * channels)))


def product_alone(rows):
    """A call that does nothing but the kernel's product, ``(Pp, Fp)``
    patches (zeros: the unit takes as long) by a bank's filters in the
    kernel's passes of ``FUSED_LANES_A_PASS`` lane tiles, each pass's
    ``(Pp, K a pass)`` into scratch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from keystone_tpu.ops import pallas_kernels as pk

    pp, fp, kp, *_ = pk._fused_geometry(*CROP_GEOMETRY, CROP_FILTERS)
    step, lanes = pk.FUSED_IMAGES_A_STEP, 128 * pk.FUSED_LANES_A_PASS

    def kernel(filt_ref, out_ref, patch_ref, raw_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            patch_ref[...] = jnp.zeros_like(patch_ref)

        def products(t, _):
            for first in range(0, kp, lanes):
                raw_ref[...] = jnp.dot(
                    patch_ref[t], filt_ref[:, first:first + lanes],
                    preferred_element_type=jnp.float32)
        jax.lax.fori_loop(0, step, products, None)
        out_ref[...] = raw_ref[:step, :]

    return jax.jit(lambda filt: pl.pallas_call(
        kernel, grid=(rows // step,),
        in_specs=[pl.BlockSpec((fp, kp), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((step, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((step, pp, fp), jnp.float32),
                        pltpu.VMEM((pp, lanes), jnp.float32)],
        name="probe_product",
    )(filt))


def crop_split(say, rows=CROP_ROWS):
    """The kernel at the augmented app's geometry, where a call of the
    fit holds ONE bank of 2,048 filters, in us a crop: the call at one
    bank and at two gives what it does once a crop (patches,
    statistics) and once a crop and bank (product, epilogue);
    ``build_alone`` and ``product_alone`` time the build and the product
    by themselves. The statistics are what is left of the first and the
    epilogue what is left of the second: what it ADDS to the product,
    under which part of it runs."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(7)
    size, patch, channels, stride, pool = CROP_GEOMETRY
    crops = jnp.asarray(rng.integers(
        0, 256, (rows, size, size, channels)).astype(np.float32))
    filters = jnp.asarray(rng.standard_normal(
        (2, CROP_FILTERS, patch * patch * channels)).astype(np.float32) / 10.0)
    means = jnp.asarray(rng.standard_normal(
        (2, patch * patch * channels)).astype(np.float32) / 10.0)
    call = jax.jit(lambda x, f, m: pk.fused_cifar_featurize_banks(
        x, f, *CROP_GEOMETRY, 10.0, 0.25, whitener_means=m))
    _, segments, regions = pk._fused_layout(size, patch, stride, pool)
    pp, fp, kp, *_ = pk._fused_geometry(*CROP_GEOMETRY, CROP_FILTERS)

    def us_a_crop(fn, *args):
        return 1e6 * timed(fn, *args) / rows

    one = us_a_crop(call, crops, filters[:1], means[:1])
    two = us_a_crop(call, crops, filters, means)
    build = us_a_crop(build_alone(rows), crops)
    product = us_a_crop(product_alone(rows), jnp.zeros((fp, kp), jnp.float32))
    a_bank = two - one
    out = {"rows": rows, "filters_a_bank": CROP_FILTERS,
           "patch_rows": int(pp), "segments": len(segments),
           "positions_laid_out": int(sum(real for _, real in segments)),
           "one_bank_call_us_a_crop": one, "two_bank_call_us_a_crop": two,
           "build_us_a_crop": build,
           "statistics_us_a_crop": one - a_bank - build,
           "product_us_a_crop_and_bank": product,
           "epilogue_over_product_us_a_crop_and_bank": a_bank - product}
    say(f"{size} x {size} crops, {rows} rows, banks of {CROP_FILTERS}: "
        f"{pp} patch rows in {len(segments)} segments "
        f"({out['positions_laid_out']} positions, {len(regions)} regions); "
        f"one bank a call {one:.3f} us a crop, two {two:.3f}: once a crop "
        f"{one - a_bank:.3f} = build {build:.3f} + statistics "
        f"{out['statistics_us_a_crop']:.3f}; once a crop and bank "
        f"{a_bank:.3f} = product {product:.3f} + what the epilogue adds "
        f"{out['epilogue_over_product_us_a_crop_and_bank']:.3f}")
    return out


def whole_fits(count, cfg, held, trace_dir, counter, names, dev, say):
    """``count`` whole fits through ``run()`` on new datasets of
    ``held``; the last under the profiler."""
    import jax

    from benchmarks import xplane
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.cifar.random_patch_cifar import run
    from keystone_tpu.workflow.env import PipelineEnv

    fits = []
    for i in range(count):
        PipelineEnv.get_or_create().clear_state()
        before = {n: counter(n).value for n in names}
        last = i == count - 1
        if last:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(io.StringIO()):
            loaded = [LabeledData(
                data=ArrayDataset.from_numpy(rows),
                labels=ArrayDataset.from_numpy(labels))
                for rows, labels in held]
            _, train_eval, test_eval = run(cfg, *loaded)
        wall = time.perf_counter() - t0
        if last:
            jax.profiler.stop_trace()
        fits.append({
            "wall_s": wall, "train_error": float(train_eval.total_error),
            "test_error": float(test_eval.total_error),
            **{n: counter(n).value - before[n] for n in names}})
        say(f"{len(held[0][1])} rows, fit {i}: {json.dumps(fits[-1])}")
        del loaded
    stats = dev.memory_stats() or {}
    trace = xplane.load(trace_dir, span_prefix="ks:")
    d0 = trace.devices[0]
    lo = min(s for _, s, _ in d0.modules)
    hi = max(e for _, _, e in d0.modules)
    out = {"fits": fits, "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "traced_fit": {
               "first_to_last_program_s": (hi - lo) / 1e9,
               "busy_s": trace.busy_seconds((lo, hi)),
               "programs_s": dict(sorted(trace.program_seconds().items(),
                                         key=lambda kv: -kv[1])[:8]),
               "ops_s": trace.op_seconds(top=16)}}
    say(f"{len(held[0][1])} rows: peak_bytes_in_use (the process's so far) "
        f"{out['peak_bytes_in_use']}; traced fit "
        f"{json.dumps(out['traced_fit'])}")
    return out


def is_tpu(dev) -> bool:
    return dev.platform == "tpu"


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=3000000001)
    p.add_argument("--fits", type=int, default=3)
    p.add_argument("--train-rows", default=str(TRAIN_ROWS),
                   help="comma-separated: whole fits at each size, of the "
                   "first rows of the 50,000 (the last fit of each traced)")
    args = p.parse_args(argv)
    os.environ.setdefault("KEYSTONE_NUMERICS", "0")

    import jax

    from keystone_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind} x{len(jax.devices())}]"

    def say(text):
        print(f"{tag} {text}", flush=True)

    if not is_tpu(dev):
        print(f"probe_cifar_blocks: JAX found {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 3

    from benchmarks.datagen import cifar_images
    from keystone_tpu.loaders.cifar_loader import cifar_loader
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.pipelines.images.cifar.random_patch_cifar import (
        RandomCifarConfig)

    work = tempfile.mkdtemp(prefix="probe_cifar_", dir=os.path.join(
        ROOT, "chiprun_out") if os.path.isdir(os.path.join(
            ROOT, "chiprun_out")) else None)
    result = {"device": dev.device_kind, "seed": args.seed}
    try:
        t0 = time.perf_counter()
        train, test = cifar_images.make_images(TRAIN_ROWS, TEST_ROWS,
                                               args.seed)
        paths = []
        for name, (pixels, labels) in (("train", train), ("test", test)):
            paths.append(os.path.join(work, name + ".bin"))
            cifar_images.write_binary(paths[-1], pixels, labels)
        t1 = time.perf_counter()
        held = [(part.data.numpy(), part.labels.numpy())
                for part in map(cifar_loader, paths)]
        say(f"data {t1 - t0:.2f} s, loader {time.perf_counter() - t1:.2f} s")

        images = jax.numpy.asarray(held[0][0])
        result["makers"] = makers(images, say)
        result["maker_split"] = maker_split(images, dev, say)
        del images
        result["crop_split"] = crop_split(say)

        counter = MetricsRegistry.get_or_create().counter
        names = ("solve.stream.fits", "solve.materialised.fits",
                 "solve.stream.blocks_generated",
                 "featurize.conv_block.pallas", "featurize.conv_block.xla",
                 "executor.nodes_executed", "executor.prefix_hits")
        cfg = RandomCifarConfig(num_filters=FILTERS, lam=LAMBDA,
                                seed=args.seed % (2 ** 32))
        result["sizes"] = {}
        sizes = args.train_rows.split(",") if args.fits else []
        for rows in map(int, sizes):
            part = [(held[0][0][:rows], held[0][1][:rows]), held[1]]
            result["sizes"][str(rows)] = whole_fits(
                args.fits, cfg, part, os.path.join(work, f"trace{rows}"),
                counter, names, dev, say)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_cifar_blocks.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
