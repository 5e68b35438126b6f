"""Chip probe for VOCSIFTFisher's stages (ISSUE 33): what each costs
alone at the cell's shapes, and the Fisher vector in each of the two
forms the automatic choice picks between.

    chiprun --timeout 1800 -- python3 tools/probe_voc.py [--chunk-only]

``--chunk-only`` stops after the stages that take a chunk (dense SIFT,
the gather, the projection, the Fisher vector) and leaves the fits out.

* dense SIFT of a chunk of 16 images padded to 384 x 512, and from the
  compiled chunk program how its output is assembled: the
  ``dynamic-update-slice`` ops whose index the compiler marks as off a
  tile's edge (each shifts its update across lanes on the way in) and
  whether the program ends in a ``copy`` of its output into another
  layout (both 0 / no since PR 47; 5 / yes before);
* a sampler's gather of 976 columns an image from that chunk, which is
  compiled against the layout the chunk leaves its program in;
* the Fisher vector of that chunk's reduced descriptors under 256
  components: the fused Pallas kernel and the split XLA form, and how
  far their rows lie apart;
* the column PCA of a million sampled descriptors (local SVD and TSQR);
* the GMM's fit on a million reduced descriptors (seeding, EM as one
  program), and how many iterations it ran;
* the block solve over 1,024 x 40,960.

Times are the host's around a blocked call of one warm program (medians
of 3): nearly all of it is the device's. Needs a TPU. Writes
``chiprun_out/probe_voc.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
REPS = 3


def timed(fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))   # compile, warm
    first = time.perf_counter() - t0
    walls = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls), first, out


def chunk_program_structure(text: str) -> dict:
    """From the text of a chunk program compiled for a TPU
    (``.lower(...).compile().as_text()``): how many
    ``dynamic-update-slice`` ops, plain or fused, carry an index that the
    compiler could not show to lie on a tile's edge, and what the
    entry's ROOT is."""
    unaligned = sum(
        1 for line in text.splitlines()
        if "dynamic-update-slice" in line
        and re.search(r'"is_index_aligned":\[[^\]]*false', line))
    root = re.search(r"ROOT %?(\S+) = \S+ ([\w-]+)\(",
                     text[text.index("ENTRY"):])
    return {"unaligned_updates": unaligned, "root": root.group(1),
            "root_is_copy": root.group(2) == "copy"}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chunk-only", action="store_true")
    chunk_only = parser.parse_args().chunk_only

    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.images import fisher_vector as fv
    from keystone_tpu.nodes.learning import gmm, pca
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.stats import sampling
    from keystone_tpu.ops import pallas_kernels, sift
    from keystone_tpu.parallel.dataset import ArrayDataset

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("probe_voc: needs a TPU")
    from keystone_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out, rng = {}, np.random.default_rng(33)

    def say(key, value):
        out[key] = value
        print(f"probe_voc: {key} = {value}", flush=True)

    # dense SIFT: a chunk of 16, sizes as the cell's
    sizes = [(375, 500)] * 10 + [(333, 500)] * 4 + [(300, 500), (260, 500)]
    imgs = np.zeros((16, 384, 512), np.float32)
    for i, (h, w) in enumerate(sizes):
        imgs[i, :h, :w] = rng.random((h, w), dtype=np.float32)
    imgs, extent = jnp.asarray(imgs), np.array(sizes, np.int32)
    ms, first, desc = timed(sift.dense_sift_chunk, imgs, extent)
    say("sift_chunk16_ms.einsum", round(1e3 * ms, 3))
    say("sift_first_call_s.einsum", round(first, 2))
    mask = jnp.asarray(np.stack([
        sift.descriptor_mask(h, w, (384, 512)) for h, w in sizes]))
    say("descriptors_a_chunk", int(mask.sum()))
    say("columns_a_chunk", int(desc.shape[2]))
    args, static = sift.chunk_call(imgs, extent)
    for key, value in chunk_program_structure(
            sift._dsift_chunk.lower(*args, **static).compile().as_text()
            ).items():
        say(f"sift_chunk_program.{key}", value)
    picks = jnp.asarray(np.stack([
        np.sort(rng.choice(np.flatnonzero(m), 976, replace=False))
        for m in np.asarray(mask)]).astype(np.int32))
    ms, _, _ = timed(sampling._take_columns, desc, picks)
    say("take_columns_chunk16_ms", round(1e3 * ms, 3))

    # the projection and the Fisher vector of that chunk
    basis = np.linalg.qr(rng.standard_normal((128, 80)))[0].astype(np.float32)
    project = pca.BatchPCATransformer(basis)
    ms, _, reduced = timed(project._batched(), desc)
    say("pca_project_chunk16_ms", round(1e3 * ms, 3))
    means = jnp.asarray(rng.standard_normal((80, 256)).astype(np.float32) * 30)
    variances = jnp.asarray(rng.uniform(50, 400, (80, 256)).astype(np.float32))
    weights = jnp.full((256,), 1 / 256, jnp.float32)

    def chunk_of(moments):
        """``_fisher_vector_chunk`` with one form's moment sums."""
        def one(xm):
            x, m = xm
            sums = moments(x, means, variances, weights, threshold=1e-4,
                           mask=m, precision=fv._PRECISION)
            return fv.fisher_vector_of_sums(
                sums, jnp.maximum(jnp.sum(m.astype(jnp.float32)), 1.0),
                means, variances, weights)
        return jax.jit(lambda X, mask: jax.lax.map(one, (X, mask)))

    rows = {}
    for mode, moments in (("einsum", fv.fv_moments_split),
                          ("pallas", pallas_kernels.fv_moments_pallas)):
        try:
            ms, first, rows[mode] = timed(chunk_of(moments), reduced, mask)
            say(f"fv_chunk16_ms.{mode}", round(1e3 * ms, 3))
            say(f"fv_first_call_s.{mode}", round(first, 2))
        except Exception as e:   # what the chip's compiler refuses
            say(f"fv_failed.{mode}", repr(e)[:400])
    if len(rows) == 2:
        a, b = (np.asarray(rows[m], np.float64) for m in ("einsum", "pallas"))
        say("fv_pallas_vs_einsum", float(
            np.linalg.norm(a - b) / np.linalg.norm(a)))

    if chunk_only:
        return write(out)

    # the two fits, on a million samples drawn from the chunk's own
    pick = jnp.asarray(np.flatnonzero(np.asarray(mask[0]))[
        rng.integers(0, 40000, (1024, 976))])     # of image 0's own 47,213
    sample = jnp.take(desc[0], pick, axis=1).transpose(1, 0, 2)  # [1024,128,976]
    ds = ArrayDataset(sample, 1024)
    for name, est in (("local", pca.LocalColumnPCAEstimator(80)),
                      ("distributed", pca.DistributedColumnPCAEstimator(80))):
        try:
            t0 = time.perf_counter()
            est.fit(ds)
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            est.fit(ds)
            say(f"pca_fit_s.{name}", round(time.perf_counter() - t0, 3))
            say(f"pca_first_fit_s.{name}", round(first, 2))
        except Exception as e:
            say(f"pca_failed.{name}", repr(e)[:400])
    cols = jnp.matmul(jnp.asarray(basis).T, sample,
                      precision="highest").transpose(0, 2, 1).reshape(-1, 80)
    est = gmm.GaussianMixtureModelEstimator(256, seed=33)
    t0 = time.perf_counter()
    model = est.fit_matrix(cols)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = est.fit_matrix(cols)
    say("gmm_fit_s", round(time.perf_counter() - t0, 3))
    say("gmm_first_fit_s", round(first, 2))
    say("gmm_iterations", model.iterations)

    # the solve
    X = ArrayDataset(jnp.asarray(
        rng.standard_normal((1024, 40960), dtype=np.float32) / 200), 1024)
    Y = ArrayDataset(jnp.asarray(
        np.where(rng.random((1024, 20)) < 0.1, 1.0, -1.0).astype(np.float32)),
        1024)
    solver = BlockLeastSquaresEstimator(4096, 1, 0.5)
    t0 = time.perf_counter()
    jax.block_until_ready(solver.fit(X, Y).weights)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(solver.fit(X, Y).weights)
    say("block_solve_s", round(time.perf_counter() - t0, 3))
    say("block_solve_first_s", round(first, 2))
    say("peak_bytes", jax.devices()[0].memory_stats()["peak_bytes_in_use"])
    write(out)


def write(out):
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_voc.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
