#!/usr/bin/env python3
"""The quickest proof that keystone_tpu still starts on the chip.

One process, one pass over the system's main path at this repo's smoke
width of RandomPatchCifar: 1,024 filters (neither ``BASELINE.md`` nor
the source, whose default is 100 and whose documented run is 10,000,
names that width; it is what one cold run of 13 minutes affords), 6x6
patches, pool 14/13, block 4,096, two gathered branches of 512 filters,
8,192 features, 50,000 + 10,000 images, through the entry points a user
calls:

1. device line; anything but a TPU ends the run non-zero, with no result;
2. CIFAR-10 binary files written from ``--seed`` (the surrogate of
   ``loaders/cifar_surrogate.py``; no network, no dataset on disk);
3. the fit through ``python -m keystone_tpu cifar.random_patch``'s
   ``main()``, then the same config through ``run()`` for the pipeline
   (answered from the prefix-state table);
4. the fitted pipeline's batch path (the fused Pallas featurizer)
   against its datum path (composed XLA ops) on the chip;
5. the model just fitted behind ``ServingPlane`` + ``serving.http.serve``
   over real HTTP, with zero unexpected recompiles;
6. every Pallas kernel compiled through its dispatcher at the shape its
   app uses, against its einsum reference, and the donated Gram carry.

Every phase raises on a mismatch, a non-finite value or a fallback taken
where a kernel was expected; nothing here catches. The last line of
stdout is one JSON object naming the device. The wall and compile
seconds it prints are smoke timings, not a benchmark.

    python3 chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import shutil
import sys
import tempfile
import time
import urllib.error
import urllib.request
import warnings

N_TRAIN, N_TEST = 50_000, 10_000
NUM_FILTERS = 1024
LAMBDA = 10.0
#: The raw-pixel linear model reads 0.93 test error on this surrogate
#: (round 5, 10,240 images); the conv+pool featurizer is what beats it.
MAX_TEST_ERROR = 0.5
PARITY_ROWS = 256
#: Batch (Pallas) and datum (XLA) featurizers both multiply at DEFAULT
#: matmul precision, one bf16 pass, in different orders: their features
#: agree to this share of the largest feature (measured 9e-4).
FEATURE_TOL = 5e-3
#: Class ids may flip where two scores tie within that rounding.
MAX_ARGMAX_FLIPS = PARITY_ROWS // 100
REQUEST_ROWS = (1, 5, 40)  # buckets 1, 8 and 64 of max_batch 64


class SmokeFailure(AssertionError):
    """A phase found something wrong; the run exits non-zero."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str):
    """Print the phase's wall and compile seconds when it ends, and the
    compiles that took the most of them."""
    from keystone_tpu.observability.compilelog import (
        compile_context,
        compile_observatory,
    )

    obs = compile_observatory()
    compile0, count0, t0 = (
        obs.wall_s_total(), obs.count_total(), time.perf_counter())
    with compile_context(f"smoke:{name}"):
        yield
    count = obs.count_total() - count0
    slowest = sorted(obs.tail()[-count:] if count else [],
                     key=lambda r: -r["wall_s"])[:3]
    print(f"phase {name}: wall {time.perf_counter() - t0:.1f} s, compile "
          f"{obs.wall_s_total() - compile0:.1f} s in {count} compiles"
          + "".join(f", {r['name']} {r['wall_s']:.1f} s" for r in slowest)
          + " (smoke timings)", flush=True)


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, text):
        for s in self.streams:
            s.write(text)
        return len(text)

    def flush(self):
        for s in self.streams:
            s.flush()


def rel_dev(got, want) -> float:
    """Largest deviation as a share of the reference's largest value."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.all(np.isfinite(got))), "non-finite values")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# -- 2. data ------------------------------------------------------------------

def write_data(root: str, seed: int):
    from keystone_tpu.loaders.cifar_surrogate import (
        make_surrogate_cifar,
        write_cifar_binary,
    )

    (tr_x, tr_y), (te_x, te_y) = make_surrogate_cifar(
        N_TRAIN, N_TEST, seed=seed)
    files = 5  # data_batch_1..5.bin + test_batch.bin, as CIFAR-10 ships
    per = N_TRAIN // files
    check(per * files == N_TRAIN, "N_TRAIN must split into 5 files")
    for i in range(files):
        write_cifar_binary(
            os.path.join(root, f"data_batch_{i + 1}.bin"),
            tr_x[i * per:(i + 1) * per], tr_y[i * per:(i + 1) * per])
    test_path = os.path.join(root, "test_batch.bin")
    write_cifar_binary(test_path, te_x, te_y)
    return os.path.join(root, "data_batch_*.bin"), test_path


# -- 3. fit -------------------------------------------------------------------

def fit_through_cli(train_path: str, test_path: str, seed: int):
    """The CLI entry (argument parsing, cache helper, app dispatch);
    ``main()`` throws the pipeline away, so the errors are read back
    from what it prints."""
    from keystone_tpu.__main__ import main as keystone_main

    captured = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, captured)):
        rc = keystone_main([
            "cifar.random_patch", "--trainLocation", train_path,
            "--testLocation", test_path, "--numFilters", str(NUM_FILTERS),
            "--lambda", str(LAMBDA), "--seed", str(seed)])
    check(rc == 0, f"keystone_tpu main() returned {rc}")
    errors = {}
    for key, label in (("train", "Training"), ("test", "Test")):
        m = re.search(rf"{label} error is: ([0-9.naife+-]+)",
                      captured.getvalue())
        check(m is not None, f"main() printed no {label} error")
        errors[key] = float(m.group(1))
    return errors


def fit_again_for_pipeline(train_path: str, test_path: str, seed: int,
                           cli_errors):
    """The same config through ``run()``, which returns the pipeline.
    Prints whether the prefix-state table answered or it refitted."""
    import numpy as np

    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.pipelines.images.cifar.random_patch_cifar import (
        RandomCifarConfig,
        run,
    )
    from keystone_tpu.workflow.env import PipelineEnv

    state = PipelineEnv.get_or_create().state
    saved = dict(state)
    hits = MetricsRegistry.get_or_create().counter("executor.prefix_hits")
    hits0, t0 = hits.value, time.perf_counter()
    pipeline, train_eval, test_eval = run(RandomCifarConfig(
        train_location=train_path, test_location=test_path,
        num_filters=NUM_FILTERS, lam=LAMBDA, seed=seed))
    refitted = sorted(
        str(k)[:60] for k, v in state.items() if saved.get(k) is not v)
    print(f"second run(): refitted={bool(refitted)} "
          f"({len(saved)} saved prefixes, {len(refitted)} replaced, "
          f"prefix hits +{hits.value - hits0:.0f}, "
          f"wall {time.perf_counter() - t0:.1f} s)", flush=True)
    for key, ev in (("train", train_eval), ("test", test_eval)):
        err = float(ev.total_error)
        check(np.isfinite(err), f"{key} error is not finite: {err}")
        check(abs(err - cli_errors[key]) < 1e-4,
              f"run() {key} error {err} != main()'s {cli_errors[key]}")
    check(float(test_eval.total_error) < MAX_TEST_ERROR,
          f"test error {test_eval.total_error:.4f} is not under "
          f"{MAX_TEST_ERROR} (raw pixels read 0.93 on this surrogate)")
    return pipeline.fit()


# -- 4. parity ----------------------------------------------------------------

def batch_vs_datum(fitted, images):
    """Features and class ids of ``images`` by the batch path (Pallas)
    and the datum path (composed XLA ops). Returns the batch path's
    class ids and features."""
    import numpy as np

    from keystone_tpu.nodes.images.core import FusedConvRectifyPool
    from keystone_tpu.observability.compilelog import compile_observatory
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.workflow.transformer import Transformer

    # over 512 filters the app gathers one node a 512-filter block (two
    # here): the same nodes both ways, in the gather's order (the order
    # they were made in), so the columns agree side by side
    graph = fitted.to_pipeline().graph
    featurizers = [
        graph.operators[node] for node in sorted(
            graph.operators, key=lambda n: n.id)
        if isinstance(graph.operators[node], FusedConvRectifyPool)]
    check(len(featurizers) == -(-NUM_FILTERS // 512),
          f"{len(featurizers)} featurizer nodes")
    ds = ArrayDataset.from_numpy(images)
    batch_feats = np.concatenate(
        [f.apply_dataset(ds).numpy() for f in featurizers], axis=1)
    # the program that holds the pallas_call is what compiled and ran,
    # not the composed fallback other backends take
    check(compile_observatory().by_name().get("fused_featurize_rows", 0) > 0,
          "the batch path did not run the fused Pallas program")
    # the datum function, vmapped over the same sharded batch
    datum_feats = np.concatenate(
        [Transformer.apply_dataset(f, ds).numpy() for f in featurizers],
        axis=1)
    check(batch_feats.shape == (len(images), 8 * NUM_FILTERS),
          f"feature shape {batch_feats.shape}")
    dev = rel_dev(batch_feats, datum_feats)
    batch_ids = np.asarray(fitted.apply(ds).get().numpy()).reshape(-1)
    datum_ids = np.asarray(
        [int(fitted.apply_datum(img).get()) for img in images])
    flips = int(np.sum(batch_ids != datum_ids))
    print(f"parity: {len(images)} test images, batch (Pallas) vs datum "
          f"(XLA) features max deviation {dev:.2e} of the largest "
          f"feature (tolerance {FEATURE_TOL:.0e}), argmax agreement "
          f"{len(images) - flips}/{len(images)}", flush=True)
    # one digit a class id: lets a one-chip and a four-chip run be diffed
    print("parity class ids: " + "".join(str(i) for i in batch_ids),
          flush=True)
    check(dev <= FEATURE_TOL, f"feature deviation {dev:.3e}")
    check(flips <= MAX_ARGMAX_FLIPS, f"{flips} argmax flips")
    return batch_ids, batch_feats


# -- 5. serving ---------------------------------------------------------------

def _http(url: str, payload=None, timeout: float = 120.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as rsp:
            return rsp.status, rsp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def serve_and_query(fitted, images, expected_ids):
    """What ``python -m keystone_tpu serve`` builds, on an ephemeral
    port: the model admitted twice, at f32 (answers must equal the
    batch path's) and at the CLI's default bf16 weights."""
    import jax
    import numpy as np

    from keystone_tpu.serving.http import serve
    from keystone_tpu.serving.plane import ServingPlane

    item = jax.ShapeDtypeStruct((32, 32, 3), np.float32)
    plane = ServingPlane(max_batch=64, queue_depth=256,
                         default_weight_dtype="bf16", drift_every=32)
    plane.expect_models(2)
    plane.start()
    server = serve(plane, port=0)
    try:
        base = f"http://127.0.0.1:{server.server_port}"
        status, _ = _http(base + "/healthz")
        check(status == 503, f"/healthz before admission: {status}")
        for name, weight_dtype in (("cifar", None), ("cifar_bf16", "default")):
            entry = plane.admit(name, fitted, item, weight_dtype=weight_dtype)
            print(f"admitted {name!r}: "
                  f"{entry.charge.total_nbytes() / 2**20:.1f} MiB "
                  f"({entry.charge.source}), buckets {list(entry.buckets)}, "
                  f"warmup {entry.warmup_s:.1f} s, weights "
                  f"{entry.weight_dtype or 'f32'}", flush=True)
        deadline = time.monotonic() + 60.0
        while _http(base + "/healthz")[0] != 200:
            check(time.monotonic() < deadline, "/healthz never turned 200")
            time.sleep(0.1)
        recompiles0 = plane.unexpected_recompiles()
        agree = total = 0
        starts = np.cumsum((0,) + REQUEST_ROWS)
        requests = [(images[s:s + n], expected_ids[s:s + n])
                    for s, n in zip(starts, REQUEST_ROWS)]
        requests.append((images[-1], expected_ids[-1:]))  # one bare item
        for rows, want in requests:
            n = len(want)
            answers = {}
            for name in ("cifar", "cifar_bf16"):
                status, body = _http(f"{base}/predict/{name}",
                                     {"instances": rows.tolist()})
                check(status == 200, f"POST /predict/{name}: {status} "
                                     f"{body[:200]!r}")
                answers[name] = np.asarray(
                    json.loads(body)["predictions"]).reshape(-1)
                check(answers[name].shape == (n,),
                      f"{name}: {answers[name].shape} answers for {n} rows")
            check(np.array_equal(answers["cifar"], want),
                  f"served answers {answers['cifar']} != batch path's {want}")
            agree += int(np.sum(answers["cifar_bf16"] == want))
            total += n
        recompiles = plane.unexpected_recompiles() - recompiles0
        print(f"serving: {len(requests)} requests x 2 models over HTTP "
              f"(rows {[len(w) for _, w in requests]}), f32 answers equal the "
              f"batch path's, bf16 answers agree {agree}/{total}, "
              f"unexpected recompiles {recompiles:.0f}", flush=True)
        check(recompiles == 0, f"{recompiles} unexpected recompiles")
        check(agree >= total - max(1, total // 20),
              f"bf16 answers agree only {agree}/{total}")
    finally:
        server.shutdown()
        plane.close()


# -- 6. kernels ---------------------------------------------------------------

def check_kernel(name: str, fn, args, reference, tol: float):
    """Lower ``fn`` (a kernel's dispatcher), require the Mosaic custom
    call in what it lowered to, compile and run it on the chip, and
    hold the result to ``reference`` within ``tol``."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    compiled = "tpu_custom_call" in lowered.as_text()
    out = lowered.compile()(*args)
    devs = [rel_dev(o, r) for o, r in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(reference))]
    print(f"kernel {name}: compiled={compiled} max deviation "
          f"{max(devs):.2e} (tolerance {tol:.0e})", flush=True)
    check(compiled, f"{name}: the dispatcher took the fallback, not the "
                    "Pallas kernel")
    check(max(devs) <= tol, f"{name}: deviation {max(devs):.3e} > {tol}")
    return out


def kernels(fitted, feats):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.nodes.images import fisher_vector as fv
    from keystone_tpu.nodes.learning.linear import (
        BlockLinearMapper,
        _dequant_affine,
        _quantize_weights,
        _quantized_affine_batch,
    )
    from keystone_tpu.ops import pallas_kernels as pk
    from keystone_tpu.ops.sift import dense_sift
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.parallel.mesh import DATA_AXIS, get_mesh

    rng = np.random.RandomState(0)
    mesh = get_mesh()
    rows = NamedSharding(mesh, P(DATA_AXIS, None))
    highest = jax.lax.Precision.HIGHEST

    # gram_cross: the d=896 / k=128 shape it always claimed, and the
    # widest d its fits-vmem predicate admits at that k
    d_edge = max(d for d in range(128, 8192 + 1, 128)
                 if pk.gram_fits_vmem(d, 128))
    for d in (896, d_edge):
        X = jax.device_put(rng.randn(4096, d).astype(np.float32), rows)
        Y = jax.device_put(rng.randn(4096, 128).astype(np.float32), rows)
        reference = (jnp.einsum("nd,ne->de", X, X, precision=highest),
                     jnp.einsum("nd,nk->dk", X, Y, precision=highest))
        check_kernel(f"gram_cross d={d} k=128",
                     lambda X, Y: pk.gram_cross(X, Y, mesh=mesh),
                     (X, Y), reference, 1e-5)

    # fv_moments at the ImageNet app's descriptor dim (64 after PCA),
    # GMM size (16) and the descriptor count of one VGA image under that
    # app's extractor config (dense SIFT is XLA's products and no
    # kernel: this is the per-image form's one run on a chip)
    img = jnp.asarray(rng.rand(480, 640).astype(np.float32))
    n_desc = dense_sift(img, 4, 6, 5, 1).shape[1]
    D, K = 64, 16
    descs = jnp.asarray(rng.randn(D, n_desc).astype(np.float32))
    gmm = (jnp.asarray(rng.randn(D, K).astype(np.float32)),
           jnp.asarray((0.5 + rng.rand(D, K)).astype(np.float32)),
           jnp.asarray((np.ones(K) / K).astype(np.float32)))
    check_kernel(
        f"fv_moments D={D} K={K} nDesc={n_desc}",
        lambda x: fv._fisher_vector(x, *gmm, 1e-4), (descs,),
        fv.fisher_vector_of_sums(
            fv.fv_moments_split(descs, *gmm, threshold=1e-4,
                                precision=fv._PRECISION),
            n_desc, *gmm), 1e-3)

    # quantized_affine at the model just fitted (8,192 x 10), both
    # weight dtypes, one row (8-row tile), a small and the largest
    # bucket, as row-sharded batches the way apply_dataset hands them on
    (mapper,) = [op for op in fitted.to_pipeline().graph.operators.values()
                 if isinstance(op, BlockLinearMapper)]
    W, mean, inv_std, b = mapper.apply_params()
    for weight_dtype in ("bf16", "int8"):
        params = _quantize_weights(W, weight_dtype) + (mean, inv_std, b)
        for n in (1, 8, 64):
            X = ArrayDataset.from_numpy(feats[:n]).data
            check_kernel(
                f"quantized_affine {weight_dtype} rows={n}",
                lambda X, *p: _quantized_affine_batch(X, *p, mesh=mesh),
                (X, *params), _dequant_affine(params, X), 1e-2)


def donated_gram_carry():
    """The CIFAR fit is resident and never reaches the donated Gram
    carry of streamed fits: a short ``StreamingDataset`` fit does."""
    import numpy as np

    from keystone_tpu.nodes.learning.linear import (
        LinearMapEstimator,
        accumulate_gram_carry,
    )
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )
    from keystone_tpu.utils.donation import donation_enabled

    check(donation_enabled(), "buffer donation is off on this backend")
    rng = np.random.RandomState(1)
    n, d, k, chunk = 8192, 896, 10, 1024
    X = rng.randn(n, d).astype(np.float32)
    W_true = rng.randn(d, k).astype(np.float32)
    Y = (X @ W_true + 0.01 * rng.randn(n, k)).astype(np.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        model = fit_streaming(
            LinearMapEstimator(lam=1e-3),
            StreamingDataset.from_numpy(X, chunk_size=chunk),
            StreamingDataset.from_numpy(Y, chunk_size=chunk))
        W = np.asarray(model.weights)
        # the update's donated inputs are dead after it
        first = accumulate_gram_carry(
            None, ArrayDataset.from_numpy(X[:chunk]),
            ArrayDataset.from_numpy(Y[:chunk]))
        accumulate_gram_carry(
            first, ArrayDataset.from_numpy(X[chunk:2 * chunk]),
            ArrayDataset.from_numpy(Y[chunk:2 * chunk]))
    donated = all(a.is_deleted() for a in first[:4])
    refused = [str(w.message) for w in caught if "donat" in str(w.message)]
    Xc = (X - X.mean(0)).astype(np.float64)
    Yc = (Y - Y.mean(0)).astype(np.float64)
    want = np.linalg.solve(Xc.T @ Xc + 1e-3 * np.eye(d), Xc.T @ Yc)
    dev = rel_dev(W, want)
    print(f"streamed fit (donated Gram carry): carry donated={donated}, "
          f"weights max deviation {dev:.2e} from the f64 solve",
          flush=True)
    check(donated, "the Gram carry update did not consume its inputs")
    check(not refused, f"donation refused: {refused[:2]}")
    check(dev <= 1e-3, f"streamed weights deviate {dev:.3e}")


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the generated dataset and the fit")
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    print(f"device: platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu_version}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}). Nothing was run and "
              "no result is printed.", file=sys.stderr)
        return 2

    from keystone_tpu import native
    from keystone_tpu.loaders.cifar_loader import load_cifar_numpy
    from keystone_tpu.utils.compile_cache import enable_compile_cache

    # the decoder the loader runs is compiled here, from
    # native/keystone_native.cpp as committed, never found on disk
    native.build()
    decoder = native.status()
    print(f"native: decoder={decoder['decoder']} built_in_this_run="
          f"{decoder['built_in_this_process']}; compile cache: "
          f"{enable_compile_cache()}", flush=True)
    check(decoder["decoder"] == "native", "the native decoder did not load")

    root = tempfile.mkdtemp(prefix="keystone-chip-smoke-")
    try:
        with phase("data"):
            train_path, test_path = write_data(root, args.seed)
        with phase("fit (CLI main)"):
            errors = fit_through_cli(train_path, test_path, args.seed)
        with phase("fit (run, prefix state)"):
            fitted = fit_again_for_pipeline(
                train_path, test_path, args.seed, errors)
        images = load_cifar_numpy(test_path)[0][:PARITY_ROWS]
        with phase("parity"):
            batch_ids, feats = batch_vs_datum(fitted, images)
        with phase("serving"):
            serve_and_query(fitted, images, batch_ids)
        with phase("kernels"):
            kernels(fitted, feats)
            donated_gram_carry()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print("peak_bytes_in_use per device: "
          + ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks), flush=True)
    check(max(peaks) <= 2 * min(peaks),
          f"devices do not hold comparable shards: {peaks}")
    print(f"train error {errors['train']:.4f}, test error "
          f"{errors['test']:.4f} at {NUM_FILTERS} filters over "
          f"{N_TRAIN} + {N_TEST} generated images; total wall "
          f"{time.perf_counter() - t_start:.1f} s (smoke timing)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
