"""Benchmark harness for the north-star RandomPatchCifar pipeline.

Default invocation emits ONE JSON line PER METRIC
(``{"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}``):

1. ``cifar_randompatch_images_per_sec_per_chip`` — featurization
   throughput (Convolver -> SymmetricRectifier -> Pooler -> classify) at
   the reference config (1024 filters, 6x6 patches, 14/13 pooling).
   vs_baseline = value / 10_000 (the BASELINE.json v5e north star).
2. ``cifar_e2e_images_per_sec_per_chip`` — END-TO-END throughput
   including the solve: featurize the train set, fit the
   BlockLeastSquares model (blockSize 4096), featurize + predict the
   test set. vs_baseline = value / 10_000.
3. ``block_ls_solver_tflops`` — one-pass BCD at CIFAR scale (n=50k,
   d=8192, blockSize 4096), HIGHEST-precision f32 GEMMs (the reference
   solved in f64). vs_baseline = value / 33 (~achievable peak at that
   precision: bf16 peak / 6 passes).
4. ``cifar_randompatch_test_error`` — test error of the REAL
   RandomPatchCifar app (full DAG: patch whitening, fused featurizer,
   StandardScaler, BlockLeastSquares, MaxClassifier). Runs on real
   CIFAR-10 when a binary copy is found ($CIFAR10_DIR or common paths);
   otherwise on a procedurally generated surrogate at CIFAR shapes,
   flagged by the extra "dataset" key. vs_baseline = 0.16 / value
   (>1 means better than the ~84% published-accuracy bar).

Plus per-app benches covering the rest of BASELINE.md's benchmark
configs: ``imagenet_rehearsal_images_per_sec_per_chip`` (SIFT->PCA->FV +
1000-class weighted solve at VGA shapes),
``mnist_random_fft_images_per_sec_per_chip`` (4 FFT branches, blockSize
2048) and ``timit_frames_per_sec_per_chip`` (8x4096 cosine features, 147
classes), each through the real app DAG on synthetic data with the
test error recorded in the metric line.

Streaming-ingest sections (``parallel/streaming.py``):
``tar_loader_sift_streamed_images_per_sec`` measures the tar -> decode
-> device -> SIFT path with the double-buffered prefetcher against the
serial path, and ``cifar_streamed_e2e_images_per_sec_per_chip`` runs the
out-of-core CIFAR fit (per-chunk featurize -> Gram/cross accumulate ->
finalize) under an asserted HBM ingest budget.

``--solver``/``--featurize``/``--e2e``/``--imagenet``/``--mnist``/
``--timit``/``--newsgroups``/``--accuracy``/``--streamed-e2e`` run a
single section (``newsgroups_docs_per_sec`` covers the BASELINE text
config: bigrams + binary TF + CommonSparseFeatures 100k + NaiveBayes).
``KEYSTONE_BENCH_SMALL=1`` shrinks sizes for CPU smoke-testing.

Budgeting: per-section durations measured on this host persist in
``.bench_durations.json``; over-budget sections SHRINK (scaled n/reps,
``"scaled"`` key on their metric lines) instead of being skipped, so
every historical metric appears in every artifact.
"""
from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

SMALL = os.environ.get("KEYSTONE_BENCH_SMALL") == "1"

#: Budget scale for the CURRENT section, set by main()'s scheduler.
#: 1.0 = full size; < 1.0 = the section was admitted over budget and
#: must SHRINK (fewer reps, scaled n) instead of being skipped, so
#: every BENCH_r*.json metric appears in every round (VERDICT r5
#: weak#1). Metric lines carry a "scaled" key whenever < 1.
_SCALE = 1.0

#: Floor for shrunk sections: below this the numbers stop meaning
#: anything (pure dispatch floor), so scaling clamps here.
_MIN_SCALE = 0.2


def _scaled(n, mult=1, floor=None):
    """``n`` shrunk by the current budget scale, rounded DOWN to a
    multiple of ``mult`` (shard/batch divisibility), floored at
    ``floor`` (default one ``mult``)."""
    floor = mult if floor is None else floor
    out = int(n * _SCALE) // mult * mult
    return max(out, floor)


#: Measured per-section durations from previous runs on this host
#: (written after every section): the scheduler budgets from evidence,
#: not hardcoded estimates — stale estimates are what skipped 4-5
#: sections in r4/r5.
_DURATIONS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".bench_durations.json")


def _load_durations() -> dict:
    """Read the persisted per-section duration estimates, validating
    them: a corrupt or hand-edited file (bad JSON, non-dict, negative /
    non-numeric / non-finite durations) is discarded with a warning and
    regenerated by the next clean runs — never allowed to crash the
    bench or poison the budget scheduler."""
    import sys

    try:
        with open(_DURATIONS_PATH) as f:
            raw = json.load(f)
    except FileNotFoundError:
        return {}
    except (json.JSONDecodeError, UnicodeDecodeError, OSError) as exc:
        print(f"bench: discarding unreadable {_DURATIONS_PATH} "
              f"({type(exc).__name__}: {exc}); section durations will "
              "be re-measured", file=sys.stderr)
        return {}
    if not isinstance(raw, dict):
        print(f"bench: discarding {_DURATIONS_PATH} (expected a JSON "
              f"object, got {type(raw).__name__}); section durations "
              "will be re-measured", file=sys.stderr)
        return {}
    out, bad = {}, []
    for key, value in raw.items():
        ok = (isinstance(key, str)
              and isinstance(value, (int, float))
              and not isinstance(value, bool)
              and np.isfinite(value) and value > 0)
        if ok:
            out[key] = float(value)
        else:
            bad.append(key)
    if bad:
        print(f"bench: ignoring {len(bad)} invalid duration "
              f"entr{'y' if len(bad) == 1 else 'ies'} in "
              f"{_DURATIONS_PATH} ({', '.join(map(str, bad[:5]))}); "
              "those sections will be re-measured", file=sys.stderr)
    return out


def _record_duration(name: str, seconds: float) -> None:
    """Persist a section duration estimate. The write POLICY lives at
    the call sites in ``main()``: clean full-size non-SMALL runs record
    their measured wall, and budget-shrunk runs only DECAY an existing
    estimate toward their observed wall (never extrapolate a shrunk
    wall upward — mostly fixed compile/setup overhead would inflate the
    estimate and ratchet the section into permanent shrinking; SMALL
    smoke runs and retried sections never write at all)."""
    durations = _load_durations()
    durations[name] = round(seconds, 1)
    tmp = _DURATIONS_PATH + f".tmp{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(durations, f, indent=1, sort_keys=True)
        os.replace(tmp, _DURATIONS_PATH)
    except Exception:
        pass

#: Wall-clock budget for the full run. Round 2's driver kill (rc=124)
#: came AFTER ~910s of completed sections (featurize/solver/imagenet/
#: e2e/mnist all emitted), so the driver timeout is >~910s. 780 keeps
#: >2 minutes of margin under that bound for a section that overruns
#: its estimate after being admitted (the per-section check bounds
#: start times, not overruns), so the process always reaches its own
#: exit path and the lowest-priority sections are the ones sacrificed,
#: explicitly. A fully warm-cache run measures ~460s total.
BUDGET_S = float(os.environ.get("KEYSTONE_BENCH_BUDGET_S", "780"))
_START = time.monotonic()

FLAGSHIP = "cifar_randompatch_images_per_sec_per_chip"

_emitted = 0
_metrics: dict = {}  # metric name -> emitted line (for the summary line)
_section_buffer = None  # list while a section runs under _run_section
_scaled_sections: set = set()  # sections run at _SCALE < 1 this run


def _emit_meta():
    """Emit the ``bench_meta`` identity line: hostname, device kind,
    jax version, and which sections ran budget-shrunk. ``benchdiff``
    reads it from the artifact's stdout tail — cross-HOST comparisons
    refuse without ``--force`` (different host = different experiment),
    and shrunk sections are excluded from regression classification
    (their metric lines carry ``scaled`` keys; the list here is the
    run-level summary). Emitted at start (so a cut-short run still
    carries its identity) and again before the final summary (with the
    complete scaled-sections list)."""
    import socket

    dev = jax.devices()[0]
    device_kind, backend, n_dev = (
        dev.device_kind, dev.platform, len(jax.devices()))
    print(json.dumps({"bench_meta": {
        "hostname": socket.gethostname(),
        "device_kind": device_kind,
        "backend": backend,
        "num_devices": n_dev,
        "jax_version": jax.__version__,
        "small": SMALL,
        "scaled_sections": sorted(_scaled_sections),
    }}), flush=True)


def _emit(metric, value, unit, vs_baseline, **extra):
    line = {"metric": metric, "value": value, "unit": unit,
            "vs_baseline": vs_baseline}
    if _SCALE < 1.0:
        # budget-shrunk section: the value was measured at reduced
        # size/reps — comparable only with other runs at scale 1.0
        # once the budget recovers, never silently absent
        line["scaled"] = round(_SCALE, 2)
    line.update(extra)
    if _section_buffer is not None:
        # held until the section completes: a failed attempt's partial
        # lines never reach stdout, so a retry cannot emit duplicate
        # metric lines with stale values
        _section_buffer.append(line)
    else:
        _flush_line(line)


def _flush_line(line):
    global _emitted
    print(json.dumps(line), flush=True)
    _metrics[line["metric"]] = line
    _emitted += 1


def _emit_summary():
    """Restate the flagship metric with every other section's value as
    extra keys. Called after EVERY section: the driver parses the LAST
    stdout JSON line as the headline, so whenever the run is cut short
    the headline is still the flagship with all evidence so far."""
    flag = _metrics.get(FLAGSHIP)
    if flag is None or len(_metrics) < 2:
        return
    line = dict(flag)
    line["summary"] = True
    for name, other in _metrics.items():
        if name == FLAGSHIP:
            continue
        line[name] = other["value"]
        if name == "cifar_randompatch_test_error":
            # linear_pixels_contrast_baseline must travel with the
            # error it contextualizes: without it the parsed headline
            # presents the raw-pixel 0.93 as a broken app (r4 weak#7)
            for key in ("dataset", "linear_pixels_test_error",
                        "linear_pixels_contrast_baseline"):
                if key in other:
                    line["accuracy_" + key if key == "dataset" else key] = \
                        other[key]
    print(json.dumps(line), flush=True)


def _timed_median(work, *, setup=None, reps=None, target_window=2.0,
                  max_mult=16, warmup_fence=False, compile_wall0=None):
    """Median-of-``reps`` seconds-per-call, each rep measured over a
    window of >= ``target_window`` seconds (the call repeated ``m``
    times per window when a single call is shorter).

    ``warmup_fence=True`` splits cold-compile wall out of the timed
    section via the compile observatory: the two estimate calls double
    as the warmup that drains every pending compile, the XLA compile
    wall they absorbed is reported as ``compile_s`` on the metric line
    (the un-attributed component of the documented 76-85k e2e noise
    band, now attributed), and the observatory's warmup fence is armed
    around the timed reps — any compile INSIDE them is a flagged
    unexpected recompile (``compile.unexpected_total``), not silent
    timing noise.

    Round 4's single-shot 0.2-0.5 s refit windows read host jitter as
    app regressions (VERDICT r4 weak#2/next#3: mnist "-53%", tar loader
    "-47%" with no code cause); a >= 2 s window caps the dispatch-floor
    share at ~1% and the median rejects one-off executable-load stalls.
    The window multiplier comes from the MIN of two estimate calls
    (ADVICE r5 low#5: a one-off executable-load stall in a single
    unguarded estimate inflates est, collapsing m to 1 and undersizing
    every rep's window — the exact jitter this helper exists to reject).
    Returns (median_dt, evidence) where evidence carries the window
    multiplier, rep count, and rep spread for the metric line.
    Budget-shrunk sections (``_SCALE < 1``) default to 2 reps over a
    proportionally smaller window — the floor-scaled trailing sections
    must fit the margin the driver's kill window leaves."""
    if reps is None:
        reps = 3 if _SCALE >= 1.0 else 2
    if _SCALE < 1.0:
        target_window = max(0.5, target_window * _SCALE)
    obs = None
    if warmup_fence:
        from keystone_tpu.observability import compile_observatory

        obs = compile_observatory()
        # sections that warm explicitly BEFORE calling in pass the
        # observatory wall snapshotted before that warm call — without
        # it the cold compiles all land in the section's own warm-up
        # and the emitted compile_s is vacuously ~0
        if compile_wall0 is None:
            compile_wall0 = obs.wall_s_total()
    est = float("inf")
    for _ in range(2):
        if setup is not None:
            setup()
        t0 = time.perf_counter()
        work()
        est = min(est, time.perf_counter() - t0)
    m = max(1, min(max_mult, int(np.ceil(target_window / max(est, 1e-3)))))
    compile_s = None
    if obs is not None:
        # compiles are synchronous on the dispatching thread, so after
        # the estimate calls return the pending set is drained; what
        # remains is steady state and the fence makes that assertable
        compile_s = round(obs.wall_s_total() - compile_wall0, 3)
        obs.arm_fence("bench:timed")
    times = []
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(m):
                if setup is not None:
                    setup()  # host-side cache clear, microseconds
                work()
            times.append((time.perf_counter() - t0) / m)
    finally:
        if obs is not None:
            obs.disarm_fence()
    med = float(np.median(times))
    ev = {"timing_reps": reps, "timing_window_mult": m,
          "timing_spread": round((max(times) - min(times)) / med, 3)}
    if compile_s is not None:
        ev["compile_s"] = compile_s
    return med, ev


def _ingest_stall_probe(n_chunks_per_run, n_images_per_run=None):
    """Snapshot the streaming metrics and return ``share(dt)``: the
    per-run ingest stall as a fraction of ``dt`` seconds. The metrics
    accumulate across every invocation ``_timed_median`` makes
    (estimation calls + window reps), so the stall delta is normalized
    by the observed run count before dividing — the ONE home of that
    subtlety, shared by the loader and streamed-e2e sections.

    ``share.h2d_bytes_per_image()`` reads the ``streaming.h2d_bytes``
    counter delta the same normalized way: actual wire bytes shipped
    host->device per image, the number that shows dtype-on-the-wire
    working (uint8 sources ~1/4 of an f32 wire) next to the wall-time
    keys."""
    from keystone_tpu.observability import MetricsRegistry

    reg = MetricsRegistry.get_or_create()
    stall_h = reg.histogram("streaming.ingest_stall_s")
    chunks_c = reg.counter("streaming.chunks_total")
    h2d_c = reg.counter("streaming.h2d_bytes")
    stall0, chunks0, h2d0 = stall_h.total, chunks_c.value, h2d_c.value

    def _runs():
        return max(1.0, (chunks_c.value - chunks0) / n_chunks_per_run)

    def share(dt):
        return round(min(
            ((stall_h.total - stall0) / _runs()) / max(dt, 1e-9), 1.0), 3)

    def h2d_bytes_per_image():
        per_run = (h2d_c.value - h2d0) / _runs()
        return round(per_run / max(n_images_per_run or 1, 1), 1)

    share.h2d_bytes_per_image = h2d_bytes_per_image
    return share


def _fence(tree) -> None:
    """End a timed region: wait for the device. On this installation
    ``block_until_ready`` waits for execution itself (a four-matmul
    chain read 18.6 ms to ready against 19.3 ms to a pulled scalar; my
    chip run, PR 21), so no scalar pull is needed, and ``np.asarray``
    of the full result would time the d2h copy instead of the chip."""
    jax.block_until_ready(
        [x for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "dtype")])


# ------------------------------------------------------- featurize bench


def build_bench(num_filters=1024, patch_size=6, alpha=0.25):
    from keystone_tpu.ops.image_ops import filter_bank_convolve, pool_image
    from keystone_tpu.ops.pallas_kernels import (
        fused_cifar_featurize,
        use_pallas,
    )

    rng = np.random.RandomState(0)
    filters = rng.randn(num_filters, patch_size * patch_size * 3).astype(np.float32)
    w = rng.randn(num_filters * 2 * 2 * 2, 10).astype(np.float32) * 0.01
    b = rng.randn(10).astype(np.float32)

    if use_pallas():
        # fused Pallas featurization: conv/rectify/pool stay in VMEM
        @jax.jit
        def featurize_and_predict(imgs):
            feats = fused_cifar_featurize(
                imgs, jnp.asarray(filters), 32, patch_size, 3, 13, 14,
                10.0, alpha)
            return jnp.argmax(feats @ w + b, axis=-1)

        return featurize_and_predict

    @jax.jit
    def featurize_and_predict(imgs):
        def one(img):
            conv = filter_bank_convolve(
                img, jnp.asarray(filters), patch_size, 3, True,
                None, 10.0,
            )
            pos = jnp.maximum(0.0, conv - alpha)
            neg = jnp.maximum(0.0, -conv - alpha)
            r = jnp.concatenate([pos, neg], axis=-1)
            pooled = pool_image(r, 13, 14, "identity", "sum")
            return pooled.reshape(-1)

        feats = jax.vmap(one)(imgs)
        return jnp.argmax(feats @ w + b, axis=-1)

    return featurize_and_predict


def featurize_bench():
    n_dev = len(jax.devices())
    batch = 256 if SMALL else 1024
    iters = 3 if SMALL else _scaled(64, mult=4, floor=8)
    imgs = jax.device_put(
        (np.random.RandomState(1).rand(batch, 32, 32, 3) * 255)
        .astype(np.float32))

    one = build_bench(num_filters=128 if SMALL else 1024)

    # all iterations in ONE dispatch (a Python loop of per-batch
    # dispatches measures the host round-trip, 1.5 ms a dispatch on
    # this installation, not the featurizer), over ONE uploaded batch
    # perturbed per iteration — the +i keeps the loop body
    # iteration-dependent so XLA cannot hoist the featurization out of
    # the lax.map
    @jax.jit
    def fn(b):
        return jax.lax.map(
            lambda i: one(b + i), jnp.arange(iters, dtype=jnp.float32))

    _fence(fn(imgs))  # warmup / compile
    _fence(fn(imgs))

    start = time.perf_counter()
    out = fn(imgs)
    _fence(out)
    elapsed = time.perf_counter() - start

    per_chip = batch * iters / elapsed / n_dev
    _emit("cifar_randompatch_images_per_sec_per_chip", round(per_chip, 1),
          "images/sec/chip", round(per_chip / 10000.0, 4))


# ------------------------------------------------------------ e2e bench


def e2e_bench():
    """Featurize + SOLVE + predict, the number VERDICT r1 asked for.

    Everything device-resident end to end: batches are staged once
    before timing (a PCIe copy that production overlaps with compute;
    the link measured 1.5 GB/s host to device), features stay on
    device, the block solve consumes the device-resident feature matrix,
    and prediction reduces to class ids before the final host sync.
    """
    from keystone_tpu.ops.pallas_kernels import (
        fused_cifar_featurize,
        use_pallas,
    )
    from keystone_tpu.ops.image_ops import filter_bank_convolve, pool_image

    n_dev = len(jax.devices())
    num_filters = 128 if SMALL else 1024
    patch = 6
    batch = 512 if SMALL else 2_048
    n_train = 2_048 if SMALL else _scaled(20_480, mult=batch, floor=2 * batch)
    n_test = 512 if SMALL else _scaled(4_096, mult=batch, floor=batch)

    rng = np.random.RandomState(2)
    filters = rng.randn(num_filters, patch * patch * 3).astype(np.float32)

    if use_pallas():
        @jax.jit
        def featurize(imgs):
            return fused_cifar_featurize(
                imgs, jnp.asarray(filters), 32, patch, 3, 13, 14, 10.0, 0.25)
    else:
        @jax.jit
        def featurize(imgs):
            def one(img):
                conv = filter_bank_convolve(
                    img, jnp.asarray(filters), patch, 3, True, None, 10.0)
                pos = jnp.maximum(0.0, conv - 0.25)
                neg = jnp.maximum(0.0, -conv - 0.25)
                return pool_image(
                    jnp.concatenate([pos, neg], -1), 13, 14, "identity", "sum"
                ).reshape(-1)

            return jax.vmap(one)(imgs)

    y_tr = rng.randint(0, 10, n_train)
    L_host = (-np.ones((n_train, 10)) + 2.0 * np.eye(10)[y_tr]).astype(np.float32)

    # images generated ON DEVICE (throughput content is irrelevant, and
    # a ~300 MB host stack would be 0.2 s of untimed staging at the
    # measured 1.5 GB/s: kept because it is less code than a host
    # stack, no longer because the link is slow); only the small label
    # matrix is uploaded. Batches sharded over the data axis so
    # dividing by device count below is earned on multi-chip hosts.
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel.mesh import make_mesh

    import functools

    sh = NamedSharding(make_mesh(jax.devices()), P(None, "data"))

    @functools.partial(jax.jit, static_argnames=("n",), out_shardings=sh)
    def gen_images(key, n):
        assert n % batch == 0, (  # silent // truncation would inflate
            f"{n} images not divisible by batch {batch}")  # the metric
        return 255.0 * jax.random.uniform(
            key, (n // batch, batch, 32, 32, 3), jnp.float32)

    train_dev = gen_images(jax.random.PRNGKey(3), n_train)
    test_dev = gen_images(jax.random.PRNGKey(4), n_test)
    L = jax.device_put(L_host, NamedSharding(sh.mesh, P("data")))
    _fence((train_dev, test_dev, L))  # staging fence, untimed

    # the whole train path (featurize every batch -> center -> BCD
    # solve) stages into ONE jit, and prediction into another: the
    # estimator's own staged solve core (block_least_squares), without a
    # host round-trip per batch. lax.map featurizes batch-at-a-
    # time so HBM holds one batch of conv activations, not all of them.
    from keystone_tpu.nodes.learning.linear import block_least_squares

    F = num_filters * 2 * 2 * 2
    bounds = tuple((i, min(F, i + 4096)) for i in range(0, F, 4096))

    @jax.jit
    def train_step(imgs_stacked, L):
        feats = jax.lax.map(featurize, imgs_stacked)
        X = feats.reshape(n_train, F)
        Ws, x_mean, y_mean = block_least_squares(
            X, L, n_train, 0.1, bounds, 1)
        return jnp.concatenate(list(Ws), axis=0), x_mean, y_mean

    @jax.jit
    def predict_all(imgs_stacked, W, x_mean, y_mean):
        f = jax.lax.map(featurize, imgs_stacked)
        return jnp.argmax(
            (f.reshape(-1, F) - x_mean) @ W + y_mean, axis=-1)

    def fit_and_predict():
        W, x_mean, y_mean = train_step(train_dev, L)
        return np.asarray(predict_all(test_dev, W, x_mean, y_mean))

    # warm EVERYTHING outside the timed region (featurize, the solver's
    # _block_solve at full shapes, predict) — steady-state throughput is
    # the metric; XLA compiles once per shape
    from keystone_tpu.observability import compile_observatory

    compile_wall0 = compile_observatory().wall_s_total()
    fit_and_predict()

    elapsed, ev = _timed_median(fit_and_predict, warmup_fence=True,
                                compile_wall0=compile_wall0)
    per_chip = (n_train + n_test) / elapsed / n_dev
    _emit("cifar_e2e_images_per_sec_per_chip", round(per_chip, 1),
          "images/sec/chip", round(per_chip / 10000.0, 4), **ev)


# --------------------------------------------------------- solver bench


def solver_bench():
    """BASELINE: "block-LS solver TFLOPS" — one-pass BCD at CIFAR scale
    (n=50k, d=8192 in 4096 blocks, k=10)."""
    import functools

    from keystone_tpu.ops import linalg

    n, d, k, bs = (5_000, 1024, 10, 512) if SMALL else (50_000, 8192, 10, 4096)
    # generated ON DEVICE: content is irrelevant here, and it saves
    # staging a 1.6 GB host matrix (about a second at the measured
    # 1.5 GB/s host-to-device rate)
    keys = jax.random.split(jax.random.PRNGKey(0), d // bs + 1)
    blocks = tuple(
        jax.random.normal(keys[i], (n, bs), jnp.float32)
        for i in range(d // bs))
    Y = jax.random.normal(keys[-1], (n, k), jnp.float32)
    _fence((blocks, Y))  # staging fence, untimed
    run = jax.jit(functools.partial(linalg.bcd_core, num_passes=1))
    _fence(run(blocks, Y, jnp.float32(0.1)))
    iters = 2 if SMALL else _scaled(5, floor=2)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run(blocks, Y, jnp.float32(0.1))
    _fence(out)
    dt = (time.perf_counter() - t0) / iters
    flops = sum(
        2 * n * A.shape[1] ** 2 + A.shape[1] ** 3 / 3 + 4 * n * A.shape[1] * k
        for A in blocks)
    # TPU-calibrated auto-solver evidence (VERDICT r4 next#4): the
    # shipped cost-model weights must pick the solver measured fastest
    # on this chip (block_ls at every solver-bench shape; calibration
    # agreement 3/3 — tools/calibrate_cost_model.py)
    from keystone_tpu.nodes.learning import (
        BlockLeastSquaresEstimator,
        LeastSquaresEstimator,
    )
    from keystone_tpu.parallel.dataset import ArrayDataset

    rng_s = np.random.RandomState(0)
    tiny = ArrayDataset.from_numpy(rng_s.rand(8, d).astype(np.float32))
    tiny_l = ArrayDataset.from_numpy(rng_s.rand(8, k).astype(np.float32))
    pick = LeastSquaresEstimator().optimize(
        tiny, tiny_l, n=n, num_machines=1).node
    # solver GEMMs run at HIGHEST f32 precision (6 bf16 MXU passes;
    # reference solvers were f64) — achievable peak is ~bf16_peak/6
    _emit("block_ls_solver_tflops", round(flops / dt / 1e12, 2), "TFLOPS",
          round(flops / dt / 1e12 / 33.0, 4),
          auto_solver_tpu_choice=type(pick).__name__,
          auto_solver_choice_matches_measured=isinstance(
              pick, BlockLeastSquaresEstimator))


# ------------------------------------------------------- accuracy bench


def find_real_cifar10():
    """Binary CIFAR-10 (data_batch_*.bin + test_batch.bin) under
    $CIFAR10_DIR or common locations; None if absent."""
    import glob

    candidates = [os.environ.get("CIFAR10_DIR", "")]
    candidates += [
        "/root/data/cifar-10-batches-bin", "/root/data/cifar10",
        "/data/cifar-10-batches-bin", "/data/cifar10",
        "./data/cifar-10-batches-bin", "/tmp/cifar-10-batches-bin",
    ]
    for base in candidates:
        if not base or not os.path.isdir(base):
            continue
        train = sorted(glob.glob(os.path.join(base, "data_batch_*.bin")))
        test = os.path.join(base, "test_batch.bin")
        if len(train) == 5 and os.path.exists(test):
            return train, test
    return None


def accuracy_bench():
    from keystone_tpu.loaders.cifar_loader import cifar_loader
    from keystone_tpu.loaders.cifar_surrogate import make_surrogate_cifar
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.cifar.random_patch_cifar import (
        RandomCifarConfig,
        run,
    )

    real = find_real_cifar10()
    if real is not None:
        train_files, test_file = real
        train = cifar_loader(os.path.dirname(train_files[0]) + "/data_batch_*.bin")
        test = cifar_loader(test_file)
        dataset = "cifar10"
        num_filters = 1024
    else:
        (tr_x, tr_y), (te_x, te_y) = make_surrogate_cifar(
            1_024 if SMALL else _scaled(10_240, mult=512, floor=4_096),
            256 if SMALL else _scaled(2_048, mult=256, floor=1_024))
        train = LabeledData(ArrayDataset.from_numpy(tr_x),
                            ArrayDataset.from_numpy(tr_y.astype(np.int32)))
        test = LabeledData(ArrayDataset.from_numpy(te_x),
                           ArrayDataset.from_numpy(te_y.astype(np.int32)))
        dataset = "surrogate"
        num_filters = 64 if SMALL else 512

    config = RandomCifarConfig(num_filters=num_filters, lam=10.0, seed=0)
    _, _, test_eval = run(config, train=train, test=test)
    err = float(test_eval.total_error)
    extra = dict(dataset=dataset, num_filters=num_filters)
    if dataset == "surrogate":
        # context: the raw-pixel linear baseline on the same data — the
        # surrogate is built so patch-conv featurization beats it by a
        # wide margin; a numerics regression in the pipeline collapses
        # the gap
        from keystone_tpu.pipelines.images.cifar.linear_pixels import (
            run as run_linear,
            LinearPixelsConfig,
        )

        _, _, lin_eval = run_linear(
            LinearPixelsConfig(lam=10.0), train=train, test=test)
        extra["linear_pixels_test_error"] = round(
            float(lin_eval.total_error), 4)
        # VERDICT r3 weak #5: this number is near-random BY DESIGN (the
        # surrogate is constructed so raw pixels fail); flag it so a
        # reader of BENCH_r*.json doesn't mistake it for a broken app
        extra["linear_pixels_contrast_baseline"] = True
    _emit("cifar_randompatch_test_error", round(err, 4), "test error",
          round(0.16 / max(err, 1e-4), 4), **extra)


# ------------------------------------------------ TIMIT / MNIST configs


def _clear_prefix_state():
    """Drop cross-pipeline prefix-cache state so a timed rerun of an app
    actually refits instead of reusing the warm run's fitted results."""
    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.get_or_create().clear_state()


def timit_bench():
    """TIMIT at the reference scale defaults (BASELINE.md: 50 x 4096
    cosine random features over 440-dim inputs, 147 classes,
    TimitPipeline.scala:24-35): featurize + one-epoch block solve +
    predict, frames/sec/chip, everything device-resident. No published
    baseline; vs_baseline against a 10k frames/sec/chip strawman."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.loaders.timit import TimitFeaturesData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.speech.timit import TimitConfig, run

    n_dev = len(jax.devices())
    # 16k x 32k features = 2.1 GB; the centered solver copy + warm-run
    # remnants must co-exist in HBM on the single bench chip
    n_train = 2_048 if SMALL else _scaled(16_384, mult=1_024, floor=4_096)
    n_test = 512 if SMALL else _scaled(2_048, mult=512, floor=1_024)
    num_cosines = 2 if SMALL else 8     # branches of 4096 features
    k, d = 147, 440

    rng = np.random.RandomState(0)
    protos = rng.randn(k, d).astype(np.float32)  # class prototypes

    def split(n, seed):
        # noise sized for genuine class overlap (||proto_i - proto_j||
        # ~ sqrt(2d) ~ 29.7, sigma 4.0 (3.0 saturated to 0.5% error at full size)
        # sigma across 146 competitors): the Bayes error is nonzero and
        # train-size-independent, so the emitted test_error cannot
        # saturate at 0.00% at full scale (VERDICT r2 weak#3) — real
        # TIMIT phone classification sits near ~33% error itself
        r = np.random.RandomState(seed)
        y = r.randint(0, k, n)
        X = (protos[y] + 4.0 * r.randn(n, d)).astype(np.float32)
        return LabeledData(ArrayDataset.from_numpy(X),
                           ArrayDataset.from_numpy(y.astype(np.int32)))

    data = TimitFeaturesData(train=split(n_train, 1), test=split(n_test, 2))
    # gamma matched to the synthetic feature scale (||x-x'||^2 ~ 2d);
    # the app default 0.0555 is calibrated for real TIMIT features
    config = TimitConfig(num_cosines=num_cosines, num_epochs=1, lam=1e-2,
                         gamma=1.0 / (2 * d))

    run(config, data=data)  # warm: DAG tracing + XLA compiles
    import gc

    gc.collect()            # release the warm run's HBM before refitting
    result = {}

    def refit():
        result["eval"] = run(config, data=data)[1]

    dt, ev = _timed_median(refit, setup=_clear_prefix_state)
    per_chip = (n_train + n_test) / dt / n_dev
    _emit("timit_frames_per_sec_per_chip", round(per_chip, 1),
          "frames/sec/chip", round(per_chip / 10_000.0, 4),
          num_cosine_features=num_cosines * 4096,
          test_error=round(float(result["eval"].total_error), 4), **ev)


def mnist_bench():
    """MnistRandomFFT at the README example scale (4 FFT branches,
    blockSize 2048, BASELINE.md): images/sec/chip through the real app
    DAG on synthetic MNIST-shaped data."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.pipelines.images.mnist.random_fft import (
        MnistRandomFFTConfig,
        run,
    )

    n_dev = len(jax.devices())
    n_train = 2_048 if SMALL else _scaled(16_384, mult=1_024, floor=4_096)
    n_test = 512 if SMALL else _scaled(2_048, mult=512, floor=1_024)

    rng = np.random.RandomState(0)
    # tight prototypes under 0.35 noise so the task has genuine overlap
    # (the old wide U[0,1] protos saturated test_error at 0.00% at full
    # train scale, VERDICT r2 weak#3). The 0.18 spread is empirical:
    # [0,1] clipping plus the sign->FFT->rectify featurization loses
    # enough margin that the full-size Bayes floor is real
    # (0.18 and 0.10 both saturated to 0.0 at the full 16384-example size; pairwise discriminant ~2.8 sigma at 0.05); the full-size value is what is checked
    # non-saturated on the bench chip.
    protos = (0.5 + 0.05 * rng.randn(10, 784)).astype(np.float32)

    def split(n, seed):
        r = np.random.RandomState(seed)
        y = r.randint(0, 10, n)
        X = np.clip(protos[y] + 0.35 * r.randn(n, 784), 0, 1).astype(
            np.float32)
        return LabeledData(ArrayDataset.from_numpy(X),
                           ArrayDataset.from_numpy(y.astype(np.int32)))

    train, test = split(n_train, 1), split(n_test, 2)
    config = MnistRandomFFTConfig(num_ffts=4, block_size=2048, lam=1e-2)

    run(config, train=train, test=test)  # warm: DAG tracing + XLA compiles
    result = {}

    def refit():
        result["eval"] = run(config, train=train, test=test)[2]

    dt, ev = _timed_median(refit, setup=_clear_prefix_state)
    per_chip = (n_train + n_test) / dt / n_dev
    _emit("mnist_random_fft_images_per_sec_per_chip", round(per_chip, 1),
          "images/sec/chip", round(per_chip / 10_000.0, 4),
          test_error=round(float(result["eval"].total_error), 4), **ev)


def newsgroups_bench():
    """NewsgroupsPipeline at the reference featurization config
    (BASELINE.md: bigrams + binary TermFrequency + CommonSparseFeatures
    100k + NaiveBayes, NewsgroupsPipeline.scala:24-31) on a synthetic
    20-class corpus: docs/sec through the real app DAG. The featurizer
    and the sparse NaiveBayes fit are host-stage (tokenize/ngram/count —
    CPU-bound in the reference's Spark executors too); scoring runs as
    the padded-COO device einsum. No published baseline; vs_baseline
    against a 1k docs/sec strawman.
    """
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset, HostDataset
    from keystone_tpu.pipelines.text.newsgroups import (
        NewsgroupsConfig,
        run,
    )

    n_classes = 20
    n_train = 512 if SMALL else _scaled(4_096, mult=256, floor=1_024)
    n_test = 128 if SMALL else _scaled(1_024, mult=128, floor=256)
    words_per_doc = 40

    rng = np.random.RandomState(0)
    # class vocabularies drawn from a SHARED sliding window — adjacent
    # classes overlap in half their discriminative words, and the
    # per-doc count of own-class words is random (binomial, sometimes
    # zero), so neighbor confusion is irreducible and the emitted
    # test_error cannot saturate at 0.00% (VERDICT r2 weak#3)
    common = [f"word{i}" for i in range(2_000)]
    class_vocab = [
        [f"g{(c * 25 + i) % (n_classes * 25)}" for i in range(50)]
        for c in range(n_classes)
    ]

    def corpus(n, seed):
        r = np.random.RandomState(seed)
        y = r.randint(0, n_classes, n)
        docs = []
        for i in range(n):
            own = r.choice(class_vocab[y[i]], r.binomial(words_per_doc // 4, 0.6))
            noise = r.choice(common, words_per_doc - len(own))
            words = np.concatenate([own, noise])
            r.shuffle(words)
            docs.append(" ".join(words))
        return LabeledData(
            data=HostDataset(docs),
            labels=ArrayDataset.from_numpy(y.astype(np.int32)),
        )

    train, test = corpus(n_train, 1), corpus(n_test, 2)
    config = NewsgroupsConfig(n_grams=2, common_features=100_000)

    run(config, train=train, test=test, num_classes=n_classes)  # warm
    result = {}

    def refit():
        result["eval"] = run(config, train=train, test=test,
                             num_classes=n_classes)[1]

    dt, ev = _timed_median(refit, setup=_clear_prefix_state)
    per_sec = (n_train + n_test) / dt
    _emit("newsgroups_docs_per_sec", round(per_sec, 1), "docs/sec",
          round(per_sec / 1_000.0, 4),
          test_error=round(float(result["eval"].total_error), 4), **ev)


def amazon_bench():
    """AmazonReviewsPipeline (reference
    AmazonReviewsPipeline.scala:25-33: bigrams + binary TermFrequency +
    CommonSparseFeatures 100k + logistic regression) on a synthetic
    sentiment corpus: docs/sec through the real app DAG. Sentiment words
    are drawn from overlapping positive/negative windows with random
    per-doc counts, so the emitted accuracy cannot saturate. No published
    baseline; vs_baseline against the same 1k docs/sec strawman as
    newsgroups."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset, HostDataset
    from keystone_tpu.pipelines.text.amazon_reviews import (
        AmazonReviewsConfig,
        run,
    )

    n_train = 512 if SMALL else _scaled(4_096, mult=256, floor=1_024)
    n_test = 128 if SMALL else _scaled(1_024, mult=128, floor=256)
    words_per_doc = 40
    common = [f"word{i}" for i in range(2_000)]
    # two overlapping 60-word sentiment windows over a shared 90-word
    # affect vocabulary: 30 words are ambiguous
    affect = [f"s{i}" for i in range(90)]
    polarity_vocab = [affect[:60], affect[30:]]

    def corpus(n, seed):
        r = np.random.RandomState(seed)
        y = r.randint(0, 2, n)
        docs = []
        for i in range(n):
            own = r.choice(polarity_vocab[y[i]],
                           r.binomial(words_per_doc // 4, 0.6))
            noise = r.choice(common, words_per_doc - len(own))
            words = np.concatenate([own, noise])
            r.shuffle(words)
            docs.append(" ".join(words))
        return LabeledData(
            data=HostDataset(docs),
            labels=ArrayDataset.from_numpy(y.astype(np.int32)),
        )

    train, test = corpus(n_train, 1), corpus(n_test, 2)
    config = AmazonReviewsConfig(n_grams=2, common_features=100_000,
                                 num_iters=10)
    run(config, train=train, test=test)  # warm
    _clear_prefix_state()
    t0 = time.perf_counter()
    _, ev = run(config, train=train, test=test)
    dt = time.perf_counter() - t0
    per_sec = (n_train + n_test) / dt
    _emit("amazon_docs_per_sec", round(per_sec, 1), "docs/sec",
          round(per_sec / 1_000.0, 4),
          test_error=round(float(ev.error), 4))


def stupid_backoff_bench():
    """StupidBackoffPipeline (reference StupidBackoffPipeline.scala:
    31-45: tokenize -> frequency-encode -> 2..n-grams -> counts ->
    Stupid Backoff LM) on a synthetic Zipf-ish corpus: scored ngrams/sec
    through the real app. Host-stage by design (the reference's is a
    Spark shuffle job); vs_baseline against a 100k ngrams/sec strawman."""
    from keystone_tpu.parallel.dataset import HostDataset
    from keystone_tpu.pipelines.nlp.stupid_backoff_pipeline import (
        StupidBackoffConfig,
        run,
    )

    n_lines = 400 if SMALL else _scaled(4_000, mult=100, floor=1_000)
    words_per_line = 20
    rng = np.random.RandomState(0)
    # Zipf-ish unigram law over a 5k vocabulary: real backoff mass
    vocab = np.array([f"w{i}" for i in range(5_000)])
    probs = 1.0 / np.arange(1, len(vocab) + 1) ** 1.1
    probs /= probs.sum()
    lines = [
        " ".join(rng.choice(vocab, words_per_line, p=probs))
        for _ in range(n_lines)
    ]
    text = HostDataset(lines)
    config = StupidBackoffConfig(n=3)
    t0 = time.perf_counter()
    lm = run(config, text=text)
    dt = time.perf_counter() - t0
    per_sec = len(lm.scores) / dt
    _emit("stupid_backoff_ngrams_per_sec", round(per_sec, 1), "ngrams/sec",
          round(per_sec / 100_000.0, 4),
          num_ngrams=len(lm.scores), num_tokens=int(lm.num_tokens))


def voc_bench():
    """VOCSIFTFisher (reference VOCSIFTFisher.scala:42-108) on a
    synthetic multi-label set with orientation-coded classes: MAP plus
    images/sec through the full SIFT -> PCA -> FV -> BlockLS -> MAP DAG.
    Oriented stripes + heavy noise keep MAP meaningfully below 1.0. No
    published baseline; vs_baseline against the 0.59 MAP the VOC paper
    config reports in the literature (Chatfield et al. FV baseline)."""
    from keystone_tpu.loaders.image_loader_utils import MultiLabeledImage
    from keystone_tpu.parallel.dataset import HostDataset
    from keystone_tpu.pipelines.images.voc.voc_sift_fisher import (
        SIFTFisherConfig,
        run,
    )

    n_imgs = 24 if SMALL else _scaled(96, mult=8, floor=32)
    side = 96
    n_cls = 20
    rng = np.random.RandomState(0)
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float32)

    def make(n, seed):
        r = np.random.RandomState(seed)
        items = []
        for i in range(n):
            labels = sorted(set(r.randint(0, n_cls, r.randint(1, 3))))
            img = r.rand(side, side, 3).astype(np.float32) * 160
            for c in labels:
                ang = np.pi * c / n_cls
                stripes = np.sin((np.cos(ang) * xx + np.sin(ang) * yy)
                                 / 2.5)
                img += 45.0 * stripes[:, :, None]
            items.append(MultiLabeledImage(
                np.clip(img, 0, 255), [int(c) for c in labels],
                f"im{i}.jpg"))
        return HostDataset(items)

    train, test = make(n_imgs, 1), make(max(n_imgs // 4, 8), 2)
    config = SIFTFisherConfig(
        lam=0.5, desc_dim=32, vocab_size=8,
        num_pca_samples=int(2e5), num_gmm_samples=int(2e5), block_size=512)
    kw = dict(step=6, num_scales=3)
    run(config, train=train, test=test, sift_kwargs=kw)  # warm
    _clear_prefix_state()
    t0 = time.perf_counter()
    _, ap = run(config, train=train, test=test, sift_kwargs=kw)
    dt = time.perf_counter() - t0
    n_total = len(train) + len(test)
    vmap = float(np.mean(ap))
    _emit("voc_map", round(vmap, 4), "MAP", round(vmap / 0.59, 4),
          images_per_sec=round(n_total / dt, 2), n_images=n_total)


# -------------------------------------------- ImageNet shape rehearsal


def imagenet_rehearsal_bench():
    """VERDICT r1 next#8: drive the SIFT -> PCA -> FV -> BlockWeightedLS
    path at realistic ImageNet per-image shapes (VGA-ish pixels, ~10^4
    descriptors/image, desc_dim 64, k=16 GMM -> 2048-dim FV per branch,
    1000-class weighted solve at the combined 4096-dim FV) on synthetic
    pixels, recording a per-stage profile. Surfaces padding/bucketing
    problems the 32x32 CIFAR tests cannot (reference scale defaults:
    ``ImageNetSiftLcsFV.scala:153-174``).

    No published baseline exists for this path (BASELINE.md); vs_baseline
    is reported against a 10 images/sec/chip strawman.
    """
    from keystone_tpu.nodes.images.extractors import SIFTExtractor
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.learning.block_weighted import (
        BlockWeightedLeastSquaresEstimator,
    )
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel

    h, w = (160, 160) if SMALL else (480, 640)
    # small-mode batch must stay divisible by the 8-device CPU test mesh
    n_imgs = 8 if SMALL else 32
    desc_dim, vocab = 64, 16
    n_classes = 100 if SMALL else 1000
    fv_dim = 2 * desc_dim * vocab          # one branch
    d_solve = 2 * fv_dim                   # SIFT + LCS branches combined
    n_solve = 512 if SMALL else _scaled(4096, mult=512, floor=1_024)

    sift = SIFTExtractor(step=4, bin_size=6, num_scales=5, scale_step=1)
    n_desc = sift.descriptor_count(h, w)

    rng = np.random.RandomState(0)
    pca = jnp.asarray(rng.randn(desc_dim, 128).astype(np.float32) / 11.3)
    gmm = GaussianMixtureModel(
        means=rng.randn(desc_dim, vocab).astype(np.float32),
        variances=(0.5 + rng.rand(desc_dim, vocab)).astype(np.float32),
        weights=(np.ones(vocab) / vocab).astype(np.float32),
    )
    fv = FisherVector(gmm)

    @jax.jit
    def featurize(img_gray):
        desc = sift.apply(img_gray)                    # (128, N)
        desc = jnp.sign(desc) * jnp.sqrt(jnp.abs(desc))  # signed Hellinger
        proj = pca @ desc                              # (64, N)
        out = fv.apply(proj).reshape(-1)               # (2*64*16,)
        out = out / jnp.maximum(jnp.linalg.norm(out), 2.2e-16)
        out = jnp.sign(out) * jnp.sqrt(jnp.abs(out))
        return out / jnp.maximum(jnp.linalg.norm(out), 2.2e-16)

    imgs = rng.rand(n_imgs, h, w).astype(np.float32)
    # device-resident before timing, and ONE dispatch for the whole
    # batch (vmap): per-image dispatches would measure the host
    # round-trip, not the featurizer, and batching same-size images is
    # how the production path feeds the chip anyway. The batch is
    # sharded over the data axis so dividing by device count below is
    # earned on multi-chip hosts too.
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel.mesh import make_mesh

    featurize_batch = jax.jit(jax.vmap(featurize))
    imgs_dev = jax.device_put(
        imgs, NamedSharding(make_mesh(jax.devices()), P("data")))
    _fence(featurize_batch(imgs_dev))                  # compile
    reps = _scaled(4, floor=2)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = featurize_batch(imgs_dev)
    _fence(out)
    feat_dt = (time.perf_counter() - t0) / reps
    per_chip = n_imgs / feat_dt / len(jax.devices())

    # batch-64 featurize via the streaming prefetcher (VERDICT r5 item
    # 3, batching half): doubled vmap batch amortizes per-dispatch
    # overhead (~+10% measured in the r5 build notes), and the host
    # feed rides the double buffer — uint8 grayscale chunks upload on
    # the prefetch thread while the chip featurizes the previous chunk,
    # so the bigger batch is actually fed. Degenerate on CPU smoke runs
    # (SMALL), honest at the rehearsal shape on chip.
    from keystone_tpu.parallel.streaming import StreamingDataset

    chunk64 = 2 * n_imgs
    n64 = 2 * chunk64
    imgs64 = (rng.rand(n64, h, w) * 255).astype(np.uint8)

    @jax.jit
    def feat_u8(X):
        return jax.vmap(featurize)(X.astype(jnp.float32) / 255.0)

    def run64():
        stream = StreamingDataset.from_numpy(
            imgs64, chunk_size=chunk64, prefetch_depth=2,
            tag="imagenet-rehearsal-64")
        outs = [feat_u8(c.data) for c in stream.chunks()]
        _fence(outs)

    run64()  # warm
    # median-of-reps like every other number here: a between-run band
    # would otherwise swing batch64_vs_base on a single sample
    dt64, ev64 = _timed_median(run64)
    per_chip64 = n64 / dt64 / len(jax.devices())

    # 1000-class weighted solve at the combined FV dimension; warmed so
    # the metric is solver time, not XLA compile time. Inputs are staged
    # on device OUTSIDE the timed region: a fresh numpy fit would time
    # the 80 MB upload with the solver — the production path consumes
    # featurizer output already on device.
    from keystone_tpu.parallel.dataset import ArrayDataset

    X = rng.randn(n_solve, d_solve).astype(np.float32)
    y = rng.randint(0, n_classes, n_solve)
    L = -np.ones((n_solve, n_classes), np.float32)
    L[np.arange(n_solve), y] = 1.0
    ds_X, ds_L = ArrayDataset.from_numpy(X), ArrayDataset.from_numpy(L)
    _fence((ds_X.data, ds_L.data))  # staging fence, untimed
    est = BlockWeightedLeastSquaresEstimator(4096, 1, 6e-5, 0.25)
    _fence(est.fit(ds_X, ds_L).weights)  # warm

    def solve():
        # completion fence only — the weights stay device-resident
        _fence(est.fit(ds_X, ds_L).weights)

    solve_dt, _ = _timed_median(solve)

    _emit("imagenet_rehearsal_images_per_sec_per_chip", round(per_chip, 2),
          "images/sec/chip", round(per_chip / 10.0, 4),
          image_shape=[h, w], descriptors_per_image=int(n_desc),
          sift_pca_fv_ms_per_image=round(1e3 * feat_dt / n_imgs, 1),
          weighted_solve_s=round(solve_dt, 2),
          solve_shape=[n_solve, d_solve, n_classes],
          batch64_images_per_sec_per_chip=round(per_chip64, 2),
          batch64_chunk=chunk64,
          batch64_vs_base=round(per_chip64 / max(per_chip, 1e-9), 3),
          batch64_timing_spread=ev64["timing_spread"],
          batch64_ingest="prefetch-depth-2-uint8")


# ----------------------------------------------- Pallas kernel program


def pallas_kernels_bench():
    """PR 13 kernel program: one bench line per kernel with an MFU
    companion, so the before/after of each kernel is denominated in
    hardware terms (PERFORMANCE.md rule 11), benchdiff-banded. Each
    section times the PRODUCTION dispatch path (``kernel_path`` names
    which implementation the dispatcher actually picked — the compiled
    Pallas kernel on TPU, the einsum fallback on CPU-sim, where these
    lines are plumbing evidence, not kernel wins) under the warmup
    fence, so a steady-state recompile in any kernel is a flagged bug,
    not timing noise.

    * ``sift_banded_images_per_sec_per_chip`` — banded-GEMM dense SIFT
      at the rehearsal image shape (vs the 502 img/s r6 VERDICT #3
      number; target >= 800 on chip).
    * ``fv_fused_images_per_sec_per_chip`` — fused GMM-posterior + FV
      at the rehearsal descriptor shape (vs a 100 img/s strawman).
    * ``predict_quantized_{f32,bf16,int8}_rows_per_sec_per_chip`` — the
      serving plane's quantized apply; vs_baseline of the narrow lines
      is the speedup over the f32 line, and each carries its parity
      evidence (argmax agreement + max relative error vs f32).
    """
    from keystone_tpu.nodes.images.fisher_vector import _fisher_vector
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
    from keystone_tpu.nodes.learning.linear import (
        LinearMapper,
        _affine_apply_batch,
        _quantized_affine_batch,
    )
    from keystone_tpu.observability import compile_observatory
    from keystone_tpu.observability.compilelog import watch_jit
    from keystone_tpu.observability.utilization import UtilizationWindow
    from keystone_tpu.ops.pallas_kernels import (
        fv_fits_vmem,
        quant_fits_vmem,
        use_pallas,
    )
    from keystone_tpu.ops.sift import _resolve_kernel_mode, dense_sift

    n_dev = len(jax.devices())
    obs = compile_observatory()
    rng = np.random.RandomState(0)

    # -- banded SIFT -------------------------------------------------------
    h, w = (96, 128) if SMALL else (480, 640)
    n_imgs = 2 if SMALL else _scaled(16, mult=2, floor=4)
    sift_path = _resolve_kernel_mode(None, h, w)
    imgs = jax.device_put(rng.rand(n_imgs, h, w).astype(np.float32))
    _fence(imgs)
    sift_fn = watch_jit(jax.jit(jax.vmap(
        lambda g: dense_sift(g, 4, 6, 5, 1))), "bench_sift_banded")
    compile_wall0 = obs.wall_s_total()
    _fence(sift_fn(imgs))  # warm
    with UtilizationWindow() as uw:
        dt, ev = _timed_median(lambda: _fence(sift_fn(imgs)),
                               warmup_fence=True,
                               compile_wall0=compile_wall0)
    util = uw.report(n_devices=n_dev)
    per_chip = n_imgs / dt / n_dev
    _emit("sift_banded_images_per_sec_per_chip", round(per_chip, 2),
          "images/sec/chip", round(per_chip / 502.0, 4),
          image_shape=[h, w], kernel_path=sift_path,
          sift_banded_mfu=round(util["mfu"], 5),
          sift_banded_membw_util=round(util["membw_util"], 5),
          roofline_bound=util["bound"], **ev)

    # -- fused FV ----------------------------------------------------------
    desc_dim, vocab = 64, 16
    n_desc = 1024 if SMALL else 10_240
    fv_batch = 4 if SMALL else _scaled(16, mult=2, floor=4)
    # the REAL dispatch decision (backend AND fits-vmem), so the label
    # can never attribute a fallback measurement to the kernel
    fv_path = ("pallas" if use_pallas() and fv_fits_vmem(desc_dim, vocab)
               else "einsum")
    gmm = GaussianMixtureModel(
        means=rng.randn(desc_dim, vocab).astype(np.float32),
        variances=(0.5 + rng.rand(desc_dim, vocab)).astype(np.float32),
        weights=(np.ones(vocab) / vocab).astype(np.float32),
    )
    params = (jnp.asarray(gmm.means), jnp.asarray(gmm.variances),
              jnp.asarray(gmm.weights))
    descs = jax.device_put(
        rng.randn(fv_batch, desc_dim, n_desc).astype(np.float32))
    _fence(descs)
    fv_fn = watch_jit(jax.jit(jax.vmap(
        lambda x: _fisher_vector(x, *params, 1e-4))), "bench_fv_fused")
    compile_wall0 = obs.wall_s_total()
    _fence(fv_fn(descs))  # warm
    with UtilizationWindow() as uw:
        dt, ev = _timed_median(lambda: _fence(fv_fn(descs)),
                               warmup_fence=True,
                               compile_wall0=compile_wall0)
    util = uw.report(n_devices=n_dev)
    per_chip = fv_batch / dt / n_dev
    _emit("fv_fused_images_per_sec_per_chip", round(per_chip, 2),
          "images/sec/chip", round(per_chip / 100.0, 4),
          descriptors_per_image=n_desc, vocab=vocab,
          kernel_path=fv_path,
          fv_fused_mfu=round(util["mfu"], 5),
          fv_fused_membw_util=round(util["membw_util"], 5),
          roofline_bound=util["bound"], **ev)

    # -- quantized predict -------------------------------------------------
    n_rows = 2_048 if SMALL else _scaled(16_384, mult=2_048, floor=4_096)
    d, k = (256, 32) if SMALL else (1024, 100)
    X = rng.randn(n_rows, d).astype(np.float32)
    teacher = rng.randn(d, k).astype(np.float32) / np.sqrt(d)
    b = (rng.randn(k) * 0.01).astype(np.float32)
    X_dev = jax.device_put(X)
    _fence(X_dev)
    rates: dict = {}
    f32_out = None
    for dtype in (None, "bf16", "int8"):
        mapper = LinearMapper(teacher, intercept=b, weight_dtype=dtype)
        params_q = mapper.apply_params()
        # time the PRODUCTION batch programs — the exact jits
        # apply_dataset's map_batch dispatches (the quantized one
        # routes to the Pallas kernel on TPU when W fits VMEM)
        batch_fn = (_affine_apply_batch if dtype is None
                    else _quantized_affine_batch)
        quant_path = (
            "f32" if dtype is None
            else "pallas" if use_pallas() and quant_fits_vmem(
                d, k, params_q[0].dtype.itemsize)
            else "einsum")
        apply_fn = watch_jit(
            jax.jit(lambda xs, p=params_q, f=batch_fn: f(xs, *p)),
            f"bench_predict_{dtype or 'f32'}")
        compile_wall0 = obs.wall_s_total()
        out = np.asarray(apply_fn(X_dev))  # warm + parity evidence
        with UtilizationWindow() as uw:
            dt, ev = _timed_median(lambda: _fence(apply_fn(X_dev)),
                                   warmup_fence=True,
                                   compile_wall0=compile_wall0)
        util = uw.report(n_devices=n_dev)
        tag = dtype or "f32"
        if dtype is None:
            f32_out = out
            parity = {}
        else:
            parity = {
                "argmax_agreement_vs_f32": round(float(
                    (out.argmax(1) == f32_out.argmax(1)).mean()), 4),
                "max_rel_err_vs_f32": round(float(
                    np.abs(out - f32_out).max()
                    / max(np.abs(f32_out).max(), 1e-12)), 5),
            }
        rates[tag] = n_rows / dt / n_dev
        _emit(f"predict_quantized_{tag}_rows_per_sec_per_chip",
              round(rates[tag], 1), "rows/sec/chip",
              round(rates[tag] / max(rates["f32"], 1e-9), 4),
              solve_shape=[n_rows, d, k], kernel_path=quant_path,
              **{f"predict_quantized_{tag}_mfu": round(util["mfu"], 5),
                 f"predict_quantized_{tag}_membw_util":
                     round(util["membw_util"], 5)},
              roofline_bound=util["bound"], **parity, **ev)


# ----------------------------------------------- loader-in-the-loop bench


def serving_bench():
    """The online serving plane (``keystone_tpu/serving``): sustained
    micro-batched QPS and tail latency through the REAL request path —
    slot-gated bounded queue, pad-to-bucket coalescing, two warm
    resident models (one f32, one bf16-quantized per the PR 13 serving
    default) under an asserted HBM admission budget. The window is
    driven by the deterministic trace-replay load generator
    (``serving/loadgen.py``, PR 19) — seeded bursty arrivals, Zipf
    model popularity, mixed request sizes — instead of uniform client
    threads, so the measured tail comes from traffic-shaped load and
    the same trace replays identically across rounds; latency is
    measured per request end-to-end (enqueue -> result, the
    ``serving.request_ms`` semantics) and the compile-observatory
    fence stays armed for the whole window — a single steady-state
    recompile fails the section, because the zero-recompile invariant
    is asserted, not hoped (PERFORMANCE.md rule 14)."""
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.observability import compile_observatory
    from keystone_tpu.observability.utilization import UtilizationWindow
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.serving import ServingPlane, model_charge

    n_dev = len(jax.devices())
    d1, d2, k = (64, 96, 10) if SMALL else (256, 384, 10)
    n_fit = 512 if SMALL else _scaled(4_096, mult=512, floor=1_024)
    max_batch = 32 if SMALL else 64
    window_s = 2.0 if SMALL else float(_scaled(8, mult=1, floor=4))
    clients = 4

    def fit(d, seed, **kw):
        r = np.random.RandomState(seed)
        X = r.rand(n_fit, d).astype(np.float32)
        Y = r.rand(n_fit, k).astype(np.float32)
        return LinearMapEstimator(lam=1e-3, **kw).with_data(
            ArrayDataset.from_numpy(X),
            ArrayDataset.from_numpy(Y)).fit(), X

    f32_model, X1 = fit(d1, seed=1)
    bf16_model, X2 = fit(d2, seed=2)

    sample1 = jax.ShapeDtypeStruct((d1,), np.float32)
    sample2 = jax.ShapeDtypeStruct((d2,), np.float32)
    budget = (model_charge(f32_model, sample1, max_batch).total_nbytes()
              + model_charge(bf16_model, sample2,
                             max_batch).total_nbytes() + (1 << 20))
    plane = ServingPlane(hbm_budget=budget, max_batch=max_batch,
                         queue_depth=1024)
    plane.start()
    # snapshot AFTER the fits: compile_s on the serve line must
    # attribute the admission warmups, not the solver-fit compiles
    compile_wall0 = compile_observatory().wall_s_total()
    try:
        plane.admit("f32", f32_model, sample1, weight_dtype=None)
        plane.admit("bf16", bf16_model, sample2, weight_dtype="bf16")
        compile_s = round(
            compile_observatory().wall_s_total() - compile_wall0, 3)

        from keystone_tpu.observability import MetricsRegistry

        reg = MetricsRegistry.get_or_create()
        fill_h = reg.histogram("serving.batch_fill")
        fill_count0, fill_total0 = fill_h.count, fill_h.total
        batches0 = reg.counter("serving.batches_total").value
        rejected0 = reg.counter("serving.rejected_total").value
        # request-path tail attribution (PR 16): snapshot the phase
        # histograms the traced window will fill, so the shares below
        # cover exactly this load window
        qw_h = reg.histogram("serving.phase_ms.queue_wait")
        disp_h = reg.histogram("serving.phase_ms.dispatch")
        req_h = reg.histogram("serving.request_ms")
        qw_total0, disp_total0 = qw_h.total, disp_h.total
        req_total0 = req_h.total
        good0, bad0 = plane.slo.totals()
        rows0 = reg.counter("serving.rows_total").value
        u0 = plane.unexpected_recompiles()

        # the deterministic load window (PR 19): a seeded trace —
        # bursty arrivals, Zipf popularity across the two models,
        # mixed request sizes — replayed by closed-loop senders. The
        # schedule is oversubscribed on purpose: the senders fall
        # behind the arrival clock and drive the plane flat out, so
        # the qps line still measures capacity while the model/size
        # SEQUENCE stays identical across rounds.
        from keystone_tpu.serving.loadgen import LoadSpec, generate_trace
        from keystone_tpu.serving.loadgen import replay as replay_trace

        spec = LoadSpec(
            seed=5, duration_s=window_s, rate_rps=1500.0,
            arrival="bursty", models=("f32", "bf16"), zipf_s=1.2,
            sizes=(1, 4, 8, max_batch // 2),
            burst_mult=2.0, burst_on_s=0.5, burst_off_s=0.25)
        trace = generate_trace(spec)
        data = {"f32": X1, "bf16": X2}

        def input_for(model, n):
            return data[model][:n]

        with UtilizationWindow() as uw:
            report = replay_trace(
                trace, plane, input_for, senders=clients,
                submit_timeout_s=30.0, result_timeout_s=60.0)
        wall = report.wall_s

        unexpected = plane.unexpected_recompiles() - u0
        if unexpected:
            raise RuntimeError(
                f"{unexpected:.0f} steady-state serving recompile(s) — "
                "the zero-recompile invariant is asserted, not hoped")
        broken = (report.outcomes["error"]
                  + report.outcomes["unclassified"]
                  + report.outcomes["poisoned"])
        if broken:
            raise RuntimeError(
                f"{broken} request(s) FAILED in the fault-free bench "
                f"window: {report.errors[:4]}")
        lat_ms = np.sort(np.asarray(report.latencies_ms, np.float64))
        if lat_ms.size == 0:
            raise RuntimeError("serving window completed zero requests")
        qps_rows = (reg.counter("serving.rows_total").value
                    - rows0) / wall
        per_chip = qps_rows / n_dev
        requests_per_sec = lat_ms.size / wall
        batches = reg.counter("serving.batches_total").value - batches0
        fill_n = fill_h.count - fill_count0
        mean_fill = ((fill_h.total - fill_total0) / fill_n
                     if fill_n else None)
        util = uw.report(n_devices=n_dev)
        common = dict(
            models=2, clients=clients, window_s=round(wall, 2),
            max_batch=max_batch,
            loadgen=dict(seed=spec.seed, arrival=spec.arrival,
                         rate_rps=spec.rate_rps, zipf_s=spec.zipf_s),
            requests_per_sec=round(requests_per_sec, 1),
            batches_per_sec=round(batches / wall, 1),
            batch_fill=(None if mean_fill is None
                        else round(mean_fill, 3)),
            rejected=int(
                reg.counter("serving.rejected_total").value - rejected0),
            hbm_budget_mib=round(budget / (1 << 20), 3),
            unexpected_recompiles=0,
        )
        _emit("serve_qps_per_chip", round(per_chip, 1),
              "rows/sec/chip", round(per_chip / 10_000.0, 4),
              serve_mfu=round(util["mfu"], 6),
              serve_membw_util=round(util["membw_util"], 6),
              compile_s=compile_s, **common)
        _emit("serve_p50_ms", round(float(np.percentile(lat_ms, 50)), 3),
              "ms", round(float(np.percentile(lat_ms, 50)) / 10.0, 4),
              **common)
        _emit("serve_p99_ms", round(float(np.percentile(lat_ms, 99)), 3),
              "ms", round(float(np.percentile(lat_ms, 99)) / 10.0, 4),
              **common)

        # tail attribution (PR 16): where the request wall actually
        # went over the window — phase-ms totals over request-ms
        # totals, straight from the telescoping per-request phase
        # decomposition (queue_wait growing while dispatch holds =
        # backpressure, not the device). Phase observes are deferred
        # onto the recorder's flush path, so flush before reading.
        from keystone_tpu.observability.timeline import flight_recorder

        flight_recorder().flush()
        req_total = req_h.total - req_total0
        if req_total > 0:
            qw_share = (qw_h.total - qw_total0) / req_total
            disp_share = (disp_h.total - disp_total0) / req_total
            _emit("serve_queue_wait_share", round(qw_share, 4),
                  "share", round(qw_share / 0.5, 3), **common)
            _emit("serve_dispatch_share", round(disp_share, 4),
                  "share", round(disp_share / 0.5, 3), **common)
        # the availability the SLO tracker observed over this window
        # (delta of lifetime good/bad totals — default policy: every
        # request under 1s counts good)
        good, bad = plane.slo.totals()
        seen = (good - good0) + (bad - bad0)
        if seen > 0:
            avail = (good - good0) / seen
            _emit("serve_availability", round(avail, 6), "fraction",
                  round(avail / 0.999, 4), **common)

        # always-on overhead of the request-path plane itself
        # (PERFORMANCE.md rule 15): interleaved A/B pairs through the
        # warm plane — the OFF request runs the same path under
        # tracing_suppressed() (runtime gate, identical programs), so
        # the pair isolates the per-request latency-path cost: the
        # mint, the stamps, the reservoir offer, the defer (span
        # construction and phase observes materialize at flush points,
        # off the latency path). The interleave is REQUEST-level — each
        # pair is one traced and one suppressed request back to back,
        # order alternating — so machine drift and scheduler bursts hit
        # both streams equally (block-pair legs at this ~ms request
        # scale carry an A/A noise floor several times the 2% signal;
        # adjacent-request pairing cancels it). Deferred thunks are
        # flushed after every traced request so displaced
        # materialization is paid between timings, not inside one. The
        # estimator is the MEDIAN of the per-pair latency differences
        # over the suppressed stream's p50 — each pair's difference
        # cancels whatever the machine was doing around that pair, and
        # the median ignores the straggler/drift-scoring spikes that
        # land on single pairs, so an A/A run of this probe reads ~0
        # where comparing stream p50s still wanders by points. Banded
        # absolutely (the shared "overhead_share" marker); the bar is
        # <2%.
        from keystone_tpu.observability.reqtrace import tracing_suppressed

        probe_pairs = 300 if SMALL else 600
        probe_x = X1[:8]

        def _one(suppress):
            if suppress:
                with tracing_suppressed():
                    t0 = time.perf_counter()
                    plane.predict("f32", probe_x, timeout_s=60.0)
                    return time.perf_counter() - t0
            t0 = time.perf_counter()
            plane.predict("f32", probe_x, timeout_s=60.0)
            return time.perf_counter() - t0

        # late in a full bench run the heap is large and collector
        # pauses dwarf the ~tens-of-us signal; collect once up front
        # and hold the collector off for the probe so both streams time
        # the request path, not the allocator
        import gc

        on_lat: list = []
        off_lat: list = []
        flight_recorder().flush()
        gc.collect()
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for _ in range(16):  # warm-up pairs, discarded
                _one(False)
                _one(True)
            flight_recorder().flush()
            for i in range(probe_pairs):
                if i % 2 == 0:
                    on_lat.append(_one(False))
                    flight_recorder().flush()
                    off_lat.append(_one(True))
                else:
                    off_lat.append(_one(True))
                    on_lat.append(_one(False))
                    flight_recorder().flush()
        finally:
            if gc_was_enabled:
                gc.enable()
        if off_lat:
            diffs = sorted(o - f for o, f in zip(on_lat, off_lat))
            p50_off = sorted(off_lat)[len(off_lat) // 2]
            if p50_off > 0:
                trace_share = diffs[len(diffs) // 2] / p50_off
                _emit("serving_trace_overhead_share",
                      round(trace_share, 4), "share",
                      round(trace_share / 0.02, 3), **common)
    finally:
        plane.close()


#: chaos-soak bench lines, one gated pair per scenario. The names are
#: spelled out literally (not derived from the scenario registry) so
#: the BENCH_METRIC_NAMES catalogue test can hold them to the same
#: rename discipline as every other bench line — and so a scenario
#: silently dropped from the catalogue fails THIS section loudly
#: instead of its lines just vanishing from the artifact.
_SOAK_LINES = {
    "burst": ("soak_burst_p99_ms", "soak_burst_availability"),
    "diurnal": ("soak_diurnal_p99_ms", "soak_diurnal_availability"),
    "zipf_churn": ("soak_zipf_churn_p99_ms",
                   "soak_zipf_churn_availability"),
    "straggler_dispatch": ("soak_straggler_dispatch_p99_ms",
                           "soak_straggler_dispatch_availability"),
    "poisoned_batch": ("soak_poisoned_batch_p99_ms",
                       "soak_poisoned_batch_availability"),
    "overload_shed": ("soak_overload_shed_p99_ms",
                      "soak_overload_shed_availability"),
}


def serving_soak_bench():
    """The chaos soak (PR 19): replay each ``serving/scenarios``
    catalogue entry — deterministic loadgen trace under its seeded
    fault plan — against a fresh plane, and emit the gated pair per
    scenario: p99 of served requests (lower-better ``_ms``) and
    accepted-request availability (higher-better, the PR 16
    ``availability`` marker). vs_baseline is the scenario's own floor,
    so >1.0 on a ``_ms`` line or <1.0 on an availability line reads as
    "this round violated the chaos-gate floor". Floors are ENFORCED by
    ``tools/chaos_gate.py`` in CI; here a violation is emitted (with
    the violations named on the line), never raised — a bench round
    must record the regression, not hide the whole section."""
    from keystone_tpu.serving.scenarios import (
        SCENARIOS,
        load_catalogue,
        run_scenario,
    )

    load_catalogue()
    missing = sorted(set(_SOAK_LINES) - set(SCENARIOS))
    if missing:
        raise RuntimeError(
            f"scenario(s) {missing} dropped from the catalogue but "
            "still carry catalogued soak bench lines")
    # SMALL smoke runs keep the pair of scenarios that exercise both
    # ends of the contract (fair-weather tail + classified faults);
    # full runs soak the whole catalogue
    names = (("burst", "poisoned_batch") if SMALL
             else tuple(sorted(_SOAK_LINES)))
    for name in names:
        p99_line, avail_line = _SOAK_LINES[name]
        res = run_scenario(name, seed=0)
        extra = dict(
            scenario=name, seed=0, injections=res.injections,
            clean=res.clean,
            p99_floor_ms=res.floors.p99_ms,
            availability_floor=res.floors.availability,
            outcomes={k: int(v) for k, v in res.report.outcomes.items()})
        if not res.clean:
            extra["violations"] = res.violations
            extra["postmortem"] = res.postmortem_path
        _emit(p99_line, round(res.p99_ms, 3), "ms",
              round(res.p99_ms / res.floors.p99_ms, 4), **extra)
        _emit(avail_line, round(res.availability, 6), "fraction",
              round(res.availability / res.floors.availability, 4),
              **extra)


def fleet_bench():
    """Multi-replica serving scale-out (PR 20): the same deterministic
    loadgen workload measured twice in one section — first against a
    single saturated plane (the ``single_qps`` baseline, same
    semantics as ``serve_qps_per_chip``), then against a 3-replica
    fleet behind the ``FleetRouter`` with placement solved by the
    fleet controller under finite per-replica budgets. The fleet
    transport is IN-PROCESS (``LocalReplicaClient`` — direct plane
    calls): the section measures router + scale-out, not JSON framing;
    the real-HTTP wire path is drilled by the fleet chaos scenarios
    and ``tools/fleet_gate.py``, where correctness (not rows/sec) is
    the product.

    Placement is load-bearing: six equal-charge models FFD-spread two
    per replica, and the two Zipf-hottest are REPLICATED into the
    leftover budget (an earned solver decision — ``qps`` demand priced
    against warmup cost), so the router's depth-ordered spill can
    level the Zipf skew across replicas instead of pinning the hot
    primary. ``router_spill_share`` prices exactly that leveling
    (lower is calmer, but zero under skew means the fleet is NOT
    balancing — PERFORMANCE.md rule 19: watch the spill share, not
    just the p99).

    The comparison is throughput-at-operating-point, the serving
    scale-out claim: ONE seeded trace, replayed twice. The single
    window replays it TIME-STRETCHED by the replica count (the same
    requests, byte-identical, at the per-replica rate — it keeps up,
    so ``single_qps`` is the rows/sec one replica serves at its
    operating point); the fleet window replays it at full speed
    against the fleet. A fleet that keeps up delivers ~Nx; the 2.4x
    acceptance bar leaves room for routing overhead and placement
    imbalance. On a single-core
    CPU sim both windows share one core, so the fleet number prices
    the router/placement/spill machinery absorbing 3x the offered
    load (batch coalescing has to survive the 3-way split); on
    multi-core or TPU hosts the same section measures real parallel
    capacity.

    * ``fleet_qps`` — fleet-window rows/sec; vs_baseline is the ratio
      against 2.4x the same-run single-replica operating point (the
      PR 20 acceptance bar), so >= 1.0 reads "scale-out delivered".
    * ``fleet_p99_ms`` — closed-loop end-to-end p99 over the fleet
      window (banded like ``serve_p99_ms``).
    * ``router_spill_share`` — spilled / routed requests over the
      window (the shared lower-better ``_share`` marker).
    """
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.observability import MetricsRegistry
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.serving import ServingPlane, model_charge
    from keystone_tpu.serving.fleet import FleetController
    from keystone_tpu.serving.loadgen import LoadSpec, generate_trace
    from keystone_tpu.serving.loadgen import replay as replay_trace
    from keystone_tpu.serving.router import FleetRouter, LocalReplicaClient

    n_dev = len(jax.devices())
    n_replicas = 3
    d, k = (64, 10) if SMALL else (256, 10)
    n_fit = 512 if SMALL else _scaled(4_096, mult=512, floor=1_024)
    max_batch = 32 if SMALL else 64
    window_s = 2.0 if SMALL else float(_scaled(8, mult=1, floor=4))
    base_clients = 4
    fleet_clients = base_clients * n_replicas

    r = np.random.RandomState(7)
    X = r.rand(n_fit, d).astype(np.float32)
    Y = r.rand(n_fit, k).astype(np.float32)
    fitted = LinearMapEstimator(lam=1e-3).with_data(
        ArrayDataset.from_numpy(X),
        ArrayDataset.from_numpy(Y)).fit()
    sample = jax.ShapeDtypeStruct((d,), np.float32)
    charge = model_charge(fitted, sample, max_batch).total_nbytes()
    # six names over one fitted model: equal charges make the FFD
    # spread deterministic (two per replica) and keep the section's
    # fit cost at one solve
    names = tuple(f"m{i}" for i in range(2 * n_replicas))

    reg = MetricsRegistry.get_or_create()
    # one trace, two replays: the fleet at full speed, the single
    # plane time-stretched x n_replicas (per-replica rate, identical
    # request sequence)
    spec = LoadSpec(
        seed=9, duration_s=window_s, rate_rps=1_200.0,
        arrival="bursty", models=names, zipf_s=1.2,
        sizes=(4, 8, max_batch // 2),
        burst_mult=2.0, burst_on_s=0.5, burst_off_s=0.25)
    trace = generate_trace(spec)

    def input_for(model, n):
        return X[:n]

    def warm(plane, hosted):
        # pay the per-bucket serve compiles BEFORE the measured
        # window: the single window runs at light load and a cold
        # bucket compile would stretch its wall into the qps
        for name in hosted:
            for n in (1, max_batch):
                plane.predict(name, X[:n], timeout_s=60.0)

    def run_window(target, senders, time_scale):
        """One closed-loop load window; returns (rows/sec, sorted
        latencies ms, report)."""
        rows0 = reg.counter("serving.rows_total").value
        report = replay_trace(
            trace, target, input_for, senders=senders,
            time_scale=time_scale,
            submit_timeout_s=30.0, result_timeout_s=60.0)
        broken = (report.outcomes["error"]
                  + report.outcomes["unclassified"]
                  + report.outcomes["poisoned"])
        if broken:
            raise RuntimeError(
                f"{broken} request(s) FAILED in the fault-free fleet "
                f"window: {report.errors[:4]}")
        lat_ms = np.sort(np.asarray(report.latencies_ms, np.float64))
        if lat_ms.size == 0:
            raise RuntimeError("fleet window completed zero requests")
        qps = (reg.counter("serving.rows_total").value
               - rows0) / report.wall_s
        return qps, lat_ms, report

    def make_plane(budget):
        plane = ServingPlane(hbm_budget=budget, max_batch=max_batch,
                             queue_depth=1024)
        plane.start()
        return plane

    # -- single-replica baseline: one saturated plane, all six models
    base_plane = make_plane(len(names) * charge + (1 << 20))
    planes = []
    try:
        for name in names:
            base_plane.admit(name, fitted, sample, weight_dtype=None)
        warm(base_plane, names)
        u0 = base_plane.unexpected_recompiles()
        single_qps, _, _ = run_window(
            base_plane, base_clients, time_scale=float(n_replicas))
        if base_plane.unexpected_recompiles() - u0:
            raise RuntimeError(
                "steady-state recompile in the fleet baseline window")

        # -- the fleet: 3 planes, placement solved under budgets that
        # fit two homes plus ONE earned replica copy each
        planes = [make_plane(int(3.3 * charge) + (1 << 20))
                  for _ in range(n_replicas)]
        clients = [LocalReplicaClient(f"r{i}", plane)
                   for i, plane in enumerate(planes)]
        # closed-loop senders keep per-plane depth <= sender count, so
        # the proactive-spill threshold sits BELOW it: a primary with
        # a couple queued loses the request to an idler sibling
        router = FleetRouter(clients, spill_queue_depth=2)
        controller = FleetController(router, bucket_rows=max_batch)
        for i, name in enumerate(names):
            # the two Zipf-hottest names carry demand, so the solver
            # replicates exactly them into the leftover budget
            qps = 500.0 if i < 2 else 0.0
            controller.register(name, fitted, sample, qps=qps,
                                warmup_s=1.0 if qps else 0.0)
        for client in clients:
            controller.set_budget(client.replica_id, 3.3 * charge)
        controller.rebalance()
        placed = controller.placement
        copies = {name: len(placed.replicas_for(name))
                  for name in names}
        for client in clients:
            warm(client.plane, client.models())

        u1 = sum(p.unexpected_recompiles() for p in planes)
        routed0 = reg.counter("router.requests_total").value
        spill0 = reg.counter("router.spill_total").value
        fleet_qps, lat_ms, report = run_window(
            router, fleet_clients, time_scale=1.0)
        if sum(p.unexpected_recompiles() for p in planes) - u1:
            raise RuntimeError(
                "steady-state recompile in the fleet scale-out window")
        routed = reg.counter("router.requests_total").value - routed0
        spilled = reg.counter("router.spill_total").value - spill0
        spill_share = spilled / routed if routed else 0.0
        scaling = fleet_qps / single_qps if single_qps else 0.0

        common = dict(
            replicas=n_replicas, models=len(names),
            clients=fleet_clients, window_s=round(report.wall_s, 2),
            max_batch=max_batch,
            loadgen=dict(seed=spec.seed, arrival=spec.arrival,
                         rate_rps=spec.rate_rps, zipf_s=spec.zipf_s),
            single_qps=round(single_qps / n_dev, 1),
            scaling=round(scaling, 3),
            copies=copies,
            spilled=int(spilled), routed=int(routed),
            unexpected_recompiles=0,
        )
        _emit("fleet_qps", round(fleet_qps / n_dev, 1),
              "rows/sec/chip",
              round(fleet_qps / (2.4 * single_qps), 4)
              if single_qps else 0.0, **common)
        _emit("fleet_p99_ms",
              round(float(np.percentile(lat_ms, 99)), 3), "ms",
              round(float(np.percentile(lat_ms, 99)) / 10.0, 4),
              **common)
        _emit("router_spill_share", round(spill_share, 4), "share",
              round(spill_share / 0.5, 4), **common)
    finally:
        base_plane.close()
        for plane in planes:
            plane.close()


def elastic_coordination_bench():
    """Multi-host coordination cost on the CPU dryrun harness (PR 18):
    shells out to ``tools/elastic_bench.py`` — real ``jax.distributed``
    + gloo worlds at sizes 1 and 2, warm steady-state fits — and
    re-emits its banded lines:

    * ``elastic_scaling_efficiency`` — (2p img/s) / (2 x 1p img/s);
      vs_baseline against the 0.8 acceptance bar. On the CPU sim both
      "hosts" share this machine, so the number prices coordination
      rounds, not hardware scaling; warm per-chunk wall is dispatch-
      latency-bound under gloo, so values above 1.0 mean the hosts
      overlap that latency (coordination adds ~nothing).
    * ``coord_overhead_share`` — blocked-await wall / round wall on the
      2-process world (PERFORMANCE.md rule 17: measure the await, not
      the round). Banded absolutely via the shared "overhead_share"
      marker; the overlapped round loop's whole point is holding this
      near zero.
    * ``coord_overlap_occupancy`` — its complement (1.0 = coordination
      fully hidden behind accumulate compute).

    The subprocess pins ``JAX_PLATFORMS=cpu`` for the worlds: this is
    a CPU section that measures the coordinator, not the accelerator.
    ``main`` skips it when this process holds a TPU (one process for
    each chip, and no CPU timing in a device run)."""
    import subprocess
    import sys as _sys

    rows = 4_096 if SMALL else _scaled(16_384, mult=4_096)
    proc = subprocess.run(
        [_sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "elastic_bench.py"),
         "--rows", str(rows), "--chunk-size", "256"],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(
            f"elastic bench subprocess failed (rc {proc.returncode}): "
            f"{proc.stdout[-800:]}\n{proc.stderr[-800:]}")
    lines = {}
    for raw in proc.stdout.splitlines():
        if raw.startswith("{"):
            blob = json.loads(raw)
            lines[blob.get("metric")] = blob
    eff = lines.get("elastic_scaling_efficiency")
    if eff is None:
        raise RuntimeError("elastic bench emitted no "
                           f"efficiency line: {proc.stdout[-800:]}")
    _emit("elastic_scaling_efficiency", round(float(eff["value"]), 4),
          "fraction", round(float(eff["value"]) / 0.8, 3),
          processes=eff.get("processes"), rows=rows,
          note=eff.get("note"))
    share = lines.get("coord_overhead_share")
    if share is not None:
        _emit("coord_overhead_share", round(float(share["value"]), 6),
              "share", round(float(share["value"]) / 0.02, 3),
              processes=share.get("processes"))
    occ = lines.get("coord_overlap_occupancy")
    if occ is not None:
        _emit("coord_overlap_occupancy", round(float(occ["value"]), 6),
              "fraction", round(float(occ["value"]) / 0.98, 3),
              processes=occ.get("processes"))


def loader_bench():
    """VERDICT r2 weak#5: time the tar -> threaded decode -> device ->
    SIFT path END TO END on a generated JPEG tar, so the ImageNet-style
    ingest is measured with the loader in the loop rather than
    shapes-only. The pipeline is the production shape: tar streams
    sequentially, PIL decode runs on the loader thread pool
    (``iter_decoded_chunks``), each chunk is device_put as uint8 (4x
    smaller than f32 on the wire) and featurized under one async
    dispatch — JAX overlaps the next chunk's decode with the device
    work. No published baseline; vs_baseline against a 100 images/sec
    strawman (reference ImageNetLoader fed cluster executors from HDFS
    tars, ``ImageLoaderUtils.scala:23-94``).

    The breakdown keys attribute the wall to decode, upload and SIFT.
    """
    import tarfile as tarmod
    import tempfile

    from keystone_tpu.loaders.image_loader_utils import iter_decoded_chunks
    from keystone_tpu.nodes.images.extractors import SIFTExtractor

    n_imgs = 64 if SMALL else _scaled(512, mult=64, floor=128)
    side = 128
    chunk = 16 if SMALL else 64
    tar_path = os.path.join(
        tempfile.gettempdir(),
        f"keystone_bench_{os.getuid()}_{n_imgs}_{side}.tar")

    def _tar_valid(path):
        try:
            with tarmod.open(path, "r") as tf:
                return sum(1 for e in tf if e.isfile()) == n_imgs
        except Exception:
            return False

    if not (os.path.exists(tar_path) and _tar_valid(tar_path)):
        from PIL import Image as PILImage
        import io

        rng = np.random.RandomState(0)
        base = (rng.rand(side, side, 3) * 255).astype(np.uint8)
        # atomic publish: a run killed mid-write must not leave a
        # truncated tar that poisons every later run on this host
        tmp_path = tar_path + f".tmp{os.getpid()}"
        with tarmod.open(tmp_path, "w") as tf:
            for i in range(n_imgs):
                arr = np.roll(base, 3 * i, axis=0)  # distinct per entry
                buf = io.BytesIO()
                PILImage.fromarray(arr).save(buf, format="JPEG", quality=90)
                data = buf.getvalue()
                info = tarmod.TarInfo(f"class{i % 10}/img{i:05d}.jpg")
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
        os.replace(tmp_path, tar_path)

    sift = SIFTExtractor(step=8, bin_size=4, num_scales=2, scale_step=1)

    @jax.jit
    def featurize_chunk(imgs_u8):
        # NTSC grayscale on device (u8 wire format, f32 compute)
        f = imgs_u8.astype(jnp.float32) / 255.0
        gray = (0.299 * f[..., 0] + 0.587 * f[..., 1] + 0.114 * f[..., 2])
        descs = jax.vmap(sift.apply)(gray)
        return jnp.sum(descs, axis=(1, 2))  # keep the d2h pull tiny

    def run_pipeline():
        outs = []
        for batch in iter_decoded_chunks([tar_path], chunk):
            arr = np.stack([img for _, img in batch]).astype(np.uint8)
            if arr.shape[0] != chunk:  # static jit shape: pad the tail
                pad = np.zeros((chunk - arr.shape[0],) + arr.shape[1:],
                               np.uint8)
                arr = np.concatenate([arr, pad])
            outs.append(featurize_chunk(jax.device_put(arr)))
        _fence(outs)
        return len(outs)

    run_pipeline()  # warm: XLA compile + page cache

    # decode-only pass: attribution for the breakdown keys
    t0 = time.perf_counter()
    n_decoded = sum(len(b) for b in iter_decoded_chunks([tar_path], chunk))
    decode_dt = time.perf_counter() - t0

    # r3 -> r4 read 155 -> 82 img/s here on one run a round; the
    # median + spread keys say how wide this section's band is
    e2e_dt, ev = _timed_median(run_pipeline)

    per_sec = n_imgs / e2e_dt
    _emit("tar_loader_sift_images_per_sec", round(per_sec, 1), "images/sec",
          round(per_sec / 100.0, 4),
          decode_only_images_per_sec=round(n_decoded / decode_dt, 1),
          image_side=side, n_images=n_imgs,
          overlap_efficiency=round(decode_dt / e2e_dt, 3), **ev)

    # -- streamed path: decode AND device_put move to the prefetch
    # thread (StreamingDataset, depth 2), so ingest of chunk i+1
    # overlaps the device work on chunk i. The serial path above pays
    # the host->device upload inline per chunk, which is the overlap a
    # double buffer recovers. Stall share comes from the process metrics.
    from keystone_tpu.loaders.image_loader_utils import stream_tar_images

    depth = 2

    def prepare(batch):
        # no tail padding here: _stage pads every chunk to chunk_size
        # and keeps the TRUE row count in chunk.n — pre-padding would
        # count zero images as real rows in any downstream carry
        return np.stack([img for _, img in batch]).astype(np.uint8)

    def run_streamed():
        stream = stream_tar_images([tar_path], chunk, prepare=prepare,
                                   n=n_imgs, prefetch_depth=depth)
        outs = [featurize_chunk(c.data) for c in stream.chunks()]
        _fence(outs)
        return len(outs)

    from keystone_tpu.observability import compile_observatory

    compile_wall0 = compile_observatory().wall_s_total()
    run_streamed()  # warm (compiles are shared with the serial path)
    share = _ingest_stall_probe(-(-n_imgs // chunk), n_imgs)
    s_dt, s_ev = _timed_median(run_streamed, warmup_fence=True,
                               compile_wall0=compile_wall0)
    s_per_sec = n_imgs / s_dt
    _emit("tar_loader_sift_streamed_images_per_sec", round(s_per_sec, 1),
          "images/sec", round(s_per_sec / 100.0, 4),
          prefetch_depth=depth,
          speedup_vs_serial=round(e2e_dt / s_dt, 3),
          ingest_stall_share=share(s_dt),
          h2d_bytes_per_image=share.h2d_bytes_per_image(),
          image_side=side, n_images=n_imgs, **s_ev)


# ----------------------------------------- streamed out-of-core e2e bench


def streamed_e2e_bench():
    """Streamed CIFAR end-to-end (the out-of-core path): host uint8
    chunks -> double-buffered device ingest (StreamingDataset, depth 2)
    -> per-chunk fused featurization -> BlockLS Gram/cross ACCUMULATE ->
    finalize -> streamed predict. The featurized training matrix never
    exists in HBM — device residency is the bounded prefetch buffer plus
    one chunk of features plus the (F, F) carry, and the ingest buffer
    is asserted against an explicit budget via
    ``parallel.dataset.device_nbytes``. vs_baseline shares the resident
    e2e's 10k img/s/chip strawman (expect a lower number: this path
    pays real host->device ingest, which the resident bench stages
    outside the timed region — the metric is the OVERLAPPED ingest
    cost, not a regression)."""
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.image_ops import filter_bank_convolve, pool_image
    from keystone_tpu.ops.pallas_kernels import (
        fused_cifar_featurize,
        use_pallas,
    )
    from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming

    n_dev = len(jax.devices())
    num_filters = 64 if SMALL else 256
    patch = 6
    chunk = 256 if SMALL else 1_024
    n_train = 1_024 if SMALL else _scaled(8_192, mult=1_024, floor=2_048)
    n_test = 256 if SMALL else _scaled(2_048, mult=1_024, floor=1_024)
    depth = 2
    F = num_filters * 2 * 2 * 2

    rng = np.random.RandomState(7)
    filters = rng.randn(num_filters, patch * patch * 3).astype(np.float32)

    from keystone_tpu.observability import observed_jit

    # observed sites: the utilization window totals flops x calls over
    # every program that ran, so the section's featurize must be a
    # watched jit, not an anonymous bench-local one
    if use_pallas():
        @functools.partial(observed_jit, name="e2e_featurize")
        def featurize(imgs_u8):
            return fused_cifar_featurize(
                imgs_u8.astype(jnp.float32), jnp.asarray(filters), 32,
                patch, 3, 13, 14, 10.0, 0.25)
    else:
        @functools.partial(observed_jit, name="e2e_featurize")
        def featurize(imgs_u8):
            def one(img):
                conv = filter_bank_convolve(
                    img, jnp.asarray(filters), patch, 3, True, None, 10.0)
                pos = jnp.maximum(0.0, conv - 0.25)
                neg = jnp.maximum(0.0, -conv - 0.25)
                return pool_image(
                    jnp.concatenate([pos, neg], -1), 13, 14, "identity",
                    "sum").reshape(-1)

            return jax.vmap(one)(imgs_u8.astype(jnp.float32))

    # uint8 on the wire (4x smaller than f32); chunk labels are sliced
    # from the resident (n, 10) matrix — tiny next to the images
    imgs_host = (rng.rand(n_train, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, n_train)
    L = (-np.ones((n_train, 10)) + 2.0 * np.eye(10)[y]).astype(np.float32)
    imgs_test = (rng.rand(n_test, 32, 32, 3) * 255).astype(np.uint8)

    # the ingest buffer may hold depth staged chunks + one working
    # chunk; anything beyond that margin means the stream is NOT
    # bounded and the out-of-core claim is false — fail loudly
    chunk_raw = chunk * 32 * 32 * 3
    budget = (depth + 1) * chunk_raw + (1 << 20)

    est = BlockLeastSquaresEstimator(min(1024, F), 1, lam=0.1)

    def feat_chunks(u8_stream):
        return u8_stream.map_chunks(lambda ad: ad.map_batch(featurize))

    result = {}

    def fit_and_predict():
        train = StreamingDataset.from_numpy(
            imgs_host, chunk_size=chunk, prefetch_depth=depth,
            tag="cifar-stream-train")
        model = fit_streaming(est, feat_chunks(train), L,
                              hbm_budget=budget)
        result["peak_stream"] = train.peak_device_nbytes
        # device-free planner prediction for the SAME stream geometry:
        # plan >= measured always (the ledger can never exceed it), and
        # plan/measured near 1 means the buffer saturated as modeled
        result["static_plan"] = train.static_plan_nbytes()
        test = StreamingDataset.from_numpy(
            imgs_test, chunk_size=chunk, prefetch_depth=depth,
            tag="cifar-stream-test")
        preds = []
        for out in model.apply_dataset(feat_chunks(test)).chunks():
            preds.append(np.asarray(
                jnp.argmax(out.data, axis=-1))[: out.n])
        result["preds"] = np.concatenate(preds)

    from keystone_tpu.observability import compile_observatory

    compile_wall0 = compile_observatory().wall_s_total()
    fit_and_predict()  # warm: one compile per chunk shape, then zero

    from keystone_tpu.observability.utilization import UtilizationWindow

    share = _ingest_stall_probe(
        -(-n_train // chunk) + -(-n_test // chunk), n_train + n_test)
    with UtilizationWindow() as uw:
        dt, ev = _timed_median(fit_and_predict, warmup_fence=True,
                               compile_wall0=compile_wall0)

    # numerics-overhead A/B pairs (PERFORMANCE.md rule 12): the OFF leg
    # runs the SAME warm path under numerics_suppressed() — the runtime
    # gate, no recompile, identical programs — so the difference is
    # purely the plane's per-chunk health words + sketch updates.
    # INTERLEAVED single-run pairs, median of per-pair shares: the ON
    # and OFF halves of a pair are adjacent in time, so slow machine
    # drift (the dominant noise on shared CPU-sim boxes, spreads up to
    # ~0.3 between sequential medians) cancels within each pair.
    # Tracked as a banded lower-is-better metric; the bar is <2% on
    # hardware (negative = below machine noise).
    from keystone_tpu.observability.numerics import numerics_suppressed

    def _single(suppress):
        t0 = time.perf_counter()
        if suppress:
            with numerics_suppressed():
                fit_and_predict()
        else:
            fit_and_predict()
        return time.perf_counter() - t0

    pair_shares = []
    for _ in range(3 if SMALL else 2):
        t_on = _single(False)
        t_off = _single(True)
        if t_off > 0:
            pair_shares.append((t_on - t_off) / t_off)
    overhead_share = (sorted(pair_shares)[len(pair_shares) // 2]
                      if pair_shares else None)
    # hardware denominator (PERFORMANCE.md rule 11): achieved FLOP/s
    # over device peak and bytes/s over HBM bandwidth, from the compile
    # observatory's per-executable cost_analysis x observed call counts
    util = uw.report(n_devices=n_dev)

    per_chip = (n_train + n_test) / dt / n_dev
    plan = result.get("static_plan")
    peak = result["peak_stream"]
    _emit("cifar_streamed_e2e_images_per_sec_per_chip", round(per_chip, 1),
          "images/sec/chip", round(per_chip / 10000.0, 4),
          chunk_size=chunk, prefetch_depth=depth, n_train=n_train,
          num_filters=num_filters,
          hbm_budget_mib=round(budget / (1 << 20), 2),
          peak_stream_mib=round(peak / (1 << 20), 2),
          # planner validation (BENCH_r06+): static_plan_hbm_mib is the
          # device-free prediction, plan_vs_measured its ratio to the
          # ledger peak (>= 1.0 by construction; ~1.0 = saturated
          # double buffer, large = the producer never filled the slots)
          static_plan_hbm_mib=(None if plan is None
                               else round(plan / (1 << 20), 2)),
          plan_vs_measured=(None if plan is None or not peak
                            else round(plan / peak, 3)),
          gram_carry_mib=round((F * F + F * 10) * 4 / (1 << 20), 2),
          ingest_stall_share=share(dt),
          h2d_bytes_per_image=share.h2d_bytes_per_image(),
          numerics_overhead_share=(None if overhead_share is None
                                   else round(overhead_share, 4)),
          e2e_mfu=round(util["mfu"], 5),
          e2e_membw_util=round(util["membw_util"], 5),
          roofline_bound=util["bound"],
          utilization_covered_sites=len(util["covered_sites"]),
          utilization_uncovered_sites=len(util["uncovered_sites"]),
          **ev)


def _section_cleanup():
    """Drop cross-section state so one section's HBM residue (datasets,
    prefix-cached fitted results) can't starve the next."""
    import gc

    try:
        _clear_prefix_state()
    except Exception:
        pass
    gc.collect()


def _run_section(section) -> bool:
    """Run one section with buffered emission: lines reach stdout only
    when the section completes, so a failed section can never leave
    partial metric lines. A failure is printed and returned as False —
    it is never retried, and ``main`` turns it into a non-zero exit."""
    global _section_buffer
    import sys
    import traceback

    _section_buffer = []
    try:
        section()
        for line in _section_buffer:
            _flush_line(line)
        return True
    except Exception:
        # stdout, not stderr: the driver captures stdout, so the
        # evidence of a failed section survives in the artifact
        traceback.print_exc(file=sys.stdout)
        return False
    finally:
        _section_buffer = None


def main():
    """Emit every BASELINE metric, one JSON line each, highest-priority
    sections first (flagship throughput, solver TFLOPS, accuracy — the
    round-2 timeout lost everything ordered after the apps). After every
    section the flagship summary line is re-emitted, so the LAST stdout
    line — what the driver parses as the headline — is always
    ``cifar_randompatch_images_per_sec_per_chip`` carrying every value
    measured so far, no matter where the run is cut off.

    Budgeting (VERDICT r5 weak#1): estimates come from MEASURED
    per-section durations persisted in ``.bench_durations.json`` by
    previous runs on this host (hardcoded values are only the cold
    fallback — stale estimates are what skipped 4-5 sections in r4/r5).
    A section whose estimate exceeds the remaining budget is SHRUNK
    (``_SCALE`` scales its n/reps; its metric lines carry a ``scaled``
    key), never skipped: every metric that has ever appeared in a
    BENCH_r*.json appears in every run."""
    global _SCALE
    # (section, fallback cost estimate in seconds — used only until a
    # measured duration exists for this host)
    # Ordering (r4 weak#1): after the flagship trio, least-recently-
    # measured sections run before well-covered repeats, so a budget
    # shortfall shrinks repeat measurements, not first measurements.
    sections = (
        (featurize_bench, 15),
        (solver_bench, 90),
        (accuracy_bench, 90),
        (voc_bench, 90),
        (amazon_bench, 25),
        (stupid_backoff_bench, 15),
        (imagenet_rehearsal_bench, 130),
        (pallas_kernels_bench, 60),
        (serving_bench, 45),
        (serving_soak_bench, 40),
        (fleet_bench, 50),
        (e2e_bench, 60),
        (loader_bench, 60),
        (streamed_e2e_bench, 60),
        (elastic_coordination_bench, 75),
        (newsgroups_bench, 30),
        (timit_bench, 120),
        (mnist_bench, 75),
    )
    # SMALL smoke runs neither consult nor record durations: their
    # seconds-long sections would poison the full-run budget estimates
    measured = {} if SMALL else _load_durations()
    deadline = _START + BUDGET_S
    _emit_meta()  # host identity up front: survives a cut-short run
    failed = []
    for section, fallback in sections:
        if (section is elastic_coordination_bench
                and jax.default_backend() == "tpu"):
            # it starts two CPU worker processes and reports their CPU
            # timings: not from a parent that holds the chip, and not
            # into a device run
            print(f"# skipping {section.__name__}: a CPU section, not "
                  "run from a process that holds a TPU", flush=True)
            continue
        est = 1.15 * measured.get(section.__name__, fallback)
        remaining = deadline - time.monotonic()
        if remaining >= est:
            _SCALE = 1.0
        else:
            # over budget: shrink, don't skip — a scaled number beats a
            # missing one (flagged via the "scaled" metric key). With
            # the deadline already passed (remaining <= 0) the section
            # still runs at the floor scale: BUDGET_S keeps >2 min of
            # margin under the driver's kill window precisely so a few
            # floor-scaled trailing sections fit inside it.
            _SCALE = max(_MIN_SCALE,
                         min(1.0, 0.8 * max(remaining, 0.0) / est))
            _scaled_sections.add(section.__name__)
            print(f"# shrinking {section.__name__} to scale "
                  f"{_SCALE:.2f}: {remaining:.0f}s of budget left < "
                  f"{est:.0f}s estimate", flush=True)
        t_sec = time.monotonic()
        ok = _run_section(section)
        took = time.monotonic() - t_sec
        if not ok:
            failed.append(section.__name__)
        if ok and not SMALL:
            if _SCALE == 1.0:
                _record_duration(section.__name__, took)
            elif section.__name__ in measured:
                # scaled runs never extrapolate (the ratchet-UP trap),
                # but an inflated estimate must also not stick forever
                # — a one-off cold-compile wall would otherwise shrink
                # this section on every future run. Decay it toward the
                # observed scaled wall (never below it), so the
                # estimate heals and the section re-earns full size.
                _record_duration(section.__name__,
                                 max(took, 0.85 * measured[section.__name__]))
        _SCALE = 1.0
        _section_cleanup()
        print(f"# {section.__name__} took {took:.0f}s", flush=True)
        _emit_summary()
    if _emitted == 0:
        raise SystemExit(f"every section failed: {failed}")
    _emit_meta()  # refresh: now carries the complete scaled list
    # The LAST stdout JSON line must be a metric line: the flagship
    # summary when available, else the flagship alone, else the best
    # (first-emitted) surviving metric.
    flag = _metrics.get(FLAGSHIP)
    if flag is not None and len(_metrics) >= 2:
        _emit_summary()
    elif flag is not None:
        print(json.dumps(flag), flush=True)
    else:
        print(json.dumps(next(iter(_metrics.values()))), flush=True)
    if failed:
        # the surviving sections' lines are above; a run with a failed
        # section is a failed run
        raise SystemExit(f"failed sections: {failed}")


def _pop_trace_out(argv):
    """Extract ``--trace-out PATH`` from argv (None when absent)."""
    if "--trace-out" not in argv:
        return None
    i = argv.index("--trace-out")
    if i + 1 >= len(argv):
        raise SystemExit("--trace-out requires a path")
    path = argv[i + 1]
    del argv[i:i + 2]
    return path


if __name__ == "__main__":
    import sys

    from keystone_tpu.utils.compile_cache import enable_compile_cache

    # the one cache rule (JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.xla_cache); only from the CLI entry — importing bench
    # for a helper must not turn on disk-cache side effects
    enable_compile_cache()
    sections = {
        "--solver": solver_bench,
        "--accuracy": accuracy_bench,
        "--imagenet": imagenet_rehearsal_bench,
        "--e2e": e2e_bench,
        "--featurize": featurize_bench,
        "--mnist": mnist_bench,
        "--timit": timit_bench,
        "--newsgroups": newsgroups_bench,
        "--loader": loader_bench,
        "--amazon": amazon_bench,
        "--stupid-backoff": stupid_backoff_bench,
        "--voc": voc_bench,
        "--streamed-e2e": streamed_e2e_bench,
        "--serving": serving_bench,
        "--serving-soak": serving_soak_bench,
        "--fleet": fleet_bench,
    }
    argv = list(sys.argv[1:])
    trace_out = _pop_trace_out(argv)
    picked = [f for f in argv if f in sections]
    unknown = [f for f in argv if f.startswith("--")
               and f not in sections]
    if unknown:
        raise SystemExit(f"unknown bench flags {unknown}; "
                         f"known: {sorted(sections)} plus --trace-out PATH")

    def _run_all():
        if picked:
            _emit_meta()  # single-section runs carry host identity too
            for f in picked:
                sections[f]()
        else:
            main()

    if trace_out is None:
        _run_all()
    else:
        # bench numbers should travel with their execution evidence
        # (PERFORMANCE.md): the trace JSON records per-node wall times,
        # optimizer rule log, auto-cache report, and solver decisions;
        # a *.perfetto.json path writes the flight recorder's Chrome
        # trace instead (ingest/H2D/compute lanes — the overlap
        # evidence, viewable at https://ui.perfetto.dev)
        from keystone_tpu.observability import (
            PipelineTrace,
            write_trace_artifact,
        )

        with PipelineTrace("bench") as _tr:
            _run_all()
        _kind = write_trace_artifact(trace_out, _tr)
        print(_tr.summary(top=30), file=sys.stderr)
        print(f"# {_kind} written to {trace_out}", file=sys.stderr)
