"""Chip probe for the streamed block solve (ISSUE 26, step 1; the
fused sweep since ISSUE 34): what one 4,096-wide cosine block costs on
the device to make, to take the Gram of, to factor, to step for the
first time (old weights zero: what the factor sweep does while the block
is alive) and to step again (the later epochs' step, with the product
``A W_old``), at 32,768 / 49,152 / 65,536 rows of 440; and what the two
whole programs of a 50-block, 5-epoch fit (``ops.linalg.
bcd_stream_factor``, which is also the first epoch, and
``bcd_stream_epochs``, the four after it) and the blockwise apply over
8,192 rows take, with the process's peak bytes after each size. A block
of the fused sweep should cost ``part_generate + part_gram +
part_factor + part_first_step``, a block of a later pass
``part_generate + part_step``.

    chiprun --timeout 1800 -- python3 tools/probe_streamed_bcd.py

Device times are the medians of the programs' ``XLA Modules`` events in
one profiler capture a size. Compile seconds are the host's, around the
first call; the cache entry sizes are the files the persistent cache
gained (entries over 192 MiB are not kept on the chip machines). Needs a
TPU: nothing here is a number a CPU can give. Writes
``chiprun_out/probe_streamed_bcd.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = (32768, 49152, 65536)
TEST_ROWS = 8192
DIM, WIDTH, BLOCKS, CLASSES, EPOCHS = 440, 4096, 50, 147, 5
GAMMA = 0.05555
REPS = 3


def make_block(params, rows):
    import jax.numpy as jnp

    W, b = params
    return jnp.cos(rows @ W.T + b)


def programs():
    """Jitted programs by name: the parts of one block, and the whole
    sweeps."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops import linalg

    def part_generate(rows, W, b):
        with linalg.solver_precision():
            return make_block((W, b), rows)

    def part_gram(A):
        with linalg.solver_precision():
            return linalg.gram(A)

    def part_factor(G):
        return jax.scipy.linalg.cho_factor(G, lower=True)[0]

    def part_first_step(A, L, target):
        with linalg.solver_precision():
            W = jax.scipy.linalg.cho_solve((L, True), linalg.cross(A, target))
            return target - A @ W, W

    def part_step(A, L, target, W_old):
        with linalg.solver_precision():
            rhs = linalg.cross(A, target + A @ W_old)
            W = jax.scipy.linalg.cho_solve((L, True), rhs)
            return target - A @ (W - W_old), W

    def sweep_factor(rows, params, Y, mask, n, lam):
        return linalg.bcd_stream_factor(
            rows, params, make_block, Y, mask, n, lam)

    def sweep_epochs(rows, params, Y, mask, means, Ls, Ws, pred):
        return linalg.bcd_stream_epochs(
            rows, params, make_block, Y, mask, means, Ls, Ws, pred,
            num_passes=EPOCHS - 1)

    def sweep_apply(rows, params, means, Ws, intercept):
        return linalg.block_stream_apply(
            rows, params, make_block, means, Ws, intercept)

    return {f.__name__: jax.jit(f) for f in (
        part_generate, part_gram, part_factor, part_first_step, part_step,
        sweep_factor, sweep_epochs, sweep_apply)}


def cache_files(path):
    out = {}
    for name in os.listdir(path) if os.path.isdir(path) else ():
        full = os.path.join(path, name)
        if os.path.isfile(full) and not name.endswith("-atime"):
            out[name] = os.path.getsize(full)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=2600000011)
    p.add_argument("--rows", type=int, nargs="*", default=list(ROWS))
    p.add_argument("--out", default="chiprun_out/probe_streamed_bcd.json")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import xplane

    from keystone_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"JAX found {dev.platform!r}: this probe measures "
                         "the chip and has no CPU fallback")
    print(f"[{dev.platform} {dev.device_kind} x{len(jax.devices())}] "
          f"jax {jax.__version__}", flush=True)

    from benchmarks.datagen import timit_frames

    rng = np.random.RandomState(args.seed % (2 ** 31))
    W = (rng.randn(BLOCKS, WIDTH, DIM) * GAMMA).astype(np.float32)
    b = (rng.rand(BLOCKS, WIDTH) * 2 * np.pi).astype(np.float32)
    params = (jax.device_put(W), jax.device_put(b))
    progs = programs()
    result = {"device": dev.device_kind, "seed": args.seed, "rows": {}}
    lam = jnp.float32(0.0)
    for n in args.rows:
        (train, labels), (test, _) = timit_frames.make_frames(
            n, TEST_ROWS, args.seed, DIM, CLASSES)
        rows = jax.device_put(train)
        Y = jax.device_put(np.where(
            np.arange(CLASSES)[None, :] == labels[:, None], 1.0, -1.0
        ).astype(np.float32))
        Y = Y - Y.mean(axis=0)
        mask = jnp.ones((n,), jnp.float32)
        nf = jnp.float32(n)
        before = cache_files(cache)
        compile_s = {}

        def first(name, *a):
            t0 = time.perf_counter()
            out = jax.block_until_ready(progs[name](*a))
            compile_s[name] = time.perf_counter() - t0
            return out

        A = first("part_generate", rows, params[0][0], params[1][0])
        A = A - A.mean(axis=0)
        G = first("part_gram", A)
        L = first("part_factor", G)
        _, W1 = first("part_first_step", A, L, Y)
        first("part_step", A, L, Y, W1)
        del G
        (means, Ls, oks, ratios), Ws, pred = first(
            "sweep_factor", rows, params, Y, mask, nf, lam)
        Ws, _ = first(
            "sweep_epochs", rows, params, Y, mask, means, Ls, Ws, pred)
        del pred
        test_dev = jax.device_put(test)
        icpt = jnp.zeros((CLASSES,), jnp.float32)
        first("sweep_apply", test_dev, params, means, Ws, icpt)
        gained = {k: v for k, v in cache_files(cache).items()
                  if k not in before}

        trace_dir = tempfile.mkdtemp(prefix="probe_trace_")
        jax.profiler.start_trace(trace_dir)
        wall = {}
        for _ in range(REPS):
            A2 = progs["part_generate"](rows, params[0][0], params[1][0])
            G2 = progs["part_gram"](A)
            progs["part_factor"](G2)
            progs["part_first_step"](A, L, Y)
            jax.block_until_ready(progs["part_step"](A, L, Y, W1))
            del A2, G2
            t0 = time.perf_counter()
            (m2, L2, ok2, _r), W2, P2 = progs["sweep_factor"](
                rows, params, Y, mask, nf, lam)
            W2, _ = progs["sweep_epochs"](
                rows, params, Y, mask, m2, L2, W2, P2)
            jax.block_until_ready(W2)
            wall.setdefault("fit", []).append(time.perf_counter() - t0)
            jax.block_until_ready(progs["sweep_apply"](
                test_dev, params, m2, W2, icpt))
            del m2, L2, ok2, W2, P2
        jax.profiler.stop_trace()
        trace = xplane.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        per = {}
        for name, start, end in trace.devices[0].modules:
            per.setdefault(xplane.program_name(name), []).append(
                (end - start) / 1e6)
        device_ms = {k: statistics.median(v) for k, v in per.items()}
        stats = dev.memory_stats() or {}
        entry = {
            "device_ms": device_ms,
            "fit_wall_s": statistics.median(wall["fit"]),
            "compile_s": compile_s,
            "cache_entries_bytes": sorted(gained.values(), reverse=True),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "unhealthy_blocks": int(BLOCKS - int(np.sum(np.asarray(oks)))),
            "min_pivot_ratio": float(np.min(np.asarray(ratios))),
        }
        result["rows"][str(n)] = entry
        print(f"rows {n}: " + json.dumps(entry), flush=True)
        del rows, Y, A, L, W1, means, Ls, oks, ratios, Ws, test_dev
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
