"""Chip probe for PaddedFFT (ISSUE 25, step 1): what the real half-spectrum
of short real rows costs on the device as a complex FFT (what the node ran
before PR 25), as ``rfft`` of the padded real rows, and as one dense
product with a cosine table at ``Precision.HIGHEST``; and how far each lies
from a float64 DFT computed on the host.

    chiprun -- python3 tools/probe_padded_fft.py

Rows are shaped like ``mnist_refit``'s (integers 0..255, sparse ink, from
``benchmarks/datagen/mnist_csv.py``), 60,000 of 784 pixels; the larger
sizes keep rows x padded length constant so every size moves the same
bytes. Device times are the medians of the programs' ``XLA Modules``
events in one profiler capture. Needs a TPU: nothing here is a number a
CPU can give. Writes ``chiprun_out/probe_padded_fft.json``.
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

#: (n, rows): padded length 1,024 / 4,096 / 8,192 / 16,384
SIZES = ((784, 60000), (3000, 15000), (6000, 7500), (12000, 3750))
BRANCHES = 32
ERROR_ROWS = 512
REPS = 5


def padded_length(n: int) -> int:
    return 1 << (n - 1).bit_length()


def rows_like_cell(rows: int, n: int, seed: int) -> np.ndarray:
    from benchmarks.datagen import mnist_csv

    rng = np.random.default_rng(seed)
    templates = mnist_csv.make_templates(10, rng)
    reps = -(-n // mnist_csv.PIXELS)
    pixels, _ = mnist_csv.make_images(rows * reps, rng, templates)
    return pixels.reshape(rows, reps * mnist_csv.PIXELS)[:, :n].astype(np.float32)


def transforms(n: int):
    """Per-item callables ``(n,) -> (padded / 2,)`` by name."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.stats import _cosine_table

    padded = padded_length(n)
    table = _cosine_table(n, padded, "float32")

    def pad(x):
        return jnp.concatenate([x, jnp.zeros((padded - n,), x.dtype)], axis=-1)

    def fft(x):
        return jnp.real(jnp.fft.fft(pad(x)))[: padded // 2].astype(x.dtype)

    def rfft(x):
        return jnp.real(jnp.fft.rfft(pad(x)))[: padded // 2].astype(x.dtype)

    def dense(x):
        return jnp.dot(x, table, precision=jax.lax.Precision.HIGHEST)

    return {"fft": fft, "rfft": rfft, "dense": dense}


def programs(n: int, signs: np.ndarray):
    """Jitted whole-batch programs by name: one branch each way, and at
    the cell's size the 32 branches with sign multiply, rectifier and
    concatenate in one program (what the fused gather builds)."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.stats import _cosine_table

    padded = padded_length(n)
    out = {}
    for kind, t in transforms(n).items():
        def one(X, _t=t):
            return jax.vmap(_t)(X)
        one.__name__ = f"one_{kind}_{padded}"
        out[one.__name__] = jax.jit(one)
        if n != SIZES[0][0]:
            continue

        def gathered(X, _t=t):
            def item(x):
                return jnp.concatenate(
                    [jnp.maximum(_t(x * s), 0.0) for s in signs])
            return jax.vmap(item)(X)
        gathered.__name__ = f"all{BRANCHES}_{kind}_{padded}"
        out[gathered.__name__] = jax.jit(gathered)
    if n == SIZES[0][0]:
        # the signs folded into one (n, branches * padded / 2) table:
        # not this PR's change, read for the issue after it
        folded = np.concatenate(
            [s[:, None] * _cosine_table(n, padded, "float32") for s in signs], axis=1)

        def all_folded(X):
            return jnp.maximum(jnp.dot(
                X, folded, precision=jax.lax.Precision.HIGHEST), 0.0)
        all_folded.__name__ = f"all{BRANCHES}_folded_{padded}"
        out[all_folded.__name__] = jax.jit(all_folded)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=2500000011)
    p.add_argument("--out", default="chiprun_out/probe_padded_fft.json")
    args = p.parse_args(argv)

    import jax

    from benchmarks import xplane

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"JAX found {dev.platform!r}: this probe measures "
                         "the chip and has no CPU fallback")
    print(f"[{dev.platform} {dev.device_kind} x{len(jax.devices())}] "
          f"jax {jax.__version__}", flush=True)

    rng = np.random.RandomState(0)
    result = {"device": dev.device_kind, "seed": args.seed, "sizes": {}}
    for n, rows in SIZES:
        padded = padded_length(n)
        signs = (2.0 * rng.randint(0, 2, size=(BRANCHES, n)) - 1.0
                 ).astype(np.float32)
        host = rows_like_cell(rows, n, args.seed)
        X = jax.device_put(host)
        progs = programs(n, signs)

        # error against float64 on the host, max |diff| / max |out|
        exact = np.real(np.fft.fft(np.pad(
            host[:ERROR_ROWS].astype(np.float64), ((0, 0), (0, padded - n))),
            axis=-1))[:, : padded // 2]
        errors = {}
        for kind in ("fft", "rfft", "dense"):
            got = np.asarray(progs[f"one_{kind}_{padded}"](X[:ERROR_ROWS]),
                             dtype=np.float64)
            errors[kind] = float(np.abs(got - exact).max() / np.abs(exact).max())

        compile_s, host_ms = {}, {}
        for name, fn in progs.items():
            t0 = time.perf_counter()
            fn(X).block_until_ready()
            compile_s[name] = time.perf_counter() - t0
        trace_dir = tempfile.mkdtemp(prefix="probe_fft_")
        try:
            jax.profiler.start_trace(trace_dir)
            for name, fn in progs.items():
                walls = []
                for _ in range(REPS):
                    t0 = time.perf_counter()
                    fn(X).block_until_ready()
                    walls.append(1e3 * (time.perf_counter() - t0))
                host_ms[name] = statistics.median(walls)
            jax.profiler.stop_trace()
            trace = xplane.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        modules = trace.devices[0].modules if trace.devices else []
        device_ms = {}
        for name in progs:
            durs = [(e - s) / 1e6 for prog, s, e in modules
                    if prog == f"jit_{name}"]
            device_ms[name] = statistics.median(durs) if durs else None
        size = {"n": n, "padded": padded, "rows": rows, "errors": errors,
                "device_ms": device_ms, "host_ms": host_ms,
                "first_call_s": compile_s,
                "ns_per_transform": {
                    k: (1e6 * v / rows / (BRANCHES if k.startswith("all") else 1)
                        if v else None) for k, v in device_ms.items()}}
        result["sizes"][str(padded)] = size
        print(json.dumps(size), flush=True)
        del X, progs

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
