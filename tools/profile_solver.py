"""One-off profiler for the block-LS solver's constituent ops at CIFAR
scale (n=50k, bs=4096, k=10). Data is generated ON DEVICE (its content
is irrelevant). Timings end by waiting for the device.

Usage: python tools/profile_solver.py [--small]
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import random

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from keystone_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

HIGHEST = jax.lax.Precision("highest")
SMALL = "--small" in sys.argv
n, bs, k = (5_000, 1024, 10) if SMALL else (50_000, 4096, 10)


fence = jax.block_until_ready


def bench(name, fn, *args, iters=5, flops=None):
    fence(fn(*args))  # compile + warm
    fence(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    dt = (time.perf_counter() - t0) / iters
    rate = f"  {flops / dt / 1e12:7.2f} TFLOPS(nominal)" if flops else ""
    print(f"{name:28s} {dt * 1e3:9.2f} ms{rate}", flush=True)
    return dt


A = random.normal(random.PRNGKey(0), (n, bs), jnp.float32)
Y = random.normal(random.PRNGKey(1), (n, k), jnp.float32)
fence((A, Y))

gram_flops = 2.0 * n * bs * bs


@jax.jit
def gram_full(A):
    return jnp.einsum("nd,ne->de", A, A, precision=HIGHEST)


def make_syrk(tile):
    T = bs // tile

    @jax.jit
    def g(A):
        ts = [A[:, i * tile:(i + 1) * tile] for i in range(T)]
        blk = {}
        for i in range(T):
            for j in range(i, T):
                blk[(i, j)] = jnp.einsum(
                    "nd,ne->de", ts[i], ts[j], precision=HIGHEST)
        rows = [
            jnp.concatenate(
                [blk[(i, j)] if i <= j else blk[(j, i)].T for j in range(T)],
                axis=1)
            for i in range(T)
        ]
        return jnp.concatenate(rows, axis=0)

    return g


@jax.jit
def chol(G):
    return jax.scipy.linalg.cho_factor(
        G + 0.1 * jnp.eye(G.shape[0], dtype=G.dtype), lower=True)[0]


@jax.jit
def cho_solve_(L, R):
    return jax.scipy.linalg.cho_solve((L, True), R)


@jax.jit
def cross_resid(A, W, Y):
    tgt = Y - A @ W
    return jnp.einsum("nd,nk->dk", A, tgt, precision=HIGHEST)


t_full = bench("gram full einsum", gram_full, A, flops=gram_flops)
for tile in (512, 1024):
    frac = (bs // tile) * (bs // tile + 1) / 2 / (bs // tile) ** 2
    t = bench(f"gram syrk tile={tile}", make_syrk(tile), A, flops=gram_flops)
    print(f"  (computed fraction {frac:.3f}, ideal {t_full * frac * 1e3:.1f} ms)")

G = gram_full(A)
fence(G)
L = chol(G)
fence(L)
W0 = jnp.zeros((bs, k), jnp.float32)
bench("cholesky factor", chol, G, flops=bs ** 3 / 3)
bench("cho_solve rhs k=10", cho_solve_, L, random.normal(random.PRNGKey(2), (bs, k), jnp.float32))
bench("cross+residual", cross_resid, A, W0, Y, flops=4.0 * n * bs * k)
print("done", flush=True)
