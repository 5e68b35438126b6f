"""Data-parallel sharded apply over the world mesh (FSDP-style).

Fit went multi-host in PR 11 (shard-local accumulate, cross-host
reduce at finalize); apply stayed single-host — every serving replica
held the WHOLE fitted model and the whole request batch. This module
closes that gap with a ``shard_map`` apply over the ``data`` axis of
the world mesh (:func:`~keystone_tpu.parallel.mesh.world_data_mesh`):

* **batch rows** shard ``P('data')`` — each device (and so each host)
  applies only its row slice; bucketed request shapes (PR 15) keep the
  per-shard shapes fixed, so each bucket compiles exactly once;

* **weight rows** of :class:`~keystone_tpu.nodes.learning.linear.
  LinearMapper` / :class:`~keystone_tpu.nodes.learning.linear.
  BlockLinearMapper` shard ``P('data', None)`` AT REST — the resident
  per-host footprint is ``model_nbytes / num_data_shards``. Inside the
  ``shard_map`` body a ``jax.lax.all_gather(..., tiled=True)``
  reassembles the weights TRANSIENTLY for the GEMM: the whole matrix
  at once for ``LinearMapper``, one feature block at a time for
  ``BlockLinearMapper`` — the block variant's transient peak is one
  block, which is what lets the serving plane place a model whose
  total ``model_nbytes`` exceeds a single host's budget
  (``serving/residency.py`` charges exactly this arithmetic:
  resident shard + gather transient + activation shard);

* **fused featurize chains** (``workflow/optimizer/fusion.py``) ride
  the same batch sharding: their one param-threaded program is
  GSPMD-partitioned by feeding it a ``P('data')`` batch — featurize
  params are small and stay replicated, only the terminal linear
  stage needs the FSDP treatment above.

Compile discipline: programs are cached per ``(mesh, flavor, static
dims)`` — the same content-free property as ``_affine_apply_batch``
(params ride as arguments), so refits reuse the program and the
serving warmup fence stays clean. Row counts that do not divide the
shard count are zero-padded to the next multiple and sliced off the
output (pad rows cost FLOPs, never correctness — the affine body is
row-local).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, get_mesh, num_data_shards, replicated_sharding

__all__ = [
    "shard_rows",
    "shard_batch",
    "unshard_batch",
    "sharded_apply",
    "sharded_chain_apply",
]


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // int(m)) * int(m)


def shard_rows(arr: Any, mesh: Optional[Mesh] = None) -> jax.Array:
    """Row-shard a ``(d, ...)`` parameter over the mesh's data axis,
    zero-padding ``d`` up to a multiple of the shard count (the apply
    bodies slice the pad rows off after the gather, so padding never
    reaches the math). This is the AT-REST placement: per host,
    ``ceil(d / shards) x cols`` of the matrix."""
    mesh = mesh or get_mesh()
    shards = num_data_shards(mesh)
    arr = jnp.asarray(arr)
    pad = _round_up(arr.shape[0], shards) - arr.shape[0]
    if pad:
        arr = jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
    spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def shard_batch(x: Any, mesh: Optional[Mesh] = None,
                ) -> Tuple[jax.Array, int]:
    """Place a row-major batch ``P('data')`` on the mesh, zero-padding
    the row count to a multiple of the shard count. Returns ``(global
    array, true row count)`` — slice the apply output back with
    ``unshard_batch``. Under a multi-process world each host passes
    its LOCAL rows (every host the same count — the PR 15 bucket
    contract) and the global batch is their process-major
    concatenation."""
    mesh = mesh or get_mesh()
    shards = num_data_shards(mesh)
    x = jnp.asarray(x)
    n = int(x.shape[0])
    if len(mesh.devices.flat) > len(jax.local_devices()):
        # world mesh: this host's rows become its shard of the global
        # batch — pad to a multiple of the LOCAL device count so the
        # per-device slices stay equal
        from jax.experimental.multihost_utils import (
            host_local_array_to_global_array,
        )

        local = sum(1 for d in mesh.devices.flat
                    if d.process_index == jax.process_index())
        pad = _round_up(n, local) - n
        if pad:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        glob = host_local_array_to_global_array(
            np.asarray(x), mesh, P(DATA_AXIS))
        return glob, n
    pad = _round_up(n, shards) - n
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS))), n


def unshard_batch(out: jax.Array, n: int,
                  mesh: Optional[Mesh] = None) -> Any:
    """Undo :func:`shard_batch` on an apply output: back to this
    host's local rows with the zero-pad sliced off."""
    mesh = mesh or get_mesh()
    if len(mesh.devices.flat) > len(jax.local_devices()):
        from jax.experimental.multihost_utils import (
            global_array_to_host_local_array,
        )

        local = global_array_to_host_local_array(out, mesh, P(DATA_AXIS))
        return np.asarray(local)[:n]
    return out[:n]


# -- the shard_map programs --------------------------------------------------
#
# ONE compiled program per (mesh, flavor, static dims): weights, means
# and intercepts ride as ARGUMENTS (the content-free discipline of
# _affine_apply_batch), so every refit of the same shapes reuses the
# entry and the serving warmup fence sees zero compiles.

_PROGRAMS: dict = {}


def _affine_program(mesh: Mesh, d: int):
    key = (mesh, "affine", int(d))
    fn = _PROGRAMS.get(key)
    if fn is None:
        def body(x, w_shard, mean, inv_std, b):
            # transient: the FULL weight matrix, gathered for the GEMM
            # (the FSDP unit — resident stays the shard)
            w = jax.lax.all_gather(w_shard, DATA_AXIS, axis=0, tiled=True)
            return ((x - mean) * inv_std) @ w[:d] + b

        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS, None), P(), P(), P()),
            out_specs=P(DATA_AXIS)))
        _PROGRAMS[key] = fn
    return fn


def _block_program(mesh: Mesh, bounds: Tuple[Tuple[int, int], ...]):
    key = (mesh, "block", tuple(bounds))
    fn = _PROGRAMS.get(key)
    if fn is None:
        def body(x, mean, b, *block_shards):
            # transient: ONE feature block at a time — the peak that
            # lets total model_nbytes exceed a single host's budget
            acc = None
            for (lo, hi), w_shard in zip(bounds, block_shards):
                w = jax.lax.all_gather(
                    w_shard, DATA_AXIS, axis=0, tiled=True)[: hi - lo]
                part = (x[:, lo:hi] - mean[lo:hi]) @ w
                acc = part if acc is None else acc + part
            return acc + b

        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(), P())
            + (P(DATA_AXIS, None),) * len(bounds),
            out_specs=P(DATA_AXIS)))
        _PROGRAMS[key] = fn
    return fn


# -- resident sharded params -------------------------------------------------

def _sharded_affine_params(model, mesh: Mesh):
    """The mapper's fitted params placed for the sharded apply: W
    row-sharded at rest, the small vectors replicated. Cached per
    (model instance, mesh) under ``_jit_`` so pickling strips it."""
    cached = model.__dict__.get("_jit_sharded_params")
    if cached is not None and cached[0] is mesh:
        return cached[1]
    w, mean, inv_std, b = model.apply_params()
    rep = replicated_sharding(mesh)
    placed = (shard_rows(w, mesh),
              jax.device_put(jnp.asarray(mean), rep),
              jax.device_put(jnp.asarray(inv_std), rep),
              jax.device_put(jnp.asarray(b), rep))
    model.__dict__["_jit_sharded_params"] = (mesh, placed)
    return placed


def _sharded_block_params(model, mesh: Mesh):
    cached = model.__dict__.get("_jit_sharded_params")
    if cached is not None and cached[0] is mesh:
        return cached[1]
    bounds = tuple(model._block_bounds())
    d = bounds[-1][1]
    k = model.weights.shape[1]
    mean = (jnp.zeros((d,), jnp.float32) if model.feature_means is None
            else jnp.asarray(model.feature_means, jnp.float32))
    b = (jnp.zeros((k,), jnp.float32) if model.intercept is None
         else jnp.asarray(model.intercept, jnp.float32))
    rep = replicated_sharding(mesh)
    placed = (bounds,
              tuple(shard_rows(jnp.asarray(w, jnp.float32), mesh)
                    for w in model.block_weights),
              jax.device_put(mean, rep), jax.device_put(b, rep))
    model.__dict__["_jit_sharded_params"] = (mesh, placed)
    return placed


# -- public entry points -----------------------------------------------------

def sharded_apply(model, x: Any, mesh: Optional[Mesh] = None) -> Any:
    """Apply a fitted linear model data-parallel over ``mesh`` (default
    the process mesh; pass :func:`~keystone_tpu.parallel.mesh.
    world_data_mesh` for the cross-host case). Numerically the same
    affine math as ``model.apply`` — parity is pinned at 1e-5 with
    identical argmax across buckets including ragged tails
    (``tests/test_spmd_apply.py``).

    ``LinearMapper`` gathers its whole (row-sharded) W per call;
    ``BlockLinearMapper`` gathers one block at a time. Quantized
    mappers (``weight_dtype``) keep their fused dequant program and
    only the BATCH is sharded — per-column scales make the row-shard
    gather a different program, deliberately out of scope here."""
    from ..nodes.learning.linear import (
        BlockLinearMapper,
        _quantized_affine_batch,
    )

    mesh = mesh or get_mesh()
    xg, n = shard_batch(x, mesh)
    if getattr(model, "weight_dtype", None) is not None:
        out = _quantized_affine_batch(
            xg, *model.apply_params(), mesh=mesh)
        return unshard_batch(out, n, mesh)
    if isinstance(model, BlockLinearMapper):
        bounds, shards, mean, b = _sharded_block_params(model, mesh)
        out = _block_program(mesh, bounds)(xg, mean, b, *shards)
        return unshard_batch(out, n, mesh)
    w, mean, inv_std, b = _sharded_affine_params(model, mesh)
    out = _affine_program(mesh, int(mean.shape[0]))(xg, w, mean, inv_std, b)
    return unshard_batch(out, n, mesh)


def sharded_chain_apply(fused, x: Any,
                        mesh: Optional[Mesh] = None) -> Any:
    """Data-parallel apply of a fused featurize chain (or any
    batch-callable transformer): the batch shards ``P('data')`` and
    the chain's one param-threaded program partitions via GSPMD —
    featurize params are small and replicate; a terminal linear stage
    wanting the FSDP weight treatment goes through
    :func:`sharded_apply` instead."""
    mesh = mesh or get_mesh()
    xg, n = shard_batch(x, mesh)
    batched = getattr(fused, "_batched", None)
    fn = batched() if callable(batched) else jax.jit(fused.apply)
    return unshard_batch(fn(xg), n, mesh)
