"""Shared measurement helpers for the tools/ scripts.

Every timed region ends by waiting for the device
(``jax.block_until_ready``: dispatch is asynchronous); and fitted models
are NOT registered pytrees, so finding their device arrays requires
walking object attributes, not tree leaves. Both live here once.
"""
import time

import jax


def device_arrays(obj, _seen=None):
    """Collect arrays reachable from ``obj``, recursing into plain
    containers AND object attributes (fitted models hand ``tree_leaves``
    the model object itself)."""
    if _seen is None:
        _seen = set()
    if id(obj) in _seen:
        return []
    _seen.add(id(obj))
    if hasattr(obj, "dtype") and hasattr(obj, "shape"):
        return [obj]
    out = []
    if isinstance(obj, dict):
        vals = obj.values()
    elif isinstance(obj, (list, tuple)):
        vals = obj
    elif hasattr(obj, "__dict__"):
        vals = vars(obj).values()
    else:
        return out
    for v in vals:
        out.extend(device_arrays(v, _seen))
    return out


def fence(tree):
    """Wait for everything producing ``tree``. Only DEVICE arrays are
    waited on: host ndarrays have nothing in flight."""
    arrays = []
    for leaf in jax.tree_util.tree_leaves(tree):
        arrays.extend(a for a in device_arrays(leaf)
                      if isinstance(a, jax.Array))
    jax.block_until_ready(arrays)


def timeit(fn, *args, iters=3):
    """Mean seconds per call over ``iters`` back-to-back dispatches
    (pipelined — one fence at the end, matching how production streams
    work onto the chip)."""
    fence(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / iters
