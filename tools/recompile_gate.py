#!/usr/bin/env python3
"""Dynamic recompile gate: a second epoch must compile NOTHING.
A CPU gate: it pins ``JAX_PLATFORMS=cpu`` whatever the caller exported.

PR 3's streaming design guarantees every chunk of a stream shares one
padded shape, so the per-chunk programs (wire cast, transform chain,
accumulate) compile exactly once — the "second epoch compiles nothing"
invariant, pinned by a tier-1 test since PR 3 and by the compile
observatory's per-fit warmup fence since PR 9. This tool pins it at the
CI level against the REAL streamed CIFAR-shaped path: it runs a smoke
streamed fit twice (fresh ``StreamingDataset`` each epoch, as a refit
on new data makes one) with the SECOND epoch wrapped in
``expect_no_compiles``, and fails (exit 1) if ``compile.unexpected_total``
grew — naming each offending jit site and the signature delta that
triggered it, which is precisely the evidence a regressed jit memo
(per-instance cache, unstable cache tag, mesh-baked closure) leaves.

Run by ``bin/ci.sh`` between the static layers and tier-1 pytest; also
usable standalone::

    python tools/recompile_gate.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.observability import (
        compile_observatory,
        expect_no_compiles,
    )
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )

    rng = np.random.RandomState(0)
    # CIFAR-shaped smoke: uint8 chunks on the wire, f32 compute, a
    # per-chunk featurize in the transform chain — the full streamed
    # program surface (cast + map_chunks + accumulate) in miniature
    n, side, chunk = 256, 8, 64
    imgs = (rng.rand(n, side * side * 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, n)
    labels = (-np.ones((n, 10)) + 2.0 * np.eye(10)[y]).astype(np.float32)

    def featurize(ad):
        return ad.map_batch(lambda x: jnp.tanh(
            x.astype(jnp.float32) / 255.0))

    def epoch():
        stream = StreamingDataset.from_numpy(
            imgs, chunk_size=chunk, wire_dtype=np.uint8,
            tag="recompile-gate").map_chunks(featurize)
        return fit_streaming(LinearMapEstimator(lam=0.1), stream, labels)

    obs = compile_observatory()
    epoch()  # epoch 1: every per-chunk program compiles once, here
    before = obs.unexpected_total()
    first_epoch_compiles = obs.count_total()
    with expect_no_compiles("recompile-gate:second-epoch"):
        epoch()  # epoch 2: steady state — must compile NOTHING
    unexpected = obs.unexpected_total() - before
    print(f"recompile gate: epoch 1 compiled {first_epoch_compiles} "
          f"program(s); epoch 2 unexpected recompiles: {unexpected}")
    if unexpected:
        for rec in obs.unexpected_records():
            print(f"  UNEXPECTED {rec.get('name')} "
                  f"({rec.get('trigger')}, {rec.get('wall_s', 0.0):.3f}s)"
                  + (f": {rec['delta']}" if rec.get("delta") else ""),
                  file=sys.stderr)
        print("recompile gate FAILED: the second epoch of a fixed-shape "
              "streamed fit recompiled — a jit memo regressed "
              "(per-instance cache / unstable tag / mesh-baked closure); "
              "the deltas above name the drifted signatures",
              file=sys.stderr)
        return 1
    print("recompile gate OK: second epoch compiled nothing")
    return 0


if __name__ == "__main__":
    sys.exit(main())
