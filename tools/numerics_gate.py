#!/usr/bin/env python3
"""Dynamic numerics gate: an injected NaN must trip; a clean fit must not.
A CPU gate: it pins ``JAX_PLATFORMS=cpu`` whatever the caller exported.

The numerics plane (``observability/numerics.py``) promises that a NaN
born in chunk k of a streamed fit raises :class:`NumericsError` naming
the chunk and stream — with a post-mortem carrying the recent health
series — instead of surfacing as garbage weights at finalize. This tool
pins that promise at the CI level against the real streamed path, both
directions:

* **clean leg** — the recompile-gate smoke fit runs with numerics ON:
  it must complete, health words must have been pulled
  (``numerics.health_words`` > 0 — the plane actually ran, it was not
  silently disabled), and NO post-mortem may be written.
* **poisoned leg** — the same fit with one ``kind="corrupt"`` fault
  injected at the ``ingest.stage`` site (``resilience/faults.py``:
  NaN into the first float element of one chunk's host data, the
  deterministic "NaN born in chunk k" failure). The fit must raise
  ``NumericsError`` naming BOTH the poisoned chunk index and the
  stream tag, and the attached post-mortem artifact must embed the
  health series with the poisoned chunk's non-finite count.

Run by ``bin/ci.sh`` next to the recompile gate; also standalone::

    python tools/numerics_gate.py
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

#: chunk index the fault plan poisons (0-based; `after=` skips visits)
POISON_CHUNK = 2


def _smoke_fit(tag):
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )

    rng = np.random.RandomState(0)
    n, d, chunk = 1024, 64, 64  # 16 chunks: the deferred-D2H window
    X = rng.rand(n, d).astype(np.float32)
    y = rng.randint(0, 10, n)
    labels = (-np.ones((n, 10)) + 2.0 * np.eye(10)[y]).astype(np.float32)

    def featurize(ad):
        return ad.map_batch(lambda x: jnp.tanh(x))

    stream = StreamingDataset.from_numpy(
        X, chunk_size=chunk, tag=tag).map_chunks(featurize)
    return fit_streaming(LinearMapEstimator(lam=0.1), stream, labels)


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("KEYSTONE_NUMERICS", None)  # the plane must be ON
    # isolate the gate's post-mortems so the clean-leg "no artifact"
    # assertion cannot be confused by a developer's real dumps
    pm_dir = tempfile.mkdtemp(prefix="keystone-numerics-gate-")
    os.environ["KEYSTONE_POSTMORTEM_DIR"] = pm_dir

    from keystone_tpu.observability import MetricsRegistry
    from keystone_tpu.observability.numerics import NumericsError
    from keystone_tpu.resilience.faults import FaultPlan

    reg = MetricsRegistry.get_or_create()

    # -- clean leg --------------------------------------------------------
    _smoke_fit("numerics-gate-clean")
    words = reg.counter("numerics.health_words").value
    dumped = os.listdir(pm_dir)
    print(f"numerics gate: clean fit OK ({words:g} health word(s) "
          f"pulled, {len(dumped)} post-mortem(s))")
    if not words:
        print("numerics gate FAILED: the clean fit pulled zero health "
              "words — the numerics plane did not run (disabled? the "
              "fit_streaming wiring regressed?)", file=sys.stderr)
        return 1
    if dumped:
        print(f"numerics gate FAILED: a CLEAN fit wrote post-mortem(s) "
              f"{dumped} — the tripwire fired on healthy data",
              file=sys.stderr)
        return 1

    # -- poisoned leg -----------------------------------------------------
    tag = "numerics-gate-poisoned"
    try:
        with FaultPlan(seed=7).add(
                "ingest.stage", kind="corrupt",
                after=POISON_CHUNK, count=1):
            _smoke_fit(tag)
    except NumericsError as exc:
        msg = str(exc)
        path = getattr(exc, "postmortem_path", None)
        ok = True
        if f"chunk {POISON_CHUNK}" not in msg or tag not in msg:
            print(f"numerics gate FAILED: tripwire fired but named "
                  f"neither chunk {POISON_CHUNK} nor stream {tag!r}: "
                  f"{msg}", file=sys.stderr)
            ok = False
        if path is None or not os.path.exists(path):
            print("numerics gate FAILED: tripwire fired without a "
                  "post-mortem artifact", file=sys.stderr)
            ok = False
        else:
            with open(path) as f:
                blob = json.load(f)
            series = (blob.get("context") or {}).get("recent_health") or []
            bad = [e for e in series
                   if e.get("chunk") == POISON_CHUNK
                   and (e.get("nan") or e.get("inf"))]
            if not bad:
                print("numerics gate FAILED: post-mortem health series "
                      f"does not show chunk {POISON_CHUNK} non-finite "
                      f"({len(series)} entries)", file=sys.stderr)
                ok = False
        if not ok:
            return 1
        print(f"numerics gate OK: injected NaN in chunk {POISON_CHUNK} "
              f"tripped NumericsError naming chunk+stream; post-mortem "
              f"at {path} carries the health series")
        return 0
    print("numerics gate FAILED: the poisoned fit completed without "
          "raising NumericsError — the tripwire is dead (the injected "
          "NaN would have reached the fitted weights)", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
