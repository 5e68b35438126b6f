"""The harness under the manifest a ``benchmark`` PR would leave: what
``BENCHMARK.json`` has, then the per-layer entries that wait in
``benchmarks/unlisted_per_layer*.json`` appended at the end of
``per_layer`` (those files say why they wait there).

    python3 -m benchmarks.unlisted --workload cifar_refit --seed <n> --seconds 40 --trace 1

takes ``benchmarks.run``'s arguments and is ``benchmarks.run`` in every
other respect; no file is written. ``BENCHMARK.json`` itself, and so
what the driver measures, is as it was.
"""
from __future__ import annotations

import glob
import os
import sys
from typing import Any, Dict

from benchmarks.harness import HERE, ROOT, load_json

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def merged() -> Dict[str, Any]:
    """``BENCHMARK.json`` with every waiting per-layer entry appended."""
    manifest = load_json(MANIFEST)
    # the oldest file first: ``unlisted_per_layer.json``, then the named
    for path in sorted(glob.glob(os.path.join(HERE, "unlisted_per_layer*.json")),
                       key=lambda p: (p.count("."), p)):
        manifest["per_layer"] += load_json(path)["per_layer"]
    return manifest


def main(argv=None) -> int:
    import benchmarks.run as harness

    def load(path):
        same = os.path.abspath(path) == os.path.abspath(MANIFEST)
        return merged() if same else load_json(path)

    harness.load_json = load
    return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
