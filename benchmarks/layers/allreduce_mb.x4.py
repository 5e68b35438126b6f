"""Megabytes a fit that the block solve hands to the reduction between
the data shards, by shapes: by how much the program's counter
``solve.allreduce_bytes`` rose in each of the window's fits, as the
configuration's job read it around every fit. Per block the
upper-triangle tiles of its Gram (what ``ops.linalg.gram`` sums before
it mirrors them: ten tiles of 512 x 512 at 2,048 columns) and its cross
product, and once a fit the column means of the design matrix and of the
labels. What a chip sends and receives for it depends on the
collective's algorithm and is not in the count. None on one shard,
where nothing is reduced."""
from benchmarks.harness import load_module


def read(run):
    fits = run.facts.get("fits")
    counts = getattr(load_module("configs", run.cell["config"]),
                     "FIT_COUNTS", None)
    if not fits or not counts or len(counts) < fits:
        return None
    total = sum(c.get("allreduce_bytes", 0.0) for c in counts[-fits:])
    return total / fits / 1e6 if total else None
