"""Feature blocks made per fit: by how much the program's counter
``solve.stream.blocks_generated`` rose in each of the window's fits, as
the configuration's job read it around every fit (blocks x epochs
for the solve since PR 34, whose factor sweep is the first epoch; blocks
x (1 + epochs) in a program that sweeps once more for the factors; and
blocks more for the blockwise test apply; the
configuration's ``real_fit.blocks_generated_min`` / ``_max`` hold the
two ends)."""
from benchmarks.harness import load_module


def read(run):
    fits = run.facts.get("fits")
    counts = getattr(load_module("configs", run.cell["config"]),
                     "FIT_COUNTS", None)
    if not fits or not counts or len(counts) < fits:
        return None
    return sum(c["blocks_generated"] for c in counts[-fits:]) / fits
