"""Feature blocks made per fit: by how much the program's counter
``solve.stream.blocks_generated`` rose in each of the window's fits, as
the configuration's job read it around every fit (blocks x (1 + epochs)
for the solve and blocks more for each blockwise apply: the training
error's and the test error's)."""
from benchmarks.harness import load_module


def read(run):
    fits = run.facts.get("fits")
    counts = getattr(load_module("configs", run.cell["config"]),
                     "FIT_COUNTS", None)
    if not fits or not counts or len(counts) < fits:
        return None
    return sum(c["blocks_generated"] for c in counts[-fits:]) / fits
