"""Passes of dense SIFT per image and fit: by how much the program's
counter ``featurize.sift.images`` rose in each of the window's fits, as
the configuration's job read it around every fit, over the fit's images.
1 is the least (every image once); a training image is passed up to
three times where the descriptor cache does not hold it."""
from benchmarks.harness import load_module


def read(run):
    fits, items = run.facts.get("fits"), run.facts.get("items")
    counts = getattr(load_module("configs", run.cell["config"]),
                     "FIT_COUNTS", None)
    if not fits or not items or not counts or len(counts) < fits:
        return None
    return sum(c["sift_images"] for c in counts[-fits:]) / (fits * items)
