"""Chunks that a streamed fit's rows were taken in, per fit: by how much
the program's counter ``solve.stream.row_chunks`` rose in each of the
window's fits, as the configuration's job read it around every fit (1
where a block of all rows fits the device; here a block of 500,000 x
4,096 floats is held once and its sums run over the chunks). Nothing is
read where the job counted none: a program without the counter."""
from benchmarks.harness import load_module


def read(run):
    fits = run.facts.get("fits")
    counts = getattr(load_module("configs", run.cell["config"]),
                     "FIT_COUNTS", None)
    if not fits or not counts or len(counts) < fits:
        return None
    chunks = [c.get("row_chunks") for c in counts[-fits:]]
    return None if not all(chunks) else sum(chunks) / fits
