"""Device milliseconds per fit in dense SIFT: the chunk program
(``ops.sift._dsift_chunk``, whose ops stand under the scope
``dense_sift``), over every pass the fit makes over its images (the
PCA's sample, what the descriptor cache does not hold, the encodings)."""
from benchmarks.layers import _common

SIFT_PROGRAMS = ("jit__dsift_chunk",)


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(run, SIFT_PROGRAMS)
    return None if not fits or seconds is None else 1e3 * seconds / fits
