"""Device milliseconds per fit in the Fisher-vector encoder: the chunk
program (``nodes.images.fisher_vector._fisher_vector_chunk``, scope
``fisher_vector``): posteriors and two moments of every training and
test image, once."""
from benchmarks.layers import _common

FV_PROGRAMS = ("jit__fisher_vector_chunk",)


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(run, FV_PROGRAMS)
    return None if not fits or seconds is None else 1e3 * seconds / fits
