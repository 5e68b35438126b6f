"""Device milliseconds per fit in the two programs of the streamed block
solve (the factor sweep and the epoch sweeps)."""
from benchmarks.layers import _common

STREAM_SOLVE_PROGRAMS = ("jit__stream_factor", "jit__stream_epochs")


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(run, STREAM_SOLVE_PROGRAMS)
    return None if not fits or seconds is None else 1e3 * seconds / fits
