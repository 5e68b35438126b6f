"""Device milliseconds per fit in the two programs of the streamed block
solve (the factor sweep and the epoch sweep) OUTSIDE the block maker:
centring and standardising a block, its Gram, the Cholesky factor, the
epoch step. (``stream_solve_dev_ms.timit`` counts the maker in: a cosine
block's ops have no name of their own in a trace.)"""
from benchmarks.layers import _common, _maker_loops

STREAM_SOLVE_PROGRAMS = ("jit__stream_factor", "jit__stream_epochs")


def solve_seconds(run):
    whole = _common.program_seconds(run, STREAM_SOLVE_PROGRAMS)
    maker = _maker_loops.maker_seconds(run, STREAM_SOLVE_PROGRAMS)
    return None if whole is None or maker is None else whole - maker


def read(run):
    fits = run.facts.get("fits")
    seconds = solve_seconds(run)
    return None if not fits or seconds is None else 1e3 * seconds / fits
