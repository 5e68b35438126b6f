"""Device milliseconds per fit in the block solve's program."""
from benchmarks.layers import _common

SOLVE_PROGRAMS = ("jit__block_solve",)


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(run, SOLVE_PROGRAMS)
    return None if not fits or seconds is None else 1e3 * seconds / fits
