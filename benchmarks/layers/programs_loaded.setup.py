"""Compile records of set-up: observed calls that loaded at least one
program, compiled or read from the cache. The count in the driver's
set-up line."""
from benchmarks.layers import _setup_compiles


def read(run):
    mine = _setup_compiles.records(run)
    return None if mine is None else float(len(mine))
