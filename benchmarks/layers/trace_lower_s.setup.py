"""Seconds of set-up in which jax traced the program's Python to jaxprs
and lowered them to MLIR: the host's own work, which no cache saves.
The sum of ``trace_s + lower_s`` over set-up's compile records."""
from benchmarks.layers import _setup_compiles


def read(run):
    return _setup_compiles.total(run, "trace_s", "lower_s")
