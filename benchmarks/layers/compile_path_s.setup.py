"""Seconds of set-up inside jax's compile path: tracing, lowering, and
the backend call, which with a warm persistent cache is the READ of each
program and with a cold one its compilation. The sum of ``wall_s`` over
set-up's compile records: what the driver's set-up line calls
"compiles or cache reads taking"."""
from benchmarks.layers import _setup_compiles


def read(run):
    return _setup_compiles.total(run, "wall_s")
