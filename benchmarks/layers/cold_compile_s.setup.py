"""Backend seconds a process with an EMPTY compile cache pays for the
programs this set-up loaded: the sum of ``cold_s`` over set-up's compile
records. For a program read from the cache that is the compile time jax
stored beside it (whole seconds: a program that compiled in under one
reads 0); for one compiled in this run, its backend seconds. A warm
run's reading of what a cold ``setup_s`` has on top."""
from benchmarks.layers import _setup_compiles


def read(run):
    return _setup_compiles.total(run, "cold_s")
