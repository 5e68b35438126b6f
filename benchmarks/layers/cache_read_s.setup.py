"""Seconds of set-up reading programs from jax's persistent compilation
cache (file read, decompression, load onto the device): the sum of
``cache_read_s`` over set-up's compile records. On a warmed machine
nearly all of ``compile_path_s.setup``; 0 in a process that compiled
everything."""
from benchmarks.layers import _setup_compiles


def read(run):
    return _setup_compiles.total(run, "cache_read_s")
