"""Seconds per fit the main thread spent inside the program's ``wait:*``
spans: stopped, waiting for the device's results."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    return None if split is None else split.per_fit(split.host_wait_s)
