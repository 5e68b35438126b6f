"""Seconds per fit the main thread spent inside the program's ``wait:*``
spans: stopped, waiting for the device's results. Every cell's reader:
the host's clock, ``_ring_spans``."""
from benchmarks.layers import _ring_spans


def read(run):
    return _ring_spans.per_fit(run, _ring_spans.seconds_of("wait:"))
