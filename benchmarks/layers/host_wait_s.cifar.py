"""Seconds per fit of ``cifar_refit`` the main thread spent inside the
program's ``wait:*`` spans: stopped, waiting for the device's results.
The reader is ``host_wait_s.timit``'s (``_ring_spans``: the cell completes three
fits a window, where ``host_wait_s.refit`` wants ten)."""
from benchmarks.harness import load_module

read = load_module("layers", "host_wait_s.timit").read
