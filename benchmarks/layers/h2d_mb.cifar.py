"""Megabytes per fit of ``cifar_refit`` put on the device from host
arrays (``ingest:h2d`` spans): the images and labels; filters, whitener
and scaler statistics ride as program arguments and are not counted.
The reader is ``h2d_mb.timit``'s (``_ring_spans``: the cell completes three
fits a window, where ``h2d_mb.refit`` wants ten)."""
from benchmarks.harness import load_module

read = load_module("layers", "h2d_mb.timit").read
