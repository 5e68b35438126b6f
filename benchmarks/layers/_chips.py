"""A cell's peak where its programs span several chips: every chip's
peak rate, ``chips`` times (the cell's ``chips`` in ``BENCHMARK.json``).
The counts (``benchmarks/counts``) are of the whole fit's work and the
device time is the first chip's, which runs a ``1 / chips`` share of
every product beside the others, so a share of a roofline over several
chips divides by this and not by ``run.peaks``: the one-chip reader is
called as it stands, on a run that carries the cell's peak."""
import dataclasses

from benchmarks.layers import _common

RATES = ("bf16_flops_per_s", "hbm_bytes_per_s")


def read_with_cell_peak(run, reader: str):
    """What the accepted one-chip ``reader`` reads of ``run`` when the
    peak is that of all the cell's chips; None where it has no peak."""
    if run.peaks is None:
        return None
    chips = int(run.cell.get("chips", 1))
    peaks = {**run.peaks, **{k: run.peaks[k] * chips for k in RATES}}
    return _common.load_reader(reader).read(
        dataclasses.replace(run, peaks=peaks))
