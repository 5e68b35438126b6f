"""Share of the window in which no op ran on the device."""
from benchmarks.layers import _common


def read(run):
    return _common.idle_pct(run)
