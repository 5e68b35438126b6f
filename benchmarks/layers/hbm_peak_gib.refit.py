"""Peak bytes in use on the fullest device when the window closed."""
from benchmarks.layers import _common


def read(run):
    return _common.hbm_peak_gib(run)
