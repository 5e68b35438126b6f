"""Share of the device's idle seconds whose innermost program span is a
leaf of the fit path's span table (PERF.md section 3). The rest fell
where no program span was open: app code between program calls, and the
harness's loop between two fits."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    if split is None or not split.idle_s:
        return None
    return 100.0 * split.covered_s / split.idle_s
