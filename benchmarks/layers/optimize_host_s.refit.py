"""Host seconds per fit inside the program's ``dag:optimize`` spans: the
optimizer's whole cost, every executor of the fit."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    return None if split is None else split.per_fit(split.optimize_s)
