"""Host seconds per fit inside the program's ``dag:optimize`` spans: the
optimizer's whole cost, every executor of the fit (the choice between
materialising the gather and handing its branches to the solver is made
there)."""
from benchmarks.layers import _ring_spans


def read(run):
    return _ring_spans.per_fit(run, _ring_spans.seconds_of("dag:optimize"))
