"""Host seconds per fit inside the program's ``dag:optimize`` spans: the
optimizer's whole cost, every executor of the fit (where a gather is too
wide to hold, the choice to hand its branches to the solver is made
there). Every cell's reader: the host's clock, ``_ring_spans``."""
from benchmarks.layers import _ring_spans


def read(run):
    return _ring_spans.per_fit(run, _ring_spans.seconds_of("dag:optimize"))
