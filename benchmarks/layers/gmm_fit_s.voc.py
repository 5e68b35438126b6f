"""Host seconds per fit inside the program's ``featurize:fit_gmm`` span:
the mixture's initialisation and EM as one device program, and the one
read of its result; the host waits here for everything dispatched
before it."""
from benchmarks.layers import _ring_spans


def read(run):
    return _ring_spans.per_fit(
        run, _ring_spans.seconds_of("featurize:fit_gmm")) or None
