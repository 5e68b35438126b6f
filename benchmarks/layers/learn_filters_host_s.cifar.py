"""Host seconds per fit inside the program's ``featurize:learn_filters``
span: the seeded sample of 100,000 patches gathered on the device and
brought to the host, the 108 x 108 ZCA whitener, the filter rows drawn
and whitened; in every fit, before the solver has anything to do."""
from benchmarks.layers import _ring_spans


def read(run):
    # no such span in the ring (a program that learns elsewhere): no number
    return _ring_spans.per_fit(
        run, _ring_spans.seconds_of("featurize:learn_filters")) or None
