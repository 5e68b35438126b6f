"""Host seconds per fit inside the program's ``featurize:draw`` spans:
the branches' ``W`` and ``b`` drawn from the seed when the pipeline is
built, in every fit, before the device has anything of that fit to do."""
from benchmarks.layers import _ring_spans


def read(run):
    # no such span in the ring (a program that draws elsewhere): no number
    return _ring_spans.per_fit(
        run, _ring_spans.seconds_of("featurize:draw")) or None
