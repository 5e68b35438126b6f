"""Host seconds per fit inside the program's ``eval:vote`` span: the
100,000 test crops' scores grouped by the image they are crops of, a
group's ten averaged, the arg-max and the confusion matrix; after the
host has waited for the scores (``wait:d2h``, which
``host_wait_s.refit`` reads), so with the device idle."""
from benchmarks.layers import _ring_spans


def read(run):
    # no such span in the ring (another app, a parent commit): no number
    return _ring_spans.per_fit(
        run, _ring_spans.seconds_of("eval:vote")) or None
