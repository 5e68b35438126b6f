"""Device idle seconds per fit at instants when the main thread was in
no ``wait:*`` span: the host was doing something else while the device
had nothing to do, which is the most a faster host could win back."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    if split is None:
        return None
    return split.per_fit(split.idle_s - split.idle_waiting_s)
