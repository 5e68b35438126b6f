"""Device idle seconds per fit at instants when the main thread was
inside a ``wait:*`` span: the host had dispatched everything and was
already blocked on the device, so no host change removes this idle time
(a transfer in flight, launch latency)."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    return None if split is None else split.per_fit(split.idle_waiting_s)
