"""Host seconds per fit of the program's ``dag:node:*`` and
``solve:fit:*`` spans themselves: each span's duration less its
children's (nested nodes, the optimizer, host-to-device, ``wait:*``), so
what is left is the host dispatching the node's own work."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    return None if split is None else split.per_fit(split.dispatch_self_s)
