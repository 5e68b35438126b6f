"""Megabytes per fit put on the device from host arrays: the ``nbytes``
of the program's ``ingest:h2d`` spans inside the window's fits (the site
that raises the counter ``ingest.h2d_bytes``). The branches' ``W`` and
``b`` go to the device as program arguments and are not counted here."""
from benchmarks.layers import _ring_spans


def read(run):
    def nbytes(span):
        if (span.cat, span.name) != ("ingest", "h2d"):
            return 0.0
        return float((span.args or {}).get("nbytes", 0))

    total = _ring_spans.per_fit(run, nbytes)
    return None if not total else total / 1e6
