"""Megabytes per fit put on the device from host arrays: the ``nbytes``
of the program's ``ingest:h2d`` spans inside the window's fits, the site
that raises the counter ``ingest.h2d_bytes`` (same site, same number).
What rides to the device as a program's argument (a branch's ``W`` and
``b``, learned filters) is not counted. As long as the ring has dropped
nothing the counter's total and the spans' must agree, or nothing is
reported. Every cell's reader: the host's clock, ``_ring_spans``."""
from benchmarks.layers import _ring_spans


def read(run):
    def nbytes(span):
        if (span.cat, span.name) != ("ingest", "h2d"):
            return 0.0
        return float((span.args or {}).get("nbytes", 0))

    total = _ring_spans.per_fit(run, nbytes)
    if not total:
        return None
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.observability.timeline import flight_recorder

    rec = flight_recorder()
    if not rec.dropped():
        counted = MetricsRegistry.get_or_create().counter(
            "ingest.h2d_bytes").value
        spanned = sum((s.args or {}).get("nbytes", 0) for s in rec.spans()
                      if (s.cat, s.name) == ("ingest", "h2d"))
        if counted != spanned:
            run.say(f"h2d_mb: the counter holds {counted:.0f} bytes, the "
                    f"spans {spanned}: not reported")
            return None
    return total / 1e6
