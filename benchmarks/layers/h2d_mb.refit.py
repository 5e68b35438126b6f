"""Megabytes per fit put on the device from host rows: what the
program's ``ingest.h2d_bytes`` counter counted for the window's fits,
read per call from the ``ingest:h2d`` spans (same site, same number). As
long as the ring has dropped nothing the counter's total and the spans'
must agree, or nothing is reported."""
from benchmarks.layers import _program_spans


def read(run):
    split = _program_spans.read(run)
    if split is None or not split.h2d_bytes:
        return None
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.observability.timeline import flight_recorder

    if not split.dropped:
        counted = MetricsRegistry.get_or_create().counter(
            "ingest.h2d_bytes").value
        spanned = sum((s.args or {}).get("nbytes", 0)
                      for s in flight_recorder().spans()
                      if (s.cat, s.name) == ("ingest", "h2d"))
        if counted != spanned:
            run.say(f"h2d_mb: the counter holds {counted:.0f} bytes, the "
                    f"spans {spanned}: not reported")
            return None
    return split.h2d_bytes / split.fits / 1e6
