"""The program's own spans a fit, for a cell with few fits a window.

``_program_spans`` maps the ring onto the device trace's clock to split
the device's idle time, and wants ten whole fits for that. A cell whose
fit takes seconds completes four to seven, and its readers of HOST
seconds need no mapping: the program's ring and the harness's ``fit``
spans are both on ``perf_counter`` seconds. This sums a quantity over
the main thread's ring spans that start inside one of the window's fits
and divides by the fits. ``None`` where there is nothing sound to read:
no fits, a ring without the fit path's spans (a parent commit), or a
ring that has dropped part of the window.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional


def per_fit(run, quantity: Callable[[object], float]) -> Optional[float]:
    """Sum of ``quantity(span)`` (0 for spans it does not count) over
    the window's fits, per fit."""
    fits = sorted((s, e) for n, s, e in run.spans.records if n == "fit")
    if not fits:
        return None
    from keystone_tpu.observability.timeline import flight_recorder

    rec = flight_recorder()
    ring = [s for s in rec.spans()
            if s.ph == "X" and s.tid == threading.main_thread().ident]
    if not any(s.cat == "solve" for s in ring):
        return None
    if rec.dropped() and min(s.start_s for s in ring) > fits[0][0]:
        run.say("ring spans: the ring dropped part of the window: not read")
        return None
    total = 0.0
    for span in ring:
        if any(lo <= span.start_s < hi for lo, hi in fits):
            total += quantity(span)
    return total / len(fits)


def seconds_of(prefix: str) -> Callable[[object], float]:
    """Duration of the spans whose ``cat:name`` starts with ``prefix``."""
    def quantity(span) -> float:
        return (span.dur_s if f"{span.cat}:{span.name}".startswith(prefix)
                else 0.0)
    return quantity
