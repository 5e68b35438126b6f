"""The program's own spans a fit, on the host's clock.

The program's ring and the harness's ``fit`` spans are both on
``perf_counter`` seconds, so a reader of HOST seconds or bytes needs no
mapping onto the device trace's clock and no trace (``_program_spans``
has both, to split the device's idle time, and wants ten whole fits for
it). This sums a quantity over the main thread's ring spans that start
inside one of the window's fits and divides by the fits: every cell's
reader of such a quantity, whether a window is 5 fits or 180.

The ring holds 8,192 spans. Where it has dropped some (a window of 180
fits), the fits read are the whole ones it still holds: it is in order
of recording, so all that was recorded after its oldest span ended is
there, and the fits that start after that are whole. ``None`` where
there is nothing sound to read: no fits, a ring without the fit path's
spans (a parent commit), or a ring that holds no whole fit.
"""
from __future__ import annotations

import bisect
import threading
from typing import Callable, Optional


def per_fit(run, quantity: Callable[[object], float]) -> Optional[float]:
    """Sum of ``quantity(span)`` (0 for spans it does not count) over
    the window's fits that the ring holds whole, per fit."""
    fits = sorted((s, e) for n, s, e in run.spans.records if n == "fit")
    if not fits:
        return None
    from keystone_tpu.observability.timeline import flight_recorder

    rec = flight_recorder()
    ring = rec.spans()
    dropped = rec.dropped()
    if dropped:
        cutoff = ring[0].start_s + ring[0].dur_s
        fits = [f for f in fits if f[0] >= cutoff]
        if not fits:
            run.say(f"ring spans: the ring dropped {dropped} spans and "
                    "holds no whole fit of the window: not read")
            return None
    main = [s for s in ring
            if s.ph == "X" and s.tid == threading.main_thread().ident]
    if not any(s.cat == "solve" for s in main):
        return None
    starts = [f[0] for f in fits]
    total = 0.0
    for span in main:
        i = bisect.bisect_right(starts, span.start_s) - 1
        if i >= 0 and span.start_s < fits[i][1]:
            total += quantity(span)
    return total / len(fits)


def seconds_of(prefix: str) -> Callable[[object], float]:
    """Duration of the spans whose ``cat:name`` starts with ``prefix``."""
    def quantity(span) -> float:
        return (span.dur_s if f"{span.cat}:{span.name}".startswith(prefix)
                else 0.0)
    return quantity
