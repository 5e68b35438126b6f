"""The program's own spans, on the device trace's clock, and the split of
the device's idle time they give.

The program (``keystone_tpu.observability.timeline``) records a span at
each layer boundary of the fit path in every run, on ``perf_counter``
seconds, each with ``seq`` / ``parent`` / ``root``. The harness deletes
the profiler's files before readers run, so the spans are read from the
program's ring and put on the trace's clock through the harness's own
``fit`` spans, which exist on both: the offset is the median, over the
window's fits, of the difference of the two starts. Read once a run
(kept in ``run.facts``); the four readers that need the trace's clock
(``dispatch_host_s``, ``idle_host_busy_s``, ``idle_host_waiting_s``,
``span_coverage_pct``) go through :func:`read`; host seconds and bytes
that need no device time are read by ``_ring_spans``. ``None`` where
there is nothing sound to read: no trace, a program without linked
spans (a parent commit), anchors that spread by more than a millisecond,
or a ring that dropped all but a few fits.

The attribution rule is ``xplane.Trace.idle_gaps``: each idle instant of
the first chip goes to the innermost span open on the main thread. It
is called on one fit at a time (a fit and the pause after it), with that
fit's ops and spans, because it costs gaps x spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics
import threading
import time
from typing import Dict, List, Optional, Tuple

from benchmarks import xplane

#: Leaves of the fit path's span table (PERF.md section 3): idle seconds
#: whose innermost span is one of these count as covered.
LEAVES = ("dag:optimize", "dag:rules:", "dag:node:", "solve:fit:",
          "ingest:h2d", "ingest:reshard", "wait:", "eval:evaluate")
MAX_ANCHOR_SPREAD_NS = 1e6
MIN_FITS = 10
FACT = "program_spans"


@dataclasses.dataclass
class Split:
    fits: int                       # whole fits the numbers are over
    seconds: float                  # of the range they cover
    idle_by_span: Dict[str, float]  # idle seconds by innermost span
    idle_waiting_s: float           # idle seconds inside a wait:* span
    dispatch_self_s: float          # self seconds of dag:node and solve:fit
    anchor_spread_ns: float
    dropped: int

    @property
    def idle_s(self) -> float:
        return sum(self.idle_by_span.values())

    @property
    def covered_s(self) -> float:
        return sum(s for name, s in self.idle_by_span.items()
                   if name.startswith(LEAVES))

    def per_fit(self, seconds: float) -> float:
        return seconds / self.fits


def read(run) -> Optional[Split]:
    if FACT not in run.facts:
        run.facts[FACT] = _read(run)
    return run.facts[FACT]


def anchors(run) -> Optional[Tuple[float, float, List[Tuple[float, float]]]]:
    """``(offset_ns, spread_ns, fits on the trace's clock)`` from the
    harness's ``fit`` spans: trace nanoseconds = perf_counter seconds x
    1e9 + offset. The spread is the distance between the quartiles of
    the per-fit offsets."""
    host = sorted((s, e) for n, s, e in run.spans.records if n == "fit")
    traced = sorted((s, e) for n, s, e in run.trace_data.spans if n == "fit")
    if not host or len(host) != len(traced):
        return None
    offsets = [t[0] - h[0] * 1e9 for h, t in zip(host, traced)]
    spread = 0.0
    if len(offsets) > 1:
        q1, _, q3 = statistics.quantiles(offsets, n=4)
        spread = q3 - q1
    return statistics.median(offsets), spread, traced


def _read(run) -> Optional[Split]:
    if run.trace_data is None or not run.trace_data.devices:
        return None
    window = run.trace_data.window()
    found = anchors(run)
    if window is None or found is None:
        return None
    offset, spread, fits = found
    if spread > MAX_ANCHOR_SPREAD_NS:
        run.say(f"program spans: the anchors' offsets spread by "
                f"{spread / 1e3:.0f} us, over 1 ms: not mapped")
        return None
    from keystone_tpu.observability.timeline import flight_recorder

    t_start = time.perf_counter()
    rec = flight_recorder()
    ring = rec.spans()
    dropped = rec.dropped()
    lo = window[0]
    if dropped:
        # the ring is in order of recording: all that was recorded after
        # its oldest span ended is still there, so the fits that started
        # after that are whole
        cutoff = (ring[0].start_s + ring[0].dur_s) * 1e9 + offset
        fits = [f for f in fits if f[0] >= cutoff]
        lo = fits[0][0] if fits else lo
    if len(fits) < MIN_FITS:
        run.say(f"program spans: the ring dropped {dropped} spans and "
                f"holds {len(fits)} whole fits, under {MIN_FITS}: not read")
        return None
    hi = window[1]
    main = threading.main_thread().ident
    spans = []   # (name, start_ns, end_ns, span) of the main thread, in range
    for s in ring:
        if s.ph != "X" or s.tid != main or getattr(s, "seq", 0) == 0:
            continue
        start = s.start_s * 1e9 + offset
        end = start + s.dur_s * 1e9
        if start >= lo and end <= hi:
            spans.append((f"{s.cat}:{s.name}", start, end, s))
    if not any(name.startswith(LEAVES) for name, *_ in spans):
        return None   # a program without the fit path's spans
    spans.sort(key=lambda x: x[1])

    dev = run.trace_data.devices[0]
    ops = sorted(dev.ops or dev.modules, key=lambda o: o[1])
    op_starts = [o[1] for o in ops]
    span_starts = [x[1] for x in spans]
    cuts = [lo] + [f[0] for f in fits if f[0] > lo] + [hi]
    idle: Dict[str, float] = {}
    waiting = 0.0
    for a, b in zip(cuts, cuts[1:]):
        i = max(bisect.bisect_left(op_starts, a) - 1, 0)
        part = xplane.DeviceTrace(
            dev.device, [], ops[i:bisect.bisect_left(op_starts, b)])
        mine = spans[bisect.bisect_left(span_starts, a):
                     bisect.bisect_left(span_starts, b)]
        by_span = xplane.Trace([part], [x[:3] for x in mine]).idle_gaps(
            (a, b), top=len(mine) + 1)
        for name, seconds in by_span:
            idle[name] = idle.get(name, 0.0) + seconds
        waits = [("wait", s, e) for name, s, e, _ in mine
                 if name.startswith("wait:")]
        waiting += dict(xplane.Trace([part], waits).idle_gaps(
            (a, b), top=2)).get("wait", 0.0)

    children: Dict[int, float] = {}
    for _, _, _, s in spans:
        if s.parent is not None:
            children[s.parent] = children.get(s.parent, 0.0) + s.dur_s
    split = Split(
        fits=len(fits), seconds=(hi - lo) / 1e9, idle_by_span=idle,
        idle_waiting_s=waiting,
        dispatch_self_s=sum(
            s.dur_s - children.get(s.seq, 0.0) for name, _, _, s in spans
            if name.startswith(("dag:node:", "solve:fit:"))),
        anchor_spread_ns=spread, dropped=dropped)
    say_table(run, split, len(spans), time.perf_counter() - t_start)
    return split


def say_table(run, split: Split, spans: int, took_s: float) -> None:
    total = split.idle_s
    run.say(f"program spans: {spans} on the main thread over "
            f"{split.fits} whole fits ({split.seconds:.3f} s), the ring "
            f"dropped {split.dropped}, anchors' offsets spread by "
            f"{split.anchor_spread_ns / 1e3:.1f} us, read in {took_s:.1f} s")
    run.say(f"device idle {total:.4f} s = {split.idle_waiting_s:.4f} s with "
            f"the host inside wait:* + {total - split.idle_waiting_s:.4f} s "
            f"with the host busy; covered by the span table "
            f"{100 * split.covered_s / total if total else 0:.2f}%")
    run.say("idle seconds by innermost program span (seconds, ms a fit, "
            "share of idle):")
    top = sorted(split.idle_by_span.items(), key=lambda kv: -kv[1])[:12]
    for name, seconds in top:
        name = name if len(name) <= 48 else name[:45] + "..."  # fused labels
        run.say(f"  {name:<48} {seconds:9.4f} "
                f"{1e3 * split.per_fit(seconds):9.3f} "
                f"{100 * seconds / total:6.2f}%")
