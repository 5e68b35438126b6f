"""Flight recorder: a bounded, always-on ring buffer of execution spans.

The :class:`~.trace.PipelineTrace` answers "how long did each node
take"; this module answers "WHEN did everything run, on WHICH thread" —
the Dapper/Perfetto-shaped view that makes prefetch-vs-compute overlap
and lock contention visually inspectable instead of argued from
aggregate counters. Every instrumented subsystem feeds it through the
funnels that already exist:

* the fit path's layer boundaries, in EVERY run (no trace needed, and
  none of them blocks on the device): ``dag:optimize`` with one
  ``dag:rules:<batch>`` child per optimizer batch, one
  ``dag:node:<label>#<id>`` per lazily computed node that is forced
  (``workflow/executor.py``), ``solve:fit:<Estimator>``,
  ``ingest:h2d`` (host rows put on the device), ``wait:d2h`` (the host
  stops and waits for device results) and ``eval:evaluate``;
* the streaming prefetcher: one ``stage:<tag>`` span per chunk on the
  producer thread (decode + pad + H2D staging) and one ``stall:<tag>``
  span per chunk on the consumer (time the device-side loop waited);
* per-shard H2D puts on the ``keystone-h2d`` pool lanes
  (``parallel/mesh.shard_put``);
* the resilience event funnel (``resilience/events.py``) as instant
  events: retries, watchdog trips, checkpoint snapshots, quarantines;
* contended :class:`~keystone_tpu.utils.guarded.TracedLock` acquires
  (one span per lost race, on the losing thread);
* ``fit_streaming``'s per-chunk ``accumulate`` spans (the compute lane
  of a streamed fit).

Every span carries ``seq`` (a process-wide number), ``parent`` (the
``seq`` of the span that was open on the same thread when it started)
and ``root`` (the ``seq`` of the outermost open span), so a span's self
time is its duration minus its children's, with no interval arithmetic.
Two clocks: ring times are ``time.perf_counter`` seconds, and
``flight_span`` also enters ``jax.profiler.TraceAnnotation(
"ks:<cat>:<name>")``, so any profiler capture (``xprof_trace``, an
operator's own ``jax.profiler.start_trace``) holds the same spans on
the device trace's clock. With no profiler session that is a flag test.

The buffer is a fixed-capacity ring (``KEYSTONE_FLIGHT_SPANS``, default
8192): recording is a lock + two list writes (~1 µs), old spans fall
off the back, and a long-lived process can never grow it. What a
process does once and a reader asks for hours later is PINNED instead:
``record(..., pin=True)`` also keeps the span in ``FlightRecorder.
pinned``, a dict by ``cat:name`` beside the ring (one span a name, the
newest), which no later span pushes out. The one pinned span today is
``startup:import``, the package's own import
(``keystone_tpu/__init__.py``, first statement to last). A crash
post-mortem (:mod:`.postmortem`) or an interpreter exit under an active
stream dumps whatever the ring holds — the last N seconds of evidence,
exactly when it matters.

``to_chrome_trace()`` exports the ring as Chrome trace-event JSON
(``chrome://tracing`` / https://ui.perfetto.dev -> Open trace file):
one lane per real thread, with overlapping spans on a thread (nested
executor nodes) overflowing to ``<thread> (nested k)`` sub-lanes so
every exported lane holds strictly non-overlapping ``ts``/``dur``
ranges. ``--trace-out something.perfetto.json`` on
``python -m keystone_tpu <app>`` writes it directly.

Thread model: the ring is mutated from every instrumented thread and
its guard is a PLAIN ``threading.Lock``, never a TracedLock — a
contended TracedLock acquire reports INTO this recorder, so tracing the
recorder's own lock would re-enter it on the same thread and deadlock
(the same boundary as ``observability/metrics.py``, documented once in
``utils/guarded.py``). ``KEYSTONE_FLIGHT_RECORDER=0`` disables
recording entirely (one branch per call — the telemetry-off side of the
PERFORMANCE.md rule 10 overhead bar).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional

from ..utils.guarded import guarded_by


class Span(NamedTuple):
    """One recorded interval (or instant, when ``ph == "i"``). Times
    are ``time.perf_counter`` seconds (monotonic, process-local)."""

    name: str
    cat: str
    start_s: float
    dur_s: float
    tid: int
    thread: str
    args: Optional[Dict[str, Any]]
    ph: str  # "X" complete event, "i" instant
    seq: int = 0                   # process-wide number
    parent: Optional[int] = None   # seq of the enclosing span, same thread
    root: Optional[int] = None     # seq of the outermost open span


#: process-wide span numbers (``next`` on a count is atomic under the GIL)
_SEQ = itertools.count(1)


class _OpenSpan:
    """The context manager behind :meth:`FlightRecorder.span`: pushes
    itself on the thread's stack, enters the profiler annotation, and
    records on exit (also when the block raises). ``with`` yields the
    span's ``args`` dict, so a site can add what it learns inside the
    block (node counts after optimizing, rows evaluated)."""

    __slots__ = ("rec", "name", "cat", "args", "seq", "parent", "root",
                 "t0", "ann")

    def __init__(self, rec: "FlightRecorder", name: str, cat: str,
                 args: Dict[str, Any]):
        self.rec, self.name, self.cat, self.args = rec, name, cat, args

    def __enter__(self) -> Dict[str, Any]:
        rec = self.rec
        self.ann = (rec._annotation or rec._load_annotation())(
            f"ks:{self.cat}:{self.name}")
        stack = rec._stack()
        self.seq = next(_SEQ)
        self.parent = stack[-1].seq if stack else None
        self.root = stack[0].seq if stack else self.seq
        stack.append(self)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self.args

    def __exit__(self, *exc) -> bool:
        dur_s = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.rec._stack().pop()
        self.rec.record(self.name, self.cat, self.t0, dur_s,
                        self.args or None,
                        link=(self.seq, self.parent, self.root))
        return False


class _NoSpan:
    """What a disabled recorder hands out: nothing is pushed, entered or
    recorded; the yielded dict is thrown away."""

    def __enter__(self) -> Dict[str, Any]:
        return {}

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


def _env_flag(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default) != "0"


def _env_capacity() -> int:
    raw = os.environ.get("KEYSTONE_FLIGHT_SPANS")
    if not raw:
        return 8192
    try:
        cap = int(raw)
    except ValueError:
        raise ValueError(
            f"KEYSTONE_FLIGHT_SPANS must be an integer, got {raw!r}"
        ) from None
    if cap < 1:
        raise ValueError("KEYSTONE_FLIGHT_SPANS must be >= 1")
    return cap


@guarded_by("_lock", "_ring", "_idx", "_total", "_pinned")
class FlightRecorder:
    """Bounded ring buffer of :class:`Span` entries; see module
    docstring. ``record``/``record_instant`` are called from every
    instrumented thread — the ring index bump is a read-modify-write
    and wraparound writes land in shared slots, so both run under the
    (plain) lock; the regression schedule for the unlocked shape lives
    in tests/test_concurrency_sched.py."""

    def __init__(self, capacity: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self.capacity = _env_capacity() if capacity is None else int(capacity)
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.enabled = (_env_flag("KEYSTONE_FLIGHT_RECORDER")
                        if enabled is None else bool(enabled))
        self._ring: List[Optional[Span]] = [None] * self.capacity
        self._idx = 0
        self._total = 0
        self._pinned: Dict[str, Span] = {}  # cat:name -> span; see pinned()
        self._lock = threading.Lock()  # plain: TracedLock reports in here
        # span-materialization thunks queued by hot paths (the serving
        # worker); drained at the next view/export. deque append and
        # popleft are GIL-atomic, so no lock rides the fast path, and
        # maxlen bounds memory if no view ever runs.
        self._deferred: Deque[Any] = deque(maxlen=self.capacity)
        #: perf_counter epoch for chrome-trace timestamps
        self.t0_s = time.perf_counter()
        self._tls = threading.local()  # per-thread stack of open spans
        self._annotation = None  # jax.profiler.TraceAnnotation, on first span

    def _load_annotation(self):
        # jax stays a lazy import throughout the observability layer
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        return TraceAnnotation

    def _stack(self) -> List[_OpenSpan]:
        try:
            return self._tls.stack
        except AttributeError:
            stack = self._tls.stack = []
            return stack

    # -- recording ---------------------------------------------------------
    def record(self, name: str, cat: str, start_s: float, dur_s: float,
               args: Optional[Dict[str, Any]] = None, ph: str = "X",
               tid: Optional[int] = None,
               thread: Optional[str] = None,
               link: Optional[tuple] = None, pin: bool = False) -> None:
        """Append one span (cheap: thread lookup + lock + two writes);
        ``pin`` also keeps it beside the ring (:meth:`pinned`).
        ``tid``/``thread`` override the recording thread's identity —
        deferred materializers pass the identity captured at defer
        time so spans still land on their originating lane. ``link`` is
        ``(seq, parent, root)`` from a span that was open as a context;
        an after-the-fact record gets a new ``seq`` and, when it is made
        on its own thread, the open spans of that thread as ancestors."""
        if not self.enabled:
            return
        if link is None:
            seq = next(_SEQ)
            stack = self._stack() if tid is None and thread is None else ()
            link = ((seq, stack[-1].seq, stack[0].seq) if stack
                    else (seq, None, seq))
        if tid is None or thread is None:
            t = threading.current_thread()
            tid = t.ident or 0 if tid is None else tid
            thread = t.name if thread is None else thread
        span = Span(name, cat, float(start_s), float(dur_s),
                    tid, thread, args, ph, *link)
        with self._lock:
            self._ring[self._idx] = span
            self._idx = (self._idx + 1) % self.capacity
            self._total += 1
            if pin:
                self._pinned[f"{cat}:{name}"] = span

    def defer(self, materialize: Any) -> None:
        """Queue a zero-argument thunk that will ``record`` one or more
        spans when the recorder is next VIEWED (``spans``, export,
        counters) instead of now. This keeps span construction —
        f-strings, args dicts, the ring lock — off latency-critical
        paths: the serving worker queues one thunk per batch between a
        batch's futures resolving and its next ``take`` (the always-on
        <2% bar, PERFORMANCE.md rule 15). Thunks must capture immutable
        data (completed traces) and the originating thread identity."""
        if self.enabled:
            self._deferred.append(materialize)

    def flush(self) -> None:
        """Run queued materializers (oldest first). Every view calls
        this; the serving worker calls it when idle, the HTTP scrape
        surface before serializing, so deferred telemetry (spans AND
        the phase-histogram observes a thunk carries) is visible at
        every read point. Thunks call ``record``, so this never runs
        under the ring lock."""
        while True:
            try:
                fn = self._deferred.popleft()
            except IndexError:
                return
            fn()

    # internal alias so views read naturally
    _drain = flush

    def record_instant(self, name: str, cat: str,
                       ts_s: Optional[float] = None,
                       args: Optional[Dict[str, Any]] = None) -> None:
        """A zero-duration marker event (resilience events, faults)."""
        self.record(name, cat,
                    time.perf_counter() if ts_s is None else ts_s,
                    0.0, args, ph="i")

    def span(self, name: str, cat: str, **args: Any):
        """Record the enclosed block as one span (recorded even when the
        block raises — a crashing stage is exactly what a post-mortem
        needs to show), a child of the span open on this thread, and
        visible to any profiler capture as ``ks:<cat>:<name>``. Never
        blocks on the device. ``with ... as args`` gives the span's args
        dict, to be added to inside the block."""
        if not self.enabled:
            return _NO_SPAN
        return _OpenSpan(self, name, cat, args)

    # -- views -------------------------------------------------------------
    def spans(self) -> List[Span]:
        """Retained spans, oldest first (at most ``capacity``)."""
        self._drain()
        with self._lock:
            ring = list(self._ring)
            idx = self._idx
            total = self._total
        if total < self.capacity:
            return [s for s in ring[:idx] if s is not None]
        return [s for s in ring[idx:] + ring[:idx] if s is not None]

    def pinned(self) -> Dict[str, Span]:
        """The pinned spans by ``cat:name``: kept however many spans
        were recorded since, until :meth:`clear`."""
        with self._lock:
            return dict(self._pinned)

    @property
    def total_recorded(self) -> int:
        self._drain()
        with self._lock:
            return self._total

    def dropped(self) -> int:
        """Spans that fell off the back of the ring."""
        self._drain()
        with self._lock:
            return max(0, self._total - self.capacity)

    def clear(self) -> None:
        self._deferred.clear()
        with self._lock:
            self._ring = [None] * self.capacity
            self._idx = 0
            self._total = 0
            self._pinned = {}

    # -- export ------------------------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        """The ring as a Chrome trace-event / Perfetto JSON object.

        Lane assignment: one lane per recording thread, in first-seen
        order. Within a thread, spans are laid greedily onto sub-lanes
        so no exported lane ever holds two overlapping ``"X"`` events
        (nested executor node spans overflow onto ``<thread>
        (nested k)``) — the strictly-non-overlapping-per-lane invariant
        the round-trip test pins, and what keeps the Perfetto render
        unambiguous. Instants ride lane 0 of their thread.

        Flow links (PR 16): a span whose args carry ``flow_out`` (one
        id) emits a flow-start (``ph:"s"``) at its own ts/lane, and a
        span whose args carry ``flow_in`` (a list of ids) emits one
        enclosed flow-finish (``ph:"f"``, ``bp:"e"``) per id — Perfetto
        draws the arrows from each request span into the batch span
        that served it. Flow events anchor to existing lanes and never
        affect lane assignment."""
        spans = self.spans()
        held = {s.seq for s in spans}
        spans = [s for s in self.pinned().values()
                 if s.seq not in held] + spans
        events: List[Dict[str, Any]] = []
        # (os thread id, sublane) -> exported integer tid, plus names
        lane_ids: Dict[tuple, int] = {}
        lane_names: Dict[int, str] = {}

        def lane(tid: int, thread: str, sub: int) -> int:
            key = (tid, sub)
            if key not in lane_ids:
                lane_ids[key] = len(lane_ids) + 1
                lane_names[lane_ids[key]] = (
                    thread if sub == 0 else f"{thread} (nested {sub})")
            return lane_ids[key]

        by_thread: Dict[int, List[Span]] = {}
        for s in spans:
            by_thread.setdefault(s.tid, []).append(s)
        for tid in by_thread:
            # longer spans first at equal start so a nested child (same
            # start, shorter) overflows, not its parent
            complete = sorted(
                (s for s in by_thread[tid] if s.ph == "X"),
                key=lambda s: (s.start_s, -s.dur_s))
            lane_end: List[float] = []  # per sub-lane, last span end
            for s in complete:
                sub = 0
                while sub < len(lane_end) and s.start_s < lane_end[sub]:
                    sub += 1
                if sub == len(lane_end):
                    lane_end.append(0.0)
                lane_end[sub] = s.start_s + s.dur_s
                ts = round((s.start_s - self.t0_s) * 1e6, 3)
                lid = lane(s.tid, s.thread, sub)
                events.append({
                    "name": s.name, "cat": s.cat, "ph": "X",
                    "ts": ts, "dur": round(s.dur_s * 1e6, 3),
                    "pid": 1, "tid": lid,
                    "args": {**(s.args or {}),
                             "seq": s.seq, "parent": s.parent},
                })
                flow_args = s.args or {}
                if "flow_out" in flow_args:
                    events.append({
                        "name": "req", "cat": s.cat, "ph": "s",
                        "id": int(flow_args["flow_out"]),
                        "ts": ts, "pid": 1, "tid": lid,
                    })
                for fid in flow_args.get("flow_in", ()):
                    events.append({
                        "name": "req", "cat": s.cat, "ph": "f",
                        "bp": "e", "id": int(fid),
                        "ts": ts, "pid": 1, "tid": lid,
                    })
            for s in by_thread[tid]:
                if s.ph != "i":
                    continue
                events.append({
                    "name": s.name, "cat": s.cat, "ph": "i", "s": "t",
                    "ts": round((s.start_s - self.t0_s) * 1e6, 3),
                    "pid": 1, "tid": lane(s.tid, s.thread, 0),
                    "args": s.args or {},
                })
        meta = [{"name": "process_name", "ph": "M", "pid": 1,
                 "args": {"name": "keystone_tpu"}}]
        for lid, lname in sorted(lane_names.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": lid, "args": {"name": lname}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"dropped_spans": self.dropped(),
                              "recorded_spans": self.total_recorded}}

    def to_chrome_json(self) -> str:
        return json.dumps(self.to_chrome_trace(), default=str)

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_chrome_json())


# -- process-global recorder -------------------------------------------------

_RECORDER: Optional[FlightRecorder] = None
_RECORDER_LOCK = threading.Lock()


def flight_recorder() -> FlightRecorder:
    """The process-global recorder (lazily built; the create is
    double-checked — worker threads record from the first chunk)."""
    global _RECORDER
    rec = _RECORDER
    if rec is None:
        with _RECORDER_LOCK:
            rec = _RECORDER
            if rec is None:
                rec = _RECORDER = FlightRecorder()
    return rec


def reset_flight_recorder() -> None:
    """Drop the global recorder (tests; the next record builds a fresh
    one, re-reading the env knobs)."""
    global _RECORDER
    with _RECORDER_LOCK:
        _RECORDER = None


def record_span(name: str, cat: str, start_s: float, dur_s: float,
                args: Optional[Dict[str, Any]] = None) -> None:
    """Module-level convenience for instrumentation sites."""
    flight_recorder().record(name, cat, start_s, dur_s, args)


def record_instant(name: str, cat: str,
                   args: Optional[Dict[str, Any]] = None) -> None:
    flight_recorder().record_instant(name, cat, args=args)


def record_startup(t_import_s: float) -> None:
    """The pinned ``startup:import`` span: from ``t_import_s`` (the
    package's first statement, ``perf_counter`` seconds) to now, its
    last. What came before it in the process (the interpreter, ``import
    jax``, the device client) is the span's start minus the process's
    own start, which only the caller that started the process knows."""
    now = time.perf_counter()
    flight_recorder().record("import", "startup", t_import_s,
                             now - t_import_s, pin=True)


def flight_span(name: str, cat: str, **args: Any):
    """The one span primitive of the layer boundaries: see
    :meth:`FlightRecorder.span`."""
    return flight_recorder().span(name, cat, **args)


def write_trace_artifact(path: str, trace=None) -> str:
    """The ``--trace-out`` dispatch shared by the app CLI and bench:
    a path ending ``.perfetto.json`` gets the flight recorder's Chrome
    trace (open in https://ui.perfetto.dev); anything else gets the
    :class:`~.trace.PipelineTrace` JSON. Returns which kind was
    written (``"perfetto"`` / ``"trace"``)."""
    if str(path).endswith(".perfetto.json"):
        flight_recorder().dump(path)
        return "perfetto"
    if trace is None:
        raise ValueError(
            "write_trace_artifact needs an active PipelineTrace for "
            "non-perfetto paths")
    with open(path, "w") as f:
        f.write(trace.to_json())
    return "trace"
