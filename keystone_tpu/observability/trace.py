"""Structured per-run pipeline tracing.

A :class:`PipelineTrace` is entered as a context manager around pipeline
execution; while active (:func:`current_trace` returns it), the workflow
stack feeds it:

* per-node execution records (``record_node`` — appended by the
  executor's instrumented expression thunks, with wall time measured
  after ``jax.block_until_ready`` on device results, the output's
  device-memory footprint, whether the value came from a cache/prefix
  hit or was computed, and the data shard count);
* optimizer rule logs (``record_rule`` — which rewrite rules fired and
  the graph-size delta per rule);
* the auto-cache rule's report (``record_auto_cache`` — the sampled
  profiles it extrapolated, the cache set it selected, and the memory
  budget it worked under);
* node-level cost-model decisions (``record_node_choice`` /
  ``record_solver_decision`` — the workload shape n/d/k/sparsity, the
  per-solver cost estimates behind each choice, and the calibration
  provenance of the cost-model weights).

Node wall times are *self* times: each instrumented thunk's elapsed time
minus the time spent inside nested instrumented thunks (dependencies are
lazy and memoized, so a parent's first ``get()`` transitively computes
its uncomputed ancestors). Self times therefore sum to the real
aggregate compute time with no double counting, which is what makes
``summary()``'s per-node percentages meaningful.

Tracing is zero-overhead by default: when no trace is active every hook
returns immediately, and the executor does not wrap expression thunks at
all.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Iterator, List, Optional

from ..utils.guarded import TracedLock, guarded_by

_ACTIVE: Optional["PipelineTrace"] = None


def current_trace() -> Optional["PipelineTrace"]:
    """The active trace, or None when tracing is disabled (the common
    case — instrumentation sites bail out on None)."""
    return _ACTIVE


_SUPPRESS_DEPTH = 0


@contextlib.contextmanager
def tracing_disabled() -> Iterator[None]:
    """Suspend the active trace AND the executor's always-on metrics
    counters for the enclosed block. Used by optimizer sampling
    (node-level optimization, auto-cache profiling): sampled sub-graph
    executions share node ids with the main graph and would pollute the
    per-node record stream and inflate ``executor.*`` counters; their
    aggregate cost is already recorded in the optimizer decision
    entries."""
    global _ACTIVE, _SUPPRESS_DEPTH
    prev = _ACTIVE
    _ACTIVE = None
    _SUPPRESS_DEPTH += 1
    try:
        yield
    finally:
        _ACTIVE = prev
        _SUPPRESS_DEPTH -= 1


def metrics_suppressed() -> bool:
    """True inside a :func:`tracing_disabled` block (throwaway sampled
    executions must not count as real executor activity)."""
    return _SUPPRESS_DEPTH > 0


@dataclass
class NodeRecord:
    """One executed graph node."""

    node_id: int
    operator: str
    wall_s: float = 0.0        # self time (nested node compute excluded)
    total_s: float = 0.0       # inclusive wall time of this node's thunk
    output_bytes: float = 0.0  # device-memory footprint of the output
    cached: bool = False       # value came from the prefix/state memo
    shards: int = 1            # data shards of the output dataset
    kind: str = ""             # expression kind (dataset/datum/transformer)
    # hardware-utilization annotations (observability/utilization.py
    # ``annotate_trace`` back-fills them from the compile observatory's
    # per-executable cost_analysis; zero = not annotated)
    flops: float = 0.0         # XLA cost-model FLOPs of this node's program
    mfu: float = 0.0           # achieved FLOP/s over device peak
    membw_util: float = 0.0    # achieved bytes/s over HBM bandwidth
    plan_vs_xla: float = 0.0   # static HbmPlan bytes / XLA output+temp bytes


class _Frame:
    __slots__ = ("child_s",)

    def __init__(self) -> None:
        self.child_s = 0.0


@guarded_by("_resilience_lock", "resilience", "resilience_stats")
@guarded_by("_lock_wait_lock", "lock_waits")
@guarded_by("_compile_lock", "compiles", "compile_stats")
@guarded_by("_numerics_lock", "numerics", "numerics_stats")
class PipelineTrace:
    """Collects one run's execution telemetry; see module docstring.

    Usage::

        with PipelineTrace("mnist") as tr:
            pipeline.apply(data).numpy()
        print(tr.summary())
        open("trace.json", "w").write(tr.to_json())

    Thread model: the per-node/chunk/optimizer streams are fed by the
    single driver thread; ``record_resilience`` and
    ``record_lock_wait`` are fed by ingest worker threads and take
    locks (declared above, checked by ``analysis.concurrency``).
    """

    def __init__(self, name: str = "pipeline"):
        self.name = name
        self.nodes: List[NodeRecord] = []
        self.optimizer_rules: List[Dict[str, Any]] = []
        self.auto_cache: List[Dict[str, Any]] = []
        self.node_choices: List[Dict[str, Any]] = []
        self.solver_decisions: List[Dict[str, Any]] = []
        #: most recent streamed-ingest chunk entries (bounded tail —
        #: an out-of-core fit can stream millions of chunks, so exact
        #: aggregates live in ``chunk_stats`` and only CHUNK_TAIL raw
        #: entries are retained for inspection)
        self.chunks: List[Dict[str, Any]] = []
        self.chunk_stats: Dict[str, float] = {
            "count": 0, "ingest_stall_s": 0.0, "nbytes": 0.0,
            "occupancy_sum": 0.0, "h2d_bytes": 0.0}
        #: one entry per streamed fit: the static HBM plan next to the
        #: measured residency peak, so the planner model is continuously
        #: validated by every traced out-of-core fit
        self.streamed_fits: List[Dict[str, Any]] = []
        #: resilience events (retries, quarantines, checkpoint
        #: saves/restores, watchdog trips, injected faults) — same
        #: bounded-tail-plus-exact-counts shape as ``chunks``
        self.resilience: List[Dict[str, Any]] = []
        self.resilience_stats: Dict[str, float] = {}
        # resilience events fire from decode/prefetch worker threads
        # concurrently; the read-modify-write on the stats dict needs a
        # real lock for the "counts stay exact" contract to hold — a
        # TracedLock, so its own contention is observable and the
        # schedule harness can interleave at it (the PR 4 race's
        # regression schedule lives in tests/test_concurrency_sched.py)
        self._resilience_lock = TracedLock("trace.resilience")
        #: compile events observed while this trace was active
        #: (``observability/compilelog.py``): site name, wall, trigger
        #: classification, signature delta, unexpected flag — same
        #: bounded-tail-plus-exact-stats shape as ``resilience``.
        #: Compiles can fire from ingest worker threads (the streaming
        #: consumer's wire-cast, decode-side helpers), hence the lock
        #: (plain: compile records also feed metrics/recorder, the
        #: usual boundary).
        self.compiles: List[Dict[str, Any]] = []
        self.compile_stats: Dict[str, float] = {
            "count": 0, "wall_s": 0.0, "unexpected": 0}
        self._compile_lock = threading.Lock()
        #: numerics events (observability/numerics.py): solver
        #: breakdowns, non-finite tripwires, drift scores/warnings —
        #: same bounded-tail-plus-exact-counts shape as ``resilience``.
        #: Solver-ledger events arrive from jax debug-callback threads,
        #: hence the lock (a TracedLock: its contention reports into
        #: metrics/recorder/lock_waits, never back into this stream).
        self.numerics: List[Dict[str, Any]] = []
        self.numerics_stats: Dict[str, float] = {}
        self._numerics_lock = TracedLock("trace.numerics")
        #: contended-lock wait table fed by TracedLock while this trace
        #: is active: {lock name: {"count": n, "wait_s": total}}. Its
        #: own guard is a PLAIN lock — TracedLock reports in here, so a
        #: traced guard would recurse (utils/guarded.py documents the
        #: boundary).
        self.lock_waits: Dict[str, Dict[str, float]] = {}
        self._lock_wait_lock = threading.Lock()
        self.meta: Dict[str, Any] = {}
        self.wall_s: float = 0.0
        self._t0: Optional[float] = None
        self._stack: List[_Frame] = []
        self._prev: Optional["PipelineTrace"] = None

    # -- context ----------------------------------------------------------
    def __enter__(self) -> "PipelineTrace":
        global _ACTIVE
        self._prev = _ACTIVE
        _ACTIVE = self
        self._t0 = time.perf_counter()
        try:
            import jax

            dev = jax.devices()[0]
            self.meta.setdefault("backend", dev.platform)
            self.meta.setdefault("device_kind", dev.device_kind)
            self.meta.setdefault("num_devices", len(jax.devices()))
        except Exception:
            pass
        return self

    def __exit__(self, *exc) -> None:
        global _ACTIVE
        if self._t0 is not None:
            self.wall_s += time.perf_counter() - self._t0
            self._t0 = None
        _ACTIVE = self._prev
        self._prev = None

    # -- recording hooks (called by the workflow stack) -------------------
    @contextlib.contextmanager
    def node_timer(self, record: NodeRecord) -> Iterator[NodeRecord]:
        """Time one node's thunk, attributing nested instrumented node
        time to the children (self-time accounting)."""
        frame = _Frame()
        self._stack.append(frame)
        t0 = time.perf_counter()
        try:
            yield record
        finally:
            total = time.perf_counter() - t0
            self._stack.pop()
            record.total_s = total
            record.wall_s = max(total - frame.child_s, 0.0)
            if self._stack:
                self._stack[-1].child_s += total
            self.nodes.append(record)

    def record_node(self, record: NodeRecord) -> None:
        """Record a node that involved no timed compute (eager constants,
        prefix/state cache hits)."""
        self.nodes.append(record)

    def record_rule(self, optimizer: str, batch: str, rule: str,
                    nodes_before: int, nodes_after: int,
                    wall_s: float) -> None:
        self.optimizer_rules.append({
            "optimizer": optimizer, "batch": batch, "rule": rule,
            "nodes_before": nodes_before, "nodes_after": nodes_after,
            "wall_s": wall_s,
        })

    def record_auto_cache(self, report: Dict[str, Any]) -> None:
        self.auto_cache.append(report)

    def record_node_choice(self, entry: Dict[str, Any]) -> None:
        self.node_choices.append(entry)

    def record_solver_decision(self, entry: Dict[str, Any]) -> None:
        self.solver_decisions.append(entry)

    #: raw per-chunk entries retained (the aggregates in ``chunk_stats``
    #: are exact over ALL chunks regardless)
    CHUNK_TAIL = 512

    def record_chunk(self, entry: Dict[str, Any]) -> None:
        """One streamed ingest chunk (``parallel.streaming``): source
        tag, chunk index, true row count, device footprint (post-cast
        working copy), the wire bytes actually shipped host->device
        (``h2d_bytes`` — narrower than ``nbytes`` when a wire dtype is
        in play), stage-lane occupancy (``stage_lanes`` per-shard H2D
        lanes / ``stage_s`` host stage wall), the time the consumer
        stalled waiting for ingest, and the prefetch-buffer occupancy at
        hand-off. The per-chunk stall attribution is the evidence behind
        'ingest overlaps compute' claims. Aggregates are exact; raw
        entries keep only the most recent ``CHUNK_TAIL`` (an out-of-core
        fit can stream unboundedly many chunks)."""
        s = self.chunk_stats
        s["count"] += 1
        s["ingest_stall_s"] += float(entry.get("ingest_stall_s", 0.0))
        s["nbytes"] += float(entry.get("nbytes", 0.0))
        s["occupancy_sum"] += float(entry.get("prefetch_occupancy", 0.0))
        s["h2d_bytes"] = (s.get("h2d_bytes", 0.0)
                          + float(entry.get("h2d_bytes", 0.0)))
        self.chunks.append(entry)
        if len(self.chunks) > self.CHUNK_TAIL:
            del self.chunks[: len(self.chunks) - self.CHUNK_TAIL]

    #: raw streamed-fit entries retained (same bounded-tail discipline
    #: as ``chunks``/``resilience`` — a long-lived retrain loop under
    #: one trace must not grow it without bound)
    STREAMED_FIT_TAIL = 512

    def record_streamed_fit(self, entry: Dict[str, Any]) -> None:
        """One completed streamed fit (``parallel.streaming``): source
        tag, chunk count, ``static_plan_nbytes`` (the device-free
        residency bound the planner computed — None for opaque
        sources), the ledger's measured ``peak_device_nbytes``, and the
        asserted ``hbm_budget`` if any. ``static_plan_nbytes >=
        peak_device_nbytes`` is the planner's correctness contract;
        bench reports the ratio as ``plan_vs_measured``."""
        self.streamed_fits.append(entry)
        if len(self.streamed_fits) > self.STREAMED_FIT_TAIL:
            del self.streamed_fits[: len(self.streamed_fits)
                                   - self.STREAMED_FIT_TAIL]

    #: raw resilience entries retained (per-event counts in
    #: ``resilience_stats`` stay exact)
    RESILIENCE_TAIL = 512

    def record_resilience(self, entry: Dict[str, Any]) -> None:
        """One resilience event (:mod:`keystone_tpu.resilience.events`):
        ``entry["event"]`` is the kind (retry / retry_exhausted /
        quarantine / checkpoint_save / checkpoint_restore /
        watchdog_trip / fault_injected), the rest is site context. May
        be called from ingest worker threads (append-only under the
        GIL, like ``record_chunk``)."""
        event = str(entry.get("event", "other"))
        with self._resilience_lock:
            self.resilience_stats[event] = (
                self.resilience_stats.get(event, 0) + 1)
            self.resilience.append(entry)
            if len(self.resilience) > self.RESILIENCE_TAIL:
                del self.resilience[: len(self.resilience)
                                    - self.RESILIENCE_TAIL]

    #: raw compile entries retained (``compile_stats`` stays exact)
    COMPILE_TAIL = 512

    def record_compile(self, entry: Dict[str, Any]) -> None:
        """One XLA compile observed while this trace was active
        (:mod:`keystone_tpu.observability.compilelog`): site name,
        compile wall, trigger (first-compile / signature-change /
        mesh-change / retrace / unowned), the signature delta when one
        is nameable, the attributing context (an executor node scope),
        the phases, cache outcome and times of the record (that
        module's "One record a compile"), and the ``unexpected`` flag
        when a warmup fence was armed."""
        with self._compile_lock:
            self.compile_stats["count"] += 1
            self.compile_stats["wall_s"] += float(entry.get("wall_s", 0.0))
            if entry.get("unexpected"):
                self.compile_stats["unexpected"] += 1
            self.compiles.append(entry)
            if len(self.compiles) > self.COMPILE_TAIL:
                del self.compiles[: len(self.compiles) - self.COMPILE_TAIL]

    #: raw numerics entries retained (per-event counts in
    #: ``numerics_stats`` stay exact)
    NUMERICS_TAIL = 512

    def record_numerics(self, entry: Dict[str, Any]) -> None:
        """One numerics event (:mod:`keystone_tpu.observability.\
numerics`): ``entry["event"]`` is the kind (nonfinite /
        nonfinite_model / breakdown / drift_score / drift_warn /
        fit_baseline), the rest is site context — solver site and pivot
        ratio for breakdowns, source/chunk for tripwires, PSI score for
        drift. May fire from jax debug-callback threads (the solver
        ledger), hence the lock."""
        event = str(entry.get("event", "other"))
        with self._numerics_lock:
            self.numerics_stats[event] = (
                self.numerics_stats.get(event, 0) + 1)
            self.numerics.append(entry)
            if len(self.numerics) > self.NUMERICS_TAIL:
                del self.numerics[: len(self.numerics)
                                  - self.NUMERICS_TAIL]

    def record_lock_wait(self, name: str, wait_s: float) -> None:
        """One contended :class:`~keystone_tpu.utils.guarded.TracedLock`
        acquire while this trace was active (called from whichever
        thread lost the race — always under ``_lock_wait_lock``).
        ``summary()`` prints the top contended locks, so a traced
        streamed fit shows WHERE its threads serialized, not just that
        they did."""
        with self._lock_wait_lock:
            entry = self.lock_waits.get(name)
            if entry is None:
                entry = self.lock_waits[name] = {
                    "count": 0, "wait_s": 0.0}
            entry["count"] += 1
            entry["wait_s"] += float(wait_s)

    def ingest_stall_s(self) -> float:
        """Total consumer-side ingest stall across ALL streamed chunks
        (exact aggregate) — compare against ``wall_s`` for the overlap
        share."""
        return float(self.chunk_stats["ingest_stall_s"])

    # -- views ------------------------------------------------------------
    def node_ids(self) -> set:
        return {r.node_id for r in self.nodes}

    def cache_hits(self) -> List[NodeRecord]:
        return [r for r in self.nodes if r.cached]

    def total_node_wall_s(self) -> float:
        return sum(r.wall_s for r in self.nodes)

    # -- export -----------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "meta": dict(self.meta),
            "wall_s": self.wall_s,
            "nodes": [asdict(r) for r in self.nodes],
            "optimizer_rules": list(self.optimizer_rules),
            "auto_cache": list(self.auto_cache),
            "node_choices": list(self.node_choices),
            "solver_decisions": list(self.solver_decisions),
            "chunks": list(self.chunks),
            "chunk_stats": dict(self.chunk_stats),
            "streamed_fits": list(self.streamed_fits),
            "resilience": list(self.resilience),
            "resilience_stats": dict(self.resilience_stats),
            "compiles": list(self.compiles),
            "compile_stats": dict(self.compile_stats),
            "numerics": list(self.numerics),
            "numerics_stats": dict(self.numerics_stats),
            "lock_waits": {k: dict(v)
                           for k, v in self.lock_waits.items()},
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=False)

    @classmethod
    def from_json(cls, blob: str) -> "PipelineTrace":
        data = json.loads(blob)
        tr = cls(data.get("name", "pipeline"))
        tr.meta = dict(data.get("meta", {}))
        tr.wall_s = float(data.get("wall_s", 0.0))
        tr.nodes = [NodeRecord(**r) for r in data.get("nodes", [])]
        tr.optimizer_rules = list(data.get("optimizer_rules", []))
        tr.auto_cache = list(data.get("auto_cache", []))
        tr.node_choices = list(data.get("node_choices", []))
        tr.solver_decisions = list(data.get("solver_decisions", []))
        tr.chunks = list(data.get("chunks", []))
        stats = data.get("chunk_stats")
        if stats is None and tr.chunks:  # older artifact: rebuild
            stats = {
                "count": len(tr.chunks),
                "ingest_stall_s": sum(
                    float(c.get("ingest_stall_s", 0.0)) for c in tr.chunks),
                "nbytes": sum(
                    float(c.get("nbytes", 0.0)) for c in tr.chunks),
                "occupancy_sum": sum(
                    float(c.get("prefetch_occupancy", 0.0))
                    for c in tr.chunks),
                "h2d_bytes": sum(
                    float(c.get("h2d_bytes", 0.0)) for c in tr.chunks),
            }
        if stats is not None:
            tr.chunk_stats = dict(stats)
        tr.streamed_fits = list(data.get("streamed_fits", []))
        tr.resilience = list(data.get("resilience", []))
        tr.resilience_stats = dict(data.get("resilience_stats", {}))
        if not tr.resilience_stats and tr.resilience:  # older artifact
            for e in tr.resilience:
                ev = str(e.get("event", "other"))
                tr.resilience_stats[ev] = (
                    tr.resilience_stats.get(ev, 0) + 1)
        tr.compiles = list(data.get("compiles", []))
        cstats = data.get("compile_stats")
        if cstats is None and tr.compiles:  # older artifact: rebuild
            cstats = {
                "count": len(tr.compiles),
                "wall_s": sum(float(c.get("wall_s", 0.0))
                              for c in tr.compiles),
                "unexpected": sum(1 for c in tr.compiles
                                  if c.get("unexpected")),
            }
        if cstats is not None:
            tr.compile_stats = dict(cstats)
        tr.numerics = list(data.get("numerics", []))
        tr.numerics_stats = dict(data.get("numerics_stats", {}))
        if not tr.numerics_stats and tr.numerics:  # older artifact
            for e in tr.numerics:
                ev = str(e.get("event", "other"))
                tr.numerics_stats[ev] = tr.numerics_stats.get(ev, 0) + 1
        tr.lock_waits = {k: dict(v) for k, v in
                         data.get("lock_waits", {}).items()}
        return tr

    def summary(self, top: int = 0) -> str:
        """Human-readable per-node table sorted by self wall time, with
        each node's share of the total, followed by optimizer decisions."""
        lines = [f"PipelineTrace {self.name!r}: "
                 f"{len(self.nodes)} node executions, "
                 f"wall {self.wall_s:.3f}s"]
        total = self.total_node_wall_s()
        lines.append(f"traced node compute: {total:.3f}s "
                     f"({100.0 * total / self.wall_s:.1f}% of wall)"
                     if self.wall_s else
                     f"traced node compute: {total:.3f}s")
        rows = sorted(self.nodes, key=lambda r: -r.wall_s)
        if top:
            rows = rows[:top]
        lines.append(f"{'node':>6} {'operator':<28} {'self ms':>10} "
                     f"{'% total':>8} {'out MiB':>9} {'shards':>6} "
                     f"{'cached':>6}")
        for r in rows:
            pct = 100.0 * r.wall_s / total if total else 0.0
            lines.append(
                f"{r.node_id:>6} {r.operator[:28]:<28} "
                f"{r.wall_s * 1e3:>10.2f} {pct:>7.1f}% "
                f"{r.output_bytes / (1 << 20):>9.2f} {r.shards:>6} "
                f"{'yes' if r.cached else '':>6}")
        if self.optimizer_rules:
            lines.append("optimizer rules fired:")
            for e in self.optimizer_rules:
                lines.append(
                    f"  {e['rule']} [{e['batch']}] nodes "
                    f"{e['nodes_before']} -> {e['nodes_after']} "
                    f"({e['wall_s'] * 1e3:.1f} ms)")
        for rep in self.auto_cache:
            sel = rep.get("selected", [])
            lines.append(
                f"auto-cache[{rep.get('strategy')}]: cached {len(sel)} "
                f"node(s) {sel} under budget "
                f"{rep.get('budget_bytes', 0) / (1 << 20):.0f} MiB "
                f"(profiled {len(rep.get('profiles', {}))} nodes)")
        if self.chunk_stats["count"]:
            count = int(self.chunk_stats["count"])
            stall = self.ingest_stall_s()
            share = (100.0 * stall / self.wall_s) if self.wall_s else 0.0
            h2d = float(self.chunk_stats.get("h2d_bytes", 0.0))
            lines.append(
                f"streamed ingest: {count} chunk(s), "
                f"stall {stall:.3f}s ({share:.1f}% of wall), "
                f"h2d {h2d / (1 << 20):.1f} MiB, "
                f"mean prefetch occupancy "
                f"{self.chunk_stats['occupancy_sum'] / count:.2f}")
        for sf in self.streamed_fits:
            plan = sf.get("static_plan_nbytes")
            peak = float(sf.get("peak_device_nbytes", 0.0))
            mib = 1 << 20
            if plan is None:
                shown = "plan n/a (opaque source)"
            else:
                ratio = (plan / peak) if peak else float("inf")
                shown = (f"plan {plan / mib:.2f} MiB, "
                         f"plan/measured {ratio:.2f}")
            lines.append(
                f"streamed fit [{sf.get('source')}]: "
                f"{sf.get('chunks', 0)} chunk(s), measured peak "
                f"{peak / mib:.2f} MiB, {shown}")
        if self.compile_stats["count"]:
            c = self.compile_stats
            worst = sorted(self.compiles,
                           key=lambda e: -float(e.get("wall_s", 0.0)))[:3]
            shown = ", ".join(
                f"{e.get('name')} ({float(e.get('wall_s', 0.0)):.2f}s, "
                f"{e.get('trigger')})" for e in worst)
            lines.append(
                f"compiles: {int(c['count'])} ({c['wall_s']:.2f}s wall, "
                f"{int(c['unexpected'])} unexpected) — top: {shown}")
        if self.resilience_stats:
            counts = " ".join(
                f"{k}={int(v)}" for k, v in sorted(
                    self.resilience_stats.items()))
            lines.append(f"resilience events: {counts}")
        if self.numerics_stats:
            counts = " ".join(
                f"{k}={int(v)}" for k, v in sorted(
                    self.numerics_stats.items()))
            lines.append(f"numerics events: {counts}")
        if self.lock_waits:
            top = sorted(self.lock_waits.items(),
                         key=lambda kv: -kv[1].get("wait_s", 0.0))[:3]
            shown = ", ".join(
                f"{name} ({int(v.get('count', 0))}x, "
                f"{v.get('wait_s', 0.0) * 1e3:.1f} ms)"
                for name, v in top)
            lines.append(f"contended locks (top {len(top)}): {shown}")
        for d in self.solver_decisions:
            costs = ", ".join(
                f"{k}={v:.3g}s" for k, v in d.get("costs", {}).items())
            sp = d.get("sparsity")
            sp = "?" if sp is None else f"{sp:.3g}"  # trimmed artifacts
            lines.append(
                f"solver choice @ n={d.get('n')} d={d.get('d')} "
                f"k={d.get('k')} sparsity={sp}: "
                f"{d.get('chosen')} ({costs}) "
                f"[weights: {d.get('provenance', {}).get('source', '?')}]")
        return "\n".join(lines)


@contextlib.contextmanager
def xprof_trace(log_dir: str) -> Iterator[Optional[PipelineTrace]]:
    """Capture an XLA profiler trace (xplane, viewable in
    TensorBoard/XProf) for everything in scope: profiler start/stop and
    nothing else, so the captured timeline is the run users have.
    Pipeline-level names reach the capture through the always-on span
    annotations (``ks:dag:node:<label>#<id>`` and the rest of
    :mod:`.timeline`'s fit-path spans); no :class:`PipelineTrace` is
    created, since one blocks on the device after every node.

    Yields the trace that is already active, or ``None``: nesting
    ``xprof_trace`` inside ``with PipelineTrace(...) as tr:`` is the
    explicit choice of profile mode, and keeps every record in ``tr``."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield current_trace()
    finally:
        jax.profiler.stop_trace()
