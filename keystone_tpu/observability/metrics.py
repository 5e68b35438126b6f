"""Process-wide metrics: counters, gauges, and timing histograms.

The cheap, always-on half of the observability layer (the detailed
per-run structure lives in :mod:`.trace`). A metric update is a dict
lookup plus a locked float add — safe to leave in hot paths like the
DAG executor. Unlike the early single-threaded-driver days, metrics are
now fed from worker threads too (the streaming prefetcher, the tar
decode pool, retry helpers — PR 3/4), so every read-modify-write here
takes a lock; the discipline is declared with
:func:`~keystone_tpu.utils.guarded.guarded_by` and checked statically
by ``analysis.concurrency``.

These are deliberately *plain* ``threading.Lock``\\ s, not TracedLocks:
a TracedLock's contended path reports INTO this registry, so tracing
the registry's own locks would re-enter them (see
``utils/guarded.py``). The uncontended cost is ~100 ns per update —
metrics fire per chunk/record/node, never per element.
"""
from __future__ import annotations

import contextlib
import re
import threading
import time
import warnings
from typing import Dict, Iterator, List, Optional

from ..utils.guarded import guarded_by


@guarded_by("_lock", "value")
class Counter:
    """Monotonically increasing count (thread-safe: the ``+=`` is a
    read-modify-write and counters are incremented from ingest worker
    threads — the resilience event funnel, the prefetcher)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount


class Gauge:
    """Last-written value (a plain overwrite — atomic enough without a
    lock; last writer wins is the semantics)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, value: float) -> None:
        self.value = float(value)


@guarded_by("_lock", "count", "total", "min", "max", "_tail")
class Histogram:
    """Streaming aggregates (count/total/min/max) plus a bounded tail of
    raw observations for percentile-ish inspection without unbounded
    memory growth in long-lived processes. ``observe`` may be called
    from multiple threads (ingest stalls, lock waits, retry timings);
    the aggregates and the tail trim are guarded so concurrent
    observations can neither lose counts nor corrupt the tail."""

    __slots__ = ("name", "count", "total", "min", "max", "_tail", "_lock")

    TAIL = 256

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._tail: List[float] = []
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self.count += 1
            self.total += value
            if value < self.min:
                self.min = value
            if value > self.max:
                self.max = value
            self._tail.append(value)
            if len(self._tail) > self.TAIL:
                del self._tail[: len(self._tail) - self.TAIL]

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Approximate percentile over the retained tail (the most
        recent ``TAIL`` observations), 0 <= q <= 100."""
        with self._lock:
            tail = list(self._tail)
        if not tail:
            return 0.0
        ordered = sorted(tail)
        idx = min(len(ordered) - 1,
                  max(0, int(round(q / 100.0 * (len(ordered) - 1)))))
        return ordered[idx]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            if not self.count:
                return {"count": 0, "total": 0.0, "mean": 0.0,
                        "min": 0.0, "max": 0.0}
            count, total = self.count, self.total
            lo, hi = self.min, self.max
        return {"count": count, "total": total, "mean": total / count,
                "min": lo, "max": hi,
                "p50": self.percentile(50), "p99": self.percentile(99)}


#: guards the singleton create (``get_or_create``/``reset`` may race a
#: worker thread's first metric against the main thread's — a lost
#: registry loses every count the loser wrote)
_REGISTRY_LOCK = threading.Lock()


@guarded_by("_lock", "_counters", "_gauges", "_histograms")
class MetricsRegistry:
    """Process-wide named metrics (``MetricsRegistry.get_or_create()``).
    The lazy per-name creates are check-then-act sequences, hit
    concurrently by ingest worker threads — both the singleton and the
    name maps are locked."""

    _instance: Optional["MetricsRegistry"] = None

    def __init__(self) -> None:
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._lock = threading.Lock()

    @classmethod
    def get_or_create(cls) -> "MetricsRegistry":
        inst = cls._instance
        if inst is None:
            with _REGISTRY_LOCK:
                inst = cls._instance
                if inst is None:
                    inst = cls._instance = MetricsRegistry()
        return inst

    @classmethod
    def reset(cls) -> None:
        """Drop the global registry (tests)."""
        with _REGISTRY_LOCK:
            cls._instance = None

    # -- access -----------------------------------------------------------
    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.get(name)
                if c is None:
                    c = self._counters[name] = Counter(name)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.get(name)
                if g is None:
                    g = self._gauges[name] = Gauge(name)
        return g

    def histogram(self, name: str) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.get(name)
                if h is None:
                    h = self._histograms[name] = Histogram(name)
        return h

    @contextlib.contextmanager
    def timer(self, name: str) -> Iterator[None]:
        """Time the enclosed block into histogram ``name`` (seconds).
        Callers timing async device work must block inside the block."""
        t0 = time.perf_counter()
        yield
        self.histogram(name).observe(time.perf_counter() - t0)

    # -- export -----------------------------------------------------------
    def snapshot(self) -> Dict[str, Dict]:
        # copy the maps under the lock before iterating: a worker
        # thread lazily creating a metric (a contended TracedLock's
        # first lock.wait_s.<name> histogram) mid-snapshot would
        # otherwise resize the dict under the iteration
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        return {
            "counters": {k: c.value for k, c in sorted(counters.items())},
            "gauges": {k: g.value for k, g in sorted(gauges.items())},
            "histograms": {
                k: h.snapshot() for k, h in sorted(histograms.items())
            },
        }

    def to_prometheus(self) -> str:
        """The registry as Prometheus text exposition (format 0.0.4):
        counters and gauges one sample each, histograms as summaries
        (``_count``/``_sum`` plus p50/p99 quantile samples from the
        retained tail). Names are namespaced ``keystone_`` and
        sanitized to the Prometheus charset (dots become underscores
        — the canonical dotted names live in ``observability/names.py``
        and the mapping is mechanical, so dashboards can be written
        from the catalogue). This is what :func:`~keystone_tpu.\
        observability.sampler.serve_metrics` serves on ``/metrics``."""
        snap = self.snapshot()
        lines: List[str] = []
        for name, value in snap["counters"].items():
            n = _prometheus_name(name) + "_total"
            lines.append(f"# TYPE {n} counter")
            lines.append(f"{n} {_prometheus_value(value)}")
        for name, value in snap["gauges"].items():
            n = _prometheus_name(name)
            lines.append(f"# TYPE {n} gauge")
            lines.append(f"{n} {_prometheus_value(value)}")
        for name, h in snap["histograms"].items():
            n = _prometheus_name(name)
            lines.append(f"# TYPE {n} summary")
            for q, key in (("0.5", "p50"), ("0.99", "p99")):
                lines.append(
                    f'{n}{{quantile="{q}"}} '
                    f"{_prometheus_value(h.get(key, 0.0))}")
            lines.append(f"{n}_sum {_prometheus_value(h['total'])}")
            lines.append(f"{n}_count {int(h['count'])}")
        return "\n".join(lines) + "\n"


_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prometheus_name(name: str) -> str:
    return "keystone_" + _PROM_BAD.sub("_", name)


def _prometheus_value(value: float) -> str:
    v = float(value)
    if v != v or v in (float("inf"), float("-inf")):
        # Prometheus exposition accepts NaN/+Inf/-Inf literals; a
        # non-finite gauge (numerics observes the pathological cases
        # by design) must not crash the scrape surface
        return "NaN" if v != v else ("+Inf" if v > 0 else "-Inf")
    return str(int(v)) if v == int(v) and abs(v) < 1e15 else repr(v)


class StepTimer:
    """DEPRECATED wall-clock step timing (formerly
    ``utils.StepTimer``; kept API-compatible for external
    callers — constructing one warns). Use
    ``MetricsRegistry.get_or_create().timer(name)`` instead: same
    one-line timing, but the samples land in the process histogram
    (p50/p99, Prometheus exposition) instead of a private dict.
    ``timed(name, fn, ...)`` blocks on the device result before reading
    the clock — the honest way to time jitted programs. ``step(name)``
    times the enclosed block as-is (callers must block_until_ready
    inside if the block dispatches async device work)."""

    def __init__(self) -> None:
        warnings.warn(
            "StepTimer is deprecated; use MetricsRegistry.get_or_create()"
            ".timer(name) (observability/metrics.py) — same block-style "
            "timing, recorded into the process histograms",
            DeprecationWarning, stacklevel=2)
        self.times: Dict[str, list] = {}

    @contextlib.contextmanager
    def step(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        yield
        self.times.setdefault(name, []).append(time.perf_counter() - t0)

    def timed(self, name: str, fn, *args, **kwargs):
        import jax

        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        out = jax.block_until_ready(out)
        self.times.setdefault(name, []).append(time.perf_counter() - t0)
        return out

    def summary(self) -> str:
        lines = []
        for name, ts in self.times.items():
            lines.append(
                f"{name}: n={len(ts)} mean={sum(ts)/len(ts)*1e3:.2f}ms "
                f"min={min(ts)*1e3:.2f}ms max={max(ts)*1e3:.2f}ms")
        return "\n".join(lines)
