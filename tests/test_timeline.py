"""Flight recorder, telemetry sampler, Prometheus exposition, and
crash post-mortems (PR 8 observability plane).

Covers: ring-buffer bounding and wraparound order; Chrome-trace export
round-tripping through ``json.loads`` with strictly non-overlapping
``ts``/``dur`` per exported lane (nested/overlapping spans overflow to
sub-lanes); the ``--trace-out *.perfetto.json`` dispatch; real streamed
runs feeding prefetch/H2D/compute lanes; sampler start/stop idempotency
and bounded series; the ``/metrics`` scrape endpoint; and post-mortem
dumps attached to ``IngestTimeoutError`` / ``RetryExhaustedError`` /
HBM-budget ``MemoryError`` with the artifact path named in the message.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.observability.sampler import TelemetrySampler, serve_metrics
from keystone_tpu.observability.timeline import (
    FlightRecorder,
    flight_recorder,
    write_trace_artifact,
)


def _nonoverlap_per_lane(blob):
    """Assert the strictly-non-overlapping invariant for every exported
    lane: complete events sorted by ts never start before the previous
    one ended."""
    lanes = {}
    for e in blob["traceEvents"]:
        if e.get("ph") == "X":
            lanes.setdefault(e["tid"], []).append((e["ts"], e["dur"]))
    assert lanes, "no complete events exported"
    for tid, events in lanes.items():
        events.sort()
        for (t1, d1), (t2, d2) in zip(events, events[1:]):
            assert t2 >= t1 + d1 - 1e-6, (
                f"lane {tid}: span at {t2} overlaps previous "
                f"[{t1}, {t1 + d1}]")
    return lanes


# -- ring buffer -------------------------------------------------------------

def test_ring_bounds_and_wraparound_order():
    rec = FlightRecorder(capacity=4, enabled=True)
    t0 = time.perf_counter()
    for i in range(7):
        rec.record(f"s{i}", "test", t0 + i, 0.5)
    spans = rec.spans()
    assert [s.name for s in spans] == ["s3", "s4", "s5", "s6"]  # oldest out
    assert rec.total_recorded == 7
    assert rec.dropped() == 3


def test_ring_clear_and_partial_fill():
    rec = FlightRecorder(capacity=8, enabled=True)
    rec.record("a", "test", 0.0, 1.0)
    rec.record("b", "test", 1.0, 1.0)
    assert [s.name for s in rec.spans()] == ["a", "b"]
    rec.clear()
    assert rec.spans() == [] and rec.dropped() == 0


def test_a_pinned_span_outlives_the_ring():
    """``pin=True`` keeps a span beside the ring, by ``cat:name``, the
    newest of a name: what a process does once and a reader asks for
    hours later (``startup:import``)."""
    rec = FlightRecorder(capacity=8, enabled=True)
    rec.record("import", "startup", 1.0, 0.25, pin=True)
    for i in range(10_000):
        rec.record(f"s{i}", "test", 2.0 + i, 0.5)
    assert rec.dropped() == 10_001 - 8
    assert all(s.cat == "test" for s in rec.spans())
    (span,) = rec.pinned().values()
    assert (span.name, span.cat, span.start_s, span.dur_s, span.seq > 0) == (
        "import", "startup", 1.0, 0.25, True)
    # an export holds it once, whether or not the ring still does
    exported = [e for e in rec.to_chrome_trace()["traceEvents"]
                if e.get("cat") == "startup"]
    assert len(exported) == 1 and exported[0]["dur"] == 0.25e6
    rec.record("import", "startup", 3.0, 0.5, pin=True)
    assert rec.pinned()["startup:import"].start_s == 3.0
    assert len([e for e in rec.to_chrome_trace()["traceEvents"]
                if e.get("cat") == "startup"]) == 1
    rec.clear()
    assert rec.pinned() == {}
    off = FlightRecorder(capacity=8, enabled=False)
    off.record("import", "startup", 1.0, 0.25, pin=True)
    assert off.pinned() == {}


def test_importing_the_package_pins_startup_import():
    import importlib

    import keystone_tpu

    assert flight_recorder().pinned() == {}   # a fresh recorder a test
    before = time.perf_counter()
    importlib.reload(keystone_tpu)
    after = time.perf_counter()
    span = flight_recorder().pinned()["startup:import"]
    assert span.ph == "X" and span.args is None
    assert before <= span.start_s <= span.start_s + span.dur_s <= after
    assert span.dur_s > 0.0


def test_disabled_recorder_records_nothing():
    rec = FlightRecorder(capacity=8, enabled=False)
    rec.record("a", "test", 0.0, 1.0)
    with rec.span("b", "test"):
        pass
    assert rec.spans() == [] and rec.total_recorded == 0


def test_env_disable_via_global(monkeypatch):
    from keystone_tpu.observability.timeline import reset_flight_recorder

    monkeypatch.setenv("KEYSTONE_FLIGHT_RECORDER", "0")
    reset_flight_recorder()
    rec = flight_recorder()
    rec.record("a", "test", 0.0, 1.0)
    assert rec.spans() == []
    monkeypatch.delenv("KEYSTONE_FLIGHT_RECORDER")
    reset_flight_recorder()
    assert flight_recorder().enabled


def test_span_context_records_on_raise():
    rec = FlightRecorder(capacity=8, enabled=True)
    with pytest.raises(ValueError):
        with rec.span("doomed", "test"):
            raise ValueError("boom")
    assert [s.name for s in rec.spans()] == ["doomed"]


# -- span links: seq / parent / root -------------------------------------------

def _by_name(rec):
    return {s.name: s for s in rec.spans()}


def test_span_links_under_nesting():
    rec = FlightRecorder(capacity=16, enabled=True)
    with rec.span("a", "test"):
        with rec.span("b", "test"):
            with rec.span("c", "test"):
                pass
        rec.record("late", "test", time.perf_counter(), 0.0)  # after the fact
    with rec.span("d", "test") as args:
        args["rows"] = 3  # ``with`` yields the span's args
    s = _by_name(rec)
    assert len({x.seq for x in s.values()}) == 5
    assert s["a"].seq < s["b"].seq < s["c"].seq < s["late"].seq < s["d"].seq
    assert (s["a"].parent, s["a"].root) == (None, s["a"].seq)
    assert (s["b"].parent, s["b"].root) == (s["a"].seq, s["a"].seq)
    assert (s["c"].parent, s["c"].root) == (s["b"].seq, s["a"].seq)
    assert (s["late"].parent, s["late"].root) == (s["a"].seq, s["a"].seq)
    assert (s["d"].parent, s["d"].root) == (None, s["d"].seq)
    assert s["d"].args == {"rows": 3}
    # the chrome export carries the links
    blob = json.loads(rec.to_chrome_json())
    exported = {e["name"]: e["args"] for e in blob["traceEvents"]
                if e.get("ph") == "X"}
    assert exported["c"]["parent"] == s["b"].seq
    assert exported["a"]["parent"] is None and exported["a"]["seq"] == s["a"].seq


def test_span_links_across_two_threads():
    """Each thread has its own stack: a span opened on a worker while
    the main thread holds one open is no child of it."""
    rec = FlightRecorder(capacity=16, enabled=True)
    inside = threading.Event()
    done = threading.Event()

    def worker():
        with rec.span("w_outer", "test"):
            with rec.span("w_inner", "test"):
                inside.set()
                assert done.wait(10)

    t = threading.Thread(target=worker)
    with rec.span("m_outer", "test"):
        t.start()
        assert inside.wait(10)
        with rec.span("m_inner", "test"):
            pass
        done.set()
        t.join(10)
        assert not t.is_alive()
    s = _by_name(rec)
    assert s["w_outer"].parent is None and s["w_outer"].root == s["w_outer"].seq
    assert s["w_inner"].parent == s["w_outer"].seq
    assert s["m_inner"].parent == s["m_outer"].seq
    assert s["m_inner"].root == s["m_outer"].seq
    assert s["w_inner"].tid != s["m_inner"].tid
    assert len({x.seq for x in s.values()}) == 4


def test_span_links_through_an_exception():
    """A block that raises still pops its span: the next span on the
    thread is no child of the dead one."""
    rec = FlightRecorder(capacity=16, enabled=True)
    with rec.span("outer", "test"):
        with pytest.raises(ValueError):
            with rec.span("doomed", "test"):
                raise ValueError("boom")
        with rec.span("next", "test"):
            pass
    s = _by_name(rec)
    assert s["doomed"].parent == s["outer"].seq
    assert s["next"].parent == s["outer"].seq
    assert rec._stack() == []


def test_self_time_is_duration_minus_children():
    rec = FlightRecorder(capacity=16, enabled=True)
    with rec.span("parent", "test"):
        with rec.span("child1", "test"):
            time.sleep(0.02)
        time.sleep(0.01)
        with rec.span("child2", "test"):
            time.sleep(0.03)
    spans = rec.spans()
    s = {x.name: x for x in spans}
    children = sum(x.dur_s for x in spans if x.parent == s["parent"].seq)
    assert children == s["child1"].dur_s + s["child2"].dur_s
    self_s = s["parent"].dur_s - children
    assert 0.01 <= self_s < s["parent"].dur_s - 0.05 + 1e-9
    assert self_s < 0.03  # the two sleeps inside children are taken out


def test_disabled_recorder_enters_no_annotation(monkeypatch):
    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("annotation entered with the recorder off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    rec = FlightRecorder(capacity=8, enabled=False)
    with rec.span("a", "test") as args:
        args["x"] = 1
        with rec.span("b", "test"):
            pass
    assert rec.spans() == [] and rec.total_recorded == 0
    assert rec._stack() == [] and rec._annotation is None


def test_span_categories_of_the_tree_are_catalogued():
    """Every literal category at a span site is listed once in
    ``observability/names.py``."""
    import ast
    import pathlib

    import keystone_tpu
    from keystone_tpu.observability.names import SPAN_CATEGORIES

    sites = {"flight_span", "record_span", "record_instant", "span",
             "record"}
    seen = set()
    root = pathlib.Path(keystone_tpu.__file__).parent
    for path in root.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call) or len(node.args) < 2:
                continue
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else getattr(fn, "attr", "")
            cat = node.args[1]
            if (name in sites and isinstance(cat, ast.Constant)
                    and isinstance(cat.value, str)
                    and isinstance(node.args[0], (ast.Constant, ast.JoinedStr,
                                                  ast.BinOp))):
                seen.add(cat.value)
    assert {"dag", "solve", "ingest", "wait", "eval"} <= seen
    assert seen <= SPAN_CATEGORIES, seen - SPAN_CATEGORIES


# -- chrome-trace export -----------------------------------------------------

def test_chrome_trace_roundtrips_with_nonoverlapping_lanes():
    """Overlapping spans recorded on ONE thread (the nested-executor
    shape) must come back on separate sub-lanes, each lane strictly
    non-overlapping, through a full json round-trip."""
    rec = FlightRecorder(capacity=64, enabled=True)
    t0 = time.perf_counter()
    rec.record("parent", "node", t0, 1.0)        # [0, 1]
    rec.record("child", "node", t0 + 0.2, 0.5)   # nested inside parent
    rec.record("next", "node", t0 + 1.5, 0.5)    # disjoint: same lane ok
    rec.record_instant("marker", "resilience", args={"k": "v"})
    blob = json.loads(rec.to_chrome_json())
    lanes = _nonoverlap_per_lane(blob)
    assert len(lanes) == 2  # parent+next on lane 0, child overflowed
    names = {e["name"] for e in blob["traceEvents"]}
    assert {"parent", "child", "next", "marker"} <= names
    # thread metadata names every lane, nested ones marked as such
    th_meta = [e for e in blob["traceEvents"]
               if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert len(th_meta) == 2
    assert any("(nested 1)" in e["args"]["name"] for e in th_meta)


def test_chrome_trace_multi_thread_lanes():
    rec = FlightRecorder(capacity=64, enabled=True)

    def worker():
        rec.record("w", "test", time.perf_counter(), 0.01)

    t = threading.Thread(target=worker, name="side-thread")
    t.start()
    t.join()
    rec.record("m", "test", time.perf_counter(), 0.01)
    blob = rec.to_chrome_trace()
    lane_names = {e["args"]["name"] for e in blob["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "side-thread" in lane_names
    assert any("MainThread" in n for n in lane_names)


def test_write_trace_artifact_dispatch(tmp_path):
    from keystone_tpu.observability import PipelineTrace

    rec = flight_recorder()
    rec.record("x", "test", time.perf_counter(), 0.01)
    perfetto = tmp_path / "run.perfetto.json"
    assert write_trace_artifact(str(perfetto)) == "perfetto"
    blob = json.loads(perfetto.read_text())
    assert any(e.get("name") == "x" for e in blob["traceEvents"])
    with PipelineTrace("t") as tr:
        pass
    plain = tmp_path / "trace.json"
    assert write_trace_artifact(str(plain), tr) == "trace"
    assert json.loads(plain.read_text())["name"] == "t"
    with pytest.raises(ValueError):
        write_trace_artifact(str(tmp_path / "other.json"))  # needs a trace


# -- streamed run feeds the lanes -------------------------------------------

def test_streamed_fit_produces_ingest_h2d_compute_lanes(mesh8):
    """The acceptance shape: a streamed fit leaves stage spans on the
    prefetch thread, h2d spans on the pool lanes, accumulate spans on
    the consumer — distinct lanes in the export, non-overlapping each."""
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming

    rng = np.random.RandomState(0)
    X = rng.randn(256, 16).astype(np.float32)
    L = rng.randn(256, 4).astype(np.float32)
    stream = StreamingDataset.from_numpy(
        X, chunk_size=64, mesh=mesh8, tag="lane-test")
    fit_streaming(LinearMapEstimator(lam=0.1), stream, L)
    rec = flight_recorder()
    cats = {s.cat for s in rec.spans()}
    assert {"ingest", "compute"} <= cats
    by_cat_thread = {(s.cat, s.thread) for s in rec.spans()}
    # stage spans ride the prefetch thread, accumulate the main thread
    assert any(c == "ingest" and "prefetch" in t
               for c, t in by_cat_thread)
    assert any(c == "compute" and "prefetch" not in t
               for c, t in by_cat_thread)
    blob = json.loads(rec.to_chrome_json())
    _nonoverlap_per_lane(blob)
    # the valid-Chrome-trace contract:
    # top-level traceEvents, complete events with ts/dur, metadata names
    assert isinstance(blob["traceEvents"], list)
    assert blob["displayTimeUnit"] == "ms"


def test_contended_traced_lock_feeds_recorder():
    from keystone_tpu.utils.guarded import TracedLock

    lock = TracedLock("timeline.contention")
    release = threading.Event()

    def holder():
        with lock:
            release.wait(2.0)

    t = threading.Thread(target=holder)
    t.start()
    time.sleep(0.05)  # let the holder take it
    release_timer = threading.Timer(0.1, release.set)
    release_timer.start()
    with lock:  # contended: records a span on this (losing) thread
        pass
    t.join()
    spans = [s for s in flight_recorder().spans()
             if s.cat == "lock" and "timeline.contention" in s.name]
    assert spans and spans[0].dur_s > 0


# -- sampler ----------------------------------------------------------------

def test_sampler_sample_once_records_probes_and_gauges():
    reg = MetricsRegistry.get_or_create()
    reg.gauge("streaming.prefetch_occupancy").set(2.0)
    sampler = TelemetrySampler(interval_s=0.05)
    values = sampler.sample_once()
    assert values["process.rss_bytes"] > 0
    assert "h2d.pool_queue_depth" in values
    assert values["streaming.prefetch_occupancy"] == 2.0
    # probe values published back as gauges -> scrapeable
    assert reg.gauge("process.rss_bytes").value > 0
    rss = sampler.series("process.rss_bytes")
    assert len(rss) == 1 and rss[0][1] > 0


def test_sampler_series_is_bounded():
    sampler = TelemetrySampler(interval_s=0.01, capacity=5)
    for _ in range(12):
        sampler.sample_once()
    for name in sampler.series_names():
        assert len(sampler.series(name)) <= 5


def test_sampler_start_stop_idempotent_and_restartable():
    sampler = TelemetrySampler(interval_s=0.01)
    assert not sampler.running
    sampler.stop()          # stop before start: no-op
    sampler.start()
    first = sampler._thread
    sampler.start()         # idempotent: same thread
    assert sampler._thread is first and sampler.running
    deadline = time.monotonic() + 5.0
    while not sampler.series_names() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sampler.series_names(), "sampler thread never sampled"
    sampler.stop()
    sampler.stop()          # idempotent
    assert not sampler.running
    sampler.start()         # restartable
    assert sampler.running
    sampler.stop()


def test_sampler_broken_probe_is_skipped():
    sampler = TelemetrySampler(interval_s=0.01)
    sampler.add_probe("broken.probe", lambda: 1 / 0)
    values = sampler.sample_once()
    assert "broken.probe" not in values
    assert "process.rss_bytes" in values  # the rest still sampled


def test_sampler_validates_args():
    with pytest.raises(ValueError):
        TelemetrySampler(interval_s=0)
    with pytest.raises(ValueError):
        TelemetrySampler(capacity=0)


def test_sampler_racing_starts_leave_one_thread():
    # regression: gating start() on is_alive() saw a created-but-unstarted
    # thread as "not running" and spawned a second, unstoppable sampler
    sampler = TelemetrySampler(interval_s=0.05)
    barrier = threading.Barrier(8)

    def go():
        barrier.wait()
        sampler.start()

    workers = [threading.Thread(target=go) for _ in range(8)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    alive = [t for t in threading.enumerate()
             if t.name == "keystone-telemetry-sampler"]
    sampler.stop()
    assert len(alive) == 1
    deadline = time.monotonic() + 5.0
    while alive[0].is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not alive[0].is_alive(), "stop() left a sampler thread behind"


# -- prometheus exposition ---------------------------------------------------

def test_to_prometheus_exposition_format():
    reg = MetricsRegistry.get_or_create()
    reg.counter("streaming.chunks_total").inc(3)
    reg.gauge("streaming.prefetch_occupancy").set(1.5)
    h = reg.histogram("streaming.ingest_stall_s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = reg.to_prometheus()
    assert "# TYPE keystone_streaming_chunks_total_total counter" in text
    assert "keystone_streaming_chunks_total_total 3" in text
    assert "keystone_streaming_prefetch_occupancy 1.5" in text
    assert "# TYPE keystone_streaming_ingest_stall_s summary" in text
    assert 'keystone_streaming_ingest_stall_s{quantile="0.5"}' in text
    assert "keystone_streaming_ingest_stall_s_count 3" in text
    # sanitized charset: no dots survive
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert "." not in line.split("{")[0].split(" ")[0]


def test_serve_metrics_endpoint():
    reg = MetricsRegistry.get_or_create()
    reg.counter("streaming.chunks_total").inc()
    server = serve_metrics(port=0)
    try:
        base = f"http://127.0.0.1:{server.server_port}"
        with urllib.request.urlopen(base + "/metrics", timeout=5) as resp:
            assert resp.status == 200
            body = resp.read().decode()
        assert "keystone_streaming_chunks_total_total" in body
        with urllib.request.urlopen(base + "/healthz", timeout=5) as resp:
            assert resp.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=5)
    finally:
        server.shutdown()
        server.server_close()


def test_serve_metrics_shutdown_releases_port():
    # regression: plain ThreadingHTTPServer.shutdown() left the listening
    # socket bound, so a same-port restart raised EADDRINUSE
    server = serve_metrics(port=0)
    port = server.server_port
    server.shutdown()
    server2 = serve_metrics(port=port)
    try:
        url = f"http://127.0.0.1:{port}/healthz"
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert resp.read() == b"ok\n"
    finally:
        server2.shutdown()


# -- post-mortems ------------------------------------------------------------

def test_dump_postmortem_contents(tmp_path, monkeypatch):
    monkeypatch.setenv("KEYSTONE_POSTMORTEM_DIR", str(tmp_path))
    from keystone_tpu.observability.postmortem import dump_postmortem

    flight_recorder().record("evidence", "test", time.perf_counter(), 0.1)
    MetricsRegistry.get_or_create().counter("streaming.chunks_total").inc()
    path = dump_postmortem("unit_test", {"chunk": 7})
    assert path is not None
    blob = json.loads(open(path).read())
    assert blob["reason"] == "unit_test"
    assert blob["context"]["chunk"] == 7
    assert blob["metrics"]["counters"]["streaming.chunks_total"] == 1
    names = {e.get("name") for e in blob["flight_recorder"]["traceEvents"]}
    assert "evidence" in names


def test_postmortem_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv("KEYSTONE_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("KEYSTONE_POSTMORTEM", "0")
    from keystone_tpu.observability.postmortem import dump_postmortem

    assert dump_postmortem("nope") is None
    assert list(tmp_path.iterdir()) == []


def test_retry_exhausted_names_postmortem(tmp_path, monkeypatch):
    monkeypatch.setenv("KEYSTONE_POSTMORTEM_DIR", str(tmp_path))
    from keystone_tpu.resilience.retry import (
        RetryExhaustedError,
        RetryPolicy,
        TransientError,
    )

    policy = RetryPolicy(max_attempts=2, backoff_s=0.001)

    def always_fails():
        raise TransientError("flaky disk")

    with pytest.raises(RetryExhaustedError) as exc_info:
        policy.call(always_fails, site="test.site")
    exc = exc_info.value
    assert exc.postmortem_path is not None
    assert f"[post-mortem: {exc.postmortem_path}]" in str(exc)
    blob = json.loads(open(exc.postmortem_path).read())
    assert blob["reason"] == "retry_exhausted"
    assert blob["context"]["site"] == "test.site"
    # the retry instants are in the dumped timeline
    names = [e.get("name") for e in blob["flight_recorder"]["traceEvents"]]
    assert "retry" in names


def test_ingest_timeout_names_postmortem(tmp_path, monkeypatch, mesh8):
    monkeypatch.setenv("KEYSTONE_POSTMORTEM_DIR", str(tmp_path))
    from keystone_tpu.parallel.streaming import StreamingDataset
    from keystone_tpu.resilience.retry import IngestTimeoutError

    block = threading.Event()

    def hung_source():
        yield np.ones((8, 4), np.float32)
        block.wait(30)  # hangs past the stall deadline
        yield np.ones((8, 4), np.float32)

    stream = StreamingDataset(
        lambda: hung_source(), chunk_size=8, mesh=mesh8,
        stall_timeout_s=0.3, tag="hung")
    with pytest.raises(IngestTimeoutError) as exc_info:
        for _ in stream.chunks():
            pass
    block.set()
    exc = exc_info.value
    assert exc.postmortem_path is not None
    assert "[post-mortem:" in str(exc)
    blob = json.loads(open(exc.postmortem_path).read())
    assert blob["reason"] == "ingest_timeout"
    assert blob["context"]["reason"] == "stall_deadline"


def test_hbm_budget_memoryerror_names_postmortem(tmp_path, monkeypatch,
                                                 mesh8):
    monkeypatch.setenv("KEYSTONE_POSTMORTEM_DIR", str(tmp_path))
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming

    X = np.random.RandomState(0).randn(64, 16).astype(np.float32)
    L = np.random.RandomState(1).randn(64, 2).astype(np.float32)
    stream = StreamingDataset.from_numpy(
        X, chunk_size=16, mesh=mesh8, tag="tiny-budget")
    with pytest.raises(MemoryError) as exc_info:
        fit_streaming(LinearMapEstimator(lam=0.1), stream, L, hbm_budget=1.0)
    exc = exc_info.value
    assert exc.postmortem_path is not None
    assert "[post-mortem:" in str(exc)
    blob = json.loads(open(exc.postmortem_path).read())
    assert blob["reason"] == "hbm_budget"


def test_postmortem_failure_never_masks_the_crash(tmp_path, monkeypatch):
    """A dump failure (the target dir path is blocked by a FILE, so
    mkdir cannot succeed — even as root) leaves the exception intact
    with no path attached — evidence collection must not mask the
    failure."""
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where the dump dir should be")
    monkeypatch.setenv("KEYSTONE_POSTMORTEM_DIR", str(blocker / "sub"))
    from keystone_tpu.observability.postmortem import attach_postmortem

    exc = attach_postmortem(ValueError("the real failure"), "unit_test")
    assert str(exc) == "the real failure"
    assert exc.postmortem_path is None


# -- streamed-fit gauges the sampler scrapes ---------------------------------

def test_streamed_fit_publishes_residency_and_carry_gauges(mesh8):
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming

    rng = np.random.RandomState(0)
    X = rng.randn(128, 16).astype(np.float32)
    L = rng.randn(128, 4).astype(np.float32)
    stream = StreamingDataset.from_numpy(X, chunk_size=32, mesh=mesh8)
    fit_streaming(LinearMapEstimator(lam=0.1), stream, L)
    reg = MetricsRegistry.get_or_create()
    # carry = Gram (d,d) + cross (d,k) + sums: > d*d*4 bytes
    assert reg.gauge("streaming.carry_bytes").value >= 16 * 16 * 4
    # residency gauge was written (last chunk may have drained to 0,
    # but the gauge must exist and be finite)
    assert "streaming.resident_bytes" in reg.snapshot()["gauges"]
