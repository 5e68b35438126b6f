"""Compile observatory & device-utilization accounting (PR 9).

Covers: compile counting/classification and signature-delta naming,
the warmup fence (runtime recompile detection), `compile:` spans in
the Perfetto export, PipelineTrace compile records + round-trip, the
zero-recompile second-epoch invariant asserted dynamically, AOT
cost/memory capture, MFU/roofline math and the UtilizationWindow,
per-node trace annotation, the plan-vs-XLA cross-check on the real
check apps, the sampler RSS fallback shim, and the device-OOM post-mortem
executable table.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.observability import (
    MetricsRegistry,
    PipelineTrace,
    compile_observatory,
    expect_no_compiles,
    observed_jit,
)
from keystone_tpu.observability.compilelog import (
    executable_table,
    is_device_oom,
    registered_sites,
    watch_jit,
)
from keystone_tpu.observability.timeline import flight_recorder
from keystone_tpu.observability.utilization import (
    DevicePeaks,
    UtilizationWindow,
    annotate_trace,
    device_peaks,
    roofline,
)


def _mm_site(name="obs_mm"):
    """A fresh observed matmul site (new function object => new jit
    cache => a real compile on first call)."""
    return observed_jit(lambda x: x @ x.T, name=name)


# -- observatory core --------------------------------------------------------


def test_first_compile_counted_timed_classified():
    obs = compile_observatory()
    reg = MetricsRegistry.get_or_create()
    count0 = obs.count_total()
    mm = _mm_site()
    mm(jnp.ones((8, 8), jnp.float32))
    recs = [r for r in obs.tail() if r["name"] == "obs_mm"]
    assert recs and recs[-1]["trigger"] == "first-compile"
    assert recs[-1]["wall_s"] > 0.0
    assert obs.count_total() > count0
    assert reg.counter("compile.count").value >= 1
    assert reg.histogram("compile.wall_s").count >= 1


def test_repeat_call_records_nothing():
    obs = compile_observatory()
    mm = _mm_site()
    x = jnp.ones((8, 8), jnp.float32)
    mm(x)
    count1 = obs.count_total()
    mm(x)  # warm executable: no compile, no record
    assert obs.count_total() == count1
    site = mm._keystone_site
    assert site.calls == 2 and site.compiles == 1


def test_signature_change_names_the_delta():
    obs = compile_observatory()
    mm = _mm_site()
    mm(jnp.ones((8, 8), jnp.float32))
    mm(jnp.ones((16, 16), jnp.float32))
    rec = [r for r in obs.tail() if r["name"] == "obs_mm"][-1]
    assert rec["trigger"] == "signature-change"
    assert "float32[8,8]" in rec["delta"]
    assert "float32[16,16]" in rec["delta"]


def test_fence_flags_unexpected_recompile_with_span():
    """The acceptance path in one test: an induced shape-change
    recompile under an armed fence is (a) detected and counted, (b)
    named with its signature delta, (c) visible as a ``compile:`` span
    in the Perfetto export."""
    obs = compile_observatory()
    reg = MetricsRegistry.get_or_create()
    mm = _mm_site(name="fenced_mm")
    mm(jnp.ones((8, 8), jnp.float32))     # warmup, outside the fence
    x16 = jnp.ones((16, 16), jnp.float32)  # staged outside the fence
    unexpected0 = obs.unexpected_total()
    with expect_no_compiles("steady-state"):
        mm(x16)                            # induced recompile
    assert obs.unexpected_total() == unexpected0 + 1
    assert reg.counter("compile.unexpected_total").value >= 1
    rec = obs.unexpected_records()[-1]
    assert rec["name"] == "fenced_mm"
    assert rec["fence"] == "steady-state"
    assert "float32[8,8]" in rec["delta"]
    blob = flight_recorder().to_chrome_trace()
    spans = [e for e in blob["traceEvents"]
             if e.get("cat") == "compile"
             and e.get("name") == "compile:fenced_mm"]
    assert len(spans) >= 2  # first-compile + the unexpected one
    assert all(e.get("dur", 0) > 0 for e in spans)
    assert any(e.get("args", {}).get("unexpected") for e in spans)


def test_fence_nesting_composes():
    obs = compile_observatory()
    obs.arm_fence("outer")
    obs.arm_fence("inner")
    obs.disarm_fence()
    assert obs.fenced
    # disarming the inner fence restores the OUTER label: a compile
    # now must be attributed to "outer", not the dead inner fence
    obs.record(name="late", wall_s=0.01, trigger="retrace")
    assert obs.unexpected_records()[-1]["fence"] == "outer"
    obs.disarm_fence()
    assert not obs.fenced


def test_no_compile_outside_fence_is_not_unexpected():
    obs = compile_observatory()
    mm = _mm_site(name="unfenced_mm")
    mm(jnp.ones((8, 8), jnp.float32))
    recs = [r for r in obs.tail() if r["name"] == "unfenced_mm"]
    assert recs and not recs[-1].get("unexpected")


def test_disabled_observation_is_passthrough(monkeypatch):
    monkeypatch.setenv("KEYSTONE_COMPILE_LOG", "0")
    obs = compile_observatory()
    count0 = obs.count_total()
    mm = _mm_site(name="disabled_mm")
    out = mm(jnp.ones((4, 4), jnp.float32))
    assert out.shape == (4, 4)
    assert obs.count_total() == count0


# -- PipelineTrace integration ----------------------------------------------


def test_trace_records_compiles_and_roundtrips():
    mm = _mm_site(name="traced_mm")
    with PipelineTrace("compiles") as tr:
        mm(jnp.ones((8, 8), jnp.float32))
    assert tr.compile_stats["count"] >= 1
    assert tr.compile_stats["wall_s"] > 0
    names = [e["name"] for e in tr.compiles]
    assert "traced_mm" in names
    tr2 = PipelineTrace.from_json(tr.to_json())
    assert tr2.compile_stats == tr.compile_stats
    assert [e["name"] for e in tr2.compiles] == names
    assert "compiles:" in tr.summary()


def test_legacy_trace_json_without_compiles_loads():
    with PipelineTrace("legacy") as tr:
        pass
    blob = json.loads(tr.to_json())
    blob.pop("compiles", None)
    blob.pop("compile_stats", None)
    tr2 = PipelineTrace.from_json(json.dumps(blob))
    assert tr2.compile_stats["count"] == 0


# -- the zero-recompile invariant, dynamically -------------------------------


def _streamed_epoch(imgs, labels, chunk=64):
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )

    stream = StreamingDataset.from_numpy(
        imgs, chunk_size=chunk, wire_dtype=np.uint8,
        tag="obs-epoch").map_chunks(
            lambda ad: ad.map_batch(
                lambda x: jnp.tanh(x.astype(jnp.float32) / 255.0)))
    return fit_streaming(LinearMapEstimator(lam=0.1), stream, labels)


def test_second_epoch_compiles_nothing():
    """The PR 3 invariant asserted through the observatory (the ci.sh
    recompile gate's tier-1 twin): a second identical streamed fit
    records zero unexpected compiles under an armed fence, and the
    per-fit fence itself saw nothing in either epoch's steady state."""
    rng = np.random.RandomState(0)
    imgs = (rng.rand(256, 48) * 255).astype(np.uint8)
    y = rng.randint(0, 10, 256)
    labels = (-np.ones((256, 10)) + 2.0 * np.eye(10)[y]).astype(np.float32)
    obs = compile_observatory()
    _streamed_epoch(imgs, labels)
    assert obs.unexpected_total() == 0  # steady-state chunks were clean
    before = obs.unexpected_total()
    with expect_no_compiles("second-epoch"):
        _streamed_epoch(imgs, labels)
    assert obs.unexpected_total() - before == 0


def test_streamed_fit_fence_catches_induced_recompile(monkeypatch):
    """A chunk-shape drift mid-fit (the bug class the fence exists
    for) is flagged: accumulate is patched to re-jit a new function
    object per chunk, so chunk 2 compiles under the armed fence."""
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )

    obs = compile_observatory()
    rng = np.random.RandomState(0)
    X = rng.rand(256, 16).astype(np.float32)
    Y = rng.rand(256, 3).astype(np.float32)
    orig = LinearMapEstimator.accumulate

    def recompiling_accumulate(self, carry, chunk, labels):
        # a FRESH watched jit per chunk: jax's trace cache keys on the
        # function object, so every call recompiles — the
        # per-instance-memo bug in miniature
        waste = watch_jit(jax.jit(lambda v: v * 2.0), name="drifting")
        waste(jnp.ones((4,), jnp.float32))
        return orig(self, carry, chunk, labels)

    monkeypatch.setattr(LinearMapEstimator, "accumulate",
                        recompiling_accumulate)
    before = obs.unexpected_total()
    fit_streaming(LinearMapEstimator(lam=0.1),
                  StreamingDataset.from_numpy(X, chunk_size=64),
                  Y)
    flagged = [r for r in obs.unexpected_records()
               if r["name"] == "drifting"]
    assert obs.unexpected_total() > before
    assert flagged and flagged[0]["fence"].startswith("fit_streaming:")


# -- cost capture & utilization ----------------------------------------------


def test_capture_stats_resolves_flops_and_memory():
    mm = _mm_site(name="stats_mm")
    mm(jnp.ones((32, 32), jnp.float32))
    stats = mm._keystone_site.capture_stats()
    assert stats is not None
    assert stats["flops"] > 0
    assert stats["bytes_accessed"] > 0
    assert stats["output_bytes"] == 32 * 32 * 4
    # memoized: second resolve returns the cached dict
    assert mm._keystone_site.capture_stats() is stats


def test_capture_does_not_count_as_workload_compile():
    obs = compile_observatory()
    mm = _mm_site(name="swallow_mm")
    mm(jnp.ones((8, 8), jnp.float32))
    count1 = obs.count_total()
    with expect_no_compiles("capture"):
        mm._keystone_site.capture_stats()  # AOT path, swallowed
    assert obs.count_total() == count1
    assert obs.unexpected_total() == 0


def test_executable_table_lists_called_sites():
    mm = _mm_site(name="table_mm")
    mm(jnp.ones((8, 8), jnp.float32))
    rows = executable_table(capture=True)
    row = [r for r in rows if r["name"] == "table_mm"]
    assert row and row[0]["calls"] == 1 and row[0]["compiles"] == 1
    assert row[0]["stats"]  # capture=True resolved memory/cost stats


def test_device_peaks_catalogue_env_unknown_raises(monkeypatch):
    assert device_peaks("TPU v4").flops_per_s == 275e12
    # a device the catalogue does not know is an error, not the cpu
    # placeholder under another name
    with pytest.raises(ValueError, match="some new chip"):
        device_peaks("some new chip")
    assert device_peaks("cpu").source == "catalogue"
    monkeypatch.setenv("KEYSTONE_PEAK_FLOPS", "1e12")
    p = device_peaks("TPU v4")
    assert p.flops_per_s == 1e12 and p.source == "env"


def test_roofline_verdicts():
    peaks = DevicePeaks("test", 100e12, 1e12, "catalogue")
    # intensity 1000 >> ridge 100 -> compute-bound
    r = roofline(1e12, 1e9, 1.0, peaks=peaks)
    assert r["bound"] == "compute"
    assert r["mfu"] == pytest.approx(0.01)
    # intensity 1 << ridge -> memory-bound
    r = roofline(1e9, 1e9, 1.0, peaks=peaks)
    assert r["bound"] == "memory"
    assert r["membw_util"] == pytest.approx(1e-3)


def test_utilization_window_reports_coverage():
    mm = _mm_site(name="window_mm")
    x = jnp.ones((64, 64), jnp.float32)
    mm(x)  # compile outside the window
    with UtilizationWindow() as uw:
        for _ in range(4):
            mm(x)
    rep = uw.report(n_devices=1)
    assert "window_mm" in rep["covered_sites"]
    assert rep["flops_total"] >= 4 * mm._keystone_site.capture_stats()["flops"] * 0.99
    assert rep["mfu"] > 0
    assert rep["bound"] in ("compute", "memory")
    assert rep["peaks_source"] in ("catalogue", "env")


def test_annotate_trace_backfills_node_mfu():
    """Executor node context attribution -> per-node MFU on the
    finished trace (the --trace-out annotation path)."""
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.workflow.transformer import Transformer

    class MatmulNode(Transformer):
        def apply(self, item):
            return item @ jnp.ones((24, 24), jnp.float32)

    _ = ArrayDataset  # per-item path: the executor wraps the node thunk
    x = np.random.RandomState(0).rand(32, 24).astype(np.float32)
    with PipelineTrace("annot") as tr:
        (MatmulNode() >> MatmulNode()).apply(x).numpy()
    node_compiles = [e for e in tr.compiles
                     if str(e.get("context", "")).startswith("node:")]
    assert node_compiles, "executor did not attribute the compile"
    n = annotate_trace(tr)
    assert n >= 1
    annotated = [r for r in tr.nodes if r.mfu > 0]
    assert annotated and annotated[0].flops > 0


# -- plan vs XLA -------------------------------------------------------------


@pytest.mark.parametrize("app", ["mnist.random_fft", "cifar.random_patch"])
def test_plan_vs_xla_on_check_apps(app):
    """Acceptance: plan_vs_xla reported for every planner-resolved
    node with a per-item program on the CIFAR and MNIST check apps,
    and the two memory models agree to within 2x."""
    from keystone_tpu.analysis.resources import (
        format_xla_verify,
        xla_verify_plan,
    )
    from keystone_tpu.pipelines import resolve_check_app

    target = resolve_check_app(app)()
    report = target.pipeline.check(
        target.input_spec, name=target.name, hbm_budget=16 << 30)
    rows = xla_verify_plan(report.analysis, report.plan)
    assert len(rows) == len(report.plan.entries)
    ok = [r for r in rows if r["status"] == "ok"]
    assert len(ok) >= 3, format_xla_verify(rows, app)
    for r in ok:
        assert r["plan_vs_xla"] is not None
        assert 0.5 <= r["plan_vs_xla"] <= 2.0, (r, app)
    # every row has an explicit status: coverage reported, not assumed
    assert all(r.get("status") for r in rows)


def test_xla_verify_uses_planner_charge_not_element_size():
    """The cross-check validates the PLANNER's per-item charge
    (operator resource_effect overrides included), not a recomputed
    raw element size — a divergence between the two is exactly what
    --xla exists to catch."""
    from keystone_tpu.analysis.resources import xla_verify_plan
    from keystone_tpu.pipelines import resolve_check_app

    target = resolve_check_app("mnist.random_fft")()
    report = target.pipeline.check(target.input_spec, name=target.name)
    baseline = {r["node_id"]: r for r in
                xla_verify_plan(report.analysis, report.plan)}
    ok_id = next(nid for nid, r in baseline.items()
                 if r["status"] == "ok")
    # planner suddenly under-charges this node 10x: the ratio must
    # track the plan's number, proving the plan is what is verified
    for e in report.plan.entries:
        if e["node_id"] == ok_id and e.get("item_nbytes"):
            e["item_nbytes"] = e["item_nbytes"] / 10.0
    skewed = {r["node_id"]: r for r in
              xla_verify_plan(report.analysis, report.plan)}
    assert skewed[ok_id]["plan_vs_xla"] == pytest.approx(
        baseline[ok_id]["plan_vs_xla"] / 10.0, rel=0.01)


def test_xla_verify_swallows_its_own_compiles():
    from keystone_tpu.analysis.resources import xla_verify_plan
    from keystone_tpu.pipelines import resolve_check_app

    obs = compile_observatory()
    target = resolve_check_app("mnist.random_fft")()
    report = target.pipeline.check(target.input_spec, name=target.name)
    count0 = obs.count_total()
    with expect_no_compiles("xla-verify"):
        xla_verify_plan(report.analysis, report.plan)
    assert obs.count_total() == count0
    assert obs.unexpected_total() == 0


# -- sampler RSS fallback (satellite) ----------------------------------------


def test_rss_fallback_uses_getrusage(monkeypatch):
    """/proc/self/statm absent (macOS, some containers) -> the
    unit-normalized getrusage peak-RSS shim answers instead."""
    import builtins

    from keystone_tpu.observability import sampler as sm

    real_open = builtins.open

    def broken_open(path, *a, **kw):
        if path == "/proc/self/statm":
            raise OSError("no procfs")
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", broken_open)
    v = sm._rss_bytes()
    assert v > 0  # ru_maxrss of a live python process is never 0
    # linux getrusage reports KB: the shim must have scaled to bytes
    import resource

    raw = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    import sys

    expect = raw if sys.platform == "darwin" else raw * 1024.0
    assert v == pytest.approx(expect, rel=0.5)


def test_ru_maxrss_unit_shim_darwin(monkeypatch):
    from keystone_tpu.observability import sampler as sm

    class FakeUsage:
        ru_maxrss = 2048

    import resource

    monkeypatch.setattr(resource, "getrusage", lambda who: FakeUsage())
    monkeypatch.setattr("sys.platform", "darwin")
    assert sm._ru_maxrss_bytes() == 2048.0  # darwin reports BYTES
    monkeypatch.setattr("sys.platform", "linux")
    assert sm._ru_maxrss_bytes() == 2048.0 * 1024  # linux reports KB


def test_broken_rss_probe_skipped_not_fatal(monkeypatch):
    """Both probe paths broken -> sample_once skips the probe for the
    tick (the broken-probe contract) and keeps sampling the rest."""
    import builtins
    import resource

    from keystone_tpu.observability.sampler import TelemetrySampler

    real_open = builtins.open

    def broken_open(path, *a, **kw):
        if path == "/proc/self/statm":
            raise OSError("no procfs")
        return real_open(path, *a, **kw)

    def broken_rusage(who):
        raise OSError("no getrusage either")

    monkeypatch.setattr(builtins, "open", broken_open)
    monkeypatch.setattr(resource, "getrusage", broken_rusage)
    s = TelemetrySampler(interval_s=0.05)
    values = s.sample_once()  # must not raise
    assert "process.rss_bytes" not in values


# -- device-OOM post-mortem (satellite) --------------------------------------


def test_is_device_oom_classification():
    assert is_device_oom(MemoryError("x"))
    assert is_device_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"))
    assert is_device_oom(RuntimeError("Allocation failure on device"))
    assert not is_device_oom(ValueError("shapes differ"))


def test_device_oom_postmortem_carries_executable_table(monkeypatch):
    """An XLA allocation failure mid-accumulate routes through
    attach_postmortem with the per-executable memory_analysis table in
    the dump: the artifact names WHICH executables held HBM."""
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )

    # a watched executable with resolvable memory stats must exist so
    # the capture path has something to table
    mm = _mm_site(name="oom_mm")
    mm(jnp.ones((16, 16), jnp.float32))

    def exploding_accumulate(self, carry, chunk, labels):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 137438953472 bytes")  # the monkeypatched allocator

    monkeypatch.setattr(LinearMapEstimator, "accumulate",
                        exploding_accumulate)
    X = np.zeros((128, 8), np.float32)
    Y = np.zeros((128, 2), np.float32)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED") as ei:
        fit_streaming(LinearMapEstimator(lam=0.1),
                      StreamingDataset.from_numpy(X, chunk_size=64), Y)
    path = getattr(ei.value, "postmortem_path", None)
    assert path and os.path.exists(path)
    blob = json.load(open(path))
    assert blob["reason"] == "device_oom"
    assert blob["context"]["phase"] == "accumulate"
    assert blob["compiles"]["count"] >= 1
    rows = {r["name"]: r for r in blob["executables"]}
    assert "oom_mm" in rows
    stats = list(rows["oom_mm"]["stats"].values())
    assert stats and "output_bytes" in stats[0]  # memory_analysis table


# -- what a record is: phases, the persistent cache, the clock (PR 36) --------

def _chol(x):
    return jnp.linalg.cholesky(x @ x.T + 8.0 * jnp.eye(x.shape[0]))


def _compile_fresh(fn, name, owned):
    """One compile of ``fn`` in a fresh observatory with jax's in-memory
    caches dropped, so only the persistent cache can answer: the
    records under ``name``, and whether the two cache counters rose by
    what ALL the records count (making the argument compiles too)."""
    from keystone_tpu.observability.compilelog import (
        compile_context, reset_compile_observatory)

    jax.clear_caches()
    reset_compile_observatory()
    reg = MetricsRegistry.get_or_create()
    counters = [reg.counter("compile.cache_hits"),
                reg.counter("compile.cache_misses")]
    before = [c.value for c in counters]
    x = jnp.ones((8, 8), jnp.float32)
    if owned:
        observed_jit(fn, name=name)(x).block_until_ready()
    else:
        with compile_context(name):
            jax.jit(fn)(x).block_until_ready()
    tail = compile_observatory().tail()
    rose = [c.value - b for c, b in zip(counters, before)]
    assert rose == [sum(r[k] for r in tail)
                    for k in ("cache_hits", "cache_misses")]
    return [r for r in tail if r["name"] == name], rose


PHASES = ("trace_s", "lower_s", "backend_s")


@pytest.mark.parametrize("owned", [True, False], ids=["owned", "unowned"])
def test_a_miss_then_a_hit_against_a_persistent_cache(
        persistent_cache_dir, owned):
    missed, rose = _compile_fresh(_chol, "chol_site", owned)
    assert missed and {r["cache"] for r in missed} == {"miss"}
    assert rose[0] == 0 and rose[1] >= sum(r["cache_misses"] for r in missed)
    assert os.listdir(persistent_cache_dir)
    for r in missed:
        assert r["cache_read_s"] == 0.0 and r["cold_s"] == r["backend_s"] > 0

    hit, rose = _compile_fresh(_chol, "chol_site", owned)
    assert len(hit) == len(missed)
    assert {r["cache"] for r in hit} == {"hit"}
    assert rose[1] == 0 and rose[0] >= sum(r["cache_hits"] for r in hit)
    for r in hit:
        assert 0.0 < r["cache_read_s"] <= r["backend_s"]
        # jax stores whole seconds beside the executable: 0 for a program
        # this small, and never the negative "saved" seconds it reports
        assert r["cold_s"] == 0.0
        assert sum(r[k] for k in PHASES) == r["wall_s"]


@pytest.mark.parametrize("stored_s,read_s,cold_s", [
    (44.0, 1.5, 44.0),     # a costly program: what a cold process pays
    (0.0, 0.25, 0.0),      # compiled in under a second: saved is negative
], ids=["stored_44_s", "stored_0_s"])
def test_the_listener_fed_a_hit_gives_the_stored_compile_time(
        stored_s, read_s, cold_s):
    from keystone_tpu.observability import compilelog

    obs = compile_observatory()
    with compilelog.compile_context("fed_node"):
        compilelog._on_jax_event(compilelog._TRACE_EVENT, 0.5)
        compilelog._on_jax_event(compilelog._LOWER_EVENT, 0.25)
        compilelog._on_jax_plain_event(compilelog._CACHE_HIT_EVENT)
        compilelog._on_jax_event(compilelog._CACHE_SAVED_EVENT,
                                 stored_s - read_s)
        compilelog._on_jax_event(compilelog._CACHE_READ_EVENT, read_s)
        compilelog._on_jax_event("/jax/unrelated/duration", 99.0)
        compilelog._on_jax_event(compilelog._BACKEND_EVENT, read_s + 0.125)
    (r,) = obs.tail()
    assert (r["name"], r["trigger"], r["cache"]) == ("fed_node", "unowned",
                                                     "hit")
    assert r["cold_s"] == pytest.approx(cold_s) and r["cold_s"] >= 0.0
    assert r["cache_read_s"] == read_s and r["backend_s"] == read_s + 0.125
    assert (r["trace_s"], r["lower_s"]) == (0.5, 0.25)
    assert r["wall_s"] == 0.75 + read_s + 0.125 == obs.wall_s_total()
    # fed all at once, every interval ends now: the longest starts first,
    # and the stored compile time, which is no interval, moves nothing
    assert r["t_end"] - r["t_start"] == pytest.approx(
        max(0.5, read_s + 0.125), abs=0.05)
    reg = MetricsRegistry.get_or_create()
    assert reg.counter("compile.cache_hits").value == 1
    assert reg.counter("compile.cache_misses").value == 0


def test_one_record_of_two_programs_counts_both():
    """An observed call that loads two programs, one read and one
    compiled: the record says miss, counts both, and its cold seconds
    are the stored time of the one and the backend seconds of the other."""
    from keystone_tpu.observability import compilelog

    site = compilelog._JitSite("two_programs", None)
    frame = compilelog._Frame(site, "two_programs")
    compilelog._stack().append(frame)
    try:
        compilelog._on_jax_event(compilelog._TRACE_EVENT, 0.25)
        compilelog._on_jax_plain_event(compilelog._CACHE_HIT_EVENT)
        compilelog._on_jax_event(compilelog._CACHE_SAVED_EVENT, 6.5)
        compilelog._on_jax_event(compilelog._CACHE_READ_EVENT, 0.5)
        compilelog._on_jax_event(compilelog._BACKEND_EVENT, 0.75)
        compilelog._on_jax_plain_event(compilelog._CACHE_MISS_EVENT)
        compilelog._on_jax_event(compilelog._BACKEND_EVENT, 3.0)
    finally:
        compilelog._stack().pop()
    got = frame.heard.fields()
    assert (got["cache"], got["cache_hits"], got["cache_misses"]) == (
        "miss", 1, 1)
    assert got["cold_s"] == 7.0 + 3.0 and got["cache_read_s"] == 0.5
    assert frame.heard.programs == 2 and frame.heard.wall_s == 4.0


@pytest.mark.parametrize("owned", [True, False], ids=["owned", "unowned"])
def test_without_a_cache_dir_a_record_says_off(
        no_persistent_cache, owned):
    records, rose = _compile_fresh(lambda x: x * 3.0 + 1.0, "off_site", owned)
    assert records and rose == [0, 0]
    for r in records:
        assert r["cache"] == "off" and r["cache_read_s"] == 0.0
        assert (r["cache_hits"], r["cache_misses"]) == (0, 0)
        assert r["cold_s"] == r["backend_s"] > 0.0
        assert sum(r[k] for k in PHASES) == r["wall_s"]


@pytest.mark.parametrize("owned", [True, False], ids=["owned", "unowned"])
def test_a_record_lies_on_perf_counter_inside_the_span_that_was_open(owned):
    import time

    from keystone_tpu.observability.compilelog import compile_context
    from keystone_tpu.observability.timeline import flight_span

    x = jnp.ones((8, 8), jnp.float32)
    before = time.perf_counter()
    with flight_span("outer", "dag"):
        if owned:
            observed_jit(lambda x: x @ x.T + 2.0, name="timed_site")(x)
        else:
            with compile_context("timed_site"):
                jax.jit(lambda x: x @ x.T + 2.0)(x)
    after = time.perf_counter()
    (r,) = [r for r in compile_observatory().tail()
            if r["name"] == "timed_site"]
    assert all(r[k] > 0.0 for k in PHASES)
    assert sum(r[k] for k in PHASES) == r["wall_s"]
    assert "lambda" in r["program"]   # jax's own name of the program
    spans = {f"{s.cat}:{s.name}": s for s in flight_recorder().spans()}
    outer, compiled = spans["dag:outer"], spans["compile:compile:timed_site"]
    assert before <= outer.start_s <= r["t_start"] <= r["t_end"]
    assert r["t_end"] <= outer.start_s + outer.dur_s <= after
    # the ring's span is linked to the one that was open and carries the
    # record's fields, so an export shows a cache read as what it is
    assert compiled.parent == outer.seq
    assert compiled.start_s == r["t_start"] and compiled.dur_s == r["wall_s"]
    for key in PHASES + ("t_end", "cache", "cache_read_s", "cold_s"):
        assert compiled.args[key] == r[key], key


def test_what_the_benchmark_driver_reads_of_a_record_is_unchanged():
    """``benchmarks/drivers/fit_loop.py`` reads ``count_total()``,
    ``wall_s_total()`` and ``tail()[i]["name" | "wall_s"]``."""
    from keystone_tpu.observability.compilelog import (
        reset_compile_observatory)

    a, b = jnp.ones((8, 8), jnp.float32), jnp.ones((4, 4), jnp.float32)
    reset_compile_observatory()   # making the arguments compiles too
    obs = compile_observatory()
    _mm_site(name="driver_a")(a)
    _mm_site(name="driver_b")(b)
    obs.record(name="by_hand", wall_s=0.25, trigger="retrace")
    tail = obs.tail()
    assert [r["name"] for r in tail] == ["driver_a", "driver_b", "by_hand"]
    assert obs.count_total() == 3
    assert obs.wall_s_total() == pytest.approx(sum(r["wall_s"] for r in tail))
    by_hand = tail[-1]
    assert by_hand["wall_s"] == by_hand["backend_s"] == by_hand["cold_s"] == 0.25
    assert by_hand["cache"] == "off" and by_hand["program"] is None
    assert by_hand["t_end"] - by_hand["t_start"] == pytest.approx(0.25)
    assert obs.snapshot()["tail"][-1] == by_hand


@pytest.mark.parametrize("owned", [True, False], ids=["owned", "unowned"])
def test_a_trace_three_jits_deep_is_counted_once(owned):
    """jax times every jit it traces, the ones an outer trace calls too,
    inside the outer's own seconds: the record holds the outermost."""
    import time

    from keystone_tpu.observability.compilelog import compile_context

    @jax.jit
    def inner2(x):
        time.sleep(0.05)
        return x + 1.0

    @jax.jit
    def inner1(x):
        time.sleep(0.05)
        return inner2(x) * 2.0

    def outer(x):
        time.sleep(0.05)
        return inner1(x) - 3.0

    x = jnp.ones((3,), jnp.float32)
    if owned:
        observed_jit(outer, name="deep_site")(x)
    else:
        with compile_context("deep_site"):
            jax.jit(outer)(x)
    (r,) = [r for r in compile_observatory().tail()
            if r["name"] == "deep_site"]
    # summed, the three traces read 0.05 + 0.10 + 0.15 s
    assert 0.15 <= r["trace_s"] < 0.25
    assert sum(r[k] for k in PHASES) == r["wall_s"]
    assert r["wall_s"] <= r["t_end"] - r["t_start"] + 0.01


T, L, B = "trace", "lower", "backend"
#: fed sequences: ("+", phase) begins one, (phase, seconds) ends one
NESTED = {
    # a lowering rule traces jitted helpers inside the lowering's seconds
    "a_trace_inside_the_lowering": (
        [("+", T), (T, 0.25), ("+", L), ("+", T), (T, 0.125), (L, 0.5),
         ("+", B), (B, 1.0)],
        dict(trace_s=0.25, lower_s=0.5, backend_s=1.0, cold_s=1.0), 1),
    # an eager operation compiles a whole program while the outer traces:
    # it is a program of the record, its seconds are the outer trace's
    "a_program_compiled_inside_the_trace": (
        [("+", T), ("+", T), (T, 0.125), ("+", L), (L, 0.125), ("+", B),
         (B, 0.5), (T, 2.0), ("+", L), (L, 0.25), ("+", B), (B, 4.0)],
        dict(trace_s=2.0, lower_s=0.25, backend_s=4.0, cold_s=4.5), 2),
}


@pytest.mark.parametrize("case", sorted(NESTED))
def test_a_phase_inside_another_phase_is_not_added_twice(case):
    from keystone_tpu.observability import compilelog

    names = {T: compilelog._TRACE_EVENT, L: compilelog._LOWER_EVENT,
             B: compilelog._BACKEND_EVENT}
    fed, want, programs = NESTED[case]
    frame = compilelog._Frame(compilelog._JitSite("nested", None), "nested")
    compilelog._stack().append(frame)
    try:
        for what, value in fed:
            if what == "+":
                compilelog._on_jax_scalar(names[value], 0.0)
            else:
                compilelog._on_jax_event(names[what], value)
    finally:
        compilelog._stack().pop()
    got = frame.heard.fields()
    assert {k: got[k] for k in want} == want
    assert frame.heard.programs == programs
    assert frame.heard.wall_s == sum(want[k] for k in PHASES)
