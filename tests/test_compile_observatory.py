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
