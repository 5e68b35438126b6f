"""Observability layer tests: per-node trace records from the executor,
optimizer decision logs (rules / auto-cache / solver choice), JSON
round-trip, and the zero-overhead-when-disabled contract."""
import json

import numpy as np
import pytest

from keystone_tpu import (
    ArrayDataset,
    Estimator,
    MetricsRegistry,
    Pipeline,
    PipelineTrace,
    Transformer,
    current_trace,
)
from keystone_tpu.observability.trace import NodeRecord, tracing_disabled


class Scale(Transformer):
    def __init__(self, k):
        self.k = k

    def apply(self, x):
        return x * self.k


class AddOne(Transformer):
    def apply(self, x):
        return x + 1


class SumBranches(Transformer):
    def apply(self, xs):
        return xs[0] + xs[1]


class MeanCenterEstimator(Estimator):
    num_fits = 0

    def _fit(self, ds):
        MeanCenterEstimator.num_fits += 1

        class Shift(Transformer):
            def __init__(self, b):
                self.b = np.asarray(b)

            def apply(self, x):
                return x + self.b

        return Shift(-ds.numpy().mean(axis=0))


def data(n=16, d=4, seed=0):
    return np.random.RandomState(seed).rand(n, d).astype(np.float32)


def _estimator_pipeline(ds):
    return AddOne().and_then(MeanCenterEstimator(), ds)


# -- per-node records -----------------------------------------------------


def test_trace_node_set_matches_optimized_graph():
    """Every node of the optimized graph — and nothing else — appears in
    the trace when the sink is fully materialized."""
    x = data()
    # duplicate branches force the CSE rule to fire, so the optimized
    # graph differs from the raw one — the trace must follow the former
    pipe = Pipeline.gather([Scale(2.0), Scale(2.0)]) >> SumBranches()
    with PipelineTrace("t") as tr:
        out = pipe.apply(x)
        result = out.numpy()
    np.testing.assert_allclose(result, x * 4.0, rtol=1e-6)
    optimized_ids = {n.id for n in out._executor.graph.nodes}
    assert tr.node_ids() == optimized_ids
    raw_ids = {n.id for n in out._executor.raw_graph.nodes}
    assert optimized_ids < raw_ids  # CSE actually shrank the graph
    # wall-time accounting is self-time: totals are sane and non-negative
    assert all(r.wall_s >= 0.0 and r.total_s >= r.wall_s for r in tr.nodes)
    assert tr.total_node_wall_s() > 0.0


def test_trace_records_operator_names_and_memory():
    x = data()
    with PipelineTrace() as tr:
        (Scale(3.0) >> AddOne()).apply(x).numpy()
    ops = {r.operator for r in tr.nodes}
    assert "Dataset" in ops
    # dataset-producing nodes carry a real device-memory footprint
    dataset_records = [r for r in tr.nodes if r.kind == "dataset"]
    assert dataset_records
    assert all(r.output_bytes > 0 for r in dataset_records)
    assert all(r.shards >= 1 for r in dataset_records)


def test_trace_records_cache_hit_on_second_apply():
    """The second apply loads the fitted estimator from the prefix state
    (SavedStateLoadRule) — the trace must show it as a cache hit, and
    the optimizer rule log must contain the substitution."""
    MeanCenterEstimator.num_fits = 0
    x = data()
    ds = ArrayDataset.from_numpy(x)
    pipe = _estimator_pipeline(ds)
    with PipelineTrace() as tr:
        pipe.apply(ds).numpy()
        assert not tr.cache_hits()
        pipe.apply(ds).numpy()
    assert MeanCenterEstimator.num_fits == 1
    hits = tr.cache_hits()
    assert hits and any(r.operator == "Saved" for r in hits)
    fired = {e["rule"] for e in tr.optimizer_rules}
    assert "SavedStateLoadRule" in fired


def test_trace_optimizer_rule_entries():
    x = data()
    pipe = Pipeline.gather([Scale(2.0), Scale(2.0)]) >> SumBranches()
    with PipelineTrace() as tr:
        pipe.apply(x).numpy()
    assert len(tr.optimizer_rules) >= 1
    entry = next(e for e in tr.optimizer_rules
                 if e["rule"] == "EquivalentNodeMergeRule")
    assert entry["nodes_before"] > entry["nodes_after"]
    assert entry["wall_s"] >= 0.0
    # the engine also logs the whole optimizer pass
    runs = tr.meta.get("optimizer_runs", [])
    assert runs and runs[0]["optimizer"] == "DefaultOptimizer"
    assert runs[0]["nodes_in"] >= runs[0]["nodes_out"]


def test_trace_json_round_trip():
    x = data()
    ds = ArrayDataset.from_numpy(x)
    pipe = _estimator_pipeline(ds)
    with PipelineTrace("round-trip") as tr:
        pipe.apply(ds).numpy()
        pipe.apply(ds).numpy()
    blob = tr.to_json()
    parsed = json.loads(blob)  # valid JSON
    assert parsed["name"] == "round-trip"
    restored = PipelineTrace.from_json(blob)
    assert restored.name == tr.name
    assert restored.node_ids() == tr.node_ids()
    assert len(restored.cache_hits()) == len(tr.cache_hits())
    assert restored.optimizer_rules == tr.optimizer_rules
    assert restored.to_json() == blob
    # summary renders without raising, and mentions the rule log
    text = tr.summary()
    assert "SavedStateLoadRule" in text and "cached" in text


def test_tracing_disabled_adds_no_entries():
    """With no active trace the executor records nothing — including
    into previously exited traces."""
    x = data()
    ds = ArrayDataset.from_numpy(x)
    with PipelineTrace() as tr:
        pass  # entered and exited before any execution
    assert current_trace() is None
    pipe = _estimator_pipeline(ds)
    pipe.apply(ds).numpy()
    pipe.apply(ds).numpy()
    assert tr.nodes == []
    assert tr.optimizer_rules == []
    assert tr.auto_cache == []
    assert tr.solver_decisions == []


def test_tracing_disabled_context_suppresses_recording():
    x = data()
    with PipelineTrace() as tr:
        with tracing_disabled():
            Scale(2.0)(x).numpy()
        assert current_trace() is None or tr.nodes == []
    assert tr.nodes == []


def test_saved_expression_outlives_its_trace():
    """A lazy fit saved into the prefix state under trace A must not
    write records into A when forced later (trace looked up at call
    time, not captured)."""
    MeanCenterEstimator.num_fits = 0
    x = data()
    ds = ArrayDataset.from_numpy(x)
    pipe = _estimator_pipeline(ds)
    with PipelineTrace() as tr_a:
        lazy = pipe.apply(ds)  # nothing forced inside the trace
    n_before = len(tr_a.nodes)
    lazy.numpy()  # forced OUTSIDE the trace
    assert len(tr_a.nodes) == n_before


# -- optimizer decision logs ----------------------------------------------


def test_auto_cache_report_in_trace(mesh8):
    from keystone_tpu.workflow.graph import Graph
    from keystone_tpu.workflow.operators import DatasetOperator
    from keystone_tpu.workflow.optimizer.auto_cache import AutoCacheRule
    from keystone_tpu.workflow.transformer import transformer

    ds = ArrayDataset.from_numpy(
        np.arange(32, dtype=np.float32).reshape(32, 1), mesh8)
    g = Graph()
    g, src = g.add_node(DatasetOperator(ds), ())
    g, a = g.add_node(transformer(lambda x: x + 1.0), (src,))
    g, b = g.add_node(transformer(lambda x: x * 2.0), (a,))
    g, c = g.add_node(transformer(lambda x: x * 3.0), (a,))
    g, s1 = g.add_sink(b)
    g, s2 = g.add_sink(c)
    with PipelineTrace() as tr:
        AutoCacheRule(AutoCacheRule.GREEDY, max_mem=1e12).apply(g)
    assert len(tr.auto_cache) == 1
    report = tr.auto_cache[0]
    assert report["strategy"] == "greedy"
    assert report["budget_bytes"] == pytest.approx(1e12)
    # the reused node was profiled and selected
    assert report["profiles"], "sampled profiles must be retained"
    assert all(v["ns"] >= 0 and v["mem"] >= 0
               for v in report["profiles"].values())
    assert a.id in report["selected"]
    assert report["estimated_cached_s"] <= report["estimated_uncached_s"]
    # profiling runs must not leak into the per-node record stream
    assert tr.nodes == []


def test_solver_decision_in_trace():
    from keystone_tpu.nodes.learning.least_squares import (
        LeastSquaresEstimator,
    )

    n, d, k = 4096, 32, 3
    sample = ArrayDataset.from_numpy(data(64, d))
    labels = ArrayDataset.from_numpy(data(64, k, seed=1))
    est = LeastSquaresEstimator(lam=0.1)
    with PipelineTrace() as tr:
        choice = est.optimize(sample, labels, n=n, num_machines=1)
    assert choice is not None
    assert len(tr.solver_decisions) == 1
    dec = tr.solver_decisions[0]
    assert (dec["n"], dec["d"], dec["k"]) == (n, d, k)
    assert 0.0 <= dec["sparsity"] <= 1.0
    # every candidate solver's cost estimate is present, and the pick
    # is the argmin
    assert len(dec["costs"]) == 4
    assert dec["chosen"] == min(dec["costs"], key=dec["costs"].get)
    assert dec["provenance"]["source"] in (
        "shipped_defaults", "artifact", "explicit")
    assert set(dec["weights"]) == {
        "cpu_weight", "mem_weight", "network_weight", "lat_weight"}


def test_solver_decision_through_full_pipeline_optimization():
    """End-to-end: a pipeline containing the optimizable estimator,
    executed under a trace, logs both the node-choice splice and the
    cost table behind it."""
    from keystone_tpu.nodes.learning.least_squares import (
        LeastSquaresEstimator,
    )

    x = data(32, 8)
    y = data(32, 2, seed=1)
    ds = ArrayDataset.from_numpy(x)
    labels = ArrayDataset.from_numpy(y)
    pipe = AddOne().and_then(LeastSquaresEstimator(lam=0.1), ds, labels)
    with PipelineTrace() as tr:
        out = pipe.apply(ds)
        np.asarray(out.numpy())
    assert len(tr.solver_decisions) >= 1
    assert len(tr.node_choices) >= 1
    nc = tr.node_choices[0]
    assert nc["optimizable"] == "LeastSquaresEstimator"
    assert nc["chosen"] == tr.solver_decisions[0]["chosen"]
    assert nc["full_n"] == 32


# -- calibration artifact --------------------------------------------------


def test_cost_weights_load_from_calibration_artifact(tmp_path, monkeypatch):
    from keystone_tpu.nodes.learning import least_squares as ls

    artifact = tmp_path / "cost_model_calibration.json"
    artifact.write_text(json.dumps({
        "cpu_weight": 1e-14, "mem_weight": 2e-11,
        "network_weight": 3e-11, "lat_weight": 4e-4,
        "timestamp": "2026-08-03T00:00:00+00:00",
        "hostname": "test-host", "device": "cpu",
    }))
    monkeypatch.setenv(ls.CALIBRATION_ENV, str(artifact))
    ls.clear_calibration_cache()
    try:
        est = ls.LeastSquaresEstimator(lam=0.1)
        assert est.cpu_weight == pytest.approx(1e-14)
        assert est.lat_weight == pytest.approx(4e-4)
        assert est._weight_provenance["source"] == "artifact"
        assert est._weight_provenance["hostname"] == "test-host"
    finally:
        ls.clear_calibration_cache()


def test_cost_weights_fall_back_when_artifact_invalid(tmp_path, monkeypatch):
    from keystone_tpu.nodes.learning import least_squares as ls

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"cpu_weight": -1.0}))  # negative + missing
    monkeypatch.setenv(ls.CALIBRATION_ENV, str(bad))
    ls.clear_calibration_cache()
    try:
        est = ls.LeastSquaresEstimator(lam=0.1)
        assert est.cpu_weight == pytest.approx(ls.DEFAULT_CPU_WEIGHT)
        assert est._weight_provenance["source"] == "shipped_defaults"
    finally:
        ls.clear_calibration_cache()


def test_explicit_weights_mark_provenance():
    from keystone_tpu.nodes.learning.least_squares import (
        LeastSquaresEstimator,
    )

    est = LeastSquaresEstimator(lam=0.1, cpu_weight=1e-12)
    assert est._weight_provenance["source"] == "explicit"
    assert est._weight_provenance["overrides"] == ["cpu_weight"]


def test_xprof_trace_reuses_active_trace(tmp_path):
    """Nesting xprof_trace inside an explicit PipelineTrace must not
    divert records to a throwaway inner trace."""
    from keystone_tpu.observability import xprof_trace

    x = data()
    with PipelineTrace("outer") as tr:
        with xprof_trace(str(tmp_path)) as inner:
            assert inner is tr
            Scale(2.0)(x).numpy()
    assert tr.nodes  # records landed in the outer trace


def test_xprof_trace_without_active_trace_creates_none(tmp_path, monkeypatch):
    """No observer effect: with no trace active, xprof_trace yields None,
    activates no PipelineTrace and so never blocks after a node; the
    capture still gets the node names, from the always-on annotations."""
    from keystone_tpu.observability import xprof_trace
    from keystone_tpu.workflow import executor

    def boom(value):
        raise AssertionError("blocked on the device in an untraced run")

    monkeypatch.setattr(executor, "_block_on_device", boom)
    x = data()
    with xprof_trace(str(tmp_path)) as inner:
        assert inner is None and current_trace() is None
        out = Scale(2.0)(x).numpy()
        assert current_trace() is None
    np.testing.assert_allclose(out, x * 2.0, rtol=1e-6)
    assert list(tmp_path.rglob("*.xplane.pb"))


def _node_spans():
    from keystone_tpu.observability.timeline import flight_recorder

    return [s for s in flight_recorder().spans()
            if s.cat == "dag" and s.name.startswith("node:")]


def test_untraced_run_records_one_node_span_per_forced_node(monkeypatch):
    """Always on, never blocking: an untraced run holds exactly one
    ``dag:node`` span for every lazily computed node that was forced,
    each id a node of the optimized graph, and ``dag:optimize`` with its
    per-batch children; ``_block_on_device`` is never reached."""
    from keystone_tpu.observability.timeline import flight_recorder
    from keystone_tpu.workflow import executor

    def boom(value):
        raise AssertionError("blocked on the device in an untraced run")

    monkeypatch.setattr(executor, "_block_on_device", boom)
    x = data()
    pipe = Pipeline.gather([Scale(2.0), Scale(2.0)]) >> SumBranches()
    out = pipe.apply(x)
    assert _node_spans() == []  # lazy: nothing forced yet
    np.testing.assert_allclose(out.numpy(), x * 4.0, rtol=1e-6)
    spans = _node_spans()
    ids = [s.args["node_id"] for s in spans]
    optimized_ids = {n.id for n in out._executor.graph.nodes}
    assert len(ids) == len(set(ids))          # exactly one span a node
    assert set(ids) <= optimized_ids          # ids of the optimized graph
    assert len(ids) == len(optimized_ids) - 1  # all but the eager Dataset
    assert all(s.name.endswith(f"#{s.args['node_id']}") for s in spans)
    # forcing again computes nothing, so records nothing
    out.numpy()
    assert len(_node_spans()) == len(ids)
    # nested under the sink's span: one root, every other node a child
    by_seq = {s.seq: s for s in spans}
    roots = [s for s in spans if s.parent not in by_seq]
    assert len(roots) == 1 and all(s.root == roots[0].root for s in spans)
    every = flight_recorder().spans()
    opt = [s for s in every if (s.cat, s.name) == ("dag", "optimize")]
    assert len(opt) == 1
    assert opt[0].args["nodes_before"] > opt[0].args["nodes_after"]  # CSE
    rules = [s for s in every if s.name.startswith("rules:")]
    assert rules and all(s.parent == opt[0].seq for s in rules)
    assert {(s.cat, s.name) for s in every} >= {("ingest", "h2d"),
                                                ("wait", "d2h")}


def test_estimator_fit_records_one_solve_span():
    from keystone_tpu.observability.timeline import flight_recorder

    x = data()
    _estimator_pipeline(x).apply(x).numpy()
    fits = [s for s in flight_recorder().spans() if s.cat == "solve"]
    assert [s.name for s in fits] == ["fit:MeanCenterEstimator"]
    nodes = {s.seq: s for s in _node_spans()}
    assert fits[0].parent in nodes  # inside its estimator node's span
    # the estimator's own numpy() is a wait inside the fit
    waits = [s for s in flight_recorder().spans()
             if s.cat == "wait" and s.parent == fits[0].seq]
    assert len(waits) == 1 and waits[0].args["nbytes"] == x.nbytes


def test_pipeline_trace_run_keeps_node_records_and_blocks(monkeypatch):
    """Profile mode is unchanged: the same NodeRecord ids as the
    optimized graph, the blocking sync after every computed node, and
    one flight-recorder span a node (no duplicate)."""
    from keystone_tpu.workflow import executor

    blocked = []
    real = executor._block_on_device
    monkeypatch.setattr(executor, "_block_on_device",
                        lambda v: (blocked.append(1), real(v))[1])
    x = data()
    pipe = Pipeline.gather([Scale(2.0), Scale(2.0)]) >> SumBranches()
    with PipelineTrace("t") as tr:
        out = pipe.apply(x)
        out.numpy()
    optimized_ids = {n.id for n in out._executor.graph.nodes}
    assert tr.node_ids() == optimized_ids
    spans = _node_spans()
    assert len(blocked) == len(spans) == len(optimized_ids) - 1
    assert {s.args["node_id"] for s in spans} <= optimized_ids


def test_evaluate_records_eval_span_with_rows():
    from keystone_tpu.evaluation.multiclass import evaluate_multiclass
    from keystone_tpu.observability.timeline import flight_recorder

    pred = ArrayDataset.from_numpy(np.array([0, 1, 2, 1], np.int32))
    m = evaluate_multiclass(pred, np.array([0, 1, 1, 1]), 3)
    assert m.total_error == 0.25
    (ev,) = [s for s in flight_recorder().spans() if s.cat == "eval"]
    assert ev.name == "evaluate" and ev.args == {"rows": 4}
    (wait,) = [s for s in flight_recorder().spans() if s.cat == "wait"]
    assert wait.parent == ev.seq


def test_h2d_and_d2h_counters_count_padded_bytes():
    reg = MetricsRegistry.get_or_create()
    x = data(n=16, d=4)
    ds = ArrayDataset.from_numpy(x)
    counters = reg.snapshot()["counters"]
    assert counters["ingest.h2d_bytes"] == ds.padded_n * 4 * 4
    ds.numpy()
    assert reg.snapshot()["counters"]["egress.d2h_bytes"] == ds.padded_n * 16


def test_sampled_executions_do_not_inflate_counters():
    """Throwaway executions inside tracing_disabled (optimizer sampling)
    must not count as real executor activity."""
    reg = MetricsRegistry.get_or_create()
    x = data()
    with tracing_disabled():
        Scale(2.0)(x).numpy()
    assert reg.snapshot()["counters"].get("executor.nodes_executed", 0) == 0
    Scale(2.0)(x).numpy()
    assert reg.snapshot()["counters"]["executor.nodes_executed"] > 0


def test_low_agreement_calibration_artifact_rejected(tmp_path, monkeypatch):
    from keystone_tpu.nodes.learning import least_squares as ls

    artifact = tmp_path / "low_agreement.json"
    artifact.write_text(json.dumps({
        "cpu_weight": 1e-14, "mem_weight": 2e-11,
        "network_weight": 3e-11, "lat_weight": 4e-4,
        "agreement": "1/3",  # model mis-ranked most validation shapes
    }))
    monkeypatch.setenv(ls.CALIBRATION_ENV, str(artifact))
    ls.clear_calibration_cache()
    try:
        weights, provenance = ls.load_calibration()
        assert provenance["source"] == "shipped_defaults"
        assert weights["cpu_weight"] == pytest.approx(ls.DEFAULT_CPU_WEIGHT)
    finally:
        ls.clear_calibration_cache()


def test_prefix_hits_counted_without_trace():
    """executor.prefix_hits is an always-on counter (README documents it
    alongside nodes_executed), not a traced-only one."""
    MeanCenterEstimator.num_fits = 0
    reg = MetricsRegistry.get_or_create()
    x = data()
    ds = ArrayDataset.from_numpy(x)
    pipe = _estimator_pipeline(ds)
    pipe.apply(ds).numpy()
    assert reg.snapshot()["counters"].get("executor.prefix_hits", 0) == 0
    pipe.apply(ds).numpy()  # fitted state loaded from the prefix memo
    assert MeanCenterEstimator.num_fits == 1
    assert reg.snapshot()["counters"]["executor.prefix_hits"] >= 1


# -- metrics registry ------------------------------------------------------


def test_metrics_registry_counts_executor_activity():
    reg = MetricsRegistry.get_or_create()
    x = data()
    (Scale(2.0) >> AddOne()).apply(x).numpy()
    snap = reg.snapshot()
    # dataset node + the (map-fused) transform chain
    assert snap["counters"]["executor.nodes_executed"] >= 2


def test_metrics_registry_basics():
    reg = MetricsRegistry.get_or_create()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(7.5)
    reg.histogram("h").observe(1.0)
    reg.histogram("h").observe(3.0)
    with reg.timer("t"):
        pass
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 7.5
    assert snap["histograms"]["h"]["count"] == 2
    assert snap["histograms"]["h"]["mean"] == pytest.approx(2.0)
    assert snap["histograms"]["t"]["count"] == 1
    # process singleton
    assert MetricsRegistry.get_or_create() is reg


def test_node_record_defaults_round_trip():
    rec = NodeRecord(node_id=3, operator="X")
    tr = PipelineTrace("unit")
    tr.record_node(rec)
    restored = PipelineTrace.from_json(tr.to_json())
    assert restored.nodes[0] == rec


def test_steptimer_deprecated_but_functional():
    """PR 8 satellite: StepTimer is a deprecated shim — constructing
    one warns, the API still works, and the MetricsRegistry.timer
    replacement records the same block timing into the histograms."""
    import warnings

    from keystone_tpu.observability import StepTimer

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        timer = StepTimer()
    assert any(issubclass(w.category, DeprecationWarning)
               and "MetricsRegistry" in str(w.message) for w in caught)
    with timer.step("s"):
        pass
    assert timer.timed("t", lambda: 1 + 1) == 2
    assert set(timer.times) == {"s", "t"} and timer.summary()
    # the replacement path
    reg = MetricsRegistry.get_or_create()
    with reg.timer("streaming.ingest_stall_s"):
        pass
    assert reg.snapshot()["histograms"]["streaming.ingest_stall_s"][
        "count"] == 1


def test_steptimer_compat_reexports_still_work():
    """Both import homes keep working (and both warn on construction);
    ``utils.trace`` is the observability layer's pure capture."""
    import warnings

    from keystone_tpu.observability import xprof_trace
    from keystone_tpu.observability.metrics import StepTimer as direct
    from keystone_tpu.utils import StepTimer as via_utils
    from keystone_tpu.utils import trace as via_utils_trace

    assert direct is via_utils
    assert via_utils_trace is xprof_trace
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        via_utils()
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
