"""AutoCacheRule tests (mirrors the reference's AutoCacheRuleSuite:
hand-built graphs + synthetic Profile maps exercise cache selection and
estimation deterministically without real profiling)."""
import numpy as np
import pytest

from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.workflow.common import Cacher
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.optimizer.auto_cache import (
    AutoCacheRule,
    Profile,
    SampleProfile,
    _children_with_multiplicity,
    estimate_cached_run_time,
    generalize_profiles,
    get_runs,
    make_cached_graph,
    profile_graph,
)
from keystone_tpu.workflow.transformer import transformer


def _diamond_graph(mesh):
    """data -> a -> (b, c) -> d ; a is consumed twice."""
    data = ArrayDataset.from_numpy(
        np.arange(32, dtype=np.float32).reshape(32, 1), mesh)
    g = Graph()
    g, src = g.add_node(DatasetOperator(data), ())
    g, a = g.add_node(transformer(lambda x: x + 1.0), (src,))
    g, b = g.add_node(transformer(lambda x: x * 2.0), (a,))
    g, c = g.add_node(transformer(lambda x: x * 3.0), (a,))
    g, d = g.add_node(transformer(lambda x: x[0:1] * 1.0), (b,))
    g, sink1 = g.add_sink(d)
    g, sink2 = g.add_sink(c)
    return g, (src, a, b, c, d)


def test_get_runs_counts_reuse(mesh8):
    g, (src, a, b, c, d) = _diamond_graph(mesh8)
    children = _children_with_multiplicity(g)
    weights = {n: 1 for n in g.nodes}
    runs = get_runs(g, children, frozenset(), weights)
    assert runs[a] == 2  # two consumers
    assert runs[b] == runs[c] == runs[d] == 1
    # caching b and c makes a's count collapse to 2 (each cached child
    # contributes its weight once)
    runs2 = get_runs(g, children, frozenset({b, c}), weights)
    assert runs2[a] == 2


def test_get_runs_weighted(mesh8):
    g, (src, a, b, c, d) = _diamond_graph(mesh8)
    children = _children_with_multiplicity(g)
    weights = {n: 1 for n in g.nodes}
    weights[b] = 5  # e.g. an iterative solver making 5 passes
    runs = get_runs(g, children, frozenset(), weights)
    assert runs[a] == 6  # 5 from b + 1 from c


def test_generalize_profiles_linear():
    samples = [
        SampleProfile(2, Profile(ns=20.0, mem=200.0)),
        SampleProfile(4, Profile(ns=40.0, mem=400.0)),
    ]
    p = generalize_profiles(100, samples)
    assert p.ns == pytest.approx(1000.0, rel=1e-6)
    assert p.mem == pytest.approx(10000.0, rel=1e-6)


def test_estimate_cached_run_time_synthetic(mesh8):
    g, (src, a, b, c, d) = _diamond_graph(mesh8)
    children = _children_with_multiplicity(g)
    profiles = {n: Profile(ns=10.0, mem=1.0) for n in g.nodes}
    t_nocache = estimate_cached_run_time(g, children, frozenset(), profiles)
    t_cache_a = estimate_cached_run_time(g, children, frozenset({a}), profiles)
    assert t_cache_a < t_nocache  # caching the reused node helps


def test_make_cached_graph_inserts_cacher(mesh8):
    g, (src, a, b, c, d) = _diamond_graph(mesh8)
    out = make_cached_graph(g, frozenset({a}))
    cachers = [n for n in out.nodes
               if isinstance(out.get_operator(n), Cacher)]
    assert len(cachers) == 1
    # b and c now consume the cacher, which consumes a
    assert out.get_dependencies(cachers[0]) == (a,)
    for n in (b, c):
        assert out.get_dependencies(n) == (cachers[0],)


def test_aggressive_cache_rule(mesh8):
    g, (src, a, b, c, d) = _diamond_graph(mesh8)
    out = AutoCacheRule(AutoCacheRule.AGGRESSIVE).apply(g)
    cachers = [n for n in out.nodes
               if isinstance(out.get_operator(n), Cacher)]
    assert len(cachers) == 1  # only 'a' is reused


def test_greedy_cache_respects_budget(mesh8):
    g, (src, a, b, c, d) = _diamond_graph(mesh8)
    # zero budget: nothing cached
    out = AutoCacheRule(AutoCacheRule.GREEDY, max_mem=0.0).apply(g)
    assert not [n for n in out.nodes
                if isinstance(out.get_operator(n), Cacher)]
    # generous budget: the reused node gets cached
    out2 = AutoCacheRule(AutoCacheRule.GREEDY, max_mem=1e12).apply(g)
    assert [n for n in out2.nodes
            if isinstance(out2.get_operator(n), Cacher)]


def test_profile_graph_measures_all_nodes(mesh8):
    g, ids = _diamond_graph(mesh8)
    profiles = profile_graph(g, scales=(1, 2))
    assert set(ids) <= set(profiles)
    assert all(p.ns >= 0 and p.mem >= 0 for p in profiles.values())


def test_device_mem_budget_never_guesses_an_accelerators_hbm(monkeypatch):
    """The CPU backend reports no memory stats and plans against a
    nominal size; an accelerator that reports none is an error."""
    import jax

    from keystone_tpu.workflow.optimizer import auto_cache

    assert auto_cache._device_mem_budget() == 0.75 * 8 * (1 << 30)

    class Silent:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return None

    class Reporting(Silent):
        def memory_stats(self):
            return {"bytes_limit": 16 << 30, "bytes_in_use": 4 << 30}

    monkeypatch.setattr(jax, "devices", lambda: [Silent()])
    with pytest.raises(RuntimeError, match="TPU v5 lite"):
        auto_cache._device_mem_budget()
    monkeypatch.setattr(jax, "devices", lambda: [Reporting()])
    assert auto_cache._device_mem_budget() == 0.75 * (12 << 30)
