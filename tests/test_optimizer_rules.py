"""Optimizer rule tests, mirroring the reference's optimizer suites."""
import numpy as np
import pytest

from keystone_tpu import ArrayDataset, Transformer
from keystone_tpu.parallel.dataset import HostDataset
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.expression import DatasetExpression
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    DatumOperator,
    ExpressionOperator,
)
from keystone_tpu.workflow.optimizer.rules import (
    EquivalentNodeMergeRule,
    SavedStateLoadRule,
    UnusedBranchRemovalRule,
)
from keystone_tpu.workflow.prefix import compute_prefix


class T(Transformer):
    def __init__(self, tag):
        self.tag = tag

    def apply(self, x):
        return x


def test_equivalent_node_merge():
    g = Graph()
    g, src = g.add_source()
    g, a1 = g.add_node(T("a"), (src,))
    g, a2 = g.add_node(T("a"), (src,))
    g, b1 = g.add_node(T("b"), (a1,))
    g, b2 = g.add_node(T("b"), (a2,))
    g, s1 = g.add_sink(b1)
    g, s2 = g.add_sink(b2)
    out = g
    # run to fixpoint manually (merging a's makes b's equal)
    for _ in range(5):
        nxt = EquivalentNodeMergeRule().apply(out)
        if nxt == out:
            break
        out = nxt
    assert len(out.nodes) == 2  # one a, one b
    assert out.get_sink_dependency(s1) == out.get_sink_dependency(s2)


def test_merge_requires_equal_params():
    g = Graph()
    g, src = g.add_source()
    g, a1 = g.add_node(T("a"), (src,))
    g, a2 = g.add_node(T("b"), (src,))
    g, s1 = g.add_sink(a1)
    g, s2 = g.add_sink(a2)
    out = EquivalentNodeMergeRule().apply(g)
    assert len(out.nodes) == 2


def test_unused_branch_removal():
    g = Graph()
    g, src = g.add_source()
    g, a = g.add_node(T("a"), (src,))
    g, dead = g.add_node(T("dead"), (src,))
    g, dead2 = g.add_node(T("dead2"), (dead,))
    g, sink = g.add_sink(a)
    out = UnusedBranchRemovalRule().apply(g)
    assert set(out.nodes) == {a}
    assert src in out.sources  # sources are kept


def test_saved_state_load_substitutes_expression():
    env = PipelineEnv.get_or_create()
    g = Graph()
    g, const = g.add_node(DatasetOperator(HostDataset([1.0])), ())
    g, a = g.add_node(T("a"), (const,))
    g, sink = g.add_sink(a)
    prefix = compute_prefix(g, a)
    assert prefix is not None
    saved = HostDataset([42.0])
    env.state[prefix] = DatasetExpression(saved, eager=True)
    out = SavedStateLoadRule().apply(g)
    op = out.get_operator(a)
    assert isinstance(op, ExpressionOperator)
    assert op.expression.get() is saved


def test_prefix_none_below_source():
    g = Graph()
    g, src = g.add_source()
    g, a = g.add_node(T("a"), (src,))
    assert compute_prefix(g, a) is None


def test_prefix_none_below_datum():
    """A datum is known only by the id() of an object nothing keeps
    alive: results below it never enter (or load from) the state table."""
    g = Graph()
    g, c = g.add_node(DatumOperator(np.zeros(3)), ())
    g, a = g.add_node(T("a"), (c,))
    assert compute_prefix(g, c) is None
    assert compute_prefix(g, a) is None


def test_datum_path_through_cacher_is_never_stale():
    """Successive temporary datums reuse each other's addresses; a
    Cacher below them must still answer for the datum it was given
    (the state table used to answer for an earlier datum's id())."""
    import gc

    from keystone_tpu.workflow.common import Cacher

    class Double(Transformer):
        def apply(self, x):
            return 2.0 * x

    pipe = Double() >> Cacher("doubled")
    first = np.array([1.0])
    address = id(first)
    assert float(pipe.apply_datum(first).get()[0]) == 2.0
    del first
    gc.collect()  # the executor that held the datum sits in a cycle
    held = []  # kept alive, so every try is handed a new address
    for _ in range(10_000):  # until the allocator hands that one out again
        later = np.array([5.0])
        if id(later) == address:
            break
        held.append(later)
    else:
        pytest.skip("the allocator never reused the first datum's address")
    assert float(pipe.apply_datum(later).get()[0]) == 10.0


def test_dataset_path_through_cacher_is_never_stale():
    """The dataset flavour of the same hazard (it answered a served
    request with another request's rows on the chip): an untagged
    dataset allocated at a dead one's address is a different dataset."""
    import gc

    from keystone_tpu.workflow.common import Cacher

    class Double(Transformer):
        def apply(self, x):
            return 2.0 * x

    pipe = Double() >> Cacher("doubled")
    for attempt in range(50):  # until the allocator reuses an address
        first = HostDataset([1.0 + attempt])
        address = id(first)
        assert pipe(first).get().collect() == [2.0 + 2.0 * attempt]
        del first
        gc.collect()
        held = []
        for _ in range(500):
            later = HostDataset([-5.0])
            if id(later) == address:
                assert pipe(later).get().collect() == [-10.0]
                return
            held.append(later)
    pytest.skip("the allocator never reused a dead dataset's address")


def test_prefix_stable_across_equal_graphs():
    def build():
        g = Graph()
        # distinct untagged dataset objects -> distinct data identities
        g, c = g.add_node(DatasetOperator(HostDataset([0.0])), ())
        g, a = g.add_node(T("a"), (c,))
        return g, a, c

    g1, a1, c1 = build()
    g2, a2, c2 = build()
    # dataset identity differs -> prefixes differ (bound to data id)
    p1 = compute_prefix(g1, a1)
    p2 = compute_prefix(g2, a2)
    assert p1 != p2
    # but same graph gives same prefix
    assert compute_prefix(g1, a1) == p1
