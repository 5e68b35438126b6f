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


# -- column samples drawn where a pass is made anyway (PR 49) ----------------
# ``workflow/optimizer/column_samples.py``: a ColumnSampler moves in front
# of the column-wise chain it reads, sibling samplers share a pass. Graphs
# only here; what the rewritten nodes compute is held in
# ``tests/test_column_samples.py``.

def _sample_graph(between=(), sibling=True, first_cache=True):
    """``rows >> project >> [cache] >> between.. >> ColumnSampler``: the
    VOC app's shape. The projection is the delegate of a column PCA
    fitted on a sample of the rows; with ``sibling`` that sample is a
    ColumnSampler on the rows themselves, else the rows. An encoder
    reads the chain too, below ``between``."""
    from keystone_tpu.nodes.learning.pca import LocalColumnPCAEstimator
    from keystone_tpu.nodes.stats.sampling import ColumnSampler
    from keystone_tpu.workflow.common import Cacher
    from keystone_tpu.workflow.operators import DelegatingOperator

    g = Graph()
    ids = {}
    g, ids["data"] = g.add_node(DatasetOperator(HostDataset([1.0])), ())
    g, ids["rows"] = g.add_node(T("describe"), (ids["data"],))
    fed = ids["rows"]
    if sibling:
        g, fed = g.add_node(ColumnSampler(5, seed=1), (ids["rows"],))
        ids["first"] = fed
    g, ids["fit"] = g.add_node(LocalColumnPCAEstimator(2), (fed,))
    g, cur = g.add_node(DelegatingOperator(), (ids["fit"], ids["rows"]))
    ids["project"] = cur
    if first_cache:
        g, cur = g.add_node(Cacher(), (cur,))
        ids["cache"] = cur
    g, ids["encode"] = g.add_node(T("encode"), (cur,))
    for i, op in enumerate(between):
        g, cur = g.add_node(op, (cur,))
        ids[f"between{i}"] = cur
    g, ids["second"] = g.add_node(ColumnSampler(4, seed=2), (cur,))
    g, _ = g.add_sink(ids["second"])
    g, _ = g.add_sink(ids["encode"])
    return g, ids


def _column_sample_rules(graph):
    from keystone_tpu.workflow.optimizer.column_samples import (
        ColumnSamplerMoveRule, SiblingSamplerRule)

    moved = ColumnSamplerMoveRule().apply(graph)
    return moved, SiblingSamplerRule().apply(moved)


def test_a_sampler_moves_over_a_column_wise_delegate_and_a_cacher():
    from keystone_tpu.nodes.stats.sampling import (
        ColumnSampleAhead, ColumnSampler, SharedColumnSampler)
    from keystone_tpu.workflow.common import Cacher

    graph, ids = _sample_graph()
    moved, shared = _column_sample_rules(graph)
    op = moved.operators[ids["second"]]
    assert isinstance(op, ColumnSampleAhead) and op.index is None
    assert op.sampler == ColumnSampler(4, seed=2)
    assert [type(c) for c in op.chain] == [Cacher, type(None)]
    # drawn from the rows, mapped by the fit that fed the delegate
    assert moved.dependencies[ids["second"]] == (ids["rows"], ids["fit"])
    # the encoder still reads the cache behind the projection
    assert moved.dependencies[ids["encode"]] == (ids["cache"],)
    assert moved.dependencies[ids["cache"]] == (ids["project"],)
    # and the two that now read the rows share a pass: no node more
    first, second = (shared.operators[ids[k]] for k in ("first", "second"))
    assert isinstance(first, SharedColumnSampler) and first.serves == 2
    assert first.samplers == (ColumnSampler(5, seed=1),
                              ColumnSampler(4, seed=2))
    assert isinstance(second, ColumnSampleAhead) and second.index == 1
    assert second.chain == op.chain
    assert shared.dependencies[ids["second"]] == (ids["first"], ids["fit"])
    assert shared.operators.keys() == graph.operators.keys()
    # a second application finds nothing to do
    assert _column_sample_rules(shared) == (shared, shared)
    assert _column_sample_rules(shared)[1] is shared


def test_what_the_state_table_is_asked_is_what_the_pipeline_wrote():
    """Fits are saved under the optimized graph's prefixes and looked up
    under the raw graph's: the moved sampler, the shared one and
    whatever reads them keep the prefixes they had."""
    graph, ids = _sample_graph()
    _, shared = _column_sample_rules(graph)
    for n in graph.operators:
        assert compute_prefix(shared, n) == compute_prefix(graph, n), n


class Columnwise(T):
    maps_columns = True


@pytest.mark.parametrize("case", [
    "a_map_that_mixes_columns", "a_delegate_of_another_estimator",
    "a_cache_and_nobody_draws_from_the_rows", "nothing_in_between"])
def test_a_sampler_stays_where_moving_is_unsound_or_adds_a_pass(case):
    from keystone_tpu.nodes.stats.sampling import ColumnSampler
    from keystone_tpu.workflow.operators import DelegatingOperator

    if case == "a_map_that_mixes_columns":
        graph, _ = _sample_graph(between=(T("mixes"),))
    elif case == "a_cache_and_nobody_draws_from_the_rows":
        # leaving the cache would add a pass over the rows
        graph, _ = _sample_graph(sibling=False)
    elif case == "nothing_in_between":
        g = Graph()
        g, data = g.add_node(DatasetOperator(HostDataset([1.0])), ())
        g, rows = g.add_node(T("describe"), (data,))
        g, a = g.add_node(ColumnSampler(5, seed=1), (rows,))
        graph, _ = g.add_sink(a)
    else:
        from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

        g = Graph()
        g, data = g.add_node(DatasetOperator(HostDataset([1.0])), ())
        g, rows = g.add_node(T("describe"), (data,))
        g, first = g.add_node(ColumnSampler(5, seed=1), (rows,))
        g, fit = g.add_node(BlockLeastSquaresEstimator(4, 1, 0.0),
                            (first, data))
        g, applied = g.add_node(DelegatingOperator(), (fit, rows))
        g, second = g.add_node(ColumnSampler(4, seed=2), (applied,))
        graph, _ = g.add_sink(second)
    moved, shared = _column_sample_rules(graph)
    assert moved is graph and shared is graph


def test_a_sampler_crosses_maps_but_no_cache_where_nobody_draws_below():
    """In front of a map always (fewer columns mapped); the cache is
    there to be read. The map the sampler alone read goes."""
    from keystone_tpu.nodes.stats.sampling import ColumnSampleAhead

    cast = Columnwise("cast")
    graph, ids = _sample_graph(between=(cast,), sibling=False)
    moved, shared = _column_sample_rules(graph)
    assert shared is moved                        # nobody to share with
    op = moved.operators[ids["second"]]
    assert isinstance(op, ColumnSampleAhead) and op.chain == (cast,)
    assert moved.dependencies[ids["second"]] == (ids["cache"],)
    assert ids["between0"] not in moved.operators
    assert compute_prefix(moved, ids["second"]) == compute_prefix(
        graph, ids["second"])


def test_a_graph_with_no_column_sampler_is_returned_as_it_came():
    from keystone_tpu.workflow.optimizer.default import DefaultOptimizer

    g = Graph()
    g, src = g.add_source()
    g, a = g.add_node(Columnwise("a"), (src,))
    g, b = g.add_node(T("b"), (a,))
    g, _ = g.add_sink(b)
    assert _column_sample_rules(g) == (g, g)
    moved, shared = _column_sample_rules(g)
    assert moved is g and shared is g
    batch = next(b for b in DefaultOptimizer().batches
                 if b.name == "column samples")
    assert DefaultOptimizer()._run_batch(batch, g, None) == (g, 1)


def test_siblings_written_on_one_node_share_a_pass_and_equal_ones_a_draw():
    from keystone_tpu.nodes.stats.sampling import (
        ColumnSampleAhead, ColumnSampler, SharedColumnSampler)

    g = Graph()
    g, data = g.add_node(DatasetOperator(HostDataset([1.0])), ())
    g, rows = g.add_node(T("describe"), (data,))
    g, a = g.add_node(ColumnSampler(5, seed=1), (rows,))
    g, b = g.add_node(ColumnSampler(7, seed=1), (rows,))
    g, c = g.add_node(ColumnSampler(5, seed=3), (rows,))
    for n in (a, b, c):
        g, _ = g.add_sink(n)
    moved, shared = _column_sample_rules(g)
    assert moved is g
    first = shared.operators[a]
    assert isinstance(first, SharedColumnSampler) and first.serves == 3
    assert [(s.num_cols, s.seed) for s in first.samplers] == [
        (5, 1), (7, 1), (5, 3)]
    for n, index in ((b, 1), (c, 2)):
        op = shared.operators[n]
        assert isinstance(op, ColumnSampleAhead)
        assert (op.index, op.chain) == (index, ())
        assert shared.dependencies[n] == (a,)
        assert compute_prefix(shared, n) == compute_prefix(g, n)


@pytest.mark.parametrize("branch", ["sift", "lcs"])
def test_the_imagenet_app_is_rewritten_by_the_same_rule(branch):
    """``sift_lcs_fv.compute_pca_fisher_branch`` writes the VOC pattern
    with ONE seed and, at its defaults, one count: both samplers pick
    the same columns. After the rules they are one draw in one pass, the
    GMM's the projection of it; the app's file is not edited."""
    from keystone_tpu.nodes.images.extractors import (
        LCSExtractor, SIFTExtractor)
    from keystone_tpu.nodes.stats.sampling import (
        ColumnSampleAhead, SharedColumnSampler)
    from keystone_tpu.pipelines.images.imagenet import sift_lcs_fv as app
    from keystone_tpu.workflow.optimizer.default import DefaultOptimizer
    from keystone_tpu.workflow.pipeline import Pipeline

    rng = np.random.RandomState(0)
    train = HostDataset([rng.rand(40, 48, 3).astype(np.float32)
                         for _ in range(3)])
    config = app.ImageNetSiftLcsFVConfig(desc_dim=4, vocab_size=2)
    prefix = (Pipeline.identity() >> LCSExtractor(4, 16, 6) if branch == "lcs"
              else Pipeline.identity() >> SIFTExtractor(step=8, num_scales=2))
    pipeline = app.compute_pca_fisher_branch(prefix, train, config, 6, 6)
    optimized = DefaultOptimizer().execute(pipeline(train)._graph)
    shared = [op for op in optimized.operators.values()
              if isinstance(op, SharedColumnSampler)]
    ahead = [op for op in optimized.operators.values()
             if isinstance(op, ColumnSampleAhead)]
    assert len(shared) == 1 and len(ahead) == 1
    assert shared[0].serves == 2 and len(shared[0].samplers) == 1
    assert ahead[0].index == 0 and ahead[0].chain == (None,)
