"""Composition in one pass gives the graphs the step-by-step form gave.

``Graph.connect_graph`` (under ``>>``, ``and_then``, ``bind``,
``bind_datum`` and ``pipeline(data)``) and ``Graph.fan_out`` (under
``Pipeline.gather``) write each entry of their result once. The oracle,
kept here, is the sequence they replaced: ``add_graph``, then
``replace_dependency`` / ``remove_source`` / ``remove_sink`` over the
whole union, once a splice or a branch. EQUAL means ids, dependency
tuples, sources, sinks, the id maps handed back, the iteration order of
the three dictionaries (rules walk them) and the largest id a graph was
told it has.
"""
from contextlib import contextmanager

import numpy as np
import pytest

from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.nodes.util import (
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
)
from keystone_tpu.observability.metrics import MetricsRegistry
from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.pipelines.images.mnist.random_fft import (
    MnistRandomFFTConfig,
    build_featurizer,
)
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.graph_ids import NodeId, SinkId, SourceId
from keystone_tpu.workflow.operators import Operator
from keystone_tpu.workflow.pipeline import (
    GatherTransformerOperator,
    Pipeline,
)
from keystone_tpu.workflow.transformer import Transformer


# -- the oracle: composition step by step, as it was before PR 39 ----------
def stepwise_connect_graph(self, other, splice):
    union, smap, kmap = self.add_graph(other)
    for o_src, my_sink in splice.items():
        new_src = smap.pop(o_src)
        target = self.sink_dependencies[my_sink]
        union = union.replace_dependency(new_src, target).remove_source(new_src)
    for my_sink in set(splice.values()):
        union = union.remove_sink(my_sink)
    return union, smap, kmap


def stepwise_gather(branches):
    g = Graph()
    g, src = g.add_source()
    outs = []
    for b in branches:
        bp = b.to_pipeline()
        g, smap, kmap = g.add_graph(bp._graph)
        g = g.replace_dependency(smap[bp._source], src).remove_source(
            smap[bp._source])
        new_sink = kmap[bp._sink]
        outs.append(g.get_sink_dependency(new_sink))
        g = g.remove_sink(new_sink)
    g, gather_node = g.add_node(GatherTransformerOperator(len(branches)), outs)
    g, sink = g.add_sink(gather_node)
    return Pipeline(g, src, sink)


@contextmanager
def stepwise():
    """Every composition inside goes the oracle's way."""
    connect, gather = Graph.connect_graph, Pipeline.__dict__["gather"]
    Graph.connect_graph = stepwise_connect_graph
    Pipeline.gather = staticmethod(stepwise_gather)
    try:
        yield
    finally:
        Graph.connect_graph, Pipeline.gather = connect, gather


# -- what the cases are made of --------------------------------------------
class T(Transformer):
    def __init__(self, tag):
        self.tag = tag

    def apply(self, x):
        return x


class Op(Operator):
    def __init__(self, tag):
        self.tag = tag


class Parts:
    """One operator a tag, so that both builds of a case hold the SAME
    objects and two graphs can be compared with ``==``."""

    def __init__(self):
        self._made = {}

    def t(self, tag):
        return self._made.setdefault(tag, T(tag))

    def op(self, tag):
        return self._made.setdefault(tag, Op(tag))

    def branch(self, i, length=3):
        pipe = self.t(f"b{i}.0").to_pipeline()
        for j in range(1, length):
            pipe = pipe >> self.t(f"b{i}.{j}")
        return pipe


def two_ended(parts, tag):
    """A graph with two sources and two sinks and holes in its ids, as no
    ``Pipeline`` call makes but ``Graph`` allows."""
    g = Graph()
    g, s1 = g.add_source()
    g, gone = g.add_node(parts.op(f"{tag}.gone"), ())
    g, s2 = g.add_source()
    g, a = g.add_node(parts.op(f"{tag}.a"), (s1, s2))
    g, k1 = g.add_sink(a)
    g, b = g.add_node(parts.op(f"{tag}.b"), (a, s2, s1))
    g, k2 = g.add_sink(b)
    return g.remove_node(gone), (s1, s2), (k1, k2)


def gather_n(n):
    def build(parts):
        gathered = Pipeline.gather([parts.branch(i) for i in range(n)])
        return [gathered, gathered >> parts.t("combine")]
    return build


def gather_unequal(parts):
    branches = [parts.branch(0, 1), parts.branch(1, 5), Pipeline.identity(),
                parts.t("bare"), parts.branch(2, 2), Pipeline.identity()]
    return [Pipeline.gather(branches), Pipeline.gather(branches[2:3]),
            Pipeline.gather([])]


def gather_of_gathers(parts):
    inner = Pipeline.gather([parts.branch(0, 2), parts.branch(1, 1)])
    other = Pipeline.gather([parts.branch(2, 3), Pipeline.identity()])
    outer = Pipeline.gather([
        inner >> parts.t("after"), parts.t("before") >> other,
        parts.branch(3, 2), inner])
    return [inner, other, outer, outer >> parts.t("combine")]


def gather_one_branch_many_times(parts):
    branch = parts.branch(0, 2)
    return [Pipeline.gather([branch] * 5)]


def gather_branches_with_further_ends(parts):
    """Branches that keep a second source and a second sink: those stay
    in the gathered graph and count when the next branch is numbered."""
    out = []
    for src_i, sink_i in ((0, 0), (1, 0), (0, 1), (1, 1)):
        branches = []
        for i in range(4):
            g, sources, sinks = two_ended(parts, f"e{i}")
            branches.append(Pipeline(g, sources[src_i], sinks[sink_i]))
        branches.insert(2, parts.branch(9, 2))
        out.append(Pipeline.gather(branches))
    return out


def chains(parts):
    a, b, c = parts.t("a"), parts.t("b"), parts.t("c")
    long = parts.branch(0, 6)
    return [a >> b, a >> b >> c, a >> (b >> c), long >> long, long >> a,
            a >> long >> (long >> long)]


def identity_on_either_side(parts):
    a, ident = parts.t("a"), Pipeline.identity()
    return [ident >> a, a >> ident, ident >> ident, ident >> a >> ident,
            ident >> Pipeline.gather([ident, a]) >> ident]


def splice_two_sources_onto_one_sink(parts):
    mine, (m1, m2), (k1, k2) = two_ended(parts, "mine")
    other, (o1, o2), _ = two_ended(parts, "other")
    return [mine.connect_graph(other, {o1: k1, o2: k1}),
            mine.connect_graph(other, {o1: k2, o2: k2}),
            parts.branch(0, 2)._graph.connect_graph(
                other, dict.fromkeys((o2, o1), parts.branch(0, 2)._sink))]


def splices_of_every_shape(parts):
    mine, _, (k1, k2) = two_ended(parts, "mine")
    other, (o1, o2), _ = two_ended(parts, "other")
    ident = Pipeline.identity()
    return [mine.connect_graph(other, {}),
            mine.connect_graph(other, {o1: k1}),
            mine.connect_graph(other, {o2: k2}),
            mine.connect_graph(other, {o1: k1, o2: k2}),
            mine.connect_graph(other, {o2: k1, o1: k2}),
            Graph().connect_graph(other, {}),
            mine.connect_graph(Graph(), {}),
            # nothing of other's stays but a sink: the union's largest id
            mine.connect_graph(ident._graph, {ident._source: k2}),
            # and nothing at all: other is one bare source
            mine.connect_graph(Graph().add_source()[0], {SourceId(1): k2})]


def binds(parts):
    rows = np.arange(12, dtype=np.float32).reshape(4, 3)
    a, wide = parts.branch(0, 3), Pipeline.gather(
        [parts.branch(i, 2) for i in range(1, 5)])
    first = a.bind(rows)
    return [first, a.bind_datum(rows[0]), wide(rows), wide(rows[0].tolist()),
            wide.bind(first), (a >> wide).bind_datum(a.bind_datum(rows[0])),
            Pipeline.identity().bind(rows), parts.t("bare")(first)]


def _labeled(rows, seed):
    rng = np.random.RandomState(seed)
    return (ArrayDataset.from_numpy(
                rng.rand(rows, 784).astype(np.float32)),
            ArrayDataset.from_numpy(rng.randint(0, 10, rows).astype(np.int32)))


def mnist_app(branches):
    """What ``MnistRandomFFT.run`` composes in one fit."""
    def build(parts):
        (train, train_labels), (test, _) = _labeled(16, 1), _labeled(8, 2)
        labels = ClassLabelIndicatorsFromIntLabels(10)(train_labels)
        featurizer = build_featurizer(MnistRandomFFTConfig(num_ffts=branches))
        pipeline = featurizer.and_then(
            BlockLeastSquaresEstimator(2048, 1, 0.0), train, labels
        ) >> MaxClassifier()
        return [labels, featurizer, pipeline, pipeline(train), pipeline(test)]
    return build


CASES = {
    "gather_1": gather_n(1),
    "gather_2": gather_n(2),
    "gather_32": gather_n(32),
    "gather_200": gather_n(200),
    "gather_unequal_branches": gather_unequal,
    "gather_of_gathers": gather_of_gathers,
    "gather_one_branch_many_times": gather_one_branch_many_times,
    "gather_branches_with_further_ends": gather_branches_with_further_ends,
    "chains": chains,
    "identity_on_either_side": identity_on_either_side,
    "splice_two_sources_onto_one_sink": splice_two_sources_onto_one_sink,
    "splices_of_every_shape": splices_of_every_shape,
    "bind_and_bind_datum": binds,
    "mnist_app_8": mnist_app(8),
    "mnist_app_200": mnist_app(200),
}


def _graph_of(made):
    """(graph, whatever came with it) of one thing a case returned."""
    if isinstance(made, tuple):  # connect_graph's (union, smap, kmap)
        return made[0], made[1:]
    ends = (made._source, made._sink) if isinstance(made, Pipeline) else (
        made._sink,)
    return made._graph, ends


def _same_operator(new, old):
    # a case's own parts are one object in both builds; what a build
    # makes for itself (a dataset's operator, a gather's, an app's nodes)
    # is made twice, and must be the same kind in the same place
    return new is old or type(new) is type(old)


def assert_equal_graphs(new: Graph, old: Graph):
    assert list(new.operators) == list(old.operators)
    assert list(new.dependencies) == list(old.dependencies)
    assert list(new.sink_dependencies) == list(old.sink_dependencies)
    assert new.dependencies == old.dependencies
    assert new.sink_dependencies == old.sink_dependencies
    assert new.sources == old.sources
    assert isinstance(new.sources, frozenset)
    for n, op in new.operators.items():
        assert _same_operator(op, old.operators[n]), n
        if isinstance(op, (T, Op)):
            assert op is old.operators[n]
    for deps in new.dependencies.values():
        assert type(deps) is tuple
    # what a graph was told of its largest id is what a walk would find
    told = new._max_id
    assert told == old._max_id
    assert told == Graph(new.sources, new.sink_dependencies, new.operators,
                         new.dependencies)._max_id


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_pass_composition_equals_the_stepwise_oracle(case):
    build, parts = CASES[case], Parts()
    made = build(parts)
    with stepwise():
        oracle = build(parts)
    assert len(made) == len(oracle) > 0
    for new, old in zip(made, oracle):
        (new_graph, new_ends), (old_graph, old_ends) = (
            _graph_of(new), _graph_of(old))
        assert new_ends == old_ends  # source and sink, or the id maps
        assert_equal_graphs(new_graph, old_graph)


def test_the_oracle_is_the_other_form_and_the_cases_are_not_vacuous():
    """The patch reaches every composition, and a case whose two builds
    share their parts compares with ``==``."""
    parts = Parts()
    new = gather_n(3)(parts)[1]
    calls = []
    rewrite = Graph.rewrite

    def counting(self, *args, **kwargs):
        calls.append(1)
        return rewrite(self, *args, **kwargs)

    Graph.rewrite = counting
    try:
        assert gather_n(3)(parts)[1]._graph == new._graph
        assert not calls  # one pass renames nothing afterwards
        with stepwise():
            old = gather_n(3)(parts)[1]
        assert len(calls) == 3 + 6 + 1  # a branch, a ``>>``
    finally:
        Graph.rewrite = rewrite
    assert old._graph == new._graph
    assert len(new._graph.operators) == 3 * 3 + 2
    assert NodeId(1) not in new._graph.operators  # ids have holes


def test_a_stage_starts_as_the_three_adding_calls_made_it():
    op = Op("a")
    g = Graph()
    g, src = g.add_source()
    g, node = g.add_node(op, (src,))
    g, sink = g.add_sink(node)
    single, single_src, single_sink = Graph.single(op)
    assert (single_src, single_sink) == (src, sink)
    assert_equal_graphs(single, g)
    stage = T("t").to_pipeline()
    assert (stage._source, stage._sink) == (src, sink)
    assert list(stage._graph.operators) == [node]
    # and a graph goes on from the largest id it was told
    assert g.add_node(op, ())[1] == single.add_node(op, ())[1] == NodeId(4)


def test_a_misnamed_source_or_sink_is_refused_as_before():
    parts = Parts()
    mine, _, (k1, _) = two_ended(parts, "mine")
    other, (o1, _), _ = two_ended(parts, "other")
    for form in (Graph.connect_graph, stepwise_connect_graph):
        with pytest.raises(KeyError):
            form(mine, other, {SourceId(99): k1})
        with pytest.raises(KeyError):
            form(mine, other, {o1: SinkId(99)})
    assert mine == two_ended(parts, "mine")[0]  # and nothing was changed


# -- the counter ------------------------------------------------------------
def _composed_by(build):
    registry = MetricsRegistry.get_or_create()
    entries = registry.counter("dag.compose.entries")
    calls = registry.counter("dag.compose.calls")
    before = entries.value, calls.value
    build(Parts())
    return entries.value - before[0], calls.value - before[1]


def test_a_fits_composition_writes_in_step_with_its_branches():
    """Counted, not timed: 6.25 times the branches write about 6.25 times
    the entries. Step by step every branch copied what was gathered so
    far, and every later ``>>`` and ``bind`` the whole union four times:
    the counter sees only the first of those copies (``add_graph``; the
    renames and removals are not adding calls) and still reads far more
    than 8 times."""
    small, small_calls = _composed_by(mnist_app(32))
    large, large_calls = _composed_by(mnist_app(200))
    assert large <= 8 * small
    assert large_calls <= 8 * small_calls
    # a fit at 200 branches holds 1,211 nodes when it is bound to its
    # rows: a few thousand entries a whole-graph composition, not
    # hundreds of thousands a fit
    assert 5_000 < large < 40_000
    with stepwise():
        small_stepwise, _ = _composed_by(mnist_app(32))
        large_stepwise, _ = _composed_by(mnist_app(200))
    assert large_stepwise > 15 * small_stepwise
    assert large_stepwise > 5 * large
