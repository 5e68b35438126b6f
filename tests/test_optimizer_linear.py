"""``DefaultOptimizer`` rewrites a graph in one pass a rule (PR 27).

Parity: on the two benchmark cells' pipelines, the apps' featurizers and
seeded random DAGs the optimized graph EQUALS what the rules it replaced
produce (``tests/optimizer_oracle.py``), node ids included. Complexity:
graphs constructed and rounds run are counted, never timed. Spans: every
``dag:rules:<batch>`` span says how many rounds it took and what it did
to the node count.
"""
import pickle

import numpy as np
import pytest

from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.nodes.util import (
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
)
from keystone_tpu.parallel.dataset import ArrayDataset, HostDataset
from keystone_tpu.workflow.common import Cacher
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.expression import DatasetExpression
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    EstimatorOperator,
    TransformerOperator,
)
from keystone_tpu.workflow.optimizer.default import DefaultOptimizer
from keystone_tpu.workflow.optimizer.fusion import (
    FusedGatherTransformer,
    FusedTransformer,
    GatherFusionRule,
    MapFusionRule,
)
from keystone_tpu.workflow.optimizer.rules import (
    EquivalentNodeMergeRule,
    UnusedBranchRemovalRule,
)
from keystone_tpu.workflow.pipeline import GatherTransformerOperator
from keystone_tpu.workflow.prefix import compute_prefix
from keystone_tpu.workflow.transformer import HostTransformer, Transformer

from tests.optimizer_oracle import (
    OldGatherFusionRule,
    OldMapFusionRule,
    OracleOptimizer,
)


# -- the cells' pipelines, as their apps build them --------------------------

def _labeled(rows, dim, classes, seed):
    rng = np.random.RandomState(seed)
    return LabeledData(
        data=ArrayDataset.from_numpy(rng.rand(rows, dim).astype(np.float32)),
        labels=ArrayDataset.from_numpy(
            rng.randint(0, classes, rows).astype(np.int32)))


def _mnist_pipeline(branches, train):
    from keystone_tpu.pipelines.images.mnist.random_fft import (
        NUM_CLASSES, MnistRandomFFTConfig, build_featurizer)

    config = MnistRandomFFTConfig(num_ffts=branches, block_size=2048)
    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(train.labels)
    return build_featurizer(config).and_then(
        BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
        train.data, labels) >> MaxClassifier()


def _timit_pipeline(branches, train):
    from keystone_tpu.pipelines.speech.timit import (
        TimitConfig, build_featurizer)

    config = TimitConfig(num_cosines=branches, num_cosine_features=64)
    labels = ClassLabelIndicatorsFromIntLabels(4)(train.labels)
    return build_featurizer(config, 20).and_then(
        BlockLeastSquaresEstimator(64, config.num_epochs, config.lam),
        train.data, labels) >> MaxClassifier()


def _save_fits(optimized: Graph) -> None:
    """What executing ``optimized`` leaves in the state table: every
    estimator's lazy fit under its prefix. Nothing is fitted: the rules
    ask whether a prefix is there, never for its value."""
    state = PipelineEnv.get_or_create().state
    for n, op in optimized.operators.items():
        if isinstance(op, EstimatorOperator):
            state[compute_prefix(optimized, n)] = op.execute([])


def _cell_graph(cell, branches, which):
    """The raw graph a fit of ``cell`` hands the optimizer: the training
    bind, or the test evaluation that follows it (whose graph meets the
    training bind's fit in the state table)."""
    if cell == "mnist":
        train, test = _labeled(64, 784, 10, 1), _labeled(16, 784, 10, 2)
        pipeline = _mnist_pipeline(branches, train)
    else:
        train, test = _labeled(64, 20, 4, 1), _labeled(16, 20, 4, 2)
        pipeline = _timit_pipeline(branches, train)
    raw = pipeline(train.data)._graph
    if which == "train":
        return raw
    _save_fits(DefaultOptimizer().execute(raw))
    return pipeline(test.data)._graph


def _assert_parity(graph: Graph) -> Graph:
    new = DefaultOptimizer().execute(graph)
    old = OracleOptimizer().execute(graph)
    assert new.operators.keys() == old.operators.keys()
    assert new == old  # ids, operators, dependencies, sinks, sources
    for n, op in new.operators.items():
        if isinstance(op, (FusedTransformer, FusedGatherTransformer)):
            assert type(old.operators[n]) is type(op)
            assert op._cached_eq_key() == old.operators[n]._cached_eq_key()
    # nothing left for a second pass of the fusion batch
    assert MapFusionRule().apply(new) is new
    assert GatherFusionRule().apply(new) is new
    return new


@pytest.mark.parametrize("which,nodes_after", [("train", 7), ("test", 5)])
@pytest.mark.parametrize("branches", [4, 8, 32, 200])
def test_mnist_cell_graph_equals_the_oracles(branches, which, nodes_after):
    """200 branches is the source's published width: 1,211 nodes."""
    graph = _cell_graph("mnist", branches, which)
    assert len(graph.nodes) == 6 * branches + 11
    assert len(_assert_parity(graph).nodes) == nodes_after


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["materialised", "streamed"])
@pytest.mark.parametrize("which", ["train", "test"])
@pytest.mark.parametrize("branches", [4, 8, 32])
def test_timit_cell_graph_equals_the_oracles(
        branches, which, streamed, monkeypatch):
    """Both forms of the fit: the gather materialised (any device holds
    64 rows) and, on a device made too small for it, the branches handed
    to the solver as the chip does at the cell's size."""
    if streamed:
        from keystone_tpu.analysis import resources

        monkeypatch.setattr(
            resources, "device_memory_bytes", lambda free=False: 1000.0)
    new = _assert_parity(_cell_graph("timit", branches, which))
    labels = sorted(op.label() for op in new.operators.values())
    assert any(lab.startswith("Streamed[") for lab in labels) == (
        streamed and which == "train")


@pytest.mark.parametrize("app", ["mnist", "timit"])
def test_app_featurizer_with_its_source_unbound_equals_the_oracles(app):
    """The apps under ``pipelines/`` that build a pipeline without data:
    a dangling source, so nothing upstream to prune or load."""
    if app == "mnist":
        from keystone_tpu.pipelines.images.mnist.random_fft import (
            MnistRandomFFTConfig, build_featurizer)

        featurizer = build_featurizer(MnistRandomFFTConfig(num_ffts=8))
    else:
        from keystone_tpu.pipelines.speech.timit import (
            TimitConfig, build_featurizer)

        featurizer = build_featurizer(
            TimitConfig(num_cosines=8, num_cosine_features=64), 20)
    new = _assert_parity(featurizer.graph)
    assert len(new.nodes) == 1 and len(new.sources) == 1


# -- seeded random DAGs ------------------------------------------------------

class Map(Transformer):
    """Fusable: the default per-item semantics."""

    def __init__(self, tag):
        self.tag = tag

    def apply(self, x):
        return x


class Host(HostTransformer):
    def __init__(self, tag):
        self.tag = tag

    def apply(self, x):
        return x


class ListKeyed(Transformer):
    """A key that cannot be hashed (a list inside it): CSE compares
    these with one another, fusion does not memoize them."""

    def __init__(self, tag):
        self.tag = tag

    def eq_key(self):
        return (ListKeyed, [self.tag])

    def apply(self, x):
        return x


class Join(TransformerOperator):
    """Two inputs: never fusable, and a reader of two nodes at once."""

    def __init__(self, tag):
        self.tag = tag


def random_dag(seed: int, steps: int = 40) -> Graph:
    """A DAG of what the rules tell apart: fusable chains, host stages,
    cachers, joins, gathers over a common input (host branches and
    repeated branches among them), subtrees built twice, nodes with many
    readers, an unbound source, nodes no sink needs, an operator whose
    key is unhashable, or else a state table that already holds some of
    the graph (odd seeds the one, even seeds the other: a prefix over an
    unhashable key cannot be looked up in the table, before PR 27 or
    after).

    A gather always gets two branches that differ. A gather whose
    branches are ALL one node is the one place where the old rules'
    result hung on ids elsewhere in the graph:
    ``test_gather_of_one_repeated_branch_fuses_the_same_wherever_it_sits``.
    """
    rng = np.random.RandomState(seed)
    g = Graph()
    g, source = g.add_source()
    pool = [source]
    for i in range(2):
        g, d = g.add_node(DatasetOperator(HostDataset([float(i)])), ())
        pool.append(d)

    def pick():
        return pool[rng.randint(len(pool))]

    def unary(tag):
        kind = rng.choice(["map"] * 6 + [
            "host", "cacher", "list" if seed % 2 else "map"])
        if kind == "map":
            return Map(tag)
        if kind == "host":
            return Host(tag)
        if kind == "cacher":
            return Cacher(f"c{tag}")
        return ListKeyed(tag)

    def chain(g, at, tags):
        for tag in tags:
            g, at = g.add_node(unary(int(tag)), (at,))
            pool.append(at)
        return g, at

    for step in range(steps):
        what = rng.choice(["chain", "chain", "twice", "join", "gather"])
        if what == "chain":
            # few tags, so that chains built apart come out equal
            g, _ = chain(g, pick(), rng.randint(0, 4, rng.randint(1, 4)))
        elif what == "twice":
            at, tags = pick(), list(rng.randint(0, 4, rng.randint(1, 4)))
            state = rng.get_state()
            for _ in range(2):
                rng.set_state(state)  # the same operators, drawn again
                g, _ = chain(g, at, tags)
        elif what == "join":
            g, n = g.add_node(Join(int(rng.randint(3))), (pick(), pick()))
            pool.append(n)
        else:
            at, heads = pick(), []
            for b in range(rng.randint(2, 5)):
                # branch b starts with a tag of its own: no two equal
                tags = [100 + b] + list(rng.randint(0, 4, rng.randint(0, 3)))
                if b >= 2 and rng.rand() < 0.4:  # a branch twice
                    heads.append(heads[rng.randint(len(heads))])
                    continue
                g, head = chain(g, at, tags)
                heads.append(head)
            g, n = g.add_node(GatherTransformerOperator(len(heads)), heads)
            pool.append(n)
            if rng.rand() < 0.5:
                g, _ = chain(g, n, rng.randint(0, 4, rng.randint(1, 3)))
    nodes = [p for p in pool if p is not source]
    for n in rng.choice(len(nodes), size=rng.randint(1, 4), replace=False):
        g, _ = g.add_sink(nodes[n])
    # what an earlier run left in the state table
    state = PipelineEnv.get_or_create().state
    memo = {}
    for n in nodes:
        prefix = compute_prefix(g, n, memo)
        if seed % 2 == 0 and prefix is not None and rng.rand() < 0.05:
            state[prefix] = DatasetExpression(HostDataset([0.0]), eager=True)
    return g


@pytest.mark.parametrize("seed", range(24))
def test_random_dag_equals_the_oracles(seed):
    graph = random_dag(seed)
    new = _assert_parity(graph)
    assert len(new.nodes) < len(graph.nodes)
    assert new.sources == graph.sources and new.sinks == graph.sinks


def test_random_dags_hold_what_they_are_meant_to():
    """The generator's promise, counted over its seeds: every kind of
    rewrite fires somewhere, and the unhashable keys are merged too."""
    fired = {"merged": 0, "pruned": 0, "loaded": 0, "chains": 0,
             "gathers": 0, "unhashable_merged": 0, "kept_gathers": 0}
    for seed in range(24):
        PipelineEnv.get_or_create().clear_state()
        g = random_dag(seed)
        pruned = UnusedBranchRemovalRule().apply(g)
        fired["pruned"] += len(g.nodes) - len(pruned.nodes)
        merged = EquivalentNodeMergeRule().apply(g)
        fired["merged"] += len(g.nodes) - len(merged.nodes)
        listed = [n for n, op in g.operators.items()
                  if isinstance(op, ListKeyed)]
        fired["unhashable_merged"] += sum(
            n not in merged.operators for n in listed)
        new = DefaultOptimizer().execute(g)
        for op in new.operators.values():
            fired["loaded"] += op.label() == "Saved"
            fired["chains"] += isinstance(op, FusedTransformer)
            fired["gathers"] += isinstance(op, FusedGatherTransformer)
            fired["kept_gathers"] += isinstance(op, GatherTransformerOperator)
    assert all(fired.values()), fired


def test_one_application_merges_a_whole_cascade_and_keeps_the_lowest_ids():
    """Duplicates whose dependencies are themselves duplicates merge in
    the same application, whatever order the ids come in: here the copy
    with the LOWER id hangs under the higher-numbered parent."""
    g = Graph()
    g, src = g.add_source()
    g, a_low = g.add_node(Map("a"), (src,))
    g, a_high = g.add_node(Map("a"), (src,))
    g, b_low = g.add_node(Map("b"), (a_high,))
    g, b_high = g.add_node(Map("b"), (a_low,))
    g, c = g.add_node(Join("c"), (b_low, b_high))
    g, sink = g.add_sink(c)
    out = EquivalentNodeMergeRule().apply(g)
    assert set(out.nodes) == {a_low, b_low, c}
    assert out.get_dependencies(b_low) == (a_low,)
    assert out.get_dependencies(c) == (b_low, b_low)
    assert EquivalentNodeMergeRule().apply(out) is out


def test_gather_of_one_repeated_branch_fuses_the_same_wherever_it_sits():
    """``a >> gather(b, b)``: the old rules fused one pair and then one
    gather a round, in id order over the WHOLE graph, so whether ``a``
    went into the branch or ahead of the fused gather hung on how many
    unrelated pairs had lower ids. The chain is fused first now, always
    (what the old rules did when the graph held nothing else)."""
    def build(unrelated_pairs):
        g = Graph()
        g, src = g.add_source()
        for i in range(unrelated_pairs):
            g, x = g.add_node(Map(f"x{i}"), (src,))
            g, y = g.add_node(Map(f"y{i}"), (x,))
            g, _ = g.add_sink(y)
        g, a = g.add_node(Map("a"), (src,))
        g, b = g.add_node(Map("b"), (a,))
        g, gather = g.add_node(GatherTransformerOperator(2), (b, b))
        g, _ = g.add_sink(gather)
        return g, gather

    def fuse(graph, rules):
        for _ in range(100):
            before = graph
            for rule in rules:
                graph = rule.apply(graph)
            if graph is before:
                return graph
        raise AssertionError("no fixed point")

    labels = {}
    for unrelated in (0, 2):
        g, gather = build(unrelated)
        for name, rules in (("old", [OldMapFusionRule(), OldGatherFusionRule()]),
                            ("new", [MapFusionRule(), GatherFusionRule()])):
            labels[name, unrelated] = fuse(g, rules).get_operator(
                gather).label()
    assert labels["old", 0] == "FusedGather[Fused[Map >> Map], Fused[Map >> Map]]"
    assert labels["old", 2] == "Fused[Map >> FusedGather[Map, Map]]"
    assert labels["new", 0] == labels["new", 2] == labels["old", 0]


# -- cost, counted and not timed ---------------------------------------------

def _batch_spans():
    from keystone_tpu.observability.timeline import flight_recorder

    return [s for s in flight_recorder().spans()
            if s.cat == "dag" and s.name.startswith("rules:")]


@pytest.mark.parametrize("which", ["train", "test"])
def test_graphs_built_and_rounds_run_do_not_grow_with_the_branches(
        which, monkeypatch):
    """One new ``Graph`` for every application that rewrote, so a pass
    over the MNIST pipeline builds the same few graphs at 32, 64 and 128
    branches (the old rules built one or two a node), and map fusion
    needs 3 rounds at every width (it took 2 a branch)."""
    from keystone_tpu.observability.timeline import reset_flight_recorder

    built = []
    init = Graph.__init__

    def counting_init(self, *args, **kwargs):
        built.append(1)
        init(self, *args, **kwargs)

    counts, rounds = {}, {}
    for branches in (32, 64, 128):
        PipelineEnv.get_or_create().clear_state()
        graph = _cell_graph("mnist", branches, which)
        reset_flight_recorder()
        with monkeypatch.context() as patch:
            patch.setattr(Graph, "__init__", counting_init)
            del built[:]
            optimized = DefaultOptimizer().execute(graph)
            counts[branches] = len(built)
        assert len(optimized.nodes) == (7 if which == "train" else 5)
        rounds[branches] = {s.name: s.args["rounds"] for s in _batch_spans()}
    batches = len(DefaultOptimizer().batches)
    assert counts[32] == counts[64] == counts[128], counts
    assert counts[32] <= 4 * batches, counts
    assert rounds[32] == rounds[64] == rounds[128], rounds
    assert rounds[32]["rules:map fusion"] <= 4
    # a round that merges and one that finds nothing (the test
    # evaluation has nothing to merge once the training branch is pruned)
    assert rounds[32]["rules:CSE"] == (2 if which == "train" else 1)
    assert max(rounds[32].values()) <= 4


def test_bulk_rewrite_is_what_the_single_node_methods_do_one_by_one():
    g = random_dag(3)
    nodes = sorted(g.nodes)
    a, b, c, d = nodes[2], nodes[5], nodes[7], nodes[9]
    one_by_one = (g.set_operator(a, Map("new")).set_dependencies(b, (a, a))
                  .remove_node(c).replace_dependency(d, a))
    at_once = g.rewrite(operators={a: Map("new")}, dependencies={b: (a, a)},
                        remove=[c], rename={d: a})
    assert at_once == one_by_one
    assert list(at_once.operators) == list(one_by_one.operators)  # order too
    assert d not in at_once.consumers and c not in at_once.nodes


def test_a_graph_keeps_what_it_derives_and_pickles_without_it():
    g = random_dag(5)
    assert g.nodes is g.nodes and g.sinks is g.sinks
    assert g.consumers is g.consumers
    top = max(i.id for i in (*g.sources, *g.nodes, *g.sinks))
    g2, n = g.add_node(Map("late"), ())
    assert n.id == top + 1 and n in g2.nodes and n not in g.nodes
    for gid in (*g.sources, *g.nodes):
        readers = {m for m, deps in g.dependencies.items() if gid in deps} | {
            k for k, dep in g.sink_dependencies.items() if dep == gid}
        assert g.get_children(gid) == readers
    assert set(vars(g)) > {"sources", "sink_dependencies", "operators",
                           "dependencies"}  # the derived tables are held
    state = g.__getstate__()
    assert set(state) == {"sources", "sink_dependencies", "operators",
                          "dependencies"}
    back = pickle.loads(pickle.dumps((Map("a") >> Map("b")).fit()))
    assert back.to_pipeline().graph.nodes  # a FittedPipeline is its graph
    back = pickle.loads(pickle.dumps(UnusedBranchRemovalRule().apply(g)))
    assert set(vars(back)) == set(state)
    assert back.nodes and set(vars(back)) > set(state)


# -- the span arguments ------------------------------------------------------

def test_rule_batch_spans_say_rounds_and_node_counts():
    """One real fit (the MNIST app, 2 branches): every ``dag:rules:*``
    span carries ``rounds``, ``nodes_before``, ``nodes_after``; they
    chain from the raw graph's size to what ``dag:optimize`` reports."""
    from keystone_tpu.observability.timeline import flight_recorder
    from keystone_tpu.pipelines.images.mnist.random_fft import (
        MnistRandomFFTConfig, run)

    train, test = _labeled(96, 784, 10, 1), _labeled(32, 784, 10, 2)
    run(MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=1.0),
        train=train, test=test)
    every = flight_recorder().spans()
    optimizes = [s for s in every if (s.cat, s.name) == ("dag", "optimize")]
    assert len(optimizes) >= 2  # the training bind, the test evaluation
    names = [f"rules:{b.name}" for b in DefaultOptimizer().batches]
    for opt in optimizes:
        batches = sorted((s for s in every if s.parent == opt.seq),
                         key=lambda s: s.seq)
        assert [s.name for s in batches] == names
        at = opt.args["nodes_before"]
        for s in batches:
            assert set(s.args) >= {"rounds", "nodes_before", "nodes_after"}
            assert s.args["nodes_before"] == at
            assert 1 <= s.args["rounds"] <= 4
            at = s.args["nodes_after"]
        assert opt.args["nodes_after"] == at
    fusion = [s for s in every if s.name == "rules:map fusion"]
    assert any(s.args["nodes_before"] > s.args["nodes_after"]
               and s.args["rounds"] == 3 for s in fusion)
