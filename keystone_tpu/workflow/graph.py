"""Immutable untyped DAG.

Mirrors ``workflow/graph/Graph.scala:32-455``: a Graph is (sources,
sink_dependencies, operators, dependencies) with mutation-by-copy
operations, id-remapping union (``add_graph``), source-to-sink splicing
(``connect_graph``), a fan-out of many graphs from one source
(``fan_out``, under ``Pipeline.gather``) and DOT export. Analysis helpers
mirror ``workflow/graph/AnalysisUtils.scala``.

Composition costs what it adds: ``connect_graph`` and ``fan_out`` write
every entry of their result once (``_graft``), where the step-by-step
form (``add_graph``, then ``replace_dependency`` / ``remove_source`` /
``remove_sink`` over the union, once a splice or a branch) rebuilt the
whole union each time. They give the graphs that form gives, ids and
the dictionaries' order included: rules walk those. What the adding
calls write is counted (``dag.compose.entries`` / ``dag.compose.calls``).
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields, replace
from functools import cached_property
from typing import (
    Collection,
    Dict,
    FrozenSet,
    Mapping,
    Optional,
    Sequence,
    Set,
    Tuple,
)

from ..observability.metrics import MetricsRegistry
from .graph_ids import GraphId, NodeId, SinkId, SourceId
from .operators import Operator


def _sorted_ids(graph: "Graph") -> list:
    return sorted(
        [s.id for s in graph.sources]
        + [s.id for s in graph.sink_dependencies]
        + [n.id for n in graph.operators]
    )


def _composed(sources, sinks, ops, deps, max_id: Optional[int],
              entries: int) -> "Graph":
    """The graph an adding call returns: told its largest id where the
    call knows it (``max_id``; the next call need not walk the ids for
    it), and counted: ``entries`` is what the call wrote into the
    containers it made, copies of what was there included."""
    graph = Graph(sources, sinks, ops, deps)
    if max_id is not None:
        graph.__dict__["_max_id"] = max_id
    registry = MetricsRegistry.get_or_create()
    registry.counter("dag.compose.calls").inc()
    registry.counter("dag.compose.entries").inc(entries)
    return graph


def _graft(
    other: "Graph", start: int, wired: Mapping[SourceId, GraphId],
    ops: Dict[NodeId, Operator], deps: Dict[NodeId, Tuple[GraphId, ...]],
) -> Tuple[Dict[int, int], Dict[GraphId, GraphId]]:
    """Write ``other``'s nodes into ``ops`` / ``deps`` under the ids
    ``add_graph`` would give them from ``start`` on (its sorted ids, in
    step), every edge at a source in ``wired`` ALREADY pointing at that
    source's value: only these nodes can name those sources, so nothing
    is renamed afterwards. Returns the new number of each of other's
    ids, in rising order, and where each of its sources and nodes now
    is."""
    ids = _sorted_ids(other)
    idmap = dict(zip(ids, range(start, start + len(ids))))
    new_of: Dict[GraphId, GraphId] = {
        s: wired[s] if s in wired else SourceId(idmap[s.id])
        for s in other.sources}
    for n in other.operators:
        new_of[n] = NodeId(idmap[n.id])
    other_deps = other.dependencies
    for n, op in other.operators.items():
        new = new_of[n]
        ops[new] = op
        deps[new] = tuple([new_of[d] for d in other_deps[n]])
    return idmap, new_of


def _largest_left(idmap: Dict[int, int],
                  gone: Collection[int]) -> Optional[int]:
    """The largest new id of a graft once the ids in ``gone`` (old
    numbers: spliced sources, consumed sinks) are out again."""
    for old in reversed(idmap):
        if old not in gone:
            return idmap[old]
    return None


@dataclass(frozen=True)
class Graph:
    sources: FrozenSet[SourceId] = frozenset()
    sink_dependencies: Mapping[SinkId, GraphId] = field(default_factory=dict)
    operators: Mapping[NodeId, Operator] = field(default_factory=dict)
    dependencies: Mapping[NodeId, Tuple[GraphId, ...]] = field(default_factory=dict)

    # -- accessors --------------------------------------------------------
    # What is derived from the four fields is made on first use and kept
    # (``cached_property`` writes the instance's ``__dict__``, which a
    # frozen dataclass allows); a graph is never changed, so none of it
    # goes stale, and none of it is compared or pickled.
    @cached_property
    def nodes(self) -> FrozenSet[NodeId]:
        return frozenset(self.operators)

    @cached_property
    def sinks(self) -> FrozenSet[SinkId]:
        return frozenset(self.sink_dependencies)

    @cached_property
    def consumers(self) -> Mapping[GraphId, Set[GraphId]]:
        """Who reads each id: nodes by their dependencies, sinks by
        theirs. One walk of the edges, shared by every question a rule
        asks of one graph: to be read, never changed."""
        table: Dict[GraphId, Set[GraphId]] = {}
        for n, deps in self.dependencies.items():
            for d in deps:
                table.setdefault(d, set()).add(n)
        for k, d in self.sink_dependencies.items():
            table.setdefault(d, set()).add(k)
        return table

    def __getstate__(self):
        return {f.name: getattr(self, f.name) for f in fields(self)}

    def get_operator(self, node: NodeId) -> Operator:
        return self.operators[node]

    def get_dependencies(self, node: NodeId) -> Tuple[GraphId, ...]:
        return self.dependencies[node]

    def get_sink_dependency(self, sink: SinkId) -> GraphId:
        return self.sink_dependencies[sink]

    @cached_property
    def _max_id(self) -> int:
        ids = (
            [s.id for s in self.sources]
            + [s.id for s in self.sink_dependencies]
            + [n.id for n in self.operators]
        )
        return max(ids) if ids else 0

    def _next_ids(self, count: int) -> range:
        start = self._max_id + 1
        return range(start, start + count)

    # -- mutation by copy (Graph.scala:115-248) ---------------------------
    def add_node(self, op: Operator, deps: Sequence[GraphId]) -> Tuple["Graph", NodeId]:
        nid = NodeId(self._max_id + 1)
        ops = {**self.operators, nid: op}
        new_deps = {**self.dependencies, nid: tuple(deps)}
        return _composed(self.sources, self.sink_dependencies, ops, new_deps,
                         nid.id, len(ops) + len(new_deps)), nid

    def add_source(self) -> Tuple["Graph", SourceId]:
        sid = SourceId(self._max_id + 1)
        sources = self.sources | {sid}
        return _composed(sources, self.sink_dependencies, self.operators,
                         self.dependencies, sid.id, len(sources)), sid

    def add_sink(self, dep: GraphId) -> Tuple["Graph", SinkId]:
        kid = SinkId(self._max_id + 1)
        sinks = {**self.sink_dependencies, kid: dep}
        return _composed(self.sources, sinks, self.operators,
                         self.dependencies, kid.id, len(sinks)), kid

    def rewrite(
        self,
        operators: Optional[Mapping[NodeId, Operator]] = None,
        dependencies: Optional[Mapping[NodeId, Sequence[GraphId]]] = None,
        remove: Collection[NodeId] = (),
        rename: Optional[Mapping[GraphId, GraphId]] = None,
    ) -> "Graph":
        """A whole rewrite as ONE new graph, each dictionary copied
        once: the nodes named by ``operators`` / ``dependencies`` get
        those, the nodes in ``remove`` go (callers reroute their
        dependents: ``rename`` does), and every edge at a key of
        ``rename`` then points at its value, sinks' edges too. A rule
        that touches many nodes calls this once, so it costs
        O(nodes + edges) whatever it changes."""
        operators = operators or {}
        dependencies = dependencies or {}
        assert all(n in self.operators for n in (*operators, *dependencies))
        if not isinstance(remove, (set, frozenset)):
            remove = set(remove)
        ops, deps = self.operators, self.dependencies
        if operators or remove:
            ops = {n: operators.get(n, op)
                   for n, op in ops.items() if n not in remove}
        if dependencies or remove:
            deps = {n: tuple(dependencies[n]) if n in dependencies else ds
                    for n, ds in deps.items() if n not in remove}
        sinks = self.sink_dependencies
        if rename:
            deps = {n: tuple([rename.get(d, d) for d in ds])
                    for n, ds in deps.items()}
            sinks = {k: rename.get(d, d) for k, d in sinks.items()}
        return replace(
            self, sink_dependencies=sinks, operators=ops, dependencies=deps)

    def set_dependencies(self, node: NodeId, deps: Sequence[GraphId]) -> "Graph":
        return self.rewrite(dependencies={node: deps})

    def set_operator(self, node: NodeId, op: Operator) -> "Graph":
        return self.rewrite(operators={node: op})

    def set_sink_dependency(self, sink: SinkId, dep: GraphId) -> "Graph":
        assert sink in self.sink_dependencies
        return replace(self, sink_dependencies={**self.sink_dependencies, sink: dep})

    def remove_node(self, node: NodeId) -> "Graph":
        """Remove a node (callers must have rerouted dependents first)."""
        return self.rewrite(remove=(node,))

    def remove_sink(self, sink: SinkId) -> "Graph":
        return replace(
            self,
            sink_dependencies={
                k: v for k, v in self.sink_dependencies.items() if k != sink
            },
        )

    def remove_source(self, source: SourceId) -> "Graph":
        return replace(self, sources=self.sources - {source})

    def replace_dependency(self, old: GraphId, new: GraphId) -> "Graph":
        """Point every edge at ``old`` to ``new`` (Graph.scala:258-275)."""
        return self.rewrite(rename={old: new})

    @staticmethod
    def single(op: Operator) -> Tuple["Graph", SourceId, SinkId]:
        """source -> ``op`` -> sink, as ``add_source`` / ``add_node`` /
        ``add_sink`` on an empty graph give it, written once: what every
        stage of a pipeline starts as."""
        src, node, sink = SourceId(1), NodeId(2), SinkId(3)
        return _composed(frozenset((src,)), {sink: node}, {node: op},
                         {node: (src,)}, 3, 4), src, sink

    # -- graph composition (Graph.scala:290-431) --------------------------
    def add_graph(
        self, other: "Graph"
    ) -> Tuple["Graph", Dict[SourceId, SourceId], Dict[SinkId, SinkId]]:
        """Disjoint union, remapping the other graph's ids to fresh ones.
        Returns (union, other_source->new_source, other_sink->new_sink)."""
        other_ids = _sorted_ids(other)
        fresh = self._next_ids(len(other_ids))
        idmap = dict(zip(other_ids, fresh))

        def rn(g: GraphId) -> GraphId:
            return type(g)(idmap[g.id])

        new_sources = self.sources | {SourceId(idmap[s.id]) for s in other.sources}
        new_ops = {**self.operators}
        new_deps = {**self.dependencies}
        for n, op in other.operators.items():
            new_ops[NodeId(idmap[n.id])] = op
            new_deps[NodeId(idmap[n.id])] = tuple(rn(d) for d in other.dependencies[n])
        new_sinks = {**self.sink_dependencies}
        for s, d in other.sink_dependencies.items():
            new_sinks[SinkId(idmap[s.id])] = rn(d)
        union = _composed(
            new_sources, new_sinks, new_ops, new_deps, None,
            len(new_sources) + len(new_sinks) + len(new_ops) + len(new_deps))
        smap = {s: SourceId(idmap[s.id]) for s in other.sources}
        kmap = {k: SinkId(idmap[k.id]) for k in other.sink_dependencies}
        return union, smap, kmap

    def connect_graph(
        self, other: "Graph", splice: Mapping[SourceId, SinkId]
    ) -> Tuple["Graph", Dict[SourceId, SourceId], Dict[SinkId, SinkId]]:
        """Union with ``other``, wiring each of other's sources in ``splice``
        to the value feeding one of self's sinks; the consumed sinks are
        removed (Graph.scala:340-364). ``splice`` keys are other's source
        ids; values are self's sink ids. One pass: a copy of self's
        dictionaries and O(other); the spliced sources and the consumed
        sinks are never written."""
        wired = {}
        for o_src, my_sink in splice.items():
            if o_src not in other.sources:
                raise KeyError(o_src)
            wired[o_src] = self.sink_dependencies[my_sink]
        consumed = set(splice.values())
        ops, deps = dict(self.operators), dict(self.dependencies)
        idmap, new_of = _graft(other, self._max_id + 1, wired, ops, deps)
        smap = {s: new_of[s] for s in other.sources if s not in wired}
        sources = self.sources | set(smap.values())
        sinks = {k: d for k, d in self.sink_dependencies.items()
                 if k not in consumed}
        kmap = {}
        for k, d in other.sink_dependencies.items():
            kmap[k] = new = SinkId(idmap[k.id])
            sinks[new] = new_of[d]
        # every new id lies above self's, so the largest of them that
        # stays is the union's; where none stays, the union finds its own
        union = _composed(
            sources, sinks, ops, deps,
            _largest_left(idmap, {s.id for s in wired}),
            len(sources) + len(sinks) + len(ops) + len(deps))
        return union, smap, kmap

    @staticmethod
    def fan_out(
        branches: Sequence[Tuple["Graph", SourceId, SinkId]], join: Operator
    ) -> Tuple["Graph", SourceId, SinkId]:
        """ONE graph in which every branch ``(graph, its source, its
        sink)`` reads one shared new source and a ``join`` node reads what
        fed each branch's sink, in order: returns it, that source and a
        sink on the join. A branch's own source and sink are never
        written; whatever else it has (further sources and sinks) is.
        O(sum of the branches' entries).

        The ids are those of adding the branches one after another with
        ``add_graph`` and taking each one's source and sink out again
        before the next: a branch numbers from the largest id LEFT by
        then, so the id of a sink that went comes back."""
        src = SourceId(1)
        sources, max_id = {src}, 1
        sinks: Dict[SinkId, GraphId] = {}
        ops: Dict[NodeId, Operator] = {}
        deps: Dict[NodeId, Tuple[GraphId, ...]] = {}
        outs = []
        for graph, b_src, b_sink in branches:
            if b_src not in graph.sources:
                raise KeyError(b_src)
            idmap, new_of = _graft(
                graph, max_id + 1, {b_src: src}, ops, deps)
            sources.update(new_of[s] for s in graph.sources if s != b_src)
            outs.append(new_of[graph.sink_dependencies[b_sink]])
            for k, d in graph.sink_dependencies.items():
                if k != b_sink:
                    sinks[SinkId(idmap[k.id])] = new_of[d]
            left = _largest_left(idmap, (b_src.id, b_sink.id))
            if left is not None:
                max_id = left
        joined, sink = NodeId(max_id + 1), SinkId(max_id + 2)
        ops[joined], deps[joined], sinks[sink] = join, tuple(outs), joined
        fanned = _composed(
            frozenset(sources), sinks, ops, deps, sink.id,
            len(sources) + len(sinks) + len(ops) + len(deps))
        return fanned, src, sink

    def induce(self, keep: FrozenSet[GraphId]) -> "Graph":
        """Subgraph on ``keep`` (nodes/sources) plus sinks depending on it."""
        ops = {n: op for n, op in self.operators.items() if n in keep}
        deps = {n: self.dependencies[n] for n in ops}
        sources = frozenset(s for s in self.sources if s in keep)
        sinks = {
            k: v for k, v in self.sink_dependencies.items() if v in keep
        }
        return Graph(sources, sinks, ops, deps)

    # -- analysis (AnalysisUtils.scala) -----------------------------------
    def get_children(self, gid: GraphId) -> FrozenSet[GraphId]:
        return frozenset(self.consumers.get(gid, ()))

    def get_descendants(self, *gids: GraphId) -> FrozenSet[GraphId]:
        """Everything downstream of any of ``gids``, in one walk."""
        seen: set = set()
        stack = list(gids)
        while stack:
            for c in self.consumers.get(stack.pop(), ()):
                if c not in seen:
                    seen.add(c)
                    stack.append(c)
        return frozenset(seen)

    def get_parents(self, gid: GraphId) -> Tuple[GraphId, ...]:
        if isinstance(gid, SinkId):
            return (self.sink_dependencies[gid],)
        if isinstance(gid, NodeId):
            return self.dependencies[gid]
        return ()

    def get_ancestors(self, *gids: GraphId) -> FrozenSet[GraphId]:
        """Everything upstream of any of ``gids``, in one walk."""
        seen: set = set()
        stack = list(gids)
        while stack:
            cur = stack.pop()
            for p in self.get_parents(cur):
                if p not in seen:
                    seen.add(p)
                    stack.append(p)
        return frozenset(seen)

    def linearize(self) -> Tuple[GraphId, ...]:
        """Deterministic topological order over all ids
        (AnalysisUtils.scala:88-121)."""
        order: list = []
        seen: set = set()

        def parents(gid: GraphId):
            found = self.get_parents(gid)
            if len(found) > 1:
                found = sorted(found, key=lambda g: (g.id, type(g).__name__))
            return iter(found)

        def visit(root: GraphId) -> None:
            # depth first, parents before a node, on a list of our own:
            # the depth of a pipeline is not the interpreter's to bound
            if root in seen:
                return
            seen.add(root)
            stack = [(root, parents(root))]
            while stack:
                gid, rest = stack[-1]
                for p in rest:
                    if p not in seen:
                        seen.add(p)
                        stack.append((p, parents(p)))
                        break
                else:
                    order.append(gid)
                    stack.pop()

        for k in sorted(self.sink_dependencies, key=lambda g: g.id):
            visit(k)
        # cover nodes unreachable from any sink, deterministically
        for n in sorted(self.operators, key=lambda g: g.id):
            visit(n)
        return tuple(order)

    # -- export (Graph.scala:436-455) -------------------------------------
    def source_descendants(self) -> FrozenSet[GraphId]:
        """Every id reachable from any (unconnected/runtime) source."""
        return self.sources | self.get_descendants(*self.sources)

    def to_dot(self, title: str = "pipeline") -> str:
        lines = [f'digraph "{title}" {{', "  rankdir=LR;"]
        for s in sorted(self.sources, key=lambda g: g.id):
            lines.append(f'  "{s!r}" [shape=oval, label="source {s.id}"];')
        for n in sorted(self.operators, key=lambda g: g.id):
            lines.append(
                f'  "{n!r}" [shape=box, label="{self.operators[n].label()}"];'
            )
        for k in sorted(self.sink_dependencies, key=lambda g: g.id):
            lines.append(f'  "{k!r}" [shape=diamond, label="sink {k.id}"];')
        for n, deps in sorted(self.dependencies.items(), key=lambda kv: kv[0].id):
            for i, d in enumerate(deps):
                lines.append(f'  "{d!r}" -> "{n!r}" [label="{i}"];')
        for k, d in sorted(self.sink_dependencies.items(), key=lambda kv: kv[0].id):
            lines.append(f'  "{d!r}" -> "{k!r}";')
        lines.append("}")
        return "\n".join(lines)
