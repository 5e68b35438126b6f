"""Rule-based graph lints + the check report.

The diagnostics engine over the abstract interpreter: propagation
errors (shape/dtype mismatches, host-sync hazards caught during
``jax.eval_shape``) come from ``interpreter.analyze``; this module adds
the structural lints —

* ``unbound-source``     a sink-reachable value depends on a source no
                         input spec was bound to
* ``dead-branch``        nodes no sink depends on (silently skipped at
                         execution; almost always a mis-wired graph)
* ``dtype-narrowing``    a node's output drops float width relative to
                         its inputs (f32 -> bf16/f16) without being an
                         explicit cast — silent precision loss across a
                         node boundary
* ``host-sync``          (static form) a device-node ``apply`` body
                         calls ``np.asarray``/``np.array`` on its item
                         argument — the AST-level gate behind ADVICE's
                         "no host coercions in hot paths" rule
* ``fusion-prefix-hazard`` a saveable node's logical prefix changes
                         under map/gather fusion, so saved fitted state
                         could never be re-matched by
                         ``SavedStateLoadRule`` (CHANGES.md PR 1 note)
* ``non-streamable-fit`` an estimator whose training input is a
                         StreamingDataset but which does not implement
                         the accumulate/finalize streaming protocol —
                         the fit would fail at runtime (or require
                         materializing the stream in HBM); also fires
                         for streamed LABELS with resident data (the
                         chunk loop is data-driven)
* ``host-stage-on-stream`` a HostTransformer consumes a streaming
                         dataset — chunks are device-resident, so the
                         host stage raises at runtime

— and packages everything as an :class:`AnalysisReport` in the
observability layer's report style (text summary + ``to_json``).
"""
from __future__ import annotations

import ast
import inspect
import json
import textwrap
from dataclasses import asdict
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax
import numpy as np

from ..workflow.graph import Graph
from ..workflow.graph_ids import GraphId, NodeId, SourceId
from .interpreter import (
    Analysis,
    Diagnostic,
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    analyze,
)
from .spec import (
    AbstractValue,
    DatasetSpec,
    DatumSpec,
    Unknown,
    as_input_spec,
    format_element,
)


# -- structural lints -------------------------------------------------------

def _sink_reachable(graph: Graph) -> set:
    needed: set = set()
    for k in graph.sinks:
        dep = graph.get_sink_dependency(k)
        needed.add(dep)
        needed |= graph.get_ancestors(dep)
    return needed


def unbound_source_lint(
    graph: Graph, source_specs: Mapping[SourceId, AbstractValue]
) -> List[Diagnostic]:
    out = []
    needed = _sink_reachable(graph)
    for s in sorted(graph.sources, key=lambda g: g.id):
        if s in source_specs:
            continue
        if s in needed:
            out.append(Diagnostic(
                code="unbound-source", severity=SEVERITY_ERROR,
                node_id=s.id, operator="Source",
                message=("a sink-reachable value depends on source "
                         f"{s.id} but no input spec was bound to it")))
    return out


def dead_branch_lint(graph: Graph) -> List[Diagnostic]:
    needed = _sink_reachable(graph)
    out = []
    for n in sorted(graph.nodes, key=lambda g: g.id):
        if n not in needed:
            out.append(Diagnostic(
                code="dead-branch", severity=SEVERITY_WARNING,
                node_id=n.id, operator=graph.get_operator(n).label(),
                message="no sink depends on this node; it will never "
                        "execute (mis-wired branch?)"))
    return out


def _float_widths(spec: AbstractValue) -> List[int]:
    element = getattr(spec, "element", None)
    if element is None:
        return []
    widths = []
    for leaf in jax.tree_util.tree_leaves(
            element, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            # covers bf16 too: ml_dtypes.bfloat16 is a 2-byte floating
            # np dtype, so itemsize*8 reports 16
            dt = np.dtype(leaf.dtype)
            if jax.numpy.issubdtype(dt, jax.numpy.floating):
                widths.append(dt.itemsize * 8)
    return widths


def dtype_narrowing_lint(analysis: Analysis) -> List[Diagnostic]:
    graph = analysis.graph
    out = []
    for n in sorted(graph.nodes, key=lambda g: g.id):
        op = graph.get_operator(n)
        if getattr(op, "narrowing_ok", False):
            continue  # explicit casts narrow on purpose
        out_w = _float_widths(analysis.value(n))
        if not out_w:
            continue
        in_w: List[int] = []
        for d in graph.get_dependencies(n):
            in_w.extend(_float_widths(analysis.value(d)))
        if in_w and min(out_w) < min(in_w):
            out.append(Diagnostic(
                code="dtype-narrowing", severity=SEVERITY_WARNING,
                node_id=n.id, operator=op.label(),
                message=(f"output narrows floats to {min(out_w)}-bit from "
                         f"{min(in_w)}-bit inputs; silent precision loss "
                         "across a node boundary (mark the operator "
                         "`narrowing_ok = True` if intentional)")))
    return out


# -- host-sync AST lint -----------------------------------------------------

_HOST_COERCIONS = {"asarray", "array", "ascontiguousarray"}
_NUMPY_ALIASES = {"np", "numpy", "onp"}


def host_coercions_in_funcdef(fdef) -> List[tuple]:
    """``(lineno, description)`` for each ``np.*`` host coercion applied
    to one of ``fdef``'s own parameters. The single source of truth for
    the host-coercion pattern — used on live classes here and on raw
    source trees by ``tools/lint.py``. Only coercions whose argument IS
    a parameter are flagged: ``np.*`` on static config (seeds, index
    tables) is legitimate."""
    params = {a.arg for a in fdef.args.args[1:]}  # skip self
    hits = []
    for node in ast.walk(fdef):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id in _NUMPY_ALIASES
                and f.attr in _HOST_COERCIONS):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Name) and arg.id in params:
            hits.append((node.lineno, f"{f.value.id}.{f.attr}({arg.id})"))
    return hits


#: directories (under ``keystone_tpu/``) where a silent swallow-all
#: handler is banned: ingest and workflow code is exactly where "skip
#: the error and keep going" turns a flaky disk or corrupt record into
#: silent data loss — the resilience layer (retry / quarantine) is the
#: sanctioned way to tolerate failures there. tools/lint.py enforces.
SWALLOW_ALL_SCOPES = ("loaders", "parallel", "serving", "workflow")

#: directories where the cast-before-transfer rule applies: loader and
#: device-staging code is where a host-side float widening right before
#: ``device_put`` quietly ships 4x the bytes the source held (the
#: pattern the ``StreamingDataset`` wire-dtype machinery removes).
CAST_BEFORE_TRANSFER_SCOPES = ("loaders", "parallel")

#: dtype spellings that count as a float widening target
_FLOAT_DTYPE_NAMES = {
    "float16", "float32", "float64", "bfloat16", "float_", "double",
}


def _is_float_dtype_expr(node) -> bool:
    """Syntactically a float dtype: ``np.float32`` / ``jnp.float32`` /
    the builtin ``float`` / a ``"float32"``-style string literal."""
    if isinstance(node, ast.Attribute):
        return node.attr in _FLOAT_DTYPE_NAMES
    if isinstance(node, ast.Name):
        return node.id in _FLOAT_DTYPE_NAMES or node.id == "float"
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value in _FLOAT_DTYPE_NAMES
    return False


def _own_scope_nodes(fdef):
    """Walk a function body WITHOUT descending into nested function
    definitions (each nested def is linted as its own scope), so a cast
    in one scope and a device_put in an unrelated closure are never
    conflated into a false co-occurrence. The tradeoff — a split
    pattern (cast in the outer body, put in a helper closure) is not
    flagged across the boundary — is the right default for a CI gate:
    false positives break the gate on legitimate code."""
    stack = list(fdef.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue  # nested def: its own scope, scanned separately
        yield node
        stack.extend(ast.iter_child_nodes(node))


def float_casts_before_transfer(tree) -> List[tuple]:
    """``(lineno, description)`` for host float-widening casts sitting
    in the same function scope as a ``device_put`` — the
    cast-before-transfer pattern: widening uint8 records to float on
    the HOST and then shipping the wide copy quadruples the wire bytes.
    Detected syntactically (dtypes are not statically known) as the
    co-occurrence, per function scope (nested defs are separate
    scopes), of (a) any ``*.device_put(...)`` call and (b) an
    ``.astype(<float dtype>)`` (positional or ``dtype=`` keyword) or
    ``np.asarray/array/stack/ascontiguousarray(..., dtype=<float
    dtype>)`` call. Fix: ship the source dtype and cast on device —
    ``StreamingDataset``'s ``wire_dtype`` / ``compute_dtype`` do
    exactly this (README 'Streaming ingest')."""
    hits = []
    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        puts = False
        casts = []
        for node in _own_scope_nodes(fdef):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if f.attr == "device_put":
                puts = True
            elif f.attr == "astype":
                dtype_args = list(node.args[:1]) + [
                    kw.value for kw in node.keywords if kw.arg == "dtype"]
                if any(_is_float_dtype_expr(a) for a in dtype_args):
                    casts.append((node.lineno, "astype(float)"))
            elif f.attr in ("asarray", "array", "stack",
                            "ascontiguousarray"):
                for kw in node.keywords:
                    if kw.arg == "dtype" and _is_float_dtype_expr(kw.value):
                        casts.append(
                            (node.lineno, f"{f.attr}(dtype=float)"))
        if puts and casts:
            hits.extend(casts)
    return sorted(set(hits))


def swallow_all_handlers(tree) -> List[tuple]:
    """``(lineno, description)`` for exception handlers that swallow
    everything silently: a bare ``except:`` (any body), or an
    ``except Exception/BaseException`` handler whose body is only
    ``pass``/``...``. Handlers that narrow the exception type, re-raise,
    log, or compute a fallback are fine — the lint targets the pattern
    that makes failures disappear without a trace."""
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            hits.append((node.lineno, "bare `except:`"))
            continue
        exc_type = node.type
        elts = (exc_type.elts if isinstance(exc_type, ast.Tuple)
                else [exc_type])
        names = [e.attr if isinstance(e, ast.Attribute)
                 else getattr(e, "id", "") for e in elts]
        if not any(n in ("Exception", "BaseException") for n in names):
            continue
        body_is_noop = all(
            isinstance(stmt, ast.Pass)
            or (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)
                and (stmt.value.value is Ellipsis
                     or isinstance(stmt.value.value, str)))
            for stmt in node.body)
        if body_is_noop:
            hits.append((node.lineno,
                         f"`except {'/'.join(names)}: pass`"))
    return hits


#: directories (under ``keystone_tpu/``) where NaN-suppressing code
#: must be PAIRED with a recorded ``numerics.*`` event: the numeric
#: compute trees are exactly where a ``nan_to_num`` or an
#: ``np.errstate(...='ignore')`` turns a real breakdown into silently
#: plausible numbers — the numerics plane (observability/numerics.py)
#: exists so suppression is always accounted. tools/lint.py enforces.
NAN_SILENCER_SCOPES = ("nodes", "ops", "parallel", "workflow")

#: call names that count as recording into the numerics event funnel
#: (observability/numerics.py — the one place sites report through)
_NUMERICS_RECORDERS = frozenset({
    "record_numerics_event", "record_solve_health", "record_block_health",
})


def _errstate_ignores(call) -> bool:
    """True when an ``errstate(...)`` call actually SUPPRESSES — any
    keyword whose value is the literal ``'ignore'``.
    ``errstate(all='raise')`` is the opposite of suppression and never
    fires the lint."""
    return any(isinstance(kw.value, ast.Constant)
               and kw.value.value == "ignore" for kw in call.keywords)


def silent_nan_silencers(tree) -> List[tuple]:
    """``(lineno, description)`` for NaN-suppressing calls with no
    recorded numerics event in the same function scope — the
    ``silent-nan-silencer`` rule. Per scope (nested defs are separate
    scopes, like the cast-before-transfer rule), the co-occurrence of:

    * a silencer — ``nan_to_num(...)`` (any receiver) or an
      ``errstate(...)`` call with an ``='ignore'`` keyword, and
    * NO recorder — a :data:`_NUMERICS_RECORDERS` call or a metric
      factory call with a ``"numerics."``-prefixed literal name.

    The rule does not ban suppression: replacing non-finites can be the
    right recovery (the clamped-eigh fallback is exactly that). It bans
    UNACCOUNTED suppression — pair the silencer with
    ``record_numerics_event(...)`` so the event lands in
    metrics/trace/flight-recorder and dashboards see the recovery
    happen (README 'Numerics health')."""
    hits = []
    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        silencers = []
        recorded = False
        for node in _own_scope_nodes(fdef):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = (f.attr if isinstance(f, ast.Attribute)
                     else getattr(f, "id", ""))
            if fname == "nan_to_num":
                silencers.append((node.lineno, "nan_to_num(...)"))
            elif fname == "errstate" and _errstate_ignores(node):
                silencers.append((node.lineno, "errstate(...='ignore')"))
            elif fname in _NUMERICS_RECORDERS:
                recorded = True
            elif fname in _METRIC_FACTORIES and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str) \
                        and arg.value.startswith("numerics."):
                    recorded = True
        if silencers and not recorded:
            hits.extend(silencers)
    return sorted(set(hits))


#: metric-factory method names whose first argument is a metric name
#: (``MetricsRegistry.counter/gauge/histogram/timer``)
_METRIC_FACTORIES = frozenset({"counter", "gauge", "histogram", "timer"})


def metric_name_drift(tree) -> List[tuple]:
    """``(lineno, code, description)`` for every
    ``counter(...)``/``gauge(...)``/``histogram(...)``/``timer(...)``
    call site whose metric name is not in the catalogue
    (``observability/names.py``). Prometheus dashboards and the benchmark's
    readers address metrics by name across process boundaries — a rename that
    skips the catalogue silently flatlines every consumer. Literal
    names must be catalogued exactly (or live under a catalogued
    prefix); f-strings must OPEN with a catalogued prefix
    (``f"resilience.{event}"``); a fully dynamic name (a bare variable)
    is uncheckable and passes through — keep those inside the
    observability layer itself."""
    from ..observability.names import (
        METRIC_PREFIXES,
        is_catalogued,
        is_catalogued_prefix,
    )

    hits: List[tuple] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_FACTORIES
                and node.args):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            if not is_catalogued(arg.value):
                hits.append((
                    node.lineno, "metric-name-drift",
                    f".{node.func.attr}({arg.value!r}) uses an "
                    "uncatalogued metric name — add it to "
                    "observability/names.py (dashboards and the benchmark "
                    "address metrics by name; an uncatalogued name is "
                    "either a typo or an unreviewed rename)"))
        elif isinstance(arg, ast.JoinedStr):
            head = ""
            if arg.values and isinstance(arg.values[0], ast.Constant) \
                    and isinstance(arg.values[0].value, str):
                head = arg.values[0].value
            if not is_catalogued_prefix(head):
                hits.append((
                    node.lineno, "metric-name-drift",
                    f".{node.func.attr}(f\"{head}...\") does not open "
                    "with a catalogued metric-name prefix "
                    f"({', '.join(METRIC_PREFIXES)}) — dynamic metric "
                    "families must be declared in "
                    "observability/names.py METRIC_PREFIXES"))
    return sorted(set(hits))


def scan_metric_names(pkg_root) -> List[dict]:
    """Run :func:`metric_name_drift` over a package tree (the shape
    ``tools/lint.py`` and ``check --json`` consume:
    ``[{file, lineno, code, message}]``)."""
    from pathlib import Path

    pkg_root = Path(pkg_root)
    out: List[dict] = []
    for path in sorted(pkg_root.rglob("*.py")):
        rel = path.relative_to(pkg_root.parent)
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue  # reported by the other passes
        for lineno, code, msg in metric_name_drift(tree):
            out.append({"file": str(rel), "lineno": lineno,
                        "code": code, "message": msg})
    return out


def apply_body_host_coercions(cls) -> List[str]:
    """Names of ``np.*`` host coercions applied to the item argument in
    ``cls.apply`` — the static (AST) form of the host-sync lint."""
    from ..workflow.transformer import HostTransformer, Transformer

    if not (isinstance(cls, type) and issubclass(cls, Transformer)):
        return []
    if issubclass(cls, HostTransformer):
        return []  # host stages are allowed host semantics
    fn = cls.__dict__.get("apply")
    if fn is None:
        return []
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError):
        return []
    fdef = tree.body[0]
    if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return []
    return [what for _, what in host_coercions_in_funcdef(fdef)]


def host_sync_lint(graph: Graph) -> List[Diagnostic]:
    out = []
    seen_types = set()
    for n in sorted(graph.nodes, key=lambda g: g.id):
        op = graph.get_operator(n)
        stages = getattr(op, "stages", None) or getattr(
            op, "branches", None) or [op]
        for stage in stages:
            if type(stage) in seen_types:
                continue
            seen_types.add(type(stage))
            hits = apply_body_host_coercions(type(stage))
            if hits:
                out.append(Diagnostic(
                    code="host-sync", severity=SEVERITY_ERROR,
                    node_id=n.id, operator=stage.label(),
                    message=(f"apply() coerces its item to host via "
                             f"{', '.join(hits)}: forces a device sync "
                             "per item; use jnp or a HostTransformer")))
    return out


# -- streaming lints --------------------------------------------------------

def host_stage_on_stream_lint(analysis: Analysis) -> List[Diagnostic]:
    """Host-side stages cannot consume a StreamingDataset (chunks are
    device-resident; the batch path would sync every chunk back —
    ``HostTransformer.apply_dataset`` raises at runtime). Flag it before
    anything executes, naming the stage."""
    from ..workflow.transformer import HostTransformer

    graph = analysis.graph
    out = []
    for n in sorted(graph.nodes, key=lambda g: g.id):
        op = graph.get_operator(n)
        stages = getattr(op, "stages", None) or getattr(
            op, "branches", None) or [op]
        if not any(isinstance(s, HostTransformer) for s in stages):
            continue
        streamed = [
            d for d in graph.get_dependencies(n)
            if isinstance(analysis.value(d), DatasetSpec)
            and analysis.value(d).streaming
        ]
        if streamed:
            host_stage = next(
                s for s in stages if isinstance(s, HostTransformer))
            out.append(Diagnostic(
                code="host-stage-on-stream", severity=SEVERITY_ERROR,
                node_id=n.id, operator=host_stage.label(),
                message=(
                    f"host stage {host_stage.label()!r} consumes a "
                    "streaming dataset; chunks are device-resident and "
                    "a host stage would sync every one back (this "
                    "raises at runtime). Run host stages before "
                    "building the stream, or materialize() it "
                    "(fix-hint: README 'Streaming ingest' / "
                    "'Resilience' document the streaming fit and "
                    "checkpoint/resume API)")))
    return out



def non_streamable_fit_lint(analysis: Analysis) -> List[Diagnostic]:
    """Estimator nodes fed a streaming dataset must implement the
    accumulate/finalize protocol (``parallel.streaming.is_streamable``)
    — otherwise ``fit`` raises at runtime, after the whole upstream
    pipeline has already run. The error names the node so the fix
    (streamable estimator, or an explicit ``materialize()``) is
    unambiguous before anything executes."""
    from ..parallel.streaming import is_streamable
    from ..workflow.operators import EstimatorOperator

    graph = analysis.graph
    out = []
    for n in sorted(graph.nodes, key=lambda g: g.id):
        op = graph.get_operator(n)
        if not isinstance(op, EstimatorOperator):
            continue
        deps = graph.get_dependencies(n)
        streamed = [
            isinstance(analysis.value(d), DatasetSpec)
            and analysis.value(d).streaming
            for d in deps
        ]
        if not any(streamed):
            continue
        # a process-shard-local source (stream_tar_shards) means the
        # stream holds one HOST's share: name it, so the diagnostic
        # (and the materialize() suggestion, which would materialize a
        # fraction of the data) reads correctly on a multi-host graph
        sharded = any(
            isinstance(analysis.value(d), DatasetSpec)
            and analysis.value(d).sharded
            for d in deps
        )
        kind = "shard-local streaming" if sharded else "streaming"
        if not is_streamable(op):
            hint = (
                "Use a streamable estimator (LeastSquares family, "
                "StandardScaler) or materialize() the stream "
                "explicitly if it fits (fix-hint: README 'Streaming "
                "ingest' / 'Resilience' document the streaming fit "
                "and checkpoint/resume API)")
            if sharded:
                hint = (
                    "Use a streamable estimator (LeastSquares family, "
                    "StandardScaler): the elastic multi-host fit "
                    "tree-reduces its carries across hosts, while "
                    "materialize() would materialize only THIS host's "
                    "shard (fix-hint: CLUSTER.md 'Elastic resume' / "
                    "README 'Resilience' document the distributed "
                    "streaming fit)")
            out.append(Diagnostic(
                code="non-streamable-fit", severity=SEVERITY_ERROR,
                node_id=n.id, operator=op.label(),
                message=(
                    f"estimator {op.label()!r} fits on a {kind} "
                    "dataset but implements no accumulate(carry, chunk"
                    "[, labels])/finalize(carry) protocol; the fit "
                    "would have to materialize the whole stream in "
                    f"HBM. {hint}")))
        elif not streamed[0]:
            # streamable estimator, but only a NON-data dependency
            # (labels) streams: the chunk loop is driven by the data
            # stream, so this shape fails at runtime
            out.append(Diagnostic(
                code="non-streamable-fit", severity=SEVERITY_ERROR,
                node_id=n.id, operator=op.label(),
                message=(
                    f"estimator {op.label()!r} has a streaming LABELS "
                    "input but resident data; the streamed chunk loop "
                    "is driven by the data input. Stream the data too "
                    "(aligned chunk sizes), or materialize() the "
                    "labels (fix-hint: README 'Streaming ingest' / "
                    "'Resilience' document the streaming fit and "
                    "checkpoint/resume API)")))
    return out


# -- donation-safety AST pass ------------------------------------------------
#
# ``utils.donation.donating_jit`` marks its donated arguments' buffers
# DEAD after the call — reading one afterwards raises on TPU/GPU and
# silently works on CPU, which is exactly the kind of backend-dependent
# bug that survives a CPU test suite. This pass finds the two dataflow
# shapes that bit us (or nearly did):
#
# * ``use-after-donate``       — a name passed at a donate position is
#                                read later in the same scope without
#                                being rebound first
# * ``checkpoint-after-donate`` — the later read sits inside a
#                                ``*.save(...)`` call: the checkpoint
#                                would snapshot a dead buffer (saves
#                                must copy the carry to host BEFORE the
#                                next accumulate donates it)
#
# The analysis is textual-order within one function scope (nested defs
# are separate scopes, like the other AST rules here): the canonical
# safe pattern ``carry = update(carry, ...)`` rebinds at the call
# statement and is never flagged; loops that donate then read without a
# rebind are flagged by their source order. The companion
# shape-compatibility rule is spec-level, not AST-level — see
# ``utils.donation.donation_shape_mismatches`` (eval_shape over each
# registered site's probe), enforced by tools/lint.py.

def donating_names(tree) -> Dict[str, frozenset]:
    """``{assigned name: donate_argnums}`` for every
    ``NAME = donating_jit(fn, donate_argnums=...)`` in ``tree``."""
    out: Dict[str, frozenset] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        fname = (call.func.attr if isinstance(call.func, ast.Attribute)
                 else getattr(call.func, "id", ""))
        if fname != "donating_jit":
            continue
        argnums_node = None
        if len(call.args) >= 2:
            argnums_node = call.args[1]
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                argnums_node = kw.value
        if argnums_node is None:
            continue
        try:
            argnums = tuple(ast.literal_eval(argnums_node))
        except (ValueError, SyntaxError):
            continue  # computed argnums: nothing static to track
        out[node.targets[0].id] = frozenset(int(a) for a in argnums)
    return out


def donation_hazards(tree) -> List[tuple]:
    """``(lineno, code, description)`` for use-after-donate /
    checkpoint-after-donate patterns (see the block comment above)."""
    donors = donating_names(tree)
    hits: List[tuple] = []
    if not donors:
        return hits
    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        own = list(_own_scope_nodes(fdef))
        # reads that happen inside a *.save(...) call (checkpoint form)
        save_reads = set()
        for node in own:
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "save"):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Name) and isinstance(
                            sub.ctx, ast.Load):
                        save_reads.add(id(sub))
        stores = [(n.id, n.lineno) for n in own
                  if isinstance(n, ast.Name) and isinstance(
                      n.ctx, ast.Store)]
        loads = [(n.id, n.lineno, id(n) in save_reads) for n in own
                 if isinstance(n, ast.Name) and isinstance(
                     n.ctx, ast.Load)]
        for node in own:
            if not isinstance(node, ast.Call):
                continue
            fname = (node.func.id if isinstance(node.func, ast.Name)
                     else getattr(node.func, "attr", ""))
            if fname not in donors:
                continue
            call_end = getattr(node, "end_lineno", node.lineno)
            for i in sorted(donors[fname]):
                if i >= len(node.args) or not isinstance(
                        node.args[i], ast.Name):
                    continue
                name = node.args[i].id
                for lname, lline, in_save in loads:
                    if lname != name or lline <= call_end:
                        continue
                    # a rebind between the donating call and the read
                    # (the call's own assignment targets included)
                    # kills the old binding — safe
                    if any(sn == name and node.lineno <= sl <= lline
                           for sn, sl in stores):
                        continue
                    code = ("checkpoint-after-donate" if in_save
                            else "use-after-donate")
                    hits.append((
                        lline, code,
                        f"`{name}` was donated to {fname}() at line "
                        f"{node.lineno} and is "
                        + ("snapshotted by a checkpoint save"
                           if in_save else "read")
                        + " afterwards — the buffer is dead on "
                        "TPU/GPU (copy to host before the donating "
                        "call, or rebind the name from the call's "
                        "result)"))
                    break  # one report per donated name per call
    return sorted(set(hits))


# -- recompile-hazard AST pass -----------------------------------------------
#
# jax's trace cache is keyed on the FUNCTION OBJECT plus avals — not on
# ambient state the trace bakes in. Two bug classes from this repo's
# history:
#
# * ``mesh-closure-jit``      — a module-level ``jax.jit`` of a function
#                               that reads the ambient mesh
#                               (``get_mesh`` directly or one call away):
#                               the first mesh's sharding constraints
#                               bake into the cached trace and a second
#                               mesh silently reuses them (the
#                               ``_bcd_jit_for`` bug, fixed in PR 2 by a
#                               per-mesh lru_cache factory — jit sites
#                               inside a function taking a ``mesh``
#                               parameter are therefore exempt)
# * ``per-instance-jit-memo`` — a compiled program memoized on ``self``
#                               with no global cache behind it: every
#                               refit builds a fresh instance and
#                               recompiles (the ``_CAST_JIT_CACHE``
#                               lesson). Storing a jit on ``self`` is
#                               fine only as a fast path over a
#                               module-level memo (the ``_cached_jit``
#                               pattern: the same scope also ``put``\\ s
#                               the program into a global cache)
# * ``unstable-jit-cache-tag`` — ``self._cached_jit(<computed tag>,...)``
#                               destabilizes the global jit cache key
#                               across sessions (moved here from
#                               tools/lint.py so all recompile rules
#                               share one home)

# ``get_mesh`` is the in-module read; the rest are the exported
# solver entry points that read the ambient mesh INTERNALLY (through
# ``_class_spec`` / their per-mesh jit factories), so a module-level
# jit in ANOTHER module that calls one of them bakes the first mesh's
# sharding into its cached trace all the same — the cross-module form
# of the same bug, found for real in `_block_solve` (the
# dryrun_multichip(8) weighted-solver phase failure: an 8-device
# sharding constraint replayed against 1-device arguments; fixed by
# the `_block_solve_for` per-mesh factory, pinned by
# tests/test_linear_solvers.py::test_block_least_squares_mesh_switch)
_AMBIENT_MESH_READS = {"get_mesh", "bcd_core", "bcd_core_columns",
                       "block_coordinate_descent", "solve_one_pass_l2",
                       "tsqr_r"}


def _function_call_names(fdef) -> set:
    out = set()
    for node in ast.walk(fdef):
        if isinstance(node, ast.Call):
            f = node.func
            out.add(f.id if isinstance(f, ast.Name)
                    else getattr(f, "attr", ""))
    return out


def _ambient_mesh_functions(tree) -> set:
    """Names of module-level defs that read the ambient global mesh —
    directly (``get_mesh``) or one call away through another module
    function that does. One transitive hop covers the historical bug
    shape (``bcd_core`` -> ``_class_spec`` -> ``get_mesh``) without
    whole-program analysis."""
    defs = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
    direct = {
        name for name, d in defs.items()
        if _function_call_names(d) & _AMBIENT_MESH_READS
    }
    onehop = set(direct)
    for name, d in defs.items():
        if name not in onehop and _function_call_names(d) & direct:
            onehop.add(name)
    return onehop


def _is_jit_func(f) -> bool:
    # ``observed_jit``/``watch_jit`` (observability/compilelog.py) are
    # jax.jit plus compile telemetry: the recompile-hazard rules must
    # treat an observed site exactly like a bare jit, so routing a
    # program through the compile observatory never weakens the gates
    return (isinstance(f, ast.Attribute)
            and f.attr in ("jit", "observed_jit", "watch_jit")) or (
        isinstance(f, ast.Name)
        and f.id in ("jit", "observed_jit", "watch_jit"))


def recompile_hazards(tree) -> List[tuple]:
    """``(lineno, code, description)`` for the recompile-hazard rules
    (see the block comment above)."""
    hits: List[tuple] = []
    mesh_fns = _ambient_mesh_functions(tree)

    # mesh-closure-jit: jax.jit(<ambient-mesh-reading fn>) outside a
    # mesh-parameterized factory; covers the decorator spelling too
    def scan(node, mesh_param_scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            params = {a.arg for a in node.args.args
                      + node.args.posonlyargs + node.args.kwonlyargs}
            mesh_param_scope = mesh_param_scope or any(
                "mesh" in p for p in params)
        if (isinstance(node, ast.Call) and _is_jit_func(node.func)
                and node.args and isinstance(node.args[0], ast.Name)
                and node.args[0].id in mesh_fns
                and not mesh_param_scope):
            hits.append((
                node.lineno, "mesh-closure-jit",
                f"jax.jit({node.args[0].id}) caches a trace of an "
                "ambient-mesh-reading function: the first mesh's "
                "sharding bakes into the cached jaxpr and a second "
                "mesh silently reuses it. Key the jit per mesh "
                "(lru_cache factory taking the mesh — see "
                "ops/linalg.py::_bcd_jit_for)"))
        for child in ast.iter_child_nodes(node):
            scan(child, mesh_param_scope)

    scan(tree, False)
    for fdef in ast.walk(tree):
        if not isinstance(fdef, ast.FunctionDef):
            continue
        if fdef.name not in mesh_fns:
            continue
        for dec in fdef.decorator_list:
            target = dec
            if isinstance(dec, ast.Call):  # functools.partial(jax.jit,..)
                target = (dec.args[0] if dec.args
                          and dec.func and getattr(
                              dec.func, "attr", "") == "partial"
                          else dec.func)
            if _is_jit_func(target):
                hits.append((
                    fdef.lineno, "mesh-closure-jit",
                    f"@jax.jit on {fdef.name}() bakes the ambient mesh "
                    "into one module-lifetime trace; key the jit per "
                    "mesh (see ops/linalg.py::_bcd_jit_for)"))

    # per-instance-jit-memo
    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        own = list(_own_scope_nodes(fdef))
        jit_locals = set()
        blessed = set()
        for node in own:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and _is_jit_func(node.value.func):
                jit_locals.add(node.targets[0].id)
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) and node.func.attr == "put":
                # stored into a module-level memo as well: the instance
                # attr is a fast path, not the program's only home
                for a in node.args:
                    if isinstance(a, ast.Name):
                        blessed.add(a.id)

        def self_target(t) -> bool:
            if isinstance(t, ast.Attribute):
                return isinstance(t.value, ast.Name) and t.value.id == "self"
            if isinstance(t, ast.Subscript):
                v = t.value
                return (isinstance(v, ast.Attribute)
                        and isinstance(v.value, ast.Name)
                        and v.value.id == "self")
            return False

        for node in own:
            if not isinstance(node, ast.Assign):
                continue
            if not any(self_target(t) for t in node.targets):
                continue
            direct = isinstance(node.value, ast.Call) and _is_jit_func(
                node.value.func)
            via_local = (isinstance(node.value, ast.Name)
                         and node.value.id in jit_locals
                         and node.value.id not in blessed)
            if direct or via_local:
                hits.append((
                    node.lineno, "per-instance-jit-memo",
                    "compiled program memoized on self with no global "
                    "cache behind it: every refit builds a fresh "
                    "instance and recompiles. Memoize in a module-level "
                    "LruMemo keyed on structure (the _CAST_JIT_CACHE / "
                    "_cached_jit pattern)"))

    # unstable-jit-cache-tag (from tools/lint.py; one home for all
    # recompile rules)
    for call in ast.walk(tree):
        if not (isinstance(call, ast.Call) and call.args):
            continue
        f = call.func
        if not (isinstance(f, ast.Attribute) and f.attr == "_cached_jit"):
            continue
        tag = call.args[0]
        if not (isinstance(tag, ast.Constant)
                and isinstance(tag.value, str)):
            hits.append((
                call.lineno, "unstable-jit-cache-tag",
                "_cached_jit tag must be a string literal (computed "
                "tags break warm-executable reuse across sessions)"))
    return sorted(set(hits))


# -- fusion/prefix hazard ---------------------------------------------------

def _fusion_fixpoint(graph: Graph) -> Graph:
    from ..workflow.optimizer.fusion import GatherFusionRule, MapFusionRule

    rules = [MapFusionRule(), GatherFusionRule()]
    for _ in range(1000):
        nxt = graph
        for r in rules:
            nxt = r.apply(nxt)
        if nxt is graph:
            return graph
        graph = nxt
    return graph


def fusion_prefix_lint(
    graph: Graph, fuse: Optional[Callable[[Graph], Graph]] = None
) -> List[Diagnostic]:
    """Saveable nodes must keep their canonical logical prefix under
    map/gather fusion, or fitted state saved by an optimized run can
    never be re-matched by ``SavedStateLoadRule`` on a later raw graph
    (the cross-pipeline cache-miss recorded in CHANGES.md). Detected
    statically by comparing each saveable node's prefix before and after
    the fusion rules run."""
    from ..workflow.executor import is_saveable
    from ..workflow.prefix import compute_prefix

    pre_memo: Dict[GraphId, Any] = {}
    pre = {
        n: compute_prefix(graph, n, pre_memo)
        for n in graph.nodes
        if is_saveable(graph.get_operator(n))
    }
    pre = {n: p for n, p in pre.items() if p is not None}
    if not pre:
        return []
    fused = (fuse or _fusion_fixpoint)(graph)
    if fused is graph:
        return []
    out = []
    post_memo: Dict[GraphId, Any] = {}
    for n, p in sorted(pre.items(), key=lambda kv: kv[0].id):
        if n not in fused.nodes:
            continue  # the saveable node itself was rewritten away
        p2 = compute_prefix(fused, n, post_memo)
        if p2 != p:
            out.append(Diagnostic(
                code="fusion-prefix-hazard", severity=SEVERITY_ERROR,
                node_id=n.id, operator=graph.get_operator(n).label(),
                message=("logical prefix changes under map/gather fusion; "
                         "saved fitted state for this node would never be "
                         "re-matched by SavedStateLoadRule (canonicalize "
                         "the fused operator's prefix — see "
                         "workflow/prefix.py)")))
    return out


# -- report -----------------------------------------------------------------

class AnalysisReport:
    """One static check's outcome: the abstract values per node plus all
    diagnostics, exportable in the observability layer's report style.
    ``plan`` carries the static HBM plan
    (:class:`~keystone_tpu.analysis.resources.HbmPlan`) when the
    resource planner ran."""

    def __init__(self, name: str, analysis: Analysis,
                 diagnostics: List[Diagnostic], plan: Any = None):
        self.name = name
        self.analysis = analysis
        self.diagnostics = diagnostics
        self.plan = plan

    @property
    def ok(self) -> bool:
        return not self.diagnostics

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == SEVERITY_ERROR]

    def resolved_nodes(self) -> int:
        return sum(
            1 for n in self.analysis.graph.nodes
            if not isinstance(self.analysis.value(n), Unknown))

    def to_dict(self) -> Dict[str, Any]:
        graph = self.analysis.graph
        nodes = []
        for n in sorted(graph.nodes, key=lambda g: g.id):
            spec = self.analysis.value(n)
            nodes.append({
                "node_id": n.id,
                "operator": graph.get_operator(n).label(),
                "spec": repr(spec),
            })
        return {
            "name": self.name,
            "nodes": nodes,
            "diagnostics": [asdict(d) for d in self.diagnostics],
            "plan": None if self.plan is None else self.plan.to_dict(),
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def summary(self) -> str:
        graph = self.analysis.graph
        total = len(graph.nodes)
        lines = [f"Static check {self.name!r}: {total} nodes, "
                 f"{self.resolved_nodes()} with resolved specs, "
                 f"{len(self.diagnostics)} diagnostic(s)"]
        lines.append(f"{'node':>6} {'operator':<34} spec")
        for n in sorted(graph.nodes, key=lambda g: g.id):
            spec = self.analysis.value(n)
            op = graph.get_operator(n).label()
            if isinstance(spec, (DatasetSpec, DatumSpec)):
                shown = (f"{format_element(spec.element)}"
                         + (f" x n={spec.n}"
                            if isinstance(spec, DatasetSpec) else ""))
            else:
                shown = repr(spec)
            lines.append(f"{n.id:>6} {op[:34]:<34} {shown}")
        if self.plan is not None:
            lines.append(self.plan.summary())
        if self.diagnostics:
            lines.append("diagnostics:")
            for d in self.diagnostics:
                lines.append(f"  {d}")
        else:
            lines.append("no diagnostics: pipeline is statically clean")
        return "\n".join(lines)


def check_graph(
    graph: Graph,
    source_specs: Optional[Mapping[SourceId, AbstractValue]] = None,
    name: str = "graph",
    hbm_budget: Optional[float] = None,
    data_shards: Optional[int] = None,
) -> AnalysisReport:
    """Run the abstract interpreter, every lint, and the static HBM
    planner over ``graph``. ``hbm_budget`` (bytes) adds an
    ``hbm-budget`` ERROR diagnostic when the plan's fit-path peak
    exceeds it — the device-free form of the runtime budget assert
    (budgets are checked twice, PERFORMANCE.md). ``data_shards``
    overrides the mesh-derived data-axis width the planner divides
    batch effects across — so ``check --budget --shards N`` verifies
    the PER-HOST charge of an N-shard world from a single-host
    machine (the sharded-apply sizing runbook, CLUSTER.md)."""
    source_specs = dict(source_specs or {})
    analysis = analyze(graph, source_specs)
    diagnostics = list(analysis.diagnostics)
    diagnostics += unbound_source_lint(graph, source_specs)
    diagnostics += dead_branch_lint(graph)
    diagnostics += dtype_narrowing_lint(analysis)
    diagnostics += host_sync_lint(graph)
    diagnostics += fusion_prefix_lint(graph)
    diagnostics += non_streamable_fit_lint(analysis)
    diagnostics += host_stage_on_stream_lint(analysis)
    from .spmd import sharding_flow_lint

    diagnostics += sharding_flow_lint(analysis)
    from .resources import plan_graph

    plan = plan_graph(analysis, name=name, data_shards=data_shards)
    if plan.over_budget(hbm_budget):
        mib = 1 << 20
        diagnostics.append(Diagnostic(
            code="hbm-budget", severity=SEVERITY_ERROR,
            node_id=plan.peak_node, operator="",
            message=(
                f"static HBM plan peaks at "
                f"{plan.fit_peak_nbytes / mib:.2f} MiB "
                f"(node {plan.peak_node}) > budget "
                f"{float(hbm_budget) / mib:.2f} MiB — the fit would "
                "violate its budget at runtime; shrink the resident "
                "working set (stream the fit, reduce chunk/prefetch "
                "geometry, cache fewer intermediates)")))
    return AnalysisReport(name, analysis, diagnostics, plan=plan)


def check_pipeline(pipeline, sample: Any = None,
                   name: str = "pipeline",
                   hbm_budget: Optional[float] = None,
                   data_shards: Optional[int] = None) -> AnalysisReport:
    """``Pipeline.check``'s engine: bind ``sample`` (an input spec — see
    ``spec.as_input_spec``) to the pipeline's dangling source and check
    the full graph (lints + static HBM plan, optionally against an
    ``hbm_budget`` in bytes; ``data_shards`` overrides the planner's
    data-axis width for per-host verification)."""
    p = pipeline.to_pipeline()
    specs = {}
    if sample is not None:
        specs[p._source] = as_input_spec(sample)
    return check_graph(_streamed_form(p._graph), specs, name=name,
                       hbm_budget=hbm_budget, data_shards=data_shards)


def _streamed_form(graph: Graph) -> Graph:
    """The graph a fit would run where the optimizer hands a gather too
    wide for the device to its solver (``workflow/optimizer/
    stream_gather.py``: a static choice, from shapes and the device's
    memory): the plan then charges the factors and a block, not a
    design matrix nothing will make. Any other graph is checked as it
    was built."""
    from ..workflow.optimizer.rules import EquivalentNodeMergeRule
    from ..workflow.optimizer.stream_gather import GatherStreamingRule

    merged = graph
    for _ in range(100):
        again = EquivalentNodeMergeRule().apply(merged)
        if again is merged:
            break
        merged = again
    streamed = GatherStreamingRule().apply(merged)
    return graph if streamed is merged else streamed
