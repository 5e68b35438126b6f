"""Compile observatory: every XLA compile as first-class telemetry.

The optimizer's whole value proposition is cost-model-driven choice of
what executes — but until now the repo had no visibility into the one
cost the cost model cannot predict: *compilation*. Cold compiles bleed
into timed bench sections (part of the documented 76-85k e2e noise
band), and an accidental recompile on the hot path (the pre-PR-5
``_CAST_JIT_CACHE`` per-instance memo, the pre-PR-2 ``_bcd_jit_for``
mesh bake) silently multiplies chunk latency. PR 6's static
recompile-hazard lints catch known *shapes* of that bug; this module is
the dynamic complement — it observes what ACTUALLY compiled, when, and
why.

Two cooperating mechanisms:

* a process-global ``jax.monitoring`` listener (registered lazily, once)
  hears every ``/jax/core/compile/*`` event the runtime emits — tracing,
  MLIR lowering, and backend compilation — and what the persistent
  compilation cache says inside the last of them
  (``/jax/compilation_cache/*``: hit or miss, the seconds the read
  took, the compile time stored beside the executable), so even jits
  the repo does NOT own (an ``@jax.jit`` local to a script) are counted;
* the jit entry points the repo owns (``utils.donation.donating_jit``,
  ``Transformer._cached_jit`` / ``struct_cached_jit``, the streaming
  wire-cast ``_CAST_JIT_CACHE``, the ``ops/linalg.py`` solvers, the
  ``ops/pallas_kernels.py`` fused kernels) route their calls through
  :func:`watch_jit`, which attributes those compile events to a named
  *site*, classifies the trigger (``first-compile`` vs
  ``signature-change`` vs ``mesh-change`` vs ``retrace``), and names the
  abstract-signature delta that caused it
  (``arg0: float32[1024,3072] -> float32[2048,3072]``).

**One record a compile** (``CompileObservatory.records``, and the args
of its ``compile:<site>`` span): ``name`` (the watched site, else the
executor node that dispatched an unowned compile, else ``<unowned>``),
``program`` (jax's own name of the record's costliest program, as a
jax logs it: ``jit(_block_solve)``), ``trigger``, ``delta``,
``context``, and

* ``wall_s`` = ``trace_s`` + ``lower_s`` + ``backend_s``: the seconds
  inside ``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration``
  and ``backend_compile_duration``, kept apart. jax times every jit it
  traces, also those an outer trace calls (every ``jnp`` operation is
  one) and those a lowering rule traces, inside the seconds of the
  phase that was open: only the outermost interval is added (until
  PR 36 ``wall_s`` summed them all, a trace three jits deep three
  times), so ``wall_s`` is at most ``t_end - t_start``. The first two are the
  host's own Python, which no cache saves. The third wraps jax's
  ``compile_or_get_cached``, so with a persistent cache configured it
  CONTAINS the cache read: on a hit ``wall_s`` is mostly retrieval
  (``cache_read_s``), not compilation;
* ``cache``: ``"hit"`` (every program of the record was read from the
  persistent cache), ``"miss"`` (at least one was compiled and
  written to it), ``"off"`` (no cache configured, or the entry under
  the cache's thresholds); ``cache_hits`` / ``cache_misses`` count the
  record's programs (one observed call can compile several);
* ``cold_s``: the backend seconds a process with an EMPTY cache pays
  for the same programs: ``backend_s`` on a miss or with the cache
  off; on a hit the compile time jax stored beside the executable
  (``saved + retrieval``, never under 0). That is a 4-byte integer of
  whole seconds, so a program that compiled in under a second reads 0;
* ``t_start`` / ``t_end``: ``time.perf_counter`` seconds, the clock of
  the flight recorder's ring: the observed call's entry (an unowned
  compile: the start of its first event) and its last backend event.

Every recorded compile feeds the three existing telemetry funnels:

* :class:`~.metrics.MetricsRegistry` — ``compile.count`` counter,
  ``compile.wall_s`` histogram, ``compile.unexpected_total`` counter,
  ``compile.cache_hits`` / ``compile.cache_misses`` (programs);
* the :class:`~.timeline.FlightRecorder` — one ``compile:<site>`` span
  per compile (its own category, so the Perfetto export shows compile
  wall on the timeline next to ingest/compute lanes, and a cache read
  and a compile as what they are);
* the active :class:`~.trace.PipelineTrace` — ``record_compile``
  entries with the full classification.

**Runtime recompile detection** (the dynamic recompile gate): a
*warmup fence* (:meth:`CompileObservatory.arm_fence`) marks the end of
a pipeline's warmup phase; ANY compile recorded while a fence is armed
is classified *unexpected*, increments ``compile.unexpected_total``,
and carries the site name plus the signature delta that triggered it.
``fit_streaming`` arms the fence once its chunk loop reaches steady
state (every chunk shares one padded shape, so the loop must compile
nothing — the PR 3 invariant, now asserted dynamically), bench's
``_timed_median(warmup_fence=True)`` arms it around timed reps, and
``bin/ci.sh``'s recompile gate (``tools/recompile_gate.py``) fails if a
second epoch compiles anything at all.

**Cost capture** for the utilization layer (:mod:`.utilization`): each
site stores the abstract signature (``jax.ShapeDtypeStruct`` avals +
static argument values) of its compiles, so
``Compiled.cost_analysis()`` / ``memory_analysis()`` can be resolved
*on demand* via the AOT path (``jitted.lower(*avals).compile()`` — a
warm in-memory/persistent-cache hit, never an execution) without
paying an eager analysis on every compile.

Thread model: compiles happen synchronously on whatever thread
dispatches the jit call (the streaming consumer, a decode worker, the
driver), so all shared state here is locked. The observatory's guard is
a PLAIN ``threading.Lock`` — records feed the metrics registry and
flight recorder, the same re-entrancy boundary as
``observability/metrics.py`` (documented in ``utils/guarded.py``).
``KEYSTONE_COMPILE_LOG=0`` disables observation entirely (wrappers
become pass-throughs; one env read per call).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from ..utils.guarded import guarded_by
from .metrics import MetricsRegistry
from .timeline import record_span
from .trace import current_trace

# -- thread-local attribution -------------------------------------------------

_TLS = threading.local()


class _Frame:
    """One in-flight observed call (or attribution context) on this
    thread. ``site`` is a :class:`_JitSite` for observed jit calls
    (compile events accumulate here and the wrapper records them on
    return), ``None`` for label-only contexts (executor node scopes —
    the listener records unowned compiles immediately, attributed to
    the label), and :data:`_SWALLOW` while the observatory itself
    compiles for cost capture (those events must not count)."""

    __slots__ = ("site", "label", "heard")

    def __init__(self, site, label):
        self.site = site
        self.label = label
        self.heard: Optional[_Heard] = None  # made by the first event heard


class _Heard:
    """What the listener has heard of ONE record's compiles on one
    thread: the three phases kept apart and what the persistent cache
    said of each program. All of it fires inside the call that the
    terminal ``backend_compile_duration`` closes, on the compiling
    thread, so it folds into the frame of the observed call (owned
    sites) or the thread's pending record (unowned compiles)."""

    __slots__ = ("t_start", "t_end", "trace_s", "lower_s", "backend_s",
                 "cache_read_s", "cold_s", "hits", "misses", "programs",
                 "program", "_longest_s", "_open", "_hit", "_saved_s",
                 "_read_s")

    def __init__(self) -> None:
        self.t_start: Optional[float] = None
        self.t_end: Optional[float] = None
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.cache_read_s = self.cold_s = 0.0
        self.hits = self.misses = self.programs = 0
        self.program: Optional[str] = None   # jax's name of the costliest
        self._longest_s = -1.0
        self._open = 0   # phases begun and not yet ended
        # the program in flight, until its backend event closes it
        self._hit = False
        self._saved_s = self._read_s = 0.0

    @classmethod
    def of_backend(cls, wall_s: float, t_start: float) -> "_Heard":
        """A record made by hand (no listener): all of it backend."""
        heard = cls()
        heard.t_start, heard.t_end = t_start, t_start + wall_s
        heard.backend_s = heard.cold_s = wall_s
        heard.programs = 1
        return heard

    def phase_begun(self) -> None:
        self._open += 1

    def duration(self, name: str, seconds: float,
                 fun_name: Optional[str] = None) -> None:
        if name == _CACHE_READ_EVENT:
            self.cache_read_s += seconds
            self._read_s = seconds
            return
        if name == _CACHE_SAVED_EVENT:   # a difference, no interval
            self._saved_s = seconds
            return
        # a phase: an interval that ends now. jax times every jit it
        # traces, the jitted functions an outer trace calls too (each
        # ``jnp`` operation is one) and those a lowering rule traces,
        # INSIDE the seconds of the phase that was open: only the
        # outermost interval is added, or the phases count twice
        if self._open:
            self._open -= 1
        outermost = not self._open
        now = time.perf_counter()
        if outermost and (self.t_start is None
                          or now - seconds < self.t_start):
            self.t_start = now - seconds
        if name == _BACKEND_EVENT:
            self.programs += 1
            self.t_end = now
            self.cold_s += (max(self._saved_s + self._read_s, 0.0)
                            if self._hit else seconds)
            if seconds > self._longest_s:
                self._longest_s, self.program = seconds, fun_name
            self._hit = False
            self._saved_s = self._read_s = 0.0
            if outermost:
                self.backend_s += seconds
        elif outermost:
            if name == _TRACE_EVENT:
                self.trace_s += seconds
            else:
                self.lower_s += seconds

    def event(self, name: str) -> None:
        if name == _CACHE_HIT_EVENT:
            self.hits += 1
            self._hit = True
        elif name == _CACHE_MISS_EVENT:
            self.misses += 1

    @property
    def wall_s(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s

    def fields(self) -> Dict[str, Any]:
        return {
            "t_start": self.t_start, "t_end": self.t_end,
            "trace_s": self.trace_s, "lower_s": self.lower_s,
            "backend_s": self.backend_s,
            "cache": ("miss" if self.misses else
                      "hit" if self.hits else "off"),
            "cache_hits": self.hits, "cache_misses": self.misses,
            "cache_read_s": self.cache_read_s, "cold_s": self.cold_s,
            "program": self.program,
        }


_SWALLOW = object()


def _stack() -> List[_Frame]:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _context_label() -> Optional[str]:
    """Innermost label-only attribution context on this thread."""
    for frame in reversed(_stack()):
        if frame.site is None and frame.label is not None:
            return frame.label
    return None


@contextlib.contextmanager
def compile_context(label: str) -> Iterator[None]:
    """Attribute any compile on this thread inside the block to
    ``label`` (the executor wraps node thunks so a compile triggered by
    an unobserved app-level jit still names the pipeline node that
    dispatched it). Registers the monitoring listener itself: the
    unowned compiles this context exists to attribute must be visible
    even when no watched jit has run yet in this process."""
    if observation_enabled():
        _ensure_listener()
    stack = _stack()
    # entering an attribution context means no unowned compile is in
    # flight on this thread, so anything pending belongs to
    # a compile that ABORTED mid-trace (its terminal backend event
    # never fired) — drop it rather than inflate the next unowned one
    _TLS.pending = None
    stack.append(_Frame(None, label))
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def _swallow_compiles() -> Iterator[None]:
    """Suppress recording for compiles the observatory itself triggers
    (AOT cost capture must not count as workload compilation, and must
    never trip an armed fence)."""
    stack = _stack()
    stack.append(_Frame(_SWALLOW, None))
    try:
        yield
    finally:
        stack.pop()


# -- the jax.monitoring listener ---------------------------------------------

_LISTENER_LOCK = threading.Lock()
_LISTENER_READY = False
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_PHASE_EVENTS = frozenset((_TRACE_EVENT, _LOWER_EVENT, _BACKEND_EVENT))
_DURATION_EVENTS = _PHASE_EVENTS | {_CACHE_READ_EVENT, _CACHE_SAVED_EVENT}
_PLAIN_EVENTS = frozenset((_CACHE_HIT_EVENT, _CACHE_MISS_EVENT))


def _hearing() -> Optional[_Heard]:
    """Where an event heard now on this thread folds in: the frame of
    the observed call in flight, else the thread's pending unowned
    record; ``None`` while the observatory itself compiles, or with
    observation switched off (the listener survives a mid-process
    disable; honor it)."""
    if not observation_enabled():
        return None
    stack = _stack()
    frame = stack[-1] if stack else None
    if frame is not None and frame.site is not None:
        if frame.site is _SWALLOW:
            return None
        if frame.heard is None:
            frame.heard = _Heard()
        return frame.heard
    pending = getattr(_TLS, "pending", None)
    if pending is None:
        pending = _TLS.pending = _Heard()
    return pending


def _on_jax_event(name: str, duration: float, fun_name: Optional[str] = None,
                  **_kw: Any) -> None:
    """Fed every jax duration event; folds the three
    ``/jax/core/compile/*`` phases and the persistent cache's two
    durations into the observatory. The terminal
    ``backend_compile_duration`` closes one program. Runs on the thread
    that dispatched the compiling call."""
    if name not in _DURATION_EVENTS:
        return
    heard = _hearing()
    if heard is None:
        return
    heard.duration(name, float(duration), fun_name)
    # unowned compile (no observed jit in flight on this thread):
    # record it the moment the backend compile completes, attributed to
    # the nearest label context (an executor node scope) if any
    if name == _BACKEND_EVENT and heard is getattr(_TLS, "pending", None):
        _TLS.pending = None
        compile_observatory().record(
            name=_context_label() or "<unowned>", wall_s=heard.wall_s,
            trigger="unowned", heard=heard)


def _on_jax_scalar(name: str, _value: float, **_kw: Any) -> None:
    """Fed every jax scalar: the runtime reports the START of each of
    its timed phases as one, which is how a phase inside another is
    told from the outermost."""
    if name not in _PHASE_EVENTS:
        return
    heard = _hearing()
    if heard is not None:
        heard.phase_begun()


def _on_jax_plain_event(name: str, **_kw: Any) -> None:
    """Fed every plain jax event: the persistent cache's hit and miss,
    which fire inside the backend event of the program they speak of."""
    if name not in _PLAIN_EVENTS:
        return
    heard = _hearing()
    if heard is not None:
        heard.event(name)


def _ensure_listener() -> None:
    global _LISTENER_READY
    if _LISTENER_READY:
        return
    with _LISTENER_LOCK:
        if _LISTENER_READY:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        jax.monitoring.register_event_listener(_on_jax_plain_event)
        jax.monitoring.register_scalar_listener(_on_jax_scalar)
        _LISTENER_READY = True


def observation_enabled() -> bool:
    return os.environ.get("KEYSTONE_COMPILE_LOG", "1") != "0"


# -- abstract signatures ------------------------------------------------------

def _leaf_desc(x: Any) -> Tuple[str, str]:
    """``(shape/dtype description, sharding description)`` for one call
    argument leaf. Static (non-array) values describe as their repr, so
    a changed static argument reads as a signature change too."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        r = repr(x)
        return (f"static:{r[:64]}", "")
    desc = f"{dtype}[{','.join(str(d) for d in shape)}]"
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return (desc, "")
    try:
        mesh = getattr(sharding, "mesh", None)
        spec = getattr(sharding, "spec", None)
        if mesh is not None and spec is not None:
            sh = (f"{tuple(sorted(dict(mesh.shape).items()))}"
                  f":{spec}")
        else:
            sh = f"devices={len(getattr(sharding, 'device_set', ()))}"
    except Exception:
        sh = "?"
    return (desc, sh)


def _has_tracer(leaves: List[Any]) -> bool:
    try:
        import jax

        return any(isinstance(l, jax.core.Tracer) for l in leaves)
    except Exception:
        return False


def _signature(args: tuple, kwargs: dict):
    """``(full_sig, shapes_sig, descs, avals)`` of one call: ``full_sig``
    includes per-leaf sharding (the jit cache's real key surface),
    ``shapes_sig`` drops it (so a new full_sig whose shapes were already
    seen classifies as a MESH change, not a shape change), ``descs`` is
    the human-readable per-leaf list deltas are named from, and
    ``avals`` is the ``(lower_args, lower_kwargs)`` pair the AOT cost
    path can replay (None when any leaf resists abstraction)."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    if _has_tracer(leaves):
        return None
    descs: List[Tuple[str, str]] = [_leaf_desc(l) for l in leaves]
    tdr = str(treedef)
    full = (tdr, tuple(descs))
    shapes = (tdr, tuple(d for d, _ in descs))
    lower_args: Optional[tuple] = None
    try:
        def to_aval(x):
            shape = getattr(x, "shape", None)
            dtype = getattr(x, "dtype", None)
            if shape is not None and dtype is not None:
                return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))
            return x  # static value: replayed verbatim

        la = tuple(jax.tree_util.tree_map(to_aval, args))
        lk = {k: jax.tree_util.tree_map(to_aval, v)
              for k, v in kwargs.items()}
        lower_args = (la, lk)
    except Exception:
        lower_args = None
    return full, shapes, tuple(d + (f"@{s}" if s else "")
                               for d, s in descs), lower_args


def _delta(prev: Optional[Tuple[str, ...]],
           cur: Tuple[str, ...]) -> Optional[str]:
    """Human-readable signature delta: which argument leaves changed."""
    if prev is None:
        return None
    parts: List[str] = []
    if len(prev) != len(cur):
        parts.append(f"arity {len(prev)} -> {len(cur)}")
    for i, (p, c) in enumerate(zip(prev, cur)):
        if p != c:
            parts.append(f"arg{i}: {p} -> {c}")
    return "; ".join(parts[:6]) + (" ..." if len(parts) > 6 else "") \
        if parts else None


# -- observed jit sites -------------------------------------------------------

@guarded_by("_site_lock", "seen", "shape_keys", "last_descs", "avals",
            "calls", "stats")
class _JitSite:
    """Per-site compile bookkeeping: seen signatures (trigger
    classification), the last signature's leaf descriptions (delta
    naming), replayable avals per signature (AOT cost capture), call
    and compile counts. Mutated from whichever thread dispatches the
    site (streaming consumer, decode workers), hence the lock."""

    AVAL_KEEP = 8  # replayable signatures retained per site

    __slots__ = ("name", "jitted", "seen", "shape_keys", "last_descs",
                 "avals", "calls", "compiles", "stats", "_site_lock")

    def __init__(self, name: str, jitted: Callable):
        self.name = name
        self.jitted = jitted
        self.seen: Dict[Any, None] = {}
        self.shape_keys: Dict[Any, None] = {}
        self.last_descs: Optional[Tuple[str, ...]] = None
        self.avals: Dict[Any, Tuple] = {}
        self.calls = 0
        self.compiles = 0
        self.stats: Dict[Any, Dict[str, float]] = {}
        self._site_lock = threading.Lock()

    def classify(self, sig) -> Tuple[str, Optional[str]]:
        """Fold one observed compile's signature in; returns
        ``(trigger, delta)``."""
        if sig is None:
            with self._site_lock:
                self.compiles += 1
            return "retrace", None
        full, shapes, descs, lower = sig
        with self._site_lock:
            self.compiles += 1
            if not self.seen:
                trigger = "first-compile"
            elif full in self.seen:
                # same abstract signature compiled again: the executable
                # fell out of a cache, or a fresh jit wrapper was built
                # for an equivalent program (the per-instance-memo bug
                # class PR 6 lints against — now visible dynamically)
                trigger = "retrace"
            elif shapes in self.shape_keys:
                trigger = "mesh-change"
            else:
                trigger = "signature-change"
            delta = _delta(self.last_descs, descs)
            self.seen[full] = None
            self.shape_keys[shapes] = None
            self.last_descs = descs
            if lower is not None:
                self.avals[full] = lower
                while len(self.avals) > self.AVAL_KEEP:
                    self.avals.pop(next(iter(self.avals)))
        return trigger, delta

    # -- AOT cost capture (utilization layer) --------------------------
    def capture_stats(self, sig_key: Any = None) -> Optional[Dict[str, float]]:
        """``cost_analysis``/``memory_analysis`` of one compiled
        signature (the most recent one by default), resolved through
        the AOT path from the stored avals — a warm cache hit, never an
        execution; compiles it triggers are swallowed. Returns None
        when the signature cannot be replayed (opaque static args) or
        analysis is unavailable on this backend."""
        with self._site_lock:
            if sig_key is None and self.avals:
                sig_key = next(reversed(self.avals))
            cached = self.stats.get(sig_key)
            lower = self.avals.get(sig_key)
        if cached is not None:
            return cached
        if lower is None:
            return None
        la, lk = lower
        try:
            with _swallow_compiles():
                compiled = self.jitted.lower(*la, **lk).compile()
            stats = executable_stats(compiled)
        except Exception:
            return None
        if stats is None:
            return None
        return self._adopt_stats(sig_key, stats)

    def _adopt_stats(self, sig_key: Any,
                     stats: Dict[str, float]) -> Dict[str, float]:
        """Atomic publish of one signature's captured stats: the
        check-then-store is ONE ``setdefault`` under ONE lock hold, so
        two captures racing the same signature converge on the FIRST
        writer's dict — the loser adopts it and every caller holds the
        same object. (The pre-PR-10 blind ``stats[sig_key] = stats``
        overwrite was a lost update: value-equal, but two callers could
        hold two distinct dicts — allowlisted then, fixed now; the AOT
        compile itself stays outside the lock, it can take seconds.)"""
        with self._site_lock:
            return self.stats.setdefault(sig_key, stats)

    def snapshot(self) -> Dict[str, Any]:
        with self._site_lock:
            return {
                "name": self.name,
                "calls": self.calls,
                "compiles": self.compiles,
                "signatures": len(self.seen),
                "last_signature": (list(self.last_descs)
                                   if self.last_descs else None),
                "stats": {str(k): dict(v) for k, v in self.stats.items()},
            }


#: every watched jit site in this process. Effectively append-only and
#: code-defined, but bounded anyway: one caller builds a watched jit
#: per call (the uncacheable-fn fallback in ``_masked_vmap`` — the
#: exact recompile hazard the observatory exists to surface), and a
#: long-running service on that path must leak site bookkeeping no
#: faster than the oldest rows can be dropped.
_SITES: List[_JitSite] = []
_SITES_CAP = 4096
_SITES_LOCK = threading.Lock()


def registered_sites() -> Tuple[_JitSite, ...]:
    with _SITES_LOCK:
        return tuple(_SITES)


def executable_stats(compiled) -> Optional[Dict[str, float]]:
    """Normalize one ``jax.stages.Compiled``'s ``cost_analysis()`` +
    ``memory_analysis()`` into a flat dict (jax returns the cost dict
    bare or as a one-per-computation list depending on version; memory
    analysis is a ``CompiledMemoryStats`` struct when the backend
    provides one)."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if ca is not None:
        parts = ca if isinstance(ca, (list, tuple)) else [ca]
        flops = sum(float(p.get("flops", 0.0)) for p in parts
                    if isinstance(p, dict))
        bytes_accessed = sum(float(p.get("bytes accessed", 0.0))
                             for p in parts if isinstance(p, dict))
        out["flops"] = flops
        out["bytes_accessed"] = bytes_accessed
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for key in ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes"):
            value = getattr(ma, key, None)
            if value is not None:
                out[key.replace("_size_in_bytes", "_bytes")] = float(value)
    return out or None


def watch_jit(jitted: Callable, name: str) -> Callable:
    """Route calls of an already-jitted callable through the compile
    observatory under ``name``. The wrapper's fast path (no compile
    this call) costs two thread-local list ops and one locked counter
    bump; signatures are only computed when the jax runtime actually
    compiled something during the call."""
    site = _JitSite(name, jitted)
    with _SITES_LOCK:
        _SITES.append(site)
        if len(_SITES) > _SITES_CAP:
            del _SITES[: len(_SITES) - _SITES_CAP]

    def wrapper(*args: Any, **kwargs: Any) -> Any:
        if not observation_enabled():
            return jitted(*args, **kwargs)
        _ensure_listener()
        with site._site_lock:
            site.calls += 1
        stack = _stack()
        if not stack and getattr(_TLS, "pending", None) is not None:
            # same reasoning as compile_context: a fresh top-level
            # observed call proves any pending unowned record is from an
            # aborted compile — discard it. UNLESS the args carry
            # tracers: then an unowned outer jit is mid-trace on this
            # thread (jit-of-jit inlining this site), what it has
            # accumulated is live and belongs to its terminal backend
            # event. The tracer scan only runs on the rare pending path,
            # so the no-compile fast path stays two list ops + a counter.
            import jax

            leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
            if not _has_tracer(leaves):
                _TLS.pending = None
        frame = _Frame(site, name)
        stack.append(frame)
        t0 = time.perf_counter()
        try:
            return jitted(*args, **kwargs)
        finally:
            stack.pop()
            # only a terminal backend_compile event counts: jaxpr-trace
            # durations alone fire when this site is being INLINED into
            # an outer program's trace (jit-of-jit), which is the outer
            # site's compile, not a new one here
            heard = frame.heard
            if heard is not None and heard.programs:
                heard.t_start = t0
                _record_site_compile(site, args, kwargs, heard)

    wrapper.__name__ = getattr(jitted, "__name__", name)
    wrapper.__doc__ = getattr(jitted, "__doc__", None)
    wrapper.__wrapped__ = jitted
    wrapper._keystone_site = site
    # AOT surface passthrough (utilization / check --xla)
    wrapper.lower = getattr(jitted, "lower", None)
    return wrapper


def observed_jit(fn: Callable = None, *, name: Optional[str] = None,
                 **jit_kwargs: Any) -> Callable:
    """``jax.jit`` with compile observation: a drop-in decorator for
    module-level jits (``@functools.partial(observed_jit,
    static_argnames=...)`` mirrors the ``jax.jit`` spelling). The
    recompile-hazard lints treat ``observed_jit`` exactly like
    ``jax.jit`` (``analysis.diagnostics._is_jit_func``), so observation
    never weakens the static gates."""
    if fn is None:
        return lambda f: observed_jit(f, name=name, **jit_kwargs)
    import jax

    return watch_jit(jax.jit(fn, **jit_kwargs),
                     name or getattr(fn, "__name__", "jit"))


def _record_site_compile(site: _JitSite, args: tuple, kwargs: dict,
                         heard: _Heard) -> None:
    sig = _signature(args, kwargs)
    trigger, delta = site.classify(sig)
    compile_observatory().record(
        name=site.name, wall_s=heard.wall_s, trigger=trigger,
        delta=delta, context=_context_label(), heard=heard,
        signature=(list(sig[2]) if sig is not None else None))


# -- the observatory ----------------------------------------------------------

@guarded_by("_lock", "records", "_wall_s", "_count", "_unexpected",
            "_fence_labels", "_by_name")
class CompileObservatory:
    """Process-global compile event log: bounded record tail, exact
    aggregates, and the warmup fence. Records are appended from
    whichever thread compiled; reads come from bench / tests / the
    post-mortem dumper."""

    RECORD_TAIL = 512

    def __init__(self) -> None:
        self.records: List[Dict[str, Any]] = []
        self._wall_s = 0.0
        self._count = 0
        self._unexpected = 0
        self._fence_labels: List[str] = []
        self._by_name: Dict[str, int] = {}
        # plain lock: records feed metrics + the flight recorder, the
        # same boundary as observability/metrics.py
        self._lock = threading.Lock()

    # -- the warmup fence ----------------------------------------------
    def arm_fence(self, label: str = "warmup") -> None:
        """End of a warmup phase: until :meth:`disarm_fence`, every
        recorded compile is *unexpected* (counted in
        ``compile.unexpected_total`` and flagged on its record). Nested
        arms compose as a stack — the innermost live label wins, and
        disarming an inner fence restores the outer one's label (a
        recompile during bench's predict phase must name the bench
        fence, not the fit fence that already ended). Arming also
        registers the monitoring listener: a fence in a fresh process
        (``expect_no_compiles`` around a plain ``jax.jit`` workload,
        no watched site run yet) would otherwise silently see nothing."""
        if observation_enabled():
            _ensure_listener()
        with self._lock:
            self._fence_labels.append(label)

    def disarm_fence(self) -> None:
        with self._lock:
            if self._fence_labels:
                self._fence_labels.pop()

    @property
    def fenced(self) -> bool:
        with self._lock:
            return bool(self._fence_labels)

    # -- recording -----------------------------------------------------
    def record(self, *, name: str, wall_s: float, trigger: str,
               delta: Optional[str] = None, context: Optional[str] = None,
               heard: Optional[_Heard] = None,
               signature: Optional[List[str]] = None) -> None:
        """Fold one compile in: aggregates + bounded record tail under
        the lock; the metrics / flight-recorder / trace fan-out happens
        OUTSIDE it (each funnel takes its own lock). ``heard`` is what
        the listener heard of it (module docstring, "One record a
        compile"); a record made by hand counts as one backend compile
        of ``wall_s`` that ends now, with no cache."""
        wall_s = float(wall_s)
        if heard is None:
            heard = _Heard.of_backend(wall_s, time.perf_counter() - wall_s)
        entry: Dict[str, Any] = {
            "name": name,
            "wall_s": wall_s,
            "trigger": trigger,
            **heard.fields(),
        }
        if delta:
            entry["delta"] = delta
        if context:
            entry["context"] = context
        if signature:
            entry["signature"] = signature
        with self._lock:
            unexpected = bool(self._fence_labels)
            if unexpected:
                entry["unexpected"] = True
                entry["fence"] = self._fence_labels[-1]
                self._unexpected += 1
            self._count += 1
            self._wall_s += wall_s
            self._by_name[name] = self._by_name.get(name, 0) + 1
            self.records.append(entry)
            if len(self.records) > self.RECORD_TAIL:
                del self.records[: len(self.records) - self.RECORD_TAIL]
        reg = MetricsRegistry.get_or_create()
        reg.counter("compile.count").inc()
        reg.histogram("compile.wall_s").observe(wall_s)
        if unexpected:
            reg.counter("compile.unexpected_total").inc()
        if heard.hits:
            reg.counter("compile.cache_hits").inc(heard.hits)
        if heard.misses:
            reg.counter("compile.cache_misses").inc(heard.misses)
        record_span(f"compile:{name}", "compile", heard.t_start, wall_s, args={
            k: v for k, v in entry.items() if k not in ("name", "wall_s")})
        tr = current_trace()
        if tr is not None:
            tr.record_compile(dict(entry))

    # -- views ---------------------------------------------------------
    def wall_s_total(self) -> float:
        with self._lock:
            return self._wall_s

    def count_total(self) -> int:
        with self._lock:
            return self._count

    def unexpected_total(self) -> int:
        with self._lock:
            return self._unexpected

    def tail(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self.records]

    def unexpected_records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self.records if e.get("unexpected")]

    def by_name(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._by_name)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "count": self._count,
                "wall_s": self._wall_s,
                "unexpected": self._unexpected,
                "by_name": dict(self._by_name),
                "tail": [dict(e) for e in self.records[-32:]],
            }


def executable_table(capture: bool = False,
                     max_capture: int = 32) -> List[Dict[str, Any]]:
    """Per-site executable summary (calls, compiles, signatures, and
    any captured cost/memory stats). ``capture=True`` resolves missing
    ``memory_analysis`` stats through the AOT path first (bounded by
    ``max_capture`` sites) — what the device-OOM post-mortem embeds so
    the dump says WHICH executables held HBM, not just that one ran
    out."""
    sites = registered_sites()
    if capture:
        # most-recently-registered first: at dump time (a device OOM)
        # the sites that matter are the ones the crashing workload just
        # built, and the capture budget must not be spent on stale
        # sites from earlier in a long-lived process
        captured = 0
        for site in reversed(sites):
            if captured >= max_capture:
                break
            if site.stats or not (site.calls or site.compiles):
                continue
            if site.capture_stats() is not None:
                captured += 1
    rows: List[Dict[str, Any]] = []
    for site in sites:
        snap = site.snapshot()
        if snap["calls"] or snap["compiles"]:
            rows.append(snap)
    return rows


# -- process-global singleton -------------------------------------------------

_OBSERVATORY: Optional[CompileObservatory] = None
_OBSERVATORY_LOCK = threading.Lock()


def compile_observatory() -> CompileObservatory:
    global _OBSERVATORY
    obs = _OBSERVATORY
    if obs is None:
        with _OBSERVATORY_LOCK:
            obs = _OBSERVATORY
            if obs is None:
                obs = _OBSERVATORY = CompileObservatory()
    return obs


def reset_compile_observatory() -> None:
    """Drop the global observatory (tests): records, aggregates, and —
    critically — any fence a failed test left armed. Per-site signature
    memory is NOT cleared (it mirrors jax's own executable caches,
    which also survive)."""
    global _OBSERVATORY
    with _OBSERVATORY_LOCK:
        _OBSERVATORY = None


@contextlib.contextmanager
def expect_no_compiles(label: str = "steady-state") -> Iterator[None]:
    """Arm the warmup fence for the enclosed block (compiles inside are
    unexpected); disarms even when the block raises."""
    obs = compile_observatory()
    obs.arm_fence(label)
    try:
        yield
    finally:
        obs.disarm_fence()


def is_device_oom(exc: BaseException) -> bool:
    """True for XLA device allocation failures (``RESOURCE_EXHAUSTED``
    / out-of-memory runtime errors) — the failure class whose
    post-mortem should carry the per-executable memory table."""
    if isinstance(exc, MemoryError):
        return True
    text = str(exc)
    return ("RESOURCE_EXHAUSTED" in text
            or "Out of memory" in text
            or "out of memory" in text
            or "Allocation failure" in text)
