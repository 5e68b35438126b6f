"""The repo's own static gate — run before every PR.

Three layers, all hermetic (no data, no device buffers):

1. **Pipeline checks**: ``python -m keystone_tpu check`` semantics over
   every registered app (``keystone_tpu.pipelines.CHECK_APPS``) — the
   abstract interpreter plus graph lints must report zero diagnostics.
2. **Custom AST rules** over the ``keystone_tpu`` source tree:
   - ``host-coercion-in-apply``: a device-side ``Transformer.apply``
     body must not call ``np.asarray``/``np.array`` on its item
     argument (forces a per-item device sync; ADVICE r2/r3 lineage).
     HostTransformers are exempt.
   - **recompile hazards** (``analysis.diagnostics.recompile_hazards``,
     tree-wide): ``mesh-closure-jit`` — a module-lifetime ``jax.jit``
     of an ambient-mesh-reading function (the pre-PR-2 ``_bcd_jit_for``
     bug: the first mesh's sharding bakes into the cached trace);
     ``per-instance-jit-memo`` — a compiled program memoized on
     ``self`` with no global cache behind it (the ``_CAST_JIT_CACHE``
     lesson: refits rebuild the instance and recompile);
     ``unstable-jit-cache-tag`` — ``self._cached_jit(tag, ...)`` must
     pass a string-literal tag (computed tags break warm-executable
     reuse across sessions).
   - **donation safety** (``analysis.diagnostics.donation_hazards``,
     tree-wide): ``use-after-donate`` / ``checkpoint-after-donate`` —
     a name passed at a ``donating_jit`` donate position and read (or
     checkpoint-saved) afterwards in the same scope: the buffer is
     dead on TPU/GPU and silently alive on CPU tests. Plus the
     spec-level ``donation-shape-mismatch`` gate: every registered
     ``donating_jit`` site with a shape probe must donate only
     arguments with a shape-compatible output (``jax.eval_shape``,
     device-free — the static promotion of jax's per-compile
     donated-buffer-not-usable warning).
   - ``swallow-all-handler`` (ingest + workflow code only —
     ``loaders/``, ``parallel/``, ``workflow/``): no bare ``except:``
     and no silent ``except Exception: pass`` — exactly where "skip
     the error and keep going" becomes silent data loss. Tolerating a
     failure there goes through the resilience layer (RetryPolicy /
     Quarantine), which accounts for it.
   - ``cast-before-transfer`` (loader + staging code — ``loaders/``,
     ``parallel/``): no host-side float widening in a function that
     also ``device_put``\\ s — widening uint8 records to float before
     the transfer ships 4x the bytes; ship the source dtype and let
     the device cast (``StreamingDataset`` ``wire_dtype`` /
     ``compute_dtype``).
   - ``silent-nan-silencer`` (numeric compute trees — ``nodes/``,
     ``ops/``, ``parallel/``, ``workflow/``): a ``nan_to_num`` or
     ``np.errstate(...='ignore')`` suppression must pair with a
     recorded ``numerics.*`` event in the same scope
     (``record_numerics_event`` / the solver-ledger recorders) —
     suppression can be the right recovery, but it must be ACCOUNTED
     (observability/numerics.py, README 'Numerics health').
   - ``metric-name-drift`` (tree-wide): every
     ``counter/gauge/histogram/timer(...)`` call site must use a name
     (or f-string prefix) from the catalogue in
     ``observability/names.py`` — Prometheus dashboards and the
     benchmark's readers address metrics by name, so an uncatalogued
     literal is a typo or an unreviewed rename.
   - **concurrency safety** (``analysis.concurrency``, PR 7):
     ``guarded-field-race`` — an RMW/compound mutation of a
     ``@guarded_by``-declared field outside its lock (tree-wide; fires
     only on declared classes); ``lock-order-cycle`` +
     ``blocking-under-lock`` — the static lock-acquisition graph from
     ``with``-nesting must be acyclic and no blocking call
     (``queue.get``, ``Event.wait``, ``device_put``, ...) may run
     under an analyzer-known lock (scoped by ``CONCURRENCY_SCOPES``);
     ``non-atomic-guarded-sequence`` — check-then-act on a guarded
     field split across two ``with`` blocks. Deliberate exceptions
     live in the commented ``CONCURRENCY_ALLOWLIST``.
   - **SPMD safety** (``analysis.spmd``, tree-wide):
     ``collective-divergence`` — a collective/barrier site reachable
     under host-divergent control flow (a branch on
     ``process_index()`` or per-host taint): one host skips the
     collective and the rest of the world wedges in it;
     ``unstable-barrier-name`` / ``non-fixed-coordination-shape`` —
     barrier tags must be string literals per call site and
     ``process_allgather`` payloads fixed-shape (the
     ``WorldCoordinator.step`` ``(cursor, done)`` discipline);
     ``unbound-collective-axis`` — ``psum``/``all_gather`` axis names
     must be bound by a mesh axis in scope;
     ``unbarriered-host0-effect`` / ``carry-restore-discipline`` —
     host-0-only world-snapshot effects must be barrier-paired and
     restored carries must re-enter through ``_restore_carry``.
     Deliberate exceptions live in the commented ``SPMD_ALLOWLIST``.
   - **hot-path safety** (``analysis.hotpath``, PR 17): the
     interprocedural request-path pass. From every ``@hotpath``-marked
     serving entry point, the static call graph is walked and each
     reachable call classified: ``hotpath-blocking`` (queue waits,
     joins, sleeps, future ``.result``), ``hotpath-host-sync``
     (``block_until_ready`` / ``device_put`` / numpy coercions — a
     host-device round trip per request), ``hotpath-io`` (filesystem /
     network / pickle on the request path), ``hotpath-lazy-import``
     (a per-request import statement), ``hotpath-unbounded-growth``
     (appending to a container no code path ever shrinks), and
     ``hotpath-lock-held-dispatch`` (a call under a held lock whose
     callee transitively blocks or syncs). Every diagnostic names the
     full call chain from the entry point. Plus the atomic-publication
     pass over ``@published_by`` classes: ``unpublished-write`` /
     ``non-atomic-publication`` / ``torn-publication`` — a published
     field may only change via a single-reference atomic flip under its
     declared lock (the swap discipline hot-swap will ride on).
     Deliberate exceptions live in the commented ``HOTPATH_ALLOWLIST``;
     the full-tree scan must also finish under
     ``HOTPATH_SCAN_BUDGET_S`` (the gate emits its runtime).
3. **ruff** (when installed): style/correctness pass over the package.
   Skipped with a notice when the container lacks ruff — layers 1–2
   are the required gate.

Usage: ``python tools/lint.py [--skip-apps]`` or
``bin/run-pipeline.sh --check`` (which also runs the budgeted
``check --all`` plan gate via ``bin/ci.sh --no-tests``). Exit code
0 = clean.
"""
from __future__ import annotations

import ast
import os
import shutil
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "keystone_tpu"


# -- layer 2: AST rules ------------------------------------------------------

def _class_is_host_transformer(cls: ast.ClassDef) -> bool:
    for base in cls.bases:
        name = base.attr if isinstance(base, ast.Attribute) else getattr(
            base, "id", "")
        if "Host" in str(name):
            return True
    return False


def _iter_transformer_applies(tree: ast.Module):
    """(class, apply FunctionDef) pairs for transformer-looking classes.

    Purely syntactic (no imports): any class whose base name mentions
    Transformer and that defines ``apply(self, item)``; classes whose
    base mentions Host are exempt."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        basenames = " ".join(
            str(b.attr if isinstance(b, ast.Attribute)
                else getattr(b, "id", "")) for b in node.bases)
        if "Transformer" not in basenames:
            continue
        if _class_is_host_transformer(node):
            continue
        for item in node.body:
            if isinstance(item, ast.FunctionDef) and item.name == "apply":
                yield node, item


def _host_coercions_in(fdef: ast.FunctionDef):
    # single source of truth for the coercion pattern lives in the
    # analysis package; this gate only adds the file-walk around it
    from keystone_tpu.analysis.diagnostics import host_coercions_in_funcdef

    yield from host_coercions_in_funcdef(fdef)


def run_ast_rules() -> int:
    from keystone_tpu.analysis.diagnostics import (
        CAST_BEFORE_TRANSFER_SCOPES,
        NAN_SILENCER_SCOPES,
        SWALLOW_ALL_SCOPES,
        donation_hazards,
        float_casts_before_transfer,
        metric_name_drift,
        recompile_hazards,
        silent_nan_silencers,
        swallow_all_handlers,
    )

    failures = 0
    for path in sorted(PKG.rglob("*.py")):
        rel = path.relative_to(REPO)
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError as exc:
            print(f"{rel}: syntax error: {exc}")
            failures += 1
            continue
        for cls, fdef in _iter_transformer_applies(tree):
            for lineno, what in _host_coercions_in(fdef):
                print(f"{rel}:{lineno}: host-coercion-in-apply: "
                      f"{cls.name}.apply calls {what} on its item "
                      "(per-item device sync; use jnp or HostTransformer)")
                failures += 1
        # recompile hazards + donation safety share one home in the
        # analysis package (single source of truth; tests parse the
        # synthetic offender fixtures through the same functions)
        for lineno, code, msg in recompile_hazards(tree):
            print(f"{rel}:{lineno}: {code}: {msg}")
            failures += 1
        for lineno, code, msg in donation_hazards(tree):
            print(f"{rel}:{lineno}: {code}: {msg}")
            failures += 1
        # metric-name drift is tree-wide: a renamed counter anywhere
        # silently flatlines dashboards and readers (catalogue:
        # observability/names.py)
        for lineno, code, msg in metric_name_drift(tree):
            print(f"{rel}:{lineno}: {code}: {msg}")
            failures += 1
        if rel.parts[:1] == ("keystone_tpu",) and \
                rel.parts[1] in SWALLOW_ALL_SCOPES:
            for lineno, what in swallow_all_handlers(tree):
                print(f"{rel}:{lineno}: swallow-all-handler: {what} in "
                      "ingest/workflow code silently loses failures; "
                      "narrow the exception type, or route it through "
                      "the resilience layer (RetryPolicy/Quarantine)")
                failures += 1
        if rel.parts[:1] == ("keystone_tpu",) and \
                rel.parts[1] in NAN_SILENCER_SCOPES:
            for lineno, what in silent_nan_silencers(tree):
                print(f"{rel}:{lineno}: silent-nan-silencer: {what} "
                      "with no recorded numerics event in scope — "
                      "suppressing non-finites without accounting hides "
                      "real breakdowns; pair it with "
                      "record_numerics_event(...) (observability/"
                      "numerics.py, README 'Numerics health')")
                failures += 1
        if rel.parts[:1] == ("keystone_tpu",) and \
                rel.parts[1] in CAST_BEFORE_TRANSFER_SCOPES:
            for lineno, what in float_casts_before_transfer(tree):
                print(f"{rel}:{lineno}: cast-before-transfer: {what} in "
                      "a function that device_puts — widening on the "
                      "host ships 4x the bytes the source held; ship "
                      "the source dtype and cast on device "
                      "(StreamingDataset wire_dtype/compute_dtype, "
                      "README 'Streaming ingest')")
                failures += 1
    return failures


# -- layer 2a: concurrency passes --------------------------------------------

def run_concurrency_rules() -> int:
    """The three concurrency-safety pass families over the package tree
    (single source of truth in ``analysis.concurrency``; the synthetic
    offender fixtures under tests/lint_fixtures pin each rule's firing
    shape)."""
    from keystone_tpu.analysis.concurrency import scan_package

    failures = 0
    for hit in scan_package(PKG):
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}")
        failures += 1
    print(f"concurrency passes: {failures} failure(s)")
    return failures


# -- layer 2a': SPMD-safety passes -------------------------------------------

def run_spmd_rules() -> int:
    """The four SPMD-safety pass families over the package tree
    (single source of truth in ``analysis.spmd``: collective
    divergence, barrier-name/coordination-shape stability, collective
    axis bindings, world-checkpoint consistency; offender fixtures
    under tests/lint_fixtures pin each rule's firing shape, and the
    divergent dryrun worker reproduces the hang dynamically)."""
    from keystone_tpu.analysis.spmd import scan_package

    failures = 0
    for hit in scan_package(PKG):
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}")
        failures += 1
    print(f"spmd passes: {failures} failure(s)")
    return failures


# -- layer 2a'': hot-path + publication passes -------------------------------

def run_hotpath_rules() -> int:
    """The interprocedural hot-path pass + the atomic-publication pass
    over the package tree (single source of truth in
    ``analysis.hotpath``; offender fixtures under tests/lint_fixtures
    pin each rule's firing shape). The scan is also WALL-BUDGETED: the
    whole-tree walk must finish under ``HOTPATH_SCAN_BUDGET_S`` so the
    gate can never quietly become the slow part of CI — an over-budget
    scan is itself a failure."""
    import time

    from keystone_tpu.analysis.hotpath import (
        HOTPATH_SCAN_BUDGET_S,
        scan_package,
    )

    failures = 0
    t0 = time.perf_counter()
    for hit in scan_package(PKG):
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}")
        failures += 1
    elapsed = time.perf_counter() - t0
    if elapsed > HOTPATH_SCAN_BUDGET_S:
        print(f"hotpath-scan-over-budget: full-tree scan took "
              f"{elapsed:.2f}s > {HOTPATH_SCAN_BUDGET_S:.0f}s budget")
        failures += 1
    print(f"hotpath passes: {failures} failure(s) in {elapsed:.2f}s "
          f"(budget {HOTPATH_SCAN_BUDGET_S:.0f}s)")
    return failures


# -- layer 2b: donation shape gate (spec-level, eval_shape) ------------------

def _donating_modules():
    """Dotted names of every package module that builds a donating_jit
    wrapper, discovered from the same AST pass the hazard rules use —
    a new donation site anywhere in the tree is probed automatically,
    never silently skipped by a stale hardcoded list."""
    from keystone_tpu.analysis.diagnostics import donating_names

    mods = []
    for path in sorted(PKG.rglob("*.py")):
        try:
            if not donating_names(ast.parse(path.read_text())):
                continue
        except SyntaxError:
            continue  # reported by run_ast_rules
        rel = path.relative_to(REPO).with_suffix("")
        parts = list(rel.parts)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        mods.append(".".join(parts))
    return mods


def run_donation_shape_gate() -> int:
    """Every registered ``donating_jit`` site with a shape probe must
    donate only arguments that have a shape-compatible output —
    verified abstractly via ``jax.eval_shape`` (no device buffers).
    The static promotion of the `_gram_bcd` per-finalize runtime warn:
    an incompatible donation is never honored by XLA, it only buys a
    donated-buffer-not-usable warning per compile on TPU/GPU. Sites
    WITHOUT a probe are reported so a donation can never dodge the
    gate by simply not declaring one."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

    for mod in _donating_modules():
        importlib.import_module(mod)
    from keystone_tpu.utils.donation import (
        donation_shape_mismatches,
        registered_donations,
    )

    failures = 0
    probed = 0
    for site in registered_donations():
        if site.probe is None:
            print(f"{site.module}: donation-without-probe: "
                  f"{site.name} donates argnums "
                  f"{site.donate_argnums} but registers no shape "
                  "probe — pass probe= so the gate can verify the "
                  "donation statically")
            failures += 1
            continue
        probed += 1
        for what in donation_shape_mismatches(site):
            print(f"{site.module}: donation-shape-mismatch: {what} "
                  "(XLA cannot honor it; drop the argnum from "
                  "donate_argnums)")
            failures += 1
    print(f"donation shape gate: {probed} probed site(s), "
          f"{failures} failure(s)")
    return failures


# -- layer 1: pipeline checks ------------------------------------------------

def run_pipeline_checks() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from keystone_tpu.pipelines import CHECK_APPS

    failures = 0
    for name in sorted(CHECK_APPS):
        target = CHECK_APPS[name]()
        report = target.pipeline.check(target.input_spec, name=name)
        status = "ok" if report.ok else "FAIL"
        print(f"check {name}: {status} "
              f"({report.resolved_nodes()}/"
              f"{len(report.analysis.graph.nodes)} specs resolved)")
        if not report.ok:
            for d in report.diagnostics:
                print(f"  {d}")
            failures += 1
    return failures


# -- layer 3: ruff -----------------------------------------------------------

def run_ruff() -> int:
    ruff = shutil.which("ruff")
    if ruff is None:
        print("ruff: not installed; skipping style pass "
              "(AST rules + pipeline checks are the required gate)")
        return 0
    proc = subprocess.run(
        [ruff, "check", "--select", "E9,F63,F7,F82", str(PKG)],
        capture_output=True, text=True)
    if proc.stdout.strip():
        print(proc.stdout)
    return 0 if proc.returncode == 0 else 1


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    failures = run_ast_rules()
    failures += run_concurrency_rules()
    failures += run_spmd_rules()
    failures += run_hotpath_rules()
    failures += run_donation_shape_gate()
    failures += run_ruff()
    if "--skip-apps" not in argv:
        failures += run_pipeline_checks()
    if failures:
        print(f"\nlint: {failures} failure(s)")
        return 1
    print("\nlint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
