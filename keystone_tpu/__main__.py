"""Unified CLI entry (the analogue of the reference's
``bin/run-pipeline.sh <class> --flags``, SURVEY.md section 2.13):

    python -m keystone_tpu <app> [--flags]
    python -m keystone_tpu check <app> [--json PATH] [--budget BYTES] [--shards N] [--replicas N]
    python -m keystone_tpu check --all [--budget BYTES] [--replicas N]
    python -m keystone_tpu numerics POSTMORTEM.json
    python -m keystone_tpu serve NAME=PATH@SHAPE[:DTYPE] ... [--port P]

Run with no arguments to list the available applications.

``serve`` is the online serving plane (``keystone_tpu/serving``):
saved fitted pipelines admitted as warm device-resident executables
under an HBM budget, request micro-batching behind a bounded queue
(pad-to-bucket, zero steady-state recompiles asserted by the compile
observatory fence), ``POST /predict/<model>`` + readiness-gated
``/healthz`` + Prometheus ``/metrics`` on one port. See README
"Serving".

``numerics`` renders a numerics-tripwire post-mortem artifact
(``observability/numerics.py``): the failure context, the embedded
recent health series as a table, and the ``numerics.*`` counters —
how to read one is documented in README "Numerics health".

``check`` statically analyzes an app's pipeline DAG — shape/dtype
propagation, the graph lints, and the static HBM plan (see
``keystone_tpu/analysis``) — plus the tree-wide concurrency-safety
scan (guarded-by races, lock-order cycles, blocking-under-lock;
``analysis/concurrency.py``) and the tree-wide SPMD-safety scan
(collective divergence, barrier/coordination-shape stability,
collective axis bindings, world-checkpoint consistency;
``analysis/spmd.py``) and the tree-wide hot-path scan
(interprocedural request-path reachability from the ``@hotpath``
serving entry points — blocking/host-sync/IO/lazy-import/unbounded-
growth/lock-held-dispatch hazards — plus the ``@published_by``
atomic-publication pass; ``analysis/hotpath.py``, the ``hotpath``
key in ``--json``), without loading data or allocating a
device buffer, and exits non-zero if any diagnostic fires.
``--budget BYTES`` (``MiB``/``GiB`` suffixes accepted) gates each app
on its planned fit-path peak and exits 2 on a predicted violation.
``--json PATH`` additionally writes the full report (per-node specs +
diagnostics + plan).

``--trace-out PATH`` runs the app under a
:class:`~keystone_tpu.observability.PipelineTrace` and writes the full
execution trace (per-node wall times and memory, optimizer rule log,
auto-cache report, solver decisions) as JSON to PATH; a per-node summary
table is printed to stderr. A PATH ending ``.perfetto.json`` instead
writes the flight recorder's Chrome trace-event timeline (node, ingest,
H2D-lane, and lock spans on per-thread lanes — load it at
https://ui.perfetto.dev).
"""
from __future__ import annotations

import sys

APPS = {
    "mnist.random_fft": "keystone_tpu.pipelines.images.mnist.random_fft",
    "cifar.linear_pixels": "keystone_tpu.pipelines.images.cifar.linear_pixels",
    "cifar.random_cifar": "keystone_tpu.pipelines.images.cifar.random_cifar",
    "cifar.random_patch": "keystone_tpu.pipelines.images.cifar.random_patch_cifar",
    "cifar.random_patch_augmented":
        "keystone_tpu.pipelines.images.cifar.random_patch_cifar_augmented",
    "imagenet.sift_lcs_fv": "keystone_tpu.pipelines.images.imagenet.sift_lcs_fv",
    "voc.sift_fisher": "keystone_tpu.pipelines.images.voc.voc_sift_fisher",
    "speech.timit": "keystone_tpu.pipelines.speech.timit",
    "text.newsgroups": "keystone_tpu.pipelines.text.newsgroups",
    "text.amazon_reviews": "keystone_tpu.pipelines.text.amazon_reviews",
    "nlp.stupid_backoff": "keystone_tpu.pipelines.nlp.stupid_backoff_pipeline",
}


def _parse_bytes(text: str) -> float:
    """Byte counts with optional binary suffixes: ``1073741824``,
    ``512MiB``, ``16GiB``, ``4g``."""
    units = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}
    s = text.strip().lower()
    for suffix in ("ib", "b"):
        if s.endswith(suffix) and len(s) > len(suffix) \
                and s[-len(suffix) - 1] in units:
            s = s[: -len(suffix)]
            break
    mult = 1
    if s and s[-1] in units:
        mult = units[s[-1]]
        s = s[:-1]
    return float(s) * mult


def check_main(rest) -> int:
    """``python -m keystone_tpu check <app>|--all [--json PATH]
    [--budget BYTES] [--shards N] [--replicas N] [--xla]``.

    ``--budget`` (bytes; ``MiB``/``GiB`` suffixes accepted) gates every
    checked app on its static HBM plan — the device-free prediction of
    the fit path's peak residency. ``--shards N`` overrides the
    planner's data-axis width, so ``--budget`` verifies the PER-HOST
    charge of an N-shard world from a single-host machine (the
    sharded-apply sizing runbook, CLUSTER.md "Serving topology").
    ``--replicas N`` (with ``--budget`` as the PER-REPLICA budget)
    additionally solves the checked apps' static serving charges into
    an N-replica fleet placement (``serving/placement.py``) — exit 2
    names the first app no replica can host. ``--xla`` cross-checks that plan
    against XLA's own memory model: every planner-resolved node with a
    per-item program is compiled-without-executing on the sample spec
    and its ``memory_analysis`` output/temp bytes are compared with the
    planner's per-item charge (``plan_vs_xla`` ratios; advisory, never
    changes the exit code). Exit codes: 0 clean, 1 lint diagnostics,
    2 predicted budget violation (or usage error)."""
    json_out = None
    if "--json" in rest:
        i = rest.index("--json")
        if i + 1 >= len(rest):
            print("--json requires a path", file=sys.stderr)
            return 2
        json_out = rest[i + 1]
        del rest[i:i + 2]
    budget = None
    if "--budget" in rest:
        i = rest.index("--budget")
        if i + 1 >= len(rest):
            print("--budget requires a byte count (e.g. 16GiB)",
                  file=sys.stderr)
            return 2
        try:
            budget = _parse_bytes(rest[i + 1])
        except ValueError:
            print(f"--budget expects bytes (e.g. 1073741824, 512MiB, "
                  f"16GiB), got {rest[i + 1]!r}", file=sys.stderr)
            return 2
        del rest[i:i + 2]
    shards = None
    if "--shards" in rest:
        i = rest.index("--shards")
        if i + 1 >= len(rest):
            print("--shards requires a data-shard count (e.g. 8)",
                  file=sys.stderr)
            return 2
        try:
            shards = int(rest[i + 1])
            if shards < 1:
                raise ValueError(shards)
        except ValueError:
            print(f"--shards expects a positive integer, got "
                  f"{rest[i + 1]!r}", file=sys.stderr)
            return 2
        del rest[i:i + 2]
    replicas = None
    if "--replicas" in rest:
        i = rest.index("--replicas")
        if i + 1 >= len(rest):
            print("--replicas requires a replica count (e.g. 3)",
                  file=sys.stderr)
            return 2
        try:
            replicas = int(rest[i + 1])
            if replicas < 1:
                raise ValueError(replicas)
        except ValueError:
            print(f"--replicas expects a positive integer, got "
                  f"{rest[i + 1]!r}", file=sys.stderr)
            return 2
        del rest[i:i + 2]
    if replicas is not None and budget is None:
        print("--replicas needs --budget BYTES (the per-replica HBM "
              "budget the fleet placement is solved against)",
              file=sys.stderr)
        return 2
    xla_verify = "--xla" in rest
    if xla_verify:
        rest.remove("--xla")

    from keystone_tpu.pipelines import CHECK_APPS, resolve_check_app

    if not rest or rest[0] in ("-h", "--help"):
        print("usage: python -m keystone_tpu check <app>|--all "
              "[--json PATH] [--budget BYTES] [--shards N] "
              "[--replicas N] [--xla]\n\n"
              "apps:")
        for name in sorted(CHECK_APPS):
            print(f"  {name}")
        return 0
    if rest[0] == "--all":
        builders = [CHECK_APPS[k] for k in sorted(CHECK_APPS)]
    else:
        try:
            builders = [resolve_check_app(rest[0])]
        except KeyError:
            print(f"unknown app '{rest[0]}'; run `check` with no "
                  "arguments to list apps", file=sys.stderr)
            return 2

    # tree-wide concurrency-safety scan (analysis.concurrency): the
    # source-level counterpart of the per-app graph lints — guarded-by
    # races, lock-order cycles, blocking-under-lock, non-atomic guarded
    # sequences. AST-only, device-free, a few hundred ms.
    import pathlib

    from keystone_tpu.analysis.concurrency import scan_package
    from keystone_tpu.analysis.diagnostics import scan_metric_names
    from keystone_tpu.analysis.spmd import scan_package as scan_spmd

    pkg_root = pathlib.Path(__file__).resolve().parent
    concurrency = scan_package(pkg_root)
    for hit in concurrency:
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}", file=sys.stderr)
    # metric-name drift: every counter/gauge/histogram call site must
    # use a catalogued name (observability/names.py) — the scrape
    # surface's contract with its dashboards
    metrics_names = scan_metric_names(pkg_root)
    for hit in metrics_names:
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}", file=sys.stderr)
    # SPMD safety: collective divergence, barrier/coordination-shape
    # stability, collective axis bindings, world-checkpoint
    # consistency (analysis/spmd.py) — the multi-host runtime's
    # correctness invariants, checked on every single-host CI run
    spmd = scan_spmd(pkg_root)
    for hit in spmd:
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}", file=sys.stderr)
    # hot-path safety: every call reachable from a @hotpath serving
    # entry point classified for blocking/host-sync/IO/lazy-import/
    # unbounded-growth/lock-held-dispatch hazards, plus the
    # @published_by atomic-publication discipline (analysis/hotpath.py)
    # — the request path's latency invariants, checked device-free
    from keystone_tpu.analysis.hotpath import scan_package as scan_hotpath

    hotpath = scan_hotpath(pkg_root)
    for hit in hotpath:
        print(f"{hit['file']}:{hit['lineno']}: {hit['code']}: "
              f"{hit['message']}", file=sys.stderr)

    failed = ((1 if concurrency else 0) + (1 if metrics_names else 0)
              + (1 if spmd else 0) + (1 if hotpath else 0))
    over_budget = 0
    reports = []
    app_names = []
    for build in builders:
        target = build()
        report = target.pipeline.check(target.input_spec, name=target.name,
                                       hbm_budget=budget,
                                       data_shards=shards)
        reports.append(report)
        app_names.append(target.name)
        print(report.summary(), file=sys.stderr)
        if xla_verify:
            from keystone_tpu.analysis.resources import (
                format_xla_verify,
                xla_verify_plan,
            )

            rows = xla_verify_plan(report.analysis, report.plan)
            report.xla_verify = rows
            print(format_xla_verify(rows, target.name), file=sys.stderr)
        violated = any(d.code == "hbm-budget" for d in report.diagnostics)
        over_budget += violated
        if not report.ok:
            failed += 1
        if report.ok:
            status = "OK"
        elif violated:
            status = (f"OVER BUDGET (plan "
                      f"{report.plan.fit_peak_nbytes / (1 << 20):.2f} MiB "
                      f"> {budget / (1 << 20):.2f} MiB)")
        else:
            status = f"FAIL ({len(report.diagnostics)} diagnostic(s))"
        print(f"{target.name}: {status}")
    # fleet-placement verification (PR 20): solve the checked apps'
    # STATIC serving charges into an N-replica placement under the
    # per-replica --budget — the device-free answer to "does this
    # catalogue fit a fleet of N such replicas", before any replica
    # boots. Exit 2 names the first unplaceable app.
    fleet_placement = None
    if replicas is not None:
        from keystone_tpu.analysis.resources import serving_residency_nbytes
        from keystone_tpu.serving.placement import (
            ModelDemand,
            PlacementError,
            plan_placement,
        )

        bucket_rows = 64
        demands, unsized = [], []
        for app, report in zip(app_names, reports):
            charge = serving_residency_nbytes(
                report.plan.model_nbytes, report.plan, bucket_rows,
                data_shards=shards or 1)
            if charge is None:
                # unresolved plan: the per-app summary above already
                # names the unresolved nodes; placement cannot invent
                # a charge for it
                unsized.append(app)
                continue
            demands.append(
                ModelDemand(name=app, charge_nbytes=float(charge)))
        if unsized:
            print(f"fleet: skipping {', '.join(unsized)} — no static "
                  f"serving charge (unresolved plan)", file=sys.stderr)
        try:
            placed = plan_placement(
                demands,
                {f"r{i}": float(budget) for i in range(replicas)})
        except PlacementError as exc:
            over_budget += 1
            fleet_placement = {"replicas": replicas,
                               "budget_nbytes": float(budget),
                               "infeasible": str(exc),
                               "model": exc.model}
            print(f"fleet: INFEASIBLE at {replicas} replica(s) x "
                  f"{budget / (1 << 20):.2f} MiB — {exc}")
        else:
            max_load = max(placed.loads.values()) if placed.loads else 0.0
            fleet_placement = {
                "replicas": replicas,
                "budget_nbytes": float(budget),
                "bucket_rows": bucket_rows,
                "assignments": {m: list(r) for m, r
                                in sorted(placed.assignments.items())},
                "loads": dict(sorted(placed.loads.items())),
            }
            print(f"fleet: {len(demands)} app(s) place on {replicas} "
                  f"replica(s) x {budget / (1 << 20):.2f} MiB "
                  f"(max replica load {max_load / (1 << 20):.2f} MiB)")
    print(f"concurrency: {'clean' if not concurrency else f'{len(concurrency)} diagnostic(s)'}")
    print(f"metrics names: {'clean' if not metrics_names else f'{len(metrics_names)} diagnostic(s)'}")
    print(f"spmd: {'clean' if not spmd else f'{len(spmd)} diagnostic(s)'}")
    print(f"hotpath: {'clean' if not hotpath else f'{len(hotpath)} diagnostic(s)'}")
    if json_out is not None:
        import json as _json

        def _dump(r):
            d = r.to_dict()
            if getattr(r, "xla_verify", None) is not None:
                d["xla_verify"] = r.xla_verify
            return d

        if len(reports) == 1:
            blob = _dump(reports[0])
            blob["concurrency"] = concurrency
            blob["metrics_names"] = metrics_names
            blob["spmd"] = spmd
            blob["hotpath"] = hotpath
        else:
            blob = {"apps": [_dump(r) for r in reports],
                    "concurrency": concurrency,
                    "metrics_names": metrics_names,
                    "spmd": spmd,
                    "hotpath": hotpath}
        if fleet_placement is not None:
            blob["fleet_placement"] = fleet_placement
        with open(json_out, "w") as f:
            f.write(_json.dumps(blob, indent=2))
        print(f"report written to {json_out}", file=sys.stderr)
    if over_budget:
        return 2  # predicted HBM-budget violation, before any device work
    return 1 if failed else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print("usage: python -m keystone_tpu <app> [--flags]\n"
              "       python -m keystone_tpu check <app>|--all\n"
              "       python -m keystone_tpu numerics "
              "POSTMORTEM.json\n"
              "       python -m keystone_tpu serve "
              "NAME=PATH@SHAPE[:DTYPE] ...\n\napps:")
        for name in sorted(APPS):
            print(f"  {name}")
        return 0
    app, rest = argv[0], argv[1:]
    if app == "check":
        return check_main(rest)
    if app == "numerics":
        # device-free: renders a numerics post-mortem artifact
        from keystone_tpu.observability.numerics import postmortem_report

        return postmortem_report(rest)
    # everything below compiles for a device: one cache rule
    from keystone_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if app == "serve":
        from keystone_tpu.serving.http import main as serve_main

        return serve_main(rest)
    import os

    # explicit multi-host wiring for non-TPU-metadata environments
    # (CLUSTER.md "Environment contract"); consumed here so individual
    # apps stay launch-agnostic
    dist_args = {}
    for flag, key, cast in (("--coordinator", "coordinator_address", str),
                            ("--num-processes", "num_processes", int),
                            ("--process-id", "process_id", int)):
        if flag in rest:
            i = rest.index(flag)
            if i + 1 >= len(rest):
                print(f"{flag} requires a value", file=sys.stderr)
                return 2
            try:
                dist_args[key] = cast(rest[i + 1])
            except ValueError:
                print(f"{flag} expects {cast.__name__}, got {rest[i + 1]!r}",
                      file=sys.stderr)
                return 2
            del rest[i:i + 2]
    if dist_args and "coordinator_address" not in dist_args:
        print("--num-processes/--process-id require --coordinator "
              "(without it the coordinator comes from the TPU metadata "
              "env; set KEYSTONE_DISTRIBUTED=1 instead)", file=sys.stderr)
        return 2

    if os.environ.get("KEYSTONE_DISTRIBUTED") or dist_args:
        # multi-host launch: every host runs the same command with
        # KEYSTONE_DISTRIBUTED=1 (coordinator resolved from the standard
        # jax.distributed environment) before any device use
        from keystone_tpu.parallel.mesh import initialize_distributed

        initialize_distributed(**dist_args)
    trace_out = None
    if "--trace-out" in rest:
        i = rest.index("--trace-out")
        if i + 1 >= len(rest):
            print("--trace-out requires a path", file=sys.stderr)
            return 2
        trace_out = rest[i + 1]
        del rest[i:i + 2]

    module = APPS.get(app)
    if module is None:
        print(f"unknown app '{app}'; run with no arguments to list apps",
              file=sys.stderr)
        return 2
    import importlib

    mod = importlib.import_module(module)
    if trace_out is None:
        mod.main(rest)
        return 0
    from keystone_tpu.observability import PipelineTrace, write_trace_artifact

    with PipelineTrace(app) as tr:
        mod.main(rest)
    # back-fill per-node MFU / bandwidth-utilization / FLOPs from the
    # compile observatory's per-executable cost_analysis before export:
    # node wall times gain the hardware denominator (PERFORMANCE.md
    # rule 11); best-effort — an app with no observed compiles simply
    # annotates zero nodes
    try:
        from keystone_tpu.observability.utilization import annotate_trace

        annotate_trace(tr)
    except Exception as exc:
        print(f"utilization annotation skipped: {exc}", file=sys.stderr)
    # *.perfetto.json gets the flight recorder's Chrome trace (load in
    # https://ui.perfetto.dev); anything else the PipelineTrace JSON
    kind = write_trace_artifact(trace_out, tr)
    print(tr.summary(), file=sys.stderr)
    print(f"{kind} written to {trace_out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
