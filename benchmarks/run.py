"""Measure one cell of ``BENCHMARK.json``.

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run. It refuses to measure unless JAX reports a TPU with
at least the chips the cell asks for: it then exits non-zero and prints
no result. ``--rehearse`` is the explicit CPU rehearsal: the cell at the
tiny size its configuration and traffic files give, through the same
code, printing counts and ``correct`` but no device metric.
``--control`` runs the configuration's lower-precision control in the
program's place (PERF.md, "correct"); it is for setting limits, never for
a measurement. Every printed line names platform, device kind and count;
the last line of stdout is the result object and nothing else, its last
key ``compared`` each number that decided ``correct`` with its limit,
which are also the last lines of stderr.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
from typing import Any, Dict

from benchmarks.harness import (CACHE_DIR, HERE, ROOT, WORK_DIR, Outcome,
                                Refused, Run, load_json, load_module,
                                load_peaks)


def parse(argv=None):
    p = argparse.ArgumentParser(prog="python -m benchmarks.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal at tiny size; prints no device metric")
    p.add_argument("--control", action="store_true",
                   help="run the configuration's lower-precision control")
    return p.parse_args(argv)


def resolve(workload: str):
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return manifest, cell, cfg, traffic


def reports(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def json_number(x):
    """A float as JSON can hold it: NaN and the infinities by name."""
    return float(x) if math.isfinite(x) else repr(float(x))


def main(argv=None) -> int:
    args = parse(argv)
    manifest, cell, cfg, traffic = resolve(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cfg = {**cfg, **cfg.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    # the configuration's environment, before the program is imported
    env = dict(cfg.get("env", {}))
    if args.control:
        env.update(cfg.get("control", {}).get("env", {}))
    os.environ.update(env)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and not args.rehearse:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    tag = f"[{platform} {kind} x{len(devices)}]"

    def say(text: str) -> None:
        for line in str(text).splitlines() or [""]:
            print(f"{tag} {line}", flush=True)

    if not args.rehearse:
        if platform != "tpu":
            raise Refused(f"JAX found {platform!r}, not a TPU "
                          "(--rehearse is the explicit CPU rehearsal)")
        if len(devices) < cell["chips"]:
            raise Refused(f"{len(devices)} chips, the cell needs {cell['chips']}")

    import keystone_tpu  # noqa: F401  (absent: the import error ends the run)

    from benchmarks.spans import Spans

    # per process: two runs of one cell in one checkout must not share files
    workdir = os.path.join(WORK_DIR, f"{cell['name']}.{os.getpid()}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    run = Run(cell=cell, cfg=cfg, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              rehearsal=args.rehearse, control=args.control, workdir=workdir,
              say=say, spans=Spans(),
              peaks=None if args.rehearse else load_peaks(kind))
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}, env {env}"
        + (" REHEARSAL (no device metric)" if args.rehearse else "")
        + (" CONTROL (not a measurement)" if args.control else ""))
    try:
        driver = load_module("drivers", traffic["kind"])
        outcome: Outcome = driver.run(run)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    gc.collect()

    correct = True
    compared: Dict[str, Any] = {}   # each number beside its limit
    verdicts = []
    for name, value, limit in outcome.checks:
        ok = bool(value <= limit)   # NaN compares false: not correct
        correct &= ok
        verdict = "ok" if ok else "NOT CORRECT"
        say(f"check {name}: {value:.6g} (limit {limit:.6g}) {verdict}")
        compared[name] = [json_number(value), json_number(limit)]
        verdicts.append(f"compared {name} {value:.6g} limit {limit:.6g} "
                        f"{verdict}")
    say(f"attempted {outcome.attempted}, failed {outcome.failed}, "
        f"correct {correct}, setup {run.setup_s:.3f} s")

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.rehearse:
        pass  # a CPU run names no device metric
    elif args.trace:
        for m in manifest["per_layer"]:
            if not reports(m, cell["name"]):
                continue
            value = load_module("layers", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(outcome.metrics, setup_s=run.setup_s)
        for m in manifest["end_to_end"]:
            if reports(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    device: Dict[str, Any] = {"platform": platform, "kind": kind,
                              "count": len(devices),
                              "memory_peak_bytes": int(run.memory_peak_bytes)}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": outcome.attempted,
        "failed": outcome.failed, "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if args.trace and run.trace_data is not None and not args.rehearse:
        window = run.trace_data.window()
        device["busy_s"] = run.trace_data.busy_seconds(window)
        device["window_s"] = ((window[1] - window[0]) / 1e9 if window else 0.0)
        progs = sorted(run.trace_data.program_seconds(window).items(),
                       key=lambda kv: -kv[1])[:12]
        say("device seconds by program: "
            + ", ".join(f"{n} {t:.4f}" for n, t in progs))
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           run.trace_data.op_seconds(window, top=10)],
            "idle_gaps": [[n, s] for n, s in
                          run.trace_data.idle_gaps(window, top=10)]}
    # what a record of a run that is not correct keeps: the last lines of
    # stderr and the last key of the result's line
    result["compared"] = compared
    print("\n".join(verdicts), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
