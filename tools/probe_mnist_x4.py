"""Several seeds of a fit cell in ONE process: for each seed the cell's
own job (``benchmarks/configs/<config>.py``) makes the data, holds the
rows, fits once on new datasets of them and is compared by the cell's
own reference (``benchmarks/reference/<config>.py``), as the harness
compares the last fit of a window. For setting and checking the limits
of a cell whose every process is dear (``mnist_refit_x4``: four chips,
and programs that take minutes to compile once): a dozen seeds cost one
start-up and one set of programs, where a dozen runs of
``benchmarks.run`` cost twelve. Not a measurement: it prints the gaps
and each seed's fit seconds, and no metric of the benchmark.

    python3 tools/probe_mnist_x4.py --seeds 3800001001 3800001002 ...
        [--workload mnist_refit_x4] [--control] [--rehearse]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import WORK_DIR, load_module  # noqa: E402
from benchmarks.run import resolve  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="mnist_refit_x4")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    _manifest, cell, cfg, _traffic = resolve(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    env = dict(cfg.get("env", {}))
    if args.control:
        env.update(cfg.get("control", {}).get("env", {}))
    os.environ.update(env)

    import jax

    from keystone_tpu.utils.compile_cache import enable_compile_cache
    from keystone_tpu.workflow.env import PipelineEnv

    if not args.rehearse:
        # where JAX_COMPILATION_CACHE_DIR is set (the chip machines) the
        # probe reads what the harness's runs wrote, and the other way
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    print(f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}, "
          f"env {env}", flush=True)
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{len(devices)} devices, the cell needs "
                         f"{cell['chips']}")
    config = load_module("configs", cell["config"])
    reference = load_module("reference", cell["config"])
    readings = []
    for seed in args.seeds:
        workdir = os.path.join(WORK_DIR, f"probe.{os.getpid()}")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        try:
            job = config.prepare(cfg, seed, workdir)
            held = job.hold()
            PipelineEnv.get_or_create().clear_state()
            t0 = time.perf_counter()
            outcome = job.fit(job.datasets(held))
            fit_s = time.perf_counter() - t0
            answers = job.answers(outcome)
            del outcome
            PipelineEnv.get_or_create().clear_state()
            gc.collect()
            t0 = time.perf_counter()
            checks = reference.check(cfg, job.reference_inputs(), answers)
            ref_s = time.perf_counter() - t0
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        got = {name: value for name, value, _limit in checks}
        bad = [name for name, value, limit in checks if not value <= limit]
        readings.append(dict(got, seed=seed))
        print(f"seed {seed}: fit {fit_s:.3f} s, reference {ref_s:.2f} s, "
              + ", ".join(f"{k} {v:.4g}" for k, v in got.items())
              + (f"  NOT CORRECT by {bad}" if bad else "  correct"),
              flush=True)
        del job, held, answers
        gc.collect()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    print("peak bytes a device:", peaks)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
