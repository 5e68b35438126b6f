"""Closed loop of whole fits, one at a time (a user runs one fit per
chip): set-up writes the seeded files and runs one whole fit to warm
every shape; the window then runs whole fits back to back until
``--seconds`` have passed, the last one finishing. The traffic file says
where each fit's rows come from:

- ``"reload": false``: the loader reads the files once, in set-up, and
  the rows stay on the host; each fit gets new datasets of them (host to
  device), as a caller does who fits again and again on rows it holds.
- ``"reload": true`` (the default): the files are read again before each
  fit, which is what ``python -m keystone_tpu <app>`` does after its
  compiles.

Each fit is a real fit: the process-global prefix-state table is cleared
and the datasets are new, and the executor's own counters say so in
every run: a fit whose estimator was answered from the table counts more
prefix hits than the configuration's file states for a real one, and is
not correct.

What is judged, under the name the traffic file gives (``"metric"``), is
all items of the fits the window completed over all of the window's
wall: rows -> device -> featurize -> solve -> both evaluations on the
host, and whatever the loop does between two fits.
"""
from __future__ import annotations

import contextlib
import gc
import io
import time

from benchmarks.harness import Outcome, Run


def one_fit(run: Run, job, counters, held=None):
    """Clear the memo, get the rows (from the files, or new datasets of
    the held ones), fit, evaluate. The app's own prints are kept off
    stdout (every line there names the device). Returns the outcome and
    by how much each of the executor's counters rose."""
    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.get_or_create().clear_state()
    before = [c.value for c in counters]
    with run.spans.span("fit"), contextlib.redirect_stdout(io.StringIO()):
        if held is None:
            with run.spans.span("ingest"):
                loaded = job.load()
        else:
            with run.spans.span("to_device"):
                loaded = job.datasets(held)
        with run.spans.span("dag"):
            outcome = job.fit(loaded)
    return outcome, [c.value - b for c, b in zip(counters, before)]


def outside(count, stated) -> float:
    """By how much ``count`` lies outside what the configuration states:
    a number (exact) or a pair ``[least, most]``; 0 inside."""
    pair = stated if isinstance(stated, (list, tuple)) else (stated, stated)
    least, most = pair
    return max(least - count, count - most, 0)


def run(run: Run) -> Outcome:
    from keystone_tpu.observability.compilelog import compile_observatory
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.workflow.env import PipelineEnv

    obs = compile_observatory()
    registry = MetricsRegistry.get_or_create()
    counters = [registry.counter("executor.prefix_hits"),
                registry.counter("executor.nodes_executed")]
    t0 = time.perf_counter()
    job = run.config_module().prepare(run.cfg, run.seed, run.workdir)
    t1 = time.perf_counter()
    held = None
    if not run.traffic.get("reload", True):
        with run.spans.span("ingest"):
            held = job.hold()
    warm, _ = one_fit(run, job, counters, held)
    run.facts["loader_s"] = run.spans.total("ingest")
    run.say(f"set-up: data {t1 - t0:.2f} s, loader "
            f"{run.facts['loader_s']:.2f} s, warming fit "
            f"{run.spans.total('dag'):.2f} s "
            f"with {obs.count_total()} compiles or cache reads taking "
            f"{obs.wall_s_total():.2f} s, the slowest "
            + ", ".join(f"{r['name']} {r['wall_s']:.2f} s" for r in sorted(
                obs.tail(), key=lambda r: -r["wall_s"])[:3]))
    del warm
    run.spans.records.clear()
    compiles0 = obs.count_total()

    outcomes, counted = [], []
    run.start_trace()
    run.end_setup()
    with run.spans.span("window"):
        start = time.perf_counter()
        while time.perf_counter() - start < run.seconds:
            last, rose = one_fit(run, job, counters, held)
            counted.append(rose)
            outcomes.append({k: v for k, v in last.items()
                             if isinstance(v, float)})
        elapsed = time.perf_counter() - start
    run.stop_trace()
    run.read_memory_peak()
    compiles = obs.count_total() - compiles0
    fits = len(outcomes)
    rate = job.items * fits / elapsed
    rows = "to_device" if held is not None else "ingest"
    run.say(f"window: {fits} whole fits of {job.items} items in "
            f"{elapsed:.3f} s, {rate:.1f} items/s; {rows} "
            f"{run.spans.total(rows) / fits:.3f} s and the rest "
            f"{run.spans.total('dag') / fits:.3f} s a fit, the slowest fit "
            f"{max(e - s for n, s, e in run.spans.records if n == 'fit'):.3f}"
            f" s; compiles in window {compiles}; prefix hits, nodes "
            f"executed a fit {sorted(set(map(tuple, counted)))}; "
            f"errors {outcomes[-1]}")
    run.facts.update(fits=fits, items=job.items)

    answers = job.answers(last)
    del last
    PipelineEnv.get_or_create().clear_state()
    gc.collect()
    t_ref = time.perf_counter()
    checks = run.reference_module().check(
        run.cfg, job.reference_inputs(), answers)
    run.say(f"reference and comparison took {time.perf_counter() - t_ref:.2f} s")
    # every fit of one seed computes the same thing
    same = all(o == outcomes[0] for o in outcomes)
    checks.append(("fits_disagree", 0.0 if same else 1.0, 0.0))
    # ... and computes it: a real fit meets the prefix-state table as often
    # as the configuration says and no oftener (an estimator answered
    # from the table is one hit more and several nodes fewer); the node
    # count may be a pair where a sound rearrangement of the graph moves it
    real = run.cfg["real_fit"]
    checks.append(("memo_hits_off", float(max(
        abs(hits - real["prefix_hits"]) for hits, _ in counted)), 0.0))
    checks.append(("nodes_executed_off", float(max(
        outside(nodes, real["nodes_executed"]) for _, nodes in counted)), 0.0))
    checks.append(("compiles_in_window", float(compiles), 0.0))
    metric = run.traffic.get("metric", "fit_items_per_s")
    return Outcome(attempted=fits, failed=0, metrics={metric: rate},
                   checks=checks)
