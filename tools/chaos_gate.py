#!/usr/bin/env python3
"""CI chaos gate for the serving plane (``bin/ci.sh``). A CPU gate: it
pins ``JAX_PLATFORMS=cpu`` whatever the caller exported.

Runs the full ``serving/scenarios`` catalogue at bounded seeds, IN
PROCESS — :class:`~keystone_tpu.resilience.faults.FaultPlan` is
process-global, so the injections cannot be installed into a
subprocess server. Each run replays a deterministic load trace
(bursty/diurnal/Zipf arrivals, churn under live load) against a fresh
plane under that scenario's seeded fault plan, then judges the
scenario's p99/availability FLOORS plus its own invariant checks
(backpressure observed, rollback observed, worker survived, ...).

The contract, inherited from the PR 7/11 chaos soaks: every run ends
CLEAN or in a CLASSIFIED failure — a floor violation writes a
post-mortem naming scenario and seed, and the gate exits 1 naming the
violated floor. An UNCLASSIFIED outcome (a request that died outside
the typed verdict set) is itself a floor violation; silent damage
never passes.

Exit 0 when every scenario x seed run is clean; exit 1 otherwise.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
# post-mortems from gated runs land somewhere writable and named, not
# wherever the runner's cwd happens to be
os.environ.setdefault(
    "KEYSTONE_POSTMORTEM_DIR",
    tempfile.mkdtemp(prefix="keystone-chaos-gate-"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=2,
                    help="seeds per scenario (0..N-1, default 2)")
    ap.add_argument("--scenario", action="append", default=None,
                    help="run only this scenario (repeatable)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="stretch (>1) or compress (<1) arrival clocks")
    args = ap.parse_args(argv)

    from keystone_tpu.serving.scenarios import (
        SCENARIOS,
        load_catalogue,
        run_scenario,
    )

    load_catalogue()
    names = sorted(SCENARIOS)
    if args.scenario:
        missing = [n for n in args.scenario if n not in SCENARIOS]
        if missing:
            print(f"chaos gate: FAIL: unknown scenario(s) {missing}; "
                  f"catalogue: {names}", file=sys.stderr)
            return 1
        names = sorted(set(args.scenario))
    if len(SCENARIOS) < 6:
        print(f"chaos gate: FAIL: catalogue has {len(SCENARIOS)} "
              "scenarios < 6 — the suite shrank", file=sys.stderr)
        return 1

    print(f"chaos gate: {len(names)} scenario(s) x {args.seeds} seed(s) "
          f"(post-mortems -> {os.environ['KEYSTONE_POSTMORTEM_DIR']})")
    failures = []
    t_gate = time.perf_counter()
    for name in names:
        for seed in range(args.seeds):
            t0 = time.perf_counter()
            res = run_scenario(name, seed, time_scale=args.time_scale)
            wall = time.perf_counter() - t0
            verdict = ("CLEAN" if res.clean else
                       f"CLASSIFIED(post-mortem="
                       f"{res.postmortem_path or 'MISSING'})")
            print(f"chaos gate: {name} seed={seed} "
                  f"p99={res.p99_ms:.1f}ms floor<={res.floors.p99_ms:.0f} "
                  f"avail={res.availability:.3f} "
                  f"floor>={res.floors.availability:.2f} "
                  f"inj={res.injections} {wall:.1f}s -> {verdict}")
            if res.clean:
                continue
            for v in res.violations:
                print(f"chaos gate:   violated: {v}", file=sys.stderr)
            if not res.postmortem_path:
                print("chaos gate:   AND the violation wrote no "
                      "post-mortem — unclassified damage",
                      file=sys.stderr)
            failures.append((name, seed, res.violations))
    if failures:
        floors = "; ".join(
            f"{n}/seed{s}: {', '.join(v)}" for n, s, v in failures)
        print(f"chaos gate: FAIL: {len(failures)} run(s) violated "
              f"their floors — {floors}", file=sys.stderr)
        return 1
    print(f"chaos gate: PASS ({len(names) * args.seeds} runs clean "
          f"in {time.perf_counter() - t_gate:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
