#!/usr/bin/env bash
# One-shot CI gate for this repo — chains the three hermetic checks a PR
# must pass, in fail-fast order of cost:
#
#   1. tools/lint.py --skip-apps   AST rules (host coercions, recompile
#                                  hazards, donation safety, swallow-all,
#                                  cast-before-transfer, the three
#                                  concurrency pass families, the four
#                                  SPMD-safety pass families:
#                                  collective divergence, barrier/
#                                  coordination-shape stability,
#                                  collective axis bindings, world-
#                                  checkpoint consistency, and the
#                                  hot-path + atomic-publication passes:
#                                  interprocedural request-path
#                                  reachability from the @hotpath entry
#                                  points, blocking/host-sync/IO/lazy-
#                                  import/unbounded-growth/lock-held-
#                                  dispatch hazards, @published_by swap
#                                  discipline — the full-tree scan is
#                                  wall-budgeted and its runtime is
#                                  printed in the gate output) + the
#                                  eval_shape donation shape gate (+ ruff
#                                  if present)
#   2. python -m keystone_tpu check --all --budget $KEYSTONE_CI_HBM_BUDGET
#                                  abstract interpretation + graph lints
#                                  (incl. the sharding-flow lattice) +
#                                  static HBM plans over every CHECK_APPS
#                                  app + the concurrency scan + the
#                                  metric-name-drift scan + the SPMD
#                                  scan (the `spmd` key in --json) +
#                                  the hot-path scan (the `hotpath` key),
#                                  device-free; exit 1 on diagnostics,
#                                  exit 2 on a predicted budget violation
#   2b. recompile gate             tools/recompile_gate.py — a smoke
#                                  streamed fit twice; ANY compile in the
#                                  second epoch fails (compile observatory
#                                  fence, the dynamic recompile-hazard gate)
#   2b'. numerics gate             tools/numerics_gate.py — a clean smoke
#                                  streamed fit must pull health words and
#                                  write NO post-mortem; the same fit with
#                                  one fault-injected NaN chunk must raise
#                                  NumericsError naming chunk+stream with
#                                  a post-mortem carrying the health series
#   2b''. elastic gate             tools/elastic_gate.py — a 2-process CPU
#                                  dryrun streamed fit (jax.distributed +
#                                  gloo); process 1 killed mid-stream by a
#                                  host_death fault, world relaunched,
#                                  resumed from the shared StreamCheckpoint;
#                                  resumed weights must be bit-identical
#   2b'''. serving gate            tools/serving_gate.py — start
#                                  `python -m keystone_tpu serve` on an
#                                  ephemeral port with 2 saved models,
#                                  wait on the readiness-gated /healthz,
#                                  drive requests across >= 2 shapes and
#                                  both models, and fail on any fenced
#                                  steady-state recompile or a
#                                  /healthz-not-ready timeout
#   2b''''. chaos gate             tools/chaos_gate.py — the serving
#                                  scenario catalogue (burst, diurnal,
#                                  zipf-churn, straggler-dispatch,
#                                  poisoned-batch, overload-shed) at
#                                  bounded seeds: deterministic trace
#                                  replay under seeded serve.* faults;
#                                  every run ends clean or CLASSIFIED
#                                  with a post-mortem naming
#                                  scenario+seed; a violated
#                                  p99/availability floor exits 1 by name
#   2b'''''. fleet gate            tools/fleet_gate.py — 3 replica
#                                  subprocesses behind the real-HTTP
#                                  fleet router, placement solved under
#                                  finite budgets; SIGKILL the busiest
#                                  replica mid-replay: the reactor must
#                                  re-place its models sha-verified,
#                                  keep p99 under the drill floor, and
#                                  classify every refusal (429/503),
#                                  never an unclassified error
#   2c. bounded-seed stress        the deterministic-interleaving suite
#                                  (tests/test_concurrency_sched.py):
#                                  historical-race regression schedules +
#                                  a bounded seeded fuzz of the prefetcher
#                                  — cheap, catches schedule-dependent
#                                  breakage before the full tier-1 bill
#   3. tier-1 pytest               tests/ -m 'not slow' on the CPU-simulated
#                                  8-device mesh
#
#   bin/ci.sh                      # the full gate (PR bar)
#   bin/ci.sh --no-tests           # static layers only (what
#                                  # bin/run-pipeline.sh --check runs)
#
# KEYSTONE_CI_HBM_BUDGET (default 16GiB — one v5e chip's HBM) bounds
# every app's statically planned fit-path peak; see README "Static
# checking" for the accounting model.
set -euo pipefail

KEYSTONE_HOME="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
CALLER_PYTHONPATH="${PYTHONPATH:-}"
export PYTHONPATH="$KEYSTONE_HOME${PYTHONPATH:+:$PYTHONPATH}"
PY=python3
command -v python3 >/dev/null 2>&1 || PY=python

run_tests=1
if [[ "${1:-}" == "--no-tests" ]]; then
  run_tests=0
  shift
fi

BUDGET="${KEYSTONE_CI_HBM_BUDGET:-16GiB}"

echo "== ci: lint (AST rules + hot-path/publication passes + donation shape gate) =="
"$PY" "$KEYSTONE_HOME/tools/lint.py" --skip-apps

echo "== ci: static pipeline checks + HBM plans (budget $BUDGET) =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  "$PY" -m keystone_tpu check --all --budget "$BUDGET"

if (( run_tests )); then
  echo "== ci: recompile gate (second epoch must compile nothing) =="
  # the dynamic complement of the static recompile-hazard lints: a
  # smoke streamed fit runs twice and any compile in the second epoch
  # fails the gate, naming the jit site + signature delta (PR 3's
  # zero-recompile invariant, now asserted by the compile observatory
  # instead of only by one tier-1 test)
  "$PY" "$KEYSTONE_HOME/tools/recompile_gate.py"

  echo "== ci: numerics gate (injected NaN must trip; clean fit must not) =="
  # the dynamic pin for the data-health plane: both directions of the
  # tripwire contract (tools/numerics_gate.py), against the real
  # streamed path with a deterministic kind="corrupt" fault injection
  "$PY" "$KEYSTONE_HOME/tools/numerics_gate.py"

  echo "== ci: elastic gate (kill one host mid-fit, relaunch, resume) =="
  # the dynamic pin for the elastic multi-host plane
  # (tools/elastic_gate.py): a 2-process CPU dryrun streamed fit over
  # real jax.distributed + gloo — process 1 is killed mid-stream by a
  # host_death fault, the world relaunches, resumes from the shared
  # StreamCheckpoint, and the resumed weights must be bit-identical to
  # the uninterrupted run with the warmup fence clean throughout
  "$PY" "$KEYSTONE_HOME/tools/elastic_gate.py"

  echo "== ci: serving gate (2 models, 2 shapes, fence-clean, readiness-gated) =="
  # the dynamic pin for the serving plane (tools/serving_gate.py): the
  # real subprocess + HTTP deployment shape — server binds, /healthz
  # reports warming until every admitted model's warmup compile
  # completed, requests across >= 2 buckets and both models, and the
  # armed observatory fence must record ZERO steady-state recompiles
  "$PY" "$KEYSTONE_HOME/tools/serving_gate.py"

  echo "== ci: chaos gate (scenario catalogue at bounded seeds, SLO floors) =="
  # the dynamic pin for graceful degradation (tools/chaos_gate.py): the
  # full serving/scenarios catalogue — bursty/diurnal/Zipf traffic,
  # churn under load, seeded dispatch/admit faults — replayed in
  # process at bounded seeds; every run must end clean or in a
  # CLASSIFIED failure with a post-mortem naming scenario+seed, and a
  # violated p99/availability floor fails the gate by name
  "$PY" "$KEYSTONE_HOME/tools/chaos_gate.py" --seeds 2

  echo "== ci: fleet gate (3 subprocess replicas, SIGKILL one mid-replay) =="
  # the dynamic pin for the serving fleet (tools/fleet_gate.py): three
  # replica SUBPROCESSES behind the real-HTTP router, placement solved
  # under finite per-replica budgets and admitted sha-verified; mid-
  # replay the busiest replica is SIGKILLed cold — the reactor must
  # count exactly one death, drop the corpse from the membership,
  # re-place its models from canonical bytes (sha-verified again), the
  # p99 must stay under the drill floor, and every refusal in the
  # window must be classified (429/503) — never an unclassified error
  "$PY" "$KEYSTONE_HOME/tools/fleet_gate.py"

  echo "== ci: bounded-seed concurrency stress (regression schedules + fuzz) =="
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    "$PY" -m pytest "$KEYSTONE_HOME/tests/test_concurrency_sched.py" -q \
    -m 'not slow' -p no:cacheprovider

  echo "== ci: tier-1 tests =="
  # from the checkout's root with the caller's PYTHONPATH, not this
  # script's: a test that copies the benchmark into a bare directory must
  # not find the package through the environment
  (cd "$KEYSTONE_HOME" && PYTHONPATH="$CALLER_PYTHONPATH" \
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    "$PY" -m pytest tests -q -m 'not slow' -p no:cacheprovider)
fi

echo "== ci: clean =="
