#!/usr/bin/env bash
# Single-host launcher — the analogue of the reference's
# bin/run-pipeline.sh local mode (reference: bin/run-pipeline.sh:6-43).
#
#   bin/run-pipeline.sh <app> [--flags]
#   bin/run-pipeline.sh                 # list apps
#   bin/run-pipeline.sh --check         # repo static gate (bin/ci.sh
#                                       # --no-tests): AST rules + donation
#                                       # shape gate + per-app pipeline
#                                       # checks with budgeted HBM plans
#   bin/run-pipeline.sh check <app>     # static-check one app's DAG
#
# The reference capped OMP_NUM_THREADS to protect OpenBLAS inside Spark
# executors (run-pipeline.sh:12-31). Here TPU compute goes through XLA,
# but host-side stages (image decode, tokenization, numpy in loaders)
# still use OpenBLAS/OpenMP through numpy — same cap, same reason.
set -euo pipefail

KEYSTONE_HOME="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

if [[ -z "${OMP_NUM_THREADS:-}" ]]; then
  ncores="$(getconf _NPROCESSORS_ONLN 2>/dev/null || echo 8)"
  export OMP_NUM_THREADS="$(( ncores < 32 ? ncores : 32 ))"
fi

# The native host library (cifar decode, text hashing, csv parse) is
# built by keystone_tpu/native on first use, and rebuilt whenever it
# was not compiled from native/keystone_native.cpp as it stands.

export PYTHONPATH="$KEYSTONE_HOME${PYTHONPATH:+:$PYTHONPATH}"
PY=python3
command -v python3 >/dev/null 2>&1 || PY=python

# --check: the pre-PR static gate — no data, no device, exit != 0 on
# any diagnostic or predicted HBM-budget violation (bin/ci.sh chains
# tools/lint.py and the budgeted `check --all`; the full gate with
# tier-1 tests is `bin/ci.sh` without flags)
if [[ "${1:-}" == "--check" ]]; then
  shift
  exec "$KEYSTONE_HOME/bin/ci.sh" --no-tests "$@"
fi

exec "$PY" -m keystone_tpu "$@"
