"""Every cell of BENCHMARK.json, end to end on the CPU at the tiny size
its files give (``--rehearse``; Pallas kernels in interpret mode), each
in a process of its own as the driver runs it. And the same runs with
the timed path broken underneath, which must come out not correct.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    WORKLOADS = {c["name"]: c for c in json.load(_f)["workloads"]}
CELLS = sorted(WORKLOADS)


def rehearse(cell, *extra, code=None, seed=2147483659):
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse", *extra]
    cmd = ([sys.executable, "-m", "benchmarks.run"] if code is None else
           [sys.executable, "-c", code]) + args
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_names_no_device_metric(cell):
    result, lines = rehearse(cell)
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, "\n".join(lines[-12:])
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    # every line but the last names platform, device kind and count
    assert all(line.startswith("[cpu cpu x") for line in lines[:-1])
    assert any("compiles in window 0" in line for line in lines)
    checks = [line for line in lines if " check " in line]
    assert checks and all("(limit " in line for line in checks)


#: The harness's look for a chip is skipped (--rehearse) and the rest of
#: a run driven with the timed path broken underneath, by the kind of
#: driver: (what is broken, the check that has to catch it, the code).
BREAK = {
    "fit_loop": [
        ("half_the_training_rows_left_out", "test_error_gap", """
import sys
import numpy as np
import benchmarks.run as harness
from keystone_tpu.loaders import csv_loader
real = csv_loader.load_csv
def half_the_rows(path, dtype=np.float32):   # part of the batch left out
    rows = real(path, dtype)
    return rows if 'test' in path else rows[: len(rows) // 2]
csv_loader.load_csv = half_the_rows
sys.exit(harness.main(sys.argv[1:]))
"""),
        ("fits_answered_from_the_memo", "memo_hits_off", """
import sys
import benchmarks.run as harness
from keystone_tpu.loaders import csv_loader
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.parallel.dataset import ArrayDataset
PipelineEnv.clear_state = lambda self: None   # the table is never cleared
def same_objects(real, seen={}):              # and every fit gets the same
    def cached(first, *a, **kw):              # datasets: files not read
        key = first if isinstance(first, str) else id(first)
        if key not in seen:                   # again, held rows not put again
            seen[key] = (first, real(first, *a, **kw))
        return seen[key][1]
    return cached
csv_loader.csv_labeled_loader = same_objects(csv_loader.csv_labeled_loader)
ArrayDataset.from_numpy = staticmethod(same_objects(ArrayDataset.from_numpy))
sys.exit(harness.main(sys.argv[1:]))
"""),
    ],
}


def kind_of(cell):
    c = WORKLOADS[cell]
    path = os.path.join(ROOT, "benchmarks", "traffic", c["traffic"] + ".json")
    with open(path) as f:
        return json.load(f)["kind"]


#: A later PR's kind of driver brings its faults in a test file of its own.
FAULTS = [(cell, fault) for cell in CELLS
          for fault in BREAK.get(kind_of(cell), ())]


@pytest.mark.parametrize(
    "cell,fault", FAULTS, ids=[f"{c}-{f[0]}" for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(cell, fault):
    _, check, code = fault
    result, lines = rehearse(cell, code=code)
    assert result["correct"] is False, "\n".join(lines[-12:])
    assert any("NOT CORRECT" in line and check in line for line in lines)


#: The control of a fit cell is the program's own lower solver precision
#: (KEYSTONE_SOLVER_PRECISION=high, three bfloat16 passes). The CPU
#: computes float32 products exactly whatever the precision asked, so
#: here the three passes are emulated where the solver multiplies.
THREE_PASSES = """
import sys
import jax.numpy as jnp
import benchmarks.run as harness
from keystone_tpu.ops import linalg
def split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
def gram3(A, preferred=None):
    hi, lo = split(A)
    return hi.T @ hi + hi.T @ lo + lo.T @ hi
def cross3(A, B, preferred=None):
    ah, al = split(A); bh, bl = split(B)
    return ah.T @ bh + ah.T @ bl + al.T @ bh
linalg.gram, linalg.cross = gram3, cross3
sys.exit(harness.main(sys.argv[1:]))
"""


@pytest.mark.parametrize(
    "cell", [c for c in CELLS if kind_of(c) == "fit_loop"])
def test_the_lower_precision_control_is_not_correct(cell):
    result, lines = rehearse(cell, code=THREE_PASSES)
    assert result["correct"] is False, "\n".join(lines[-12:])
    assert any("NOT CORRECT" in line and name in line for line in lines
               for name in ("weights_gap", "test_scores_gap"))
