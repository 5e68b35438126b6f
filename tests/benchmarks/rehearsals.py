"""What the cells' rehearsal files share: a cell of BENCHMARK.json end to
end on the CPU at the tiny size its files give (``--rehearse``; Pallas
kernels in interpret mode), in a process of its own as the driver runs
it; the same run with the timed path broken underneath, which must come
out not correct; and the same with the lower-precision control in the
program's place.

One FILE a cell runs them, ``test_bench_rehearsal_<cell>.py``, three
lines that call :func:`cases`: the driver's tier-1 run hands a file to
one worker (``--dist loadfile``), and in one file for all cells the
rehearsals were the run's whole wall (824 s of 902 at PR 45), every new
cell adding all of its cost to it. A ``model_config`` PR adds one such
file, new, beside its cell's test file; ``test_bench_rehearsal.py``
fails with the path where a cell of the manifest has none.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    WORKLOADS = {c["name"]: c for c in json.load(_f)["workloads"]}
CELLS = sorted(WORKLOADS)


def rehearse(cell, *extra, code=None, script=None, seed=2147483659):
    """The harness in a process of its own, as the driver runs it; or
    ``code`` / the file ``script``, which break something underneath and
    then call the harness's ``main``."""
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse", *extra]
    cmd = [sys.executable, "-m", "benchmarks.run"]
    if code is not None:
        cmd = [sys.executable, "-c", code]
    elif script is not None:
        cmd = [sys.executable, script]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    # a script's own directory, not the checkout, heads its sys.path
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in [env.get("PYTHONPATH")] if p])
    done = subprocess.run(cmd + args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


#: The harness's look for a chip is skipped (--rehearse) and the rest of
#: a run driven with the timed path broken underneath, by the kind of
#: driver: (what is broken, the check that has to catch it, the code; or
#: no code, where what has to be broken is the configuration's own: the
#: code is then the file ``faults/<what is broken>/<configuration>.py``).
BREAK = {
    "fit_loop": [
        # the loader the configuration reads its rows with hands back
        # half of the training rows
        ("half_the_training_rows_left_out", "test_error_gap", None),
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
FAULTS_DIR = os.path.join(ROOT, "tests", "benchmarks", "faults")


def fault_file(what, config):
    """The configuration's own form of a fault, found by the
    configuration's name as every other file of a cell is."""
    path = os.path.join(FAULTS_DIR, what, config + ".py")
    if not os.path.exists(path):
        pytest.fail(
            f"the configuration {config} brings no fault {what!r}: add the "
            f"file tests/benchmarks/faults/{what}/{config}.py, which breaks "
            "the loader this configuration reads its rows with (half of the "
            "training rows, every test row) and then calls "
            "benchmarks.run.main(sys.argv[1:]); the files beside it show how")
    return path


def kind_of(cell):
    c = WORKLOADS[cell]
    path = os.path.join(ROOT, "benchmarks", "traffic", c["traffic"] + ".json")
    with open(path) as f:
        return json.load(f)["kind"]


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


#: Every fit runs its whole graph a second time, on the same rows, the
#: state table cleared between the two: no prefix hit says so, every
#: other count is a sound fit's, and the node count alone reads double.
GRAPH_RUN_TWICE = """
import sys
import benchmarks.run as harness
from benchmarks import harness as shared
from keystone_tpu.workflow.env import PipelineEnv
real = shared.load_module
def load_module(kind, name):
    module = real(kind, name)
    if kind == "configs" and not hasattr(module, "fits_once"):
        module.fits_once = module.prepare
        def prepare(cfg, seed, workdir):
            job = module.fits_once(cfg, seed, workdir)
            once = job.fit
            def twice(loaded):
                once(loaded)
                PipelineEnv.get_or_create().clear_state()
                return once(loaded)
            job.fit = twice
            return job
        module.prepare = prepare
    return module
shared.load_module = load_module
sys.exit(harness.main(sys.argv[1:]))
"""


def failed(lines):
    """The names of the checks a run's lines say are NOT CORRECT."""
    return {line.split(" check ")[1].split(":")[0] for line in lines
            if "NOT CORRECT" in line}


def rehearsal_file(cell):
    """The file that runs a cell's rehearsals, found by the cell's name."""
    path = os.path.join(HERE, f"test_bench_rehearsal_{cell}.py")
    if not os.path.exists(path):
        pytest.fail(
            f"the cell {cell} brings no rehearsal file: add "
            f"tests/benchmarks/test_bench_rehearsal_{cell}.py, new, with the "
            "three lines the files beside it have (import rehearsals; the "
            "cell's name; rehearsals.cases(CELL)): its rehearsal, its faults "
            "and its control then run as one worker's file of their own")
    return path


def faults_of(cell):
    """The faults of the cell's kind of driver (a later PR's kind of
    driver brings its faults in a test file of its own)."""
    return list(BREAK.get(kind_of(cell), ()))


def cases(cell):
    """The three tests of one cell's rehearsal file, under the ids they
    had when one file ran every cell (``[<cell>]``, ``[<cell>-<fault>]``):
    bind them to the names they are returned in."""
    faults = faults_of(cell)

    @pytest.mark.parametrize("cell", [cell])
    def test_cell_rehearses_and_names_no_device_metric(cell):
        result, lines = rehearse(cell)
        assert set(result) >= {"correct", "attempted", "failed", "metrics",
                               "device"}
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
        # and the result's last key holds each number compared, its limit
        assert list(result)[-1] == "compared"
        assert len(result["compared"]) == len(checks)
        assert all(len(pair) == 2 for pair in result["compared"].values())

    @pytest.mark.parametrize(
        "cell,fault", [(cell, fault) for fault in faults],
        ids=[f"{cell}-{fault[0]}" for fault in faults])
    def test_a_broken_timed_path_is_not_correct(cell, fault):
        what, check, code = fault
        script = None if code is not None else fault_file(
            what, WORKLOADS[cell]["config"])
        result, lines = rehearse(cell, code=code, script=script)
        assert result["correct"] is False, "\n".join(lines[-12:])
        assert any("NOT CORRECT" in line and check in line for line in lines)

    @pytest.mark.parametrize(
        "cell", [cell] if kind_of(cell) == "fit_loop" else [])
    def test_the_lower_precision_control_is_not_correct(cell):
        result, lines = rehearse(cell, code=THREE_PASSES)
        assert result["correct"] is False, "\n".join(lines[-12:])
        assert failed(lines) & {"weights_gap", "test_scores_gap"}

    return (test_cell_rehearses_and_names_no_device_metric,
            test_a_broken_timed_path_is_not_correct,
            test_the_lower_precision_control_is_not_correct)
