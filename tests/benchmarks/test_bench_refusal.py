"""The harness refuses to measure without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["-m", "benchmarks.run", "--workload", "mnist_refit", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def run_in(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *ARGS, *extra], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_and_prints_no_result():
    done = run_in(ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "not a TPU" in done.stderr


def test_a_directory_with_only_the_benchmark_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # past the look for a chip: it is the program that is missing
    done = run_in(tmp_path, "--rehearse")
    assert done.returncode != 0
    assert "keystone_tpu" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
