"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files plus entries in BENCHMARK.json, and edits no file that is
there: done here in a temporary copy, and run."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "keystone_tpu"), tmp_path / "keystone_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*")
              if p.is_file()}
    bench = tmp_path / "benchmarks"

    # a configuration: its file of sizes, its builder, its plain reference
    cfg = json.loads((bench / "configs" / "mnist_random_fft_32.json").read_text())
    cfg["rehearsal"].update(num_ffts=4, block_size=512, train_rows=2048)
    (bench / "configs" / "mnist_random_fft_4.json").write_text(json.dumps(cfg))
    for kind, attr in (("configs", "prepare"), ("reference", "check")):
        (bench / kind / "mnist_random_fft_4.py").write_text(
            "from benchmarks.harness import load_module\n"
            f"{attr} = load_module({kind!r}, 'mnist_random_fft_32').{attr}\n")
    # a traffic mix: parameters only
    (bench / "traffic" / "fit_again.json").write_text(
        json.dumps({"kind": "fit_loop", "reload": False,
                    "metric": "refit_items_per_s"}))
    # a per-layer metric: a reader of its own
    (bench / "layers" / "fits_done.new.py").write_text(
        "def read(run):\n    return run.facts.get('fits')\n")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "mnist_random_fft_4", "source": cfg["source"],
        "file": "benchmarks/configs/mnist_random_fft_4.json",
        "reduced": ["num_ffts", "env"], "why": "discovery test"})
    manifest["workloads"].append({
        "name": "mnist4_fit", "config": "mnist_random_fft_4",
        "traffic": "fit_again", "chips": 1, "why": "discovery test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "refit_items_per_s":
            m.setdefault("workloads", ["mnist_refit"]).append("mnist4_fit")
    manifest["per_layer"].append({
        "name": "fits_done.new", "unit": "fits", "better": "higher",
        "source": "program_counter", "layer": "DAG execution",
        "moves": "refit_items_per_s", "workloads": ["mnist4_fit"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", "mnist4_fit",
         "--seed", "5", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["attempted"] >= 1
    assert "config mnist_random_fft_4, traffic fit_again" in done.stdout

    # the reader is resolved by the metric's name, as a traced run does it
    probe = subprocess.run(
        [sys.executable, "-c",
         "import types, benchmarks.harness as r\n"
         "run = types.SimpleNamespace(facts={'fits': 3})\n"
         "print(r.load_module('layers', 'fits_done.new').read(run))"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert probe.stdout.strip() == "3", probe.stderr[-2000:]

    after = {p: p.read_bytes() for p in before}
    assert after == before        # nothing that was there has been edited
