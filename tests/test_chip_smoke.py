"""What tier-1 can check of the chip path without a chip: the smoke
refuses to run off-TPU, the compile cache is placed by one rule, and the
package imports without a deprecation warning under the installed JAX.
What needs the chip is ``chip_smoke.py`` itself (README "Quick start")."""
import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_overrides):
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get(
        "PYTHONPATH", ""), **env_overrides)
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=300, env=env, cwd=REPO)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero, names
    the platform it found, and prints no result line."""
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode not in (0, None), proc.stdout
    assert "platform=cpu" in proc.stdout          # the device line
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert "ok" not in json.loads(line), line
    assert "phase" not in proc.stdout             # nothing was run


def test_compile_cache_is_left_alone_when_placed_from_outside(monkeypatch):
    from keystone_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert calls == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from keystone_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".xla_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_no_entry_point_sets_the_cache_dir_itself():
    """One helper owns ``jax_compilation_cache_dir``: no other file of
    the repo names the option."""
    owners = []
    for root in ("keystone_tpu", "tools", "tests"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            owners += [os.path.join(dirpath, f) for f in files
                       if f.endswith(".py")]
    owners += [os.path.join(REPO, f)
               for f in ("chip_smoke.py", "__graft_entry__.py")]
    setters = sorted(
        os.path.relpath(path, REPO) for path in owners
        if "jax_compilation_cache_dir" in open(path).read())
    assert setters == ["keystone_tpu/utils/compile_cache.py",
                       "tests/test_chip_smoke.py"]


def test_every_module_imports_clean_under_deprecation_errors():
    """The installed JAX deprecates what older code reached for
    (``jax.experimental.shard_map`` since 0.8.0): importing any module
    of the package must not warn."""
    proc = _run(["-W", "error::DeprecationWarning", "-c", (
        "import importlib, pkgutil, keystone_tpu\n"
        "names = [m.name for m in pkgutil.walk_packages("
        "keystone_tpu.__path__, 'keystone_tpu.')]\n"
        "for n in names: importlib.import_module(n)\n"
        "print(len(names))\n")], JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert int(proc.stdout.strip()) > 100
