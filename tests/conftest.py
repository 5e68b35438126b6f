"""Test harness: simulate an 8-device TPU mesh on CPU.

The analogue of the reference's ``LocalSparkContext`` trait
(``src/test/scala/pipelines/LocalSparkContext.scala:9-26``): the full
distributed code path (sharding, collectives, mesh solvers) runs in one
process over 8 virtual devices.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch the real TPU
# Hermeticity: LeastSquaresEstimator loads the per-host cost-model
# calibration artifact (~/.keystone_tpu/...) when present; a machine
# that has run tools/calibrate_cost_model.py must not change
# shipped-default cost-model test outcomes. Point the lookup at a
# nonexistent path unless a test overrides it explicitly.
os.environ["KEYSTONE_COST_CALIBRATION"] = (
    "/nonexistent/keystone-test-calibration.json")
# Crash post-mortems (observability/postmortem.py) default to
# ~/.keystone_tpu/postmortems; tests deliberately trigger the failure
# paths that dump them, so point the dumps at a throwaway temp dir —
# a test run must not litter (or depend on) the host's artifact dir.
import tempfile  # noqa: E402

os.environ.setdefault(
    "KEYSTONE_POSTMORTEM_DIR",
    tempfile.mkdtemp(prefix="keystone-test-postmortems-"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end test")


@pytest.fixture(autouse=True)
def fresh_env():
    """Reset global pipeline state between tests (the reference stops and
    recreates its SparkContext per test)."""
    from keystone_tpu.nodes.learning.least_squares import (
        clear_calibration_cache,
    )
    from keystone_tpu.observability.compilelog import (
        reset_compile_observatory,
    )
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.observability.numerics import reset_health_series
    from keystone_tpu.observability.reqtrace import reset_exemplars
    from keystone_tpu.observability.timeline import reset_flight_recorder
    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.reset()
    MetricsRegistry.reset()
    reset_flight_recorder()
    reset_compile_observatory()
    reset_health_series()
    reset_exemplars()
    clear_calibration_cache()
    yield
    PipelineEnv.reset()
    MetricsRegistry.reset()
    reset_flight_recorder()
    reset_compile_observatory()
    reset_health_series()
    reset_exemplars()
    clear_calibration_cache()


@pytest.fixture
def mesh8():
    from keystone_tpu.parallel.mesh import make_mesh, mesh_scope

    with mesh_scope(make_mesh(jax.devices()[:8])) as m:
        yield m


def _set_persistent_cache(path, min_compile_s, min_entry_bytes):
    """jax's persistent compilation cache at ``path`` (None: off),
    through jax's own setter: no file but ``utils/compile_cache.py``
    names the option (tests/test_chip_smoke.py)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cc.set_cache_dir(path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_s)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      min_entry_bytes)
    cc.reset_cache()


@pytest.fixture
def persistent_cache_dir(tmp_path):
    """jax's persistent compilation cache at a temporary directory that
    takes every program, and as the environment has it afterwards."""
    before = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or None,
              jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    _set_persistent_cache(str(tmp_path / "xla"), 0.0, 0)
    try:
        yield str(tmp_path / "xla")
    finally:
        _set_persistent_cache(*before)


@pytest.fixture
def no_persistent_cache():
    before = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or None,
              jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    _set_persistent_cache(None, *before[1:])
    try:
        yield
    finally:
        _set_persistent_cache(*before)
