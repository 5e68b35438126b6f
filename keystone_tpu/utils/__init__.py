"""Utilities: checkpointing, profiling, lock discipline (reference
``utils/`` + SURVEY.md section 5 auxiliary subsystems).

Submodule re-exports are lazy (PEP 562): ``utils.guarded`` is imported
by the observability layer's class definitions, and an eager
``checkpoint`` import here would pull resilience -> events ->
observability back in mid-initialization (a real import cycle, hit
when ``observability.metrics`` declared its lock discipline)."""
from typing import Any

__all__ = [
    "donating_jit",
    "donation_enabled",
    "load_pipeline",
    "load_state",
    "save_pipeline",
    "save_state",
    "StepTimer",
    "trace",
]

#: name -> (module, relative to this package, and the attribute there)
_HOMES = {
    "donating_jit": (".donation", "donating_jit"),
    "donation_enabled": (".donation", "donation_enabled"),
    "load_pipeline": (".checkpoint", "load_pipeline"),
    "load_state": (".checkpoint", "load_state"),
    "save_pipeline": (".checkpoint", "save_pipeline"),
    "save_state": (".checkpoint", "save_state"),
    # the profiling hooks live in the observability layer
    "StepTimer": ("..observability.metrics", "StepTimer"),
    "trace": ("..observability.trace", "xprof_trace"),
}


def __getattr__(name: str) -> Any:
    home = _HOMES.get(name)
    if home is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    module, attr = home
    return getattr(importlib.import_module(module, __name__), attr)
