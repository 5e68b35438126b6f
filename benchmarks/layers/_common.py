"""Arithmetic shared by the per-layer readers. A reader returns None
where it finds nothing to read, and the harness leaves the metric out."""
from __future__ import annotations

from typing import Optional, Sequence

from benchmarks.harness import load_module


def window_seconds(run) -> Optional[float]:
    if run.trace_data is None:
        return None
    w = run.trace_data.window()
    return None if w is None else (w[1] - w[0]) / 1e9


def idle_pct(run) -> Optional[float]:
    seconds = window_seconds(run)
    if not seconds:
        return None
    busy = run.trace_data.busy_seconds(run.trace_data.window())
    return 100.0 * (1.0 - busy / seconds)


def hbm_peak_gib(run) -> Optional[float]:
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None


def program_seconds(run, prefixes: Sequence[str]) -> Optional[float]:
    """Device seconds in the window of the programs whose name starts
    with one of ``prefixes``."""
    if run.trace_data is None:
        return None
    per = run.trace_data.program_seconds(run.trace_data.window())
    hit = [s for name, s in per.items() if name.startswith(tuple(prefixes))]
    return sum(hit) if hit else None


def load_reader(metric: str):
    return load_module("layers", metric)


def load_counts(program: str):
    return load_module("counts", program)

