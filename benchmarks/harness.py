"""What the entry point, the drivers and the per-layer readers share:
where things live, how a file is found by name, and the objects a run
hands around. (Kept apart from ``run.py`` so that nothing imports the
module that runs as ``__main__`` a second time.)"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
#: Fixed paths inside the checkout (the cache's path is part of its key).
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "xla")
WORK_DIR = os.path.join(ROOT, ".bench_work")

Check = Tuple[str, float, float]  # (name, value, limit): sound iff value <= limit


class Refused(SystemExit):
    """The harness will not measure here; exit code 3, no result line."""

    def __init__(self, why: str):
        print(f"benchmarks.run: refused: {why}", file=sys.stderr)
        super().__init__(3)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` by file, so that a name may hold
    dots (``device_idle_pct.fit``) and adding a file registers it."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    modname = f"benchmarks.{kind}.{name.replace('.', '__')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


def load_peaks(device_kind: str) -> Dict[str, Any]:
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "benchmarks/peaks.json; add it with its source")
    return peaks[device_kind]


@dataclasses.dataclass
class Run:
    """What a driver and the layer readers are handed."""

    cell: Dict[str, Any]
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    control: bool
    workdir: str
    say: Callable[[str], None]
    spans: Any = None
    trace_data: Any = None        # benchmarks.xplane.Trace after the window
    peaks: Optional[Dict[str, Any]] = None
    setup_s: float = 0.0
    memory_peak_bytes: int = 0
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _trace_dir: Optional[str] = None

    def config_module(self):
        return load_module("configs", self.cell["config"])

    def reference_module(self):
        return load_module("reference", self.cell["config"])

    def end_setup(self) -> None:
        """Called by the driver at the first timed event."""
        self.setup_s = time.perf_counter() - T_START

    def start_trace(self) -> None:
        if not self.trace:
            return
        import jax

        self._trace_dir = os.path.join(self.workdir, "trace")
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # harness spans only, no call tracing
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)

    def stop_trace(self) -> None:
        if self._trace_dir is None:
            return
        import jax

        from benchmarks import xplane

        jax.profiler.stop_trace()
        self.trace_data = xplane.load(self._trace_dir)

    def read_memory_peak(self) -> None:
        """Peak bytes on the fullest device: read when the window closes,
        before the plain reference runs, so that it stays the program's."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        self.memory_peak_bytes = max(self.memory_peak_bytes, *peaks)


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: Dict[str, float]     # end-to-end, without setup_s
    checks: List[Check]
