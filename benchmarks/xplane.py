"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device busy
time, per-program and per-op device time, and idle gaps labelled by the
harness span that was open on the host.

Layout read here (recorded on a TPU v5e, jax 0.9.0; the recorded trace
under ``tests/benchmarks/data`` pins it): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
executed program) and ``XLA Ops`` (one event per HLO op); host threads
are lines of ``/host:CPU`` on the same clock, and a
``jax.profiler.TraceAnnotation`` shows there under its own name.
Times are nanoseconds from the start of the trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"

Interval = Tuple[float, float]


def union_seconds(intervals: Sequence[Interval]) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, seconds."""
    return sum(e - s for s, e in merge(intervals)) / 1e9


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def program_name(event_name: str) -> str:
    """``jit__block_solve(123456)`` -> ``jit__block_solve``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


@dataclasses.dataclass
class DeviceTrace:
    device: int
    modules: List[Tuple[str, float, float]]  # (program, start_ns, end_ns)
    ops: List[Tuple[str, float, float]]      # (op, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]    # harness spans, same clock

    def window(self, name: str = "window") -> Optional[Interval]:
        """The harness span that brackets the measured window."""
        for n, s, e in self.spans:
            if n == name:
                return (s, e)
        return None

    def busy_intervals(self, device: DeviceTrace,
                       within: Optional[Interval] = None) -> List[Interval]:
        ivs = [(s, e) for _, s, e in (device.ops or device.modules)]
        if within is not None:
            ivs = clip(ivs, *within)
        return merge(ivs)

    def busy_seconds(self, within: Optional[Interval] = None) -> float:
        """Seconds in which an op ran, averaged over the chips traced."""
        if not self.devices:
            return 0.0
        return sum(union_seconds(self.busy_intervals(d, within))
                   for d in self.devices) / len(self.devices)

    def program_seconds(self, within: Optional[Interval] = None
                        ) -> Dict[str, float]:
        """Device seconds per program name, from the first chip's
        ``XLA Modules`` line (all chips run the same programs)."""
        out: Dict[str, float] = {}
        if not self.devices:
            return out
        for name, s, e in self.devices[0].modules:
            for cs, ce in clip([(s, e)], *(within or (s, e))):
                out[name] = out.get(name, 0.0) + (ce - cs) / 1e9
        return out

    def op_seconds(self, within: Optional[Interval] = None,
                   top: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device time, ``program/op`` keyed."""
        if not self.devices:
            return []
        dev = self.devices[0]
        mods = sorted(dev.modules, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        totals: Dict[str, float] = {}
        for name, s, e in dev.ops:
            if within is not None and (e <= within[0] or s >= within[1]):
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][0] if i >= 0 and s < mods[i][2] else "?"
            key = f"{prog}/{name}"
            totals[key] = totals.get(key, 0.0) + (e - s) / 1e9
        return sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    def idle_gaps(self, within: Optional[Interval] = None,
                  top: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds of the first chip by the innermost harness span
        open on the host at that moment, longest first."""
        if not self.devices:
            return []
        dev = self.devices[0]
        window = within or self.window()
        if window is None:
            return []
        busy = self.busy_intervals(dev, window)
        gaps, cur = [], window[0]
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < window[1]:
            gaps.append((cur, window[1]))
        # innermost = the latest-started span that covers the instant
        spans = sorted((s for s in self.spans if s[0] != "window"),
                       key=lambda x: x[1])
        totals: Dict[str, float] = {}
        for gs, ge in gaps:
            cuts = sorted({gs, ge, *(t for _, s, e in spans
                                     for t in (s, e) if gs < t < ge)})
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                label = "unspanned"
                for n, s, e in spans:
                    if s <= mid < e:
                        label = n
                totals[label] = totals.get(label, 0.0) + (b - a) / 1e9
        return sorted(totals.items(), key=lambda kv: -kv[1])[:top]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, span_prefix: str = SPAN_PREFIX) -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(int(m.group(1)), [], [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev.modules = [
                        (program_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
                elif line.name == OPS_LINE:
                    dev.ops = [
                        (op_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.name[len(span_prefix):], ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    devices.sort(key=lambda d: d.device)
    return Trace(devices, spans)
