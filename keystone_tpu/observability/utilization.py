"""Device-utilization accounting: MFU and roofline position.

Every bench headline so far has been denominated in img/s — a number
with no hardware denominator. Following the MFU accounting popularized
by PaLM (Chowdhery et al., 2022: achieved FLOP/s over the chip's peak
FLOP/s, no credit for rematerialization) and classic roofline analysis
(Williams et al., 2009), this module converts measured wall time plus
the compile observatory's per-executable ``cost_analysis()`` /
``memory_analysis()`` into:

* **MFU** — achieved model FLOP/s as a fraction of the device's peak
  (``*_mfu`` bench keys);
* **memory-bandwidth utilization** — achieved bytes/s over HBM
  bandwidth (``*_membw_util``);
* a **roofline verdict** — arithmetic intensity (FLOPs per byte
  accessed) against the device's ridge point says whether the section
  is compute-bound or memory-bound, i.e. which of the two numbers is
  the one to optimize.

Peaks come from a small per-device-kind catalogue
(:data:`DEVICE_PEAKS`, dense-matmul peak + HBM bandwidth per chip from
public spec sheets), overridable via ``KEYSTONE_PEAK_FLOPS`` /
``KEYSTONE_PEAK_HBM_BW``. A device kind the catalogue does not know is
an error, never a default. The
``cpu`` entry is an explicit PLACEHOLDER (order-of-magnitude host
numbers) so the CPU-simulated test mesh exercises the full code path —
CPU-sim MFU values are plumbing evidence, not performance claims
(README "Reading utilization" carries the caveat).

FLOP counts come from the jit sites the compile observatory watches:
each site's calls are counted and its executable's ``cost_analysis``
is resolved on demand through the AOT path (never an execution), so a
:class:`UtilizationWindow` around a bench region can total
``flops x calls`` across every observed program that ran, divide by
wall, and report coverage honestly (sites whose stats could not be
captured are listed, never silently dropped).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from .compilelog import registered_sites

#: (peak dense-matmul FLOP/s, HBM bytes/s) per chip, keyed by substrings
#: of ``jax.devices()[0].device_kind``. Peaks are the vendor bf16/f32
#: matmul peaks — the PaLM-MFU convention denominates in peak matmul
#: throughput. Sources: public TPU/GPU spec sheets.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v2": {"flops_per_s": 45e12, "hbm_bytes_per_s": 700e9},
    "TPU v3": {"flops_per_s": 123e12, "hbm_bytes_per_s": 900e9},
    "TPU v4": {"flops_per_s": 275e12, "hbm_bytes_per_s": 1200e9},
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5p": {"flops_per_s": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v6": {"flops_per_s": 918e12, "hbm_bytes_per_s": 1640e9},
    "H100": {"flops_per_s": 989e12, "hbm_bytes_per_s": 3350e9},
    "A100": {"flops_per_s": 312e12, "hbm_bytes_per_s": 2039e9},
    # explicit placeholder: a CPU host has no meaningful single peak;
    # these order-of-magnitude numbers keep the CPU-simulated mesh
    # exercising the full MFU plumbing without pretending precision
    "cpu": {"flops_per_s": 100e9, "hbm_bytes_per_s": 50e9},
}


@dataclass(frozen=True)
class DevicePeaks:
    """One device kind's roofline parameters. ``source`` says where the
    numbers came from (``catalogue`` / ``env``) so every derived MFU can
    be audited back to its denominator."""

    kind: str
    flops_per_s: float
    hbm_bytes_per_s: float
    source: str

    @property
    def ridge_intensity(self) -> float:
        """FLOPs/byte at which the roofline's compute and memory
        ceilings intersect; below it a kernel is memory-bound."""
        return self.flops_per_s / self.hbm_bytes_per_s


def device_peaks(device_kind: Optional[str] = None) -> DevicePeaks:
    """Roofline parameters for ``device_kind`` (default: the first jax
    device). Env overrides win (``KEYSTONE_PEAK_FLOPS`` /
    ``KEYSTONE_PEAK_HBM_BW``, both floats). A kind that matches no
    catalogue row raises: a utilization figure against another
    device's peak is worse than none."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    flops_env = os.environ.get("KEYSTONE_PEAK_FLOPS")
    bw_env = os.environ.get("KEYSTONE_PEAK_HBM_BW")
    entry = None
    source = "catalogue"
    for key, value in DEVICE_PEAKS.items():
        if key.lower() in device_kind.lower():
            entry = dict(value)
            break
    if entry is None:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth recorded for device_kind "
            f"{device_kind!r}; known: {sorted(DEVICE_PEAKS)}. Add its "
            "row to observability.utilization.DEVICE_PEAKS with the "
            "source of the figures")
    if flops_env:
        entry["flops_per_s"] = float(flops_env)
        source = "env"
    if bw_env:
        entry["hbm_bytes_per_s"] = float(bw_env)
        source = "env"
    return DevicePeaks(kind=device_kind, flops_per_s=entry["flops_per_s"],
                       hbm_bytes_per_s=entry["hbm_bytes_per_s"],
                       source=source)


def roofline(flops: float, bytes_accessed: float, elapsed_s: float,
             n_devices: int = 1,
             peaks: Optional[DevicePeaks] = None) -> Dict[str, Any]:
    """MFU + bandwidth utilization + roofline verdict for a measured
    region: ``flops``/``bytes_accessed`` are TOTALS over ``elapsed_s``
    seconds across ``n_devices`` chips (peaks are per-chip)."""
    peaks = peaks or device_peaks()
    elapsed_s = max(float(elapsed_s), 1e-12)
    denom_flops = peaks.flops_per_s * max(1, n_devices)
    denom_bw = peaks.hbm_bytes_per_s * max(1, n_devices)
    achieved_flops = float(flops) / elapsed_s
    achieved_bw = float(bytes_accessed) / elapsed_s
    intensity = (float(flops) / float(bytes_accessed)
                 if bytes_accessed else float("inf"))
    return {
        "mfu": achieved_flops / denom_flops,
        "membw_util": achieved_bw / denom_bw,
        "achieved_flops_per_s": achieved_flops,
        "achieved_bytes_per_s": achieved_bw,
        "arithmetic_intensity": intensity,
        "ridge_intensity": peaks.ridge_intensity,
        "bound": ("compute" if intensity >= peaks.ridge_intensity
                  else "memory"),
        "device_kind": peaks.kind,
        "peaks_source": peaks.source,
    }


class UtilizationWindow:
    """Measure MFU over a region by counting observed-jit calls.

    Usage::

        with UtilizationWindow() as uw:
            run_the_benchmark()
        u = uw.report(n_devices=8)
        # u["mfu"], u["membw_util"], u["bound"], u["covered_sites"], ...

    On entry it snapshots every watched jit site's call count; on
    report it totals ``per-call flops x call delta`` over the sites
    that ran, resolving each site's ``cost_analysis`` through the AOT
    path on demand. Sites whose stats cannot be captured (opaque static
    arguments, backend without analysis) are returned in
    ``uncovered_sites`` — coverage is reported, never assumed. Per-call
    stats come from each site's most recent signature, so a window in
    which one site ran several different shapes is approximate (bench
    regions run one shape steady-state, which is the intended use)."""

    def __init__(self) -> None:
        self._calls0: Dict[int, int] = {}
        self._t0 = 0.0
        self.wall_s = 0.0

    def __enter__(self) -> "UtilizationWindow":
        self._calls0 = {id(s): s.calls for s in registered_sites()}
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.wall_s = time.perf_counter() - self._t0

    def report(self, elapsed_s: Optional[float] = None,
               n_devices: Optional[int] = None,
               peaks: Optional[DevicePeaks] = None) -> Dict[str, Any]:
        if n_devices is None:
            try:
                import jax

                n_devices = len(jax.devices())
            except Exception:
                n_devices = 1
        flops = 0.0
        bytes_accessed = 0.0
        covered: List[str] = []
        uncovered: List[str] = []
        for site in registered_sites():
            delta = site.calls - self._calls0.get(id(site), 0)
            if delta <= 0:
                continue
            stats = site.capture_stats()
            if stats is None:
                uncovered.append(site.name)
                continue
            # zero-FLOP programs (pure data movement, e.g. the streamed
            # wire cast) are still covered: their bytes_accessed is real
            # HBM traffic and often the section's largest mover —
            # dropping them would under-report membw_util and could
            # flip the roofline verdict
            flops += stats.get("flops", 0.0) * delta
            bytes_accessed += stats.get("bytes_accessed", 0.0) * delta
            covered.append(site.name)
        out = roofline(flops, bytes_accessed,
                       elapsed_s if elapsed_s is not None else self.wall_s,
                       n_devices=n_devices, peaks=peaks)
        out["flops_total"] = flops
        out["bytes_accessed_total"] = bytes_accessed
        out["covered_sites"] = sorted(covered)
        out["uncovered_sites"] = sorted(set(uncovered))
        return out


def annotate_trace(trace: Any,
                   peaks: Optional[DevicePeaks] = None,
                   plan: Any = None) -> int:
    """Back-fill per-node MFU onto a finished
    :class:`~.trace.PipelineTrace`: every ``record_compile`` entry the
    executor attributed to a node context (``node:<label>#<id>``) is
    resolved to its site's executable stats, and the matching
    :class:`~.trace.NodeRecord` gains ``flops`` / ``mfu`` /
    ``membw_util`` (denominator: the node's inclusive wall minus its
    compile wall — the first execution is the one that compiled).
    With ``plan`` (a PR 6 :class:`~..analysis.resources.HbmPlan`) the
    record also gains ``plan_vs_xla``: the planner's charge for the
    node (output + transient bytes) over XLA's own ``memory_analysis``
    accounting (output + temp bytes) — ~1.0 means the static model
    matches what the compiler actually allocates. Returns how many
    node records were annotated."""
    peaks = peaks or device_peaks()
    plan_entries: Dict[int, Dict[str, Any]] = {}
    for e in (getattr(plan, "entries", None) or []):
        if e.get("resolved"):
            plan_entries[int(e["node_id"])] = e
    sites = {s.name: s for s in registered_sites()}
    by_node: Dict[int, Dict[str, float]] = {}
    for entry in getattr(trace, "compiles", []):
        context = entry.get("context") or ""
        if not context.startswith("node:") or "#" not in context:
            continue
        try:
            node_id = int(context.rsplit("#", 1)[1])
        except ValueError:
            continue
        site = sites.get(entry.get("name", ""))
        stats = site.capture_stats() if site is not None else None
        if not stats:
            continue
        agg = by_node.setdefault(node_id, {
            "flops": 0.0, "bytes": 0.0, "compile_s": 0.0,
            "out_temp": 0.0})
        agg["flops"] += float(stats.get("flops", 0.0))
        agg["bytes"] += float(stats.get("bytes_accessed", 0.0))
        agg["out_temp"] += (float(stats.get("output_bytes", 0.0))
                            + float(stats.get("temp_bytes", 0.0)))
        agg["compile_s"] += float(entry.get("wall_s", 0.0))
    annotated = 0
    for record in getattr(trace, "nodes", []):
        agg = by_node.get(record.node_id)
        if agg is None or record.cached:
            continue
        compute_s = max(record.total_s - agg["compile_s"], 1e-9)
        r = roofline(agg["flops"], agg["bytes"], compute_s,
                     n_devices=max(1, record.shards), peaks=peaks)
        record.flops = agg["flops"]
        record.mfu = r["mfu"]
        record.membw_util = r["membw_util"]
        pe = plan_entries.get(record.node_id)
        if pe is not None and agg["out_temp"]:
            record.plan_vs_xla = round(
                (float(pe.get("out_nbytes", 0.0))
                 + float(pe.get("transient_nbytes", 0.0)))
                / agg["out_temp"], 3)
        annotated += 1
    return annotated
