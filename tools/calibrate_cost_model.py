"""Calibrate the auto-solver cost-model weights on THIS chip
(VERDICT r3 next#2; reference weights were calibrated on 16x EC2
r3.4xlarge — ``LeastSquaresEstimator.scala:17,26-31`` — and encode a
2015 CPU-cluster cost surface that has nothing to do with a TPU).

The reference cost form is kept (it is what the solvers' ``cost()``
methods implement):

    cost = iters * ( max(cpu_w * flops, mem_w * elements_scanned)
                     + net_w * elements_over_network )

On TPU the three weights have direct hardware meanings:

    cpu_w  = seconds per MXU flop at solver precision (HIGHEST)
    mem_w  = seconds per f32 element streamed from HBM
    net_w  = seconds per f32 element over ICI (all-reduce leg)

This tool measures the first two directly (a compute-bound HIGHEST
Gram for the flop rate; a bandwidth-bound reduction for the stream
rate), derives the third from the chip generation's published ICI
bandwidth (not measurable on a single chip; the value only matters
multi-chip where log2(machines) > 0), then VALIDATES: it times the
three dense solver options end-to-end at several (n, d) shapes and
checks the fitted model ranks them like the measurements do.

Data is generated ON DEVICE (its content is irrelevant) and every timed
region ends by waiting for the device (tools/_bench.py).

Floor-cancelled differences are GUARDED (ADVICE r5 low#3): host
jitter can make dt_large - dt_small near-zero or negative, which would
silently print nonsensical (even negative) weights; each pair is
re-measured once and the run aborts with a clear message if the
difference stays non-positive, and every derived rate is bounds-checked
before the ship block is printed.

Besides the copy-pasteable ship block, the tool writes a calibration
ARTIFACT (JSON with the four weights plus timestamp / hostname /
device): ``keystone_tpu.nodes.learning.least_squares`` loads it in
preference to the shipped defaults, and pipeline traces report its
provenance with every solver decision.

Usage: python tools/calibrate_cost_model.py [--small] [--out PATH]
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import random

sys.path.insert(0, ".")  # repo root

from keystone_tpu.ops import linalg  # noqa: E402
from keystone_tpu.parallel.dataset import ArrayDataset  # noqa: E402

SMALL = "--small" in sys.argv


from tools._bench import device_arrays as _device_arrays  # noqa: E402,F401
from tools._bench import fence, timeit  # noqa: E402


# -- primitive rates -------------------------------------------------------

def _floor_cancelled(label, measure):
    """rate = numer / (dt_large - dt_small) with a jitter guard:
    ``measure()`` returns (dt_small, dt_large, numer); a non-positive
    difference (host jitter swamping the size delta) is re-measured
    once, then aborts — a negative weight must never reach the ship
    block or the artifact."""
    for attempt in (0, 1):
        dt_small, dt_large, numer = measure()
        if dt_large > dt_small:
            return numer / (dt_large - dt_small)
        print(f"WARNING: {label}: dt_large ({dt_large * 1e3:.1f} ms) <= "
              f"dt_small ({dt_small * 1e3:.1f} ms) — host jitter "
              "swamped the floor-cancelled difference; "
              + ("retrying once" if attempt == 0 else "aborting"),
              flush=True)
    raise SystemExit(
        f"calibration aborted: {label} unmeasurable on this host (the "
        "large-shape timing is not slower than the small-shape timing "
        "after a retry). Re-run when the host is quieter; do NOT "
        "hand-edit weights from a run that printed this message.")


def _sanity_bound(name, value, lo, hi, unit):
    """Abort before printing/shipping a physically implausible rate."""
    if not (lo <= value <= hi) or not np.isfinite(value):
        raise SystemExit(
            f"calibration aborted: {name} = {value:.3e} {unit} is outside "
            f"the plausible range [{lo:.0e}, {hi:.0e}] — the measurement "
            "is untrustworthy (host jitter, thermal throttling, or a "
            "mis-detected device). Re-run; do not ship these weights.")
    return value


def measure_flop_rate():
    """Sustained solver-precision (HIGHEST) MXU rate on a Gram at the
    solver's own shape class. FLOOR-CANCELLED: every timed call pays a
    dispatch round trip, which at the small shapes is comparable to
    the compute itself — so the rate is taken from the
    DIFFERENCE between two row counts, where the per-call latency
    cancels (r5: the single-shape estimate read 18.5 TFLOPS for a
    ~40 TFLOPS gram)."""
    n_small, n_large, d = ((4_096, 16_384, 1_024) if SMALL
                           else (16_384, 49_152, 4_096))
    g = jax.jit(linalg.gram)

    def measure():
        dts = {}
        for n in (n_small, n_large):
            A = random.normal(random.PRNGKey(0), (n, d), jnp.float32)
            fence(A)
            dts[n] = timeit(g, A)
        return (dts[n_small], dts[n_large],
                2.0 * (n_large - n_small) * d * d)

    # plausible sustained MXU rates: ~GFLOPS (CPU smoke) to <2 PFLOPS
    return _sanity_bound("MXU flop rate",
                         _floor_cancelled("MXU flop rate", measure),
                         1e8, 2e15, "FLOPS")


def measure_stream_rate():
    """Sustained HBM read rate (f32 elements/s) on a bandwidth-bound
    reduction — floor-cancelled like the flop rate (the single-size
    estimate read 12.7 GB/s for a ~2 TB/s stream: pure dispatch
    floor)."""
    e_small = (8 << 20) if SMALL else (32 << 20)
    e_large = (32 << 20) if SMALL else (160 << 20)

    @jax.jit
    def scan_sum(x):
        return jnp.sum(x)

    def measure():
        dts = {}
        for elems in (e_small, e_large):
            A = random.normal(random.PRNGKey(1), (elems,), jnp.float32)
            fence(A)
            dts[elems] = timeit(scan_sum, A, iters=4)
        return dts[e_small], dts[e_large], float(e_large - e_small)

    # ~4 MB/s (broken) .. 4 TB/s-class HBM in f32 elements/s
    return _sanity_bound("HBM stream rate",
                         _floor_cancelled("HBM stream rate", measure),
                         1e6, 1e13, "elements/s")


def measure_dispatch_latency():
    """Seconds per serial device round: the time of a trivial jitted op
    (all latency, no compute). This is the ``lat_w`` the TPU cost
    extension charges per dispatch round — the term that lets the model
    rank latency-dominated small-d solves (the scan-based BCD's 3
    rounds beat the exact solver's ~10 at every d tested)."""
    x = random.normal(random.PRNGKey(2), (128,), jnp.float32)
    fence(x)

    @jax.jit
    def bump(v):
        return v + 1.0

    return timeit(bump, x, iters=8)


#: Published per-chip ICI bandwidth by generation (bytes/s, one
#: direction). Used for net_w only — a single-chip calibration cannot
#: measure ICI; on one chip every log2(machines) term is zero anyway.
_ICI_BYTES_PER_S = {
    "v4": 3 * 2 * 37.5e9,   # 3 links x 75 GB/s bidirectional
    "v5 lite": 1600e9 / 8 / 2,  # 1600 Gbps total, half per direction
    "v5": 4800e9 / 8 / 2,
    "v6": 4 * 2 * 56.0e9,
}


def derive_net_weight():
    kind = jax.devices()[0].device_kind.lower()
    for tag, rate in _ICI_BYTES_PER_S.items():
        if tag in kind:
            return 4.0 / rate  # seconds per f32 element
    return 4.0 / 100e9


# -- end-to-end solver timings --------------------------------------------

def solver_options(lam=0.1):
    from keystone_tpu.nodes.learning.lbfgs import DenseLBFGSwithL2
    from keystone_tpu.nodes.learning.linear import (
        BlockLeastSquaresEstimator,
        LinearMapEstimator,
    )

    return [
        ("dense_lbfgs", DenseLBFGSwithL2(lam=lam, num_iterations=20)),
        ("block_ls", BlockLeastSquaresEstimator(1000, 3, lam=lam)),
        ("exact", LinearMapEstimator(lam=lam)),
    ]


def time_solvers(n, d, k=10):
    X = random.normal(random.PRNGKey(2), (n, d), jnp.float32)
    Y = random.normal(random.PRNGKey(3), (n, k), jnp.float32)
    fence((X, Y))
    ds = ArrayDataset(X, n)
    labels = ArrayDataset(Y, n)
    out = {}
    for name, solver in solver_options():
        dt = timeit(lambda: solver._fit(ds, labels), iters=2)
        out[name] = dt
        print(f"  n={n} d={d} {name:12s} {dt * 1e3:9.1f} ms", flush=True)
    return out


def predicted_ranking(n, d, k, cpu_w, mem_w, net_w, lat_w):
    costs = {
        name: solver.cost(n, d, k, 1.0, 1, cpu_w, mem_w, net_w,
                          lat_w=lat_w)
        for name, solver in solver_options()
    }
    return sorted(costs, key=costs.get), costs


def write_artifact(path, weights, agreement, shapes_checked):
    """Persist the calibration as the JSON artifact that
    ``least_squares.load_calibration`` picks up, stamped with enough
    provenance (timestamp, hostname, device) for the observability layer
    to report where a solver decision's weights came from."""
    import datetime
    import json
    import os
    import socket

    blob = dict(weights)
    blob.update({
        "device": jax.devices()[0].device_kind,
        "hostname": socket.gethostname(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "agreement": f"{agreement}/{shapes_checked}",
        "small": SMALL,
        "tool": "tools/calibrate_cost_model.py",
    })
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(blob, f, indent=2)
    os.replace(tmp, path)
    return path


def main():
    from keystone_tpu.nodes.learning.least_squares import (
        DEFAULT_CALIBRATION_PATH,
    )
    from keystone_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    out_path = DEFAULT_CALIBRATION_PATH
    if "--out" in sys.argv:
        i = sys.argv.index("--out")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--out requires a path")
        out_path = sys.argv[i + 1]

    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    flop_rate = measure_flop_rate()
    stream_rate = measure_stream_rate()
    lat_w = _sanity_bound("dispatch latency", measure_dispatch_latency(),
                          1e-7, 1.0, "s/round")
    cpu_w = 1.0 / flop_rate
    mem_w = 1.0 / stream_rate
    net_w = derive_net_weight()
    print(f"MXU rate (HIGHEST gram, floor-cancelled): "
          f"{flop_rate / 1e12:.2f} TFLOPS -> cpu_w = {cpu_w:.3e} s/flop",
          flush=True)
    print(f"HBM stream rate (floor-cancelled): "
          f"{stream_rate * 4 / 1e9:.1f} GB/s -> mem_w = {mem_w:.3e} s/elem",
          flush=True)
    print(f"dispatch latency: lat_w = {lat_w:.3e} s/round", flush=True)
    print(f"ICI (spec-derived): net_w = {net_w:.3e} s/elem", flush=True)

    shapes = [(65_536, 256), (65_536, 1_024), (32_768, 4_096)]
    if SMALL:
        shapes = [(8_192, 256), (8_192, 1_024)]
    agree = 0
    for n, d in shapes:
        measured = time_solvers(n, d)
        m_rank = sorted(measured, key=measured.get)
        p_rank, p_costs = predicted_ranking(n, d, 10, cpu_w, mem_w,
                                            net_w, lat_w)
        ok = m_rank[0] == p_rank[0]
        agree += ok
        print(f"  -> measured fastest: {m_rank[0]}, model picks: "
              f"{p_rank[0]}  {'OK' if ok else 'MISMATCH'}", flush=True)
        print(f"     predicted costs: "
              + ", ".join(f"{k2}={v:.3f}s" for k2, v in p_costs.items()),
              flush=True)
    print()
    print("ship these as the TPU defaults in "
          "keystone_tpu/nodes/learning/least_squares.py:", flush=True)
    print(f"DEFAULT_CPU_WEIGHT = {cpu_w:.3e}", flush=True)
    print(f"DEFAULT_MEM_WEIGHT = {mem_w:.3e}", flush=True)
    print(f"DEFAULT_NETWORK_WEIGHT = {net_w:.3e}", flush=True)
    print(f"DEFAULT_LAT_WEIGHT = {lat_w:.3e}", flush=True)
    print(f"model-vs-measurement agreement: {agree}/{len(shapes)} shapes",
          flush=True)
    if 2 * agree <= len(shapes):
        # the agreement check used to gate a human copy-pasting the ship
        # block; now that the artifact is auto-loaded it must gate the
        # write — weights that mis-rank the measured solvers on most
        # validation shapes would silently mis-rank every future solve
        print(f"NOT writing calibration artifact: model-vs-measurement "
              f"agreement {agree}/{len(shapes)} is too low to trust "
              "(rates may be individually plausible but jitter-skewed). "
              "Re-run on a quieter host; shipped defaults stay active.",
              flush=True)
        return
    weights = {"cpu_weight": cpu_w, "mem_weight": mem_w,
               "network_weight": net_w, "lat_weight": lat_w}
    path = write_artifact(out_path, weights, agree, len(shapes))
    print(f"calibration artifact written to {path} — "
          "LeastSquaresEstimator loads it automatically (override with "
          "$KEYSTONE_COST_CALIBRATION); pipeline traces report its "
          "provenance with every solver decision", flush=True)


if __name__ == "__main__":
    main()
