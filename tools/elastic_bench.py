#!/usr/bin/env python3
"""1-vs-N-process streamed-fit scaling bench on the CPU dryrun harness.
A CPU tool: it pins ``JAX_PLATFORMS=cpu`` for itself and its worker
processes, and its timings are CPU timings wherever it is started.

Runs the same shard-local streamed LinearMap fit at world size 1 and
world size N (default 2) through ``parallel.distributed.DryrunWorld``
+ ``parallel.dryrun_worker`` — real ``jax.distributed`` + gloo, real
coordination rounds, real finalize tree-reduce — and emits the
benchdiff-parseable metric lines MULTICHIP_r06+ records::

    {"metric": "elastic_streamed_images_per_sec_1p", "value": ...}
    {"metric": "elastic_streamed_images_per_sec_2p", "value": ...}
    {"metric": "elastic_scaling_efficiency", "value": ...}
    {"metric": "coord_overhead_share", "value": ...}
    {"metric": "coord_overlap_occupancy", "value": ...}

``elastic_scaling_efficiency`` = (N-process img/s) / (N x 1-process
img/s). On the CPU sim every "host" shares one machine, so the number
is a COORDINATION-OVERHEAD floor, not a hardware scaling claim: it
bounds what the round barriers + carry merge cost when the compute
itself cannot speed up. On real pod hardware the same harness measures
true scaling.

Both worlds fit WARM by default (``--cold`` disables): the worker runs
one untimed fit first, so the timed number is the steady state — per-
chunk accumulate with coordination overlapped behind it — rather than
each process's one-off trace/compile wall amortized over the row count
(which is what put MULTICHIP_r06 at 0.27: ~2s of per-process fixed cost
against ~2ms/chunk of actual work). The ``coord_overhead_share`` /
``coord_overlap_occupancy`` pair (blocked-await wall over round wall,
and its complement) is forwarded from the N-process world so the
artifact records WHY the efficiency moved — PERFORMANCE.md rule 17:
measure the await, not the round.

    python tools/elastic_bench.py [--processes N]
    [--rows R] [--dim D] [--chunk-size C] [--cold]
"""
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _run_world(nproc, npz, chunk, workdir, warmup=True):
    from keystone_tpu.parallel.distributed import DryrunWorld

    # the numerics health plane stays OFF in both worlds: its per-chunk
    # health-word D2H hits a ~30ms fixed latency under the initialized
    # gloo runtime (a distributed-client transfer path, paid even at
    # world size 1) that buries the per-chunk compute either world
    # actually does — the scaling ratio would measure that stall, not
    # coordination. The plane's cost has its own banded line
    # (numerics_overhead_share, bench.py) on real hardware.
    world = DryrunWorld(num_processes=nproc, devices_per_process=2,
                        workdir=workdir, grace_s=30,
                        env={"KEYSTONE_NUMERICS": "0"})
    cmd = [sys.executable, "-m", "keystone_tpu.parallel.dryrun_worker",
           "--data", npz, "--chunk-size", str(chunk), "--bench"]
    if warmup:
        cmd.append("--warmup")
    world.launch(cmd)
    codes = world.wait(timeout_s=900)
    if any(codes):
        for p in range(nproc):
            print(world.output(p)[-1500:], file=sys.stderr)
        raise SystemExit(f"elastic bench: world size {nproc} failed "
                         f"(exit codes {codes})")
    out = world.output(0)
    m = re.search(r'^\{.*"elastic_streamed_images_per_sec".*\}$', out,
                  re.MULTILINE)
    if not m:
        raise SystemExit(f"elastic bench: world size {nproc} emitted "
                         "no metric line")
    blob = json.loads(m.group(0))
    fence = [l for l in out.splitlines() if l.startswith("ELASTIC_OK")]
    coord = [json.loads(l) for l in out.splitlines()
             if l.startswith('{') and '"coord_' in l]
    return float(blob["value"]), fence, coord


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    args = sys.argv[1:]

    def _flag(name, default, cast=int):
        if name in args:
            i = args.index(name)
            v = cast(args[i + 1])
            del args[i:i + 2]
            return v
        return default

    nproc = _flag("--processes", 2)
    rows = _flag("--rows", 32768)
    dim = _flag("--dim", 64)
    chunk = _flag("--chunk-size", 256)
    warmup = "--cold" not in args

    import numpy as np

    workdir = tempfile.mkdtemp(prefix="keystone-elastic-bench-")
    rng = np.random.RandomState(0)
    npz = os.path.join(workdir, "data.npz")
    np.savez(npz, X=rng.randn(rows, dim).astype(np.float32),
             Y=rng.randn(rows, 8).astype(np.float32))

    print(f"elastic bench: {rows}x{dim} f32, chunk {chunk}, "
          f"world sizes 1 and {nproc} (CPU dryrun, "
          f"{'warm steady-state' if warmup else 'cold'})")
    ips_1, _, _ = _run_world(1, npz, chunk, workdir, warmup=warmup)
    ips_n, fence, coord = _run_world(nproc, npz, chunk, workdir,
                                     warmup=warmup)
    for line in fence:
        print(line)
    efficiency = ips_n / (nproc * ips_1) if ips_1 else 0.0
    print(json.dumps({"metric": "elastic_streamed_images_per_sec_1p",
                      "value": ips_1, "rows": rows, "dim": dim,
                      "warm": warmup}))
    print(json.dumps({"metric":
                      f"elastic_streamed_images_per_sec_{nproc}p",
                      "value": ips_n, "rows": rows, "dim": dim,
                      "warm": warmup}))
    print(json.dumps({"metric": "elastic_scaling_efficiency",
                      "value": efficiency, "processes": nproc,
                      "note": "cpu-sim: coordination-overhead floor, "
                              "hosts share one machine; warm per-chunk "
                              "wall is dispatch-latency-bound under the "
                              "gloo runtime, so N hosts overlapping "
                              "that latency can exceed 1.0 — the claim "
                              "is 'coordination adds ~nothing', not "
                              "'extra hardware appeared'"}))
    for blob in coord:
        print(json.dumps(blob))
    return 0


if __name__ == "__main__":
    sys.exit(main())
