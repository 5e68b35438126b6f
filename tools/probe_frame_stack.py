"""Host probe: what a Python call costs where a chunk of CPython's frame
stack ends, on this machine (PERF.md section 6, PR 42).

    python3 tools/probe_frame_stack.py            # here, or through chiprun

CPython (3.11 on) keeps a thread's frames in chunks of 16 KB and gives a
chunk back the moment the frame at its start returns: a loop that calls
from the depth where a chunk ends maps a chunk, touches it and unmaps it
at every call. That is 10 us in this repo's sandbox and 150 us on the
host of a TPU v5e (3,500 times a call), and tracing an unrolled Pallas
kernel is some hundred thousand calls at one depth: seconds of a
process's set-up that no profiler shows, because a profiler's own frames
move the chunk's end somewhere else. The probe times an empty call from
every depth up to ``--depths``, names the depths where it is slow and
times an ``mmap``, a touch and a ``munmap`` of 16 KB made by hand, once
before jax is imported and once with the backend's threads up, and then
the slowest call again under ``ops.pallas_kernels._with_frame_room``
(one frame of 512 KB, whose chunk of 1 MB holds every call beneath it).
Host times only; nothing here is a device time.
"""
from __future__ import annotations

import argparse
import mmap
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mapping_us(n: int = 2000, size: int = 16384) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        m = mmap.mmap(-1, size)
        m[0] = 1
        m.close()
    return (time.perf_counter() - t0) / n * 1e6


def leaf():
    return None


def call_us(depth: int, calls: int) -> float:
    if depth > 0:
        return call_us(depth - 1, calls)
    t0 = time.perf_counter()
    for _ in range(calls):
        leaf()
    return (time.perf_counter() - t0) / calls * 1e6


def sweep(tag: str, depths: int, calls: int, roomy=None) -> None:
    us = [call_us(d, calls) for d in range(depths)]
    base = sorted(us)[len(us) // 2]
    slow = [(d, round(u, 2)) for d, u in enumerate(us) if u > 5 * base + 1]
    print(f"{tag}: an empty call {base:.3f} us (median over {depths} "
          f"depths); slow at {slow[:8]}; a mapping of 16 KB by hand "
          f"{mapping_us():.1f} us; threads "
          f"{len(os.listdir('/proc/self/task'))}", flush=True)
    if slow and roomy is not None:
        d = max(slow, key=lambda du: du[1])[0]
        print(f"{tag}: depth {d} again {call_us(d, calls):.2f} us; with "
              f"room {roomy(d, calls):.3f} us", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--depths", type=int, default=260)
    ap.add_argument("--calls", type=int, default=300)
    args = ap.parse_args()
    sys.setrecursionlimit(max(sys.getrecursionlimit(), args.depths + 200))
    print(f"python {sys.version.split()[0]}", flush=True)
    sweep("before jax", args.depths, args.calls)
    import jax

    from keystone_tpu.ops.pallas_kernels import _with_frame_room

    print(f"{jax.devices()[0].device_kind} x{len(jax.devices())}", flush=True)
    sweep("backend up", args.depths, args.calls, _with_frame_room(call_us))


if __name__ == "__main__":
    main()
