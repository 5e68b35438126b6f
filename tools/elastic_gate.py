#!/usr/bin/env python3
"""Elastic-resume CI gate: kill one host mid-fit, relaunch, resume —
the resumed weights must be BIT-IDENTICAL to the uninterrupted run.
A CPU gate: it pins ``JAX_PLATFORMS=cpu`` (and ``DryrunWorld`` pins its
members), so its worker processes never contend for a chip.

The dynamic pin for the elastic multi-host plane
(``parallel/distributed.py``), the cross-process complement of the
recompile and numerics gates. Five worlds of 2 CPU processes (2
virtual devices each) run the same shard-local streamed LinearMap fit
through the real ``jax.distributed`` + gloo path:

1. **uninterrupted** — the reference weights;
2. **killed** — a ``host_death`` fault takes out process 1 entering
   coordination round 2 (exit code 117, after exactly 2 coordinated
   checkpoints); the launcher applies gang semantics and reaps the
   wedged survivor — the world snapshot (per-host cursors + carries,
   merged by host 0 from the durably-renamed sidecars) is what
   survives;
3. **relaunched** — the same world resumes from the shared
   ``StreamCheckpoint``: every worker must report ``resumed=1`` and
   ``unexpected_compiles=0`` (the PR 9 warmup fence stays clean across
   a resume), and host 0's weights must equal run 1's bit for bit;
4. **killed mid-overlap** — the kill lands at round 2's AWAIT point,
   i.e. BETWEEN a round's dispatch and its await under the overlapped
   loop (PR 18): round 2's allgather and the lagged carry snapshot are
   both in flight when the host dies — the hardest window, because the
   surviving sidecars may legitimately trail the live cursor by one
   round (the overlap's lagged-snapshot contract);
5. **relaunched again** — resume from the mid-overlap kill's snapshot:
   sidecar-trailing resume replays the un-snapshotted round and must
   STILL produce bit-identical weights with a clean fence (resume
   re-accumulates from the quiesced boundary, never from a torn one).

Exit 1 names the divergent artifact (which run, which file, max
delta). Run by ``bin/ci.sh``; standalone::

    python tools/elastic_gate.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N, D, K, CHUNK = 192, 12, 3, 16
KILL_ROUND = 2


def _check_world(world, codes, name, expect_resumed):
    for pid, code in enumerate(codes):
        if code != 0:
            print(world.output(pid)[-2000:], file=sys.stderr)
            print(f"elastic gate FAILED: {name} run process {pid} "
                  f"exited {code} (log above)", file=sys.stderr)
            return False
        line = [l for l in world.output(pid).splitlines()
                if l.startswith("ELASTIC_OK")]
        if not line:
            print(f"elastic gate FAILED: {name} run process {pid} "
                  "printed no ELASTIC_OK line", file=sys.stderr)
            return False
        fields = dict(kv.split("=", 1) for kv in line[0].split()[1:])
        if int(fields["unexpected_compiles"]) != 0:
            print(f"elastic gate FAILED: {name} run process {pid} saw "
                  f"{fields['unexpected_compiles']} unexpected "
                  "recompile(s) under the fit fence — the distributed "
                  "path must compile only in round 1", file=sys.stderr)
            return False
        if int(fields["resumed"]) != expect_resumed:
            print(f"elastic gate FAILED: {name} run process {pid} "
                  f"reported resumed={fields['resumed']}, expected "
                  f"{expect_resumed} — the relaunched world did not "
                  "restore the shared StreamCheckpoint",
                  file=sys.stderr)
            return False
    return True


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from keystone_tpu.parallel.distributed import DryrunWorld
    from keystone_tpu.resilience.faults import HOST_DEATH_EXIT_CODE

    workdir = tempfile.mkdtemp(prefix="keystone-elastic-gate-")
    rng = np.random.RandomState(0)
    npz = os.path.join(workdir, "data.npz")
    np.savez(npz, X=rng.randn(N, D).astype(np.float32),
             Y=rng.randn(N, K).astype(np.float32))
    ckdir = os.path.join(workdir, "ck")
    out_a = os.path.join(workdir, "uninterrupted.npz")
    out_c = os.path.join(workdir, "resumed.npz")
    base = [sys.executable, "-m", "keystone_tpu.parallel.dryrun_worker",
            "--data", npz, "--chunk-size", str(CHUNK)]

    world = DryrunWorld(num_processes=2, devices_per_process=2,
                        workdir=workdir, grace_s=20)
    print("elastic gate: run 1/5 — uninterrupted 2-process streamed fit")
    codes = world.launch(base + ["--out", out_a]).wait(timeout_s=300)
    if not _check_world(world, codes, "uninterrupted", expect_resumed=0):
        return 1

    print(f"elastic gate: run 2/5 — kill process 1 at round {KILL_ROUND}")
    codes = world.launch(
        base + ["--checkpoint-dir", ckdir, "--checkpoint-every", "1",
                "--die-process", "1",
                "--die-at-round", str(KILL_ROUND)]).wait(timeout_s=300)
    if world.host_death_exits(codes) != [1]:
        print(f"elastic gate FAILED: expected process 1 to die of "
              f"host_death (exit {HOST_DEATH_EXIT_CODE}), got exit "
              f"codes {codes}", file=sys.stderr)
        return 1
    if not os.path.exists(os.path.join(ckdir, "stream_fit.ckpt")):
        print("elastic gate FAILED: the killed world left no shared "
              f"world snapshot under {ckdir} — nothing to resume from",
              file=sys.stderr)
        return 1

    print("elastic gate: run 3/5 — relaunch the world, resume, compare")
    codes = world.launch(
        base + ["--checkpoint-dir", ckdir, "--checkpoint-every", "1",
                "--out", out_c]).wait(timeout_s=300)
    if not _check_world(world, codes, "resumed", expect_resumed=1):
        return 1

    w_a = np.load(out_a)["weights"]
    w_c = np.load(out_c)["weights"]
    if not (w_a == w_c).all():
        delta = float(np.abs(w_a - w_c).max())
        print(f"elastic gate FAILED: resumed weights diverge from the "
              f"uninterrupted run (max |delta| {delta:.3e}; divergent "
              f"artifact: {out_c} vs reference {out_a}) — the "
              "kill-and-resume path is no longer bit-identical",
              file=sys.stderr)
        return 1
    if os.path.exists(os.path.join(ckdir, "stream_fit.ckpt")):
        print("elastic gate FAILED: the world snapshot survived a "
              "successful finalize (stale snapshots must be cleared)",
              file=sys.stderr)
        return 1

    # -- the overlap window: kill BETWEEN dispatch and await -----------------
    ckdir2 = os.path.join(workdir, "ck-overlap")
    out_e = os.path.join(workdir, "resumed-overlap.npz")
    print(f"elastic gate: run 4/5 — kill process 1 at round "
          f"{KILL_ROUND}'s await (mid-overlap: allgather + carry "
          "snapshot in flight)")
    codes = world.launch(
        base + ["--checkpoint-dir", ckdir2, "--checkpoint-every", "1",
                "--die-process", "1",
                "--die-at-await-round", str(KILL_ROUND)]
    ).wait(timeout_s=300)
    if world.host_death_exits(codes) != [1]:
        print(f"elastic gate FAILED: expected process 1 to die of "
              f"host_death at the await point (exit "
              f"{HOST_DEATH_EXIT_CODE}), got exit codes {codes}",
              file=sys.stderr)
        return 1
    if not os.path.exists(os.path.join(ckdir2, "stream_fit.ckpt")):
        print("elastic gate FAILED: the mid-overlap kill left no "
              f"shared world snapshot under {ckdir2} — nothing to "
              "resume from", file=sys.stderr)
        return 1

    print("elastic gate: run 5/5 — relaunch after the mid-overlap "
          "kill, resume, compare")
    codes = world.launch(
        base + ["--checkpoint-dir", ckdir2, "--checkpoint-every", "1",
                "--out", out_e]).wait(timeout_s=300)
    if not _check_world(world, codes, "overlap-resumed",
                        expect_resumed=1):
        return 1
    w_e = np.load(out_e)["weights"]
    if not (w_a == w_e).all():
        delta = float(np.abs(w_a - w_e).max())
        print(f"elastic gate FAILED: weights resumed from a "
              f"mid-overlap kill diverge from the uninterrupted run "
              f"(max |delta| {delta:.3e}; divergent artifact: {out_e} "
              f"vs reference {out_a}) — the lagged-snapshot resume is "
              "no longer bit-identical", file=sys.stderr)
        return 1
    if os.path.exists(os.path.join(ckdir2, "stream_fit.ckpt")):
        print("elastic gate FAILED: the overlap-run world snapshot "
              "survived a successful finalize (stale snapshots must "
              "be cleared)", file=sys.stderr)
        return 1
    print("elastic gate OK: killed worlds (round entry AND "
          "mid-overlap await) resumed to bit-identical weights, "
          "fence clean, snapshots cleared")
    return 0


if __name__ == "__main__":
    sys.exit(main())
