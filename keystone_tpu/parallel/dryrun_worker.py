"""SPMD worker for the elastic multi-host dryrun harness.

One member of a :class:`~keystone_tpu.parallel.distributed.DryrunWorld`:
wires ``jax.distributed`` over the launcher's loopback coordinator,
builds the host-LOCAL mesh, runs a shard-local streamed fit through the
REAL distributed ``fit_streaming`` path (round coordination,
coordinated checkpoints, cross-host carry tree-reduce at finalize), and
prints a machine-checkable result line::

    ELASTIC_OK pid=0 world=2 rows=128 chunks=4 resumed=0 \
unexpected_compiles=0 solves=1 digest=91f2a4...

Fault scenarios are injected with the host-level
:class:`~keystone_tpu.resilience.faults.FaultPlan` kinds
(``--die-process`` installs a ``host_death``, ``--straggle-process`` a
``straggler`` at the coordination site, ``--partition-process`` a
``partition``) — every host installs the SAME plan (the SPMD contract)
and the ``process_id`` gate picks the victim.

Invariants asserted IN the worker, so a green exit code means more
than "didn't crash": the fitted weights' digest is allgathered and
must be identical on every host (the finalize merge replicates), and
``unexpected_compiles`` reports the PR 9 warmup-fence verdict on the
distributed path (the launcher-side tests assert it printed 0).

Usage (the launcher appends the positionals)::

    python -m keystone_tpu.parallel.dryrun_worker [flags] \
        <process_id> <num_processes> <coordinator_port>
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import sys
import time


def _parse(argv):
    p = argparse.ArgumentParser(prog="dryrun_worker")
    p.add_argument("--data", default=None,
                   help=".npz with arrays X (n, d) and Y (n, k); each "
                        "host takes its contiguous 1/world block")
    p.add_argument("--tar-dir", default=None,
                   help="shard-local tar ingest mode: each host "
                        "decodes only its process-strided archives "
                        "(stream_tar_shards) and fits a StandardScaler")
    p.add_argument("--chunk-size", type=int, default=32)
    p.add_argument("--estimator", default="linear",
                   choices=("linear", "auto", "scaler"))
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=2)
    p.add_argument("--out", default=None,
                   help="host 0 writes the fitted weights here (.npz)")
    p.add_argument("--die-process", type=int, default=None)
    p.add_argument("--die-at-chunk", type=int, default=None,
                   help="host_death fires after this many produced "
                        "chunks on --die-process (the prefetch "
                        "producer runs ahead of the consumer, so the "
                        "kill lands early in the fit)")
    p.add_argument("--die-at-round", type=int, default=None,
                   help="host_death fires entering this coordination "
                        "round on --die-process — deterministic in "
                        "ROUND terms, i.e. after exactly that many "
                        "coordinated checkpoints")
    p.add_argument("--die-at-await-round", type=int, default=None,
                   help="host_death fires at this round's AWAIT point "
                        "on --die-process — i.e. BETWEEN a round's "
                        "dispatch and its await under the overlapped "
                        "loop, the window where a carry snapshot and "
                        "an allgather are both in flight")
    p.add_argument("--straggle-process", type=int, default=None)
    p.add_argument("--partition-process", type=int, default=None)
    p.add_argument("--partition-at-round", type=int, default=1)
    p.add_argument("--bench", action="store_true",
                   help="host 0 emits an images/sec metric line (plus "
                        "the coordination-cost pair when distributed)")
    p.add_argument("--warmup", action="store_true",
                   help="fit once untimed first: the timed fit then "
                        "measures the warm steady state (per-chunk "
                        "accumulate + coordination), not trace/compile "
                        "— the number scaling efficiency is about")
    p.add_argument("process_id", type=int)
    p.add_argument("num_processes", type=int)
    p.add_argument("port")
    return p.parse_args(argv)


def _build_plan(args):
    from keystone_tpu.resilience.faults import FaultPlan

    plan = FaultPlan(seed=0)
    used = False
    if args.die_process is not None:
        if args.die_at_await_round is not None:
            plan.add("coord.await", kind="host_death",
                     after=args.die_at_await_round, count=1,
                     process_id=args.die_process)
        elif args.die_at_round is not None:
            plan.add("coord.step", kind="host_death",
                     after=args.die_at_round, count=1,
                     process_id=args.die_process)
        else:
            plan.add("ingest.produce", kind="host_death",
                     after=(3 if args.die_at_chunk is None
                            else args.die_at_chunk), count=1,
                     process_id=args.die_process)
        used = True
    if args.straggle_process is not None:
        plan.add("coord.step", kind="straggler",
                 process_id=args.straggle_process)
        used = True
    if args.partition_process is not None:
        plan.add("coord.step", kind="partition",
                 after=args.partition_at_round, count=1,
                 process_id=args.partition_process)
        used = True
    return plan if used else None


def main(argv=None) -> int:
    args = _parse(sys.argv[1:] if argv is None else list(argv))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    from keystone_tpu.parallel.mesh import (
        initialize_distributed,
        local_mesh,
        mesh_scope,
    )

    initialize_distributed(f"127.0.0.1:{args.port}", args.num_processes,
                           args.process_id)
    pid, nproc = jax.process_index(), jax.process_count()
    assert nproc == args.num_processes, (nproc, args.num_processes)

    from keystone_tpu.observability.compilelog import compile_observatory
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.parallel.streaming import (
        StreamingDataset,
        fit_streaming,
    )

    plan = _build_plan(args)
    obs = compile_observatory()
    with mesh_scope(local_mesh()):
        labels = None
        archives = None
        if args.tar_dir is not None:
            from keystone_tpu.loaders.image_loader_utils import (
                stream_tar_shards,
            )

            def prepare(batch):
                return np.stack([img for _, img in batch]).reshape(
                    len(batch), -1).astype(np.float32)

            stream = stream_tar_shards(args.tar_dir, args.chunk_size,
                                       prepare=prepare)
            archives = [os.path.basename(a)
                        for a in stream.shard_archives]
            rows_total = None
            from keystone_tpu.nodes.stats import StandardScaler

            est = StandardScaler()
        else:
            blob = np.load(args.data)
            X, Y = blob["X"], blob["Y"]
            # contiguous block shard: host i owns rows [lo, hi) — the
            # same partition every relaunch, which is what makes
            # kill-and-resume bit-identical
            bounds = np.linspace(0, X.shape[0], nproc + 1).astype(int)
            lo, hi = int(bounds[pid]), int(bounds[pid + 1])
            Xl = np.ascontiguousarray(X[lo:hi])
            rows_total = int(X.shape[0])
            stream = StreamingDataset.from_numpy(
                Xl, chunk_size=args.chunk_size, tag="elastic")
            if args.estimator == "scaler":
                from keystone_tpu.nodes.stats import StandardScaler

                est = StandardScaler()
            else:
                labels = np.ascontiguousarray(Y[lo:hi])
                if args.estimator == "linear":
                    from keystone_tpu.nodes.learning.linear import (
                        LinearMapEstimator,
                    )

                    est = LinearMapEstimator(lam=0.1)
                else:
                    from keystone_tpu.nodes.learning.least_squares import (
                        LeastSquaresEstimator,
                    )

                    est = LeastSquaresEstimator(lam=0.1)

        if args.warmup and args.data is not None:
            # untimed first fit: trace + compile + gather-program
            # warmup land here, OUTSIDE the fault plan (injected
            # faults count rounds of the measured fit only). The timed
            # fit below then reruns the identical program shapes warm,
            # so its wall is the steady state the scaling-efficiency
            # claim is about — per-chunk accumulate with coordination
            # hidden behind it — not a per-process constant of
            # compile wall amortized over however many rows we chose.
            fit_streaming(
                est, StreamingDataset.from_numpy(
                    Xl, chunk_size=args.chunk_size,
                    tag="elastic-warmup"),
                labels)
        t0 = time.perf_counter()
        ctx = plan if plan is not None else contextlib.nullcontext()
        try:
            with ctx:
                model = fit_streaming(
                    est, stream, labels,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=(args.checkpoint_every
                                      if args.checkpoint_dir else None))
        except BaseException:
            # gang semantics: a failed SPMD step kills the host, HARD.
            # A normal interpreter exit can wedge in the distributed
            # runtime's teardown (the coordinator-client shutdown waits
            # on peers that are themselves stuck in a collective this
            # host just abandoned) — and a worker that neither exits
            # nor progresses defeats the launcher's dead-member
            # detection. os._exit skips teardown, exactly like a real
            # crash; the launcher reaps the wedged survivors.
            import traceback

            traceback.print_exc()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)
        wall = time.perf_counter() - t0

        if hasattr(model, "weights"):
            w = np.asarray(model.weights, np.float32)
        else:  # StandardScalerModel: mean (+ std when normalizing)
            w = np.asarray(model.mean, np.float32)
            std = getattr(model, "std", None)
            if std is not None:
                w = np.concatenate([w, np.asarray(std, np.float32)])
        digest = hashlib.sha256(np.ascontiguousarray(w).tobytes()
                                ).hexdigest()[:16]
        if nproc > 1:
            # the finalize merge replicates: every host must have
            # solved the SAME merged carry into the SAME weights
            from jax.experimental.multihost_utils import process_allgather

            token = np.frombuffer(
                bytes.fromhex(digest), dtype=np.int64)
            gathered = np.asarray(process_allgather(token))
            assert (gathered == gathered[0]).all(), (
                f"cross-host weight divergence: digests {gathered}")

        snap = MetricsRegistry.get_or_create().snapshot()
        counters = snap.get("counters", {})
        resumed = int(counters.get("resilience.checkpoint_restore", 0))
        solves = int(counters.get("numerics.solves_total", 0))
        unexpected = obs.unexpected_total()
        if pid == 0 and args.out:
            np.savez(args.out, weights=w)
        line = (f"ELASTIC_OK pid={pid} world={nproc} "
                f"rows={rows_total if rows_total is not None else '?'} "
                f"resumed={resumed} unexpected_compiles={unexpected} "
                f"solves={solves} digest={digest}")
        if archives is not None:
            line += f" archives={','.join(archives)}"
        print(line, flush=True)
        if args.bench and pid == 0 and rows_total:
            print(json.dumps({
                "metric": "elastic_streamed_images_per_sec",
                "value": rows_total / wall,
                "processes": nproc, "chunk_size": args.chunk_size,
                "warm": bool(args.warmup),
            }), flush=True)
            # the coordination-cost pair the overlapped loop exists to
            # move (PERFORMANCE.md rule 17: measure the await, not the
            # round): blocked-await wall over round wall, and its
            # complement, straight from the coordinator's gauge
            occ = snap.get("gauges", {}).get("coord.overlap_occupancy")
            if nproc > 1 and occ is not None:
                print(json.dumps({
                    "metric": "coord_overhead_share",
                    "value": round(1.0 - float(occ), 6),
                    "processes": nproc,
                }), flush=True)
                print(json.dumps({
                    "metric": "coord_overlap_occupancy",
                    "value": round(float(occ), 6),
                    "processes": nproc,
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
