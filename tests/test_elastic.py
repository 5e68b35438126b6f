"""Elastic multi-host streamed fits (ISSUE 11 tentpole): the CPU
dryrun harness spawns REAL ``jax.distributed`` worlds (gloo
collectives), kills one host mid-fit, relaunches, and resumes from the
shared ``StreamCheckpoint`` — pinning the acceptance criteria:

* kill-one-host-mid-fit resume is BIT-IDENTICAL to the uninterrupted
  2-process run (LinearMap; the auto-solver variant is pinned at the
  1e-5 bar by the parity test),
* a resume at a different world size raises
  ``CheckpointMismatchError`` (both directions, plus wrong-world-size
  world-to-world),
* 1-vs-2-process streamed-fit weight parity <= 1e-5 with identical
  argmax,
* the PR 9 warmup fence stays clean on the distributed path
  (``unexpected_compiles=0`` reported by every worker, fresh AND
  resumed runs).

The heavyweight subprocess worlds are launched ONCE per module
(``elastic_runs`` fixture: uninterrupted / killed / resumed); the
checkpoint-format and fault-kind semantics are unit-tested in-process.
The chaos soak (bounded seeded ``FaultPlan`` sweep across the ingest
sites, every seed ending in a clean finish, a classified failure, or a
resumable checkpoint — never a hang, never silent truncation) runs
in-process too; the host-level kinds ride the dryrun worlds.
"""
import os
import sys

import numpy as np
import pytest

from keystone_tpu.nodes.learning.linear import LinearMapEstimator
from keystone_tpu.parallel.distributed import DryrunWorld
from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming
from keystone_tpu.resilience import (
    HOST_DEATH_EXIT_CODE,
    CheckpointMismatchError,
    FaultPlan,
    IngestTimeoutError,
    PartitionError,
    RetryExhaustedError,
    StreamCheckpoint,
    fit_fingerprint,
)

N, D, K, CHUNK = 192, 12, 3, 16


def _xy(n=N, d=D, k=K, seed=0):
    rng = np.random.RandomState(seed)
    X = (rng.randn(n, d) * (1.0 + rng.rand(d))).astype(np.float32)
    Y = (X @ rng.randn(d, k) + 0.1 * rng.randn(n, k)).astype(np.float32)
    return X, Y


def _worker_argv(npz, extra=()):
    return [sys.executable, "-m", "keystone_tpu.parallel.dryrun_worker",
            "--data", npz, "--chunk-size", str(CHUNK), *extra]


def _ok_fields(world, pid):
    lines = [l for l in world.output(pid).splitlines()
             if l.startswith("ELASTIC_OK")]
    assert lines, (f"worker {pid} printed no ELASTIC_OK line:\n"
                   f"{world.output(pid)[-2000:]}")
    return dict(kv.split("=", 1) for kv in lines[0].split()[1:])


@pytest.fixture(scope="module")
def elastic_runs(tmp_path_factory):
    """Three 2-process worlds over the same data: uninterrupted,
    killed-at-round-2 (host 1 ``host_death``), and
    relaunched-and-resumed. One launch sequence serves every
    acceptance assertion below."""
    base_dir = tmp_path_factory.mktemp("elastic")
    X, Y = _xy()
    npz = str(base_dir / "data.npz")
    np.savez(npz, X=X, Y=Y)
    ckdir = str(base_dir / "ck")
    out_a = str(base_dir / "uninterrupted.npz")
    out_c = str(base_dir / "resumed.npz")
    world = DryrunWorld(num_processes=2, devices_per_process=2,
                        workdir=str(base_dir), grace_s=20)
    runs = {"X": X, "Y": Y, "npz": npz, "ckdir": ckdir, "world": world}

    world.launch(_worker_argv(npz, ["--out", out_a, "--bench"]))
    runs["codes_a"] = world.wait(timeout_s=300)
    runs["fields_a"] = [_ok_fields(world, p) for p in range(2)]
    runs["bench_a"] = [l for l in world.output(0).splitlines()
                       if l.startswith("{")]

    world.launch(_worker_argv(npz, [
        "--checkpoint-dir", ckdir, "--checkpoint-every", "1",
        "--die-process", "1", "--die-at-round", "2"]))
    runs["codes_b"] = world.wait(timeout_s=300)
    runs["snapshot_after_kill"] = sorted(os.listdir(ckdir))

    world.launch(_worker_argv(npz, [
        "--checkpoint-dir", ckdir, "--checkpoint-every", "1",
        "--out", out_c]))
    runs["codes_c"] = world.wait(timeout_s=300)
    runs["fields_c"] = [_ok_fields(world, p) for p in range(2)]
    runs["w_a"] = np.load(out_a)["weights"]
    runs["w_c"] = np.load(out_c)["weights"]
    return runs


def test_kill_one_host_resume_bit_identical(elastic_runs):
    """Acceptance: an N-process streamed LinearMap fit killed
    mid-stream, relaunched, and resumed from the shared
    StreamCheckpoint produces BIT-identical weights to the
    uninterrupted run."""
    r = elastic_runs
    assert r["codes_a"] == [0, 0], r["codes_a"]
    # host 1 died of the injected host_death (exit 117); the launcher's
    # gang semantics reaped the wedged survivor
    assert r["codes_b"][1] == HOST_DEATH_EXIT_CODE, r["codes_b"]
    assert r["codes_b"][0] != 0
    # the killed world left a resumable coordinated snapshot: the world
    # file plus both host sidecars
    assert "stream_fit.ckpt" in r["snapshot_after_kill"]
    assert {"stream_fit.host0.ckpt", "stream_fit.host1.ckpt"} <= set(
        r["snapshot_after_kill"])
    assert r["codes_c"] == [0, 0], r["codes_c"]
    for f in r["fields_c"]:
        assert f["resumed"] == "1", f  # restored, not refit from scratch
    assert (r["w_a"] == r["w_c"]).all(), (
        f"resumed weights diverge: max delta "
        f"{np.abs(r['w_a'] - r['w_c']).max()}")
    # the snapshot cleared after the successful finalize
    assert not os.path.exists(os.path.join(r["ckdir"], "stream_fit.ckpt"))


def test_distributed_path_fence_clean(elastic_runs):
    """Acceptance: the PR 9 warmup fence is clean on the distributed
    path — fresh AND resumed runs compile only in round 1."""
    for f in elastic_runs["fields_a"] + elastic_runs["fields_c"]:
        assert f["unexpected_compiles"] == "0", f


def test_world_weights_replicated_and_ledger_live(elastic_runs):
    """Every host finalizes the same merged carry (identical weight
    digests — asserted in-worker via an allgather, reported here), and
    the conditioning ledger saw the finalize solve on each host."""
    for fields in (elastic_runs["fields_a"], elastic_runs["fields_c"]):
        assert fields[0]["digest"] == fields[1]["digest"]
        for f in fields:
            assert int(f["solves"]) >= 1


def test_one_vs_two_process_weight_parity(elastic_runs):
    """Acceptance: 1-vs-2-process streamed-fit weight parity (the
    cross-host Gram tree-reduce changes only the f32 summation order)
    <= 1e-5 with identical prediction argmax."""
    X, Y = elastic_runs["X"], elastic_runs["Y"]
    m1 = fit_streaming(
        LinearMapEstimator(lam=0.1),
        StreamingDataset.from_numpy(X, chunk_size=CHUNK, tag="p1"), Y)
    w1 = np.asarray(m1.weights)
    w2 = elastic_runs["w_a"]
    rel = np.abs(w1 - w2).max() / max(np.abs(w1).max(), 1.0)
    assert rel <= 1e-5, f"1-vs-2 process weight delta {rel}"
    np.testing.assert_array_equal(
        np.argmax(X @ w1, axis=1), np.argmax(X @ w2, axis=1))


def test_scaling_metric_emitted(elastic_runs):
    """The harness emits its images/sec metric as one JSON line."""
    import json

    lines = [json.loads(l) for l in elastic_runs["bench_a"]]
    metrics = [l for l in lines
               if l.get("metric") == "elastic_streamed_images_per_sec"]
    assert metrics and metrics[0]["value"] > 0
    assert metrics[0]["processes"] == 2


def test_two_process_sharded_apply_parity(tmp_path):
    """ISSUE 18 tentpole b: ``sharded_apply`` over the WORLD mesh —
    weights row-sharded across both hosts, batches entering as
    host-local rows through the real ``host_local_array_to_global_array``
    path — matches the single-host ``model.apply`` <= 1e-5 with
    identical argmax, across buckets including ragged tails (asserted
    in-worker, see ``spmd_apply_worker.py``)."""
    worker = os.path.join(os.path.dirname(__file__),
                          "spmd_apply_worker.py")
    world = DryrunWorld(num_processes=2, devices_per_process=2,
                        workdir=str(tmp_path), grace_s=20)
    codes = world.launch([sys.executable, worker]).wait(timeout_s=300)
    for p in range(2):
        assert codes[p] == 0, (p, codes, world.output(p)[-2000:])
        assert f"SPMD_APPLY_OK pid={p}" in world.output(p)


# -- world-size / checkpoint-format semantics (in-process) -------------------

def _world_snapshot(ckdir, fingerprints, cursors, carries):
    ckpt = StreamCheckpoint(str(ckdir))
    for pid, (fp, cur, carry) in enumerate(
            zip(fingerprints, cursors, carries)):
        ckpt.save_host(fp, pid, cur, carry)
    ckpt.merge_hosts(len(fingerprints))
    return ckpt


def test_single_process_resume_of_world_snapshot_refuses(tmp_path):
    """Acceptance: a resume at a different world size raises
    CheckpointMismatchError — here the single-process direction,
    through the real fit_streaming resume path."""
    X, Y = _xy(n=96)
    stream = StreamingDataset.from_numpy(X, chunk_size=CHUNK, tag="ws")
    fp = fit_fingerprint(LinearMapEstimator(lam=0.1), stream, Y)
    carry = (np.zeros((D, D), np.float32), np.zeros((D, K), np.float32),
             np.zeros((D,), np.float32), np.zeros((K,), np.float32), 0)
    _world_snapshot(tmp_path, [fp, fp], [2, 2], [carry, carry])
    with pytest.raises(CheckpointMismatchError, match="2-process world"):
        fit_streaming(LinearMapEstimator(lam=0.1), stream, Y,
                      checkpoint_dir=str(tmp_path), checkpoint_every=1)


def test_world_resume_of_single_snapshot_refuses(tmp_path):
    ckpt = StreamCheckpoint(str(tmp_path))
    ckpt.save("fp0", 3, (np.zeros(4, np.float32),))
    with pytest.raises(CheckpointMismatchError,
                       match="single-process fit"):
        ckpt.load_world("fp0", process_id=0, processes=2)


def test_world_resume_at_wrong_world_size_refuses(tmp_path):
    carry = (np.ones(4, np.float32),)
    _world_snapshot(tmp_path, ["fp", "fp"], [1, 1], [carry, carry])
    ckpt = StreamCheckpoint(str(tmp_path))
    with pytest.raises(CheckpointMismatchError, match="2-process world"):
        ckpt.load_world("fp", process_id=0, processes=4)


def test_world_snapshot_roundtrip_and_clear(tmp_path):
    """Per-host slices restore exactly (cursor, carry, per-host
    fingerprint checked), and clear() removes the sidecars too."""
    carries = [(np.arange(4, dtype=np.float32),),
               (np.arange(4, 8, dtype=np.float32),)]
    ckpt = _world_snapshot(tmp_path, ["fpA", "fpB"], [3, 5], carries)
    h0 = ckpt.load_world("fpA", process_id=0, processes=2)
    h1 = ckpt.load_world("fpB", process_id=1, processes=2)
    assert h0["cursor"] == 3 and h1["cursor"] == 5
    np.testing.assert_array_equal(h0["carry"][0], carries[0][0])
    np.testing.assert_array_equal(h1["carry"][0], carries[1][0])
    with pytest.raises(CheckpointMismatchError, match="fingerprint"):
        ckpt.load_world("fpA", process_id=1, processes=2)
    ckpt.clear()
    assert os.listdir(str(tmp_path)) == []


def test_fit_fingerprint_folds_topology(monkeypatch):
    """The fingerprint changes with the world size (so even without
    the explicit topology check, a wrong-size resume mismatches)."""
    import keystone_tpu.parallel.distributed as dist

    X, Y = _xy(n=96)
    stream = StreamingDataset.from_numpy(X, chunk_size=CHUNK, tag="fp")
    est = LinearMapEstimator(lam=0.1)
    fp1 = fit_fingerprint(est, stream, Y)
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    fp2 = fit_fingerprint(est, stream, Y)
    assert fp1 != fp2


# -- host-level fault kinds (in-process semantics) ---------------------------

def test_host_death_gated_to_other_process_is_dormant():
    """A host_death rule aimed at another process index never fires —
    the SPMD contract: every host installs the same plan, the gate
    picks the victim (process_index is 0 here, the rule aims at 1)."""
    X, Y = _xy(n=96)
    plan = FaultPlan().add("ingest.produce", kind="host_death",
                           after=0, count=1, process_id=1)
    with plan:
        model = fit_streaming(
            LinearMapEstimator(lam=0.1),
            StreamingDataset.from_numpy(X, chunk_size=CHUNK), Y)
    assert plan.injections() == 0
    assert np.isfinite(np.asarray(model.weights)).all()


def test_partition_kind_raises_connection_error():
    plan = FaultPlan().add("ingest.stage", kind="partition", count=1,
                           process_id=0)
    from keystone_tpu.resilience.faults import inject

    with plan:
        with pytest.raises(PartitionError):
            inject("ingest.stage", context="t")
    assert isinstance(PartitionError("x"), ConnectionError)


def test_straggler_kind_delays_but_completes():
    import time

    plan = FaultPlan().add("ingest.produce", kind="straggler", count=2,
                           delay_s=0.15)
    X, Y = _xy(n=96)
    t0 = time.perf_counter()
    with plan:
        fit_streaming(LinearMapEstimator(lam=0.1),
                      StreamingDataset.from_numpy(X, chunk_size=CHUNK), Y)
    assert time.perf_counter() - t0 >= 0.3  # both delays served
    assert plan.injections() == 2


# -- chaos soak (satellite): bounded seeded sweep ----------------------------

def _soak_plan(seed):
    """A seeded random plan over the ingest sites: retryable errors,
    partitions, value corruption at the staging site; latency /
    straggler / bounded hangs in the producer loop. host_death is
    deliberately aimed at process 1 — dormant in-process (tier-1 runs
    single-process), LIVE in the dryrun worlds that reuse this shape."""
    rng = np.random.RandomState(1000 + seed)
    plan = FaultPlan(seed=seed)
    stage_kinds = ("error", "corrupt", "partition")
    produce_kinds = ("latency", "straggler", "hang")
    for _ in range(1 + rng.randint(3)):
        if rng.rand() < 0.5:
            plan.add("ingest.stage",
                     kind=stage_kinds[rng.randint(len(stage_kinds))],
                     rate=float(0.3 + 0.5 * rng.rand()),
                     after=int(rng.randint(3)),
                     count=int(1 + rng.randint(3)))
        else:
            plan.add("ingest.produce",
                     kind=produce_kinds[rng.randint(len(produce_kinds))],
                     rate=float(0.3 + 0.5 * rng.rand()),
                     after=int(rng.randint(3)),
                     count=int(1 + rng.randint(2)), delay_s=0.1)
    plan.add("coord.step", kind="host_death", process_id=1, count=1)
    # the overlap window (ISSUE 18): a second host_death aimed at the
    # AWAIT point — between a round's dispatch and its await, when the
    # allgather and the lagged carry snapshot are both in flight. Same
    # process gate: dormant here, live in the dryrun worlds.
    plan.add("coord.await", kind="host_death", process_id=1, count=1)
    return plan


@pytest.mark.parametrize("seed", range(6))
def test_chaos_soak_bounded_outcomes(tmp_path, seed):
    """Satellite: every seed ends in a clean finish, a CLASSIFIED
    failure (retry exhaustion / ingest timeout / numerics tripwire —
    each of which leaves a resumable checkpoint), and the follow-up
    fit converges to the fault-free weights bit for bit. Any other
    exception, any hang (the producer watchdog is armed), or any
    silent truncation fails the test."""
    from keystone_tpu.observability.numerics import NumericsError

    X, Y = _xy(n=128, d=8, seed=seed)

    def stream():
        return StreamingDataset.from_numpy(
            X, chunk_size=32, tag=f"soak{seed}", stall_timeout_s=15.0)

    clean = np.asarray(fit_streaming(
        LinearMapEstimator(lam=0.1), stream(), Y).weights)
    ckdir = str(tmp_path / "ck")
    outcome = "clean"
    try:
        with _soak_plan(seed):
            fit_streaming(LinearMapEstimator(lam=0.1), stream(), Y,
                          checkpoint_dir=ckdir, checkpoint_every=1)
    except (RetryExhaustedError, IngestTimeoutError, NumericsError):
        outcome = "failed-classified"
    # clean finish cleared the snapshot (fresh refit); a classified
    # failure left a resumable one — either way the follow-up run must
    # land on the fault-free weights exactly
    resumed = np.asarray(fit_streaming(
        LinearMapEstimator(lam=0.1), stream(), Y,
        checkpoint_dir=ckdir, checkpoint_every=1).weights)
    assert (resumed == clean).all(), (
        f"seed {seed} ({outcome}): weights diverged by "
        f"{np.abs(resumed - clean).max()}")


# -- shard-local ingest + analysis flag --------------------------------------

def test_sharded_spec_flag_and_lint_message():
    """stream_tar_shards marks its stream process-sharded; the spec
    carries the flag (repr included) and the non-streamable-fit lint
    names the shard-local provenance instead of suggesting a
    materialize() of one host's fraction."""
    import jax

    from keystone_tpu.analysis.diagnostics import check_graph
    from keystone_tpu.analysis.spec import dataset_spec
    from keystone_tpu.nodes.learning.pca import ColumnPCAEstimator

    X, _ = _xy(n=80)
    stream = StreamingDataset.from_numpy(X, chunk_size=40)
    stream.process_sharded = True
    spec = dataset_spec(stream)
    assert spec.sharded and "sharded" in repr(spec)
    # derived views keep the provenance
    assert dataset_spec(stream.map_chunks(lambda ad: ad)).sharded
    p = ColumnPCAEstimator(4).with_data(stream)
    rep = check_graph(
        p._graph, {p._source: jax.ShapeDtypeStruct((D,), np.float32)},
        name="sharded-stream")
    hits = [d for d in rep.diagnostics if d.code == "non-streamable-fit"]
    assert len(hits) == 1
    assert "shard-local" in hits[0].message
    assert "CLUSTER.md" in hits[0].message


def _make_image_tars(tar_dir, shards=2, per_shard=12, side=8, seed=0):
    import io
    import tarfile

    from PIL import Image as PILImage

    rng = np.random.RandomState(seed)
    os.makedirs(tar_dir, exist_ok=True)
    imgs = []
    for t in range(shards):
        with tarfile.open(os.path.join(tar_dir, f"shard{t}.tar"),
                          "w") as tf:
            for i in range(per_shard):
                arr = (rng.rand(side, side, 3) * 255).astype(np.uint8)
                imgs.append(arr)
                buf = io.BytesIO()
                PILImage.fromarray(arr).save(buf, format="PNG")
                info = tarfile.TarInfo(f"img{t}_{i:02d}.png")
                info.size = buf.getbuffer().nbytes
                buf.seek(0)
                tf.addfile(info, buf)
    return imgs


def test_shard_local_tar_ingest_two_hosts(tmp_path):
    """Sharded streaming ingest over a real 2-process world: each host
    decodes ONLY its process-strided tar shard, the moment carries
    tree-reduce at finalize, and the merged scaler equals the resident
    computation over ALL images."""
    tar_dir = str(tmp_path / "tars")
    imgs = _make_image_tars(tar_dir)
    out = str(tmp_path / "scaler.npz")
    world = DryrunWorld(num_processes=2, devices_per_process=2,
                        workdir=str(tmp_path), grace_s=20)
    world.launch([sys.executable, "-m",
                  "keystone_tpu.parallel.dryrun_worker",
                  "--tar-dir", tar_dir, "--chunk-size", "8",
                  "--out", out])
    codes = world.wait(timeout_s=300)
    assert codes == [0, 0], [world.output(p)[-1500:] for p in range(2)]
    fields = [_ok_fields(world, p) for p in range(2)]
    # shard-locality: host 0 touched only shard0, host 1 only shard1
    assert fields[0]["archives"] == "shard0.tar"
    assert fields[1]["archives"] == "shard1.tar"
    assert fields[0]["digest"] == fields[1]["digest"]
    for f in fields:
        assert f["unexpected_compiles"] == "0"
    flat = np.stack(imgs).reshape(len(imgs), -1).astype(np.float32)
    got = np.load(out)["weights"]
    mean, std = got[:flat.shape[1]], got[flat.shape[1]:]
    assert np.abs(mean - flat.mean(0)).max() <= 1e-4
    assert np.abs(std - flat.std(0, ddof=1)).max() <= 1e-3


@pytest.mark.slow
def test_straggler_world_completes_with_parity(tmp_path):
    """Host-level chaos in the dryrun harness: a straggling host 0 plus
    the coordination barriers — the world completes with replicated
    weights (the straggler just makes everyone wait)."""
    X, Y = _xy()
    npz = str(tmp_path / "data.npz")
    np.savez(npz, X=X, Y=Y)
    world = DryrunWorld(num_processes=2, devices_per_process=2,
                        workdir=str(tmp_path), grace_s=25)
    world.launch(_worker_argv(npz, ["--straggle-process", "0"]))
    codes = world.wait(timeout_s=300)
    assert codes == [0, 0], [world.output(p)[-1500:] for p in range(2)]
    fields = [_ok_fields(world, p) for p in range(2)]
    assert fields[0]["digest"] == fields[1]["digest"]


@pytest.mark.slow
def test_partitioned_world_relaunches_and_resumes(tmp_path):
    """A network partition at a coordination round kills the step (the
    injected PartitionError crashes host 1); the relaunched world
    resumes from the coordinated snapshot — same recovery story as
    host death, different failure mode."""
    X, Y = _xy()
    npz = str(tmp_path / "data.npz")
    np.savez(npz, X=X, Y=Y)
    ckdir = str(tmp_path / "ck")
    out_a = str(tmp_path / "a.npz")
    out_c = str(tmp_path / "c.npz")
    world = DryrunWorld(num_processes=2, devices_per_process=2,
                        workdir=str(tmp_path), grace_s=20)
    world.launch(_worker_argv(npz, ["--out", out_a]))
    assert world.wait(300) == [0, 0]
    world.launch(_worker_argv(npz, [
        "--checkpoint-dir", ckdir, "--checkpoint-every", "1",
        "--partition-process", "1", "--partition-at-round", "2"]))
    codes = world.wait(300)
    assert codes[1] not in (0, HOST_DEATH_EXIT_CODE), codes
    assert os.path.exists(os.path.join(ckdir, "stream_fit.ckpt"))
    world.launch(_worker_argv(npz, [
        "--checkpoint-dir", ckdir, "--checkpoint-every", "1",
        "--out", out_c]))
    assert world.wait(300) == [0, 0]
    fields = [_ok_fields(world, p) for p in range(2)]
    assert all(f["resumed"] == "1" for f in fields)
    assert (np.load(out_a)["weights"] == np.load(out_c)["weights"]).all()


# -- the divergent-collective hazard, reproduced for real --------------------

@pytest.mark.slow
def test_divergent_collective_deadlocks_and_is_reaped(tmp_path):
    """ISSUE 12 satellite: the hazard class the `collective-divergence`
    pass (analysis/spmd.py) flags statically — a barrier under an
    `if process_index() == 0:` branch — reproduced dynamically: the
    deliberately divergent worker (tests/spmd_divergent_worker.py,
    flagged by tests/test_spmd_passes.py) enters a collective its peer
    never matches. The divergent host makes NO progress and raises NO
    error (the silent gang-schedule hang); the peer finishes, exits 0,
    and the DryrunWorld launcher's gang grace reaps the wedged
    member."""
    worker = os.path.join(os.path.dirname(__file__),
                          "spmd_divergent_worker.py")
    world = DryrunWorld(num_processes=2, devices_per_process=1,
                        workdir=str(tmp_path), grace_s=8)
    world.launch([sys.executable, worker])
    codes = world.wait(timeout_s=180)
    # the straight host completed the matched barrier and exited clean
    assert codes[1] == 0, world.output(1)[-1500:]
    assert "DIVERGE_DONE pid=1" in world.output(1)
    # the divergent host entered the world (the matched barrier), then
    # wedged in the host-0-only collective: never printed its done
    # line, never errored on its own — it was killed by gang grace
    out0 = world.output(0)
    assert "DIVERGE_ENTER pid=0" in out0, out0[-1500:]
    assert "DIVERGE_DONE pid=0" not in out0, (
        "the divergent host was expected to wedge in the unmatched "
        "collective, but it completed — the hazard did not reproduce")
    assert codes[0] != 0, codes
