"""Resilient execution (ISSUE 4): deterministic fault injection,
retry/backoff ingest, corrupt-record quarantine, the producer watchdog,
and checkpoint/resume for streaming fits."""
import io
import json
import os
import pickle
import tarfile
import time

import numpy as np
import pytest

from keystone_tpu.loaders.image_loader_utils import (
    iter_decoded_chunks,
    iter_tar_images,
    stream_tar_images,
)
from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
from keystone_tpu.nodes.learning.linear import LinearMapEstimator
from keystone_tpu.nodes.stats import StandardScaler
from keystone_tpu.observability import MetricsRegistry, PipelineTrace
from keystone_tpu.parallel.dataset import ArrayDataset, ensure_array
from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming
from keystone_tpu.resilience import (
    CheckpointCorruptError,
    CheckpointMismatchError,
    CorruptRecordError,
    FaultPlan,
    IngestTimeoutError,
    InjectedFaultError,
    Quarantine,
    QuarantineBudgetExceededError,
    RetryExhaustedError,
    RetryPolicy,
    StreamCheckpoint,
    TransientError,
    fit_fingerprint,
    inject,
)


def _xy(n=240, d=12, k=3, seed=0):
    rng = np.random.RandomState(seed)
    X = (rng.randn(n, d) * (1.0 + rng.rand(d))).astype(np.float32)
    Y = (X @ rng.randn(d, k) + 0.1 * rng.randn(n, k)).astype(np.float32)
    return X, Y


def _make_tar(path, n_images=10, corrupt=(), side=8, seed=0):
    """A tar of PNGs; indices in ``corrupt`` hold garbage bytes."""
    rng = np.random.RandomState(seed)
    from PIL import Image as PILImage

    with tarfile.open(path, "w") as tf:
        for i in range(n_images):
            if i in corrupt:
                data = b"definitely not an image"
            else:
                arr = (rng.rand(side, side, 3) * 255).astype(np.uint8)
                buf = io.BytesIO()
                PILImage.fromarray(arr).save(buf, format="PNG")
                data = buf.getvalue()
            info = tarfile.TarInfo(f"img{i:03d}.png")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
    return str(path)


# -- RetryPolicy -------------------------------------------------------------

def test_retry_succeeds_after_transients():
    calls = []
    policy = RetryPolicy(max_attempts=3, backoff_s=0.001)

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientError("flaky disk")
        return "ok"

    with PipelineTrace("r") as tr:
        assert policy.call(flaky, site="unit") == "ok"
    assert len(calls) == 3
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["counters"]["resilience.retry"] >= 2
    assert tr.resilience_stats.get("retry") == 2
    assert all(e["site"] == "unit" for e in tr.resilience)


def test_retry_non_retryable_propagates_immediately():
    calls = []
    policy = RetryPolicy(max_attempts=5, backoff_s=0.001)

    def broken():
        calls.append(1)
        raise ValueError("deterministic bug")

    with pytest.raises(ValueError):
        policy.call(broken, site="unit")
    assert len(calls) == 1  # no useless retries

    # corrupt records are explicitly non-retryable: quarantine, don't spin
    def corrupt():
        calls.append(1)
        raise CorruptRecordError("bad jpeg")

    calls.clear()
    with pytest.raises(CorruptRecordError):
        policy.call(corrupt, site="unit")
    assert len(calls) == 1


def test_retry_exhaustion_raises_with_cause():
    policy = RetryPolicy(max_attempts=3, backoff_s=0.001)

    def always():
        raise TransientError("still down")

    with pytest.raises(RetryExhaustedError) as exc:
        policy.call(always, site="ingest.read")
    assert "ingest.read" in str(exc.value)
    assert "3 attempt" in str(exc.value)
    assert isinstance(exc.value.__cause__, TransientError)
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["counters"]["resilience.retry_exhausted"] >= 1


def test_retry_backoff_deterministic_and_capped():
    a = RetryPolicy(backoff_s=0.1, multiplier=2.0, max_backoff_s=0.3,
                    jitter=0.5, seed=7)
    b = RetryPolicy(backoff_s=0.1, multiplier=2.0, max_backoff_s=0.3,
                    jitter=0.5, seed=7)
    seq_a = [a.backoff(i) for i in range(1, 6)]
    seq_b = [b.backoff(i) for i in range(1, 6)]
    assert seq_a == seq_b  # seeded jitter
    # base is capped at max_backoff_s; jitter stretches by at most 50%
    assert all(d <= 0.3 * 1.5 for d in seq_a)
    assert all(d >= 0.1 for d in seq_a)


def test_retry_attempt_timeout_counts_as_transient():
    calls = []
    policy = RetryPolicy(max_attempts=2, backoff_s=0.001,
                         attempt_timeout_s=0.2)

    def slow_then_fast():
        calls.append(1)
        if len(calls) == 1:
            time.sleep(1.0)  # overruns the per-attempt timeout
        return "done"

    assert policy.call(slow_then_fast, site="unit") == "done"
    assert len(calls) == 2


# -- fault-injection harness -------------------------------------------------

def test_fault_plan_seeded_and_deterministic():
    def run(seed):
        hits = 0
        with FaultPlan(seed=seed).add("site", rate=0.3):
            for i in range(200):
                try:
                    inject("site", i)
                except InjectedFaultError:
                    hits += 1
        return hits

    h1, h2 = run(11), run(11)
    assert h1 == h2 and 20 < h1 < 100  # same seed, ~30% rate
    assert run(12) != h1  # a different seed lands differently


def test_fault_plan_after_and_count_are_exact():
    plan = FaultPlan().add("site", after=3, count=2)
    seen = []
    with plan:
        for i in range(10):
            try:
                inject("site", i)
                seen.append(i)
            except InjectedFaultError:
                pass
    # visits 4 and 5 injected (after=3 skips the first 3), count caps at 2
    assert seen == [0, 1, 2, 5, 6, 7, 8, 9]
    assert plan.injections("site") == 2


def test_inject_is_noop_without_plan_and_plans_do_not_nest():
    inject("anything", context="no plan active")  # must not raise
    with FaultPlan():
        with pytest.raises(RuntimeError, match="already active"):
            FaultPlan().__enter__()


# -- quarantine --------------------------------------------------------------

def test_quarantine_budget_exceeded_names_source():
    q = Quarantine(max_bad_fraction=0.01, min_records=10, label="cifar")
    q.record_ok(500)
    q.quarantine("a.tar::img1.png", "undecodable")  # 1 of 501: fine
    for i in range(2, 6):
        q.quarantine(f"a.tar::img{i}.png", "undecodable")
    with pytest.raises(QuarantineBudgetExceededError) as exc:
        q.quarantine("a.tar::img6.png", "undecodable")
    msg = str(exc.value)
    assert "cifar" in msg and "a.tar::img6.png" in msg
    assert "max_bad_fraction" in msg


def test_quarantine_idempotent_manifest_and_state(tmp_path):
    manifest = str(tmp_path / "quarantine.jsonl")
    q = Quarantine(max_bad_fraction=0.5, min_records=1,
                   manifest_path=manifest, label="t")
    q.record_ok(10)
    q.quarantine("tar::a.png", "bad bytes")
    q.quarantine("tar::a.png", "bad bytes")  # replay: same identity
    q.quarantine("tar::b.png", "bad bytes")
    assert q.bad_count == 2 and q.ok_count == 10
    lines = [json.loads(ln) for ln in open(manifest)]
    assert [e["source"] for e in lines] == ["tar::a.png", "tar::b.png"]
    # checkpoint round-trip: bad records persist, oks reset (a resume
    # replays the stream and recounts them)
    state = q.state()
    q2 = Quarantine(max_bad_fraction=0.5, min_records=1, label="t")
    q2.restore(state)
    assert q2.bad_count == 2 and q2.ok_count == 0
    q2.quarantine("tar::a.png", "bad bytes")  # replayed: still deduped
    assert q2.bad_count == 2


# -- tar decode pool under faults (satellite) --------------------------------

def test_tar_one_corrupt_member_streamed_not_fatal_not_silent(tmp_path):
    """One corrupt member in a tar stream is quarantined: the stream
    completes with the other images, and the bad record is COUNTED
    (quarantine manifest + metrics), never silently dropped."""
    tar = _make_tar(tmp_path / "imgs.tar", n_images=10, corrupt={4})
    with PipelineTrace("tar") as tr:
        stream = stream_tar_images([tar], chunk_size=4)
        rows = sum(c.n for c in stream.chunks())
    assert rows == 9  # not fatal: the other nine images arrive
    assert stream.quarantine.bad_count == 1
    assert stream.quarantine.ok_count == 9
    (rec,) = stream.quarantine.records
    assert rec["source"].endswith("imgs.tar::img004.png")
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["counters"]["resilience.quarantine"] >= 1
    assert tr.resilience_stats.get("quarantine") == 1


@pytest.mark.parametrize("serial", [True, False])
def test_tar_one_corrupt_member_serial_and_pooled(tmp_path, monkeypatch,
                                                  serial):
    """The same guarantee under serial iteration (iter_tar_images) and
    the single-threaded decode pool."""
    tar = _make_tar(tmp_path / "imgs.tar", n_images=8, corrupt={2})
    q = Quarantine(label="t")
    if serial:
        imgs = list(iter_tar_images(tar, quarantine=q))
    else:
        monkeypatch.setenv("KEYSTONE_LOADER_THREADS", "1")
        imgs = [item for chunk in iter_decoded_chunks(
            [tar], 4, quarantine=q) for item in chunk]
    assert len(imgs) == 7
    assert q.bad_count == 1 and q.ok_count == 7
    assert q.records[0]["source"].endswith("::img002.png")


def test_tar_quarantine_budget_fails_loudly(tmp_path):
    tar = _make_tar(tmp_path / "imgs.tar", n_images=10,
                    corrupt={1, 3, 5, 7})
    q = Quarantine(max_bad_fraction=0.1, min_records=10, label="imgs")
    stream = stream_tar_images([tar], chunk_size=4, quarantine=q)
    with pytest.raises(QuarantineBudgetExceededError) as exc:
        list(stream.chunks())
    assert "imgs.tar::img" in str(exc.value)


def test_tar_transient_decode_faults_are_retried(tmp_path):
    """Seeded transient faults at the decode site: every image still
    arrives (the retry absorbed the fault) and the retries are counted
    in metrics and the trace."""
    tar = _make_tar(tmp_path / "imgs.tar", n_images=12)
    policy = RetryPolicy(max_attempts=5, backoff_s=0.001)
    plan = FaultPlan(seed=5).add("ingest.decode", rate=0.3)
    with PipelineTrace("faulty") as tr:
        with plan:
            stream = stream_tar_images([tar], chunk_size=4,
                                       retry_policy=policy)
            rows = sum(c.n for c in stream.chunks())
    assert rows == 12  # nothing lost to transient faults
    assert plan.injections("ingest.decode") > 0
    assert stream.quarantine.bad_count == 0
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["counters"]["resilience.retry"] >= plan.injections()
    assert tr.resilience_stats.get("retry", 0) >= 1
    assert tr.resilience_stats.get("fault_injected", 0) >= 1


def test_tar_transient_read_faults_are_retried(tmp_path):
    tar = _make_tar(tmp_path / "imgs.tar", n_images=6)
    policy = RetryPolicy(max_attempts=4, backoff_s=0.001)
    plan = FaultPlan(seed=2).add("ingest.read", rate=0.4)
    with plan:
        q = Quarantine(label="t")
        imgs = list(iter_tar_images(tar, quarantine=q,
                                    retry_policy=policy))
    assert len(imgs) == 6
    assert plan.injections("ingest.read") > 0


# -- staging retry + producer watchdog ---------------------------------------

def test_staging_transient_faults_retried_with_exact_results():
    """Transient device-staging failures are retried; the fit's result
    is bit-identical to a fault-free run (a retried upload re-stages the
    same chunk)."""
    X, Y = _xy()
    clean = fit_streaming(LinearMapEstimator(lam=0.1),
                          StreamingDataset.from_numpy(X, chunk_size=64), Y)
    policy = RetryPolicy(max_attempts=5, backoff_s=0.001)
    plan = FaultPlan(seed=9).add("ingest.stage", rate=0.3)
    with plan:
        faulty = fit_streaming(
            LinearMapEstimator(lam=0.1),
            StreamingDataset.from_numpy(X, chunk_size=64,
                                        retry_policy=policy), Y)
    assert plan.injections("ingest.stage") > 0
    np.testing.assert_array_equal(np.asarray(clean.weights),
                                  np.asarray(faulty.weights))


def test_staging_retry_exhaustion_fails_loudly():
    X, _ = _xy(n=128)
    policy = RetryPolicy(max_attempts=2, backoff_s=0.001)
    with FaultPlan().add("ingest.stage", rate=1.0):  # every attempt fails
        stream = StreamingDataset.from_numpy(X, chunk_size=64,
                                             retry_policy=policy)
        with pytest.raises(RetryExhaustedError, match="ingest.stage"):
            list(stream.chunks())


def test_watchdog_converts_hung_producer_to_clear_error():
    X, _ = _xy(n=256)
    plan = FaultPlan().add("ingest.produce", kind="hang", after=1,
                           count=1, delay_s=30.0)
    t0 = time.monotonic()
    with plan:
        stream = StreamingDataset.from_numpy(
            X, chunk_size=64, tag="hung", stall_timeout_s=0.5)
        with pytest.raises(IngestTimeoutError) as exc:
            list(stream.chunks())
    assert time.monotonic() - t0 < 10.0  # no indefinite block
    msg = str(exc.value)
    assert "hung" in msg and "stall_timeout_s" in msg
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["counters"]["resilience.watchdog_trip"] >= 1


def test_latency_spike_stalls_but_completes():
    """A latency spike (not a hang) shows up as ingest stall, not an
    error — the stream completes with every row."""
    X, _ = _xy(n=256)
    plan = FaultPlan().add("ingest.produce", kind="latency", after=1,
                           count=1, delay_s=0.3)
    with plan:
        stream = StreamingDataset.from_numpy(
            X, chunk_size=64, stall_timeout_s=5.0)
        rows = sum(c.n for c in stream.chunks())
    assert rows == 256
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["histograms"]["streaming.ingest_stall_s"]["max"] >= 0.2


# -- checkpoint/resume -------------------------------------------------------

@pytest.mark.parametrize("chunk_size", [48, 64, 96])
def test_kill_and_resume_matches_uninterrupted(tmp_path, chunk_size):
    """Acceptance: a streamed fit killed mid-stream (injected fault
    after chunk k) and resumed from its last checkpoint yields weights
    within 1e-5 (identical argmax) of the uninterrupted fit, across
    chunk sizes including a ragged tail."""
    X, Y = _xy(n=200)  # 200/48, 200/64, 200/96 all leave ragged tails

    def stream():
        return StreamingDataset.from_numpy(X, chunk_size=chunk_size,
                                           tag="kr")

    uninterrupted = fit_streaming(LinearMapEstimator(lam=0.1), stream(), Y)
    ckdir = str(tmp_path / f"ck{chunk_size}")
    plan = FaultPlan().add("ingest.produce", after=2, count=1,
                           error=RuntimeError)
    with plan:
        with pytest.raises(RuntimeError, match="injected fault"):
            fit_streaming(LinearMapEstimator(lam=0.1), stream(), Y,
                          checkpoint_dir=ckdir, checkpoint_every=1)
    assert os.path.exists(os.path.join(ckdir, "stream_fit.ckpt"))
    with PipelineTrace("resume") as tr:
        resumed = fit_streaming(LinearMapEstimator(lam=0.1), stream(), Y,
                                checkpoint_dir=ckdir, checkpoint_every=1)
    assert tr.resilience_stats.get("checkpoint_restore") == 1
    w_u = np.asarray(uninterrupted.weights)
    w_r = np.asarray(resumed.weights)
    assert np.abs(w_u - w_r).max() <= 1e-5 * max(np.abs(w_u).max(), 1.0)
    ds = ArrayDataset.from_numpy(X)
    pred_u = np.argmax(np.asarray(
        ensure_array(uninterrupted.apply_dataset(ds)).numpy()), axis=1)
    pred_r = np.argmax(np.asarray(
        ensure_array(resumed.apply_dataset(ds)).numpy()), axis=1)
    np.testing.assert_array_equal(pred_u, pred_r)
    # the snapshot is cleared after a successful finalize
    assert not os.path.exists(os.path.join(ckdir, "stream_fit.ckpt"))


def test_kill_and_resume_auto_solver(tmp_path):
    """The LeastSquares auto-solver resumes through the same carry."""
    X, Y = _xy(n=160, d=8)

    def stream():
        return StreamingDataset.from_numpy(X, chunk_size=48, tag="auto")

    base = fit_streaming(LeastSquaresEstimator(lam=0.1), stream(), Y)
    ckdir = str(tmp_path / "ck")
    with FaultPlan().add("ingest.produce", after=2, count=1,
                         error=RuntimeError):
        with pytest.raises(RuntimeError):
            fit_streaming(LeastSquaresEstimator(lam=0.1), stream(), Y,
                          checkpoint_dir=ckdir, checkpoint_every=1)
    resumed = fit_streaming(LeastSquaresEstimator(lam=0.1), stream(), Y,
                            checkpoint_dir=ckdir, checkpoint_every=1)
    w_b, w_r = np.asarray(base.weights), np.asarray(resumed.weights)
    assert np.abs(w_b - w_r).max() <= 1e-5 * max(np.abs(w_b).max(), 1.0)


def test_checkpoint_fingerprint_mismatch_refuses_resume(tmp_path):
    X, Y = _xy(n=160)
    ckdir = str(tmp_path / "ck")
    with FaultPlan().add("ingest.produce", after=2, count=1,
                         error=RuntimeError):
        with pytest.raises(RuntimeError):
            fit_streaming(
                LinearMapEstimator(lam=0.1),
                StreamingDataset.from_numpy(X, chunk_size=48), Y,
                checkpoint_dir=ckdir, checkpoint_every=1)
    # different lam -> different fingerprint -> refuse
    with pytest.raises(CheckpointMismatchError, match="refusing to resume"):
        fit_streaming(
            LinearMapEstimator(lam=0.5),
            StreamingDataset.from_numpy(X, chunk_size=48), Y,
            checkpoint_dir=ckdir)
    # different chunk geometry -> refuse too
    with pytest.raises(CheckpointMismatchError):
        fit_streaming(
            LinearMapEstimator(lam=0.1),
            StreamingDataset.from_numpy(X, chunk_size=96), Y,
            checkpoint_dir=ckdir)


def test_stream_checkpoint_corrupt_file_raises(tmp_path):
    ckdir = str(tmp_path / "ck")
    ck = StreamCheckpoint(ckdir)
    with open(ck.path, "wb") as f:
        f.write(b"\x80garbage not a pickle")
    with pytest.raises(CheckpointCorruptError, match="stream_fit.ckpt"):
        ck.load("anything")
    # an unrelated complete pickle is "wrong format", also corrupt
    with open(ck.path, "wb") as f:
        pickle.dump({"some": "dict"}, f)
    with pytest.raises(CheckpointCorruptError, match="format header"):
        ck.load("anything")


def test_checkpoint_persists_quarantine_state(tmp_path):
    X, Y = _xy(n=200)
    q = Quarantine(max_bad_fraction=0.5, min_records=10, label="t")
    q.quarantine("tar::bad.png", "bad")
    ckdir = str(tmp_path / "ck")
    with FaultPlan().add("ingest.produce", after=2, count=1,
                         error=RuntimeError):
        with pytest.raises(RuntimeError):
            fit_streaming(
                LinearMapEstimator(lam=0.1),
                StreamingDataset.from_numpy(X, chunk_size=48), Y,
                checkpoint_dir=ckdir, checkpoint_every=1, quarantine=q)
    q2 = Quarantine(max_bad_fraction=0.5, min_records=10, label="t")
    fit_streaming(LinearMapEstimator(lam=0.1),
                  StreamingDataset.from_numpy(X, chunk_size=48), Y,
                  checkpoint_dir=ckdir, checkpoint_every=1, quarantine=q2)
    assert q2.bad_count == 1  # restored from the snapshot
    assert q2.records[0]["source"] == "tar::bad.png"


def test_checkpoint_every_requires_dir_and_validates():
    X, Y = _xy(n=96)
    with pytest.raises(ValueError, match="requires checkpoint_dir"):
        fit_streaming(LinearMapEstimator(lam=0.1),
                      StreamingDataset.from_numpy(X, chunk_size=48), Y,
                      checkpoint_every=2)


def test_estimator_fit_forwards_stream_options(tmp_path):
    """The resilience options ride Estimator.fit / LabelEstimator.fit;
    resident fits reject them with a clear error."""
    X, Y = _xy(n=160)
    ckdir = str(tmp_path / "ck")
    model = LinearMapEstimator(lam=0.1).fit(
        StreamingDataset.from_numpy(X, chunk_size=48), Y,
        checkpoint_dir=ckdir, checkpoint_every=2)
    resident = LinearMapEstimator(lam=0.1)._fit(
        ArrayDataset.from_numpy(X), ArrayDataset.from_numpy(Y))
    assert np.abs(np.asarray(model.weights)
                  - np.asarray(resident.weights)).max() <= 1e-4
    scaler = StandardScaler().fit(
        StreamingDataset.from_numpy(X, chunk_size=48),
        checkpoint_dir=str(tmp_path / "ck2"))
    assert scaler is not None
    with pytest.raises(TypeError, match="require a StreamingDataset"):
        LinearMapEstimator(lam=0.1).fit(X, Y, checkpoint_dir=ckdir)
    with pytest.raises(TypeError, match="require a StreamingDataset"):
        StandardScaler().fit(ArrayDataset.from_numpy(X),
                             checkpoint_dir=ckdir)


# -- acceptance: mixed faults at CIFAR scale ---------------------------------

def test_streamed_fit_completes_under_mixed_faults():
    """Acceptance: seeded 10%+ transient staging faults plus one
    producer stall — the streamed fit completes with results identical
    to the fault-free run, and retry counts land in metrics and the
    PipelineTrace."""
    X, Y = _xy(n=1024, d=24, k=10, seed=3)
    clean = fit_streaming(
        LinearMapEstimator(lam=0.1),
        StreamingDataset.from_numpy(X, chunk_size=64), Y)
    policy = RetryPolicy(max_attempts=6, backoff_s=0.001)
    plan = (FaultPlan(seed=7)
            .add("ingest.stage", rate=0.1)
            .add("ingest.produce", kind="latency", after=2, count=1,
                 delay_s=0.2))
    MetricsRegistry.reset()
    with PipelineTrace("mixed-faults") as tr:
        with plan:
            model = fit_streaming(
                LinearMapEstimator(lam=0.1),
                StreamingDataset.from_numpy(
                    X, chunk_size=64, retry_policy=policy,
                    stall_timeout_s=30.0), Y)
    assert plan.injections("ingest.stage") > 0
    np.testing.assert_array_equal(np.asarray(clean.weights),
                                  np.asarray(model.weights))
    snap = MetricsRegistry.get_or_create().snapshot()
    assert snap["counters"]["resilience.retry"] >= plan.injections(
        "ingest.stage")
    assert snap["counters"]["resilience.fault_injected"] == (
        plan.injections())
    assert tr.resilience_stats.get("retry", 0) >= 1
    assert "resilience events" in tr.summary()
    # round trip keeps the resilience stream
    rt = PipelineTrace.from_json(tr.to_json())
    assert rt.resilience_stats == tr.resilience_stats
    assert rt.resilience[-1]["event"] == tr.resilience[-1]["event"]


def test_streamed_tar_fit_quarantines_and_completes(tmp_path):
    """End-to-end over the tar path: a corrupt member plus transient
    decode faults; the fit completes on the 15 good images and the
    quarantine/retry counts are visible."""
    tar = _make_tar(tmp_path / "imgs.tar", n_images=16, corrupt={5},
                    side=8)
    policy = RetryPolicy(max_attempts=5, backoff_s=0.001)
    plan = FaultPlan(seed=4).add("ingest.decode", rate=0.2)
    with PipelineTrace("tar-fit") as tr:
        with plan:
            root = stream_tar_images([tar], chunk_size=4,
                                     retry_policy=policy)
            stream = root.map_chunks(lambda ad: ArrayDataset(
                ad.data.reshape(ad.padded_n, -1), ad.n, ad.mesh,
                _already_sharded=True))
            # derived views carry the loader's quarantine, and
            # fit_streaming picks it up without being told
            assert stream.quarantine is root.quarantine
            scaler = fit_streaming(StandardScaler(), stream)
    assert np.asarray(scaler.mean).shape == (8 * 8 * 3,)
    assert root.quarantine.bad_count == 1
    assert root.quarantine.ok_count == 15
    assert tr.resilience_stats.get("quarantine") == 1
    assert tr.resilience_stats.get("retry", 0) >= 1


# -- utils/checkpoint hardening (satellite) ----------------------------------

def test_resident_labels_content_change_refuses_resume(tmp_path):
    """The fingerprint digests RESIDENT label content: resuming with
    different labels of the same shape refuses instead of silently
    folding the stale carry into new data."""
    X, Y = _xy(n=160)
    ckdir = str(tmp_path / "ck")
    with FaultPlan().add("ingest.produce", after=2, count=1,
                         error=RuntimeError):
        with pytest.raises(RuntimeError):
            fit_streaming(
                LinearMapEstimator(lam=0.1),
                StreamingDataset.from_numpy(X, chunk_size=48), Y,
                checkpoint_dir=ckdir, checkpoint_every=1)
    Y2 = Y.copy()
    Y2[0, 0] += 1.0  # same shape/dtype, different content
    with pytest.raises(CheckpointMismatchError):
        fit_streaming(
            LinearMapEstimator(lam=0.1),
            StreamingDataset.from_numpy(X, chunk_size=48), Y2,
            checkpoint_dir=ckdir)


def test_pipeline_checkpoint_corrupt_file_raises(tmp_path):
    from keystone_tpu.utils import load_pipeline, load_state

    path = str(tmp_path / "model.pkl")
    with open(path, "wb") as f:
        f.write(b"\x80\x04 truncated pickle garbage")
    with pytest.raises(CheckpointCorruptError, match="model.pkl"):
        load_pipeline(path)
    with pytest.raises(CheckpointCorruptError):
        load_state(path)
    with pytest.raises(FileNotFoundError):
        load_pipeline(str(tmp_path / "missing.pkl"))


def test_pipeline_checkpoint_wrong_kind_and_legacy(tmp_path):
    from keystone_tpu.utils import load_pipeline, load_state, save_state
    from keystone_tpu.utils.checkpoint import _FORMAT, _VERSION

    state_path = str(tmp_path / "state.pkl")
    assert save_state(state_path) == 0  # fresh env: zero entries, valid
    assert load_state(state_path) == 0
    # a state artifact is not a pipeline artifact
    with pytest.raises(CheckpointCorruptError, match="state"):
        load_pipeline(state_path)
    # future versions are refused with a clear error, not a traceback
    vpath = str(tmp_path / "future.pkl")
    with open(vpath, "wb") as f:
        pickle.dump({"format": _FORMAT, "version": _VERSION + 1,
                     "kind": "state", "payload": {}}, f)
    with pytest.raises(CheckpointCorruptError, match="version"):
        load_state(vpath)
    # legacy headerless artifacts (pre-resilience) still load
    legacy = str(tmp_path / "legacy.pkl")
    with open(legacy, "wb") as f:
        pickle.dump({}, f)
    assert load_state(legacy) == 0


def test_save_state_write_is_atomic(tmp_path, monkeypatch):
    """A crash mid-write must leave the previous artifact intact: the
    dump goes to a temp file first, then os.replace."""
    from keystone_tpu.utils import checkpoint as cp

    path = str(tmp_path / "state.pkl")
    cp.save_state(path)
    before = open(path, "rb").read()

    real_dump = pickle.dump

    def exploding_dump(obj, f, *a, **kw):
        f.write(b"partial")
        raise OSError("disk full")

    monkeypatch.setattr(cp.pickle, "dump", exploding_dump)
    with pytest.raises(OSError):
        cp.save_state(path)
    monkeypatch.setattr(cp.pickle, "dump", real_dump)
    assert open(path, "rb").read() == before  # untouched
    assert cp.load_state(path) == 0


# -- swallow-all-handler lint (satellite) ------------------------------------

def test_swallow_all_handler_lint_fires_on_offenders():
    import ast

    from keystone_tpu.analysis.diagnostics import swallow_all_handlers

    src = (
        "try:\n    x()\nexcept Exception:\n    pass\n"
        "try:\n    y()\nexcept:\n    z = 1\n"
        "try:\n    w()\nexcept ValueError:\n    pass\n"          # narrow: ok
        "try:\n    v()\nexcept Exception as e:\n    raise\n"     # re-raise: ok
        "try:\n    u()\nexcept (OSError, Exception):\n    ...\n"
    )
    hits = swallow_all_handlers(ast.parse(src))
    assert len(hits) == 3
    kinds = [what for _, what in hits]
    assert any("bare" in k for k in kinds)
    assert sum("Exception" in k for k in kinds) == 2


def test_ingest_and_workflow_tree_has_no_swallow_all_handlers():
    """The repo gate's own invariant: zero offenders in the scoped
    directories (tools/lint.py enforces this before every PR)."""
    import ast
    import pathlib

    from keystone_tpu.analysis.diagnostics import (
        SWALLOW_ALL_SCOPES,
        swallow_all_handlers,
    )

    pkg = pathlib.Path(__file__).resolve().parent.parent / "keystone_tpu"
    offenders = []
    for scope in SWALLOW_ALL_SCOPES:
        for path in sorted((pkg / scope).rglob("*.py")):
            tree = ast.parse(path.read_text())
            offenders += [(str(path), lineno, what)
                          for lineno, what in swallow_all_handlers(tree)]
    assert not offenders, offenders


# -- quarantine/label alignment helper (ISSUE 11 satellite) ------------------

def test_drop_quarantined_rows_pairs_corrupt_tar_with_full_labels(tmp_path):
    """The PR 4 footgun, closed: a corrupt-member tar SHRINKS the
    stream, so labels sized for the full member count (the natural way
    to build them — one row per tar member) misalign. The misalignment
    error now names drop_quarantined_rows; applying it makes the fit
    succeed with exactly the surviving rows."""
    from keystone_tpu.resilience import drop_quarantined_rows

    n_images, corrupt_idx = 12, {4}
    tar = _make_tar(tmp_path / "imgs.tar", n_images=n_images,
                    corrupt=corrupt_idx)
    # labels built for EVERY member, keyed the way the loader keys
    # quarantine entries: "<tar>::<member>"
    keys = [f"{tar}::img{i:03d}.png" for i in range(n_images)]
    rng = np.random.RandomState(0)
    y_full = rng.randn(n_images, 3).astype(np.float32)

    def prepare(batch):
        return np.stack([img for _, img in batch]).reshape(
            len(batch), -1).astype(np.float32)

    # pass 1: consume the stream so the quarantine fills, then prove
    # the misalignment error points at the helper
    stream = stream_tar_images([tar], chunk_size=4, prepare=prepare,
                               quarantine=Quarantine(max_bad_fraction=0.5,
                                                     min_records=1))
    with pytest.raises(ValueError, match="drop_quarantined_rows"):
        fit_streaming(LinearMapEstimator(lam=0.1), stream, y_full,
                      quarantine=stream.quarantine)
    assert stream.quarantine.bad_count == len(corrupt_idx)

    # pass 2: drop the quarantined rows -> aligned fit succeeds
    y_aligned = drop_quarantined_rows(y_full, keys, stream.quarantine)
    assert y_aligned.shape[0] == n_images - len(corrupt_idx)
    stream2 = stream_tar_images([tar], chunk_size=4, prepare=prepare,
                                quarantine=stream.quarantine)
    model = fit_streaming(LinearMapEstimator(lam=0.1), stream2, y_aligned,
                          quarantine=stream2.quarantine)
    assert np.isfinite(np.asarray(model.weights)).all()


def test_drop_quarantined_rows_validates_key_count():
    from keystone_tpu.resilience import drop_quarantined_rows

    q = Quarantine()
    with pytest.raises(ValueError, match="record keys"):
        drop_quarantined_rows(np.zeros((4, 2)), ["a", "b"], q)


# -- RetryPolicy repr (ISSUE 11 satellite) -----------------------------------

def test_retry_policy_repr_names_the_policy_in_force():
    """Post-mortems and logs print the policy; the repr must name the
    effective attempts/backoff/timeout instead of an address."""
    r = repr(RetryPolicy(max_attempts=5, backoff_s=0.1, multiplier=3.0,
                         max_backoff_s=4.0, jitter=0.25,
                         attempt_timeout_s=2.5))
    assert "attempts=5" in r and "0.1s*3^k<=4s" in r
    assert "jitter=0.25" in r and "attempt_timeout=2.5s" in r
    assert "0x" not in r  # no memory addresses
    assert "attempt_timeout=none" in repr(RetryPolicy())


def test_retry_exhausted_postmortem_names_policy(tmp_path):
    """The retry-exhausted post-mortem context carries the one-line
    policy identity."""
    policy = RetryPolicy(max_attempts=2, backoff_s=0.001)

    def always_fails():
        raise TransientError("nope")

    with pytest.raises(RetryExhaustedError) as exc_info:
        policy.call(always_fails, site="t")
    pm = getattr(exc_info.value, "postmortem_path", None)
    if pm:  # postmortem dumping enabled in this environment
        blob = json.load(open(pm))
        assert "attempts=2" in blob.get("context", {}).get("policy", "")
