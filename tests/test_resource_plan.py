"""Static HBM resource planner (keystone_tpu/analysis/resources):
device-free plans over every bundled app, budget gating through
``check --budget`` / ``Pipeline.check(hbm_budget=...)`` (exit 2 /
``hbm-budget`` diagnostic BEFORE any device work), and the
plan-vs-measured parity contract on streamed fits — the static plan
must bound the runtime residency ledger's peak from above, tightly."""
import time

import jax
import numpy as np
import pytest

from keystone_tpu.__main__ import _parse_bytes, check_main
from keystone_tpu.analysis import plan_graph
from keystone_tpu.analysis.resources import (
    ResourceEffect,
    StreamGeometry,
    element_nbytes,
    gram_carry_nbytes,
    padded_rows,
)
from keystone_tpu.nodes.learning import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
)
from keystone_tpu.observability import PipelineTrace
from keystone_tpu.parallel.streaming import StreamingDataset, fit_streaming
from keystone_tpu.pipelines import CHECK_APPS, resolve_check_app


# -- plan resolution over the registry --------------------------------------

@pytest.mark.parametrize("app", sorted(CHECK_APPS))
def test_every_app_resolves_a_plan(app, mesh8):
    target = CHECK_APPS[app]()
    report = target.pipeline.check(target.input_spec, name=target.name)
    plan = report.plan
    assert plan is not None
    assert plan.fit_peak_nbytes >= 0.0
    assert plan.entries  # one entry per planned node
    # the JSON form carries the plan alongside the diagnostics
    blob = report.to_dict()
    assert blob["plan"]["fit_peak_nbytes"] == plan.fit_peak_nbytes


def test_array_app_plans_charge_real_bytes(mesh8):
    # dense apps have fully resolved byte counts: the fit peak must at
    # least cover the training dataset itself
    target = resolve_check_app("mnist.random_fft")()
    report = target.pipeline.check(target.input_spec, name="mnist")
    plan = report.plan
    assert not plan.unresolved, plan.unresolved
    train_bytes = padded_rows(60_000, 8) * 784 * 4
    assert plan.fit_peak_nbytes >= train_bytes
    # fitted models persist (apply-path residency) and the per-item
    # activation bound is known for the serving path
    assert plan.model_nbytes > 0
    assert plan.apply_item_nbytes > 0


def test_plan_is_device_free(mesh8):
    before = {id(a) for a in jax.live_arrays()}
    target = resolve_check_app("mnist.random_fft")()
    report = target.pipeline.check(target.input_spec,
                                   hbm_budget=float(1 << 40))
    assert report.ok and report.plan is not None
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert not new, [(a.shape, a.dtype) for a in new[:5]]


# -- budget gating ----------------------------------------------------------

def test_hbm_budget_diagnostic_fires(mesh8):
    target = resolve_check_app("mnist.random_fft")()
    report = target.pipeline.check(target.input_spec,
                                   hbm_budget=float(1 << 20))  # 1 MiB
    codes = {d.code for d in report.diagnostics}
    assert "hbm-budget" in codes
    over = [d for d in report.diagnostics if d.code == "hbm-budget"]
    assert over[0].severity == "error"
    assert over[0].node_id == report.plan.peak_node


def test_check_cli_budget_exit_codes(mesh8, capsys):
    # over budget -> exit 2, predicted before any device work
    before = {id(a) for a in jax.live_arrays()}
    rc = check_main(["mnist.random_fft", "--budget", "1MiB"])
    assert rc == 2
    assert "OVER BUDGET" in capsys.readouterr().out
    assert not [a for a in jax.live_arrays() if id(a) not in before]
    # generous budget -> clean
    assert check_main(["mnist.random_fft", "--budget", "1TiB"]) == 0
    # malformed budget -> usage error
    assert check_main(["mnist.random_fft", "--budget", "much"]) == 2


def test_check_budget_verifies_per_host_charge(mesh8, capsys):
    """ISSUE 18 acceptance: ``check --budget --shards N`` verifies the
    per-host charge device-free. ``data_shards`` reaches the plan (the
    pad-to-shard width changes the charged rows), the CLI accepts the
    spelling, and the serving admission arithmetic derived FROM that
    plan divides the shardable fitted state across the shard count."""
    from keystone_tpu.analysis.resources import (
        serving_residency_nbytes,
        sharded_apply_nbytes,
    )

    target = resolve_check_app("mnist.random_fft")()
    # a 7-shard world pads 60000 rows to 60004: the plumbed-through
    # width is visible in the plan's charged bytes
    r7 = target.pipeline.check(target.input_spec, data_shards=7)
    r8 = target.pipeline.check(target.input_spec, data_shards=8)
    assert r7.plan.fit_peak_nbytes > r8.plan.fit_peak_nbytes
    # the per-host serving charge from the SAME device-free plan: a
    # fitted block model's shardable state divides across the shards,
    # so the 8-shard charge undercuts the replicated one
    X = np.random.RandomState(0).rand(64, 96).astype(np.float32)
    Y = np.random.RandomState(1).rand(64, 8).astype(np.float32)
    fitted = BlockLeastSquaresEstimator(32, num_iter=1, lam=1e-3)\
        .with_data(StreamingDataset.from_numpy(X, chunk_size=32)
                   .materialize(),
                   StreamingDataset.from_numpy(Y, chunk_size=32)
                   .materialize()).fit()
    report = fitted.check(jax.ShapeDtypeStruct((96,), np.float32))
    graph = fitted.to_pipeline().graph
    from keystone_tpu.analysis.resources import fitted_model_nbytes

    model_b = fitted_model_nbytes(graph)
    shardable, gather = sharded_apply_nbytes(graph)
    assert shardable > 0 and 0 < gather < shardable
    charge1 = serving_residency_nbytes(model_b, report.plan, 16)
    charge8 = serving_residency_nbytes(
        model_b, report.plan, 16, data_shards=8,
        shardable_nbytes=shardable, gather_nbytes=gather)
    assert charge8 is not None and charge1 is not None
    assert charge8 < charge1
    assert charge8 == pytest.approx(
        model_b - shardable + shardable / 8 + gather
        + 2 * report.plan.apply_item_nbytes)  # ceil(16/8) rows
    # the CLI spelling: --shards plumbs through with --budget
    assert check_main(["mnist.random_fft", "--budget", "1TiB",
                       "--shards", "8"]) == 0
    assert check_main(["mnist.random_fft", "--budget", "1MiB",
                       "--shards", "8"]) == 2
    capsys.readouterr()


def test_parse_bytes_spellings():
    assert _parse_bytes("1024") == 1024
    assert _parse_bytes("4k") == 4096
    assert _parse_bytes("512MiB") == 512 * (1 << 20)
    assert _parse_bytes("16GiB") == 16 * (1 << 30)
    assert _parse_bytes("2g") == 2 * (1 << 30)
    with pytest.raises(ValueError):
        _parse_bytes("sixteen")


# -- effect derivation units -------------------------------------------------

def test_element_nbytes_and_helpers():
    el = {"x": jax.ShapeDtypeStruct((32, 32, 3), np.uint8),
          "y": jax.ShapeDtypeStruct((10,), np.float32)}
    assert element_nbytes(el) == 32 * 32 * 3 + 40
    from keystone_tpu.analysis.spec import DatasetSpec, Unknown

    assert element_nbytes(Unknown("host")) is None
    specs = [DatasetSpec(jax.ShapeDtypeStruct((128,), np.float32), n=64),
             DatasetSpec(jax.ShapeDtypeStruct((10,), np.float32), n=64)]
    assert gram_carry_nbytes(specs) == 4 * (128 * 128 + 128 * 10 + 138)


def test_stream_geometry_plan_math():
    # u8 wire, f32 compute: depth*w + 4w + w transient
    g = StreamGeometry(chunk_rows=256, prefetch_depth=2,
                       wire_row_nbytes=3072.0, work_row_nbytes=12288.0,
                       cast=True)
    w = 256 * 3072.0
    assert g.plan_nbytes() == 2 * w + 4 * w + w
    # no cast: the documented (depth + 1) * chunk budget unit
    g2 = StreamGeometry(chunk_rows=256, prefetch_depth=2,
                        wire_row_nbytes=3072.0, work_row_nbytes=3072.0)
    assert g2.plan_nbytes() == 3 * w


def test_liveness_releases_dead_values(mesh8):
    # source -> a -> b chain over a known-n dataset: at b's step the
    # source is already released (its last consumer was a), so the peak
    # is the widest CONSECUTIVE pair, not the sum of every node
    from keystone_tpu.analysis import spec_dataset
    from keystone_tpu.workflow.transformer import LambdaTransformer

    n = 800
    pipe = (LambdaTransformer(lambda x: x * 2.0, "a")
            >> LambdaTransformer(lambda x: x.sum(axis=-1), "b"))
    report = pipe.check(spec_dataset((64,), np.float32, n=n).spec)
    wide = padded_rows(n, 8) * 64 * 4
    # peak = input + same-width intermediate; b's scalar output and the
    # released input never stack on top
    assert report.plan.fit_peak_nbytes == pytest.approx(2 * wide)


# -- estimator carry accounting ---------------------------------------------

def test_estimator_carry_rides_the_plan(mesh8):
    from keystone_tpu.analysis import spec_dataset
    from keystone_tpu.nodes.util import (
        ClassLabelIndicatorsFromIntLabels,
        MaxClassifier,
    )

    d, n, k = 256, 4096, 10
    train = spec_dataset((d,), np.float32, n=n)
    labels = ClassLabelIndicatorsFromIntLabels(k)(
        spec_dataset((), np.int32, n=n))
    pipe = LinearMapEstimator(0.0).with_data(train, labels) \
        >> MaxClassifier()
    report = pipe.check(jax.ShapeDtypeStruct((d,), np.float32))
    est = [e for e in report.plan.entries
           if e["operator"] == "LinearMapEstimator"]
    assert len(est) == 1
    assert est[0]["carry_nbytes"] == 4 * (d * d + d * k + d + k)
    assert est[0]["out_nbytes"] == 4 * (d * k + d + k)
    assert report.plan.model_nbytes >= est[0]["out_nbytes"]


# -- Pallas kernel workspace charges (PR 13 satellite) -----------------------

def test_fv_apply_workspace_rides_the_plan(mesh8):
    """The FV apply's kernel/fallback workspace is charged at the
    Delegate node: on CPU (no Pallas dispatch) that is the (nDesc, K)
    posterior matrix the split fallback materializes, scaled by the
    padded batch inside the one batched program."""
    from keystone_tpu.analysis import spec_dataset
    from keystone_tpu.analysis.resources import fv_apply_transient_nbytes
    from keystone_tpu.nodes.images.fisher_vector import (
        GMMFisherVectorEstimator,
    )

    d, nd, k, n = 64, 200, 33, 32
    train = spec_dataset((d, nd), np.float32, n=n)
    pipe = GMMFisherVectorEstimator(k).with_data(train)
    report = pipe.check(jax.ShapeDtypeStruct((d, nd), np.float32))
    delegates = [e for e in report.plan.entries
                 if e["operator"] == "Delegate"
                 and "kernel workspace" in e["note"]]
    assert delegates, report.plan.entries
    per_item = fv_apply_transient_nbytes(d, k, nd)
    assert per_item == 4.0 * nd * k  # CPU: the fallback's q matrix
    # the apply-path source has unknown n -> charged once per item
    assert delegates[0]["transient_nbytes"] == per_item


def test_sift_band_constants_ride_the_plan(mesh8):
    """A SIFT node charges its per-config band operators as a
    transient, once."""
    from keystone_tpu.analysis.resources import sift_band_operator_nbytes
    from keystone_tpu.nodes.images.extractors import SIFTExtractor

    h, w = 64, 80
    node = SIFTExtractor(step=8, bin_size=4, num_scales=2, scale_step=1)
    report = node.check(jax.ShapeDtypeStruct((h, w), np.float32))
    entries = [e for e in report.plan.entries
               if e["operator"] == "SIFTExtractor"]
    assert len(entries) == 1
    want = sift_band_operator_nbytes(h, w, 8, 4, 2, 1)
    assert want > 0
    assert entries[0]["transient_nbytes"] == want


# -- streamed plan vs measured ledger (satellite: parity test) ---------------

def _slow(ad):
    time.sleep(0.01)  # let the producer saturate the double buffer
    return ad


def test_streamed_plan_bounds_measured_peak(mesh8):
    """Streamed CIFAR-shaped fit under an asserted budget: the static
    plan must bound the measured ledger peak from above (hard
    guarantee: the slot semaphore can never stage past the plan) and,
    with a saturated buffer, from below within 1.5x (the acceptance
    tolerance — the plan is tight, not just safe)."""
    n, chunk, depth = 2048, 256, 2
    rng = np.random.RandomState(0)
    imgs = (rng.rand(n, 32 * 32 * 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, n)
    L = np.eye(10, dtype=np.float32)[y]
    stream = StreamingDataset.from_numpy(
        imgs, chunk_size=chunk, prefetch_depth=depth,
        compute_dtype=np.float32, tag="plan-parity")
    plan = stream.static_plan_nbytes()
    # u8 wire + f32 working copy + u8 transient during the cast
    w = chunk * 32 * 32 * 3
    assert plan == pytest.approx(depth * w + 4 * w + w)
    with PipelineTrace("parity") as tr:
        fit_streaming(BlockLeastSquaresEstimator(1024, 1, lam=0.1),
                      stream.map_chunks(_slow), L, hbm_budget=plan)
    measured = stream.peak_device_nbytes
    assert 0 < measured <= plan
    assert plan <= 1.5 * measured, (plan, measured)
    # the trace closed the loop: plan recorded next to the measurement
    [entry] = tr.streamed_fits
    assert entry["static_plan_nbytes"] == plan
    assert entry["peak_device_nbytes"] == measured
    assert "plan/measured" in tr.summary()
    # round-trips with the artifact
    from keystone_tpu.observability import PipelineTrace as PT

    assert PT.from_json(tr.to_json()).streamed_fits == [entry]


def test_static_budget_rejects_before_any_staging(mesh8):
    """Over-budget geometry dies on the STATIC check: no chunk is ever
    decoded or staged (the source would record the attempt)."""
    pulls = []

    def source():
        pulls.append(1)
        yield {"x": np.zeros((64, 8), np.float32)}

    stream = StreamingDataset.from_chunks(source, chunk_size=64)
    stream._element_probe = lambda: {
        "x": jax.ShapeDtypeStruct((8,), np.float32)}
    with pytest.raises(MemoryError, match="before any chunk"):
        fit_streaming(_Scaler(), stream, hbm_budget=64.0)
    assert not pulls  # rejected device-free, source untouched


def _Scaler():
    from keystone_tpu.nodes.stats import StandardScaler

    return StandardScaler()


def test_derived_view_shares_root_plan(mesh8):
    X = np.random.RandomState(0).rand(512, 16).astype(np.float32)
    stream = StreamingDataset.from_numpy(X, chunk_size=64,
                                         prefetch_depth=2)
    view = stream.map_chunks(lambda ad: ad)
    assert view.static_plan_nbytes() == stream.static_plan_nbytes()
    assert stream.static_plan_nbytes() == 3 * 64 * 16 * 4


def test_opaque_stream_has_no_plan_but_runtime_budget_holds(mesh8):
    def source():
        yield np.zeros((64, 8), np.float32)

    stream = StreamingDataset.from_chunks(source, chunk_size=64)
    assert stream.static_plan_nbytes() is None
    with pytest.raises(MemoryError, match="HBM budget"):
        fit_streaming(_Scaler(), stream, hbm_budget=16.0)


# -- graph-level streaming plan ---------------------------------------------

def test_plan_charges_stream_not_logical_size(mesh8):
    """A streamed training input charges its residency bound — depth+1
    chunks — not n * element (the whole point of streaming)."""
    chunk = 128
    X = np.zeros((256, 64), np.float32)  # only shapes matter
    stream = StreamingDataset.from_numpy(X, chunk_size=chunk)
    pipe = _Scaler().with_data(stream)
    report = pipe.to_pipeline().check(
        jax.ShapeDtypeStruct((64,), np.float32))
    ds_entries = [e for e in report.plan.entries
                  if e["operator"] == "Dataset"]
    assert len(ds_entries) == 1
    assert ds_entries[0]["out_nbytes"] == 3 * 128 * 64 * 4
    assert report.plan.fit_peak_nbytes < 64 * 64 * 4 * 100_000
