"""The cell ``cifar_refit`` (ISSUE 30; its per-layer entries listed by
ISSUE 32): what the manifest holds of it, the count
``counts/conv_rectify_pool.py`` against a hand count, each of its
readers on a hand-built run whose answer is known, the seeded images and
their binary records, the configuration's file, and the cell's
rehearsal and controls at the rehearsal size. (Its fault, half of the
training rows left out of the loader it reads with, is a file of
``tests/benchmarks/faults/`` and runs from
``test_bench_rehearsal_cifar_refit.py`` as every cell's does from its.)
"""
import json
import os
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

import manifest_checks
import rehearsals
from benchmarks import xplane
from benchmarks.harness import Run, load_json, load_module, load_peaks
from benchmarks.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = manifest_checks.load_manifest()
CONFIG = load_json(os.path.join(
    ROOT, "benchmarks", "configs", "cifar_random_patch_10k.json"))
# accepted metrics whose readers find something to read in the cell: their
# ``workloads`` gained it (five in PR 30, the three host readers in PR 32)
WIDENED = ["loader_s.setup", "to_device_s.refit", "dag_host_s.refit",
           "device_idle_pct.refit", "hbm_peak_gib.refit",
           "optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit"]
# the cell's own readers and the layer each is a metric of
LAYERS = {"conv_dev_ms.cifar": "featurize kernels",
          "conv_roofline.cifar": "featurize kernels",
          "stream_solve_dev_ms.cifar": "solve",
          "stream_solve_roofline.cifar": "solve",
          "learn_filters_host_s.cifar": "featurize kernels",
          "blocks_generated.cifar": "featurize kernels"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the manifest ---------------------------------------------------------------

def manifest_holds(manifest):
    """What this file relies on in ``BENCHMARK.json``: looked up by name,
    held by membership and relative order (``manifest_checks``)."""
    manifest_checks.cell_is_held(
        manifest, cell="cifar_refit", config="cifar_random_patch_10k",
        traffic="fit_in_memory", chips=1, reduced=["env"],
        configs_before=["mnist_random_fft_32", "timit_50x4096"],
        cells_before=["mnist_refit", "timit_refit"],
        per_layer=WIDENED + list(LAYERS),
        end_to_end={"refit_items_per_s": 0.029, "setup_s": 0.1})
    source = manifest_checks.named(
        manifest["configs"], "cifar_random_patch_10k")["source"]
    assert "RandomPatchCifar.scala" in source
    assert "--numFilters 10000 --lambda 3000" in source
    assert manifest["run_seconds"] == 40


def test_the_manifest_holds_the_configuration_the_cell_and_its_readers():
    manifest_holds(MANIFEST)
    assert len([m for m in MANIFEST["per_layer"]
                if "cifar_refit" in m["workloads"]]) >= 14


def test_the_cells_own_entries_say_their_layer_and_double_no_reader():
    manifest_checks.own_entries_are_held(MANIFEST, LAYERS, ".cifar")


def test_the_configuration_states_the_documented_widths_uncut():
    assert CONFIG["architecture"] is None
    documented = {"num_filters": 10000, "lambda": 3000.0, "patch_size": 6,
                  "patch_steps": 1, "pool_size": 14, "pool_stride": 13,
                  "alpha": 0.25, "whitening_epsilon": 0.1,
                  "whitener_patches": 100000, "block_size": 4096,
                  "num_epochs": 1, "num_classes": 10, "image_size": 32,
                  "train_rows": 50000, "test_rows": 10000}
    assert {k: CONFIG[k] for k in documented} == documented
    assert list(CONFIG["reduced_why"]) == ["env"]
    shape = CONFIG["solve_shape"]
    assert (shape["rows"], shape["test_rows"]) == (
        CONFIG["train_rows"], CONFIG["test_rows"])
    assert shape["blocks"] == -(-10000 // CONFIG["filters_a_block"]) == 20
    assert shape["last_block"] == 8 * (10000 - 19 * 512) == 2176
    assert shape["positions"] == 27 * 27 and shape["patch_dim"] == 108
    # every width in 80,000 columns: no chip holds them for these rows
    assert 4 * CONFIG["train_rows"] * 80000 > 0.5 * 16e9
    assert "device_memory_bytes" not in {k for k in CONFIG if k != "rehearsal"}
    small = CONFIG["rehearsal"]
    columns = 8 * small["num_filters"]
    assert 4 * small["train_rows"] * columns > 0.5 * small[
        "device_memory_bytes"]
    assert small["block_size"] == 8 * small["filters_a_block"]
    for real, cfg in ((CONFIG["real_fit"], CONFIG), (small["real_fit"],
                                                     {**CONFIG, **small})):
        blocks = -(-cfg["num_filters"] // cfg["filters_a_block"])
        assert real["stream_fits"] == 1 and real["materialised_fits"] == 0
        # bounds, not a number: at least a sweep that makes a block once
        # a pass and the test rows' blockwise apply (the fit answers for
        # the rows it was fitted on); at most a factor sweep of its own
        # besides, and an apply of the training rows too
        assert real["blocks_generated_min"] == blocks * 1 + blocks
        assert real["blocks_generated_max"] == blocks * 2 + 2 * blocks
    assert (CONFIG["real_fit"]["blocks_generated_min"],
            CONFIG["real_fit"]["blocks_generated_max"]) == (40, 80)
    assert CONFIG["real_fit"]["maker"] == ["pallas"]
    for key in ("limits", "limits_why", "assumed", "deployment", "control",
                "guarantees"):
        assert CONFIG[key]
    assert set(CONFIG["limits"]) == set(small["limits"]) == {
        "filters_gap", "features_gap", "weights_gap", "weights_gap_ratio",
        "test_scores_gap", "test_scores_gap_ratio", "train_error_gap",
        "test_error_gap"}
    assert CONFIG["control"]["env"] == {
        "KEYSTONE_SOLVER_PRECISION": "high",
        "BENCH_FEATURE_CONTROL": "bf16_output"}


# -- the count ------------------------------------------------------------------

@pytest.fixture(scope="module")
def counts():
    return load_module("counts", "conv_rectify_pool")


def test_counts_against_a_hand_count_at_a_tiny_shape(counts):
    # 10 + 4 images, 6 filters in blocks of 4, 9 positions, 5-deep
    # patches, 4 pools, 2 epochs: (2 * 10 + 4) = 24 images convolved
    got = counts.fit_counts(10, 4, 6, 9, 5, 4, 2, filters_a_block=4,
                            image_floats=7)
    assert got == {
        "product_flops": 2.0 * 24 * 9 * 5 * 6,
        "elementwise_ops": 9.0 * 24 * 9 * 6,
        # the image once a block of filters (2 blocks x 7 floats), the
        # pooled features once (4 pools x 2 halves x 6 filters)
        "bytes": 4 * 24 * (2 * 7 + 48.0),
    }
    assert counts.generation_flops(1, 10000, 729, 108) == 2 * 729 * 108 * 1e4


def test_counts_at_the_cell_size(counts):
    shape = CONFIG["solve_shape"]
    args = (shape["rows"], shape["test_rows"], shape["filters"],
            shape["positions"], shape["patch_dim"], shape["pools"],
            shape["epochs"])
    got = counts.fit_counts(*args)
    images = shape["rows"] + shape["test_rows"]
    assert got["product_flops"] == 1.57464e9 * images   # 1.575 GFLOP an image
    seconds, bound = counts.roofline_seconds(
        load_peaks("TPU v5 lite"), *args, precision=shape["conv_precision"])
    assert bound == "compute"
    assert seconds == pytest.approx(got["product_flops"] / 197e12, rel=1e-12)
    # one bfloat16 pass: a sixth of what the solver's precision would cost
    high, _ = counts.roofline_seconds(PEAKS, *args, precision="highest")
    assert high == pytest.approx(6 * seconds)


# -- the readers ------------------------------------------------------------------

def make_run(tmp_path, trace_data=None, fits=2, cfg=None, peaks=PEAKS):
    said = []
    run = Run(cell={"name": "cifar_refit", "config": "cifar_random_patch_10k"},
              cfg=dict(CONFIG if cfg is None else cfg), traffic={}, seed=0,
              seconds=1.0, trace=True, rehearsal=False, control=False,
              workdir=str(tmp_path), say=said.append, spans=Spans(),
              peaks=peaks)
    run.said = said
    run.trace_data = trace_data
    if fits is not None:
        run.facts["fits"] = fits
    return run


def hand_trace():
    """A window of 20 s; two fits, each a factor sweep of 3 s (two blocks:
    a maker loop of 1.0 s holding two kernel calls, then 0.5 s of Gram and
    factor inside a loop of its own), an epoch sweep of 2.4 s (maker
    loops of 1.0 s, 0.2 s of step) and an apply of 1.2 s whose rows fit
    one batch (the kernel call of 0.5 s is the whole maker, twice, inside
    a scan that holds another loop)."""
    s = 1e9
    modules, ops = [], []
    for t0 in (0.0, 10.0):
        modules += [("jit__stream_factor", (t0 + 0.5) * s, (t0 + 3.5) * s),
                    ("jit__stream_epochs", (t0 + 3.5) * s, (t0 + 5.9) * s),
                    ("jit__stream_apply", (t0 + 6.0) * s, (t0 + 7.2) * s),
                    ("jit_evaluate", (t0 + 7.2) * s, (t0 + 7.3) * s)]
        ops.append(("while.3", (t0 + 0.5) * s, (t0 + 3.5) * s))   # blocks
        for b in range(2):
            at = t0 + 0.5 + 1.5 * b
            ops.append(("while.4", at * s, (at + 1.0) * s))       # row batches
            for i in range(2):
                ops.append(("fusion.9", (at + 0.5 * i) * s,
                            (at + 0.5 * i + 0.2) * s))            # im2col
                ops.append(("fused_cifar_featurize.7",
                            (at + 0.5 * i + 0.2) * s, (at + 0.5 * i + 0.5) * s))
            ops.append(("while.5", (at + 1.0) * s, (at + 1.5) * s))   # blocks
            ops.append(("fusion.11", (at + 1.0) * s, (at + 1.5) * s))
        ops.append(("while.55", (t0 + 3.5) * s, (t0 + 5.9) * s))
        for b in range(2):
            at = t0 + 3.5 + 1.2 * b
            ops.append(("while.57", at * s, (at + 1.0) * s))
            ops.append(("fused_cifar_featurize.7", (at + 0.1) * s,
                        (at + 0.9) * s))
            ops.append(("fusion.20", (at + 1.0) * s, (at + 1.2) * s))
        ops.append(("while.42", (t0 + 6.0) * s, (t0 + 7.2) * s))  # groups
        for b in range(2):
            at = t0 + 6.0 + 0.6 * b
            ops.append(("fused_cifar_featurize.7", at * s, (at + 0.5) * s))
            ops.append(("while.44", (at + 0.5) * s, (at + 0.6) * s))
            ops.append(("fusion.30", (at + 0.5) * s, (at + 0.6) * s))
        ops.append(("fusion.40", (t0 + 7.2) * s, (t0 + 7.3) * s))
    return xplane.Trace([xplane.DeviceTrace(0, modules, ops)],
                        [("window", 0.0, 20 * s)])


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_maker_is_the_smallest_loop_around_each_kernel_call():
    loops = load_module("layers", "_maker_loops")
    events = loops.maker_events(hand_trace())
    assert len(events) == 2 * (2 + 2 + 2)
    assert [e[0] for e in events[:6]] == [
        "jit__stream_factor"] * 2 + ["jit__stream_epochs"] * 2 + [
        "jit__stream_apply"] * 2
    # the row-batch loop, not the scan over blocks around it; the bare
    # call where there is no loop
    assert [round((e[2] - e[1]) / 1e9, 6) for e in events[:6]] == [
        1.0, 1.0, 1.0, 1.0, 0.5, 0.5]
    assert loops.maker_events(None) == []


def test_device_readers_on_a_hand_built_trace(tmp_path, counts):
    run = make_run(tmp_path, hand_trace())
    # a fit: 2 x 1.0 + 2 x 1.0 + 2 x 0.5 = 5 s in the maker
    assert read("conv_dev_ms.cifar", run) == pytest.approx(5000.0)
    # the two sweeps take 3.0 + 2.4 s, 4 s of it the maker's
    assert read("stream_solve_dev_ms.cifar", run) == pytest.approx(1400.0)
    shape = CONFIG["solve_shape"]
    least, _ = counts.roofline_seconds(
        PEAKS, shape["rows"], shape["test_rows"], 10000, 729, 108, 4, 1)
    assert read("conv_roofline.cifar", run) == pytest.approx(
        100 * least / 5.0)
    bcd = load_module("counts", "streamed_bcd")
    flops = sum(bcd.fit_flops(shape["rows"], 0, 4096, 19, 10, 1).values()) + sum(
        bcd.fit_flops(shape["rows"], 0, 2176, 1, 10, 1).values())
    assert read("stream_solve_roofline.cifar", run) == pytest.approx(
        100 * (6 * flops / 197e12) / 1.4)
    assert 0 < read("stream_solve_roofline.cifar", run) < 100
    assert read("device_idle_pct.refit", run) == pytest.approx(
        100 * (20 - 2 * 6.7) / 20)


def test_device_readers_find_nothing_without_the_kernel(tmp_path):
    s = 1e9
    mods = [("jit__stream_factor", 0.0, 2 * s), ("jit__stream_epochs",
                                                2 * s, 4 * s)]
    timit = xplane.Trace([xplane.DeviceTrace(
        0, mods, [("while.3", 0.0, 2 * s), ("fusion.1", 0.0, 1 * s)])],
        [("window", 0.0, 4 * s)])
    names = ("conv_dev_ms.cifar", "conv_roofline.cifar",
             "stream_solve_dev_ms.cifar", "stream_solve_roofline.cifar")
    for trace_data in (None, timit):
        run = make_run(tmp_path, trace_data)
        assert [read(n, run) for n in names] == [None] * 4
    assert [read(n, make_run(tmp_path, hand_trace(), fits=None))
            for n in names] == [None] * 4
    no_peaks = make_run(tmp_path, hand_trace(), peaks=None)
    assert read("conv_roofline.cifar", no_peaks) is None
    assert read("stream_solve_roofline.cifar", no_peaks) is None


def ring_span(cat, name, start, dur, args=None, tid=None):
    return types.SimpleNamespace(
        ph="X", cat=cat, name=name, start_s=start, dur_s=dur, args=args,
        tid=threading.main_thread().ident if tid is None else tid)


def test_host_and_counter_readers(tmp_path, monkeypatch):
    from keystone_tpu.observability import timeline

    holder = types.SimpleNamespace(items=[], lost=0)
    fake = types.SimpleNamespace(spans=lambda: list(holder.items),
                                 dropped=lambda: holder.lost)
    monkeypatch.setattr(timeline, "flight_recorder", lambda: fake)
    run = make_run(tmp_path)
    assert read("learn_filters_host_s.cifar", run) is None   # no fit spans
    run.spans.records += [("fit", 10.0, 14.0), ("fit", 15.0, 19.0)]
    holder.items = [
        ring_span("featurize", "learn_filters", 9.0, 2.0),    # warming fit
        ring_span("featurize", "learn_filters", 10.01, 1.2),
        ring_span("solve", "fit:BlockLeastSquaresEstimator", 11.3, 0.01),
        ring_span("featurize", "learn_filters", 15.01, 1.4),
        ring_span("featurize", "learn_filters", 15.5, 9.0, tid=-1),
    ]
    assert read("learn_filters_host_s.cifar", run) == pytest.approx(1.3)
    holder.items = [ring_span("solve", "fit:X", 11.3, 0.01)]
    assert read("learn_filters_host_s.cifar", run) is None    # no such span

    # every cell's three host readers, on this cell's run
    from keystone_tpu.observability.metrics import MetricsRegistry

    holder.items = [
        ring_span("dag", "optimize", 10.1, 0.04),
        ring_span("dag", "optimize", 15.1, 0.06),
        ring_span("dag", "optimize", 14.5, 0.5),              # between fits
        ring_span("wait", "d2h", 11.0, 2.0),
        ring_span("wait", "block", 16.0, 3.0),
        ring_span("ingest", "h2d", 10.0, 0.01, args={"nbytes": 150e6}),
        ring_span("ingest", "h2d", 15.0, 0.01, args={"nbytes": 250e6}),
        ring_span("solve", "fit:X", 11.3, 0.01),
    ]
    MetricsRegistry.get_or_create().counter("ingest.h2d_bytes").inc(400e6)
    assert read("optimize_host_s.refit", run) == pytest.approx(0.05)
    assert read("host_wait_s.refit", run) == pytest.approx(2.5)
    assert read("h2d_mb.refit", run) == pytest.approx(200.0)
    holder.items = [ring_span("dag", "optimize", 10.1, 0.04)]
    assert read("optimize_host_s.refit", run) is None   # a parent's ring

    job = load_module("configs", "cifar_random_patch_10k")
    monkeypatch.setattr(job, "FIT_COUNTS", [
        {"blocks_generated": 99.0}, {"blocks_generated": 80.0},
        {"blocks_generated": 60.0}])
    assert read("blocks_generated.cifar", run) == pytest.approx(70.0)
    monkeypatch.setattr(job, "FIT_COUNTS", [{"blocks_generated": 80.0}])
    assert read("blocks_generated.cifar", run) is None        # fewer than fits


# -- the data ---------------------------------------------------------------------

def test_images_are_seeded_and_read_back_through_the_loader(tmp_path):
    from keystone_tpu.loaders.cifar_loader import load_cifar_numpy

    images = load_module("datagen", "cifar_images")
    (train, labels), (test, test_labels) = images.make_images(
        64, 32, 2 ** 31 + 11)
    again = images.make_images(64, 32, 2 ** 31 + 11)
    other = images.make_images(64, 32, 12)
    assert np.array_equal(train, again[0][0]) and not np.array_equal(
        train, other[0][0])
    assert train.shape == (64, 32, 32, 3) and train.dtype == np.uint8
    assert test.shape == (32, 32, 32, 3) and set(labels) <= set(range(10))
    # every pixel has noise of its own, and the bytes are not clipped away
    assert 20 < train.astype(np.float64).std() < 90
    assert 0.0 < (train == 0).mean() < 0.2
    path = os.path.join(str(tmp_path), "train_batch.bin")
    images.write_binary(path, train, labels)
    assert os.path.getsize(path) == 64 * 3073
    back, back_labels = load_cifar_numpy(path)
    assert np.array_equal(back, train.astype(np.float32))
    assert np.array_equal(back_labels, labels)
    assert len(test_labels) == 32


# -- the cell at the rehearsal size: sound, its controls, its faults --------------

def rehearse(*extra, code=None, env=None, seed=2147483659):
    """The harness in a process of its own, as the driver runs it."""
    args = ["--workload", "cifar_refit", "--seed", str(seed), "--seconds",
            "2", "--trace", "0", "--rehearse", *extra]
    cmd = ([sys.executable, "-m", "benchmarks.run"] if code is None else
           [sys.executable, "-c", code]) + args
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(JAX_PLATFORMS="cpu", **(env or {}))
    done = subprocess.run(cmd, cwd=ROOT, env=full, capture_output=True,
                          text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


failed = rehearsals.failed


def test_the_harness_refuses_a_cell_the_manifest_lacks():
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", "cifar_fit",
         "--seed", "1", "--seconds", "2", "--rehearse"], cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and "unknown workload" in done.stderr
    assert "'cifar_refit'" in done.stderr


def test_the_lower_solver_precision_fails_the_solve_part_and_no_other():
    # the control every fit cell's rehearsal file drives it through: here
    # the same code, held to the part it must fail
    result, lines = rehearse(code=rehearsals.THREE_PASSES)
    assert result["correct"] is False, "\n".join(lines[-16:])
    # nearer the reference's three-pass solve than its full-precision one
    assert failed(lines) == {"weights_gap", "weights_gap_ratio",
                             "test_scores_gap", "test_scores_gap_ratio"}


def test_the_features_control_fails_the_features_part_and_no_other():
    result, lines = rehearse(env={"BENCH_FEATURE_CONTROL": "bf16_output"})
    assert result["correct"] is False, "\n".join(lines[-16:])
    assert failed(lines) == {"features_gap"}


def test_the_filters_control_fails_the_filters_gap():
    result, lines = rehearse(env={"BENCH_FEATURE_CONTROL": "bf16_filters"})
    assert result["correct"] is False, "\n".join(lines[-16:])
    assert "filters_gap" in failed(lines)
    assert failed(lines) <= {"filters_gap", "train_error_gap",
                             "test_error_gap"}


def test_the_control_flag_degrades_both_parts():
    """``--control``: the solver at ``high`` (which the CPU multiplies
    exactly, so only the features part can show here) and the job's
    bfloat16 output."""
    result, lines = rehearse("--control")
    assert "CONTROL (not a measurement)" in lines[0]
    assert "KEYSTONE_SOLVER_PRECISION" in lines[0]
    assert result["correct"] is False and "features_gap" in failed(lines)
