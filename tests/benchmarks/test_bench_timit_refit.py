"""The cell ``timit_refit`` (ISSUE 26; its per-layer entries listed by
ISSUE 32): what the manifest holds of it, the count
``counts/streamed_bcd.py`` against a hand count, each of its readers on
a hand-built run whose answer is known, the seeded frames and their CSV,
and the configuration's file. (The CPU rehearsal, both faults and the
control of the cell run from ``test_bench_rehearsal_timit_refit.py``.)"""
import os
import threading
import types

import numpy as np
import pytest

import manifest_checks
from benchmarks import xplane
from benchmarks.harness import Run, load_json, load_module, load_peaks
from benchmarks.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = manifest_checks.load_manifest()
CONFIG = load_json(os.path.join(
    ROOT, "benchmarks", "configs", "timit_50x4096.json"))
# accepted metrics whose readers find something to read in the cell: their
# ``workloads`` gained it (five in PR 26, the three host readers in PR 32)
WIDENED = ["loader_s.setup", "to_device_s.refit", "dag_host_s.refit",
           "device_idle_pct.refit", "hbm_peak_gib.refit",
           "optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit"]
# the cell's own readers and the layer each is a metric of
LAYERS = {"stream_solve_dev_ms.timit": "solve",
          "stream_solve_roofline.timit": "solve",
          "blocks_generated.timit": "featurize kernels",
          "apply_dev_ms.timit": "featurize kernels",
          "draw_host_s.timit": "featurize kernels"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the manifest ---------------------------------------------------------------

def manifest_holds(manifest):
    """What this file relies on in ``BENCHMARK.json``: looked up by name,
    held by membership and relative order (``manifest_checks``)."""
    manifest_checks.cell_is_held(
        manifest, cell="timit_refit", config="timit_50x4096",
        traffic="fit_in_memory", chips=1,
        reduced=["train_rows", "test_rows", "env"],
        configs_before=["mnist_random_fft_32"], cells_before=["mnist_refit"],
        per_layer=WIDENED + list(LAYERS),
        end_to_end={"refit_items_per_s": 0.029, "setup_s": 0.1})


def test_the_manifest_holds_the_configuration_the_cell_and_its_readers():
    manifest_holds(MANIFEST)
    assert len([m for m in MANIFEST["per_layer"]
                if "timit_refit" in m["workloads"]]) >= 13


def test_the_cells_own_entries_say_their_layer_and_double_no_reader():
    manifest_checks.own_entries_are_held(MANIFEST, LAYERS, ".timit")


def test_the_configuration_states_the_published_widths_uncut():
    assert CONFIG["architecture"] is None
    published = {"num_cosines": 50, "num_cosine_features": 4096,
                 "input_dim": 440, "num_classes": 147, "num_epochs": 5,
                 "gamma": 0.05555, "rf_type": "gaussian", "lambda": 0.0}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["train_rows"] in (65536, 49152, 32768)
    assert CONFIG["test_rows"] == 8192
    shape = CONFIG["solve_shape"]
    assert shape == {"rows": CONFIG["train_rows"], "input_dim": 440,
                     "block_size": 4096, "blocks": 50, "classes": 147,
                     "epochs": 5, "test_rows": 8192, "precision": "highest"}
    for real in (CONFIG["real_fit"], CONFIG["rehearsal"]["real_fit"]):
        assert real["stream_fits"] == 1 and real["materialised_fits"] == 0
    # bounds, not a number: every block made once an epoch and once more
    # for the blockwise apply of the test rows, the least any streamed fit
    # must; or once more a block for a factor sweep of its own, which is
    # what the program makes today
    small = CONFIG["rehearsal"]
    for real, blocks in ((CONFIG["real_fit"], 50),
                         (small["real_fit"], small["num_cosines"])):
        assert "blocks_generated" not in real
        assert real["blocks_generated_min"] == blocks * 5 + blocks
        assert real["blocks_generated_max"] == blocks * (1 + 5) + blocks
    assert (CONFIG["real_fit"]["blocks_generated_min"],
            CONFIG["real_fit"]["blocks_generated_max"]) == (300, 350)
    # the rehearsal's stated device cannot hold its gather, the chip's
    # 16 GB cannot hold the timed one: both stream
    gathered = 4 * small["train_rows"] * (
        small["num_cosines"] * small["num_cosine_features"])
    assert gathered > 0.5 * small["device_memory_bytes"]
    assert "device_memory_bytes" not in {
        k for k in CONFIG if k != "rehearsal"}
    assert 4 * CONFIG["train_rows"] * 50 * 4096 > 0.5 * 16e9
    for key in ("limits", "limits_why", "assumed", "deployment", "control"):
        assert CONFIG[key]
    assert set(CONFIG["limits"]) == set(small["limits"]) == {
        "weights_gap", "test_scores_gap", "train_error_gap", "test_error_gap"}


# -- the count ------------------------------------------------------------------

@pytest.fixture(scope="module")
def counts():
    return load_module("counts", "streamed_bcd")


def test_counts_against_a_hand_count_at_a_tiny_shape(counts):
    # 8 rows of 3 inputs, 2 blocks of 4 features, 5 label columns, 2 epochs
    n, d_in, bs, blocks, k, epochs = 8, 3, 4, 2, 5, 2
    flops = counts.fit_flops(n, d_in, bs, blocks, k, epochs)
    assert flops == {
        # x W^T: 2*8*3*4 = 192 a block, 2 blocks, made once an epoch:
        # the least a streamed fit needs, not the 3 times the program's
        # own factor sweep makes it
        "generation": 2 * 2 * 192.0,
        # A^T A, one triangle with the diagonal: 8 * 4 * 5 = 160 a block
        "gram": 2 * 160.0,
        # Cholesky 4^3 / 3 once; two triangular solves 2*4*4*5 an epoch
        "factor": 2 * (64 / 3 + 2 * 160.0),
        # A W, A^T R, A dW: 2*8*4*5 = 320 each, 2 epochs, 2 blocks
        "epoch_products": 2 * 2 * 3 * 320.0,
    }
    assert counts.apply_flops(n, d_in, bs, blocks, k) == {
        "generation": 2 * 192.0, "scores": 2 * 320.0}
    # cosine, bias, centring, mask: 4 a feature, 8 * 4 features a block
    assert counts.elementwise_ops(n, bs, blocks, 3) == 4 * 2 * 3 * 32
    # a fit: per block and generation the block out and back (2 * 32)
    # and the rows (24); per block and epoch the residual out and back
    # (2 * 40); the factor written once and read an epoch (3 * 16)
    assert counts.fit_bytes(n, d_in, bs, blocks, k, epochs) == 4 * 2 * (
        2 * (64 + 24) + 2 * 80 + 3 * 16)


def test_counts_at_the_cell_size(counts):
    shape = CONFIG["solve_shape"]
    args = (shape["rows"], shape["input_dim"], shape["block_size"],
            shape["blocks"], shape["classes"], shape["epochs"])
    flops = counts.fit_flops(*args)
    n = shape["rows"]
    assert flops["generation"] == 50 * 5 * 2.0 * n * 440 * 4096
    assert flops["gram"] == 50.0 * n * 4096 * 4097
    assert flops["epoch_products"] == 50 * 5 * 6.0 * n * 4096 * 147
    assert flops["factor"] == pytest.approx(50 * (4096 ** 3 / 3 + 5 * 2 * 4096 ** 2 * 147))
    seconds, bound = counts.roofline_seconds(load_peaks("TPU v5 lite"), *args)
    assert bound == "compute"
    assert seconds == pytest.approx(
        6 * sum(flops.values()) / 197e12, rel=1e-12)
    # memory: 5 generations x 50 blocks x (block twice + rows): under a
    # second at 819 GB/s, a sixth of the compute time
    assert counts.fit_bytes(*args) / 819e9 < 0.35 * seconds
    high, _ = counts.roofline_seconds(PEAKS, *args, precision="high")
    assert high == pytest.approx(seconds / 2)


# -- the readers ------------------------------------------------------------------

def make_run(tmp_path, trace_data=None, fits=2, cfg=None, peaks=PEAKS):
    said = []
    run = Run(cell={"name": "timit_refit", "config": "timit_50x4096"},
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
    """A window of 10 s; two fits, each a factor sweep of 2 s, an epoch
    sweep of 2 s and 0.5 s of other programs: 9 s busy, 1 s idle."""
    s = 1e9
    modules = []
    for t0 in (0.0, 5.0):
        modules += [("jit__stream_factor", (t0 + 0.2) * s, (t0 + 2.2) * s),
                    ("jit__stream_epochs", (t0 + 2.2) * s, (t0 + 4.2) * s),
                    ("jit__stream_apply", (t0 + 4.2) * s, (t0 + 4.6) * s),
                    ("jit_evaluate", (t0 + 4.6) * s, (t0 + 4.7) * s)]
    dev = xplane.DeviceTrace(0, modules, list(modules))
    return xplane.Trace([dev], [("window", 0.0, 10 * s)])


def read(name, run):
    return load_module("layers", name).read(run)


def test_device_readers_on_a_hand_built_trace(tmp_path):
    run = make_run(tmp_path, hand_trace())
    assert read("stream_solve_dev_ms.timit", run) == pytest.approx(4000.0)
    assert read("apply_dev_ms.timit", run) == pytest.approx(500.0)
    assert read("device_idle_pct.refit", run) == pytest.approx(10.0)
    counts = load_module("counts", "streamed_bcd")
    shape = CONFIG["solve_shape"]
    least, _ = counts.roofline_seconds(
        PEAKS, shape["rows"], 440, 4096, 50, 147, 5)
    assert read("stream_solve_roofline.timit", run) == pytest.approx(
        100 * least / 4.0)
    run.memory_peak_bytes = 5 * 2 ** 30
    assert read("hbm_peak_gib.refit", run) == pytest.approx(5.0)


def test_device_readers_find_nothing_on_a_program_without_the_solve(tmp_path):
    s = 1e9
    dev = xplane.DeviceTrace(0, [("jit__block_solve", 0.0, 2 * s)],
                             [("jit__block_solve", 0.0, 2 * s)])
    parent = xplane.Trace([dev], [("window", 0.0, 4 * s)])
    for trace_data in (None, parent):
        run = make_run(tmp_path, trace_data)
        for name in ("stream_solve_dev_ms.timit", "apply_dev_ms.timit",
                     "stream_solve_roofline.timit"):
            assert read(name, run) is None
    no_fits = make_run(tmp_path, hand_trace(), fits=None)
    assert read("stream_solve_dev_ms.timit", no_fits) is None
    assert read("stream_solve_roofline.timit", make_run(
        tmp_path, hand_trace(), peaks=None)) is None


def ring_span(cat, name, start, dur, args=None, tid=None):
    return types.SimpleNamespace(
        ph="X", cat=cat, name=name, start_s=start, dur_s=dur, args=args,
        tid=threading.main_thread().ident if tid is None else tid)


@pytest.fixture
def ring(monkeypatch):
    """A hand-built ring in the flight recorder's place."""
    from keystone_tpu.observability import timeline

    holder = types.SimpleNamespace(items=[], lost=0)
    fake = types.SimpleNamespace(spans=lambda: list(holder.items),
                                 dropped=lambda: holder.lost)
    monkeypatch.setattr(timeline, "flight_recorder", lambda: fake)
    return holder


def test_host_readers_on_a_hand_built_ring(tmp_path, ring):
    run = make_run(tmp_path)
    run.spans.records += [("fit", 10.0, 14.0), ("fit", 15.0, 19.0),
                          ("window", 10.0, 19.5)]
    ring.items = [
        ring_span("dag", "optimize", 9.0, 0.5),           # the warming fit's
        ring_span("featurize", "draw", 9.5, 0.9),         # the warming fit's
        ring_span("featurize", "draw", 10.02, 0.4),
        ring_span("dag", "optimize", 10.1, 0.2),
        ring_span("dag", "optimize", 13.0, 0.1),
        ring_span("solve", "fit:BlockLeastSquaresEstimator", 10.4, 0.01),
        ring_span("ingest", "h2d", 10.0, 0.05, {"nbytes": 86_000_000}),
        ring_span("ingest", "h2d", 10.05, 0.01, {"nbytes": 4_000_000}),
        ring_span("wait", "d2h", 13.2, 0.7),
        ring_span("featurize", "draw", 15.02, 0.5),
        ring_span("dag", "optimize", 15.1, 0.3),
        ring_span("dag", "optimize", 15.2, 9.0, tid=-1),  # another thread
        ring_span("ingest", "h2d", 15.0, 0.05, {"nbytes": 90_000_000}),
        ring_span("wait", "d2h", 18.0, 0.9),
        ring_span("wait", "d2h", 19.2, 5.0),              # after the last fit
    ]
    assert read("optimize_host_s.refit", run) == pytest.approx(0.3)
    assert read("host_wait_s.refit", run) == pytest.approx(0.8)
    assert read("draw_host_s.timit", run) == pytest.approx(0.45)
    # the bytes are reported where the program's counter bears the spans out
    assert read("h2d_mb.refit", run) is None
    assert any("not reported" in line for line in run.said)
    from keystone_tpu.observability.metrics import MetricsRegistry

    MetricsRegistry.get_or_create().counter("ingest.h2d_bytes").inc(180e6)
    assert read("h2d_mb.refit", run) == pytest.approx(90.0)


def test_the_widened_harness_readers_on_a_run_of_the_new_cell(tmp_path):
    run = make_run(tmp_path, hand_trace())
    assert read("loader_s.setup", run) is None
    assert read("to_device_s.refit", run) is None
    assert read("dag_host_s.refit", run) is None
    run.facts["loader_s"] = 3.5
    run.spans.records += [("to_device", 0.0, 0.004), ("fit", 0.0, 4.9),
                          ("to_device", 5.0, 5.006), ("fit", 5.0, 9.9)]
    assert read("loader_s.setup", run) == 3.5
    assert read("to_device_s.refit", run) == pytest.approx(0.005)
    # two fits of 4.9 s, 0.01 s of them putting rows, 9 s of device work
    assert read("dag_host_s.refit", run) == pytest.approx(
        (9.8 - 0.01 - 9.0) / 2)


def test_host_readers_find_nothing_without_fits_spans_or_a_whole_fit(
        tmp_path, ring):
    names = ("optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit",
             "draw_host_s.timit")
    run = make_run(tmp_path)
    ring.items = [ring_span("solve", "fit:X", 10.4, 0.01)]
    assert [read(n, run) for n in names] == [None] * 4    # no fit spans
    run.spans.records.append(("fit", 10.0, 14.0))
    ring.items = [ring_span("dag", "node:x#1", 10.4, 0.01)]
    assert [read(n, run) for n in names] == [None] * 4    # a parent's ring
    ring.items = [ring_span("solve", "fit:X", 12.0, 0.01),
                  ring_span("dag", "optimize", 12.5, 0.25)]
    ring.lost = 7                                  # the one fit's start is gone
    assert [read(n, run) for n in names] == [None] * 4
    assert any("no whole fit" in line for line in run.said)
    # a fit that starts after the oldest span the ring holds ended is whole
    run.spans.records.append(("fit", 15.0, 19.0))
    ring.items += [ring_span("dag", "optimize", 15.5, 0.125),
                   ring_span("ingest", "h2d", 15.0, 0.01, {"nbytes": 5e6})]
    assert read("optimize_host_s.refit", run) == pytest.approx(0.125)
    assert read("h2d_mb.refit", run) == pytest.approx(5.0)  # counter not asked
    ring.lost = 0
    assert read("optimize_host_s.refit", run) == pytest.approx(0.375 / 2)
    assert read("host_wait_s.refit", run) == 0.0          # it never waited
    assert read("draw_host_s.timit", run) is None         # no draw, no number
    ring.items = ring.items[:2]
    assert read("h2d_mb.refit", run) is None              # no put, no number


def test_blocks_generated_reads_the_jobs_counts_of_the_windows_fits(tmp_path):
    job = load_module("configs", "timit_50x4096")
    run = make_run(tmp_path, fits=2)
    saved = list(job.FIT_COUNTS)
    try:
        job.FIT_COUNTS[:] = []
        assert read("blocks_generated.timit", run) is None
        job.FIT_COUNTS[:] = [{"blocks_generated": 9999.0},   # the warming fit
                             {"blocks_generated": 350.0},
                             {"blocks_generated": 350.0}]
        assert read("blocks_generated.timit", run) == 350.0
        assert read("blocks_generated.timit",
                    make_run(tmp_path, fits=None)) is None
    finally:
        job.FIT_COUNTS[:] = saved


def test_a_model_without_health_counts_every_block_unhealthy():
    job = load_module("configs", "timit_50x4096")
    sound = types.SimpleNamespace(
        health=(np.array([True, True, False]), np.array([0.4, 0.5, 1e-9])))
    assert job.block_health(sound, 3) == (1.0, 1e-9)
    for mapper in (types.SimpleNamespace(), types.SimpleNamespace(health=None)):
        unhealthy, ratio = job.block_health(mapper, 50)
        assert unhealthy == 50.0 and np.isnan(ratio)


# -- the seeded frames ------------------------------------------------------------

def test_the_frames_are_seeded_unit_scale_and_read_back_exactly(tmp_path):
    frames = load_module("datagen", "timit_frames")
    (x, y), (tx, ty) = frames.make_frames(4096, 512, 2600000011)
    (x2, y2), _ = frames.make_frames(4096, 512, 2600000011)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    (x3, _), _ = frames.make_frames(4096, 512, 2600000012)
    assert not np.array_equal(x, x3)
    assert x.shape == (4096, 440) and x.dtype == np.float32
    assert tx.shape == (512, 440) and ty.shape == (512,)
    assert set(np.unique(y)) == set(range(147))
    # about unit variance a coordinate: gamma w.x is of order one radian
    assert 0.9 < x.std() < 1.1 and abs(x.mean()) < 0.05
    assert 0.8 < (0.05555 * x @ np.random.RandomState(0).randn(440)).std() < 1.6
    assert np.array_equal(x * 64, np.rint(x * 64)) and np.abs(x).max() < 8
    # full rank: every coordinate has noise of its own
    assert np.linalg.matrix_rank(x[:880].astype(np.float64)) == 440

    from keystone_tpu.loaders.csv_loader import csv_labeled_loader

    path = os.path.join(str(tmp_path), "train-frames.csv")
    frames.write_csv(path, x[:300], y[:300], label_offset=1)
    with open(path, "rb") as f:
        first = f.readline()
    assert len(first) == 10 * 441 and first.startswith(b"%09d," % (y[0] + 1))
    back = csv_labeled_loader(path, label_offset=1)
    assert np.array_equal(back.data.numpy(), x[:300])
    assert np.array_equal(back.labels.numpy(), y[:300])
