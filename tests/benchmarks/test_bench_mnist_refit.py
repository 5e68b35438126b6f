"""The cell ``mnist_refit`` (ISSUE 23, the first): what the manifest
holds of it, and the three readers of host seconds and bytes that every
cell shares (``optimize_host_s.refit``, ``host_wait_s.refit``,
``h2d_mb.refit``; ``benchmarks/layers/_ring_spans.py``) on hand-built
windows of the two kinds there are: 180 short fits, of which the
program's ring of 8,192 spans has dropped the first, and 5 long ones.
(The cell's other readers are tested where they came: the harness's
spans in ``test_bench_fit_loop.py``, the device's in
``test_bench_xplane.py`` and ``test_bench_featurize_roofline.py``, the
idle split in ``test_bench_program_spans.py``; its rehearsal, faults
and control in ``test_bench_rehearsal_mnist_refit.py``.)"""
import threading

import pytest

import manifest_checks
from benchmarks.harness import Run, load_module
from benchmarks.spans import Spans

MANIFEST = manifest_checks.load_manifest()
# every per-layer entry a traced run of the cell reports, as accepted
PER_LAYER = [
    "loader_s.setup", "to_device_s.refit", "dag_host_s.refit",
    "featurize_dev_ms.refit", "solve_dev_ms.refit", "solve_roofline.refit",
    "device_idle_pct.refit", "hbm_peak_gib.refit", "optimize_host_s.refit",
    "dispatch_host_s.refit", "host_wait_s.refit", "idle_host_busy_s.refit",
    "idle_host_waiting_s.refit", "h2d_mb.refit", "span_coverage_pct.refit",
    "featurize_roofline.refit"]
HOST = ["optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit"]


def manifest_holds(manifest):
    """What this file relies on in ``BENCHMARK.json``: looked up by name,
    held by membership and relative order (``manifest_checks``)."""
    manifest_checks.cell_is_held(
        manifest, cell="mnist_refit", config="mnist_random_fft_32",
        traffic="fit_in_memory", chips=1, reduced=["num_ffts", "env"],
        configs_before=[], cells_before=[], per_layer=PER_LAYER,
        end_to_end={"refit_items_per_s": 0.029, "setup_s": 0.1})


def test_the_manifest_holds_the_configuration_the_cell_and_its_readers():
    manifest_holds(MANIFEST)
    assert len([m for m in MANIFEST["per_layer"]
                if "mnist_refit" in m["workloads"]]) >= 16


def test_the_three_host_readers_are_every_cells():
    cells = [c["name"] for c in MANIFEST["workloads"]
             if c["traffic"] == "fit_in_memory"]
    assert len(cells) >= 3
    for name in HOST:
        listed = manifest_checks.named(MANIFEST["per_layer"], name)["workloads"]
        assert listed[:len(cells)] == cells, name


# -- the three readers on a window of 180 fits and of 5 ---------------------------

#: One fit, as fractions of its length: (cat, name, start, length, bytes).
FIT_SPANS = [
    ("ingest", "h2d", 0.00, 0.02, 188_160_000),     # the training rows
    ("ingest", "h2d", 0.02, 0.01, 31_640_000),      # labels, test rows
    ("dag", "optimize", 0.04, 0.03, None),
    ("solve", "fit:BlockLeastSquaresEstimator", 0.10, 0.01, None),
    ("wait", "d2h", 0.12, 0.50, None),
    ("dag", "optimize", 0.63, 0.01, None),
    ("wait", "d2h", 0.65, 0.30, None),
    ("eval", "evaluate", 0.96, 0.03, None),
]
#: spans of no counted kind that fill the ring as a real fit's nodes do
FILLER = 42
T0 = 1000.0


def window(tmp_path, fits, fit_s, pause_s):
    """A run whose window is ``fits`` identical fits on the host's clock,
    after a warming fit that is no part of it; the program's spans in the
    real ring, and what its counter holds beside them."""
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.observability.timeline import flight_recorder

    rec = flight_recorder()
    said = []
    run = Run(cell={"name": "t"}, cfg={}, traffic={}, seed=0, seconds=1.0,
              trace=True, rehearsal=True, control=False,
              workdir=str(tmp_path), say=said.append, spans=Spans())
    run.said = said
    for i in range(-1, fits):          # -1: the warming fit, before the window
        t = T0 + i * (fit_s + pause_s)
        if i >= 0:
            run.spans.records.append(("fit", t, t + fit_s))
        for cat, name, at, length, nbytes in FIT_SPANS:
            rec.record(name, cat, t + at * fit_s, length * fit_s,
                       None if nbytes is None else {"nbytes": nbytes})
            if nbytes is not None:
                MetricsRegistry.get_or_create().counter(
                    "ingest.h2d_bytes").inc(nbytes)
        for k in range(FILLER):
            rec.record(f"node:n#{k}", "dag", t + 0.97 * fit_s, 1e-6)
        # between two fits, and on another thread: never counted
        rec.record("optimize", "dag", t + fit_s + 0.5 * pause_s, 7.0)
        rec.record("d2h", "wait", t + 0.5 * fit_s, 7.0,
                   tid=threading.main_thread().ident + 1, thread="pool")
    run.spans.records.append(
        ("window", T0, T0 + fits * (fit_s + pause_s)))
    return run


@pytest.mark.parametrize("fits,fit_s,pause_s", [
    (180, 0.2, 0.02),    # mnist_refit: the ring has dropped the first fits
    (5, 9.0, 0.5),       # timit_refit, cifar_refit: it holds them all
], ids=["180_short_fits", "5_long_fits"])
def test_the_host_readers_on_a_hand_built_window(tmp_path, fits, fit_s,
                                                 pause_s):
    from keystone_tpu.observability.timeline import flight_recorder

    run = window(tmp_path, fits, fit_s, pause_s)
    dropped = flight_recorder().dropped()
    per_fit = len(FIT_SPANS) + FILLER + 2
    assert dropped == max(0, (fits + 1) * per_fit - 8192)
    assert bool(dropped) == (fits == 180)
    got = {name: load_module("layers", name).read(run) for name in HOST}
    assert got == pytest.approx({
        "optimize_host_s.refit": 0.04 * fit_s,
        "host_wait_s.refit": 0.80 * fit_s,
        "h2d_mb.refit": 219.8}, rel=1e-9)
    assert run.said == []


def test_a_ring_that_holds_no_whole_fit_reads_nothing(tmp_path, monkeypatch):
    from keystone_tpu.observability.timeline import reset_flight_recorder

    monkeypatch.setenv("KEYSTONE_FLIGHT_SPANS", "40")   # under one fit's spans
    reset_flight_recorder()
    run = window(tmp_path, 5, 9.0, 0.5)
    assert [load_module("layers", name).read(run) for name in HOST] == [None] * 3
    assert all("no whole fit" in line for line in run.said) and run.said
