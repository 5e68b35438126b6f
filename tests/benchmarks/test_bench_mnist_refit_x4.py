"""The cell ``mnist_refit_x4`` (ISSUE 38, the first on four chips): what
the manifest holds of it; the configuration's file; its five readers on
hand-built traces with four device planes whose answers are known, the
two shares of a roofline against hand counts and under 100% at
``mnist_refit``'s own shares; the reference's two checks of where the
design matrix lay; the job's refusal of a program that cannot say; and
the fault of the mesh (rows put whole on every chip), which must come
out not correct by those two checks alone. (Its rehearsal on four
virtual CPU devices, the fault every fit cell has and the solver control
run from ``test_bench_rehearsal_mnist_refit_x4.py`` (``rehearsals.py``);
the fit itself against the reference and a 1 x 1 mesh, the counters and
the planner are in ``tests/test_mnist_x4_mesh.py``.)"""
import os

import pytest

import manifest_checks
from benchmarks import xplane
from benchmarks.harness import Run, load_json, load_module
from benchmarks.spans import Spans
from rehearsals import fault_file, rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = manifest_checks.load_manifest()
CONFIG = load_json(os.path.join(
    ROOT, "benchmarks", "configs", "mnist_random_fft_200.json"))
CELL = "mnist_refit_x4"
# accepted metrics whose readers find something to read in the cell
WIDENED = ["loader_s.setup", "to_device_s.refit", "dag_host_s.refit",
           "featurize_dev_ms.refit", "solve_dev_ms.refit",
           "device_idle_pct.refit", "hbm_peak_gib.refit",
           "optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit"]
LAYERS = {"collective_dev_ms.x4": "collectives",
          "allreduce_mb.x4": "collectives", "chip_skew_pct.x4": "device",
          "solve_roofline.x4": "solve",
          "featurize_roofline.x4": "featurize kernels"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SECOND = 1e9


# -- the manifest ---------------------------------------------------------------

def manifest_holds(manifest):
    manifest_checks.cell_is_held(
        manifest, cell=CELL, config="mnist_random_fft_200",
        traffic="fit_in_memory", chips=4, reduced=["env"],
        configs_before=["mnist_random_fft_32", "timit_50x4096",
                        "cifar_random_patch_10k", "voc_sift_fisher_256"],
        cells_before=["mnist_refit", "timit_refit", "cifar_refit",
                      "voc_refit"],
        per_layer=WIDENED + list(LAYERS),
        end_to_end={"refit_items_per_s": 0.029, "setup_s": 0.1})
    source = manifest_checks.named(
        manifest["configs"], "mnist_random_fft_200")["source"]
    assert "MnistRandomFFT.scala" in source and "--numFFTs 200" in source
    for name, layer in LAYERS.items():
        m = manifest_checks.named(manifest["per_layer"], name)
        assert (m["layer"], m["moves"]) == (layer, "refit_items_per_s")
        assert m["workloads"][0] == CELL
    # the one-chip shares divide by ONE chip's peak: not this cell's
    for name in ("solve_roofline.refit", "featurize_roofline.refit"):
        assert CELL not in manifest_checks.named(
            manifest["per_layer"], name)["workloads"]
    # at most a quarter of the cells, and one always, may take four chips
    cells = manifest["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert manifest["run_seconds"] == 40


def test_the_manifest_holds_the_configuration_the_cell_and_its_readers():
    manifest_holds(MANIFEST)
    assert len([m for m in MANIFEST["per_layer"]
                if CELL in m["workloads"]]) >= 15
    # appended: the cell's five stand after every entry that was there
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index("collective_dev_ms.x4") > names.index("startup_s.setup")
    assert [n for n in names if n in LAYERS] == list(LAYERS)


def test_the_next_cell_breaks_nothing_here_and_damage_is_seen():
    more = manifest_checks.grown(MANIFEST)
    manifest_holds(more)
    for damage in (
            lambda m: m["workloads"].remove(
                manifest_checks.named(m["workloads"], CELL)),
            lambda m: manifest_checks.named(
                m["workloads"], CELL).update(chips=1),
            lambda m: manifest_checks.named(
                m["per_layer"], "solve_roofline.x4").update(unit="ms"),
            lambda m: manifest_checks.named(
                m["per_layer"], "solve_dev_ms.refit")["workloads"].remove(CELL),
            lambda m: manifest_checks.named(
                m["per_layer"], "solve_roofline.refit")["workloads"].append(
                    CELL),
            lambda m: manifest_checks.named(
                m["configs"], "mnist_random_fft_200")["reduced"].append(
                    "num_ffts")):
        broken = manifest_checks.grown(MANIFEST)
        damage(broken)
        with pytest.raises(AssertionError):
            manifest_holds(broken)


def test_the_file_states_the_source_uncut_on_four_chips():
    assert CONFIG["architecture"] is None
    assert (CONFIG["num_ffts"], CONFIG["block_size"], CONFIG["num_iter"],
            CONFIG["lambda"]) == (200, 2048, 1, 0.0)
    assert (CONFIG["image_size"], CONFIG["fft_size"],
            CONFIG["features_per_fft"], CONFIG["num_classes"]) == (
                784, 1024, 512, 10)
    assert (CONFIG["train_rows"], CONFIG["test_rows"]) == (60000, 10000)
    assert CONFIG["chips"] == 4 and CONFIG["mesh"] == {"data": 4, "model": 1}
    shape = CONFIG["solve_shape"]
    assert shape["features"] == 200 * 512 == 50 * shape["block_size"]
    assert shape["rows"] * shape["features"] * 4 == 24_576_000_000
    assert list(CONFIG["reduced_why"]) == ["env"]
    assert CONFIG["env"] == {"KEYSTONE_NUMERICS": "0"}
    assert CONFIG["control"]["env"] == {"KEYSTONE_SOLVER_PRECISION": "high"}
    # the rehearsal's env replaces the file's: it has to repeat it, and
    # gives the CPU four devices before jax is imported
    assert CONFIG["rehearsal"]["env"] == {
        "KEYSTONE_NUMERICS": "0",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    assert set(CONFIG["limits"]) == set(CONFIG["rehearsal"]["limits"]) == {
        "weights_gap", "test_scores_gap", "train_error_gap", "test_error_gap"}
    for key in ("limits_why", "deployment"):
        assert len(CONFIG[key]) > 80
    assert set(CONFIG["real_fit"]) == {"prefix_hits", "nodes_executed", "why"}


# -- the readers on hand-built traces of four device planes -------------------------

def make_run(tmp_path, trace, fits=2, peaks=PEAKS, chips=4, cfg=CONFIG):
    said = []
    run = Run(cell={"name": CELL, "config": "mnist_random_fft_200",
                    "chips": chips}, cfg=dict(cfg), traffic={}, seed=0,
              seconds=1.0, trace=True, rehearsal=False, control=False,
              workdir=str(tmp_path), say=said.append, spans=Spans(),
              peaks=peaks)
    run.said = said
    run.trace_data = trace
    if fits is not None:
        run.facts["fits"] = fits
    return run


def four_planes(solve_s=0.4, other_s=0.3, late=(0.0, 0.0, 0.0, 0.04)):
    """A window of one second on four chips: the solve, with a
    synchronous all-reduce of 50 ms and an asynchronous pair whose
    ``-done`` waits 20 ms; then every other program, with an all-gather
    outside the window's end. Chip ``i`` ends ``late[i]`` seconds after
    the first."""
    devices = []
    for chip, more in enumerate(late):
        end = solve_s + other_s + more
        modules = [("jit__block_solve", 0.0, solve_s * SECOND),
                   ("jit_featurize", solve_s * SECOND, end * SECOND),
                   ("jit_late", 1.2 * SECOND, 1.3 * SECOND)]
        ops = [("fusion.1", 0.0, 0.10 * SECOND),
               ("all-reduce.24", 0.10 * SECOND, 0.15 * SECOND),
               ("all-reduce-start.3", 0.15 * SECOND, 0.16 * SECOND),
               ("fusion.2", 0.16 * SECOND, 0.20 * SECOND),
               ("all-reduce-done.3", 0.20 * SECOND, 0.22 * SECOND),
               ("fusion.3", 0.22 * SECOND, end * SECOND),
               ("all-gather.7", 1.2 * SECOND, 1.3 * SECOND)]
        devices.append(xplane.DeviceTrace(chip, modules, ops))
    return xplane.Trace(devices, [("window", 0.0, 1.0 * SECOND)])


def test_collective_time_is_the_first_chips_sync_ops_and_dones(tmp_path):
    reader = load_module("layers", "collective_dev_ms.x4")
    for name, counted in [("all-reduce.24", True), ("all-gather-done.3", True),
                          ("reduce-scatter.1", True), ("all-to-all", True),
                          ("collective-permute-done.12", True),
                          ("all-reduce-start.3", False), ("fusion.7", False),
                          ("all-reduce-scatter-fusion.2", False),
                          ("while.319", False)]:
        assert reader.is_collective(name) is counted, name
    run = make_run(tmp_path, four_planes())
    # 50 ms + 20 ms in a window of two fits; the start and what ran
    # after the window are not counted
    assert reader.read(run) == pytest.approx(35.0)
    assert "all-reduce.24 x1 0.0500 s" in run.said[0]
    assert "all-reduce-done.3 x1 0.0200 s" in run.said[0]
    assert "all-gather" not in run.said[0] and "start" not in run.said[0]


def test_chip_skew_is_busiest_less_idlest_over_the_mean(tmp_path):
    reader = load_module("layers", "chip_skew_pct.x4")
    run = make_run(tmp_path, four_planes(late=(0.0, 0.0, 0.0, 0.04)))
    assert reader.busy_by_chip(run.trace_data) == pytest.approx(
        [0.7, 0.7, 0.7, 0.74])
    assert reader.read(run) == pytest.approx(100 * 0.04 / 0.71)
    assert reader.read(make_run(tmp_path, four_planes(late=(0,) * 4))) == 0.0
    one = four_planes()
    one.devices = one.devices[:1]
    assert reader.read(make_run(tmp_path, one)) is None


def test_the_x4_shares_count_the_whole_fit_against_four_chips_peak(tmp_path):
    run = make_run(tmp_path, four_planes(solve_s=0.4, other_s=0.3))
    solve = load_module("counts", "block_solve")
    dft = load_module("counts", "dense_dft")
    # hand counts at the cell's shape
    flops = 50 * (60000 * 2048 * 2049 + 4 * 60000 * 2048 * 10) + 50 * (
        2048 ** 3 / 3 + 2 * 2048 * 2048 * 10)
    assert solve.flops(60000, 102400, 2048, 10) == pytest.approx(flops)
    least_solve = flops * 6 / (4 * 197e12)
    assert least_solve == pytest.approx(0.0989, rel=2e-3)
    least_dft = 2.0 * 70000 * 784 * 512 * 200 * 6 / (4 * 197e12)
    assert dft.flops(70000, 784, 512, 200) == 2.0 * 70000 * 784 * 512 * 200
    got = load_module("layers", "solve_roofline.x4").read(run)
    assert got == pytest.approx(100 * 2 * least_solve / 0.4)
    got = load_module("layers", "featurize_roofline.x4").read(run)
    assert got == pytest.approx(100 * 2 * least_dft / 0.3)
    # the one-chip readers on the same run read four times as much: why
    # the cell is on neither
    one = load_module("layers", "solve_roofline.refit").read(run)
    assert one == pytest.approx(4 * 100 * 2 * least_solve / 0.4)


def test_the_x4_shares_stay_under_100_at_mnist_refits_own_shares(tmp_path):
    """A chip of the four that ran its quarter of the products as fast as
    ``mnist_refit``'s one chip runs its whole (58.1% and 69.8% of a
    roofline: ledger, PR 37, 108.87 and 78.449 ms a fit) reads those
    shares again, not four times them."""
    solve = load_module("counts", "block_solve")
    dft = load_module("counts", "dense_dft")
    least_solve, _ = solve.roofline_seconds(PEAKS, 60000, 102400, 2048, 10)
    least_dft, _ = dft.roofline_seconds(PEAKS, 70000, 784, 512, 200)
    solve_s = least_solve / 4 / 0.581
    other_s = least_dft / 4 / 0.698
    run = make_run(tmp_path, four_planes(solve_s, other_s, (0,) * 4), fits=1)
    assert load_module("layers", "solve_roofline.x4").read(run) == (
        pytest.approx(58.1))
    assert load_module("layers", "featurize_roofline.x4").read(run) == (
        pytest.approx(69.8))
    assert load_module("layers", "solve_roofline.refit").read(run) > 105


def test_allreduce_mb_is_the_counters_rise_in_the_windows_fits(tmp_path):
    reader = load_module("layers", "allreduce_mb.x4")
    job = load_module("configs", "mnist_random_fft_200")
    run = make_run(tmp_path, None, fits=2)
    by_shapes = 4.0 * (50 * (10 * 512 * 512 + 2048 * 10) + 102400 + 10)
    try:
        # the warming fit's count is no part of the window
        job.FIT_COUNTS[:] = [{"allreduce_bytes": 1.0}] + [
            {"allreduce_bytes": by_shapes}] * 2
        assert reader.read(run) == pytest.approx(528.79364)
        # within 2% of the ten upper-triangle tiles a block, and 62.7% of
        # what whole Grams would be (ISSUE 38's acceptance line)
        whole = 4.0 * (50 * (2048 * 2048 + 2048 * 10) + 102400 + 10) / 1e6
        assert reader.read(run) / whole == pytest.approx(0.627, abs=1e-3)
        job.FIT_COUNTS[:] = [{"allreduce_bytes": 0.0}] * 3   # one shard
        assert reader.read(run) is None
        job.FIT_COUNTS[:] = [{"allreduce_bytes": by_shapes}]  # fewer than fits
        assert reader.read(run) is None
    finally:
        job.FIT_COUNTS[:] = []


@pytest.mark.parametrize("name", list(LAYERS))
@pytest.mark.parametrize("why", ["no trace", "no fits", "no peaks"])
def test_a_reader_returns_none_where_there_is_nothing_to_read(tmp_path, name,
                                                              why):
    kwargs = {"no trace": dict(trace=None), "no fits": dict(
        trace=four_planes(), fits=None), "no peaks": dict(
            trace=four_planes(), peaks=None)}[why]
    run = make_run(tmp_path, **kwargs)
    needs = {"collective_dev_ms.x4": {"no trace", "no fits"},
             "allreduce_mb.x4": {"no trace", "no fits", "no peaks"},
             "chip_skew_pct.x4": {"no trace"},
             "solve_roofline.x4": {"no trace", "no fits", "no peaks"},
             "featurize_roofline.x4": {"no trace", "no fits", "no peaks"}}
    value = load_module("layers", name).read(run)
    if why in needs[name]:
        assert value is None
    else:
        assert value is not None


def test_the_readers_find_nothing_in_a_trace_of_one_chip_without_collectives(
        tmp_path):
    """As on the parent, or in a one-chip cell: no collective op, one
    device plane, no count of reduced bytes."""
    trace = xplane.load(os.path.join(HERE, "data", "tiny_trace.xplane.pb"),
                        span_prefix="harness:")
    trace.spans.append(("window", trace.devices[0].modules[0][1],
                        trace.devices[0].modules[-1][2]))
    run = make_run(tmp_path, trace, fits=3)
    for name in ("collective_dev_ms.x4", "allreduce_mb.x4",
                 "chip_skew_pct.x4", "solve_roofline.x4"):
        assert load_module("layers", name).read(run) is None, name


def test_the_readers_on_the_trace_recorded_on_four_chips(tmp_path):
    """``data/tiny_trace_x4.xplane.pb``: three runs of one jitted program
    on the four chips of a v5e host (a product over row shards and its
    all-reduce), each under ``harness:span_<i>`` (my chip run, PR 38)."""
    trace = xplane.load(os.path.join(HERE, "data", "tiny_trace_x4.xplane.pb"),
                        span_prefix="harness:")
    assert [d.device for d in trace.devices] == [0, 1, 2, 3]
    for dev in trace.devices:
        assert [m[0] for m in dev.modules] == ["jit_step"] * 3
        assert [op for op, _, _ in dev.ops].count("all-reduce") == 3
    assert [s[0] for s in trace.spans] == ["span_0", "span_1", "span_2"]
    # the device's events are not inside the host's spans to the
    # microsecond: the window is every module of every chip
    trace.spans.append(("window",
                        min(d.modules[0][1] for d in trace.devices),
                        max(d.modules[-1][2] for d in trace.devices)))
    run = make_run(tmp_path, trace, fits=3)
    reduced = [e - s for op, s, e in trace.devices[0].ops if op == "all-reduce"]
    got = load_module("layers", "collective_dev_ms.x4").read(run)
    assert got == pytest.approx(1e3 * sum(reduced) / 1e9 / 3)
    assert 0.003 < got < 0.02                 # 5 to 7 us a run
    assert run.said[0].endswith("all-reduce x3 %.4f s" % (sum(reduced) / 1e9))
    skew = load_module("layers", "chip_skew_pct.x4")
    busy = skew.busy_by_chip(trace)
    assert len(busy) == 4 and all(15e-6 < b < 40e-6 for b in busy)
    assert skew.read(run) == pytest.approx(
        100 * (max(busy) - min(busy)) / (sum(busy) / 4))
    # the harness's own reductions see every plane
    assert trace.busy_seconds(trace.window()) == pytest.approx(sum(busy) / 4)


# -- where the design matrix lay: the reference's two exact checks ---------------------

SHARE = 15000 * 102400 * 4.0


@pytest.mark.parametrize("counts,shards_off,replicated_off", [
    ([dict(data_shards=4, sharded_fits=1, shard_bytes_max=SHARE)] * 3, 0, 0),
    # one fit of the three on two shards of half the rows each
    ([dict(data_shards=4, sharded_fits=1, shard_bytes_max=SHARE)] * 2
     + [dict(data_shards=2, sharded_fits=1, shard_bytes_max=2 * SHARE)], 2, 1),
    # the matrix whole on every chip: one row range, not counted sharded
    ([dict(data_shards=1, sharded_fits=0, shard_bytes_max=4 * SHARE)], 4, 3),
    ([], 4, 4),     # a program that said nothing
], ids=["four_shards", "one_fit_on_two", "whole_on_every_chip", "nothing_said"])
def test_layout_checks(counts, shards_off, replicated_off):
    reference = load_module("reference", "mnist_random_fft_200")
    assert reference.layout_checks(CONFIG, counts) == [
        ("shards_off", float(shards_off), 0.0),
        ("replicated_off", float(replicated_off), 0.0)]


def test_rows_that_do_not_divide_by_the_chips_are_padded_not_off():
    reference = load_module("reference", "mnist_random_fft_200")
    cfg = dict(CONFIG, train_rows=59998)    # 15,000 a chip, two of them zero
    counts = [dict(data_shards=4, sharded_fits=1, shard_bytes_max=SHARE)]
    assert [v for _, v, _ in reference.layout_checks(cfg, counts)] == [0, 0]


def test_the_job_refuses_a_program_that_cannot_say_where_its_rows_lay(
        monkeypatch, tmp_path):
    from keystone_tpu.observability import names

    job = load_module("configs", "mnist_random_fft_200")
    monkeypatch.setattr(names, "METRIC_NAMES", names.METRIC_NAMES - {
        "solve.data_shards", "solve.allreduce_bytes"})
    with pytest.raises(SystemExit) as refusal:
        job.prepare(CONFIG, 1, str(tmp_path))
    assert "solve.allreduce_bytes, solve.data_shards" in str(refusal.value)
    assert os.listdir(tmp_path) == []       # at once: before any file


# -- the fault of the mesh ----------------------------------------------------------------

def test_rows_put_whole_on_every_chip_are_not_correct():
    """Every gap reads sound (each chip fits all the rows): only the two
    checks of the layout catch it."""
    script = fault_file("rows_put_whole_on_every_chip", "mnist_random_fft_200")
    result, lines = rehearse(CELL, script=script)
    assert result["correct"] is False, "\n".join(lines[-14:])
    assert result["device"]["count"] == 4
    checks = {line.split(" check ")[1].split(":")[0]: "NOT CORRECT" in line
              for line in lines if " check " in line}
    assert checks == {
        "weights_gap": False, "test_scores_gap": False,
        "train_error_gap": False, "test_error_gap": False,
        "shards_off": True, "replicated_off": True, "fits_disagree": False,
        "memo_hits_off": False, "nodes_executed_off": False,
        "compiles_in_window": False}


def test_the_rehearsal_runs_on_four_virtual_devices():
    result, lines = rehearse(CELL)
    assert result["correct"] is True and result["device"]["count"] == 4
    assert all(line.startswith("[cpu cpu x4]") for line in lines[:-1])
    assert any("check shards_off: 0 " in line for line in lines)
    assert any("check replicated_off: 0 " in line for line in lines)
