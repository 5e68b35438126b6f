"""The cell ``cifar_aug_refit`` (ISSUE 45): what the manifest holds of
it, its configuration's file, the counts at its geometry, its three own
readers and the six ``.cifar`` readers on a hand-built run of its shape
(a maker inside a loop over row chunks), the reference's crops and vote
against the program's nodes, the reference's sums against float64, and
the cell's controls and its own faults (a row chunk left out of the
Gram; one crop voting for its image) at the rehearsal size. (Its rehearsal,
the solver's control and the two faults every fit cell has run from
``test_bench_rehearsal_cifar_aug_refit.py``.)
"""
import os
import threading
import types

import numpy as np
import pytest

import manifest_checks
import rehearsals
import test_bench_cifar_refit as plain_tests
from benchmarks import xplane
from benchmarks.harness import Run, load_json, load_module
from benchmarks.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = manifest_checks.load_manifest()
CONFIG = load_json(os.path.join(
    ROOT, "benchmarks", "configs", "cifar_random_patch_aug_10k.json"))
CELL = "cifar_aug_refit"
# accepted metrics whose readers find something to read in the cell
WIDENED = plain_tests.WIDENED + list(plain_tests.LAYERS)
# the cell's own readers and the layer each is a metric of
LAYERS = {"augment_dev_ms.cifar_aug": "featurize kernels",
          "row_chunks.cifar_aug": "solve",
          "vote_host_s.cifar_aug": "DAG execution"}
PEAKS = plain_tests.PEAKS
FAULT = os.path.join(HERE, "faults", "a_row_chunk_left_out_of_the_gram",
                     "cifar_random_patch_aug_10k.py")
VOTE_FAULT = os.path.join(HERE, "faults", "one_crop_votes_for_its_image",
                          "cifar_random_patch_aug_10k.py")


# -- the manifest ---------------------------------------------------------------

def manifest_holds(manifest):
    manifest_checks.cell_is_held(
        manifest, cell=CELL, config="cifar_random_patch_aug_10k",
        traffic="fit_in_memory", chips=1, reduced=["env"],
        configs_before=["mnist_random_fft_32", "timit_50x4096",
                        "cifar_random_patch_10k", "voc_sift_fisher_256",
                        "mnist_random_fft_200"],
        cells_before=["mnist_refit", "timit_refit", "cifar_refit",
                      "voc_refit", "mnist_refit_x4"],
        per_layer=WIDENED + list(LAYERS),
        end_to_end={"refit_items_per_s": 0.029, "setup_s": 0.1})
    source = manifest_checks.named(
        manifest["configs"], "cifar_random_patch_aug_10k")["source"]
    assert "RandomPatchCifarAugmented.scala" in source
    assert "--numFilters 10000 --lambda 3000" in source


def test_the_manifest_holds_the_configuration_the_cell_and_its_readers():
    manifest_holds(MANIFEST)
    # at least the seventeen it came with, by name (nothing here speaks
    # of a list's end or length: PR 48 put the cell on the six set-up
    # readers, and the next cell is appended behind it)
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m["workloads"]}
    assert len(WIDENED) + len(LAYERS) == 8 + 6 + 3
    assert listed >= set(WIDENED) | set(LAYERS)
    # appended: in each list it is on, the cells that were there before
    # it stand before it, in the manifest's order
    cells = MANIFEST["workloads"]
    order = [c["name"] for c in cells]
    for m in MANIFEST["per_layer"]:
        if CELL in m["workloads"]:
            at = [order.index(name) for name in m["workloads"]]
            assert at == sorted(at), m["name"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_the_cells_own_entries_say_their_layer_and_double_no_reader():
    manifest_checks.own_entries_are_held(MANIFEST, LAYERS, ".cifar_aug")


def test_a_later_cell_appended_breaks_nothing_here():
    manifest_holds(manifest_checks.grown(MANIFEST))


def test_the_configuration_states_the_documented_widths_uncut():
    assert CONFIG["architecture"] is None
    documented = {"num_filters": 10000, "lambda": 3000.0, "patch_size": 6,
                  "patch_steps": 1, "pool_size": 14, "pool_stride": 13,
                  "alpha": 0.25, "whitening_epsilon": 0.1,
                  "whitener_patches": 100000, "block_size": 4096,
                  "num_epochs": 1, "num_classes": 10, "image_size": 32,
                  "crop_size": 24, "train_rows": 50000, "test_rows": 10000,
                  "crops_a_train_image": 10, "crops_a_test_image": 10,
                  "flip_chance": 0.5}
    assert {k: CONFIG[k] for k in documented} == documented
    assert list(CONFIG["reduced_why"]) == ["env"]
    from keystone_tpu.nodes.images.core import FusedConvRectifyPool

    one = FusedConvRectifyPool(np.zeros((1, 108), np.float32), 24, 6, 3, 13,
                               14, 0.25)
    assert one.columns_a_filter() == 2
    assert CONFIG["filters_a_block"] == 4096 // 2
    shape = CONFIG["solve_shape"]
    assert (shape["rows"], shape["test_rows"]) == (500000, 100000) == (
        CONFIG["train_rows"] * 10, CONFIG["test_rows"] * 10)
    assert shape["blocks"] == -(-10000 // 2048) == 5
    assert shape["last_block"] == 2 * (10000 - 4 * 2048) == 3616
    assert shape["positions"] == 19 * 19 and shape["patch_dim"] == 108
    # the positions some pooling region covers, from the source's geometry
    # (the Pooler's centres and spans) and not from the kernel's layout
    size, stride = CONFIG["pool_size"], CONFIG["pool_stride"]
    side = CONFIG["crop_size"] - CONFIG["patch_size"] + 1
    pooled = {p for centre in range(size // 2, side, stride)
              for p in range(centre - size // 2, min(centre + size // 2, side))}
    assert side == 19 and len(pooled) == 14
    assert shape["pooled_positions"] == len(pooled) ** 2 == 196
    assert "196" in shape["pooled_positions_why"]
    assert shape["filters_a_block"] == CONFIG["filters_a_block"] == 2048
    assert shape["image_floats"] == 24 * 24 * 3 == 1728
    assert shape["pools"] == 1 and shape["block_size"] == 4096
    # one block of all rows and its centred copy: more than a chip has
    assert 2 * 4 * shape["rows"] * shape["block_size"] > 16e9
    assert "device_memory_bytes" not in {k for k in CONFIG if k != "rehearsal"}
    small = {**CONFIG, **CONFIG["rehearsal"]}
    crops = small["train_rows"] * small["crops_a_train_image"]
    memory = small["device_memory_bytes"]
    # the gather streams and a block of all rows does not fit
    assert 4 * crops * 2 * small["num_filters"] > 0.5 * memory
    assert 2 * 4 * crops * small["block_size"] > 0.5 * memory
    assert small["block_size"] == 2 * small["filters_a_block"]
    for real, cfg, chunk in ((CONFIG["real_fit"], CONFIG, 15872),
                             (small["real_fit"], small, 256)):
        blocks = -(-cfg["num_filters"] // cfg["filters_a_block"])
        rows = cfg["train_rows"] * cfg["crops_a_train_image"]
        assert real["stream_fits"] == 1 and real["materialised_fits"] == 0
        assert real["row_chunks"] == -(-rows // chunk)
        assert real["rows"] == rows
        # at least a sweep that makes a block once an epoch and the test
        # crops' apply; at most a second generation a block for the
        # update of P (a block never held)
        assert real["blocks_generated_min"] == blocks + blocks
        assert real["blocks_generated_max"] == 2 * blocks + blocks
    assert CONFIG["real_fit"]["row_chunks"] == 32
    assert CONFIG["real_fit"]["maker"] == ["pallas"]
    for key in ("limits", "limits_why", "assumed", "deployment", "control",
                "guarantees", "crops", "sizing"):
        assert CONFIG[key] and CONFIG[key] != "TBD", key
    assert set(CONFIG["limits"]) == set(small["limits"]) == {
        "crops_off", "filters_gap", "features_gap", "weights_gap",
        "weights_gap_ratio", "test_scores_gap", "test_scores_gap_ratio",
        "voted_scores_gap", "test_error_gap"}
    assert CONFIG["limits"]["crops_off"] == 0.0
    assert CONFIG["control"]["env"] == {
        "KEYSTONE_SOLVER_PRECISION": "high",
        "BENCH_FEATURE_CONTROL": "bf16_output"}


def test_the_chip_takes_the_rows_in_the_chunks_the_file_states(monkeypatch):
    from keystone_tpu.analysis import resources

    monkeypatch.setattr(resources, "device_memory_bytes",
                        lambda free=False: 15.75 * 2 ** 30)
    chunk = resources.stream_row_chunk(500000, 4096)
    assert -(-500000 // chunk) == CONFIG["real_fit"]["row_chunks"]
    assert resources.stream_row_chunk(100000, 4096) is None   # test crops


# -- the counts --------------------------------------------------------------------

def test_counts_at_the_cell_size():
    counts = load_module("counts", "conv_rectify_pool")
    shape = CONFIG["solve_shape"]
    args = (shape["rows"], shape["test_rows"], shape["filters"],
            shape["pooled_positions"], shape["patch_dim"], shape["pools"],
            shape["epochs"])
    geometry = {k: shape[k] for k in ("filters_a_block", "image_floats")}
    got = counts.fit_counts(*args, **geometry)
    # 0.42 GFLOP a crop over the 196 positions its one region pools,
    # 600,000 crops: 2.5e14, 2.7 times cifar_refit's
    assert got["product_flops"] == 2.0 * 196 * 108 * 10000 * 600000
    # a crop read once a block of 2,048 filters (5 x 1,728 floats) and
    # its 20,000 features written once; cifar_refit's defaults (20 blocks
    # of 512 filters, 3,072 floats) would count 1.9 times the bytes
    assert got["bytes"] == 4 * 600000 * (5 * 1728 + 2.0 * 10000)
    assert counts.fit_counts(*args)["bytes"] == 4 * 600000 * (
        20 * 3072 + 2.0 * 10000)
    seconds, bound = counts.roofline_seconds(
        PEAKS, *args, precision=shape["conv_precision"], **geometry)
    assert bound == "compute"
    assert seconds == pytest.approx(got["product_flops"] / 197e12)
    # compute-bound at either count of bytes (by 15 and by 5), so the
    # stated geometry moves no share: the product's time is the least
    assert seconds > 15 * got["bytes"] / 819e9
    assert seconds > 5 * counts.fit_counts(*args)["bytes"] / 819e9
    bcd = load_module("counts", "streamed_bcd")
    grams = bcd.fit_flops(500000, 0, 4096, 5, 10, 1)["gram"]
    assert grams == pytest.approx(5 * 500000 * 4096 * 4097)


# -- the readers ------------------------------------------------------------------

def make_run(tmp_path, trace_data=None, fits=2):
    run = Run(cell={"name": CELL, "config": "cifar_random_patch_aug_10k"},
              cfg=dict(CONFIG), traffic={}, seed=0, seconds=1.0, trace=True,
              rehearsal=False, control=False, workdir=str(tmp_path),
              say=lambda text: None, spans=Spans(), peaks=PEAKS)
    run.trace_data = trace_data
    if fits is not None:
        run.facts["fits"] = fits
    return run


def hand_trace():
    """A window of 40 s; two fits, each: augmentation programs of 0.2 +
    0.1 + 0.3 s, then a factor sweep of 8 s over two blocks (a loop over
    two row chunks of 1.5 s each that holds the kernel's call and the
    write into the held block, then 1 s of sums over the chunks in loops
    of their own), the test crops' augmentation of 0.1 s and an apply of
    2 s whose blocks are whole (the kernel's call of 0.8 s, twice, inside
    the one scan over blocks)."""
    s = 1e9
    modules, ops = [], []
    for t0 in (0.0, 20.0):
        modules += [("jit_random_patches", (t0 + 0.1) * s, (t0 + 0.3) * s),
                    ("jit_random_transform", (t0 + 0.3) * s, (t0 + 0.4) * s),
                    ("jit_vectorize_images", (t0 + 0.4) * s, (t0 + 0.7) * s),
                    ("jit__stream_factor", (t0 + 1.0) * s, (t0 + 9.0) * s),
                    ("jit_center_corner_patches", (t0 + 9.0) * s,
                     (t0 + 9.1) * s),
                    ("jit__stream_apply", (t0 + 9.5) * s, (t0 + 11.5) * s),
                    ("jit_other", (t0 + 11.5) * s, (t0 + 11.6) * s)]
        ops.append(("while.3", (t0 + 1.0) * s, (t0 + 9.0) * s))    # blocks
        for b in range(2):
            at = t0 + 1.0 + 4.0 * b
            ops.append(("while.4", at * s, (at + 3.0) * s))        # chunks
            for i in range(2):
                ops.append(("fused_cifar_featurize.5", (at + 1.5 * i) * s,
                            (at + 1.5 * i + 1.3) * s))
                ops.append(("dynamic-update-slice.9",
                            (at + 1.5 * i + 1.3) * s, (at + 1.5 * i + 1.5) * s))
            for j in range(2):                                      # sums
                ops.append((f"while.{6 + j}", (at + 3.0 + 0.5 * j) * s,
                            (at + 3.5 + 0.5 * j) * s))
                ops.append(("fusion.12", (at + 3.0 + 0.5 * j) * s,
                            (at + 3.5 + 0.5 * j) * s))
        ops.append(("while.42", (t0 + 9.5) * s, (t0 + 11.5) * s))
        for b in range(2):
            at = t0 + 9.5 + 1.0 * b
            ops.append(("fused_cifar_featurize.2", at * s, (at + 0.8) * s))
            ops.append(("fusion.30", (at + 0.8) * s, (at + 1.0) * s))
    return xplane.Trace([xplane.DeviceTrace(0, modules, ops)],
                        [("window", 0.0, 40 * s)])


def read(name, run):
    return load_module("layers", name).read(run)


def test_device_readers_on_a_hand_built_trace_of_a_chunked_sweep(tmp_path):
    run = make_run(tmp_path, hand_trace())
    # the maker is the loop over row chunks (2 x 3 s a fit) and, where
    # the rows are whole, the scan that holds the call (2 s)
    assert read("conv_dev_ms.cifar", run) == pytest.approx(8000.0)
    # the factor sweep takes 8 s, 6 of them the maker's
    assert read("stream_solve_dev_ms.cifar", run) == pytest.approx(2000.0)
    assert read("augment_dev_ms.cifar_aug", run) == pytest.approx(700.0)
    counts = load_module("counts", "conv_rectify_pool")
    least, _ = counts.roofline_seconds(
        PEAKS, 500000, 100000, 10000, 196, 108, 1, 1)
    assert read("conv_roofline.cifar", run) == pytest.approx(
        100 * least / 8.0)
    # the count is the configuration's: a file that states no pooled
    # positions is read on all it convolves, as cifar_refit's is
    unpooled = make_run(tmp_path, hand_trace())
    unpooled.cfg["solve_shape"] = {
        k: v for k, v in CONFIG["solve_shape"].items()
        if k != "pooled_positions"}
    assert read("conv_roofline.cifar", unpooled) == pytest.approx(
        read("conv_roofline.cifar", run) * 361 / 196)
    assert 0 < read("conv_roofline.cifar", run) < 100
    bcd = load_module("counts", "streamed_bcd")
    flops = sum(bcd.fit_flops(500000, 0, 4096, 4, 10, 1).values()) + sum(
        bcd.fit_flops(500000, 0, 3616, 1, 10, 1).values())
    assert read("stream_solve_roofline.cifar", run) == pytest.approx(
        100 * (6 * flops / 197e12) / 2.0)
    assert 0 < read("stream_solve_roofline.cifar", run) < 100


def test_the_augmentation_reader_finds_nothing_in_another_apps_trace(
        tmp_path):
    assert read("augment_dev_ms.cifar_aug", make_run(tmp_path)) is None
    assert read("augment_dev_ms.cifar_aug", make_run(
        tmp_path, plain_tests.hand_trace())) is None
    assert read("augment_dev_ms.cifar_aug", make_run(
        tmp_path, hand_trace(), fits=None)) is None


def test_host_and_counter_readers(tmp_path, monkeypatch):
    from keystone_tpu.observability import timeline

    holder = types.SimpleNamespace(items=[], lost=0)
    fake = types.SimpleNamespace(spans=lambda: list(holder.items),
                                 dropped=lambda: holder.lost)
    monkeypatch.setattr(timeline, "flight_recorder", lambda: fake)
    run = make_run(tmp_path)
    assert read("vote_host_s.cifar_aug", run) is None       # no fit spans
    run.spans.records += [("fit", 10.0, 14.0), ("fit", 15.0, 19.0)]

    def span(cat, name, start, dur, tid=None):
        return types.SimpleNamespace(
            ph="X", cat=cat, name=name, start_s=start, dur_s=dur, args=None,
            tid=threading.main_thread().ident if tid is None else tid)

    holder.items = [
        span("eval", "vote", 9.0, 2.0),                     # the warming fit
        span("eval", "vote", 13.5, 0.2),
        span("eval", "evaluate", 13.6, 0.05),               # inside the vote
        span("solve", "fit:BlockLeastSquaresEstimator", 11.3, 0.01),
        span("eval", "vote", 18.5, 0.4),
        span("eval", "vote", 18.5, 9.0, tid=-1),            # another thread
    ]
    assert read("vote_host_s.cifar_aug", run) == pytest.approx(0.3)
    holder.items = [span("solve", "fit:X", 11.3, 0.01)]
    assert read("vote_host_s.cifar_aug", run) is None       # no such span

    job = load_module("configs", "cifar_random_patch_aug_10k")
    monkeypatch.setattr(job, "FIT_COUNTS", [
        {"blocks_generated": 99.0, "row_chunks": 1.0},
        {"blocks_generated": 10.0, "row_chunks": 32.0},
        {"blocks_generated": 10.0, "row_chunks": 32.0}])
    assert read("row_chunks.cifar_aug", run) == pytest.approx(32.0)
    assert read("blocks_generated.cifar", run) == pytest.approx(10.0)
    monkeypatch.setattr(job, "FIT_COUNTS", [{"blocks_generated": 10.0}] * 2)
    assert read("row_chunks.cifar_aug", run) is None        # not counted
    monkeypatch.setattr(job, "FIT_COUNTS", [{"row_chunks": 32.0}])
    assert read("row_chunks.cifar_aug", run) is None        # fewer than fits


# -- the reference's crops and vote against the program's nodes ---------------------

def test_the_reference_redraws_the_programs_crops_byte_for_byte():
    import jax

    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel.dataset import ArrayDataset
    from keystone_tpu.parallel.mesh import make_mesh, mesh_scope
    from keystone_tpu.pipelines.images.cifar import (
        random_patch_cifar_augmented as app)

    reference = load_module("reference", "cifar_random_patch_aug_10k")
    (pixels, labels), (test_pixels, test_labels) = load_module(
        "datagen", "cifar_images").make_images(24, 6, 2 ** 31 + 5)
    seed = 2 ** 31 + 5
    with mesh_scope(make_mesh(jax.devices()[:1])):
        def held(px, y):
            return LabeledData(
                data=ArrayDataset.from_numpy(px.astype(np.float32)),
                labels=ArrayDataset.from_numpy(y.astype(np.int32)))

        crops, indicators = app.augment_train(
            app.AugmentedConfig(seed=seed), held(pixels, labels))
        tens, ids, tens_labels = app.augment_test(
            held(test_pixels, test_labels))
        crops, indicators, tens = (
            crops.numpy(), indicators.get().numpy(), tens.numpy())
    want = reference.train_crops(CONFIG, pixels, seed)
    assert want.dtype == np.uint8 and want.shape == (240, 24, 24, 3)
    assert reference._bytes_off(crops, want) == 0
    # about half are mirrored, and a row redrawn alone is the same row
    _, _, mirrored = reference.train_offsets(CONFIG, 24, seed)
    assert 80 < mirrored.sum() < 160
    rows = np.array([3, 77, 239])
    assert np.array_equal(
        reference.train_crops(CONFIG, pixels, seed, rows), want[rows])
    assert np.array_equal(np.argmax(indicators, axis=1),
                          np.repeat(labels, 10))
    assert reference._bytes_off(tens, reference.test_crops(
        CONFIG, test_pixels)) == 0
    assert np.array_equal(ids, np.repeat(np.arange(6), 10))
    assert np.array_equal(tens_labels, np.repeat(test_labels, 10))
    # a crop off by one pixel is counted, a wrong shape is all of them
    broken = crops.copy()
    broken[5, 100] += 1.0
    assert reference._bytes_off(broken, want) == 1
    assert reference._bytes_off(crops[:, :100], want) == want.size


def test_the_references_vote_is_the_evaluators():
    from keystone_tpu.evaluation.augmented import evaluate_augmented

    reference = load_module("reference", "cifar_random_patch_aug_10k")
    rng = np.random.default_rng(2)
    scores = rng.normal(size=(70, 10))
    labels = rng.integers(0, 10, size=7)
    theirs = evaluate_augmented(np.repeat(np.arange(7), 10), scores,
                                np.repeat(labels, 10), 10)
    assert reference.voted_error(scores, labels, 10) == pytest.approx(
        theirs.total_error)


def test_the_references_sums_carry_what_float32_drops(monkeypatch):
    """Columns whose means stand far over their deviations, more rows
    than a term takes and a ragged last term: the step's weights are
    float64's to float32's last digits, which a float32 sum of the same
    rows is not."""
    import jax.numpy as jnp

    reference = load_module("reference", "cifar_random_patch_aug_10k")
    monkeypatch.setattr(reference, "ROWS_A_SUM", 64)
    monkeypatch.setattr(reference, "ROWS_A_CHUNK", 1000)
    rng = np.random.default_rng(45)
    held = (np.abs(rng.normal(size=(4133, 24))) + 300.0).astype(np.float32)
    residual = rng.normal(size=(4133, 5)).astype(np.float32)
    W, mean, std, moved = reference.block_step(
        jnp.asarray(held), jnp.asarray(residual), 3.0)
    X = held.astype(np.float64)
    want_mean = X.mean(axis=0)
    # the centred squares about the float32 mean the step centres by
    want_std = np.sqrt(((X - np.asarray(mean, np.float64)) ** 2).sum(axis=0)
                       / 4132)
    A = (X - np.asarray(mean, np.float64)) / np.asarray(std, np.float64)
    want_W = np.linalg.solve(A.T @ A + 3.0 * np.eye(24),
                             A.T @ residual.astype(np.float64))
    gap = reference._block_ls.rel_gap
    assert gap(mean, want_mean) < 1e-7 and gap(std, want_std) < 1e-7
    assert gap(W, want_W) < 3e-7
    assert gap(moved, A @ want_W) < 1e-6
    # two floats carry a sum that one drops digits of: 50,000 terms of
    # 4 + 2 ** -16 each (exact in float32) sum to a number of 34 bits
    monkeypatch.setattr(reference, "ROWS_A_SUM", 4)
    ones = jnp.full((200000, 1), 1.0 + 2.0 ** -18, jnp.float32)
    total, low = reference._column_sums(ones)
    (carried,) = reference._as_doubles((total, low))
    assert carried[0] == 200000 * (1.0 + 2.0 ** -18)
    assert float(total[0][0]) != carried[0]


# -- the cell at the rehearsal size: its controls, its faults ------------------------

def rehearse(*extra, **kwargs):
    return rehearsals.rehearse(CELL, *extra, **kwargs)


failed = rehearsals.failed


def test_the_features_control_fails_the_features_part_and_no_other(
        monkeypatch):
    monkeypatch.setenv("BENCH_FEATURE_CONTROL", "bf16_output")
    result, lines = rehearse()
    assert result["correct"] is False, "\n".join(lines[-20:])
    assert failed(lines) == {"features_gap"}


def test_the_filters_control_fails_the_filters_gap(monkeypatch):
    monkeypatch.setenv("BENCH_FEATURE_CONTROL", "bf16_filters")
    result, lines = rehearse()
    assert result["correct"] is False, "\n".join(lines[-20:])
    assert "filters_gap" in failed(lines)
    assert failed(lines) <= {"filters_gap", "test_error_gap"}


def test_the_control_flag_degrades_both_parts():
    result, lines = rehearse("--control")
    assert "CONTROL (not a measurement)" in lines[0]
    assert "KEYSTONE_SOLVER_PRECISION" in lines[0]
    assert result["correct"] is False and "features_gap" in failed(lines)


def test_a_row_chunk_left_out_of_the_gram_is_not_correct():
    result, lines = rehearse(script=FAULT)
    assert result["correct"] is False, "\n".join(lines[-20:])
    assert {"rows_solved_off", "weights_gap"} <= failed(lines)
    # the crops, the filters and the features are as they were
    assert not failed(lines) & {"crops_off", "filters_gap", "features_gap"}


def test_one_crop_voting_for_its_image_is_not_correct():
    result, lines = rehearse(script=VOTE_FAULT)
    assert result["correct"] is False, "\n".join(lines[-20:])
    assert "voted_scores_gap" in failed(lines)
    # the solve's own scores are as they were: the fault is the vote's
    assert not failed(lines) & {"weights_gap", "test_scores_gap",
                                "features_gap", "rows_solved_off"}
