"""The cell ``voc_refit`` (ISSUE 33): what the manifest holds of it, the
counts ``counts/dense_sift.py`` and ``counts/fisher_vector.py`` against
hand counts, each of its readers on a hand-built run whose answer is
known, the seeded images and their files, the configuration's file, and
the reference's own ``check()`` on answers that are the reference's, a
count one outside each bound. (Its fault, half of the training images
left out of the loader it reads with, is a file of
``tests/benchmarks/faults/`` and runs from
``test_bench_rehearsal_voc_refit.py`` with its rehearsal and its solver
control, as every cell's do from its own.)
"""
import os
import threading
import types

import numpy as np
import pytest

import manifest_checks
from benchmarks import xplane
from benchmarks.harness import Run, load_json, load_module
from benchmarks.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = manifest_checks.load_manifest()
CONFIG = load_json(os.path.join(
    ROOT, "benchmarks", "configs", "voc_sift_fisher_256.json"))
# accepted metrics whose readers find something to read in the cell
WIDENED = ["loader_s.setup", "to_device_s.refit", "dag_host_s.refit",
           "solve_dev_ms.refit", "solve_roofline.refit",
           "device_idle_pct.refit", "hbm_peak_gib.refit",
           "optimize_host_s.refit", "host_wait_s.refit", "h2d_mb.refit"]
LAYERS = {name: "featurize kernels" for name in (
    "sift_dev_ms.voc", "sift_roofline.voc", "fv_dev_ms.voc",
    "fv_roofline.voc", "gmm_fit_s.voc", "pca_fit_s.voc", "sift_passes.voc")}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the manifest ---------------------------------------------------------------

def manifest_holds(manifest):
    manifest_checks.cell_is_held(
        manifest, cell="voc_refit", config="voc_sift_fisher_256",
        traffic="fit_in_memory", chips=1,
        reduced=["train_rows", "test_rows", "env"],
        configs_before=["mnist_random_fft_32", "timit_50x4096",
                        "cifar_random_patch_10k"],
        cells_before=["mnist_refit", "timit_refit", "cifar_refit"],
        per_layer=WIDENED + list(LAYERS),
        end_to_end={"refit_items_per_s": 0.029, "setup_s": 0.1})
    source = manifest_checks.named(
        manifest["configs"], "voc_sift_fisher_256")["source"]
    assert "VOCSIFTFisher.scala" in source and "defaults" in source
    assert manifest["run_seconds"] == 40


def test_the_manifest_holds_the_configuration_the_cell_and_its_readers():
    manifest_holds(MANIFEST)
    # at least the seventeen it came with, by name (a later PR may put
    # the cell on readers that find something to read in it)
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if "voc_refit" in m["workloads"]}
    assert len(WIDENED) + len(LAYERS) == 17
    assert listed >= set(WIDENED) | set(LAYERS)
    # nothing the benchmark had was moved: the cell's seven stand after
    # every entry of the cells before it
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index("sift_dev_ms.voc") > names.index(
        "blocks_generated.cifar")


def test_the_next_cell_breaks_nothing_here_and_damage_is_seen():
    more = manifest_checks.grown(MANIFEST)
    manifest_holds(more)
    for damage in (
            lambda m: m["workloads"].remove(
                manifest_checks.named(m["workloads"], "voc_refit")),
            lambda m: manifest_checks.named(
                m["per_layer"], "sift_roofline.voc").update(unit="ms"),
            lambda m: manifest_checks.named(
                m["per_layer"], "solve_dev_ms.refit")["workloads"].remove(
                    "voc_refit"),
            lambda m: manifest_checks.named(
                m["configs"], "voc_sift_fisher_256")["reduced"].append(
                    "vocab_size")):
        broken = manifest_checks.grown(MANIFEST)
        damage(broken)
        with pytest.raises(AssertionError):
            manifest_holds(broken)


def test_the_cells_own_entries_say_their_layer_and_double_no_reader():
    manifest_checks.own_entries_are_held(MANIFEST, LAYERS, ".voc")


def test_the_configuration_states_the_sources_widths_uncut():
    assert CONFIG["architecture"] is None
    source = {"desc_dim": 80, "vocab_size": 256, "lambda": 0.5,
              "scale_step": 0, "block_size": 4096,
              "num_pca_samples": 1000000, "num_gmm_samples": 1000000,
              "num_classes": 20, "sift_step": 4, "sift_bin_size": 6,
              "sift_num_scales": 5, "gmm_max_iterations": 100,
              "gmm_stop_tolerance": 1e-4}
    assert {k: CONFIG[k] for k in source} == source
    assert CONFIG["fisher_columns"] == 2 * 80 * 256 == 40960
    assert list(CONFIG["reduced_why"]) == ["train_rows", "test_rows", "env"]
    assert CONFIG["train_rows"] == CONFIG["test_rows"]
    assert CONFIG["train_rows"] in (5011, 2048, 1024, 512)
    shape = CONFIG["solve_shape"]
    assert (shape["rows"], shape["features"], shape["block_size"],
            shape["classes"]) == (CONFIG["train_rows"], 40960, 4096, 20)
    assert (CONFIG["long_side"], CONFIG["common_sides"],
            CONFIG["short_side_min"]) == (500, [375, 333], 250)
    for key in ("sizes", "data", "labels"):
        assert key in CONFIG["assumed"]
    assert CONFIG["control"]["env"] == {
        "KEYSTONE_SOLVER_PRECISION": "high",
        "BENCH_FEATURE_CONTROL": "one_pass"}
    assert not CONFIG["whole_chain"] and CONFIG["rehearsal"]["whole_chain"]
    assert "test_error_gap" not in CONFIG["limits"]
    for real in (CONFIG["real_fit"], CONFIG["rehearsal"]["real_fit"]):
        assert (real["pca_fits"], real["gmm_fits"]) == (1, 1)
        assert real["sift_passes_max"] == 3
        assert set(real["maker"]) == {"sift", "fv"}
    app = load_module("configs", "voc_sift_fisher_256")
    job = types.SimpleNamespace(cfg=CONFIG, seed=2 ** 31 + 11)
    made = app.Job.app_config(job)
    assert (made.desc_dim, made.vocab_size, made.lam, made.block_size,
            made.num_pca_samples, made.seed) == (
                80, 256, 0.5, 4096, 1000000, 2 ** 31 + 11)


# -- the counts -------------------------------------------------------------------

def test_dense_sift_counts_against_a_hand_count():
    counts = load_module("counts", "dense_sift")
    # 375 x 500, scale 0: bin 6, lower bound 11: rows (374 - 12 - 23) // 4
    # + 1 = 85, columns (499 - 12 - 23) // 4 + 1 = 117
    assert counts.grid(375, 0, 4, 6, 5, 0) == 85
    assert counts.grid(500, 0, 4, 6, 5, 0) == 117
    assert counts.grid(40, 4, 4, 6, 5, 0) == 0     # the box does not fit
    by_hand = 0.0
    for s, (ny, nx) in enumerate(
            [(85, 117), (84, 115), (83, 114), (82, 113), (80, 111)]):
        assert (counts.grid(375, s, 4, 6, 5, 0),
                counts.grid(500, s, 4, 6, 5, 0)) == (ny, nx)
        by_hand += (2 * 375 * 375 * 500 + 2 * 375 * 500 * 500
                    + 2 * 8 * 4 * ny * 375 * 500
                    + 2 * 8 * 4 * ny * 500 * 4 * nx)
    assert counts.flops(375, 500) == by_hand
    assert counts.descriptors(375, 500) == 47213  # 9,945 + 9,660 + ... + 8,880
    assert 12e9 < by_hand < 13e9                  # 12.65 GFLOP an image
    shares = counts.sizes(CONFIG)
    assert sum(s for s, _, _ in shares) == pytest.approx(1.0)
    assert sum(s for s, h, w in shares if w == 500 and h < 500) == \
        pytest.approx(0.75)
    assert {(h, w) for _, h, w in shares} >= {(375, 500), (500, 333),
                                              (250, 500), (500, 499)}
    mean = counts.expected(CONFIG, counts.descriptors)
    assert 41286 < mean < 47213 + 2000
    seconds, bound = counts.roofline_seconds(PEAKS, CONFIG, 2048)
    assert bound == "compute"
    assert seconds == pytest.approx(
        2048 * counts.expected(CONFIG, counts.flops) * 3 / 197e12)
    assert CONFIG["sift_precision"] == "high"


def test_fisher_vector_counts():
    counts = load_module("counts", "fisher_vector")
    assert counts.flops(47213, 80, 256) == 8 * 47213 * 80 * 256
    assert 7.6e9 < counts.flops(47213, 80, 256) < 7.8e9
    seconds, bound = counts.roofline_seconds(PEAKS, 47213, 80, 256, 10)
    assert bound == "compute"
    assert seconds == pytest.approx(10 * 6 * 8 * 47213 * 80 * 256 / 197e12)


# -- the readers ------------------------------------------------------------------

def make_run(tmp_path, trace_data=None, fits=2, peaks=PEAKS):
    run = Run(cell={"name": "voc_refit", "config": "voc_sift_fisher_256"},
              cfg=dict(CONFIG), traffic={}, seed=0, seconds=1.0, trace=True,
              rehearsal=False, control=False, workdir=str(tmp_path),
              say=lambda text: None, spans=Spans(), peaks=peaks)
    run.trace_data = trace_data
    if fits is not None:
        run.facts.update(fits=fits, items=2 * CONFIG["train_rows"])
    return run


def hand_trace():
    """A window of 20 s, two fits: in each, three calls of the SIFT
    chunk program (1.0 s each), two of the Fisher-vector program (0.5 s),
    the mixture's EM (0.4 s) and the block solve (0.25 s)."""
    s = 1e9
    modules = []
    for t0 in (0.0, 10.0):
        for i in range(3):
            modules.append(("jit__dsift_chunk", (t0 + 1.2 * i) * s,
                            (t0 + 1.2 * i + 1.0) * s))
        modules += [("jit__em_fit", (t0 + 4.0) * s, (t0 + 4.4) * s),
                    ("jit__fisher_vector_chunk", (t0 + 5.0) * s,
                     (t0 + 5.5) * s),
                    ("jit__fisher_vector_chunk", (t0 + 5.6) * s,
                     (t0 + 6.1) * s),
                    ("jit__block_solve", (t0 + 7.0) * s, (t0 + 7.25) * s)]
    ops = [("fusion.1", a, b) for _, a, b in modules]
    return xplane.Trace([xplane.DeviceTrace(0, modules, ops)],
                        [("window", 0.0, 20 * s)])


def read(name, run):
    return load_module("layers", name).read(run)


def test_device_readers_on_a_hand_built_trace(tmp_path):
    run = make_run(tmp_path, hand_trace())
    assert read("sift_dev_ms.voc", run) == pytest.approx(3000.0)
    assert read("fv_dev_ms.voc", run) == pytest.approx(1000.0)
    assert read("solve_dev_ms.refit", run) == pytest.approx(250.0)
    sift = load_module("counts", "dense_sift")
    items = 2 * CONFIG["train_rows"]
    least, _ = sift.roofline_seconds(PEAKS, CONFIG, items)
    assert read("sift_roofline.voc", run) == pytest.approx(100 * least / 3.0)
    fv = load_module("counts", "fisher_vector")
    least, _ = fv.roofline_seconds(
        PEAKS, sift.expected(CONFIG, sift.descriptors), 80, 256, items)
    assert read("fv_roofline.voc", run) == pytest.approx(100 * least / 1.0)
    assert 0 < read("solve_roofline.refit", run) < 100
    assert read("device_idle_pct.refit", run) == pytest.approx(
        100 * (20 - 2 * 4.65) / 20)


def test_device_readers_find_nothing_in_another_programs_trace(tmp_path):
    s = 1e9
    other = xplane.Trace([xplane.DeviceTrace(
        0, [("jit__block_solve", 0.0, s)], [("fusion.1", 0.0, s)])],
        [("window", 0.0, 2 * s)])
    names = ("sift_dev_ms.voc", "sift_roofline.voc", "fv_dev_ms.voc",
             "fv_roofline.voc")
    for trace_data in (None, other):
        run = make_run(tmp_path, trace_data)
        assert [read(n, run) for n in names] == [None] * 4
    assert [read(n, make_run(tmp_path, hand_trace(), fits=None))
            for n in names] == [None] * 4
    no_peaks = make_run(tmp_path, hand_trace(), peaks=None)
    assert read("sift_roofline.voc", no_peaks) is None
    assert read("fv_roofline.voc", no_peaks) is None


def ring_span(cat, name, start, dur, tid=None):
    return types.SimpleNamespace(
        ph="X", cat=cat, name=name, start_s=start, dur_s=dur, args=None,
        tid=threading.main_thread().ident if tid is None else tid)


def test_host_and_counter_readers(tmp_path, monkeypatch):
    from keystone_tpu.observability import timeline

    holder = types.SimpleNamespace(items=[])
    fake = types.SimpleNamespace(spans=lambda: list(holder.items),
                                 dropped=lambda: 0)
    monkeypatch.setattr(timeline, "flight_recorder", lambda: fake)
    run = make_run(tmp_path)
    assert read("gmm_fit_s.voc", run) is None             # no fit spans
    run.spans.records += [("fit", 10.0, 14.0), ("fit", 15.0, 19.0)]
    holder.items = [
        ring_span("featurize", "fit_gmm", 8.0, 1.0),      # the warming fit
        ring_span("featurize", "fit_pca", 10.5, 0.2),
        ring_span("featurize", "fit_gmm", 11.0, 0.8),
        ring_span("solve", "fit:BlockLeastSquaresEstimator", 13.0, 0.1),
        ring_span("featurize", "fit_pca", 15.5, 0.4),
        ring_span("featurize", "fit_gmm", 16.0, 1.2),
        ring_span("featurize", "fit_gmm", 16.0, 9.0, tid=-1),
    ]
    assert read("gmm_fit_s.voc", run) == pytest.approx(1.0)
    assert read("pca_fit_s.voc", run) == pytest.approx(0.3)
    holder.items = [ring_span("solve", "fit:X", 11.3, 0.01)]
    assert read("gmm_fit_s.voc", run) is None             # no such span
    assert read("pca_fit_s.voc", run) is None

    job = load_module("configs", "voc_sift_fisher_256")
    items = 2 * CONFIG["train_rows"]
    monkeypatch.setattr(job, "FIT_COUNTS", [
        {"sift_images": 9e9}, {"sift_images": 1.5 * items},
        {"sift_images": 2.5 * items}])
    assert read("sift_passes.voc", run) == pytest.approx(2.0)
    monkeypatch.setattr(job, "FIT_COUNTS", [{"sift_images": 1.0}])
    assert read("sift_passes.voc", run) is None           # fewer than the fits


# -- the seeded images and their files ------------------------------------------

def test_the_images_have_the_stated_sizes_and_follow_the_seed(tmp_path):
    voc = load_module("datagen", "voc_images")
    sizes = voc.image_sizes(20000, np.random.default_rng(1), 500, (375, 333),
                            250)
    assert (sizes.max(1) == 500).all()
    short = sizes.min(1)
    assert abs((short == 375).mean() - 0.60) < 0.02
    assert abs((short == 333).mean() - 0.25) < 0.02
    tail = short[(short != 375) & (short != 333)]
    assert tail.min() >= 250 and tail.max() <= 499 and len(
        np.unique(tail)) > 200
    assert abs((sizes[:, 1] == 500).mean() - 0.75) < 0.02   # landscape
    small = dict(long_side=96, common_sides=(72, 64), short_side_min=64)
    images, labels = voc.make_images(12, 5, "train", **small)
    again, _ = voc.make_images(12, 5, "train", **small)
    other, _ = voc.make_images(12, 6, "train", **small)
    test, _ = voc.make_images(12, 5, "test", **small)
    assert all(np.array_equal(a, b) for a, b in zip(images, again))
    assert not all(a.shape == b.shape and np.array_equal(a, b)
                   for a, b in zip(images, other))
    assert not all(a.shape == b.shape and np.array_equal(a, b)
                   for a, b in zip(images, test))
    assert all(im.dtype == np.uint8 and im.shape[2] == 3 and max(
        im.shape[:2]) == 96 for im in images)
    assert all(1 <= len(own) <= 3 and own == sorted(set(own))
               and 0 <= min(own) and max(own) < 20 for own in labels)

    # the files, read by the package's loader and by the reference's
    from keystone_tpu.loaders.voc import (VOCDataPath, VOCLabelPath,
                                          voc_loader)

    tar = str(tmp_path / "train.tar")
    names = voc.write_tar(tar, images, "train")
    voc.write_labels(str(tmp_path / "labels.csv"), names, labels)
    loaded = voc_loader(VOCDataPath(tar, voc.PREFIX),
                        VOCLabelPath(str(tmp_path / "labels.csv"))).collect()
    plain = voc.read_tar(tar)
    assert len(loaded) == len(plain) == 12
    for item, decoded, original, own in zip(loaded, plain, images, labels):
        assert item.image.dtype == np.uint8
        np.testing.assert_array_equal(item.image, decoded)
        assert sorted(item.labels) == own
        # JPEG at quality 90 over noise: near, not equal
        assert 0 < np.abs(decoded.astype(int) - original).mean() < 12


# -- the reference's check, on answers that are the reference's own --------------

@pytest.fixture(scope="module")
def perfect():
    """A tiny configuration with ``real_fit`` as the timed size states
    it, seeded inputs, and answers computed by the reference itself."""
    ref = load_module("reference", "voc_sift_fisher_256")
    voc = load_module("datagen", "voc_images")
    cfg = dict(CONFIG, **{k: v for k, v in CONFIG["rehearsal"].items()
                          if k not in ("real_fit", "limits")})
    cfg.update(desc_dim=6, vocab_size=3, block_size=16, whole_chain=False,
               sampled_images=3)
    small = dict(long_side=96, common_sides=(72, 64), short_side_min=64)
    train = voc.make_images(6, 3, "train", **small)
    test = voc.make_images(4, 3, "test", **small)
    inputs = {"train": train, "test": test, "seed": 3}
    sift = dict(step=4, bin_size=6, num_scales=5, scale_step=0)
    described = [ref.dense_sift(ref.gray_of(im), **sift) for im in train[0]]
    picks = [ref.sampled_columns(d.shape[1], 40, 3, i)
             for i, d in enumerate(described)]
    pca_sample = np.stack([d[:, p] for d, p in zip(described, picks)])
    basis = ref.pca_basis(ref.columns_as_rows(pca_sample), 6)
    reduced = [basis.T @ d for d in described]
    gmm_sample = np.stack([r[:, ref.sampled_columns(r.shape[1], 40, 4, i)]
                           for i, r in enumerate(reduced)])
    rows = ref.columns_as_rows(gmm_sample)
    rng = np.random.default_rng(3)
    initial = (rows[rng.choice(len(rows), 3, replace=False)],
               np.tile(rows.var(0), (3, 1)), np.full(3, 1 / 3))
    params = ref.em(rows, initial, 3, cfg)

    def design(images):
        return np.stack([ref.normalised_row(ref.fisher_vector(
            basis.T @ ref.dense_sift(ref.gray_of(im), **sift), params, 1e-4))
            for im in images]).astype(np.float32)

    train_design, test_design = design(train[0]), design(test[0])
    targets = ref.targets_of(train[1], 20)
    W, means, intercept, scores = ref.block_least_squares(
        train_design, targets, test_design, 16, 0.5)
    answers = dict(
        sampled=[{"id": i, "descriptors": described[i],
                  "reduced": reduced[i]} for i in (0, 2, 5)],
        pca_sample=pca_sample, pca_mat=basis, gmm_sample=gmm_sample,
        gmm=(params[0].T, params[1].T, params[2]), gmm_initial=initial,
        gmm_updates=3, train_design=train_design, test_design=test_design,
        train_labels=targets, weights=W, feature_means=means,
        intercept=intercept, test_scores=scores,
        map=float(ref.average_precisions(test[1], scores, 20).mean()),
        pca_fits=1.0, gmm_fits=1.0, fv_images=10.0, sift_images=22.0,
        gmm_iterations=4.0, maker=CONFIG["real_fit"]["maker"])
    return ref, cfg, inputs, answers


def wrong(ref, cfg, inputs, answers):
    return {name for name, value, limit in ref.check(cfg, inputs, answers)
            if not value <= limit}


def test_the_references_own_answers_are_correct(perfect):
    ref, cfg, inputs, answers = perfect
    checks = ref.check(cfg, inputs, answers)
    names = [name for name, _, _ in checks]
    assert names[:11] == ["sift_gap", "projection_gap", "fv_gap", "pca_gap",
                          "gmm_gap", "loglik_gap", "weights_gap",
                          "test_scores_gap", "apply_gap", "labels_gap",
                          "map_gap"]
    assert "test_error_gap" not in names
    assert wrong(ref, cfg, inputs, answers) == set()


@pytest.mark.parametrize("count,value,check", [
    ("sift_images", 9.0, "sift_passes_off"),     # an image not described
    ("sift_images", 10.0, None),                 # every image once: the least
    ("sift_images", 22.0, None),                 # 3 x 6 + 4: the most
    ("sift_images", 23.0, "sift_passes_off"),
    ("gmm_iterations", 0.0, "em_iterations_off"),
    ("gmm_iterations", 1.0, None),
    ("gmm_iterations", 100.0, None),
    ("gmm_iterations", 101.0, "em_iterations_off"),
    ("fv_images", 9.0, "fv_images_off"),
    ("fv_images", 11.0, "fv_images_off"),
    ("pca_fits", 0.0, "pca_fits_off"),
    ("pca_fits", 2.0, "pca_fits_off"),
    ("gmm_fits", 2.0, "gmm_fits_off"),
    ("maker", {"sift": ["banded", "einsum"], "fv": ["pallas"]}, "maker_off"),
])
def test_a_count_one_outside_its_bound_is_not_correct(
        perfect, count, value, check):
    ref, cfg, inputs, answers = perfect
    assert wrong(ref, cfg, inputs, dict(answers, **{count: value})) == (
        {check} if check else set())


def test_a_wrong_stage_fails_its_own_gap_and_no_other(perfect):
    ref, cfg, inputs, answers = perfect
    worse = [dict(item) for item in answers["sampled"]]
    worse[1]["descriptors"] = worse[1]["descriptors"] * 1.01
    assert wrong(ref, cfg, inputs, dict(answers, sampled=worse)) == {
        "sift_gap", "projection_gap"}
    basis = np.array(answers["pca_mat"])
    basis[:, 0] = np.roll(basis[:, 0], 1)
    assert "pca_gap" in wrong(ref, cfg, inputs, dict(answers, pca_mat=basis))
    means, variances, weights = answers["gmm"]
    assert wrong(ref, cfg, inputs, dict(
        answers, gmm=(means * 1.2, variances, weights))) >= {"gmm_gap"}
    # a wrong model is seen as a model and in prediction space, and its
    # own application of itself is no longer what the scores are
    assert wrong(ref, cfg, inputs, dict(
        answers, weights=answers["weights"] * 1.01)) == {
            "weights_gap", "test_scores_gap", "apply_gap"}
    assert wrong(ref, cfg, inputs, dict(
        answers, test_scores=answers["test_scores"] * 1.01)) >= {"apply_gap"}
    assert wrong(ref, cfg, inputs, dict(
        answers, map=answers["map"] + 0.01)) == {"map_gap"}
