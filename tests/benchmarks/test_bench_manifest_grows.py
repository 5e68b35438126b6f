"""The point of ISSUE 32: the next cell appends to ``BENCHMARK.json`` and
breaks no test that is there. Every cell's test file (and
``test_bench_program_spans.py``, for PR 24's seven readers) states what
it holds of the manifest in a ``manifest_holds(manifest)``; here each
runs on a copy with what the next ``model_config`` PR adds appended at
the ends (``manifest_checks.grown``), which must pass, and on that copy
with an older entry moved, renamed or dropped, which must not."""
import pytest

import manifest_checks
import test_bench_cifar_aug_refit
import test_bench_cifar_refit
import test_bench_mnist_refit
import test_bench_mnist_refit_x4
import test_bench_program_spans
import test_bench_timit_refit
import test_bench_voc_refit

HOLDERS = [test_bench_mnist_refit, test_bench_timit_refit,
           test_bench_cifar_refit, test_bench_program_spans,
           test_bench_voc_refit, test_bench_mnist_refit_x4,
           test_bench_cifar_aug_refit]
MANIFEST = manifest_checks.load_manifest()


def all_hold(manifest):
    for module in HOLDERS:
        module.manifest_holds(manifest)


def test_what_is_held_is_there_today():
    all_hold(MANIFEST)


def test_a_fourth_configuration_cell_and_entry_at_the_ends_break_nothing():
    more = manifest_checks.grown(MANIFEST)
    assert more["configs"][-1]["name"] == "a_fourth_config"
    assert more["workloads"][-1]["name"] == "a_fourth_cell"
    assert more["per_layer"][-1]["workloads"] == ["a_fourth_cell"]
    widened = [m["name"] for m in more["per_layer"]
               if m["workloads"][-1] == "a_fourth_cell"][:-1]
    assert len(widened) >= 8 and "h2d_mb.refit" in widened
    for key in ("configs", "workloads", "per_layer"):
        assert len(more[key]) == len(MANIFEST[key]) + 1
    assert more["end_to_end"] == MANIFEST["end_to_end"]
    all_hold(more)


def move_to_end(entries, name):
    entry = manifest_checks.named(entries, name)
    entries.remove(entry)
    entries.append(entry)


def rename(entries, name):
    manifest_checks.named(entries, name)["name"] = name + ".renamed"


def drop(entries, name):
    entries.remove(manifest_checks.named(entries, name))


#: (what is done to the grown copy, to which list, to which older entry)
DAMAGE = [
    (move_to_end, "configs", "timit_50x4096"),
    (move_to_end, "workloads", "mnist_refit"),
    (move_to_end, "per_layer", "optimize_host_s.refit"),
    (move_to_end, "per_layer", "stream_solve_roofline.timit"),
    (rename, "configs", "cifar_random_patch_10k"),
    (rename, "workloads", "timit_refit"),
    (rename, "per_layer", "featurize_roofline.refit"),
    (rename, "per_layer", "conv_roofline.cifar"),
    (drop, "configs", "mnist_random_fft_32"),
    (drop, "workloads", "cifar_refit"),
    (drop, "per_layer", "dag_host_s.refit"),
    (drop, "per_layer", "blocks_generated.timit"),
    (drop, "end_to_end", "setup_s"),
]


@pytest.mark.parametrize(
    "damage,key,name", DAMAGE,
    ids=[f"{d.__name__}-{k}-{n}" for d, k, n in DAMAGE])
def test_an_older_entry_moved_renamed_or_dropped_fails(damage, key, name):
    broken = manifest_checks.grown(MANIFEST)
    damage(broken[key], name)
    with pytest.raises(AssertionError):
        all_hold(broken)


@pytest.mark.parametrize("name,cell", [
    ("host_wait_s.refit", "timit_refit"), ("h2d_mb.refit", "cifar_refit"),
    ("loader_s.setup", "cifar_refit"), ("span_coverage_pct.refit",
                                        "mnist_refit")])
def test_a_cell_taken_off_an_entry_it_relies_on_fails(name, cell):
    broken = manifest_checks.grown(MANIFEST)
    manifest_checks.named(broken["per_layer"], name)["workloads"].remove(cell)
    with pytest.raises(AssertionError):
        all_hold(broken)


def test_a_changed_bound_or_reduction_fails():
    for change in (
            lambda m: manifest_checks.named(
                m["end_to_end"], "refit_items_per_s").update(bound=0.05),
            lambda m: manifest_checks.named(
                m["configs"], "timit_50x4096")["reduced"].remove("env"),
            lambda m: manifest_checks.named(
                m["workloads"], "cifar_refit").update(chips=4)):
        broken = manifest_checks.grown(MANIFEST)
        change(broken)
        with pytest.raises(AssertionError):
            all_hold(broken)
