"""What a cell's test file holds of ``BENCHMARK.json``, as functions of a
manifest: membership, relative order and content. Nothing here speaks of
the END of a list or of a list's whole length, because later PRs append
to every list and may edit no file that is there: a pin on ``configs[-1]``
or an exact list of ``per_layer`` names is true only until the next cell
(PRs 26 and 30 each broke the tests of the cell before them that way).

A cell's test file states what it relies on in a ``manifest_holds(
manifest)`` of its own that calls :func:`cell_is_held`; the next cell's
file does the same and adds no pin. ``test_bench_manifest_grows.py``
runs every file's ``manifest_holds`` on a copy grown by :func:`grown`,
which must pass, and on copies with an older entry moved, renamed or
dropped, which must not.
"""
import copy
import os

from benchmarks.harness import HERE, ROOT, load_json, load_module

ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}


def load_manifest():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def named(entries, name):
    """The one entry of a list that has this name."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, f"{name!r} is listed {len(found)} times"
    return found[0]


def in_order(entries, names):
    """``names`` are all listed, in this order among themselves (anything
    may stand between, before or after them)."""
    listed = [e["name"] for e in entries]
    for name in names:
        assert listed.count(name) == 1, f"{name!r}: {listed.count(name)} times"
    at = [listed.index(name) for name in names]
    assert at == sorted(at), f"moved: {[n for _, n in sorted(zip(at, names))]}"


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_is_held(manifest, names, cell, moves=None):
    """Each of ``names`` is a ``per_layer`` entry with a reader that the
    cell is listed on, in this order among themselves."""
    in_order(manifest["per_layer"], names)
    for name in names:
        m = named(manifest["per_layer"], name)
        assert set(m) == ENTRY_KEYS, name
        assert cell in m["workloads"], f"{name} does not list {cell}"
        assert moves is None or m["moves"] == moves, name
        assert callable(load_module("layers", name).read), name
        if "roofline" in name:   # a share: a percentage more is better of
            assert (m["unit"], m["better"]) == ("%", "higher"), name


def own_entries_are_held(manifest, layers, suffix):
    """A cell's own entries (``{name: layer}``, names ending in
    ``suffix``) each move the rate and name a layer the manifest had
    before the cell came, letter for letter; and none of them, and no
    file, is a double of a quantity that one ``.refit`` reader reads for
    every cell."""
    first = manifest["workloads"][0]["name"]
    had = {m["layer"] for m in manifest["per_layer"] if reports(m, first)}
    for name, layer in layers.items():
        m = named(manifest["per_layer"], name)
        assert name.endswith(suffix) and m["moves"] == "refit_items_per_s"
        assert m["layer"] == layer and layer in had, name
    names = {m["name"] for m in manifest["per_layer"]}
    for quantity in ("optimize_host_s", "host_wait_s", "h2d_mb"):
        assert quantity + ".refit" in names
        assert quantity + suffix not in names
        assert not os.path.exists(os.path.join(
            HERE, "layers", quantity + suffix + ".py"))


def cell_is_held(manifest, *, cell, config, traffic, chips, reduced,
                 configs_before, cells_before, per_layer, end_to_end):
    """The configuration and the cell, looked up by name, say what their
    files say; what the manifest had before them comes before them, in
    the order it had; every per-layer entry the cell relies on lists it;
    and the cell has a reading that moves each end-to-end metric it
    reports. ``end_to_end`` is ``{name: bound}`` of the metrics it
    reports."""
    stated = load_json(os.path.join(HERE, "configs", config + ".json"))
    cfg = named(manifest["configs"], config)
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"] == f"benchmarks/configs/{config}.json"
    assert cfg["source"] == stated["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == reduced == list(stated["reduced_why"])
    assert 1 <= len(cfg["why"]) <= 200
    entry = named(manifest["workloads"], cell)
    assert entry == {"name": cell, "config": config, "traffic": traffic,
                     "chips": chips, "why": entry["why"]}
    # the reason describes the traffic the cell runs
    rows = "{:,}+{:,}".format(stated["train_rows"], stated["test_rows"])
    assert rows in entry["why"] and len(entry["why"]) <= 200
    in_order(manifest["configs"], [*configs_before, config])
    in_order(manifest["workloads"], [*cells_before, cell])
    per_layer_is_held(manifest, per_layer, cell)
    for name, bound in end_to_end.items():
        m = named(manifest["end_to_end"], name)
        assert m["bound"] == bound and reports(m, cell), name
    moved = {m["moves"] for m in manifest["per_layer"] if reports(m, cell)}
    assert moved == {m["name"] for m in manifest["end_to_end"]
                     if reports(m, cell)}


def grown(manifest):
    """A copy with what the next ``model_config`` PR adds: one more
    configuration, cell and per-layer entry, each at the end of its list,
    and the cell's name at the end of every ``workloads`` list that all
    the cells before it are on."""
    more = copy.deepcopy(manifest)
    cells = [c["name"] for c in more["workloads"]]
    more["configs"].append({
        "name": "a_fourth_config", "source": "https://example.org/fourth",
        "file": "benchmarks/configs/a_fourth_config.json",
        "reduced": ["env"], "why": "the next deployment"})
    more["workloads"].append({
        "name": "a_fourth_cell", "config": "a_fourth_config",
        "traffic": "fit_in_memory", "chips": 1, "why": "the next cell"})
    for m in more["end_to_end"] + more["per_layer"]:
        if m.get("workloads") == cells:
            m["workloads"].append("a_fourth_cell")
    more["per_layer"].append({
        "name": "a_kernel_roofline.fourth", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "featurize kernels",
        "moves": "refit_items_per_s", "workloads": ["a_fourth_cell"]})
    return more
