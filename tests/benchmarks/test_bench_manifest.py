"""BENCHMARK.json against the contract's static rules and the files it
names: nothing here needs a device."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    # a full check with all 24 cells fits the driver's 43,200 s
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(manifest["configs"]) <= 24
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) <= max(1, cells // 4)


def test_names_units_and_entries(manifest):
    names = [m["name"] for m in metrics_of(manifest)]
    assert len(names) == len(set(names))
    for m in metrics_of(manifest):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for c in manifest["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert 1 <= len(c["why"]) <= 200 and c["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_at_most_four_end_to_end_metrics_besides_setup(manifest):
    names = [m["name"] for m in manifest["end_to_end"]]
    assert "setup_s" in names and len(names) - 1 <= 4
    (setup,) = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.1


def test_every_cell_reports_what_its_metrics_move(manifest):
    cells = {c["name"] for c in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    for m in metrics_of(manifest):
        assert reported_in(m) <= cells, m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert reported_in(m) <= reported_in(e2e[m["moves"]]), m["name"]
    for cell in cells:
        mine = [m["name"] for m in manifest["end_to_end"] if cell in reported_in(m)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(cell in reported_in(m) for m in manifest["per_layer"]), cell
    # metrics of one layer give the same layer name, letter for letter
    assert len({m["layer"] for m in manifest["per_layer"]}) <= 12


def test_files_are_where_the_names_say(manifest):
    assert manifest["command"] == ["python3", "-m", "benchmarks.run"]
    assert manifest["paths"] == ["benchmarks", "tests/benchmarks"]
    used = {c["config"] for c in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg.get("reduced_why", {}))
        for kind in ("configs", "reference"):
            assert os.path.exists(os.path.join(HERE, kind, c["name"] + ".py"))
    for c in manifest["workloads"]:
        with open(os.path.join(HERE, "traffic", c["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(HERE, "drivers", kind + ".py"))
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "layers", m["name"] + ".py"))
    for dirpath, _, names in os.walk(HERE):
        for n in names:
            if "__pycache__" not in dirpath:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


def test_peaks_name_their_source():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["source"] and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    from benchmarks.harness import load_peaks

    with pytest.raises(KeyError):
        load_peaks("TPU v9 imaginary")
