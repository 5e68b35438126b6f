"""Set-up as the compile observatory saw it, as the benchmark reads it:
the six ``.setup`` readers of ISSUE 36 (``benchmarks/layers/
_setup_compiles.py`` and ``startup_s.setup.py``) on a synthetic run
whose records are known exactly, on real compiles against a temporary
persistent cache, and in the manifest.
"""
import time

import jax
import jax.numpy as jnp
import pytest

import manifest_checks
import test_bench_manifest_grows
from benchmarks.harness import Run, load_module
from benchmarks.layers import _setup_compiles
from benchmarks.spans import Spans

MANIFEST = manifest_checks.load_manifest()
FROM_RECORDS = ["compile_path_s.setup", "cache_read_s.setup",
                "trace_lower_s.setup", "cold_compile_s.setup",
                "programs_loaded.setup"]
NEW = FROM_RECORDS + ["startup_s.setup"]
CELLS = ["mnist_refit", "timit_refit", "cifar_refit"]


def make_run(tmp_path, setup_s=0.0):
    said = []
    run = Run(cell={"name": "t"}, cfg={}, traffic={}, seed=0, seconds=1.0,
              trace=True, rehearsal=True, control=False,
              workdir=str(tmp_path), say=said.append, spans=Spans(),
              setup_s=setup_s)
    run.said = said
    return run


def read_all(run, names=NEW):
    return {name: load_module("layers", name).read(run) for name in names}


# -- the manifest ----------------------------------------------------------------

def manifest_holds(manifest):
    """The six are listed, in their order among themselves, each with a
    reader, on at least the three cells they came with (those at the
    head of their lists, in that order; the cells appended since stand
    behind them), moving ``setup_s`` from the layer ``compile``;
    ``loader_s.setup`` comes before them."""
    for cell in CELLS:
        manifest_checks.per_layer_is_held(
            manifest, ["loader_s.setup", *NEW], cell, moves="setup_s")
    for name in NEW:
        m = manifest_checks.named(manifest["per_layer"], name)
        assert m["workloads"][:len(CELLS)] == CELLS, name
        assert (m["layer"], m["better"]) == ("compile", "lower"), name
        assert m["source"] == ("program_span" if name == "startup_s.setup"
                               else "program_counter"), name
        assert m["unit"] == ("programs" if name == "programs_loaded.setup"
                             else "s"), name


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_the_six_readers_on_the_cell(cell):
    manifest_holds(MANIFEST)
    manifest_checks.per_layer_is_held(MANIFEST, NEW, cell, moves="setup_s")
    # appended: nothing that was listed before them stands after them
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index("sift_passes.voc") < names.index(NEW[0])
    assert [n for n in names if n in NEW] == NEW


def test_the_grown_copy_still_holds_with_the_six():
    more = manifest_checks.grown(MANIFEST)
    manifest_holds(more)
    test_bench_manifest_grows.all_hold(more)
    # whatever the copy appends to them (the six are on every cell since
    # PR 48, so the next cell joins them as it joins ``h2d_mb.refit``),
    # the three they came with stay at the head, in their order
    for name in NEW:
        listed = manifest_checks.named(more["per_layer"], name)["workloads"]
        assert listed[:len(CELLS)] == CELLS and len(set(listed)) == len(listed)


@pytest.mark.parametrize("damage", ["moved", "renamed", "cell_taken_off"])
def test_a_damaged_entry_fails(damage):
    broken = manifest_checks.grown(MANIFEST)
    entries = broken["per_layer"]
    if damage == "moved":
        entries.append(entries.pop(entries.index(
            manifest_checks.named(entries, "cache_read_s.setup"))))
    elif damage == "renamed":
        manifest_checks.named(entries, "cold_compile_s.setup")["name"] += ".x"
    else:
        manifest_checks.named(
            entries, "startup_s.setup")["workloads"].remove("timit_refit")
    with pytest.raises(AssertionError):
        manifest_holds(broken)


# -- the readers on records that are known exactly -------------------------------

SETUP_S = 30.0
#: (name, seconds after T_START it started, trace, lower, backend, cache,
#: cache_read_s, cold_s); set-up is [0, 30), the window comes after it
RECORDS = [
    ("before_the_harness", -2.0, 0.5, 0.5, 1.0, "off", 0.0, 1.0),
    ("_block_solve", 4.0, 0.25, 0.5, 9.0, "hit", 8.5, 182.0),
    ("raw", 14.0, 0.125, 0.25, 0.5, "hit", 0.375, 0.0),
    ("evicted", 20.0, 0.5, 0.25, 40.0, "miss", 0.0, 40.0),
    ("last_of_setup", 29.75, 0.0, 0.125, 0.125, "off", 0.0, 0.125),
    ("in_the_window", 30.0, 1.0, 1.0, 1.0, "miss", 0.0, 1.0),
    ("the_reference", 75.0, 2.0, 2.0, 2.0, "hit", 1.5, 3.0),
]
IN_SETUP = RECORDS[1:5]


def fabricate(tmp_path, monkeypatch, records=RECORDS, timed=True):
    """A run whose observatory holds ``records``: each counted by the
    observatory itself, then given the times and phases the table says
    (without any, where the program is a parent commit's)."""
    from keystone_tpu.observability.compilelog import compile_observatory

    t_start = time.perf_counter()
    monkeypatch.setattr(_setup_compiles, "T_START", t_start)
    obs = compile_observatory()
    for name, at, trace, lower, backend, cache, read, cold in records:
        obs.record(name=name, wall_s=trace + lower + backend,
                   trigger="first-compile")
        entry = obs.records[-1]
        entry.update(
            t_start=t_start + at, t_end=t_start + at + backend,
            trace_s=trace, lower_s=lower, backend_s=backend, cache=cache,
            cache_hits=int(cache == "hit"), cache_misses=int(cache == "miss"),
            cache_read_s=read, cold_s=cold, program=f"jit({name})")
        if not timed:
            for key in ("t_start", "t_end", "trace_s", "lower_s", "backend_s",
                        "cache", "cache_hits", "cache_misses",
                        "cache_read_s", "cold_s", "program"):
                del entry[key]
    return make_run(tmp_path, setup_s=SETUP_S)


def test_only_the_records_that_start_in_setup_are_summed(
        tmp_path, monkeypatch):
    run = fabricate(tmp_path, monkeypatch)
    got = read_all(run, FROM_RECORDS)
    assert got == {
        "compile_path_s.setup": sum(r[2] + r[3] + r[4] for r in IN_SETUP),
        "cache_read_s.setup": 8.5 + 0.375,
        "trace_lower_s.setup": sum(r[2] + r[3] for r in IN_SETUP),
        "cold_compile_s.setup": 182.0 + 0.0 + 40.0 + 0.125,
        "programs_loaded.setup": 4.0}
    assert got["compile_path_s.setup"] == 51.625
    assert (got["cache_read_s.setup"] + got["trace_lower_s.setup"]
            <= got["compile_path_s.setup"])
    # the table is said once, however many readers asked: the slowest
    # first, each with its phases and what the cache said, and the misses
    table = [line for line in run.said if "set-up compiles" in line]
    assert len(table) == 1
    assert "4 records in set-up (3 after it)" in table[0]
    assert "2 programs read from the cache, 1 missed it" in table[0]
    rows = [line.split() for line in run.said if line.startswith("  ")]
    assert [r[:2] for r in rows] == [
        [f"jit({name})", f"[{name}]"]
        for name in ("evicted", "_block_solve", "raw", "last_of_setup")]
    assert rows[0][2:] == ["40.750", "=", "0.500", "+", "0.250", "+",
                           "40.000;", "miss", "0.000;", "40.000"]
    assert rows[1][-3:] == ["hit", "8.500;", "182.000"]
    assert max(map(len, run.said)) < 200


@pytest.mark.parametrize("why", ["records_without_a_time", "a_dropped_tail",
                                 "no_records"])
def test_the_five_return_none_where_nothing_sound_is_there(
        tmp_path, monkeypatch, why):
    from keystone_tpu.observability.compilelog import CompileObservatory

    if why == "records_without_a_time":   # the parent commit's program
        run = fabricate(tmp_path, monkeypatch, timed=False)
    elif why == "a_dropped_tail":
        monkeypatch.setattr(CompileObservatory, "RECORD_TAIL", 5)
        run = fabricate(tmp_path, monkeypatch)
    else:
        run = fabricate(tmp_path, monkeypatch, records=[])
    assert read_all(run, FROM_RECORDS) == dict.fromkeys(FROM_RECORDS)
    said = [line for line in run.said if "set-up compiles" in line]
    assert len(said) == (1 if why == "a_dropped_tail" else 0)
    assert all("counted 7 records and holds 5" in line for line in said)


# -- start-up --------------------------------------------------------------------

def test_startup_is_the_end_of_the_pinned_import_span(tmp_path, monkeypatch):
    from keystone_tpu.observability.timeline import (flight_recorder,
                                                     record_startup)

    reader = load_module("layers", "startup_s.setup")
    run = make_run(tmp_path)
    assert reader.read(run) is None       # nothing pinned: a fresh recorder
    t_start = time.perf_counter() - 11.0
    monkeypatch.setattr(reader, "T_START", t_start)
    record_startup(t_start + 10.5)        # the package's first statement
    end = flight_recorder().pinned()["startup:import"]
    end = end.start_s + end.dur_s
    # a window of 180 fits records thousands of spans into a ring of 8,192
    for i in range(10_000):
        flight_recorder().record(f"s{i}", "dag", t_start + 12.0 + i, 0.5)
    assert flight_recorder().dropped() > 0
    assert not any(s.cat == "startup" for s in flight_recorder().spans())
    got = reader.read(run)
    assert got == end - t_start and 11.0 <= got < 11.5
    (said,) = run.said
    assert "the package's own import took 0.5" in said


def test_startup_reads_nothing_from_a_program_that_pins_no_span(
        tmp_path, monkeypatch):
    """A parent commit's recorder has no ``pinned``; a recorder switched
    off holds nothing."""
    from keystone_tpu.observability import timeline

    reader = load_module("layers", "startup_s.setup")

    class Parent:
        def spans(self):
            return []

    monkeypatch.setattr(timeline, "_RECORDER", Parent())
    assert reader.read(make_run(tmp_path)) is None
    monkeypatch.setattr(timeline, "_RECORDER", None)
    monkeypatch.setenv("KEYSTONE_FLIGHT_RECORDER", "0")
    timeline.record_startup(time.perf_counter() - 0.5)
    assert reader.read(make_run(tmp_path)) is None


# -- the readers on real compiles ------------------------------------------------

def test_the_six_on_real_compiles_against_a_persistent_cache(
        tmp_path, monkeypatch, persistent_cache_dir):
    """A set-up that compiles one program into an empty cache and reads
    it back, then a window that compiles another: what the readers say
    is what the observatory's own totals said when set-up ended."""
    from keystone_tpu.observability.compilelog import (compile_observatory,
                                                       observed_jit)
    from keystone_tpu.observability.timeline import record_startup

    x = jnp.ones((8, 8), jnp.float32)
    x.block_until_ready()
    t_start = time.perf_counter()
    monkeypatch.setattr(_setup_compiles, "T_START", t_start)
    monkeypatch.setattr(load_module("layers", "startup_s.setup"),
                        "T_START", t_start)
    record_startup(time.perf_counter())
    obs = compile_observatory()
    count0, wall0 = obs.count_total(), obs.wall_s_total()

    def program(x):
        return jnp.tanh(x @ x.T) + 5.0

    observed_jit(program, name="setup_program")(x).block_until_ready()
    jax.clear_caches()
    observed_jit(program, name="setup_program")(x).block_until_ready()
    count, wall = obs.count_total() - count0, obs.wall_s_total() - wall0
    run = make_run(tmp_path, setup_s=time.perf_counter() - t_start)
    observed_jit(lambda x: x - 7.0, name="window_program")(
        x).block_until_ready()
    got = read_all(run)
    assert all(v is not None for v in got.values()), got
    assert got["programs_loaded.setup"] == count == 2
    assert got["compile_path_s.setup"] == pytest.approx(wall, rel=1e-9)
    assert 0.0 < got["cache_read_s.setup"]      # the second was read
    assert 0.0 < got["trace_lower_s.setup"]
    assert (got["cache_read_s.setup"] + got["trace_lower_s.setup"]
            < got["compile_path_s.setup"])      # the first was compiled
    miss = [r for r in obs.tail() if r["name"] == "setup_program"][0]
    assert got["cold_compile_s.setup"] == miss["backend_s"]   # the hit: 0 s
    assert 0.0 < got["startup_s.setup"] < run.setup_s
    (table,) = [line for line in run.said if "set-up compiles" in line]
    assert "2 records in set-up (" in table and "(0 after it)" not in table
    assert "1 programs read from the cache, 1 missed it" in table
