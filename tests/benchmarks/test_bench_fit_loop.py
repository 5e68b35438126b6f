"""The fit loop's arithmetic on a stub job (no pipeline, no device), in
both of its mixes: what is judged is every item of the window's whole
fits over the window's whole wall, whatever gets a fit its rows
included (the loader when the files are read again, host to device when
the rows are held); the loader of a held-rows cell runs once, in set-up,
and its reader says so; a fit that meets the prefix-state table
oftener than the configuration's file says is not correct; and one that
runs more or fewer nodes than the file states, a number or a pair
``[least, most]``, is not either."""
import time
import types

import pytest

from benchmarks.harness import Run, load_json, load_module, HERE
from benchmarks.spans import Spans

ITEMS, LOAD_S, PUT_S, FIT_S = 1000, 0.06, 0.01, 0.02
MIXES = {name: load_json(f"{HERE}/traffic/{name}.json")
         for name in ("fit_from_disk", "fit_in_memory")}
ROWS_S = {"fit_from_disk": LOAD_S, "fit_in_memory": PUT_S}


class StubJob:
    items = ITEMS

    def __init__(self, extra_hits=0, slow=1, nodes=0):
        self.extra_hits, self.slow, self.loads = extra_hits, slow, 0
        self.nodes = nodes

    def load(self):
        self.loads += 1
        time.sleep(self.slow * LOAD_S)
        return "rows"

    def hold(self):
        return [self.load()]

    def datasets(self, held):
        assert held == ["rows"]
        time.sleep(self.slow * PUT_S)
        return "rows"

    def fit(self, loaded):
        from keystone_tpu.observability.metrics import MetricsRegistry

        assert loaded == "rows"
        time.sleep(FIT_S)
        counter = MetricsRegistry.get_or_create().counter
        counter("executor.prefix_hits").inc(self.extra_hits)
        counter("executor.nodes_executed").inc(self.nodes)
        return {"train_error": 0.25, "test_error": 0.5}

    def answers(self, outcome):
        return outcome

    def reference_inputs(self):
        return {}


def drive(job, mix, seconds=0.5, nodes_stated=0):
    run = Run(cell={"name": "stub", "config": "stub"},
              cfg={"real_fit": {"prefix_hits": 0,
                                "nodes_executed": nodes_stated}},
              traffic=MIXES[mix], seed=1, seconds=seconds,
              trace=False, rehearsal=True, control=False, workdir="unused",
              say=lambda text: None, spans=Spans())
    run.config_module = lambda: types.SimpleNamespace(
        prepare=lambda cfg, seed, workdir: job)
    run.reference_module = lambda: types.SimpleNamespace(
        check=lambda cfg, inputs, answers: [])
    return run, load_module("drivers", "fit_loop").run(run)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_the_judged_rate_is_all_the_work_over_the_whole_window(mix):
    job = StubJob()
    run, outcome = drive(job, mix)
    fits = outcome.attempted
    assert fits >= 3 and outcome.failed == 0
    assert list(outcome.metrics) == [MIXES[mix]["metric"]]
    (judged,) = outcome.metrics.values()
    assert run.spans.count("fit") == fits
    window = run.spans.total("window")
    assert judged == pytest.approx(ITEMS * fits / window, rel=0.02)
    assert window >= fits * (ROWS_S[mix] + FIT_S)
    assert judged <= ITEMS / (ROWS_S[mix] + FIT_S)
    # a stall in what gets a fit its rows moves it
    _, slower = drive(StubJob(slow=3), mix)
    (slow,) = slower.metrics.values()
    assert slow < 0.85 * judged


def test_files_read_again_are_read_before_every_fit():
    job = StubJob()
    run, outcome = drive(job, "fit_from_disk")
    assert job.loads == outcome.attempted + 1          # and the warming fit
    assert run.spans.count("ingest") == outcome.attempted
    assert run.spans.count("to_device") == 0
    assert LOAD_S <= run.facts["loader_s"] < 2 * LOAD_S


def test_held_rows_are_loaded_once_in_set_up_and_put_before_every_fit():
    job = StubJob()
    run, outcome = drive(job, "fit_in_memory")
    assert job.loads == 1
    assert run.spans.count("ingest") == 0              # none in the window
    assert run.spans.count("to_device") == outcome.attempted
    put = load_module("layers", "to_device_s.refit").read(run)
    loader = load_module("layers", "loader_s.setup").read(run)
    assert PUT_S <= put < 2 * PUT_S
    assert LOAD_S <= loader < 2 * LOAD_S
    assert outcome.metrics["refit_items_per_s"] > ITEMS / (LOAD_S + FIT_S)


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("extra_hits,off", [(0, 0.0), (1, 1.0)])
def test_a_fit_answered_from_the_table_is_not_correct(mix, extra_hits, off):
    _, outcome = drive(StubJob(extra_hits), mix, seconds=0.2)
    checks = {name: (value, limit) for name, value, limit in outcome.checks}
    assert checks["memo_hits_off"] == (off, 0.0)
    assert checks["nodes_executed_off"] == (0.0, 0.0)
    assert checks["fits_disagree"] == (0.0, 0.0)
    assert checks["compiles_in_window"] == (0.0, 0.0)


@pytest.mark.parametrize("stated,ran,off", [
    (28, 28, 0.0), (28, 26, 2.0), (28, 56, 28.0),           # exact, as ever
    ([27, 33], 27, 0.0), ([27, 33], 28, 0.0), ([27, 33], 33, 0.0),
    ([27, 33], 26, 1.0), ([27, 33], 34, 1.0), ([27, 33], 56, 23.0),
    ([28, 28], 28, 0.0), ([28, 28], 29, 1.0)])
def test_a_node_count_is_held_to_a_number_or_to_a_pair(stated, ran, off):
    """``real_fit.nodes_executed`` is exact or ``[least, most]``: inside
    reads 0, outside its distance; the memo's and the other checks do
    not move with it."""
    _, outcome = drive(StubJob(nodes=ran), "fit_in_memory", seconds=0.1,
                       nodes_stated=stated)
    checks = {name: (value, limit) for name, value, limit in outcome.checks}
    assert checks["nodes_executed_off"] == (off, 0.0)
    assert checks["memo_hits_off"] == (0.0, 0.0)
