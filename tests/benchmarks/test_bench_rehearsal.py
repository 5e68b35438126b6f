"""What every cell of BENCHMARK.json must bring for its rehearsal, and
what a cell or configuration that lacks it is told: its fault, a file of
``faults/`` found by the configuration's name, and its rehearsal file,
``test_bench_rehearsal_<cell>.py``, found by the cell's. (The rehearsals
themselves ran from here until PR 48, every cell's in this one file,
which was one worker's and the tier-1 run's whole wall; what they share
is ``rehearsals.py``.)"""
import os

import pytest

from rehearsals import (CELLS, WORKLOADS, fault_file, kind_of,
                        rehearsal_file)


def test_a_configuration_without_its_fault_is_told_which_file_to_add():
    with pytest.raises(pytest.fail.Exception) as failure:
        fault_file("half_the_training_rows_left_out", "a_fourth_config")
    assert ("tests/benchmarks/faults/half_the_training_rows_left_out/"
            "a_fourth_config.py") in str(failure.value)
    # every configuration of the manifest has brought its own
    for cell in CELLS:
        if kind_of(cell) == "fit_loop":
            assert os.path.exists(fault_file(
                "half_the_training_rows_left_out", WORKLOADS[cell]["config"]))
    # and every cell its rehearsal file: the cell that the grown copy of
    # the manifest appends (manifest_checks.grown) is the one without
    with pytest.raises(pytest.fail.Exception) as failure:
        rehearsal_file("a_fourth_cell")
    assert ("tests/benchmarks/test_bench_rehearsal_a_fourth_cell.py"
            in str(failure.value))
    for cell in CELLS:
        with open(rehearsal_file(cell)) as f:
            text = f.read()
        assert f'CELL = "{cell}"' in text and "rehearsals.cases(CELL)" in text
