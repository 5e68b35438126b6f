"""The cell ``voc_refit`` end to end on the CPU at the tiny size its files
give, its faults and its control (``rehearsals.cases``): a file of its
own, so that the driver's tier-1 run hands it to one worker. And the
fault that only its node count sees, since that count is a pair here
(``real_fit.nodes_executed`` [27, 33], PR 48): the graph run twice."""
import rehearsals

CELL = "voc_refit"
(test_cell_rehearses_and_names_no_device_metric,
 test_a_broken_timed_path_is_not_correct,
 test_the_lower_precision_control_is_not_correct) = rehearsals.cases(CELL)


def test_a_graph_run_twice_is_not_correct_by_the_node_count_alone():
    """56 nodes a fit where the pair allows 33 at most; the table is
    cleared between the two runs, so no hit and no other count moves."""
    result, lines = rehearsals.rehearse(CELL, code=rehearsals.GRAPH_RUN_TWICE)
    assert result["correct"] is False, "\n".join(lines[-12:])
    assert rehearsals.failed(lines) == {"nodes_executed_off"}
    assert result["compared"]["nodes_executed_off"] == [56.0 - 33.0, 0.0]
    assert result["compared"]["memo_hits_off"] == [0.0, 0.0]
