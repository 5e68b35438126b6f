"""The cell ``mnist_refit_x4`` end to end on the CPU at the tiny size its files
give, its faults and its control (``rehearsals.cases``): a file of its
own, so that the driver's tier-1 run hands it to one worker."""
import rehearsals

CELL = "mnist_refit_x4"
(test_cell_rehearses_and_names_no_device_metric,
 test_a_broken_timed_path_is_not_correct,
 test_the_lower_precision_control_is_not_correct) = rehearsals.cases(CELL)
