"""The partitioned block solve's share of its roofline on a cell of
several chips: the least time the cell's chips together could take for
the solve (``counts/block_solve.py`` as it stands, with ``chips`` times
one chip's peak; compute-bound at these shapes) over the first chip's
device time of its program, which every chip runs at once: the reader
``solve_roofline.refit`` as it stands, on the cell's peak
(``_chips.py``). The Cholesky factor and its solves run whole on every
chip and are counted once, and the all-reduce a block step is inside the
program's time and in no count: both can only lower the share."""
from benchmarks.layers import _chips


def read(run):
    return _chips.read_with_cell_peak(run, "solve_roofline.refit")
