"""The featurizer's share of its roofline on a cell of several chips:
the least time the cell's chips together could take for the
dense-product transforms of one fit's rows (``counts/dense_dft.py`` as
it stands, with ``chips`` times one chip's peak) over
``featurize_dev_ms.refit``, the first chip's device time of every
program but the solve: the reader ``featurize_roofline.refit`` as it
stands, on the cell's peak (``_chips.py``). Apply, evaluation and the
column means are in that time and not in the count, so they can only
lower the share."""
from benchmarks.layers import _chips


def read(run):
    return _chips.read_with_cell_peak(run, "featurize_roofline.refit")
