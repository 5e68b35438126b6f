"""The block solve's share of its roofline: the least time the chip
could take for the solve (``counts/block_solve.py``; compute-bound at
these shapes) over the device time of its program."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    solve = _common.load_reader("solve_dev_ms.refit")
    seconds = _common.program_seconds(run, solve.SOLVE_PROGRAMS)
    shape = run.cfg.get("solve_shape")
    if not fits or not seconds or not shape or run.peaks is None:
        return None
    counts = _common.load_counts("block_solve")
    least, _bound = counts.roofline_seconds(
        run.peaks, shape["rows"], shape["features"], shape["block_size"],
        shape["classes"], shape["passes"], shape["precision"])
    return 100.0 * least * fits / seconds
