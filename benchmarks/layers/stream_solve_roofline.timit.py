"""The streamed block solve's share of its roofline: the least time the
chip could take for one fit's matrix products (``counts/streamed_bcd.py``;
compute-bound at these shapes, the cosines not counted) over the device
time of its two programs."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    solve = _common.load_reader("stream_solve_dev_ms.timit")
    seconds = _common.program_seconds(run, solve.STREAM_SOLVE_PROGRAMS)
    shape = run.cfg.get("solve_shape")
    if not fits or not seconds or not shape or run.peaks is None:
        return None
    counts = _common.load_counts("streamed_bcd")
    least, _bound = counts.roofline_seconds(
        run.peaks, shape["rows"], shape["input_dim"], shape["block_size"],
        shape["blocks"], shape["classes"], shape["epochs"],
        shape["precision"])
    return 100.0 * least * fits / seconds
