"""Device milliseconds per fit outside the streamed solve: the blockwise
apply of the fitted model to the test rows (``jit__stream_apply``, which
makes every feature block once more), the label indicators, the argmax
and the evaluation (small beside the first)."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    if not fits or run.trace_data is None:
        return None
    solve = _common.load_reader(
        "stream_solve_dev_ms.timit").STREAM_SOLVE_PROGRAMS
    per = run.trace_data.program_seconds(run.trace_data.window())
    if not any(name.startswith(solve) for name in per):
        return None   # a program without the streamed solve
    other = sum(s for name, s in per.items() if not name.startswith(solve))
    return 1e3 * other / fits
