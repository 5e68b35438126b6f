"""Device milliseconds per fit outside the solve: the featurizer's
programs over train and test rows, the model's apply and the evaluation
(the last two are small beside the first)."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    if not fits or run.trace_data is None:
        return None
    solve = _common.load_reader("solve_dev_ms.refit").SOLVE_PROGRAMS
    per = run.trace_data.program_seconds(run.trace_data.window())
    other = sum(s for name, s in per.items() if not name.startswith(solve))
    return 1e3 * other / fits
