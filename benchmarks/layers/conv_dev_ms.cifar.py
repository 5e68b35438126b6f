"""Device milliseconds per fit in the block maker of the streamed solve:
im2col, the Pallas kernel (patch product, normalisation, rectifier,
pooling) and the copy of its output, over every generation the fit
makes (factor sweep, epoch sweep, both blockwise applies)."""
from benchmarks.layers import _maker_loops


def read(run):
    fits = run.facts.get("fits")
    seconds = _maker_loops.maker_seconds(run)
    return None if not fits or seconds is None else 1e3 * seconds / fits
