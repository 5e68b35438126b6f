"""Dense SIFT's share of its roofline: the least time the chip could
take for ONE pass over every training and test image of the stated
sizes (``counts/dense_sift.py``: the band products, dense, at the stated
precision) over ``sift_dev_ms.voc``. The program passes over a training
image up to three times (``sift_passes.voc``), so the share cannot pass
100% and reads lower for every repeat."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(
        run, _common.load_reader("sift_dev_ms.voc").SIFT_PROGRAMS)
    if not fits or not seconds or run.peaks is None:
        return None
    least, _bound = _common.load_counts("dense_sift").roofline_seconds(
        run.peaks, run.cfg, run.facts["items"])
    return 100.0 * least * fits / seconds
