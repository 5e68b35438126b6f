"""The Fisher-vector encoder's share of its roofline: the least time
for the posteriors and two moments of every image's descriptors
(``counts/fisher_vector.py``, the expected descriptor count of the
stated sizes, ``counts/dense_sift.py``) over ``fv_dev_ms.voc``."""
from benchmarks.layers import _common


def read(run):
    fits = run.facts.get("fits")
    seconds = _common.program_seconds(
        run, _common.load_reader("fv_dev_ms.voc").FV_PROGRAMS)
    if not fits or not seconds or run.peaks is None:
        return None
    sift = _common.load_counts("dense_sift")
    least, _bound = _common.load_counts("fisher_vector").roofline_seconds(
        run.peaks, sift.expected(run.cfg, sift.descriptors),
        run.cfg["desc_dim"], run.cfg["vocab_size"], run.facts["items"],
        run.cfg["fv_precision"])
    return 100.0 * least * fits / seconds
