"""Host seconds per fit inside the program's ``featurize:fit_pca`` span:
the sampled descriptors centred and factored, and the small
decomposition read back."""
from benchmarks.layers import _ring_spans


def read(run):
    return _ring_spans.per_fit(
        run, _ring_spans.seconds_of("featurize:fit_pca")) or None
