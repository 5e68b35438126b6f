"""``sample_indices`` is the head of NumPy's legacy permutation, bit for
bit, however computed (ISSUE 43): the node-level contract and the
native library's entry are held to ``RandomState.choice`` on the same
cases."""
import numpy as np
import pytest

import keystone_tpu.native as kn
from keystone_tpu.nodes.stats import sampling
from keystone_tpu.observability.metrics import MetricsRegistry

LAST_SEED = 2 ** 32 - 1

CASES = [
    # n of 0, 1 and 2; size 0, 1, n - 1, n and over n
    (0, 3, 0), (1, 0, 0), (1, 1, LAST_SEED), (2, 1, 0), (2, 2, LAST_SEED),
    (2, 5, 1), (1000, 0, 4), (1000, 1, 5), (1000, 999, 3), (1000, 1000, 0),
    (1000, 1500, 7),
    # a power of two, one under, one over: the mask changes there
    (4095, 64, 0), (4096, 64, LAST_SEED), (4097, 64, 2),
    (65535, 300, 0), (65536, 300, 1), (65537, 300, LAST_SEED),
    (131071, 5000, 6), (131072, 5000, 0), (131073, 131073, 8),
    # over one 624-word block of the generator, under and over a sample
    # of an eighth; the 10,000 filter rows of 100,000 (sample_rows)
    (625, 600, 9), (1 << 20, 1 << 17, 10), (1 << 20, 1 << 19, LAST_SEED),
    (100_000, 10_000, 0),
    # a few million, as a filter-learning sample is drawn
    (3_000_000, 100_000, 43),
]


def numpy_choice(n, size, seed):
    return np.random.RandomState(seed).choice(n, min(size, n), replace=False)


def contract(n, size, seed, monkeypatch):
    return sampling.sample_indices(n, size, seed), True


def native_entry(n, size, seed, monkeypatch):
    if not kn.available():
        pytest.skip("native library not built and no toolchain")
    got = kn.permutation_head(n, min(size, n), seed)
    assert got is not None           # the library itself, never a fallback
    return got, False


@pytest.mark.parametrize("form", [contract, native_entry],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("n,size,seed", CASES)
def test_sample_is_the_head_of_the_legacy_permutation(n, size, seed, form,
                                                      monkeypatch):
    got, is_sorted = form(n, size, seed, monkeypatch)
    want = numpy_choice(n, size, seed)
    assert got.dtype == want.dtype == np.int64
    np.testing.assert_array_equal(got, np.sort(want) if is_sorted else want)


def test_counters_say_which_form_a_draw_took(monkeypatch):
    counter = MetricsRegistry.get_or_create().counter
    sparse = counter("featurize.sample_draw.sparse")
    dense = counter("featurize.sample_draw.dense")

    def taken(n, size):
        before = sparse.value, dense.value
        sampling.sample_indices(n, size, 0)
        return sparse.value - before[0], dense.value - before[1]

    native = (1, 0) if kn.available() else (0, 1)
    assert taken(100_000, 10_000) == native
    assert taken(5, 50) == native                      # size is cut to n
    monkeypatch.setattr(kn, "_load", lambda: None)
    assert taken(100_000, 10_000) == (0, 1)
    from keystone_tpu.observability import names
    assert {sparse.name, dense.name} <= names.METRIC_NAMES


def test_without_the_library_numpy_draws_the_sample(monkeypatch):
    monkeypatch.setattr(kn, "_load", lambda: None)
    np.testing.assert_array_equal(
        sampling.sample_indices(70_000, 2000, 11),
        np.sort(numpy_choice(70_000, 2000, 11)))


@pytest.mark.parametrize("n,size", [(5, 6), (5, -1), ((1 << 32) + 1, 3)])
def test_outside_its_range_the_entry_leaves_the_draw_to_numpy(n, size):
    assert kn.permutation_head(n, size, 0) is None


def test_an_entry_out_of_memory_warns_and_leaves_the_draw_to_numpy(
        monkeypatch):
    class OutOfMemory:
        @staticmethod
        def permutation_head(key, pos, n, size, out):
            return -1

    monkeypatch.setattr(kn, "_load", lambda: OutOfMemory)
    with pytest.warns(RuntimeWarning, match="could not allocate"):
        got = sampling.sample_indices(1000, 10, 3)
    np.testing.assert_array_equal(got, np.sort(numpy_choice(1000, 10, 3)))
