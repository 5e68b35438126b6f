"""Native host runtime tests: bit-parity of the C++ paths with the pure
Python implementations (the analogue of the reference's VLFeatSuite /
EncEvalSuite golden checks against its JNI library)."""
import numpy as np
import pytest

import keystone_tpu.native as kn
from keystone_tpu.nodes.nlp.hashing import (
    HashingTF,
    NGramsHashingTF,
    java_string_hash,
)


@pytest.fixture(scope="module")
def native_lib():
    if not kn.available():
        pytest.skip("native library not built and no toolchain")
    return kn


def test_native_cifar_decode_parity(native_lib):
    rng = np.random.RandomState(0)
    raw = rng.randint(0, 256, 7 * 3073, dtype=np.uint8).tobytes()
    imgs, labels = kn.cifar_decode(raw)
    arr = np.frombuffer(raw, np.uint8).reshape(7, 3073)
    want = arr[:, 1:].reshape(7, 3, 32, 32).transpose(0, 2, 3, 1)
    np.testing.assert_array_equal(imgs, want.astype(np.float32))
    np.testing.assert_array_equal(labels, arr[:, 0].astype(np.int32))


def test_stale_library_is_rebuilt_not_loaded(native_lib, monkeypatch,
                                             tmp_path):
    """A library that was not compiled from the source on disk (no
    stamp, or another file's) is rebuilt on first use, never loaded."""
    import shutil

    lib = tmp_path / "libkeystone_native.so"
    shutil.copy(kn._LIB_PATH, lib)
    monkeypatch.setattr(kn, "_LIB_PATH", str(lib))
    assert kn._is_current()
    # the same binary against a source that has since changed
    source = tmp_path / "keystone_native.cpp"
    source.write_text(open(kn._SOURCE_PATH).read() + "\n// edited\n")
    monkeypatch.setattr(kn, "_SOURCE_PATH", str(source))
    assert not kn._is_current()
    monkeypatch.setattr(kn, "_lib", None)
    monkeypatch.setattr(kn, "_load_failed", False)
    monkeypatch.setattr(kn, "_built_here", False)
    assert kn.status() == {"decoder": "native",
                           "built_in_this_process": True}
    assert kn._is_current()


def test_failed_build_warns_and_falls_back(monkeypatch, tmp_path):
    """No compiler / a source that does not compile: the Python twins
    take over, and say so."""
    source = tmp_path / "keystone_native.cpp"
    source.write_text("this is not C++\n")
    monkeypatch.setattr(kn, "_SOURCE_PATH", str(source))
    monkeypatch.setattr(kn, "_LIB_PATH", str(tmp_path / "lib.so"))
    monkeypatch.setattr(kn, "_lib", None)
    monkeypatch.setattr(kn, "_load_failed", False)
    with pytest.warns(RuntimeWarning, match="pure-Python decoders"):
        assert kn.status()["decoder"] == "python"
    raw = np.arange(2 * 3073, dtype=np.uint8).tobytes()
    imgs, labels = kn.cifar_decode(raw)
    assert imgs.shape == (2, 32, 32, 3) and labels.tolist() == [0, 1]


def test_native_string_hash_parity(native_lib):
    toks = ["", "a", "Seq", "hello world", "wörld", "日本語", "🚀rocket"]
    got = kn.java_hash_tokens(toks)
    want = [java_string_hash(t) for t in toks]
    assert got.tolist() == want


def test_native_ngram_features_parity(native_lib):
    doc = "the quick brown fox jumps over the lazy dog the quick".split()
    for orders in ([1], [1, 2], [2, 3, 4]):
        feats = kn.ngram_hash_features(doc, orders, 1 << 14)
        sv = NGramsHashingTF(orders, 1 << 14).apply(doc)
        idx, counts = np.unique(feats, return_counts=True)
        np.testing.assert_array_equal(idx, sv.indices)
        np.testing.assert_array_equal(counts.astype(np.float32), sv.values)


def test_ngram_hashing_node_native_equals_python(native_lib):
    # the node's native fast path must equal its python fallback exactly
    doc = "a b c a b a".split()
    node = NGramsHashingTF([1, 2], 64)
    with_native = node.apply(doc)
    saved = kn._lib, kn._load_failed
    try:
        kn._lib, kn._load_failed = None, True
        without = node.apply(doc)
    finally:
        kn._lib, kn._load_failed = saved
    assert with_native == without


def test_native_csv_parse(tmp_path, native_lib):
    p = tmp_path / "m.csv"
    p.write_text("1.5,2.25,3\n-4,5e-3,6\n")
    out = kn.csv_parse(str(p))
    np.testing.assert_allclose(out, [[1.5, 2.25, 3], [-4, 5e-3, 6]])


def test_native_csv_parse_rejects_empty_trailing_field(tmp_path, native_lib):
    # "1,\n2,\n" has an empty trailing field per row; strtof would skip the
    # newline and swallow the next row's value, yielding [[1,2]] silently.
    # The strict parser must bail to numpy, which raises.
    p = tmp_path / "bad.csv"
    p.write_text("1,\n2,\n")
    with pytest.raises(ValueError):
        kn.csv_parse(str(p), num_cols=2)
