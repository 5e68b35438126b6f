"""ctypes bindings for the native host runtime (``native/``).

The reference loads its C++ kernels over JNI
(``utils/external/VLFeat.scala:4`` + ``bin/run-main.sh``'s
``-Djava.library.path=lib``); here the shared library is loaded lazily
with ctypes and every entry point has a pure-Python twin, so the
framework runs on a host with no compiler and accelerates with one.

The library is never trusted for merely being on disk: it is stamped
with a hash of ``native/keystone_native.cpp`` and rebuilt on first use
whenever that stamp is not the source's (or the file is absent). When
the build fails the Python twins take over and a ``RuntimeWarning``
says why; :func:`status` says which side is running.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SOURCE_PATH = os.path.join(_NATIVE_DIR, "keystone_native.cpp")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libkeystone_native.so")

_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_built_here = False


def _source_id() -> str:
    with open(_SOURCE_PATH, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def build(quiet: bool = True) -> None:
    """Compile the native library in-tree from ``keystone_native.cpp``
    as it stands, stamped with that file's hash. Raises
    ``CalledProcessError`` / ``OSError`` when the compiler fails or is
    missing.

    Builds to a process-unique temp name and atomically renames into
    place, so concurrent first-use builds never leave a torn .so."""
    global _built_here
    tmp = _LIB_PATH + f".tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-fopenmp", "-std=c++17", "-shared",
             f'-DNATIVE_SOURCE_ID="{_source_id()}"',
             "-o", tmp, _SOURCE_PATH],
            check=True,
            capture_output=quiet,
        )
        os.replace(tmp, _LIB_PATH)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    _built_here = True


def _is_current() -> bool:
    """True when the library on disk carries the stamp of the source on
    disk (read from the file, before anything is loaded)."""
    try:
        with open(_LIB_PATH, "rb") as f:
            # the NUL-terminated string keystone_native_source_id() returns
            stamp = f"keystone-native-source:{_source_id()}\0".encode()
            return stamp in f.read()
    except OSError:
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    try:
        if not _is_current():
            build()
        lib = ctypes.CDLL(_LIB_PATH)
    except (OSError, subprocess.CalledProcessError) as exc:
        _load_failed = True
        stderr = (getattr(exc, "stderr", None) or b"").decode(
            errors="replace")[-400:]
        warnings.warn(
            f"native host library unavailable ({exc}) {stderr}".rstrip()
            + "; using the pure-Python decoders",
            RuntimeWarning, stacklevel=3)
        return None
    lib.cifar_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.java_string_hash.restype = ctypes.c_int32
    lib.java_string_hash.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.java_string_hash_batch.argtypes = [
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.ngram_hash_doc.restype = ctypes.c_int64
    lib.ngram_hash_doc.argtypes = [
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ]
    lib.csv_parse_f32.restype = ctypes.c_int64
    lib.csv_parse_f32.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ]
    lib.permutation_head.restype = ctypes.c_int64
    lib.permutation_head.argtypes = [
        np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def status() -> Dict[str, object]:
    """Which decoder this process runs: ``{"decoder": "native" |
    "python", "built_in_this_process": bool}`` (loads, and if need be
    builds, the library)."""
    return {"decoder": "native" if available() else "python",
            "built_in_this_process": _built_here}


# ---------------- CIFAR decode ----------------

def cifar_decode(raw: bytes, rows: int = 32, cols: int = 32,
                 chans: int = 3):
    """Decode CIFAR binary records -> (images f32 (n,rows,cols,chans) in
    [0,255], labels int32 (n,)). Falls back to numpy."""
    rec = 1 + rows * cols * chans
    n = len(raw) // rec
    assert len(raw) % rec == 0, "corrupt CIFAR buffer"
    lib = _load()
    if lib is not None:
        images = np.empty((n, rows, cols, chans), np.float32)
        labels = np.empty(n, np.int32)
        lib.cifar_decode(raw, n, rows, cols, chans, images, labels)
        return images, labels
    arr = np.frombuffer(raw, np.uint8).reshape(n, rec)
    labels = arr[:, 0].astype(np.int32)
    planes = arr[:, 1:].reshape(n, chans, rows, cols)
    return planes.transpose(0, 2, 3, 1).astype(np.float32), labels


def cifar_decode_u8(raw: bytes, rows: int = 32, cols: int = 32,
                    chans: int = 3):
    """Decode CIFAR binary records WITHOUT float inflation ->
    (images uint8 (n,rows,cols,chans), labels int32 (n,)).

    The byte-packed analogue of the reference's
    ``RowColumnMajorByteArrayVectorizedImage`` (Image.scala:333-365),
    which existed exactly to avoid 4x memory blow-up at CIFAR load time;
    the f32 conversion happens on device, fused by XLA into the first
    consuming op.
    """
    rec = 1 + rows * cols * chans
    n = len(raw) // rec
    assert len(raw) % rec == 0, "corrupt CIFAR buffer"
    arr = np.frombuffer(raw, np.uint8).reshape(n, rec)
    labels = arr[:, 0].astype(np.int32)
    planes = arr[:, 1:].reshape(n, chans, rows, cols)
    return np.ascontiguousarray(planes.transpose(0, 2, 3, 1)), labels


# ---------------- seeded sample ----------------

def permutation_head(n: int, size: int, seed: int) -> Optional[np.ndarray]:
    """The first ``size`` entries of
    ``np.random.RandomState(seed).permutation(n)``, bit for bit and in
    its order, without the shuffle of ``n`` entries (int64). ``None``
    where the library cannot give them and NumPy's own call has to (no
    library, arguments outside ``0 <= size <= n <= 2**32``, or no memory
    for the entry's scratch, which a ``RuntimeWarning`` says)."""
    lib = _load()
    if lib is None or not 0 <= size <= n <= 1 << 32:
        return None
    _, key, pos = np.random.RandomState(seed).get_state()[:3]
    out = np.empty(size, np.int64)
    if lib.permutation_head(key, pos, n, size, out) != 0:
        warnings.warn(
            f"native permutation_head could not allocate its scratch for "
            f"n={n}; using numpy's shuffle", RuntimeWarning, stacklevel=3)
        return None
    return out


# ---------------- text hashing ----------------

def java_hash_tokens(tokens: Sequence[str]) -> np.ndarray:
    """JVM String.hashCode of each token (int32 array)."""
    lib = _load()
    if lib is not None and tokens:
        encoded = [t.encode("utf-8") for t in tokens]
        offsets = np.zeros(len(encoded) + 1, np.int64)
        np.cumsum([len(e) for e in encoded], out=offsets[1:])
        arena = b"".join(encoded)
        out = np.empty(len(encoded), np.int32)
        lib.java_string_hash_batch(arena, offsets, len(encoded), out)
        return out
    from ..nodes.nlp.hashing import java_string_hash

    return np.asarray([java_string_hash(t) for t in tokens], np.int32)


def ngram_hash_features(tokens: Sequence[str], orders: Sequence[int],
                        num_features: int) -> np.ndarray:
    """Feature indices of every ngram of the given orders — the native
    core of NGramsHashingTF. Returns int32 indices (with repeats; caller
    counts)."""
    from ..nodes.nlp.hashing import SEQ_SEED

    lo, hi = min(orders), max(orders)
    n = len(tokens)
    if n < lo:
        return np.zeros(0, np.int32)
    hashes = java_hash_tokens(tokens)
    lib = _load()
    cap = (n - lo + 1) * (hi - lo + 1)
    if lib is not None:
        out = np.empty(cap, np.int32)
        wrote = lib.ngram_hash_doc(
            hashes, n, lo, hi, num_features, SEQ_SEED, out, cap)
        return out[:wrote]
    from ..nodes.nlp.hashing import NGramsHashingTF

    sv = NGramsHashingTF(list(orders), num_features).apply(list(tokens))
    return np.repeat(sv.indices, sv.values.astype(np.int64))


# ---------------- CSV ----------------

def csv_parse(path: str, num_cols: Optional[int] = None) -> np.ndarray:
    """Parse a float CSV file into an (n, num_cols) float32 array."""
    with open(path, "rb") as f:
        buf = f.read()
    lib = _load()
    if lib is not None:
        first = buf.split(b"\n", 1)[0]
        cols = num_cols or (first.count(b",") + 1)
        cap = buf.count(b",") + buf.count(b"\n") + 2
        out = np.empty(cap, np.float32)
        wrote = lib.csv_parse_f32(buf, len(buf), out, cap)
        if wrote >= 0 and wrote % cols == 0:
            return out[:wrote].reshape(-1, cols)
        # malformed (empty fields / ragged rows): defer to numpy, which
        # raises a descriptive error
    return np.loadtxt(path, delimiter=",", dtype=np.float32, ndmin=2)
