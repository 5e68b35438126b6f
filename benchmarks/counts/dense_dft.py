"""Operations and bytes that the real half-spectrum of short real rows
needs when it is taken as a dense product: ``rows`` vectors of ``n``
reals, ``bins`` kept bins of the padded transform (``padded / 2``),
``branches`` independent transforms of every row (each behind its own
sign vector, which costs no product).

Per branch one ``(rows, n) x (n, bins)`` product with the cosine table,
``2 rows n bins`` flops. Bytes: every row read once, every feature
written once; the table (``4 n bins``) is small beside either. A fast
transform needs fewer flops (``5 P log2 P`` a row) but not on the MXU, so
the product is the program's algorithm and this count is the product's.

The product is float32 at ``Precision.HIGHEST``, six bfloat16 passes on
this chip's MXU, counted as ``block_solve.py`` counts them.
"""
from __future__ import annotations

from benchmarks.counts.block_solve import MXU_PASSES


def flops(rows: int, n: int, bins: int, branches: int) -> float:
    return 2.0 * rows * n * bins * branches


def bytes_moved(rows: int, n: int, bins: int, branches: int,
                itemsize: int = 4) -> float:
    return float(itemsize) * rows * (n + branches * bins)


def roofline_seconds(peaks, rows, n, bins, branches, precision="highest"):
    """``(seconds, bound)``: the least time the chip could take to
    featurize ``rows`` rows, and which peak sets it."""
    compute = (flops(rows, n, bins, branches) * MXU_PASSES[precision]
               / peaks["bf16_flops_per_s"])
    memory = bytes_moved(rows, n, bins, branches) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
