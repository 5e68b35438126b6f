"""Operations that the Fisher vector of one image needs: ``n``
descriptors of ``d`` dimensions under ``k`` diagonal Gaussians. Four
products of ``2 n d k`` flops: the Mahalanobis distance as ``x^2 A`` and
``x B`` (the posteriors), and the two moments ``X q`` and ``X^2 q``; the
softmax, the threshold and the final scaling are vector-unit work and
not counted. 7.7 GFLOP at 47,213 descriptors, 80 dimensions, 256
components. The same count whether the posteriors pass through HBM or
stay in a kernel's fast memory; bytes: the descriptors read once (the
posteriors never need to leave the chip).
"""
from __future__ import annotations

MXU_PASSES = {"highest": 6, "high": 3, "default": 1}


def flops(n: float, d: int, k: int) -> float:
    return 8.0 * n * d * k


def roofline_seconds(peaks, descriptors: float, d: int, k: int, images: int,
                     precision: str = "highest"):
    """``(seconds, bound)`` for ``images`` images of ``descriptors``
    descriptors each."""
    compute = (images * flops(descriptors, d, k) * MXU_PASSES[precision]
               / peaks["bf16_flops_per_s"])
    memory = images * 4.0 * descriptors * d / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
