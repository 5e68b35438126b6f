"""Operations and bytes that one block least-squares solve needs, from
its shapes: ``n`` rows, ``d`` features in blocks of ``bs``, ``k`` label
columns, ``passes`` sweeps over the blocks.

Per block and sweep: the symmetric Gram ``A^T A`` (``n bs^2``
multiply-adds counted once for the two triangles, so ``n bs (bs + 1)``
flops), the cross product ``A^T R`` and the residual update ``A dW``
(``2 n bs k`` each); the Cholesky factor (``bs^3 / 3``) and its two
triangular solves (``2 bs^2 k``) once per block. Bytes: the design
matrix read once per sweep and the residual read and written per block;
fusing centring, Gram and cross product into one pass is the least an
implementation can move.

The solver's float32 products run at ``Precision.HIGHEST``, which this
chip's MXU carries out as six bfloat16 passes; ``mxu_passes`` turns the
float32 flops into the bfloat16 flops the peak is quoted in.
"""
from __future__ import annotations

MXU_PASSES = {"highest": 6, "high": 3, "default": 1}


def flops(n: int, d: int, bs: int, k: int, passes: int = 1) -> float:
    blocks = -(-d // bs)
    per_sweep = blocks * (n * bs * (bs + 1) + 4 * n * bs * k)
    once = blocks * (bs ** 3 / 3 + 2 * bs * bs * k)
    return passes * per_sweep + once


def bytes_moved(n: int, d: int, bs: int, k: int, passes: int = 1,
                itemsize: int = 4) -> float:
    blocks = -(-d // bs)
    return itemsize * passes * (n * d + blocks * 2 * n * k)


def roofline_seconds(peaks, n, d, bs, k, passes=1, precision="highest"):
    """``(seconds, bound)``: the least time the chip could take for one
    solve, and which peak sets it."""
    compute = (flops(n, d, bs, k, passes) * MXU_PASSES[precision]
               / peaks["bf16_flops_per_s"])
    memory = bytes_moved(n, d, bs, k, passes) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
