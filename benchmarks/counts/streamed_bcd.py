"""Operations and bytes that one streamed block least-squares fit needs,
from its shapes: ``n`` rows of ``d_in`` inputs, ``blocks`` feature blocks
of ``bs`` random cosines, ``k`` label columns, ``epochs`` sweeps; and of
the blockwise apply of the fitted model to ``n`` rows.

A block is never stored, so any streamed fit makes it at least ``epochs``
times: once an epoch for the step, the first epoch's generation also
giving the mean, the Gram and the factor. That least is what is counted
here, whatever the program does: the program makes a block ``epochs``
times since PR 34 (its factor sweep is the first epoch); one that makes
it ``1 + epochs`` times (a sweep of its own for the factor, as until
then; ``blocks_generated.timit`` reads what a program does) spends the
extra generation outside this count and reads a lower share for it.
Itemised, per block:

* ``generation``: the product ``x W^T`` (``2 n d_in bs`` flops), ``epochs``
  times;
* ``gram``: the symmetric ``A^T A``, the two triangles counted once
  (``n bs (bs + 1)``), once a fit;
* ``factor``: Cholesky (``bs^3 / 3``) once a fit, and its two triangular
  solves (``2 bs^2 k``) every epoch;
* ``epoch_products``: ``A W_old``, ``A^T R`` and ``A dW`` (``2 n bs k``
  each) every epoch;
* ``cosines``: one cosine, one add of ``b``, one subtract of the mean
  and one multiply by the row mask for every feature made: elementwise
  work of the vector unit, counted apart and NOT among the flops the
  matrix unit's peak is held to.

Bytes: what the least an implementation can move through HBM when a
block (``n bs`` floats) does not stay in fast memory: the block written
once and read once each time it is made, the rows and the residual read
per block, the factor written once and read every epoch. Far under the
compute time at these shapes.

The float32 products run at ``Precision.HIGHEST``, six bfloat16 passes of
this chip's matrix unit (``counts/block_solve.py``).
"""
from __future__ import annotations

from typing import Dict

MXU_PASSES = {"highest": 6, "high": 3, "default": 1}


def fit_flops(n: int, d_in: int, bs: int, blocks: int, k: int,
              epochs: int) -> Dict[str, float]:
    """Matrix-unit flops of one fit, by item."""
    return {
        "generation": blocks * epochs * 2.0 * n * d_in * bs,
        "gram": blocks * float(n) * bs * (bs + 1),
        "factor": blocks * (bs ** 3 / 3.0 + epochs * 2.0 * bs * bs * k),
        "epoch_products": blocks * epochs * 3 * 2.0 * n * bs * k,
    }


def apply_flops(n: int, d_in: int, bs: int, blocks: int, k: int
                ) -> Dict[str, float]:
    """Matrix-unit flops of one blockwise apply to ``n`` rows."""
    return {"generation": blocks * 2.0 * n * d_in * bs,
            "scores": blocks * 2.0 * n * bs * k}


def elementwise_ops(n: int, bs: int, blocks: int, made: int) -> float:
    """Vector-unit operations: cosine, bias, centring, mask a feature."""
    return 4.0 * blocks * made * n * bs


def fit_bytes(n: int, d_in: int, bs: int, blocks: int, k: int, epochs: int,
              itemsize: int = 4) -> float:
    block = 2.0 * n * bs                       # written, read back
    rows = float(n) * d_in
    residual = 2.0 * n * k                     # read, written
    return itemsize * blocks * (
        epochs * (block + rows + residual)
        + (1 + epochs) * float(bs) * bs)       # factor written, read


def roofline_seconds(peaks, n, d_in, bs, blocks, k, epochs,
                     precision="highest"):
    """``(seconds, bound)``: the least time the chip could take for one
    fit's matrix products, and which peak sets it."""
    compute = (sum(fit_flops(n, d_in, bs, blocks, k, epochs).values())
               * MXU_PASSES[precision] / peaks["bf16_flops_per_s"])
    memory = fit_bytes(n, d_in, bs, blocks, k, epochs) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
