"""Operations and bytes that the convolutions of one RandomPatchCifar
fit need, from its shapes: ``n`` training and ``n_test`` test images of
``positions`` patch positions, a ``patch_dim``-deep (6 x 6 x 3 = 108)
product with ``filters`` filters, ``pools`` pooling regions and two
rectifier halves.

``positions`` are the positions that SOME POOLING REGION COVERS, since
no feature depends on any other: all 27 x 27 of a 32 x 32 image under
its 2 x 2 regions, and 14 x 14 = 196 of the 19 x 19 of a 24 x 24 crop,
whose one region of 14 leaves five rows and columns out. The caller
takes the count from the configuration's geometry (``solve_shape.
pooled_positions`` where the file states it, ``positions`` where every
position is pooled), never from the program's layout or its counters.

A block of features is never stored, so any streamed fit convolves the
training rows at least ``epochs`` times (once an epoch for the step, the
first generation also giving mean, deviation, Gram and factor) and the
test rows once, for their scores. That least is what is counted,
whatever the program does: one that makes a block again (for the update
of ``P``, for a training error; ``blocks_generated.cifar`` reads what it
does) spends the extra generations outside this count and reads a lower
share for them.

* ``product``: ``2 x positions x patch_dim x filters`` flops an image,
  on the matrix unit at ``precision`` (``default``: one bfloat16 pass);
* ``elementwise``: per convolution output nine operations of the vector
  unit (multiply, subtract, multiply for the normalisation with the
  patch's reciprocal deviation; subtract and maximum twice for the two
  rectifier halves against ``bias + alpha`` and ``bias - alpha``; two
  adds into the region's sums), counted apart and NOT among the flops
  the matrix unit's peak is held to;
* bytes: the images (``image_floats`` floats each: 32 x 32 x 3 = 3,072,
  or what the configuration's ``solve_shape`` states) read once a block
  of ``filters_a_block`` filters (512, or what it states) and the pooled
  features written once (``pools x 2 x filters`` floats an image): what a
  maker moves that builds its patches in fast memory. One that stores
  an im2col operand in HBM (``positions x patch_dim`` floats an image and
  block of filters, 26 times the image) moves far more, outside this
  count.
"""
from __future__ import annotations

from typing import Dict

MXU_PASSES = {"highest": 6, "high": 3, "default": 1}
ELEMENTWISE_OPS_AN_OUTPUT = 9.0


def generation_flops(images: int, filters: int, positions: int,
                     patch_dim: int) -> float:
    """Matrix-unit flops of one generation of all blocks for ``images``."""
    return 2.0 * images * positions * patch_dim * filters


def fit_counts(n: int, n_test: int, filters: int, positions: int,
               patch_dim: int, pools: int, epochs: int,
               filters_a_block: int = 512, image_floats: int = 3072,
               itemsize: int = 4) -> Dict[str, float]:
    images = float(epochs) * n + n_test
    blocks = -(-filters // filters_a_block)
    return {
        "product_flops": generation_flops(images, filters, positions,
                                          patch_dim),
        "elementwise_ops": ELEMENTWISE_OPS_AN_OUTPUT * images * positions
        * filters,
        "bytes": itemsize * images * (blocks * image_floats
                                      + pools * 2.0 * filters),
    }


def roofline_seconds(peaks, n, n_test, filters, positions, patch_dim, pools,
                     epochs, precision="default", **geometry):
    """``(seconds, bound)``: the least time the chip could take for one
    fit's convolutions, and which peak sets it. ``geometry``: the
    ``filters_a_block`` and ``image_floats`` of :func:`fit_counts` where
    the configuration states them."""
    counts = fit_counts(n, n_test, filters, positions, patch_dim, pools,
                        epochs, **geometry)
    compute = (counts["product_flops"] * MXU_PASSES[precision]
               / peaks["bf16_flops_per_s"])
    memory = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
