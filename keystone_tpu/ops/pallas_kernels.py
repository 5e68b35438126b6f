"""Pallas TPU kernels for the image and solver hot paths.

The kernel program (PERFORMANCE.md rule 13: write the kernel only where
the roofline says so):

* :func:`gram_cross` — fused ``(X^T X, X^T Y)`` for the least-squares
  family (SURVEY.md section 3.2 — the reference's per-partition Gram +
  treeReduce). As separate XLA ops each GEMM reads X from HBM once; the
  fused kernel streams each row-tile of X through VMEM exactly once and
  accumulates both products on the MXU.
* :func:`fv_moments_pallas` — fused GMM-posterior + Fisher-vector
  moment accumulation (dispatched from
  ``nodes/images/fisher_vector.py``). The split form materializes the
  ``(nDesc, K)`` posterior matrix in HBM between the posterior and
  moment programs; the fused kernel computes posteriors tile-by-tile
  and accumulates the q/s1/s2 moment sums in VMEM — the stage flips
  from memory-bound to compute-bound (PR 9 roofline).
* :func:`quantized_affine_pallas` — the serving plane's quantized
  predict (dispatched from ``nodes/learning/linear.py``):
  ``((x - mean) * inv_std) @ W + b`` with W resident in VMEM at bf16 or
  int8 (per-column scales), dequantized on the fly, f32 accumulation.

All reductions follow the standard Pallas pattern: outputs map to the
same block every grid step, zeroed on the first step and accumulated.
Row padding is zero-filled by the wrappers, so padded rows contribute
nothing (the FV kernel additionally masks padded descriptor columns —
a zero descriptor still has a nonzero posterior).

Every kernel dispatches via :func:`use_pallas` plus a per-kernel
VMEM-fit predicate (one shared budget, :func:`fits_vmem`) and keeps an
einsum fallback for the CPU backend and for shapes past the budget.
Each ``pallas_call`` asks the compiler for exactly the scoped VMEM its
footprint needs (:func:`_compiler_params`). Tests exercise the kernel
bodies in interpreter mode on CPU (``interpret=True``); what only the
Mosaic compiler can refuse (VMEM, layouts, dot precisions) is checked
on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.compilelog import observed_jit

ROW_TILE = 512
_LANE = 128
_SUBLANE = 8
_F32 = 4  # bytes


def _gram_cross_kernel(x_ref, y_ref, gram_ref, cross_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        gram_ref[:] = jnp.zeros_like(gram_ref)
        cross_ref[:] = jnp.zeros_like(cross_ref)

    x = x_ref[:]
    # these Grams feed Cholesky solves, so the solver precision policy
    # applies. Mosaic lowers only DEFAULT and HIGHEST (HIGH is refused
    # at compile time), and the policy's floor is HIGH: always HIGHEST.
    gram_ref[:] += jax.lax.dot_general(
        x, x, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    cross_ref[:] += jax.lax.dot_general(
        x, y_ref[:], dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(observed_jit, static_argnames=("interpret",))
def gram_cross_pallas(X: jax.Array, Y: jax.Array,
                      interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(X^T X, X^T Y) in one pass over X. Pads to tile alignment
    (lane = 128, sublane = 8 for f32) and slices back."""
    n, d = X.shape
    k = Y.shape[1]
    dp, kp, tile = _gram_dims(n, d, k)
    np_rows = _round_up(n, tile)
    Xp = _pad_to(X.astype(jnp.float32), np_rows, dp)
    Yp = _pad_to(Y.astype(jnp.float32), np_rows, kp)

    grid = (np_rows // tile,)
    gram, cross = pl.pallas_call(
        _gram_cross_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, dp), lambda i: (i, 0)),
            pl.BlockSpec((tile, kp), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((dp, dp), lambda i: (0, 0)),
            pl.BlockSpec((dp, kp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, kp), jnp.float32),
        ],
        compiler_params=_compiler_params(gram_vmem_bytes(d, k, tile)),
        interpret=interpret,
    )(Xp, Yp)
    return gram[:d, :d], cross[:d, :k]


def use_pallas() -> bool:
    """True on the TPU backend: the only one these kernels compile for.
    Other backends take each dispatcher's einsum path."""
    return jax.default_backend() == "tpu"


#: Per-core VMEM by ``device_kind``. Only kinds a builder has compiled
#: these kernels on are listed; ``memory_stats()`` reports HBM only, so
#: the table is the probe. A kind that is not here is an error, not a
#: default: add its row after establishing the boundary on that chip.
_VMEM_BYTES_BY_KIND = {
    "TPU v5 lite": 128 * 1024 * 1024,
}

#: The share of VMEM one kernel may claim through ``vmem_limit_bytes``.
#: The rest stays with the compiler (its own spills and the scoped
#: allocations of neighbouring fusions). On a v5e under jax 0.9.0 every
#: kernel here compiled at every footprint tried up to this share
#: (gram at d=2560, k=128: 89 MiB) once its ``pallas_call`` asked for
#: it; without the request the 16 MiB scoped default is the ceiling
#: the old "d=896 compiles, d=1024 crashes" boundary measured.
_VMEM_KERNEL_SHARE = 0.75

#: Mosaic's scoped-VMEM default on the kinds above. Kernels under it
#: compile with no request; ``_compiler_params`` never asks for less.
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


def vmem_budget_bytes() -> int:
    """The shared per-kernel VMEM budget in bytes: ONE home for the
    fits-vmem arithmetic every dispatcher uses (gram, fused featurizer,
    fused FV, quantized predict)."""
    kind = jax.devices()[0].device_kind
    if kind not in _VMEM_BYTES_BY_KIND:
        raise ValueError(
            f"no VMEM size recorded for device_kind {kind!r}; known: "
            f"{sorted(_VMEM_BYTES_BY_KIND)}. Establish the kernels' "
            "compile boundary on that chip (chip_smoke.py) and add its "
            "row to ops.pallas_kernels._VMEM_BYTES_BY_KIND")
    return int(_VMEM_BYTES_BY_KIND[kind] * _VMEM_KERNEL_SHARE)


def fits_vmem(nbytes: float) -> bool:
    """True when a kernel whose VMEM-resident footprint is ``nbytes``
    (double-buffered blocks + live temporaries, as each kernel's
    ``*_vmem_bytes`` counts them) fits the shared budget. Beyond it the
    dispatchers take the einsum path instead of attempting a compile
    Mosaic would refuse."""
    return nbytes <= vmem_budget_bytes()


def _compiler_params(vmem_bytes: float) -> "pltpu.CompilerParams":
    """Scoped VMEM for one ``pallas_call``: the kernel's own footprint
    plus a quarter for the compiler's temporaries, never less than the
    default the small shapes already compile under."""
    return pltpu.CompilerParams(vmem_limit_bytes=max(
        _DEFAULT_SCOPED_VMEM, int(1.25 * vmem_bytes)))


def _gram_dims(n: int, d: int, k: int) -> Tuple[int, int, int]:
    """(padded feature dim, padded label dim, row tile) of the fused
    gram kernel for an (n, d) x (n, k) call."""
    dp = _round_up(max(d, _LANE), _LANE)
    kp = _round_up(max(k, _LANE), _LANE)
    tile = min(ROW_TILE, _round_up(max(n, _SUBLANE), _SUBLANE))
    return dp, kp, tile


def gram_vmem_bytes(d: int, k: int, tile: int = ROW_TILE) -> int:
    """VMEM footprint of the fused gram kernel: the (dp, dp) + (dp, kp)
    accumulator blocks live across the whole grid, double-buffered like
    every pipelined block, plus one more copy as the dot results before
    they are added in; and the double-buffered (tile, dp) / (tile, kp)
    input blocks."""
    dp, kp, _ = _gram_dims(tile, d, k)
    return _F32 * (3 * dp * (dp + kp) + 2 * tile * (dp + kp))


def gram_fits_vmem(d: int, k: int) -> bool:
    """True when the fused gram kernel fits the VMEM budget for feature
    dim d and label dim k (pre-padding)."""
    return fits_vmem(gram_vmem_bytes(d, k))


def gram_cross(X: jax.Array, Y: jax.Array,
               mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Fused (X^T X, X^T Y): Pallas on TPU when the footprint fits
    VMEM; the einsum fallback keeps the solver precision policy.

    ``pallas_call`` has no partitioning rule, so a caller whose rows are
    sharded over a mesh's ``data`` axis passes that ``mesh``: each
    device then runs the kernel on its own rows and the partial
    products are summed across the axis (the reference's per-partition
    Gram + treeReduce). With ``mesh=None`` the inputs must live on one
    device.

    Integer inputs (uint8 wire-dtype chunks fed straight into a Gram
    accumulate) are promoted to f32 up front in BOTH paths: the pallas
    wrapper casts internally anyway, and the einsum fallback would
    otherwise wrap the products mod 256. Inside the surrounding jit the
    promotion fuses with the first read of each row tile — no separate
    f32 copy of the chunk is materialized in HBM."""
    if not jnp.issubdtype(X.dtype, jnp.floating):
        X = X.astype(jnp.float32)
    if not jnp.issubdtype(Y.dtype, jnp.floating):
        Y = Y.astype(jnp.float32)
    if use_pallas() and gram_fits_vmem(X.shape[1], Y.shape[1]):
        if mesh is None or mesh.size == 1:
            return gram_cross_pallas(X, Y)
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS

        def local(x, y):
            return jax.lax.psum(gram_cross_pallas(x, y), DATA_AXIS)

        rows = P(DATA_AXIS, None)
        return jax.shard_map(local, mesh=mesh, in_specs=(rows, rows),
                             out_specs=(P(), P()), check_vma=False)(X, Y)
    from .linalg import SOLVER_PRECISION

    G = jnp.einsum("nd,ne->de", X, X, precision=SOLVER_PRECISION)
    C = jnp.einsum("nd,nk->dk", X, Y, precision=SOLVER_PRECISION)
    return G, C


# -- fused CIFAR featurization ---------------------------------------------
#
# The north-star pipeline (Convolver -> SymmetricRectifier -> Pooler,
# SURVEY.md section 6) is HBM-bound as separate XLA ops: the (27, 27, 2K)
# rectifier intermediate alone is ~6 MB/image written + read back. The
# fused kernel moves through HBM the images (12 KB each) and the
# (regions, 2K) pooled features and nothing else: the im2col operand
# (397 KB an image, built by XLA in HBM until PR 42), the patch GEMM on
# the MXU, patch normalization, symmetric rectification and region-sum
# pooling all stay in VMEM.
#
# Pooling is NOT a mask GEMM (it was until PR 30): with the rectified
# (P, K) block as the stationary operand of an 8-row product the MXU
# spends its time loading 24 weight tiles an image and half, 4.8 us an
# image where the patch GEMM needs 0.5 (my chip run, PR 30). The pooling
# regions are rectangles that overlap in one row and one column of patch
# positions, so the rows of the patch matrix are laid out as the
# DISJOINT rectangles between the regions' edges (3 x 3 = 9 segments at
# CIFAR shapes), each padded to whole sublane tiles; a region's sum is
# then a few row-range sums on the vector unit.
#
# Only the rectangles that some region covers are laid out (PR 46). The
# reference's ``Pooler`` starts its regions at 0 and stops where the
# next centre would pass the last position, so positions past the last
# region (and, where the stride is over the size, between two) are
# pooled by nothing: on a 24 x 24 crop the one region ``[0, 14)``
# squared keeps 196 of 19 x 19 = 361, and the patches, statistics and
# products of the other 165 (176 of 376 padded rows) were work that
# nothing read. Every row of all three is independent of every other,
# so leaving them out moves no pooled sum; at 32 x 32 the four regions
# cover all 729 and the layout is what it was.
#
# The vector unit's issue slots bound the kernel, not the product (the
# compiler's schedule for a v5e, PR 37): so the epilogue divides on the
# (P, 1) column only and folds bias and alpha into two rows, nine
# operations an output, and the patch statistics (30 operations on each
# of 97 nearly empty registers: as much again as the epilogue of one
# bank) are made once an image for all the banks of a call, the banks
# being the grid's inner axis.


def _pool_layout(out_dim: int, pool_stride: int, pool_size: int):
    """``(intervals, regions)``: the cuts of one axis of patch positions
    at every pooling region's edges, and for each region (one axis) the
    indices of the intervals it covers. Reference ``Pooler`` geometry:
    centres from ``pool_size // 2`` every ``pool_stride``, a region
    ``[c - half, min(c + half, out_dim))``."""
    half = pool_size // 2
    spans = [(c - half, min(c + half, out_dim))
             for c in range(half, out_dim, pool_stride)]
    cuts = sorted({0, out_dim, *(e for span in spans for e in span)})
    intervals = [(a, b) for a, b in zip(cuts, cuts[1:])]
    regions = [[i for i, (a, b) in enumerate(intervals) if lo <= a and b <= hi]
               for lo, hi in spans]
    return intervals, regions


def _fused_layout(img_size, patch_size, pool_stride, pool_size):
    """Where the kernel's patch matrix keeps each patch position:
    ``(windows, segments, regions)``. Of the disjoint rectangles of
    ``_pool_layout``, x-major, ONLY those that some region pools are
    laid out (a rectangle past the last region or in a gap between two
    gets no rows and no windows: nothing would read its patches, their
    statistics or their products; at 24 x 24 the one region keeps 196 of
    the 361 positions, at 32 x 32 every position is kept), a segment
    ``(first row, real rows)`` each, padded at its END to whole sublane
    tiles (the kernel leaves those rows out of the segment's sums);
    ``regions`` index the segments. Inside a segment the order is free
    (a segment is only ever summed): a column of the image at a time, so
    that the positions of one window ``(first image row, rows, image
    column, first patch row)`` are consecutive rows of the image AND of
    the matrix."""
    out_dim = img_size - patch_size + 1
    intervals, axis_regions = _pool_layout(out_dim, pool_stride, pool_size)
    n = len(intervals)
    of_regions = [tuple(i * n + j for i in xs for j in ys)
                  for xs in axis_regions for ys in axis_regions]  # x-major
    pooled = sorted({r for members in of_regions for r in members})
    windows, segments, at = [], [], 0
    for r in pooled:              # x-major, as the rectangles are numbered
        (x0, x1), (y0, y1) = intervals[r // n], intervals[r % n]
        rows = (x1 - x0) * (y1 - y0)
        windows.extend((x0, x1 - x0, y, at + (y - y0) * (x1 - x0))
                       for y in range(y0, y1))
        segments.append((at, rows))
        at += _round_up(rows, _SUBLANE)
    regions = tuple(tuple(pooled.index(r) for r in members)
                    for members in of_regions)
    return tuple(windows), tuple(segments), regions


def fused_positions_kept(img_size, patch_size, pool_stride, pool_size):
    """``(kept, left out)``: how many of an image's patch positions the
    fused featurizer lays out (``_fused_layout``: those some region
    pools) and how many it never builds."""
    _, segments, _ = _fused_layout(
        img_size, patch_size, pool_stride, pool_size)
    kept = sum(real for _, real in segments)
    return kept, (img_size - patch_size + 1) ** 2 - kept


def _build_patches(img_ref, patch_ref, t, windows, patch_size, channels):
    """Image ``t`` of the ``(T, H, W * C)`` block into its ``(Pp, Fp)``
    patch matrix, features ``(dy, dx, c)``: a patch's row ``dy`` is ``S
    * C`` consecutive lanes of image row ``x + dy``, so a window of
    ``_fused_layout`` is, for each ``dy``, a copy of a few rows, lanes
    ``y * C ...`` to lanes ``dy * S * C ...``. The image is read once,
    whole: the compiler then rotates each of its registers once a
    DISTANCE (``(dy, y)`` and ``(dy + 1, y + S)`` share theirs: 57 at
    CIFAR shapes, 224 rotates an image where a load a window makes
    785), and what is left is a masked store a register and window."""
    patch_row = patch_size * channels
    image, patches = img_ref[t], patch_ref.at[t]
    for x0, rows, y, at in windows:
        for dy in range(patch_size):
            patches[at:at + rows, dy * patch_row:(dy + 1) * patch_row] = (
                jax.lax.slice(image, (x0 + dy, y * channels),
                              (x0 + dy + rows, y * channels + patch_row)))


def _with_frame_room(fn):
    """``fn`` under one Python frame of 512 KB. CPython (3.11 on) keeps
    a thread's frames in chunks of 16 KB and gives a chunk back, an
    ``mmap`` and a ``munmap``, the moment the frame at its start
    returns: a loop whose calls cross a chunk's end pays both at every
    call, 10 us in a sandbox and 150 us on the host of a TPU, 3,500
    times a call. Tracing an unrolled kernel body is some hundred
    thousand calls at one depth, and whether a chunk ends there is the
    luck of the stack above it: ``cifar_refit`` spent six seconds of
    every process's set-up so (PERF.md section 6, PR 42). A frame too
    large for what is left of a chunk starts a new one, of 1 MB, which
    lives as long as the frame does and holds every call beneath it."""
    @functools.wraps(fn)
    def roomy(*args, **kwargs):
        return fn(*args, **kwargs)
    roomy.__code__ = roomy.__code__.replace(co_stacksize=1 << 16)
    return roomy


@_with_frame_room
def _fused_featurize_kernel(img_ref, filt_ref, rows_ref, out_ref, patch_ref,
                            mean_ref, inv_sd_ref, *, patch_size, channels,
                            var_constant, windows, segments, regions):
    """A few images against one filter bank: grid ``(image groups,
    banks)``, the banks innermost, so what depends on the images alone
    (their patches, the patches' statistics) is made at bank 0, into
    scratch, and stays where it is while the banks go by. Patches,
    statistics, product and epilogue all run over the ``Pp`` rows of
    ``_fused_layout``, which holds no position that no region pools, so
    the epilogue sums every segment."""
    images = img_ref.shape[0]
    bank = pl.program_id(1)
    f_true = float(patch_size * patch_size * channels)

    @pl.when((pl.program_id(0) == 0) & (bank == 0))
    def _():
        # the columns past the features' and the rows past a segment's
        # are never written: zero, once, where the product reads them
        patch_ref[...] = jnp.zeros_like(patch_ref)

    @pl.when(bank == 0)
    def _():
        def patches_and_statistics(t, _):
            _build_patches(img_ref, patch_ref, t, windows, patch_size,
                           channels)
            # the patch's mean and reciprocal deviation, once an image
            # and kept, lane-replicated, for every bank; the one divide
            # is on this column and not on the (P, K) block
            p = patch_ref[t]                       # (P, F)
            psum = jnp.sum(p, axis=1, keepdims=True)
            psq = jnp.sum(p * p, axis=1, keepdims=True)
            m = psum / f_true
            var = (psq - f_true * m * m) / (f_true - 1.0)
            mean_ref[t] = jnp.broadcast_to(m, mean_ref.shape[1:])
            inv_sd_ref[t] = jnp.broadcast_to(
                1.0 / jnp.sqrt(var + var_constant), inv_sd_ref.shape[1:])
        jax.lax.fori_loop(0, images, patches_and_statistics, None)

    rows = rows_ref[bank]
    k = rows.shape[1]
    lanes = [slice(c, c + _LANE) for c in range(0, k, _LANE)]
    # the filters go by in passes of ``FUSED_LANES_A_PASS`` lane tiles:
    # a pass keeps two sums a lane tile and its thresholds in registers,
    # and a bank of 2,048 filters would need more than there are
    passes = [lanes[i:i + FUSED_LANES_A_PASS]
              for i in range(0, len(lanes), FUSED_LANES_A_PASS)]
    tile = (_SUBLANE, _LANE)

    def thresholds(cols):
        # bias = filters @ whitener_means is subtracted post-normalization
        # exactly like filter_bank_convolve (image_ops.py:110-111): it
        # rides in the rectifier's thresholds, bias + alpha and bias - alpha
        return tuple([jnp.broadcast_to(rows[i:i + 1, c], tile) for c in cols]
                     for i in range(3))

    # one pass: made once a step; more: once a pass, where they are used
    held = thresholds(passes[0]) if len(passes) == 1 else None

    def featurize(t, _):
        for cols_of_pass in passes:
            fsum, above, below = held or thresholds(cols_of_pass)
            first, last = cols_of_pass[0].start, cols_of_pass[-1].stop
            filt = (filt_ref[bank] if held is not None
                    else filt_ref[bank, :, first:last])
            raw = jnp.dot(patch_ref[t], filt,
                          preferred_element_type=jnp.float32)  # (P, K a pass)
            _pool_one_pass(raw, first, cols_of_pass, fsum, above, below, t)

    def _pool_one_pass(raw, first, lanes, fsum, above, below, t):
        means, inv_sds = mean_ref.at[t], inv_sd_ref.at[t]
        zero = jnp.zeros(tile, jnp.float32)
        # One pass over the product, a register (8 rows x 128 filters)
        # at a time: nine vector operations an output (mul, sub, mul;
        # sub, max; sub, max; an add a half into the segment's sums).
        # Spelt as ``lax`` primitives on views of the image's refs: the
        # 4,000 operations are traced in every process that holds this
        # kernel, and a ``jnp`` operator costs several times a bind.
        sums = []            # a segment: its (pos, neg) sums a lane tile
        for start, real in segments:   # every one is in some region
            acc = [[None, None] for _ in lanes]
            for at in range(start, start + real, _SUBLANE):
                m, inv_sd = (ref[at:at + _SUBLANE, :]
                             for ref in (means, inv_sds))
                # a segment's padding rows are its last: left out
                left = start + real - at
                keep = (None if left >= _SUBLANE else
                        jax.lax.broadcasted_iota(jnp.int32, tile, 0) < left)
                for c, cols in enumerate(lanes):
                    u = jax.lax.mul(jax.lax.sub(
                        jax.lax.slice(raw, (at, cols.start - first),
                                      (at + _SUBLANE, cols.stop - first)),
                        jax.lax.mul(m, fsum[c])), inv_sd)
                    for half, h in enumerate((
                            jax.lax.max(jax.lax.sub(u, above[c]), zero),
                            jax.lax.max(jax.lax.sub(below[c], u), zero))):
                        if keep is not None:
                            h = jax.lax.select(keep, h, zero)
                        acc[c][half] = h if acc[c][half] is None else (
                            jax.lax.add(acc[c][half], h))
            sums.append(acc)
        # an image's features are one row, (region, half, filter), of
        # the block the caller keeps, so nothing is copied after the
        # call; row ``t`` of a register of the step's images is chosen
        # by a select (Mosaic stores no single row at a dynamic index)
        mine = jax.lax.broadcasted_iota(
            jnp.int32, (images, _LANE), 0) == t
        for r, members in enumerate(regions):
            for half in (0, 1):
                for c, cols in enumerate(lanes):
                    total = sums[members[0]][c][half]
                    for i in members[1:]:
                        total = jax.lax.add(total, sums[i][c][half])
                    at = (2 * r + half) * k + cols.start
                    out_ref[0, :, at:at + _LANE] = jnp.where(
                        mine, jnp.sum(total, axis=0, keepdims=True),
                        out_ref[0, :, at:at + _LANE])
    jax.lax.fori_loop(0, images, featurize, None)


#: Lane tiles of filters (128 each) the fused featurizer's epilogue
#: takes a pass: 512 filters, the bank the kernel was tuned on.
FUSED_LANES_A_PASS = 4
#: Images a grid step of the fused featurizer, at most: a step costs
#: about 0.09 us whatever it does, a tenth of one image's work against
#: one bank (my chip run, PR 37: 1.19 us an image and bank at one image
#: a step, 1.15 at two, 1.11 at 4, 8 and 16).
FUSED_IMAGES_A_STEP = 8


def fused_featurize_vmem_bytes(p: int, f: int, k: int, r: int, banks: int,
                               images: int, image_floats: int) -> int:
    """VMEM footprint of one grid step of the fused featurizer for
    (padded) P patch positions, F patch features, K filters, R pooling
    regions, ``banks`` filter banks and ``images`` images a step of
    ``image_floats`` (padded) floats each: the images' (P, F) patch
    matrices are scratch, built once an image and kept for the banks;
    the (P, K) product of one image is the one intermediate of that size
    (the epilogue walks it a register at a time); the patch statistics
    are two lane-replicated (P, 128) scratch arrays an image; the
    images' own and output blocks and the banks' filters and rows are
    double-buffered."""
    blocks = images * (image_floats + r * 2 * k) + banks * (f + _SUBLANE) * k
    scratch = images * p * (f + 2 * _LANE)
    temps = p * k + 2 * p * f
    return _F32 * (2 * blocks + scratch + temps)


def _fused_geometry(img_size, patch_size, channels, pool_stride, pool_size,
                    k):
    """``(Pp, Fp, Kp, R, padded floats an image)`` of the fused
    featurizer, from the shapes."""
    _, segments, regions = _fused_layout(
        img_size, patch_size, pool_stride, pool_size)
    start, real = segments[-1]
    return (start + _round_up(real, _SUBLANE),
            _round_up(patch_size * patch_size * channels, _LANE),
            _round_up(k, _LANE), len(regions),
            _round_up(img_size, _SUBLANE)
            * _round_up(img_size * channels, _LANE))


def fused_featurize_fits_vmem(img_size, patch_size, channels, pool_stride,
                              pool_size, k, banks=1) -> bool:
    """True when the fused featurizer, whose kernel holds the patch
    matrices of ``FUSED_IMAGES_A_STEP`` images in VMEM, fits the budget
    at this geometry, ``k`` filters a bank and ``banks`` banks a call;
    where it does not the callers take the composed ops."""
    pp, fp, kp, r, image = _fused_geometry(
        img_size, patch_size, channels, pool_stride, pool_size, k)
    return fits_vmem(fused_featurize_vmem_bytes(
        pp, fp, kp, r, banks, FUSED_IMAGES_A_STEP, image))


_FUSED_STATICS = ("img_size", "patch_size", "channels", "pool_stride",
                  "pool_size", "var_constant", "alpha", "interpret")


@functools.partial(observed_jit, static_argnames=_FUSED_STATICS)
def fused_cifar_featurize_banks(imgs, filters, img_size=32, patch_size=6,
                                channels=3, pool_stride=13, pool_size=14,
                                var_constant=10.0, alpha=0.25,
                                whitener_means=None, interpret=False):
    """Batched fused featurization for ``g`` filter banks: images ``(B,
    H, W, C)``, filters ``(g, K, S*S*C)`` (``whitener_means`` ``(g,
    S*S*C)`` or None) -> pooled ``(g, B, nPools*nPools*2K)`` features, a
    bank's numerically identical to Convolver(normalize) >>
    SymmetricRectifier >> Pooler(sum) >> vectorize. The images go in as
    they are and the features come out as the caller keeps them: the
    patches and their statistics are the same for every bank, and one
    call makes each once an image, in VMEM."""
    B = imgs.shape[0]
    S, C = patch_size, channels
    F = S * S * C
    g, K = filters.shape[:2]
    windows, segments, regions = _fused_layout(
        img_size, S, pool_stride, pool_size)
    Pp, Fp, Kp, R, image_floats = _fused_geometry(
        img_size, S, C, pool_stride, pool_size, K)
    filters = filters.astype(jnp.float32)
    # the features in the kernel's order, (dy, dx, c): the filters' own
    filt = jnp.pad(filters.transpose(0, 2, 1),
                   ((0, 0), (0, Fp - F), (0, Kp - K)))
    fsum = jnp.sum(filters, axis=2)
    if whitener_means is not None:
        # in float32 whatever the precision the products around run at
        bias = jnp.sum(filters * jnp.asarray(
            whitener_means, jnp.float32)[:, None, :], axis=2)
    else:
        bias = jnp.zeros((g, K), jnp.float32)
    # a bank's rows: the filters' sums and the rectifier's two
    # thresholds on the normalised product,
    # pos = max(u - (bias + alpha), 0), neg = max((bias - alpha) - u, 0)
    rows = jnp.pad(jnp.stack([fsum, bias + alpha, bias - alpha], axis=1),
                   ((0, 0), (0, _SUBLANE - 3), (0, Kp - K)))
    kernel = functools.partial(
        _fused_featurize_kernel, patch_size=S, channels=C,
        var_constant=float(var_constant), windows=windows,
        segments=segments, regions=regions)
    T = min(FUSED_IMAGES_A_STEP, B)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(B, T), g),
        in_specs=[
            pl.BlockSpec((T, img_size, img_size * C), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((g, Fp, Kp), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((g, _SUBLANE, Kp), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, R * 2 * Kp), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, B, R * 2 * Kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((T, Pp, Fp), jnp.float32)]
        + [pltpu.VMEM((T, Pp, _LANE), jnp.float32)] * 2,
        compiler_params=_compiler_params(fused_featurize_vmem_bytes(
            Pp, Fp, Kp, R, g, T, image_floats)),
        interpret=interpret,
        name="fused_cifar_featurize",
    )(imgs.astype(jnp.float32).reshape(B, img_size, img_size * C), filt,
      rows)
    if Kp == K:
        return out
    # strip the filters' padding: K a half and region
    return out.reshape(g, B, 2 * R, Kp)[..., :K].reshape(g, B, R * 2 * K)


# -- fused GMM-posterior + Fisher-vector moments ---------------------------
#
# The FV stage's split form (nodes/images/fisher_vector.py) runs the
# posterior program, writes the (nDesc, K) responsibility matrix q to
# HBM, then reads it back for the three moment GEMMs — at ImageNet
# shapes (~1e4 descriptors x K) that round trip made the stage
# memory-bound on the PR 9 roofline. The fused kernel computes q one
# descriptor tile at a time entirely in VMEM and accumulates the moment
# sums (s0 = sum q, s1 = X q, s2 = (X*X) q) into VMEM-resident
# accumulators; q never exists in HBM. s0 rides as an extra all-ones
# row of X (row D of the padded operand), so the kernel has exactly two
# outputs and the sums stay exact.

FV_TILE = 512  # descriptor columns per grid step


def _fv_moments_kernel(x_ref, a_ref, b_ref, c_ref, s1_ref, s2_ref, *,
                       n_valid, tile, threshold, precision):
    @pl.when(pl.program_id(0) == 0)
    def _():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    x = x_ref[:]                                  # (Dp, T) tile of X
    xsq = x * x
    # sq_mahl/llh exactly as _posteriors (gmm.py): XSq A - X B + const,
    # with the per-k constants folded host-side into c (padded K
    # columns carry -1e30 so they vanish under the max-shift)
    mahl = jax.lax.dot_general(
        xsq, a_ref[:], dimension_numbers=(((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    mahl -= jax.lax.dot_general(
        x, b_ref[:], dimension_numbers=(((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    llh = c_ref[:] - mahl                         # (1, Kp) - (T, Kp)
    shifted = llh - jnp.max(llh, axis=1, keepdims=True)
    q = jnp.exp(shifted)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > threshold, q, 0.0)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    # padded descriptor columns: a zero descriptor still has a nonzero
    # posterior, so mask by global column index (n_valid is static)
    col = (pl.program_id(0) * tile
           + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0))
    q = jnp.where(col < n_valid, q, 0.0)
    s1_ref[:] += jax.lax.dot_general(
        x, q, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    s2_ref[:] += jax.lax.dot_general(
        xsq, q, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


@functools.partial(
    observed_jit, name="fv_moments",
    static_argnames=("threshold", "interpret", "precision"),
)
def fv_moments_pallas(X, means, variances, weights, *, threshold,
                      interpret=False, mask=None, precision=None):
    """Raw moment sums ``(s0, s1, s2)`` of the thresholded GMM
    posteriors of ``X`` (a (D, nDesc) descriptor matrix) without ever
    materializing the (nDesc, K) posterior matrix in HBM. Returns SUMS
    (the caller divides by nDesc, matching the fallback's means).
    ``mask`` ``(nDesc,)``: which columns are descriptors at all, where
    ``X`` is padded with zero columns: it rides in the row that carries
    ``s0`` (a padded column adds nothing to ``s1`` and ``s2`` as it is).
    ``precision``: of the four products (Mosaic's default for float32
    operands is one bfloat16 pass)."""
    d, n = X.shape
    k = means.shape[1]
    # one extra all-ones row carries s0 = sum(q) through the s1 GEMM
    dp = _round_up(max(d + 1, _LANE), _LANE)
    kp = _round_up(max(k, _LANE), _LANE)
    tile = min(FV_TILE, _round_up(max(n, _LANE), _LANE))
    np_cols = _round_up(n, tile)
    Xp = jnp.zeros((dp, np_cols), jnp.float32)
    Xp = Xp.at[:d, :n].set(X.astype(jnp.float32))
    Xp = Xp.at[d, :n].set(1.0 if mask is None else mask.astype(jnp.float32))
    A = jnp.zeros((dp, kp), jnp.float32).at[:d, :k].set(0.5 / variances)
    B = jnp.zeros((dp, kp), jnp.float32).at[:d, :k].set(means / variances)
    const = (-0.5 * d * jnp.log(2.0 * jnp.pi)
             - 0.5 * jnp.sum(jnp.log(variances), axis=0)
             + jnp.log(weights)
             - 0.5 * jnp.sum(means * means / variances, axis=0))
    c = jnp.full((1, kp), -1e30, jnp.float32).at[0, :k].set(const)

    kernel = functools.partial(
        _fv_moments_kernel, n_valid=n, tile=tile,
        threshold=float(threshold), precision=precision)
    s1, s2 = pl.pallas_call(
        kernel,
        grid=(np_cols // tile,),
        in_specs=[
            pl.BlockSpec((dp, tile), lambda i: (0, i)),
            pl.BlockSpec((dp, kp), lambda i: (0, 0)),
            pl.BlockSpec((dp, kp), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((dp, kp), lambda i: (0, 0)),
            pl.BlockSpec((dp, kp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp, kp), jnp.float32),
            jax.ShapeDtypeStruct((dp, kp), jnp.float32),
        ],
        compiler_params=_compiler_params(fv_vmem_bytes(d, k)),
        interpret=interpret,
    )(Xp, A, B, c)
    return s1[d, :k], s1[:d, :k], s2[:d, :k]


def fv_vmem_bytes(d: int, k: int) -> int:
    """VMEM footprint of the fused FV kernel: the two (Dp, Kp) moment
    accumulators and the (Dp, Kp) A/B parameter blocks (all four
    double-buffered), double-buffered (Dp, tile) descriptor tiles plus
    their squares, and the (tile, Kp) q/llh working set (~3 live
    temps)."""
    dp = _round_up(max(d + 1, _LANE), _LANE)
    kp = _round_up(max(k, _LANE), _LANE)
    return _F32 * (8 * dp * kp + 3 * dp * FV_TILE + 3 * FV_TILE * kp
                   + 2 * _SUBLANE * kp)


def fv_fits_vmem(d: int, k: int) -> bool:
    return fits_vmem(fv_vmem_bytes(d, k))


# -- quantized predict (serving plane) -------------------------------------
#
# The fitted-model apply is one affine program (linear.py
# _affine_apply_batch); at serving batch sizes it is weight-bandwidth
# bound: every request batch re-reads the full f32 (d, k) weight
# matrix from HBM. The quantized kernel holds W VMEM-resident at bf16
# or int8 (per-column scales — the PR 5 wire_dtype discipline applied
# to weights), dequantizes on the fly, and accumulates in f32.

QUANT_TILE = 128  # batch rows per grid step


def _quantized_affine_kernel(x_ref, w_ref, scale_ref, mean_ref, inv_ref,
                             b_ref, o_ref):
    xn = (x_ref[:] - mean_ref[:]) * inv_ref[:]     # (1, Dp) rows broadcast
    w = w_ref[:].astype(jnp.float32) * scale_ref[:]
    o_ref[:] = jnp.dot(xn, w, preferred_element_type=jnp.float32) \
        + b_ref[:]


@functools.partial(observed_jit, name="quantized_affine",
                   static_argnames=("interpret",))
def quantized_affine_pallas(X, Wq, scale, mean, inv_std, b,
                            interpret=False):
    """``((X - mean) * inv_std) @ dequant(Wq) + b`` with ``Wq`` in bf16
    or int8 and ``scale`` the per-column dequantization scales (ones
    for bf16). W stays VMEM-resident across the whole batch; only the
    batch tiles stream."""
    n, d = X.shape
    k = Wq.shape[1]
    dp = _round_up(max(d, _LANE), _LANE)
    kp = _round_up(max(k, _LANE), _LANE)
    tile = min(QUANT_TILE, _round_up(max(n, _SUBLANE), _SUBLANE))
    np_rows = _round_up(n, tile)
    Xp = _pad_to(X.astype(jnp.float32), np_rows, dp)
    Wp = _pad_to(Wq, dp, kp)
    def row(v, width):
        return _pad_to(v.astype(jnp.float32).reshape(1, -1), 1, width)

    scale_p, mean_p, inv_p, b_p = (row(scale, kp), row(mean, dp),
                                   row(inv_std, dp), row(b, kp))
    out = pl.pallas_call(
        _quantized_affine_kernel,
        grid=(np_rows // tile,),
        in_specs=[
            pl.BlockSpec((tile, dp), lambda i: (i, 0)),
            pl.BlockSpec((dp, kp), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_rows, kp), jnp.float32),
        compiler_params=_compiler_params(
            quant_vmem_bytes(d, k, Wq.dtype.itemsize)),
        interpret=interpret,
    )(Xp, Wp, scale_p, mean_p, inv_p, b_p)
    return out[:n, :k]


def quant_vmem_bytes(d: int, k: int, weight_itemsize: int = 1) -> int:
    """VMEM footprint of the quantized-affine kernel: the narrow (Dp,
    Kp) weight block (double-buffered) plus its f32 dequantized copy,
    double-buffered (tile, Dp) input / (tile, Kp) output tiles and the
    normalized input tile, and the four parameter rows."""
    dp = _round_up(max(d, _LANE), _LANE)
    kp = _round_up(max(k, _LANE), _LANE)
    return int(dp * kp * (_F32 + 2 * weight_itemsize)
               + _F32 * (QUANT_TILE * (3 * dp + 2 * kp)
                         + 4 * _SUBLANE * (dp + kp)))


def quant_fits_vmem(d: int, k: int, weight_itemsize: int = 1) -> bool:
    return fits_vmem(quant_vmem_bytes(d, k, weight_itemsize))
