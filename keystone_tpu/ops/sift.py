"""Dense multi-scale SIFT on TPU (replaces the reference's VLFeat JNI
kernel, ``cpp/VLFeat.cxx`` + ``utils/external/VLFeat.scala:17-27``).

Algorithm (vl_phow-style, matching ``getMultiScaleDSIFTs_f``):
for each scale s in 0..num_scales-1:
  * bin size = ``bin + 2*s``; Gaussian-smooth the grayscale image with
    sigma = bin_size / magnif (magnif = 6), like ``vl_imsmooth_f``;
  * compute gradient magnitude/orientation, soft-assign magnitude to 8
    orientation bins by linear angle interpolation;
  * accumulate 4x4 spatial bins of size bin_size with bilinear (triangle)
    spatial weighting — expressed as a separable depthwise convolution so
    the whole extractor is conv + gather, mapping onto the MXU/VPU;
  * sample descriptors on the keypoint grid with the given step and the
    reference's bounds (min = (1 + 2*num_scales) - 3*s, max = dim - 1);
  * L2-normalize, clamp at 0.2, renormalize (standard SIFT), zero
    descriptors whose pre-normalization norm < 0.005 (the reference's
    contrast threshold), and quantize v -> min(512*v, 255).

Descriptors from all scales are concatenated scale-major, matching the
reference's output layout (a 128 x numDesc matrix).
"""
from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NBP = 4          # spatial bins per side
NBO = 8          # orientation bins
DIMS = NBP * NBP * NBO  # 128
MAGNIF = 6.0
CONTRAST_THRESHOLD = 0.005


def gaussian_kernel(sigma: float) -> np.ndarray:
    """Separable Gaussian taps (vl_imsmooth uses radius ceil(4 sigma))."""
    if sigma < 1e-8:
        return np.ones(1, np.float32)
    radius = int(math.ceil(4.0 * sigma))
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _sep_conv2d(img: jax.Array, kernel: np.ndarray) -> jax.Array:
    """Separable 'same' convolution of a (H, W) image."""
    k = jnp.asarray(kernel)
    r = (len(kernel) - 1) // 2
    padded = jnp.pad(img, ((r, r), (r, r)), mode="edge")
    # rows then cols via conv_general_dilated on (1, 1, H, W)
    x = padded[None, None, :, :]
    kr = k.reshape(1, 1, -1, 1)
    kc = k.reshape(1, 1, 1, -1)
    x = jax.lax.conv_general_dilated(x, kr, (1, 1), "VALID")
    x = jax.lax.conv_general_dilated(x, kc, (1, 1), "VALID")
    return x[0, 0]


def _triangle_kernel(bin_size: int) -> np.ndarray:
    """Bilinear spatial weighting window: w(t) = max(0, 1 - |t|/binSize)
    over the 2*binSize-1 support (the SIFT spatial interpolation)."""
    t = np.arange(-(bin_size - 1), bin_size, dtype=np.float64)
    k = np.maximum(0.0, 1.0 - np.abs(t) / bin_size)
    return k.astype(np.float32)


def _orientation_maps(smoothed: jax.Array) -> jax.Array:
    """(H, W) -> (NBO, H, W) gradient magnitude soft-assigned to
    orientation bins (linear interpolation in angle, as vl_dsift)."""
    gy, gx = jnp.gradient(smoothed)
    return _orientation_bins(gy, gx)


def _orientation_bins(gy: jax.Array, gx: jax.Array) -> jax.Array:
    """Gradients ``(..., H, W)`` -> ``(..., NBO, H, W)``: a pixel's
    magnitude shared between the two orientation bins its angle lies
    between. Pointwise, so it is the same for one image and for a chunk."""
    mag = jnp.sqrt(gx * gx + gy * gy)
    angle = jnp.arctan2(gy, gx) % (2.0 * jnp.pi)
    a = angle * (NBO / (2.0 * jnp.pi))  # in [0, NBO)
    lo = jnp.floor(a)
    frac = a - lo
    lo_bin = lo.astype(jnp.int32) % NBO
    hi_bin = (lo_bin + 1) % NBO
    maps = []
    for o in range(NBO):
        w = jnp.where(lo_bin == o, 1.0 - frac, 0.0) + jnp.where(
            hi_bin == o, frac, 0.0)
        maps.append(mag * w)
    return jnp.stack(maps, axis=-3)


def _keypoint_grid(dim: int, lo: int, hi: int, step: int,
                   extent: float) -> np.ndarray:
    """Descriptor-center coordinates along one axis: vl_dsift places
    descriptor bounding boxes starting at ``lo`` with the given step; the
    center is offset by half the descriptor extent."""
    half = extent / 2.0
    first = lo + half
    last = hi - half
    if last < first:
        return np.zeros(0, np.float64)
    count = int((last - first) // step) + 1
    return first + step * np.arange(count, dtype=np.float64)


@functools.lru_cache(maxsize=128)
def _smooth_band(length: int, bin_size: int) -> np.ndarray:
    """(L, L) band matrix applying the edge-padded Gaussian along one
    axis. Expressing the smoothing as a dense matmul instead of a
    1-channel ``conv_general_dilated`` moves it from the VPU onto the
    MXU — the r5 per-stage profile showed the five per-scale smoothing
    convs were the single largest stage (~50%) of ImageNet
    featurization."""
    k = gaussian_kernel(bin_size / MAGNIF).astype(np.float64)
    r = (len(k) - 1) // 2
    G = np.zeros((length, length), np.float64)
    rows = np.arange(length)
    for t, w in enumerate(k):
        cols = np.clip(rows + t - r, 0, length - 1)
        np.add.at(G, (rows, cols), w)
    return G.astype(np.float32)


@functools.lru_cache(maxsize=128)
def _sampling_operator(length: int, lo: int, step: int,
                       bin_size: int) -> Tuple[np.ndarray, int]:
    """(NBP*n, L) operator folding, along one axis, the triangle
    (bilinear spatial binning) convolution, the shared fractional
    offset of the regular keypoint grid, and the strided descriptor
    sampling into ONE band matrix:

        row (b, i) of T = the weights producing spatial-bin b of the
        descriptor centered at keypoint i.

    ``T_y @ omaps @ T_x.T`` then yields every spatial bin of every
    descriptor as two MXU matmuls, replacing the depthwise triangle
    convs + 16 strided slices of the previous implementation (which the
    r5 profile measured at ~45% of featurization time combined)."""
    extent = float(bin_size * NBP)
    centers = _keypoint_grid(length, lo, length - 1, step, extent)
    offs = (np.arange(NBP) - (NBP - 1) / 2.0) * bin_size
    n = len(centers)
    if n == 0:
        return np.zeros((0, length), np.float32), 0
    tri = _triangle_kernel(bin_size).astype(np.float64)
    r = bin_size - 1
    frac = float((centers[0] + offs[0]) % 1.0)
    shifts = [(0, 1.0)] if frac == 0.0 else [(0, 1.0 - frac), (1, frac)]
    T = np.zeros((NBP * n, length), np.float64)
    idx = np.arange(n)
    for b, off in enumerate(offs):
        p0 = int(math.floor(centers[0] + off))
        pos = p0 + idx * step                      # integer sample rows
        for ds, w in shifts:
            q = np.minimum(pos + ds, length - 1)
            for t, tw in enumerate(tri):
                cols = np.clip(q + t - r, 0, length - 1)
                np.add.at(T, (b * n + idx, cols), w * tw)
    return T.astype(np.float32), n


#: Band-matmul precision. HIGH (3-pass bf16 ≈ f32) measured 577 img/s
#: vs HIGHEST's 412 on the 480x640 rehearsal batch; quantized
#: descriptors stay within the golden test's envelope either way (CPU
#: tests ignore the flag and run exact f32). The claim is PINNED on the
#: device: ``voc_refit`` holds the chunk form's descriptors at this
#: precision to a float64 reference (``sift_gap``), and
#: ``tests/test_golden_fixtures.py::test_dense_sift_high_precision_parity``
#: is the @slow HIGH-vs-HIGHEST form, so bf16 quantization drift cannot
#: ship unnoticed (ADVICE medium#2).
_PRECISION = jax.lax.Precision.HIGH


@functools.partial(
    jax.jit,
    static_argnames=("height", "width", "step", "bin_size", "lo",
                     "precision"),
)
def _dsift_one_scale(img, height, width, step, bin_size, lo,
                     precision=None):
    """Dense SIFT at one scale. Returns (128, numDesc) NORMALIZED,
    quantized descriptors. All heavy lifting is band-matrix matmuls
    (MXU): smoothing via ``_smooth_band``, spatial binning + sampling
    via ``_sampling_operator``; normalization runs in the binned
    layout so no (N, 128) round-trip transpose is materialized.

    ``precision`` overrides the module default for the band matmuls —
    static, so each precision gets its own compiled program (the parity
    gate compares HIGH against HIGHEST on identical inputs)."""
    precision = _PRECISION if precision is None else precision
    Gy = jnp.asarray(_smooth_band(height, bin_size))
    Gx = jnp.asarray(_smooth_band(width, bin_size))
    smoothed = jnp.einsum("ih,hw,jw->ij", Gy, img, Gx,
                          precision=precision)
    omaps = _orientation_maps(smoothed)            # (8, H, W)

    Ty, ny = _sampling_operator(height, lo, step, bin_size)
    Tx, nx = _sampling_operator(width, lo, step, bin_size)
    if ny == 0 or nx == 0:
        return jnp.zeros((DIMS, 0), smoothed.dtype)
    # (8, NBP*ny, NBP*nx): spatial bin (by, bx) of descriptor (iy, ix)
    bins = jnp.einsum("ph,ohw,qw->opq", jnp.asarray(Ty), omaps,
                      jnp.asarray(Tx), precision=precision)
    return _normalize_quantize_binned(
        bins.reshape(NBO, NBP, ny, NBP, nx))


def _normalize_quantize_binned(b5: jax.Array) -> jax.Array:
    """SIFT normalization (L2 normalize, clamp 0.2, renormalize; zero
    descriptors whose pre-normalization norm per unit bin mass is under
    the contrast threshold; quantize to min(512 v, 255) — reference
    VLFeat.cxx JNI body + ``vl_dsift``), applied in the native
    (o, by, ny, bx, nx) layout of the sampling matmul and emitting the
    final (128, ny*nx) column-per-descriptor matrix directly — one
    output transpose instead of materializing (N, 128) and transposing
    back (the r5 profile's 'norm' stage was pure relayout cost)."""
    _, _, ny, _, nx = b5.shape
    norm = jnp.sqrt(jnp.sum(b5 * b5, axis=(0, 1, 3)))      # (ny, nx)
    bcast = (None, None, slice(None), None, slice(None))
    d = jnp.minimum(b5 / jnp.maximum(norm, 1e-12)[bcast], 0.2)
    norm2 = jnp.maximum(jnp.sqrt(jnp.sum(d * d, axis=(0, 1, 3))), 1e-12)
    d = d / norm2[bcast]
    area = NBP * NBP
    d = jnp.where((norm / area < CONTRAST_THRESHOLD)[bcast], 0.0, d)
    d = jnp.minimum(512.0 * d, 255.0)
    # (by, bx, o)-major 128-dim layout, descriptors column-major
    return d.transpose(1, 3, 0, 2, 4).reshape(DIMS, ny * nx)


def _scale_params(scale: int, step: int, bin_size: int, num_scales: int,
                  scale_step: int) -> Tuple[int, int, int]:
    """(step, bin size, lower bound) at one scale — the per-scale setup of
    ``getMultiScaleDSIFTs_f`` (VLFeat.cxx)."""
    scale_value = bin_size + 2 * scale
    lo = max((1 + num_scales * 2) - scale * 3, 0)
    return step + scale * scale_step, scale_value, lo


def dense_sift(
    img_gray: jax.Array,
    step: int = 4,
    bin_size: int = 6,
    num_scales: int = 5,
    scale_step: int = 0,
    precision=None,
) -> jax.Array:
    """Multi-scale dense SIFT of a grayscale (H, W) image in [0, 1].

    Returns (128, numDesc) float32, scales concatenated in order —
    matching ``VLFeat.getSIFTs`` (reference
    ``utils/external/VLFeat.scala:17-27``). ``precision`` overrides the
    band-matmul default (parity gating; None = module default HIGH).
    """
    height, width = int(img_gray.shape[0]), int(img_gray.shape[1])
    outs: List[jax.Array] = []
    for scale in range(num_scales):
        s, scale_value, lo = _scale_params(
            scale, step, bin_size, num_scales, scale_step)
        outs.append(_dsift_one_scale(
            img_gray, height, width, s, scale_value, lo,
            precision=precision))
    return jnp.concatenate(outs, axis=1)  # (128, N)


def sift_descriptor_count(
    height: int, width: int,
    step: int = 4, bin_size: int = 6,
    num_scales: int = 5, scale_step: int = 0,
) -> int:
    """Static descriptor count for shape planning (padding/bucketing)."""
    total = 0
    for scale in range(num_scales):
        s, scale_value, lo = _scale_params(
            scale, step, bin_size, num_scales, scale_step)
        extent = scale_value * NBP
        ys = _keypoint_grid(height, lo, height - 1, s, extent)
        xs = _keypoint_grid(width, lo, width - 1, s, extent)
        total += len(ys) * len(xs)
    return total


# -- a chunk of images of different sizes, padded to one shape ---------------
#
# Images whose sizes differ are held in chunks padded to a bucket's shape
# (``parallel.ragged``). Every heavy stage is a band-matrix product, and
# the band matrices of the BUCKET's shape give, for an image that fills
# only the top-left (h, w) of it, what the image's own matrices give,
# once three things follow the image's true size: its pixels are
# repeated past its last row and column (the smoothing pads by
# repeating the edge), the gradient is one-sided at ITS last row and
# column, and the gradients are repeated past them (the spatial binning
# clamps at the edge too). Keypoints are placed from the top-left corner
# at a fixed step, so an image's keypoints are the first (ny, nx) of the
# bucket's grid; the rest are zeroed and masked. One program a bucket,
# whatever sizes the images in it have.
#
# A chunk is ``[b, 128, chunk_width(bucket)]``: each scale's descriptors
# stand in a SEGMENT of whole 128-column tiles (:func:`chunk_segments`),
# the columns past the scale's own count zero and masked like the
# keypoints an image does not have. The descriptor axis is the TPU's lane
# axis, so a segment that starts and ends on a tile is written once, in
# place, and the chunk leaves the program in the layout it was built in.
# Joined at their own widths (10,560, 10,148, ... at 384 x 512) every
# scale after the first was shifted across lanes on its way in and the
# whole output was then copied into another layout: 1.09 of the 4.47 s
# of dense SIFT in a ``voc_refit`` fit, where the aligned writes take
# 0.26 (``PERF.md`` section 6, PR 47). Only this padded form is laid out so:
# :func:`dense_sift` and :func:`sift_descriptor_count` keep the
# reference's exact ``128 x numDesc``.

#: columns a lane tile of the TPU holds
LANES = 128


def scale_grid(height: int, width: int, scale: int, step: int, bin_size: int,
               num_scales: int, scale_step: int) -> Tuple[int, int]:
    """Keypoints along each axis at one scale, for an image of this size."""
    s, scale_value, lo = _scale_params(
        scale, step, bin_size, num_scales, scale_step)
    extent = scale_value * NBP
    return (len(_keypoint_grid(height, lo, height - 1, s, extent)),
            len(_keypoint_grid(width, lo, width - 1, s, extent)))


@functools.lru_cache(maxsize=256)
def chunk_segments(height: int, width: int, step: int = 4, bin_size: int = 6,
                   num_scales: int = 5, scale_step: int = 0,
                   ) -> Tuple[Tuple[int, int, int], ...]:
    """Where each scale's descriptors stand in a chunk of this bucket:
    ``(offset, count, padded)`` a scale, ``count`` the keypoints of the
    bucket's grid (``ny * nx``, row-major) and ``padded`` that rounded up
    to whole tiles of ``LANES`` columns, so every offset is a multiple of
    ``LANES`` too. The one place that says how a chunk is laid out."""
    segments, offset = [], 0
    for scale in range(num_scales):
        ny, nx = scale_grid(height, width, scale, step, bin_size,
                            num_scales, scale_step)
        count = ny * nx
        padded = -(-count // LANES) * LANES
        segments.append((offset, count, padded))
        offset += padded
    return tuple(segments)


def chunk_width(height: int, width: int, step: int = 4, bin_size: int = 6,
                num_scales: int = 5, scale_step: int = 0) -> int:
    """Columns of a chunk of this bucket: its segments' padded widths."""
    return sum(padded for _, _, padded in chunk_segments(
        height, width, step, bin_size, num_scales, scale_step))


@functools.lru_cache(maxsize=4096)
def descriptor_mask(height: int, width: int, bucket: Tuple[int, int],
                    step: int = 4, bin_size: int = 6, num_scales: int = 5,
                    scale_step: int = 0) -> np.ndarray:
    """bool ``[chunk_width(*bucket)]``: which columns of a chunk of this
    bucket hold a descriptor of an image of this size; false on the
    bucket's keypoints the image does not have and on the columns that
    fill a scale's segment up to whole tiles. Counting the true ones in
    order gives the image's own numbering (scale-major, then rows of its
    own nx keypoints), ``sift_descriptor_count(height, width)`` in all."""
    config = (step, bin_size, num_scales, scale_step)
    mask = np.zeros(chunk_width(*bucket, *config), bool)
    for scale, (offset, count, _) in enumerate(
            chunk_segments(*bucket, *config)):
        ny, nx = scale_grid(height, width, scale, *config)
        nyb, nxb = scale_grid(*bucket, scale, *config)
        mask[offset:offset + count] = (
            (np.arange(nyb)[:, None] < ny)
            & (np.arange(nxb)[None, :] < nx)).ravel()
    mask.setflags(write=False)
    return mask


def _edge_pad(x: jax.Array, h: jax.Array, w: jax.Array) -> jax.Array:
    """``x[b, ..., min(i, h_b - 1), min(j, w_b - 1)]``: every item's
    last real row and column repeated over its padding."""
    lead = (slice(None),) + (None,) * (x.ndim - 1)
    i = jnp.arange(x.shape[-2])[:, None]
    j = jnp.arange(x.shape[-1])[None, :]
    x = jnp.where(i < h[lead], x, _last(x, h, -2))
    return jnp.where(j < w[lead], x, _last(x, w, -1))


def _last(x: jax.Array, size: jax.Array, axis: int) -> jax.Array:
    """Item ``b``'s slice ``size_b - 1`` along ``axis``, kept as an axis
    of one."""
    lead = (slice(None),) + (None,) * (x.ndim - 1)
    at = jnp.broadcast_to(jnp.maximum(size - 1, 0)[lead],
                          tuple(1 if a == axis % x.ndim else n
                                for a, n in enumerate(x.shape)))
    return jnp.take_along_axis(x, at, axis=axis)


def _true_gradient(s: jax.Array, size: jax.Array, axis: int) -> jax.Array:
    """``jnp.gradient`` along ``axis`` of items whose true length there
    is ``size_b``: central differences inside, one-sided at index 0 and
    at ``size_b - 1``."""
    lead = (slice(None),) + (None,) * (s.ndim - 1)
    shape = [1] * s.ndim
    shape[axis] = s.shape[axis]
    at = jnp.arange(s.shape[axis]).reshape(shape)
    after, before = jnp.roll(s, -1, axis), jnp.roll(s, 1, axis)
    return jnp.where(at == 0, after - s,
                     jnp.where(at == size[lead] - 1, s - before,
                               (after - before) * 0.5))


@functools.lru_cache(maxsize=64)
def _bucket_operators(height: int, width: int, step: int, bin_size: int,
                      lo: int):
    """The bucket's four band matrices at one scale, on the device once
    a process: arguments of the chunk program, not constants of it."""
    ty, _ = _sampling_operator(height, lo, step, bin_size)
    tx, _ = _sampling_operator(width, lo, step, bin_size)
    return tuple(jnp.asarray(m) for m in (
        _smooth_band(height, bin_size), _smooth_band(width, bin_size),
        ty, tx))


@functools.partial(jax.jit, static_argnames=("config", "precision"))
def _dsift_chunk(imgs, extent, grids, operators, config, precision):
    """Every scale of a chunk ``[b, H, W]`` in one program. ``grids``
    ``[b, scales, 2]``: each image's own keypoint counts; ``operators``:
    the bucket's four band matrices a scale (:func:`_bucket_operators`).
    Smoothing and spatial binning are dense products with them: XLA's
    own, which beat a Pallas kernel that visited only the bands' live
    tiles by 3.5 times on the chip (``PERF.md`` section 6, PR 33).
    Returns ``[b, 128, chunk_width(H, W)]``, a scale's descriptors in
    its segment of whole lane tiles (:func:`chunk_segments`)."""
    from ..observability.metrics import MetricsRegistry

    # raised when the program is traced, once a shape
    MetricsRegistry.get_or_create().counter("featurize.sift.einsum").inc()
    b, height, width = imgs.shape
    h, w = extent[:, 0], extent[:, 1]
    outs = []
    with jax.named_scope("dense_sift"):
        imgs = _edge_pad(imgs, h, w)
        for scale, (_, count, padded) in enumerate(
                chunk_segments(height, width, *config)):
            if count == 0:
                continue
            ny, nx = scale_grid(height, width, scale, *config)
            gy_op, gx_op, ty_op, tx_op = operators[scale]
            smoothed = jnp.einsum("ih,bhw,jw->bij", gy_op, imgs, gx_op,
                                  precision=precision)
            gy = _edge_pad(_true_gradient(smoothed, h, 1), h, w)
            gx = _edge_pad(_true_gradient(smoothed, w, 2), h, w)
            bins = jnp.einsum("ph,bohw,qw->bopq", ty_op,
                              _orientation_bins(gy, gx), tx_op,
                              precision=precision)
            desc = jax.vmap(_normalize_quantize_binned)(
                bins.reshape(b, NBO, NBP, ny, NBP, nx))  # (b, 128, ny*nx)
            real = ((jnp.arange(ny)[None, :, None]
                     < grids[:, scale, 0, None, None])
                    & (jnp.arange(nx)[None, None, :]
                       < grids[:, scale, 1, None, None]))
            desc = desc * real.reshape(b, 1, count).astype(desc.dtype)
            outs.append(jnp.pad(desc, ((0, 0), (0, 0), (0, padded - count))))
    if not outs:
        return jnp.zeros((b, DIMS, 0), jnp.float32)
    return jnp.concatenate(outs, axis=2)


def dense_sift_chunk(imgs: jax.Array, extent: np.ndarray, step: int = 4,
                     bin_size: int = 6, num_scales: int = 5,
                     scale_step: int = 0, precision=None) -> jax.Array:
    """:func:`dense_sift` of a chunk of grayscale images ``[b, H, W]``,
    image ``i`` filling the top-left ``extent[i] = (h, w)`` and zero
    elsewhere: ``[b, 128, chunk_width(H, W)]`` with image ``i``'s
    descriptors where :func:`descriptor_mask` says and zeros elsewhere.
    The width is the bucket's descriptor count with every scale's
    segment rounded up to whole lane tiles (:func:`chunk_segments`; 50,176
    columns for 49,745 descriptors at 384 x 512), so read a chunk's
    columns through the mask and never by a count. ``extent`` is a host
    array: the keypoint counts come from it without touching the
    device."""
    args, static = chunk_call(imgs, extent, step, bin_size, num_scales,
                              scale_step, precision)
    return _dsift_chunk(*args, **static)


def chunk_call(imgs: jax.Array, extent: np.ndarray, step: int = 4,
               bin_size: int = 6, num_scales: int = 5, scale_step: int = 0,
               precision=None):
    """``(args, static)`` with which :func:`dense_sift_chunk` calls the
    chunk program ``_dsift_chunk``: a probe lowers the same call to read
    what the compiler made of it."""
    precision = _PRECISION if precision is None else precision
    b, height, width = (int(n) for n in imgs.shape)
    extent = np.asarray(extent, np.int32).reshape(b, 2)
    config = (step, bin_size, num_scales, scale_step)
    grids = np.array([[scale_grid(int(hh), int(ww), scale, *config)
                       for scale in range(num_scales)]
                      for hh, ww in extent], np.int32).reshape(
                          b, num_scales, 2)
    operators = tuple(
        _bucket_operators(height, width, *_scale_params(scale, *config))
        for scale in range(num_scales))
    return ((imgs, jnp.asarray(extent), jnp.asarray(grids), operators),
            dict(config=config, precision=precision))
