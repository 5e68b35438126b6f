"""Image array ops: window extraction, patch-normalized filter-bank
convolution, row normalization.

Images are plain ``(H, W, C)`` float arrays (the TPU-native layout
replacing the reference's four Image layout classes,
``utils/images/Image.scala``). Patch feature vectors are flattened in
``(dy, dx, c)`` order, matching the packing shared by the reference's
``Windower`` (Windower.scala:35-50) and ``Convolver.makePatches``
(Convolver.scala:152-190), so whiteners/filters are interchangeable.

The reference computes filter-bank convolution by materializing an im2col
patch matrix per image and calling GEMM (Convolver.scala:120-190). On TPU
the same math is expressed as XLA convolutions: the per-patch
normalization (p - m)/sd and the whitener mean subtraction decompose into
box-filter statistics, so

    out[y,x,k] = (raw[y,x,k] - m[y,x] * fsum[k]) / sd[y,x] - (mu . f_k)

with raw = conv(img, filters). Everything stays on the MXU, nothing is
materialized at patch granularity.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def extract_windows(img: jax.Array, size: int, stride: int) -> jax.Array:
    """All (size x size) windows of an (H, W, C) image with the given
    stride; returns (nH, nW, size, size, C)."""
    H, W, C = img.shape
    nH = (H - size) // stride + 1
    nW = (W - size) // stride + 1
    rows = jnp.arange(nH) * stride
    cols = jnp.arange(nW) * stride
    idx = jnp.arange(size)
    w1 = img[rows[:, None] + idx[None, :], :, :]  # (nH, size, W, C)
    w2 = w1[:, :, cols[:, None] + idx[None, :], :]  # (nH, size, nW, size, C)
    return w2.transpose(0, 2, 1, 3, 4)


def normalize_rows(mat: jax.Array, alpha: float = 1.0) -> jax.Array:
    """Per-row mean-centering and variance normalization
    (reference ``utils/Stats.scala:112-123``): subtract the row mean
    (NaN -> 0) and divide by sqrt(row variance + alpha), ddof=1."""
    d = mat.shape[-1]
    means = jnp.mean(mat, axis=-1, keepdims=True)
    means = jnp.where(jnp.isnan(means), 0.0, means)
    var = jnp.sum((mat - means) ** 2, axis=-1, keepdims=True) / (d - 1.0)
    sds = jnp.sqrt(var + alpha)
    sds = jnp.where(jnp.isnan(sds), np.sqrt(alpha), sds)
    return (mat - means) / sds


def _conv2d_valid(img: jax.Array, kernels: jax.Array) -> jax.Array:
    """VALID cross-correlation of (H, W, C) with (K, S, S, C) -> (H', W', K)."""
    lhs = img[None]  # NHWC
    rhs = kernels.transpose(1, 2, 3, 0)  # HWIO
    out = jax.lax.conv_general_dilated(
        lhs,
        rhs,
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out[0]


def filter_bank_convolve(
    img: jax.Array,
    filters: jax.Array,
    conv_size: int,
    channels: int,
    normalize_patches: bool = True,
    whitener_means: Optional[jax.Array] = None,
    var_constant: float = 10.0,
) -> jax.Array:
    """Patch-normalized filter-bank convolution of one image.

    ``filters`` is (num_filters, conv_size*conv_size*channels) in
    (dy, dx, c) feature order — the same matrix the reference's Convolver
    takes (already whitened/normalized by the caller, Convolver.scala:20-45).
    Matches ``Convolver.convolve`` + ``makePatches`` semantics:
    per-patch normalize_rows(var_constant), optional whitener mean
    subtraction, then the filter GEMM.
    """
    K = filters.shape[0]
    S, C = conv_size, channels
    F = S * S * C
    kernels = filters.reshape(K, S, S, C)
    raw = _conv2d_valid(img, kernels)  # (H', W', K)

    if normalize_patches:
        box = jnp.ones((1, S, S, C), img.dtype)
        psum = _conv2d_valid(img, box)[..., 0]  # (H', W')
        psqsum = _conv2d_valid(img * img, box)[..., 0]
        m = psum / F
        var = (psqsum - F * m * m) / (F - 1.0)
        sd = jnp.sqrt(var + var_constant)
        sd = jnp.where(jnp.isnan(sd), np.sqrt(var_constant), sd)
        fsum = jnp.sum(filters, axis=1)  # (K,)
        out = (raw - m[..., None] * fsum) / sd[..., None]
    else:
        out = raw

    if whitener_means is not None:
        out = out - (filters @ whitener_means)

    return out


def pool_image(
    img: jax.Array,
    stride: int,
    pool_size: int,
    pixel_fn: str = "identity",
    pool_fn: str = "sum",
) -> jax.Array:
    """Strided spatial pooling (reference ``images/Pooler.scala:20-68``):
    pool centers start at pool_size/2; each region spans
    [x - pool_size/2, min(x + pool_size/2, dim))."""
    H, W, C = img.shape
    start = pool_size // 2
    xs = list(range(start, H, stride))
    ys = list(range(start, W, stride))

    px = {"identity": lambda v: v, "abs": jnp.abs, "square": jnp.square}[pixel_fn]
    img = px(img)

    rows = []
    for x in xs:
        row = []
        x0, x1 = x - pool_size // 2, min(x + pool_size // 2, H)
        for y in ys:
            y0, y1 = y - pool_size // 2, min(y + pool_size // 2, W)
            region = img[x0:x1, y0:y1, :]
            if pool_fn == "sum":
                row.append(jnp.sum(region, axis=(0, 1)))
            elif pool_fn == "max":
                row.append(jnp.max(region, axis=(0, 1)))
            elif pool_fn == "mean":
                row.append(jnp.mean(region, axis=(0, 1)))
            else:
                raise ValueError(pool_fn)
        rows.append(jnp.stack(row, axis=0))
    return jnp.stack(rows, axis=0)  # (nPoolsX, nPoolsY, C)


# MATLAB rgb2gray weights (reference ``utils/images/ImageUtils.scala:73-105``;
# the reference assumes BGR channel order — our loaders use RGB, same math).
NTSC_RED, NTSC_GREEN, NTSC_BLUE = 0.2989, 0.5870, 0.1140


def to_grayscale(img: jax.Array) -> jax.Array:
    """Grayscale with a single kept channel. 3-channel images use the
    MATLAB luma weights; otherwise the reference's RMS-over-channels.

    Integer images (the packed-u8 load path) are promoted to f32 first —
    luma weights truncate to zero in an integer dtype."""
    if jnp.issubdtype(img.dtype, jnp.integer):
        img = img.astype(jnp.float32)
    if img.shape[-1] == 1:
        return img
    if img.shape[-1] == 3:
        # a weighted sum, written out: as a product with a 3-vector the
        # TPU multiplies it at one bfloat16 pass (0.2% of a pixel), and
        # a minor axis of 3 is padded to 128 lanes on its way there
        return (NTSC_RED * img[..., 0:1] + NTSC_GREEN * img[..., 1:2]
                + NTSC_BLUE * img[..., 2:3])
    return jnp.sqrt(jnp.mean(img * img, axis=-1, keepdims=True))
