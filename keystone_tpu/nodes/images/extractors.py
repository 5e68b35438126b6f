"""Dense feature extractors: SIFT and LCS (reference
``nodes/images/external/SIFTExtractor.scala``,
``nodes/images/LCSExtractor.scala``).

Both return a per-image (D, numDesc) float matrix — the reference's
column-per-descriptor layout — computed as jitted conv + gather programs
instead of JNI calls.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...observability.metrics import MetricsRegistry
from ...observability.timeline import flight_span
from ...ops.sift import (
    dense_sift,
    dense_sift_chunk,
    descriptor_mask,
    sift_descriptor_count,
)
from ...parallel.ragged import unfold
from ...workflow.transformer import Transformer


class SIFTExtractor(Transformer):
    """Multi-scale dense SIFT (reference
    ``SIFTExtractor.scala:27-34`` / ``VLFeat.cxx``): input is a grayscale
    (H, W) or (H, W, 1) image scaled to [0, 1]; output (128, numDesc)."""

    def __init__(self, step: int = 4, bin_size: int = 6,
                 num_scales: int = 5, scale_step: int = 0):
        self.step = step
        self.bin_size = bin_size
        self.num_scales = num_scales
        self.scale_step = scale_step

    def apply(self, img):
        if img.ndim == 3:
            img = img[..., 0]
        return dense_sift(
            img, self.step, self.bin_size, self.num_scales, self.scale_step)

    def chunk_stage(self):
        """Images of different sizes, padded to their bucket's shape:
        one program a bucket (``ops.sift.dense_sift_chunk``). A chunk of
        descriptors is ``[b, 128, ops.sift.chunk_width(bucket)]``: each
        scale's stand in a segment of whole 128-column tiles, which the
        device writes once and in place, so the chunk is a little wider
        than the bucket's descriptor count. The descriptors of image
        ``i`` stand where the chunk's ``mask`` says, in the image's own
        order, and a consumer reaches a chunk's columns through the
        mask alone."""
        config = (self.step, self.bin_size, self.num_scales, self.scale_step)

        def stage(chunk):
            # grayscale images: (h, w) or (h, w, 1), which a chunk holds
            # as [b, H, W] either way (``Chunk.tail``)
            imgs = (chunk.data if math.prod(chunk.tail) == 1
                    else unfold(chunk.data, chunk.tail)[..., 0])
            bucket = tuple(int(n) for n in imgs.shape[1:])
            mask = np.stack([descriptor_mask(int(h), int(w), bucket, *config)
                             for h, w in chunk.extent])
            images = int(chunk.real.sum())
            with flight_span("sift", "featurize", images=images,
                             bucket=f"{bucket[0]}x{bucket[1]}",
                             descriptors=int(mask.sum())):
                data = dense_sift_chunk(imgs, chunk.extent, *config)
            MetricsRegistry.get_or_create().counter(
                "featurize.sift.images").inc(images)
            return dataclasses.replace(
                chunk, data=data, mask=mask, tail=(),
                extent=np.zeros((len(chunk.ids), 0), np.int32))

        return stage

    def descriptor_count(self, height: int, width: int) -> int:
        return sift_descriptor_count(
            height, width, self.step, self.bin_size,
            self.num_scales, self.scale_step)

    # -- static HBM planning (analysis.resources) --------------------------
    def resource_effect(self, dep_specs, out_spec, data_shards=1):
        """SIFT nodes charge their per-config band operators
        (smoothing + sampling matrices) as a one-off transient — the
        lru caches keep the arrays alive across every image of a
        config."""
        import dataclasses

        from ...analysis.resources import (
            sift_band_operator_nbytes,
            spec_effect,
        )

        element = (getattr(dep_specs[0], "element", None)
                   if dep_specs else None)
        if not (isinstance(element, jax.ShapeDtypeStruct)
                and len(element.shape) >= 2):
            return None
        base = spec_effect(out_spec, data_shards)
        extra = sift_band_operator_nbytes(
            int(element.shape[0]), int(element.shape[1]), self.step,
            self.bin_size, self.num_scales, self.scale_step)
        return dataclasses.replace(
            base, transient_nbytes=base.transient_nbytes + extra,
            note=(base.note + "; " if base.note else "")
            + "SIFT band-operator constants")


class BatchSIFTExtractor(SIFTExtractor):
    """SIFT over per-item image batches via vmap (fixed image size)."""

    def apply_dataset(self, ds):
        return ds.map(self.apply)


@functools.partial(
    jax.jit, static_argnames=("stride", "stride_start", "sub_patch_size"))
def _lcs(img, stride, stride_start, sub_patch_size):
    """Local color statistics (reference ``LCSExtractor.scala:50-130``):
    per-channel box-filter means and stddevs, sampled on a keypoint grid
    at a 4x4 neighborhood of sub-patch offsets -> (96, numKeypoints)."""
    H, W, C = img.shape
    k = jnp.full((sub_patch_size,), 1.0 / sub_patch_size)

    def box2d(ch):
        # 'same' separable box filter, zero padding like ImageUtils.conv2D
        r0 = (sub_patch_size - 1) // 2
        r1 = sub_patch_size - 1 - r0
        x = jnp.pad(ch, ((r0, r1), (r0, r1)))[None, None]
        kr = k.reshape(1, 1, -1, 1)
        kc = k.reshape(1, 1, 1, -1)
        x = jax.lax.conv_general_dilated(x, kr, (1, 1), "VALID")
        x = jax.lax.conv_general_dilated(x, kc, (1, 1), "VALID")
        return x[0, 0]

    chans = [img[:, :, c] for c in range(C)]
    means = [box2d(ch) for ch in chans]
    stds = [
        jnp.sqrt(jnp.maximum(box2d(ch * ch) - m * m, 0.0))
        for ch, m in zip(chans, means)
    ]

    xs = np.arange(stride_start, H - stride_start, stride)
    ys = np.arange(stride_start, W - stride_start, stride)
    # sub-patch offsets: start = -2s + s//2 - 1, end = s + s//2 - 1, step s
    start = -2 * sub_patch_size + sub_patch_size // 2 - 1
    end = sub_patch_size + sub_patch_size // 2 - 1
    offs = np.arange(start, end + 1, sub_patch_size)

    xx, yy = np.meshgrid(xs, ys, indexing="ij")  # keypoints (x-major)
    xx, yy = xx.ravel(), yy.ravel()

    rows = []
    for c in range(C):
        for nx in offs:
            for ny in offs:
                px = np.clip(xx + nx, 0, H - 1)
                py = np.clip(yy + ny, 0, W - 1)
                rows.append(means[c][px, py])
                rows.append(stds[c][px, py])
    return jnp.stack(rows).astype(jnp.float32)  # (C*16*2, numKeypoints)


class LCSExtractor(Transformer):
    """Local Color Statistics on a regular grid (reference
    ``LCSExtractor.scala:26-130``; Clinchant et al. 2007): 4x4 sub-region
    means + stddevs of each channel -> 96-dim descriptors (for 3
    channels). Input (H, W, C) image; output (96, numKeypoints)."""

    def __init__(self, stride: int = 4, stride_start: int = 16,
                 sub_patch_size: int = 6):
        self.stride = stride
        self.stride_start = stride_start
        self.sub_patch_size = sub_patch_size

    def apply(self, img):
        return _lcs(img, self.stride, self.stride_start, self.sub_patch_size)
