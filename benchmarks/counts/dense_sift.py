"""Operations that one pass of multi-scale dense SIFT over an image
needs, from its size: the band products alone, counted DENSE (every
entry of each band matrix times its operand), whichever implementation
runs them: a kernel that skips a band's empty tiles does fewer and reads
a larger share of this roofline for it.

Per scale ``s`` (bin ``b = bin + 2 s``, lower bound ``max(1 + 2 scales -
3 s, 0)``, keypoints every ``step`` pixels with the 4-bin box inside the
image: ``ny`` x ``nx`` of them): smoothing ``G_y img G_x'`` (``2 h h w +
2 h w w`` flops), then the spatial binning of the eight orientation maps
``T_y maps T_x'`` with ``T`` of ``4 n`` rows (``2 x 8 x 4 ny x h x w +
2 x 8 x 4 ny x w x 4 nx``). Gradients, orientation assignment and the
normalisation are vector-unit work and not counted among the flops the
matrix unit's peak is held to. 12.65 GFLOP at 375 x 500.

``expected_*`` weigh a size distribution as the configuration's file
states it (``long_side``, ``common_sides`` at 60% and 25%, the rest
uniform from ``short_side_min``; three in four landscape).
"""
from __future__ import annotations

MXU_PASSES = {"highest": 6, "high": 3, "default": 1}
NBP, NBO = 4, 8


def grid(length: int, scale: int, step: int, bin_size: int, num_scales: int,
         scale_step: int) -> int:
    """Keypoints along one axis of ``length`` pixels at one scale."""
    b = bin_size + 2 * scale
    lo = max(1 + 2 * num_scales - 3 * scale, 0)
    first, last = lo + 2 * b, (length - 1) - 2 * b
    return (last - first) // (step + scale * scale_step) + 1 \
        if last >= first else 0


def descriptors(h: int, w: int, step=4, bin_size=6, num_scales=5,
                scale_step=0) -> int:
    return sum(grid(h, s, step, bin_size, num_scales, scale_step)
               * grid(w, s, step, bin_size, num_scales, scale_step)
               for s in range(num_scales))


def flops(h: int, w: int, step=4, bin_size=6, num_scales=5,
          scale_step=0) -> float:
    total = 0.0
    for s in range(num_scales):
        ny = grid(h, s, step, bin_size, num_scales, scale_step)
        nx = grid(w, s, step, bin_size, num_scales, scale_step)
        total += 2.0 * h * h * w + 2.0 * h * w * w
        total += 2.0 * NBO * NBP * ny * h * w
        total += 2.0 * NBO * NBP * ny * w * NBP * nx
    return total


def sizes(cfg):
    """``[(share, h, w)]`` of the configuration's stated distribution."""
    long_side = cfg["long_side"]
    first, second = cfg["common_sides"]
    tail = range(cfg["short_side_min"], long_side)
    shorts = [(0.60, first), (0.25, second)] + [
        (0.15 / len(tail), s) for s in tail]
    return [(share * turn, *hw) for share, s in shorts
            for turn, hw in ((0.75, (s, long_side)), (0.25, (long_side, s)))]


def expected(cfg, what) -> float:
    """``what(h, w, step, bin, scales, scale_step)`` over the stated
    sizes."""
    sift = (cfg["sift_step"], cfg["sift_bin_size"], cfg["sift_num_scales"],
            cfg["scale_step"])
    return sum(share * what(h, w, *sift) for share, h, w in sizes(cfg))


def roofline_seconds(peaks, cfg, images: int):
    """``(seconds, bound)``: the least time for ONE pass over ``images``
    images of the stated sizes at the stated precision."""
    compute = (images * expected(cfg, flops)
               * MXU_PASSES[cfg["sift_precision"]] / peaks["bf16_flops_per_s"])
    # the image read and its descriptors written, float32
    memory = images * 4.0 * (
        cfg["long_side"] * cfg["common_sides"][0]
        + 128 * expected(cfg, descriptors)) / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
