"""Pallas kernel tests (interpreter mode on CPU; what only the Mosaic
compiler can refuse is exercised on the chip by chip_smoke.py)."""
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.ops.pallas_kernels import gram_cross, gram_cross_pallas

_V5E_VMEM = 128 * 1024 * 1024


@pytest.fixture
def v5e_budget(monkeypatch):
    """The VMEM budget as on a TPU v5e (the CPU test backend has no
    row in the table, and must not get one)."""
    from keystone_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(
        pk, "vmem_budget_bytes",
        lambda: int(_V5E_VMEM * pk._VMEM_KERNEL_SHARE))


@pytest.mark.parametrize("n,d,k", [(100, 37, 5), (513, 128, 16), (7, 3, 2)])
def test_gram_cross_pallas_interpret(n, d, k):
    rng = np.random.RandomState(0)
    X = rng.randn(n, d).astype(np.float32)
    Y = rng.randn(n, k).astype(np.float32)
    g, c = gram_cross_pallas(jnp.asarray(X), jnp.asarray(Y), interpret=True)
    np.testing.assert_allclose(np.asarray(g), X.T @ X, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(c), X.T @ Y, rtol=2e-4, atol=2e-4)


def test_gram_cross_fallback_matches():
    rng = np.random.RandomState(1)
    X = rng.randn(64, 10).astype(np.float32)
    Y = rng.randn(64, 3).astype(np.float32)
    g, c = gram_cross(jnp.asarray(X), jnp.asarray(Y))  # cpu fallback path
    np.testing.assert_allclose(np.asarray(g), X.T @ X, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c), X.T @ Y, rtol=1e-4, atol=1e-4)


def test_fused_cifar_featurize_matches_composed_ops():
    from keystone_tpu.ops.image_ops import filter_bank_convolve, pool_image
    from keystone_tpu.ops.pallas_kernels import fused_cifar_featurize_banks

    rng = np.random.RandomState(0)
    B, K, S = 3, 32, 6
    imgs = rng.rand(B, 32, 32, 3).astype(np.float32) * 255
    filters = rng.randn(K, S * S * 3).astype(np.float32)
    (got,) = np.asarray(fused_cifar_featurize_banks(
        jnp.asarray(imgs), jnp.asarray(filters)[None], interpret=True))

    def one(img):
        conv = filter_bank_convolve(
            jnp.asarray(img), jnp.asarray(filters), S, 3, True, None, 10.0)
        pos = jnp.maximum(0.0, conv - 0.25)
        neg = jnp.maximum(0.0, -conv - 0.25)
        return np.asarray(pool_image(
            jnp.concatenate([pos, neg], -1), 13, 14, "identity", "sum"
        )).reshape(-1)

    want = np.stack([one(i) for i in imgs])
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _exact_bank(rng, k, scale=16, features=108):
    """Filters in sixteenths within +-1/2: a byte times one of them, and
    a patch's 108 such products summed in any order, are exact in
    float32, so the CPU's product is the same number whichever way the
    kernel and the composed ops add it up."""
    return rng.randint(-8, 9, (k, features)).astype(np.float32) / scale


def _fused_cases():
    rng = np.random.RandomState(37)
    noisy = rng.randint(0, 256, (4, 32, 32, 3)).astype(np.float32)
    # patch variances under 1; dark, because F * m * m against psq
    # cancels, and on bright flat patches the composed ops themselves
    # move by 1e-5 with how XLA's CPU backend contracts that expression
    flat = np.stack([
        7.0 + rng.randint(0, 2, (32, 32, 3)),
        np.full((32, 32, 3), 3.0),
        np.where(np.arange(32)[:, None, None] < 16, 12.0,
                 10.0 + rng.randint(0, 2, (32, 32, 3)))]).astype(np.float32)
    cifar = dict(img_size=32, patch_size=6, pool_stride=13, pool_size=14)
    big_means = rng.randint(-32, 33, (2, 108)).astype(np.float32) / 8.0
    small = rng.randint(0, 256, (3, 20, 20, 3)).astype(np.float32)
    two_steps = rng.randint(0, 256, (16, 20, 20, 3)).astype(np.float32)
    grey = rng.randint(0, 256, (11, 20, 20, 1)).astype(np.float32)
    crops = rng.randint(0, 256, (9, 24, 24, 3)).astype(np.float32)
    return {
        # two steps of 8 images x two banks: the second step's patches
        # and statistics are built over the first's, the second bank
        # reads what the first left (ISSUE 42)
        "two_steps_two_banks": (
            two_steps, np.stack([_exact_bank(rng, 24), _exact_bank(rng, 24)]),
            big_means / 4.0,
            dict(img_size=20, patch_size=6, pool_stride=7, pool_size=7)),
        # one channel (36 features a patch), 11 images where a step
        # takes 8, 24 filters where a lane tile holds 128
        "one_channel_ragged_batch": (
            grey, _exact_bank(rng, 24, features=36)[None], None,
            dict(img_size=20, patch_size=6, channels=1, pool_stride=5,
                 pool_size=6)),
        # 1 / sd is largest where a patch hardly varies
        "flat_patches": (flat, _exact_bank(rng, 40)[None], None, cifar),
        # |bias| far over alpha: a padded position rectifies to
        # -(bias + alpha) > 0 and must be left out of the pooled sums
        "bias_beyond_alpha": (noisy, _exact_bank(rng, 24)[None],
                              big_means[:1], cifar),
        # the cell's last bank, and a second bank on the statistics the
        # first one left
        "banks_of_272": (noisy[:2], np.stack(
            [_exact_bank(rng, 272), _exact_bank(rng, 272)]), big_means,
            cifar),
        # 5 x 5 segments (25, 5, 20 and 16 rows among them: one with no
        # padding) and 3 x 3 regions, where CIFAR has 3 x 3 and 2 x 2
        "five_by_five_segments": (
            small, _exact_bank(rng, 16)[None], big_means[:1] / 4.0,
            dict(img_size=20, patch_size=6, pool_stride=5, pool_size=6)),
        "one_segment_a_region": (
            small, _exact_bank(rng, 16)[None], None,
            dict(img_size=20, patch_size=6, pool_stride=7, pool_size=7)),
        # the augmented app's crops (ISSUE 45): 19 x 19 positions of
        # which the ONE region pools 14 x 14 (three rectangles are pooled
        # by nothing and get no rows: ISSUE 46), and banks of 5 lane
        # tiles, which the epilogue takes in two passes of at most 4
        "crops_of_24_two_passes": (
            crops, np.stack([_exact_bank(rng, 600), _exact_bank(rng, 600)]),
            big_means, dict(img_size=24, patch_size=6, pool_stride=13,
                            pool_size=14)),
    }


@pytest.mark.parametrize("case", sorted(_fused_cases()))
def test_fused_kernel_refolded_epilogue_at_float32_distance(case):
    """The kernel's refolded epilogue (a reciprocal deviation on the
    column, the bias and alpha in two rows of thresholds, sums of real
    rows only, a register at a time; ISSUE 37) against the composed ops
    where the refolding could break, on operands whose products the CPU
    forms exactly: what is left is float32 rounding, not the 2e-3 of the
    tests beside this one."""
    from keystone_tpu.nodes.images.core import FusedConvRectifyPool
    from keystone_tpu.ops.pallas_kernels import fused_cifar_featurize_banks

    imgs, banks, means, geometry = _fused_cases()[case]
    got = np.asarray(fused_cifar_featurize_banks(
        jnp.asarray(imgs), jnp.asarray(banks), alpha=0.25,
        whitener_means=None if means is None else jnp.asarray(means),
        interpret=True, **geometry), np.float64)
    assert np.isfinite(got).all()
    for j, bank in enumerate(banks):
        node = FusedConvRectifyPool(
            bank, geometry["img_size"], geometry["patch_size"],
            geometry.get("channels", 3),
            geometry["pool_stride"], geometry["pool_size"], 0.25,
            whitener=None if means is None else SimpleNamespace(
                means=means[j]))
        want = np.asarray(jax.vmap(lambda img: node.apply_with_params(
            node.apply_params(), img))(jnp.asarray(imgs)), np.float64)
        assert want.shape == got[j].shape and np.linalg.norm(want) > 0
        for mine, theirs in zip(got[j], want):      # an image at a time
            rel_gap = np.linalg.norm(mine - theirs) / max(
                np.linalg.norm(theirs), 1e-30)
            assert rel_gap <= 1e-6, (case, j, rel_gap)


def _geometries():
    return {
        **{case: geometry
           for case, (_, _, _, geometry) in _fused_cases().items()},
        "one_channel_cifar": dict(img_size=32, patch_size=6, channels=1,
                                  pool_stride=13, pool_size=14),
    }


@pytest.mark.parametrize("case", sorted(_geometries()))
def test_fused_kernel_builds_the_patches_of_im2col_exactly(case):
    """The patch matrix the kernel builds in VMEM from the ``(H, W * C)``
    image (``_build_patches``, read back as the output of a call that
    does nothing else) is ``conv_general_dilated_patches`` laid out by
    ``_pool_layout``: copies, so equal bit for bit; features ``(dy, dx,
    c)``, a segment's positions a column of the image at a time, zeros
    where a segment or the features are padded (ISSUE 42)."""
    from jax.experimental import pallas as pl

    from keystone_tpu.ops import pallas_kernels as pk

    geometry = dict(_geometries()[case])
    C = geometry.pop("channels", 3)
    H, S = geometry["img_size"], geometry["patch_size"]
    B, F = 3, S * S * C
    rng = np.random.RandomState(42)
    # not bytes: a copy keeps all 24 bits of any float
    imgs = rng.standard_normal((B, H, H, C)).astype(np.float32)
    windows, segments, _ = pk._fused_layout(**geometry)
    Pp = segments[-1][0] + -(-segments[-1][1] // 8) * 8
    Fp = -(-F // 128) * 128

    def kernel(img_ref, patch_ref):
        patch_ref[...] = jnp.zeros_like(patch_ref)
        jax.lax.fori_loop(0, B, lambda t, _: pk._build_patches(
            img_ref, patch_ref, t, windows, S, C), None)

    got = np.asarray(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((B, Pp, Fp), jnp.float32),
        interpret=True)(jnp.asarray(imgs.reshape(B, H, H * C))))
    out = H - S + 1
    want = np.asarray(jax.lax.conv_general_dilated_patches(
        jnp.asarray(imgs), (S, S), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST))   # (B, out, out, (c, dy, dx))
    want = want.reshape(B, out, out, C, S * S).transpose(
        0, 1, 2, 4, 3).reshape(B, out, out, F)
    intervals, axis_regions = pk._pool_layout(
        out, geometry["pool_stride"], geometry["pool_size"])
    laid = np.zeros((B, Pp, Fp), np.float32)
    # exactly the rectangles that some region covers, x-major
    rects = [(x, y) for i, x in enumerate(intervals)
             for j, y in enumerate(intervals)
             if any(i in xs and j in ys
                    for xs in axis_regions for ys in axis_regions)]
    assert len(rects) == len(segments)
    for ((x0, x1), (y0, y1)), (at, rows) in zip(rects, segments):
        assert rows == (x1 - x0) * (y1 - y0)
        laid[:, at:at + rows, :F] = want[:, x0:x1, y0:y1].transpose(
            0, 2, 1, 3).reshape(B, rows, F)
    np.testing.assert_array_equal(got, laid)


def _layout_of_every_rectangle(img_size, patch_size, pool_stride, pool_size):
    """``_fused_layout`` as it was until PR 46: every rectangle between
    the regions' edges a segment, pooled or not."""
    from keystone_tpu.ops import pallas_kernels as pk

    out_dim = img_size - patch_size + 1
    intervals, axis_regions = pk._pool_layout(out_dim, pool_stride, pool_size)
    windows, segments, at = [], [], 0
    for x0, x1 in intervals:
        for y0, y1 in intervals:
            rows = (x1 - x0) * (y1 - y0)
            windows.extend((x0, x1 - x0, y, at + (y - y0) * (x1 - x0))
                           for y in range(y0, y1))
            segments.append((at, rows))
            at += pk._round_up(rows, 8)
    n = len(intervals)
    regions = tuple(tuple(i * n + j for i in xs for j in ys)
                    for xs in axis_regions for ys in axis_regions)
    return tuple(windows), tuple(segments), regions


#: what a geometry of ``_geometries`` lays out, by (image, pooling
#: stride): (rectangles between the regions' edges, segments, padded
#: rows, positions kept, positions left out)
_LAID_OUT = {
    (32, 13): (9, 9, 776, 729, 0),
    (24, 13): (4, 1, 200, 196, 165),
    (20, 5): (25, 25, 320, 225, 0),
    # regions [0, 6) and [7, 13) of 15 positions: gaps BETWEEN regions
    (20, 7): (16, 4, 160, 144, 81),
}


@pytest.mark.parametrize("case", sorted(_geometries()))
def test_fused_layout_holds_the_pooled_positions_alone(case):
    """The patch matrix has rows for the rectangles that some region
    pools and for no other (ISSUE 46): the segments are the union of
    ``regions``, they are the old layout's pooled segments in their old
    order, packed, and where every rectangle is pooled (32 x 32) the
    three tuples are the old layout's to the letter, so the program is."""
    from keystone_tpu.ops import pallas_kernels as pk

    geometry = dict(_geometries()[case])
    geometry.pop("channels", None)
    windows, segments, regions = pk._fused_layout(**geometry)
    old_windows, old_segments, old_regions = _layout_of_every_rectangle(
        **geometry)
    rectangles, kept_segments, padded, kept, left_out = _LAID_OUT[
        geometry["img_size"], geometry["pool_stride"]]
    assert (len(old_segments), len(segments)) == (rectangles, kept_segments)
    assert sorted({i for r in regions for i in r}) == list(
        range(len(segments)))
    pooled = sorted({i for r in old_regions for i in r})
    assert [real for _, real in segments] == [
        old_segments[i][1] for i in pooled]
    assert [[pooled[i] for i in r] for r in regions] == [
        list(r) for r in old_regions]
    # packed: a segment starts where the one before it ends, padded
    starts = np.cumsum([0] + [-(-real // 8) * 8 for _, real in segments])
    assert [at for at, _ in segments] == list(starts[:-1])
    assert starts[-1] == padded
    # a window's image rectangle is the old one's; only its row moved
    moved = {old_segments[i][0]: at for i, (at, _) in zip(pooled, segments)}
    want = [w[:3] + (moved[start] + w[3] - start,)
            for start, real in (old_segments[i] for i in pooled)
            for w in old_windows if start <= w[3] < start + real]
    assert list(windows) == want
    out_dim = geometry["img_size"] - geometry["patch_size"] + 1
    assert pk.fused_positions_kept(**geometry) == (kept, left_out)
    assert kept + left_out == out_dim ** 2
    if not left_out:
        assert (windows, segments, regions) == (
            old_windows, old_segments, old_regions)


def test_fused_geometry_of_a_crop_and_of_cifar():
    from keystone_tpu.ops import pallas_kernels as pk

    pp, fp, kp, r, image = pk._fused_geometry(24, 6, 3, 13, 14, 2048)
    assert (pp, fp, kp, r, image) == (200, 128, 2048, 1, 24 * 128)
    assert pk._fused_layout(24, 6, 13, 14)[1:] == (((0, 196),), ((0,),))
    windows, segments, regions = pk._fused_layout(32, 6, 13, 14)
    assert len(windows) == 3 * 27 and regions == (
        (0, 1, 3, 4), (1, 2, 4, 5), (3, 4, 6, 7), (4, 5, 7, 8))
    assert segments == (
        (0, 169), (176, 13), (192, 169), (368, 13), (384, 1), (392, 13),
        (408, 169), (584, 13), (600, 169))
    assert pk._fused_geometry(32, 6, 3, 13, 14, 512)[0] == 776


@pytest.mark.parametrize("case", [
    "crops_of_24_two_passes", "one_segment_a_region", "two_steps_two_banks"])
def test_fused_features_are_those_of_the_layout_of_every_rectangle(
        case, monkeypatch):
    """Leaving out the rows that no region pools moves no feature by a
    bit (ISSUE 46): every row of the patches, of their statistics and of
    the product is independent of every other row, and a pooled
    segment's rows are summed in the order they were. The kernel under
    the layout of every rectangle (it then sums segments no region
    reads) against the kernel as it is, interpret mode, the geometries
    where positions are left out."""
    from keystone_tpu.ops import pallas_kernels as pk

    imgs, banks, means, geometry = _fused_cases()[case]
    assert pk.fused_positions_kept(
        geometry["img_size"], geometry["patch_size"],
        geometry["pool_stride"], geometry["pool_size"])[1] > 0
    # under the jit and its cache: the layout is read at trace time
    featurize = pk.fused_cifar_featurize_banks.__wrapped__.__wrapped__

    def features():
        return np.asarray(featurize(
            jnp.asarray(imgs), jnp.asarray(banks), alpha=0.25,
            whitener_means=None if means is None else jnp.asarray(means),
            interpret=True, **geometry))

    got = features()
    monkeypatch.setattr(pk, "_fused_layout", _layout_of_every_rectangle)
    want = features()
    assert np.linalg.norm(want) > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("img_size,kept,left_out", [
    (24, 196, 165), (32, 729, 0)])
def test_fused_maker_counts_the_positions_it_keeps_and_leaves_out(
        img_size, kept, left_out, v5e_budget, monkeypatch):
    """Counted at trace time, beside ``featurize.conv_block.pallas``:
    how far the shorter layout engages at a geometry (ISSUE 46)."""
    from keystone_tpu.nodes.images import core
    from keystone_tpu.observability import names
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    counted = ("featurize.conv_block.pallas", "featurize.conv_positions.kept",
               "featurize.conv_positions.left_out")
    assert set(counted) <= set(names.METRIC_NAMES)
    counter = MetricsRegistry.get_or_create().counter
    before = [counter(n).value for n in counted]
    node = core.FusedConvRectifyPool(
        np.zeros((8, 108), np.float32), img_size, 6)
    out = jax.eval_shape(lambda x: node.make_blocks_with_params(
        (jnp.zeros((2, 8, 108)), jnp.zeros((2, 108))), x),
        jax.ShapeDtypeStruct((16, img_size, img_size, 3), jnp.float32))
    assert out.shape == (2, 16, node.columns_a_filter() * 8)
    assert [counter(n).value - b for n, b in zip(counted, before)] == [
        1, kept, left_out]


def test_fused_call_reads_images_not_patches():
    """Counted, not timed: the maker's program holds no convolution
    that would build an im2col operand outside the kernel, and what the
    ``pallas_call`` reads is the images, the filters and their rows,
    not 26 times the images (ISSUE 42)."""
    from keystone_tpu.ops.pallas_kernels import fused_cifar_featurize_banks

    imgs = jnp.zeros((64, 32, 32, 3), jnp.float32)
    filters = jnp.zeros((2, 512, 108), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, f, m: fused_cifar_featurize_banks(
        x, f, whitener_means=m))(imgs, filters, jnp.zeros((2, 108)))

    def eqns(j):
        for eqn in j.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue             # the kernel's own body
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    seen = list(eqns(jaxpr.jaxpr))
    calls = [e for e in seen if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert not [e for e in seen if "conv_general_dilated" in e.primitive.name]
    read = sum(v.aval.size * v.aval.dtype.itemsize for v in calls[0].invars)
    assert read < 2 * imgs.nbytes + filters.nbytes, read
    # and it writes the blocks as the caller keeps them
    assert [v.aval.shape for v in calls[0].outvars] == [(2, 64, 4096)]
    assert jaxpr.out_avals[0].shape == (2, 64, 4096)
    assert seen[-1] is calls[0]       # nothing is copied after the call


def test_fused_featurize_vmem_guard_boundary(v5e_budget, monkeypatch):
    """The kernel's footprint counts the patch matrices it keeps in VMEM
    for a step's images; the guard's boundary is exact, and a geometry
    past it takes the composed ops and says so."""
    from keystone_tpu.nodes.images import core
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.ops import pallas_kernels as pk

    cifar = (32, 6, 3, 13, 14)
    pp, fp, kp, r, image = pk._fused_geometry(*cifar, 512)
    assert (pp, fp, kp, r, image) == (776, 128, 512, 4, 32 * 128)
    T = pk.FUSED_IMAGES_A_STEP
    nbytes = pk.fused_featurize_vmem_bytes(pp, fp, kp, r, 5, T, image)
    # the patches of 8 images, 3.2 MB, are in it once (scratch)
    assert nbytes - pk.fused_featurize_vmem_bytes(
        pp, 0, kp, r, 5, T, image) == 4 * (T * pp * fp + 2 * pp * fp
                                           + 2 * 5 * fp * kp)
    assert nbytes < 16 << 20
    assert pk.fused_featurize_fits_vmem(*cifar, 512, banks=5)
    monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: nbytes)
    assert pk.fused_featurize_fits_vmem(*cifar, 512, banks=5)
    monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: nbytes - 1)
    assert not pk.fused_featurize_fits_vmem(*cifar, 512, banks=5)
    monkeypatch.undo()
    monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: 96 << 20)
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    # 251 x 251 positions: 63,000 rows of patches an image
    wide = core.FusedConvRectifyPool(
        np.zeros((8, 108), np.float32), 256, 6, 3, 13, 14)
    assert not pk.fused_featurize_fits_vmem(256, 6, 3, 13, 14, 8)
    assert not wide._kernel_fits(1, 8)
    assert core.FusedConvRectifyPool(
        np.zeros((8, 108), np.float32), 32, 6)._kernel_fits(1, 8)
    counter = MetricsRegistry.get_or_create().counter
    before = [counter(n).value for n in (
        "featurize.conv_block.xla", "featurize.conv_block.pallas",
        "featurize.conv_patches.vmem")]
    monkeypatch.setattr(core, "FUSED_ROW_BATCH", 1)
    jax.eval_shape(lambda x: wide.make_blocks_with_params(
        (jnp.zeros((1, 8, 108)), jnp.zeros((1, 108))), x),
        jax.ShapeDtypeStruct((1, 256, 256, 3), jnp.float32))
    assert [counter(n).value for n in (
        "featurize.conv_block.xla", "featurize.conv_block.pallas",
        "featurize.conv_patches.vmem")] == [before[0] + 1, before[1],
                                            before[2]]


def test_kernel_trace_keeps_its_chunk_of_the_frame_stack():
    """Counted, not timed: a loop of calls across the end of a chunk of
    CPython's frame stack maps and unmaps a chunk a call, a page fault
    each; under ``_with_frame_room`` the same loop faults on nothing.
    The fused kernel's body, traced in every process that holds it, is
    wrapped so."""
    import resource

    from keystone_tpu.ops import pallas_kernels as pk

    def leaf():
        return None

    def faults(depth, calls):
        if depth:
            return faults(depth - 1, calls)
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        for _ in range(calls):
            leaf()
        return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before

    @pk._with_frame_room
    def roomy(depth, calls=0, *, scale=1):
        """doc"""
        if depth < 0:
            raise ValueError(depth)
        return scale * faults(depth, calls)

    assert (roomy.__name__, roomy.__doc__) == ("roomy", "doc")
    assert roomy(0) == 0 and roomy(3, scale=2) == 0   # no calls, no faults
    with pytest.raises(ValueError):
        roomy(-1)
    assert pk._fused_featurize_kernel.__code__.co_stacksize == 1 << 16
    assert pk._fused_featurize_kernel.__wrapped__.__name__ == (
        "_fused_featurize_kernel")
    calls = 2000
    ends = [d for d in range(400) if faults(d, calls) >= calls // 2]
    if not ends:
        pytest.skip("this interpreter keeps its frames some other way")
    assert roomy(ends[0], calls) < calls // 20


def test_fused_node_off_tpu_composes(mesh8):
    from keystone_tpu.nodes.images.core import FusedConvRectifyPool
    from keystone_tpu.parallel.dataset import ArrayDataset

    rng = np.random.RandomState(0)
    imgs = rng.rand(8, 32, 32, 3).astype(np.float32)
    filters = rng.randn(16, 108).astype(np.float32)
    node = FusedConvRectifyPool(filters, 32, 6)
    out = node.apply_dataset(ArrayDataset.from_numpy(imgs)).numpy()
    assert out.shape == (8, 2 * 2 * 2 * 16)
    single = np.asarray(node.apply(imgs[0]))
    np.testing.assert_allclose(out[0], single, rtol=1e-4, atol=1e-4)


def _interpreted(fn):
    """``fn`` with ``interpret=True`` forced: the CPU stand-in for a
    kernel the dispatchers would compile on the chip."""
    def run(*args, **kwargs):
        kwargs["interpret"] = True
        return fn(*args, **kwargs)
    return run


def test_fused_node_batch_path_runs_the_kernel_per_shard(
        mesh8, monkeypatch, v5e_budget):
    """The TPU batch path: the kernel under ``shard_map`` (pallas_call
    has no partitioning rule), every device on its own rows, all of
    them a call. Ragged rows (75 over 8 shards: 10 a shard, which the
    kernel's 8 images a step do not divide) must come back exactly as
    one whole-batch kernel call computes them."""
    from keystone_tpu.nodes.images import core
    from keystone_tpu.ops import pallas_kernels as pk
    from keystone_tpu.parallel.dataset import ArrayDataset

    whole_batch = pk.fused_cifar_featurize_banks
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    monkeypatch.setattr(pk, "fused_cifar_featurize_banks",
                        _interpreted(whole_batch))
    core._fused_rows_program.cache_clear()
    rng = np.random.RandomState(0)
    imgs = (rng.rand(75, 32, 32, 3) * 255).astype(np.float32)
    filters = rng.randn(16, 108).astype(np.float32)

    class Whitener:
        means = rng.randn(108).astype(np.float32)

    try:
        for whitener in (None, Whitener):
            node = core.FusedConvRectifyPool(
                filters, 32, 6, whitener=whitener)
            out = node.apply_dataset(ArrayDataset.from_numpy(imgs))
            assert out.data.sharding.spec == ("data",)
            (want,) = np.asarray(whole_batch(
                jnp.asarray(imgs), jnp.asarray(filters)[None],
                whitener_means=None if whitener is None
                else jnp.asarray(whitener.means)[None], interpret=True))
            np.testing.assert_array_equal(out.numpy(), want)
            single = np.asarray(node.apply(imgs[74]))
            np.testing.assert_allclose(out.numpy()[74], single,
                                       rtol=2e-3, atol=2e-3)
    finally:
        core._fused_rows_program.cache_clear()


def test_gram_cross_runs_per_shard_on_a_mesh(mesh8, monkeypatch):
    """Row-sharded chunks: each device runs the kernel on its own rows
    and the partial products are summed over the data axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: _V5E_VMEM)
    monkeypatch.setattr(pk, "gram_cross_pallas",
                        _interpreted(pk.gram_cross_pallas))
    rng = np.random.RandomState(0)
    X = rng.randn(64, 37).astype(np.float32)
    Y = rng.randn(64, 5).astype(np.float32)
    rows = NamedSharding(mesh8, P("data", None))
    g, c = jax.jit(lambda x, y: pk.gram_cross(x, y, mesh=mesh8))(
        jax.device_put(X, rows), jax.device_put(Y, rows))
    np.testing.assert_allclose(np.asarray(g), X.T @ X, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(c), X.T @ Y, rtol=2e-4, atol=2e-4)


def test_quantized_affine_runs_per_shard_on_a_mesh(mesh8, monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically: on a mesh
    the quantized predict runs under shard_map, rows sharded, weights
    replicated (the TPU compiler refuses the unwrapped call)."""
    from keystone_tpu.nodes.learning.linear import (
        LinearMapper,
        _dequant_affine,
    )
    from keystone_tpu.ops import pallas_kernels as pk
    from keystone_tpu.parallel.dataset import ArrayDataset

    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: _V5E_VMEM)
    monkeypatch.setattr(pk, "quantized_affine_pallas",
                        _interpreted(pk.quantized_affine_pallas))
    rng = np.random.RandomState(0)
    X = rng.randn(21, 24).astype(np.float32)   # ragged over 8 shards
    for weight_dtype in ("bf16", "int8"):
        mapper = LinearMapper(rng.randn(24, 3).astype(np.float32),
                              intercept=rng.randn(3).astype(np.float32),
                              weight_dtype=weight_dtype)
        out = mapper.apply_dataset(ArrayDataset.from_numpy(X))
        assert out.data.sharding.spec[0] == "data"
        want = np.asarray(_dequant_affine(
            mapper.apply_params(), jnp.asarray(X)))
        np.testing.assert_allclose(out.numpy(), want, rtol=1e-4, atol=1e-4)


def test_fused_featurize_whitener_means_parity():
    from keystone_tpu.ops.image_ops import filter_bank_convolve, pool_image
    from keystone_tpu.ops.pallas_kernels import fused_cifar_featurize_banks

    rng = np.random.RandomState(2)
    B, K, S = 2, 16, 6
    imgs = rng.rand(B, 32, 32, 3).astype(np.float32) * 255
    filters = rng.randn(K, S * S * 3).astype(np.float32)
    means = rng.randn(S * S * 3).astype(np.float32)
    (got,) = np.asarray(fused_cifar_featurize_banks(
        jnp.asarray(imgs), jnp.asarray(filters)[None],
        whitener_means=jnp.asarray(means)[None], interpret=True))

    def one(img):
        conv = filter_bank_convolve(
            jnp.asarray(img), jnp.asarray(filters), S, 3, True,
            jnp.asarray(means), 10.0)
        pos = jnp.maximum(0.0, conv - 0.25)
        neg = jnp.maximum(0.0, -conv - 0.25)
        return np.asarray(pool_image(
            jnp.concatenate([pos, neg], -1), 13, 14, "identity", "sum"
        )).reshape(-1)

    want = np.stack([one(i) for i in imgs])
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_gram_vmem_guard_boundary(v5e_budget):
    """The fused gram kernel's (d, d)+(d, k) accumulators are VMEM-
    resident for the whole grid; past the per-kernel share of VMEM the
    wrappers take the einsum path instead of attempting the kernel."""
    from keystone_tpu.ops.pallas_kernels import gram_fits_vmem

    assert gram_fits_vmem(512, 16)
    assert gram_fits_vmem(896, 128)
    assert gram_fits_vmem(1024, 16)       # compiles with vmem_limit_bytes
    assert gram_fits_vmem(2560, 128)      # 89 MiB of the 96 MiB share
    assert not gram_fits_vmem(4096, 10)   # ImageNet-scale solve dims
    assert not gram_fits_vmem(3072, 10)   # LinearPixels dims


def test_gram_vmem_guard_counts_input_tiles(v5e_budget):
    """Small-d / large-k shapes blow VMEM through the streamed Y block,
    not the accumulators — the budget must count input tiles too."""
    from keystone_tpu.ops.pallas_kernels import gram_fits_vmem, gram_vmem_bytes

    assert not gram_fits_vmem(128, 65536)
    acc_only = 4 * 3 * 128 * (128 + 65536)
    assert gram_vmem_bytes(128, 65536) > acc_only


def test_unknown_device_kind_has_no_vmem_budget():
    """A device kind with no row in the VMEM table is an error, never
    another chip's figure: the CPU test backend is such a kind."""
    from keystone_tpu.ops import pallas_kernels as pk

    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        pk.vmem_budget_bytes()
    assert not pk.use_pallas()  # so no dispatcher ever asks on CPU


# -- shared fits-vmem predicate (PR 13 satellite) ---------------------------


def test_fits_vmem_boundary_is_exact(monkeypatch):
    """Every kernel dispatcher asks the ONE shared predicate with its
    own footprint; pin the fallback trigger exactly at the boundary."""
    from keystone_tpu.ops import pallas_kernels as pk

    cases = {
        "gram": (lambda: pk.gram_fits_vmem(512, 16),
                 pk.gram_vmem_bytes(512, 16)),
        "fv": (lambda: pk.fv_fits_vmem(64, 16), pk.fv_vmem_bytes(64, 16)),
        "quant": (lambda: pk.quant_fits_vmem(64, 16, 1),
                  pk.quant_vmem_bytes(64, 16, 1)),
    }
    for name, (predicate, nbytes) in cases.items():
        monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: nbytes)
        assert predicate(), f"{name}: must fit AT its own footprint"
        monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: nbytes - 1)
        assert not predicate(), f"{name}: must fall back one byte under"


# -- dense SIFT: XLA's band products on every platform ----------------------


def test_dense_sift_traces_no_kernel_on_a_tpu(monkeypatch):
    """What a TPU traces for the per-image form is what the CPU suite
    verifies: no dispatcher stands between ``dense_sift`` and XLA's
    products, so a VGA image with ``use_pallas`` true holds no
    ``pallas_call``."""
    from keystone_tpu.ops import pallas_kernels as pk
    from keystone_tpu.ops.sift import dense_sift

    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    monkeypatch.setattr(pk, "vmem_budget_bytes", lambda: _V5E_VMEM)
    jaxpr = jax.make_jaxpr(lambda g: dense_sift(g, 4, 6, 5, 1))(
        jax.ShapeDtypeStruct((480, 640), jnp.float32))
    text = str(jaxpr)
    assert "dot_general" in text
    assert "pallas_call" not in text


@pytest.mark.parametrize("h,w", [(128, 128), (384, 512)])
def test_sift_band_operator_bytes_are_the_operators(h, w):
    """The HBM plan charges dense SIFT's band operators once: the bytes
    of the arrays the chunk form holds on the device
    (``_bucket_operators``), which are the per-image form's own
    (``_smooth_band`` / ``_sampling_operator``), every scale."""
    from keystone_tpu.analysis.resources import sift_band_operator_nbytes
    from keystone_tpu.ops import sift as S

    config = (4, 6, 5, 1)
    chunk = image = 0
    for scale in range(config[2]):
        step, bin_size, lo = S._scale_params(scale, *config)
        chunk += sum(op.nbytes
                     for op in S._bucket_operators(h, w, step, bin_size, lo))
        image += sum(S._smooth_band(n, bin_size).nbytes
                     + S._sampling_operator(n, lo, step, bin_size)[0].nbytes
                     for n in (h, w))
    assert sift_band_operator_nbytes(h, w, *config) == chunk == image


# -- fused GMM-posterior + FV moments (PR 13 tentpole 2) --------------------


def _gmm_params(rng, d, k):
    return (rng.randn(d, k).astype(np.float32),
            (0.5 + rng.rand(d, k)).astype(np.float32),
            (rng.dirichlet(np.ones(k))).astype(np.float32))


@pytest.mark.parametrize("d,k,n", [(64, 16, 513), (32, 8, 100), (7, 3, 12)])
def test_fv_moments_pallas_interpret(d, k, n):
    """Kernel moments == fallback (posterior matrix) moments at mixed
    shapes including ragged descriptor counts (n not a tile multiple:
    the kernel must mask padded descriptor columns — a zero descriptor
    still has a nonzero posterior)."""
    from keystone_tpu.nodes.learning.gmm import _posteriors
    from keystone_tpu.ops.pallas_kernels import fv_moments_pallas

    rng = np.random.RandomState(0)
    X = rng.randn(d, n).astype(np.float32)
    means, variances, weights = _gmm_params(rng, d, k)
    q = np.asarray(_posteriors(
        jnp.asarray(X.T), jnp.asarray(means.T), jnp.asarray(variances.T),
        jnp.asarray(weights), 1e-4))
    s0, s1, s2 = fv_moments_pallas(
        jnp.asarray(X), jnp.asarray(means), jnp.asarray(variances),
        jnp.asarray(weights), threshold=1e-4, interpret=True)
    np.testing.assert_allclose(np.asarray(s0), q.sum(0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), X @ q, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), (X * X) @ q,
                               rtol=2e-4, atol=2e-4)


def test_fisher_vector_fused_matches_fallback():
    """End-to-end FV parity of the two forms of the moment sums under
    the one normalisation, per item and under vmap (the ImageNet
    featurizer vmaps the encoder over an image batch)."""
    from keystone_tpu.nodes.images.fisher_vector import (
        fisher_vector_of_sums,
        fv_moments_split,
    )
    from keystone_tpu.ops.pallas_kernels import fv_moments_pallas

    rng = np.random.RandomState(1)
    d, k, n, batch = 64, 16, 200, 3
    Xb = rng.randn(batch, d, n).astype(np.float32)
    means, variances, weights = _gmm_params(rng, d, k)
    args = (jnp.asarray(means), jnp.asarray(variances),
            jnp.asarray(weights))

    def fused(x):
        return fisher_vector_of_sums(
            fv_moments_pallas(x, *args, threshold=1e-4, interpret=True),
            n, *args)

    def fallback(x):
        return fisher_vector_of_sums(
            fv_moments_split(x, *args, threshold=1e-4), n, *args)

    a = np.asarray(jax.vmap(fallback)(jnp.asarray(Xb)))
    b = np.asarray(jax.vmap(fused)(jnp.asarray(Xb)))
    assert a.shape == (batch, d, 2 * k)
    np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("case", ["cpu", "tpu_fits", "tpu_over"])
def test_fv_moments_form_is_chosen_from_platform_and_fit(case, monkeypatch):
    """The one choice between the forms, from ``use_pallas()`` and
    ``fv_fits_vmem(d, k)`` alone, and the counter each raises when it is
    traced (``voc_refit``'s ``maker_off`` stands on them)."""
    from keystone_tpu.nodes.images import fisher_vector as fv
    from keystone_tpu.observability.metrics import MetricsRegistry
    from keystone_tpu.ops import pallas_kernels as pk

    ran, split = [], fv.fv_moments_split

    def spy(name, fn, **fixed):
        def run(*args, **kwargs):
            ran.append(name)
            return fn(*args, **kwargs, **fixed)
        return run

    monkeypatch.setattr(pk, "fv_moments_pallas",
                        spy("pallas", pk.fv_moments_pallas, interpret=True))
    monkeypatch.setattr(fv, "fv_moments_split", spy("einsum", split))
    if case != "cpu":
        monkeypatch.setattr(pk, "use_pallas", lambda: True)
        monkeypatch.setattr(
            pk, "vmem_budget_bytes",
            lambda: int(_V5E_VMEM * pk._VMEM_KERNEL_SHARE)
            if case == "tpu_fits" else 1)
    want = "pallas" if case == "tpu_fits" else "einsum"
    counters = {form: MetricsRegistry.get_or_create().counter(
        "featurize.fv." + form) for form in ("pallas", "einsum")}
    before = {form: c.value for form, c in counters.items()}

    rng = np.random.RandomState(3)
    d, k, n = 16, 4, 150
    X = jnp.asarray(rng.randn(d, n).astype(np.float32))
    args = tuple(jnp.asarray(p) for p in _gmm_params(rng, d, k))
    got = np.asarray(jax.jit(
        lambda x: fv._fisher_vector_of(x, *args, 1e-4))(X))
    assert ran == [want]
    assert {form: c.value - before[form] for form, c in counters.items()} \
        == {form: int(form == want) for form in counters}
    reference = fv.fisher_vector_of_sums(
        split(X, *args, threshold=1e-4), n, *args)
    np.testing.assert_allclose(got, np.asarray(reference),
                               rtol=2e-3, atol=2e-4)


def test_a_fisher_vector_chunk_on_a_tpu_traces_one_kernel_in_its_map(
        monkeypatch, v5e_budget):
    """What ``voc_refit`` reads as ``fv_dev_ms.voc``: at the cell's GMM
    (80 x 256) a chunk's program holds the fused kernel once, inside the
    map over the chunk's matrices, and no posterior matrix beside it."""
    from keystone_tpu.nodes.images.fisher_vector import _fisher_vector_chunk
    from keystone_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    d, k, n, b = 80, 256, 1024, 4
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((b, d, n), (d, k), (d, k), (k,))]
    mask = jax.ShapeDtypeStruct((b, n), jnp.bool_)
    text = str(jax.make_jaxpr(
        lambda X, m, *gmm: _fisher_vector_chunk(
            X, m, *gmm, weight_threshold=1e-4))(shapes[0], mask, *shapes[1:]))
    assert text.count("pallas_call") == 1
    assert "scan" in text                       # lax.map over the chunk
    assert f"f32[{n},{k}]" not in text          # no posteriors through HBM


# -- quantized predict (PR 13 tentpole 3) -----------------------------------


def test_quantized_affine_pallas_interpret():
    """Kernel == dequantizing-einsum fallback (bit-compatible: the same
    dequantize-then-f32-matmul math) for int8 and bf16 weights at a
    ragged batch size."""
    from keystone_tpu.ops.pallas_kernels import quantized_affine_pallas

    rng = np.random.RandomState(0)
    n, d, k = 77, 50, 11
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    mean = rng.randn(d).astype(np.float32)
    inv = (1.0 + rng.rand(d)).astype(np.float32)
    b = rng.randn(k).astype(np.float32)
    scale = (np.abs(W).max(axis=0) / 127.0).astype(np.float32)
    Wq = np.clip(np.round(W / scale), -127, 127).astype(np.int8)
    got = np.asarray(quantized_affine_pallas(
        jnp.asarray(X), jnp.asarray(Wq), jnp.asarray(scale),
        jnp.asarray(mean), jnp.asarray(inv), jnp.asarray(b),
        interpret=True))
    want = ((X - mean) * inv) @ (Wq.astype(np.float32) * scale) + b
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    Wb = jnp.asarray(W, jnp.bfloat16)
    got = np.asarray(quantized_affine_pallas(
        jnp.asarray(X), Wb, jnp.ones((k,), jnp.float32),
        jnp.asarray(mean), jnp.asarray(inv), jnp.asarray(b),
        interpret=True))
    want = ((X - mean) * inv) @ np.asarray(Wb.astype(jnp.float32)) + b
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("weight_dtype,min_agree,max_rel", [
    ("bf16", 1.0, 0.02), ("int8", 0.98, 0.03)])
def test_quantized_predict_parity_gate(weight_dtype, min_agree, max_rel,
                                       mesh8):
    """The serving-plane parity bar: quantized apply must agree with
    the f32 apply on argmax and stay inside a relative error bound,
    per item AND on the batched dataset path, with the quantization
    error recorded into the numerics funnel."""
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.observability import MetricsRegistry
    from keystone_tpu.parallel.dataset import ArrayDataset

    rng = np.random.RandomState(0)
    n, d, k = 256, 64, 10
    X = rng.randn(n, d).astype(np.float32)
    # a separable teacher task: agreement on pure-noise labels would
    # measure near-tie argmax flips, not quantization quality
    teacher = rng.randn(d, k).astype(np.float32)
    Y = -np.ones((n, k), np.float32)
    Y[np.arange(n), (X @ teacher).argmax(1)] = 1.0
    model = LinearMapEstimator(1e-3).fit(
        ArrayDataset.from_numpy(X), ArrayDataset.from_numpy(Y))
    quant = LinearMapEstimator(1e-3, weight_dtype=weight_dtype).fit(
        ArrayDataset.from_numpy(X), ArrayDataset.from_numpy(Y))
    assert quant.weight_dtype == weight_dtype

    reg = MetricsRegistry.get_or_create()
    events0 = reg.counter("numerics.quant_error").value
    a = model.apply_dataset(ArrayDataset.from_numpy(X)).numpy()
    b = quant.apply_dataset(ArrayDataset.from_numpy(X)).numpy()
    assert (a.argmax(1) == b.argmax(1)).mean() >= min_agree
    assert np.abs(a - b).max() / np.abs(a).max() <= max_rel
    # the quantization error landed in the numerics funnel
    assert reg.counter("numerics.quant_error").value >= events0 + 1
    assert reg.gauge("numerics.quant_rel_error").value > 0.0
    # per-item path agrees with the batch path
    pi = np.asarray(quant.apply(jnp.asarray(X[0])))
    np.testing.assert_allclose(pi, b[0], rtol=1e-4, atol=1e-4)


def test_weight_dtype_contract():
    """Config validation + program identity: a typo fails eagerly;
    differently-quantized models never share struct-keyed programs;
    pickling re-quantizes on first use (the cache is a _jit_ key)."""
    import pickle

    from keystone_tpu.nodes.learning.linear import (
        BlockLinearMapper,
        LinearMapper,
        _canon_weight_dtype,
    )

    with pytest.raises(ValueError):
        _canon_weight_dtype("float16")
    assert _canon_weight_dtype("bfloat16") == "bf16"
    assert _canon_weight_dtype(np.int8) == "int8"
    assert _canon_weight_dtype(None) is None

    W = np.eye(4, dtype=np.float32)
    m32 = LinearMapper(W)
    m8 = LinearMapper(W, weight_dtype="int8")
    assert m32.struct_key() != m8.struct_key()
    assert m32.eq_key() != m8.eq_key()
    bm = BlockLinearMapper([W[:2], W[2:]], 2, weight_dtype="bf16")
    assert bm.struct_key() != BlockLinearMapper([W[:2], W[2:]], 2).struct_key()

    m8.apply_params()  # builds + caches the quantized params
    clone = pickle.loads(pickle.dumps(m8))
    assert clone.weight_dtype == "int8"
    assert "_jit_affine_params" not in clone.__dict__
    x = np.ones(4, np.float32)
    np.testing.assert_allclose(np.asarray(clone.apply(jnp.asarray(x))),
                               np.asarray(m8.apply(jnp.asarray(x))))
