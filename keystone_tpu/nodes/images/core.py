"""Image pipeline nodes.

TPU-native re-designs of the reference's ``nodes/images`` package
(SURVEY.md section 2.4). Images are (H, W, C) float arrays; batch
execution vmaps/convolves over the sharded batch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...observability.compilelog import observed_jit
from ...ops import image_ops
from ...parallel.dataset import ArrayDataset, Dataset
from ...workflow.transformer import Transformer, struct_cached_jit


@observed_jit
def vectorize_images(imgs):
    return imgs.reshape(imgs.shape[0], -1)


class ImageVectorizer(Transformer):
    """Flatten an image to a vector (reference ``images/ImageVectorizer``)."""

    def apply(self, img):
        return img.reshape(-1)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset) and isinstance(ds.data, jax.Array):
            # one named program (``jit_vectorize_images`` in a trace)
            return ds.map_batch(vectorize_images)
        return super().apply_dataset(ds)


class PixelScaler(Transformer):
    """Divide pixels by 255 (reference ``images/PixelScaler``)."""

    keeps_padding = True

    def apply(self, img):
        return img / 255.0


class GrayScaler(Transformer):
    """MATLAB-weight grayscale (reference ``images/GrayScaler``)."""

    keeps_padding = True

    def apply(self, img):
        return image_ops.to_grayscale(img)


class Cropper(Transformer):
    """Static crop [x0:x1, y0:y1] (reference ``images/Cropper``)."""

    def __init__(self, x0: int, y0: int, x1: int, y1: int):
        self.x0, self.y0, self.x1, self.y1 = x0, y0, x1, y1

    def apply(self, img):
        return img[self.x0 : self.x1, self.y0 : self.y1, :]


class SymmetricRectifier(Transformer):
    """Channel-doubling rectifier [max(v, x-a), max(v, -x-a)]
    (reference ``images/SymmetricRectifier.scala:12-30``)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = float(max_val)
        self.alpha = float(alpha)

    def apply(self, img):
        pos = jnp.maximum(self.max_val, img - self.alpha)
        neg = jnp.maximum(self.max_val, -img - self.alpha)
        return jnp.concatenate([pos, neg], axis=-1)


class Pooler(Transformer):
    """Strided spatial pooling (reference ``images/Pooler.scala:20-68``).
    pixel_fn/pool_fn are named ('identity'|'abs'|'square',
    'sum'|'max'|'mean') so node equality stays structural."""

    def __init__(
        self,
        stride: int,
        pool_size: int,
        pixel_fn: str = "identity",
        pool_fn: str = "sum",
    ):
        self.stride = stride
        self.pool_size = pool_size
        self.pixel_fn = pixel_fn
        self.pool_fn = pool_fn

    def apply(self, img):
        return image_ops.pool_image(
            img, self.stride, self.pool_size, self.pixel_fn, self.pool_fn
        )


class Convolver(Transformer):
    """Filter-bank convolution with optional per-patch normalization and
    whitening fold-in (reference ``images/Convolver.scala:20-45``).

    ``filters`` is (num_filters, conv_size^2 * channels) in (dy, dx, c)
    feature order, pre-whitened by the caller exactly as in the reference
    (filters_normalized @ whitener.T); the whitener's means are subtracted
    from each normalized patch. Executes as pure XLA convolutions — see
    ``ops/image_ops.filter_bank_convolve``.
    """

    def __init__(
        self,
        filters: np.ndarray,
        img_height: int,
        img_width: int,
        img_channels: int,
        whitener: Optional["ZCAWhitener"] = None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
    ):
        self.filters = np.asarray(filters, dtype=np.float32)
        self.img_height = img_height
        self.img_width = img_width
        self.img_channels = img_channels
        self.whitener = whitener
        self.normalize_patches = normalize_patches
        self.var_constant = var_constant
        self.conv_size = int(
            round((self.filters.shape[1] / img_channels) ** 0.5)
        )

    def eq_key(self):
        return (
            Convolver,
            self.filters.tobytes(),
            self.img_height,
            self.img_width,
            self.img_channels,
            None if self.whitener is None else self.whitener.means.tobytes(),
            self.normalize_patches,
            self.var_constant,
        )

    def apply(self, img):
        means = None if self.whitener is None else jnp.asarray(self.whitener.means)
        return image_ops.filter_bank_convolve(
            img,
            jnp.asarray(self.filters),
            self.conv_size,
            self.img_channels,
            self.normalize_patches,
            means,
            self.var_constant,
        )

    # fitted-param protocol: the (whitened) filter bank is fitted per
    # run, so programs built over plain apply() bake it as constants and
    # recompile on every refit; threading it as arguments lets fused
    # featurizer chains share one compiled program across refits.
    def apply_params(self):
        params = self.__dict__.get("_jit_conv_params")
        if params is None:
            means = (None if self.whitener is None
                     else jnp.asarray(self.whitener.means))
            params = (jnp.asarray(self.filters), means)
            self.__dict__["_jit_conv_params"] = params  # _jit_*: unpickled
        return params

    def apply_with_params(self, params, img):
        filters, means = params
        return image_ops.filter_bank_convolve(
            img, filters, self.conv_size, self.img_channels,
            self.normalize_patches, means, self.var_constant,
        )

    def struct_key(self):
        return (Convolver, self.conv_size, self.img_channels,
                self.normalize_patches, self.var_constant,
                self.whitener is None)


class Windower(Transformer):
    """Dense sliding-window patch extraction (reference
    ``images/Windower.scala:14-55``). A 1->many node: each image yields
    all its windows, so the output dataset has n * num_windows items.
    Padding rows of the input batch map to trailing zero windows, so the
    true count stays exact."""

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def apply(self, img):
        w = image_ops.extract_windows(img, self.window_size, self.stride)
        nH, nW, S, _, C = w.shape
        return w.reshape(nH * nW, S, S, C)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        assert isinstance(ds, ArrayDataset)
        out = ds.map_batch(self._batched())
        data = out.data  # (P, num_windows, S, S, C)
        P, num_windows = data.shape[0], data.shape[1]
        flat = _flatten_leading(data)
        return ArrayDataset(
            flat, n=ds.n * num_windows, mesh=ds.mesh, _already_sharded=True
        )


@functools.partial(jax.jit, static_argnames=("size",))
def _gather_windows(imgs, starts, size):
    """Flattened (size x size) windows of ``imgs`` (N, H, W, C) at
    ``starts`` rows of (image, y, x): the sampled images gathered whole
    (one row gather of H*W*C floats a sample), then the window's rows
    and its ``size * C`` contiguous columns picked out of each by two
    batched products with 0/1 selectors, at ``highest`` so that a pixel
    comes through exactly. The result is the sample, never the full
    window set, and the program holds no loop.

    It was a ``dynamic_slice`` a row-run of every window until PR 30:
    600,000 iterations of a ``while`` for CIFAR's 100,000 windows, 1.26 s
    of a fit on the chip, and over a million device events a fit, which
    filled a profiler capture after two fits. Sliced as 4-D (1, size,
    size, C) boxes, the TPU compiler first copies the whole operand into
    a layout with the C=3 axis padded to 128 lanes: 26.2 GB for 50,000
    CIFAR images (my chip run, PR 21)."""
    N, H, W, C = imgs.shape
    picked = jnp.take(imgs.reshape(N, H, W * C), starts[:, 0], axis=0)
    rows = (starts[:, 1, None] + jnp.arange(size))[:, :, None] == jnp.arange(H)
    cols = jnp.arange(W * C)[:, None] == (
        starts[:, 2, None] * C + jnp.arange(size * C))[:, None, :]
    with jax.default_matmul_precision("highest"):
        runs = jnp.einsum("mdy,myx->mdx", rows.astype(imgs.dtype), picked)
        return jnp.einsum("mdx,mxj->mdj", runs,
                          cols.astype(imgs.dtype)).reshape(len(starts), -1)


class WindowSampler(Transformer):
    """``Windower(stride, window_size) >> ImageVectorizer() >>
    Sampler(size, seed)`` as one node, for filter learning over a whole
    training set. The chain materializes every window of every image
    before it picks ``size`` of them (50,000 CIFAR images x 729 windows
    x 108 floats = 15.7 GB, all but 43 MB discarded); this node draws
    the same seeded sample over the same flat (image-major, row-major)
    window order first and gathers only those windows, so the output is
    identical."""

    def __init__(self, stride: int, window_size: int, size: int,
                 seed: int = 42):
        self.stride = stride
        self.window_size = window_size
        self.size = size
        self.seed = seed

    def apply_dataset(self, ds: Dataset) -> Dataset:
        from ..stats.sampling import sample_indices

        assert isinstance(ds, ArrayDataset)
        H, W = ds.data.shape[1:3]
        nH = (H - self.window_size) // self.stride + 1
        nW = (W - self.window_size) // self.stride + 1
        idx = sample_indices(ds.n * nH * nW, self.size, self.seed)
        img, win = np.divmod(idx, nH * nW)
        wy, wx = np.divmod(win, nW)
        starts = np.stack(
            [img, wy * self.stride, wx * self.stride], axis=1)
        data = _gather_windows(
            ds.data, jnp.asarray(starts, jnp.int32), self.window_size)
        return ArrayDataset(data, len(idx), ds.mesh)


#: Images whose crops ``RandomPatcher`` makes in one step of its loop:
#: the selectors of a step are ``step x num_patches x (W * C) x (py *
#: C)`` floats (142 MB at CIFAR's shapes and ten crops an image).
PATCHER_IMAGES_A_STEP = 512


def _crop_offsets(key, num_images: int, num_patches: int, rows: int,
                  cols: int):
    """``RandomPatcher.offsets`` from the seed's key: starts in
    ``[0, rows) x [0, cols)``."""
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(num_images))

    def one(key):
        kx, ky = jax.random.split(key)
        return (jax.random.randint(kx, (num_patches,), 0, rows),
                jax.random.randint(ky, (num_patches,), 0, cols))

    return jax.vmap(one)(keys)


class RandomPatcher(Transformer):
    """Uniformly random crops, ``num_patches`` per image (reference
    ``images/RandomPatcher.scala:17-46``). Deterministic per (seed, item
    index)."""

    def __init__(self, num_patches: int, patch_size_x: int, patch_size_y: int,
                 seed: int = 0):
        self.num_patches = num_patches
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self.seed = seed

    def offsets(self, num_images: int, height: int, width: int):
        """``(xs, ys)``, each ``(num_images, num_patches)``: where image
        ``i``'s crops start, by rows and by columns. A function of the
        seed and the image's index alone: ``fold_in(PRNGKey(seed), i)``
        split in two, a ``randint`` of ``num_patches`` from each."""
        return _crop_offsets(
            jax.random.PRNGKey(self.seed), num_images, self.num_patches,
            height - self.patch_size_x + 1, width - self.patch_size_y + 1)

    def _make_batch(self):
        px, py, npp = self.patch_size_x, self.patch_size_y, self.num_patches

        def random_patches(key, imgs):
            P, H, W, C = imgs.shape
            xs, ys = _crop_offsets(key, P, npp, H - px + 1, W - py + 1)
            step = min(P, PATCHER_IMAGES_A_STEP)
            steps = -(-P // step)
            pad = steps * step - P
            flat = imgs.reshape(P, H, W * C)
            if pad:
                flat, xs, ys = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (
                    a.ndim - 1)) for a in (flat, xs, ys))

            def crops(part):
                # a crop's rows, then its ``py * C`` contiguous columns,
                # picked out of the image's 2-D view by two products
                # with 0/1 selectors at ``highest``, so that a pixel
                # comes through exactly (``_gather_windows`` says why
                # not slices: 4-D boxes make the TPU compiler pad the
                # C=3 axis to 128 lanes, a ``dynamic_slice`` a crop is
                # a loop of half a million steps)
                img, x, y = part
                rows = (x[:, :, None] + jnp.arange(px))[..., None] == (
                    jnp.arange(H))
                cols = jnp.arange(W * C)[:, None] == (
                    y[:, :, None] * C + jnp.arange(py * C))[:, :, None, :]
                with jax.default_matmul_precision("highest"):
                    runs = jnp.einsum("bpdy,byx->bpdx",
                                      rows.astype(img.dtype), img)
                    # a step's crops as rows of vectors: the layout
                    # the loop's stacked output keeps without padding
                    return jnp.einsum(
                        "bpdx,bpxj->bpdj", runs, cols.astype(img.dtype)
                    ).reshape(step * npp, px * py * C)

            out = jax.lax.map(crops, tuple(
                a.reshape((steps, step) + a.shape[1:])
                for a in (flat, xs, ys)))
            # crop-major rows here, inside the program: flattened as an
            # op of its own, the TPU lays the 5-D array out with C = 3
            # padded to 128 lanes first (18.4 GB for 500,000 crops)
            return out.reshape((steps * step * npp, px * py * C))[
                :P * npp].reshape((P * npp, px, py, C))

        return random_patches

    def apply_dataset(self, ds: Dataset) -> Dataset:
        assert isinstance(ds, ArrayDataset)
        # the key is an argument: a program serves every seed, in this
        # process and from the persistent compile cache
        program = struct_cached_jit(
            ("RandomPatcher.random_patch", self.num_patches,
             self.patch_size_x, self.patch_size_y), self._make_batch)
        crops = program(jax.random.PRNGKey(self.seed), ds.data)
        return ArrayDataset(crops, n=ds.n * self.num_patches, mesh=ds.mesh,
                            _already_sharded=True)

    def abstract_eval(self, dep_specs):
        return _patcher_abstract_eval(
            self, dep_specs, self.patch_size_x, self.patch_size_y,
            self.num_patches)


class CenterCornerPatcher(Transformer):
    """Center + four corner crops, optionally with horizontal flips —
    test-time augmentation (reference ``images/CenterCornerPatcher.scala``).
    Yields 5 (or 10) patches per image."""

    def __init__(self, patch_size_x: int, patch_size_y: int, horizontal_flips: bool = False):
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self.horizontal_flips = horizontal_flips

    @property
    def patches_per_image(self) -> int:
        return 10 if self.horizontal_flips else 5

    def apply(self, img):
        H, W, C = img.shape
        px, py = self.patch_size_x, self.patch_size_y
        starts = [
            (0, 0),
            (0, W - py),
            (H - px, 0),
            (H - px, W - py),
            ((H - px) // 2, (W - py) // 2),
        ]
        crops = [img[x : x + px, y : y + py, :] for x, y in starts]
        if self.horizontal_flips:
            crops = crops + [c[:, ::-1, :] for c in crops]
        return jnp.stack(crops)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        assert isinstance(ds, ArrayDataset)

        def build():
            def center_corner_patches(imgs):
                # image-major rows, flattened inside the program (see
                # ``RandomPatcher``)
                return _flatten_leading(jax.vmap(self.apply)(imgs))
            return center_corner_patches

        crops = self._cached_jit("center_corner", build)(ds.data)
        return ArrayDataset(
            crops,
            n=ds.n * self.patches_per_image,
            mesh=ds.mesh,
            _already_sharded=True,
        )

    def abstract_eval(self, dep_specs):
        return _patcher_abstract_eval(
            self, dep_specs, self.patch_size_x, self.patch_size_y,
            self.patches_per_image)


def _patcher_abstract_eval(op, dep_specs, px, py, patches_per_image):
    """Shared static semantics of the cropping augmenters: each (H, W, C)
    image becomes ``patches_per_image`` items of (px, py, C), multiplying
    the dataset's item count."""
    from ...analysis.spec import DatasetSpec, Unknown

    (d,) = dep_specs
    if not isinstance(d, DatasetSpec):
        return Unknown(f"{type(op).__name__} is dataset-only")
    e = d.element
    if not (isinstance(e, jax.ShapeDtypeStruct) and len(e.shape) == 3):
        return Unknown("patcher input not an (H, W, C) image element")
    H, W, C = e.shape
    if H < px or W < py:
        raise ValueError(
            f"{type(op).__name__}: patch ({px}, {py}) larger than "
            f"input image ({H}, {W})")
    out = jax.ShapeDtypeStruct((px, py, C), e.dtype)
    n = None if d.n is None else d.n * patches_per_image
    return DatasetSpec(out, n=n, host=d.host, sparsity=1.0)


def _flip_h(img):
    return img[:, ::-1, :]


class RandomFlipper(Transformer):
    """Horizontal flip with probability p — the common specialization of
    RandomImageTransformer (reference
    ``images/RandomImageTransformer.scala:16-30`` used with
    ``ImageUtils.flipHorizontal``). Kept as its own class for a stable,
    picklable eq_key."""

    def __init__(self, prob: float = 0.5, seed: int = 0):
        self.prob = prob
        self.seed = seed

    def eq_key(self):
        return (RandomFlipper, self.prob, self.seed)

    def apply(self, img):
        return img

    def apply_dataset(self, ds: Dataset) -> Dataset:
        return RandomImageTransformer(
            self.prob, _flip_h, self.seed).apply_dataset(ds)


class LabelExtractor(Transformer):
    """(image, label) -> label (reference ``images/LabeledImageExtractors``)."""

    def apply(self, item):
        return item[1]


class ImageExtractor(Transformer):
    """(image, label) -> image."""

    def apply(self, item):
        return item[0]


def _flatten_leading(data):
    """(P, M, ...) -> (P*M, ...), preserving row sharding."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), data
    )


class RandomImageTransformer(Transformer):
    """Apply an image->image transform with probability p per item
    (reference ``images/RandomImageTransformer.scala:16-30``); the
    transform must be jax-traceable and shape-preserving. RandomFlipper
    is the common flip case."""

    def __init__(self, prob: float, transform, seed: int = 0):
        self.prob = prob
        self.transform = transform
        self.seed = seed

    def eq_key(self):
        # function objects are not picklable/stably-hashable; key on
        # identity (session-local reuse only, like untagged datasets)
        return (RandomImageTransformer, self.prob, self.seed,
                id(self.transform))

    def apply(self, img):
        return img

    def _make_batch(self):
        prob, fn = self.prob, self.transform

        def random_transform(key, imgs):
            P = imgs.shape[0]
            hit = jax.random.uniform(key, (P,)) < prob
            changed = jax.vmap(fn)(imgs)
            return jnp.where(
                hit.reshape((-1,) + (1,) * (imgs.ndim - 1)), changed, imgs)

        return random_transform

    def apply_dataset(self, ds: Dataset) -> Dataset:
        assert isinstance(ds, ArrayDataset)
        # as ``RandomPatcher``: one program whatever the seed
        program = struct_cached_jit(
            ("RandomImageTransformer.random_transform", self.prob,
             self.transform), self._make_batch)
        key = jax.random.PRNGKey(self.seed)
        return ds.map_batch(lambda imgs: program(key, imgs))


#: Rows the composed XLA ops featurize at a time where they stand in for
#: the fused kernel inside a larger program (the streamed solve's block
#: maker off the TPU, or where the kernel's patches do not fit VMEM):
#: their rectifier intermediate is 6 MB an image. The kernel itself
#: takes the images as they are, 12 KB each, and builds its patches in
#: VMEM: its callers hand it all their rows at once.
FUSED_ROW_BATCH = 2048
#: What the blocks of one ``FusedConvRectifyPool.make_blocks_with_params``
#: call may take: a quarter of a 16 GB chip, the rest being for the rows,
#: the factors and a block's centred copies (five blocks of 50,000 x
#: 4,096 floats).
BANKS_A_CALL_BYTES = 4 << 30


def _banks_in_row_batches(featurize, imgs):
    """``featurize(batch) -> (g, batch rows, width)`` over ``imgs``,
    ``FUSED_ROW_BATCH`` rows at a time, each batch written into its
    place of one ``(g, rows rounded up, width)`` buffer: a map over the
    batches would stack them in front of ``g`` and a transposed copy of
    every block would follow."""
    n = imgs.shape[0]
    if n <= FUSED_ROW_BATCH:
        return featurize(imgs)
    nb = -(-n // FUSED_ROW_BATCH)
    imgs = jnp.pad(imgs, ((0, nb * FUSED_ROW_BATCH - n),)
                   + ((0, 0),) * (imgs.ndim - 1))

    one = jax.eval_shape(featurize, imgs[:FUSED_ROW_BATCH])

    def write(i, out):
        made = featurize(jax.lax.dynamic_slice_in_dim(
            imgs, i * FUSED_ROW_BATCH, FUSED_ROW_BATCH))
        return jax.lax.dynamic_update_slice_in_dim(
            out, made, i * FUSED_ROW_BATCH, axis=1)

    return jax.lax.fori_loop(0, nb, write, jnp.zeros(
        (one.shape[0], nb * FUSED_ROW_BATCH, one.shape[2]), one.dtype))


@functools.lru_cache(maxsize=None)
def _fused_rows_program(mesh, statics):
    """Jitted fused featurization of a row-sharded image batch, one
    program per (mesh, kernel config). ``pallas_call`` has no
    partitioning rule, so the kernel runs under ``shard_map``: every
    device featurizes its own rows. Filters and whitener means ride as
    arguments, so a refit reuses the compiled program."""
    from jax.sharding import PartitionSpec as P

    from ...observability.compilelog import watch_jit
    from ...ops.pallas_kernels import fused_cifar_featurize_banks
    from ...parallel.mesh import DATA_AXIS

    def local(imgs, filters, means):
        # the banks kernel with a group of one
        return fused_cifar_featurize_banks(
            imgs, filters[None], *statics, whitener_means=means[None])[0]

    rows = P(DATA_AXIS)
    return watch_jit(jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(rows, P(), P()), out_specs=rows,
        check_vma=False)), name="fused_featurize_rows")


class FusedConvRectifyPool(Transformer):
    """Fused Convolver >> SymmetricRectifier >> Pooler(sum) >> vectorize
    as one Pallas TPU kernel
    (``ops/pallas_kernels.fused_cifar_featurize_banks``):
    the conv/rectifier intermediates never leave VMEM, which roughly
    doubles featurization throughput on the north-star CIFAR benchmark.
    Falls back to the composed XLA ops off-TPU. Same contract as
    Convolver: ``filters`` arrive pre-whitened by the caller
    (filters_normalized @ whitener.T); the whitener contributes only its
    means, subtracted post-normalization. An item is an ``(H, W, C)``
    image or its row-major vector."""

    def __init__(self, filters, img_size: int, patch_size: int,
                 channels: int = 3, pool_stride: int = 13,
                 pool_size: int = 14, alpha: float = 0.25,
                 whitener=None, var_constant: float = 10.0):
        import numpy as _np

        self.filters = _np.asarray(filters, _np.float32)
        self.whitener_means = None
        if whitener is not None:
            self.whitener_means = _np.asarray(whitener.means, _np.float32)
        self.img_size = img_size
        self.patch_size = patch_size
        self.channels = channels
        self.pool_stride = pool_stride
        self.pool_size = pool_size
        self.alpha = alpha
        self.var_constant = var_constant

    def eq_key(self):
        return (FusedConvRectifyPool, self.filters.tobytes(),
                self.filters.shape, self.img_size, self.patch_size,
                self.channels, self.pool_stride, self.pool_size,
                self.alpha, self.var_constant,
                None if self.whitener_means is None
                else self.whitener_means.tobytes())

    def _kernel_statics(self):
        return (self.img_size, self.patch_size, self.channels,
                self.pool_stride, self.pool_size, self.var_constant,
                self.alpha)

    def _fused_batch(self, imgs, mesh):
        filters, means = self.apply_params()
        if means is None:  # zero means: the kernel's bias term is 0
            means = jnp.zeros((self.filters.shape[1],), jnp.float32)
        program = _fused_rows_program(mesh, self._kernel_statics())
        return program(imgs, filters, means)

    def _image(self, img):
        """An item as an image: it may come as its row-major vector
        (a dataset of vectors is laid out in rows on a TPU, one of
        4-D images batch-minor, and a program that takes a few rows of
        such a dataset first copies all of it into another layout:
        3.5 GB for 500,000 crops of 24 x 24 x 3)."""
        return img.reshape(self.img_size, self.img_size, self.channels)

    def apply(self, img):
        # single-item / off-TPU path: the composed ops
        from ...ops.image_ops import filter_bank_convolve, pool_image

        conv = filter_bank_convolve(
            self._image(img), jnp.asarray(self.filters), self.patch_size,
            self.channels,
            True,
            None if self.whitener_means is None
            else jnp.asarray(self.whitener_means),
            self.var_constant)
        pos = jnp.maximum(0.0, conv - self.alpha)
        neg = jnp.maximum(0.0, -conv - self.alpha)
        pooled = pool_image(
            jnp.concatenate([pos, neg], -1), self.pool_stride,
            self.pool_size, "identity", "sum")
        return pooled.reshape(-1)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset) and self._kernel_fits(
                1, self.filters.shape[0]):
            return ds.map_batch(
                lambda imgs: self._fused_batch(imgs, ds.mesh))
        return super().apply_dataset(ds)

    # fitted-param protocol (off-TPU composed path; the Pallas batch
    # path already takes filters as arguments): the fitted whitened
    # filter bank rides as a runtime argument so refits never recompile
    def apply_params(self):
        params = self.__dict__.get("_jit_conv_params")
        if params is None:
            means = (None if self.whitener_means is None
                     else jnp.asarray(self.whitener_means))
            params = (jnp.asarray(self.filters), means)
            self.__dict__["_jit_conv_params"] = params
        return params

    def apply_with_params(self, params, img):
        from ...ops.image_ops import filter_bank_convolve, pool_image

        filters, means = params
        conv = filter_bank_convolve(
            self._image(img), filters, self.patch_size, self.channels, True,
            means, self.var_constant)
        pos = jnp.maximum(0.0, conv - self.alpha)
        neg = jnp.maximum(0.0, -conv - self.alpha)
        pooled = pool_image(
            jnp.concatenate([pos, neg], -1), self.pool_stride,
            self.pool_size, "identity", "sum")
        return pooled.reshape(-1)

    def make_blocks_with_params(self, params, imgs):
        """``apply_with_params`` for whole sets of rows and ``g`` filter
        banks inside a larger program: what the streamed block solve
        makes its blocks with (``nodes/learning/linear.py``
        ``_block_maker``; may be called on an array-free shim).
        ``params`` are stacked ``(g, K, F)`` filters and ``(g, F)``
        means: ``(g, at least the images' rows, width)``; rows past the
        images' are padding. The same choice as ``apply_dataset``: on a
        TPU the Pallas kernel, which reads the images and writes the
        blocks as the caller keeps them (its patches are built in VMEM,
        once an image for the ``g`` banks), unless its patches do not
        fit VMEM at this geometry; else the composed ops,
        ``FUSED_ROW_BATCH`` rows at a time. Which one a trace took is
        counted (``featurize.conv_block.pallas`` / ``.xla``; with the
        kernel, ``featurize.conv_patches.vmem`` and, by the patch
        positions an image that the kernel lays out and that it never
        builds because no region pools them,
        ``featurize.conv_positions.kept`` / ``.left_out``), and the ops
        carry the scope ``conv_rectify_pool`` in the program's HLO
        metadata. The products run at the featurizer's own precision
        (the default: one bfloat16 pass, float32 accumulation), as in
        ``apply_dataset``, whatever the solver around them multiplies
        at: both forms of one graph then make the same columns."""
        from ...observability.metrics import MetricsRegistry
        from ...ops import pallas_kernels

        filters, means = params
        pallas = self._kernel_fits(*filters.shape[:2])
        counter = MetricsRegistry.get_or_create().counter
        counter("featurize.conv_block." + ("pallas" if pallas else "xla")).inc()
        with jax.named_scope("conv_rectify_pool"), \
                jax.default_matmul_precision("default"):
            if pallas:
                counter("featurize.conv_patches.vmem").inc()
                kept, left_out = pallas_kernels.fused_positions_kept(
                    self.img_size, self.patch_size, self.pool_stride,
                    self.pool_size)
                counter("featurize.conv_positions.kept").inc(kept)
                counter("featurize.conv_positions.left_out").inc(left_out)
                return pallas_kernels.fused_cifar_featurize_banks(
                    imgs, filters, *self._kernel_statics(),
                    whitener_means=means)
            return _banks_in_row_batches(lambda batch: jnp.stack([
                jax.vmap(lambda img, j=j: self.apply_with_params(
                    (filters[j], None if means is None else means[j]),
                    img))(batch)
                for j in range(filters.shape[0])]), imgs)

    def _kernel_fits(self, banks: int, filters: int) -> bool:
        """Whether a batch of images goes through the Pallas kernel:
        on a TPU, where the patches of a step's images fit VMEM beside
        ``banks`` banks of ``filters`` filters."""
        from ...ops import pallas_kernels

        return pallas_kernels.use_pallas() and (
            pallas_kernels.fused_featurize_fits_vmem(
                self.img_size, self.patch_size, self.channels,
                self.pool_stride, self.pool_size, filters, banks))

    def blocks_a_call(self, rows: int, params) -> int:
        """How many of the stacked ``params``' blocks to make a call,
        from the shapes alone: the patches and their statistics depend
        on the images alone and the kernel makes them once a call, so
        one call convolves as many filter banks as keep its blocks under
        ``BANKS_A_CALL_BYTES`` (a divisor of their number: the scan over
        groups has no ragged end)."""
        blocks, filters = params[0].shape[:2]
        fit = BANKS_A_CALL_BYTES // max(
            4 * rows * filters * self.columns_a_filter(), 1)
        return max([g for g in range(1, blocks + 1)
                    if blocks % g == 0 and g <= fit] or [1])

    def columns_a_filter(self) -> int:
        pools = len(range(self.pool_size // 2,
                          self.img_size - self.patch_size + 1,
                          self.pool_stride))
        return pools * pools * 2   # column = (pool, half, filter)

    def widened_like(self, other: "FusedConvRectifyPool"):
        """This featurizer with ``other``'s number of filters, the new
        ones all zero: ``(node, real, width)`` where the node's output
        of ``width`` columns holds this one's columns, in order, at
        ``real`` and exact zeros elsewhere (a zero filter convolves to
        0, which the rectifier at ``alpha >= 0`` keeps at 0). None where
        the two differ in more than the number of filters, or this one
        has more."""
        k, k_wide = self.filters.shape[0], other.filters.shape[0]
        if (type(other) is not type(self) or k > k_wide or self.alpha < 0
                or self._with_filters(other.filters).struct_key()
                != other.struct_key()):
            return None
        wide = self._with_filters(np.concatenate([
            self.filters, np.zeros((k_wide - k,) + self.filters.shape[1:],
                                   np.float32)]))
        groups = self.columns_a_filter()
        real = (np.arange(groups)[:, None] * k_wide
                + np.arange(k)[None, :]).reshape(-1)
        return wide, real, groups * k_wide

    def _with_filters(self, filters):
        node = object.__new__(type(self))
        node.__dict__.update({
            key: v for key, v in self.__dict__.items()
            if not key.startswith("_jit_") and key != "_eq_key_val"})
        node.filters = filters
        return node

    def struct_key(self):
        return (FusedConvRectifyPool, self.filters.shape, self.img_size,
                self.patch_size, self.channels, self.pool_stride,
                self.pool_size, self.alpha, self.var_constant,
                self.whitener_means is None)
