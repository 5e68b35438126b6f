"""Per-stage profile of the ImageNet SIFT/LCS/FV featurization path
(VERDICT r4 next#1: "publish a per-stage profile, then attack the
dominant stage").

Method: the stages run fused inside one jit in production, so timing
them one jit per stage would charge each stage a dispatch round trip
(1.5 ms on this installation) and lose the fusion. Instead this times CUMULATIVE PREFIXES of the pipeline
(smooth; +orient; +sample; +norm; +PCA; +FV), each as one jitted
program over the same image batch, and reports adjacent differences —
the floor and the shared input staging cancel.

Stages (per scale s: bin = bin_size + 2s, step = step + s*scale_step),
as implemented by the band-matmul kernel in ``keystone_tpu/ops/sift.py``:
  smooth    Gaussian blur as band matmuls          (MXU)
  orient    gradient -> 8 soft-assigned magnitude maps
  sample    triangle binning + frac shift + strided sampling,
            folded into T_y @ omaps @ T_x^T        (MXU)
  norm      L2-clamp-renorm-quantize in the binned layout
  pca       signed Hellinger + 64x128 projection
  fv        GMM posteriors + s0/s1/s2 moments -> 2048-dim FV

Host-side work (tar decode, grayscale) is not profiled here; LCS is
timed whole (it is one box-filter program).

Usage: python tools/profile_imagenet.py [--small] [--images N]
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from keystone_tpu.ops import sift as S  # noqa: E402

SMALL = "--small" in sys.argv
N_IMGS = int(sys.argv[sys.argv.index("--images") + 1]) \
    if "--images" in sys.argv else (4 if SMALL else 16)
H, W = (160, 160) if SMALL else (480, 640)
STEP, BIN, NSCALES, SSTEP = 4, 6, 5, 1
DESC_DIM, VOCAB = 64, 16


from tools._bench import fence, timeit  # noqa: E402


def scale_plan():
    out = []
    for sc in range(NSCALES):
        st, bs, lo = S._scale_params(sc, STEP, BIN, NSCALES, SSTEP)
        out.append((st, bs, lo))
    return out


def prefix_fn(depth, pca=None, gmm=None):
    """Build the featurizer truncated after `depth` stages (1=smooth ...
    7=fv). Returns a per-image function for vmap."""
    plan = scale_plan()

    def one(img):
        per_scale = []
        for st, bs, lo in plan:
            Gy = jnp.asarray(S._smooth_band(H, bs))
            Gx = jnp.asarray(S._smooth_band(W, bs))
            sm = jnp.einsum("ih,hw,jw->ij", Gy, img, Gx, precision=S._PRECISION)
            if depth == 1:
                per_scale.append(jnp.sum(sm))
                continue
            om = S._orientation_maps(sm)
            if depth == 2:
                per_scale.append(jnp.sum(om))
                continue
            Ty, ny = S._sampling_operator(H, lo, st, bs)
            Tx, nx = S._sampling_operator(W, lo, st, bs)
            bins = jnp.einsum("ph,ohw,qw->opq", jnp.asarray(Ty), om,
                              jnp.asarray(Tx), precision=S._PRECISION)
            if depth == 3:
                per_scale.append(jnp.sum(bins))
                continue
            per_scale.append(S._normalize_quantize_binned(
                bins.reshape(S.NBO, S.NBP, ny, S.NBP, nx)))
        if depth <= 3:
            return jnp.stack(per_scale).sum()
        desc = jnp.concatenate(per_scale, axis=1)     # (128, N)
        if depth == 4:
            return desc
        desc = jnp.sign(desc) * jnp.sqrt(jnp.abs(desc))
        proj = pca @ desc                             # (64, N)
        if depth == 5:
            return proj
        from keystone_tpu.nodes.images.fisher_vector import _fisher_vector
        out = _fisher_vector(proj, *gmm, 1e-2).reshape(-1)
        out = out / jnp.maximum(jnp.linalg.norm(out), 2.2e-16)
        out = jnp.sign(out) * jnp.sqrt(jnp.abs(out))
        return out / jnp.maximum(jnp.linalg.norm(out), 2.2e-16)

    return one


def main():
    from keystone_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"device: {jax.devices()[0].device_kind}; batch {N_IMGS} "
          f"{H}x{W}, step {STEP} bin {BIN} scales {NSCALES}(+{SSTEP})",
          flush=True)
    rng = np.random.RandomState(0)
    imgs = jax.device_put(rng.rand(N_IMGS, H, W).astype(np.float32))
    fence(imgs)
    pca = jax.device_put(rng.randn(DESC_DIM, 128).astype(np.float32) / 11.3)
    gmm = tuple(jax.device_put(a) for a in (
        rng.randn(DESC_DIM, VOCAB).astype(np.float32),
        (0.5 + rng.rand(DESC_DIM, VOCAB)).astype(np.float32),
        (np.ones(VOCAB) / VOCAB).astype(np.float32)))

    names = ["smooth", "orient", "sample", "norm", "pca", "fv"]
    cum = []
    for depth in range(1, 7):
        fn = jax.jit(jax.vmap(prefix_fn(depth, pca, gmm)))
        dt = timeit(fn, imgs)
        cum.append(dt)
        stage_ms = 1e3 * (dt - (cum[-2] if len(cum) > 1 else 0.0))
        print(f"  prefix {depth} (+{names[depth-1]:9s}): "
              f"{1e3 * dt:8.1f} ms cum  | +{stage_ms:7.1f} ms", flush=True)

    total = cum[-1]
    print(f"full featurize: {1e3 * total / N_IMGS:.2f} ms/img "
          f"= {N_IMGS / total:.1f} img/s/chip", flush=True)

    # batch-64 measurement (VERDICT r5 item 3): the bigger vmap batch
    # amortizes per-dispatch overhead ~+10% — worth taking only when the
    # host can feed it (the streaming prefetcher's job); here the delta
    # itself is recorded.
    # Skipped in --small (tiny shapes make the comparison meaningless).
    if not SMALL and N_IMGS != 64:
        imgs64 = jax.device_put(rng.rand(64, H, W).astype(np.float32))
        fence(imgs64)
        fn64 = jax.jit(jax.vmap(prefix_fn(6, pca, gmm)))
        dt64 = timeit(fn64, imgs64)
        print(f"batch 64: {1e3 * dt64 / 64:.2f} ms/img "
              f"= {64 / dt64:.1f} img/s/chip "
              f"({100.0 * (64 / dt64) / (N_IMGS / total) - 100.0:+.1f}% "
              f"vs batch {N_IMGS})", flush=True)

    # LCS branch, timed whole
    from keystone_tpu.nodes.images.extractors import LCSExtractor
    lcs = LCSExtractor()
    imgs_rgb = jax.device_put(
        rng.rand(N_IMGS, H, W, 3).astype(np.float32))
    fence(imgs_rgb)
    lcs_fn = jax.jit(jax.vmap(lcs.apply))
    dt = timeit(lcs_fn, imgs_rgb)
    print(f"LCS whole: {1e3 * dt / N_IMGS:.2f} ms/img "
          f"= {N_IMGS / dt:.1f} img/s/chip", flush=True)

    # parity: prefix-6 must match the production featurizer
    from keystone_tpu.nodes.images.extractors import SIFTExtractor
    from keystone_tpu.nodes.images.fisher_vector import _fisher_vector
    sx = SIFTExtractor(step=STEP, bin_size=BIN, num_scales=NSCALES,
                       scale_step=SSTEP)

    def prod(img):
        d = sx.apply(img)
        d = jnp.sign(d) * jnp.sqrt(jnp.abs(d))
        p = pca @ d
        out = _fisher_vector(p, *gmm, 1e-2).reshape(-1)
        out = out / jnp.maximum(jnp.linalg.norm(out), 2.2e-16)
        out = jnp.sign(out) * jnp.sqrt(jnp.abs(out))
        return out / jnp.maximum(jnp.linalg.norm(out), 2.2e-16)

    a = np.asarray(jax.jit(jax.vmap(prefix_fn(6, pca, gmm)))(imgs[:2]))
    b = np.asarray(jax.jit(jax.vmap(prod))(imgs[:2]))
    err = float(np.max(np.abs(a - b)))
    print(f"parity prefix-6 vs production: max abs delta {err:.2e}",
          flush=True)
    assert err < 1e-4, err

    # Device-mode precision parity gate (ADVICE medium#2): the shipped
    # Precision.HIGH band matmuls must keep quantized descriptors within
    # the golden test's envelope of a HIGHEST (6-pass, ~f32) reference —
    # the same bound test_dense_sift_descriptor_golden_gantrycrane pins
    # against VLFeat (diff.max <= 2 quantization levels, mean <= 0.15).
    # On CPU the flag is a no-op (exact equality); on TPU this is the
    # automated check that bf16 drift cannot ship unnoticed.
    def sift_at(precision):
        return jax.jit(jax.vmap(
            lambda g: S.dense_sift(g, STEP, BIN, NSCALES, SSTEP,
                                   precision=precision)))(imgs[:2])

    hi = np.asarray(sift_at(jax.lax.Precision.HIGH))
    ref = np.asarray(sift_at(jax.lax.Precision.HIGHEST))
    diff = np.abs(hi - ref)
    print(f"precision parity HIGH vs HIGHEST: max {diff.max():.3f} "
          f"mean {diff.mean():.4f} (envelope: max <= 2.0, mean <= 0.15)",
          flush=True)
    assert diff.max() <= 2.0, diff.max()
    assert diff.mean() <= 0.15, diff.mean()

    kernel_gates(imgs, gmm)


def kernel_gates(imgs, gmm):
    """PR 13 parity gates: every Pallas kernel must reproduce its
    einsum fallback inside its envelope ON THIS DEVICE, every profile —
    the banded SIFT against the descriptor golden envelope, the fused
    FV against a tight absolute bound, the quantized predict against
    argmax agreement + an error bound. On TPU the compiled kernels run;
    elsewhere the kernel bodies run on the interpreter over a cropped
    batch (interpret-mode at full VGA is minutes per image)."""
    from keystone_tpu.nodes.images.fisher_vector import _fisher_vector
    from keystone_tpu.ops.pallas_kernels import use_pallas

    on_tpu = use_pallas()
    banded_mode = "banded" if on_tpu else "banded_interpret"
    fv_mode = "pallas" if on_tpu else "pallas_interpret"

    # banded SIFT GEMM vs einsum: the golden envelope (quantized
    # descriptor levels), same bound as the precision gate above
    crop = imgs[:2] if on_tpu else imgs[:1, :96, :128]
    def sift_mode(mode):
        return jax.jit(jax.vmap(
            lambda g: S.dense_sift(g, STEP, BIN, NSCALES, SSTEP,
                                   kernel_mode=mode)))(crop)

    banded = np.asarray(sift_mode(banded_mode))
    ref = np.asarray(sift_mode("einsum"))
    diff = np.abs(banded - ref)
    print(f"banded-kernel parity vs einsum: max {diff.max():.3f} "
          f"mean {diff.mean():.4f} (envelope: max <= 2.0, mean <= 0.15)",
          flush=True)
    assert diff.max() <= 2.0, diff.max()
    assert diff.mean() <= 0.15, diff.mean()

    # fused GMM-posterior + FV kernel vs the split fallback
    rng = np.random.RandomState(7)
    proj = jnp.asarray(rng.randn(DESC_DIM, 2048).astype(np.float32))
    fused = np.asarray(_fisher_vector(proj, *gmm, 1e-2,
                                      kernel_mode=fv_mode))
    split = np.asarray(_fisher_vector(proj, *gmm, 1e-2,
                                      kernel_mode="einsum"))
    err = np.abs(fused - split)
    print(f"fused-FV parity vs fallback: max {err.max():.2e} "
          f"mean {err.mean():.2e} (envelope: max <= 1e-3)", flush=True)
    assert err.max() <= 1e-3, err.max()

    # quantized predict: argmax agreement + error bound vs f32 apply
    # at the rehearsal solve shape (separable teacher labels — ties on
    # noise would measure argmax fragility, not quantization). The
    # quantized leg goes through apply_dataset — the PRODUCTION batch
    # dispatch, which is the path that actually reaches
    # quantized_affine_pallas on TPU (per-item apply is always the
    # dequantizing fallback).
    from keystone_tpu.nodes.learning.linear import LinearMapper
    from keystone_tpu.parallel.dataset import ArrayDataset

    n, d, k = 512, 1024, 100
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32) / np.sqrt(d)
    b = rng.randn(k).astype(np.float32) * 0.01
    ds = ArrayDataset.from_numpy(X)
    f32 = LinearMapper(W, intercept=b).apply_dataset(ds).numpy()
    for dtype, min_agree, max_rel in (("bf16", 0.999, 0.02),
                                      ("int8", 0.98, 0.03)):
        q = LinearMapper(W, intercept=b, weight_dtype=dtype)
        out = q.apply_dataset(ds).numpy()
        agree = float((f32.argmax(1) == out.argmax(1)).mean())
        rel = float(np.abs(out - f32).max() / np.abs(f32).max())
        # the per-item path must match the batched kernel path too
        item = np.asarray(q.apply(jnp.asarray(X[0])))
        item_delta = float(np.abs(item - out[0]).max())
        print(f"quantized predict {dtype} (apply_dataset dispatch): "
              f"argmax agreement {agree:.4f} (>= {min_agree}), max rel "
              f"err {rel:.4f} (<= {max_rel}), item-vs-batch "
              f"{item_delta:.2e}", flush=True)
        assert agree >= min_agree, (dtype, agree)
        assert rel <= max_rel, (dtype, rel)
        assert item_delta <= 1e-4, (dtype, item_delta)


if __name__ == "__main__":
    main()
