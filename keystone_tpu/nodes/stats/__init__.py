"""Statistical feature nodes.

TPU-native re-designs of the reference's ``nodes/stats`` package
(SURVEY.md section 2.7). Every node's per-item ``apply`` is jax-traceable,
so batch execution is a single fused XLA program over the sharded batch.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...observability.metrics import MetricsRegistry
from ...observability.timeline import flight_span
from ...parallel.dataset import ArrayDataset, Dataset
from ...workflow.estimator import Estimator
from ...workflow.transformer import Transformer

EPS = 2.2e-16  # matches the reference's varConstant floor usage


class RandomSignNode(Transformer):
    """Elementwise multiply by a fixed +-1 vector
    (reference ``stats/RandomSignNode.scala:11-23``)."""

    def __init__(self, signs: np.ndarray):
        self.signs = np.asarray(signs, dtype=np.float32)

    @staticmethod
    def create(size: int, seed: int = 0) -> "RandomSignNode":
        rng = np.random.RandomState(seed)
        return RandomSignNode(2.0 * rng.randint(0, 2, size=size) - 1.0)

    def apply(self, x):
        return x * self.signs


#: Largest padded length whose half-spectrum PaddedFFT takes as one dense
#: product; above it the FFT. The product grows as P^2, the FFT as P log P.
#: Device ns a transform on a TPU v5e, dense at Precision.HIGHEST against
#: XLA's FFT (tools/probe_padded_fft.py, PR 25, n about 0.75 P): P = 1,024:
#: 28.5 against 126.4; 4,096: 397 against 506; 8,192: 1,544 against 1,181;
#: 16,384: 6,195 against 2,298. At 4,096 the product still wins up to
#: n = 3,800 and loses 7% at n = 4,096.
DENSE_MAX_PADDED = 4096


@functools.lru_cache(maxsize=8)
def _cosine_table(n: int, padded: int, dtype: str) -> np.ndarray:
    """``C[j, k] = cos(2 pi j k / padded)`` for ``j < n``, ``k < padded / 2``:
    ``x @ C`` is the real part of the first half of the DFT of ``x``
    zero-padded to ``padded``. Computed in float64 with ``j k`` reduced
    modulo ``padded`` as integers before the division, so the angle's
    error does not grow with ``j k``; read-only, because every caller
    shares it."""
    jk = np.outer(np.arange(n, dtype=np.int64),
                  np.arange(padded // 2, dtype=np.int64)) % padded
    table = np.cos(2.0 * np.pi * jk / padded).astype(dtype)
    table.setflags(write=False)
    return table


class PaddedFFT(Transformer):
    """Zero-pad to the next power of two, FFT, keep the real part of the
    first half (reference ``stats/PaddedFFT.scala:13-20``).

    Up to ``DENSE_MAX_PADDED`` that half-spectrum is one product with a
    cosine table at float32 precision, which the MXU runs several times
    faster than a complex FFT of which three quarters is thrown away;
    longer vectors take the FFT. The choice is static (the input's
    length), so it is made, and counted in ``featurize.padded_fft.dense``
    / ``.fft``, when ``apply`` is traced."""

    def apply(self, x):
        n = x.shape[-1]
        padded = 1 << (n - 1).bit_length()
        registry = MetricsRegistry.get_or_create()
        if padded <= DENSE_MAX_PADDED:
            registry.counter("featurize.padded_fft.dense").inc(1)
            table = _cosine_table(
                n, padded, jnp.result_type(x.dtype, jnp.float32).name)
            return jnp.dot(
                x, table, precision=jax.lax.Precision.HIGHEST
            ).astype(x.dtype)
        registry.counter("featurize.padded_fft.fft").inc(1)
        xp = jnp.concatenate(
            [x, jnp.zeros((padded - n,), x.dtype)], axis=-1
        )
        return jnp.real(jnp.fft.fft(xp))[: padded // 2].astype(x.dtype)


class LinearRectifier(Transformer):
    """f(x) = max(max_val, x - alpha)
    (reference ``stats/LinearRectifier.scala:12-17``)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = float(max_val)
        self.alpha = float(alpha)

    def apply(self, x):
        return jnp.maximum(self.max_val, x - self.alpha)


class NormalizeRows(Transformer):
    """L2-normalize each vector, flooring the norm at machine epsilon
    (reference ``stats/NormalizeRows.scala:8-14``)."""

    def apply(self, x):
        norm = jnp.maximum(jnp.linalg.norm(x), EPS)
        return x / norm


class SignedHellingerMapper(Transformer):
    """sign(x) * sqrt(|x|) (reference ``stats/SignedHellingerMapper.scala``)."""

    def apply(self, x):
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


class BatchSignedHellingerMapper(Transformer):
    """Matrix-input variant (applied to per-image descriptor matrices)."""

    def apply(self, x):
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


def _draw_cosine_features(num_input_features: int, num_output_features: int,
                          gamma: float, w_dist: str, b_dist: str, seed: int):
    """``(W, b)`` of one ``CosineRandomFeatures.create``, drawn anew on
    every call (1.8 million normals at 4,096 x 440, 70 ms)."""
    rng = np.random.RandomState(seed)
    if w_dist == "gaussian":
        W = rng.randn(num_output_features, num_input_features)
    elif w_dist == "cauchy":
        W = rng.standard_cauchy((num_output_features, num_input_features))
    elif w_dist == "uniform":
        W = rng.rand(num_output_features, num_input_features)
    else:
        raise ValueError(w_dist)
    W = (W * gamma).astype(np.float32)
    if b_dist == "uniform":
        b = rng.rand(num_output_features) * 2 * np.pi
    elif b_dist == "gaussian":
        b = rng.randn(num_output_features) * 2 * np.pi
    else:
        raise ValueError(b_dist)
    b = b.astype(np.float32)
    # the recipe is their identity (``eq_key``): keep them as drawn
    W.flags.writeable = b.flags.writeable = False
    return W, b


class CosineRandomFeatures(Transformer):
    """Random Fourier features cos(x W^T + b)
    (reference ``stats/CosineRandomFeatures.scala:19-60``).

    ``W`` and ``b`` are program ARGUMENTS (the fitted-param protocol),
    not constants of the HLO: one compiled program serves every instance
    of a shape, whatever its seed, and fifty 4,096 x 440 branches are
    not 344 MiB of constants in every program that holds them. The
    product runs at the solver's precision: the argument of a cosine is
    of the order of a radian, and a one-pass bfloat16 product of 440
    terms is 1e-2 of a feature."""

    def __init__(self, W: np.ndarray, b: np.ndarray, recipe=None):
        self.W = np.asarray(W, dtype=np.float32)  # (out, in)
        self.b = np.asarray(b, dtype=np.float32)  # (out,)
        assert self.b.shape[0] == self.W.shape[0]
        #: what ``create`` drew them from: a cheap content identity
        self.recipe = recipe

    @staticmethod
    def create(
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        w_dist: str = "gaussian",
        b_dist: str = "uniform",
        seed: int = 0,
    ) -> "CosineRandomFeatures":
        return CosineRandomFeatures.create_branches(
            1, num_input_features, num_output_features, gamma, w_dist,
            b_dist, seed)[0]

    @staticmethod
    def create_branches(
        count: int,
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        w_dist: str = "gaussian",
        b_dist: str = "uniform",
        seed: int = 0,
    ) -> "list[CosineRandomFeatures]":
        """``count`` featurizers, branch ``i`` as ``create(..., seed=seed
        + i)`` draws it, drawn side by side on threads: ``RandomState``
        fills an array with the GIL released, and fifty 4,096 x 440
        branches are 3.6 s of one core that every pipeline built from
        them pays before the device has anything to do."""
        recipes = [(int(num_input_features), int(num_output_features),
                    float(gamma), w_dist, b_dist, int(seed) + i)
                   for i in range(count)]
        with flight_span("draw", "featurize", branches=count,
                         width=int(num_output_features)):
            workers = max(1, min(count, os.cpu_count() or 1))
            with ThreadPoolExecutor(workers) as pool:
                drawn = list(pool.map(
                    lambda r: _draw_cosine_features(*r), recipes))
        return [CosineRandomFeatures(W, b, recipe=r)
                for (W, b), r in zip(drawn, recipes)]

    def eq_key(self):
        # content identity (CSE, the prefix-state table): two seeds are
        # two nodes. The recipe says all there is to say about arrays
        # drawn from it, without hashing 7 MB a branch
        if self.recipe is not None:
            return (CosineRandomFeatures, "recipe", self.recipe)
        return (CosineRandomFeatures, "arrays",
                self.W.shape, self.W.tobytes(), self.b.tobytes())

    def apply(self, x):
        return self.apply_with_params((self.W, self.b), x)

    # fitted-param protocol: W and b ride as jit arguments
    def apply_params(self):
        params = self.__dict__.get("_jit_cos_params")
        if params is None:
            params = (jnp.asarray(self.W), jnp.asarray(self.b))
            self.__dict__["_jit_cos_params"] = params  # _jit_*: unpickled
        return params

    def apply_with_params(self, params, x):
        from ...ops.linalg import SOLVER_PRECISION

        W, b = params
        return jnp.cos(jnp.matmul(x, W.T, precision=SOLVER_PRECISION) + b)

    def struct_key(self):
        return (CosineRandomFeatures, "cos", self.W.shape)


@jax.jit
def _center_scale_batch(X, mean, inv_std):
    """Whole-batch scaler apply with params as ARGUMENTS (not baked HLO
    constants): one compiled program serves every fitted scaler, so
    refitting on new data never recompiles (see
    ``nodes/learning/linear._affine_apply_batch`` for the rationale)."""
    return (X - mean) * inv_std


class StandardScalerModel(Transformer):
    """(x - mean) [/ std] (reference ``stats/StandardScaler.scala:16-31``)."""

    def __init__(self, mean: np.ndarray, std: Optional[np.ndarray] = None):
        self.mean = np.asarray(mean)
        self.std = None if std is None else np.asarray(std)

    def apply(self, x):
        out = x - self.mean
        if self.std is not None:
            out = out / self.std
        return out

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset):
            m, inv = self.apply_params()
            return ds.map_batch(lambda X: _center_scale_batch(X, m, inv))
        return super().apply_dataset(ds)

    # fitted-param protocol: fused chains thread these as jit arguments
    fusion_safe = True

    def apply_params(self):
        params = self.__dict__.get("_jit_scale_params")
        if params is None:
            mean = jnp.asarray(self.mean, jnp.float32)
            inv = (jnp.ones_like(mean) if self.std is None
                   else jnp.asarray(1.0 / self.std, jnp.float32))
            params = (mean, inv)
            self.__dict__["_jit_scale_params"] = params  # _jit_*: unpickled
        return params

    def apply_with_params(self, params, x):
        mean, inv = params
        return (x - mean) * inv

    def struct_key(self):
        return (StandardScalerModel, "center_scale")


class StandardScaler(Estimator):
    """Fit column means (and optionally stds) over the dataset.

    The reference aggregates a MultivariateOnlineSummarizer via
    treeAggregate (``stats/StandardScaler.scala:44-58``); here the moments
    are two all-reduced column sums over the sharded batch. Degenerate
    stds (NaN/inf/<eps) are replaced by 1.0, as in the reference.
    """

    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-12):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import identity_fit

        return identity_fit(dep_specs)

    # -- static HBM planning (analysis.resources) --------------------------
    def carry_nbytes(self, dep_specs):
        from ...analysis.resources import moments_carry_nbytes

        return moments_carry_nbytes(dep_specs)

    def fitted_nbytes(self, dep_specs):
        from ...analysis.resources import moments_carry_nbytes

        # fitted model = mean + std, same footprint as the moment carry
        return moments_carry_nbytes(dep_specs)

    def _fit(self, ds: Dataset) -> StandardScalerModel:
        assert isinstance(ds, ArrayDataset), "StandardScaler needs array data"
        s, sq = _moments(ds.data)
        return self.finalize((s, sq, ds.n))

    # -- streaming fit (accumulate/finalize protocol) ----------------------
    def accumulate(self, carry, chunk):
        """Fold one chunk's column sums / sums-of-squares into the carry
        (padded rows are zero, so the moments stay exact); the resident
        ``_fit`` is the one-chunk special case of this."""
        assert isinstance(chunk, ArrayDataset), \
            "StandardScaler streams over array chunks"
        if carry is None:
            # replicated zero init + the SAME update program as every
            # later chunk: seeding from _moments(chunk.data) handed
            # chunk 2 a differently-SHARDED carry, so _accum_moments
            # compiled twice per fit (jax's cache keys on input
            # shardings) — flagged by the PR 9 fit fence, same fix as
            # the least-squares Gram carry
            from ...parallel.mesh import replicated_zeros

            d = chunk.data.shape[1]
            carry = tuple(replicated_zeros(
                chunk.mesh, ((d,), (d,)))) + (0,)
        S, SQ, n = carry
        S, SQ = _accum_moments(S, SQ, chunk.data)
        return (S, SQ, n + chunk.n)

    def finalize(self, carry) -> StandardScalerModel:
        s, sq, n = carry
        mean = np.asarray(s, dtype=np.float64) / n
        if not self.normalize_std_dev:
            return StandardScalerModel(mean.astype(np.float32))
        # unbiased sample variance, matching MultivariateOnlineSummarizer
        var = (np.asarray(sq, dtype=np.float64) - n * mean * mean) / max(n - 1, 1)
        std = np.sqrt(np.maximum(var, 0.0))
        bad = ~np.isfinite(std) | (np.abs(std) < self.eps)
        std = np.where(bad, 1.0, std)
        return StandardScalerModel(
            mean.astype(np.float32), std.astype(np.float32)
        )


@jax.jit
def _moments(X):
    # promote INTEGER chunks to f32 (a uint8-wire chunk fed straight to
    # the scaler must not wrap its X*X mod 256); float inputs keep
    # their width — f64 moments stay f64 under jax_enable_x64
    if not jnp.issubdtype(X.dtype, jnp.floating):
        X = X.astype(jnp.float32)
    return jnp.sum(X, axis=0), jnp.sum(X * X, axis=0)


def _accum_moments_impl(S, SQ, X):
    if not jnp.issubdtype(X.dtype, jnp.floating):
        X = X.astype(jnp.float32)
    return S + jnp.sum(X, axis=0), SQ + jnp.sum(X * X, axis=0)


from ...utils.donation import donating_jit  # noqa: E402


def _moments_probe(d: int = 8, n: int = 16):
    S, f32 = jax.ShapeDtypeStruct, np.float32
    return ((S((d,), f32), S((d,), f32), S((n, d), f32)), {})


#: the streamed moment carry donates (S, SQ): the per-chunk update
#: writes into the old moment buffers instead of reallocating them —
#: same in-place discipline as the least-squares Gram carry
#: (``nodes.learning.linear._gram_carry_update``). The probe keeps the
#: donation shape-compatible under the static gate (tools/lint.py).
_accum_moments = donating_jit(_accum_moments_impl, donate_argnums=(0, 1),
                              probe=_moments_probe)


from ...workflow.transformer import HostTransformer  # noqa: E402


class TermFrequency(HostTransformer):
    """Seq of terms -> seq of (unique term, weighting(count)) pairs
    (reference ``stats/TermFrequency.scala:20-22``). A host-stage node;
    output order is first appearance, deterministically.
    """

    def __init__(self, fun=None):
        self.fun = fun or (lambda x: x)

    def eq_key(self):
        return (TermFrequency, self.fun)

    def apply(self, terms):
        counts = {}
        order = []
        for t in terms:
            key = tuple(t) if isinstance(t, list) else t
            if key not in counts:
                counts[key] = 0
                order.append(key)
            counts[key] += 1
        return [(k, float(self.fun(counts[k]))) for k in order]

