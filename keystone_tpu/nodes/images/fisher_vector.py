"""Fisher vector encoding (reference ``nodes/images/FisherVector.scala``
and the enceval JNI variant ``nodes/images/external/FisherVector.scala`` /
``cpp/EncEval.cxx``).

The FV of a descriptor matrix under a diagonal GMM, in the s0/s1/s2
moment form of the Sanchez et al. survey (``FisherVector.scala:33-52``):

    q  = GMM posteriors               (nDesc, K)
    s0 = mean(q)                      (K,)
    s1 = X q / nDesc                  (D, K)
    s2 = (X*X) q / nDesc              (D, K)
    fv1 = (s1 - means s0) / (sqrt(vars) sqrt(w))
    fv2 = (s2 - 2 means s1 + (means^2 - vars) s0) / (vars sqrt(2 w))

One jitted program: the q/s1/s2 GEMMs are the hot path and map straight
onto the MXU — this *is* the TPU-native "native" implementation, so the
reference's scala-vs-enceval split becomes jit-per-item vs batched-vmap.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...observability.metrics import MetricsRegistry
from ...observability.timeline import flight_span
from ...parallel.dataset import ArrayDataset, Dataset
from ...parallel.ragged import RaggedDataset
from ...workflow.estimator import Estimator
from ...workflow.optimizable import NodeChoice, OptimizableEstimator
from ...workflow.transformer import Transformer
from ..learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
    _PRECISION,
    _posteriors,
)


def fv_moments_split(X, means, variances, weights, *, threshold, mask=None,
                     precision=None):
    """The moment sums ``(sum q, X q, (X*X) q)`` as one posterior program
    and three products, the (nDesc, K) posteriors passing through HBM:
    the form of every platform ``ops.pallas_kernels.fv_moments_pallas``
    does not compile for, and of a GMM whose accumulators do not fit
    VMEM. The same arguments, the same sums."""
    q = _posteriors(X.T, means.T, variances.T, weights, threshold)
    if mask is not None:
        q = q * mask[:, None].astype(q.dtype)
    return (jnp.sum(q, axis=0),
            jnp.matmul(X, q, precision=precision),
            jnp.matmul(X * X, q, precision=precision))


def _fv_moment_sums(X, means, variances, weights, weight_threshold,
                    mask=None):
    """Raw posterior moment sums ``(sum q, X q, (X*X) q)`` of a
    (D, nDesc) descriptor matrix — the FV encoder's hot path. ``mask``
    ``(nDesc,)`` where ``X`` is padded with zero columns that are no
    descriptors: their posteriors count for nothing.

    The fused Pallas kernel on a TPU when its accumulators fit VMEM
    (posteriors computed tile-by-tile in VMEM, the (nDesc, K) posterior
    matrix never written to HBM), else :func:`fv_moments_split`. Which
    one a trace took is counted (``featurize.fv.pallas`` / ``.einsum``),
    and the ops of both stand under the scope ``fisher_vector``."""
    from ...ops import pallas_kernels

    fused = pallas_kernels.use_pallas() and pallas_kernels.fv_fits_vmem(
        *means.shape)
    MetricsRegistry.get_or_create().counter(
        "featurize.fv." + ("pallas" if fused else "einsum")).inc()
    moments = pallas_kernels.fv_moments_pallas if fused else fv_moments_split
    with jax.named_scope("fisher_vector"):
        return moments(X, means, variances, weights,
                       threshold=weight_threshold, mask=mask,
                       precision=_PRECISION)


def fisher_vector_of_sums(sums, n_desc, means, variances, weights):
    """The Fisher vector (D, 2K) of ``n_desc`` descriptors' moment sums,
    whichever form made them."""
    s0, s1, s2 = (s / n_desc for s in sums)       # (K,), (D, K), (D, K)
    sqrt_w = jnp.sqrt(weights)
    fv1 = (s1 - means * s0[None, :]) / (jnp.sqrt(variances) * sqrt_w[None, :])
    fv2 = (s2 - 2.0 * means * s1 + (means * means - variances) * s0[None, :]) \
        / (variances * jnp.sqrt(2.0 * weights)[None, :])
    return jnp.concatenate([fv1, fv2], axis=1)


def _fisher_vector_of(X, means, variances, weights, weight_threshold,
                      mask=None):
    """X is (D, nDesc); means/variances (D, K); weights (K,)."""
    n_desc = X.shape[1] if mask is None else jnp.maximum(
        jnp.sum(mask.astype(jnp.float32)), 1.0)
    return fisher_vector_of_sums(
        _fv_moment_sums(X, means, variances, weights, weight_threshold, mask),
        n_desc, means, variances, weights)


_fisher_vector = jax.jit(
    _fisher_vector_of, static_argnames=("weight_threshold",))


@functools.partial(jax.jit, static_argnames=("weight_threshold",))
def _fisher_vector_chunk(X, mask, means, variances, weights,
                         weight_threshold):
    """A chunk ``[b, D, nDesc]`` of descriptor matrices padded with zero
    columns, ``mask`` ``[b, nDesc]`` saying which columns are
    descriptors: ``[b, D, 2K]``, one matrix after another (the kernel's
    grid is a matrix's column tiles)."""
    return jax.lax.map(
        lambda xm: _fisher_vector_of(
            xm[0], means, variances, weights, weight_threshold, xm[1]),
        (X, mask))


class FisherVector(Transformer):
    """FV transformer: (D, nDesc) descriptor matrix -> (D, 2K) matrix
    (reference ``FisherVector.scala:22-54``)."""

    def __init__(self, gmm: GaussianMixtureModel):
        self.gmm = gmm
        # plain config copy: struct-keyed programs capture an array-free
        # shim (config_shim drops the nested gmm node), and
        # apply_with_params may only read config attributes
        self.weight_threshold = gmm.weight_threshold

    def eq_key(self):
        return (FisherVector, id(self.gmm))

    def apply(self, x):
        return self.apply_with_params(self.apply_params(), x)

    # fitted-param protocol (PERFORMANCE.md rule 6): a refitted GMM
    # codebook never recompiles the FV encoder
    def apply_params(self):
        params = self.__dict__.get("_jit_fv_params")
        if params is None:
            params = (jnp.asarray(self.gmm.means),
                      jnp.asarray(self.gmm.variances),
                      jnp.asarray(self.gmm.weights))
            self.__dict__["_jit_fv_params"] = params
        return params

    def apply_with_params(self, params, x):
        means, variances, weights = params
        return _fisher_vector(
            x.astype(jnp.float32), means, variances, weights,
            self.weight_threshold,
        )

    def struct_key(self):
        return (FisherVector, self.weight_threshold)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        """Descriptor matrices of different widths come in padded chunks
        with a mask; the encodings are of one shape and leave as an
        ``ArrayDataset`` in the dataset's order."""
        if not isinstance(ds, RaggedDataset):
            return super().apply_dataset(ds)
        params = self.apply_params()

        def encode(chunk):
            width = chunk.data.shape[-1]
            mask = (np.ones((len(chunk.ids), width), bool)
                    if chunk.mask is None else chunk.mask)
            return _fisher_vector_chunk(
                chunk.data.astype(jnp.float32), jnp.asarray(mask), *params,
                weight_threshold=self.weight_threshold)

        with flight_span("fisher", "featurize", images=len(ds)):
            out = ds.gather(encode)
        MetricsRegistry.get_or_create().counter(
            "featurize.fv.images").inc(len(ds))
        return out

    # -- static HBM planning (analysis.resources) --------------------------
    def resource_effect(self, dep_specs, out_spec, data_shards=1):
        """A pre-fitted FV node charges the same apply workspace the
        estimator's Delegate node would (fused-kernel accumulators or
        the fallback's posterior matrix)."""
        from ...analysis.resources import transform_workspace_effect

        return transform_workspace_effect(
            _fisher_apply_transient(self.gmm.k), dep_specs, out_spec,
            data_shards)


def _gmm_from_columns(ds: Dataset, k: int,
                      seed: Optional[int] = None) -> GaussianMixtureModel:
    """Fit the GMM treating every column of every item as a sample
    (reference ``ScalaGMMFisherVectorEstimator``,
    ``FisherVector.scala:67-73``). Items of one shape on the device stay
    there."""
    if isinstance(ds, ArrayDataset):
        x = ds.data[:ds.n]                        # (n, d, cols)
        cols = x.transpose(0, 2, 1).reshape(-1, x.shape[1])
    else:
        cols = np.concatenate(
            [np.asarray(m, np.float32).T for m in ds.collect()], axis=0)
    return GaussianMixtureModelEstimator(k, seed=seed or 0).fit_matrix(cols)


def _fisher_abstract_fit(k: int):
    """Fitted FV encoder spec: (D, nDesc) descriptor matrix -> (D, 2K)."""
    import jax

    from ...analysis.spec import Unknown

    def apply_element(element):
        if isinstance(element, jax.ShapeDtypeStruct) and len(
                element.shape) == 2:
            return jax.ShapeDtypeStruct(
                (int(element.shape[0]), 2 * k), np.float32)
        return Unknown("fisher-vector input not a (D, nDesc) matrix")

    return apply_element


def _fisher_fitted_nbytes(k: int, dep_specs):
    """Fitted GMM: means + covariances (D, K) f32 each + weights (K,),
    D from the input element's descriptor axis."""
    import jax

    element = getattr(dep_specs[0], "element", None) if dep_specs else None
    if not (isinstance(element, jax.ShapeDtypeStruct)
            and len(element.shape) == 2):
        return None
    d = float(element.shape[0])
    return 4.0 * (2.0 * d * k + k)


def _fisher_apply_transient(k: int):
    """Per-item apply workspace for the HBM planner: the fused-kernel
    moment accumulators when the Pallas dispatch will take them, else
    the (nDesc, K) posterior matrix the split fallback materializes
    (``analysis.resources.fv_apply_transient_nbytes`` mirrors the
    runtime dispatch)."""
    import jax

    from ...analysis.resources import fv_apply_transient_nbytes

    def workspace(element):
        if not (isinstance(element, jax.ShapeDtypeStruct)
                and len(element.shape) == 2):
            return None
        return fv_apply_transient_nbytes(
            int(element.shape[0]), k, int(element.shape[1]))

    return workspace


class ScalaGMMFisherVectorEstimator(Estimator):
    """Per-item-jit FV estimator (reference ``FisherVector.scala:67-73``;
    the name mirrors the reference's scala implementation)."""

    def __init__(self, k: int, seed: int = 0):
        self.k = k
        self.seed = seed

    def abstract_fit(self, dep_specs):
        return _fisher_abstract_fit(self.k)

    # -- static HBM planning (analysis.resources) --------------------------
    def fitted_nbytes(self, dep_specs):
        return _fisher_fitted_nbytes(self.k, dep_specs)

    def abstract_apply_transient(self, dep_specs):
        return _fisher_apply_transient(self.k)

    def _fit(self, ds: Dataset) -> FisherVector:
        return FisherVector(_gmm_from_columns(ds, self.k, self.seed))


class EncEvalGMMFisherVectorEstimator(ScalaGMMFisherVectorEstimator):
    """Counterpart of the reference's native enceval estimator
    (``external/FisherVector.scala:17-55``): same GMM fit, same FV math —
    on TPU the jitted GEMM formulation IS the fast native path, so this
    is the scala variant under the reference's native name."""


class GMMFisherVectorEstimator(OptimizableEstimator):
    """Auto-choosing FV estimator (reference ``FisherVector.scala:85-94``:
    picks the native implementation when k >= 32)."""

    def __init__(self, k: int, seed: int = 0):
        self.k = k
        self.seed = seed

    def abstract_fit(self, dep_specs):
        return _fisher_abstract_fit(self.k)

    # -- static HBM planning (analysis.resources) --------------------------
    def fitted_nbytes(self, dep_specs):
        return _fisher_fitted_nbytes(self.k, dep_specs)

    def abstract_apply_transient(self, dep_specs):
        return _fisher_apply_transient(self.k)

    @property
    def default(self) -> Estimator:
        return ScalaGMMFisherVectorEstimator(self.k, self.seed)

    def optimize(self, sample: Dataset, n: int, num_machines: int) -> NodeChoice:
        if self.k >= 32:
            return NodeChoice(
                EncEvalGMMFisherVectorEstimator(self.k, self.seed))
        return NodeChoice(ScalaGMMFisherVectorEstimator(self.k, self.seed))

    def optimize_static(self, spec, n: int, num_machines: int):
        # the choice depends only on k: always statically resolvable
        return self.optimize(None, n, num_machines)
