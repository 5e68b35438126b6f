"""Diagonal-covariance Gaussian mixtures (reference
``nodes/learning/GaussianMixtureModel.scala`` and
``GaussianMixtureModelEstimator.scala``), trained per Sanchez et al.'s
Fisher-vector guidelines.

The reference's driver-local EM becomes a jitted EM step; posterior
computation keeps the exact "Mahalanobis via GEMM" + max-shifted softmax +
aggressive thresholding structure that the Fisher-vector encoder depends
on.
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
from ...workflow.estimator import Estimator
from ...workflow.transformer import Transformer

KMEANS_PLUS_PLUS_INITIALIZATION = "kmeans++"
RANDOM_INITIALIZATION = "random"

#: Every product of the posteriors, of the EM step and of the Fisher
#: vector's moments. The Mahalanobis distance is a difference of sums of
#: thousands (descriptors reach 255, squared): at the TPU's default for
#: float32 operands, one bfloat16 pass, the log-likelihoods are wrong by
#: tens and the posteriors are noise.
_PRECISION = jax.lax.Precision.HIGHEST


def _log_likelihoods(X, XSq, means, variances, weights):
    """``log(w_k N(x | mu_k, diag var_k))`` of every row, ``(n, k)``:
    the Mahalanobis distance as two products (reference
    GaussianMixtureModel.scala:46-70). means/vars are (k, d)."""
    d = X.shape[-1]
    sq_mahl = (
        jnp.matmul(XSq, (0.5 / variances).T, precision=_PRECISION)
        - jnp.matmul(X, (means / variances).T, precision=_PRECISION)
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    return (
        -0.5 * d * jnp.log(2 * jnp.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
        - sq_mahl
    )


def _posteriors(X, means, variances, weights, weight_threshold):
    """Thresholded posterior responsibilities of a batch (reference
    GaussianMixtureModel.scala:46-82). means/vars are (k, d), weights (k,)."""
    llh = _log_likelihoods(X, X * X, means, variances, weights)
    shifted = llh - jnp.max(llh, axis=-1, keepdims=True)
    q = jnp.exp(shifted)
    q = q / jnp.sum(q, axis=-1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    return q / jnp.sum(q, axis=-1, keepdims=True)


class GaussianMixtureModel(Transformer):
    """Thresholded posterior assignment transformer. Stored column-major
    like the reference: means/variances are (d, k), weights (k,)."""

    def __init__(self, means, variances, weights, weight_threshold: float = 1e-4):
        self.means = np.asarray(means, dtype=np.float32)
        self.variances = np.asarray(variances, dtype=np.float32)
        self.weights = np.asarray(weights, dtype=np.float32)
        self.weight_threshold = weight_threshold
        assert self.means.shape == self.variances.shape
        assert self.weights.shape[0] == self.means.shape[1]

    @property
    def k(self) -> int:
        return self.means.shape[1]

    @property
    def dim(self) -> int:
        return self.means.shape[0]

    def apply(self, x):
        return self.apply_with_params(self.apply_params(), x)

    # fitted-param protocol (PERFORMANCE.md rule 6): refitted mixtures
    # never recompile the posterior program
    def apply_params(self):
        params = self.__dict__.get("_jit_gmm_params")
        if params is None:
            params = (jnp.asarray(self.means.T),
                      jnp.asarray(self.variances.T),
                      jnp.asarray(self.weights))
            self.__dict__["_jit_gmm_params"] = params
        return params

    def apply_with_params(self, params, x):
        means_t, vars_t, weights = params
        return _posteriors(
            x[None, :], means_t, vars_t, weights, self.weight_threshold,
        )[0]

    def struct_key(self):
        return (GaussianMixtureModel, self.weight_threshold)

    @staticmethod
    def load(mean_file: str, vars_file: str, weights_file: str) -> "GaussianMixtureModel":
        """CSV artifact loading (reference GaussianMixtureModel.scala:97-105)."""
        means = np.loadtxt(mean_file, delimiter=",", ndmin=2)
        variances = np.loadtxt(vars_file, delimiter=",", ndmin=2)
        weights = np.loadtxt(weights_file, delimiter=",").ravel()
        return GaussianMixtureModel(means, variances, weights)

    def save(self, mean_file: str, vars_file: str, weights_file: str) -> None:
        """Write the CSV artifacts ``load`` reads (same layout the
        reference's MATLAB/enceval tooling produced: (d, k) means and
        variances, a k-vector of weights)."""
        np.savetxt(mean_file, self.means, delimiter=",")
        np.savetxt(vars_file, self.variances, delimiter=",")
        np.savetxt(weights_file, self.weights, delimiter=",")


class GaussianMixtureModelEstimator(Estimator):
    """EM for diagonal GMMs (reference GaussianMixtureModelEstimator.scala:
    25-190): kmeans++ (1 round) or range-uniform random init, variance
    floor max(small_var_thresh * global_var, abs_var_thresh), incremental
    LSE log-likelihood stopping, min-cluster-size abort."""

    def __init__(
        self,
        k: int,
        max_iterations: int = 100,
        min_cluster_size: int = 40,
        stop_tolerance: float = 1e-4,
        weight_threshold: float = 1e-4,
        small_variance_threshold: float = 1e-2,
        absolute_variance_threshold: float = 1e-9,
        initialization_method: str = KMEANS_PLUS_PLUS_INITIALIZATION,
        seed: int = 0,
    ):
        assert min_cluster_size > 0 and max_iterations > 0
        self.k = k
        self.max_iterations = max_iterations
        self.min_cluster_size = min_cluster_size
        self.stop_tolerance = stop_tolerance
        self.weight_threshold = weight_threshold
        self.small_variance_threshold = small_variance_threshold
        self.absolute_variance_threshold = absolute_variance_threshold
        self.initialization_method = initialization_method
        self.seed = seed

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import map_last_dim

        return map_last_dim(self.k)

    def _fit(self, ds: Dataset) -> GaussianMixtureModel:
        if isinstance(ds, ArrayDataset):
            return self.fit_matrix(ds.data[:ds.n])   # stays on the device
        return self.fit_matrix(np.stack(ds.collect()))

    def fit_matrix(self, X) -> GaussianMixtureModel:
        """EM on an ``(n, d)`` sample, host or device array, as ONE
        program: initialisation, every iteration and both stopping rules
        run on the device, and the host reads the result once (it read
        two scalars an iteration, the device idle each time)."""
        n, d = X.shape
        X = jnp.asarray(X, jnp.float32)
        with flight_span("fit_gmm", "featurize", samples=int(n),
                         components=self.k) as span:
            if self.initialization_method == KMEANS_PLUS_PLUS_INITIALIZATION:
                centres = _kmeans_pp_centres(
                    X, jax.random.PRNGKey(self.seed % (1 << 32)), k=self.k)
                init = _hard_assignment_moments(X, centres)
            else:
                init = _random_init(X, jax.random.PRNGKey(
                    self.seed % (1 << 32)), k=self.k)
            means, variances, weights, iterations, stopped = _em_fit(
                X, init, self.small_variance_threshold,
                self.absolute_variance_threshold, self.weight_threshold,
                float(self.min_cluster_size), self.stop_tolerance,
                self.max_iterations)
            model = GaussianMixtureModel(
                np.asarray(means).T, np.asarray(variances).T,
                np.asarray(weights), self.weight_threshold)
            span["iterations"] = int(iterations)
        #: EM steps computed, and those adopted: a step that trips a
        #: stopping rule is dropped
        model.iterations = int(iterations)
        model.updates = int(iterations) - int(stopped)
        model.initial = tuple(np.asarray(part) for part in init)
        counter = MetricsRegistry.get_or_create().counter
        counter("featurize.gmm.fits").inc()
        counter("featurize.gmm.iterations").inc(int(iterations))
        return model


@functools.partial(jax.jit, static_argnames=("k",))
def _kmeans_pp_centres(X, key, k):
    """k-means++ seeding (reference KMeansPlusPlus.scala:100-123) on the
    device: the first centre uniform, each next one drawn with
    probability proportional to the squared distance to the nearest
    centre so far."""
    n = X.shape[0]
    x_sq_half = 0.5 * jnp.sum(X * X, axis=1)
    key, first_key = jax.random.split(key)
    first = jax.random.randint(first_key, (), 0, n)

    def add(i, carry):
        centres, at, nearest, key = carry
        c = X[at]
        to_new = jnp.maximum(
            x_sq_half - jnp.matmul(X, c, precision=_PRECISION)
            + 0.5 * jnp.dot(c, c), 0.0)
        nearest = jnp.minimum(nearest, to_new)
        key, draw = jax.random.split(key)
        # a categorical draw by Gumbel's maximum: no cumulative sum of n
        nxt = jnp.argmax(jnp.log(nearest) + jax.random.gumbel(draw, (n,)))
        return centres.at[i].set(c), nxt, nearest, key

    centres, last, _, _ = jax.lax.fori_loop(
        0, k - 1, add,
        (jnp.zeros((k, X.shape[1]), X.dtype), first,
         jnp.full((n,), jnp.inf, X.dtype), key))
    return centres.at[k - 1].set(X[last])


@jax.jit
def _hard_assignment_moments(X, centres):
    """One Lloyd step from the seeded centres, then weights, means and
    variances of the hard assignment to the moved centres: what
    ``KMeansPlusPlusEstimator(k, 1)`` and a one-hot apply gave."""
    from .kmeans import _lloyd_step

    n, k = X.shape[0], centres.shape[0]
    moved, _ = _lloyd_step(X, centres)
    sq_dist = (-jnp.matmul(X, moved.T, precision=_PRECISION)
               + 0.5 * jnp.sum(moved * moved, axis=1))
    assign = jax.nn.one_hot(jnp.argmin(sq_dist, axis=1), k, dtype=X.dtype)
    mass = jnp.maximum(jnp.sum(assign, axis=0), 1e-12)
    means = jnp.matmul(assign.T, X, precision=_PRECISION) / mass[:, None]
    variances = (jnp.matmul(assign.T, X * X, precision=_PRECISION)
                 / mass[:, None] - means ** 2)
    return means, variances, mass / n


@functools.partial(jax.jit, static_argnames=("k",))
def _random_init(X, key, k):
    """Means uniform in each column's range (reference
    GaussianMixtureModelEstimator.scala:60-75)."""
    col_min, col_max = jnp.min(X, axis=0), jnp.max(X, axis=0)
    col_range = col_max - col_min
    means = jax.random.uniform(key, (k, X.shape[1])) * col_range + col_min
    variances = jnp.full((k, X.shape[1]), 0.1, X.dtype) * col_range ** 2
    return means, variances, jnp.full((k,), 1.0 / k, X.dtype)


@jax.jit
def _em_fit(X, init, small_var, abs_var, weight_threshold, min_cluster_size,
            stop_tolerance, max_iterations):
    """EM from ``init`` until the mean log-likelihood gains less than
    ``stop_tolerance`` of itself, a component falls under
    ``min_cluster_size``, or ``max_iterations``; an iteration that trips
    a rule is not adopted (reference :150-180). Returns the parameters,
    how many iterations were run and whether the last tripped a rule."""
    XSq = X * X
    mean_global = jnp.mean(X, axis=0)
    var_lb = jnp.maximum(
        small_var * (jnp.mean(XSq, axis=0) - mean_global ** 2), abs_var)
    means, variances, weights = init
    variances = jnp.maximum(variances, var_lb)

    def more(state):
        _, _, it, stopped = state
        return jnp.logical_and(it < max_iterations, ~stopped)

    def step(state):
        params, prev_cost, it, _ = state
        *new, cost, unbalanced = _em_iter(
            X, XSq, *params, var_lb, weight_threshold, min_cluster_size)
        flat = jnp.logical_and(
            it > 0, (cost - prev_cost) < stop_tolerance * jnp.abs(prev_cost))
        stopped = jnp.logical_or(flat, unbalanced)
        params = jax.tree_util.tree_map(
            lambda old, new: jnp.where(stopped, old, new), params, tuple(new))
        return params, jnp.where(stopped, prev_cost, cost), it + 1, stopped

    params, _, iterations, stopped = jax.lax.while_loop(
        more, step, ((means, variances, weights), jnp.float32(0.0),
                     jnp.int32(0), jnp.bool_(False)))
    return (*params, iterations, stopped)


@jax.jit
def _em_iter(X, XSq, means, variances, weights, var_lb,
             weight_threshold, min_cluster_size):
    """One full EM iteration on device. Returns the UPDATED parameters
    plus (mean log-likelihood of the CURRENT parameters, unbalanced
    flag); the host adopts the update only if neither stopping rule
    fires, preserving the reference's stop-without-updating semantics."""
    n = X.shape[0]
    q, llh_mean = _e_step(X, XSq, means, variances, weights,
                          weight_threshold)
    q_sum = jnp.sum(q, axis=0)
    unbalanced = jnp.any(q_sum < min_cluster_size)
    safe = jnp.maximum(q_sum, 1e-12)
    new_weights = q_sum / n
    # HIGHEST matmul precision: E[x^2] - mean^2 is cancellation-prone,
    # and the default bf16-pass matmul error would swamp small variances
    hi = _PRECISION
    new_means = jnp.matmul(q.T, X, precision=hi) / safe[:, None]
    new_vars = jnp.maximum(
        jnp.matmul(q.T, XSq, precision=hi) / safe[:, None]
        - new_means**2, var_lb)
    return new_means, new_vars, new_weights, llh_mean, unbalanced


@jax.jit
def _e_step(X, XSq, means, variances, weights, weight_threshold):
    llh = _log_likelihoods(X, XSq, means, variances, weights)
    lse = jax.scipy.special.logsumexp(llh, axis=1)
    shifted = llh - jnp.max(llh, axis=1, keepdims=True)
    q = jnp.exp(shifted)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    return q, jnp.mean(lse)
