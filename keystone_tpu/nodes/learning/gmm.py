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

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset
from ...workflow.estimator import Estimator
from ...workflow.transformer import Transformer
from .kmeans import KMeansPlusPlusEstimator

KMEANS_PLUS_PLUS_INITIALIZATION = "kmeans++"
RANDOM_INITIALIZATION = "random"


def _posteriors(X, means, variances, weights, weight_threshold):
    """Thresholded posterior responsibilities of a batch (reference
    GaussianMixtureModel.scala:46-82). means/vars are (k, d), weights (k,)."""
    d = X.shape[-1]
    XSq = X * X
    sq_mahl = (
        XSq @ (0.5 / variances).T
        - X @ (means / variances).T
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    llh = (
        -0.5 * d * jnp.log(2 * jnp.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
        - sq_mahl
    )
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
        X = ds.numpy() if isinstance(ds, ArrayDataset) else np.stack(ds.collect())
        return self.fit_matrix(np.asarray(X, np.float32))

    def fit_matrix(self, X: np.ndarray) -> GaussianMixtureModel:
        n, d = X.shape
        k = self.k
        # X crosses to device ONCE; XSq derives on device (a host XSq
        # would double the h2d volume)
        X_dev = jnp.asarray(np.asarray(X, np.float32))
        XSq_dev = X_dev * X_dev
        mean_global = X.mean(axis=0)
        var_global = (X * X).mean(axis=0) - mean_global**2

        if self.initialization_method == KMEANS_PLUS_PLUS_INITIALIZATION:
            km = KMeansPlusPlusEstimator(k, 1, seed=self.seed).fit_matrix(X)
            assign = jax.vmap(km.apply)(X_dev)  # (n, k), stays on device
            mass = jnp.maximum(jnp.sum(assign, axis=0), 1e-12)
            weights = mass / n
            means = (assign.T @ X_dev) / mass[:, None]
            variances = (assign.T @ XSq_dev) / mass[:, None] - means**2
        else:
            rng = np.random.RandomState(self.seed)
            col_min, col_max = X.min(axis=0), X.max(axis=0)
            col_range = col_max - col_min
            means = rng.rand(k, d).astype(np.float32) * col_range + col_min
            variances = np.full((k, d), 0.1, np.float32) * (col_range**2)
            weights = np.full(k, 1.0 / k, np.float32)

        var_lb_dev = jnp.asarray(
            np.maximum(
                self.small_variance_threshold * var_global,
                self.absolute_variance_threshold,
            ),
            jnp.float32,
        )

        # E and M both stay on device; only the 8-byte (cost, unbalanced)
        # pair crosses to host per iteration for the stopping decisions.
        # The old loop pulled the whole (n, k) responsibility matrix and
        # ran the M-step in numpy — minutes of d2h at FV-training scale.
        means = jnp.asarray(means, jnp.float32)
        variances = jnp.maximum(
            jnp.asarray(variances, jnp.float32), var_lb_dev)
        weights = jnp.asarray(weights, jnp.float32)

        prev_cost = None
        for it in range(self.max_iterations):
            new_means, new_vars, new_weights, llh_mean, unbalanced = _em_iter(
                X_dev, XSq_dev, means, variances, weights, var_lb_dev,
                self.weight_threshold, float(self.min_cluster_size),
            )
            cost = float(llh_mean)
            if prev_cost is not None:
                if (cost - prev_cost) < self.stop_tolerance * abs(prev_cost):
                    break
            if bool(unbalanced):
                # unbalanced clustering: stop updating (reference :176-178)
                break
            means, variances, weights = new_means, new_vars, new_weights
            prev_cost = cost

        return GaussianMixtureModel(
            np.asarray(means).T, np.asarray(variances).T,
            np.asarray(weights), self.weight_threshold
        )


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
    hi = jax.lax.Precision.HIGHEST
    new_means = jnp.matmul(q.T, X, precision=hi) / safe[:, None]
    new_vars = jnp.maximum(
        jnp.matmul(q.T, XSq, precision=hi) / safe[:, None]
        - new_means**2, var_lb)
    return new_means, new_vars, new_weights, llh_mean, unbalanced


@jax.jit
def _e_step(X, XSq, means, variances, weights, weight_threshold):
    d = X.shape[1]
    sq_mahl = (
        XSq @ (0.5 / variances).T
        - X @ (means / variances).T
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    llh = (
        -0.5 * d * jnp.log(2 * jnp.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
        - sq_mahl
    )
    lse = jax.scipy.special.logsumexp(llh, axis=1)
    shifted = llh - jnp.max(llh, axis=1, keepdims=True)
    q = jnp.exp(shifted)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    return q, jnp.mean(lse)
