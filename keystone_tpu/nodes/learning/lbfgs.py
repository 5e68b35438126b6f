"""LBFGS-based least-squares solvers (reference
``nodes/learning/LBFGS.scala`` + ``Gradient.scala``).

Objective (reference CostFun, LBFGS.scala:79-121):
    loss(W) = ||A W - B||^2 / (2 n) + (lambda/2) ||W||^2
with the gradient accumulated across the row-sharded data by XLA
all-reduce (the treeReduce replacement) inside one jitted L-BFGS program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import linalg
from ...ops.lbfgs import lbfgs
from ...parallel.dataset import ensure_array, ArrayDataset, Dataset
from ...workflow.label_estimator import LabelEstimator
from ..stats import StandardScalerModel
from .linear import LinearMapper


class DenseLBFGSwithL2(LabelEstimator):
    """Dense least-squares via L-BFGS (reference LBFGS.scala:127-193).
    fit_intercept mean-centers features/labels and stores the scalers on
    the returned LinearMapper, exactly like the reference."""

    def __init__(
        self,
        fit_intercept: bool = True,
        num_corrections: int = 10,
        convergence_tol: float = 1e-4,
        num_iterations: int = 100,
        lam: float = 0.0,
    ):
        self.fit_intercept = fit_intercept
        self.num_corrections = num_corrections
        self.convergence_tol = convergence_tol
        self.num_iterations = num_iterations
        self.lam = lam

    @property
    def weight(self) -> int:
        return self.num_iterations + 1

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import labels_width_fit

        return labels_width_fit(dep_specs)

    def _fit(self, ds: Dataset, labels: Dataset) -> LinearMapper:
        ds, labels = ensure_array(ds), ensure_array(labels)
        n = ds.n
        X, Y = ds.data, labels.data
        mask = ds.mask

        if self.fit_intercept:
            x_mean = np.asarray(linalg.distributed_mean(X, n))
            y_mean = np.asarray(linalg.distributed_mean(Y, n))
        else:
            x_mean = np.zeros(X.shape[1], np.float32)
            y_mean = np.zeros(Y.shape[1], np.float32)

        W = _run_lbfgs(
            X,
            Y,
            jnp.asarray(x_mean),
            jnp.asarray(y_mean),
            mask,
            n,
            jnp.asarray(self.lam, X.dtype),
            self.num_iterations,
            self.num_corrections,
            self.convergence_tol,
        )
        if self.fit_intercept:
            return LinearMapper(
                np.asarray(W),
                intercept=y_mean,
                feature_scaler=StandardScalerModel(x_mean),
            )
        return LinearMapper(np.asarray(W))

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w,
             lat_w=0.0) -> float:
        """Reference cost model (LBFGS.scala:175-191) plus the TPU
        dispatch-latency term: L-BFGS is inherently iterative, one
        serial device round per iteration (~375 ms fixed cost for 20
        iterations at tiny compute in the round-5 calibration, whose
        dispatch floor this installation does not have: ROADMAP S7).
        ``lat_w=0`` reproduces the reference surface."""
        flops = n * d * k / num_machines
        bytes_scanned = n * d / num_machines
        network = 2.0 * d * k * np.log2(max(num_machines, 1))
        return self.num_iterations * (
            max(cpu_w * flops, mem_w * bytes_scanned) + net_w * network
            + lat_w
        )


@functools.partial(
    jax.jit, static_argnames=("n", "num_iterations", "num_corrections", "tol")
)
def _run_lbfgs(X, Y, x_mean, y_mean, mask, n, lam, num_iterations,
               num_corrections, tol):
    m = mask[:, None].astype(X.dtype)
    Xc = (X - x_mean) * m
    Yc = (Y - y_mean) * m
    d, k = X.shape[1], Y.shape[1]

    def value_and_grad(W):
        R = Xc @ W - Yc  # padded rows contribute 0
        loss = 0.5 * jnp.sum(R * R) / n + 0.5 * lam * jnp.sum(W * W)
        grad = linalg.cross(Xc, R) / n + lam * W
        return loss, grad

    res = lbfgs(
        value_and_grad,
        jnp.zeros((d, k), X.dtype),
        max_iters=num_iterations,
        num_corrections=num_corrections,
        tol=tol,
    )
    return res.x


class SparseLBFGSwithL2(LabelEstimator):
    """Sparse-input least-squares via L-BFGS (reference
    ``LBFGS.scala:209-262`` + ``Gradient.scala:58-119``).

    TPU-native layout: the sparse batch becomes fixed-width padded COO
    arrays (indices/values), sharded over the mesh data axis like any
    ArrayDataset. The gradient A^T(AW - B) is a gather (W rows by index,
    weighted by values) plus a scatter-add — static shapes, one jitted
    L-BFGS program. ``fit_intercept`` uses the reference's ones-column
    trick (one extra COO slot per row).
    """

    def __init__(
        self,
        fit_intercept: bool = True,
        num_corrections: int = 10,
        convergence_tol: float = 1e-4,
        num_iterations: int = 100,
        lam: float = 0.0,
        sparse_overhead: float = 8.0,
    ):
        self.fit_intercept = fit_intercept
        self.num_corrections = num_corrections
        self.convergence_tol = convergence_tol
        self.num_iterations = num_iterations
        self.lam = lam
        self.sparse_overhead = sparse_overhead

    @property
    def weight(self) -> int:
        return self.num_iterations + 1

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import labels_width_fit

        return labels_width_fit(dep_specs)

    def _fit(self, ds: Dataset, labels: Dataset):
        from .classifiers import SparseLinearMapper
        from ..util.sparse import pack_sparse_fit_inputs

        if isinstance(ds, ArrayDataset):
            raise TypeError(
                "SparseLBFGSwithL2 expects a host dataset of SparseVectors; "
                "dense arrays should use DenseLBFGSwithL2")
        indices, values, d, y_arr = pack_sparse_fit_inputs(ds, labels)
        n = len(y_arr)
        if self.fit_intercept:
            # ones column: index d, value 1 in an extra slot per row
            indices = np.concatenate(
                [indices, np.full((n, 1), d, np.int32)], axis=1)
            values = np.concatenate(
                [values, np.ones((n, 1), np.float32)], axis=1)
            d_aug = d + 1
        else:
            d_aug = d

        coo = ArrayDataset.from_numpy(
            {"indices": indices, "values": values})
        Y = ArrayDataset.from_numpy(np.asarray(y_arr, np.float32)).data

        W = _run_sparse_lbfgs(
            coo.data["indices"], coo.data["values"], Y, coo.mask,
            d_aug, n,
            jnp.asarray(self.lam, jnp.float32),
            self.num_iterations, self.num_corrections, self.convergence_tol,
            penalize_last=not self.fit_intercept,
        )
        W = np.asarray(W)
        if self.fit_intercept:
            return SparseLinearMapper(W[:-1], intercept=W[-1])
        return SparseLinearMapper(W)

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w,
             lat_w=0.0) -> float:
        """Reference cost model (LBFGS.scala:264-280) plus the TPU
        dispatch-latency term (one serial round per iteration; see
        ``DenseLBFGSwithL2.cost``)."""
        flops = n * sparsity * d * k / num_machines
        bytes_scanned = n * d * sparsity / num_machines
        network = 2.0 * d * k * np.log2(max(num_machines, 1))
        return self.num_iterations * (
            self.sparse_overhead * max(cpu_w * flops, mem_w * bytes_scanned)
            + net_w * network
            + lat_w
        )


@functools.partial(
    jax.jit,
    static_argnames=("d", "n", "num_iterations", "num_corrections", "tol",
                     "penalize_last"),
)
def _run_sparse_lbfgs(indices, values, Y, mask, d, n, lam, num_iterations,
                      num_corrections, tol, penalize_last=True):
    m = mask.astype(values.dtype)
    vals = values * m[:, None]  # padded rows contribute nothing
    Ym = Y * m[:, None]
    k = Y.shape[1]
    flat_idx = indices.reshape(-1)
    # with an intercept ones-column, the bias row is not regularized
    # (matches DenseLBFGSwithL2, whose intercept is the label mean)
    pen = jnp.ones((d, 1), jnp.float32)
    if not penalize_last:
        pen = pen.at[-1, 0].set(0.0)

    def value_and_grad(W):
        # A W: gather rows of W at the nz indices, weight, reduce over slots
        gathered = W[indices]                 # (rows, slots, k)
        pred = jnp.einsum("rs,rsk->rk", vals, gathered)
        R = pred - Ym
        Wp = W * pen
        loss = 0.5 * jnp.sum(R * R) / n + 0.5 * lam * jnp.sum(Wp * Wp)
        # A^T R: scatter-add value-weighted residual rows
        contrib = (vals[:, :, None] * R[:, None, :]).reshape(-1, k)
        grad = jnp.zeros_like(W).at[flat_idx].add(contrib) / n + lam * Wp
        return loss, grad

    res = lbfgs(
        value_and_grad,
        jnp.zeros((d, k), jnp.float32),
        max_iters=num_iterations,
        num_corrections=num_corrections,
        tol=tol,
    )
    return res.x
