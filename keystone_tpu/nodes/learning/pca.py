"""PCA family (reference ``nodes/learning/PCA.scala`` and
``DistributedPCA.scala``, ``ApproximatePCA.scala``).

The reference's driver-local LAPACK sgesvd becomes a replicated XLA SVD;
the distributed variant keeps the communication-avoiding TSQR structure
(per-shard QR + all-gather + QR) with only the small R factor crossing the
interconnect.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...observability.metrics import MetricsRegistry
from ...observability.timeline import flight_span
from ...ops import linalg
from ...parallel.dataset import ArrayDataset, Dataset, HostDataset
from ...workflow.estimator import Estimator
from ...workflow.optimizable import NodeChoice, OptimizableEstimator
from ...workflow.transformer import Transformer


def enforce_matlab_sign_convention(pca: np.ndarray) -> np.ndarray:
    """Largest-magnitude element of each column becomes positive
    (reference PCA.scala:238-247)."""
    col_max = pca.max(axis=0)
    abs_max = np.abs(pca).max(axis=0)
    signs = np.where(col_max == abs_max, 1.0, -1.0).astype(pca.dtype)
    return pca * signs


class _PcaParamMixin:
    """Fitted-param protocol shared by the PCA projections: the fitted
    basis rides as a jit argument, so refits (new PCA on new data)
    never recompile the apply program (PERFORMANCE.md rule 6)."""

    def apply_params(self):
        params = self.__dict__.get("_jit_pca_params")
        if params is None:
            params = (jnp.asarray(self.pca_mat),)
            self.__dict__["_jit_pca_params"] = params  # _jit_*: unpickled
        return params

    def apply_with_params(self, params, x):
        (pca_mat,) = params
        # float32 as the source computes it: one bfloat16 pass, the
        # TPU's default for float32 operands, is 2e-3 on a 128-deep sum
        return jnp.matmul(pca_mat.T, x, precision=jax.lax.Precision.HIGHEST)

    def struct_key(self):
        return (type(self), "project")


class PCATransformer(_PcaParamMixin, Transformer):
    """x -> pca_mat^T x (reference PCA.scala:19-30). pca_mat is (d, k)."""

    def __init__(self, pca_mat: np.ndarray):
        self.pca_mat = np.asarray(pca_mat, dtype=np.float32)

    def apply(self, x):
        return self.apply_with_params(self.apply_params(), x)


class BatchPCATransformer(_PcaParamMixin, Transformer):
    """Per-item matrix projection: (d, cols) -> (k, cols)
    (reference PCA.scala:38-43). A product from the left: a zero column
    stays a zero column, and every column is mapped by itself."""

    keeps_padding = True
    maps_columns = True

    def __init__(self, pca_mat: np.ndarray):
        self.pca_mat = np.asarray(pca_mat, dtype=np.float32)

    def apply(self, x):
        return self.apply_with_params(self.apply_params(), x)


@jax.jit
def _centered_svd_vt(X):
    # true-f32 (see _fit_zca): the "exact" local PCA must not sit
    # below the randomized one in fidelity
    with linalg.solver_precision():
        means = jnp.mean(X, axis=0)
        _, _, vt = jnp.linalg.svd(X - means, full_matrices=False)
        return vt


def _svd_pca(data: jnp.ndarray, dims: int) -> np.ndarray:
    vt = np.asarray(_centered_svd_vt(data))
    pca = enforce_matlab_sign_convention(vt.T)
    return pca[:, :dims]


class _PcaAbstractFitMixin:
    """abstract_fit shared by every PCA estimator: the fitted projection
    replaces the leading (descriptor) axis with ``dims``."""

    def abstract_fit(self, dep_specs):
        import jax

        from ...analysis.spec import Unknown

        dims = self.dims

        def apply_element(element):
            if isinstance(element, jax.ShapeDtypeStruct) and element.shape:
                return jax.ShapeDtypeStruct(
                    (dims,) + tuple(element.shape[1:]), element.dtype)
            return Unknown("pca input not an array element")

        return apply_element

    # -- static HBM planning (analysis.resources) --------------------------
    def fitted_nbytes(self, dep_specs):
        """Fitted projection matrix: (d, dims) f32, d = the input
        element's leading (descriptor) axis."""
        import jax

        element = getattr(dep_specs[0], "element", None) if dep_specs \
            else None
        if not (isinstance(element, jax.ShapeDtypeStruct)
                and element.shape):
            return None
        return 4.0 * float(element.shape[0]) * self.dims


class PCAEstimator(_PcaAbstractFitMixin, Estimator):
    """Local PCA: collect the (sampled) data, center, SVD
    (reference PCA.scala:163-210)."""

    def __init__(self, dims: int):
        self.dims = dims

    def _fit(self, ds: Dataset) -> PCATransformer:
        X = _collect_matrix(ds)
        return PCATransformer(self.compute_pca(X))

    def compute_pca(self, X: np.ndarray) -> np.ndarray:
        return _svd_pca(jnp.asarray(X, jnp.float32), self.dims)

    #: gather + one big host SVD: two serial rounds.
    DISPATCH_ROUNDS = 2

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w,
             lat_w=0.0) -> float:
        """Reference cost model (PCA.scala:~213-226): all data moves to
        one machine. ``lat_w`` is the TPU dispatch-latency extension
        (see ``LinearMapEstimator.cost``); 0 reproduces the reference."""
        flops = n * d * d
        bytes_scanned = n * d
        network = n * d
        return (max(cpu_w * flops, mem_w * bytes_scanned) + net_w * network
                + lat_w * self.DISPATCH_ROUNDS)


@jax.jit
def _center_masked(X, means, mask):
    return (X - means) * mask[:, None].astype(X.dtype)


class DistributedPCAEstimator(_PcaAbstractFitMixin, Estimator):
    """Distributed PCA via TSQR: center by broadcast means, tree-QR to the
    small R factor, local SVD of R (reference DistributedPCA.scala:34-57)."""

    def __init__(self, dims: int):
        self.dims = dims

    def _fit(self, ds: Dataset) -> PCATransformer:
        assert isinstance(ds, ArrayDataset)
        n = ds.n
        X = ds.data
        means = linalg.distributed_mean(X, n)
        Xc = _center_masked(X, means, ds.mask)
        R = linalg.tsqr_r(Xc)
        _, _, vt = np.linalg.svd(np.asarray(R))
        pca = enforce_matlab_sign_convention(vt.T.astype(np.float32))
        return PCATransformer(pca[:, : self.dims])

    #: mean + center + device TSQR + small host SVD: four serial rounds.
    DISPATCH_ROUNDS = 4

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w,
             lat_w=0.0) -> float:
        """Reference cost model (DistributedPCA.scala:59-73) plus the
        TPU dispatch-latency term; ``lat_w=0`` reproduces the
        reference."""
        log2m = np.log2(max(num_machines, 1))
        flops = n * d * d / num_machines + d * d * d * log2m
        bytes_scanned = n * d
        network = d * d * log2m
        return (max(cpu_w * flops, mem_w * bytes_scanned) + net_w * network
                + lat_w * self.DISPATCH_ROUNDS)


@functools.partial(jax.jit, static_argnames=("q",))
def _randomized_svd_vt(X, omega, *, q: int):
    # true-f32 matmuls (see _fit_zca): power iterations at bf16
    # precision lose the small singular directions they exist to refine
    with linalg.solver_precision():
        means = jnp.mean(X, axis=0)
        A = X - means
        Y = A @ omega
        Q, _ = jnp.linalg.qr(Y)
        for _ in range(q):
            Q, _ = jnp.linalg.qr(A.T @ Q)
            Q, _ = jnp.linalg.qr(A @ Q)
        B = Q.T @ A
        _, _, vt = jnp.linalg.svd(B, full_matrices=False)
        return vt


class ApproximatePCAEstimator(_PcaAbstractFitMixin, Estimator):
    """Randomized-sketch PCA, Halko-Martinsson-Tropp algs 4.4/5.1
    (reference ApproximatePCA.scala:38-86): Gaussian sketch, q power
    iterations with intermediate QRs, then SVD of the projected matrix."""

    def __init__(self, dims: int, q: int = 10, p: int = 5, seed: int = 0):
        self.dims = dims
        self.q = q
        self.p = p
        self.seed = seed

    def _fit(self, ds: Dataset) -> PCATransformer:
        X = _collect_matrix(ds)
        return PCATransformer(self.approximate_pca(X))

    def approximate_pca(self, X: np.ndarray) -> np.ndarray:
        rng = np.random.RandomState(self.seed)
        ell = self.dims + self.p
        omega = rng.randn(X.shape[1], ell).astype(np.float32)
        vt = np.asarray(_randomized_svd_vt(
            jnp.asarray(X, jnp.float32), jnp.asarray(omega), q=self.q))
        pca = enforce_matlab_sign_convention(vt.T)
        return pca[:, : self.dims]


class LocalColumnPCAEstimator(_PcaAbstractFitMixin, Estimator):
    """Fits PCA treating each column of per-item matrices as a sample
    (reference PCA.scala:51-76); emits BatchPCATransformer."""

    fitted_maps_columns = True

    def __init__(self, dims: int):
        self.dims = dims

    def _fit(self, ds: Dataset) -> BatchPCATransformer:
        with _column_fit_span(ds, self.dims):
            cols = _stack_item_columns(ds)
            pca = PCAEstimator(self.dims).compute_pca(cols)
        return BatchPCATransformer(pca)


class DistributedColumnPCAEstimator(_PcaAbstractFitMixin, Estimator):
    """Distributed variant of the column PCA (reference PCA.scala:78-102)."""

    fitted_maps_columns = True

    def __init__(self, dims: int):
        self.dims = dims

    def _fit(self, ds: Dataset) -> BatchPCATransformer:
        with _column_fit_span(ds, self.dims):
            cols = _stack_item_columns(ds)
            fitted = DistributedPCAEstimator(self.dims).fit(
                ArrayDataset(cols, cols.shape[0]))
        return BatchPCATransformer(fitted.pca_mat)


class ColumnPCAEstimator(_PcaAbstractFitMixin, OptimizableEstimator):
    """Cost-model-optimizable column PCA (reference PCA.scala:118-156):
    the node-level optimizer picks local vs distributed by the reference's
    calibrated cost models; until then it runs distributed."""

    fitted_maps_columns = True      # either option's BatchPCATransformer

    def __init__(self, dims: int, cpu_weight: float = None,
                 mem_weight: float = None, network_weight: float = None,
                 lat_weight: float = None):
        from .least_squares import (
            DEFAULT_CPU_WEIGHT, DEFAULT_LAT_WEIGHT, DEFAULT_MEM_WEIGHT,
            DEFAULT_NETWORK_WEIGHT)
        cpu_weight = DEFAULT_CPU_WEIGHT if cpu_weight is None else cpu_weight
        mem_weight = DEFAULT_MEM_WEIGHT if mem_weight is None else mem_weight
        network_weight = (DEFAULT_NETWORK_WEIGHT if network_weight is None
                          else network_weight)
        lat_weight = DEFAULT_LAT_WEIGHT if lat_weight is None else lat_weight
        self.dims = dims
        self.cpu_weight = cpu_weight
        self.mem_weight = mem_weight
        self.network_weight = network_weight
        self.lat_weight = lat_weight

    @property
    def options(self):
        return [LocalColumnPCAEstimator(self.dims),
                DistributedColumnPCAEstimator(self.dims)]

    @property
    def default(self):
        return DistributedColumnPCAEstimator(self.dims)

    def optimize(self, sample: Dataset, n: int, num_machines: int) -> NodeChoice:
        # the column PCA's sample unit is a (d, cols) matrix; the cost
        # models see total column count as n (reference PCA.scala:134-151)
        items = sample.collect()
        cols_per_item = int(np.asarray(items[0]).shape[-1]) if items else 1
        d = int(np.asarray(items[0]).shape[0]) if items else 1
        return self._choose(d, cols_per_item, n, num_machines)

    def optimize_static(self, spec, n: int, num_machines: int):
        """Static form: the (d, cols) item geometry comes from the
        analyzer's element spec instead of a sampled matrix."""
        element = getattr(spec, "element", None)
        if not (isinstance(element, jax.ShapeDtypeStruct)
                and len(element.shape) == 2):
            return None
        d, cols_per_item = (int(element.shape[0]), int(element.shape[1]))
        return self._choose(d, cols_per_item, n, num_machines)

    def _choose(self, d: int, cols_per_item: int, n: int,
                num_machines: int) -> NodeChoice:
        total_cols = n * cols_per_item
        local = PCAEstimator(self.dims)
        dist = DistributedPCAEstimator(self.dims)
        costs = [
            (local.cost(total_cols, d, self.dims, 1.0, num_machines,
                        self.cpu_weight, self.mem_weight,
                        self.network_weight, lat_w=self.lat_weight), 0),
            (dist.cost(total_cols, d, self.dims, 1.0, num_machines,
                       self.cpu_weight, self.mem_weight,
                       self.network_weight, lat_w=self.lat_weight), 1),
        ]
        _, best = min(costs)
        return NodeChoice(self.options[best])


def _collect_matrix(ds: Dataset) -> np.ndarray:
    if isinstance(ds, ArrayDataset):
        return ds.numpy()
    return np.stack(ds.collect())


def _stack_item_columns(ds: Dataset):
    """Items are (d, cols) matrices; stack all columns as rows (the
    reference's matrixToColArray flatMap). Items of one shape on the
    device stay there: a million sampled descriptors are half a
    gigabyte each way."""
    if isinstance(ds, ArrayDataset):
        arr = ds.data[:ds.n]  # (n, d, cols)
        return arr.transpose(0, 2, 1).reshape(-1, arr.shape[1])
    items = ds.collect()
    return np.concatenate([np.asarray(m).T for m in items], axis=0)


def _column_fit_span(ds: Dataset, dims: int):
    """``featurize:fit_pca`` around a column PCA's fit, and the count of
    such fits."""
    MetricsRegistry.get_or_create().counter("featurize.pca.fits").inc()
    return flight_span("fit_pca", "featurize", items=len(ds), dims=dims)
