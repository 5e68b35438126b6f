"""Linear models and least-squares estimators.

TPU-native re-designs of reference ``nodes/learning/LinearMapper.scala``
and ``nodes/learning/BlockLinearMapper.scala`` (SURVEY.md section 2.3):
the Spark Gram-accumulate + driver-Cholesky becomes a sharded GEMM +
all-reduce + replicated Cholesky, and block coordinate descent runs as one
jitted program with per-block Gram psums.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import linalg
from ...parallel.dataset import (ArrayDataset, Dataset, ensure_array,
                                 row_shards)
from ...parallel.mesh import replicated_zeros
from ...utils.donation import donating_jit
from ...workflow.label_estimator import LabelEstimator
from ...observability.metrics import MetricsRegistry
from ...observability.timeline import flight_span
from ...workflow.transformer import (
    Transformer,
    config_shim,
    struct_cached_jit,
)
from ..stats import StandardScaler, StandardScalerModel


@jax.jit
def _moments3(a):
    # nan-ignoring moments + a non-finite count: finite arrays get the
    # plain moments (count 0); broken arrays stay distinguishable by
    # their finite content instead of collapsing to one NaN token
    a32 = a.astype(jnp.float32)
    finite = jnp.isfinite(a32)
    z = jnp.where(finite, a32, 0.0)
    return jnp.stack(
        [jnp.sum(z), jnp.sum(jnp.square(z)), jnp.sum(jnp.abs(z)),
         jnp.sum(~finite).astype(jnp.float32)])


def _array_token(a):
    """Device-cheap content identity for ``eq_key``: shape + dtype +
    three global moments (ONE dispatch, one 12-byte pull) instead of
    serializing the whole array — the default ``tobytes`` key would
    drag a fitted (d, C) model through d2h just to hash it during
    fusion/CSE, and three separate scalar pulls would pay three
    host round trips. A collision needs identical shape AND
    identical f32 sum / sum-of-squares / sum-of-abs; its only
    consequence is CSE or the fusion cache merging two
    indistinguishable models."""
    if a is None:
        return None
    arr = jnp.asarray(a)
    m = np.asarray(_moments3(arr))
    if m[3] != 0.0:
        # NaN would poison dict keys (NaN != NaN makes a fitted model
        # unequal to ITSELF, silently defeating CSE/fusion/jit caches
        # forever) — the nan-ignoring moments keep the key stable AND
        # content-distinguishing, and a non-finite fitted array is
        # worth shouting about: a silently-NaN solve predicts a
        # constant class. The warning also lands in the numerics event
        # funnel (metrics/trace/flight-recorder), so dashboards see it
        # even when nobody reads the log.
        import logging

        from ...observability.numerics import record_numerics_event

        record_numerics_event("nonfinite_model",
                              shape=tuple(arr.shape), count=int(m[3]))
        logging.getLogger(__name__).warning(
            "fitted array %s contains %d non-finite values — the solve "
            "likely failed; check conditioning/lambda",
            arr.shape, int(m[3]))
    return (arr.shape, str(arr.dtype),
            float(m[0]), float(m[1]), float(m[2]), float(m[3]))


# -- quantized predict (serving plane) -------------------------------------
#
# The PR 5 wire_dtype contract applied to WEIGHTS: a fitted mapper may
# hold its weight matrix at a narrower dtype than f32 — bf16, or int8
# with per-column scales — for the serving plane, where the apply path
# re-reads the full (d, k) matrix from HBM per request batch. Accuracy
# is policed two ways: the quantization error is recorded into the
# numerics funnel the moment the weights narrow (``numerics.quant_error``
# event + ``numerics.quant_rel_error`` gauge), and the parity gate
# (tests/test_pallas_kernels.py; chip_smoke.py on the chip) pins
# argmax agreement and an error bound against the f32 apply.

def _canon_weight_dtype(weight_dtype):
    if weight_dtype is None:
        return None
    alias = {"bf16": "bf16", "bfloat16": "bf16", "int8": "int8"}
    try:
        key = alias.get(str(np.dtype(weight_dtype)), None) \
            if not isinstance(weight_dtype, str) else alias.get(weight_dtype)
    except TypeError:
        key = alias.get(str(weight_dtype))
    if key is None:
        raise ValueError(
            f"weight_dtype must be None, 'bf16' or 'int8', got "
            f"{weight_dtype!r}")
    return key


def _quantize_weights(W, weight_dtype):
    """Quantize a fitted (d, k) f32 weight matrix: bf16 (scales of
    ones), or int8 with per-COLUMN scales (symmetric, 127 levels —
    each output class keeps its own dynamic range, so one large-norm
    column cannot crush the resolution of the rest). Returns
    ``(Wq, scale)`` and records the dequantization error into the
    numerics funnel — quantization drift is a numbers-plane event, not
    a silent precision choice."""
    from ...observability import MetricsRegistry
    from ...observability.numerics import record_numerics_event

    Wf = jnp.asarray(W, jnp.float32)
    k = Wf.shape[1]
    if weight_dtype == "bf16":
        Wq = Wf.astype(jnp.bfloat16)
        scale = jnp.ones((k,), jnp.float32)
    else:
        amax = jnp.max(jnp.abs(Wf), axis=0)
        scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
        Wq = jnp.clip(jnp.round(Wf / scale[None, :]), -127.0, 127.0) \
            .astype(jnp.int8)
    deq = Wq.astype(jnp.float32) * scale[None, :]
    denom = jnp.maximum(jnp.max(jnp.abs(Wf)), 1e-12)
    err = jnp.abs(deq - Wf)
    max_rel = float(jnp.max(err) / denom)
    rms_rel = float(jnp.sqrt(jnp.mean(err * err)) / denom)
    MetricsRegistry.get_or_create().gauge(
        "numerics.quant_rel_error").set(max_rel)
    record_numerics_event(
        "quant_error", dtype=weight_dtype, shape=tuple(Wf.shape),
        max_rel=round(max_rel, 6), rms_rel=round(rms_rel, 6))
    return Wq, scale


def _maybe_quantized_params(affine, weight_dtype):
    """The shared apply_params tail of both mappers: narrow the f32
    affine params when a weight_dtype is set (4-tuple stays the plain
    `_affine_apply_batch` contract; 5-tuple is the quantized one)."""
    if weight_dtype is None:
        return affine
    W, mean, inv_std, b = affine
    Wq, scale = _quantize_weights(W, weight_dtype)
    return (Wq, scale, mean, inv_std, b)


def _dequant_affine(params, x):
    """The ONE home of the dequantizing affine math — shared by the
    per-item apply, the fused-chain apply_with_params, and the batched
    program's einsum fallback, so the quantization semantics cannot
    silently diverge between paths."""
    Wq, scale, mean, inv_std, b = params
    return ((x - mean) * inv_std) @ (
        Wq.astype(jnp.float32) * scale[None, :]) + b


@functools.partial(jax.jit, static_argnames=("mesh",))
def _quantized_affine_batch(X, Wq, scale, mean, inv_std, b, mesh=None):
    """Whole-batch quantized fitted-model apply, params as ARGUMENTS
    (the `_affine_apply_batch` contract — one compile serves every
    refit): ``((X - mean) * inv_std) @ dequant(Wq) + b`` with f32
    accumulation. Dispatch: the Pallas kernel on TPU when the
    VMEM-resident weight block fits (``ops.pallas_kernels.
    quantized_affine_pallas``), else the dequantizing einsum fallback
    (same dequantize-then-f32-matmul math).

    ``mesh`` (static) is the mesh the batch rows are sharded over: a
    Mosaic kernel cannot be partitioned automatically (the compiler
    refuses it), so on more than one device each runs the kernel on its
    own rows under ``shard_map``, weights replicated."""
    from ...ops.pallas_kernels import (
        quant_fits_vmem,
        quantized_affine_pallas,
        use_pallas,
    )

    d, k = Wq.shape
    if use_pallas() and quant_fits_vmem(d, k, Wq.dtype.itemsize):
        if mesh is None or mesh.size == 1:
            return quantized_affine_pallas(X, Wq, scale, mean, inv_std, b)
        from jax.sharding import PartitionSpec as P

        from ...parallel.mesh import DATA_AXIS

        return jax.shard_map(
            quantized_affine_pallas, mesh=mesh,
            in_specs=(P(DATA_AXIS),) + (P(),) * 5,
            out_specs=P(DATA_AXIS), check_vma=False,
        )(X, Wq, scale, mean, inv_std, b)
    return _dequant_affine((Wq, scale, mean, inv_std, b), X)


class LinearMapper(Transformer):
    """out = x_model^T in (+ b), with optional feature scaler
    (reference ``LinearMapper.scala:18-62``). ``weight_dtype`` narrows
    the stored weights on the apply path (None = f32; ``"bf16"`` /
    ``"int8"`` per-column-scaled — the serving plane's quantized
    predict, see ``_quantize_weights``)."""

    def __init__(
        self,
        weights: np.ndarray,
        intercept: Optional[np.ndarray] = None,
        feature_scaler: Optional[StandardScalerModel] = None,
        weight_dtype: Optional[str] = None,
    ):
        # host or device arrays, kept as handed in (see BlockLinearMapper)
        self.weights = weights
        self.intercept = intercept
        self.feature_scaler = feature_scaler
        self.weight_dtype = _canon_weight_dtype(weight_dtype)
        if (self.weight_dtype is not None and feature_scaler is not None
                and type(feature_scaler) is not StandardScalerModel):
            raise ValueError(
                "weight_dtype quantization requires a plain "
                "StandardScalerModel feature scaler (or none): the "
                "quantized apply is one fused affine program")

    def __getstate__(self):
        d = super().__getstate__()  # strips per-instance jit caches
        d["weights"] = np.asarray(self.weights)
        if d["intercept"] is not None:
            d["intercept"] = np.asarray(d["intercept"])
        return d

    def eq_key(self):
        return (
            LinearMapper,
            self.weight_dtype,
            _array_token(self.weights),
            _array_token(self.intercept),
            None if self.feature_scaler is None
            else self.feature_scaler._cached_eq_key(),
        )

    def apply(self, x):
        if self.weight_dtype is not None:
            return self.apply_with_params(self.apply_params(), x)
        if self.feature_scaler is not None:
            x = self.feature_scaler.apply(x)
        out = x @ self.weights
        if self.intercept is not None:
            out = out + self.intercept
        return out

    def _simple_scaler(self):
        """The scaler when it is exactly a StandardScalerModel (the
        fitted shape); anything else keeps the default baked path."""
        from ..stats import StandardScalerModel

        s = self.feature_scaler
        return s if s is None or type(s) is StandardScalerModel else False

    def apply_dataset(self, ds: Dataset) -> Dataset:
        params = self.apply_params()
        if isinstance(ds, ArrayDataset) and params is not None:
            if self.weight_dtype is not None:
                return ds.map_batch(
                    lambda X: _quantized_affine_batch(
                        X, *params, mesh=ds.mesh))
            return ds.map_batch(
                lambda X: _affine_apply_batch(X, *params))
        return super().apply_dataset(ds)

    # fitted-param protocol: fused chains thread these as jit arguments
    fusion_safe = True

    def apply_params(self):
        scaler = self._simple_scaler()
        if scaler is False:
            return None  # arbitrary scaler node: baked/content-keyed path
        params = self.__dict__.get("_jit_affine_params")
        if params is None:
            mean = None if scaler is None else scaler.mean
            inv = (None if scaler is None or scaler.std is None
                   else 1.0 / np.asarray(scaler.std))
            params = _maybe_quantized_params(
                _affine_params(self.weights, mean, inv, self.intercept),
                self.weight_dtype)
            self.__dict__["_jit_affine_params"] = params  # _jit_*: unpickled
        return params

    def apply_with_params(self, params, x):
        if self.weight_dtype is not None:
            return _dequant_affine(params, x)
        W, mean, inv_std, b = params
        return ((x - mean) * inv_std) @ W + b

    def struct_key(self):
        if self._simple_scaler() is False:
            return super().struct_key()
        return (LinearMapper, "affine", self.weight_dtype)

    def sharded_apply_nbytes(self):
        """(shardable at rest, gather transient) under the spmd
        sharded apply — W row-shards, and the whole matrix gathers
        per call (the FSDP unit). Quantized mappers keep the fused
        dequant program with only the batch sharded: nothing shards
        at rest, nothing gathers."""
        if self.weight_dtype is not None:
            return 0.0, 0.0
        nb = float(self.weights.nbytes)
        return nb, nb


class LinearMapEstimator(LabelEstimator):
    """OLS/ridge via distributed normal equations on mean-centered features
    and labels; intercept = label mean (reference
    ``LinearMapper.scala:71-98``)."""

    def __init__(self, lam: Optional[float] = None,
                 weight_dtype: Optional[str] = None):
        self.lam = lam
        # serving-plane quantized predict: the fitted mapper narrows
        # its weights (validated eagerly so a typo fails at config
        # time, not after the fit)
        self.weight_dtype = _canon_weight_dtype(weight_dtype)

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import labels_width_fit

        return labels_width_fit(dep_specs)

    # -- static HBM planning (analysis.resources) --------------------------
    def carry_nbytes(self, dep_specs):
        from ...analysis.resources import gram_carry_nbytes

        return gram_carry_nbytes(dep_specs)

    def fitted_nbytes(self, dep_specs):
        from ...analysis.resources import linear_model_nbytes

        return linear_model_nbytes(dep_specs)

    # -- streaming fit (accumulate/finalize protocol) ----------------------
    def accumulate(self, carry, chunk, labels):
        """One chunk's contribution to the raw Gram/cross/sum carry (the
        fused ``gram_cross`` kernel streams each row tile through VMEM
        once). Padded chunk rows are zero, so sums stay exact."""
        return accumulate_gram_carry(carry, chunk, labels)

    def finalize(self, carry):
        """Centered ridge normal equations from the accumulated raw
        moments: Gc = G - n mu_x mu_x^T, Cc = C - n mu_x mu_y^T —
        algebraically identical to the resident ``_fit``, with only the
        carry (d x d + d x k) ever resident in HBM."""
        G, C, sx, sy, n = carry
        x_mean, y_mean, W = _finalize_normal_equations(
            G, C, sx, sy, jnp.asarray(n, G.dtype),
            jnp.asarray(float(self.lam or 0.0), G.dtype))
        return LinearMapper(
            W,
            intercept=y_mean,
            feature_scaler=StandardScalerModel(x_mean),
            weight_dtype=self.weight_dtype,
        )

    def _fit(self, ds: Dataset, labels: Dataset) -> LinearMapper:
        ds, labels = ensure_array(ds), ensure_array(labels)
        n = ds.n
        X, Y = ds.data, labels.data
        # ONE dispatch for means + centering + normal equations: the
        # split form cost three jit round-trips per fit, which dominated
        # the measured solve time at small d (tools/calibrate_cost_model
        # finding, round 4)
        x_mean, y_mean, W = _means_and_normal_equations(
            X, Y, ds.mask, jnp.asarray(n, X.dtype),
            float(self.lam or 0.0))
        return LinearMapper(
            W,
            intercept=y_mean,
            feature_scaler=StandardScalerModel(x_mean),
            weight_dtype=self.weight_dtype,
        )

    #: Serial device round-trips per fit (center / gram / factorize /
    #: solve / intercept plus eigendecomposition host syncs), measured
    #: shape-independent at ~180 ms in the round-5 calibration
    #: (184/163/198 ms across n=1k..65k at tiny compute; not measured
    #: on this installation, ROADMAP S7).
    DISPATCH_ROUNDS = 10

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w,
             lat_w=0.0) -> float:
        """Reference cost model (LinearMapper.scala:100-115) extended
        with a dispatch-latency term (``lat_w`` seconds per serial
        device round): on TPU the compute terms alone mis-rank small-d
        solves, where per-round dispatch latency dominates (r5
        calibration, tools/calibrate_cost_model.py). ``lat_w=0``
        reproduces the reference surface exactly."""
        flops = n * d * (d + k) / num_machines
        bytes_scanned = n * d / num_machines + d * d
        network = d * (d + k)
        return (max(cpu_w * flops, mem_w * bytes_scanned) + net_w * network
                + lat_w * self.DISPATCH_ROUNDS)

    @staticmethod
    def compute_cost(
        ds,
        labels,
        lam: float,
        weights: np.ndarray,
        intercept: Optional[np.ndarray] = None,
    ) -> float:
        """Ridge objective at (weights, intercept): ||XW + b - L||_F^2 / (2n)
        + lam/2 ||W||_F^2 (reference ``LinearMapper.scala:124-161``,
        ``LinearMapEstimator.computeCost``). ``ds``/``labels`` may be
        Datasets or arrays; the residual reduction runs on device over the
        sharded batch."""
        ds, labels = ensure_array(ds), ensure_array(labels)
        b = (
            jnp.zeros((weights.shape[1],), jnp.float32)
            if intercept is None
            else jnp.asarray(intercept)
        )
        cost = _squared_residual_sum(
            ds.data, labels.data, jnp.asarray(weights), b, ds.mask
        )
        total = float(cost) / (2.0 * ds.n)
        if lam != 0.0:
            total += lam / 2.0 * float(np.sum(np.asarray(weights) ** 2))
        return total


@jax.jit
def _affine_apply_batch(X, W, mean, inv_std, b):
    """Whole-batch fitted-model apply with params as ARGUMENTS:
    ((X - mean) * inv_std) @ W + b. A jit built over ``self.apply``
    closes over the fitted arrays and bakes them into the HLO as
    constants, so every refit on new data produces a brand-new program
    (measured: the fitted model's batched apply was the ONLY program
    recompiling when app data changed — minutes per cold fit on the
    bench chip). With params as arguments the program is content-free:
    one compile serves every refit, in-process and via the persistent
    compilation cache."""
    return ((X - mean) * inv_std) @ W + b


def _affine_params(W, mean, inv_std, b):
    dt = jnp.float32
    Wd = jnp.asarray(W, dt)
    d, k = Wd.shape
    return (
        Wd,
        jnp.zeros((d,), dt) if mean is None else jnp.asarray(mean, dt),
        jnp.ones((d,), dt) if inv_std is None else jnp.asarray(inv_std, dt),
        jnp.zeros((k,), dt) if b is None else jnp.asarray(b, dt),
    )


# -- streaming carry (shared by the whole least-squares family) ------------
#
# The carry is the Spark analogue of per-partition Gram reduction
# (SURVEY.md section 3.2): raw second moments (G = X^T X, C = X^T Y) plus
# raw first moments (column sums) and the true row count. Centering is
# recovered at finalize time (Gc = G - n mu mu^T), so accumulation is a
# pure sum — chunk order cannot change the result beyond f32 rounding.


def _gram_carry_update_impl(G, C, sx, sy, X, Y, mesh=None):
    from ...ops.pallas_kernels import gram_cross

    X = X.astype(jnp.float32)
    Y = Y.astype(jnp.float32)
    # fused: one pass over the chunk's rows; ``mesh`` (static) is the
    # mesh the chunk's rows are sharded over, so each device runs the
    # kernel on its own rows
    g, c = gram_cross(X, Y, mesh=mesh)
    return (G + g, C + c,
            sx + jnp.sum(X, axis=0), sy + jnp.sum(Y, axis=0))


def _carry_probe(d: int = 8, k: int = 3, n: int = 16):
    """Tiny shape witness for the donation gate: every donated carry
    piece must have a shape-compatible output (checked abstractly by
    ``utils.donation.donation_shape_mismatches`` — see tools/lint.py)."""
    S, f32 = jax.ShapeDtypeStruct, np.float32
    return ((S((d, d), f32), S((d, k), f32), S((d,), f32), S((k,), f32),
             S((n, d), f32), S((n, k), f32)), {})


#: The per-chunk carry update DONATES the carry buffers (G, C, sx, sy):
#: XLA writes the updated carry into the old carry's HBM instead of
#: allocating a fresh (d, d) + (d, k) pair per chunk — a streamed fit
#: holds ONE carry, with zero per-chunk allocator traffic. The chunk
#: arrays (X, Y) are NOT donated: the prefetch buffer still owns them.
#: Callers must treat the passed-in carry as dead after the call
#: (``fit_streaming``'s loop reassigns immediately, and checkpointing
#: copies the carry to host BEFORE the next accumulate donates it).
_gram_carry_update = donating_jit(
    _gram_carry_update_impl, donate_argnums=(0, 1, 2, 3),
    static_argnames=("mesh",), probe=_carry_probe)


def accumulate_gram_carry(carry, chunk, labels):
    """Fold one (features, labels) chunk pair into the
    ``(G, C, sx, sy, n)`` carry (``n`` stays a host int — it is the only
    piece of the carry the driver loop reads). Chunks must be
    ArrayDatasets with the zero-pad invariant (StreamingDataset output
    or any masked resident dataset)."""
    chunk, labels = ensure_array(chunk), ensure_array(labels)
    X, Y = chunk.data, labels.data
    if X.ndim != 2 or Y.ndim != 2:
        raise ValueError(
            f"streamed least-squares needs 2-D (n, d)/(n, k) chunks, got "
            f"{X.shape} / {Y.shape}")
    if X.shape[0] != Y.shape[0]:
        raise ValueError(
            f"chunk/labels padded rows differ: {X.shape[0]} vs "
            f"{Y.shape[0]}")
    if carry is None:
        d, k = X.shape[1], Y.shape[1]
        # the zero carry is REPLICATED on the chunk's mesh explicitly:
        # a plain jnp.zeros is SingleDeviceSharding, and since jax's
        # jit cache keys on input shardings, chunk 2 (whose carry is
        # the mesh-sharded output of chunk 1's update) would recompile
        # _gram_carry_update once per fit — an ~80 ms chunk-2 stall the
        # compile observatory's fit fence flagged the moment it was
        # armed (PR 9); with a replicated init the output shardings are
        # stable from call 1 and the whole fit compiles exactly once
        carry = tuple(replicated_zeros(chunk.mesh, (
            (d, d), (d, k), (d,), (k,)))) + (0,)
    G, C, sx, sy, n = carry
    G, C, sx, sy = _gram_carry_update(G, C, sx, sy, X, Y, mesh=chunk.mesh)
    return (G, C, sx, sy, n + chunk.n)


def _finalize_normal_equations_impl(G, C, sx, sy, n, lam):
    with linalg.solver_precision():
        x_mean = sx / n
        y_mean = sy / n
        Gc = G - n * jnp.outer(x_mean, x_mean)
        Cc = C - n * jnp.outer(x_mean, y_mean)
        return x_mean, y_mean, linalg.ridge_cho_solve(
            Gc, Cc, lam, site="finalize_normal_equations")


def _finalize_probe(d: int = 8, k: int = 3):
    S, f32 = jax.ShapeDtypeStruct, np.float32
    return ((S((d, d), f32), S((d, k), f32), S((d,), f32), S((k,), f32),
             S((), f32), S((), f32)), {})


#: finalize consumes the carry: donate the pieces with a
#: SHAPE-COMPATIBLE output — C (d,k) -> W, sx -> x_mean, sy -> y_mean.
#: G (d,d) matches no output, so donating it cannot be honored and
#: would only emit jax's donated-buffer-not-usable warning per compile
#: on the backends where donation is real (pinned: the probe makes this
#: a static gate, tests/test_analysis_passes.py a no-warnings test).
_finalize_normal_equations = donating_jit(
    _finalize_normal_equations_impl, donate_argnums=(1, 2, 3),
    probe=_finalize_probe)


def _gram_bcd_impl(G, C, sx, sy, n, lam, bounds, num_iter):
    """Block coordinate descent driven entirely from the accumulated
    Gram/cross carry: the update

        W_b <- (Gc[b,b] + lam I)^-1 (Cc[b] - Gc[b,:] W + Gc[b,b] W_b)

    is algebraically the data-form update A_b^T (Yc - P + A_b W_b) of
    ``ops.linalg.bcd_core`` (same sequential block order, same per-block
    Cholesky reuse and breakdown recovery), so streamed and resident
    BlockLS fits agree to f32 rounding — without the (n, d) data ever
    being resident."""
    with linalg.solver_precision():
        dtype = G.dtype
        k = C.shape[1]
        x_mean = sx / n
        y_mean = sy / n
        Gc = G - n * jnp.outer(x_mean, x_mean)
        Cc = C - n * jnp.outer(x_mean, y_mean)
        factors, oks, ratios = [], [], []
        for lo, hi in bounds:
            Gb = Gc[lo:hi, lo:hi] + lam * jnp.eye(hi - lo, dtype=dtype)
            L = jax.scipy.linalg.cho_factor(Gb, lower=True)
            factors.append(L)
            ok, ratio = linalg._chol_health(L[0], Gb)
            oks.append(ok)
            ratios.append(ratio)
        # streamed BlockLS breakdowns land in the conditioning ledger
        # exactly like the resident BCD's (one callback, all blocks)
        from ...observability.numerics import record_block_health

        record_block_health("gram_bcd", jnp.stack(oks),
                            jnp.stack(ratios))
        W = jnp.zeros((G.shape[0], k), dtype)
        for _ in range(num_iter):
            for i, (lo, hi) in enumerate(bounds):
                rhs = (Cc[lo:hi] - Gc[lo:hi, :] @ W
                       + Gc[lo:hi, lo:hi] @ W[lo:hi])
                Wi = jax.scipy.linalg.cho_solve(factors[i], rhs)
                Wi = linalg._finite_or_eigh_solve(
                    Wi,
                    lambda lo=lo, hi=hi: Gc[lo:hi, lo:hi]
                    + lam * jnp.eye(hi - lo, dtype=dtype),
                    rhs, ok=oks[i])
                W = W.at[lo:hi].set(Wi)
        return tuple(W[lo:hi] for lo, hi in bounds), x_mean, y_mean


def _gram_bcd_probe(d: int = 8, k: int = 3):
    S, f32 = jax.ShapeDtypeStruct, np.float32
    return ((S((d, d), f32), S((d, k), f32), S((d,), f32), S((k,), f32),
             S((), f32), S((), f32)),
            {"bounds": ((0, 4), (4, 8)), "num_iter": 1})


#: the Gram-form BCD finalize donates the carry pieces XLA can actually
#: reuse: sx -> x_mean, sy -> y_mean. G (d,d) and C (d,k) match no
#: output (the weights come back as per-block slices), so donating them
#: would only trigger the not-usable warning — see
#: ``_finalize_normal_equations``.
_gram_bcd = donating_jit(
    _gram_bcd_impl, donate_argnums=(2, 3),
    static_argnames=("bounds", "num_iter"), probe=_gram_bcd_probe)


@jax.jit
def _centered_normal_equations(X, Y, x_mean, y_mean, mask, lam):
    m = mask[:, None].astype(X.dtype)
    Xc = (X - x_mean) * m
    Yc = (Y - y_mean) * m
    return linalg.ridge_cho_solve(linalg.gram(Xc), linalg.cross(Xc, Yc), lam)


@jax.jit
def _means_and_normal_equations(X, Y, mask, n, lam):
    """Column means + centered ridge normal equations as one program
    (one device dispatch per fit; see ``LinearMapEstimator._fit``)."""
    m = mask[:, None].astype(X.dtype)
    x_mean = jnp.sum(X * m, axis=0) / n
    y_mean = jnp.sum(Y * m, axis=0) / n
    W = _centered_normal_equations.__wrapped__(X, Y, x_mean, y_mean, mask, lam)
    return x_mean, y_mean, W


@jax.jit
def _masked_sse(pred, Y, b, mask):
    m = mask[:, None].astype(pred.dtype)
    resid = (pred + b - Y) * m
    return jnp.sum(resid * resid)


@jax.jit
def _squared_residual_sum(X, Y, W, b, mask):
    return _masked_sse(X @ W, Y, b, mask)


class BlockLinearMapper(Transformer):
    """Block-partitioned linear model (reference
    ``BlockLinearMapper.scala:22-73``).

    The reference stores ``Seq[DenseMatrix]`` blocks and applies them one
    broadcast-GEMM at a time to bound executor memory; on TPU the blocks
    concatenate into one sharded GEMM (the MXU-friendly layout), while the
    per-block view is kept for API parity.
    """

    def __init__(
        self,
        block_weights: Sequence[np.ndarray],
        block_size: int,
        intercept: Optional[np.ndarray] = None,
        feature_means: Optional[np.ndarray] = None,
        weights: Optional[np.ndarray] = None,
        weight_dtype: Optional[str] = None,
    ):
        # blocks are kept as handed in (host OR device arrays): forcing
        # np.asarray here would drag freshly-fitted device weights to
        # host — a multi-second d2h for ImageNet-scale (d x 1000)
        # models — only for apply() to ship them straight back.
        # ``weights`` lets a caller that already assembled the full
        # matrix skip the concat copy.
        self.block_weights = list(block_weights)
        self.block_size = block_size
        self.intercept = intercept
        self.feature_means = feature_means
        self.weight_dtype = _canon_weight_dtype(weight_dtype)
        if weights is not None:
            self.weights = weights
        else:
            concat = (
                jnp.concatenate
                if any(isinstance(w, jax.Array) for w in self.block_weights)
                else np.concatenate
            )
            self.weights = concat(self.block_weights, axis=0)

    def eq_key(self):
        return (
            BlockLinearMapper,
            self.block_size,
            self.weight_dtype,
            _array_token(self.weights),
            _array_token(self.intercept),
            _array_token(self.feature_means),
        )

    def __getstate__(self):
        # device arrays pickle as host copies (checkpoint/FittedPipeline
        # serialization); super() strips per-instance jit caches
        d = super().__getstate__()
        d["block_weights"] = [np.asarray(w) for w in self.block_weights]
        d["weights"] = np.asarray(self.weights)
        for f in ("intercept", "feature_means"):
            if d[f] is not None:
                d[f] = np.asarray(d[f])
        return d

    def apply(self, x):
        if self.weight_dtype is not None:
            return self.apply_with_params(self.apply_params(), x)
        if self.feature_means is not None:
            x = x - self.feature_means
        out = x @ self.weights
        if self.intercept is not None:
            out = out + self.intercept
        return out

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset):
            params = self.apply_params()
            if self.weight_dtype is not None:
                return ds.map_batch(
                    lambda X: _quantized_affine_batch(
                        X, *params, mesh=ds.mesh))
            return ds.map_batch(
                lambda X: _affine_apply_batch(X, *params))
        return super().apply_dataset(ds)

    # fitted-param protocol: fused chains thread these as jit arguments
    fusion_safe = True

    def apply_params(self):
        params = self.__dict__.get("_jit_affine_params")
        if params is None:
            params = _maybe_quantized_params(
                _affine_params(self.weights, self.feature_means,
                               None, self.intercept),
                self.weight_dtype)
            self.__dict__["_jit_affine_params"] = params  # _jit_*: unpickled
        return params

    def apply_with_params(self, params, x):
        if self.weight_dtype is not None:
            return _dequant_affine(params, x)
        W, mean, inv_std, b = params
        return ((x - mean) * inv_std) @ W + b

    def struct_key(self):
        return (BlockLinearMapper, "affine", self.weight_dtype)

    def sharded_apply_nbytes(self):
        """(shardable at rest, gather transient) under the spmd
        sharded apply: every block row-shards, and the in-body gather
        reassembles ONE block at a time — the transient peak is the
        largest block, which is what lets a model whose total
        ``weights.nbytes`` exceeds a single host's budget still be
        admitted (the concatenated ``weights`` view is derived state
        the sharded apply never materializes)."""
        if self.weight_dtype is not None:
            return 0.0, 0.0
        # charge the concat view too: it shards right alongside the
        # blocks (fitted_model_nbytes counted it, so we must as well)
        total = float(self.weights.nbytes) + sum(
            float(w.nbytes) for w in self.block_weights)
        unit = max(float(w.nbytes) for w in self.block_weights)
        return total, unit

    def _block_bounds(self) -> List[tuple]:
        bounds, lo = [], 0
        for w in self.block_weights:
            bounds.append((lo, lo + w.shape[0]))
            lo += w.shape[0]
        return bounds

    def apply_and_evaluate(self, blocks, evaluator) -> None:
        """Incremental per-block evaluation (reference
        ``BlockLinearMapper.scala:105-142``): after adding feature block i's
        contribution, call ``evaluator`` on the running prediction (partial
        sum + intercept). Lets callers track test error as the block solve
        consumes features, without materializing all blocks at once.

        ``blocks`` is a sequence of per-block feature Datasets/arrays
        aligned with ``block_weights``; each is centered by its slice of
        ``feature_means``. The partial sums stay on device; only the
        evaluated copy is handed to the callback.
        """
        assert len(blocks) == len(self.block_weights)
        bounds = self._block_bounds()
        partial = None
        for (lo, hi), w, block in zip(bounds, self.block_weights, blocks):
            block = ensure_array(block)
            x = block.data
            if self.feature_means is not None:
                x = x - self.feature_means[lo:hi]
            contrib = x @ jnp.asarray(w)
            partial = contrib if partial is None else partial + contrib
            out = partial
            if self.intercept is not None:
                out = out + jnp.asarray(self.intercept)
            # Re-zero pad rows (centering/intercept made them nonzero) so
            # the emitted dataset keeps ArrayDataset's zero-pad invariant.
            out = out * block.mask[:, None].astype(out.dtype)
            evaluator(
                ArrayDataset(out, block.n, block.mesh, _already_sharded=True)
            )


# -- blocks made on demand (a gather too wide to materialise) --------------

def _block_maker(featurizer: Transformer):
    """``make_block(params_i, rows)`` of one branch featurizer, from an
    array-free shim: the cached programs must not pin a fit's arrays.
    A featurizer with a batch form of its own
    (``make_blocks_with_params``: an image featurizer that maps a kernel
    over row batches) makes its blocks with it; any other is its
    ``apply_with_params`` under ``vmap``."""
    shim = config_shim(featurizer)
    if hasattr(shim, "make_blocks_with_params"):
        return _BatchMaker(shim)

    def make_block(params_i, rows):
        return jax.vmap(lambda x: shim.apply_with_params(params_i, x))(rows)

    return make_block


class _BatchMaker:
    """The block maker of a featurizer with a batch form of its own:
    ``many(params_g, rows) -> [g, >= n, bs]`` makes ``g`` blocks a call
    from work it then does once, ``blocks_a_call(n, params)`` says how
    many, from the shapes, and the sweeps of ``ops.linalg`` scan over
    groups of that many. One block is a group of one."""

    def __init__(self, shim):
        self.many = shim.make_blocks_with_params
        self.blocks_a_call = shim.blocks_a_call

    def __call__(self, params_i, rows):
        one = jax.tree_util.tree_map(lambda p: p[None], params_i)
        return self.many(one, rows)[0, :rows.shape[0]]


def _stream_program(which: str, featurizer: Transformer, more_passes: int = 0,
                    scale_eps: Optional[float] = None,
                    row_chunk: Optional[int] = None, block_width: int = 0):
    """The jitted programs of the streamed block solve, one compile a
    featurizer STRUCTURE (parameters ride as arguments). Their XLA
    modules are ``jit__stream_factor`` / ``_epochs`` / ``_apply``: the
    factor sweep, which is also the first epoch; the ``more_passes``
    epochs after it, a program only where there are any; the blockwise
    apply. What the factor program leaves as ``P`` is the next
    program's carry where passes follow (``more_passes`` true) and the
    fit's scores where none does. ``scale_eps``: the blocks are
    standardised inside the sweep, and the later programs take the
    ``1 / std`` the first returned as one argument more. ``row_chunk``:
    the rows are taken that many at a time (``stream_row_chunk``; None
    where a block of all rows fits: the programs are then what they
    were), and the factor program returns the rows each Gram counted
    beside ``P``."""
    if which == "factor":
        more_passes = bool(more_passes)   # one program, however many

    def factor():
        make_block = _block_maker(featurizer)

        def _stream_factor(rows, params, Y, y_mean, mask, n, lam):
            m = mask[:, None].astype(Y.dtype)
            factors, Ws, pred, *counted = linalg.bcd_stream_factor(
                rows, params, make_block, (Y - y_mean) * m, mask, n, lam,
                scale_eps=scale_eps, row_chunk=row_chunk,
                block_width=block_width)
            return (factors, Ws, (pred if more_passes
                                  else (pred + y_mean) * m), *counted)
        return _stream_factor

    def epochs():
        make_block = _block_maker(featurizer)

        def _stream_epochs(rows, params, Y, y_mean, mask, means, Ls, Ws,
                           pred, *inv_stds):
            m = mask[:, None].astype(Y.dtype)
            Ws, pred = linalg.bcd_stream_epochs(
                rows, params, make_block, (Y - y_mean) * m, mask, means, Ls,
                Ws, pred, num_passes=more_passes,
                inv_stds=(inv_stds or (None,))[0], row_chunk=row_chunk)
            # the fitted model's scores on these rows, zero on padded ones
            # as a dataset keeps them: what ``_stream_apply`` would give
            return Ws, (pred + y_mean) * m
        return _stream_epochs

    def apply():
        make_block = _block_maker(featurizer)

        def _stream_apply(rows, params, means, Ws, intercept, *inv_stds):
            return linalg.block_stream_apply(
                rows, params, make_block, means, Ws, intercept,
                inv_stds=(inv_stds or (None,))[0], row_chunk=row_chunk)
        return _stream_apply

    builder = {"factor": factor, "epochs": epochs, "apply": apply}[which]
    return struct_cached_jit(
        (f"stream_{which}", featurizer.struct_key(), more_passes, scale_eps)
        + (() if row_chunk is None else (row_chunk, block_width)), builder)


def _row_chunk_of(rows: int, block_width: int, itemsize: int = 4
                  ) -> Optional[int]:
    """How many of a streamed sweep's ``rows`` (as the program holds
    them, padding included) it takes at a time, or None where it takes
    them all: ``analysis.resources.stream_row_chunk`` of the rows ONE
    device holds."""
    from ...analysis.resources import stream_row_chunk
    from ...parallel.mesh import num_data_shards

    return stream_row_chunk(-(-rows // num_data_shards()), block_width,
                            itemsize)


def _chunks(rows: int, row_chunk: Optional[int]) -> int:
    return 1 if row_chunk is None else -(-rows // row_chunk)


def _equal_blocks(branches: Sequence[Transformer]):
    """``(branches of one structure, columns)`` for the scan over
    blocks, or None where they cannot be had. Branches of one structure
    are returned as they are, ``columns`` None. A LAST branch of another
    structure is replaced by what its ``widened_like(first)`` gives: the
    same featurizer with the first's structure, whose block holds the
    branch's own columns at ``real`` and zeros elsewhere; ``columns``
    are then the positions of the gather's columns among all blocks'."""
    branches = list(branches)
    keys = [b.struct_key() for b in branches]
    if len(set(keys)) == 1:
        return branches, None
    widen = getattr(branches[-1], "widened_like", None)
    if len(set(keys[:-1])) != 1 or widen is None:
        return None
    widened = widen(branches[0])
    if widened is None:
        return None
    wide, real, width = widened
    if wide.struct_key() != keys[0]:
        return None
    whole = (len(branches) - 1) * width
    columns = np.concatenate([np.arange(whole), whole + np.asarray(real)])
    return branches[:-1] + [wide], columns


def stack_branch_params(branches: Sequence[Transformer]):
    """The branches' ``apply_params`` stacked on a new leading axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[b.apply_params() for b in branches])


class StreamedBlockLinearMapper(BlockLinearMapper):
    """A block model over features that are never whole: it holds the
    branch featurizers and takes RAW rows, makes block ``i`` of the
    features, adds ``(block_i - mean_i) W_i`` to the scores and lets the
    block go (``ops.linalg.block_stream_apply``; the reference's
    ``BlockLinearMapper`` applies block by block for the same reason,
    ``BlockLinearMapper.scala:40-73``). What ``BlockLeastSquares\
Estimator.fit_branches`` returns."""

    fusion_safe = False   # the per-item affine of the parent is not this
    inv_stds = None       # models pickled before these existed
    columns = None
    rows_solved = None

    def __init__(self, featurizers: Sequence[Transformer], Ws, block_means,
                 intercept, params=None, health=None, inv_stds=None,
                 columns=None, rows_solved=None):
        self.featurizers = list(featurizers)
        self.Ws = Ws                      # [B, bs, k]
        self.block_means = block_means    # [B, bs]
        self.intercept = intercept        # [k]
        #: ``1 / std`` a column [B, bs] where the fit standardised its
        #: blocks (a ``StandardScaler`` carried into the sweep), or None
        self.inv_stds = inv_stds
        #: where a narrower last branch was widened to the others'
        #: structure: the positions, among the ``B * bs`` columns, of the
        #: gather's own columns in the gather's order; None: all of them
        self.columns = columns
        self.block_size = int(Ws.shape[1])
        self.weight_dtype = None
        #: (factor ok [B], min pivot ratio [B]) of the fit, on the device
        self.health = health
        #: the rows that entered each block's Gram [B], on the device,
        #: where the fit took its rows in chunks and counted them; else
        #: None: a block of all rows is summed whole
        self.rows_solved = rows_solved
        if params is not None:
            self.__dict__["_jit_stream_params"] = params

    def _gathered(self, stacked):
        """``[B, bs, ...]`` as the gathered matrix's columns."""
        flat = stacked.reshape((-1,) + tuple(stacked.shape[2:]))
        return flat if self.columns is None else flat[self.columns]

    # the parent's views, for callers that read a fitted block model
    @property
    def weights(self):
        return self._gathered(self.Ws)

    @property
    def feature_means(self):
        return self._gathered(self.block_means)

    @property
    def feature_inv_stds(self):
        return (None if self.inv_stds is None
                else self._gathered(self.inv_stds))

    @property
    def block_weights(self):
        return [self.Ws[i] for i in range(self.Ws.shape[0])]

    def eq_key(self):
        return (StreamedBlockLinearMapper,
                tuple(f._cached_eq_key() for f in self.featurizers),
                _array_token(self.Ws), _array_token(self.block_means),
                _array_token(self.intercept), _array_token(self.inv_stds))

    def __getstate__(self):
        d = {k: v for k, v in self.__dict__.items()
             if not k.startswith("_jit_") and k != "_eq_key_val"}
        for f in ("Ws", "block_means", "intercept"):
            d[f] = np.asarray(d[f])
        if d["inv_stds"] is not None:
            d["inv_stds"] = np.asarray(d["inv_stds"])
        if d["health"] is not None:
            d["health"] = tuple(np.asarray(h) for h in d["health"])
        if d.get("rows_solved") is not None:
            d["rows_solved"] = np.asarray(d["rows_solved"])
        return d

    def stream_params(self):
        params = self.__dict__.get("_jit_stream_params")
        if params is None:
            params = stack_branch_params(self.featurizers)
            self.__dict__["_jit_stream_params"] = params
        return params

    def apply_params(self):
        return None

    def _scores(self, rows):
        blocks = len(self.featurizers)
        chunk = _row_chunk_of(int(rows.shape[0]), self.block_size,
                              rows.dtype.itemsize)
        with flight_span("stream", "apply", blocks=blocks,
                         rows=int(rows.shape[0]),
                         block_width=self.block_size,
                         row_chunks=_chunks(int(rows.shape[0]), chunk)):
            scaled = self.inv_stds is not None
            out = _stream_program(
                "apply", self.featurizers[0], scale_eps=scaled or None,
                row_chunk=chunk, block_width=self.block_size)(
                rows, self.stream_params(), jnp.asarray(self.block_means),
                jnp.asarray(self.Ws), jnp.asarray(self.intercept),
                *((jnp.asarray(self.inv_stds),) if scaled else ()))
        MetricsRegistry.get_or_create().counter(
            "solve.stream.blocks_generated").inc(blocks)
        return out

    def apply(self, x):
        return self._scores(jnp.asarray(x)[None, :])[0]

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset):
            return ds.map_batch(self._scores)
        return Transformer.apply_dataset(self, ds)

    def abstract_single(self, elements):
        # stated, not traced: tracing ``apply`` would count its blocks
        return jax.ShapeDtypeStruct((int(self.Ws.shape[2]),), self.Ws.dtype)

    def struct_key(self):
        return self._cached_eq_key()

    def sharded_apply_nbytes(self):
        return 0.0, 0.0

    def apply_and_evaluate(self, blocks, evaluator) -> None:
        raise NotImplementedError(
            "StreamedBlockLinearMapper makes its own feature blocks from "
            "raw rows; apply it to the rows")


class BlockLeastSquaresEstimator(LabelEstimator):
    """The workhorse distributed solver (reference
    ``BlockLinearMapper.scala:196-257``): per-block mean-centering, label
    mean-centering, block coordinate descent with L2, intercept from the
    joint means. ``weight`` = 3*num_iter+1 passes over the data
    (reference :204) for the auto-cache planner.
    """

    def __init__(self, block_size: int, num_iter: int, lam: float = 0.0,
                 weight_dtype: Optional[str] = None):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.weight_dtype = _canon_weight_dtype(weight_dtype)

    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import labels_width_fit

        return labels_width_fit(dep_specs)

    # -- static HBM planning (analysis.resources) --------------------------
    def carry_nbytes(self, dep_specs):
        from ...analysis.resources import gram_carry_nbytes

        return gram_carry_nbytes(dep_specs)

    def fitted_nbytes(self, dep_specs):
        from ...analysis.resources import linear_model_nbytes

        return linear_model_nbytes(dep_specs)

    # -- streaming fit (accumulate/finalize protocol) ----------------------
    def accumulate(self, carry, chunk, labels):
        """Same carry as the exact solver: raw Gram + cross + sums. Note
        the carry is (d, d) — streaming bounds HBM in ``n`` (the usual
        out-of-core axis: n >> d), not in ``d``."""
        return accumulate_gram_carry(carry, chunk, labels)

    def finalize(self, carry):
        G, C, sx, sy, n = carry
        d = G.shape[0]
        bs = self.block_size
        bounds = tuple((i, min(d, i + bs)) for i in range(0, d, bs))
        Ws, x_mean, y_mean = _gram_bcd(
            G, C, sx, sy, jnp.asarray(n, G.dtype),
            jnp.asarray(float(self.lam), G.dtype), bounds, self.num_iter)
        return BlockLinearMapper(
            list(Ws), bs, intercept=y_mean, feature_means=x_mean,
            weight_dtype=self.weight_dtype)

    # -- a gather handed over as rows plus branch featurizers --------------
    def streams_branches(self, branches: Sequence[Transformer],
                         widths: Sequence[int],
                         between: Sequence = ()) -> bool:
        """Can this estimator fit from raw rows plus ``branches`` in
        place of their gathered, combined output? Each branch must be
        one block (block coordinate descent needs one block alive at a
        time), and all of one structure with their arrays as arguments:
        the sweep is a scan that is traced once. A narrower LAST branch
        will do where it can be widened to the others' structure with
        columns that are zero for every row (``widened_like``): a zero
        column decouples in ``G + lam I`` and gets weight 0. ``between``
        holds the estimators whose fitted transformers stand between the
        gather and this one: a ``StandardScaler`` works column by column
        and rides into the sweep, anything else cannot."""
        if self.weight_dtype is not None or not branches:
            return False
        if self.num_iter < 1:   # the factor sweep IS the first epoch
            return False
        if any(type(e) is not StandardScaler for e in between) or len(
                between) > 1:
            return False
        if any(w != self.block_size for w in widths[:-1]) or (
                widths[-1] > self.block_size):
            return False
        try:
            if _equal_blocks(branches) is None:
                return False
        except TypeError:
            return False
        return all(b.apply_params() is not None for b in branches)

    def fit_branches(self, rows: Dataset, labels: Dataset,
                     branches: Sequence[Transformer],
                     between: Sequence = ()
                     ) -> "StreamedBlockLinearMapper":
        """The model of ``fit_transform_branches``, without the scores."""
        return self.fit_transform_branches(rows, labels, branches, between)[0]

    def fit_transform_branches(self, rows: Dataset, labels: Dataset,
                               branches: Sequence[Transformer],
                               between: Sequence = ()):
        """The same fit as ``_fit`` on ``combine(gather(branches))(rows)``
        (through ``between``'s fitted scaler, where there is one)
        without that matrix: a sweep over the raw rows that makes each
        block when it reaches it, factors it and takes the first
        epoch's step on it (``ops.linalg.bcd_stream_factor``), then,
        where ``num_iter`` is over 1, a second program for the passes
        after the first (``bcd_stream_epochs``), which takes the
        factors, the weights and ``P`` from the first. A block is made
        ``num_iter`` times. Nothing here waits for the device. Returns
        the model and, beside it, the model's scores on ``rows``: the
        last sweep carries ``sum_i A_i W_i`` over exactly these rows and
        these blocks, so no block is made for them. The scores are the
        fit's, not the model's: it holds neither them nor the rows.

        The same numbers while every block's first factor is healthy,
        which the model's ``health`` says. A block whose Gram + lam I is
        numerically singular is recovered here by factoring it again
        with a raised diagonal, where ``_fit`` solves through
        ``clamped_eigh``: two sound answers some 1e-3 apart, not one
        (``ops/linalg.py``; a tier-1 test pins it). Data that needs
        neither, or a lambda over 0, makes the form a fit took
        invisible in its model."""
        rows, labels = ensure_array(rows), ensure_array(labels)
        branches, columns = _equal_blocks(branches)
        scale_eps = next((float(e.eps) for e in between
                          if e.normalize_std_dev), None)
        blocks, n = len(branches), rows.n
        dt = rows.data.dtype
        params = stack_branch_params(branches)
        y_mean = linalg.distributed_mean(labels.data, n)
        nf, lam = jnp.asarray(n, dt), jnp.asarray(float(self.lam), dt)
        held = int(rows.data.shape[0])
        chunked = dict(row_chunk=_row_chunk_of(held, self.block_size,
                                               dt.itemsize),
                       block_width=self.block_size)
        row_chunks = _chunks(held, chunked["row_chunk"])
        shape = dict(blocks=blocks, rows=n, block_width=self.block_size,
                     epochs=self.num_iter, row_chunks=row_chunks)
        counter = MetricsRegistry.get_or_create().counter
        more = self.num_iter - 1
        with flight_span("stream:factor", "solve", **shape):
            ((means, Ls, oks, ratios, *inv_stds), Ws, scores,
             *rows_solved) = _stream_program(
                "factor", branches[0], more, scale_eps=scale_eps, **chunked)(
                rows.data, params, labels.data, y_mean, rows.mask, nf, lam)
        if more:
            with flight_span("stream:epochs", "solve", **shape):
                Ws, scores = _stream_program(
                    "epochs", branches[0], more,
                    scale_eps=scale_eps is not None or None, **chunked)(
                    rows.data, params, labels.data, y_mean, rows.mask, means,
                    Ls, Ws, scores, *inv_stds)
        counter("solve.stream.blocks_generated").inc(blocks * self.num_iter)
        counter("solve.stream.fits").inc()
        counter("solve.stream.row_chunks").inc(row_chunks)
        counter("solve.stream.rows").inc(n)
        model = StreamedBlockLinearMapper(
            branches, Ws, means, y_mean, params=params, health=(oks, ratios),
            inv_stds=inv_stds[0] if inv_stds else None, columns=columns,
            rows_solved=rows_solved[0] if rows_solved else None)
        return model, ArrayDataset(scores, n, rows.mesh, _already_sharded=True)

    def _fit(self, ds: Dataset, labels: Dataset) -> BlockLinearMapper:
        ds, labels = ensure_array(ds), ensure_array(labels)
        registry = MetricsRegistry.get_or_create()
        registry.counter("solve.materialised.fits").inc()
        n, d = ds.n, ds.data.shape[1]
        k = labels.data.shape[1]
        bs = self.block_size
        bounds = [(i, min(d, i + bs)) for i in range(0, d, bs)]
        # where the design matrix lies, from its sharding alone: every
        # block step of a row-sharded fit sums its Gram and cross
        # product over the data shards inside the one solver program
        shards, fullest = row_shards(ds.data)
        registry.gauge("solve.data_shards").set(shards)
        registry.gauge("solve.shard_bytes_max").set(fullest)
        if shards > 1:
            registry.counter("solve.sharded.fits").inc()
            registry.counter("solve.allreduce_bytes").inc(
                block_solve_allreduce_nbytes(
                    bounds, k, self.num_iter, ds.data.dtype.itemsize))

        Ws, x_mean, y_mean = block_least_squares(
            ds.data, labels.data, n, float(self.lam), tuple(bounds),
            self.num_iter, mask=ds.mask)
        # blocks stay device-resident (see BlockLinearMapper.__init__)
        intercept = y_mean  # apply() centers x by the means, so b = y_mean
        return BlockLinearMapper(
            list(Ws), bs, intercept=intercept, feature_means=x_mean,
            weight_dtype=self.weight_dtype,
        )

    #: The scan-based BCD stages the whole multi-pass solve into ONE
    #: program (ops/linalg.py), so rounds do not scale with
    #: num_iter x num_blocks: ~51-65 ms fixed at 1..4 blocks x 3 passes
    #: in the round-5 calibration (not measured on this installation,
    #: ROADMAP S7).
    DISPATCH_ROUNDS = 3

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w,
             lat_w=0.0) -> float:
        """Reference cost model (BlockLinearMapper.scala:268-282) plus
        the TPU dispatch-latency term (see ``LinearMapEstimator.cost``);
        ``lat_w=0`` reproduces the reference surface exactly."""
        flops = n * d * (self.block_size + k) / num_machines
        bytes_scanned = n * d / num_machines + d * k
        network = 2.0 * (d * (self.block_size + k)) * np.log2(max(num_machines, 1))
        return self.num_iter * (
            max(cpu_w * flops, mem_w * bytes_scanned) + net_w * network
        ) + lat_w * self.DISPATCH_ROUNDS

    @staticmethod
    def compute_cost(
        blocks,
        labels,
        lam: float,
        block_weights: Sequence[np.ndarray],
        intercept: Optional[np.ndarray] = None,
    ) -> float:
        """Training objective for a block model (reference
        ``BlockLinearMapper.scala:144-187`` ``computeCost``):
        ||sum_i X_i W_i + b - L||_F^2 / (2n) + lam/2 * sum_i ||W_i||_F^2.
        ``blocks`` holds the per-block features (Datasets or arrays)."""
        blocks = list(blocks)
        assert blocks and len(blocks) == len(block_weights), (
            f"{len(blocks)} feature blocks vs {len(block_weights)} weight blocks"
        )
        labels = ensure_array(labels)
        partial = None
        for w, block in zip(block_weights, blocks):
            block = ensure_array(block)
            contrib = block.data @ jnp.asarray(w)
            partial = contrib if partial is None else partial + contrib
        b = (
            jnp.zeros((labels.data.shape[1],), jnp.float32)
            if intercept is None
            else jnp.asarray(intercept)
        )
        cost = float(
            _masked_sse(partial, labels.data, b, labels.mask)
        ) / (2.0 * labels.n)
        if lam != 0.0:
            cost += lam / 2.0 * float(
                sum(np.sum(np.asarray(w) ** 2) for w in block_weights)
            )
        return cost


def block_solve_allreduce_nbytes(bounds, k: int, num_iter: int,
                                 itemsize: int = 4) -> int:
    """Bytes one materialised block solve over ``bounds`` hands to the
    reduction between data shards, by shapes: the column means of the
    design matrix and of the labels; a block's Gram (what
    ``linalg.gram`` sums: ``gram_reduced_elems``) and its cross product
    in the first pass, the cross product alone in every later pass (the
    factors are kept). What each chip sends and receives for it depends
    on the collective's algorithm and is not counted here."""
    widths = [hi - lo for lo, hi in bounds]
    grams = sum(linalg.gram_reduced_elems(w) for w in widths)
    crosses = int(num_iter) * sum(w * k for w in widths)
    return itemsize * (grams + crosses + sum(widths) + k)


@functools.lru_cache(maxsize=None)
def _block_solve_for(mesh):
    """Jitted block solve, one trace cache per mesh (the
    ``_bcd_jit_for`` discipline): ``bcd_core`` reads the ambient mesh
    through ``_class_spec``, so a module-lifetime jit here baked the
    FIRST mesh's class-sharding constraints into the cached trace and
    silently replayed them under a second mesh at the same shapes —
    the dryrun_multichip(8) weighted-solver phase failure of round 6
    (an 8-device sharding constraint against 1-device arguments). The
    mesh parameter keys the cache; the caller passes the ambient mesh
    so each mesh gets its own trace. The cross-module
    ``mesh-closure-jit`` lint (analysis/diagnostics.py) now flags the
    old shape statically."""

    @functools.partial(jax.jit, static_argnames=("bounds", "num_iter"))
    def _block_solve(X, Y, x_mean, y_mean, mask, lam, bounds, num_iter):
        Yc = (Y - y_mean) * mask[:, None].astype(X.dtype)
        return linalg.bcd_core_columns(
            X, x_mean, mask, bounds, Yc, jnp.asarray(lam, X.dtype),
            num_passes=num_iter)

    return _block_solve


def block_least_squares(X, Y, n, lam, bounds, num_iter, mask=None):
    """Staged, jittable core of ``BlockLeastSquaresEstimator``: sharded
    column means + mean-centered block coordinate descent. Returns
    ``(per-block weights, x_mean, y_mean)``; prediction is
    ``(x - x_mean) @ concat(Ws) + y_mean``. The estimator's ``_fit``
    routes through this, so a caller that stages the solve into a larger
    jit runs exactly the production solver path."""
    from ...parallel.mesh import get_mesh

    if mask is None:
        mask = jnp.ones(X.shape[0], X.dtype)
    x_mean = linalg.distributed_mean(X, n)
    y_mean = linalg.distributed_mean(Y, n)
    solve = _block_solve_for(get_mesh())
    return (
        solve(X, Y, x_mean, y_mean, mask, lam, bounds, num_iter),
        x_mean,
        y_mean,
    )
