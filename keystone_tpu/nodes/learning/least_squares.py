"""Cost-model-driven least squares auto-solver (reference
``nodes/learning/LeastSquaresEstimator.scala``).

The flagship node-level optimization: choose among DenseLBFGS,
Sparsify -> SparseLBFGS, Densify -> BlockLeastSquares(1000, 3), and
Densify -> exact normal equations by evaluating each solver's cost model
at the observed workload shape (n, d, k, sparsity, num_machines).

The DEFAULT weights are TPU-calibrated on the bench chip (r5,
``tools/calibrate_cost_model.py``): seconds per solver-precision MXU
flop (floor-cancelled HIGHEST-gram rate), seconds per f32 element
streamed from HBM (floor-cancelled reduction), seconds per f32 element
over ICI (spec-derived; only matters multi-chip), and — the TPU-first
extension — seconds per serial device dispatch round (``lat_w``). The
latency term exists because on TPU the compute terms alone mis-rank
every small-d solve: measured end-to-end, BlockLS(1000,3) beats the
exact solver at (65536, 256) 38 ms vs 198 ms purely on dispatch
structure (the scan-based BCD is ONE program; the exact path is ~10
serial rounds), which no (cpu, mem) pair can express.

The reference's empirical calibration on 16x r3.4xlarge
(``LeastSquaresEstimator.scala:17,26-31``) is kept as
``REFERENCE_EC2_WEIGHTS`` for parity experiments; with those weights
and ``lat_weight=0`` the choice surface is the reference's exactly.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ...observability.trace import current_trace
from ...parallel.dataset import ArrayDataset, Dataset
from ...workflow.optimizable import NodeChoice, OptimizableLabelEstimator
from ..util import Densify
from ..util.sparse import SparseVector, Sparsify
from .lbfgs import DenseLBFGSwithL2, SparseLBFGSwithL2
from .linear import BlockLeastSquaresEstimator, LinearMapEstimator

#: Measured 2026-07-31 (round 5) on a ``TPU v5 lite`` reached through a
#: remote-device plug-in under jax 0.4.37, by
#: ``python tools/calibrate_cost_model.py`` (model-vs-measurement
#: agreement 3/3 shapes). cpu: floor-cancelled HIGHEST-precision gram
#: rate; mem: floor-cancelled HBM reduction stream; net: ICI spec; lat:
#: measured per-dispatch-round latency.
#:
#: ``DEFAULT_LAT_WEIGHT`` is known to be too high for this installation:
#: it carries that plug-in's ~18-20 ms dispatch floor, where a dispatch
#: round trip reads 1.5 ms on the local chip under jax 0.9.0 (my chip
#: run, PR 21). So these defaults can over-prefer few-dispatch solvers
#: (e.g. BlockLS). They stay until ROADMAP S7 recalibrates on the chip
#: with fit cells to be judged on. They are the *fallback*:
#: ``python tools/calibrate_cost_model.py`` writes a calibration
#: artifact (JSON with timestamp + hostname) that this module loads in
#: preference to the shipped values (see :func:`load_calibration`), and
#: whose provenance the observability layer reports with every solver
#: decision.
DEFAULT_CPU_WEIGHT = 5.090e-15
DEFAULT_MEM_WEIGHT = 3.543e-11
DEFAULT_NETWORK_WEIGHT = 4.0e-11
DEFAULT_LAT_WEIGHT = 1.442e-2

#: Where ``tools/calibrate_cost_model.py`` writes its artifact and where
#: :func:`load_calibration` looks by default; override with the
#: ``KEYSTONE_COST_CALIBRATION`` environment variable.
CALIBRATION_ENV = "KEYSTONE_COST_CALIBRATION"
DEFAULT_CALIBRATION_PATH = os.path.join(
    os.path.expanduser("~"), ".keystone_tpu", "cost_model_calibration.json")

_WEIGHT_KEYS = ("cpu_weight", "mem_weight", "network_weight", "lat_weight")

#: resolved-path -> (weights, provenance); the artifact is tiny but read
#: once per estimator construction otherwise
_CALIBRATION_CACHE: Dict[str, Tuple[Dict[str, float], Dict]] = {}


def _shipped_weights() -> Dict[str, float]:
    return {
        "cpu_weight": DEFAULT_CPU_WEIGHT,
        "mem_weight": DEFAULT_MEM_WEIGHT,
        "network_weight": DEFAULT_NETWORK_WEIGHT,
        "lat_weight": DEFAULT_LAT_WEIGHT,
    }


def load_calibration(
        path: Optional[str] = None,
) -> Tuple[Dict[str, float], Dict]:
    """Resolve the cost-model weights and their provenance.

    Returns ``(weights, provenance)`` where weights come from the
    calibration artifact written by ``tools/calibrate_cost_model.py``
    when one is present and valid (all four weights finite, compute
    weights positive), and otherwise fall back to the shipped
    ``DEFAULT_*`` values. ``provenance`` carries
    ``source`` (``"artifact"`` / ``"shipped_defaults"``) plus the
    artifact's timestamp/hostname/device so trace consumers can judge
    whether the weights match the deployment that produced a decision.
    """
    candidate = (path or os.environ.get(CALIBRATION_ENV)
                 or DEFAULT_CALIBRATION_PATH)
    cached = _CALIBRATION_CACHE.get(candidate)
    if cached is not None:
        return cached
    weights = _shipped_weights()
    provenance: Dict = {
        "source": "shipped_defaults",
        "note": ("measured 2026-07-31 on a TPU v5 lite behind a "
                 "remote-device plug-in (jax 0.4.37); lat_weight is "
                 "known to be too high for this installation (it "
                 "carries that plug-in's ~20 ms dispatch floor); "
                 "recalibration is ROADMAP S7"),
    }
    try:
        with open(candidate) as f:
            blob = json.load(f)
        parsed = {k: float(blob[k]) for k in _WEIGHT_KEYS}
        ok = all(np.isfinite(v) for v in parsed.values()) and all(
            parsed[k] > 0 for k in ("cpu_weight", "mem_weight",
                                    "network_weight")
        ) and parsed["lat_weight"] >= 0
        # the tool refuses to write low-agreement artifacts, but guard
        # against hand-made / older ones: weights whose recorded
        # model-vs-measurement agreement was <= half are not trustworthy
        agreement = str(blob.get("agreement", ""))
        if ok and "/" in agreement:
            try:
                hits, total = (int(p) for p in agreement.split("/", 1))
                ok = 2 * hits > total
            except ValueError:
                pass
        if ok:
            weights = parsed
            provenance = {
                "source": "artifact",
                "path": candidate,
                "timestamp": blob.get("timestamp"),
                "hostname": blob.get("hostname"),
                "device": blob.get("device"),
            }
        else:
            provenance["note"] = (
                f"calibration artifact {candidate} has out-of-range "
                "weights; using shipped defaults")
    except FileNotFoundError:
        pass
    except Exception as exc:  # malformed artifact: fall back loudly
        provenance["note"] = (
            f"calibration artifact {candidate} unreadable ({exc}); "
            "using shipped defaults")
    _CALIBRATION_CACHE[candidate] = (weights, provenance)
    return weights, provenance


def clear_calibration_cache() -> None:
    """Drop memoized calibration lookups (tests, recalibration)."""
    _CALIBRATION_CACHE.clear()

#: The reference's EC2 calibration (LeastSquaresEstimator.scala:17,
#: 26-31) — documented fallback, not the default: it encodes a 2015
#: CPU-cluster cost surface.
REFERENCE_EC2_WEIGHTS = {
    "cpu_weight": 3.8e-4,
    "mem_weight": 2.9e-1,
    "network_weight": 1.32,
    "lat_weight": 0.0,
}


def estimate_sparsity(sample: Dataset) -> float:
    """Mean fraction of active entries per item
    (reference ``LeastSquaresEstimator.scala:68``)."""
    items = sample.collect() if not isinstance(sample, ArrayDataset) else None
    if items is not None:
        fracs = []
        for it in items:
            if isinstance(it, SparseVector):
                fracs.append(it.nnz / max(it.size, 1))
            else:
                arr = np.asarray(it)
                fracs.append(np.count_nonzero(arr) / max(arr.size, 1))
        return float(np.mean(fracs)) if fracs else 1.0
    arr = np.asarray(sample.numpy())
    return float(np.count_nonzero(arr) / max(arr.size, 1))


def _item_dim(sample: Dataset) -> int:
    if isinstance(sample, ArrayDataset):
        return int(np.asarray(
            __import__("jax").tree_util.tree_leaves(sample.data)[0]
        ).shape[-1])
    first = sample.collect()[0]
    return first.size if isinstance(first, SparseVector) else int(
        np.asarray(first).shape[-1])


class LeastSquaresEstimator(OptimizableLabelEstimator):
    """Auto-selecting least-squares solver
    (reference ``LeastSquaresEstimator.scala:27-86``)."""

    def __init__(
        self,
        lam: float = 0.0,
        num_machines: Optional[int] = None,
        cpu_weight: Optional[float] = None,
        mem_weight: Optional[float] = None,
        network_weight: Optional[float] = None,
        num_iterations: int = 20,
        lat_weight: Optional[float] = None,
    ):
        # weights default to the per-host calibration artifact when one
        # exists, else the shipped defaults; explicit
        # arguments always win (and mark provenance as "explicit")
        calibrated, provenance = load_calibration()
        explicit = {
            "cpu_weight": cpu_weight,
            "mem_weight": mem_weight,
            "network_weight": network_weight,
            "lat_weight": lat_weight,
        }
        if any(v is not None for v in explicit.values()):
            provenance = {"source": "explicit", "overrides": sorted(
                k for k, v in explicit.items() if v is not None)}
        self.lam = lam
        self.num_machines = num_machines
        self.cpu_weight = (cpu_weight if cpu_weight is not None
                           else calibrated["cpu_weight"])
        self.mem_weight = (mem_weight if mem_weight is not None
                           else calibrated["mem_weight"])
        self.network_weight = (network_weight if network_weight is not None
                               else calibrated["network_weight"])
        self.num_iterations = num_iterations
        self.lat_weight = (lat_weight if lat_weight is not None
                           else calibrated["lat_weight"])
        self._weight_provenance = provenance  # underscore: not in eq_key

    @property
    def options(self) -> Sequence[Tuple[object, NodeChoice]]:
        """(cost-model solver, choice) pairs
        (reference ``LeastSquaresEstimator.scala:36-53``)."""
        dense = DenseLBFGSwithL2(
            lam=self.lam, num_iterations=self.num_iterations)
        sparse = SparseLBFGSwithL2(
            lam=self.lam, num_iterations=self.num_iterations)
        block = BlockLeastSquaresEstimator(1000, 3, lam=self.lam)
        exact = LinearMapEstimator(lam=self.lam)
        return [
            (dense, NodeChoice(dense, (Densify(),))),
            (sparse, NodeChoice(sparse, (Sparsify(),))),
            (block, NodeChoice(block, (Densify(),))),
            (exact, NodeChoice(exact, (Densify(),))),
        ]

    @property
    def default(self):
        return DenseLBFGSwithL2(
            lam=self.lam, num_iterations=self.num_iterations)

    @property
    def weight(self) -> int:
        return self.default.weight

    def abstract_fit(self, dep_specs):
        from ...analysis.spec import labels_width_fit

        return labels_width_fit(dep_specs)

    # -- static HBM planning (analysis.resources) --------------------------
    def carry_nbytes(self, dep_specs):
        # every gram-capable candidate finalizes from the one shared
        # Gram/cross carry, so the carry bound is solver-independent
        from ...analysis.resources import gram_carry_nbytes

        return gram_carry_nbytes(dep_specs)

    def fitted_nbytes(self, dep_specs):
        from ...analysis.resources import linear_model_nbytes

        return linear_model_nbytes(dep_specs)

    def _fit(self, ds: Dataset, labels: Dataset):
        # fallback path when the node-level optimizer has not sampled:
        # densify host sparse data for the dense default
        if not isinstance(ds, ArrayDataset):
            ds = Densify().apply_dataset(ds)
        if not isinstance(labels, ArrayDataset):
            labels = Densify().apply_dataset(labels)
        return self.default._fit(ds, labels)

    # -- streaming fit (accumulate/finalize protocol) ----------------------
    def accumulate(self, carry, chunk, labels):
        """Streamed fits share the linear family's Gram/cross carry;
        every Gram-capable candidate solver can finalize from it, so the
        solver choice is deferred to :meth:`finalize` (where n, d, k are
        all known exactly — no sampling, no extra pass)."""
        from .linear import accumulate_gram_carry

        return accumulate_gram_carry(carry, chunk, labels)

    def finalize(self, carry):
        """Cost-model choice over the GRAM-CAPABLE solvers at the exact
        accumulated workload shape, via the SAME ``_choose`` surface the
        optimizer uses (``streaming=True`` filters ``self.options`` to
        solvers that can finalize from the one-pass carry — the LBFGS
        candidates need per-pass data access a stream cannot provide).
        The decision rides the active trace with ``shape_source:
        "streamed"`` and ``streaming_restricted: true``."""
        from ...parallel.distributed import process_count
        from ...parallel.mesh import get_mesh, num_data_shards

        G, C, _, _, n = carry
        d, k = int(G.shape[0]), int(C.shape[1])
        # same machine count the static/sampled optimizer paths use —
        # the cost surface must not shift between a streamed fit and a
        # graph-optimized fit of the identical workload. Under a live
        # multi-process world the workload really is spread over
        # nproc x local shards (each host accumulated its shard-local
        # stream), so the cost surface sees the GLOBAL machine count —
        # every host computes the same number and makes the same choice.
        machines = self.num_machines or (
            num_data_shards(get_mesh()) * process_count())
        choice = self._choose(n, d, k, 1.0, machines,
                              "streamed", streaming=True)
        return choice.node.finalize(carry)

    def optimize(self, sample: Dataset, sample_labels: Dataset, n: int,
                 num_machines: int) -> NodeChoice:
        d = _item_dim(sample)
        k = _item_dim(sample_labels)
        sparsity = estimate_sparsity(sample)
        return self._choose(n, d, k, sparsity,
                            self.num_machines or num_machines, "sampled")

    def optimize_static(self, spec, n: int, num_machines: int,
                        labels_spec=None) -> Optional[NodeChoice]:
        """Cost-model choice from statically inferred (n, d, k, sparsity)
        — no sampled execution, no device time. ``sparsity`` here is the
        analyzer's STRUCTURAL density (1.0 for dense-stored elements),
        not the sampled value-level density ``estimate_sparsity``
        measures; solvers for dense-stored data are ranked as dense.
        Declines (returns None -> sampling fallback) when any cost input
        is unresolved, e.g. sparse host elements of unknown density."""
        from ...analysis.spec import element_feature_dim

        d = element_feature_dim(spec)
        k = element_feature_dim(labels_spec) if labels_spec is not None \
            else None
        sparsity = getattr(spec, "sparsity", None)
        if d is None or k is None or sparsity is None:
            return None
        return self._choose(n, d, k, sparsity,
                            self.num_machines or num_machines, "static",
                            streaming=getattr(spec, "streaming", False))

    def _choose(self, n: int, d: int, k: int, sparsity: float,
                machines: int, shape_source: str,
                streaming: bool = False) -> NodeChoice:
        """``streaming=True`` restricts the surface to solvers that can
        fit from the one-pass Gram/cross carry (exact, BlockLS): the
        LBFGS candidates need repeated data passes a stream cannot
        provide, and the Sparsify prefix is a host stage — choosing
        either for a StreamingDataset would fail (or materialize) at
        fit time."""
        from ...parallel.streaming import is_streamable

        options = self.options
        if streaming:
            options = [(solver, choice) for solver, choice in options
                       if is_streamable(choice.node)]
        costs = [
            (solver.cost(n, d, k, sparsity, machines, self.cpu_weight,
                         self.mem_weight, self.network_weight,
                         lat_w=self.lat_weight), i)
            for i, (solver, _) in enumerate(options)
        ]
        _, best = min(costs)
        choice = options[best][1]
        trace = current_trace()
        if trace is not None:
            # the full decision surface: workload shape, every candidate's
            # cost estimate, the pick, where the weights came from, and
            # whether the shape was sampled or statically inferred — the
            # record that makes a silent solver mis-ranking visible
            trace.record_solver_decision({
                "estimator": type(self).__name__,
                "n": n, "d": d, "k": k,
                "sparsity": sparsity,
                "num_machines": machines,
                "costs": {
                    type(solver).__name__: cost
                    for (cost, i), (solver, _) in zip(costs, options)
                },
                "chosen": type(choice.node).__name__,
                "weights": {
                    "cpu_weight": self.cpu_weight,
                    "mem_weight": self.mem_weight,
                    "network_weight": self.network_weight,
                    "lat_weight": self.lat_weight,
                },
                "provenance": dict(self._weight_provenance),
                "shape_source": shape_source,
                **({"streaming_restricted": True} if streaming else {}),
            })
        return choice
