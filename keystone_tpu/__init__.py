"""keystone_tpu: a TPU-native large-scale ML pipeline framework.

A ground-up re-design of the capabilities of KeystoneML (AMPLab's
Spark/Scala pipeline framework, surveyed in SURVEY.md) for TPUs: type-safe
composable Transformer/Estimator pipelines over an optimizing DAG, executed
on `jax.sharding.Mesh` device meshes with XLA collectives instead of a
Spark cluster, with distributed linear algebra (normal equations, block
coordinate descent, TSQR) as sharded JAX programs and image/NLP feature
kernels as TPU-friendly ops.
"""
import time as _time

_T_IMPORT = _time.perf_counter()  # the startup:import span's start

from .observability import (  # noqa: E402
    MetricsRegistry,
    PipelineTrace,
    current_trace,
    xprof_trace,
)
from .parallel.dataset import ArrayDataset, Dataset, HostDataset, as_dataset
from .parallel.mesh import get_mesh, make_mesh, mesh_scope, set_mesh
from .parallel.streaming import StreamingDataset, fit_streaming, is_streamable
from .resilience import (
    FaultPlan,
    IngestTimeoutError,
    Quarantine,
    RetryPolicy,
)
from .workflow import (
    Cacher,
    Estimator,
    FittedPipeline,
    Identity,
    LabelEstimator,
    Pipeline,
    PipelineDataset,
    PipelineDatum,
    PipelineEnv,
    Transformer,
    transformer,
)

from .observability.timeline import record_startup as _record_startup

__version__ = "0.1.0"

__all__ = [
    "MetricsRegistry",
    "PipelineTrace",
    "current_trace",
    "xprof_trace",
    "ArrayDataset",
    "Dataset",
    "HostDataset",
    "StreamingDataset",
    "as_dataset",
    "fit_streaming",
    "is_streamable",
    "FaultPlan",
    "IngestTimeoutError",
    "Quarantine",
    "RetryPolicy",
    "get_mesh",
    "make_mesh",
    "mesh_scope",
    "set_mesh",
    "Cacher",
    "Estimator",
    "FittedPipeline",
    "Identity",
    "LabelEstimator",
    "Pipeline",
    "PipelineDataset",
    "PipelineDatum",
    "PipelineEnv",
    "Transformer",
    "transformer",
    "__version__",
]

_record_startup(_T_IMPORT)
