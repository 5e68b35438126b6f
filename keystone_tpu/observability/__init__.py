"""Observability layer: pipeline-wide tracing and process metrics.

The reference framework leans on its AutoCacheRule profiler plus the
Spark UI to answer "which node is slow, what did the optimizer decide,
and was it right?" (PAPER.md, whole-pipeline optimizer). This package is
the TPU port's equivalent, threaded through the workflow stack:

* :class:`MetricsRegistry` — process-wide counters / gauges / timing
  histograms (executor memo hits, prefix-state loads, nodes executed).
* :class:`PipelineTrace` — a structured per-run trace recording, for
  every executed graph node: operator name, wall time (honest — device
  results are blocked on before the clock is read), output
  device-memory footprint, cache/prefix hit vs compute, and shard
  count; plus the optimizer's decision logs (which rules fired and
  their graph-size delta, the auto-cache rule's sampled profiles and
  selected cache set, and the node-level cost-model's per-solver cost
  estimates with calibration provenance).
* :func:`xprof_trace` — an XLA profiler (XProf/TensorBoard) capture
  and nothing else; the always-on span annotations (``ks:<cat>:<name>``,
  :mod:`.timeline`) carry pipeline-level operator names into it.

Two modes: the layer-boundary spans of the fit path are recorded in
every run and never block; the blocking per-node measurements of a
:class:`PipelineTrace` happen only while one is active (every such
site first checks :func:`current_trace`).

PR 8 grew the package into a full telemetry plane:

* :mod:`.timeline` — the always-on :class:`FlightRecorder` span ring
  buffer with Chrome-trace/Perfetto export (``--trace-out
  run.perfetto.json``).
* :mod:`.sampler` — the background :class:`TelemetrySampler` plus the
  Prometheus scrape endpoint (:func:`serve_metrics`,
  ``MetricsRegistry.to_prometheus``).
* :mod:`.postmortem` — crash dumps of recorder + metrics, attached to
  the failure exceptions.
* :mod:`.names` — the metric-name catalogue the ``metric-name-drift``
  lint enforces.

PR 9 added the hardware denominator:

* :mod:`.compilelog` — the compile observatory: every XLA compile
  counted, timed, attributed to a named jit site, and classified
  (first-compile / signature-change / mesh-change); a warmup fence
  turns any later compile into an *unexpected* recompile
  (``compile.unexpected_total``), the dynamic complement of the static
  recompile-hazard lints.
* :mod:`.utilization` — MFU / roofline accounting from per-executable
  ``cost_analysis()``/``memory_analysis()`` against a per-device-kind
  peak catalogue (``*_mfu`` / ``*_membw_util`` bench keys).

PR 16 added the request-path plane for the serving era:

* :mod:`.reqtrace` — per-request span trees through the micro-batcher:
  a process-unique trace id minted at submit, phase timestamps at each
  lifecycle edge (queue_wait / coalesce / dispatch / respond, summing
  exactly to ``serving.request_ms``), Chrome-trace flow links from
  request spans into their coalesced batch span, and the bounded
  slowest-N exemplar reservoir behind ``GET /debug/slow``.
* :mod:`.slo` — error-budget accounting: :class:`SloPolicy` evaluated
  over rolling per-model windows, availability / burn-rate gauges, and
  one post-mortem per violated window (model + window + exemplar span
  trees embedded).

PR 10 added the third plane — the NUMBERS, not the machine:

* :mod:`.numerics` — on-device tensor-health words (finite/NaN/Inf
  counts, bounds, moments) piggybacked on streamed chunks and traced
  node outputs with a deferred D2H pull; :class:`NumericsError`
  tripwires through post-mortems; the solver conditioning ledger
  (``numerics.breakdown`` events, pivot-ratio/residual histograms);
  and PSI distribution-drift scoring of apply-time inputs against a
  fit-time feature sketch (:class:`DriftBaseline`,
  :func:`score_drift`) that rides checkpoints and fitted models.
"""
from .compilelog import (
    CompileObservatory,
    compile_context,
    compile_observatory,
    expect_no_compiles,
    observed_jit,
    reset_compile_observatory,
    watch_jit,
)
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, StepTimer
from .numerics import (
    DriftBaseline,
    NumericsError,
    health_word,
    numerics_enabled,
    numerics_suppressed,
    record_numerics_event,
    score_drift,
)
from .postmortem import attach_postmortem, dump_postmortem
from .reqtrace import (
    ExemplarReservoir,
    ReqTrace,
    exemplar_reservoir,
    reset_exemplars,
    tracing_active,
    tracing_suppressed,
)
from .sampler import TelemetrySampler, serve_metrics
from .slo import SloPolicy, SloTracker, SloViolation, record_slo_event
from .timeline import (
    FlightRecorder,
    flight_recorder,
    record_span,
    write_trace_artifact,
)
from .trace import (
    NodeRecord,
    PipelineTrace,
    current_trace,
    xprof_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StepTimer",
    "NodeRecord",
    "PipelineTrace",
    "current_trace",
    "xprof_trace",
    "FlightRecorder",
    "flight_recorder",
    "record_span",
    "write_trace_artifact",
    "TelemetrySampler",
    "serve_metrics",
    "attach_postmortem",
    "dump_postmortem",
    "CompileObservatory",
    "compile_context",
    "compile_observatory",
    "expect_no_compiles",
    "observed_jit",
    "reset_compile_observatory",
    "watch_jit",
    "ExemplarReservoir",
    "ReqTrace",
    "exemplar_reservoir",
    "reset_exemplars",
    "tracing_active",
    "tracing_suppressed",
    "SloPolicy",
    "SloTracker",
    "SloViolation",
    "record_slo_event",
    "DriftBaseline",
    "NumericsError",
    "health_word",
    "numerics_enabled",
    "numerics_suppressed",
    "record_numerics_event",
    "score_drift",
]
