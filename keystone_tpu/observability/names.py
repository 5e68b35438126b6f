"""The metric-name catalogue: every process-metric name, in one place.

Prometheus dashboards, the benchmark's readers and exact checks, and
the telemetry sampler all address metrics BY NAME across process
boundaries — a renamed counter silently breaks every one of them (the
dashboard shows a flat zero, not an error). So the names are catalogued
here and the ``metric-name-drift`` AST pass (:func:`keystone_tpu.analysis.\
diagnostics.metric_name_drift`, enforced by ``tools/lint.py`` and
``python -m keystone_tpu check``) flags any
``counter(...)``/``gauge(...)``/``histogram(...)``/``timer(...)`` call
site whose literal name is not listed below. Renaming a metric is a
two-line change (the call site and this catalogue), and therefore a
reviewable one.

Families with a dynamic tail (``resilience.<event>``,
``lock.wait_s.<lock name>``) are catalogued as PREFIXES: the pass
checks an f-string's literal head against :data:`METRIC_PREFIXES`.
Fully dynamic names (a bare variable) are uncheckable and pass through
— keep those inside the observability layer itself
(``MetricsRegistry.timer`` forwarding to ``histogram(name)``).
"""
from __future__ import annotations

from typing import FrozenSet, Tuple

#: exact metric names (counters, gauges, histograms) the tree may use
METRIC_NAMES: FrozenSet[str] = frozenset({
    # workflow/executor.py — always-on DAG executor counters
    "executor.nodes_executed",
    "executor.memo_hits",
    "executor.prefix_hits",
    # workflow/operators.py — delegating nodes answered with what a fit
    # held on the very rows it was fitted on (no apply, no block made)
    "executor.fit_outputs_reused",
    # workflow/graph.py — what building a pipeline's graph writes (PR 39):
    # calls that add to a graph (``add_node`` / ``add_source`` /
    # ``add_sink`` / ``add_graph`` / ``connect_graph`` under every ``>>``,
    # ``and_then`` and ``bind`` / ``fan_out`` under ``Pipeline.gather``),
    # and the entries (operators, dependency tuples, sources, sinks) of the
    # containers each made, copies of what was there included: a fit's
    # composition grows with its nodes, not with nodes x branches
    "dag.compose.calls",
    "dag.compose.entries",
    # parallel/dataset.py — the resident (ArrayDataset) path of every
    # fit app: bytes put on the device from host rows, and bytes pulled
    # back where the host stops to wait (the ``ingest:h2d`` and
    # ``wait:d2h`` spans carry the same numbers per call)
    "ingest.h2d_bytes",
    "egress.d2h_bytes",
    # nodes/learning/linear.py — which form of the block solve a fit
    # took (the optimizer's choice, ``optimizer/stream_gather.py``), and
    # how many feature blocks the streamed form made: blocks x (1 +
    # epochs) a fit, blocks more for every blockwise apply of the model
    "solve.stream.fits",
    "solve.materialised.fits",
    "solve.stream.blocks_generated",
    # ... and, a streamed fit, the chunks its rows were taken in (1
    # where a block of all rows fits the device: ``analysis.resources.
    # stream_row_chunk``) and the rows it was fitted on (what each
    # block's Gram summed; a chunked fit counts them on the device too:
    # ``StreamedBlockLinearMapper.rows_solved``)
    "solve.stream.row_chunks",
    "solve.stream.rows",
    # nodes/learning/linear.py — where a materialised fit's design
    # matrix lay, read off its sharding (PR 38): the row shards of the
    # last fit (gauge; 1 on one chip, and for a matrix held whole on
    # every chip), the bytes of it on the fullest device (gauge), fits
    # whose matrix lay on more than one shard, and what their block
    # steps handed to the reduction between shards, by shapes
    # (``block_solve_allreduce_nbytes``)
    "solve.data_shards",
    "solve.shard_bytes_max",
    "solve.sharded.fits",
    "solve.allreduce_bytes",
    # ops/linalg.py — which form of the materialised block solve the
    # shapes chose, raised once per trace of ``bcd_core_columns`` /
    # ``bcd_core`` (static: equal widths and at least 4 blocks sweep,
    # cutting each block out of the design matrix in place or choosing
    # it from a list; ragged or fewer blocks unroll)
    "solve.bcd.sliced",
    "solve.bcd.listed",
    "solve.bcd.unrolled",
    # nodes/stats PaddedFFT — which way the half-spectrum was taken,
    # raised once per trace of ``apply`` (the choice is static: the
    # padded length against DENSE_MAX_PADDED)
    "featurize.padded_fft.dense",
    "featurize.padded_fft.fft",
    # nodes/images/core.py FusedConvRectifyPool — which maker a block of
    # the streamed solve took, raised once per trace of
    # ``make_blocks_with_params`` (the
    # choice is ``use_pallas()``: the Pallas kernel on a TPU, the
    # composed XLA ops elsewhere)
    "featurize.conv_block.pallas",
    "featurize.conv_block.xla",
    # the same place, beside ``.pallas``: the kernel builds its patches
    # in VMEM from the images (no im2col operand in HBM), once a trace.
    # Raised in step with ``.pallas`` while the kernel has this one
    # form: it says nothing that counter does not, and tests alone read
    # it (ISSUE 42 asked for it; a tracing PR may take it out)
    "featurize.conv_patches.vmem",
    # the same place, by the patch positions an IMAGE at the traced
    # geometry: those the kernel lays out, builds and multiplies (some
    # pooling region covers them) and those it leaves out because none
    # does (PR 46; 196 and 165 at 24 x 24 crops, 729 and 0 at 32 x 32:
    # how far the mechanism engages in a cell)
    "featurize.conv_positions.kept",
    "featurize.conv_positions.left_out",
    # nodes/stats/sampling.py sample_indices — which form a seeded draw
    # took, once a call (the head of the legacy permutation without the
    # shuffle where the native library loads, NumPy's
    # ``RandomState.choice`` where it does not; the same indices either
    # way)
    "featurize.sample_draw.sparse",
    "featurize.sample_draw.dense",
    # nodes/stats/sampling.py — sibling ``ColumnSampler`` nodes drawn
    # from ONE making of a dataset (``SharedColumnSampler``, put there by
    # ``workflow/optimizer/column_samples.py``): rises by the number of
    # siblings served, once a shared pass
    "featurize.sample_pass.siblings",
    # the VOC featurizers (PR 33). ops/sift.py, nodes/images/extractors.py:
    # every image passed through dense SIFT (a training image up to three
    # times a fit), and a chunk's program, once a trace
    "featurize.sift.images",
    "featurize.sift.einsum",
    # nodes/images/fisher_vector.py: images encoded, and the form, once a
    # trace (the fused Pallas kernel, or posteriors through HBM)
    "featurize.fv.images",
    "featurize.fv.pallas",
    "featurize.fv.einsum",
    # nodes/learning/pca.py column fits; nodes/learning/gmm.py fits and
    # the EM steps they computed
    "featurize.pca.fits",
    "featurize.gmm.fits",
    "featurize.gmm.iterations",
    # parallel/streaming.py — streamed-ingest telemetry
    "streaming.ingest_stall_s",
    "streaming.prefetch_occupancy",
    "streaming.chunks_total",
    "streaming.h2d_bytes",
    "streaming.resident_bytes",
    "streaming.carry_bytes",
    # utils/guarded.py — lock-contention instrumentation
    "lock.contended_total",
    # observability/sampler.py — background sampler probes (exposed as
    # gauges so the Prometheus endpoint scrapes them)
    "process.rss_bytes",
    "h2d.pool_queue_depth",
    # observability/compilelog.py — the compile observatory (PR 9):
    # every XLA compile counted and timed; compiles recorded while a
    # warmup fence is armed are runtime recompiles, i.e. bugs
    "compile.count",
    "compile.wall_s",
    "compile.unexpected_total",
    # programs read from / written to jax's persistent compilation cache
    # (PR 36): the hit ratio after a deploy
    "compile.cache_hits",
    "compile.cache_misses",
    # observability/numerics.py — the data/math-health plane (PR 10).
    # The event-counter family (`numerics.<event>`: nonfinite,
    # breakdown, drift_warn, ...) rides the `numerics.` prefix below;
    # these are the non-event scalars dashboards address directly.
    "numerics.health_words",     # counter: chunk/node health words pulled
    "numerics.nan_total",        # counter: non-finite values detected
    "numerics.inf_total",
    "numerics.solves_total",     # counter: instrumented solver solves
    "numerics.breakdown_total",  # counter: Cholesky breakdowns (== eigh
                                 # fallback recoveries taken)
    "numerics.pivot_ratio",      # histogram: scale-free min L_ii/sqrt(G_ii)
    "numerics.residual_rel",     # histogram: per-solve relative residual
    "numerics.drift_score",      # gauge: latest apply-vs-fit PSI max
    "numerics.health_age_s",     # gauge (sampler probe): seconds since
                                 # the last health word was pulled
    "numerics.quant_rel_error",  # gauge: max relative dequantization
                                 # error of the most recently narrowed
                                 # weight matrix (weight_dtype predict)
    # parallel/distributed.py — cross-host chunk-step coordination
    # (PR 11): the elastic multi-host streamed-fit plane
    "coord.world_size",      # gauge: jax process count of the live world
    "coord.rounds_total",    # counter: coordination rounds completed
    "coord.barrier_wait_s",  # histogram: time spent waiting for peers
                             # at a round boundary / named barrier — a
                             # persistently hot host here is a straggler
    "coord.overlap_occupancy",  # gauge: 1 - blocked-await wall over
                             # round wall under the overlapped round
                             # loop (PR 18) — 1.0 means coordination is
                             # fully hidden behind accumulate compute,
                             # 0.0 means every round blocks (the old
                             # synchronous floor)
    # keystone_tpu/serving — the low-latency multi-tenant serving plane
    # (PR 15). Catalogued from day one: these names cross the scrape
    # surface into dashboards AND the serving CI gate reads them back
    # from /metrics (tools/serving_gate.py), so a rename breaks both.
    "serving.requests_total",    # counter: requests served (one per
                                 # submitted request, not per batch)
    "serving.rows_total",        # counter: items (rows) served
    "serving.batches_total",     # counter: micro-batches executed
    "serving.rejected_total",    # counter: submits refused at the slot
                                 # gate (bounded queue full — the
                                 # backpressure signal)
    "serving.errors_total",      # counter: batches that raised
    "serving.evictions_total",   # counter: models evicted for HBM space
    "serving.admission_rejected_total",  # counter: admissions refused
                                 # (over the HBM budget even after
                                 # every allowed eviction)
    "serving.queue_depth",       # gauge: pending requests behind the
                                 # slot gate at last submit/take
    "serving.models_resident",   # gauge: warm device-resident models
    "serving.models_warming",    # gauge: admissions mid-warmup
    "serving.hbm_budget_bytes",  # gauge: the configured residency budget
    "serving.hbm_charged_bytes",  # gauge: admission-charged bytes
                                 # (model_nbytes + bucket activation
                                 # bound, analysis/resources.py)
    "serving.request_ms",        # histogram: per-request latency,
                                 # enqueue -> result (all models; the
                                 # per-model family rides the prefix)
    "serving.batch_ms",          # histogram: device execution wall per
                                 # micro-batch
    "serving.batch_fill",        # histogram: true rows / bucket rows of
                                 # each executed micro-batch (all
                                 # models; per-model family below)
    "serving.warmup_s",          # histogram: per-admission warmup wall
                                 # (every bucket compiled, fence-clean)
    # keystone_tpu/observability/slo.py — the request-path SLO plane
    # (PR 16): rolling-window error-budget accounting over the serving
    # traffic; the serving gate and the /slo endpoint read these back
    "serving.availability",      # gauge: aggregate rolling good-request
                                 # fraction (per-model family below)
    "serving.error_budget_burn_rate",  # gauge: bad fraction over the
                                 # allowed bad fraction (1.0 = exactly
                                 # on target; per-model family below)
    "serving.slo_violations_total",  # counter: windows that crossed the
                                 # availability target (one post-mortem
                                 # each)
    # graceful degradation under chaos (PR 19): the shed/poison verdict
    # counters the chaos gate and dashboards read back — a deadline
    # shed or a poisoned batch that doesn't move a counter is silent
    # damage
    "serving.deadline_expired_total",  # counter: requests whose
                                 # deadline expired while queued —
                                 # failed BEFORE dispatch, zero device
                                 # time burned
    "serving.shed_total",        # counter: requests shed at batch
                                 # formation (currently == deadline
                                 # sheds; kept separate so future
                                 # load-shedding policies share the
                                 # dashboard line)
    "serving.poisoned_batches_total",  # counter: batches whose outputs
                                 # came back non-finite — the whole
                                 # batch fails classified (500 +
                                 # post-mortem), the worker survives
    # the serving fleet (PR 20): queue-wait is the one measured
    # congestion signal the router's spill eligibility, the autoscaler,
    # and the bench fleet line all share (satellite: "attack the 0.65
    # serve_queue_wait_share")
    "serving.queue_wait_s",      # histogram: seconds a request spent
                                 # queued, enqueue -> coalesce start
                                 # (per-model family rides the prefix)
    # serving/router.py — the fleet front door. Every refusal is a
    # counted, classified verdict: an unavailable fleet answers 503
    # with Retry-After, never an unclassified error.
    "router.requests_total",     # counter: requests the router fronted
    "router.spill_total",        # counter: requests NOT served by their
                                 # rendezvous-primary replica (spilled
                                 # to the least-loaded eligible one on
                                 # queue depth / refusal)
    "router.rebalance_total",    # counter: model migrations completed
                                 # (admit on target -> verify canonical
                                 # bytes -> evict on source)
    "router.unavailable_total",  # counter: requests refused 503 — no
                                 # eligible replica hosted the model
    "router.replicas_live",      # gauge: replicas passing health probes
    "fleet.models_placed",       # gauge: (model, replica) assignments
                                 # in the live placement
    "fleet.replica_deaths_total",  # counter: replicas declared dead and
                                 # re-placed around
})

#: catalogued name FAMILIES: a dynamic metric name must start with one
#: of these literal heads (``f"resilience.{event}"`` is fine; a bare
#: ``f"{x}"`` is not checkable and is flagged)
METRIC_PREFIXES: Tuple[str, ...] = (
    "resilience.",   # resilience/events.py: one counter per event kind
    "lock.wait_s.",  # utils/guarded.py: one histogram per traced lock
    "numerics.",     # observability/numerics.py: one counter per
                     # numerics event kind (record_numerics_event)
    # serving/plane.py: the per-MODEL latency/fill families
    # (f"serving.request_ms.{model}"). Deliberately the narrow
    # families rather than a blanket "serving." prefix — a typo'd
    # literal serving counter name must still fail the drift lint.
    "serving.request_ms.",
    "serving.batch_fill.",
    # the request-path plane (PR 16), same narrow-family rule:
    "serving.phase_ms.",         # tail attribution histograms —
                                 # f"serving.phase_ms.{phase}" aggregate
                                 # and f"...{phase}.{model}" per model
    "serving.rejected_total.",   # per-model 429 accounting (a rejection
                                 # storm names its model)
    "serving.availability.",     # per-model rolling availability gauges
    "serving.error_budget_burn_rate.",  # per-model burn-rate gauges
    "serving.queue_wait_s.",     # per-model queued-time family (the
                                 # router's spill signal, PR 20)
    "slo.",                      # observability/slo.py: one counter per
                                 # SLO event kind (record_slo_event)
    "placement.",                # serving/placement.py: solver
                                 # accounting (placement.solves_total,
                                 # placement.replicated_models,
                                 # placement.migrations_planned) — one
                                 # family, like "chaos." below
    "router.spill_total.",       # per-model spill family: a spill storm
                                 # names its model (PR 20)
    "chaos.",                    # serving/scenarios: chaos-suite run
                                 # accounting (chaos.runs_total,
                                 # chaos.injections_total,
                                 # chaos.violations_total,
                                 # chaos.clean_total) — one family so
                                 # new scenarios don't each touch the
                                 # catalogue
)


#: flight-recorder span categories (the ``cat`` of a span; a profiler
#: capture shows a span as ``ks:<cat>:<name>``). The first five are the
#: layer boundaries of the fit path, named as PERF.md section 3 names
#: the layers; the rest are the subsystems that feed the ring.
SPAN_CATEGORIES: FrozenSet[str] = frozenset({
    "dag",         # workflow/: dag:optimize, dag:rules:<batch>,
                   # dag:node:<label>#<id> (was "node", traced runs only)
    "solve",       # solve:fit:<Estimator class> (args data_shards,
                   # rows_a_shard of the rows it fits on); under it, for a fit
                   # from branches, solve:stream:factor (also the first
                   # epoch) and, past one epoch, solve:stream:epochs
    "apply",       # apply:stream — the blockwise apply of such a model
    "featurize",   # featurize:draw — random branch featurizers drawn on
                   # the host (CosineRandomFeatures.create_branches);
                   # featurize:learn_filters — RandomPatchCifar's patch
                   # sample, ZCA whitener and filter bank (patches, filters);
                   # featurize:augment — RandomPatchCifarAugmented's crops
                   # and flips made on the device (rows, crops)
    "ingest",      # ingest:h2d (args nbytes, data_shards, rows_a_shard),
                   # ingest:reshard; stage:/stall: of streams
    "wait",        # wait:d2h — the host blocks on the device
    "eval",        # eval:evaluate; eval:vote — augmented copies'
                   # scores averaged an image on the host (rows, groups)
    "h2d",         # per-shard puts on the keystone-h2d pool lanes
    "compute",     # accumulate:<tag> of a streamed fit
    "compile",     # compile:<site>, after the fact
    "startup",     # startup:import — the package's own import, pinned
    "lock",        # contended TracedLock acquires
    "coord",       # multi-host rounds and barriers
    "serving",     # request:/batch: spans (deferred)
    "resilience",  # instants
    "numerics",    # instants
    "slo",         # instants
})


def is_catalogued(name: str) -> bool:
    """True when a LITERAL metric name is in the catalogue (exact, or
    under a catalogued prefix family)."""
    return name in METRIC_NAMES or any(
        name.startswith(p) for p in METRIC_PREFIXES)


def is_catalogued_prefix(head: str) -> bool:
    """True when an f-string's literal head lands inside a catalogued
    prefix family (``"resilience."`` matches; so does the longer
    ``"lock.wait_s.stream."``)."""
    return bool(head) and any(
        head.startswith(p) for p in METRIC_PREFIXES)
