"""Prometheus metric registry helpers.

The reference keeps one module-global registry and needs idempotent metric
creation because tests build several services per process (reference:
src/service/core.py:45-52 scans ``REGISTRY._collector_to_names``). We keep a
private name → collector map instead: every series this package emits is
declared below via ``_series`` and created exactly once through
``get_or_create``, whose cache — not private prometheus_client registry
state — is the authority for "already exists".

``REGISTERED_SERIES`` maps every declared exposition name to its metric
class; tests/test_observability.py derives the dashboard-sync known-series
set from it, so a new series here is automatically held to dashboard
coverage.

Metric names and label sets are the reference's observable contract
(reference: src/service/core.py:24-61, src/service/features/engine.py:14-54,
docs/prometheus.md:29-47) and must not change.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Sequence, Type

from prometheus_client import Counter, Enum, Gauge, Histogram

_LOCK = threading.Lock()
_CACHE: Dict[str, object] = {}


def get_or_create(
    metric_cls: Type,
    name: str,
    documentation: str,
    labelnames: Sequence[str] = (),
    **kwargs,
):
    """Return the process-wide collector for ``name``, creating it once.

    All of this package's metric creation funnels through here under one
    lock, so our ``_CACHE`` is the single source of truth — a duplicate
    ``ValueError`` from prometheus_client would mean some *other* code
    registered the name first, which is a real conflict to surface, not one
    to paper over by scanning the registry's private state."""
    with _LOCK:
        found = _CACHE.get(name)
        if found is not None:
            return found
        metric = metric_cls(name, documentation, labelnames=labelnames, **kwargs)
        _CACHE[name] = metric
        return metric


# -- reference metric contract (labels: component_type, component_id) -------
LABELS = ("component_type", "component_id")

# every exposition name this package can emit → metric class; the declared
# lambda registry tests iterate (see module docstring)
REGISTERED_SERIES: Dict[str, Type] = {}


def _series(metric_cls: Type, name: str, documentation: str,
            labelnames: Sequence[str] = LABELS, **kwargs) -> Callable:
    REGISTERED_SERIES[name] = metric_cls
    return lambda: get_or_create(metric_cls, name, documentation,
                                 labelnames, **kwargs)


# engine-owned series (reference: engine.py:14-54)
DATA_READ_BYTES = _series(Counter, "data_read_bytes_total", "Bytes read from the engine socket")
DATA_READ_LINES = _series(Counter, "data_read_lines_total", "Lines read from the engine socket")
DATA_WRITTEN_BYTES = _series(Counter, "data_written_bytes_total", "Bytes written to outputs")
DATA_WRITTEN_LINES = _series(Counter, "data_written_lines_total", "Lines written to outputs")
DATA_DROPPED_BYTES = _series(Counter, "data_dropped_bytes_total", "Bytes dropped on slow/dead outputs")
DATA_DROPPED_LINES = _series(Counter, "data_dropped_lines_total", "Lines dropped on slow/dead outputs")
PROCESSING_ERRORS = _series(Counter, "processing_errors_total", "Exceptions raised by process()")

# service-owned series (reference: core.py:24-61)
ENGINE_RUNNING = _series(Enum, "engine_running", "Engine run state", states=["running", "stopped"])
ENGINE_STARTS = _series(Counter, "engine_starts_total", "Engine starts")
PROCESSING_DURATION = _series(
    Histogram,
    "processing_duration_seconds",
    "End-to-end process() duration",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0),
)
DATA_PROCESSED_BYTES = _series(Counter, "data_processed_bytes_total", "Bytes handed to process()")
DATA_PROCESSED_LINES = _series(Counter, "data_processed_lines_total", "Lines handed to process()")

# TPU-build additions: per-chip throughput (BASELINE.json north star asks the
# /metrics endpoint to report per-chip rates; new series, new 'device' label,
# existing series untouched)
DEVICE_LABELS = ("component_type", "component_id", "device")
# both tick when a device-path batch's scores are host-readable
# (jax_scorer._observe_batch): rows that arrived but are still held, and
# rows the host twin scored, are not in them
DEVICE_BATCHES = _series(Counter, "detector_device_batches_total", "Scored batches per device", DEVICE_LABELS)
DEVICE_LINES = _series(Counter, "detector_device_lines_total", "Scored lines per device", DEVICE_LABELS)
BATCH_SIZE_HIST = _series(
    Histogram,
    "detector_batch_size",
    "Dispatched micro-batch sizes",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
)
# fused native featurization (utils/matchkern dm_featurize_batch/_frames):
# native = rows the C kernel tokenized; fallback = rows it flagged for the
# exact-parity Python path (plus every row when the kernel is unavailable
# or native_featurize is off). fallback/(native+fallback) is the fraction
# of traffic NOT riding the fast path — a sustained rise means malformed
# or parity-hostile payloads are eating the featurization budget.
FEATURIZE_NATIVE_ROWS = _series(
    Counter, "featurize_native_rows_total",
    "Rows featurized by the native (C, row-parallel) kernel")
FEATURIZE_FALLBACK_ROWS = _series(
    Counter, "featurize_fallback_rows_total",
    "Rows featurized by the Python fallback path (kernel-flagged or kernel unavailable)")

# zero-copy host path (PR 7): which path decoded + serialized each parser
# row. native = the fused whole-row kernel OR the decode-span + native-emit
# hybrid; fallback = rows that crossed into pb2 objects (kernel-flagged
# strict failures, or the kernels unavailable / native_parse off). A
# sustained fallback rise means parity-hostile payloads are eating the
# parse budget — same reading as the featurize pair.
PARSE_NATIVE_ROWS = _series(
    Counter, "parse_native_rows_total",
    "Parser rows decoded and serialized by the native (C) host path")
PARSE_FALLBACK_ROWS = _series(
    Counter, "parse_fallback_rows_total",
    "Parser rows that fell back to the pb2 Python path (kernel-flagged or "
    "kernel unavailable)")
# shm zero-copy framing (engine/shm.py): frames the engine sent by
# reference into a shared-memory slot (mode=zero_copy) vs frames that
# copy-downgraded onto the wire (mode=copy — remote peer, oversized
# payload, or no free slot because a receiver is slow/dead). A copy-mode
# climb with zero_copy_framing on is the slow-receiver signal.
SHM_LABELS = ("component_type", "component_id", "mode")
SHM_FRAMES = _series(
    Counter, "shm_frames_total",
    "Frames sent through the zero-copy shm path (mode=zero_copy) or "
    "copy-downgraded (mode=copy) while zero_copy_framing is enabled",
    SHM_LABELS)

# pipeline tracing series (engine_trace: true — engine.py hop stamping).
# Stage dwell and transit are observed by every tracing stage; e2e only by
# the terminal stage (no forwarding outputs), so its count is the pipeline's
# completed-trace count, not a per-hop multiple.
_DWELL_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
PIPELINE_STAGE_DWELL = _series(
    Histogram,
    "pipeline_stage_dwell_seconds",
    "Frame time inside this stage: ingress recv to egress send",
    buckets=_DWELL_BUCKETS,
)
PIPELINE_TRANSIT = _series(
    Histogram,
    "pipeline_transit_seconds",
    "Wire + queue time from the upstream stage's send to this stage's recv",
    buckets=_DWELL_BUCKETS,
)
PIPELINE_E2E_LATENCY = _series(
    Histogram,
    "pipeline_e2e_latency_seconds",
    "Pipeline ingest to terminal-stage completion (terminal stage only)",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0),
)
INGRESS_BACKLOG = _series(
    Gauge,
    "engine_ingress_backlog",
    "Messages drained into the current dispatch burst; pinned at "
    "engine_batch_size means the ingress is saturated",
)
OUTPUT_SEND_BACKLOG = _series(
    Gauge,
    "output_send_backlog",
    "Output sockets currently waiting on a full peer queue",
)
SEND_BLOCKED_SECONDS = _series(
    Counter,
    "engine_send_blocked_seconds_total",
    "Seconds the engine thread spent waiting on a full peer queue inside "
    "the send path (the stretches in which output_send_backlog reads > 0)",
)

# self-diagnosis series (engine/health.py): the watchdog rolls the
# per-subsystem checks into one Enum per process and exports every
# registered loop's heartbeat age; ops/alerts.yml alerts on both (and the
# alert rules are pinned to this registry by tests/test_observability.py,
# the same both-directions discipline as the Grafana panels).
ENGINE_HEALTH_STATE = _series(
    Enum,
    "engine_health_state",
    "Watchdog roll-up of the per-subsystem health checks",
    states=["healthy", "degraded", "unhealthy"],
)
HEARTBEAT_LABELS = ("component_type", "component_id", "loop")
HEARTBEAT_AGE = _series(
    Gauge,
    "engine_heartbeat_age_seconds",
    "Seconds since the named loop last stamped its heartbeat",
    HEARTBEAT_LABELS,
)
BUILD_INFO_LABELS = ("version", "dm_feature_version", "dmt_feature_version")
BUILD_INFO = _series(
    Gauge,
    "dm_build_info",
    "Constant 1; the labels carry the deployed package version and the "
    "native kernels' feature versions",
    BUILD_INFO_LABELS,
)

# device-side observability (engine/device_obs.py): the XLA compile ledger
# attributes every backend compile to the dispatch bucket that triggered it
# (few compiled shapes is the TPU-serving contract — SURVEY.md hard part #2),
# and flags compiles that happen on the dispatch path AFTER warm-up completed
# as unexpected recompiles, the RecompileStorm alert signal.
XLA_LABELS = ("component_type", "component_id", "bucket", "backend")
XLA_COMPILES = _series(
    Counter,
    "scorer_xla_compiles_total",
    "XLA backend compiles, attributed to the batch bucket that triggered them",
    XLA_LABELS,
)
XLA_COMPILE_SECONDS = _series(
    Counter,
    "scorer_xla_compile_seconds_total",
    "Wall seconds spent in XLA backend compiles per bucket",
    XLA_LABELS,
)
XLA_RECOMPILES_UNEXPECTED = _series(
    Counter,
    "scorer_xla_recompiles_unexpected_total",
    "Compiles on the dispatch path after warm-up completed — each one "
    "stalls the engine loop for the full compile; a nonzero rate is a "
    "recompile storm (ops/alerts.yml RecompileStorm)",
)
# warm-start serving (dmwarm, PR 17): the cold-start contract. The warm-up
# gauge splits boot→first-score into its three phases — aot (the
# lower().compile() pass over the warm bucket set), cache_load (persistent-
# cache deserialization time folded into those compiles), device_put
# (params landing in HBM / mesh shards) — set once per boot, so a replica
# whose aot phase blows past the fleet norm is visible per-phase
# (ops/alerts.yml ReplicaColdStartSlow). The cache pair only moves while
# the persistent compile cache is armed (utils/profiling.py): hits are
# deserialized cache entries (jax's /jax/compilation_cache/cache_hits
# events), misses are real backend compiles whose result was written to
# the cache (cache_misses events) — a fleet whose replicas share a cache
# directory should see hits dominate from the second boot on.
WARMUP_PHASE_LABELS = ("component_type", "component_id", "phase")
SCORER_WARMUP_SECONDS = _series(
    Gauge,
    "scorer_warmup_seconds",
    "Wall seconds of the scorer's boot warm-up by phase: aot (warm-set "
    "lower+compile), cache_load (persistent-cache deserialization), "
    "device_put (params to HBM/mesh); set once per boot",
    WARMUP_PHASE_LABELS,
)
COMPILE_CACHE_HITS = _series(
    Counter,
    "compile_cache_hits_total",
    "Persistent compile-cache hits: compiles served by deserializing a "
    "cached executable instead of running XLA (only moves while the cache "
    "is armed)",
)
COMPILE_CACHE_MISSES = _series(
    Counter,
    "compile_cache_misses_total",
    "Persistent compile-cache misses: real XLA backend compiles that ran "
    "with the cache armed (each one then populates the shared dir)",
)
# HBM residency, refreshed AT SCRAPE TIME (Gauge.set_function bound to
# jax Device.memory_stats) — absent on backends without memory stats (CPU)
HBM_LABELS = ("component_type", "component_id", "device", "kind")
DEVICE_HBM = _series(
    Gauge,
    "device_hbm_bytes",
    "Device memory from jax Device.memory_stats(), kind=in_use|limit, "
    "read at scrape time",
    HBM_LABELS,
)

# per-dispatch batch telemetry (library/detectors/jax_scorer.py): occupancy
# is real rows / padded bucket rows (padding waste is 1 - occupancy); the
# queue-wait vs device-time split attributes each batch's latency to host
# queueing (upload workers / fit backlog) vs device compute + readback, with
# the host-CPU-twin path and the accelerator path as separate label values.
PATH_LABELS = ("component_type", "component_id", "path")
BATCH_OCCUPANCY = _series(
    Histogram,
    "detector_batch_occupancy",
    "Real rows / padded bucket size per dispatched batch (1.0 = no padding)",
    PATH_LABELS,
    buckets=(0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
)
BATCH_QUEUE_WAIT = _series(
    Histogram,
    "detector_queue_wait_seconds",
    "Dispatch-call to scoring-call-start wait per batch (worker queue / "
    "inline ~0)",
    PATH_LABELS,
    buckets=_DWELL_BUCKETS,
)
BATCH_DEVICE_SECONDS = _series(
    Histogram,
    "detector_device_seconds",
    "Scoring-call start to host-readable scores per batch (device compute "
    "+ readback on the device path; synchronous compute on the host path)",
    PATH_LABELS,
    buckets=_DWELL_BUCKETS,
)
BUCKET_LABELS = ("component_type", "component_id", "bucket", "path")
BUCKET_SELECTED = _series(
    Counter,
    "detector_bucket_selected_total",
    "Dispatches per compile bucket and scoring path (host CPU twin vs "
    "accelerator)",
    BUCKET_LABELS,
)

# open-loop load generation (loadgen/): the CLIENT-side view of the
# pipeline a load run drives. sent/received count the generator's traced
# frames and their contained lines; lost counts trace ids that never
# reached the collector after the settle window (loss, not filtering — the
# soak profiles are configured so every row flows through); the e2e
# histogram is client-observed latency measured from each frame's SCHEDULED
# arrival time (coordinated-omission guard), the external twin of
# pipeline_e2e_latency_seconds — their p99 gap is the ingress/egress blind
# spot (docs/walkthrough.md "read the client skew").
LOADGEN_SENT_FRAMES = _series(
    Counter, "loadgen_sent_frames_total",
    "Traced wire frames the open-loop load generator scheduled and sent")
LOADGEN_SENT_LINES = _series(
    Counter, "loadgen_sent_lines_total",
    "Lines (corpus rows) the open-loop load generator sent")
LOADGEN_RECEIVED_FRAMES = _series(
    Counter, "loadgen_received_frames_total",
    "Frames the load collector received at the pipeline sink")
LOADGEN_RECEIVED_LINES = _series(
    Counter, "loadgen_received_lines_total",
    "Lines the load collector received at the pipeline sink")
LOADGEN_LOST_TRACES = _series(
    Counter, "loadgen_lost_traces_total",
    "Sent trace ids never observed at the collector after the settle "
    "window — client-visible loss, the soak harness's loss==0 gate")
LOADGEN_E2E_LATENCY = _series(
    Histogram, "loadgen_e2e_latency_seconds",
    "Client-observed e2e latency: collector receive time minus the frame's "
    "scheduled (open-loop) arrival time",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0),
)
LOADGEN_OFFERED_RATE = _series(
    Gauge, "loadgen_offered_lines_per_s",
    "Configured open-loop arrival rate of the active load run (0 = idle)")
LOADGEN_SEND_LAG = _series(
    Gauge, "loadgen_send_lag_seconds",
    "How far the load sender is running behind its arrival schedule; "
    "sustained growth means the generator itself cannot source the "
    "offered rate (the scheduled stamps still keep latency honest)")

# replica-parallel serving tier (router/): one routing stage fanning frames
# across N scorer replicas. frames_total splits traffic by replica and the
# policy that picked it; replica_state is the supervisor's state machine
# (3=active, 2=recovering, 1=draining, 0=drained) — anything below 3 for
# long is the ReplicaDrainedSustained page; requeue_total counts frames
# resent to a healthy peer after a replica died holding them (at-least-once
# redelivery, the replica_kill soak's zero-loss mechanism); inflight is the
# unacked credit window per replica (pinned at router_credit_window means
# that replica is not draining its ingest).
REPLICA_LABELS = ("component_type", "component_id", "replica", "policy")
ROUTER_FRAMES = _series(
    Counter, "router_frames_total",
    "Frames the replica router dispatched, by replica and balancing policy",
    REPLICA_LABELS)
ROUTER_REPLICA_STATE = _series(
    Gauge, "router_replica_state",
    "Supervisor state per replica: 3=active, 2=recovering, 1=draining, "
    "0=drained",
    ("component_type", "component_id", "replica"))
ROUTER_REQUEUE = _series(
    Counter, "router_requeue_total",
    "Frames requeued to a healthy peer after their replica was drained "
    "while still holding them unacked (at-least-once redelivery)")
ROUTER_INFLIGHT = _series(
    Gauge, "router_inflight",
    "Unacked frames outstanding per replica (the credit window); pinned at "
    "router_credit_window means the replica is not draining its ingest",
    ("component_type", "component_id", "replica"))

# model lifecycle (rollout/): the dmroll subsystem's observable contract.
# Swaps count every cutover attempt by outcome (promoted / rolled_back /
# holdback / pinned / failed); shadow divergence is the per-row |candidate
# score - live score| while a canary shadows (the ModelCanaryDiverging
# signal — decision flips gate promotion separately, /admin/model has
# both); checkpoint age is computed at scrape time off the versioned
# store's manifest (a wedged trainer looks stale, ModelCheckpointStale);
# version info is a constant-1 gauge whose labels carry the live
# checkpoint version + model family (the fleet-skew view: one query shows
# which replica serves which version).
SWAP_LABELS = ("component_type", "component_id", "result")
MODEL_SWAPS = _series(
    Counter, "model_swaps_total",
    "Model hot-swap/cutover attempts by outcome: promoted, rolled_back, "
    "holdback (canary gate refused), pinned, failed",
    SWAP_LABELS)
MODEL_SHADOW_DIVERGENCE = _series(
    Histogram, "model_shadow_divergence",
    "Per-row |candidate - live| score delta while a candidate shadows",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 25.0))
MODEL_CHECKPOINT_AGE = _series(
    Gauge, "model_checkpoint_age_seconds",
    "Seconds since the rollout store's newest checkpoint was committed "
    "(read at scrape time; ages from manager start when none exists yet)")
MODEL_VERSION_LABELS = ("component_type", "component_id", "version", "model")
MODEL_VERSION_INFO = _series(
    Gauge, "model_version_info",
    "Constant 1; the labels carry the live model checkpoint version and "
    "model family (0 = the boot-time fit, never hot-swapped)",
    MODEL_VERSION_LABELS)

# drift & capacity observability (obs/): the dmdrift contract. Drift score
# compares the LIVE score distribution (the dmroll reservoir's paired
# rows+scores) against the baseline pinned at promote time: stat="ks" is
# the two-sample Kolmogorov–Smirnov statistic, stat="psi" the population
# stability index over baseline-quantile bins; features_over_threshold is
# how many token columns exceed the per-feature PSI ceiling — together the
# ModelDriftSustained signal. Capacity is the calibrated per-replica
# throughput model (busy-time arithmetic while traffic flows, a bounded
# idle micro-probe otherwise); headroom is offered rate ÷ modeled capacity
# — the router republishes both under its own labels as the tier-wide
# predictive scale-out signal (CapacityHeadroomLow, ops/k8s-replicas.yaml).
DRIFT_LABELS = ("component_type", "component_id", "stat")
MODEL_DRIFT_SCORE = _series(
    Gauge, "model_drift_score",
    "Live-vs-baseline score-distribution divergence, by statistic: "
    "stat=\"ks\" (two-sample Kolmogorov–Smirnov) or stat=\"psi\" "
    "(population stability index)",
    DRIFT_LABELS)
MODEL_DRIFT_FEATURES = _series(
    Gauge, "model_drift_features_over_threshold",
    "Token feature columns whose per-feature PSI against the pinned "
    "baseline exceeds drift_feature_psi_threshold")
REPLICA_CAPACITY = _series(
    Gauge, "replica_capacity_lines_per_s",
    "Modeled scoring capacity of this replica (lines/s at full device "
    "busy): rows ÷ device-seconds over the live window, or the idle "
    "micro-probe's measured rate when no traffic flows")
CAPACITY_HEADROOM = _series(
    Gauge, "capacity_headroom_ratio",
    "Offered line rate ÷ modeled capacity (0 = idle, 1 = saturated); the "
    "predictive scale-out signal beside the reactive backlog gauge")

# durable ingress spool (wal/, PR 11): the dmwal observability contract.
# Depth/bytes/age are computed AT SCRAPE TIME (Gauge.set_function bound to
# the live spool — a wedged engine thread cannot freeze them, the same
# discipline as the heartbeat ages); depth is appended-minus-acked frames,
# age is how long the OLDEST unacked record has been waiting — the two
# SpoolDepthHigh/SpoolAgeHigh alert signals (a growing age with a flat
# depth means the stage stopped draining entirely: the ingress_crash soak
# fires it during the outage). fsync seconds attribute the durability tax;
# replayed frames count recovery replays (mode="recovery", after a crash),
# operator pipeline replays (mode="pipeline") and offline canary scoring
# (mode="shadow") separately.
WAL_SPOOL_DEPTH = _series(
    Gauge, "wal_spool_depth_frames",
    "Frames appended to the durable ingress spool but not yet acked "
    "(handed downstream); read at scrape time off the live spool")
WAL_SPOOL_BYTES = _series(
    Gauge, "wal_spool_bytes",
    "On-disk bytes of the ingress spool's segment files (retention prunes "
    "sealed fully-acked segments; the unacked suffix is never pruned)")
WAL_OLDEST_UNACKED_AGE = _series(
    Gauge, "wal_oldest_unacked_age_seconds",
    "Age of the oldest unacked spool record; keeps growing while the "
    "stage is down or wedged (the SpoolAgeHigh signal)")
WAL_FSYNC_SECONDS = _series(
    Counter, "wal_fsync_seconds_total",
    "Wall seconds spent in WAL fsync batches (the durability tax of "
    "wal_fsync_interval_ms)")
WAL_REPLAY_LABELS = ("component_type", "component_id", "mode")
WAL_REPLAYED_FRAMES = _series(
    Counter, "wal_replayed_frames_total",
    "Recorded frames re-driven through the pipeline, by mode: recovery "
    "(post-crash unacked-suffix replay), pipeline (operator replay/"
    "backfill via /admin/replay), shadow (offline dmroll canary scoring)",
    WAL_REPLAY_LABELS)

# adaptive continuous batching (library/detectors/jax_scorer.py coalescer):
# rows held across process_batch calls toward the best-fitting warm bucket
# under a latency budget. Depth is the current hold; releases count why
# each coalesced batch left — full (target occupancy reached), deadline
# (oldest row's batch_deadline_ms budget spent), flush (idle/teardown
# drain). A deadline-dominated mix with low occupancy means the budget is
# too small for the arrival rate (ops/alerts.yml BatchOccupancyLow).
COALESCE_DEPTH = _series(
    Gauge,
    "detector_coalesce_depth",
    "Rows currently held by the adaptive batch coalescer, waiting for a "
    "bucket to fill or for the oldest row's deadline",
)
RELEASE_LABELS = ("component_type", "component_id", "reason")
DEADLINE_RELEASES = _series(
    Counter,
    "detector_deadline_releases_total",
    "Coalesced micro-batch releases by reason: full (target occupancy "
    "reached), deadline (latency budget spent), flush (idle/teardown)",
    RELEASE_LABELS,
)
# the hold of the MEAN row (detector_queue_wait_seconds observes the oldest
# row's): at each coalesced release, rows x (release - the segment's arrival
# stamp) summed over the released segments, and the rows released by reason.
# seconds / rows = mean hold of a row in the coalescer.
ROW_HOLD_SECONDS = _series(
    Counter,
    "detector_row_hold_seconds_total",
    "Row-seconds spent in the batch coalescer: sum over released rows of "
    "(release time - the row's arrival at the coalescer)",
)
ROWS_RELEASED = _series(
    Counter,
    "detector_rows_released_total",
    "Rows released by the batch coalescer, by reason (full / deadline / "
    "flush)",
    RELEASE_LABELS,
)

# the sparse-expert scorer's routing, counted on the device in the scoring
# call and read back with the scores (one [3] int32 array beside them, no
# second device->host sync): every (non-PAD token, chosen expert) pair of
# every expert layer; those that fell on experts this chip holds; and, per
# call and expert layer, the largest count among the held experts, summed.
# held / assignments is the chip's share of the routing (held experts /
# router experts under even routing); busiest x held experts / held is the
# skew (1.0 = balanced). Exported as 0 by every detector: a scorer without
# experts never moves them.
MOE_ASSIGNMENTS = _series(
    Counter,
    "detector_moe_assignments_total",
    "Expert assignments routed by the scorer: non-PAD tokens x experts per "
    "token x expert layers, over all published experts",
)
MOE_HELD_ASSIGNMENTS = _series(
    Counter,
    "detector_moe_held_assignments_total",
    "Expert assignments that fell on experts held on this chip (the ones "
    "it computes)",
)
MOE_BUSIEST_ASSIGNMENTS = _series(
    Counter,
    "detector_moe_busiest_expert_assignments_total",
    "Per scoring call and expert layer the largest assignment count among "
    "the held experts, summed",
)

# batch spans a per-layer metric reads (engine/device_obs.py span()): wall
# seconds inside the span and the number of spans, per phase — upload
# (narrow + device_put, dispatch worker), readback (np.asarray of the
# scores, engine thread), alert_build (parse + alert construction for the
# rows over the threshold, engine thread). One observation per device batch.
PHASE_LABELS = ("component_type", "component_id", "phase")
PHASE_SECONDS = _series(
    Counter,
    "detector_phase_seconds_total",
    "Wall seconds inside a dm.<phase> batch span, by phase",
    PHASE_LABELS,
)
PHASE_COUNT = _series(
    Counter,
    "detector_phase_total",
    "dm.<phase> batch spans completed, by phase",
    PHASE_LABELS,
)

# what the host knows of the device's idle time: from the moment the last
# unfinished device batch was seen readable to the next scoring call being
# issued, split by what the coalescer held meanwhile — fill (rows held,
# release rule not met yet), no_rows (nothing held), host (the release rule
# was met, or the batch was released, and the call had not been issued yet)
IDLE_LABELS = ("component_type", "component_id", "cause")
DEVICE_IDLE_SECONDS = _series(
    Counter,
    "detector_device_idle_seconds_total",
    "Host-known device idle seconds (last batch readable to next call "
    "issued), by cause: fill / no_rows / host",
    IDLE_LABELS,
)

# what a POST /admin/profile capture cost the process that took it
# (utils/profiling.py ProfileManager): every gauge is absent until a
# capture has ended, set when one ends with state "done" and cleared when
# the next one ends. seconds: phase=start (the call of start_trace), stop
# (the call of stop_trace: the profiler collecting and writing the file),
# traced (between the two: the stretch the capture holds). stall: a 5 ms
# heartbeat thread's late wakes over the capture, stat=max (the longest
# time the interpreter or the processor was withheld from a waiting
# thread) and sum. span_max: the longest single dm.<span> of the capture,
# by span (dm.recv_wait, the fill, is not exported). idle_share: the
# DeviceIdleClock's totals by cause over the traced stretch, in per cent
# of it — the host's reading of what the capture's device plane shows.
CAPTURE_PHASE_LABELS = ("component_type", "component_id", "phase")
PROFILE_CAPTURE_SECONDS = _series(
    Gauge, "profile_capture_seconds",
    "The last profiler capture's own phases, by phase: start (start_trace), "
    "stop (stop_trace), traced (between the two)",
    CAPTURE_PHASE_LABELS)
CAPTURE_STAT_LABELS = ("component_type", "component_id", "stat")
PROFILE_CAPTURE_STALL = _series(
    Gauge, "profile_capture_stall_seconds",
    "Late wakes (over 20 ms) of the 5 ms heartbeat thread that ran for the "
    "last profiler capture: stat=max the longest, stat=sum their total",
    CAPTURE_STAT_LABELS)
CAPTURE_SPAN_LABELS = ("component_type", "component_id", "span")
PROFILE_CAPTURE_SPAN_MAX = _series(
    Gauge, "profile_capture_span_max_seconds",
    "The longest single dm.<span> closed during the last profiler capture, "
    "by span name",
    CAPTURE_SPAN_LABELS)
PROFILE_CAPTURE_IDLE_SHARE = _series(
    Gauge, "profile_capture_idle_share",
    "Host-known device idle time over the last profiler capture's traced "
    "stretch, in per cent of it, by cause: fill / no_rows / host",
    IDLE_LABELS)
CAPTURE_STATE_LABELS = ("component_type", "component_id", "state")
PROFILE_CAPTURES = _series(
    Counter, "profile_captures_total",
    "Profiler captures ended, by state: done, or error (start_trace or "
    "stop_trace raised, or no *.xplane.pb was left)",
    CAPTURE_STATE_LABELS)

# multi-tenant admission control (shed/, dmshed): the ingress overload
# contract. Cardinality discipline — tenant-attributed series carry the
# quota tier and a BOUNDED hashed tenant bucket (shed_tenant_buckets label
# values), never raw tenant ids; exact per-tenant counts live behind
# GET /admin/tenants. shed reasons: quota (that tenant's own token bucket
# is empty) vs ladder (the global degradation ladder gated its whole
# tier). The ladder Enum is the deterministic-overload state machine:
# normal → shed_best_effort → shed_burst → emergency, climb fast / recover
# slow like the watchdog (ops/alerts.yml DegradationLadderActive).
SHED_LABELS = ("component_type", "component_id", "tier", "tenant_bucket",
               "reason")
SHED_FRAMES = _series(
    Counter, "shed_frames_total",
    "Ingress frames refused by admission control, by quota tier, hashed "
    "tenant bucket, and reason: quota (tenant over its own token bucket) "
    "or ladder (tier gated by the degradation ladder)",
    SHED_LABELS)
ADMIT_LABELS = ("component_type", "component_id", "tier", "tenant_bucket")
ADMITTED_FRAMES = _series(
    Counter, "admitted_frames_total",
    "Ingress frames admitted past admission control, by quota tier and "
    "hashed tenant bucket",
    ADMIT_LABELS)
SHED_NACKS = _series(
    Counter, "shed_nacks_total",
    "Structured retry-after NACK replies sent for refused frames in "
    "reply mode (admission shed or drop-mode overflow) — the sender-"
    "visible twin of shed_frames_total",
)
SHED_LADDER_STATE = _series(
    Enum, "shed_ladder_state",
    "The global overload degradation ladder: which tiers ingress "
    "admission currently sheds",
    states=["normal", "shed_best_effort", "shed_burst", "emergency"],
)

# fault tolerance (faults/ + wal/deadletter.py + spool degradation, dmfault).
# faults_injected_total only moves while a FaultPlan is armed (chaos runs);
# in production it stays flat at absence. The WAL disk-error pair is the
# degradation policy's contract: errors count every append/fsync OSError
# the spool absorbed instead of letting it kill the EngineLoop thread, and
# the degraded gauge is 1 exactly while the spool is serving NON-DURABLY
# after a disk error (wal_on_disk_error: degrade) — the WalDegraded page,
# cleared when a write succeeds and durability re-arms. The DLQ series are
# the poison-frame quarantine: depth is read at scrape time off the live
# spool (same discipline as the WAL gauges), quarantined counts frames
# moved aside by reason (processing_error / replay / requeue_failed), and
# a depth that grows run-over-run is the DeadLetterGrowing ticket.
FAULT_LABELS = ("component_type", "component_id", "site", "kind")
FAULTS_INJECTED = _series(
    Counter, "faults_injected_total",
    "Faults executed by the armed FaultPlan, by instrumented site and "
    "fault kind (flat at absence unless a chaos plan is armed)",
    FAULT_LABELS)
WAL_FSYNC_ERRORS = _series(
    Counter, "wal_fsync_errors_total",
    "OSErrors (EIO/ENOSPC/...) absorbed by the ingress spool's append/"
    "fsync path instead of escaping into the EngineLoop thread")
WAL_SPOOL_DEGRADED = _series(
    Gauge, "wal_spool_degraded",
    "1 while the ingress spool is serving non-durably after a disk error "
    "(wal_on_disk_error: degrade); re-arms to 0 when writes succeed again")
DLQ_DEPTH = _series(
    Gauge, "dlq_depth_frames",
    "Frames quarantined in the dead-letter spool and not yet requeued or "
    "purged; read at scrape time off the live DLQ")
DLQ_REASON_LABELS = ("component_type", "component_id", "reason")
DLQ_QUARANTINED = _series(
    Counter, "dlq_quarantined_total",
    "Frames moved to the dead-letter quarantine after exhausting their "
    "processing attempts, by reason",
    DLQ_REASON_LABELS)
DLQ_REQUEUED = _series(
    Counter, "dlq_requeued_total",
    "Quarantined frames re-driven through the pipeline via "
    "POST /admin/dlq requeue")

# cross-stage telemetry (telemetry/, dmtel). The exporter side
# (telemetry/spans.py) runs inside every traced engine: its only hot-loop
# footprint is one bounded deque append per frame, so the single series it
# owns counts what the bounded queue/sender REFUSED (queue full, dead
# telemetry link) — spans are shed, never the pipeline. Everything else is
# collector-side (telemetry/collector.py): spans counted by their assembled
# trace's tail-sampling verdict, traces assembled vs dropped (healthy traces
# the sampler declined) vs incomplete (watermark/timeout flush without a
# terminal hop), duplicate hop spans deduped (router at-least-once requeue
# makes duplicates NORMAL, not an error), OTLP push outcomes, and the
# backlog gauge (open traces + unparsed frames) behind the
# TelemetryCollectorBacklog alert.
TELEMETRY_EXPORT_DROPPED = _series(
    Counter, "telemetry_spans_export_dropped_total",
    "Spans dropped by the engine-side exporter instead of blocking the hot "
    "loop (bounded queue full, or the telemetry link refused the frame)")
VERDICT_LABELS = ("component_type", "component_id", "verdict")
TELEMETRY_SPANS = _series(
    Counter, "telemetry_spans_total",
    "Hop spans ingested by the telemetry collector, by the tail-sampling "
    "verdict of the trace they were assembled into",
    VERDICT_LABELS)
TELEMETRY_TRACES_ASSEMBLED = _series(
    Counter, "telemetry_traces_assembled_total",
    "Pipeline traces fully assembled by the collector (terminal hop seen "
    "and the completion watermark passed)")
TELEMETRY_TRACES_DROPPED = _series(
    Counter, "telemetry_traces_dropped_total",
    "Healthy assembled traces the tail sampler declined to retain "
    "(1 - telemetry_sample_healthy_ratio of healthy traffic)")
TELEMETRY_TRACES_INCOMPLETE = _series(
    Counter, "telemetry_traces_incomplete_total",
    "Traces flushed by the collector without a terminal hop after "
    "telemetry_trace_timeout_s (a stage died, shed mid-pipeline, or its "
    "exporter dropped the span)")
TELEMETRY_SPANS_DEDUPED = _series(
    Counter, "telemetry_spans_deduped_total",
    "Duplicate (trace, stage) hop spans discarded during assembly — "
    "router at-least-once redelivery makes these normal")
OTLP_LABELS = ("component_type", "component_id", "result")
TELEMETRY_OTLP_PUSHES = _series(
    Counter, "telemetry_otlp_pushes_total",
    "OTLP/JSON export batches pushed to telemetry_otlp_url, by result "
    "(ok / error)",
    OTLP_LABELS)
TELEMETRY_COLLECTOR_BACKLOG = _series(
    Gauge, "telemetry_collector_backlog",
    "Open (not yet completed or flushed) traces held by the collector's "
    "assembler; sustained growth means the completion watermark is not "
    "advancing (a stage's exporter went quiet) or ingest outruns assembly")
