"""Service settings: YAML + environment configuration with typed addresses.

Capability parity with the reference's ``ServiceSettings``
(reference: src/service/settings.py:40-173):

* typed transport URLs restricted to the schemes the data plane supports
  (reference: settings.py:31-37),
* ``DETECTMATE_``-prefixed environment overrides with ``__`` nesting, env
  winning over YAML per-field (reference: settings.py:80-84,134-173),
* deterministic UUIDv5 component identity, stable across restarts
  (reference: settings.py:93-114),
* TLS cross-field validation failing at startup (reference: settings.py:116-132).

This build has no ``pydantic_settings`` dependency; the env layer is a small
explicit merge, which is what the reference's ``from_yaml`` does anyway.

TPU-build additions (not in the reference): micro-batching knobs
(``engine_batch_size``, ``engine_batch_timeout_ms``), accelerator backend
selection, and mesh shape for multi-chip scale-out.
"""
from __future__ import annotations

import json
import os
import sys
import uuid
from typing import Annotated, Any, Dict, List, Mapping, Optional

import yaml
from pydantic import (
    AfterValidator,
    BaseModel,
    ConfigDict,
    Field,
    ValidationError,
    model_validator,
)

ENV_PREFIX = "DETECTMATE_"
ENV_NESTED_DELIMITER = "__"

# nng+tcp / nng+tls+tcp are TPU-build additions beyond the reference scheme
# set: the NNG SP Pair0 wire protocol over plain TCP (resp. inside a real TLS
# session — byte-compatible with NNG's mbedTLS ``tls+tcp`` transport), so real
# NNG/fluentd peers can dial this data plane, encrypted or not
# (engine/socket.py NngTcpSocketFactory / NngTlsTcpSocketFactory).
SUPPORTED_SCHEMES = ("ipc", "tcp", "tls+tcp", "nng+tcp", "nng+tls+tcp", "ws",
                     "inproc")

# The TLS-bearing scheme prefixes. ONE home, used by both settings
# cross-validation (material must exist) and the engine's socket setup
# (material must be FORWARDED to the factory) — those two drifted once,
# breaking every encrypted NNG output at dial while validation passed.
TLS_SCHEME_PREFIXES = ("tls+tcp://", "nng+tls+tcp://")


# ws:// historical note: through round 2, ws rode libzmq's WebSocket
# transport — a compile-time option this image's libzmq lacks, so settings
# validation probed zmq.has("ws") and failed the scheme up front. Round 3
# replaced that with an in-tree RFC 6455 transport (engine/socket.py
# WsSocketFactory, NNG ws dialect), making the scheme unconditionally
# available; the probe is gone.


class SettingsError(Exception):
    """Raised for invalid service settings."""


def _validate_addr(addr: str) -> str:
    """Validate a transport address against the supported scheme set.

    Mirrors the reference's NngAddr union constraints (settings.py:31-37):
    unknown schemes are rejected at validation time
    (pinned by tests/test_engine_multi_output.py:328-342 in the reference).
    """
    if "://" not in addr:
        raise ValueError(f"address {addr!r} has no scheme; expected one of {SUPPORTED_SCHEMES}")
    scheme, rest = addr.split("://", 1)
    if scheme not in SUPPORTED_SCHEMES:
        raise ValueError(f"unsupported scheme {scheme!r} in {addr!r}; expected one of {SUPPORTED_SCHEMES}")
    if not rest:
        raise ValueError(f"address {addr!r} has an empty target")
    if scheme in ("tcp", "tls+tcp", "nng+tcp", "nng+tls+tcp", "ws"):
        host_port = rest.split("/", 1)[0]
        if ":" not in host_port:
            raise ValueError(f"address {addr!r} requires an explicit port")
    return addr


TransportAddr = Annotated[str, AfterValidator(_validate_addr)]


class TlsInputConfig(BaseModel):
    """Server-side TLS material for the engine listener (reference: settings.py:11-17)."""

    model_config = ConfigDict(extra="forbid")
    cert_key_file: str


class TlsOutputConfig(BaseModel):
    """Client-side TLS material for output dialers (reference: settings.py:20-27)."""

    model_config = ConfigDict(extra="forbid")
    ca_file: str
    server_name: Optional[str] = None


class ServiceSettings(BaseModel):
    """All per-process service configuration (reference: settings.py:40-173)."""

    model_config = ConfigDict(extra="forbid", validate_assignment=True)

    # -- identity (reference: settings.py:49-52) --------------------------
    component_name: Optional[str] = None
    component_id: Optional[str] = None
    component_type: str = "core"
    component_config_class: Optional[str] = None

    # -- logging (reference: settings.py:55-58) ---------------------------
    log_level: str = "INFO"
    log_dir: str = "./logs"
    log_to_console: bool = True
    log_to_file: bool = True

    # -- engine data channel (reference: settings.py:61-65) ---------------
    engine_addr: TransportAddr = "ipc:///tmp/detectmate.engine.ipc"
    # N-shard ingress (the multi-ingress regime, docs/benchmarks.md): when
    # non-empty the engine listens on ALL of these — one socket, fd and
    # kernel buffer per shard, each fed by its own sender — merged into the
    # single dispatch loop. engine_addr keeps identity/reply duties; it is
    # NOT implicitly included in the shard set.
    engine_ingress_addrs: List[TransportAddr] = Field(default_factory=list)
    engine_autostart: bool = True
    engine_recv_timeout: int = Field(default=100, ge=1)  # ms
    engine_retry_count: int = Field(default=10, ge=1)
    engine_buffer_size: int = Field(default=100, ge=0, le=8192)

    # -- outputs (reference: settings.py:68-70) ---------------------------
    out_addr: List[TransportAddr] = Field(default_factory=list)
    out_dial_timeout: int = Field(default=1000, ge=0)  # ms

    # -- TLS (reference: settings.py:73-74) -------------------------------
    tls_input: Optional[TlsInputConfig] = None
    tls_output: Optional[TlsOutputConfig] = None

    # -- admin HTTP (reference: settings.py:77-78) ------------------------
    http_host: str = "127.0.0.1"
    http_port: int = Field(default=8000, ge=0, le=65535)

    # -- component config file (reference: settings.py:86) ----------------
    config_file: Optional[str] = None

    # -- TPU-build additions ----------------------------------------------
    # engine_batch_size == 1 keeps the reference's strict per-message
    # contract; > 1 enables micro-batched dispatch to the accelerator.
    engine_batch_size: int = Field(default=1, ge=1, le=16384)
    engine_batch_timeout_ms: float = Field(default=2.0, ge=0.0)
    # pack up to N results per outgoing wire frame (engine/framing.py):
    # amortizes the per-message socket cost that caps stage-to-stage rates
    # (~80k msg/s per Python sender, measured). 1 = single-message wire,
    # compatible with reference-style peers; receivers auto-detect either.
    engine_frame_batch: int = Field(default=1, ge=1, le=8192)
    # ingress batch-frame auto-detection rests on every pipeline payload
    # being protobuf (no valid protobuf message starts with the 0xD7 magic —
    # wire type 7 does not exist). A pipeline carrying NON-protobuf payloads
    # that could legitimately begin with b"\xd7DM\x01" (UTF-8 "×DM…") must
    # disable detection or such a payload would be mis-split/dropped.
    engine_frame_autodetect: bool = True
    # pipeline tracing (engine/framing.py v2 frames): opt-in PER SENDER like
    # engine_frame_batch — when true this engine stamps hop records and emits
    # v2 traced frames downstream; framework receivers auto-detect and strip
    # or propagate them. Leave false (the default) on links whose peer is a
    # v1-only or raw-protobuf consumer: wire bytes then stay byte-identical
    # to the untraced format. Requires engine_frame_autodetect (v2 headers
    # ride the same magic-byte detection as batch frames).
    engine_trace: bool = False
    # stage name stamped into hop records; defaults to component_name or
    # component_type so a 3-stage pipeline reads parser→detector→output
    trace_stage: Optional[str] = None
    # terminal-stage override. Default (None) = auto: a stage with no
    # forwarding outputs finalizes traces (observes e2e, feeds the flight
    # recorder). Set true on a stage that DOES forward (e.g. an output
    # writer with a downstream sink that is not a framework engine): it
    # finalizes instead of propagating, and its downstream sees plain v1.
    trace_terminal: Optional[bool] = None
    # egress e2e observation for a FORWARDING stage: when true this stage
    # observes pipeline_e2e_latency_seconds (and feeds its flight recorder)
    # as each frame leaves, while STILL propagating the v2 trace downstream
    # — unlike trace_terminal, which finalizes and strips. Set it on the
    # last framework stage of a pipeline whose sink is an external consumer
    # that keys on trace ids (e.g. the loadgen scorecard collector): the
    # internal e2e then measures ingest→egress, and the collector's
    # client-observed latency minus it is the ingress/egress blind spot
    # (docs/walkthrough.md "read the client skew").
    trace_observe_e2e: bool = False
    # flight recorder bounds (engine/tracing.py): N slowest traces kept,
    # ring of sampled traces, and the 1-in-K completed-trace sampling rate
    trace_slowest: int = Field(default=32, ge=1, le=1024)
    trace_sampled: int = Field(default=128, ge=1, le=8192)
    trace_sample_every: int = Field(default=64, ge=1)
    # fan-out under backpressure: "drop" = the reference contract (bounded
    # retries with 10 ms sleeps, then drop + count — engine.py:286-296);
    # "block" = flow control (send blocks until the peer drains), the right
    # mode INSIDE a high-rate pipeline where a slower downstream stage must
    # throttle its upstream instead of losing data in 100 ms retry windows.
    out_backpressure: str = Field(default="drop", pattern="^(drop|block)$")
    # drain-then-close: in "block" mode a stop() no longer abandons the
    # in-flight message immediately — pending sends share ONE window of this
    # many milliseconds (starting when the stop flag is first observed by a
    # blocked send) to land before being dropped+counted. Aggregate across
    # all messages the final flush emits, so the le=1500 cap keeps it under
    # the engine's 2 s stop-join deadline.
    out_stop_drain_ms: float = Field(default=250.0, ge=0.0, le=1500.0)
    # -- zero-copy host path (engine/shm.py, PR 7) ------------------------
    # Colocated links only: when true AND every out_addr is ipc:// or
    # inproc://, outgoing frames ride a refcounted shared-memory slot (the
    # wire carries a ~40-byte reference; inproc peers get the identical
    # payload object, zero copies). Anything else — a remote scheme in
    # out_addr, an oversized payload, no free slot because a receiver is
    # slow/dead — copy-downgrades that frame to plain bytes: byte-identical
    # payload, just slower. Receivers auto-detect reference frames (rides
    # engine_frame_autodetect, like batch frames).
    zero_copy_framing: bool = False
    # slot pool geometry: payloads larger than zero_copy_slot_bytes always
    # copy-downgrade; all slots held by slow readers ⇒ copy-downgrade too
    # (shm_frames_total{mode="copy"} is the signal)
    zero_copy_slots: int = Field(default=32, ge=2, le=4096)
    zero_copy_slot_bytes: int = Field(default=262144, ge=4096, le=67108864)
    # output fan-out batching: up to this many wire frames per native
    # send_many call (one GIL crossing per micro-batch on the output pump,
    # the send-side twin of the ingest recv_many). 1 = per-frame sends.
    send_batch_max: int = Field(default=64, ge=1, le=8192)
    # transport_backend selects the data-plane implementation: "native" is
    # the in-tree C++ transport (native/transport), "zmq" the Python pyzmq
    # backend; both are wire-compatible. "auto" prefers native when built.
    transport_backend: str = Field(default="auto", pattern="^(auto|zmq|native)$")
    backend: str = Field(default="auto", pattern="^(auto|cpu|tpu)$")
    mesh_shape: Optional[Dict[str, int]] = None  # e.g. {"data": 8}
    # component state checkpointing (core.py): restore at setup_io when a
    # checkpoint exists, save at clean shutdown and on POST /admin/checkpoint
    checkpoint_dir: Optional[str] = None
    # on-demand jax.profiler capture (POST /admin/profile): captures land in
    # numbered subdirectories of profile_dir (default: a per-process dir
    # under the system temp dir), pruned to the newest profile_max_captures
    # so a capture-happy operator cannot fill the disk
    profile_dir: Optional[str] = None
    profile_max_captures: int = Field(default=4, ge=1, le=64)
    # device observability (engine/device_obs.py): when true, a compile on
    # the dispatch path after warm-up completes emits an unexpected_recompile
    # structured event and arms the xla_recompile_storm watchdog check (the
    # scorer_xla_recompiles_unexpected_total counter feeding the
    # RecompileStorm alert moves either way)
    recompile_alert_enabled: bool = True
    # multi-host chip plane (parallel/distributed.py): when a coordinator is
    # set, jax.distributed joins this process's devices into the global mesh
    # (ICI within a pod, DCN across pods). Env (via the standard settings
    # env layer — names match the fields): DETECTMATE_COORDINATOR_ADDRESS /
    # DETECTMATE_NUM_PROCESSES / DETECTMATE_PROCESS_ID.
    coordinator_address: Optional[str] = None  # "host:port"
    num_processes: int = Field(default=1, ge=1)
    process_id: int = Field(default=0, ge=0)
    # -- replica-parallel serving tier (router/, PR 9) --------------------
    # Non-empty turns this stage into a REPLICA ROUTER: instead of
    # duplicating every outgoing frame to all ``out_addr`` peers, each frame
    # is load-balanced to exactly ONE of these downstream replica addresses
    # (the PAPER §0/§7 production topology: one parser feeding a tier of
    # detector processes). Mutually exclusive with ``out_addr`` — a router
    # routes, it does not also fan out.
    router_replicas: List[TransportAddr] = Field(default_factory=list)
    # Admin-plane URL per replica, parallel to router_replicas (same length
    # or empty). With URLs the supervisor polls each replica's deep health
    # (GET /admin/health?deep=1) and ingest watermark (/metrics) to drive
    # drain/undrain and the least_backlog policy; without them health is
    # inferred from send failures only (no proactive drain).
    router_admin_urls: List[str] = Field(default_factory=list)
    # balancing policy: least_backlog routes to the replica with the fewest
    # unacked frames + lowest polled ingress backlog; round_robin rotates;
    # sticky_trace rendezvous-hashes the PR-1 trace id so one source's
    # frames keep per-source ordering on a single replica while it stays
    # healthy.
    router_policy: str = Field(default="least_backlog",
                               pattern="^(least_backlog|round_robin|sticky_trace)$")
    # drain window: a replica whose probe goes unhealthy/unreachable stops
    # receiving new frames immediately; after this many seconds its still-
    # unacked frames are requeued to healthy peers (at-least-once — a frame
    # the dead replica did process may be scored twice; duplicates are
    # harmless to detection, loss is not).
    router_drain_timeout_s: float = Field(default=5.0, ge=0.0, le=600.0)
    # credit window: max unacked frames outstanding per replica. Acks ride
    # the supervisor's watermark poll (the replica's data_read_lines_total
    # covering the window head); a full window removes the replica from
    # dispatch until credit frees — per-replica flow control.
    router_credit_window: int = Field(default=64, ge=1, le=8192)
    # supervisor poll cadence (deep health + watermark per replica)
    router_health_interval_s: float = Field(default=2.0, ge=0.05, le=300.0)

    # -- model lifecycle: dmroll (rollout/, PR 10) ------------------------
    # Turns the served model into a versioned, continuously refreshed
    # artifact: a background trainer fine-tunes candidates on a sampled
    # tail of live traffic, candidates shadow-score a traffic copy, and a
    # promotion gate hot-swaps them onto the dispatch path with zero
    # unexpected XLA recompiles (docs/model_lifecycle.md). Requires a
    # component with the rollout hooks (jax_scorer).
    rollout_enabled: bool = False
    # versioned checkpoint store root (crash-atomic manifest, keep-N
    # rotation). Point every replica of a tier at the SAME directory and
    # `client.py model deploy` rolls one version across the fleet.
    rollout_dir: Optional[str] = None
    # continuous fine-tune cadence; each cycle = sample → fine-tune →
    # checkpoint → shadow → (promote | holdback)
    rollout_interval_s: float = Field(default=600.0, ge=0.05)
    # dispatch-path traffic tap: fraction of dispatched rows offered to the
    # reservoir, and the reservoir's bounded size (rows; memory bound is
    # capacity * seq_len * 4 bytes)
    rollout_sample_ratio: float = Field(default=0.05, gt=0.0, le=1.0)
    rollout_sample_capacity: int = Field(default=4096, ge=16, le=262144)
    # a cycle only fine-tunes once this many sampled rows are banked
    rollout_min_fit_rows: int = Field(default=256, ge=1)
    rollout_train_epochs: int = Field(default=1, ge=1, le=100)
    # shadow-scoring canary gate: a candidate must shadow at least this
    # many rows, then promotes only when mean |score delta| and the
    # alert-decision flip ratio both stay under their ceilings; otherwise
    # it is held back (structured model_canary_holdback event)
    rollout_min_shadow_samples: int = Field(default=512, ge=1)
    rollout_shadow_timeout_s: float = Field(default=300.0, gt=0.0)
    rollout_max_mean_delta: float = Field(default=0.25, ge=0.0)
    rollout_max_flip_ratio: float = Field(default=0.01, ge=0.0, le=1.0)
    # false = candidates stop at the gate and wait for an operator
    # POST /admin/model {"action": "promote"}
    rollout_auto_promote: bool = True
    # keep-N checkpoint rotation (live/pinned/newest never pruned)
    rollout_keep_checkpoints: int = Field(default=4, ge=1, le=64)

    # -- drift & capacity observability: dmdrift (obs/) -------------------
    # When true, a background DriftMonitor (obs/drift.py) compares the live
    # score distribution (the dmroll TrafficSampler reservoir, which also
    # carries per-row scores) against a baseline pinned at promote time and
    # persisted in the CheckpointStore manifest: rolling two-sample KS and
    # PSI over scores plus per-feature PSI on the token rows, exported as
    # model_drift_score{stat} / model_drift_features_over_threshold, with
    # hysteresis-gated drift_detected/drift_cleared events and a
    # GET /admin/drift snapshot (docs/drift.md). Requires rollout_enabled —
    # the detector's reservoir and versioned store are the substrate.
    drift_enabled: bool = False
    # evaluation cadence of the drift monitor thread
    drift_interval_s: float = Field(default=30.0, ge=0.05)
    # rows kept in the pinned baseline (score sample + per-feature
    # histogram edges); bounded so the manifest entry stays small
    drift_baseline_size: int = Field(default=512, ge=16, le=65536)
    # an evaluation is skipped (stats hold their last value) until at least
    # this many scored rows are in the live window
    drift_min_rows: int = Field(default=64, ge=8)
    # detection thresholds: KS statistic on scores, PSI on scores, and the
    # per-feature PSI above which a token column counts as drifting
    drift_ks_threshold: float = Field(default=0.25, ge=0.0, le=1.0)
    drift_psi_threshold: float = Field(default=0.2, ge=0.0)
    drift_feature_psi_threshold: float = Field(default=0.25, ge=0.0)
    # hysteresis: drift_detected only after this many CONSECUTIVE
    # over-threshold evaluations; drift_cleared only after this many
    # consecutive clean ones — no event flapping at the threshold
    drift_trigger_intervals: int = Field(default=3, ge=1, le=1000)
    drift_clear_intervals: int = Field(default=2, ge=1, le=1000)
    # sustained drift kicks RolloutManager.run_cycle(reason="drift") early,
    # but never more often than this cooldown (0 disables the auto-cycle —
    # drift then only pages, it does not retrain)
    drift_min_cycle_interval_s: float = Field(default=900.0, ge=0.0)
    # When true, a CapacityMonitor (obs/capacity.py) maintains the modeled
    # per-replica scoring capacity: pure arithmetic from the dispatch tap
    # (rows ÷ device-seconds) while traffic is live, a bounded synthetic
    # micro-probe through rollout_scores during idle windows — exported as
    # replica_capacity_lines_per_s + capacity_headroom_ratio (offered rate
    # ÷ modeled capacity), the predictive scale-out signal the router
    # aggregates (ops/k8s-replicas.yaml).
    capacity_enabled: bool = False
    # capacity model refresh cadence
    capacity_interval_s: float = Field(default=15.0, ge=0.05)
    # rows per idle micro-probe burst (rides the warm train-bucket compile
    # shape; bounded so a probe can never starve live traffic)
    capacity_probe_rows: int = Field(default=256, ge=1, le=65536)
    # only probe after the dispatch path has been idle this long (0 = never
    # probe; live-traffic arithmetic is then the only capacity source)
    capacity_probe_idle_s: float = Field(default=30.0, ge=0.0)
    # sliding window over which offered rate and busy-time capacity are
    # averaged
    capacity_window_s: float = Field(default=60.0, ge=1.0)

    # -- durable ingress: dmwal (wal/, PR 11) -----------------------------
    # When true, the engine appends every ingress frame to a WAL-backed
    # spool (wal/spool.py) BEFORE processing it, acks the sequence once the
    # frame's results have left the process (router watermark settling when
    # the replica tier is armed), and — after a crash — replays the unacked
    # suffix through the pipeline before accepting new traffic: a parser or
    # router kill -9 no longer loses the in-flight window
    # (docs/durability.md). Off (the default) leaves the hot path
    # byte-identical to the pre-WAL build.
    durable_ingress: bool = False
    # spool directory (segment files + crash-atomic MANIFEST.json);
    # required when durable_ingress is on. Point replay/backfill tooling
    # (client.py replay, POST /admin/replay) at the same directory.
    wal_dir: Optional[str] = None
    # roll to a new segment file once the active one exceeds this many
    # bytes; retention prunes whole sealed segments, so smaller segments =
    # finer-grained reclamation, more files
    wal_segment_bytes: int = Field(default=64 * 1024 * 1024,
                                   ge=4096, le=4 * 1024 * 1024 * 1024)
    # fsync batching: appends are made durable at most this long after they
    # land (0 = fsync EVERY append — the strict-durability mode; the
    # default trades a bounded window of unsynced tail for throughput,
    # measured by wal_fsync_seconds_total)
    wal_fsync_interval_ms: float = Field(default=50.0, ge=0.0, le=10000.0)
    # bounded retention: sealed, fully-acked segments are pruned from the
    # front once the spool exceeds wal_retain_bytes, or once a sealed
    # segment's newest record is older than wal_retain_age_s. The UNACKED
    # suffix is never pruned by either bound — SpoolDepthHigh/SpoolAgeHigh
    # (ops/alerts.yml) page before disk becomes the operator's problem.
    wal_retain_bytes: int = Field(default=1024 * 1024 * 1024, ge=4096)
    wal_retain_age_s: float = Field(default=86400.0, gt=0.0)
    # disk-fault policy (wal/spool.py): what the spool does when an
    # append/fsync/manifest OSError (EIO/ENOSPC) is absorbed — the error
    # itself can never kill the EngineLoop thread. degrade (default):
    # keep serving NON-durably with wal_spool_degraded raised, re-arming
    # on the next successful write; shed: drop frames that could not be
    # made durable (durability over availability); halt: escalate as
    # WalError and stop the stage.
    wal_on_disk_error: str = Field(default="degrade",
                                   pattern="^(degrade|shed|halt)$")

    # -- fault injection + dead-letter quarantine: dmfault (faults/) ------
    # JSON FaultPlan file ({"seed": int, "specs": [{site, kind, rate,
    # start_op, stop_op, delay_ms, match}, ...]}) armed at service start;
    # None (the default) arms nothing and every fault site costs one
    # is-None branch. POST /admin/faults arms/disarms at runtime.
    fault_plan_file: Optional[str] = None
    # dead-letter quarantine (wal/deadletter.py): a frame whose processing
    # raised on every one of dlq_max_attempts attempts moves to the DLQ
    # (reason + error + tenant/seq context) instead of crash-looping
    # recovery replay or being silently dropped-and-acked.
    dlq_max_attempts: int = Field(default=3, ge=1, le=100)
    # bound on retained quarantined frames; at capacity the oldest entry
    # is evicted (newest evidence wins)
    dlq_max_frames: int = Field(default=1024, ge=1, le=1048576)
    # DLQ directory; defaults to <wal_dir>/dlq when durable_ingress is on,
    # memory-only quarantine otherwise
    dlq_dir: Optional[str] = None

    # -- warm-start serving: dmwarm (utils/profiling.py, PR 17) -----------
    # When true, the JAX persistent compilation cache is armed in Service
    # construction — BEFORE the component's first jit — so a restarted
    # replica (or a dmroll candidate swap on the same host) reuses every
    # already-seen (kernel, bucket) compile instead of paying cold-start.
    # Point every replica of a tier at the SAME cache directory and HPA
    # scale-out boots against a warm cache (docs/walkthrough.md "make
    # scale-out honest"). Off (the default) leaves arming to the scorer's
    # first jax use: on an accelerator the cache is on anyway, on the CPU
    # backend it stays off unless a directory is named.
    compile_cache_enabled: bool = False
    # cache directory, used exactly as given. JAX_COMPILATION_CACHE_DIR,
    # where set, wins over it (the cache is placed from outside); with
    # neither, the fixed in-checkout default (utils/profiling.py
    # DEFAULT_CACHE_DIR).
    compile_cache_dir: Optional[str] = None

    # -- multi-tenant admission control: dmshed (shed/) -------------------
    # When true, the engine ingress runs per-tenant token-bucket admission
    # BEFORE spooling/processing each frame: frames carry an optional
    # tenant block (engine/framing.py MAGIC_TEN), quotas come from
    # tenants_file (or the tenant_default_* fields for unmapped/anonymous
    # tenants), and refused frames are counted + shed instead of growing
    # an unbounded backlog (docs/overload.md). Off (the default) leaves
    # the hot path byte-identical to the pre-shed build — the tenant
    # block, when present, is still stripped cleanly.
    shed_enabled: bool = False
    # tenants.yaml quota map: tier + rate (sustained lines/s) + burst
    # headroom per tenant, with a 'default' entry for unmapped tenants.
    # None = every tenant rides the tenant_default_* quota below.
    tenants_file: Optional[str] = None
    tenant_default_tier: str = Field(
        default="best_effort", pattern="^(guaranteed|burst|best_effort)$")
    tenant_default_rate: float = Field(default=10000.0, gt=0.0)
    # None = 2x tenant_default_rate (one second of doubled arrivals)
    tenant_default_burst: Optional[float] = Field(default=None, gt=0.0)
    # cardinality bound for the tenant_bucket metric label: tenant ids
    # hash into this many stable buckets (never per-tenant label values)
    shed_tenant_buckets: int = Field(default=16, ge=1, le=256)
    # retry hint stamped into the structured NACK a refused frame gets in
    # reply mode (never an empty reply — the dm_nack payload carries
    # reason, tier, and this backoff)
    shed_retry_after_ms: float = Field(default=100.0, ge=0.0, le=60000.0)
    # global degradation ladder (engine/health.py DegradationLadder):
    # aggregate process backlog (detector pending + router unacked + spool
    # depth) at which the ladder climbs to shed_best_effort / shed_burst /
    # emergency. Climb is immediate to the highest exceeded threshold;
    # recovery steps down one state per shed_ladder_recovery_intervals
    # consecutive clean watchdog evaluations (watchdog-style hysteresis).
    shed_ladder_backlog_t1: float = Field(default=256.0, gt=0.0)
    shed_ladder_backlog_t2: float = Field(default=1024.0, gt=0.0)
    shed_ladder_backlog_t3: float = Field(default=4096.0, gt=0.0)
    shed_ladder_recovery_intervals: int = Field(default=2, ge=1)

    # -- self-diagnosis (engine/health.py) --------------------------------
    # "json" renders every log record as one JSON object per line (component
    # identity + message + attached structured event), for fleet log
    # aggregation; "plain" keeps the reference's human format.
    log_format: str = Field(default="plain", pattern="^(plain|json)$")
    # per-process health watchdog: a daemon thread evaluates the subsystem
    # checks (process_wedged / ingest_stalled / output_saturated /
    # device_inflight_stuck) every interval and rolls them into the
    # engine_health_state Enum behind GET /admin/health.
    watchdog_enabled: bool = True
    watchdog_interval_s: float = Field(default=2.0, ge=0.05, le=300.0)
    # heartbeat age (or continuous blocked-send / stuck-inflight time) at
    # which a check degrades resp. goes unhealthy. stall must exceed
    # engine_recv_timeout or an idle loop's recv tick would false-alarm.
    watchdog_stall_seconds: float = Field(default=10.0, gt=0.0)
    watchdog_unhealthy_seconds: float = Field(default=30.0, gt=0.0)
    # hysteresis: checks degrade on the FIRST failing evaluation but only
    # recover after this many consecutive clean ones (no alert flapping)
    watchdog_recovery_intervals: int = Field(default=2, ge=1)
    # 0 (default) = an idle ingress is healthy; > 0 = this stage expects
    # traffic, and that many seconds of ingress silence is a degradation
    watchdog_ingest_stall_seconds: float = Field(default=0.0, ge=0.0)
    # bounded ring of structured events behind GET /admin/events
    event_ring_size: int = Field(default=512, ge=8, le=65536)

    # -- cross-stage telemetry: dmtel (telemetry/) ------------------------
    # Span export (every traced stage): where this engine ships its
    # completed hop spans — the collector stage's telemetry_collector_addr.
    # Requires engine_trace: spans ARE the hop records the tracing path
    # stamps. Unset (default) = hop records stay in the local flight
    # recorder only, exactly the pre-dmtel behavior.
    telemetry_addr: Optional[TransportAddr] = None
    # bounded hot-path span queue; when full, spans are dropped (counted in
    # telemetry_spans_export_dropped_total) — never the pipeline's frames
    telemetry_queue_size: int = Field(default=4096, ge=16, le=1048576)
    # sender-thread drain cadence: spans batch for up to this long before
    # one JSON encode + one socket send ships them
    telemetry_flush_interval_ms: float = Field(default=50.0, ge=1.0,
                                               le=10000.0)
    # Collector (one stage per pipeline, like the router): assemble spans
    # into whole-pipeline traces, tail-sample, serve GET /admin/traces.
    telemetry_collector: bool = False
    telemetry_collector_addr: Optional[TransportAddr] = None
    # tail sampling: the anomalous tail (error / shed / quarantined /
    # fault / slow / incomplete) is ALWAYS kept; healthy traces are kept at
    # this ratio by a deterministic hash of the trace id
    telemetry_sample_healthy_ratio: float = Field(default=0.05, ge=0.0,
                                                  le=1.0)
    # e2e latency above which a completed trace is "slow" (kept 100%)
    telemetry_slo_ms: float = Field(default=1000.0, gt=0.0)
    # watermark settle window: a trace with its terminal hop completes once
    # the newest send_ns seen across ALL spans has advanced this far past
    # the trace's own newest hop (out-of-order stragglers had their chance)
    telemetry_settle_ms: float = Field(default=200.0, ge=0.0, le=60000.0)
    # collector-clock deadline after which a trace is flushed regardless —
    # without a terminal hop it counts as incomplete (itself a signal)
    telemetry_trace_timeout_s: float = Field(default=5.0, gt=0.0, le=600.0)
    # bounded ring of kept traces behind GET /admin/traces
    telemetry_retain_traces: int = Field(default=256, ge=8, le=65536)
    # optional OTLP/HTTP traces endpoint (e.g. http://tempo:4318/v1/traces):
    # kept traces are pushed as OTLP/JSON by a dedicated export thread
    telemetry_otlp_url: Optional[str] = None

    # -- derived identity (reference: settings.py:93-114) -----------------
    @model_validator(mode="after")
    def _ensure_component_id(self) -> "ServiceSettings":
        if not self.component_id:
            if self.component_name:
                seed = f"detectmate/{self.component_type}/{self.component_name}"
            else:
                seed = f"detectmate/{self.component_type}|{self.engine_addr}"
            object.__setattr__(
                self, "component_id", uuid.uuid5(uuid.NAMESPACE_URL, seed).hex
            )
        return self

    # -- watchdog cross-validation ----------------------------------------
    @model_validator(mode="after")
    def _check_watchdog(self) -> "ServiceSettings":
        if self.watchdog_unhealthy_seconds < self.watchdog_stall_seconds:
            raise ValueError(
                "watchdog_unhealthy_seconds must be >= watchdog_stall_seconds "
                f"({self.watchdog_unhealthy_seconds} < {self.watchdog_stall_seconds})")
        return self

    # -- router cross-validation ------------------------------------------
    @model_validator(mode="after")
    def _check_router(self) -> "ServiceSettings":
        if self.router_replicas and self.out_addr:
            raise ValueError(
                "router_replicas and out_addr are mutually exclusive: a "
                "router load-balances each frame to ONE replica; plain "
                "fan-out duplicates to every out_addr")
        if (self.router_admin_urls
                and len(self.router_admin_urls) != len(self.router_replicas)):
            raise ValueError(
                "router_admin_urls must be empty or match router_replicas "
                f"1:1 ({len(self.router_admin_urls)} urls for "
                f"{len(self.router_replicas)} replicas)")
        return self

    # -- rollout cross-validation -----------------------------------------
    @model_validator(mode="after")
    def _check_rollout(self) -> "ServiceSettings":
        if self.rollout_enabled and not self.rollout_dir:
            raise ValueError(
                "rollout_enabled requires rollout_dir (the versioned "
                "checkpoint store root)")
        return self

    # -- drift cross-validation -------------------------------------------
    @model_validator(mode="after")
    def _check_drift(self) -> "ServiceSettings":
        if self.drift_enabled and not self.rollout_enabled:
            raise ValueError(
                "drift_enabled requires rollout_enabled: the drift monitor "
                "reads the dmroll traffic reservoir and pins its baseline "
                "in the rollout checkpoint store")
        return self

    # -- durable-ingress cross-validation ---------------------------------
    @model_validator(mode="after")
    def _check_wal(self) -> "ServiceSettings":
        if self.durable_ingress and not self.wal_dir:
            raise ValueError(
                "durable_ingress requires wal_dir (the WAL spool directory)")
        return self

    # -- compile-cache cross-validation -----------------------------------
    @model_validator(mode="after")
    def _check_compile_cache(self) -> "ServiceSettings":
        """A non-writable ``compile_cache_dir`` must fail at settings load,
        with the field named, not at the first compile."""
        if self.compile_cache_enabled and self.compile_cache_dir:
            probe = os.path.join(self.compile_cache_dir,
                                 f".dmwarm_probe_{os.getpid()}")
            try:
                os.makedirs(self.compile_cache_dir, exist_ok=True)
                with open(probe, "w", encoding="utf-8") as fh:
                    fh.write("ok")
                os.unlink(probe)
            except OSError as exc:
                raise ValueError(
                    f"compile_cache_dir {self.compile_cache_dir!r} is not "
                    f"writable: {exc}")
        return self

    # -- shed cross-validation --------------------------------------------
    @model_validator(mode="after")
    def _check_shed(self) -> "ServiceSettings":
        if not (self.shed_ladder_backlog_t1 <= self.shed_ladder_backlog_t2
                <= self.shed_ladder_backlog_t3):
            raise ValueError(
                "shed ladder thresholds must be ordered t1 <= t2 <= t3 "
                f"({self.shed_ladder_backlog_t1} / "
                f"{self.shed_ladder_backlog_t2} / "
                f"{self.shed_ladder_backlog_t3})")
        if (self.tenant_default_burst is not None
                and self.tenant_default_burst < self.tenant_default_rate):
            raise ValueError(
                "tenant_default_burst must be >= tenant_default_rate "
                f"({self.tenant_default_burst} < {self.tenant_default_rate})")
        return self

    # -- telemetry cross-validation ---------------------------------------
    @model_validator(mode="after")
    def _check_telemetry(self) -> "ServiceSettings":
        if self.telemetry_collector and not self.telemetry_collector_addr:
            raise ValueError(
                "telemetry_collector requires telemetry_collector_addr "
                "(the address the collector listens for span frames on)")
        if self.telemetry_addr and not self.engine_trace:
            raise ValueError(
                "telemetry_addr requires engine_trace: spans are built "
                "from the hop records the tracing path stamps")
        return self

    # -- TLS cross-validation (reference: settings.py:116-132) ------------
    @model_validator(mode="after")
    def _check_tls(self) -> "ServiceSettings":
        # both TLS-bearing schemes (framework-private tls+tcp and the
        # NNG-wire-compatible nng+tls+tcp) need their material up front —
        # fail at startup, not at first connection
        tls_schemes = TLS_SCHEME_PREFIXES
        if self.engine_addr.startswith(tls_schemes) and self.tls_input is None:
            raise ValueError(
                f"engine_addr uses {self.engine_addr.split('://')[0]}:// "
                "but tls_input is not configured")
        if (any(a.startswith(tls_schemes) for a in self.engine_ingress_addrs)
                and self.tls_input is None):
            raise ValueError("an engine_ingress_addr uses a TLS scheme but tls_input is not configured")
        if any(a.startswith(tls_schemes) for a in self.out_addr) and self.tls_output is None:
            raise ValueError("an out_addr uses a TLS scheme but tls_output is not configured")
        if (any(a.startswith(tls_schemes) for a in self.router_replicas)
                and self.tls_output is None):
            raise ValueError("a router_replicas address uses a TLS scheme "
                             "but tls_output is not configured")
        return self

    # -- loading -----------------------------------------------------------
    @classmethod
    def from_yaml(cls, path: str) -> "ServiceSettings":
        """Load from YAML, apply env overrides (env wins), validate.

        Exits the process on validation failure, like the reference CLI
        (reference: settings.py:134-173; precedence pinned by
        tests/test_config_reading.py:122-145).
        """
        try:
            with open(path, "r", encoding="utf-8") as fh:
                data = yaml.safe_load(fh) or {}
            if not isinstance(data, dict):
                raise SettingsError(f"settings file {path} must contain a mapping")
            merged = _deep_merge(data, _env_overrides())
            return cls.model_validate(merged)
        except (OSError, yaml.YAMLError, ValidationError, SettingsError) as exc:
            print(f"Invalid service settings ({path}): {exc}", file=sys.stderr)
            raise SystemExit(1)

    @classmethod
    def from_env(cls) -> "ServiceSettings":
        return cls.model_validate(_env_overrides())


def _env_overrides(environ: Optional[Mapping[str, str]] = None) -> Dict[str, Any]:
    """Collect ``DETECTMATE_*`` environment variables into a nested dict.

    ``__`` nests into sub-models (reference: settings.py:80-84). List- and
    dict-typed fields accept JSON values.
    """
    environ = environ if environ is not None else os.environ
    out: Dict[str, Any] = {}
    for key, value in environ.items():
        if not key.startswith(ENV_PREFIX):
            continue
        path = key[len(ENV_PREFIX):].lower().split(ENV_NESTED_DELIMITER)
        parsed: Any = value
        stripped = value.strip()
        if stripped and stripped[0] in "[{":
            try:
                parsed = json.loads(stripped)
            except json.JSONDecodeError:
                parsed = value
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
            if not isinstance(node, dict):
                break
        else:
            node[path[-1]] = parsed
    return out


def _deep_merge(base: Dict[str, Any], override: Dict[str, Any]) -> Dict[str, Any]:
    """Merge ``override`` onto ``base``, override winning per-field."""
    merged = dict(base)
    for key, value in override.items():
        if key in merged and isinstance(merged[key], dict) and isinstance(value, dict):
            merged[key] = _deep_merge(merged[key], value)
        else:
            merged[key] = value
    return merged
