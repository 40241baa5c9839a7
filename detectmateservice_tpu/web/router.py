"""Admin route table: every HTTP route the admin plane serves, declared once.

``web/server.py`` dispatches requests through :data:`ROUTES` — there is no
second place a route can be added, so the table is the single source of
truth for the admin API surface. dmlint's cross-artifact contract DM-C007/8
(analysis/contracts.py) parses the ``Route(...)`` declarations below and
holds them in sync with the route table in ``docs/usage.md`` in both
directions: an undocumented route and a documented-but-phantom route both
fail the gate. The thread-affinity analyzer (DM-A) also parses this table:
every handler named in ROUTES is an ``admin``-domain thread entry point,
so a handler reaching an engine-owned seam (a replica socket, the WAL
spool write path) is a build-breaking finding — the state-mutating POST
handlers additionally carry explicit ``# dmlint: thread(admin)`` pragmas.

Handlers take ``(service, query, payload)`` — ``query`` is the parsed query
string (``parse_qs`` shape), ``payload`` the decoded JSON body (``{}`` for
an empty body; GET handlers receive ``None``) — and return a
:class:`Response`. Exceptions surface as HTTP 500 with a JSON detail;
``ValueError`` as HTTP 400 (client error semantics for bad parameters).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from prometheus_client import CONTENT_TYPE_LATEST, generate_latest


@dataclass(frozen=True)
class Response:
    status: int
    body: Any                        # dict/list → JSON; bytes → raw
    content_type: str = "application/json"
    # run AFTER the reply hits the wire (e.g. shutdown must answer first)
    after: Optional[Callable[[], None]] = None


@dataclass(frozen=True)
class Route:
    method: str
    path: str
    handler: Callable[..., Response]
    doc: str


def _int_param(query: Dict[str, List[str]], name: str,
               default: Optional[int] = None) -> Optional[int]:
    raw = (query.get(name) or [None])[0]
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer") from None


def _float_param(query: Dict[str, List[str]], name: str,
                 default: Optional[float] = None) -> Optional[float]:
    raw = (query.get(name) or [None])[0]
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number") from None


# -- GET handlers -----------------------------------------------------------
def _metrics(service, query, payload) -> Response:
    fmt = (query.get("format") or ["prometheus"])[0]
    if fmt == "openmetrics":
        # OpenMetrics exposition carries the exemplars (trace ids on the
        # e2e/queue-wait histogram buckets, dmtel); the handler contract
        # has no request headers, so the format is a query param instead
        # of Accept-negotiation
        from prometheus_client import REGISTRY
        from prometheus_client.openmetrics import exposition as om

        return Response(200, om.generate_latest(REGISTRY), om.CONTENT_TYPE_LATEST)
    if fmt != "prometheus":
        return Response(400, {"detail": f"unknown format {fmt!r}"})
    return Response(200, generate_latest(), CONTENT_TYPE_LATEST)


def _status(service, query, payload) -> Response:
    return Response(200, service._create_status_report())


def _health(service, query, payload) -> Response:
    deep = (query.get("deep") or ["0"])[0] not in ("", "0", "false")
    monitor = getattr(service, "health", None)
    if monitor is None:
        return Response(200, {"state": "unknown",
                              "detail": "no health monitor"})
    if deep:
        # fresh evaluation with per-check detail; non-200 on anything short
        # of healthy so orchestration healthchecks (docker-compose/k8s) can
        # gate on it directly
        report = monitor.evaluate()
        return Response(200 if report["state"] == "healthy" else 503, report)
    # cheap liveness: the watchdog's last roll-up, no evaluation on the
    # request path; degraded stays 200 (restarting a merely-degraded
    # container makes it worse)
    state = monitor.state
    return Response(503 if state == "unhealthy" else 200, {"state": state})


def _events(service, query, payload) -> Response:
    events = getattr(service, "events", None)
    if events is None:
        return Response(404, {"detail": "service has no event log"})
    limit = _int_param(query, "limit", default=-1)
    return Response(200, events.snapshot(limit if limit >= 0 else None))


def _trace(service, query, payload) -> Response:
    fmt = (query.get("format") or ["json"])[0]
    recorder = getattr(service.engine, "trace_recorder", None)
    if recorder is None:
        return Response(404, {"detail": "engine has no flight recorder"})
    if fmt == "chrome":
        # the pipeline view: on the collector stage this serves the
        # CROSS-STAGE Perfetto export (assembled traces, every hop of every
        # stage); elsewhere only the local recorder exists, and the local
        # view says so instead of masquerading as the pipeline
        collector = getattr(service, "telemetry", None)
        if collector is not None:
            return Response(200, collector.perfetto_events())
        doc = recorder.chrome_events()
        doc["localOnly"] = True  # hops of THIS process only (walkthrough.md)
        return Response(200, doc)
    if fmt == "json":
        body = recorder.snapshot()
        body["tracing_enabled"] = bool(
            getattr(service.settings, "engine_trace", False))
        return Response(200, body)
    return Response(400, {"detail": f"unknown format {fmt!r}"})


def _traces(service, query, payload) -> Response:
    collector = getattr(service, "telemetry", None)
    if collector is None:
        return Response(404, {"detail": "this stage runs no telemetry "
                                        "collector (telemetry_collector "
                                        "not set)"})
    trace_id = (query.get("id") or [None])[0]
    if trace_id is not None:
        trace = collector.trace(trace_id)
        if trace is None:
            return Response(404, {"detail": f"trace {trace_id!r} is not in "
                                            "the retained ring (sampled "
                                            "out, expired, or never seen)"})
        return Response(200, trace)
    fmt = (query.get("format") or ["json"])[0]
    if fmt == "perfetto":
        return Response(200, collector.perfetto_events())
    if fmt == "otlp":
        return Response(200, collector.otlp_payload())
    if fmt == "json":
        return Response(200, collector.snapshot(
            _int_param(query, "limit", default=None)))
    return Response(400, {"detail": f"unknown format {fmt!r}"})


def _xla(service, query, payload) -> Response:
    from ..engine import device_obs

    limit = _int_param(query, "limit", default=-1)
    snapshot = device_obs.get_ledger().snapshot(
        limit if limit is not None and limit >= 0 else None)
    return Response(200, snapshot)


def _replicas(service, query, payload) -> Response:
    router = getattr(service.engine, "router", None)
    if router is None:
        return Response(404, {"detail": "this stage is not a replica "
                                        "router (router_replicas not set)"})
    return Response(200, router.snapshot())


def _model(service, query, payload) -> Response:
    rollout = getattr(service, "rollout", None)
    if rollout is None:
        return Response(404, {"detail": "model lifecycle is not enabled on "
                                        "this stage (rollout_enabled)"})
    if (query.get("history") or ["0"])[0] not in ("", "0", "false"):
        limit = _int_param(query, "limit", default=0) or None
        return Response(200, rollout.history(limit))
    return Response(200, rollout.status())


def _drift(service, query, payload) -> Response:
    drift = getattr(service, "drift", None)
    if drift is None:
        return Response(404, {"detail": "drift monitoring is not enabled "
                                        "on this stage (drift_enabled)"})
    return Response(200, drift.status())


def _slo(service, query, payload) -> Response:
    tracker = getattr(service, "slo", None)
    if tracker is None:
        return Response(404, {"detail": "service has no SLO tracker"})
    body = tracker.snapshot()
    capacity = getattr(service, "capacity", None)
    # the capacity model rides along: burn says how fast the budget goes,
    # headroom says whether more traffic would make it worse
    body["capacity"] = capacity.status() if capacity is not None else None
    return Response(200, body)


def _load_status(service, query, payload) -> Response:
    from ..loadgen.generator import LOADGEN

    return Response(200, LOADGEN.status())


def _replay_status(service, query, payload) -> Response:
    from ..wal.replay import REPLAY

    status = REPLAY.status()
    spool = getattr(service.engine, "spool", None)
    status["spool"] = spool.stats() if spool is not None else None
    status["wal_dir"] = getattr(service.settings, "wal_dir", None)
    return Response(200, status)


def _tenants(service, query, payload) -> Response:
    admission = getattr(service, "admission", None)
    if admission is None:
        return Response(404, {"detail": "admission control is not enabled "
                                        "on this stage (shed_enabled)"})
    limit = _int_param(query, "limit", default=64)
    return Response(200, admission.snapshot(limit=limit))


def _profile_status(service, query, payload) -> Response:
    from ..utils.profiling import PROFILER

    status = PROFILER.status()
    status["profile_dir"] = (service.settings.profile_dir
                             or PROFILER.default_dir())
    return Response(200, status)


def _profile_latest(service, query, payload) -> Response:
    from ..utils.profiling import PROFILER

    base_dir = service.settings.profile_dir or PROFILER.default_dir()
    if PROFILER.status()["running"]:
        return Response(409, {"detail": "capture still running; retry when "
                                        "GET /admin/profile reports done"})
    archive = PROFILER.zip_latest(base_dir)
    if archive is None:
        return Response(404, {"detail": f"no completed capture under "
                                        f"{base_dir}"})
    _name, data = archive
    return Response(200, data, content_type="application/zip")


# -- POST handlers ----------------------------------------------------------
# dmlint: thread(admin)
def _start(service, query, payload) -> Response:
    return Response(200, {"detail": service.start()})


# dmlint: thread(admin)
def _stop(service, query, payload) -> Response:
    service.stop()
    return Response(200, {"detail": "engine stopped"})


# dmlint: thread(admin)
def _shutdown(service, query, payload) -> Response:
    # the reply must leave before run() unparks and tears the server down
    return Response(200, {"detail": "service shutting down"},
                    after=service.shutdown)


# dmlint: thread(admin)
def _reconfigure(service, query, payload) -> Response:
    config = (payload or {}).get("config") or {}
    persist = bool((payload or {}).get("persist", False))
    updated = service.reconfigure(config, persist=persist)
    return Response(200, {"detail": "reconfigured", "config": updated})


# dmlint: thread(admin)
def _checkpoint(service, query, payload) -> Response:
    return Response(200, service.checkpoint())


# dmlint: thread(admin)
def _profile_start(service, query, payload) -> Response:
    from ..utils.profiling import PROFILER, ProfileBusyError

    payload = payload or {}
    seconds = _float_param(query, "seconds")
    if seconds is None:
        seconds = payload.get("seconds", 1.0)
    base_dir = (payload.get("out_dir") or service.settings.profile_dir
                or PROFILER.default_dir())
    labels = dict(
        component_type=service.settings.component_type,
        component_id=service.settings.component_id or "unknown")
    try:
        info = PROFILER.start(base_dir, float(seconds),
                              service.settings.profile_max_captures,
                              labels=labels)
    except ProfileBusyError as exc:
        return Response(409, {"detail": str(exc)})
    info["detail"] = "capture started"
    return Response(200, info)


# dmlint: thread(admin)
def _load_control(service, query, payload) -> Response:
    from ..loadgen.generator import (
        LOADGEN,
        LoadBusyError,
        LoadIdleError,
        LoadProfile,
    )

    payload = payload or {}
    action = str(payload.get("action", "start"))
    try:
        if action == "stop":
            return Response(200, LOADGEN.stop())
        if action != "start":
            raise ValueError(f"unknown action {action!r} "
                             "(expected 'start' or 'stop')")
        profile = LoadProfile.from_payload(payload)
        labels = dict(
            component_type=service.settings.component_type,
            component_id=service.settings.component_id or "loadgen")
        return Response(200, LOADGEN.start(profile, labels=labels))
    except (LoadBusyError, LoadIdleError) as exc:
        # one run per process; a second start (or a stop with nothing
        # running) is a state conflict, same semantics as /admin/profile
        return Response(409, {"detail": str(exc)})


# dmlint: thread(admin)
def _model_control(service, query, payload) -> Response:
    from ..rollout import RolloutError, StoreError

    rollout = getattr(service, "rollout", None)
    if rollout is None:
        return Response(404, {"detail": "model lifecycle is not enabled on "
                                        "this stage (rollout_enabled)"})
    payload = payload or {}
    action = str(payload.get("action", ""))
    version = payload.get("version")
    if version is not None:
        try:
            version = int(version)
        except (TypeError, ValueError):
            raise ValueError("version must be an integer") from None
    try:
        if action == "promote":
            return Response(200, rollout.promote(version))
        if action == "rollback":
            return Response(200, rollout.rollback())
        if action == "pin":
            return Response(200, rollout.pin(version))
        if action == "unpin":
            return Response(200, rollout.unpin())
        if action == "cycle":
            block = bool(payload.get("block", False))
            return Response(200, rollout.run_cycle(reason="operator",
                                                   block=block))
    except (RolloutError, StoreError) as exc:
        # state conflicts (nothing shadowing, unknown version, nothing to
        # roll back to) are client errors, not server faults
        raise ValueError(str(exc)) from exc
    raise ValueError(f"unknown action {action!r} (expected 'promote', "
                     "'rollback', 'pin', 'unpin', or 'cycle')")


# dmlint: thread(admin)
def _replay_control(service, query, payload) -> Response:
    from ..wal.replay import ReplayBusyError, ReplayError, start_service_replay

    try:
        return Response(200, start_service_replay(service, payload or {}))
    except ReplayError as exc:
        raise ValueError(str(exc)) from exc          # HTTP 400
    except ReplayBusyError as exc:
        # one replay per process, and pipeline mode must not interleave
        # with a running engine — state conflicts, same semantics as
        # /admin/profile and /admin/load
        return Response(409, {"detail": str(exc)})


# dmlint: thread(admin)
def _replicas_control(service, query, payload) -> Response:
    router = getattr(service.engine, "router", None)
    if router is None:
        return Response(404, {"detail": "this stage is not a replica "
                                        "router (router_replicas not set)"})
    payload = payload or {}
    action = str(payload.get("action", ""))
    addr = payload.get("replica")
    if action not in ("drain", "undrain"):
        raise ValueError(f"unknown action {action!r} "
                         "(expected 'drain' or 'undrain')")
    if not addr:
        raise ValueError("replica (the configured replica address) "
                         "is required")
    # ValueError from an unknown address surfaces as HTTP 400 with the
    # configured address list in the detail — the router raises it
    verb = router.drain if action == "drain" else router.undrain
    return Response(200, {"detail": f"{action} applied",
                          "replica": verb(str(addr))})


def _faults_status(service, query, payload) -> Response:
    from .. import faults

    inj = faults.active()
    if inj is None:
        return Response(200, {"armed": False})
    tail = _int_param(query, "tail", default=100) or 0
    return Response(200, inj.snapshot(fired_tail=tail))


# dmlint: thread(admin)
def _faults_control(service, query, payload) -> Response:
    from .. import faults
    from ..faults import FaultPlan, FaultPlanError

    payload = payload or {}
    action = str(payload.get("action", ""))
    if action == "disarm":
        previous = faults.disarm()
        body = {"detail": "disarmed", "armed": False}
        if previous is not None:
            # the final fired log, so a chaos driver can collect its
            # schedule artifact in the same call that ends the run
            body["final"] = previous.snapshot(fired_tail=0)
            body["final"]["armed"] = False
            body["fired_schedule"] = previous.fired_schedule()
        return Response(200, body)
    if action != "arm":
        raise ValueError(f"unknown action {action!r} "
                         "(expected 'arm' or 'disarm')")
    try:
        plan = FaultPlan.from_dict(payload.get("plan") or {})
    except FaultPlanError as exc:
        raise ValueError(str(exc)) from exc
    inj = faults.arm(plan, labels=dict(service._labels),
                     events=service.health.emit_event,
                     logger=service.logger)
    service.health.emit_event({
        "kind": "faults_armed", "seed": plan.seed,
        "specs": len(plan.specs), "source": "admin",
    })
    return Response(200, inj.snapshot(fired_tail=0))


def _dlq_status(service, query, payload) -> Response:
    dlq = getattr(service.engine, "dlq", None)
    if dlq is None:
        return Response(404, {"detail": "this stage has no dead-letter "
                                        "queue (engine not built)"})
    limit = _int_param(query, "limit", default=64) or 0
    return Response(200, dlq.snapshot(limit=limit))


# dmlint: thread(admin)
def _dlq_control(service, query, payload) -> Response:
    dlq = getattr(service.engine, "dlq", None)
    if dlq is None:
        return Response(404, {"detail": "this stage has no dead-letter "
                                        "queue (engine not built)"})
    payload = payload or {}
    action = str(payload.get("action", ""))
    entry_id = payload.get("id")
    if entry_id is not None:
        try:
            entry_id = int(entry_id)
        except (TypeError, ValueError):
            raise ValueError("id must be an integer DLQ entry id") from None
    if action == "purge":
        purged = dlq.purge(entry_id)
        return Response(200, {"detail": "purged", "purged": purged,
                              "depth_frames": int(dlq.depth_frames())})
    if action == "requeue":
        # at-most-once: once handed to the engine's requeue deque the
        # frames are no longer the DLQ's to protect
        taken = dlq.requeue(entry_id)
        queued = service.engine.requeue_frames(
            [frame for _id, frame in taken])
        return Response(200, {"detail": "requeued", "requeued": queued,
                              "ids": [i for i, _frame in taken],
                              "depth_frames": int(dlq.depth_frames())})
    raise ValueError(f"unknown action {action!r} "
                     "(expected 'requeue' or 'purge')")


# one row per route; dmlint DM-C007/8 keeps this table and the route table
# in docs/usage.md synchronized in both directions
ROUTES: Tuple[Route, ...] = (
    Route("GET", "/metrics", _metrics, "Prometheus exposition"),
    Route("GET", "/admin/status", _status, "status report"),
    Route("GET", "/admin/health", _health, "liveness / deep health"),
    Route("GET", "/admin/events", _events, "structured event ring"),
    Route("GET", "/admin/trace", _trace, "pipeline flight recorder"),
    Route("GET", "/admin/traces", _traces,
          "telemetry collector: assembled cross-stage traces "
          "(?id=<hex> for one, ?format=perfetto|otlp for exports)"),
    Route("GET", "/admin/xla", _xla,
          "XLA compile ledger + device-batch spans"),
    Route("GET", "/admin/profile", _profile_status,
          "profiler capture status"),
    Route("GET", "/admin/load", _load_status,
          "live SLO scorecard of the open-loop load run"),
    Route("GET", "/admin/profile/latest", _profile_latest,
          "download the newest completed capture as a zip"),
    Route("GET", "/admin/replicas", _replicas,
          "replica-router roll-up: per-replica state/backlog/inflight"),
    Route("GET", "/admin/model", _model,
          "model lifecycle status (?history=1 for the checkpoint log)"),
    Route("GET", "/admin/replay", _replay_status,
          "WAL replay status + the live ingress spool's stats"),
    Route("GET", "/admin/faults", _faults_status,
          "fault-injection status: armed plan, op counters, fired log"),
    Route("GET", "/admin/dlq", _dlq_status,
          "dead-letter queue: quarantined poison frames + totals"),
    Route("GET", "/admin/drift", _drift,
          "drift monitor snapshot: live-vs-baseline stats, hysteresis "
          "state, top drifting features"),
    Route("GET", "/admin/slo", _slo,
          "multi-window SLO burn rates, per-stage dwell attribution, and "
          "the capacity model"),
    Route("GET", "/admin/tenants", _tenants,
          "admission control: per-tier/per-tenant admitted+shed counters "
          "and the current degradation-ladder state"),
    Route("POST", "/admin/start", _start, "start the engine"),
    Route("POST", "/admin/stop", _stop, "stop the engine"),
    Route("POST", "/admin/shutdown", _shutdown, "shut the service down"),
    Route("POST", "/admin/reconfigure", _reconfigure,
          "validate + apply component config"),
    Route("POST", "/admin/checkpoint", _checkpoint,
          "checkpoint component state"),
    Route("POST", "/admin/profile", _profile_start,
          "start an on-demand jax.profiler capture"),
    Route("POST", "/admin/load", _load_control,
          "start/stop an open-loop load run against a pipeline"),
    Route("POST", "/admin/replicas", _replicas_control,
          "operator drain/undrain of one replica"),
    Route("POST", "/admin/model", _model_control,
          "model lifecycle verbs: promote/rollback/pin/unpin/cycle"),
    Route("POST", "/admin/faults", _faults_control,
          "arm a seeded fault plan or disarm the active one"),
    Route("POST", "/admin/dlq", _dlq_control,
          "requeue or purge quarantined frames (one id or all)"),
    Route("POST", "/admin/replay", _replay_control,
          "replay a recorded WAL spool: pipeline re-drive or offline "
          "shadow-scoring of a dmroll candidate"),
)


def route_table() -> Dict[Tuple[str, str], Route]:
    table: Dict[Tuple[str, str], Route] = {}
    for route in ROUTES:
        key = (route.method, route.path)
        if key in table:
            raise ValueError(f"duplicate route {key}")
        table[key] = route
    return table
