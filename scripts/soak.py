#!/usr/bin/env python
"""Chaos soak harness: open-loop load + fault injection + live alert rules.

Boots the full PAPER.md §0 pipeline IN PROCESS — loadgen (the reader role)
→ MatcherParser → JaxScorerDetector → OutputWriter → scorecard collector —
over inproc sockets (the ``replica_kill`` scenario swaps the single
detector for the REAL replica tier: parser → router → 2 scorer replicas,
``boot_replica_pipeline``), drives it with wall-clock-scheduled open-loop traffic
from the shared corpus (audit rows, JSON ``@type`` reroute, invalid UTF-8),
scrapes ``/metrics`` once a second into a sample store, and evaluates the
*actual* ``ops/alerts.yml`` expressions against it (loadgen/alerteval.py).
Two phases, one ``SOAK_*.json`` verdict:

1. **baseline** (the pre-fault window): client-visible ``loss == 0``,
   achieved rate ≥ 95% of offered, a populated client-latency histogram —
   the external view ``pipeline_e2e_latency_seconds`` cannot provide, and
   with ``--scenario none`` additionally that NO alert rule fired;
2. **chaos**: the scenario's fault is injected under continued load and
   every rule it is expected to trip must actually transition to
   ``firing`` — alert coverage tested by execution, not cross-referencing —
   then the fault clears and the pipeline must be seen delivering again.

The scorer runs with an explicit alert-all ``score_threshold`` so every row
flows end to end (loss accounting is exact: a missing trace id is loss, not
filtering); aggregation is 1:1 at the output stage for the same reason.

Durations: a CI-sized run cannot hold a fault for a literal ``for: 1m`` on
top of 5m rate windows, so ``--time-scale K`` divides every rule *duration*
(holds and range windows) by K while leaving value thresholds untouched
(loadgen/alerteval.py). ``docs/benchmarks.md`` documents the record schema.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# scenario -> (expected alerts, one-line story). dmlint DM-C009 keeps this
# table and the docs/benchmarks.md soak-scenario table in sync.
SCENARIOS = {
    "none": ((), "no fault: the loss==0 / goodput / histogram baseline"),
    "stall": (("EngineLoopStalled", "StageUnhealthy"),
              "parser hot loop wedged mid-process for the fault window"),
    "slow_sink": (("MessageDropRateHigh",),
                  "collector stops draining; the output stage's bounded "
                  "retries exhaust and drop"),
    "recompile": (("RecompileStorm",),
                  "post-warm-up dispatch compiles injected into the XLA "
                  "ledger"),
    "replica_kill": (("StageScrapeDown", "ReplicaDrainedSustained"),
                     "one of two scorer replicas behind the REAL router "
                     "tier wedges, dies cold mid-load (engine stopped, "
                     "admin plane gone), and is restarted; gates: the "
                     "router's replica_drain event, requeue_total > 0, "
                     "post-settle loss == 0, survivors' unexpected "
                     "recompiles == 0"),
    "rollout": (("ModelCanaryDiverging",),
                "under continued load the dmroll cycle fine-tunes a "
                "candidate on sampled live traffic, shadows it, "
                "auto-promotes through the gate, and hot-swaps it "
                "mid-stream (gates: loss == 0, zero unexpected "
                "recompiles, divergence series populated); then a "
                "deliberately-broken candidate shadows — gated on "
                "ModelCanaryDiverging firing and the "
                "model_canary_holdback event"),
    "noisy_neighbor": (("ShedRateHigh",),
                       "an aggressor tenant offers 10x its admission quota "
                       "alongside an in-quota victim tenant; the parser's "
                       "ingress admission (shed_enabled + tenants.yaml) "
                       "sheds the aggressor's excess at the front door; "
                       "gates: victim p99 inside the --slo-ms SLO, zero "
                       "victim unique-frame loss, shed counted on the "
                       "aggressor only (exact per-tenant counters off "
                       "/admin/tenants), the load_shed event emitted, and "
                       "ShedRateHigh actually firing"),
    "chaos_mesh": (("WalDegraded", "DeadLetterGrowing"),
                   "a seeded dmfault plan composes three fault families "
                   "under continued load: 5% socket-send latency, a "
                   "wal_fsync EIO burst against the parser's durable "
                   "spool (wal_on_disk_error=degrade), and a poison "
                   "payload marker the processor site raises on — gates: "
                   "zero non-poison loss, every poison frame quarantined "
                   "in the DLQ and drained back through requeue after "
                   "disarm, the engine loop alive through the whole fsync "
                   "burst, WalDegraded + DeadLetterGrowing actually "
                   "firing, and the fired fault log equal to the plan's "
                   "precomputed schedule (the determinism artifact: the "
                   "committed seed replays the run)"),
    "drift": (("ModelDriftSustained",),
              "the live traffic mix shifts hard mid-stream (a second "
              "generator streams 100% anomalous comms alongside the "
              "baseline mix); the dmdrift monitor watches the live score "
              "distribution walk away from the baseline pinned over the "
              "pre-shift window, emits drift_detected, and kicks the "
              "dmroll cycle early — fine-tune on the drifted sample → "
              "shadow → promote → baseline re-pin → drift_cleared; "
              "gates: zero unique-frame loss across the swap, "
              "ModelDriftSustained actually firing (off the recorded "
              "burn-rate evaluator), drift_cleared landing after the "
              "promotion re-pin, and the calibrated "
              "replica_capacity_lines_per_s within 25% of a closed-loop "
              "probe on the same host"),
    "ingress_crash": (("SpoolAgeHigh",),
                      "the parser (durable_ingress on) wedges mid-burst "
                      "with frames banked unacked in its WAL spool, then "
                      "dies cold (crash_abort: no drain, no acks, results "
                      "of the in-flight burst lost exactly as kill -9 "
                      "loses them) and stays down for the fault window; "
                      "gates: SpoolAgeHigh actually firing during the "
                      "outage, restart recovery replaying the unacked "
                      "suffix (wal_replayed recovery > 0), zero "
                      "unique-frame loss end-to-end, and the spool fully "
                      "acked (depth 0) after the settle window"),
}

AUDIT_LOG_FORMAT = "type=<Type> msg=audit(<Time>): <Content>"
AUDIT_TEMPLATE = ("arch=<*> syscall=<*> success=<*> exit=<*> pid=<*> "
                  "uid=<*> comm=<*> exe=<*>")


def build_settings(tmp: Path, burst: int, rollout_dir=None, wal_dir=None,
                   tenants_file=None, drift=False):
    """The three service settings + component configs of the soak pipeline.
    Frame sizes are kept uniform (engine_frame_batch == loadgen burst) so
    wire frames map ~1:1 through every stage and the FIFO trace attachment
    stays exact — the precondition for trace-id loss accounting."""
    from detectmateservice_tpu.settings import ServiceSettings

    common = dict(
        http_port=0, log_to_file=False, log_to_console=False,
        engine_trace=True, backend="cpu",
        engine_batch_size=max(512, 2 * burst), engine_batch_timeout_ms=5.0,
        engine_frame_batch=burst, engine_recv_timeout=50,
        # dmtel rides along on every soak: each stage exports its hop spans
        # to the collector the parser service hosts. Purely additive
        # observability — no soak gate reads it, the stats land in the
        # verdict JSON as evidence
        telemetry_addr="inproc://soak-telemetry",
    )
    wal = {}
    if wal_dir is not None:
        # durable ingress on the pipeline's front stage: a fast fsync tick
        # (CI-sized) and a small segment so the scenario exercises a roll
        wal = dict(durable_ingress=True, wal_dir=str(wal_dir),
                   wal_fsync_interval_ms=20.0,
                   wal_segment_bytes=4 * 1024 * 1024)
    shed = {}
    if tenants_file is not None:
        # dmshed on the pipeline's front stage only: admission belongs at
        # the front door, and the inner stages see already-admitted traffic
        shed = dict(shed_enabled=True, tenants_file=str(tenants_file))
    parser = ServiceSettings(
        component_type="parsers.template_matcher.MatcherParser",
        component_id="soak-parser", trace_stage="parser",
        engine_addr="inproc://soak-parser",
        out_addr=["inproc://soak-detector"],
        telemetry_collector=True,
        telemetry_collector_addr="inproc://soak-telemetry",
        **wal, **shed, **common)
    rollout = {}
    if rollout_dir is not None:
        # the dmroll cycle, CI-sized: a generous mean-delta gate (a 1-epoch
        # fine-tune on a tiny MLP legitimately moves scores a little; the
        # gate semantics themselves are pinned by tests/test_rollout.py)
        # and a huge interval — the harness drives cycles explicitly
        rollout = dict(
            rollout_enabled=True, rollout_dir=str(rollout_dir),
            rollout_interval_s=3600.0,
            # drift scenario thins the reservoir tap: Algorithm R replaces
            # slots with probability capacity/seen, so a lower ratio keeps
            # `seen` small enough that a mid-stream mix shift turns the
            # reservoir over within a CI-sized fault window
            rollout_sample_ratio=0.05 if drift else 1.0,
            rollout_sample_capacity=256, rollout_min_fit_rows=64,
            rollout_train_epochs=1, rollout_min_shadow_samples=128,
            rollout_shadow_timeout_s=60.0, rollout_max_mean_delta=3.0,
            rollout_max_flip_ratio=0.05, rollout_auto_promote=True,
            rollout_keep_checkpoints=4)
        if drift:
            # dmdrift, CI-sized: a fast evaluation tick, hysteresis deep
            # enough that ModelDriftSustained's (scaled) hold elapses while
            # the gauges are pinned high, a cooldown long enough for
            # exactly one kicked cycle per run, and a capacity model that
            # falls back to the idle micro-probe seconds after load stops
            rollout.update(
                drift_enabled=True, drift_interval_s=2.0,
                drift_baseline_size=256, drift_min_rows=64,
                drift_trigger_intervals=5, drift_clear_intervals=2,
                drift_min_cycle_interval_s=300.0,
                capacity_enabled=True, capacity_interval_s=2.0,
                capacity_probe_rows=256, capacity_probe_idle_s=5.0,
                capacity_window_s=30.0)
    detector = ServiceSettings(
        component_type="detectors.jax_scorer.JaxScorerDetector",
        component_id="soak-detector", trace_stage="detector",
        engine_addr="inproc://soak-detector",
        out_addr=["inproc://soak-output"], **rollout, **common)
    output = ServiceSettings(
        component_type="outputs.file_sink.OutputWriter",
        component_id="soak-output", trace_stage="output",
        engine_addr="inproc://soak-output",
        out_addr=["inproc://soak-collector"],
        # the collector is an external consumer keying on trace ids: this
        # stage is the pipeline's internal completion point but must keep
        # propagating the v2 trace — the egress-observe mode
        trace_observe_e2e=True, **common)

    templates = tmp / "soak_templates.txt"
    templates.write_text(AUDIT_TEMPLATE + "\n", encoding="utf-8")
    parser_cfg = {"parsers": {"MatcherParser": {
        "method_type": "matcher_parser", "auto_config": False,
        "log_format": AUDIT_LOG_FORMAT, "accept_raw_lines": True,
        "params": {"path_templates": str(templates)},
    }}}
    detector_cfg = {"detectors": {"JaxScorerDetector": {
        "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
        "data_use_training": 64, "train_epochs": 1, "min_train_steps": 8,
        # the drift scenario needs the VARIABLES in the token row: the
        # 8-word audit template alone fills seq_len=8, and a reservoir of
        # identical rows can never show a content shift (KS would pin at
        # exactly 0 no matter how anomalous the traffic mix turns)
        "seq_len": 24 if drift else 8, "dim": 16, "max_batch": 2 * burst,
        # pipeline_depth 0 = drain every dispatch before returning: outputs
        # leave in the same engine iteration as their ingest, which is what
        # keeps the FIFO trace attachment exact (a deferred output would
        # leave on an idle drain tick with no pending context and the
        # trace would finalize at the detector instead of the collector)
        "async_fit": False, "pipeline_depth": 0,
        # alert-all: every scored row emits, so the collector sees every
        # line and a missing trace id can only mean loss
        "score_threshold": -1e30,
    }}}
    output_cfg = {"outputs": {"OutputWriter": {
        "method_type": "output_writer", "aggregate_count": 1,
        "write_files": False, "emit_records": True,
    }}}
    return [(parser, parser_cfg), (detector, detector_cfg),
            (output, output_cfg)]


def boot_pipeline(tmp: Path, factory, burst: int, rollout_dir=None,
                  wal_dir=None, tenants_file=None, drift=False):
    from detectmateservice_tpu.core import Service

    services = []
    for settings, config in build_settings(tmp, burst,
                                           rollout_dir=rollout_dir,
                                           wal_dir=wal_dir,
                                           tenants_file=tenants_file,
                                           drift=drift):
        service = Service(settings, component_config=config,
                          socket_factory=factory)
        service.setup_io()
        service.web_server.start()
        service.start()
        services.append(service)
    return services


def boot_replica_pipeline(tmp: Path, factory, burst: int,
                          n_replicas: int = 2):
    """The replica-tier topology for the ``replica_kill`` scenario:
    parser → ROUTER → N scorer replicas → one output stage. Replicas boot
    first so the router's supervisor can be given their (ephemeral) admin
    URLs; every stage keeps the uniform-frame settings that make the FIFO
    trace attachment exact. Returns ``[parser, router, *replicas,
    output]``."""
    from detectmateservice_tpu.core import Service
    from detectmateservice_tpu.settings import ServiceSettings

    base = build_settings(tmp, burst)
    (parser_settings, parser_cfg) = base[0]
    (detector_settings, detector_cfg) = base[1]
    (output_settings, output_cfg) = base[2]

    def boot(settings, config):
        service = Service(settings, component_config=config,
                          socket_factory=factory)
        service.setup_io()
        service.web_server.start()
        service.start()
        return service

    output = boot(output_settings, output_cfg)
    replicas = []
    for i in range(n_replicas):
        settings = detector_settings.model_copy(update=dict(
            component_id=f"soak-detector-{i}",
            engine_addr=f"inproc://soak-detector-{i}"))
        replicas.append(boot(settings, detector_cfg))
    router_settings = ServiceSettings(
        component_type="core", component_id="soak-router",
        trace_stage="router", engine_addr="inproc://soak-router",
        router_replicas=[r.settings.engine_addr for r in replicas],
        router_admin_urls=[f"http://127.0.0.1:{r.web_server.port}"
                           for r in replicas],
        router_health_interval_s=1.0, router_drain_timeout_s=5.0,
        http_port=0, log_to_file=False, log_to_console=False,
        engine_trace=True, backend="cpu",
        engine_batch_size=max(512, 2 * burst), engine_batch_timeout_ms=5.0,
        engine_frame_batch=burst, engine_recv_timeout=50)
    router = boot(router_settings, None)
    parser = boot(parser_settings.model_copy(update=dict(
        out_addr=["inproc://soak-router"])), parser_cfg)
    return [parser, router, *replicas, output]


def teardown_pipeline(services) -> None:
    for service in reversed(services):
        steps = [service.stop, service.health.stop, service.web_server.stop]
        if service.rollout is not None:
            steps.insert(0, service.rollout.stop)
        for step in steps:
            try:
                step()
            except Exception:
                pass


class Scraper(threading.Thread):
    """Once a second: one pass over the process-wide prometheus registry
    into the sample store (every in-process stage shares the registry, so
    one scrape covers the fleet) + a synthetic per-stage ``up`` series +
    one rule-evaluator tick — the soak's stand-in for a Prometheus server
    on its evaluation interval."""

    def __init__(self, store, evaluator, services,
                 interval_s: float = 1.0) -> None:
        super().__init__(name="soak-scraper", daemon=True)
        self._store = store
        self._evaluator = evaluator
        self._services = services
        self._interval = interval_s
        self._halt = threading.Event()

    def run(self) -> None:
        from prometheus_client import generate_latest

        while not self._halt.is_set():
            t = time.monotonic()
            text = generate_latest().decode("utf-8", errors="replace")
            self._store.ingest_exposition(text, t)
            for service in self._services:
                self._store.add("up", {
                    "job": "detectmate",
                    "instance": service.settings.component_id or "?",
                }, t, 1.0 if service.engine.running else 0.0)
            self._evaluator.tick(self._store, t)
            self._halt.wait(self._interval)

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5.0)


# -- fault injectors ---------------------------------------------------------

def install_stall(services, flag: threading.Event) -> None:
    """Wedge the parser's hot loop while ``flag`` is set: its
    component-level process_frames blocks exactly where a pathological
    payload or a GIL-holding native call would wedge it. Instance-attribute
    shadowing — the adapter resolves the component hook per call, so this
    takes effect on the very next frame burst."""
    parser = services[0].library_component
    original = parser.process_frames

    def stalled(frames):
        while flag.is_set():
            time.sleep(0.05)
        return original(frames)

    parser.process_frames = stalled


def install_crash_stall(services, flag: threading.Event) -> None:
    """The ingress_crash wedge: like ``install_stall``, but abort-aware —
    ``crash_abort`` must be able to kill the engine thread while it sits
    INSIDE the wedged component call (the frames of that burst are exactly
    the in-flight state a dying process loses). On abort the wrapper
    raises (the engine counts the error and the loop exits); on a later
    restart the cleared flags make it a plain passthrough, so recovery
    replays through the REAL parser."""
    parser = services[0].library_component
    engine = services[0].engine
    original = parser.process_frames

    def stalled(frames):
        while flag.is_set() and not engine._abort_event.is_set():
            time.sleep(0.02)
        if engine._abort_event.is_set():
            raise RuntimeError("crash_abort mid-process (ingress_crash)")
        return original(frames)

    parser.process_frames = stalled


def inject_recompiles(n: int = 4, spacing_s: float = 0.5) -> None:
    """Feed post-warm-up dispatch-path compiles into the XLA ledger (the
    same injection seam tests/test_device_obs.py uses): each one is what a
    bucket miss costs — here without actually stalling the engine, so the
    RecompileStorm rule is exercised in isolation."""
    from detectmateservice_tpu.engine import device_obs

    ledger = device_obs.get_ledger()
    ledger.mark_warmup_complete()
    for i in range(n):
        ledger.record_compile(0.4, bucket=4096 + i, backend="cpu",
                              where="dispatch", expected=False)
        time.sleep(spacing_s)


# chaos_mesh: the committed seed IS the reproduction recipe — rerunning
# with this plan replays the same fault schedule op-for-op (the
# fired_equals_planned_schedule gate below proves it on every run). The
# wal_fsync op window is sized in fsync *attempts*: pre-burst the spool
# fsyncs once per generator burst (~1-2 ops/s at the soak cadence, the
# only times dirty bytes exist), degraded it retries every fsync
# interval (~20/s, dirty stays set), so ops 8..308 is a ~15 s EIO burst
# starting ~4-8 s into the chaos phase — held well past WalDegraded's
# scaled `for:`, finished well before the window ends so the re-arm and
# alert-clear are observed too.
CHAOS_MESH_POISON = "POISON-PILL"
CHAOS_MESH_PLAN = {
    "seed": 411,
    "specs": [
        {"site": "sock_send", "kind": "latency", "rate": 0.05,
         "delay_ms": 20.0},
        {"site": "wal_fsync", "kind": "eio", "rate": 1.0,
         "start_op": 8, "stop_op": 308},
        {"site": "proc", "kind": "raise", "match": CHAOS_MESH_POISON},
    ],
}


def admin_call(port: int, path: str, doc=None):
    """One admin-plane round trip against an in-process stage — the soak
    drives dmfault through the REAL HTTP surface an operator would."""
    import urllib.request

    data = json.dumps(doc).encode("utf-8") if doc is not None else None
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read().decode("utf-8"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", choices=sorted(SCENARIOS), default="none")
    ap.add_argument("--seconds", type=float, default=60.0,
                    help="baseline (pre-fault) load window (default 60)")
    ap.add_argument("--fault-seconds", type=float, default=None,
                    help="fault hold; default per scenario")
    # defaults sized for a shared-GIL in-process pipeline on a small CI
    # box: the scorer's per-dispatch cost dominates (~100 ms readback on
    # XLA:CPU), so bigger-but-fewer frames buy headroom, and 1000 lines/s
    # keeps utilization low enough that queueing stays out of the baseline
    ap.add_argument("--rate", type=float, default=1000.0,
                    help="offered lines/s (default 1000)")
    ap.add_argument("--burst", type=int, default=500,
                    help="lines per traced frame (default 500)")
    ap.add_argument("--time-scale", type=float, default=None,
                    help="divide alert-rule durations by this; default "
                         "per scenario")
    ap.add_argument("--settle", type=float, default=8.0,
                    help="baseline drain window before loss is counted")
    ap.add_argument("--slo-ms", type=float, default=2000.0,
                    help="noisy_neighbor: the victim tenant's p99 SLO "
                         "gate in ms (default 2000)")
    ap.add_argument("--mix", default="anomaly=0.005,json=0.01,"
                                     "invalid_utf8=0.005")
    ap.add_argument("--out-dir", default=str(REPO))
    args = ap.parse_args()

    # per-scenario fault/scale defaults: each fault must outlive its rule's
    # (scaled) detection horizon — threshold crossing + for: hold
    fault_defaults = {"none": 0.0, "stall": 45.0, "slow_sink": 45.0,
                      "recompile": 8.0, "replica_kill": 40.0,
                      "rollout": 45.0, "ingress_crash": 45.0,
                      "noisy_neighbor": 45.0, "chaos_mesh": 45.0,
                      # drift must outlive reservoir turnover + hysteresis
                      # + the kicked cycle + the post-promote clear window
                      "drift": 75.0}
    scale_defaults = {"none": 6.0, "stall": 6.0, "slow_sink": 12.0,
                      "recompile": 6.0, "replica_kill": 12.0,
                      "rollout": 12.0, "ingress_crash": 12.0,
                      "noisy_neighbor": 12.0, "chaos_mesh": 12.0,
                      "drift": 30.0}
    fault_s = (args.fault_seconds if args.fault_seconds is not None
               else fault_defaults[args.scenario])
    time_scale = (args.time_scale if args.time_scale is not None
                  else scale_defaults[args.scenario])

    import tempfile

    from detectmateservice_tpu.engine.framing import pack_batch
    from detectmateservice_tpu.engine.socket import InprocQueueSocketFactory
    from detectmateservice_tpu.loadgen.alerteval import (
        RuleEvaluator,
        SampleStore,
        load_recording_rules,
        load_rules,
    )
    from detectmateservice_tpu.loadgen.corpus import (
        PayloadMix,
        training_preamble,
    )
    from detectmateservice_tpu.loadgen.generator import (
        LoadGenerator,
        LoadProfile,
    )

    expected_alerts = list(SCENARIOS[args.scenario][0])
    mix = PayloadMix.from_dict(
        {k.strip(): float(v) for k, _, v in
         (part.partition("=") for part in args.mix.split(",") if part)})

    checks = []

    def check(name: str, ok: bool, detail: str) -> bool:
        checks.append({"name": name, "ok": bool(ok), "detail": str(detail)})
        print(f"[soak] {'PASS' if ok else 'FAIL'} {name}: {detail}")
        return ok

    # noisy_neighbor splits the box's characterized comfortable rate in
    # half: the victim tenant gets one half (in quota, by a wide margin),
    # the aggressor's QUOTA is the other half — but it OFFERS 10x that, so
    # admission must shed ~90% of it to hold admitted load at ~args.rate
    noisy = args.scenario == "noisy_neighbor"
    victim_rate = args.rate / 2 if noisy else args.rate
    aggr_quota = args.rate / 2

    def new_generator(factory, seconds: float, settle: float,
                      rate=None, tenant=None, listen=True,
                      component_id="soak-loadgen", mix_override=None):
        profile = LoadProfile(
            target_addr="inproc://soak-parser",
            listen_addr="inproc://soak-collector" if listen else None,
            rate=rate if rate is not None else victim_rate,
            burst=args.burst, seconds=seconds,
            mix=mix_override if mix_override is not None else mix,
            settle_s=settle,
            tenant=tenant if tenant is not None
            else ("victim" if noisy else None))
        return LoadGenerator(profile, labels=dict(
            component_type="loadgen", component_id=component_id),
            socket_factory=factory)

    # deep ingress/inter-stage queues: a stall scenario banks the whole
    # fault window's arrivals and must drain them afterwards, not drop
    # them. The collector link alone stays shallow so a paused collector
    # (slow_sink) exhausts the output stage's bounded retries within the
    # fault window — depth is fixed by whichever factory touches the
    # address first (the registry is per-address).
    factory = InprocQueueSocketFactory(maxsize=65536)
    InprocQueueSocketFactory(maxsize=64)._pair("inproc://soak-collector")
    store = SampleStore()
    # recording rules evaluate each tick BEFORE the alert rules, so alerts
    # referencing recorded names (PipelineSloBurnRecorded) read this-tick
    # values — the same order Prometheus guarantees within a group interval
    evaluator = RuleEvaluator(
        load_rules(REPO / "ops" / "alerts.yml"),
        time_scale=time_scale,
        recording=load_recording_rules(REPO / "ops" / "recording_rules.yml"))
    t_start_utc = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    t0 = time.monotonic()

    record = {
        "schema": "soak-v1",
        "scenario": args.scenario,
        "scenario_story": SCENARIOS[args.scenario][1],
        "expected_alerts": expected_alerts,
        "started_utc": t_start_utc,
        "time_scale": time_scale,
        "profile": {"rate_lines_per_s": args.rate, "burst": args.burst,
                    "baseline_seconds": args.seconds,
                    "fault_seconds": fault_s, "mix": mix.to_dict()},
    }

    with tempfile.TemporaryDirectory() as tmp:
        if args.scenario == "replica_kill":
            services = boot_replica_pipeline(Path(tmp), factory, args.burst)
        elif args.scenario == "rollout":
            services = boot_pipeline(Path(tmp), factory, args.burst,
                                     rollout_dir=Path(tmp) / "rollout")
        elif args.scenario == "drift":
            services = boot_pipeline(Path(tmp), factory, args.burst,
                                     rollout_dir=Path(tmp) / "rollout",
                                     drift=True)
        elif args.scenario in ("ingress_crash", "chaos_mesh"):
            services = boot_pipeline(Path(tmp), factory, args.burst,
                                     wal_dir=Path(tmp) / "wal")
        elif args.scenario == "noisy_neighbor":
            # the default quota stays effectively unlimited: the untenanted
            # warm traffic (and any damaged tenant block) must never shed —
            # only the two NAMED tenants are under test
            tenants_file = Path(tmp) / "tenants.yaml"
            tenants_file.write_text(
                "default:\n"
                "  tier: guaranteed\n"
                "  rate: 10000000\n"
                "tenants:\n"
                "  victim:\n"
                "    tier: guaranteed\n"
                f"    rate: {victim_rate * 3:.0f}\n"
                f"    burst: {victim_rate * 6:.0f}\n"
                "  aggr:\n"
                "    tier: burst\n"
                f"    rate: {aggr_quota:.0f}\n"
                f"    burst: {aggr_quota * 2:.0f}\n",
                encoding="utf-8")
            services = boot_pipeline(Path(tmp), factory, args.burst,
                                     tenants_file=tenants_file)
        else:
            services = boot_pipeline(Path(tmp), factory, args.burst)
        scraper = Scraper(store, evaluator, services)
        generator = None
        stall_flag = threading.Event()
        try:
            # warm: train + calibrate the scorer and pay every jit compile
            # before the measured window; confirmation = the output stage
            # writing lines (read off the shared in-process registry) AND
            # the XLA compile ledger going quiet — the scorer keeps warming
            # its host-twin buckets on a background thread after the warm
            # traffic has drained, and on a small CPU box each of those
            # compiles would stall the shared-GIL pipeline mid-measurement
            # (a 1-2 s e2e spike per compile, enough to burn-rate-page a
            # no-fault baseline)
            from detectmateservice_tpu.engine import device_obs
            from detectmateservice_tpu.engine import metrics as m

            # replica mode: the warm traffic splits across N replicas and
            # EVERY replica must see enough rows to train + calibrate
            n_replicas = sum(1 for s in services
                             if s.settings.component_id.startswith(
                                 "soak-detector"))
            warm_rows = training_preamble(6 * args.burst
                                          * max(1, n_replicas))
            ingress = factory.create_output("inproc://soak-parser")
            for start in range(0, len(warm_rows), args.burst):
                ingress.send(pack_batch(warm_rows[start:start + args.burst]))
            out_service = next(s for s in services
                               if s.settings.component_id == "soak-output")
            out_labels = dict(
                component_type=out_service.settings.component_type,
                component_id="soak-output")
            written = m.DATA_WRITTEN_LINES().labels(**out_labels)
            ledger = device_obs.get_ledger()
            deadline = time.monotonic() + 180
            prev = -1.0
            prev_compiles = -1
            quiet_ticks = 0
            while True:
                if time.monotonic() > deadline:
                    raise RuntimeError("pipeline never warmed: no output-"
                                       "stage writes within 180 s")
                time.sleep(0.5)
                now_written = written._value.get()
                compiles = ledger.snapshot(limit=1)["totals"]["compiles"]
                quiet_ticks = (quiet_ticks + 1
                               if (now_written == prev
                                   and compiles == prev_compiles) else 0)
                # three quiet ticks: drained AND no compile for ~1.5 s
                # (the host-bucket warm sequence spaces compiles well
                # inside that)
                if now_written > 0 and quiet_ticks >= 3:
                    break
                prev = now_written
                prev_compiles = compiles
            ingress.close()
            print(f"[soak] pipeline warm ({written._value.get():.0f} lines "
                  "through); starting baseline load")

            scraper.start()

            # -- phase 1: baseline (the pre-fault window) -----------------
            generator = new_generator(factory, args.seconds, args.settle)
            generator.start()
            generator.wait(timeout=args.seconds + args.settle + 120)
            baseline = generator.stop()
            generator = None
            card = baseline["scorecard"]
            record["baseline"] = card
            check("baseline_loss_zero", card["loss"] == 0,
                  f"loss={card['loss']} of {card['sent_frames']} frames "
                  f"({card['sent_lines']} lines)")
            check("baseline_goodput",
                  (card["goodput_ratio"] or 0) >= 0.95,
                  f"achieved {card['achieved_lines_per_s']}/s of "
                  f"{card['offered_lines_per_s']}/s offered "
                  f"(ratio {card['goodput_ratio']})")
            check("baseline_histogram_populated",
                  card["latency"]["count"] > 0,
                  f"{card['latency']['count']} client-observed samples, "
                  f"p99={card['latency']['p99_ms']}ms")
            baseline_fired = set(evaluator.fired())
            if args.scenario == "none":
                check("no_alert_fired", not baseline_fired,
                      f"fired={sorted(baseline_fired)}")

            # -- phase 2: chaos under continued load ----------------------
            if args.scenario != "none":
                print(f"[soak] injecting fault: {args.scenario} "
                      f"({fault_s:.0f} s, time scale {time_scale:g})")
                if args.scenario == "stall":
                    install_stall(services, stall_flag)
                elif args.scenario == "ingress_crash":
                    install_crash_stall(services, stall_flag)
                lead_s, tail_s = 5.0, 20.0
                generator = new_generator(
                    factory, lead_s + fault_s + tail_s,
                    settle=fault_s + 60.0)
                generator.start()
                time.sleep(lead_s)
                fault_t0 = time.monotonic()
                if args.scenario == "stall":
                    stall_flag.set()
                    time.sleep(fault_s)
                    stall_flag.clear()
                elif args.scenario == "slow_sink":
                    generator.collector_pause.set()
                    time.sleep(fault_s)
                    generator.collector_pause.clear()
                elif args.scenario == "recompile":
                    inject_recompiles()
                    time.sleep(max(0.0, fault_s - 2.0))
                elif args.scenario == "replica_kill":
                    # victim = the last replica behind the REAL router.
                    # Wedge first (engine stopped, admin plane still up):
                    # dispatched frames pile up unacked in its ingress —
                    # the state a dying process leaves behind. Then the
                    # admin plane goes too and the supervisor's probe
                    # turns unreachable → drain → deadline requeue.
                    router_service = services[1]
                    victim = next(
                        s for s in reversed(services)
                        if s.settings.component_id.startswith(
                            "soak-detector"))
                    victim_pos = router_service.settings.router_replicas \
                        .index(victim.settings.engine_addr)
                    victim.stop()
                    time.sleep(5.0)      # bank unacked frames on the victim
                    victim.web_server.stop()
                    time.sleep(max(0.0, fault_s - 5.0))
                    victim.web_server.start()
                    victim.start()
                    # http_port=0 re-binds an ephemeral port on restart:
                    # re-point the supervisor (deployments use stable URLs)
                    router_service.engine.router.replicas[victim_pos] \
                        .admin_url = (f"http://127.0.0.1:"
                                      f"{victim.web_server.port}")
                elif args.scenario == "noisy_neighbor":
                    # the "fault" is traffic: a second generator, tenant
                    # "aggr", offered 10x its quota while the victim keeps
                    # streaming — admission at the parser's ingress is what
                    # stands between the aggressor and the victim's SLO
                    aggressor = new_generator(
                        factory, fault_s, settle=2.0,
                        rate=aggr_quota * 10, tenant="aggr", listen=False,
                        component_id="soak-loadgen-aggr")
                    aggressor.start()
                    aggressor.wait(timeout=fault_s + 60.0)
                    record["aggressor"] = aggressor.stop()["scorecard"]
                elif args.scenario == "chaos_mesh":
                    # arm the seeded plan through the parser's REAL admin
                    # plane (arming zeroes the per-site op counters, so the
                    # plan's op windows are chaos-phase-relative), then
                    # plant the poison: marker frames sent straight into
                    # the ingress OUTSIDE the generator's trace accounting
                    # — the loss gate stays exact (generator loss must be
                    # zero, poison must land in the DLQ; neither may
                    # vanish into the other's ledger)
                    parser_service = services[0]
                    admin_port = parser_service.web_server.port
                    armed = admin_call(
                        admin_port, "/admin/faults",
                        {"action": "arm", "plan": CHAOS_MESH_PLAN})
                    record["fault_plan"] = armed["plan"]
                    poison_lines = [
                        f"type=CHAOS msg=audit(999): {CHAOS_MESH_POISON}"
                        f"-{i} injected poison payload" for i in range(5)]
                    poison_sock = factory.create_output(
                        "inproc://soak-parser")
                    # spread the sends across the first ~60% of the window:
                    # DeadLetterGrowing is about ACTIVE growth (its
                    # increase() conjunct), so the quarantine counter must
                    # step while depth stands — five frames in one burst
                    # would be a counter born at 5 that never increases
                    poison_t0 = time.monotonic()
                    gap_s = fault_s * 0.6 / len(poison_lines)
                    for line in poison_lines:
                        poison_sock.send(pack_batch([line.encode("utf-8")]))
                        time.sleep(gap_s)
                    poison_sock.close()
                    record["poison_frames_sent"] = len(poison_lines)
                    time.sleep(max(0.0, fault_s
                                   - (time.monotonic() - poison_t0)))
                elif args.scenario == "ingress_crash":
                    # wedge first so ingress frames bank UNACKED in the
                    # parser's spool (appended at recv, ack blocked behind
                    # the stalled component call), then die cold inside
                    # the wedge: no drain epilogue, no acks, no clean
                    # manifest commit — the in-flight burst's results are
                    # gone exactly as kill -9 loses them. The outage then
                    # runs with the engine thread dead while the
                    # scrape-time spool-age gauge keeps climbing.
                    parser_service = services[0]
                    stall_flag.set()
                    time.sleep(4.0)      # bank unacked frames in the wedge
                    parser_service.engine.crash_abort()
                    stall_flag.clear()
                    crash_spool = parser_service.engine.spool
                    record["wal_at_crash"] = crash_spool.stats()
                    print(f"[soak] parser crashed with "
                          f"{record['wal_at_crash']['depth_frames']} "
                          "unacked spool frames; outage begins")
                    time.sleep(max(0.0, fault_s - 4.0))
                    # "restarted process": recovery must replay the
                    # unacked suffix before accepting the banked backlog
                    parser_service.start()
                elif args.scenario == "rollout":
                    # phase A (healthy): one full dmroll cycle under load —
                    # sample → fine-tune → checkpoint → shadow → promote →
                    # hot-swap, all while the generator streams
                    det_service = services[1]
                    mgr = det_service.rollout
                    info = mgr.run_cycle(reason="soak", block=True)
                    record["rollout_cycle"] = info
                    outcome = info.get("outcome") or {}
                    check("rollout_promoted_mid_stream",
                          outcome.get("result") == "promoted",
                          f"cycle: {info.get('skipped') or outcome}")
                    # phase B (broken canary): live params scaled 10x —
                    # saturated logits, scores orders of magnitude off;
                    # the gate overrides keep it shadowing (divergence
                    # flowing) for most of the fault window, then the
                    # shadow timeout resolves it to a holdback. The
                    # manager thread ticks the shadow ~1/s by itself.
                    import jax

                    det = det_service.library_component
                    broken = jax.tree_util.tree_map(lambda a: a * 10.0,
                                                    det._exec.params)
                    mgr.inject_candidate(
                        broken, det._exec.opt_state, tag="broken-injected",
                        min_samples=10**9,
                        timeout_s=max(5.0, fault_s - 10.0))
                    time.sleep(fault_s)
                elif args.scenario == "drift":
                    # the "fault" is traffic: a second generator streams
                    # 100% anomalous comms alongside the baseline mix the
                    # outer generator keeps offering (its scorecard stays
                    # the exact zero-loss ledger). The dmdrift monitor is
                    # on its own: it must notice the live score
                    # distribution walking away from the pinned baseline,
                    # emit drift_detected, kick the dmroll cycle early,
                    # and come back clean after the promotion re-pins —
                    # the harness only watches.
                    det_service = services[1]
                    shift_mix = PayloadMix.from_dict({
                        "anomaly": 1.0, "json": 0.0, "invalid_utf8": 0.0})
                    shifter = new_generator(
                        factory, fault_s, settle=2.0,
                        rate=args.rate, listen=False,
                        component_id="soak-loadgen-shift",
                        mix_override=shift_mix)
                    shifter.start()
                    cleared_at = None
                    while time.monotonic() - fault_t0 < fault_s:
                        st = det_service.drift.status()
                        if (cleared_at is None and st["ticks"] > 0
                                and not st["drifting"]
                                and any(e.get("kind") == "drift_cleared"
                                        for e in st["events"])):
                            cleared_at = time.monotonic() - fault_t0
                            print(f"[soak] drift detected, retrained, and "
                                  f"cleared {cleared_at:.0f}s into the "
                                  "shift; holding load to the window end")
                        time.sleep(1.0)
                    shifter.wait(timeout=fault_s + 60.0)
                    record["shift_traffic"] = shifter.stop()["scorecard"]
                    record["drift_cleared_after_s"] = (
                        None if cleared_at is None else round(cleared_at, 1))
                fault_held_s = time.monotonic() - fault_t0
                generator.wait(timeout=lead_s + fault_s + tail_s
                               + fault_s + 60.0 + 60.0)
                chaos = generator.stop()
                generator = None
                record["chaos"] = chaos["scorecard"]
                record["chaos"]["fault_held_s"] = round(fault_held_s, 1)
                fired = set(evaluator.fired())
                for alert in expected_alerts:
                    check(f"alert_{alert}_fired", alert in fired,
                          "transitioned to firing under the fault"
                          if alert in fired else
                          f"never fired (fired={sorted(fired)})")
                check("recovered_after_fault",
                      chaos["scorecard"]["received_frames"] > 0,
                      f"received {chaos['scorecard']['received_frames']} "
                      "frames across the chaos window")
                if args.scenario == "replica_kill":
                    # the router-tier contract, gated by execution: the
                    # drain was observed, the victim's unacked frames were
                    # redelivered, nothing was lost after the settle
                    # window, and the survivors' warm compile set held
                    router_service = services[1]
                    snap = router_service.engine.router.snapshot()
                    record["router"] = snap
                    check("router_requeue_positive",
                          snap["requeue_total"] > 0,
                          f"router_requeue_total={snap['requeue_total']}")
                    kinds = [e.get("kind") for e in
                             router_service.events.snapshot()["events"]]
                    check("replica_drain_event_emitted",
                          "replica_drain" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("post_settle_loss_zero",
                          chaos["scorecard"]["loss"] == 0,
                          f"loss={chaos['scorecard']['loss']} of "
                          f"{chaos['scorecard']['sent_frames']} frames")
                    ledger_doc = device_obs.get_ledger().snapshot()
                    unexpected = ledger_doc["totals"]["unexpected"]
                    record["xla_unexpected"] = [
                        c for c in ledger_doc.get("compiles", [])
                        if c.get("unexpected")]
                    check("no_unexpected_recompiles_on_survivors",
                          unexpected == 0,
                          f"scorer_xla_recompiles_unexpected_total="
                          f"{unexpected}")
                if args.scenario == "noisy_neighbor":
                    # the isolation contract, gated by execution: every
                    # victim frame was admitted and delivered inside its
                    # SLO, every shed frame belonged to the aggressor, and
                    # the shed storm was visible (load_shed event + the
                    # ShedRateHigh rule via the generic alert loop above)
                    parser_service = services[0]
                    snap = parser_service.admission.snapshot()
                    record["admission"] = snap
                    victim_counts = snap["tenants"].get(
                        "victim", {"admitted_frames": 0, "shed_frames": 0})
                    aggr_counts = snap["tenants"].get(
                        "aggr", {"admitted_frames": 0, "shed_frames": 0})
                    check("victim_loss_zero",
                          chaos["scorecard"]["loss"] == 0,
                          f"loss={chaos['scorecard']['loss']} of "
                          f"{chaos['scorecard']['sent_frames']} victim "
                          "frames (unique trace ids)")
                    p99 = chaos["scorecard"]["latency"]["p99_ms"]
                    check("victim_p99_inside_slo",
                          p99 is not None and p99 <= args.slo_ms,
                          f"victim p99={p99}ms against slo={args.slo_ms}ms "
                          "with the aggressor at 10x quota")
                    check("shed_on_aggressor_only",
                          aggr_counts["shed_frames"] > 0
                          and victim_counts["shed_frames"] == 0,
                          f"aggr shed={aggr_counts['shed_frames']} "
                          f"admitted={aggr_counts['admitted_frames']}; "
                          f"victim shed={victim_counts['shed_frames']} "
                          f"admitted={victim_counts['admitted_frames']}")
                    check("aggressor_throttled_to_quota",
                          aggr_counts["shed_frames"]
                          > aggr_counts["admitted_frames"],
                          "the majority of the aggressor's frames were "
                          f"refused ({aggr_counts['shed_frames']} shed vs "
                          f"{aggr_counts['admitted_frames']} admitted)")
                    kinds = [e.get("kind") for e in
                             parser_service.events.snapshot()["events"]]
                    check("load_shed_event_emitted",
                          "load_shed" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                if args.scenario == "ingress_crash":
                    # the durability contract, gated by execution: frames
                    # were banked unacked at the crash, recovery actually
                    # replayed them, the collector saw every unique trace
                    # id end-to-end, and the spool drained back to acked
                    parser_service = services[0]
                    spool = parser_service.engine.spool
                    record["wal"] = spool.stats()
                    check("wal_unacked_at_crash",
                          record["wal_at_crash"]["depth_frames"] > 0,
                          f"{record['wal_at_crash']['depth_frames']} "
                          "frames banked unacked when the parser died")
                    replayed = parser_service.engine \
                        ._m_wal_recovered._value.get()
                    check("wal_recovery_replayed",
                          replayed > 0,
                          "wal_replayed_frames_total{mode='recovery'}="
                          f"{replayed:.0f}")
                    check("post_settle_loss_zero",
                          chaos["scorecard"]["loss"] == 0,
                          f"loss={chaos['scorecard']['loss']} of "
                          f"{chaos['scorecard']['sent_frames']} frames "
                          "(unique trace ids; recovery duplicates "
                          "collapse)")
                    check("wal_spool_drained",
                          record["wal"]["depth_frames"] == 0,
                          f"depth={record['wal']['depth_frames']} acked="
                          f"{record['wal']['acked_seq']} of "
                          f"{record['wal']['last_appended_seq']}")
                if args.scenario == "chaos_mesh":
                    # the dmfault contract, gated by execution: nothing
                    # non-poison was lost, every poison frame reached the
                    # DLQ, the engine loop outlived the fsync EIO burst
                    # with durability re-armed, the whole fault family's
                    # evidence trail (events + alerts) actually appeared,
                    # the fired log equals the seed's precomputed schedule
                    # (determinism proved by execution, not by assertion),
                    # and requeue drains the quarantine back to zero
                    parser_service = services[0]
                    admin_port = parser_service.web_server.port
                    n_poison = record["poison_frames_sent"]
                    spool = parser_service.engine.spool
                    record["wal"] = spool.stats()
                    check("non_poison_loss_zero",
                          chaos["scorecard"]["loss"] == 0,
                          f"loss={chaos['scorecard']['loss']} of "
                          f"{chaos['scorecard']['sent_frames']} generator "
                          "frames (unique trace ids) across latency + "
                          "fsync EIO + poison")
                    check("engine_alive_through_fsync_eio",
                          parser_service.engine.running,
                          "the parser's engine loop survived "
                          f"{record['wal']['disk_errors']} absorbed disk "
                          "errors (the pre-dmfault build died at the "
                          "first fsync EIO)")
                    check("wal_degraded_and_rearmed",
                          record["wal"]["disk_errors"] > 0
                          and not record["wal"]["degraded"],
                          f"disk_errors={record['wal']['disk_errors']} "
                          "absorbed, durability re-armed after the burst "
                          f"(degraded={record['wal']['degraded']})")
                    dlq_doc = admin_call(admin_port, "/admin/dlq")
                    record["dlq"] = dlq_doc
                    reasons = {e["reason"] for e in dlq_doc["entries"]}
                    check("poison_quarantined",
                          dlq_doc["depth_frames"] == n_poison
                          and dlq_doc["quarantined_total"] >= n_poison
                          and reasons <= {"processing_error",
                                          "recovery_replay"},
                          f"depth={dlq_doc['depth_frames']} of {n_poison} "
                          f"poison frames, quarantined_total="
                          f"{dlq_doc['quarantined_total']}, "
                          f"reasons={sorted(reasons)}")
                    kinds = [e.get("kind") for e in
                             parser_service.events.snapshot()["events"]]
                    check("faults_armed_event_emitted",
                          "faults_armed" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("fault_injected_event_emitted",
                          "fault_injected" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("wal_degraded_event_emitted",
                          "wal_degraded" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("frame_quarantined_event_emitted",
                          "frame_quarantined" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    # disarm through the admin plane and collect the final
                    # fired log in the same call — then prove determinism:
                    # two fresh plans from the committed doc must compute
                    # identical schedules, and every rate/window fault
                    # that FIRED must be exactly the faults the schedule
                    # PLANNED for the ops each site performed (match-spec
                    # poison hits are payload-driven and excluded by
                    # construction)
                    from detectmateservice_tpu.faults import FaultPlan

                    final = admin_call(admin_port, "/admin/faults",
                                       {"action": "disarm"})
                    fired = final.get("fired_schedule", [])
                    ops = final.get("final", {}).get("ops", {})
                    record["fired_schedule"] = fired
                    record["fault_ops"] = ops
                    plan_a = FaultPlan.from_dict(CHAOS_MESH_PLAN)
                    plan_b = FaultPlan.from_dict(
                        json.loads(json.dumps(CHAOS_MESH_PLAN)))
                    sched_sites = ("wal_fsync", "sock_send")
                    check("fault_schedule_deterministic",
                          all(plan_a.schedule(s, ops.get(s, 0))
                              == plan_b.schedule(s, ops.get(s, 0))
                              for s in sched_sites),
                          f"seed={CHAOS_MESH_PLAN['seed']}: two fresh "
                          "plans computed identical schedules over "
                          f"ops={ {s: ops.get(s, 0) for s in sched_sites} }")
                    mismatches = {
                        site: (len([f for f in fired
                                    if f["site"] == site]),
                               len(plan_a.schedule(site, ops.get(site, 0))))
                        for site in sched_sites
                        if [(f["op"], f["kind"]) for f in fired
                            if f["site"] == site]
                        != plan_a.schedule(site, ops.get(site, 0))}
                    check("fired_equals_planned_schedule", not mismatches,
                          "every fired rate/window fault matches the "
                          "seed's precomputed schedule op-for-op"
                          if not mismatches else
                          f"fired != planned (site: fired, planned) "
                          f"{mismatches}")
                    # recovery: requeue the quarantine with the plan
                    # disarmed — the frames must reprocess cleanly and
                    # the DLQ must drain to zero
                    requeued = admin_call(admin_port, "/admin/dlq",
                                          {"action": "requeue"})
                    deadline = time.monotonic() + 30
                    while (time.monotonic() < deadline
                           and parser_service.engine.dlq.depth_frames()):
                        time.sleep(0.5)
                    dlq_after = admin_call(admin_port, "/admin/dlq")
                    record["dlq_after_requeue"] = dlq_after
                    check("dlq_drained_after_requeue",
                          requeued["requeued"] == n_poison
                          and dlq_after["depth_frames"] == 0
                          and dlq_after["requeued_total"] == n_poison,
                          f"requeued {requeued['requeued']} frames, "
                          f"depth={dlq_after['depth_frames']} after "
                          "reprocessing with the plan disarmed")
                if args.scenario == "rollout":
                    # the rollout contract, gated by execution: the swap
                    # was served, nothing was lost across it, the compile
                    # set held, the divergence series populated, and the
                    # broken canary was held back
                    det_service = services[1]
                    det = det_service.library_component
                    status = det_service.rollout.status()
                    record["rollout_status"] = status
                    check("rollout_loss_zero_across_swap",
                          chaos["scorecard"]["loss"] == 0,
                          f"loss={chaos['scorecard']['loss']} of "
                          f"{chaos['scorecard']['sent_frames']} frames")
                    check("rollout_live_version_served",
                          (status["live_version"] is not None
                           and det.model_version()
                           == status["live_version"]),
                          f"detector serves v{det.model_version()}, store "
                          f"live v{status['live_version']}")
                    kinds = [e.get("kind") for e in
                             det_service.events.snapshot()["events"]]
                    check("model_canary_holdback_event",
                          "model_canary_holdback" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    from prometheus_client import generate_latest
                    div_count = sum(
                        float(line.rsplit(" ", 1)[1])
                        for line in generate_latest().decode().splitlines()
                        if line.startswith("model_shadow_divergence_count"))
                    check("divergence_series_populated", div_count > 0,
                          f"model_shadow_divergence_count={div_count:.0f}")
                    ledger_doc = device_obs.get_ledger().snapshot()
                    unexpected = ledger_doc["totals"]["unexpected"]
                    check("no_unexpected_recompiles_across_swap",
                          unexpected == 0,
                          f"scorer_xla_recompiles_unexpected_total="
                          f"{unexpected}")
                if args.scenario == "drift":
                    # the dmdrift contract, gated by execution: the monitor
                    # (not the harness) noticed the shift, retrained
                    # through the kicked cycle, came back clean after the
                    # promotion re-pinned the baseline, nothing was lost
                    # across the hot-swap, and the capacity model the
                    # router would scale on agrees with a closed-loop
                    # probe run right now on the same host
                    det_service = services[1]
                    det = det_service.library_component
                    dstatus = det_service.drift.status()
                    rstatus = det_service.rollout.status()
                    record["drift_status"] = dstatus
                    record["rollout_status"] = rstatus
                    check("drift_loss_zero_across_swap",
                          chaos["scorecard"]["loss"] == 0,
                          f"loss={chaos['scorecard']['loss']} of "
                          f"{chaos['scorecard']['sent_frames']} baseline-"
                          "mix frames (unique trace ids)")
                    kinds = [e.get("kind") for e in
                             det_service.events.snapshot()["events"]]
                    check("drift_baseline_pinned_event",
                          "drift_baseline_pinned" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("drift_detected_event",
                          "drift_detected" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("drift_cycle_event",
                          "drift_cycle" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("drift_cleared_event",
                          "drift_cleared" in kinds,
                          f"event kinds seen: {sorted(set(kinds))}")
                    check("drift_kicked_cycle_promoted",
                          (rstatus["live_version"] is not None
                           and det.model_version()
                           == rstatus["live_version"]),
                          f"detector serves v{det.model_version()}, store "
                          f"live v{rstatus['live_version']} (fine-tuned on "
                          "the drifted sample via the kicked cycle)")
                    # the flag must have CLEARED after the promotion
                    # re-pinned the baseline from the shifted traffic.
                    # (By check time the load has reverted to the
                    # baseline mix, which correctly re-registers as
                    # drift against the v1 baseline — end-state
                    # `drifting` is the detector working, not a bug.)
                    check("drift_cleared_after_promotion",
                          (record.get("drift_cleared_after_s") is not None
                           and (dstatus["baseline"] or {}).get("version")
                           == rstatus["live_version"]),
                          f"cleared_after_s="
                          f"{record.get('drift_cleared_after_s')} with "
                          f"baseline "
                          f"v{(dstatus['baseline'] or {}).get('version')} "
                          f"re-pinned at the v{rstatus['live_version']} "
                          f"promotion ({dstatus['ticks']} evaluations)")
                    # traffic-arithmetic evidence first: the model was fed
                    # by the live dispatch tap throughout the load phases
                    cstatus = det_service.capacity.status()
                    record["capacity_status_under_load"] = cstatus
                    modeled = cstatus["capacity_lines_per_s"]
                    check("capacity_model_populated",
                          modeled is not None and modeled > 0,
                          f"replica_capacity_lines_per_s={modeled} "
                          f"(source={cstatus['capacity_source']})")
                    # then the calibration gate: traffic arithmetic under
                    # a shared-GIL drain reads the CONTENDED device rate,
                    # so let the pipeline finish its backlog and the
                    # monitor refresh off the idle micro-probe before
                    # comparing against a fresh closed-loop bench — both
                    # sides then measure the same uncontended host
                    flip_deadline = time.monotonic() + 150.0
                    while time.monotonic() < flip_deadline:
                        cstatus = det_service.capacity.status()
                        if cstatus["capacity_source"] == "probe":
                            break
                        time.sleep(1.0)
                    record["capacity_status"] = cstatus
                    modeled = cstatus["capacity_lines_per_s"]
                    bench = det_service.capacity.probe_now()
                    record["capacity_bench_lines_per_s"] = bench
                    ratio = (modeled / bench
                             if modeled and bench else None)
                    check("capacity_within_25pct_of_bench",
                          ratio is not None and 0.75 <= ratio <= 1.25,
                          f"modeled {modeled} "
                          f"(source={cstatus['capacity_source']}) vs "
                          f"closed-loop bench {bench} lines/s "
                          f"(ratio={ratio})")
                    from prometheus_client import generate_latest
                    scrape = generate_latest().decode()
                    series_present = [
                        s for s in ("model_drift_score",
                                    "model_drift_features_over_threshold",
                                    "replica_capacity_lines_per_s",
                                    "capacity_headroom_ratio")
                        if any(line.startswith(s)
                               for line in scrape.splitlines())]
                    check("drift_capacity_series_scraped",
                          len(series_present) == 4,
                          f"series on /metrics: {series_present}")
                    ledger_doc = device_obs.get_ledger().snapshot()
                    unexpected = ledger_doc["totals"]["unexpected"]
                    check("no_unexpected_recompiles_across_swap",
                          unexpected == 0,
                          f"scorer_xla_recompiles_unexpected_total="
                          f"{unexpected}")
        finally:
            if generator is not None:
                try:
                    generator.stop()
                except Exception:
                    pass
            # dmtel evidence: the collector's assembly/sampling stats ride
            # in the verdict JSON (no gate — the telemetry-smoke CI job
            # owns the hard assertions)
            for service in services:
                if getattr(service, "telemetry", None) is not None:
                    record["telemetry"] = (
                        service.telemetry.snapshot()["stats"])
            scraper.stop()
            teardown_pipeline(services)

    record["alerts"] = evaluator.report()
    record["recording_rules"] = evaluator.recording_report()
    record["elapsed_s"] = round(time.monotonic() - t0, 1)
    record["checks"] = checks
    record["pass"] = all(c["ok"] for c in checks)

    out = (Path(args.out_dir)
           / f"SOAK_{args.scenario}_{time.strftime('%Y%m%d-%H%M%S')}.json")
    out.write_text(json.dumps(record, indent=2) + "\n", encoding="utf-8")
    print(f"[soak] verdict {'PASS' if record['pass'] else 'FAIL'} "
          f"({record['elapsed_s']:.0f}s) -> {out}")
    return 0 if record["pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
