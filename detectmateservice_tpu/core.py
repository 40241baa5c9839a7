"""Service: the control-plane core wrapping one Engine + one component.

Capability parity with the reference's ``Service`` (reference:
src/service/core.py:64-436) with one deliberate design change: the reference
makes ``Service`` *inherit* Engine and pass itself as the Engine's processor
(reference: core.py:64,155 — noted as a quirk in SURVEY.md §1); here the
Service *owns* an Engine and hands it a ``LibraryComponentProcessor`` adapter.
The observable contract is identical: metrics wrap ``process``, ``None``
means the message is filtered, lifecycle verbs behave the same.

Lifecycle (reference: core.py:213-351): ``run()`` starts the admin server,
autostarts the engine, parks on an exit event; ``start``/``stop`` wrap the
Engine and flip the ``engine_running`` metric; ``reconfigure`` updates the
ConfigManager with optional persistence; ``shutdown`` unparks ``run``.
Context-manager use calls ``setup_io()`` on enter (the documented
load-models-here hook, reference: core.py:209-211,424-436) and ``shutdown()``
on exit.

Improvement over a reference gap (SURVEY.md §2.3): ``reconfigure`` *does*
re-apply config to the loaded component when the component exposes a
``reconfigure(dict)`` hook; components without the hook keep running on their
old config, which is then only visible to new instances — the reference
silently always did the latter.
"""
from __future__ import annotations

import json
import logging
import sys
import threading
from pathlib import Path
from typing import Any, Dict, Optional, Type

from .config import ComponentLoader, ComponentResolver, ConfigClassLoader, ConfigManager
from .config.manager import ConfigError
from .engine import Engine, EngineSocketFactory, make_socket_factory
from .engine import metrics as m
from .engine.health import (
    EventLog,
    EventLogHandler,
    HealthMonitor,
    JsonLogFormatter,
    install_thread_excepthook,
    remove_excepthook_sink,
    set_build_info,
)
from .library.common.core import CoreComponent, CoreConfig
from .settings import ServiceSettings
from .web.server import WebServer


class ServiceError(Exception):
    pass


class LibraryComponentProcessor:
    """Adapter: wraps a CoreComponent with the service-level metrics
    (reference behavior: core.py:176-206). With no component, echoes input
    (passthrough, reference: core.py:201-205)."""

    def __init__(self, component: Optional[CoreComponent], labels: Dict[str, str]):
        self.component = component
        self._processed_b = m.DATA_PROCESSED_BYTES().labels(**labels)
        self._processed_l = m.DATA_PROCESSED_LINES().labels(**labels)
        self._duration = m.PROCESSING_DURATION().labels(**labels)
        self._batch_hist = m.BATCH_SIZE_HIST().labels(**labels)
        # fused-frame contract is opt-in per component: expose process_frames
        # ONLY when the component implements it, so the engine's capability
        # probe (getattr) sees the truth through the adapter
        if callable(getattr(component, "process_frames", None)):
            self.process_frames = self._process_frames

    def process(self, data: bytes) -> Optional[bytes]:
        self._processed_b.inc(len(data))
        self._processed_l.inc(max(1, data.count(b"\n") + (0 if data.endswith(b"\n") else 1)))
        with self._duration.time():
            if self.component is None:
                return data
            return self.component.process(data)

    def process_batch(self, batch):
        """Batched dispatch for accelerator-backed components; falls back to a
        per-message loop so any component works under micro-batching."""
        # aggregated counter updates: per-message .inc() calls were a
        # measurable slice of the per-message service floor at 100k+ rates
        self._processed_b.inc(sum(map(len, batch)))
        self._processed_l.inc(sum(
            max(1, data.count(b"\n") + (0 if data.endswith(b"\n") else 1))
            for data in batch))
        self._batch_hist.observe(len(batch))
        with self._duration.time():
            if self.component is None:
                return list(batch)
            batch_fn = getattr(self.component, "process_batch", None)
            if callable(batch_fn):
                return batch_fn(batch)
            return [self.component.process(data) for data in batch]

    def _process_frames(self, frames):
        """Fused-frame dispatch: whole wire frames straight to the component
        (which expands + featurizes them natively); returns
        ``(outputs, n_messages, n_lines)`` per the engine's process_frames
        contract. Byte metrics count wire bytes; line metrics use the
        component-reported newline-rule total so the read/processed/written
        series stay in one unit."""
        self._processed_b.inc(sum(map(len, frames)))
        with self._duration.time():
            outs, n_msgs, n_lines = self.component.process_frames(frames)
        self._processed_l.inc(n_lines)
        self._batch_hist.observe(n_msgs)
        return outs, n_msgs, n_lines

    def flush(self):
        """Drain a pipelined component (engine calls this on idle)."""
        if self.component is None:
            return []
        flush_fn = getattr(self.component, "flush", None)
        return flush_fn() if callable(flush_fn) else []

    def pending_count(self) -> int:
        """In-flight results held by the component (engine poll hint)."""
        fn = getattr(self.component, "pending_count", None)
        return fn() if callable(fn) else 0

    def drain_ready(self):
        """Non-blocking drain of already-landed results (engine short-poll
        tick); components without the hook fall back to flush()."""
        fn = getattr(self.component, "drain_ready", None)
        return fn() if callable(fn) else self.flush()

    def flush_final(self):
        """Stop-time drain: unlike ``flush`` this may block (e.g. waiting out
        a background boundary fit) so nothing pending is lost at shutdown."""
        if self.component is None:
            return []
        final_fn = (getattr(self.component, "flush_final", None)
                    or getattr(self.component, "flush", None))
        return final_fn() if callable(final_fn) else []


class Service:
    def __init__(
        self,
        settings: ServiceSettings,
        component_config: Optional[Dict[str, Any]] = None,
        socket_factory: Optional[EngineSocketFactory] = None,
    ) -> None:
        self.settings = settings
        self.logger = self._setup_logging()
        # record the platform choice WITHOUT importing jax — non-jax
        # components (parsers, readers) must not pay jax's import cost;
        # jax-using components apply the pin before their first jax op
        # (DETECTMATE_BACKEND=cpu reaches here via the settings env layer)
        from .utils.backend import request_platform

        request_platform(settings.backend)
        # multi-host chip plane: when a coordinator is configured, join this
        # process's devices into the global mesh BEFORE any component can
        # initialize a jax backend. The import stays behind the check — the
        # parallel package pulls in jax, which non-jax stages must not pay.
        import os as _os

        if (settings.coordinator_address
                or _os.environ.get("DETECTMATE_COORDINATOR_ADDRESS")):
            from .parallel.distributed import initialize_from_settings

            initialize_from_settings(settings, self.logger)
        # persistent compile cache (utils/profiling.py): armed BEFORE the
        # component loads so the very first jit — warm-up included — is
        # cache-backed. JAX_COMPILATION_CACHE_DIR places it; without that
        # variable compile_cache_dir does (the settings validator already
        # proved it writable), else the fixed in-checkout default. Gated on
        # the setting so non-jax stages never pay the jax import; the
        # backend is resolved first because the cache's off-on-CPU default
        # asks jax which backend it runs on.
        self.compile_cache_dir: Optional[str] = None
        if settings.compile_cache_enabled:
            from .utils.backend import apply_platform_pin
            from .utils.profiling import enable_compilation_cache

            apply_platform_pin()
            self.compile_cache_dir = enable_compilation_cache(
                settings.compile_cache_dir or "")
            if self.compile_cache_dir:
                self.logger.info("persistent compile cache armed at %s",
                                 self.compile_cache_dir)
            else:
                self.logger.warning(
                    "compile_cache_enabled but the persistent cache stayed "
                    "off (CPU backend with no directory named — set "
                    "compile_cache_dir or JAX_COMPILATION_CACHE_DIR)")
        self._labels = dict(
            component_type=settings.component_type,
            component_id=settings.component_id or "unknown",
        )
        self._service_exit_event = threading.Event()

        # self-diagnosis plane (engine/health.py): the structured event ring
        # behind GET /admin/events, the watchdog behind GET /admin/health,
        # the process-wide thread excepthook (no daemon worker dies silently
        # to stderr), and the dm_build_info gauge. All wired before the
        # component loads so its workers can register heartbeats.
        self.events = EventLog(maxlen=settings.event_ring_size)
        self.health = HealthMonitor(
            dict(self._labels),
            stage=(settings.trace_stage or settings.component_name
                   or settings.component_type),
            stall_seconds=settings.watchdog_stall_seconds,
            unhealthy_seconds=settings.watchdog_unhealthy_seconds,
            interval_s=settings.watchdog_interval_s,
            recovery_intervals=settings.watchdog_recovery_intervals,
            ingest_stall_seconds=settings.watchdog_ingest_stall_seconds,
            events=self.events,
            logger=self.logger,
        )
        # the logger mirrors WARNING+ records into the ring; a re-created
        # Service with the same identity reuses the logger, so stale handlers
        # pointing at a dead ring are replaced, not accumulated
        for handler in list(self.logger.handlers):
            if isinstance(handler, EventLogHandler):
                self.logger.removeHandler(handler)
        self.logger.addHandler(EventLogHandler(self.events))
        self._excepthook_sink = install_thread_excepthook(self.logger, self.events)
        set_build_info()

        # admin server constructed here, started in run() (reference: core.py:81)
        self.web_server = WebServer(self)

        # component-type resolution for non-core types (reference: core.py:85-112)
        self._component_path: Optional[str] = None
        if settings.component_type and settings.component_type != "core":
            resolver = ComponentResolver(logger=self.logger)
            self._component_path, config_class_path = resolver.resolve(settings.component_type)
            if not settings.component_config_class and config_class_path:
                settings.component_config_class = config_class_path

        # config manager (reference: core.py:119-133)
        self.config_manager: Optional[ConfigManager] = None
        if settings.config_file:
            self.config_manager = ConfigManager(
                settings.config_file, self.get_config_schema(), logger=self.logger
            )
            try:
                component_config = self.config_manager.load()
            except ConfigError as exc:
                raise ServiceError(f"cannot load component config: {exc}") from exc

        # component instantiation (reference: core.py:135-152)
        self.library_component: Optional[CoreComponent] = None
        if self._component_path:
            loader = ComponentLoader(logger=self.logger)
            self.library_component = loader.load_component(
                self._component_path, component_config
            )
            # component-side error counts must land in THIS service's
            # processing_errors_total series (same labels the engine uses),
            # not a parallel series keyed by class name
            self.library_component.metrics_labels = dict(self._labels)
            # component-side heartbeats (e.g. the scorer's dispatch workers)
            # register through the same monitor; a pipelined component with a
            # drain-progress counter also gets the stuck-inflight check
            self.library_component.health_monitor = self.health
            pending_fn = getattr(self.library_component, "pending_count", None)
            drained_fn = getattr(self.library_component, "drained_total", None)
            if callable(pending_fn) and callable(drained_fn):
                self.health.register_progress(
                    "device_inflight", pending_fn, drained_fn)

        self.processor = LibraryComponentProcessor(self.library_component, self._labels)

        # multi-tenant overload control (shed/): quota map + degradation
        # ladder + admission controller, built BEFORE the Engine so ingress
        # can consult them from the first frame. A tenants.yaml typo fails
        # construction here — a quota misload must stop the service, not
        # silently admit everything under the default.
        self.admission = None
        self.shed_ladder = None
        if settings.shed_enabled:
            from .engine.health import DegradationLadder
            from .shed import AdmissionController, load_quota_map
            from .shed.quota import default_quota_map

            if settings.tenants_file:
                quota_map = load_quota_map(
                    settings.tenants_file,
                    default_tier=settings.tenant_default_tier,
                    default_rate=settings.tenant_default_rate,
                    default_burst=settings.tenant_default_burst)
            else:
                quota_map = default_quota_map(
                    tier=settings.tenant_default_tier,
                    rate=settings.tenant_default_rate,
                    burst=settings.tenant_default_burst)
            self.shed_ladder = DegradationLadder(
                (settings.shed_ladder_backlog_t1,
                 settings.shed_ladder_backlog_t2,
                 settings.shed_ladder_backlog_t3),
                dict(self._labels),
                recovery_intervals=settings.shed_ladder_recovery_intervals,
                events=self.health.emit_event)
            self.health.add_check(self.shed_ladder)
            self.admission = AdmissionController(
                quota_map, dict(self._labels),
                buckets=settings.shed_tenant_buckets,
                retry_after_ms=settings.shed_retry_after_ms,
                ladder=self.shed_ladder,
                events=self.health.emit_event,
                logger=self.logger)
            self.logger.info(
                "admission control armed: %d named tenants, default "
                "tier=%s rate=%.0f/s, ladder thresholds %d/%d/%d",
                len(quota_map.tenants), quota_map.default.tier,
                quota_map.default.rate, settings.shed_ladder_backlog_t1,
                settings.shed_ladder_backlog_t2,
                settings.shed_ladder_backlog_t3)

        # deterministic fault injection (faults/): arm a seeded plan from
        # disk BEFORE the engine is built, so recovery replay and spool
        # setup already run under it. A malformed plan fails construction —
        # a chaos run that silently tested nothing is worse than no run.
        if settings.fault_plan_file:
            self._arm_fault_plan(settings.fault_plan_file)

        self.engine = Engine(settings, self.processor, socket_factory,
                             self.logger, health=self.health,
                             admission=self.admission)
        self.health.trace_recorder = self.engine.trace_recorder
        if self.shed_ladder is not None:
            # backlog probes the ladder sums each watchdog interval: rows
            # held/in flight in the processor, unsettled replica windows,
            # and the durable spool's unacked depth — every place pressure
            # pools when the process falls behind
            pending_fn = getattr(self.processor, "pending_count", None)
            if callable(pending_fn):
                self.shed_ladder.add_backlog_source(pending_fn)
            if self.engine.router is not None:
                self.shed_ladder.add_backlog_source(
                    self.engine.router.unacked_total)
            if self.engine.spool is not None:
                spool = self.engine.spool
                self.shed_ladder.add_backlog_source(spool.depth_frames)
        # device-observability plane (engine/device_obs.py): bind the
        # process-wide XLA compile ledger to THIS service's identity and
        # health plane, so an unexpected recompile lands in the event ring,
        # the xla_recompile_storm check, and scorer_xla_* series with the
        # right labels. Importless on non-jax stages — the ledger's jax
        # monitoring listener installs lazily from the scorer.
        from .engine import device_obs

        device_obs.get_ledger().bind(
            labels=dict(self._labels), monitor=self.health,
            emit_events=settings.recompile_alert_enabled,
            register_check=settings.recompile_alert_enabled)
        if settings.watchdog_enabled:
            self.health.start()

        # model lifecycle (rollout/): continuous fine-tuning + shadow-
        # scoring canary + zero-downtime hot-swap behind /admin/model.
        # Built only for components exposing the rollout hooks (the jax
        # scorer); the manager owns its own thread and versioned store.
        self.rollout = None
        if settings.rollout_enabled:
            if callable(getattr(self.library_component, "install_candidate",
                                None)):
                from .rollout import RolloutManager

                self.rollout = RolloutManager(
                    self.library_component, settings,
                    labels=dict(self._labels), monitor=self.health,
                    logger=self.logger)
                self.rollout.start()
            else:
                self.logger.warning(
                    "rollout_enabled but component %r has no rollout hooks; "
                    "model lifecycle disabled for this stage",
                    settings.component_type)

        # continuous observability (obs/): drift rides the rollout
        # subsystem's reservoir + store (the settings validator enforces
        # rollout_enabled), capacity taps the scorer directly; the SLO
        # tracker is threadless and always available behind GET /admin/slo.
        self.drift = None
        self.capacity = None
        if settings.drift_enabled and self.rollout is not None:
            from .obs import DriftMonitor

            self.drift = DriftMonitor(
                settings, sampler=self.rollout.sampler,
                store=self.rollout.store, rollout=self.rollout,
                labels=dict(self._labels), monitor=self.health,
                logger=self.logger)
            self.drift.start()
        if settings.capacity_enabled:
            if callable(getattr(self.library_component, "set_capacity_tap",
                                None)):
                from .obs import CapacityMonitor

                self.capacity = CapacityMonitor(
                    self.library_component, settings,
                    labels=dict(self._labels), logger=self.logger)
                self.capacity.start()
            else:
                self.logger.warning(
                    "capacity_enabled but component %r has no capacity "
                    "tap; capacity model disabled for this stage",
                    settings.component_type)
        from .obs import SloTracker

        self.slo = SloTracker()

        # cross-stage telemetry collector (telemetry/, dmtel): one stage
        # per pipeline runs it, like the router — assembles the span stream
        # every traced engine exports into whole-pipeline traces behind
        # GET /admin/traces. It reuses this service's socket factory so an
        # inproc test/smoke pipeline and its collector share one transport
        # namespace.
        self.telemetry = None
        if settings.telemetry_collector:
            from .telemetry import TelemetryCollector

            factory = socket_factory or make_socket_factory(
                getattr(settings, "transport_backend", "auto"), self.logger)
            self.telemetry = TelemetryCollector(
                settings, factory, labels=dict(self._labels),
                monitor=self.health, logger=self.logger)
            self.telemetry.start()
            self.logger.info(
                "telemetry collector listening on %s (healthy sample "
                "ratio %.3f, SLO %.0f ms)",
                settings.telemetry_collector_addr,
                settings.telemetry_sample_healthy_ratio,
                settings.telemetry_slo_ms)

        self._running_metric = m.ENGINE_RUNNING().labels(**self._labels)
        self._starts_metric = m.ENGINE_STARTS().labels(**self._labels)
        self._running_metric.state("stopped")

    # ------------------------------------------------------------------
    def get_config_schema(self) -> Type[CoreConfig]:
        """Dynamic config-class load with CoreConfig fallback
        (reference: core.py:158-174)."""
        path = self.settings.component_config_class
        if path:
            try:
                return ConfigClassLoader(logger=self.logger).load_config_class(path)
            except (ImportError, AttributeError, RuntimeError) as exc:
                self.logger.warning("cannot load config class %s: %s", path, exc)
        return CoreConfig

    def _arm_fault_plan(self, path: str) -> None:
        """Arm the seeded fault plan in ``path`` (JSON, FaultPlan.from_dict
        shape). Chaos harnesses point ``fault_plan_file`` here; production
        configs leave it unset and every site stays one untaken branch."""
        from . import faults
        from .faults import FaultPlan, FaultPlanError

        try:
            with open(path, "r", encoding="utf-8") as fh:
                doc = json.load(fh)
            plan = FaultPlan.from_dict(doc)
        except (OSError, ValueError, FaultPlanError) as exc:
            raise ServiceError(
                f"cannot arm fault plan from {path}: {exc}") from exc
        faults.arm(plan, labels=dict(self._labels),
                   events=self.health.emit_event, logger=self.logger)
        self.health.emit_event({
            "kind": "faults_armed", "seed": plan.seed,
            "specs": len(plan.specs), "source": path,
        })
        self.logger.warning(
            "FAULT INJECTION ARMED from %s: seed=%d, %d spec(s) — this "
            "process will deliberately fail", path, plan.seed,
            len(plan.specs))

    # -- lifecycle ------------------------------------------------------
    def setup_io(self) -> None:
        """Load models / pin params in HBM before traffic
        (reference hook: core.py:209-211). With ``checkpoint_dir`` set and a
        checkpoint present, the component's state (params + calibrated
        threshold) is restored here — a restarted detector resumes alerting
        without retraining (closes SURVEY §5.4 at the operator layer)."""
        if self.library_component is not None:
            self.library_component.setup_io()
            self._maybe_restore_checkpoint()
        self.logger.info("setup_io: ready to process messages")

    def _maybe_restore_checkpoint(self) -> None:
        directory = self.settings.checkpoint_dir
        if not directory:
            return
        load_fn = getattr(self.library_component, "load_checkpoint", None)
        if not callable(load_fn):
            return
        if not (Path(directory) / "meta.json").exists():
            self.logger.info(
                "checkpoint_dir %s has no checkpoint yet; starting fresh",
                directory)
            return
        try:
            load_fn(directory)
        except Exception as exc:
            # a present-but-unloadable checkpoint (tree-version mismatch,
            # corruption) is an operator problem — starting silently fresh
            # would discard the calibration they asked to keep
            raise ServiceError(
                f"cannot restore checkpoint from {directory}: {exc}") from exc
        self.logger.info("component state restored from %s", directory)

    def checkpoint(self) -> Dict[str, Any]:
        """Save the component's state to ``settings.checkpoint_dir`` (admin
        verb; also called automatically at clean shutdown)."""
        directory = self.settings.checkpoint_dir
        if not directory:
            raise ServiceError(
                "no checkpoint_dir configured (settings.checkpoint_dir)")
        save_fn = getattr(self.library_component, "save_checkpoint", None)
        if not callable(save_fn):
            raise ServiceError(
                "component does not support checkpointing "
                "(no save_checkpoint hook)")
        save_fn(directory)
        self.logger.info("component state checkpointed to %s", directory)
        return {"checkpoint": "saved", "directory": directory}

    def run(self) -> None:
        """Blocking main: admin server up, engine (auto)started, park until
        shutdown (reference: core.py:213-237)."""
        self.web_server.start()
        # web_server.port, not settings.http_port: with an ephemeral port
        # request (http_port: 0) the log must name the port that actually
        # bound, or the operator has no way to find the admin plane
        self.logger.info(
            "HTTP Admin active at %s:%s", self.settings.http_host, self.web_server.port
        )
        if self.settings.engine_autostart:
            self.logger.info("Auto-starting engine...")
            self.start()
        try:
            self._service_exit_event.wait()
        finally:
            self._teardown()

    def start(self) -> str:
        result = self.engine.start()
        self._starts_metric.inc()
        self._running_metric.state("running")
        return result

    def stop(self) -> None:
        self.engine.stop()
        self._running_metric.state("stopped")

    def shutdown(self) -> None:
        self._service_exit_event.set()

    def _teardown(self) -> None:
        # obs monitors stop FIRST: drift may be mid-run_cycle against the
        # rollout manager and capacity holds a tap into the detector —
        # both must quiesce before the things they observe are torn down
        for mon, what in ((self.drift, "drift"), (self.capacity, "capacity")):
            if mon is not None:
                try:
                    mon.stop()
                except Exception as exc:
                    self.logger.error("%s monitor stop failed: %s", what, exc)
        if self.rollout is not None:
            try:
                self.rollout.stop()
            except Exception as exc:
                self.logger.error("rollout manager stop failed: %s", exc)
        try:
            self.stop()
        except Exception as exc:
            self.logger.error("engine stop during teardown failed: %s", exc)
        # the collector outlives the engine stop above so the exporters'
        # final flushes still land; one last pump() inside stop() flushes
        # its own assembly tail
        if self.telemetry is not None:
            try:
                self.telemetry.stop()
            except Exception as exc:
                self.logger.error("telemetry collector stop failed: %s", exc)
        # clean-shutdown checkpoint: after the engine stopped (so the final
        # flush landed) but before component teardown releases the state
        if (self.settings.checkpoint_dir and self.library_component is not None
                and callable(getattr(self.library_component,
                                     "save_checkpoint", None))):
            try:
                self.checkpoint()
            except Exception as exc:
                self.logger.error("shutdown checkpoint failed: %s", exc)
        if self.library_component is not None:
            try:
                self.library_component.teardown()
            except Exception as exc:
                self.logger.error("component teardown failed: %s", exc)
        if self.settings.fault_plan_file:
            # disarm the process-global injector this service armed, so an
            # embedding process (tests, notebooks) is not left chaotic
            from . import faults

            faults.disarm()
        self.health.stop()
        remove_excepthook_sink(self._excepthook_sink)
        self.web_server.stop()
        self.logger.info("service shut down")

    # -- admin verbs ----------------------------------------------------
    def status(self) -> Dict[str, Any]:
        return self._create_status_report()

    def _create_status_report(self) -> Dict[str, Any]:
        """Status JSON shape pinned by the reference
        (reference: core.py:280-297,386-421); the ``distributed`` block is a
        TPU-build addition reporting this process's place in the global mesh
        (parallel/distributed.py — stays importless on non-jax stages)."""
        from .parallel.distributed import process_info

        return {
            "status": {
                "component_type": self.settings.component_type,
                "component_id": self.settings.component_id,
                "running": self.engine.running,
                "health": self.health.state,
            },
            "distributed": process_info(),
            "settings": self.settings.model_dump(mode="json"),
            "configs": self.config_manager.get() if self.config_manager else {},
        }

    def reconfigure(self, config_data: Dict[str, Any], persist: bool = False) -> Dict[str, Any]:
        """Validate + apply new component config; optionally persist
        (reference: core.py:299-345)."""
        if self.config_manager is None:
            raise ServiceError("no config manager: service was started without config_file")
        if not config_data:
            return self.config_manager.get()
        # the COMPONENT validates/applies first: a vetoed or failed change
        # must neither reach the manager nor be persisted — otherwise /status
        # and the on-disk YAML report a config the running instance refused,
        # and the next restart silently builds something different
        hook = getattr(self.library_component, "reconfigure", None)
        if callable(hook):
            try:
                hook(self.config_manager.validate(config_data))
                self.logger.info("component reconfigured in place")
            except Exception as exc:
                self.logger.error("component reconfigure rejected: %s", exc)
                raise ServiceError(f"component rejected reconfigure: {exc}") from exc
        else:
            self.logger.warning(
                "component has no reconfigure hook; running instance keeps its old config"
            )
        updated = self.config_manager.update(config_data)
        if persist:
            self.config_manager.save()
        return updated

    # -- context manager (reference: core.py:424-436) -------------------
    def __enter__(self) -> "Service":
        self.setup_io()
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- logging (reference: core.py:355-384) ---------------------------
    def _setup_logging(self) -> logging.Logger:
        name = f"{self.settings.component_type}.{self.settings.component_id}"
        logger = logging.getLogger(name)
        logger.setLevel(self.settings.log_level.upper())
        logger.propagate = False
        have = {type(h).__name__ + getattr(h, "_dm_tag", "") for h in logger.handlers}
        if self.settings.log_format == "json":
            fmt: logging.Formatter = JsonLogFormatter(
                static=dict(
                    component_type=self.settings.component_type,
                    component_id=self.settings.component_id or "unknown"),
                # trace correlation buckets tenants the same way metrics do
                tenant_buckets=self.settings.shed_tenant_buckets)
        else:
            fmt = logging.Formatter(
                "[%(asctime)s] %(levelname)s %(name)s: %(message)s"
            )
        if self.settings.log_to_console and "StreamHandlerconsole" not in have:
            console = logging.StreamHandler(sys.__stdout__)
            console.setFormatter(fmt)
            console._dm_tag = "console"  # type: ignore[attr-defined]
            logger.addHandler(console)
        else:
            # a reused logger (same component identity) must still honor THIS
            # settings' log_format — re-point the existing handlers' formatter
            for handler in logger.handlers:
                if getattr(handler, "_dm_tag", "") in ("console", "file"):
                    handler.setFormatter(fmt)
        if self.settings.log_to_file and "FileHandlerfile" not in have:
            log_dir = Path(self.settings.log_dir)
            try:
                log_dir.mkdir(parents=True, exist_ok=True)
                file_handler = logging.FileHandler(
                    log_dir
                    / f"{self.settings.component_type.replace('.', '_')}_{self.settings.component_id}.log",
                    delay=True,  # lazy open (reference: core.py:370-374)
                )
                file_handler.setFormatter(fmt)
                file_handler._dm_tag = "file"  # type: ignore[attr-defined]
                logger.addHandler(file_handler)
            except OSError as exc:
                logger.warning("cannot attach file handler: %s", exc)
        return logger
