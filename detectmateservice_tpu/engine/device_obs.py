"""Device-side observability: the XLA compile ledger and batch span log.

The accelerator side of the pipeline — ``jax.jit`` scoring, bucket warm-up,
host/device routing — was a black box: a recompile storm or a
padding-wasteful bucket mix was invisible until it surfaced as e2e latency.
This module closes that gap with the same contract machinery the host
pipeline already has (declared series, Grafana row, alert rules, structured
events):

* :class:`CompileLedger` — every XLA backend compile in the process is
  recorded (jax.monitoring's ``backend_compile_duration`` event) and
  attributed to the dispatch bucket / code path that triggered it via a
  thread-local :meth:`CompileLedger.context` the scorer wraps around its jit
  call sites. Counters: ``scorer_xla_compiles_total{bucket,backend}`` and
  ``scorer_xla_compile_seconds_total{bucket,backend}``. A bounded ring of
  compile events is served at ``GET /admin/xla``.
* **unexpected-recompile detection** — after the scorer marks warm-up
  complete, any compile inside a *dispatch* context (``expected=False``) is
  a recompile the bucket design promised would never happen. Each one
  increments ``scorer_xla_recompiles_unexpected_total`` (the
  ``RecompileStorm`` alert signal), emits a structured
  ``unexpected_recompile`` event through the bound
  :class:`~detectmateservice_tpu.engine.health.HealthMonitor` (ring +
  logger, with the flight recorder's last trace id), and arms the
  ``xla_recompile_storm`` watchdog check.
* **batch span log** — each drained device batch records a span (bucket,
  real rows, path, queue-wait vs device-time split, the PR-1 trace id
  current at dispatch, and the batch's stamps as offsets from its release)
  into a bounded ring, also on ``GET /admin/xla``.
* :func:`span` — the one helper every layer boundary is marked with: a
  ``jax.profiler.TraceAnnotation`` on the profiler's host plane (the clock
  the device plane of a ``POST /admin/profile`` capture is on) and, for the
  names a metric reads, ``detector_phase_seconds_total{phase}``.
* :class:`DeviceIdleClock` — what the host knows of the device's idle time,
  split by what the coalescer held meanwhile
  (``detector_device_idle_seconds_total{cause}``).
* :class:`CaptureSpans` — while a profiler capture runs, the longest span
  of each name, for the capture's own record (``utils/profiling.py``).
* :func:`export_hbm_gauges` — ``device_hbm_bytes{device,kind}`` computed at
  scrape time from ``jax.Device.memory_stats()`` (absent on CPU backends,
  which return ``None`` — then nothing is exported).

Attribution contract: only compiles that fire inside *some* ledger context
participate in unexpected-recompile detection. Compiles with no active
context (another library jit-compiling in the same process) are still
recorded in the ring — ``where: external`` — but never flagged, so the
detector cannot false-alarm on co-tenant compilation.

The module imports no jax at import time: non-jax stages (parsers, readers)
construct Services without paying jax's import cost; the monitoring listener
installs lazily from the scorer.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, Optional, Tuple

from . import metrics as m
from .health import DEGRADED, PASS, UNHEALTHY

# the jax.monitoring duration event around compile-or-get-cached: one per
# program the jit path had to obtain an executable for, whether the backend
# compiled it or the persistent cache served it
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the jax.monitoring events that say which of the two it was: the
# persistent cache deserialized a stored executable (hit), or the backend
# compiled and the result was written to the cache (miss)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# duration event covering the deserialization itself — the ground truth for
# the warm-up's cache_load phase split
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# the batch span ring: at the ~40 releases a second a 25 ms deadline gives,
# 4,096 spans cover ~100 s — more than a benchmark window
MAX_SPANS = 4096

# the order a batch's stamps come in (record_span stores each as an offset
# in seconds from ``release``)
SPAN_STAMPS = ("oldest_arrival", "release", "pickup", "call_issued",
               "readable", "sent")

# how long after the last unexpected recompile the watchdog check stays
# degraded (long enough to survive a scrape/evaluation gap, short enough
# that a one-off mis-sized batch does not page for an hour)
RECOMPILE_STORM_WINDOW_S = 120.0


class CompileLedger:
    """Bounded record of XLA compiles + device-batch spans for one process.

    Thread-safe; the hot cost is zero when no compile happens (the listener
    only fires on actual backend compiles, and span recording is one lock +
    deque append per *drained batch*, never per message)."""

    def __init__(self, max_events: int = 256, max_spans: int = MAX_SPANS,
                 storm_window_s: float = RECOMPILE_STORM_WINDOW_S) -> None:
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(1, max_events))
        self._spans: deque = deque(maxlen=max(1, max_spans))
        self._seq = 0
        self._span_seq = 0
        self._warmed = False
        self._storm_window_s = storm_window_s
        self._labels = {"component_type": "core", "component_id": "unknown"}
        self.monitor = None               # HealthMonitor, set via bind()
        self._emit_events = True
        self._tls = threading.local()
        # label-children cache: a compile is rare but the .labels() dict
        # hash on every record would still be waste (dmlint DM-H001 idiom)
        self._compile_children: Dict[Tuple[str, str], tuple] = {}
        self._unexpected_child = None
        self._totals = {"compiles": 0, "seconds": 0.0, "unexpected": 0}
        self._last_unexpected_mono: Optional[float] = None
        self._recent_unexpected: deque = deque(maxlen=64)  # monotonic stamps
        # persistent compile-cache counters: silent until the cache is
        # armed; then jax's own cache_hits / cache_misses events drive them
        self._cache_armed = False
        self._cache_totals = {"hits": 0, "misses": 0}
        self._cache_children: Optional[tuple] = None
        self._cache_load_seconds = 0.0
        # boot warm-up phase timings (scorer_warmup_seconds{phase}); the
        # scorer records aot / cache_load / device_put once per boot
        self._warmup_phases: Dict[str, float] = {}
        self._warmup_children: Dict[str, Any] = {}
        # bucket-state provider (the scorer's adaptive batcher): lets
        # GET /admin/xla report the LIVE warm/retired compile-bucket sets
        # next to the compile history they explain
        self._bucket_state_fn = None
        # device-info provider (the scorer's resolved placement): platform,
        # device kind/count, host twin, native featurize, compile-cache dir
        self._device_info_fn = None

    # -- wiring ----------------------------------------------------------
    def bind(self, labels: Optional[Dict[str, str]] = None, monitor=None,
             emit_events: bool = True, register_check: bool = True) -> None:
        """Attach component identity + the health plane (called by the
        Service at construction; last bind wins — the ledger is per-process,
        like the metric registry)."""
        with self._lock:
            if labels:
                self._labels = dict(labels)
                self._compile_children.clear()
                self._unexpected_child = None
                self._cache_children = None
                self._warmup_children.clear()
            if monitor is not self.monitor:
                # a storm that predates this binding belongs to the previous
                # service — a freshly-bound monitor starts with a clean
                # storm window (the ring and counters keep the history)
                self._recent_unexpected.clear()
                self._last_unexpected_mono = None
            self.monitor = monitor
            self._emit_events = emit_events
        if monitor is not None and register_check:
            monitor.remove_check(RecompileStormCheck.name)
            monitor.add_check(RecompileStormCheck(self, monitor,
                                                  self._storm_window_s))

    def set_bucket_state_provider(self, fn) -> None:
        """Attach a callable returning the scorer's live compile-bucket
        state (warm / retired sets); surfaced under ``buckets`` in
        :meth:`snapshot`. Last registration wins — the ledger is
        per-process, like the metric registry."""
        with self._lock:
            self._bucket_state_fn = fn

    def set_device_info_provider(self, fn) -> None:
        """Attach a callable returning where the scorer runs (resolved
        platform, device kind and count, host twin, native featurize,
        compile-cache directory); surfaced under ``device`` in
        :meth:`snapshot`. Last registration wins, like the bucket state."""
        with self._lock:
            self._device_info_fn = fn

    # -- attribution contexts -------------------------------------------
    @contextlib.contextmanager
    def context(self, bucket: Optional[int] = None,
                backend: Optional[str] = None, where: Optional[str] = None,
                expected: Optional[bool] = None) -> Iterator[None]:
        """Attribute compiles fired by the enclosed code to (bucket, where).

        ``expected`` is inherited from the enclosing context when ``None``
        (outermost default: True) — so a sharded-scorer context nested
        inside the dispatch path keeps the dispatch path's ``False``."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append({"bucket": bucket, "backend": backend, "where": where,
                      "expected": expected})
        try:
            yield
        finally:
            stack.pop()

    def _effective_context(self) -> Optional[Dict[str, Any]]:
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return None
        eff: Dict[str, Any] = {"bucket": None, "backend": None,
                               "where": None, "expected": True}
        for frame in stack:
            for key, value in frame.items():
                if value is not None:
                    eff[key] = value
        return eff

    # -- persistent compile-cache counters (dmwarm) ----------------------
    def arm_cache_counters(self) -> None:
        """The persistent compilation cache is on
        (utils/profiling.enable_compilation_cache calls this after
        configuring jax): hit/miss counting starts."""
        with self._lock:
            self._cache_armed = True

    @property
    def cache_armed(self) -> bool:
        with self._lock:
            return self._cache_armed

    def _cache_counters(self) -> tuple:
        pair = self._cache_children
        if pair is None:
            pair = (m.COMPILE_CACHE_HITS().labels(**self._labels),
                    m.COMPILE_CACHE_MISSES().labels(**self._labels))
            self._cache_children = pair
        return pair

    def record_cache_lookup(self, hit: bool) -> None:
        """One persistent-cache lookup, as jax itself reports it: a
        ``cache_hits`` event (the stored executable was deserialized, no
        backend compile ran) or a ``cache_misses`` event (a real compile
        whose result was written to the cache). The compile-duration event
        fires around both, so durations cannot tell them apart."""
        with self._lock:
            if not self._cache_armed:
                return
            self._cache_totals["hits" if hit else "misses"] += 1
            hits_c, misses_c = self._cache_counters()
        (hits_c if hit else misses_c).inc()

    def record_cache_retrieval(self, duration_s: float) -> None:
        """Accumulate persistent-cache deserialization wall time (the jax
        ``cache_retrieval_time_sec`` duration event) — the warm-up's
        cache_load phase reads the running total."""
        with self._lock:
            self._cache_load_seconds += max(0.0, float(duration_s))

    def cache_load_seconds(self) -> float:
        with self._lock:
            return self._cache_load_seconds

    # -- boot warm-up phase timings (dmwarm) -----------------------------
    def record_warmup_phase(self, phase: str, seconds: float) -> None:
        """Record one boot warm-up phase's wall time
        (``scorer_warmup_seconds{phase=aot|cache_load|device_put}``)."""
        seconds = max(0.0, float(seconds))
        with self._lock:
            self._warmup_phases[phase] = round(seconds, 6)
            child = self._warmup_children.get(phase)
            if child is None:
                child = m.SCORER_WARMUP_SECONDS().labels(
                    phase=phase, **self._labels)
                self._warmup_children[phase] = child
        child.set(seconds)

    def warmup_phases(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._warmup_phases)

    # -- warm-up lifecycle ----------------------------------------------
    def mark_warmup_complete(self) -> None:
        with self._lock:
            self._warmed = True

    @property
    def warmup_complete(self) -> bool:
        with self._lock:
            return self._warmed

    def reset(self) -> None:
        """Back to the un-warmed state with empty rings and zeroed totals
        (tests; a rebuilt scorer re-runs its warm-up and re-marks). The
        Prometheus counters are cumulative by contract and stay untouched."""
        with self._lock:
            self._warmed = False
            self._events.clear()
            self._spans.clear()
            self._totals = {"compiles": 0, "seconds": 0.0, "unexpected": 0}
            self._cache_totals = {"hits": 0, "misses": 0}
            self._cache_load_seconds = 0.0
            self._warmup_phases.clear()
            self._last_unexpected_mono = None
            self._recent_unexpected.clear()
            self._bucket_state_fn = None  # bound to a dead scorer otherwise
            self._device_info_fn = None

    # -- recording -------------------------------------------------------
    def _compile_counters(self, bucket: str, backend: str) -> tuple:
        pair = self._compile_children.get((bucket, backend))
        if pair is None:
            labels = dict(self._labels, bucket=bucket, backend=backend)
            pair = (m.XLA_COMPILES().labels(**labels),
                    m.XLA_COMPILE_SECONDS().labels(**labels))
            self._compile_children[(bucket, backend)] = pair
        return pair

    def record_compile(self, duration_s: float,
                       bucket: Optional[int] = None,
                       backend: Optional[str] = None,
                       where: Optional[str] = None,
                       expected: Optional[bool] = None) -> Dict[str, Any]:
        """Record one backend compile. Normally driven by the monitoring
        listener (attribution from the thread-local context); the explicit
        keyword arguments are the injection seam for tests."""
        eff = self._effective_context()
        attributed = eff is not None or bucket is not None
        if eff is None:
            eff = {"bucket": None, "backend": None, "where": None,
                   "expected": True}
        if bucket is not None:
            eff["bucket"] = bucket
        if backend is not None:
            eff["backend"] = backend
        if where is not None:
            eff["where"] = where
        if expected is not None:
            eff["expected"] = expected
        bucket_s = "?" if eff["bucket"] is None else str(eff["bucket"])
        backend_s = eff["backend"] or _default_backend()
        where_s = eff["where"] or ("unattributed" if attributed else "external")
        event: Dict[str, Any]
        with self._lock:
            self._seq += 1
            phase = "runtime" if self._warmed else "warmup"
            unexpected = bool(self._warmed and attributed
                              and not eff["expected"])
            self._totals["compiles"] += 1
            self._totals["seconds"] += float(duration_s)
            compiles_c, seconds_c = self._compile_counters(bucket_s, backend_s)
            event = {
                "seq": self._seq,
                "ts": round(time.time(), 6),
                "bucket": bucket_s,
                "backend": backend_s,
                "seconds": round(float(duration_s), 6),
                "where": where_s,
                "phase": phase,
                "unexpected": unexpected,
            }
            unexpected_c = None
            if unexpected:
                self._totals["unexpected"] += 1
                now = time.monotonic()
                self._last_unexpected_mono = now
                self._recent_unexpected.append(now)
                if self._unexpected_child is None:
                    self._unexpected_child = (
                        m.XLA_RECOMPILES_UNEXPECTED().labels(**self._labels))
                unexpected_c = self._unexpected_child
            monitor = self.monitor
            emit = unexpected and self._emit_events and monitor is not None
            self._events.append(event)
        compiles_c.inc()
        seconds_c.inc(float(duration_s))
        if unexpected_c is not None:
            unexpected_c.inc()
        if emit:
            # outside the ledger lock: the monitor fans out to the event
            # ring and the logger, neither of which may nest under it
            monitor.emit_event(dict(event, kind="unexpected_recompile"))
        return event

    def next_batch_seq(self) -> int:
        """The identifier of the next device batch, allotted when the batch
        is released: its ``dm.*`` annotations carry it as ``batch`` and
        :meth:`record_span` files the ring entry under it."""
        with self._lock:
            self._span_seq += 1
            return self._span_seq

    def record_span(self, bucket: int, real: int, path: str,
                    queue_wait_s: float, device_s: float,
                    trace_id: Optional[str] = None,
                    release: Optional[str] = None,
                    seq: Optional[int] = None,
                    stamps: Optional[Dict[str, Optional[float]]] = None
                    ) -> Dict[str, Any]:
        """One drained device batch: the span the flight recorder's trace id
        links back to (PR-1 `/admin/trace` ↔ this batch). ``release`` names
        why the coalescer let the batch go (full/deadline/flush); None for
        uncoalesced dispatches. ``seq`` is the identifier
        :meth:`next_batch_seq` gave the batch at release (allotted here
        when the caller has none). ``stamps`` are the batch's monotonic
        stamps by :data:`SPAN_STAMPS` name; each is stored as an offset in
        seconds from ``release`` (``sent`` is filled in by
        :meth:`note_sent`). Returns the ring entry."""
        offsets: Dict[str, Optional[float]] = {}
        base = (stamps or {}).get("release")
        if base is not None:
            for name in SPAN_STAMPS:
                at = stamps.get(name)
                offsets[name] = None if at is None else round(at - base, 6)
        with self._lock:
            if seq is None:
                self._span_seq += 1
                seq = self._span_seq
            entry = {
                "seq": seq,
                "ts": round(time.time(), 6),
                "bucket": int(bucket),
                "real": int(real),
                "occupancy": round(int(real) / max(1, int(bucket)), 4),
                "path": path,
                "queue_wait_s": round(float(queue_wait_s), 6),
                "device_s": round(float(device_s), 6),
                "trace_id": trace_id,
                "release": release,
                "offsets_s": offsets,
            }
            self._spans.append(entry)
        return entry

    def note_sent(self, entry: Dict[str, Any], after_release_s: float) -> None:
        """The batch's alerts were built and handed to the engine's send
        path ``after_release_s`` after its release: the last of the ring
        entry's offsets."""
        with self._lock:
            entry["offsets_s"]["sent"] = round(after_release_s, 6)

    # -- reads -----------------------------------------------------------
    def unexpected_in_window(self, window_s: Optional[float] = None,
                             now: Optional[float] = None) -> int:
        window = self._storm_window_s if window_s is None else window_s
        now = time.monotonic() if now is None else now
        with self._lock:
            return sum(1 for t in self._recent_unexpected
                       if now - t <= window)

    def snapshot(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """The ``GET /admin/xla`` document."""
        with self._lock:
            events = list(self._events)
            spans = list(self._spans)
            totals = dict(self._totals)
            totals["seconds"] = round(totals["seconds"], 6)
            warmed = self._warmed
            bucket_fn = self._bucket_state_fn
            device_fn = self._device_info_fn
            cache_armed = self._cache_armed
            cache_totals = dict(self._cache_totals)
            warmup_phases = dict(self._warmup_phases)
        if limit is not None and limit >= 0:
            events = events[-limit:] if limit else []
            spans = spans[-limit:] if limit else []
        # copies: note_sent fills an entry's last offset after it is filed
        spans = [dict(span, offsets_s=dict(span["offsets_s"]))
                 for span in spans]
        doc = {
            "warmup_complete": warmed,
            "totals": totals,
            "compiles": events,
            "batches": spans,
            "compile_cache": {"armed": cache_armed, **cache_totals},
            "warmup_phases": warmup_phases,
        }
        for key, fn in (("buckets", bucket_fn), ("device", device_fn)):
            if fn is not None:
                try:
                    doc[key] = fn()
                except Exception as exc:  # noqa: BLE001 — a racing scorer must not kill the read
                    doc[key] = {"error": f"{type(exc).__name__}: {exc}"}
        return doc


class WarmupPendingCheck:
    """Watchdog check: UNHEALTHY while the scorer's boot warm-up is in
    flight. The replica supervisor dispatches to healthy AND degraded
    replicas (router/router.py ``dispatchable``), so a booting replica that
    has not finished AOT-compiling its warm set must probe UNHEALTHY — not
    merely degraded — or scale-out would route traffic onto a replica whose
    first dispatch pays a synchronous XLA compile (exactly the cold-start
    this check makes impossible to hide). PASS once the ledger's
    ``mark_warmup_complete`` lands; the scorer registers this check at the
    top of ``setup_io`` so deep-health evaluated mid-warm-up refuses
    ACTIVE."""

    name = "scorer_warmup_pending"

    def __init__(self, ledger: CompileLedger, monitor) -> None:
        self._ledger = ledger
        self._monitor = monitor

    def evaluate(self, now: float) -> Tuple[str, str]:
        if self._ledger.monitor is not self._monitor:
            return PASS, "ledger bound to another service"
        if not self._ledger.warmup_complete:
            return UNHEALTHY, ("scorer warm-up in flight — refusing ACTIVE "
                               "until the warm set is AOT-compiled")
        phases = self._ledger.warmup_phases()
        if phases:
            total = sum(phases.values())
            return PASS, f"warm-up complete in {total:.3f}s ({phases})"
        return PASS, "warm-up complete"


class RecompileStormCheck:
    """Watchdog check: degraded while unexpected recompiles are recent.

    Only reports for the monitor the ledger is currently bound to — a
    monitor from an earlier Service in the same process (tests build many)
    keeps the check object but it evaluates to PASS, so a storm can never be
    blamed on a component that did not dispatch the batch."""

    name = "xla_recompile_storm"

    def __init__(self, ledger: CompileLedger, monitor,
                 window_s: float = RECOMPILE_STORM_WINDOW_S) -> None:
        self._ledger = ledger
        self._monitor = monitor
        self._window_s = window_s

    def evaluate(self, now: float) -> Tuple[str, str]:
        if self._ledger.monitor is not self._monitor:
            return PASS, "ledger bound to another service"
        recent = self._ledger.unexpected_in_window(self._window_s)
        if recent:
            return DEGRADED, (
                f"{recent} unexpected XLA recompile(s) in the last "
                f"{self._window_s:.0f}s — see GET /admin/xla")
        return PASS, "no unexpected recompiles"


# ---------------------------------------------------------------------------
# process-wide ledger + the (single) jax.monitoring listener
# ---------------------------------------------------------------------------
_ACTIVE = CompileLedger()
_INSTALL_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def get_ledger() -> CompileLedger:
    return _ACTIVE


def activate(ledger: CompileLedger) -> CompileLedger:
    """Swap the ledger the process-wide listener feeds (tests); returns the
    previous one so callers can restore it."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, ledger
    return prev


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    try:
        if event == COMPILE_EVENT:
            _ACTIVE.record_compile(duration)
        elif event == CACHE_RETRIEVAL_EVENT:
            _ACTIVE.record_cache_retrieval(duration)
    except Exception:  # noqa: BLE001 — telemetry must never break a compile
        pass


def install_listener() -> bool:
    """Register the compile listener with jax.monitoring (idempotent; once
    per process). Returns False when jax is unavailable."""
    global _LISTENER_INSTALLED
    with _INSTALL_LOCK:
        if _LISTENER_INSTALLED:
            return True
        try:
            from jax import monitoring
        except ImportError:
            return False
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _LISTENER_INSTALLED = True
        return True


_CACHE_LISTENER_INSTALLED = False


def _on_cache_event(event: str, **kwargs) -> None:
    if event not in (CACHE_HIT_EVENT, CACHE_MISS_EVENT):
        return
    try:
        _ACTIVE.record_cache_lookup(event == CACHE_HIT_EVENT)
    # dmlint: ignore[DM-R001] cache counting is telemetry riding a compile —
    except Exception:  # noqa: BLE001 — it must never break the compile
        pass


def install_cache_listener() -> bool:
    """Register the persistent-cache hit/miss listener (idempotent; once
    per process). Called by ``enable_compilation_cache`` when the cache
    arms; returns False when jax is unavailable."""
    global _CACHE_LISTENER_INSTALLED
    with _INSTALL_LOCK:
        if _CACHE_LISTENER_INSTALLED:
            return True
        try:
            from jax import monitoring
        except ImportError:
            return False
        monitoring.register_event_listener(_on_cache_event)
        _CACHE_LISTENER_INSTALLED = True
        return True


def _default_backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # noqa: BLE001 — jax absent or not yet initialized
        return "unknown"


# ---------------------------------------------------------------------------
# spans: one helper, two sinks
# ---------------------------------------------------------------------------
# The vocabulary (docs/telemetry.md has the table): dm.recv_wait, dm.featurize,
# dm.release, dm.upload, dm.call, dm.readback, dm.alert_build, dm.send. Every
# span is taken per engine burst or per device batch by the thread that does
# the work, never per line. Batch-scoped spans carry batch=<seq>, bucket, rows
# and release, so all spans of one device batch and its ring entry share an
# identifier.

# the spans a per-layer metric reads: these also feed
# detector_phase_seconds_total / detector_phase_total{phase}
PHASE_SPANS = {"dm.upload": "upload", "dm.readback": "readback",
               "dm.alert_build": "alert_build"}

# jax.profiler.TraceAnnotation once a scorer has resolved it; None in a
# stage that never imports jax (parser, output): span() is then a no-op
_ANNOTATION = None
# span name -> (seconds child, count child), bound by the scorer
_PHASE_CHILDREN: Dict[str, tuple] = {}


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class CaptureSpans:
    """The longest ``dm.*`` span of each name while a ``POST /admin/profile``
    capture runs (``utils/profiling.py`` arms one before ``start_trace`` and
    disarms it after ``stop_trace``): seconds, the monotonic stamp of its
    start and its ``batch`` where it has one. Written by whichever thread
    closes the span, read by the capture thread once it is disarmed."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # name -> (seconds, started at (monotonic), batch or None)
        self.longest: Dict[str, Tuple[float, float, Optional[int]]] = {}

    def note(self, name: str, started: float, seconds: float,
             batch: Optional[int]) -> None:
        with self._lock:
            held = self.longest.get(name)
            if held is None or seconds > held[0]:
                self.longest[name] = (seconds, started, batch)

    def snapshot(self) -> Dict[str, Tuple[float, float, Optional[int]]]:
        """A copy: a span opened while armed may still close after."""
        with self._lock:
            return dict(self.longest)


# the armed capture's record; None with no capture running, and span()
# then takes the paths it always took
_CAPTURE: Optional[CaptureSpans] = None


def arm_capture(record: Optional[CaptureSpans]) -> None:
    """Arm (``None``: disarm) the per-name span maxima of one capture."""
    global _CAPTURE
    _CAPTURE = record


class _Span:
    __slots__ = ("_annotation", "_phase", "_t0")

    def __init__(self, annotation, phase) -> None:
        self._annotation = annotation
        self._phase = phase
        self._t0 = 0.0

    def __enter__(self) -> None:
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._phase is not None:
            self._t0 = time.monotonic()

    def __exit__(self, *exc) -> bool:
        if self._phase is not None:
            seconds_c, count_c = self._phase
            seconds_c.inc(time.monotonic() - self._t0)
            count_c.inc()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class _CaptureSpan(_Span):
    """A span closed while a capture is armed: also timed for the capture's
    own record, whatever its name."""

    __slots__ = ("_record", "_name", "_batch", "_started")

    def __init__(self, record, name, batch, annotation, phase) -> None:
        super().__init__(annotation, phase)
        self._record = record
        self._name = name
        self._batch = batch
        self._started = 0.0

    def __enter__(self) -> None:
        self._started = time.monotonic()
        super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        self._record.note(self._name, self._started,
                          time.monotonic() - self._started, self._batch)
        return False


def span(name: str, **kv):
    """Context manager marking one layer boundary. Enters a
    ``TraceAnnotation(name, **kv)`` — an event on the profiler's host plane
    while a capture runs, a flag test otherwise — and, for the names in
    :data:`PHASE_SPANS`, adds the elapsed monotonic time to
    ``detector_phase_seconds_total{phase}`` and one to
    ``detector_phase_total{phase}``. Where neither sink is armed it returns
    a shared no-op. While a capture is armed (:func:`arm_capture`) every
    span is also timed for the capture's record, in every stage."""
    phase = _PHASE_CHILDREN.get(name)
    record = _CAPTURE
    if record is not None:
        return _CaptureSpan(
            record, name, kv.get("batch"),
            None if _ANNOTATION is None else _ANNOTATION(name, **kv), phase)
    if _ANNOTATION is not None:
        return _Span(_ANNOTATION(name, **kv), phase)
    return NULL_SPAN if phase is None else _Span(None, phase)


def arm_spans(labels: Dict[str, str]) -> None:
    """Scorer set-up: resolve the annotation class (this process has jax)
    and bind the phase counters' children, so that each series is exported
    as 0 from boot. Last call wins, like the ledger's binding."""
    global _ANNOTATION
    from jax.profiler import TraceAnnotation

    _ANNOTATION = TraceAnnotation
    for name, phase in PHASE_SPANS.items():
        _PHASE_CHILDREN[name] = (
            m.PHASE_SECONDS().labels(phase=phase, **labels),
            m.PHASE_COUNT().labels(phase=phase, **labels))


class DeviceIdleClock:
    """What the host knows of the device's idle time, split by cause.

    An idle stretch runs from the moment the last unfinished device batch
    was seen readable (:meth:`idle_from`) to the moment the next scoring
    call has been issued. The scorer's engine thread calls :meth:`advance`
    before every change to what the coalescer holds and at every pump, with
    ``release_at``: ``None`` when nothing is held, else the time at which
    the held rows met (or will meet) the release rule — ``-inf`` once the
    release target is reached, the oldest row's due time otherwise. The
    stretch since the last call then splits into ``no_rows`` (nothing
    held), ``fill`` (held, rule not met) and ``host`` (rule met, the engine
    thread had not pumped yet). :meth:`issued` adds the rest of a release
    made onto an idle device — release to call issued — to ``host``.

    Pure bookkeeping on stamps the caller passes (tests run it on a fake
    clock); single-owner, the engine thread. It falls short of the device's
    own idle time by the lateness of the ``is_ready()`` poll that sees a
    batch readable."""

    CAUSES = ("fill", "no_rows", "host")

    def __init__(self, children: Optional[Dict[str, Any]] = None) -> None:
        self.seconds = dict.fromkeys(self.CAUSES, 0.0)
        self._children = children or {}
        self._mark: Optional[float] = None

    @property
    def idle(self) -> bool:
        return self._mark is not None

    def _add(self, cause: str, seconds: float) -> None:
        if seconds <= 0.0:
            return
        self.seconds[cause] += seconds
        child = self._children.get(cause)
        if child is not None:
            child.inc(seconds)

    def idle_from(self, now: float) -> None:
        if self._mark is None:
            self._mark = now

    @staticmethod
    def _split(mark: float, now: float, release_at: Optional[float]):
        """The idle stretch ``mark`` → ``now`` as (cause, seconds) pairs."""
        if release_at is None:
            return (("no_rows", now - mark),)
        met = min(max(release_at, mark), now)
        return (("fill", met - mark), ("host", now - met))

    def advance(self, now: float, release_at: Optional[float]) -> None:
        mark = self._mark
        if mark is None or now <= mark:
            return
        for cause, seconds in self._split(mark, now, release_at):
            self._add(cause, seconds)
        self._mark = now

    def reading(self, now: float,
                release_at: Optional[float]) -> Dict[str, float]:
        """The account as :meth:`advance` would leave it at ``now``, an idle
        stretch still open counted up to ``now`` — and nothing changed. The
        one read another thread may make (a capture's marks): it sees the
        owner's totals as they stand, between two of its updates."""
        seconds = dict(self.seconds)
        mark = self._mark
        if mark is not None and now > mark:
            for cause, part in self._split(mark, now, release_at):
                seconds[cause] += max(0.0, part)
        return seconds

    def busy_from(self, now: float, release_at: Optional[float]) -> bool:
        """A batch was released to the device at ``now``. True when it
        found the device idle: its release → call issued is then idle time
        too (:meth:`issued`)."""
        if self._mark is None:
            return False
        self.advance(now, release_at)
        self._mark = None
        return True

    def issued(self, seconds: float) -> None:
        self._add("host", seconds)


# ---------------------------------------------------------------------------
# HBM gauges
# ---------------------------------------------------------------------------
_HBM_LOCK = threading.Lock()
_HBM_EXPORTED: set = set()

# jax Device.memory_stats() key → exported `kind` label value
_HBM_KINDS = (("in_use", "bytes_in_use"), ("limit", "bytes_limit"))


def _hbm_reader(device, stat_key: str):
    def read() -> float:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — a dead device must not kill the scrape
            return 0.0
        return float((stats or {}).get(stat_key, 0.0))

    return read


def export_hbm_gauges(labels: Dict[str, str]) -> int:
    """Export ``device_hbm_bytes{device,kind}`` for every local device whose
    backend reports memory stats, computed at scrape time. Returns how many
    devices export (0 on CPU, whose ``memory_stats()`` is ``None``)."""
    try:
        import jax
    except ImportError:
        return 0
    exported = 0
    for device in jax.local_devices():
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — probe failure == no stats
            stats = None
        if not stats:
            continue
        exported += 1
        key = (tuple(sorted(labels.items())), str(device))
        with _HBM_LOCK:
            if key in _HBM_EXPORTED:
                continue
            _HBM_EXPORTED.add(key)
        for kind, stat_key in _HBM_KINDS:
            m.DEVICE_HBM().labels(device=str(device), kind=kind,
                                  **labels).set_function(
                _hbm_reader(device, stat_key))
    return exported
