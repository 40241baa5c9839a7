"""Engine: the data-plane runtime.

Capability parity with the reference's ``Engine``
(reference: src/service/features/engine.py:73-342):

* construction validates the processor, creates the input socket through the
  factory seam, sets the receive timeout, and dials every output with
  non-blocking background connects — one bad output logs and continues, a bad
  *input* closes everything (reference: engine.py:93-129,133-179),
* the loop is recv → count → process → fan-out; ``None`` from the processor
  filters the message with no output at all (reference: engine.py:196-264),
* fan-out retries a non-blocking send up to ``retry_count`` times with a 10 ms
  sleep, then drops and counts; hard transport errors drop immediately
  (reference: engine.py:266-302),
* with no outputs configured, the reply goes back on the input socket
  (reference: engine.py:249-259),
* ``stop()`` flags the loop, joins ≤ 2 s, raises ``EngineException`` when the
  thread will not die, then closes input and outputs; the thread is recreated
  on restart (reference: engine.py:185-192,304-342).

TPU-first redesign: when ``engine_batch_size > 1`` the loop becomes an
*accumulate → dispatch* pipeline: up to B messages (or whatever arrived within
``engine_batch_timeout_ms`` of the first) are handed to the processor's
``process_batch`` as one list, so a jit-compiled scorer sees fixed-shape
batches instead of one Python callback per message. Per-message semantics are
preserved exactly: results come back in order, ``None`` entries are filtered
per-message, and a lone message still flushes after the batch timeout.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque
from typing import List, Optional, Protocol, Tuple, runtime_checkable

from .. import faults
from ..settings import TLS_SCHEME_PREFIXES, ServiceSettings
from . import metrics as m
from .framing import (
    MAGIC_SHM,
    MAGIC_TEN,
    MAGIC_V2,
    FramingError,
    Hop,
    TraceContext,
    frame_msg_count,
    pack_batch,
    peek_trace_id,
    unpack_batch,
    unwrap_tenant,
    unwrap_trace,
    wrap_tenant,
    wrap_trace,
)
from .device_obs import span
from .health import Heartbeat
from .tracing import FRAME_CONTEXT, FlightRecorder
from .socket import (
    EngineSocket,
    EngineSocketFactory,
    TransportAgain,
    TransportError,
    TransportTimeout,
    make_socket_factory,
)


class EngineException(Exception):
    """Engine lifecycle failure (reference: engine.py:57)."""


@runtime_checkable
class Processor(Protocol):
    """Per-message processing contract (reference: engine.py:61-70)."""

    def process(self, data: bytes) -> Optional[bytes]: ...


@runtime_checkable
class BatchProcessor(Protocol):
    """Batched contract for accelerator-backed processors (TPU addition).

    ``process_batch`` returns the in-order outputs that are *ready* — a
    pipelined processor may defer a batch's results to a later call to
    overlap device compute/readback with host-side work, and a COALESCING
    processor (the scorer's deadline-aware batcher) may additionally hold
    input rows across calls, releasing them as device batches later;
    ordering across calls must be preserved either way. ``flush()``
    (optional) drains anything pending — including held rows — and is
    called by the engine when the input goes idle and at stop.

    Optional poll plumbing the engine honors when present:

    * ``pending_count()`` — in-flight results plus held rows; while > 0 the
      engine polls with a short recv timeout and calls ``drain_ready()`` on
      each timeout tick so deferred results (and deadline releases) land
      within one tick, not at the idle lull;
    * ``drain_poll_ms`` — the short-poll width a deadline-aware processor
      needs (e.g. ``batch_deadline_ms / 4``); without it the engine ticks
      at 5 ms.
    """

    def process_batch(self, data: List[bytes]) -> List[Optional[bytes]]: ...


_RETRY_SLEEP_S = 0.01   # reference: engine.py:291
_STOP_JOIN_S = 2.0      # reference: engine.py:320


def _count_lines(data: bytes) -> int:
    """The reference's newline line-count rule (engine.py:213): newline
    count, plus one for a final unterminated line, minimum 1. One home for
    the expression so read/written/dropped metrics can't desynchronize."""
    return max(1, data.count(b"\n") + (0 if data.endswith(b"\n") else 1))


class Engine:
    def __init__(
        self,
        settings: ServiceSettings,
        processor: Processor,
        socket_factory: Optional[EngineSocketFactory] = None,
        logger: Optional[logging.Logger] = None,
        health=None,
        admission=None,
    ) -> None:
        if processor is None or not callable(getattr(processor, "process", None)):
            raise EngineException("processor must provide a callable process(bytes)")
        self.settings = settings
        self.processor = processor
        self.logger = logger or logging.getLogger("engine")
        self._factory = socket_factory or make_socket_factory(
            getattr(settings, "transport_backend", "auto"), self.logger
        )
        self._running = False
        self._stop_event = threading.Event()
        # crash seam (crash_abort): when set, the loop thread exits at the
        # next check WITHOUT the drain epilogue and _send_results becomes a
        # no-op — the closest an in-process harness gets to kill -9. The
        # ingress_crash soak and the WAL recovery tests die through this.
        self._abort_event = threading.Event()
        # drain-then-close deadline, set ONCE when the first blocked send
        # observes the stop flag and shared by every message drained after it
        # — an aggregate budget, so N pending messages at stop cannot stack
        # N × out_stop_drain_ms past the 2 s stop-join deadline
        self._stop_drain_deadline: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._sockets_closed = False
        self._labels = dict(
            component_type=settings.component_type,
            component_id=settings.component_id or "unknown",
        )
        # labeled metric children resolved ONCE: _send_to_outputs runs per
        # message, and a .labels() call is a dict-build + hash per metric —
        # four of them per message was a measurable slice of the send floor
        # (dmlint DM-H001 is the rule that keeps it this way)
        self._m_written_b = m.DATA_WRITTEN_BYTES().labels(**self._labels)
        self._m_written_l = m.DATA_WRITTEN_LINES().labels(**self._labels)
        self._m_dropped_b = m.DATA_DROPPED_BYTES().labels(**self._labels)
        self._m_dropped_l = m.DATA_DROPPED_LINES().labels(**self._labels)
        self._m_send_backlog = m.OUTPUT_SEND_BACKLOG().labels(**self._labels)
        # seconds of the stretches in which that gauge reads > 0: the time
        # this stage's engine thread stood blocked on a full peer
        self._m_send_blocked = m.SEND_BLOCKED_SECONDS().labels(**self._labels)

        # self-diagnosis heartbeats (engine/health.py): one monotonic clock
        # write per loop iteration — the beats happen unconditionally (they
        # cost an attribute store); only the watchdog checks need a monitor
        self._hb_loop = Heartbeat("engine_loop")
        self._hb_ingest = Heartbeat("ingest")
        self._hb_output = Heartbeat("output_pump")
        if health is not None:
            health.register_engine(self._hb_loop, self._hb_ingest,
                                   self._hb_output, lambda: self._running)

        # pipeline tracing (engine_trace): hop stamping + the flight
        # recorder behind GET /admin/trace. Inbound v2 headers are stripped
        # even when tracing is off (clean downgrade for v1-only peers);
        # stamping/forwarding only happens when this sender opted in. Trace
        # handling rides the batch-frame magic detection, so the autodetect
        # gate governs it too.
        self._trace_enabled = bool(
            getattr(settings, "engine_trace", False)
            and getattr(settings, "engine_frame_autodetect", True))
        self._trace_stage = (getattr(settings, "trace_stage", None)
                             or settings.component_name
                             or settings.component_type)
        self._trace_terminal = getattr(settings, "trace_terminal", None)
        self._trace_observe_e2e = bool(
            getattr(settings, "trace_observe_e2e", False))
        # FIFO of (TraceContext, recv_ns) for frames of the burst being
        # dispatched; consumed by outgoing v2 frames, finalized at burst end
        self._trace_pending: deque = deque()
        self.trace_recorder = FlightRecorder(
            max_slowest=getattr(settings, "trace_slowest", 32),
            max_sampled=getattr(settings, "trace_sampled", 128),
            sample_every=getattr(settings, "trace_sample_every", 64))
        if self._trace_enabled:
            self._dwell_obs = m.PIPELINE_STAGE_DWELL().labels(**self._labels).observe
            self._transit_obs = m.PIPELINE_TRANSIT().labels(**self._labels).observe
            self._e2e_obs = m.PIPELINE_E2E_LATENCY().labels(**self._labels).observe

        # cross-stage telemetry (telemetry/spans.py, dmtel): the hop records
        # the tracing path already stamps also leave the process as spans —
        # offer() is the hot loop's only added surface (one bounded deque
        # append per frame; everything else runs on the sender thread). The
        # per-thread FRAME_CONTEXT mirrors the in-flight frame's trace id +
        # tenant for log↔trace correlation (JsonLogFormatter) and for the
        # approximate tenant attribution of spans — same best-effort pairing
        # contract as _tenant_pending.
        self._frame_ctx = FRAME_CONTEXT
        self._telemetry = None
        if self._trace_enabled and getattr(settings, "telemetry_addr", None):
            from ..telemetry.spans import SpanExporter
            self._telemetry = SpanExporter(
                settings, self._factory, self._trace_stage, self._labels,
                self.logger,
                events=(health.emit_event if health is not None else None))

        # multi-tenant admission control (shed/): tenant blocks are stripped
        # at ingress UNCONDITIONALLY (clean downgrade for tenant-unaware
        # configs, mirroring v2 trace handling) and re-stamped OUTERMOST on
        # forwarded egress frames; the admission decision only runs when a
        # controller was wired (core.py, shed_enabled). _tenant_pending is
        # the egress FIFO — exact when frames map 1:1 through the stage,
        # approximate under merging/re-chunking, same contract as
        # _trace_pending. The NACK child is hoisted per DM-H001.
        self.admission = admission
        self._tenant_pending: deque = deque()
        self._m_nacks = m.SHED_NACKS().labels(**self._labels)
        # tenant-attribution seam for coalescing processors (the scorer's
        # weighted-fair batcher): told the current ingress frame's tenant so
        # held rows can be segmented per tenant. Hoisted: one getattr at
        # construction, not one per frame.
        self._note_tenant = getattr(processor, "note_tenant", None)

        # router slot initialized before any socket exists so the failure
        # cleanup path (_close_all) can always probe it
        self._health = health
        self.router = None

        # input socket (close nothing else exists yet on failure)
        self._pair_sock: EngineSocket = self._create_ingress()

        # output sockets: background dials; one bad address logs and continues,
        # but a *setup* crash closes the input socket before re-raising
        self._out_socks: List[EngineSocket] = []
        try:
            self._setup_output_sockets()
        except Exception:
            self._pair_sock.close()
            raise

        # zero-copy framing (engine/shm.py): sender-side slot pool when every
        # output is colocated; the reader side is created lazily on the first
        # reference frame received (auto-detected, like batch frames)
        self._shm_writer = None
        self._shm_reader = None
        self._m_shm_zero = self._m_shm_copy = None
        try:
            self._setup_zero_copy()
        except Exception:
            self._close_all()
            raise

        # replica-parallel tier (router/): with ``router_replicas`` set this
        # stage load-balances each outgoing frame to ONE downstream scorer
        # replica instead of duplicating to every output (settings validation
        # keeps out_addr empty in that mode). The router owns the replica
        # sockets; its supervisor drives drain/requeue/re-dial.
        try:
            self._setup_router()
        except Exception:
            self._close_all()
            raise

        # durable ingress (wal/): with ``durable_ingress`` every received
        # frame is appended to the WAL spool before processing; acks advance
        # once results leave the process, and _run_loop replays the unacked
        # suffix before accepting new traffic after a restart. None when
        # off — the hot path then pays one attribute read per frame.
        self._spool = None
        self._replaying = False
        # dead-letter quarantine (wal/deadletter.py): the destination for
        # frames that exhausted their dlq_max_attempts processing attempts.
        # Always constructed — memory-only without a directory — so poison
        # isolation converges in every configuration. _requeue_pending is
        # the admin→engine hand-off for POST /admin/dlq requeue: web
        # threads append under the lock, the engine loop drains it at the
        # top of each iteration and re-drives the frames replay-style.
        self._dlq = None
        self._dlq_max_attempts = max(
            1, int(getattr(settings, "dlq_max_attempts", 3)))
        self._requeue_pending: deque = deque()
        self._requeue_lock = threading.Lock()
        try:
            self._setup_spool()
            self._setup_dlq()
        except Exception:
            self._close_all()
            raise

    # ------------------------------------------------------------------
    def _create_ingress(self) -> EngineSocket:
        """Build the input side: one listener on ``engine_addr``, or — when
        ``engine_ingress_addrs`` is set — N listener shards merged into this
        loop (the multi-ingress regime: per-shard fds/buffers/senders, one
        dispatch queue, one device pipeline)."""
        shards = list(getattr(self.settings, "engine_ingress_addrs", ()) or ())
        if not shards:
            sock = self._factory.create(
                self.settings.engine_addr, self.logger, self.settings.tls_input)
            sock.recv_timeout = self.settings.engine_recv_timeout
            return sock
        from .socket import MergedIngressSocket

        socks: List[EngineSocket] = []
        try:
            for addr in shards:
                socks.append(self._factory.create(
                    addr, self.logger, self.settings.tls_input))
        except Exception:
            for s in socks:
                try:
                    s.close()
                except TransportError:
                    pass
            raise
        merged = MergedIngressSocket(socks)
        merged.recv_timeout = self.settings.engine_recv_timeout
        return merged

    def _setup_zero_copy(self) -> None:
        """Arm the sender-side shm slot pool when ``zero_copy_framing`` is on
        AND every output is a colocated scheme (ipc/inproc). Anything else —
        a remote peer, the native kernel missing — logs once and stays in
        plain copy mode: payloads are byte-identical either way."""
        self._shm_writer = None
        if not getattr(self.settings, "zero_copy_framing", False):
            return
        addrs = list(self.settings.out_addr)
        schemes = {a.split("://", 1)[0] for a in addrs}
        if not addrs or not schemes <= {"ipc", "inproc"}:
            if addrs:
                self.logger.warning(
                    "zero_copy_framing: non-colocated output scheme(s) %s — "
                    "staying in copy mode", sorted(schemes - {"ipc", "inproc"}))
            return
        from . import shm as shm_mod

        if not shm_mod.shm_available():
            self.logger.warning(
                "zero_copy_framing: native shm kernel unavailable — staying "
                "in copy mode")
            return
        self._shm_writer = shm_mod.ShmWriter(
            slots=getattr(self.settings, "zero_copy_slots", 32),
            slot_bytes=getattr(self.settings, "zero_copy_slot_bytes", 262144),
            inproc=(schemes == {"inproc"}),
            logger=self.logger)
        self._m_shm_zero = m.SHM_FRAMES().labels(mode="zero_copy",
                                                 **self._labels)
        self._m_shm_copy = m.SHM_FRAMES().labels(mode="copy", **self._labels)
        self.logger.info(
            "zero-copy framing armed (%s mode, %d slots x %d bytes)",
            "inproc" if schemes == {"inproc"} else "shm",
            getattr(self.settings, "zero_copy_slots", 32),
            getattr(self.settings, "zero_copy_slot_bytes", 262144))

    def _resolve_shm(self, raw: bytes, err_c) -> Optional[bytes]:
        """Reference frame → payload bytes via the (lazily created) reader;
        None counts a framing error — the payload is unreachable, which is
        the shm analog of a corrupt batch frame."""
        if self._shm_reader is None:
            from . import shm as shm_mod

            self._shm_reader = shm_mod.ShmReader(self.logger)
        payload = self._shm_reader.resolve_release(raw)
        if payload is None:
            err_c.inc()
        return payload

    def _setup_router(self) -> None:
        replicas = list(getattr(self.settings, "router_replicas", ()) or ())
        if not replicas:
            return
        from ..router import ReplicaRouter

        self.router = ReplicaRouter(
            self.settings, self._factory, self.logger, self._labels,
            monitor=self._health, abort_check=self._router_abort)

    def _setup_spool(self) -> None:
        """Open (or recover) the durable ingress spool and bind the dmwal
        gauges to it at scrape time — depth/bytes/age stay readable even
        while the engine thread is dead, which is exactly when the
        SpoolAgeHigh alert must keep climbing."""
        if not getattr(self.settings, "durable_ingress", False):
            return
        from ..wal import IngressSpool

        s = self.settings
        events = (self._health.emit_event
                  if self._health is not None else None)
        self._spool = IngressSpool(
            s.wal_dir,
            segment_bytes=s.wal_segment_bytes,
            fsync_interval_ms=s.wal_fsync_interval_ms,
            retain_bytes=s.wal_retain_bytes,
            retain_age_s=s.wal_retain_age_s,
            fsync_observer=m.WAL_FSYNC_SECONDS().labels(**self._labels).inc,
            on_disk_error=getattr(s, "wal_on_disk_error", "degrade"),
            events=events,
            disk_error_observer=m.WAL_FSYNC_ERRORS()
            .labels(**self._labels).inc,
            logger=self.logger)
        spool = self._spool
        m.WAL_SPOOL_DEPTH().labels(**self._labels) \
            .set_function(spool.depth_frames)
        m.WAL_SPOOL_BYTES().labels(**self._labels) \
            .set_function(spool.spool_bytes)
        m.WAL_OLDEST_UNACKED_AGE().labels(**self._labels) \
            .set_function(spool.oldest_unacked_age_seconds)
        m.WAL_SPOOL_DEGRADED().labels(**self._labels) \
            .set_function(spool.degraded_value)
        self._m_wal_recovered = m.WAL_REPLAYED_FRAMES().labels(
            mode="recovery", **self._labels)
        self.logger.info(
            "durable ingress armed: spool at %s (%d unacked to replay)",
            s.wal_dir, int(spool.depth_frames()))

    def _setup_dlq(self) -> None:
        """Open (or reopen after a restart) the dead-letter quarantine and
        bind its depth gauge; memory-only when no directory applies."""
        s = self.settings
        dlq_dir = getattr(s, "dlq_dir", None)
        if dlq_dir is None and getattr(s, "durable_ingress", False) \
                and getattr(s, "wal_dir", None):
            import os as _os

            dlq_dir = _os.path.join(s.wal_dir, "dlq")
        from ..wal.deadletter import DeadLetterSpool

        self._dlq = DeadLetterSpool(
            dlq_dir,
            max_frames=getattr(s, "dlq_max_frames", 1024),
            labels=self._labels,
            events=(self._health.emit_event
                    if self._health is not None else None),
            logger=self.logger)
        m.DLQ_DEPTH().labels(**self._labels) \
            .set_function(self._dlq.depth_frames)
        if self._dlq.depth_frames():
            self.logger.warning(
                "DLQ holds %d quarantined frames at start (inspect with "
                "GET /admin/dlq)", int(self._dlq.depth_frames()))

    @property
    def dlq(self):
        """The dead-letter quarantine spool (the /admin/dlq verbs read and
        mutate it; never None after construction)."""
        return self._dlq

    # dmlint: thread(any) — web/admin threads enqueue; the engine loop drains
    def requeue_frames(self, frames: List[bytes]) -> int:
        """Hand previously-quarantined frames back to the engine loop for
        re-processing (POST /admin/dlq requeue). At-most-once: a frame
        that fails again is re-quarantined with a fresh attempt budget."""
        with self._requeue_lock:
            self._requeue_pending.extend(frames)
        return len(frames)

    def _router_abort(self) -> bool:
        """Stop-aware backpressure escape for the router's block mode: the
        same single shared drain window the output pump uses, so a stop with
        every replica down still lands inside the 2 s stop-join deadline."""
        if self._running and not self._stop_event.is_set():
            return False
        if self._stop_drain_deadline is None:
            self._stop_drain_deadline = (
                time.monotonic() + self.settings.out_stop_drain_ms / 1000.0)
        return time.monotonic() >= self._stop_drain_deadline

    def _setup_output_sockets(self) -> None:
        for addr in self.settings.out_addr:
            try:
                # sock_dial fault site: an injected dial error takes the
                # same log-and-continue path as a real failed dial
                inj = faults._ACTIVE
                if inj is not None:
                    inj.sock("sock_dial")
                # TLS-bearing schemes get the client material; others get
                # None so a fake factory never sees surprise TLS args. The
                # scheme list is shared with settings validation on purpose:
                # the two diverging is exactly the bug that broke encrypted
                # NNG outputs at dial.
                is_tls = addr.startswith(TLS_SCHEME_PREFIXES)
                sock = self._factory.create_output(
                    addr,
                    self.logger,
                    self.settings.tls_output if is_tls else None,
                    dial_timeout=self.settings.out_dial_timeout,
                    buffer_size=self.settings.engine_buffer_size,
                )
                self._out_socks.append(sock)
            except (TransportError, OSError) as exc:
                self.logger.error("cannot dial output %s: %s (continuing)", addr, exc)

    # -- lifecycle ------------------------------------------------------
    # admin/main lifecycle verbs; start() spawns the engine thread,
    # stop() joins it before any teardown touches its state
    # dmlint: thread(any)
    def start(self) -> str:
        """Start (or restart) the engine loop thread; returns a status string.

        ``stop()`` closes all sockets, so a restart rebuilds them before the
        loop thread comes back up (the reference recreates only the thread,
        engine.py:185-192, because its stop also closed the sockets — a
        restart-after-stop there reads a dead socket; fixed here)."""
        if self._running:
            return "already running"
        if self._sockets_closed:
            self._pair_sock = self._create_ingress()
            self._out_socks = []
            try:
                self._setup_output_sockets()
                self._setup_zero_copy()
                self._setup_router()
                self._setup_spool()
                self._setup_dlq()
            except Exception:
                self._close_all()
                raise
            self._sockets_closed = False
        self._stop_event.clear()
        self._abort_event.clear()
        self._stop_drain_deadline = None
        # re-stamp the heartbeats so a restart does not instantly trip the
        # watchdog on ages accumulated while the engine was (healthily) down
        self._hb_loop.beat()
        self._hb_ingest.beat()
        self._hb_output.wait_end()
        self._running = True
        if self._telemetry is not None:
            self._telemetry.start()
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run_loop, name="EngineLoop", daemon=True
            )
        self._thread.start()
        self.logger.info("engine started")
        return "engine started"

    # dmlint: thread(any) — joins the engine thread before teardown
    def stop(self) -> None:
        if not self._running and self._thread is None:
            self._close_all()
            return
        self._running = False
        self._stop_event.set()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=_STOP_JOIN_S)
            if thread.is_alive():
                raise EngineException("engine thread did not stop within deadline")
        self._thread = None
        self._close_all()
        self.logger.info("engine stopped")

    def _close_all(self) -> None:
        self._sockets_closed = True
        if self._telemetry is not None:
            # final flush happens in stop(): the sender thread drains the
            # queue once more before joining, so short-lived runs lose
            # nothing that was offered before the stop
            self._telemetry.stop()
        try:
            self._pair_sock.close()
        except TransportError:
            pass
        for sock in self._out_socks:
            try:
                sock.close()
            except TransportError:
                pass
        if self._shm_writer is not None:
            self._shm_writer.close()
            self._shm_writer = None
        if self._shm_reader is not None:
            self._shm_reader.close()
            self._shm_reader = None
        if self.router is not None:
            self.router.close()
            self.router = None
        if self._spool is not None:
            # clean shutdown: final fsync + manifest commit, so the next
            # start replays nothing (a CRASH never reaches here — that is
            # the unacked suffix recovery's whole job)
            try:
                self._spool.close()
            except Exception as exc:
                self.logger.error("WAL spool close failed: %s", exc)
            self._spool = None
        dlq = getattr(self, "_dlq", None)
        if dlq is not None:
            # entries are already durable per-record; close just releases
            # the append handle (start() reopens and reloads)
            dlq.close()

    def crash_abort(self) -> None:
        """CHAOS/TEST SEAM — die like kill -9, minus the process exit: the
        loop thread stops at its next check without the drain epilogue, no
        processor flush runs, nothing further leaves the process
        (_send_results is gated), the spool is neither acked nor cleanly
        committed, and the sockets stay open. ``start()`` afterwards is the
        "restarted process": with durable_ingress on it must replay the
        unacked suffix. Used by the ingress_crash soak scenario and the WAL
        recovery tests; never called by production code paths."""
        self._abort_event.set()
        self._running = False
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=_STOP_JOIN_S)
        self._thread = None

    @property
    def running(self) -> bool:
        return self._running

    @property
    def spool(self):
        """The durable ingress spool (None when ``durable_ingress`` is
        off) — the admin plane reads its stats via GET /admin/replay."""
        return self._spool

    # -- hot loop -------------------------------------------------------
    def _ingest_trace(self, raw: bytes, err_c) -> Optional[bytes]:
        """Strip (and, when tracing, record) a v2 trace header from one wire
        frame. Returns the v1-equivalent payload — byte-identical to what an
        untraced sender would have emitted — or None when the frame is
        unusable. One clock read per frame, never per message; a garbled
        trace block is counted as a framing error but its payload messages
        survive (the block is skipped by its declared length)."""
        ctx = None
        if raw.startswith(MAGIC_V2):
            try:
                raw, ctx, damaged = unwrap_trace(raw)
            except FramingError as exc:
                err_c.inc()
                self.logger.error("corrupt traced frame dropped: %s", exc)
                return None
            if damaged:
                err_c.inc()
                self.logger.warning(
                    "garbled trace block stripped; payload messages kept")
        if not self._trace_enabled:
            return raw
        now = time.time_ns()
        if ctx is not None:
            prev = ctx.hops[-1].send_ns if ctx.hops else ctx.ingest_ns
            self._transit_obs(max(0, now - prev) / 1e9)
        else:
            # untraced inbound (or a damaged block): this stage originates
            ctx = TraceContext.new(now)
        self._trace_pending.append((ctx, now))
        # log↔trace correlation: records logged while this frame is in
        # flight carry its id (one GIL-atomic attribute store per frame)
        self._frame_ctx.trace_id = ctx.trace_id
        return raw

    def _stamp_trace(self, payload: bytes, now_ns: int) -> bytes:
        """Complete the oldest pending context's hop and wrap ``payload``
        as a v2 frame for the downstream stage. With ``trace_observe_e2e``
        this egress is ALSO the pipeline's internal completion point — e2e
        is observed and the flight recorder fed here, while the trace still
        propagates (the downstream consumer keys on its id); the recorder
        snapshots the context into a dict, so downstream hops appended
        later never mutate the recorded view."""
        ctx, recv_ns = self._trace_pending.popleft()
        ctx.hops.append(Hop(self._trace_stage, recv_ns, now_ns))
        self._dwell_obs(max(0, now_ns - recv_ns) / 1e9)
        tel = self._telemetry
        if self._trace_observe_e2e:
            e2e = max(0, now_ns - ctx.ingest_ns) / 1e9
            if tel is not None:
                # exemplar: the histogram bucket links to the trace the
                # collector assembled (OpenMetrics exposition only)
                self._e2e_obs(e2e, {"trace_id": f"{ctx.trace_id:016x}"})
            else:
                self._e2e_obs(e2e)
            self.trace_recorder.record(ctx, e2e)
        if tel is not None:
            tel.offer(ctx.trace_id, ctx.ingest_ns, recv_ns, now_ns, False,
                      getattr(self._frame_ctx, "tenant", None))
        return wrap_trace(payload, ctx)

    def _finalize_traces(self) -> None:
        """Close out contexts whose frames did not leave as v2 (filtered
        messages, deferred/pipelined outputs, or a terminal stage). Dwell is
        observed for every context; e2e latency and the flight recorder fire
        only at the terminal stage — no forwarding outputs, or the
        ``trace_terminal`` override — where the trace's life genuinely
        ends."""
        # tenant attribution shares the finalize point: pending tenants whose
        # frames did not leave this burst (filtered / deferred outputs) must
        # not re-stamp a later burst's frames with a stale tenant
        self._tenant_pending.clear()
        fc = self._frame_ctx
        if not self._trace_pending:
            # burst done: log records must stop carrying the last frame's id
            fc.trace_id = None
            fc.tenant = None
            return
        now = time.time_ns()
        terminal = (self._trace_terminal if self._trace_terminal is not None
                    else not self._out_socks and self.router is None)
        tel = self._telemetry
        tenant = getattr(fc, "tenant", None)
        while self._trace_pending:
            ctx, recv_ns = self._trace_pending.popleft()
            ctx.hops.append(Hop(self._trace_stage, recv_ns, now))
            self._dwell_obs(max(0, now - recv_ns) / 1e9)
            if terminal:
                e2e = max(0, now - ctx.ingest_ns) / 1e9
                if tel is not None:
                    self._e2e_obs(e2e, {"trace_id": f"{ctx.trace_id:016x}"})
                else:
                    self._e2e_obs(e2e)
                self.trace_recorder.record(ctx, e2e)
            if tel is not None:
                tel.offer(ctx.trace_id, ctx.ingest_ns, recv_ns, now,
                          terminal, tenant)
        fc.trace_id = None
        fc.tenant = None

    def _strip_tenant(self, raw: bytes,
                      err_c) -> Tuple[Optional[bytes], Optional[str]]:
        """Strip one tenant block → ``(payload, tenant)``. A garbled id is
        counted and the payload survives (admitted as the anonymous tenant,
        so damage cannot buy a better quota); only a declared id length
        running past the frame end loses the frame."""
        try:
            payload, tenant, damaged = unwrap_tenant(raw)
        except FramingError as exc:
            err_c.inc()
            self.logger.error("corrupt tenant frame dropped: %s", exc)
            return None, None
        if damaged:
            err_c.inc()
            self.logger.warning(
                "garbled tenant block stripped; payload messages kept")
        return (payload or None), tenant

    def _admit_frame(self, tenant: Optional[str], raw: bytes) -> bool:
        """One frame's admission decision; False means shed (the controller
        already counted + evented it). In reply mode the requester gets a
        structured retry-after NACK instead of a silent empty reply."""
        ok, reason, tier = self.admission.admit(
            tenant, frame_msg_count(raw), time.monotonic())
        if ok:
            return True
        if self._telemetry is not None:
            # the frame dies here, before trace ingest, so its upstream
            # spans would assemble into a quietly-incomplete trace — the
            # flag makes the shed visible (and keeps the trace, tail rule)
            self._telemetry.offer_flag(peek_trace_id(raw), "shed")
        if not self._out_socks and self.router is None:
            self._send_nack(reason or "quota", tier, tenant)
        return False

    def _send_nack(self, reason: str, tier: Optional[str],
                   tenant: Optional[str], origin=None) -> None:
        """Best-effort reply-mode NACK: a compact ``dm_nack`` JSON body
        (reason + retry_after_ms) the requester can back off on, counted on
        shed_nacks_total. A NACK the transport will not take is dropped —
        it exists to shed load, never to add backpressure."""
        if self.admission is not None:
            body = self.admission.nack_payload(reason, tier, tenant)
        else:
            body = {"dm_nack": {
                "reason": reason, "tier": tier, "tenant": tenant,
                "retry_after_ms": getattr(
                    self.settings, "shed_retry_after_ms", 100.0)}}
        payload = json.dumps(body, separators=(",", ":")).encode("utf-8")
        send_to = getattr(self._pair_sock, "send_to", None)
        try:
            if origin is not None and callable(send_to):
                send_to(origin, payload)
            else:
                self._pair_sock.send(payload)
        except (TransportAgain, TransportError) as exc:
            self.logger.warning("shed NACK undeliverable: %s", exc)
            return
        self._m_nacks.inc()

    def _expand_frame(self, raw: bytes, read_b, read_l, err_c) -> List[bytes]:
        """One wire frame → its messages. Batch frames (framing.py) are
        auto-detected by magic — the 0xD7 lead byte cannot open a valid
        protobuf message — so a sender that packs and one that doesn't can
        share this engine. The engine itself is schema-agnostic: a pipeline
        carrying non-protobuf payloads must set
        ``engine_frame_autodetect: false`` (settings.py) or a payload that
        happens to start with the magic would be mis-split. Read metrics
        count PAYLOAD bytes once per frame (a resolved shm reference counts
        its payload, not its ~40 wire bytes) and lines per contained message
        (the reference's newline rule)."""
        if not getattr(self.settings, "engine_frame_autodetect", True):
            if self._spool is not None and not self._replaying:
                if (self._spool.append(raw) is None
                        and self._spool.on_disk_error == "shed"):
                    err_c.inc()
                    return []       # not durable → shed per policy
            read_b.inc(len(raw))
            read_l.inc(_count_lines(raw))
            return [raw]
        if raw[0] == 0xD7 and raw.startswith(MAGIC_SHM):
            raw = self._resolve_shm(raw, err_c)
            if not raw:
                return []
        # tenant attribution + admission (shed/): the tenant block is the
        # outermost wrapper, so it is stripped first — before the spool
        # append decision, because a SHED frame must never be made durable
        # (shedding is only cheap at the front door). Replay is exempt from
        # admission: a recovered frame was admitted and metered when it
        # first arrived.
        wire = raw              # pre-strip bytes: the spool stays byte-faithful
        tenant = None
        if raw[0] == 0xD7 and raw.startswith(MAGIC_TEN):
            raw, tenant = self._strip_tenant(raw, err_c)
            if not raw:
                return []
        # unconditional store (None clears a previous frame's tenant): log
        # records and spans for this frame attribute to the right tenant
        self._frame_ctx.tenant = tenant
        if self._note_tenant is not None:
            self._note_tenant(tenant)
        if (self.admission is not None and not self._replaying
                and not self._admit_frame(tenant, raw)):
            return []
        if tenant is not None and (self._out_socks or self.router is not None):
            self._tenant_pending.append(tenant)
        # durable ingress: record the frame BEFORE any processing — post
        # shm-resolution (a slot reference is not durable), pre trace-strip
        # (the recorded bytes keep their original trace id + ingest stamp,
        # which is what makes replay byte-faithful; the tenant block is
        # recorded too, so replayed frames keep their attribution). The
        # tick keeps the fsync cadence honest inside long burst-collect
        # windows, when the loop-top tick cannot run.
        if self._spool is not None and not self._replaying:
            if (self._spool.append(wire) is None
                    and self._spool.on_disk_error == "shed"):
                err_c.inc()
                return []           # not durable → shed per policy
            self._spool.tick()
        read_b.inc(len(raw))
        # first-byte probe before the slice compare: protobuf payloads never
        # start 0xD7, so the untraced common case pays one int compare here
        if self._trace_enabled or (raw[0] == 0xD7
                                   and raw.startswith(MAGIC_V2)):
            raw = self._ingest_trace(raw, err_c)
            if not raw:
                return []
        try:
            msgs = unpack_batch(raw)
        except FramingError as exc:
            err_c.inc()
            self.logger.error("corrupt batch frame dropped: %s", exc)
            return []
        if msgs is None:
            msgs = [raw]
        else:
            # packed empties get the same fate as plain empty frames (the
            # loop's `if not raw` / `if nxt` guards): silently skipped
            msgs = [msg for msg in msgs if msg]
        # one aggregated inc per frame: a labeled counter inc costs ~1-2 µs
        # and per-message incs were a measurable slice of the service floor
        read_l.inc(sum(map(_count_lines, msgs)))
        return msgs

    def _collect_burst(self, deadline: float, remaining_fn, on_frame,
                       per_frame: bool = False) -> None:
        """Drain further wire frames from the input socket until ``deadline``
        or until ``remaining_fn()`` (items still wanted, also the recv_many
        count hint) drops to zero; ``on_frame`` consumes each non-empty
        frame. One home for the recv_many probe and the recv-timeout
        save/restore subtlety, shared by the classic micro-batch and the
        fused-frame collection paths. ``per_frame=True`` forces one recv per
        frame even when recv_many exists — required when the caller reads
        ``last_origin`` after each frame (a recv_many burst can span shards/
        connections but reports only one origin, which would misroute
        replies)."""
        recv_many = (None if per_frame
                     else getattr(self._pair_sock, "recv_many", None))
        saved_timeout = (None if callable(recv_many)
                         else self._pair_sock.recv_timeout)
        while remaining_fn() > 0:
            remaining_ms = (deadline - time.monotonic()) * 1000.0
            if remaining_ms <= 0:
                break
            try:
                with span("dm.recv_wait"):
                    if callable(recv_many):
                        frames = recv_many(remaining_fn(),
                                           max(1, int(remaining_ms)))
                    else:
                        self._pair_sock.recv_timeout = max(
                            1, int(remaining_ms))
                        frames = [self._pair_sock.recv()]
            except (TransportTimeout, TransportError):
                break
            for nxt in frames:
                if nxt:
                    on_frame(nxt)
        if saved_timeout is not None:
            self._pair_sock.recv_timeout = saved_timeout

    # THE engine thread entry point: every replica socket, spool
    # append/ack/tick, and output send descends from here
    # dmlint: thread(engine)
    def _run_loop(self) -> None:
        read_b = m.DATA_READ_BYTES().labels(**self._labels)
        read_l = m.DATA_READ_LINES().labels(**self._labels)
        err_c = m.PROCESSING_ERRORS().labels(**self._labels)
        # burst-level gauge (set once per dispatch, not per message): pinned
        # at engine_batch_size means the ingress is saturating the engine
        ingress_g = m.INGRESS_BACKLOG().labels(**self._labels)
        batch_size = max(1, self.settings.engine_batch_size)
        batch_fn = getattr(self.processor, "process_batch", None)
        use_batches = batch_size > 1 and callable(batch_fn)
        # fused-frame mode: a processor exposing process_frames(frames) ->
        # (outputs, n_messages, n_lines) takes whole wire frames — frame expansion
        # and per-message work happen inside the component (natively for
        # the jax scorer), so the engine loop holds no per-message Python
        # objects at all. Requires frame auto-detection semantics (the
        # component unpacks by magic), hence the autodetect gate.
        frames_fn = getattr(self.processor, "process_frames", None)
        use_frames = (use_batches and callable(frames_fn)
                      and getattr(self.settings, "engine_frame_autodetect", True))
        batch_timeout_s = self.settings.engine_batch_timeout_ms / 1000.0
        if self.settings.engine_frame_batch > 1 and not use_batches:
            # results arrive at _send_results one at a time in this mode, so
            # nothing ever packs — say so instead of silently underdelivering
            self.logger.warning(
                "engine_frame_batch=%d has no effect without micro-batching "
                "(engine_batch_size > 1 and a batch-capable component)",
                self.settings.engine_frame_batch)

        # flush is wired for EVERY processor (not just batched ones): a
        # single-message component may also hold time-windowed state it emits
        # on idle (e.g. OutputWriter's partial aggregation group)
        flush_fn = getattr(self.processor, "flush", None)
        # while the processor holds in-flight (pipelined) results, poll with a
        # short timeout so they drain within milliseconds of readiness instead
        # of waiting out the full idle-lull timeout — the sparse-traffic
        # latency contract (<10 ms p50) depends on this
        pending_fn = getattr(self.processor, "pending_count", None) if use_batches else None
        # reply-mode origin tracking: with no outputs configured and a fan-in
        # input listener, replies must route to the exact requesting
        # connection — the last-recv heuristic misroutes under multi-dialer
        # interleaving. Exact in single-message mode; aligned per-message in
        # micro-batch mode when the processor returns immediate in-order
        # outputs; unavailable (falls back to the heuristic) for fused-frame
        # and pipelined processors, which decouple outputs from this call's
        # inputs.
        track_origins = (not self._out_socks and self.router is None
                         and hasattr(self._pair_sock, "last_origin"))
        # a short-poll tick is NOT true idleness: drain only what is already
        # host-readable (drain_ready) so the loop never blocks on an unready
        # device readback while new traffic queues in the socket buffer
        drain_fn = getattr(self.processor, "drain_ready", None)
        base_timeout = self.settings.engine_recv_timeout
        # deadline-aware processors (the scorer's coalescer) export a drain
        # poll hint — tick at ~deadline/4 so a held row's release lands
        # within one tick of its budget without hard-coding 5 ms polling
        # onto second-scale budgets; 5 ms stays the default for plain
        # pipelined processors
        try:
            hint = int(getattr(self.processor, "drain_poll_ms", 0) or 0)
        except (TypeError, ValueError):
            hint = 0
        short_timeout = (min(base_timeout, max(1, hint)) if hint > 0
                         else min(5, base_timeout))
        current_timeout = base_timeout
        # replica-router deferred work (re-dials, drain deadlines, requeue
        # redelivery) runs on THIS thread — sockets are single-threaded by
        # design; the no-work tick is one lock acquire + three scans
        router = self.router
        # durable ingress: replay the spool's unacked suffix through the
        # pipeline BEFORE accepting new socket traffic — the restart half
        # of the crash-recovery contract (docs/durability.md)
        spool = self._spool
        if spool is not None:
            self._replay_recovered(read_b, read_l, err_c)
        # dmlint: hot-loop
        while (self._running and not self._stop_event.is_set()
               and not self._abort_event.is_set()):
            self._hb_loop.beat()
            if spool is not None:
                # FIFO ack: everything appended before now has been handed
                # to the processor and its immediate results dispatched;
                # held rows (coalescer/pipelined) and unsettled router
                # windows hold the watermark back until they drain — acks
                # then advance at the next quiet point (at-least-once:
                # conservative lag, never an early ack)
                if ((pending_fn is None or pending_fn() == 0)
                        and (router is None
                             or router.unacked_total() == 0)):
                    spool.ack(spool.last_appended_seq)
                spool.tick()
            if router is not None:
                router.tick()
            # dmlint: ignore[DM-L001] lock-free emptiness peek: the GIL makes the deque truth-test atomic, and _drain_requeue re-checks under _requeue_lock
            if self._requeue_pending:
                self._drain_requeue(read_b, read_l, err_c)
            if callable(pending_fn):
                want = short_timeout if pending_fn() > 0 else base_timeout
                if want != current_timeout:
                    self._pair_sock.recv_timeout = want
                    current_timeout = want
            try:
                with span("dm.recv_wait"):
                    raw = self._pair_sock.recv()
            except TransportTimeout:
                # input went idle (or a short-poll tick passed): drain
                # pipelined results so a quiet stream still gets bounded
                # latency; blocking flush only at the true idle timeout
                fn = (drain_fn if current_timeout == short_timeout
                      and callable(drain_fn) else flush_fn)
                if callable(fn):
                    try:
                        self._send_results(fn())
                    except Exception as exc:
                        err_c.inc()
                        self.logger.error("idle drain raised: %s", exc)
                continue
            except TransportError as exc:
                if not self._running:
                    break
                self.logger.error("engine recv failed: %s", exc)
                time.sleep(0.05)  # don't busy-spin a persistently failing socket
                continue
            if not raw:
                continue
            # sock_recv fault site: latency sleeps inside sock(); "drop"
            # discards the received frame (simulated ingress packet loss);
            # an injected error treats this frame like a transport error
            inj = faults._ACTIVE
            if inj is not None:
                try:
                    if inj.sock("sock_recv") == "drop":
                        continue
                except OSError as exc:
                    err_c.inc()
                    self.logger.error("injected sock_recv fault: %s", exc)
                    continue
            self._hb_ingest.beat()

            if use_frames:
                # collect the burst as whole frames (each may pack hundreds
                # of messages); the component expands + featurizes natively.
                # The burst is capped by ESTIMATED contained messages
                # (frame_msg_count reads just the header varint), so the
                # component's per-call batch cap holds to within one
                # frame's overshoot — without it a sustained packed burst
                # would hand the component millions of messages per call.
                # v2 trace headers are stripped HERE, host-side — and shm
                # reference frames resolved — so the native expand path
                # (dm_count_frame_msgs / dm_featurize_frames) only ever
                # sees v1 wire units.
                def ingest_wire(nxt: bytes) -> Optional[bytes]:
                    if nxt[0] == 0xD7 and nxt.startswith(MAGIC_SHM):
                        nxt = self._resolve_shm(nxt, err_c)
                        if not nxt:
                            return None
                    # tenant strip + admission: same placement contract as
                    # _expand_frame (shed frames never reach the spool)
                    wire = nxt
                    tenant = None
                    if nxt[0] == 0xD7 and nxt.startswith(MAGIC_TEN):
                        nxt, tenant = self._strip_tenant(nxt, err_c)
                        if not nxt:
                            return None
                    self._frame_ctx.tenant = tenant
                    if self._note_tenant is not None:
                        self._note_tenant(tenant)
                    if (self.admission is not None
                            and not self._admit_frame(tenant, nxt)):
                        return None
                    if tenant is not None and (self._out_socks
                                               or self.router is not None):
                        self._tenant_pending.append(tenant)
                    # durable ingress: same append point (and mid-burst
                    # fsync tick) as _expand_frame
                    if spool is not None:
                        if (spool.append(wire) is None
                                and spool.on_disk_error == "shed"):
                            err_c.inc()
                            return None   # not durable → shed per policy
                        spool.tick()
                    read_b.inc(len(nxt))
                    if self._trace_enabled or nxt.startswith(MAGIC_V2):
                        nxt = self._ingest_trace(nxt, err_c)
                    return nxt or None

                raw = ingest_wire(raw)
                frames = [raw] if raw else []
                est = [frame_msg_count(raw) if raw else 0]

                def on_frame(nxt: bytes) -> None:
                    nxt = ingest_wire(nxt)
                    if nxt is None:
                        return
                    frames.append(nxt)
                    est[0] += frame_msg_count(nxt)

                self._collect_burst(time.monotonic() + batch_timeout_s,
                                    lambda: batch_size - est[0], on_frame)
                if not frames:
                    continue
                ingress_g.set(est[0])
                outs, n_lines = self._dispatch_frames(frames_fn, frames,
                                                      err_c)
                read_l.inc(n_lines)
                self._send_results(outs)
                self._finalize_traces()
                continue

            msgs = self._expand_frame(raw, read_b, read_l, err_c)
            if not msgs:
                self._finalize_traces()
                continue
            origin = self._pair_sock.last_origin if track_origins else None

            if not use_batches:
                for msg_raw in msgs:
                    out = self._dispatch_single(msg_raw, err_c)
                    if out is not None:
                        self._send_results([out], [origin])
                if self._trace_pending:
                    self._finalize_traces()
                continue

            # micro-batch mode: drain what arrived within the window. The
            # native transport's recv_many takes a whole burst per GIL
            # crossing; other sockets fall back to one recv per frame. A
            # packed frame may carry the whole batch in one recv.
            batch = msgs
            batch_origins = [origin] * len(msgs) if track_origins else None

            def on_burst_frame(nxt: bytes) -> None:
                ms = self._expand_frame(nxt, read_b, read_l, err_c)
                batch.extend(ms)
                if batch_origins is not None:
                    batch_origins.extend(
                        [self._pair_sock.last_origin] * len(ms))

            # per-frame recv (no recv_many burst) only when origins can
            # actually differ: misrouting needs >= 2 live reply peers; the
            # common single-dialer reply pipe keeps burst draining. (A peer
            # connecting mid-burst can misattribute that one burst's
            # origins — accepted: the alternative taxes every burst.)
            self._collect_burst(
                time.monotonic() + batch_timeout_s,
                lambda: batch_size - len(batch),
                on_burst_frame,
                per_frame=(track_origins and
                           getattr(self._pair_sock, "peer_count", 1) > 1))
            ingress_g.set(len(batch))
            # a packed ingress frame can carry more messages than
            # engine_batch_size; re-chunk so the component never sees a batch
            # beyond the configured cap (its memory/latency contract)
            for start in range(0, len(batch), batch_size):
                chunk = batch[start:start + batch_size]
                outs = self._dispatch_chunk(batch_fn, chunk, err_c)
                # in-order, per-message None filter; origin alignment holds
                # only when outputs are immediate (len match) — a pipelined
                # processor defers results across calls
                if batch_origins is not None and len(outs) == len(chunk):
                    self._send_results(outs,
                                       batch_origins[start:start + batch_size])
                else:
                    self._send_results(outs)
            if self._trace_pending:
                self._finalize_traces()

        # crash seam: a kill -9 runs no drain epilogue — the spool keeps its
        # unacked suffix and the restart replays it (the recovery contract)
        if self._abort_event.is_set():
            return
        # loop exiting (stop requested): drain the pipeline before sockets
        # close — flush_final (when provided) also waits out work the
        # idle-time flush leaves running, e.g. a background boundary fit
        final_fn = getattr(self.processor, "flush_final", None) or flush_fn
        if callable(final_fn):
            try:
                self._send_results(final_fn())
            except Exception as exc:
                self.logger.error("flush at stop raised: %s", exc)
        self._finalize_traces()
        if router is not None:
            # last redelivery pass so frames requeued from a drained replica
            # are not abandoned in the requeue queue at stop
            router.tick()
        if spool is not None:
            # clean stop: the final flush drained everything the processor
            # held, so the whole appended prefix is handed off — ack it and
            # commit, UNLESS the router tier still holds unsettled frames
            # (those stay unacked; a restart replays them, at-least-once)
            if router is None or router.unacked_total() == 0:
                spool.ack(spool.last_appended_seq)
            spool.tick(force=True)

    # -- poison isolation + dead-letter quarantine -----------------------
    # A chunk-level processing exception used to drop (and then silently
    # ack) every frame in the chunk — the confirmed replay-wedge /
    # silent-loss bug. Now the failing chunk is re-dispatched one message
    # at a time: healthy messages complete, and a message that fails on
    # every one of its dlq_max_attempts attempts moves to the DLQ with its
    # reason and last error. Deterministic poison converges in ONE pass;
    # a transient error just costs the bounded retries.

    def _telemetry_flag(self, flag: str,
                        trace_id: Optional[int] = None) -> None:
        """Cold-path verdict annotation for the trace being processed. The
        failing MESSAGE's own trace id is unknowable post-expand, so this
        pairs with the oldest pending context — approximate under
        re-chunking, the same documented contract as _tenant_pending; the
        point is that the trace of a failing burst is flagged and kept."""
        tel = self._telemetry
        if tel is None:
            return
        if trace_id is None and self._trace_pending:
            trace_id = self._trace_pending[0][0].trace_id
        tel.offer_flag(trace_id, flag)

    def _quarantine_msg(self, msg: bytes, reason: str, exc: BaseException,
                        attempts: int) -> None:
        self._telemetry_flag("quarantined")
        if self._dlq is None or not msg:
            return
        self._dlq.quarantine(
            msg, reason=reason, error=f"{type(exc).__name__}: {exc}",
            attempts=attempts,
            seq=(self._spool.last_appended_seq
                 if self._spool is not None else None))

    # dmlint: thread(engine)
    def _dispatch_chunk(self, batch_fn, chunk: List[bytes], err_c,
                        reason: str = "processing_error") -> List:
        """``process_batch`` with the proc fault site armed and poison
        isolation on failure; always returns the ready outputs."""
        inj = faults._ACTIVE
        try:
            if inj is not None:
                inj.proc(chunk)
            return batch_fn(chunk)
        except Exception as exc:
            err_c.inc(len(chunk))
            self._telemetry_flag("error")
            self.logger.error(
                "process_batch() raised: %s — isolating %d messages",
                exc, len(chunk))
            return self._isolate_poison(batch_fn, chunk, exc, reason)

    def _isolate_poison(self, batch_fn, chunk: List[bytes],
                        chunk_exc: BaseException, reason: str) -> List:
        """Cold path: re-dispatch a failed chunk one message at a time;
        messages still failing after the attempt budget are quarantined.
        The chunk-level failure counts as each message's first attempt."""
        inj = faults._ACTIVE
        retries = max(1, self._dlq_max_attempts - 1)
        outs: List = []
        for msg in chunk:
            last: BaseException = chunk_exc
            res = None
            done = False
            for _ in range(retries):
                try:
                    if inj is not None:
                        inj.proc([msg])
                    res = batch_fn([msg])
                    done = True
                    break
                except Exception as exc:
                    last = exc
            if done:
                if res:
                    outs.extend(res)
            else:
                self._quarantine_msg(msg, reason, last, 1 + retries)
        return outs

    # dmlint: thread(engine)
    def _dispatch_single(self, msg: bytes, err_c,
                         reason: str = "processing_error"):
        """``process`` with the proc fault site armed and a bounded attempt
        budget; a message failing every attempt is quarantined, not
        silently dropped."""
        inj = faults._ACTIVE
        last: Optional[BaseException] = None
        for _ in range(self._dlq_max_attempts):
            try:
                if inj is not None:
                    inj.proc([msg])
                return self.processor.process(msg)
            except Exception as exc:
                last = exc
        err_c.inc()
        self._telemetry_flag("error")
        self.logger.error("process() raised on all %d attempts: %s",
                          self._dlq_max_attempts, last)
        self._quarantine_msg(msg, reason, last, self._dlq_max_attempts)
        return None

    # dmlint: thread(engine)
    def _dispatch_frames(self, frames_fn, frames: List[bytes], err_c,
                         reason: str = "processing_error"):
        """Fused-frame dispatch with the same isolation contract; returns
        ``(outs, n_lines)``."""
        inj = faults._ACTIVE
        try:
            if inj is not None:
                inj.proc(frames)
            outs, _n_msgs, n_lines = frames_fn(frames)
            return outs, n_lines
        except Exception as exc:
            err_c.inc(len(frames))
            self._telemetry_flag("error")
            self.logger.error(
                "process_frames() raised: %s — isolating %d frames",
                exc, len(frames))
        retries = max(1, self._dlq_max_attempts - 1)
        outs, n_lines = [], 0
        for frame in frames:
            last = None
            got = None
            done = False
            for _ in range(retries):
                try:
                    if inj is not None:
                        inj.proc([frame])
                    got = frames_fn([frame])
                    done = True
                    break
                except Exception as exc:
                    last = exc
            if done:
                f_outs, _n, f_lines = got
                if f_outs:
                    outs.extend(f_outs)
                n_lines += f_lines
            else:
                self._quarantine_msg(frame, reason, last, 1 + retries)
        return outs, n_lines

    # dmlint: thread(engine)
    def _drain_requeue(self, read_b, read_l, err_c) -> None:
        """Re-drive DLQ-requeued frames through the pipeline, replay-style
        (no re-append, no admission — they were admitted and metered when
        they first arrived). Runs at the loop top, on the engine thread."""
        with self._requeue_lock:
            items = list(self._requeue_pending)
            self._requeue_pending.clear()
        if not items:
            return
        self.logger.info("re-driving %d DLQ-requeued frames", len(items))
        batch_fn = getattr(self.processor, "process_batch", None)
        batch_size = max(1, self.settings.engine_batch_size)
        use_batches = batch_size > 1 and callable(batch_fn)
        self._replaying = True
        try:
            for raw in items:
                if not raw:
                    continue
                msgs = self._expand_frame(raw, read_b, read_l, err_c)
                if not msgs:
                    self._finalize_traces()
                    continue
                if use_batches:
                    for start in range(0, len(msgs), batch_size):
                        self._send_results(self._dispatch_chunk(
                            batch_fn, msgs[start:start + batch_size],
                            err_c, reason="requeue_failed"))
                else:
                    for msg in msgs:
                        out = self._dispatch_single(
                            msg, err_c, reason="requeue_failed")
                        if out is not None:
                            self._send_results([out])
                self._finalize_traces()
        finally:
            self._replaying = False

    def _replay_recovered(self, read_b, read_l, err_c) -> None:
        """Durable-ingress restart recovery: re-drive the spool's unacked
        suffix through the processor before the loop touches the socket —
        one frame at a time (recovery is a cold path; burst shaping would
        buy nothing and cost determinism of the drain below), through the
        same expand/trace/dispatch machinery as live traffic, with spool
        re-appends suppressed. The suffix only acks once everything has
        actually left: processor-held rows drained AND (router mode) the
        replica windows watermark-settled — interrupted or incomplete
        recovery leaves it unacked for the next start (at-least-once)."""
        spool = self._spool
        pending = spool.recover_unacked()
        if not pending:
            return
        self.logger.warning(
            "durable ingress: replaying %d unacked spool frames "
            "(seq %d..%d) before accepting new traffic",
            len(pending), pending[0][0], pending[-1][0])
        batch_fn = getattr(self.processor, "process_batch", None)
        frames_fn = getattr(self.processor, "process_frames", None)
        batch_size = max(1, self.settings.engine_batch_size)
        use_batches = batch_size > 1 and callable(batch_fn)
        use_frames = (use_batches and callable(frames_fn)
                      and getattr(self.settings,
                                  "engine_frame_autodetect", True))
        self._replaying = True
        try:
            for _seq, raw in pending:
                if self._stop_event.is_set() or self._abort_event.is_set():
                    return
                if use_frames:
                    read_b.inc(len(raw))
                    if raw.startswith(MAGIC_TEN):
                        # recovered frames keep their attribution for the
                        # egress re-stamp; admission is NOT re-run (they
                        # were admitted and metered when they first arrived)
                        raw, tenant = self._strip_tenant(raw, err_c)
                        if not raw:
                            self._finalize_traces()
                            continue
                        if tenant is not None and (
                                self._out_socks or self.router is not None):
                            self._tenant_pending.append(tenant)
                    if self._trace_enabled or raw.startswith(MAGIC_V2):
                        raw = self._ingest_trace(raw, err_c)
                    if raw:
                        # poison isolation keeps a poisoned recovery frame
                        # from wedging the replay: it quarantines, the rest
                        # of the suffix completes, the ack below advances
                        outs, n_lines = self._dispatch_frames(
                            frames_fn, [raw], err_c,
                            reason="recovery_replay")
                        read_l.inc(n_lines)
                        self._send_results(outs)
                    self._finalize_traces()
                    continue
                msgs = self._expand_frame(raw, read_b, read_l, err_c)
                for start in range(0, len(msgs), batch_size):
                    chunk = msgs[start:start + batch_size]
                    if use_batches:
                        self._send_results(self._dispatch_chunk(
                            batch_fn, chunk, err_c,
                            reason="recovery_replay"))
                    else:
                        for msg in chunk:
                            out = self._dispatch_single(
                                msg, err_c, reason="recovery_replay")
                            if out is not None:
                                self._send_results([out])
                self._finalize_traces()
            # drain held/pipelined rows so the replayed frames are really
            # delivered before they ack (bounded: an unhealthy processor
            # must not wedge startup forever — the remainder stays unacked)
            flush_fn = getattr(self.processor, "flush", None)
            pending_fn = getattr(self.processor, "pending_count", None)
            drain_fn = getattr(self.processor, "drain_ready", None) \
                or flush_fn
            if callable(flush_fn):
                try:
                    self._send_results(flush_fn())
                except Exception as exc:
                    err_c.inc()
                    self.logger.error("recovery flush raised: %s", exc)
            deadline = time.monotonic() + 30.0
            while (callable(pending_fn) and pending_fn() > 0
                   and time.monotonic() < deadline
                   and not self._stop_event.is_set()
                   and not self._abort_event.is_set()):
                try:
                    self._send_results(drain_fn())
                except Exception as exc:
                    err_c.inc()
                    self.logger.error("recovery drain raised: %s", exc)
                    break
                time.sleep(0.005)
            if callable(pending_fn) and pending_fn() > 0:
                self.logger.error(
                    "recovery: %d results still pending after the drain "
                    "window; their frames stay unacked", pending_fn())
                return
            router = self.router
            if router is not None:
                deadline = time.monotonic() + 30.0
                while (router.unacked_total() > 0
                       and time.monotonic() < deadline
                       and not self._stop_event.is_set()):
                    router.tick()
                    time.sleep(0.01)
                if router.unacked_total() > 0:
                    return
            spool.ack(spool.last_appended_seq)
            spool.tick(force=True)
            self._m_wal_recovered.inc(len(pending))
            self.logger.info("durable ingress: recovery replay complete "
                             "(%d frames)", len(pending))
        finally:
            self._replaying = False

    # -- fan-out --------------------------------------------------------
    def _send_results(self, outs, origins=None) -> None:
        """Fan out processor results, packing ``engine_frame_batch`` of them
        per wire frame when configured (>1). Packing amortizes the
        per-message socket cost that otherwise caps the stage-to-stage rate;
        the default of 1 keeps the wire single-message for reference-style
        peers. Downstream framework engines auto-detect either format.

        ``origins`` (aligned with ``outs``, pre-None-filter) carries each
        message's originating-connection token for reply mode on a fan-in
        listener: replies route to the exact requester instead of the
        last-recv heuristic. Packing only groups consecutive same-origin
        replies — a packed frame has one destination.

        With tracing enabled and forwarding outputs, each outgoing frame
        consumes the oldest pending trace context (FIFO — exact when frames
        map 1:1 through the stage, approximate under merging/re-chunking)
        and leaves as a v2 traced frame; replies (no outputs) never carry
        trace headers — that stage is the pipeline terminal."""
        if self._abort_event.is_set():
            # crash seam: a killed process sends nothing — results of the
            # in-flight burst are lost here exactly as a real kill -9 loses
            # them, which is what the WAL recovery replay must cover
            return
        # sock_send fault site: latency stalls the send (inside sock());
        # drop and injected errors discard this call's results — simulated
        # egress loss, visible to the loadgen loss gate by design
        inj = faults._ACTIVE
        if inj is not None and outs:
            try:
                if inj.sock("sock_send") == "drop":
                    return
            except OSError as exc:
                self.logger.error("injected sock_send fault: %s", exc)
                return
        if origins is not None and len(origins) == len(outs):
            pending = [(o, origins[i]) for i, o in enumerate(outs)
                       if o is not None]
        else:
            pending = [(o, None) for o in outs if o is not None]
        if pending:
            with span("dm.send", results=len(pending)):
                self._fan_out(pending)

    def _fan_out(self, pending: List) -> None:
        """Build the wire units for ``pending`` (result, origin) pairs and
        send them: the body of :meth:`_send_results`, under its ``dm.send``
        span."""
        frame_batch = getattr(self.settings, "engine_frame_batch", 1)
        attach = bool(self._trace_enabled
                      and (self._out_socks or self.router is not None)
                      and not self._trace_terminal
                      and self._trace_pending and pending)
        now_ns = time.time_ns() if attach else 0  # one clock read per call
        built: List = []                 # (wire-unit, lines, origin)
        start = 0
        while start < len(pending):
            end = start + 1
            if frame_batch > 1:
                # == not `is`: merged-ingress origins are (shard, conn)
                # tuples built per access; plain conn origins compare by
                # identity either way
                while (end < len(pending) and end - start < frame_batch
                       and pending[end][1] == pending[start][1]):
                    end += 1
            chunk = [p[0] for p in pending[start:end]]
            origin = pending[start][1]
            if len(chunk) == 1:
                data, lines = chunk[0], None
            else:
                data = pack_batch(chunk)
                lines = sum(map(_count_lines, chunk))
            if attach and self._trace_pending:
                # line/byte metrics must count payload, not header, bytes —
                # a varint inside the trace block can collide with '\n'
                if lines is None:
                    lines = _count_lines(data)
                data = self._stamp_trace(data, now_ns)
            if self._tenant_pending:
                # tenant block re-stamped OUTERMOST (after the trace wrap)
                # so the next stage's admission reads it from the first
                # bytes; only forwarded frames ever enqueue here
                if lines is None:
                    lines = _count_lines(data)
                data = wrap_tenant(data, self._tenant_pending.popleft())
            built.append((data, lines, origin))
            start = end
        # batched fan-out (send_many): one GIL crossing per send_batch_max
        # frames on the single-forwarding-output hot path; multi-output
        # fan-outs, replies (origin routing), and send_many-less transports
        # keep the per-frame path
        sock = self._out_socks[0] if len(self._out_socks) == 1 else None
        if (len(built) > 1 and sock is not None
                and callable(getattr(sock, "send_many", None))
                and getattr(self.settings, "send_batch_max", 1) > 1
                and all(item[2] is None for item in built)):
            self._send_to_outputs_many(built)
            return
        for data, lines, origin in built:
            self._send_to_outputs(data, lines=lines, origin=origin)

    def _drop_frame(self, meta, wire: bytes) -> None:
        plen, lines, is_ref = meta
        self._m_dropped_b.inc(plen)
        self._m_dropped_l.inc(lines)
        if is_ref:
            # a reference no peer will ever resolve must release its slot
            self._shm_writer.release_ref(wire)

    def _send_to_outputs_many(self, built) -> None:
        """Batched single-output fan-out: the whole result burst crosses the
        transport in ``send_many`` chunks of ``send_batch_max`` frames — one
        GIL crossing per chunk instead of per frame (the send-side twin of
        the ingest ``recv_many``). Per-frame accounting (written/dropped
        bytes+lines, shm slot refs) and the drop-retry / block-flow-control
        semantics of ``_send_to_outputs`` are preserved; shm publication
        happens per frame exactly as on the per-frame path."""
        sock = self._out_socks[0]
        writer = self._shm_writer
        wires: List[bytes] = []
        metas: List[tuple] = []          # (payload_len, lines, is_ref)
        for data, lines, _ in built:
            if lines is None:
                lines = _count_lines(data)
            wire = data
            if writer is not None:
                ref = writer.publish(data, refs=1)
                if ref is not None:
                    wire = ref
                    self._m_shm_zero.inc()
                else:
                    self._m_shm_copy.inc()
            wires.append(wire)
            metas.append((len(data), lines, wire is not data))
        batch_max = max(1, getattr(self.settings, "send_batch_max", 64))
        block_mode = self.settings.out_backpressure == "block"
        backlog_g = self._m_send_backlog
        idx = 0
        retries = 0
        waited = False
        blocked_from = 0.0
        # dmlint: hot-loop
        while idx < len(wires):
            hard = False
            try:
                n = sock.send_many(wires[idx:idx + batch_max], block=False)
            except TransportAgain:
                n = 0
            except TransportError as exc:
                self.logger.warning("output send failed hard: %s", exc)
                hard = True
                n = 0
            if hard:
                # hard transport failure: this frame is gone; the next may
                # still make it once the socket recovers (reconnects ride
                # the transport's background redial)
                self._drop_frame(metas[idx], wires[idx])
                idx += 1
                retries = 0
                continue
            if n > 0:
                for j in range(idx, idx + n):
                    self._m_written_b.inc(metas[j][0])
                    self._m_written_l.inc(metas[j][1])
                idx += n
                retries = 0
                continue
            # nothing left the process this pass: peer backpressure
            if block_mode:
                if not self._running or self._stop_event.is_set():
                    if self._stop_drain_deadline is None:
                        self._stop_drain_deadline = (
                            time.monotonic()
                            + self.settings.out_stop_drain_ms / 1000.0)
                    if time.monotonic() >= self._stop_drain_deadline:
                        break                    # drop the remainder below
                backlog_g.set(1)
                if not waited:
                    self._hb_output.wait_begin()
                    blocked_from = time.monotonic()
                else:
                    self._hb_output.beat()
                waited = True
                # a raw blocking send would make the engine unstoppable:
                # dmlint: ignore[DM-H004] the 1 ms poll IS flow control
                time.sleep(0.001)
                continue
            retries += 1
            if retries >= self.settings.engine_retry_count:
                self._drop_frame(metas[idx], wires[idx])
                idx += 1
                retries = 0
                continue
            self._hb_output.beat()
            # the reference-mandated 10 ms retry backoff between attempts:
            # dmlint: ignore[DM-H004] bounded by engine_retry_count
            time.sleep(_RETRY_SLEEP_S)
        for j in range(idx, len(wires)):     # stop-drain expiry remainder
            self._drop_frame(metas[j], wires[j])
        if waited:
            backlog_g.set(0)
            self._m_send_blocked.inc(time.monotonic() - blocked_from)
            self._hb_output.wait_end()

    def _send_to_outputs(self, data: bytes, lines: Optional[int] = None,
                         origin=None) -> bool:
        written_b = self._m_written_b
        written_l = self._m_written_l
        dropped_b = self._m_dropped_b
        dropped_l = self._m_dropped_l
        if lines is None:
            lines = _count_lines(data)

        # replica-router mode: exactly ONE replica gets the frame (policy
        # choice + credit flow control live in router/); written counts a
        # delivered frame once, dropped counts a frame no dispatchable
        # replica accepted within the backpressure budget
        if self.router is not None:
            if self.router.dispatch(data, lines):
                written_b.inc(len(data))
                written_l.inc(lines)
                return True
            dropped_b.inc(len(data))
            dropped_l.inc(lines)
            return False

        # zero-copy framing: the payload moves into a refcounted shm slot
        # and a ~40-byte reference goes on the wire instead. A reply (origin
        # set) or a publish failure (no free slot / oversized) keeps the
        # plain bytes — byte-identical payload, just copied. Metrics keep
        # counting PAYLOAD bytes either way.
        wire = data
        if (self._shm_writer is not None and self._out_socks
                and origin is None):
            ref = self._shm_writer.publish(data, refs=len(self._out_socks))
            if ref is not None:
                wire = ref
                self._m_shm_zero.inc()
            else:
                self._m_shm_copy.inc()

        def drop_ref() -> None:
            # a reference a peer will never resolve must release its slot
            # sender-side or the pool leaks one slot per dropped frame
            if wire is not data:
                self._shm_writer.release_ref(wire)

        if not self._out_socks:
            # no outputs: reply on the input pair socket (reference:
            # engine.py:249-259). With an origin token and a fan-in listener,
            # the reply goes to the exact requesting connection; a requester
            # that disconnected means the reply is undeliverable (counted
            # dropped), never misrouted to another peer.
            send_to = getattr(self._pair_sock, "send_to", None)
            try:
                if origin is not None and callable(send_to):
                    send_to(origin, data)
                else:
                    self._pair_sock.send(data)
                written_b.inc(len(data))
                written_l.inc(lines)
                return True
            except TransportAgain as exc:
                self.logger.warning("reply undeliverable: %s", exc)
                dropped_b.inc(len(data))
                dropped_l.inc(lines)
                # drop-mode overflow fix: the requester used to see NOTHING
                # when its reply was dropped here — send the compact
                # structured NACK instead (a ~100-byte body often fits the
                # very buffer a full reply overflowed), so the sender can
                # back off instead of timing out blind
                self._send_nack("overflow", None, None, origin=origin)
                return False
            except TransportError as exc:
                self.logger.error("reply on input socket failed: %s", exc)
                dropped_b.inc(len(data))
                dropped_l.inc(lines)
                return False

        any_ok = False
        wrote_once = False

        def mark_sent() -> None:
            nonlocal any_ok, wrote_once
            any_ok = True
            if not wrote_once:
                # written counted once per message, dropped once per
                # socket (reference: docs/prometheus.md:46-47)
                written_b.inc(len(data))
                written_l.inc(lines)
                wrote_once = True

        if self.settings.out_backpressure == "block":
            # Flow-control mode: wait for peers instead of the
            # drop-after-retries reference contract — inside a high-rate
            # pipeline a slower downstream throttles its upstream. The wait
            # is a 1 ms-poll loop over ALL not-yet-sent sockets, NOT a raw
            # blocking send, for two reasons: (a) the engine must stay
            # stoppable while a peer stalls (a thread stuck in zmq send
            # would make stop() raise and leak sockets); (b) skip-and-retry
            # delivery — a single stalled peer must not head-of-line-block
            # healthy peers in a multi-output fan-out. Note ingest still
            # pauses until every peer accepts (that IS the flow control),
            # so a cyclic blocking topology (A blocks on B, B on A) can
            # deadlock until stop — wire cycles with "drop" on one edge.
            # Stop is drain-then-close: pending sends share ONE
            # ``out_stop_drain_ms`` window starting when the stop flag is
            # first observed — aggregate, so a multi-message final flush
            # stays inside the 2 s stop-join deadline.
            backlog_g = self._m_send_backlog
            pending_socks = list(self._out_socks)
            waited = False
            blocked_from = 0.0
            # dmlint: hot-loop
            while pending_socks:
                if not self._running or self._stop_event.is_set():
                    if self._stop_drain_deadline is None:
                        self._stop_drain_deadline = (
                            time.monotonic()
                            + self.settings.out_stop_drain_ms / 1000.0)
                    if time.monotonic() >= self._stop_drain_deadline:
                        break
                still: List[EngineSocket] = []
                for sock in pending_socks:
                    try:
                        sock.send(wire, block=False)
                    except TransportAgain:
                        still.append(sock)
                        continue
                    except TransportError as exc:
                        self.logger.warning("output send failed hard: %s", exc)
                        dropped_b.inc(len(data))
                        dropped_l.inc(lines)
                        drop_ref()
                        continue
                    mark_sent()
                if len(still) == len(pending_socks):
                    # gauge + heartbeat only touched on the already-slow
                    # stalled path, so an unobstructed send pays nothing
                    backlog_g.set(len(still))
                    if not waited:
                        self._hb_output.wait_begin()
                        blocked_from = time.monotonic()
                    else:
                        self._hb_output.beat()
                    waited = True
                    # a raw blocking send would make the engine unstoppable:
                    # dmlint: ignore[DM-H004] the 1 ms poll IS flow control
                    time.sleep(0.001)
                pending_socks = still
            for _ in pending_socks:  # stop-drain deadline expired
                dropped_b.inc(len(data))
                dropped_l.inc(lines)
                drop_ref()
            if waited:
                backlog_g.set(0)
                self._m_send_blocked.inc(time.monotonic() - blocked_from)
                self._hb_output.wait_end()
            return any_ok

        waited = False
        blocked_from = 0.0
        for sock in self._out_socks:
            sent = False
            # dmlint: hot-loop
            for _ in range(self.settings.engine_retry_count):
                try:
                    sock.send(wire, block=False)
                    sent = True
                    break
                except TransportAgain:
                    if not waited:
                        # gauge only touched once a peer actually stalls
                        self._m_send_backlog.set(1)
                        blocked_from = time.monotonic()
                        waited = True
                    # bounded retries (max retry_count × 10 ms) never trip
                    # the saturation check — drop mode surfaces through the
                    # drop-rate alert instead — but the beat keeps the pump
                    # heartbeat honest while the loop sleeps here
                    self._hb_output.beat()
                    # the reference-mandated 10 ms retry backoff; lives on
                    # the except (cold) path, which the DM-H004 hot-loop
                    # rule skips by contract
                    time.sleep(_RETRY_SLEEP_S)
                except TransportError as exc:
                    self.logger.warning("output send failed hard: %s", exc)
                    break
            if sent:
                mark_sent()
            else:
                dropped_b.inc(len(data))
                dropped_l.inc(lines)
                drop_ref()
        if waited:
            self._m_send_backlog.set(0)
            self._m_send_blocked.inc(time.monotonic() - blocked_from)
        return any_ok
