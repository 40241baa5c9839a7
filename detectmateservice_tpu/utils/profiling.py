"""jax.profiler integration (closes the tracing gap noted in SURVEY.md §5.1:
the reference has no profiling subsystem at all)."""
from __future__ import annotations

import heapq
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple


_cache_enabled = False
_cache_dir: Optional[str] = None
_cache_lock = threading.Lock()

# where JAX_COMPILATION_CACHE_DIR names a directory, the cache lives exactly
# there and this module never touches jax_compilation_cache_dir
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# otherwise: one fixed, git-ignored directory inside the checkout. The path
# is part of what a later process must find again, so it is never derived
# from a temp name, a pid or the clock.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

# every compile persists, including the sub-second CPU compiles the
# warm-start parity tests rely on (jax's own floor is 1 s)
_MIN_COMPILE_S = 0.0


class CompileCacheError(RuntimeError):
    """The persistent compile cache's directory cannot be used. A boot
    failure, not a quiet cold start on every restart: the message names the
    variable that places the cache somewhere writable."""

    def __init__(self, cache_dir: str, from_env: bool, cause: object) -> None:
        origin = (CACHE_DIR_ENV if from_env
                  else "compile_cache_dir / the in-checkout default")
        super().__init__(
            f"compile cache directory {cache_dir!r} (from {origin}) is "
            f"unusable: {cause}. Set {CACHE_DIR_ENV} to a directory this "
            "process can write.")
        self.cache_dir = cache_dir


def resolve_cache_dir(path: str = "") -> Tuple[str, bool]:
    """Where the persistent compilation cache goes → ``(directory,
    placed_by_env)``. ``JAX_COMPILATION_CACHE_DIR`` wins over everything
    (``path`` — the ``compile_cache_dir`` setting — included); without it
    ``path``, else :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_DIR_ENV, "").strip()
    if env:
        return env, True
    return (path or DEFAULT_CACHE_DIR), False


def enable_compilation_cache(path: str = "") -> Optional[str]:
    """Enable JAX's persistent compilation cache (idempotent; the first
    decision in a process stands).

    Service restarts then skip the XLA compiles for every already-seen
    (kernel, bucket) shape — the largest component of a scorer service's
    cold-start time. Returns the armed cache directory, or ``None`` when
    persistence stayed off; raises :class:`CompileCacheError` when the
    directory cannot be created or written (an installed package's default
    sits beside site-packages — deployments set the variable).

    The directory is :func:`resolve_cache_dir`'s. With neither the
    environment variable nor ``path`` naming one, persistence stays off on
    the CPU backend: XLA:CPU compiles here are small and its serialized
    executables are tuned to the build host.

    On success the compile ledger's (engine/device_obs.py) cache counters
    are armed, so ``compile_cache_{hits,misses}_total`` start moving with
    the first cache-backed compile."""
    global _cache_enabled, _cache_dir
    with _cache_lock:
        if _cache_enabled:
            return _cache_dir
        import jax

        cache_dir, from_env = resolve_cache_dir(path)
        if not from_env and not path and jax.default_backend() == "cpu":
            _cache_enabled = True
            return None
        try:
            os.makedirs(cache_dir, exist_ok=True)
            writable = os.access(cache_dir, os.W_OK | os.X_OK)
        except OSError as exc:
            raise CompileCacheError(cache_dir, from_env, exc) from exc
        if not writable:
            raise CompileCacheError(cache_dir, from_env, "not writable")
        if not from_env:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          _MIN_COMPILE_S)
        # keep the cache at the jax/StableHLO level only: XLA's own
        # sub-caches embed compile-machine tuning that the loader distrusts
        # on any feature drift
        jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
        _cache_dir = cache_dir
        # only now: a failed attempt above must fail again on the next call,
        # not read as "decided: off"
        _cache_enabled = True
    # arm the ledger's hit/miss counters OUTSIDE the cache lock (the ledger
    # has its own); jax's cache_hits / cache_misses events drive them
    from ..engine import device_obs

    device_obs.get_ledger().arm_cache_counters()
    device_obs.install_cache_listener()
    return cache_dir


def persistent_cache_dir() -> Optional[str]:
    """The armed cache directory (None while off)."""
    with _cache_lock:
        return _cache_dir


class ProfileError(ValueError):
    """On-demand profiler capture failure (ValueError so the admin layer
    maps bad capture parameters to HTTP 400, not 500)."""


class ProfileBusyError(ProfileError):
    """A capture is already running in this process (jax.profiler allows at
    most one trace at a time; the admin route surfaces this as HTTP 409)."""


_CAPTURE_PREFIX = "capture-"
_DONE_MARKER = "capture.json"
MAX_CAPTURE_SECONDS = 300.0

# the zero-length annotation the capture thread leaves on the host plane at
# either end of the traced stretch: edge="start"|"stop", mono_ns=<this
# process's time.monotonic_ns() at that moment>
CAPTURE_MARK = "dm.capture_mark"
# the engine loop blocked in recv(): the fill, not a stall — kept apart from
# the other spans' maxima and not exported
RECV_WAIT_SPAN = "dm.recv_wait"
_DEFAULT_LABELS = {"component_type": "core", "component_id": "unknown"}


class StallHeartbeat:
    """A thread that sleeps 5 ms at a time for the length of a capture and
    writes down every wake that came more than 20 ms late. It contends for
    the interpreter as the engine thread does, so what it loses is what the
    engine thread can have lost meanwhile; a stall it does not see, under a
    span that ran long, was that span's own. Clock and sleep are injected
    (tests run :meth:`run` on a scripted clock)."""

    PERIOD_S = 0.005
    LATE_S = 0.020
    KEEP = 32

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep) -> None:
        self._clock, self._sleep = clock, sleep
        self._halt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0
        self._longest: List[Tuple[float, float]] = []   # heap of (late, due)

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run,
                                        name="ProfileHeartbeat", daemon=True)
        self._thread.start()

    def run(self) -> None:
        clock, sleep, period = self._clock, self._sleep, self.PERIOD_S
        while not self._halt.is_set():
            due = clock() + period
            sleep(period)
            late = clock() - due
            if late > self.LATE_S:
                self.count += 1
                self.sum_s += late
                self.max_s = max(self.max_s, late)
                heapq.heappush(self._longest, (late, due))
                if len(self._longest) > self.KEEP:
                    heapq.heappop(self._longest)

    def stop(self) -> None:
        """End the loop and, where :meth:`start` made a thread, join it."""
        self._halt.set()
        thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join()

    def summary(self, origin: float) -> Dict[str, Any]:
        """Count, sum and maximum of the late wakes and the :data:`KEEP`
        longest, each as (``at_s``: when the wake was due, as an offset from
        ``origin``; ``late_s``), in the order they came."""
        return {
            "count": self.count,
            "sum_s": round(self.sum_s, 6),
            "max_s": round(self.max_s, 6),
            "longest": [{"at_s": round(due - origin, 6),
                         "late_s": round(late, 6)}
                        for late, due in sorted(self._longest,
                                                key=lambda entry: entry[1])],
        }


def idle_share(before: Dict[str, float], after: Dict[str, float],
               seconds: float) -> Dict[str, float]:
    """Two readings of a ``DeviceIdleClock`` (``reading``) a stretch of
    ``seconds`` apart → the stretch's idle time by cause, in per cent of
    it."""
    return {cause: round(100.0 * (after[cause] - before[cause]) / seconds, 4)
            for cause in after}


def _xplane_bytes(capture_dir: str) -> int:
    return sum(os.path.getsize(os.path.join(root, name))
               for root, _dirs, files in os.walk(capture_dir)
               for name in files if name.endswith(".xplane.pb"))


def _rusage_delta(before, after) -> Dict[str, Any]:
    """Was the process busy over the capture, descheduled, or waiting on
    the disk: ``resource.getrusage(RUSAGE_SELF)`` end minus start."""
    return {
        "user_s": round(after.ru_utime - before.ru_utime, 6),
        "system_s": round(after.ru_stime - before.ru_stime, 6),
        "voluntary_switches": after.ru_nvcsw - before.ru_nvcsw,
        "involuntary_switches": after.ru_nivcsw - before.ru_nivcsw,
        "major_faults": after.ru_majflt - before.ru_majflt,
        "blocks_in": after.ru_inblock - before.ru_inblock,
        "blocks_out": after.ru_oublock - before.ru_oublock,
    }


class ProfileManager:
    """Bounded, concurrency-guarded ``jax.profiler`` captures.

    ``POST /admin/profile`` calls :meth:`start`: one capture per process at
    a time (the guard, not jax's crash), each landing in its own numbered
    ``capture-NNNN`` subdirectory of the configured ``profile_dir``, pruned
    to the newest ``max_captures`` so repeated captures cannot fill the
    disk. A finished capture writes a ``capture.json`` marker — only marked
    directories count as downloadable, so ``GET /admin/profile/latest``
    never serves a half-written trace.

    **The capture accounts for itself.** ``capture.json`` (and ``last`` of
    :meth:`status`) holds what the capture cost the process that took it,
    everything armed when the capture starts and disarmed when it ends:

    * ``start_trace_s``, ``traced_s``, ``stop_trace_s`` — the call of
      ``start_trace``, the stretch between its return and the call of
      ``stop_trace``, and that call; ``xplane_bytes`` — the
      ``*.xplane.pb`` left (``state`` is ``error`` where it is 0);
    * ``mark_mono_ns`` — ``time.monotonic_ns()`` as the two
      :data:`CAPTURE_MARK` annotations carry it: the host plane's event
      gives the same instant on the capture's clock, so every ``at_s``
      below — an offset from the ``start`` mark — can be laid on the
      device plane;
    * ``rusage`` — process deltas over the whole capture;
    * ``stalls`` — :class:`StallHeartbeat`'s late wakes;
    * ``spans`` — the longest ``dm.*`` span of each name
      (``engine/device_obs.py`` ``CaptureSpans``), ``recv_wait`` apart;
    * ``idle_share`` — the scorer's ``DeviceIdleClock`` by cause over
      ``traced_s``, in per cent (absent in a stage without a scorer).

    A capture that ends ``done`` sets the ``profile_capture_*`` gauges
    (``engine/metrics.py``); any capture's end first clears the last one's.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._current: Optional[Dict[str, Any]] = None
        self._last: Optional[Dict[str, Any]] = None
        self._idle_reader: Optional[Callable[[float], Dict[str, float]]] = None
        # (gauge, label values) of the last capture's gauges
        self._exported: List[Tuple[Any, Tuple[str, ...]]] = []

    def set_idle_reader(
            self, fn: Optional[Callable[[float], Dict[str, float]]]) -> None:
        """The scorer's reader for its ``DeviceIdleClock``: ``fn(now)`` →
        idle seconds by cause as they stand at ``now`` (monotonic), called
        on the capture thread at the two marks. Last registration wins,
        like the ledger's providers."""
        with self._lock:
            self._idle_reader = fn

    @staticmethod
    def default_dir() -> str:
        import tempfile

        return os.path.join(tempfile.gettempdir(),
                            f"detectmate_profile_{os.getpid()}")

    # -- capture ---------------------------------------------------------
    def start(self, base_dir: str, seconds: float, max_captures: int = 4,
              labels: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
        """Start a capture of ``seconds``; ``labels`` are the asking
        service's (``component_type``, ``component_id``), for the series
        the capture's end sets."""
        seconds = float(seconds)
        if not 0.0 < seconds <= MAX_CAPTURE_SECONDS:
            raise ProfileError(
                f"seconds must be in (0, {MAX_CAPTURE_SECONDS:.0f}], "
                f"got {seconds}")
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                raise ProfileBusyError(
                    "a profiler capture is already running "
                    f"({(self._current or {}).get('dir')})")
            os.makedirs(base_dir, exist_ok=True)
            seq = 1 + max((int(name[len(_CAPTURE_PREFIX):])
                           for name in os.listdir(base_dir)
                           if name.startswith(_CAPTURE_PREFIX)
                           and name[len(_CAPTURE_PREFIX):].isdigit()),
                          default=0)
            out_dir = os.path.join(base_dir, f"{_CAPTURE_PREFIX}{seq:04d}")
            os.makedirs(out_dir)
            info: Dict[str, Any] = {
                "state": "running",
                "dir": out_dir,
                "seq": seq,
                "seconds": seconds,
                "started_ts": round(time.time(), 6),
            }
            self._current = info
            self._thread = threading.Thread(
                target=self._run,
                args=(dict(info), base_dir, max_captures,
                      dict(labels or _DEFAULT_LABELS), self._idle_reader),
                name="ProfileCapture", daemon=True)
            self._thread.start()
            return dict(info)

    def _run(self, info: Dict[str, Any], base_dir: str, max_captures: int,
             labels: Dict[str, str], idle_reader) -> None:
        import json
        import resource

        import jax

        from ..engine import device_obs

        def mark(edge: str) -> float:
            """Leave the zero-length annotation; → its instant in seconds."""
            at_ns = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(CAPTURE_MARK, edge=edge,
                                              mono_ns=at_ns):
                pass
            info.setdefault("mark_mono_ns", {})[edge] = at_ns
            return at_ns / 1e9

        def read_idle(now: float) -> Optional[Dict[str, float]]:
            # the engine thread owns the account and what the coalescer
            # holds: a read that fell into one of its updates is made again
            for _ in range(3):
                try:
                    return idle_reader(now)
                except (RuntimeError, IndexError, KeyError):
                    continue
            return None

        spans = device_obs.CaptureSpans()
        heartbeat = StallHeartbeat()
        usage0 = resource.getrusage(resource.RUSAGE_SELF)
        origin = time.monotonic()       # the start mark's instant, once made
        heartbeat.start()
        device_obs.arm_capture(spans)
        try:
            # the Python call tracer (level 1 by default) hooks every call
            # on every thread for the length of the capture and stalls the
            # engine thread while it starts; the host plane still takes the
            # dm.* TraceAnnotation events (host_tracer_level stays as it is)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            t_call = time.monotonic()
            jax.profiler.start_trace(info["dir"], profiler_options=options)
            t_started = time.monotonic()
            # how long the profiler took to start: the stall a capture
            # costs the threads that wait on the interpreter meanwhile
            info["start_trace_s"] = round(t_started - t_call, 6)
            origin = mark("start")
            idle0 = read_idle(origin) if idle_reader is not None else None
            time.sleep(info["seconds"])
            at_stop = mark("stop")
            idle1 = read_idle(at_stop) if idle0 is not None else None
            t_stop = time.monotonic()
            info["traced_s"] = round(t_stop - t_started, 6)
            jax.profiler.stop_trace()
            info["stop_trace_s"] = round(time.monotonic() - t_stop, 6)
            info["xplane_bytes"] = _xplane_bytes(info["dir"])
            if idle1 is not None:
                info["idle_share"] = idle_share(idle0, idle1,
                                                at_stop - origin)
            if info["xplane_bytes"] > 0:
                info["state"] = "done"
            else:
                info["state"] = "error"
                info["error"] = ("stop_trace returned and left no "
                                 "*.xplane.pb of more than 0 bytes under "
                                 f"{info['dir']}")
        except Exception as exc:  # noqa: BLE001 — a failed capture must report, not die silently
            info["state"] = "error"
            info["error"] = repr(exc)
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — trace may not have started
                pass
        finally:
            device_obs.arm_capture(None)
            heartbeat.stop()
        info["finished_ts"] = round(time.time(), 6)
        info["rusage"] = _rusage_delta(
            usage0, resource.getrusage(resource.RUSAGE_SELF))
        info["stalls"] = heartbeat.summary(origin)
        longest = {}
        for name, (seconds, started, batch) in sorted(
                spans.snapshot().items()):
            longest[name] = {"seconds": round(seconds, 6),
                             "at_s": round(started - origin, 6)}
            if batch is not None:
                longest[name]["batch"] = batch
        recv_wait = longest.pop(RECV_WAIT_SPAN, None)
        if recv_wait is not None:
            info["recv_wait"] = recv_wait
        info["spans"] = longest
        try:
            with open(os.path.join(info["dir"], _DONE_MARKER), "w",
                      encoding="utf-8") as fh:
                json.dump(info, fh)
        except OSError:
            pass
        self._publish(info, labels)
        with self._lock:
            self._last = info
            self._current = None
        self._prune(base_dir, max_captures)

    def _publish(self, info: Dict[str, Any], labels: Dict[str, str]) -> None:
        """One more of ``profile_captures_total{state}``; the last capture's
        gauges go, and a capture that ended ``done`` sets its own."""
        from ..engine import metrics as m

        ident = (labels["component_type"], labels["component_id"])
        m.PROFILE_CAPTURES().labels(*ident, info["state"]).inc()
        for gauge, values in self._exported:
            gauge.remove(*values)
        self._exported = []
        if info["state"] != "done":
            return

        def put(gauge, key: str, value: float) -> None:
            gauge.labels(*ident, key).set(value)
            self._exported.append((gauge, ident + (key,)))

        for phase, key in (("start", "start_trace_s"), ("traced", "traced_s"),
                           ("stop", "stop_trace_s")):
            put(m.PROFILE_CAPTURE_SECONDS(), phase, info[key])
        for stat in ("max", "sum"):
            put(m.PROFILE_CAPTURE_STALL(), stat, info["stalls"][f"{stat}_s"])
        for name, entry in info["spans"].items():
            put(m.PROFILE_CAPTURE_SPAN_MAX(), name, entry["seconds"])
        for cause, share in info.get("idle_share", {}).items():
            put(m.PROFILE_CAPTURE_IDLE_SHARE(), cause, share)

    @staticmethod
    def _prune(base_dir: str, max_captures: int) -> None:
        import os
        import shutil

        try:
            captures = sorted(
                name for name in os.listdir(base_dir)
                if name.startswith(_CAPTURE_PREFIX))
        except OSError:
            return
        for name in captures[:max(0, len(captures) - max(1, max_captures))]:
            shutil.rmtree(os.path.join(base_dir, name), ignore_errors=True)

    # -- reads -----------------------------------------------------------
    def status(self) -> Dict[str, Any]:
        with self._lock:
            running = (self._thread is not None and self._thread.is_alive())
            return {
                "running": running,
                "current": dict(self._current) if self._current else None,
                "last": dict(self._last) if self._last else None,
            }

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the running capture (if any) finishes; True when no
        capture is left running (tests / CI smoke)."""
        with self._lock:
            thread = self._thread
        if thread is None:
            return True
        thread.join(timeout)
        return not thread.is_alive()

    def latest_dir(self, base_dir: str) -> Optional[str]:
        """Newest *completed* capture directory under ``base_dir``."""
        import os

        try:
            captures = sorted(
                (name for name in os.listdir(base_dir)
                 if name.startswith(_CAPTURE_PREFIX)), reverse=True)
        except OSError:
            return None
        for name in captures:
            path = os.path.join(base_dir, name)
            if os.path.exists(os.path.join(path, _DONE_MARKER)):
                return path
        return None

    def zip_latest(self, base_dir: str) -> Optional[tuple]:
        """(archive_name, zip_bytes) of the newest completed capture, or
        None when no completed capture exists."""
        import io
        import os
        import zipfile

        latest = self.latest_dir(base_dir)
        if latest is None:
            return None
        buffer = io.BytesIO()
        with zipfile.ZipFile(buffer, "w", zipfile.ZIP_DEFLATED) as archive:
            for root, _dirs, files in os.walk(latest):
                for name in files:
                    full = os.path.join(root, name)
                    archive.write(full, os.path.relpath(full, latest))
        return os.path.basename(latest) + ".zip", buffer.getvalue()


# one per process, like the jax profiler itself
PROFILER = ProfileManager()
