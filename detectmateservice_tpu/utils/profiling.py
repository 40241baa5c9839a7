"""jax.profiler integration (closes the tracing gap noted in SURVEY.md §5.1:
the reference has no profiling subsystem at all)."""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional, Tuple


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


class ProfileManager:
    """Bounded, concurrency-guarded ``jax.profiler`` captures.

    ``POST /admin/profile`` calls :meth:`start`: one capture per process at
    a time (the guard, not jax's crash), each landing in its own numbered
    ``capture-NNNN`` subdirectory of the configured ``profile_dir``, pruned
    to the newest ``max_captures`` so repeated captures cannot fill the
    disk. A finished capture writes a ``capture.json`` marker — only marked
    directories count as downloadable, so ``GET /admin/profile/latest``
    never serves a half-written trace.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._current: Optional[Dict[str, Any]] = None
        self._last: Optional[Dict[str, Any]] = None

    @staticmethod
    def default_dir() -> str:
        import os
        import tempfile

        return os.path.join(tempfile.gettempdir(),
                            f"detectmate_profile_{os.getpid()}")

    # -- capture ---------------------------------------------------------
    def start(self, base_dir: str, seconds: float,
              max_captures: int = 4) -> Dict[str, Any]:
        import os

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
                target=self._run, args=(dict(info), base_dir, max_captures),
                name="ProfileCapture", daemon=True)
            self._thread.start()
            return dict(info)

    def _run(self, info: Dict[str, Any], base_dir: str,
             max_captures: int) -> None:
        import json
        import os

        import jax

        try:
            # the Python call tracer (level 1 by default) hooks every call
            # on every thread for the length of the capture and stalls the
            # engine thread while it starts; the host plane still takes the
            # dm.* TraceAnnotation events (host_tracer_level stays as it is)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            t0 = time.monotonic()
            jax.profiler.start_trace(info["dir"], profiler_options=options)
            # how long the profiler took to start: the stall a capture
            # costs the threads that wait on the interpreter meanwhile
            info["start_trace_s"] = round(time.monotonic() - t0, 6)
            time.sleep(info["seconds"])
            jax.profiler.stop_trace()
            info["state"] = "done"
        except Exception as exc:  # noqa: BLE001 — a failed capture must report, not die silently
            info["state"] = "error"
            info["error"] = repr(exc)
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — trace may not have started
                pass
        info["finished_ts"] = round(time.time(), 6)
        try:
            with open(os.path.join(info["dir"], _DONE_MARKER), "w",
                      encoding="utf-8") as fh:
                json.dump(info, fh)
        except OSError:
            pass
        with self._lock:
            self._last = info
            self._current = None
        self._prune(base_dir, max_captures)

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
