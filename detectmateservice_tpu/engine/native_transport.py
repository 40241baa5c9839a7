"""ctypes bindings for the native C++ transport (native/transport/dmtransport.cpp).

Role of the reference's pynng-over-libnng data plane (reference:
src/service/features/engine_socket.py:35-78; SURVEY.md §2.8): the wire is
owned by native code. Frames ride libzmq DEALER sockets, so native sockets
interoperate with the Python zmq backend (socket.py) frame-for-frame — a
pipeline can mix both.

What the native layer adds: ``recv_many`` drains a whole micro-batch in ONE
call (one GIL crossing per batch instead of per message — SURVEY.md §7 hard
part #3). The engine's batch loop uses it when the input socket provides it.

Thread-safety contract (matches the engine's usage): ``recv``/``recv_many``
are called only from the engine loop thread; ``close`` only after that thread
has been joined (engine.py stop()). The C layer serializes calls with a
mutex, but close must not race an in-flight blocking recv.
"""
from __future__ import annotations

import ctypes
import logging
import subprocess
import threading
from pathlib import Path
from typing import List, Optional

from .socket import (
    EngineSocket,
    TransportAgain,
    TransportClosed,
    TransportError,
    TransportTimeout,
)

_PKG_DIR = Path(__file__).resolve().parent.parent
_LIB_PATH = _PKG_DIR / "_native" / "libdmtransport.so"
_SRC_PATH = _PKG_DIR.parent / "native" / "transport" / "dmtransport.cpp"

# keep in sync with dmtransport.cpp
_OK, _ETIMEOUT, _EAGAIN, _ECLOSED, _EERR, _ETOOBIG = 0, -1, -2, -3, -4, -5

# Feature version this binding expects the library to report
# (dmt_feature_version). Built from native/, never shipped: the loader and
# native/build.sh both stamp THIS number, and a library that is missing or
# reports another number is rebuilt (the same rule as utils/matchkern.py).
# A library that cannot be built or loaded raises ImportError so "auto"
# backend selection falls back to the Python transport LOUDLY. Bump it with
# the wire surface (and the default in native/transport/dmtransport.cpp).
DMT_FEATURE_VERSION = 3

_INITIAL_BUF = 16 * 1024 * 1024  # starting recv buffer; grows on demand —
                                 # oversized frames are stashed native-side
                                 # (dmt_pending_size) and retried, never lost


def _rebuild() -> None:
    """Compile to a temp file and atomically replace (same discipline as
    utils/matchkern.py), linking against the soname directly — this image
    ships libzmq.so.5 but no dev symlink or header."""
    import os
    import tempfile

    _LIB_PATH.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=str(_LIB_PATH.parent))
    os.close(fd)
    try:
        subprocess.run(
            ["c++", "-O2", "-std=c++17", "-shared", "-fPIC",
             f"-DDMT_FEATURE_VERSION={DMT_FEATURE_VERSION}", "-o", tmp,
             str(_SRC_PATH), "-l:libzmq.so.5", "-lpthread"],
            check=True, capture_output=True, timeout=120,
        )
        os.chmod(tmp, 0o755)
        os.replace(tmp, str(_LIB_PATH))
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _lib_feature_version(lib: ctypes.CDLL) -> int:
    """Version the loaded library reports; 0 for pre-versioning builds."""
    try:
        fn = lib.dmt_feature_version
    except AttributeError:
        return 0
    fn.restype = ctypes.c_int
    return int(fn())


def _dlopen() -> ctypes.CDLL:
    try:
        return ctypes.CDLL(str(_LIB_PATH))
    except OSError as exc:
        # e.g. no libzmq.so.5 on this host — surface as ImportError so
        # "auto" backend selection falls back to the pure-Python transport
        raise ImportError(f"cannot load native transport: {exc}")


def _load() -> ctypes.CDLL:
    lib = _dlopen() if _LIB_PATH.exists() else None
    if lib is None or _lib_feature_version(lib) != DMT_FEATURE_VERSION:
        # missing or stale: build from source. dlopen returns the object it
        # already holds for a path, so the stale mapping is dropped first
        if lib is not None:
            import _ctypes

            _ctypes.dlclose(lib._handle)
        try:
            _rebuild()
        except (subprocess.SubprocessError, OSError) as exc:
            raise ImportError(
                f"cannot build native transport from {_SRC_PATH}: {exc}")
        lib = _dlopen()
        if _lib_feature_version(lib) != DMT_FEATURE_VERSION:
            raise ImportError(
                f"stale native transport library {_LIB_PATH}: reports "
                f"feature version {_lib_feature_version(lib)} after a "
                f"rebuild, bindings expect {DMT_FEATURE_VERSION}")
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.dmt_listen.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
    lib.dmt_listen.restype = ctypes.c_void_p
    lib.dmt_dial.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
                             ctypes.c_int]
    lib.dmt_dial.restype = ctypes.c_void_p
    lib.dmt_set_recv_timeout.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dmt_recv.argtypes = [ctypes.c_void_p, u8p, ctypes.c_longlong]
    lib.dmt_recv.restype = ctypes.c_longlong
    lib.dmt_recv_many.argtypes = [ctypes.c_void_p, u8p, ctypes.c_longlong,
                                  ctypes.c_int, ctypes.c_int,
                                  ctypes.POINTER(ctypes.c_longlong)]
    lib.dmt_recv_many.restype = ctypes.c_int
    lib.dmt_pending_size.argtypes = [ctypes.c_void_p]
    lib.dmt_pending_size.restype = ctypes.c_longlong
    lib.dmt_send.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
                             ctypes.c_int]
    lib.dmt_send.restype = ctypes.c_int
    lib.dmt_send_many.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_longlong, ctypes.c_int,
                                  ctypes.c_int]
    lib.dmt_send_many.restype = ctypes.c_int
    lib.dmt_close.argtypes = [ctypes.c_void_p]
    return lib


_lib = _load()
_U8P = ctypes.POINTER(ctypes.c_uint8)


def _raise(code: int, what: str) -> None:
    if code == _ETIMEOUT:
        raise TransportTimeout(f"{what} timeout")
    if code == _EAGAIN:
        raise TransportAgain(f"{what} would block")
    if code == _ECLOSED:
        raise TransportClosed(f"{what} on closed socket")
    raise TransportError(f"{what} failed (code {code})")


class NativePairSocket:
    """EngineSocket over the C++ transport (surface of socket.ZmqPairSocket)."""

    def __init__(self, handle: int, addr: str):
        self._handle = handle
        self._addr = addr
        self._closed = False
        self._recv_timeout: Optional[int] = None
        self._buf = None  # allocated on first recv; output sockets never pay
        self._close_lock = threading.Lock()

    def _ensure_buf(self, cap: int):
        if self._buf is None or len(self._buf) < cap:
            self._buf = (ctypes.c_uint8 * cap)()
        return self._buf

    @property
    def recv_timeout(self) -> Optional[int]:
        return self._recv_timeout

    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None:
        self._recv_timeout = ms
        if not self._closed:
            _lib.dmt_set_recv_timeout(self._handle, -1 if ms is None else int(ms))

    def recv(self) -> bytes:
        if self._closed:
            raise TransportClosed(f"recv on closed socket {self._addr}")
        buf = self._ensure_buf(_INITIAL_BUF)
        while True:
            n = _lib.dmt_recv(self._handle, buf, len(buf))
            if n == _ETOOBIG:
                # frame is stashed native-side; grow and retry — no data loss
                need = int(_lib.dmt_pending_size(self._handle))
                buf = self._ensure_buf(max(need, len(buf) * 2))
                continue
            if n < 0:
                _raise(int(n), "recv")
            return bytes(memoryview(buf)[: int(n)])

    def recv_many(self, max_n: int, first_timeout_ms: int) -> List[bytes]:
        """Drain up to ``max_n`` queued frames in one native call. Blocks up
        to ``first_timeout_ms`` for the first frame only; raises
        TransportTimeout when nothing arrived."""
        if self._closed:
            raise TransportClosed(f"recv on closed socket {self._addr}")
        buf = self._ensure_buf(max(_INITIAL_BUF, max_n * 4096))
        used = ctypes.c_longlong(0)
        while True:
            count = _lib.dmt_recv_many(self._handle, buf, len(buf), max_n,
                                       int(first_timeout_ms), ctypes.byref(used))
            if count == _ETOOBIG:
                # first frame alone exceeds the buffer: it is stashed
                # native-side; grow and retry — no data loss
                need = int(_lib.dmt_pending_size(self._handle))
                buf = self._ensure_buf(max(need + 4, len(buf) * 2))
                continue
            break
        if count < 0:
            _raise(int(count), "recv_many")
        frames: List[bytes] = []
        view = memoryview(buf)
        off = 0
        for _ in range(count):
            ln = int.from_bytes(view[off:off + 4], "little")
            frames.append(bytes(view[off + 4:off + 4 + ln]))
            off += 4 + ln
        return frames

    def send(self, data: bytes, block: bool = True) -> None:
        if self._closed:
            raise TransportClosed(f"send on closed socket {self._addr}")
        rc = _lib.dmt_send(self._handle, data, len(data), 1 if block else 0)
        if rc != _OK:
            _raise(int(rc), "send")

    def send_many(self, frames: List[bytes], block: bool = False) -> int:
        """Send a whole output micro-batch in ONE native call (the send-side
        twin of ``recv_many``: one GIL crossing per batch, not per frame).
        Returns how many leading frames were handed to the transport — the
        caller retries the remainder (per-frame retry/drop accounting stays
        exact). Raises the usual taxonomy only when not even the first frame
        went out."""
        if self._closed:
            raise TransportClosed(f"send on closed socket {self._addr}")
        if not frames:
            return 0
        buf = bytearray()
        for frame in frames:
            buf += len(frame).to_bytes(4, "little")
            buf += frame
        rc = _lib.dmt_send_many(self._handle, bytes(buf), len(buf),
                                len(frames), 1 if block else 0)
        if rc < 0:
            _raise(int(rc), "send_many")
        return int(rc)

    def close(self) -> None:
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        _lib.dmt_close(self._handle)
        self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NativePairSocketFactory:
    """EngineSocketFactory over the C++ transport. tls+tcp stays on the
    Python ssl transport, and ws AND inproc on the Python zmq backend — the
    factory delegates those schemes, so every address the zmq factory accepts
    works here too. inproc in particular MUST delegate: the native layer's
    private zmq context can never rendezvous with pyzmq's process-wide
    ``Context.instance()``, so a native-side inproc endpoint would silently
    never connect to a zmq-side (or auto-fallback) peer in the same process."""

    SCHEMES = ("ipc", "tcp")

    def _delegate(self, scheme: str):
        if scheme == "tls+tcp":
            from .socket import TlsTcpSocketFactory

            return TlsTcpSocketFactory()
        if scheme == "nng+tcp":
            from .socket import NngTcpSocketFactory

            return NngTcpSocketFactory()
        if scheme == "nng+tls+tcp":
            from .socket import NngTlsTcpSocketFactory

            return NngTlsTcpSocketFactory()
        if scheme in ("ws", "inproc"):
            from .socket import ZmqPairSocketFactory

            return ZmqPairSocketFactory()
        return None

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme = addr.split("://", 1)[0] if "://" in addr else ""
        delegate = self._delegate(scheme)
        if delegate is not None:
            return delegate.create(addr, logger, tls_config)
        if scheme not in self.SCHEMES:
            raise TransportError(f"unsupported scheme {scheme!r} in {addr!r}")
        if scheme == "tcp":
            host_port = addr.split("://", 1)[1].split("/", 1)[0]
            if ":" not in host_port:
                raise TransportError(f"tcp address {addr!r} requires an explicit port")
        err = ctypes.create_string_buffer(256)
        handle = _lib.dmt_listen(addr.encode(), err, len(err))
        if not handle:
            raise TransportError(
                f"cannot listen on {addr}: {err.value.decode(errors='replace')}")
        logger.debug("native transport listening on %s", addr)
        return NativePairSocket(handle, addr)

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme = addr.split("://", 1)[0] if "://" in addr else ""
        delegate = self._delegate(scheme)
        if delegate is not None:
            return delegate.create_output(
                addr, logger, tls_config, dial_timeout, buffer_size)
        if scheme not in self.SCHEMES:
            raise TransportError(f"unsupported scheme {scheme!r} in {addr!r}")
        err = ctypes.create_string_buffer(256)
        handle = _lib.dmt_dial(addr.encode(), max(1, buffer_size), err, len(err))
        if not handle:
            raise TransportError(
                f"cannot dial {addr}: {err.value.decode(errors='replace')}")
        logger.debug("native transport dialing %s (background connect)", addr)
        return NativePairSocket(handle, addr)
