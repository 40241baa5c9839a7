"""Data-plane transport: pair-socket surface over multiple backends.

The reference's data plane is NNG Pair0 via pynng (reference:
src/service/features/engine_socket.py:35-78, engine.py:133-179). This build
has no libnng; the same observable surface — ``listen/dial/send/recv`` with
receive timeouts, non-blocking sends, background reconnect, drop-don't-block —
is provided over:

* **zmq DEALER** pairs for ``ipc:// tcp:// inproc://`` (libzmq does background
  reconnect and bounded buffering natively; DEALER-DEALER is bidirectional 1:1
  like Pair0),
* a pure-Python **length-prefixed TLS/TCP** transport for ``tls+tcp://``
  (real ssl: server cert/key, client CA + server-name verification — parity
  with the reference's mbedTLS modes, engine_socket.py:60-71, engine.py:165-170),
* an in-process queue transport for tests,
* an optional in-tree **C++ transport** (native/transport) loaded when built,
  with the same surface.

Exception taxonomy maps 1:1 onto pynng's (Timeout / TryAgain / NNGException →
TransportTimeout / TransportAgain / TransportError), because the engine's
retry/drop logic is written against it (reference: engine.py:216-218,290-299).

The factory protocol is the seam tests use to inject fakes — kept verbatim
(reference: engine_socket.py:23-32).
"""
from __future__ import annotations

import errno
import logging
import os
import queue
import socket as _stdsocket
import ssl
import struct
import threading
import time
from typing import Dict, List, Optional, Protocol, runtime_checkable

import zmq


class TransportError(Exception):
    """Base transport failure (maps to pynng.NNGException)."""


class TransportTimeout(TransportError):
    """recv timed out (maps to pynng.Timeout)."""


class TransportAgain(TransportError):
    """Non-blocking send would block (maps to pynng.TryAgain)."""


class TransportClosed(TransportError):
    """Operation on a closed socket."""


@runtime_checkable
class EngineSocket(Protocol):
    """Minimal socket surface the engine loop uses (reference: engine_socket.py:12-20)."""

    def recv(self) -> bytes: ...
    def send(self, data: bytes, block: bool = True) -> None: ...
    def close(self) -> None: ...
    @property
    def recv_timeout(self) -> Optional[int]: ...
    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None: ...


@runtime_checkable
class EngineSocketFactory(Protocol):
    """Factory seam (reference: engine_socket.py:23-32). ``create`` returns a
    listening socket bound to ``addr``; ``create_output`` returns a dialing
    socket connected (possibly in the background) to ``addr``."""

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket: ...

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket: ...


def _split_scheme(addr: str) -> tuple:
    if "://" not in addr:
        raise TransportError(f"address {addr!r} has no scheme")
    scheme, rest = addr.split("://", 1)
    return scheme, rest


# ---------------------------------------------------------------------------
# zmq backend
# ---------------------------------------------------------------------------

_shared_ctx: Optional[zmq.Context] = None
_ctx_lock = threading.Lock()


def _context() -> zmq.Context:
    # one process-wide context so inproc:// endpoints are visible everywhere
    global _shared_ctx
    with _ctx_lock:
        if _shared_ctx is None or _shared_ctx.closed:
            _shared_ctx = zmq.Context.instance()
        return _shared_ctx


class ZmqPairSocket:
    """DEALER socket with the pair surface. 1:1 bidirectional, background
    reconnect, bounded HWM buffering; ``send(block=False)`` raises
    TransportAgain when buffers are full (drop handling is the engine's job,
    reference: engine.py:286-296)."""

    def __init__(self, sock: zmq.Socket, addr: str, unlink_on_close: Optional[str] = None):
        self._sock = sock
        self._addr = addr
        self._closed = False
        self._recv_timeout: Optional[int] = None
        self._unlink_on_close = unlink_on_close
        self._lock = threading.Lock()

    @property
    def recv_timeout(self) -> Optional[int]:
        return self._recv_timeout

    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None:
        self._recv_timeout = ms
        self._sock.setsockopt(zmq.RCVTIMEO, -1 if ms is None else int(ms))

    def recv(self) -> bytes:
        if self._closed:
            raise TransportClosed(f"recv on closed socket {self._addr}")
        try:
            return self._sock.recv()
        except zmq.Again as exc:
            raise TransportTimeout(str(exc) or "recv timeout") from exc
        except zmq.ZMQError as exc:
            if self._closed:
                raise TransportClosed(str(exc)) from exc
            raise TransportError(str(exc)) from exc

    def recv_many(self, max_n: int, first_timeout_ms: int) -> List[bytes]:
        """Drain up to ``max_n`` frames in one call: a timed recv for the
        first, then non-blocking drains — the engine's burst collector pays
        one call per BURST instead of one per frame (the native transport's
        recv_many contract, minus its single-buffer copy). Raises
        TransportTimeout when nothing arrives within ``first_timeout_ms``."""
        if self._closed:
            raise TransportClosed(f"recv on closed socket {self._addr}")
        if max_n <= 0:
            return []  # native contract: never over-deliver past the cap
        frames: List[bytes] = []
        try:
            self._sock.setsockopt(zmq.RCVTIMEO, max(1, int(first_timeout_ms)))
            try:
                frames.append(self._sock.recv())
            finally:
                try:
                    self._sock.setsockopt(
                        zmq.RCVTIMEO,
                        -1 if self._recv_timeout is None
                        else int(self._recv_timeout))
                except zmq.ZMQError:
                    pass  # closing mid-call: frames already read still count
            while len(frames) < max_n:
                try:
                    frames.append(self._sock.recv(flags=zmq.DONTWAIT))
                except zmq.Again:
                    break
            return frames
        except zmq.Again as exc:
            raise TransportTimeout(str(exc) or "recv timeout") from exc
        except zmq.ZMQError as exc:
            if frames:
                # frames already consumed from the queue must reach the
                # caller, not vanish — the native backend returns partial
                # batches in the same situation (delivered-or-counted
                # accounting depends on it)
                return frames
            if self._closed:
                raise TransportClosed(str(exc)) from exc
            raise TransportError(str(exc)) from exc

    def send(self, data: bytes, block: bool = True) -> None:
        if self._closed:
            raise TransportClosed(f"send on closed socket {self._addr}")
        try:
            self._sock.send(data, flags=0 if block else zmq.DONTWAIT)
        except zmq.Again as exc:
            raise TransportAgain(str(exc) or "send would block") from exc
        except zmq.ZMQError as exc:
            if self._closed:
                raise TransportClosed(str(exc)) from exc
            raise TransportError(str(exc)) from exc

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        try:
            self._sock.close(linger=0)
        finally:
            if self._unlink_on_close:
                try:
                    os.unlink(self._unlink_on_close)
                except OSError:
                    pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ZmqPairSocketFactory:
    """Default factory (role of the reference's NngPairSocketFactory,
    engine_socket.py:35-78)."""

    SCHEMES = ("ipc", "tcp", "inproc")

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme == "tls+tcp":
            factory = TlsTcpSocketFactory()
            return factory.create(addr, logger, tls_config)
        if scheme == "nng+tcp":
            return NngTcpSocketFactory().create(addr, logger, tls_config)
        if scheme == "nng+tls+tcp":
            return NngTlsTcpSocketFactory().create(addr, logger, tls_config)
        if scheme == "ws":
            # the Python RFC6455 transport, NOT libzmq's ws (a compile-time
            # option this image's libzmq lacks) — and wire-compatible with
            # NNG ws peers, which zmq's ws would not be
            return WsSocketFactory().create(addr, logger, tls_config)
        if scheme not in self.SCHEMES:
            raise TransportError(f"unsupported scheme {scheme!r} in {addr!r}")
        unlink = None
        if scheme == "ipc":
            # unlink a stale ipc file before bind (reference: engine_socket.py:46-54)
            path = rest
            if os.path.exists(path):
                try:
                    os.unlink(path)
                    logger.debug("unlinked stale ipc file %s", path)
                except OSError as exc:
                    raise TransportError(f"cannot unlink stale ipc file {path}: {exc}") from exc
            unlink = path
        if scheme == "tcp":
            host_port = rest.split("/", 1)[0]
            if ":" not in host_port:
                raise TransportError(f"tcp address {addr!r} requires an explicit port")
        sock = _context().socket(zmq.DEALER)
        sock.setsockopt(zmq.LINGER, 0)
        try:
            sock.bind(addr)
        except zmq.ZMQError as exc:
            sock.close(linger=0)  # close on bind failure (reference: engine_socket.py:72-78)
            raise TransportError(f"cannot listen on {addr}: {exc}") from exc
        logger.debug("listening on %s", addr)
        return ZmqPairSocket(sock, addr, unlink_on_close=unlink)

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, _ = _split_scheme(addr)
        if scheme == "tls+tcp":
            factory = TlsTcpSocketFactory()
            return factory.create_output(addr, logger, tls_config, dial_timeout, buffer_size)
        if scheme == "nng+tcp":
            return NngTcpSocketFactory().create_output(addr, logger, tls_config,
                                                       dial_timeout, buffer_size)
        if scheme == "nng+tls+tcp":
            return NngTlsTcpSocketFactory().create_output(addr, logger, tls_config,
                                                          dial_timeout, buffer_size)
        if scheme == "ws":
            return WsSocketFactory().create_output(addr, logger, tls_config,
                                                   dial_timeout, buffer_size)
        if scheme not in self.SCHEMES:
            raise TransportError(f"unsupported scheme {scheme!r} in {addr!r}")
        sock = _context().socket(zmq.DEALER)
        sock.setsockopt(zmq.LINGER, 0)
        sock.setsockopt(zmq.SNDHWM, max(1, buffer_size))
        sock.setsockopt(zmq.RCVHWM, max(1, buffer_size))
        sock.setsockopt(zmq.RECONNECT_IVL, 100)
        # ZMQ_IMMEDIATE: queue only to live connections so non-blocking sends
        # to a dead peer raise Again instead of buffering forever — matches
        # the reference's drop accounting (engine.py:286-296)
        sock.setsockopt(zmq.IMMEDIATE, 1)
        try:
            sock.connect(addr)  # async connect, like nng dial(block=False)
        except zmq.ZMQError as exc:
            sock.close(linger=0)
            raise TransportError(f"cannot dial {addr}: {exc}") from exc
        logger.debug("dialing %s (background connect)", addr)
        return ZmqPairSocket(sock, addr)


# ---------------------------------------------------------------------------
# framed-TCP core: length-prefixed frames over a (possibly wrapped) stream.
# Two users: the tls+tcp backend (ssl wrap, 4-byte frames) and the NNG
# SP-wire backend (plain TCP, SP handshake, 8-byte frames).
# ---------------------------------------------------------------------------

_FRAME_HDR = struct.Struct("!I")
_MAX_FRAME = 64 * 1024 * 1024
# Steady-state socket timeout on ESTABLISHED framed/ws connections. Serves
# two contracts at once (advisor r3 high+medium): (a) it REPLACES the dial/
# handshake timeout, which must not govern steady-state reads — a ~1 s
# connect timeout left on the socket made the reader tear down and redial
# every second of inbound idle on one-way output pipes; recv treats a tick
# as "no data yet", not an error; (b) it bounds each SEND ATTEMPT, so a
# stalled peer cannot wedge the engine thread indefinitely. Plain-TCP sends
# retry in chunks as long as the peer keeps draining (a slow reader — e.g.
# one paused in an XLA compile — is backpressure, not failure) and tear the
# connection down only after _SEND_STALL_WINDOWS consecutive zero-progress
# windows; ssl sends cannot resume a partially-written record, so a single
# timeout there tears down immediately.
_STEADY_TIMEOUT = 2.0
_SEND_STALL_WINDOWS = 5   # ~10 s of ZERO progress before giving up


def _send_with_progress(sock: _stdsocket.socket, data: bytes) -> None:
    """sendall with per-chunk timeouts and progress-based retry (plain TCP).

    ``socket.sendall`` gives no way to know how much was written when it
    times out, so a timeout there corrupts the frame stream. ``send`` does:
    loop it, retry zero-progress windows up to the stall limit, and raise
    ``socket.timeout`` only for a genuinely wedged peer."""
    view = memoryview(data)
    stalls = 0
    while view:
        try:
            sent = sock.send(view)
        except _stdsocket.timeout:
            stalls += 1
            if stalls >= _SEND_STALL_WINDOWS:
                raise
            continue
        if sent:
            stalls = 0
            view = view[sent:]


class _FramedConn:
    """One established stream connection with length-prefix framing."""

    def __init__(self, sock: _stdsocket.socket, hdr: struct.Struct = _FRAME_HDR):
        self.sock = sock
        self.send_lock = threading.Lock()
        self._hdr = hdr
        self._is_ssl = isinstance(sock, ssl.SSLSocket)

    def send_frame(self, data: bytes) -> None:
        with self.send_lock:
            try:
                payload = self._hdr.pack(len(data)) + data
                if self._is_ssl:
                    # an SSL record interrupted mid-write cannot be resumed
                    # byte-wise; rely on sendall and treat timeout as fatal
                    self.sock.sendall(payload)
                else:
                    _send_with_progress(self.sock, payload)
            except _stdsocket.timeout as exc:
                # partial frame may have hit the wire → framing is corrupt;
                # close so the reader thread runs the normal teardown path
                self.close()
                raise TransportError(
                    "send stalled (no progress for "
                    f"{_SEND_STALL_WINDOWS * _STEADY_TIMEOUT:.0f}s); "
                    "connection dropped") from exc

    def recv_frame(self) -> bytes:
        hdr = self._recv_exact(self._hdr.size)
        (length,) = self._hdr.unpack(hdr)
        if length > _MAX_FRAME:
            raise TransportError(f"oversized frame: {length} bytes")
        return self._recv_exact(length)

    def _recv_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            try:
                chunk = self.sock.recv(n - len(buf))
            except (_stdsocket.timeout, ssl.SSLWantReadError):
                continue  # idle tick, not an error: keep accumulating
            if not chunk:
                raise ConnectionError("peer closed")
            buf.extend(chunk)
        return bytes(buf)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


class FramedTcpListener:
    """Server side of a framed-TCP transport. Accepts any number of dialers
    (fan-in, like many NNG dialers to one listener) and merges their frames
    into one recv queue. Replies route exactly via ``last_origin``/``send_to``
    (the engine's reply mode uses them); plain ``send`` falls back to the
    connection the last message arrived on — correct for Pair0 1:1, a
    heuristic under multi-dialer interleaving. ``prepare(raw_sock,
    server_side)`` turns an accepted TCP connection into a ``_FramedConn``
    (ssl wrap for tls+tcp, SP handshake for nng+tcp) or raises to reject
    the peer."""

    def __init__(self, host: str, port: int, prepare,
                 logger: logging.Logger, buffer_size: int = 100,
                 label: str = "framed+tcp"):
        self._logger = logger
        self._prepare = prepare
        self._label = label
        self._rq: "queue.Queue" = queue.Queue(maxsize=max(1, buffer_size))
        self._conns: List[_FramedConn] = []
        self._conns_lock = threading.Lock()
        self._last_conn: Optional[_FramedConn] = None
        self._closed = threading.Event()
        self._recv_timeout: Optional[int] = None
        self._listener = _stdsocket.socket(_stdsocket.AF_INET, _stdsocket.SOCK_STREAM)
        self._listener.setsockopt(_stdsocket.SOL_SOCKET, _stdsocket.SO_REUSEADDR, 1)
        try:
            self._listener.bind((host, port))
            self._listener.listen(16)
        except OSError as exc:
            self._listener.close()
            raise TransportError(f"cannot listen on {label}://{host}:{port}: {exc}") from exc
        self._accept_thread = threading.Thread(target=self._accept_loop, daemon=True,
                                               name=f"{label}-accept")
        self._accept_thread.start()

    @property
    def recv_timeout(self) -> Optional[int]:
        return self._recv_timeout

    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None:
        self._recv_timeout = ms

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                raw_conn, peer = self._listener.accept()
            except OSError:
                return
            if self._closed.is_set():
                # accepted by a listener that close() had already taken
                # down: refuse before the handshake, or the dialer would
                # believe it reconnected and write frames nobody reads
                raw_conn.close()
                return
            try:
                conn = self._prepare(raw_conn, True)
            except (ssl.SSLError, OSError, TransportError) as exc:
                self._logger.warning("%s handshake failed from %s: %s",
                                     self._label, peer, exc)
                raw_conn.close()
                continue
            conn.sock.settimeout(_STEADY_TIMEOUT)
            with self._conns_lock:
                if self._closed.is_set():
                    # close() ran during the handshake and has cleared
                    # _conns: this connection is nobody's to read
                    conn.close()
                    return
                self._conns.append(conn)
            threading.Thread(target=self._reader_loop, args=(conn,), daemon=True,
                             name=f"{self._label}-reader").start()

    def _reader_loop(self, conn: _FramedConn) -> None:
        try:
            while not self._closed.is_set():
                frame = conn.recv_frame()
                self._rq.put((conn, frame))
        except (ConnectionError, OSError, TransportError):
            pass
        finally:
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            conn.close()

    def recv(self) -> bytes:
        if self._closed.is_set():
            raise TransportClosed(f"recv on closed {self._label} listener")
        timeout = None if self._recv_timeout is None else self._recv_timeout / 1000.0
        try:
            conn, frame = self._rq.get(timeout=timeout)
        except queue.Empty:
            raise TransportTimeout("recv timeout")
        self._last_conn = conn
        return frame

    @property
    def peer_count(self) -> int:
        """Live fan-in connections. The engine uses this to skip per-frame
        origin bookkeeping when only one peer exists (misrouting needs two).
        Taken under the conns lock: the probe runs once per burst, and a
        torn read during an accept would misclassify the whole burst."""
        with self._conns_lock:
            return len(self._conns)

    @property
    def last_origin(self):
        """Opaque token identifying the connection the most recent ``recv``'d
        frame arrived on. Capture it right after ``recv`` and pass it to
        ``send_to`` to route a reply to the requester — with multiple dialers
        fanned in, plain ``send`` can only guess (last-recv heuristic)."""
        return self._last_conn

    def send_to(self, origin, data: bytes, block: bool = True) -> None:
        """Send to the exact connection ``origin`` (a ``last_origin`` token).
        Raises TransportAgain if that peer has disconnected — a reply to a
        gone requester is undeliverable, not misroutable to someone else."""
        if self._closed.is_set():
            raise TransportClosed(f"send on closed {self._label} listener")
        with self._conns_lock:
            alive = origin in self._conns
        if not alive:
            raise TransportAgain("reply peer disconnected")
        try:
            origin.send_frame(data)
        except (ConnectionError, OSError) as exc:
            raise TransportError(str(exc)) from exc

    def send(self, data: bytes, block: bool = True) -> None:
        if self._closed.is_set():
            raise TransportClosed(f"send on closed {self._label} listener")
        conn = self._last_conn
        if conn is None:
            with self._conns_lock:
                conn = self._conns[0] if self._conns else None
        if conn is None:
            raise TransportAgain("no connected peer")
        try:
            conn.send_frame(data)
        except (ConnectionError, OSError) as exc:
            raise TransportError(str(exc)) from exc

    def close(self) -> None:
        if self._closed.is_set():
            return
        self._closed.set()
        # close() alone leaves the accept thread blocked in accept(): the
        # syscall holds the socket, so the port stays in LISTEN and the
        # next redial of a peer is accepted by a listener that is gone —
        # frames the peer then counts written are lost, and a restarted
        # listener cannot bind (EADDRINUSE). shutdown() ends the accept
        # at once (tests/test_chaos.py is the witness)
        try:
            self._listener.shutdown(_stdsocket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        with self._conns_lock:
            for conn in self._conns:
                conn.close()
            self._conns.clear()


class FramedTcpDialer:
    """Client side of a framed-TCP transport with background redial (parity
    with nng dial(block=False) + reconnect, reference: engine.py:148,172-175).
    ``prepare(raw_sock, server_side)`` performs the ssl wrap / SP handshake
    and returns the framed connection."""

    def __init__(self, host: str, port: int, prepare,
                 logger: logging.Logger,
                 dial_timeout_ms: Optional[int], buffer_size: int = 100,
                 label: str = "framed+tcp"):
        self._host, self._port = host, port
        self._prepare = prepare
        self._label = label
        self._logger = logger
        self._dial_timeout = (dial_timeout_ms or 1000) / 1000.0
        self._conn: Optional[_FramedConn] = None
        self._conn_lock = threading.Lock()
        self._rq: "queue.Queue" = queue.Queue(maxsize=max(1, buffer_size))
        self._closed = threading.Event()
        self._recv_timeout: Optional[int] = None
        self._dial_thread = threading.Thread(target=self._dial_loop, daemon=True,
                                             name=f"{label}-dialer")
        self._dial_thread.start()

    @property
    def recv_timeout(self) -> Optional[int]:
        return self._recv_timeout

    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None:
        self._recv_timeout = ms

    def _dial_loop(self) -> None:
        backoff = 0.05
        while not self._closed.is_set():
            with self._conn_lock:
                have = self._conn is not None
            if have:
                time.sleep(0.1)
                continue
            try:
                raw = _stdsocket.create_connection((self._host, self._port),
                                                   timeout=self._dial_timeout)
                # TCP self-connect guard: redialing a DOWN localhost listener,
                # the kernel can pick the target port as this socket's
                # ephemeral source port and the simultaneous-open handshake
                # connects the socket TO ITSELF. The SP/ws handshake then
                # "succeeds" against our own bytes, the dialer believes the
                # peer is back (black-holing traffic into an echo loop), and
                # the port stays captured so the real listener can never
                # rebind (EADDRINUSE). Found by tests/test_chaos.py.
                if raw.getsockname() == raw.getpeername():
                    raw.close()
                    raise TransportError("self-connect (peer is down)")
                conn = self._prepare(raw, False)
                # the connect timeout must NOT govern steady-state reads
                # (it made the reader tear down + redial on every ~1 s of
                # inbound idle); switch to the steady-state timeout, under
                # which recv treats a tick as idle and send stays bounded
                conn.sock.settimeout(_STEADY_TIMEOUT)
                with self._conn_lock:
                    self._conn = conn
                threading.Thread(target=self._reader_loop, args=(conn,), daemon=True,
                                 name=f"{self._label}-dial-reader").start()
                backoff = 0.05
            except (OSError, ssl.SSLError, TransportError):
                time.sleep(backoff)
                backoff = min(backoff * 2, 1.0)

    def _reader_loop(self, conn: _FramedConn) -> None:
        try:
            while not self._closed.is_set():
                self._rq.put(conn.recv_frame())
        except (ConnectionError, OSError, TransportError):
            pass
        finally:
            with self._conn_lock:
                if self._conn is conn:
                    self._conn = None
            conn.close()

    def recv(self) -> bytes:
        if self._closed.is_set():
            raise TransportClosed(f"recv on closed {self._label} dialer")
        timeout = None if self._recv_timeout is None else self._recv_timeout / 1000.0
        try:
            return self._rq.get(timeout=timeout)
        except queue.Empty:
            raise TransportTimeout("recv timeout")

    def send(self, data: bytes, block: bool = True) -> None:
        if self._closed.is_set():
            raise TransportClosed(f"send on closed {self._label} dialer")
        with self._conn_lock:
            conn = self._conn
        if conn is None:
            raise TransportAgain("not connected")
        try:
            conn.send_frame(data)
        except (ConnectionError, OSError) as exc:
            with self._conn_lock:
                if self._conn is conn:
                    self._conn = None
            if self._closed.is_set():
                # close() raced this send and pulled the fd out from under
                # us (observed as a spurious "[Errno 9] Bad file descriptor"
                # under full-suite load) — that is a clean shutdown, not a
                # transport failure
                raise TransportClosed(
                    f"send on closed {self._label} dialer") from exc
            if getattr(exc, "errno", None) == errno.EBADF:
                # conn torn down concurrently (redial in flight): retryable
                raise TransportAgain("connection lost during send") from exc
            raise TransportError(str(exc)) from exc

    def close(self) -> None:
        if self._closed.is_set():
            return
        self._closed.set()
        with self._conn_lock:
            if self._conn is not None:
                self._conn.close()
                self._conn = None


def _host_port(rest: str, addr: str) -> tuple:
    host_port = rest.split("/", 1)[0]
    if ":" not in host_port:
        raise TransportError(f"address {addr!r} requires an explicit port")
    host, port_s = host_port.rsplit(":", 1)
    try:
        return host, int(port_s)
    except ValueError as exc:
        raise TransportError(f"bad port in {addr!r}") from exc


# Shared TLS plumbing for the two TLS-bearing schemes (tls+tcp and
# nng+tls+tcp). Contexts are fully configured — and their material errors
# raised — BEFORE the listener binds / the dialer connects, the ordering the
# reference pins (reference: tests/test_tls_transport.py:156-188). One home
# for TLS policy, so hardening (min version, ciphers, client certs) cannot
# drift between the schemes.

def _server_ssl_ctx(tls_config: Optional[object], addr: str,
                    scheme: str) -> ssl.SSLContext:
    if tls_config is None or not getattr(tls_config, "cert_key_file", None):
        raise TransportError(
            f"{scheme} listener {addr!r} requires tls_input.cert_key_file")
    ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    try:
        ssl_ctx.load_cert_chain(tls_config.cert_key_file)
    except (OSError, ssl.SSLError) as exc:
        raise TransportError(
            f"cannot load TLS cert/key {tls_config.cert_key_file}: {exc}") from exc
    return ssl_ctx


def _client_ssl_ctx(tls_config: Optional[object], addr: str, scheme: str,
                    host: str) -> tuple:
    if tls_config is None or not getattr(tls_config, "ca_file", None):
        raise TransportError(f"{scheme} output {addr!r} requires tls_output.ca_file")
    ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    try:
        ssl_ctx.load_verify_locations(tls_config.ca_file)
    except (OSError, ssl.SSLError) as exc:
        raise TransportError(f"cannot load TLS CA {tls_config.ca_file}: {exc}") from exc
    return ssl_ctx, getattr(tls_config, "server_name", None) or host


def _tls_server_wrap(ssl_ctx: ssl.SSLContext,
                     raw: _stdsocket.socket) -> ssl.SSLSocket:
    """Server-side TLS handshake with a bounded deadline. The accepted socket
    arrives blocking with NO timeout, and ``wrap_socket`` blocks in
    ``do_handshake`` waiting for a ClientHello — a peer that connects and
    sends nothing (port scanner, half-open connection) would wedge the single
    accept loop forever, a silent DoS on every later dialer. Same guard
    ``_sp_prepare`` applies to the SP header read; the accept loop sets the
    steady-state timeout right after ``prepare`` returns."""
    raw.settimeout(5.0)
    return ssl_ctx.wrap_socket(raw, server_side=True)


class TlsTcpSocketFactory:
    """tls+tcp:// factory: real ssl around the framework's 4-byte
    length-prefixed framing (for NNG-wire TLS interop see
    NngTlsTcpSocketFactory)."""

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "tls+tcp":
            raise TransportError(f"TlsTcpSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        ssl_ctx = _server_ssl_ctx(tls_config, addr, "tls+tcp")

        def prepare(raw: _stdsocket.socket, server_side: bool) -> _FramedConn:
            return _FramedConn(_tls_server_wrap(ssl_ctx, raw))

        return FramedTcpListener(host, port, prepare, logger, label="tls+tcp")

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "tls+tcp":
            raise TransportError(f"TlsTcpSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        ssl_ctx, server_name = _client_ssl_ctx(tls_config, addr, "tls+tcp", host)

        def prepare(raw: _stdsocket.socket, server_side: bool) -> _FramedConn:
            return _FramedConn(ssl_ctx.wrap_socket(raw, server_hostname=server_name))

        return FramedTcpDialer(host, port, prepare, logger, dial_timeout,
                               buffer_size, label="tls+tcp")


# ---------------------------------------------------------------------------
# nng+tcp backend: NNG/nanomsg SP wire protocol (Pair0 over TCP), so real
# NNG peers — e.g. a reference-style fluentd with fluent-plugin-nng
# (reference: container/Dockerfile_fluentd:5-9) — can dial this data plane
# without libnng on either linking path here.
#
# Wire format (nanomsg TCP mapping, which NNG's tcp transport speaks):
#   on connect, both peers send 8 bytes:  0x00 'S' 'P' 0x00  proto_be16  0x0000
#   (Pair0's protocol number is 16); a peer whose header disagrees is
#   rejected. After the handshake every message is
#   uint64_be length | payload.
# ---------------------------------------------------------------------------

SP_PAIR0_PROTO = 16
_SP_HDR = struct.Struct("!Q")  # u64 BE message length


def sp_handshake_bytes(proto: int = SP_PAIR0_PROTO) -> bytes:
    return b"\x00SP\x00" + struct.pack("!HH", proto, 0)


def _sp_prepare(raw: _stdsocket.socket, server_side: bool) -> _FramedConn:
    """Exchange and validate the SP protocol header (both directions —
    TCP is full duplex and NNG sends immediately on connect)."""
    raw.sendall(sp_handshake_bytes())
    saved = raw.gettimeout()
    raw.settimeout(5.0)  # a silent non-SP peer must not wedge the accept loop
    try:
        got = bytearray()
        while len(got) < 8:
            chunk = raw.recv(8 - len(got))
            if not chunk:
                raise TransportError("peer closed during SP handshake")
            got.extend(chunk)
    except OSError as exc:
        raise TransportError(f"SP handshake read failed: {exc}") from exc
    finally:
        raw.settimeout(saved)
    if bytes(got[:4]) != b"\x00SP\x00":
        raise TransportError(f"not an SP peer (header {bytes(got[:4])!r})")
    (proto, _reserved) = struct.unpack("!HH", bytes(got[4:]))
    if proto != SP_PAIR0_PROTO:
        raise TransportError(
            f"SP protocol mismatch: peer speaks {proto}, want Pair0 ({SP_PAIR0_PROTO})")
    return _FramedConn(raw, hdr=_SP_HDR)


# ---------------------------------------------------------------------------
# ws backend: RFC 6455 WebSocket, NNG dialect — one pipeline message per
# binary ws message, subprotocol "pair.sp.nanomsg.org" (what NNG's ws://
# transport speaks, reference: settings.py:31-37 lists ws among the NNG
# schemes). Implemented over the framed-TCP listener/dialer machinery with
# a ws "conn" in place of the length-prefix codec, so this build needs
# neither libzmq's compile-time ws option nor libnng.
# ---------------------------------------------------------------------------

_WS_GUID = b"258EAFA5-E914-47DA-95CA-C5AB0DC85B11"
_WS_SUBPROTO = "pair.sp.nanomsg.org"


def _ws_accept_key(key: str) -> str:
    import base64
    import hashlib

    return base64.b64encode(
        hashlib.sha1(key.encode() + _WS_GUID).digest()).decode()


def _ws_xor(data: bytes, mask: bytes) -> bytes:
    """Apply the RFC 6455 masking XOR. Data-plane hot path: every client→
    server byte passes through this, so it must NOT be a per-byte Python
    loop (1 interpreter op/byte ≈ seconds on a 64 MB frame). int.xor runs
    in C over the whole buffer."""
    n = len(data)
    if n == 0:
        return data
    full = mask * (n // 4) + mask[: n % 4]
    return (int.from_bytes(data, "little")
            ^ int.from_bytes(full, "little")).to_bytes(n, "little")


class _WsConn:
    """One established WebSocket connection: binary messages in/out, control
    frames handled inline (pong for ping, clean close). Duck-typed to the
    ``_FramedConn`` surface the framed listener/dialer use."""

    def __init__(self, sock: _stdsocket.socket, mask_outgoing: bool,
                 initial: bytes = b""):
        self.sock = sock
        self.send_lock = threading.Lock()
        self._mask = mask_outgoing            # RFC 6455: clients MUST mask
        # bytes the handshake read past the HTTP terminator (TCP may
        # coalesce the peer's first frame with its handshake): consumed
        # before any socket read, or the stream desyncs permanently
        self._buf = bytearray(initial)

    def send_frame(self, data: bytes) -> None:
        n = len(data)
        head = bytearray([0x82])              # FIN + binary opcode
        mask_bit = 0x80 if self._mask else 0
        if n < 126:
            head.append(mask_bit | n)
        elif n < 1 << 16:
            head.append(mask_bit | 126)
            head += struct.pack("!H", n)
        else:
            head.append(mask_bit | 127)
            head += struct.pack("!Q", n)
        if self._mask:
            mask = os.urandom(4)
            head += mask
            data = _ws_xor(data, mask)
        with self.send_lock:
            try:
                if isinstance(self.sock, ssl.SSLSocket):
                    self.sock.sendall(bytes(head) + data)
                else:
                    _send_with_progress(self.sock, bytes(head) + data)
            except _stdsocket.timeout as exc:
                self.close()  # partial frame on the wire → stream corrupt
                raise TransportError(
                    "ws send stalled (no progress for "
                    f"{_SEND_STALL_WINDOWS * _STEADY_TIMEOUT:.0f}s); "
                    "connection dropped") from exc

    def recv_frame(self) -> bytes:
        message = bytearray()
        while True:
            b0, b1 = self._recv_exact(2)
            fin, opcode = b0 & 0x80, b0 & 0x0F
            masked, length = b1 & 0x80, b1 & 0x7F
            if length == 126:
                (length,) = struct.unpack("!H", self._recv_exact(2))
            elif length == 127:
                (length,) = struct.unpack("!Q", self._recv_exact(8))
            if length > _MAX_FRAME:
                raise TransportError(f"oversized ws frame: {length} bytes")
            mask = self._recv_exact(4) if masked else None
            payload = self._recv_exact(length) if length else b""
            if mask:
                payload = _ws_xor(payload, mask)
            if opcode == 0x9:                 # ping → pong, keep reading
                self._send_control(0xA, payload)
                continue
            if opcode == 0xA:                 # unsolicited pong: ignore
                continue
            if opcode == 0x8:                 # close
                try:
                    self._send_control(0x8, payload[:2])
                except OSError:
                    pass
                raise ConnectionError("ws peer closed")
            if opcode in (0x1, 0x2, 0x0):     # text/binary/continuation
                # per-frame _MAX_FRAME alone does not bound the ASSEMBLED
                # message: a peer streaming FIN-less fragments could grow
                # it without limit (advisor r3 low — memory exhaustion)
                if len(message) + len(payload) > _MAX_FRAME:
                    raise TransportError(
                        f"oversized ws message: fragmented past {_MAX_FRAME} bytes")
                message += payload
                if fin:
                    return bytes(message)
                continue
            raise TransportError(f"unexpected ws opcode {opcode:#x}")

    def _send_control(self, opcode: int, payload: bytes) -> None:
        head = bytearray([0x80 | opcode])
        mask_bit = 0x80 if self._mask else 0
        head.append(mask_bit | len(payload))
        if self._mask:
            mask = os.urandom(4)
            head += mask
            payload = _ws_xor(payload, mask)
        with self.send_lock:
            try:
                self.sock.sendall(bytes(head) + payload)
            except _stdsocket.timeout as exc:
                self.close()
                raise TransportError("ws control send timed out") from exc

    def _recv_exact(self, n: int) -> bytes:
        buf = bytearray()
        if self._buf:
            take = self._buf[:n]
            del self._buf[:len(take)]
            buf.extend(take)
        while len(buf) < n:
            try:
                chunk = self.sock.recv(n - len(buf))
            except (_stdsocket.timeout, ssl.SSLWantReadError):
                continue  # idle tick, not an error: keep accumulating
            if not chunk:
                raise ConnectionError("peer closed")
            buf.extend(chunk)
        return bytes(buf)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


def _ws_server_prepare(raw: _stdsocket.socket, path: str) -> _WsConn:
    """Accept an HTTP Upgrade request and complete the ws handshake."""
    saved = raw.gettimeout()
    raw.settimeout(5.0)
    try:
        request = b""
        while b"\r\n\r\n" not in request:
            chunk = raw.recv(4096)
            if not chunk:
                raise TransportError("peer closed during ws handshake")
            request += chunk
            if len(request) > 64 * 1024:
                raise TransportError("oversized ws handshake request")
        # split at the terminator FIRST: TCP may coalesce the client's first
        # frame with the request, and those bytes are frame data, not header
        head, _, rest = request.partition(b"\r\n\r\n")
        headers = {}
        for line in head.split(b"\r\n")[1:]:
            if b":" in line:
                k, v = line.split(b":", 1)
                # latin-1 never raises; a peer sending garbage header bytes
                # must be rejected below, not kill the accept thread
                headers[k.strip().lower().decode("latin-1")] = (
                    v.strip().decode("latin-1"))
        key = headers.get("sec-websocket-key")
        if not key or "websocket" not in headers.get("upgrade", "").lower():
            raise TransportError("not a websocket upgrade request")
        offered = [p.strip() for p in
                   headers.get("sec-websocket-protocol", "").split(",") if p.strip()]
        try:
            accept = _ws_accept_key(key)
        except (ValueError, UnicodeEncodeError) as exc:
            raise TransportError(f"bad Sec-WebSocket-Key: {exc}") from exc
        lines = [
            "HTTP/1.1 101 Switching Protocols",
            "Upgrade: websocket",
            "Connection: Upgrade",
            f"Sec-WebSocket-Accept: {accept}",
        ]
        if _WS_SUBPROTO in offered:           # echo NNG's pair0 subprotocol
            lines.append(f"Sec-WebSocket-Protocol: {_WS_SUBPROTO}")
        raw.sendall(("\r\n".join(lines) + "\r\n\r\n").encode())
    finally:
        raw.settimeout(saved)
    return _WsConn(raw, mask_outgoing=False, initial=rest)


def _ws_client_prepare(raw: _stdsocket.socket, host: str, port: int,
                       path: str) -> _WsConn:
    """Send the HTTP Upgrade request and validate the 101 response."""
    import base64

    key = base64.b64encode(os.urandom(16)).decode()
    request = (
        f"GET {path or '/'} HTTP/1.1\r\n"
        f"Host: {host}:{port}\r\n"
        "Upgrade: websocket\r\n"
        "Connection: Upgrade\r\n"
        f"Sec-WebSocket-Key: {key}\r\n"
        "Sec-WebSocket-Version: 13\r\n"
        f"Sec-WebSocket-Protocol: {_WS_SUBPROTO}\r\n"
        "\r\n")
    saved = raw.gettimeout()
    raw.settimeout(5.0)
    try:
        raw.sendall(request.encode())
        response = b""
        while b"\r\n\r\n" not in response:
            chunk = raw.recv(4096)
            if not chunk:
                raise TransportError("peer closed during ws handshake")
            response += chunk
            if len(response) > 64 * 1024:
                raise TransportError("oversized ws handshake response")
    finally:
        raw.settimeout(saved)
    # bytes past the terminator are the server's first frame(s) — keep them
    head, _, rest = response.partition(b"\r\n\r\n")
    status = head.split(b"\r\n", 1)[0]
    if b"101" not in status:
        raise TransportError(f"ws upgrade refused: {status.decode(errors='replace')}")
    want = _ws_accept_key(key).encode()
    if want not in head:
        raise TransportError("ws handshake: bad Sec-WebSocket-Accept")
    return _WsConn(raw, mask_outgoing=True, initial=rest)


class WsSocketFactory:
    """ws:// factory: RFC 6455 over the framed listener/dialer machinery,
    independent of libzmq's compile-time ws option."""

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "ws":
            raise TransportError(f"WsSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        path = "/" + rest.split("/", 1)[1] if "/" in rest else "/"

        def prepare(raw: _stdsocket.socket, server_side: bool) -> _WsConn:
            return _ws_server_prepare(raw, path)

        return FramedTcpListener(host, port, prepare, logger, label="ws")

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "ws":
            raise TransportError(f"WsSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        path = "/" + rest.split("/", 1)[1] if "/" in rest else "/"

        def prepare(raw: _stdsocket.socket, server_side: bool) -> _WsConn:
            return _ws_client_prepare(raw, host, port, path)

        return FramedTcpDialer(host, port, prepare, logger, dial_timeout,
                               buffer_size, label="ws")


class NngTlsTcpSocketFactory:
    """nng+tls+tcp:// factory: SP Pair0 wire protocol INSIDE a real TLS
    stream — byte-compatible with NNG's ``tls+tcp`` transport (mbedTLS under
    libnng), which is how the reference's encrypted deployments speak on the
    wire (reference: src/service/features/engine_socket.py:60-71 server-side
    TLSConfig applied before listen; engine.py:165-170 client CA config).
    NNG's TLS transport completes the TLS handshake first and then runs the
    same 8-byte SP header exchange and u64-be length framing inside the
    session, so composing the ssl wrap with ``_sp_prepare`` reproduces the
    wire exactly. The plain-``tls+tcp://`` scheme here remains the
    framework-private 4-byte framing; THIS scheme is the one a genuine
    NNG/fluentd peer can dial encrypted.

    Ordering contract preserved: the TLS context is fully configured before
    the listener binds / the dialer connects (reference:
    tests/test_tls_transport.py:156-188)."""

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "nng+tls+tcp":
            raise TransportError(f"NngTlsTcpSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        ssl_ctx = _server_ssl_ctx(tls_config, addr, "nng+tls+tcp")

        def prepare(raw: _stdsocket.socket, server_side: bool) -> _FramedConn:
            # TLS first, then the SP header exchange inside the session —
            # NNG's layering (its tls+tcp transport wraps the SP stream)
            return _sp_prepare(_tls_server_wrap(ssl_ctx, raw), True)

        return FramedTcpListener(host, port, prepare, logger, label="nng+tls+tcp")

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "nng+tls+tcp":
            raise TransportError(f"NngTlsTcpSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        ssl_ctx, server_name = _client_ssl_ctx(tls_config, addr, "nng+tls+tcp", host)

        def prepare(raw: _stdsocket.socket, server_side: bool) -> _FramedConn:
            return _sp_prepare(
                ssl_ctx.wrap_socket(raw, server_hostname=server_name), False)

        return FramedTcpDialer(host, port, prepare, logger, dial_timeout,
                               buffer_size, label="nng+tls+tcp")


class NngTcpSocketFactory:
    """nng+tcp:// factory: SP Pair0 wire compatibility over plain TCP."""

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "nng+tcp":
            raise TransportError(f"NngTcpSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        return FramedTcpListener(host, port, _sp_prepare, logger, label="nng+tcp")

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        logger = logger or logging.getLogger(__name__)
        scheme, rest = _split_scheme(addr)
        if scheme != "nng+tcp":
            raise TransportError(f"NngTcpSocketFactory cannot handle scheme {scheme!r}")
        host, port = _host_port(rest, addr)
        return FramedTcpDialer(host, port, _sp_prepare, logger, dial_timeout,
                               buffer_size, label="nng+tcp")


# ---------------------------------------------------------------------------
# in-process queue backend (test seam; also used by the process-free demo)
# ---------------------------------------------------------------------------

class _QueuePair:
    def __init__(self, maxsize: int = 1024):
        self.a_to_b: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self.b_to_a: "queue.Queue" = queue.Queue(maxsize=maxsize)


_inproc_registry: Dict[str, _QueuePair] = {}
_inproc_lock = threading.Lock()


class InprocQueueSocket:
    def __init__(self, addr: str, rq: "queue.Queue", sq: "queue.Queue"):
        self._addr = addr
        self._rq, self._sq = rq, sq
        self._closed = False
        self._recv_timeout: Optional[int] = None

    @property
    def recv_timeout(self) -> Optional[int]:
        return self._recv_timeout

    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None:
        self._recv_timeout = ms

    def recv(self) -> bytes:
        if self._closed:
            raise TransportClosed(f"recv on closed {self._addr}")
        timeout = None if self._recv_timeout is None else self._recv_timeout / 1000.0
        try:
            return self._rq.get(timeout=timeout)
        except queue.Empty:
            raise TransportTimeout("recv timeout")

    def send(self, data: bytes, block: bool = True) -> None:
        if self._closed:
            raise TransportClosed(f"send on closed {self._addr}")
        try:
            self._sq.put(data, block=block)
        except queue.Full:
            raise TransportAgain("send queue full")

    def close(self) -> None:
        self._closed = True


class MergedIngressSocket:
    """N listener shards draining into ONE engine loop (the multi-ingress
    regime of docs/benchmarks.md): each shard is an independent listening
    socket — its own fd, its own kernel buffer, its own sender — and the
    merge happens here at recv time, so a single dispatch loop (and a
    single device pipeline behind it) aggregates what N single-ingress
    pipes deliver.

    Fairness: recv rotates the starting shard; recv_many (exposed only when
    every shard supports it, i.e. the native transport) takes the first
    burst from whichever shard produces one, then drains the OTHER shards
    non-blockingly into the same batch — one GIL crossing per shard per
    call, bursts stay aggregated. Replies (send) go to the shard the last
    message arrived on; reply mode across shards keeps per-shard 1:1
    semantics."""

    def __init__(self, socks: List[EngineSocket]):
        if not socks:
            raise TransportError("MergedIngressSocket needs >= 1 shard")
        self._socks = list(socks)
        self._idx = 0
        self._last: EngineSocket = self._socks[0]
        self._recv_timeout: Optional[int] = None
        if all(callable(getattr(s, "recv_many", None)) for s in self._socks):
            self.recv_many = self._recv_many  # engine capability probe

    @property
    def recv_timeout(self) -> Optional[int]:
        return self._recv_timeout

    @recv_timeout.setter
    def recv_timeout(self, ms: Optional[int]) -> None:
        self._recv_timeout = ms
        # per-shard slice of the poll budget (recv walks all shards); an
        # unbounded merged recv still polls shards on a finite slice — a
        # blocking recv on shard 0 would starve the others
        share = 100 if ms is None else max(1, ms // len(self._socks))
        for s in self._socks:
            s.recv_timeout = share

    def recv(self) -> bytes:
        k = len(self._socks)
        # one full rotation covers the whole configured timeout (each shard
        # holds a 1/k slice); an infinite timeout loops rotations forever
        while True:
            for i in range(k):
                sock = self._socks[(self._idx + i) % k]
                try:
                    data = sock.recv()
                except TransportTimeout:
                    continue
                self._idx = (self._idx + i + 1) % k
                self._last = sock
                return data
            if self._recv_timeout is not None:
                raise TransportTimeout("recv timeout (all shards idle)")

    def _recv_many(self, max_n: int, first_timeout_ms: int) -> List[bytes]:
        k = len(self._socks)
        frames: List[bytes] = []
        share = max(1, first_timeout_ms // k)
        for i in range(k):
            sock = self._socks[(self._idx + i) % k]
            try:
                got = sock.recv_many(max_n - len(frames),
                                     share if not frames else 1)
            except TransportTimeout:
                # an idle shard must not discard what other shards already
                # delivered — empty is a per-shard non-event here
                continue
            if got:
                self._last = sock
                frames.extend(got)
            if len(frames) >= max_n:
                break
        self._idx = (self._idx + 1) % k
        return frames

    @property
    def peer_count(self) -> int:
        """Reply destinations across all shards: shards with their own
        peer accounting report it; a plain pair shard counts as one."""
        return sum(getattr(s, "peer_count", 1) for s in self._socks)

    @property
    def last_origin(self):
        """Reply token: (shard, shard-level origin). Exact per-message reply
        routing composes across the merge — the engine captures this per
        recv'd frame and ``send_to`` unwraps it, so micro-batches that mix
        shards still reply to the right shard (and, on fan-in listeners,
        the right connection)."""
        return (self._last, getattr(self._last, "last_origin", None))

    def send_to(self, origin, data: bytes, block: bool = True) -> None:
        sock, inner = origin
        if inner is not None and callable(getattr(sock, "send_to", None)):
            sock.send_to(inner, data, block=block)
        else:
            sock.send(data, block=block)

    def send(self, data: bytes, block: bool = True) -> None:
        self._last.send(data, block=block)

    def close(self) -> None:
        for s in self._socks:
            try:
                s.close()
            except TransportError:
                pass


def make_socket_factory(backend: str = "auto",
                        logger: Optional[logging.Logger] = None) -> EngineSocketFactory:
    """Resolve a transport backend name to a factory.

    ``native`` = the in-tree C++ transport (raises if it cannot be built),
    ``zmq`` = the Python backend, ``auto`` = native when available else zmq.
    Native and zmq frames are wire-compatible, so a pipeline can mix them.
    """
    if backend in ("auto", "native"):
        try:
            from .native_transport import NativePairSocketFactory

            return NativePairSocketFactory()
        except (ImportError, OSError) as exc:
            if backend == "native":
                raise TransportError(f"native transport unavailable: {exc}")
            if logger:
                logger.debug("native transport unavailable (%s); using zmq", exc)
    return ZmqPairSocketFactory()


class InprocQueueSocketFactory:
    """Queue-based factory for tests and single-process demos."""

    def __init__(self, maxsize: int = 1024):
        self._maxsize = maxsize

    def _pair(self, addr: str) -> _QueuePair:
        with _inproc_lock:
            pair = _inproc_registry.get(addr)
            if pair is None:
                pair = _QueuePair(self._maxsize)
                _inproc_registry[addr] = pair
            return pair

    def create(self, addr: str, logger: Optional[logging.Logger] = None,
               tls_config: Optional[object] = None) -> EngineSocket:
        pair = self._pair(addr)
        return InprocQueueSocket(addr, rq=pair.a_to_b, sq=pair.b_to_a)

    def create_output(self, addr: str, logger: Optional[logging.Logger] = None,
                      tls_config: Optional[object] = None,
                      dial_timeout: Optional[int] = None,
                      buffer_size: int = 100) -> EngineSocket:
        pair = self._pair(addr)
        return InprocQueueSocket(addr, rq=pair.b_to_a, sq=pair.a_to_b)
