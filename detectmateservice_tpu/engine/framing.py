"""Batch frames: many serialized messages in one wire frame.

The per-message socket cost (zmq enqueue + GIL crossing + syscall amortization)
caps a single Python sender at ~80k sends/s (measured via
scripts/bench_service.py) — below a batched detector's rate. Packing K messages per frame amortizes that cost K-fold on
both ends; this is SURVEY.md §7 hard part #3 ("batch *frames* before
crossing into Python") applied to the whole service mesh, not just ingest.

Wire format (version 1):

    0xD7 'D' 'M' 0x01 | varint n | n × (varint len | len bytes)

The first byte 0xD7 decodes as protobuf field 26 / wire type 7 — wire type 7
does not exist, so no valid protobuf message (all pipeline schemas are
protobuf) can begin with it: receivers can safely auto-detect batch frames
and stay wire-compatible with single-message peers. Senders only emit batch
frames when ``engine_frame_batch > 1`` is configured, so interop with
reference-style peers is the default.

Wire format (version 2, traced frames — opt-in via ``engine_trace``):

    0xD7 'D' 'M' 0x02 | varint trace_len | trace block | payload

``payload`` is a complete v1 wire unit — either a v1 batch frame or a plain
single message — so downgrading a v2 frame for a v1-only peer is a slice:
everything after the trace block, byte-identical to what an untraced sender
would have emitted. The trace block:

    trace_id (8 bytes) | varint ingest_ns | varint n_hops
    | n_hops × (varint name_len | name utf-8 | varint recv_ns | varint send_ns)

Timestamps are ``time.time_ns()`` epoch nanoseconds — comparable across the
processes of one pipeline host (and across NTP-synced hosts to clock-sync
precision). The length prefix exists for damage containment: a garbled trace
block is skipped by its declared length and the payload messages survive
(the error is counted); only a declared length running past the frame end
loses the frame.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import List, NamedTuple, Optional, Tuple

MAGIC = b"\xd7DM\x01"
MAGIC_V2 = b"\xd7DM\x02"
# Zero-copy shm reference frame (v2 format family, PR 7): instead of payload
# bytes, the frame carries a (segment name, slot, gen, offset, length)
# reference into a shared-memory segment owned by the SENDING engine
# (engine/shm.py). The referenced payload is a complete v1/v2 wire unit —
# byte-identical to what a copy-mode sender would have put on the wire — so
# resolving a shm frame and receiving a plain frame are indistinguishable
# downstream. Senders only emit these on colocated links (ipc/inproc peers
# with ``zero_copy_framing`` enabled) and copy-downgrade everywhere else.
MAGIC_SHM = b"\xd7DM\x03"
# Tenant-attributed frame (v2 format family, dmshed): the OUTERMOST wrapper —
# a tenant id rides in front of whatever the sender emits (a v2 traced frame,
# a v1 batch frame, or a plain single message), so ingress admission control
# can attribute and shed a frame from its first bytes without touching the
# trace block or payload. Stripping it for a tenant-unaware peer is a slice
# (everything after the block), the same clean-downgrade contract v2 has:
#
#     0xD7 'D' 'M' 0x04 | varint id_len | tenant id utf-8 | payload
MAGIC_TEN = b"\xd7DM\x04"
# Span frame (dmtel): a batch of completed hop spans shipped from an engine's
# telemetry sender thread to the collector (telemetry/collector.py). Spans are
# operator-facing telemetry, not pipeline payload — they never mix with data
# frames on a data link and the collector is their only receiver — so the body
# is JSON (a list of span dicts, docs/transport.md "span wire format") rather
# than a packed binary block: the encode cost is paid on the sender THREAD,
# off the hot loop, and debuggability of the telemetry channel itself wins:
#
#     0xD7 'D' 'M' 0x05 | varint body_len | span JSON utf-8
MAGIC_SPAN = b"\xd7DM\x05"


class FramingError(ValueError):
    """A frame carried the batch magic but its body was malformed."""


def _put_varint(out: bytearray, value: int) -> None:
    while True:
        bits = value & 0x7F
        value >>= 7
        if value:
            out.append(bits | 0x80)
        else:
            out.append(bits)
            return


def _get_varint(data: bytes, pos: int) -> tuple:
    result = 0
    shift = 0
    while True:
        if pos >= len(data):
            raise FramingError("truncated varint in batch frame")
        byte = data[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise FramingError("varint overflow in batch frame")


def pack_batch(messages: List[bytes]) -> bytes:
    """Pack serialized messages into one batch frame."""
    out = bytearray(MAGIC)
    _put_varint(out, len(messages))
    for msg in messages:
        _put_varint(out, len(msg))
        out += msg
    return bytes(out)


def frame_msg_count(data: bytes) -> int:
    """Cheap message-count estimate for burst sizing: the header varint of a
    batch frame, 1 for a single message, 0 for an empty/garbled header.
    v2 (traced) frames are counted by their payload. Does NOT validate the
    body — use ``unpack_batch`` (or the native kernel's count pass) for
    that."""
    if not data:
        return 0
    if data.startswith(MAGIC_TEN):
        try:
            id_len, pos = _get_varint(data, len(MAGIC_TEN))
        except FramingError:
            return 0
        start = pos + id_len
        if start > len(data):
            return 0
        return frame_msg_count(data[start:])
    if data.startswith(MAGIC_V2):
        try:
            trace_len, pos = _get_varint(data, len(MAGIC_V2))
        except FramingError:
            return 0
        start = pos + trace_len
        if start > len(data):
            return 0
        return frame_msg_count(data[start:])
    if not data.startswith(MAGIC):
        return 1
    try:
        count, _ = _get_varint(data, len(MAGIC))
    except FramingError:
        return 0
    return count


# -- shm reference frames (zero-copy framing) --------------------------------


class ShmRef(NamedTuple):
    """A shared-memory payload reference: which segment, which slot (and its
    publish generation, so a stale ref is detected instead of reading a
    recycled slot), and the payload's byte range within the segment."""

    name: str        # segment path, or "@inproc:<pid>:<id>" for the
                     # in-process object registry (true zero-copy)
    slot: int
    gen: int
    offset: int
    length: int


def pack_shm_ref(ref: ShmRef) -> bytes:
    """ShmRef → wire frame:
    ``MAGIC_SHM | varint name_len | name | varint slot | varint gen
    | varint offset | varint length``."""
    out = bytearray(MAGIC_SHM)
    name = ref.name.encode("utf-8")
    _put_varint(out, len(name))
    out += name
    _put_varint(out, ref.slot)
    _put_varint(out, ref.gen)
    _put_varint(out, ref.offset)
    _put_varint(out, ref.length)
    return bytes(out)


def unpack_shm_ref(data: bytes) -> ShmRef:
    """Wire frame → ShmRef; raises FramingError on a garbled reference (the
    payload itself is unreachable then — unlike a garbled v2 trace block,
    there is nothing to salvage)."""
    if not data.startswith(MAGIC_SHM):
        raise FramingError("not a shm reference frame")
    name_len, pos = _get_varint(data, len(MAGIC_SHM))
    end = pos + name_len
    if end > len(data):
        raise FramingError("truncated segment name in shm reference")
    try:
        name = data[pos:end].decode("utf-8")
    except UnicodeDecodeError as exc:
        raise FramingError(f"non-UTF-8 segment name in shm reference: {exc}")
    slot, pos = _get_varint(data, end)
    gen, pos = _get_varint(data, pos)
    offset, pos = _get_varint(data, pos)
    length, pos = _get_varint(data, pos)
    if pos != len(data):
        raise FramingError("trailing bytes after shm reference")
    return ShmRef(name, slot, gen, offset, length)


# -- trace context (v2 frames) ----------------------------------------------

# trace-id stream: one getrandom() at import, then a counter (GIL-atomic
# ``next``) — collision-safe within a process by construction, across
# processes by the 64-bit random base
_TRACE_ID_BASE = int.from_bytes(os.urandom(8), "big")
_TRACE_ID_SEQ = itertools.count()


class Hop(NamedTuple):
    """One stage transit record: when the frame entered and left the stage."""

    stage: str
    recv_ns: int
    send_ns: int


class TraceContext:
    """Per-frame trace state threaded through the wire (v2 trace block)."""

    __slots__ = ("trace_id", "ingest_ns", "hops")

    def __init__(self, trace_id: int, ingest_ns: int,
                 hops: Optional[List[Hop]] = None) -> None:
        self.trace_id = trace_id
        self.ingest_ns = ingest_ns
        self.hops: List[Hop] = hops if hops is not None else []

    @classmethod
    def new(cls, ingest_ns: int) -> "TraceContext":
        # random 64-bit base + per-process counter, not os.urandom per
        # trace: id generation sits on the per-frame ingest path and a
        # getrandom(2) syscall there costs more than the whole hop stamp
        return cls((_TRACE_ID_BASE + next(_TRACE_ID_SEQ))
                   & 0xFFFFFFFFFFFFFFFF, ingest_ns)

    def __eq__(self, other) -> bool:
        return (isinstance(other, TraceContext)
                and self.trace_id == other.trace_id
                and self.ingest_ns == other.ingest_ns
                and self.hops == other.hops)

    def __repr__(self) -> str:
        return (f"TraceContext({self.trace_id:#018x}, ingest={self.ingest_ns},"
                f" hops={self.hops!r})")


def pack_trace_block(ctx: TraceContext) -> bytes:
    out = bytearray(ctx.trace_id.to_bytes(8, "big"))
    _put_varint(out, ctx.ingest_ns)
    _put_varint(out, len(ctx.hops))
    for hop in ctx.hops:
        name = hop.stage.encode("utf-8")
        _put_varint(out, len(name))
        out += name
        _put_varint(out, hop.recv_ns)
        _put_varint(out, hop.send_ns)
    return bytes(out)


def parse_trace_block(block: bytes) -> TraceContext:
    """Trace block bytes → TraceContext; raises FramingError on damage."""
    if len(block) < 8:
        raise FramingError("trace block shorter than the 8-byte trace id")
    trace_id = int.from_bytes(block[:8], "big")
    ingest_ns, pos = _get_varint(block, 8)
    n_hops, pos = _get_varint(block, pos)
    hops: List[Hop] = []
    for _ in range(n_hops):
        name_len, pos = _get_varint(block, pos)
        end = pos + name_len
        if end > len(block):
            raise FramingError("truncated hop name in trace block")
        try:
            stage = block[pos:end].decode("utf-8")
        except UnicodeDecodeError as exc:
            raise FramingError(f"non-UTF-8 hop name in trace block: {exc}")
        pos = end
        recv_ns, pos = _get_varint(block, pos)
        send_ns, pos = _get_varint(block, pos)
        hops.append(Hop(stage, recv_ns, send_ns))
    if pos != len(block):
        raise FramingError("trailing bytes after trace block hops")
    return TraceContext(trace_id, ingest_ns, hops)


def wrap_trace(payload: bytes, ctx: TraceContext) -> bytes:
    """Payload (a v1 batch frame or a plain single message) → v2 frame."""
    block = pack_trace_block(ctx)
    out = bytearray(MAGIC_V2)
    _put_varint(out, len(block))
    out += block
    out += payload
    return bytes(out)


def unwrap_trace(data: bytes) -> Tuple[bytes, Optional[TraceContext], bool]:
    """v2 frame → ``(payload, trace, trace_damaged)``.

    Non-v2 input passes through as ``(data, None, False)``. A v2 frame whose
    trace block is internally garbled still yields its payload — the block is
    skipped by its declared length and ``trace_damaged`` is True so the
    caller can count a framing error without dropping the payload messages.
    Only a declared trace length running past the frame end (no payload can
    exist) raises FramingError."""
    if not data.startswith(MAGIC_V2):
        return data, None, False
    trace_len, pos = _get_varint(data, len(MAGIC_V2))
    start = pos + trace_len
    if start > len(data):
        raise FramingError("trace block length exceeds frame size")
    try:
        ctx = parse_trace_block(data[pos:start])
    except FramingError:
        return data[start:], None, True
    return data[start:], ctx, False


def peek_trace_id(data: bytes) -> Optional[int]:
    """The trace id of a v2 frame WITHOUT parsing the hop records — the
    router's sticky_trace policy runs this per dispatched frame, so it reads
    exactly one varint and eight bytes (plus one varint skip when a tenant
    block rides in front). None for non-v2 frames and for frames whose
    declared trace block cannot hold an id."""
    if data.startswith(MAGIC_TEN):
        try:
            id_len, pos = _get_varint(data, len(MAGIC_TEN))
        except FramingError:
            return None
        start = pos + id_len
        if start > len(data):
            return None
        data = data[start:]
    if not data.startswith(MAGIC_V2):
        return None
    try:
        trace_len, pos = _get_varint(data, len(MAGIC_V2))
    except FramingError:
        return None
    if trace_len < 8 or pos + 8 > len(data):
        return None
    return int.from_bytes(data[pos:pos + 8], "big")


# -- tenant attribution (dmshed frames) --------------------------------------


def wrap_tenant(payload: bytes, tenant: str) -> bytes:
    """Payload (any complete wire unit: v2 traced frame, v1 batch frame, or
    a plain single message) → tenant-attributed frame. The tenant block is
    always the OUTERMOST wrapper; senders stamp it last."""
    out = bytearray(MAGIC_TEN)
    name = tenant.encode("utf-8")
    _put_varint(out, len(name))
    out += name
    out += payload
    return bytes(out)


def unwrap_tenant(data: bytes) -> Tuple[bytes, Optional[str], bool]:
    """Tenant frame → ``(payload, tenant, tenant_damaged)``.

    Non-tenant input passes through as ``(data, None, False)``. A tenant
    block whose id bytes are not valid UTF-8 still yields its payload — the
    block is skipped by its declared length and ``tenant_damaged`` is True
    so the caller can count the damage (and admit under the default quota)
    without dropping the payload messages. Only a declared id length
    running past the frame end (no payload can exist) raises
    FramingError."""
    if not data.startswith(MAGIC_TEN):
        return data, None, False
    id_len, pos = _get_varint(data, len(MAGIC_TEN))
    start = pos + id_len
    if start > len(data):
        raise FramingError("tenant id length exceeds frame size")
    try:
        tenant = data[pos:start].decode("utf-8")
    except UnicodeDecodeError:
        return data[start:], None, True
    return data[start:], tenant, False


def peek_tenant_id(data: bytes) -> Optional[str]:
    """The tenant id of a tenant-attributed frame WITHOUT touching the
    payload — admission control runs this per ingress frame, so it reads
    exactly one varint and the id bytes. None for frames with no tenant
    block or an undecodable id."""
    if not data.startswith(MAGIC_TEN):
        return None
    try:
        id_len, pos = _get_varint(data, len(MAGIC_TEN))
    except FramingError:
        return None
    start = pos + id_len
    if start > len(data):
        return None
    try:
        return data[pos:start].decode("utf-8")
    except UnicodeDecodeError:
        return None


# -- span frames (dmtel telemetry channel) -----------------------------------


def pack_spans(spans: List[dict]) -> bytes:
    """Span dicts → one span frame for the telemetry channel. Runs on the
    exporter's sender thread (telemetry/spans.py), never the hot loop."""
    body = json.dumps(spans, separators=(",", ":")).encode("utf-8")
    out = bytearray(MAGIC_SPAN)
    _put_varint(out, len(body))
    out += body
    return bytes(out)


def unpack_spans(data: bytes) -> Optional[List[dict]]:
    """Span frame → span dicts; None when ``data`` is not a span frame.
    Raises FramingError on a garbled body — unlike a damaged v2 trace block
    there is no payload to salvage behind it, the frame IS the telemetry."""
    if not data.startswith(MAGIC_SPAN):
        return None
    body_len, pos = _get_varint(data, len(MAGIC_SPAN))
    end = pos + body_len
    if end > len(data):
        raise FramingError("span body length exceeds frame size")
    if end != len(data):
        raise FramingError("trailing bytes after span frame body")
    try:
        spans = json.loads(data[pos:end].decode("utf-8"))
    except (UnicodeDecodeError, ValueError) as exc:
        raise FramingError(f"undecodable span frame body: {exc}")
    if not isinstance(spans, list):
        raise FramingError("span frame body is not a JSON list")
    return spans


def unpack_batch(data: bytes) -> Optional[List[bytes]]:
    """Batch frame → messages; None when ``data`` is a plain single message
    (no magic). Raises FramingError on a corrupt batch body."""
    if not data.startswith(MAGIC):
        return None
    count, pos = _get_varint(data, len(MAGIC))
    messages: List[bytes] = []
    for _ in range(count):
        length, pos = _get_varint(data, pos)
        end = pos + length
        if end > len(data):
            raise FramingError("truncated message in batch frame")
        messages.append(data[pos:end])
        pos = end
    if pos != len(data):
        raise FramingError("trailing bytes after batch frame body")
    return messages
