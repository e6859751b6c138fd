"""Wire framing.

Parity: orpc/src/message/rpc_message.rs — same shape as orpc's
``[total_len][header_len][header][data]`` frame with a small fixed metadata
block (version, code, req_id, status, flags). Control payloads are msgpack;
block data rides in ``data`` untouched (zero-copy: encode emits the caller's
buffer without copying; decode returns a memoryview slice)."""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Any

import msgpack

from curvine_tpu.common.errors import CurvineError, ErrorCode
from curvine_tpu.rpc.deadline import DEADLINE_KEY, Deadline  # noqa: F401
# DEADLINE_KEY: reserved header field carrying the request's remaining
# time budget in ms (rpc/deadline.py); restamped (decremented) per hop.
from curvine_tpu.obs.trace import TRACE_KEY, SpanCtx  # noqa: F401
# TRACE_KEY: reserved header field carrying the caller's trace context
# [trace_id, span_id, sampled] (obs/trace.py); rides the same rail as
# the deadline and is re-stamped with the local span id per hop.

# SRV_KEY: reserved REPLY header field carrying the server's own time
# for the request, [queue_us, handle_us]: frame parsed → handler start,
# handler start → reply built. The receiving Connection pops it before
# the caller sees the header and hands it on as ``Message.srv``.
SRV_KEY = "srv"

VERSION = 1
# fixed metadata after the u32 frame length:
#   u8 version | u16 code | u64 req_id | u8 status | u8 flags | u32 header_len
_FIXED = struct.Struct(">BHQBBI")
FIXED_LEN = _FIXED.size
LEN_PREFIX = struct.Struct(">I")
MAX_FRAME = 64 * 1024 * 1024 + 1024  # one chunk + slack

STATUS_OK = 0
STATUS_ERROR = 1


class Flags:
    REQUEST = 0
    RESPONSE = 1 << 0
    CHUNK = 1 << 1   # intermediate streaming frame
    EOF = 1 << 2     # final streaming frame


@dataclass
class Message:
    code: int = 0
    req_id: int = 0
    status: int = STATUS_OK
    flags: int = Flags.REQUEST
    header: dict = field(default_factory=dict)
    data: bytes | bytearray | memoryview = b""
    # server-side: the parsed deadline budget (set once at dispatch from
    # the DEADLINE_KEY header field; never serialized)
    deadline: "Deadline | None" = None
    # server-side: the caller's trace context (set once at dispatch from
    # the TRACE_KEY header field; never serialized)
    trace: "SpanCtx | None" = None
    # server-side: perf_counter() when the request frame was parsed off
    # the wire (0.0 = not stamped; never serialized)
    parsed: float = 0.0
    # client-side: the server's [queue_us, handle_us] for this reply,
    # popped off the header (None from an older or native peer)
    srv: "list | None" = None

    @property
    def is_response(self) -> bool:
        return bool(self.flags & Flags.RESPONSE)

    @property
    def is_chunk(self) -> bool:
        return bool(self.flags & Flags.CHUNK)

    @property
    def is_eof(self) -> bool:
        return bool(self.flags & Flags.EOF)

    def budget(self) -> "Deadline | None":
        """The caller-propagated deadline budget, restarted on this
        process's monotonic clock; None when the request carries none.
        Server dispatch calls this once and caches it on the message
        (``msg.deadline``) so handlers share one expiry point."""
        return Deadline.from_header(self.header)

    def trace_ctx(self) -> "SpanCtx | None":
        """The caller's trace context, if the request carries one."""
        return SpanCtx.from_header(self.header)

    def srv_seconds(self) -> "tuple[float, float] | None":
        """(queue_s, handle_s) the server spent on this reply's request,
        or None where the peer sent none (or something else)."""
        try:
            queue_us, handle_us = self.srv
            return queue_us / 1e6, handle_us / 1e6
        except (TypeError, ValueError):
            return None

    def check(self) -> "Message":
        """Raise the carried remote error, if any."""
        if self.status != STATUS_OK:
            code = self.header.get("error_code", ErrorCode.UNDEFINED)
            e = CurvineError.from_wire(code, self.header.get("error", ""))
            ra = self.header.get("retry_after_ms")
            if ra is not None:
                # server-supplied backoff hint (THROTTLED): the retry
                # policy prefers it over its own exponential backoff
                e.retry_after_ms = int(ra)
            hint = self.header.get("leader_hint")
            if hint:
                # NOT_LEADER redirect: where the current leader lives
                e.leader_hint = str(hint)
            members = self.header.get("members")
            if members:
                e.members = list(members)
            raise e
        return self

    def encode(self) -> list[bytes | memoryview]:
        """Returns buffers to write, data buffer passed through uncopied."""
        hdr = msgpack.packb(self.header, use_bin_type=True) if self.header else b""
        total = FIXED_LEN + len(hdr) + len(self.data)
        prefix = LEN_PREFIX.pack(total) + _FIXED.pack(
            VERSION, self.code, self.req_id, self.status, self.flags, len(hdr)
        )
        out: list[bytes | memoryview] = [prefix]
        if hdr:
            out.append(hdr)
        if len(self.data):
            out.append(self.data)
        return out

    def encode_into(self, out: bytearray, inline_max: int = 0,
                    ) -> "bytes | bytearray | memoryview | None":
        """Append this frame to ``out`` (the coalesced-writer batch
        path: many small frames flattened into one buffer → one send).
        A data payload longer than ``inline_max`` is NOT copied — it is
        returned for the caller to emit as its own iovec entry right
        after ``out``'s bytes; smaller payloads are flattened into
        ``out`` and None is returned."""
        hdr = msgpack.packb(self.header, use_bin_type=True) if self.header else b""
        total = FIXED_LEN + len(hdr) + len(self.data)
        out += LEN_PREFIX.pack(total)
        out += _FIXED.pack(VERSION, self.code, self.req_id, self.status,
                           self.flags, len(hdr))
        if hdr:
            out += hdr
        if not len(self.data):
            return None
        if len(self.data) <= inline_max:
            out += self.data
            return None
        return self.data

    @staticmethod
    def decode(payload: memoryview) -> "Message":
        """Decode one frame body (without the u32 length prefix)."""
        version, code, req_id, status, flags, hdr_len = _FIXED.unpack_from(payload, 0)
        if version != VERSION:
            raise CurvineError(f"unsupported frame version {version}",
                               code=ErrorCode.ABNORMAL_DATA)
        off = FIXED_LEN
        header: dict = {}
        if hdr_len:
            header = msgpack.unpackb(payload[off:off + hdr_len], raw=False, strict_map_key=False)
            off += hdr_len
        data = payload[off:]
        return Message(code=code, req_id=req_id, status=status, flags=flags,
                       header=header, data=data)


def response_for(req: Message, header: dict | None = None,
                 data: bytes | memoryview = b"",
                 flags: int = Flags.RESPONSE) -> Message:
    return Message(code=req.code, req_id=req.req_id, status=STATUS_OK,
                   flags=flags, header=header or {}, data=data)


def error_for(req: Message, err: Exception) -> Message:
    if isinstance(err, CurvineError):
        code, msg = int(err.code), str(err)
    else:
        code, msg = int(ErrorCode.IO), f"{type(err).__name__}: {err}"
    header = {"error_code": code, "error": msg}
    ra = getattr(err, "retry_after_ms", None)
    if ra is not None:
        header["retry_after_ms"] = int(ra)
    hint = getattr(err, "leader_hint", None)
    if hint:
        header["leader_hint"] = str(hint)
    members = getattr(err, "members", None)
    if members:
        header["members"] = list(members)
    return Message(code=req.code, req_id=req.req_id, status=STATUS_ERROR,
                   flags=Flags.RESPONSE | Flags.EOF, header=header)


def pack(obj: Any) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def unpack(buf: bytes | memoryview) -> Any:
    return msgpack.unpackb(buf, raw=False, strict_map_key=False) if len(buf) else None


ENVELOPE_MAX = 4 + FIXED_LEN  # bytes needed before hdr_len is known


def decode_envelope(buf, pos: int, limit: int,
                    ) -> "tuple[int, int, int, int, int, dict, int] | None":
    """Batch decode: one frame *envelope* (length prefix + fixed block +
    msgpack header) out of ``buf[pos:limit]``, leaving the data payload
    unread. Returns ``(end, code, req_id, status, flags, header,
    data_len)`` with ``end`` = the first payload byte's offset, or None
    when the envelope isn't fully buffered yet. This is the single
    framing parser shared by both peers (client read loop and server
    conn loop drive it through ``transport.BulkDecoder``); validation
    errors raise CurvineError before any state is consumed."""
    avail = limit - pos
    if avail < 4:
        return None
    (total,) = LEN_PREFIX.unpack_from(buf, pos)
    if total > MAX_FRAME or total < FIXED_LEN:
        raise CurvineError(f"bad frame length {total}",
                           code=ErrorCode.ABNORMAL_DATA)
    if avail < ENVELOPE_MAX:
        return None
    version, code, req_id, status, flags, hdr_len = \
        _FIXED.unpack_from(buf, pos + 4)
    if version != VERSION:
        raise CurvineError(f"unsupported frame version {version}",
                           code=ErrorCode.ABNORMAL_DATA)
    if FIXED_LEN + hdr_len > total:
        raise CurvineError(f"bad header length {hdr_len}",
                           code=ErrorCode.ABNORMAL_DATA)
    end = pos + ENVELOPE_MAX + hdr_len
    if limit < end:
        return None
    header: dict = {}
    if hdr_len:
        header = msgpack.unpackb(memoryview(buf)[pos + ENVELOPE_MAX:end],
                                 raw=False, strict_map_key=False)
        if not isinstance(header, dict):
            raise CurvineError(
                f"frame header is {type(header).__name__}, not a map",
                code=ErrorCode.ABNORMAL_DATA)
    return end, code, req_id, status, flags, header, total - FIXED_LEN - hdr_len
