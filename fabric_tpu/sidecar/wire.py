"""Wire format of the validation sidecar's ``validate`` stream.

The paper's north-star deployment ships *signature batches* to the
device fabric ("a new BCCSP-style provider shipping signature batches
over gRPC") — so the unit on the wire is one block's signature batch:
a list of ``(e, r, s, qx, qy)`` integer tuples (digest, DER-split
signature halves, public-key affine coordinates — exactly what
``ops/p256v3.verify_host`` consumes), and the reply is that batch's
boolean verdict vector.  Parse, policy evaluation and MVCC stay on
the peer, which owns the state they read.

Frames ride ``comm.rpc`` MSG payloads:

    hello    := JSON {"tenant": str, "weight": float}
    welcome  := JSON {"ok": true, "coalesce": int}
    request  := u32 hdr_len | JSON {"seq": int, "n": int
                [, "trace": {"block", "root", "tenant"}]} | items
    response := u32 hdr_len | JSON {"seq": int [, "status", "error",
                "retry_ms"] [, "remote": {"spans", "t_rx", "t_tx"}]}
                | verdict bytes (one 0/1 byte per item)

The optional ``trace`` request field propagates the peer's trace
context (its block number, root span id and tenant) so the sidecar
roots its queue_wait/dispatch spans under it; the optional ``remote``
response field ships the finished remote subtree back — ``spans`` is
the ``Span.to_dict(0.0)`` tree with ABSOLUTE times on the sidecar's
clock, and ``t_rx``/``t_tx`` (request receive / response send, same
clock) let the client estimate the clock offset NTP-style from the
request/response timestamp midpoints and stitch the subtree onto its
own timeline.

``items`` packs each tuple as five 32-byte big-endian integers — the
natural width of P-256 scalars/field elements.  A component that does
not fit (a malformed DER signature can carry an arbitrary-precision
integer) is replaced by the all-zero item, which every verifier
rejects (r = 0 is never a valid ECDSA signature), so an unpackable
lane degrades to "invalid", never to a protocol error.

A response with ``status == "BUSY"`` is the sidecar's typed
backpressure signal: the tenant's admission queue is full, retry after
backoff.  ``status == "ERROR"`` means the dispatch itself failed —
the client re-verifies that batch locally.
"""

from __future__ import annotations

import json
import struct

INT_BYTES = 32
ITEM_BYTES = 5 * INT_BYTES
_LEN = struct.Struct(">I")

#: the item every unpackable tuple degrades to — rejected by every
#: verifier (r = 0), so wire-layer sanitation can only turn a lane
#: invalid, never valid
INVALID_ITEM = (0, 0, 0, 0, 0)

_MAX = 1 << (8 * INT_BYTES)


def pack_items(tuples) -> bytes:
    """[(e, r, s, qx, qy)] → packed item bytes (see module docstring)."""
    out = bytearray()
    for item in tuples:
        vals = tuple(int(v) for v in item)
        if len(vals) != 5 or any(v < 0 or v >= _MAX for v in vals):
            vals = INVALID_ITEM
        for v in vals:
            out += v.to_bytes(INT_BYTES, "big")
    return bytes(out)


def unpack_items(buf: bytes) -> list:
    if len(buf) % ITEM_BYTES:
        raise ValueError(
            f"packed item buffer of {len(buf)} bytes is not a multiple "
            f"of {ITEM_BYTES}"
        )
    out = []
    for off in range(0, len(buf), ITEM_BYTES):
        out.append(tuple(
            int.from_bytes(buf[off + i * INT_BYTES:off + (i + 1) * INT_BYTES],
                           "big")
            for i in range(5)
        ))
    return out


def _frame(hdr: dict, body: bytes = b"") -> bytes:
    raw = json.dumps(hdr).encode()
    return _LEN.pack(len(raw)) + raw + body


def _unframe(payload: bytes) -> tuple[dict, bytes]:
    (n,) = _LEN.unpack_from(payload)
    hdr = json.loads(payload[_LEN.size:_LEN.size + n])
    return hdr, payload[_LEN.size + n:]


def encode_request(seq: int, tuples, trace: dict | None = None) -> bytes:
    hdr = {"seq": int(seq), "n": len(tuples)}
    if trace:
        hdr["trace"] = trace
    return _frame(hdr, pack_items(tuples))


def decode_request(payload: bytes) -> tuple[dict, list]:
    hdr, body = _unframe(payload)
    items = unpack_items(body)
    if len(items) != int(hdr.get("n", len(items))):
        raise ValueError(
            f"request {hdr.get('seq')}: header says {hdr.get('n')} items, "
            f"payload carries {len(items)}"
        )
    return hdr, items


def encode_response(seq: int, verdicts, remote: dict | None = None) -> bytes:
    hdr = {"seq": int(seq)}
    if remote:
        hdr["remote"] = remote
    return _frame(hdr, bytes(1 if v else 0 for v in verdicts))


def encode_busy(seq: int, retry_ms: float) -> bytes:
    return _frame({"seq": int(seq), "status": "BUSY",
                   "retry_ms": round(float(retry_ms), 3)})


def encode_error(seq: int, msg: str) -> bytes:
    return _frame({"seq": int(seq), "status": "ERROR", "error": msg[:500]})


def decode_response(payload: bytes) -> tuple[dict, list]:
    """→ (header, verdicts); verdicts empty for BUSY/ERROR headers."""
    hdr, body = _unframe(payload)
    return hdr, [bool(b) for b in body]
