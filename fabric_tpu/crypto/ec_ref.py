"""Pure-Python NIST P-256 reference implementation (correctness oracle).

This is the host-side oracle the TPU kernel (`fabric_tpu.ops.p256v3`) is
tested bit-exactly against, and the arithmetic backing for key/cert
generation where the `cryptography` package is not used.  Semantics
mirror the reference's SW BCCSP verifier: ECDSA P-256 with SHA-256
digests and the low-S rule (reference: bccsp/sw/ecdsa.go:41-58 —
signatures with s > n/2 are rejected; signing normalizes s to low-S).

Python ints only; NOT constant-time; verify-only paths don't need to be.
"""

from __future__ import annotations

import hashlib
import hmac
import secrets
from dataclasses import dataclass

# NIST P-256 (secp256r1) domain parameters.
P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
A = P - 3
B = 0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B
N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
GX = 0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296
GY = 0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5
HALF_N = N >> 1

INF = None  # point at infinity


def is_on_curve(pt) -> bool:
    if pt is INF:
        return True
    x, y = pt
    return (y * y - (x * x * x + A * x + B)) % P == 0


def pt_add(p1, p2):
    if p1 is INF:
        return p2
    if p2 is INF:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return INF
        return pt_double(p1)
    lam = ((y2 - y1) * pow(x2 - x1, -1, P)) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


def pt_double(pt):
    if pt is INF:
        return INF
    x, y = pt
    if y == 0:
        return INF
    lam = ((3 * x * x + A) * pow(2 * y, -1, P)) % P
    x3 = (lam * lam - 2 * x) % P
    y3 = (lam * (x - x3) - y) % P
    return (x3, y3)


def pt_mul(k: int, pt):
    k %= N
    acc = INF
    addend = pt
    while k:
        if k & 1:
            acc = pt_add(acc, addend)
        addend = pt_double(addend)
        k >>= 1
    return acc


G = (GX, GY)


# ---------------------------------------------------------------------------
# RFC 6979 deterministic nonce derivation (HMAC-SHA256, qlen = 256).
#
# This is the nonce contract shared by the serial signer below and the
# device batch-sign lane (fabric_tpu.ops.p256sign): both derive k from
# (d, e) with the exact HMAC_DRBG construction of RFC 6979 §3.2, so a
# signature is a pure function of (key, digest) — seeded replay works,
# and the device lane has a bit-equal CPU oracle to diff against.
# Pinned against the RFC's published A.2.5 P-256/SHA-256 vectors in
# tests/test_p256sign.py.

_QLEN_BYTES = 32  # qlen = 256 bits; SHA-256 ⇒ holen = 32 too


def rfc6979_candidates(d: int, e: int):
    """Successive RFC 6979 §3.2 nonce candidates for P-256/SHA-256.

    ``d``: private scalar in [1, n−1].  ``e``: the message digest as a
    256-bit integer (``digest_int``) — re-serialized to the 32 bytes
    H(m) so the derivation matches the RFC byte for byte.  With
    qlen == hlen == 256, bits2int is the identity and bits2octets is
    one reduction mod n.  Yields k values in [1, n−1]; the caller
    advances past a candidate only when it degenerates (r or s zero,
    the RFC's step h.3 retry — probability ≈ 2⁻²⁵⁶)."""
    if not (1 <= d < N):
        raise ValueError("private scalar out of range")
    x_oct = int(d).to_bytes(_QLEN_BYTES, "big")          # int2octets(x)
    h_oct = (int(e) % N).to_bytes(_QLEN_BYTES, "big")    # bits2octets
    V = b"\x01" * 32
    K = b"\x00" * 32
    mac = lambda key, msg: hmac.new(key, msg, hashlib.sha256).digest()
    K = mac(K, V + b"\x00" + x_oct + h_oct)
    V = mac(K, V)
    K = mac(K, V + b"\x01" + x_oct + h_oct)
    V = mac(K, V)
    while True:
        V = mac(K, V)
        k = int.from_bytes(V, "big")  # T is exactly qlen bits
        if 1 <= k < N:
            yield k
        K = mac(K, V + b"\x00")
        V = mac(K, V)


def rfc6979_k(d: int, e: int) -> int:
    """First RFC 6979 nonce candidate — THE deterministic k for
    (d, e) in every practical case (later candidates exist only for
    the 2⁻²⁵⁶ degenerate-signature retry)."""
    return next(rfc6979_candidates(d, e))


# ---------------------------------------------------------------------------
# Minimal DER (r, s) codec — the SW BCCSP signature wire form, pure
# Python so the sign lane (and its tests) run without `cryptography`.
# P-256 r/s are < 2^256, so every length fits the short form.


def _der_int(v: int) -> bytes:
    b = int(v).to_bytes((v.bit_length() + 8) // 8 or 1, "big")
    return b"\x02" + bytes([len(b)]) + b


def der_encode_sig(r: int, s: int) -> bytes:
    """(r, s) → DER ECDSA-Sig-Value (SEQUENCE of two INTEGERs)."""
    if not (0 < r < N and 0 < s < N):
        raise ValueError("r/s out of range")
    body = _der_int(r) + _der_int(s)
    return b"\x30" + bytes([len(body)]) + body


def der_decode_sig(der: bytes) -> tuple[int, int]:
    """DER ECDSA-Sig-Value → (r, s); strict short-form parse."""
    if len(der) < 8 or der[0] != 0x30 or der[1] != len(der) - 2:
        raise ValueError("bad DER signature envelope")
    out = []
    off = 2
    for _ in range(2):
        if off + 2 > len(der) or der[off] != 0x02:
            raise ValueError("bad DER integer tag")
        ln = der[off + 1]
        off += 2
        if ln == 0 or off + ln > len(der) or ln > 33:
            raise ValueError("bad DER integer length")
        out.append(int.from_bytes(der[off:off + ln], "big"))
        off += ln
    if off != len(der):
        raise ValueError("trailing DER bytes")
    return out[0], out[1]


@dataclass(frozen=True)
class SigningKey:
    d: int  # private scalar in [1, n-1]

    @property
    def public(self):
        return pt_mul(self.d, G)

    @classmethod
    def generate(cls) -> "SigningKey":
        return cls(d=secrets.randbelow(N - 1) + 1)

    def sign_digest(self, e: int, k: int | None = None) -> tuple[int, int]:
        """ECDSA sign; returns low-S normalized (r, s).

        ``k`` None derives the nonce DETERMINISTICALLY per RFC 6979
        (``rfc6979_k``) — a signature is then a pure function of
        (d, e): replayable, and the bit-equal oracle the device batch
        signer (fabric_tpu.ops.p256sign) is diffed against.  An
        explicit ``k`` is for tests/vectors only; r == 0 or s == 0
        with a fixed k raises instead of looping."""
        fixed = k is not None
        cands = iter([k]) if fixed else rfc6979_candidates(self.d, e)
        for kk in cands:
            x1, _ = pt_mul(kk, G)
            r = x1 % N
            s = (pow(kk, -1, N) * (e + r * self.d)) % N if r else 0
            if r == 0 or s == 0:
                if fixed:
                    raise ValueError("bad fixed k")
                continue  # RFC 6979 step h.3: next candidate
            if s > HALF_N:
                s = N - s  # low-S normalization (bccsp/sw/ecdsa.go ToLowS)
            return r, s
        raise ValueError("bad fixed k")  # exhausted the fixed candidate

    def sign(self, msg: bytes) -> tuple[int, int]:
        return self.sign_digest(digest_int(msg))


def digest_int(msg: bytes) -> int:
    return int.from_bytes(hashlib.sha256(msg).digest(), "big")


def verify_digest(pub, e: int, r: int, s: int) -> bool:
    """Reference verify incl. Fabric's low-S rule."""
    if pub is INF or not (0 <= pub[0] < P and 0 <= pub[1] < P) or not is_on_curve(pub):
        return False
    if not (1 <= r < N and 1 <= s < N):
        return False
    if s > HALF_N:  # low-S enforcement per bccsp/sw/ecdsa.go:41-58
        return False
    w = pow(s, -1, N)
    u1 = (e * w) % N
    u2 = (r * w) % N
    pt = pt_add(pt_mul(u1, G), pt_mul(u2, pub))
    if pt is INF:
        return False
    return pt[0] % N == r % N


def verify(pub, msg: bytes, r: int, s: int) -> bool:
    return verify_digest(pub, digest_int(msg), r, s)
