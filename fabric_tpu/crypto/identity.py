"""X.509 identities: parsing, signing, verification glue.

Host-side identity handling (analog of msp/identities.go).  The
expensive part — ECDSA verification — is NOT done here per-identity:
identities expose their public-key coordinates so the commit pipeline
can feed the whole block's (digest, r, s, qx, qy) tuples to the batched
TPU kernel (fabric_tpu.ops.p256v3).  ``verify`` below is the host
fallback (reference semantics: msp/identities.go:170-199 — SHA-256 the
message, then ECDSA-verify with low-S enforcement per
bccsp/sw/ecdsa.go:41-58).

Signatures are DER-encoded (r, s) with low-S normalization at signing,
exactly like the reference's SW BCCSP signer.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import cached_property

from cryptography import x509
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature,
    encode_dss_signature,
)

from fabric_tpu.crypto import ec_ref
from fabric_tpu.protos import common_pb2

ROLE_CLIENT = "client"
ROLE_PEER = "peer"
ROLE_ADMIN = "admin"
ROLE_ORDERER = "orderer"


def sig_to_ints(der_sig: bytes) -> tuple[int, int]:
    return decode_dss_signature(der_sig)


def ints_to_sig(r: int, s: int) -> bytes:
    return encode_dss_signature(r, s)


def low_s(s: int) -> int:
    return ec_ref.N - s if s > ec_ref.HALF_N else s


@dataclass
class Identity:
    """A deserialized (mspid, certificate) pair."""

    msp_id: str
    cert: x509.Certificate
    serialized: bytes  # the SerializedIdentity bytes (cache key)
    # filled by MSP.validate:
    is_valid: bool = False
    role: str = ROLE_CLIENT
    ous: tuple = ()

    @classmethod
    def from_serialized(cls, data: bytes) -> "Identity":
        sid = common_pb2.SerializedIdentity()
        sid.ParseFromString(data)
        cert = x509.load_pem_x509_certificate(sid.id_bytes)
        ident = cls(msp_id=sid.mspid, cert=cert, serialized=data)
        ident.ous = tuple(
            a.value
            for a in cert.subject.get_attributes_for_oid(
                x509.NameOID.ORGANIZATIONAL_UNIT_NAME
            )
        )
        return ident

    @cached_property
    def public_numbers(self):
        pub = self.cert.public_key()
        if not isinstance(pub, ec.EllipticCurvePublicKey):
            raise ValueError("only EC public keys supported")
        n = pub.public_numbers()
        return (n.x, n.y)

    @cached_property
    def rns_pub(self):
        """(qx_residues, qy_residues) [2n] int32 — cached per identity
        so the commit path's signature-batch assembly is a numpy gather
        over the block's (few) distinct endorser keys, not a per-item
        bigint→residue conversion (a block re-presents the same certs
        thousands of times)."""
        from fabric_tpu.ops import rns

        qx, qy = self.public_numbers
        res = rns.ints_to_rns([qx, qy])
        return res[0], res[1]

    def verify_item(self, message: bytes, der_sig: bytes):
        """→ (digest_int, r, s, qx, qy) for the batched TPU verifier."""
        r, s = decode_dss_signature(der_sig)
        qx, qy = self.public_numbers
        return (int.from_bytes(hashlib.sha256(message).digest(), "big"), r, s, qx, qy)

    def verify(self, message: bytes, der_sig: bytes) -> bool:
        """Host verify via OpenSSL (the reference's SW-BCCSP speed
        class) with the exact reference accept set: low-S enforced on
        top of the raw curve check (bccsp/sw/ecdsa.go:41-58)."""
        try:
            r, s = decode_dss_signature(der_sig)
        except Exception:
            return False
        if not (0 < r < ec_ref.N and 0 < s <= ec_ref.HALF_N):
            return False
        try:
            self.cert.public_key().verify(der_sig, message, ec.ECDSA(hashes.SHA256()))
            return True
        except Exception:
            return False


class SigningIdentity:
    """Private key + cert: the local signer (analog of
    msp.signingidentity; low-S normalization as in bccsp/sw signer)."""

    def __init__(self, msp_id: str, key: ec.EllipticCurvePrivateKey, cert: x509.Certificate):
        if not isinstance(key.curve, ec.SECP256R1):
            raise ValueError("P-256 keys only")
        self.msp_id = msp_id
        self.key = key
        self.cert = cert

    @classmethod
    def from_pem(cls, msp_id: str, key_pem: bytes, cert_pem: bytes) -> "SigningIdentity":
        key = serialization.load_pem_private_key(key_pem, password=None)
        cert = x509.load_pem_x509_certificate(cert_pem)
        return cls(msp_id, key, cert)

    @cached_property
    def cert_pem(self) -> bytes:
        return self.cert.public_bytes(serialization.Encoding.PEM)

    @cached_property
    def serialized(self) -> bytes:
        return common_pb2.SerializedIdentity(
            mspid=self.msp_id, id_bytes=self.cert_pem
        ).SerializeToString()

    def sign(self, message: bytes) -> bytes:
        der = self.key.sign(message, ec.ECDSA(hashes.SHA256()))
        r, s = decode_dss_signature(der)
        return encode_dss_signature(r, low_s(s))

    @property
    def identity(self) -> Identity:
        ident = Identity.from_serialized(self.serialized)
        ident.is_valid = True
        return ident
