"""Python binding for the native rwset/MVCC preparation.

``prep(parsed_block, use)`` → MvccPrep (flat arrays over the shared
blob) or None when the native library is unavailable.  Per-tx
``status``: 0 = fast arrays valid, 1 = the tx needs the Python rwset
path, 2 = not used (use[i] was 0)."""

from __future__ import annotations

import ctypes
from dataclasses import dataclass

import numpy as np

from fabric_tpu.native import mvccprep_lib


@dataclass
class MvccPrep:
    blob: bytes
    status: np.ndarray        # [n] uint8
    tx_ns_start: np.ndarray   # [n]
    tx_ns_count: np.ndarray
    ns_ids_flat: np.ndarray   # [.] int32
    r_start: np.ndarray
    r_count: np.ndarray
    w_start: np.ndarray
    w_count: np.ndarray
    r_uid: np.ndarray         # [nr] int32
    r_has_ver: np.ndarray     # [nr] uint8
    r_ver: np.ndarray         # [nr, 2] uint64
    w_uid: np.ndarray
    w_is_del: np.ndarray
    w_key_span: np.ndarray    # [nw, 2]
    w_val_span: np.ndarray
    ns_of_ukey: np.ndarray    # [n_keys] int32
    ns_span: np.ndarray       # [n_ns, 2]
    ukey_span: np.ndarray     # [n_keys, 2]
    m_start: np.ndarray       # [n] metadata writes (KVRWSet field 4)
    m_count: np.ndarray
    m_uid: np.ndarray         # [nm] int32
    m_only: np.ndarray        # [nm] uint8: no value write to the key in its tx
    m_ent_span: np.ndarray    # [nm, 2] the write's entries, as bytes of the
    #                           blob that parse as a keyless KVMetadataWrite;
    #                           length 0: the metadata is cleared
    n_ns: int
    n_keys: int
    n_reads: int
    n_writes: int
    n_meta: int = 0

    def ns_names(self) -> list:
        return [
            self.blob[self.ns_span[i, 0]:
                      self.ns_span[i, 0] + self.ns_span[i, 1]].decode()
            for i in range(self.n_ns)
        ]

    def ukey_strs(self) -> list:
        """[n_keys] decoded key strings (UTF-8 guaranteed by the
        native parser's validation)."""
        return [
            self.blob[self.ukey_span[i, 0]:
                      self.ukey_span[i, 0] + self.ukey_span[i, 1]].decode()
            for i in range(self.n_keys)
        ]


def prep(pb, use: np.ndarray) -> MvccPrep | None:
    lib = mvccprep_lib()
    if lib is None:
        return None
    n = len(use)
    total_len = int(pb.results_span[:, 1].clip(min=0).sum())
    cap = max(64, total_len // 4 + 8 * n)
    cap_ns = 1024
    use8 = np.ascontiguousarray(use.astype(np.uint8))
    rs = np.ascontiguousarray(pb.results_span)
    out = MvccPrep(
        blob=pb.blob,
        status=np.zeros(n, np.uint8),
        tx_ns_start=np.zeros(n, np.int64),
        tx_ns_count=np.zeros(n, np.int64),
        ns_ids_flat=np.zeros(cap, np.int32),
        r_start=np.zeros(n, np.int64), r_count=np.zeros(n, np.int64),
        w_start=np.zeros(n, np.int64), w_count=np.zeros(n, np.int64),
        r_uid=np.zeros(cap, np.int32),
        r_has_ver=np.zeros(cap, np.uint8),
        r_ver=np.zeros((cap, 2), np.uint64),
        w_uid=np.zeros(cap, np.int32),
        w_is_del=np.zeros(cap, np.uint8),
        w_key_span=np.zeros((cap, 2), np.int64),
        w_val_span=np.zeros((cap, 2), np.int64),
        ns_of_ukey=np.zeros(cap, np.int32),
        ns_span=np.zeros((cap_ns, 2), np.int64),
        ukey_span=np.zeros((cap, 2), np.int64),
        m_start=np.zeros(n, np.int64), m_count=np.zeros(n, np.int64),
        m_uid=np.zeros(cap, np.int32),
        m_only=np.zeros(cap, np.uint8),
        m_ent_span=np.zeros((cap, 2), np.int64),
        n_ns=0, n_keys=0, n_reads=0, n_writes=0,
    )
    counts = np.zeros(5, np.int64)

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    lib.mvcc_prep(
        ctypes.c_char_p(pb.blob), ptr(rs), ptr(use8),
        ctypes.c_int64(n), ctypes.c_int64(cap), ctypes.c_int64(cap_ns),
        ctypes.c_int64(cap),
        ptr(out.status), ptr(out.tx_ns_start), ptr(out.tx_ns_count),
        ptr(out.ns_ids_flat),
        ptr(out.r_start), ptr(out.r_count), ptr(out.w_start), ptr(out.w_count),
        ptr(out.r_uid), ptr(out.r_has_ver), ptr(out.r_ver),
        ptr(out.w_uid), ptr(out.w_is_del), ptr(out.w_key_span),
        ptr(out.w_val_span),
        ptr(out.ns_of_ukey), ptr(out.ns_span), ptr(out.ukey_span),
        ptr(out.m_start), ptr(out.m_count), ptr(out.m_uid), ptr(out.m_only),
        ptr(out.m_ent_span),
        ptr(counts),
    )
    (out.n_ns, out.n_keys, out.n_reads, out.n_writes,
     out.n_meta) = (int(c) for c in counts)
    return out
