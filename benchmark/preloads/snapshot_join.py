"""A peer that joined its channel from a ledger snapshot taken at height
``first_block`` (Fabric >= 2.3, ``docs/source/peer_ledger_snapshot.md``;
``kvledger/snapshot.go`` ``CreateFromSnapshot``): the state DB, the txid
of every tx of the blocks before the snapshot in the duplicate-check
index, the chain and commit-hash anchors, no block files and no history.

The child writes the snapshot's files and joins through
``fabric_tpu.ledger.snapshot.create_from_snapshot``, the path
``peer/node.py`` takes, not rows written behind the store's back:

* ``public_state.data``: ``preload_keys`` keys of ``value_bytes`` seeded
  bytes at ``preload_version`` (the keys ``benchmark/preload.py`` loads);
* ``txids.data``: ``first_block x block_tx`` records, one 64-hex txid for
  every tx of the blocks below that height with its validation code (a share
  ``snapshot_valid_share`` of them ``VALID``), in the sorted order an
  export gives them.  Seeded hex, not hashes of real nonces;
* ``_snapshot_signable_metadata.json`` with the two files' SHA-256 and
  seeded ``last_block_hash`` / ``last_commit_hash``.

The snapshot is the channel's past, which a run's traffic does not
choose: it is made from the configuration's ``snapshot_seed`` and is the
same in every run, whatever ``--seed`` (the stream that follows it comes
from ``--seed``).  So a checkout joins once, as it compiles once: the
join's loop over ten million records takes a minute and a half of every
set-up that has to make it, and a cache keyed by ``--seed`` never hits
while a check's seeds rotate (PERF.md, PR 27).

Both data files are laid out with numpy, a hundred thousand records at a
time: ten million records from a Python loop would take minutes.  The
joined ledger is cached per configuration like the state DB, the
snapshot's files are deleted once joined.  No ``jax`` here.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

from benchmark import preload

#: joined ledgers kept of one configuration: each holds a txid index of
#: a gigabyte or two, and a configuration has one snapshot
KEEP = 1
_CHUNK = 100_000
#: validation codes of the snapshot's invalid txs, in turn
#: (BAD_CREATOR_SIGNATURE, ENDORSEMENT_POLICY_FAILURE, MVCC_READ_CONFLICT)
_INVALID_CODES = (4, 10, 11)


def anchors(seed: int) -> dict:
    """The hashes a snapshot of this ``snapshot_seed`` carries: those of
    its last block (what the stream's first block must extend), of the
    one before and of the commit-hash chain."""
    def h(what: str) -> bytes:
        return hashlib.sha256(f"snapshot|{int(seed)}|{what}".encode()).digest()

    return {"last_block_hash": h("last_block_hash"),
            "previous_block_hash": h("previous_block_hash"),
            "last_commit_hash": h("last_commit_hash")}


def _records(*fields):
    """A numpy dtype for length-prefixed records (``<u4`` length, then
    the bytes) whose fields all have fixed sizes → (dtype, lengths)."""
    spec, lengths = [], {}
    for name, size in fields:
        spec.append((f"{name}_len", "<u4"))
        lengths[f"{name}_len"] = size
        if size:
            spec.append((name, f"V{size}"))
    return np.dtype(spec), lengths


def _write(path: str, chunks) -> str:
    """Write the chunks (arrays of records) → the file's SHA-256."""
    h = hashlib.sha256()
    with open(path, "wb") as f:
        for arr in chunks:
            buf = arr.tobytes()
            f.write(buf)
            h.update(buf)
    return h.hexdigest()


def _state_chunks(config: dict, seed: int):
    from benchmark.reference import key_name

    ns = config["chaincode"].encode()
    n_keys, vb = int(config["preload_keys"]), int(config["value_bytes"])
    version = np.array(config["preload_version"], "<u4").tobytes()
    key_len = len(key_name(0))
    dtype, lengths = _records(("ns", len(ns)), ("key", key_len),
                              ("value", vb), ("version", 8), ("md", 0))
    rng = np.random.default_rng([int(seed), 0x57A7E])
    for lo in range(0, n_keys, _CHUNK):
        n = min(_CHUNK, n_keys - lo)
        arr = np.zeros(n, dtype)
        for name, size in lengths.items():
            arr[name] = size
        arr["ns"] = np.void(ns)
        arr["key"] = np.array([key_name(lo + j).encode() for j in range(n)],
                              f"S{key_len}").view(f"V{key_len}")
        arr["value"] = np.frombuffer(rng.bytes(n * vb), f"V{vb}")
        arr["version"] = np.void(version)
        yield arr


_HEX = np.array([int.from_bytes(f"{i:02x}".encode(), "little")
                 for i in range(256)], "<u2")


def _txid_chunks(n: int, valid_share: float, seed: int):
    dtype, lengths = _records(("txid", 64), ("code", 1))
    rng = np.random.default_rng([int(seed), 0x7A1D5])
    # sorted by their first eight bytes, which is the order of the hex
    # (two of ten million share all eight once in 300,000 seeds: they
    # are then neighbours in either order, and the index takes any)
    head = np.sort(rng.integers(0, 2**64, n, dtype=np.uint64))
    invalid = np.array(_INVALID_CODES, np.uint8)
    for lo in range(0, n, _CHUNK):
        m = min(_CHUNK, n - lo)
        raw = np.empty((m, 32), np.uint8)
        raw[:, :8] = head[lo:lo + m].astype(">u8").view(np.uint8).reshape(m, 8)
        raw[:, 8:] = np.frombuffer(rng.bytes(m * 24), np.uint8).reshape(m, 24)
        arr = np.zeros(m, dtype)
        for name, size in lengths.items():
            arr[name] = size
        arr["txid"] = _HEX[raw].view(np.uint8).reshape(m, 64).view("V64")[:, 0]
        codes = np.where(rng.random(m) < valid_share, np.uint8(0),
                         invalid[np.arange(lo, lo + m) % len(invalid)])
        arr["code"] = codes.view("V1")
        yield arr


def write_snapshot(snap_dir: str, config: dict, seed: int) -> dict:
    """The snapshot's three files → its metadata."""
    from fabric_tpu.ledger import snapshot

    os.makedirs(snap_dir)
    height = int(config["first_block"])
    a = anchors(seed)
    files = {
        snapshot.STATE_FILE: _write(
            os.path.join(snap_dir, snapshot.STATE_FILE),
            _state_chunks(config, seed)),
        snapshot.TXIDS_FILE: _write(
            os.path.join(snap_dir, snapshot.TXIDS_FILE),
            _txid_chunks(height * int(config["block_tx"]),
                         float(config["snapshot_valid_share"]), seed)),
    }
    meta = {
        "channel_name": config["channel"],
        "last_block_number": height - 1,
        "last_block_hash": a["last_block_hash"].hex(),
        "previous_block_hash": a["previous_block_hash"].hex(),
        "last_commit_hash": a["last_commit_hash"].hex(),
        "height": height,
        "state_savepoint": [height - 1, 0],
        "config": "",
        "files": files,
    }
    with open(os.path.join(snap_dir, snapshot.META_FILE), "w") as f:
        json.dump(meta, f, sort_keys=True, indent=1)
    return meta


def _join(ledger_dir: str, config: dict, seed: int, parts: dict) -> None:
    from fabric_tpu.ledger import snapshot

    snap_dir = f"{ledger_dir}.snapshot"
    shutil.rmtree(snap_dir, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        write_snapshot(snap_dir, config, seed)
        t1 = time.perf_counter()
        lg, _meta = snapshot.create_from_snapshot(
            snap_dir, ledger_dir, enable_history=bool(config["history_db"]))
        lg.close()
        parts["snapshot_files"] = t1 - t0
        parts["join_from_snapshot"] = time.perf_counter() - t1
    finally:
        shutil.rmtree(snap_dir, ignore_errors=True)


def ensure(cache_dir: str, config: dict, seed: int) -> tuple:
    """→ (the joined ledger directory of the configuration, the seconds
    it took to build: 0 where the cache held it, what the stream
    continues from and where the build's seconds went).  ``seed`` (the
    run's) chooses nothing here: the snapshot comes from the
    configuration's ``snapshot_seed``."""
    height = int(config["first_block"])
    snap_seed = int(config["snapshot_seed"])
    if height < 1:
        raise ValueError("a snapshot is taken at a height of 1 or more: "
                         f"first_block is {height}")
    tag = (f"snap{snap_seed}-h{height}x{int(config['block_tx'])}"
           f"-{int(config['preload_keys'])}x{int(config['value_bytes'])}"
           f"-valid{config['snapshot_valid_share']}")
    parts: dict = {}
    want, built = preload.cached(
        cache_dir, config["name"], tag,
        lambda tmp: _join(tmp, config, snap_seed, parts), keep=KEEP)
    return want, built, {"prev_hash": anchors(snap_seed)["last_block_hash"],
                         "build_parts_s": parts}
