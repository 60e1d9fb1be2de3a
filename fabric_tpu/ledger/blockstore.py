"""Append-only block store with sqlite index and crash recovery.

Analog of the reference's block storage
(common/ledger/blkstorage/blockfile_mgr.go:281 addBlock; index
blockindex.go).  Blocks are length-prefixed protobufs in numbered
segment files; a sqlite index maps number/hash/txid → (file, offset).
On open, a partially written tail record (crash mid-append) is
truncated — the reference's atomic-write recovery — and the index is
rebuilt forward from the last indexed block, so the FILES are the
source of truth and the index is derived state.
"""

from __future__ import annotations

import logging
import os
import sqlite3
import struct
import threading
import time

from google.protobuf.message import DecodeError

from fabric_tpu import faults as _faults
from fabric_tpu import protoutil
# the index's names for the write-back constants the three stores share;
# a store takes the bound in force when it opens
from fabric_tpu.ledger.walcheckpoint import (  # noqa: F401
    BACKSTOP_FACTOR as _BACKSTOP_FACTOR,
    CACHE_KIB as _IDX_CACHE_KIB,
    CHECKPOINT_ROWS as _CHECKPOINT_ROWS,
    WalCheckpoint,
    writer_pragmas,
)
from fabric_tpu.observe.tracer import global_tracer
from fabric_tpu.protos import common_pb2

_log = logging.getLogger("fabric_tpu.ledger")

_SEGMENT_MAX = 64 * 1024 * 1024
_LEN = struct.Struct("<I")
#: one tree a txid: the primary key IS the table, where a rowid table
#: keeps the rows in one b-tree and the random 64-character key a
#: second time in the automatic index beside it
_TXIDS_COLUMNS = (
    " (txid TEXT PRIMARY KEY, num INTEGER, txnum INTEGER, code INTEGER)"
    " WITHOUT ROWID"
)


class BlockStore:
    def __init__(self, dirpath: str, group_commit: int = 8,
                 group_max_lag_s: float = 0.5):
        """``group_commit``: fsync the segment file every N blocks
        instead of every block (1 = always).  Safe because the commit
        path is replay-recoverable end to end: a crash inside the
        window loses only the unsynced TAIL of the segment file, which
        _recover truncates; the peer's deliver loop then re-fetches
        those blocks from the ordering service and state/history catch
        up through the normal replay path (kv_ledger.go:357 recoverDBs
        analog) — no committed-and-acknowledged data is at risk
        because downstream acknowledgment (gateway commit status)
        keys off the block store height after recovery.
        ``group_max_lag_s`` bounds the window WHILE TRAFFIC FLOWS (the
        check runs at the next add_block); a burst followed by silence
        is closed by callers of ``sync()`` — the peer forces it before
        acknowledging commit status (node.py commit_block), and
        close() always syncs."""
        self.dir = dirpath
        self.group_commit = max(1, int(group_commit))
        self.group_max_lag_s = group_max_lag_s
        self._unsynced = 0
        self._oldest_unsynced: float | None = None
        self._fsync_ctr = None  # lazy blockstore_fsync_total counter
        # serializes segment-file writes/fsyncs between the committer
        # thread (add_block) and the async engine's applier thread
        # (ensure_synced — the durability fence); uncontended cost is
        # one futex op per block
        self._io_lock = threading.Lock()
        # The index has two sqlite connections, one for the thread that
        # writes and one for everybody who reads; each has a lock of its
        # own and neither side ever takes the other's.  Each side hands
        # sqlite a block's work in one statement (_index_block,
        # existing_txids), never one a tx.
        # ``_idx`` (``_idx_lock``) belongs to those who write:
        # add_block's insert + commit on the committer thread and its
        # WAL checkpoint a group of blocks (checkpoint_if_due),
        # _recover, bootstrap_from_snapshot, and the two snapshot
        # iterators (their cursors live as long as the export, which a
        # reader's must not: see _rd_rows).
        # ``_rd`` (``_rd_lock``) answers every lookup: the validator's
        # duplicate-txid query, the gateway's status reads, and the
        # committer's own ``height``.  The index is in WAL mode, so a
        # read on ``_rd`` never waits for the writer's transaction and
        # sees every transaction committed before the read began.  The
        # lock is for the readers among themselves: two threads running
        # the SAME statement text on one sqlite3 connection share its
        # cached prepared statement, and a bind racing a step fails
        # with "bad parameter or other API misuse".
        self._idx_lock = threading.Lock()
        self._rd_lock = threading.Lock()
        # seconds spent WAITING for a lock, by the side that waited:
        # readers for ``_rd_lock`` (held for one statement by another
        # reader), the writer for ``_idx_lock`` (nobody else holds it
        # on the commit path).  Only a contended acquire reads the
        # clock; each float is updated while holding the lock.  The
        # ``dup_txid`` and ``commit.index`` spans carry the delta over
        # their extent.
        self.idx_wait_reader_s = 0.0
        self.idx_wait_writer_s = 0.0
        # statements existing_txids has sent (the launching thread is
        # its one caller): ``dup_txid`` carries the delta as
        # ``queries``, 1 a block
        self.txid_queries = 0
        os.makedirs(dirpath, exist_ok=True)
        self._idx = sqlite3.connect(
            os.path.join(dirpath, "index.db"), check_same_thread=False
        )
        # variables one statement takes (32,766 since sqlite 3.32, 999
        # before): what cuts the block-wide statements of both sides
        self._max_vars = self._idx.getlimit(
            sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER
        )
        self._idx.execute("PRAGMA journal_mode=WAL")
        # the index is DERIVED state (rebuilt forward — and clamped
        # backward — from the segment files by _recover), so commits
        # need no fsync; NORMAL (not OFF) keeps the WAL checkpoint
        # itself crash-safe — OFF can corrupt the main DB file on
        # power loss, and there is no drop-and-rebuild path
        self._idx.execute("PRAGMA synchronous=NORMAL")
        # the writer decides when the WAL is written back
        # (checkpoint_if_due: between transactions, after the block is
        # handed to the applier), on its own connection: the thread that
        # writes is the thread that checkpoints
        writer_pragmas(self._idx, _CHECKPOINT_ROWS)
        self._wal = WalCheckpoint(
            self._idx, self._idx_lock, bound=_CHECKPOINT_ROWS,
            counter=("blockstore_index_checkpoints_total",
                     "WAL checkpoints of the block index by trigger"),
            span="commit.checkpoint")
        self._idx.execute(
            "CREATE TABLE IF NOT EXISTS blocks ("
            " num INTEGER PRIMARY KEY, hash BLOB, seg INTEGER, off INTEGER)"
        )
        self._idx.execute(
            "CREATE TABLE IF NOT EXISTS txids" + _TXIDS_COLUMNS
        )
        self._rebuild_rowid_txids()
        self._idx.execute(
            "CREATE INDEX IF NOT EXISTS blocks_hash ON blocks(hash)"
        )
        self._idx.execute(
            "CREATE TABLE IF NOT EXISTS bootstrap ("
            " id INTEGER PRIMARY KEY CHECK (id = 0),"
            " first_block INTEGER, prev_hash BLOB, commit_hash BLOB)"
        )
        self._recover()
        # opened after recovery, so its first read sees the index
        # as _recover left it
        self._rd = sqlite3.connect(
            os.path.join(dirpath, "index.db"), check_same_thread=False
        )
        self._rd.execute("PRAGMA query_only=ON")
        # fsync watermark in block numbers: everything recovery left in
        # the files is already durable (or was truncated away), so the
        # synced watermark starts at the tip
        self._last_appended = self.height - 1
        self._synced_num = self._last_appended

    # -- segment file plumbing --------------------------------------------

    def _seg_path(self, seg: int) -> str:
        return os.path.join(self.dir, f"blocks_{seg:06d}.bin")

    def _segments(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("blocks_") and name.endswith(".bin"):
                out.append(int(name[7:13]))
        return sorted(out)

    def _rebuild_rowid_txids(self) -> None:
        """A store built before ``txids`` was ``WITHOUT ROWID`` is
        rebuilt once, here, in one transaction: no second schema lives
        on.  Copied, not dropped and re-indexed: a snapshot-joined
        store holds txids that are in no file."""
        (sql,) = self._idx.execute(
            "SELECT sql FROM sqlite_master WHERE name='txids'"
        ).fetchone()
        if "WITHOUT ROWID" in sql.upper():
            return
        t0 = time.perf_counter()
        self._idx.execute("BEGIN")
        self._idx.execute("CREATE TABLE txids_one_tree" + _TXIDS_COLUMNS)
        rows = self._idx.execute(
            "INSERT INTO txids_one_tree SELECT txid, num, txnum, code"
            " FROM txids ORDER BY txid"
        ).rowcount
        self._idx.execute("DROP TABLE txids")
        self._idx.execute("ALTER TABLE txids_one_tree RENAME TO txids")
        self._idx.commit()
        # the whole table went through the WAL: write it back and cut
        # the file down before anybody reads
        self._idx.execute("PRAGMA wal_checkpoint(TRUNCATE)").fetchall()
        _log.info(
            "%s: txid index rebuilt WITHOUT ROWID, %d rows in %.2f s",
            self.dir, rows, time.perf_counter() - t0,
        )

    def _recover(self) -> None:
        segs = self._segments()
        if not segs:
            self._seg = 0
            self._fh = open(self._seg_path(0), "ab")
            return
        # truncate torn tail record of the last segment
        last = segs[-1]
        path = self._seg_path(last)
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            off = 0
            while off + _LEN.size <= size:
                (n,) = _LEN.unpack(f.read(_LEN.size))
                if off + _LEN.size + n > size:
                    break
                f.seek(n, 1)
                off += _LEN.size + n
        if off < size:
            with open(path, "ab") as f:
                f.truncate(off)
        # re-index anything beyond the last indexed block
        row = self._idx.execute("SELECT MAX(num) FROM blocks").fetchone()
        next_num = (row[0] + 1) if row[0] is not None else 0
        file_max = -1
        for seg in segs:
            for block, offset in self._scan(seg):
                file_max = max(file_max, block.header.number)
                if block.header.number >= next_num:
                    self._index_block(block, seg, offset)
        # clamp the index BACK to the files: group commit means the
        # sqlite index (WAL) can be durably ahead of an unsynced
        # segment tail a crash truncated — the FILES are the source of
        # truth in both directions
        if next_num - 1 > file_max:
            self._idx.execute(
                "DELETE FROM blocks WHERE num > ?", (file_max,)
            )
            self._idx.execute(
                "DELETE FROM txids WHERE num > ?", (file_max,)
            )
        self._idx.commit()
        self._seg = last
        self._fh = open(path, "ab")

    def _scan(self, seg: int):
        path = self._seg_path(seg)
        with open(path, "rb") as f:
            off = 0
            while True:
                hdr = f.read(_LEN.size)
                if len(hdr) < _LEN.size:
                    return
                (n,) = _LEN.unpack(hdr)
                data = f.read(n)
                if len(data) < n:
                    return
                block = common_pb2.Block()
                block.ParseFromString(data)
                yield block, off
                off += _LEN.size + n

    # -- index -------------------------------------------------------------

    def _index_block(
        self, block: common_pb2.Block, seg: int, off: int, txids=None
    ) -> None:
        """txids: optional pre-parsed [(txid, tx_num)] — the commit
        path already holds the parsed envelopes, so re-unmarshalling
        every envelope here (3 protobuf parses per tx) is skipped."""
        self._idx.execute(
            "INSERT OR REPLACE INTO blocks VALUES (?,?,?,?)",
            (block.header.number, protoutil.block_header_hash(block.header), seg, off),
        )
        flags = protoutil.get_tx_filter(block)
        if txids is None:
            txids = []
            for i, env_bytes in enumerate(block.data.data):
                try:
                    env = protoutil.unmarshal(common_pb2.Envelope, env_bytes)
                    payload = protoutil.unmarshal(common_pb2.Payload, env.payload)
                    ch = protoutil.unmarshal(
                        common_pb2.ChannelHeader, payload.header.channel_header
                    )
                except DecodeError:
                    continue  # non-envelope payload: nothing to index
                if ch.tx_id:
                    txids.append((ch.tx_id, i))
        num = block.header.number
        rows = [
            (txid, num, i, flags[i] if i < len(flags) else 254)
            for txid, i in txids if txid
        ]
        self._wal.note(len(rows))
        # the block's rows in one statement (as many as sqlite takes
        # variables in one), not one step a row: sqlite3 hands the
        # interpreter lock over around every step, and 1000 hand-overs
        # a block beside the launching, prefetch and applier threads
        # cost the committer, and them, more than the rows do
        per = self._max_vars // 4
        for at in range(0, len(rows), per):
            part = rows[at:at + per]
            self._idx.execute(
                "INSERT OR IGNORE INTO txids VALUES "
                + ",".join(["(?,?,?,?)"] * len(part)),
                [v for row in part for v in row],
            )

    # -- public API --------------------------------------------------------

    @property
    def unsynced(self) -> int:
        """Blocks appended since the last fsync — the open group-commit
        window's depth (0 = everything durable)."""
        return self._unsynced

    def _count_fsync(self, trigger: str) -> None:
        """``blockstore_fsync_total{trigger}``: how each fsync window
        closed — ``group`` (window filled), ``lag`` (max-lag bound),
        ``forced`` (explicit sync(): barrier/tail/ack/close).  Under
        the deep-pipelined committer's deferred syncs this is the
        number that shows the fsync batching actually engaging."""
        ctr = self._fsync_ctr
        if ctr is None:
            from fabric_tpu.ops_metrics import global_registry

            ctr = self._fsync_ctr = global_registry().counter(
                "blockstore_fsync_total",
                "segment fsyncs by closing trigger",
            )
        ctr.add(1, trigger=trigger)

    def _rd_rows(self, sql: str, args=()) -> list:
        """Every row of one statement on the read connection, under the
        readers' lock.  The cursor is fetched to its end before the
        lock is released: an open cursor on a WAL reader pins its
        snapshot, so later reads on the connection would answer from
        before the commits since, checkpoints could not finish and
        ``index.db-wal`` would grow without bound."""
        lock = self._rd_lock
        if not lock.acquire(False):
            t0 = time.perf_counter()
            lock.acquire()
            self.idx_wait_reader_s += time.perf_counter() - t0
        try:
            return self._rd.execute(sql, args).fetchall()
        finally:
            lock.release()

    def _idx_row(self, sql: str, args: tuple = ()):
        """One index row (or None)."""
        rows = self._rd_rows(sql, args)
        return rows[0] if rows else None

    @property
    def height(self) -> int:
        row = self._idx_row("SELECT MAX(num) FROM blocks")
        if row[0] is not None:
            return row[0] + 1
        boot = self._idx_row("SELECT first_block FROM bootstrap WHERE id=0")
        return boot[0] if boot else 0

    def bootstrap_from_snapshot(self, first_block: int, prev_hash: bytes,
                                txid_codes, commit_hash: bytes = b"") -> None:
        """Position an EMPTY store at a snapshot boundary: height
        becomes ``first_block``, the snapshot's committed txids (WITH
        their original validation codes) join the dup-check index, and
        the chain/commit-hash anchors persist for reopen + continuity
        checks (blkstorage bootstrapping snapshot,
        kvledger/snapshot.go:222 CreateFromSnapshot)."""
        if self.height != 0:
            raise ValueError("bootstrap requires an empty block store")
        self._idx.execute(
            "INSERT OR REPLACE INTO bootstrap VALUES (0, ?, ?, ?)",
            (first_block, prev_hash, commit_hash),
        )
        self._wal.note(self._idx.executemany(
            "INSERT OR IGNORE INTO txids VALUES (?,?,?,?)",
            ((t, -1, -1, c) for t, c in txid_codes),
        ).rowcount)
        self._idx.commit()
        self.checkpoint_if_due()

    def bootstrap_info(self):
        """→ (first_block, prev_hash, commit_hash) or None."""
        boot = self._idx_row(
            "SELECT first_block, prev_hash, commit_hash FROM bootstrap WHERE id=0"
        )
        return tuple(boot) if boot else None

    def iter_txids(self):
        """All committed txids in sorted order (snapshot export)."""
        for (t,) in self._idx.execute("SELECT txid FROM txids ORDER BY txid"):
            yield t

    def iter_txid_codes(self):
        """(txid, validation_code) in sorted order — codes survive the
        snapshot so a joined peer's tx-status queries stay truthful."""
        for t, c in self._idx.execute(
            "SELECT txid, code FROM txids ORDER BY txid"
        ):
            yield t, int(c)

    def expected_prev_hash(self) -> bytes | None:
        """Hash the next block's previous_hash must carry, when known
        (last stored block, or the snapshot anchor).  Cached in memory
        after the first lookup — this sits on the commit hot path."""
        cached = getattr(self, "_last_hash", None)
        if cached is not None:
            return cached
        row = self._idx_row("SELECT MAX(num) FROM blocks")
        if row[0] is not None:
            self._last_hash = self._idx_row(
                "SELECT hash FROM blocks WHERE num=?", (row[0],)
            )[0]
            return self._last_hash
        boot = self.bootstrap_info()
        return boot[1] if boot else None

    def add_block(self, block: common_pb2.Block, txids=None,
                  hd_bytes: bytes | None = None) -> None:
        """``hd_bytes``: optional pre-serialized header+data fields
        (protoutil.block_header_data_bytes, built off the commit
        thread) — metadata is spliced on here so the committer never
        re-serializes the envelopes."""
        if block.header.number != self.height:
            raise ValueError(
                f"block number {block.header.number} != height {self.height}"
            )
        want_prev = self.expected_prev_hash()
        if want_prev and block.header.previous_hash != want_prev:
            raise ValueError(
                f"block {block.header.number} previous_hash does not "
                "extend this chain"
            )
        if hd_bytes is not None:
            data = protoutil.append_block_metadata(hd_bytes, block)
        else:
            data = block.SerializeToString()
        with self._io_lock:
            if (self._fh.tell() + len(data) > _SEGMENT_MAX
                    and self._fh.tell() > 0):
                # a finished segment must be durable
                self._sync_locked("forced")
                self._fh.close()
                self._seg += 1
                self._fh = open(self._seg_path(self._seg), "ab")
            off = self._fh.tell()
            self._fh.write(_LEN.pack(len(data)))
            self._fh.write(data)
            self._fh.flush()
            self._last_appended = block.header.number
            # group commit: amortize the fsync over a window of blocks
            # (see __init__ for the replay-safety argument)
            self._unsynced += 1
            if self._oldest_unsynced is None:
                self._oldest_unsynced = time.monotonic()
            if (
                self._unsynced >= self.group_commit
                or time.monotonic() - self._oldest_unsynced
                >= self.group_max_lag_s
            ):
                # crash-consistency hooks: the kill-mid-fsync chaos
                # tests exit the process inside _sync_locked (before =
                # the whole window is lost and _recover must truncate
                # the torn tail; after = the window is durable) and
                # assert replay to a consistent height on reopen
                self._sync_locked(
                    "group" if self._unsynced >= self.group_commit
                    else "lag"
                )
        tracer = global_tracer()
        # a span only under a traced commit (no-op without a current span)
        with tracer.span("commit.index") as isp:
            waited0, lock = self.idx_wait_writer_s, self._idx_lock
            if not lock.acquire(False):
                t0 = time.perf_counter()
                lock.acquire()
                self.idx_wait_writer_s += time.perf_counter() - t0
            try:
                self._index_block(block, self._seg, off, txids=txids)
                self._idx.commit()
            finally:
                lock.release()
            if isp is not None:
                isp.attrs.update(
                    idx_wait_ms=(self.idx_wait_writer_s - waited0) * 1000.0,
                    txids=len(block.data.data if txids is None else txids),
                )
        self._last_hash = protoutil.block_header_hash(block.header)
        # where nobody called checkpoint_if_due for a whole multiple of
        # its bound: the WAL must not grow without one
        self._wal.backstop()

    def checkpoint_if_due(self) -> None:
        """Write the index's WAL back into ``index.db`` once a group of
        blocks has put ``_CHECKPOINT_ROWS`` txids into it, or where the
        last try left frames behind (``WalCheckpoint.if_due``; span
        ``commit.checkpoint``, counter
        ``blockstore_index_checkpoints_total{trigger}``).  For the
        thread that appends, once the block is out of its hands:
        ``KVLedger.commit_block`` calls this after the block went to the
        apply engine (or was applied), the orderer's chain where its
        append ends.  Between index transactions and outside
        ``commit.index``, so no block's ``add_block`` pays for the pages
        of eight."""
        self._wal.if_due()

    @property
    def _ckpt_rows(self) -> int:
        return self._wal.rows

    @property
    def _ckpt_retry(self) -> bool:
        return self._wal.retry

    def _read_at(self, seg: int, off: int) -> common_pb2.Block | None:
        try:
            with open(self._seg_path(seg), "rb") as f:
                f.seek(off)
                (n,) = _LEN.unpack(f.read(_LEN.size))
                block = common_pb2.Block()
                block.ParseFromString(f.read(n))
                return block
        except (OSError, struct.error):
            return None

    def get_block(self, number: int) -> common_pb2.Block | None:
        row = self._idx_row(
            "SELECT seg, off FROM blocks WHERE num=?", (number,)
        )
        return self._read_at(*row) if row else None

    def get_block_by_hash(self, h: bytes) -> common_pb2.Block | None:
        row = self._idx_row(
            "SELECT seg, off FROM blocks WHERE hash=?", (h,)
        )
        return self._read_at(*row) if row else None

    def get_tx_loc(self, txid: str):
        """→ (block_num, tx_num, validation_code) or None (dup-txid
        check + qscc GetTransactionByID)."""
        row = self._idx_row(
            "SELECT num, txnum, code FROM txids WHERE txid=?", (txid,)
        )
        return tuple(row) if row else None

    def tx_exists(self, txid: str) -> bool:
        return self.get_tx_loc(txid) is not None

    def existing_txids(self, txids) -> set[str]:
        """The subset of ``txids`` the index holds: the duplicate check
        of a whole block in one statement (as many as sqlite takes
        variables in one), never one a tx."""
        txids = list(txids)
        found: set[str] = set()
        for i in range(0, len(txids), self._max_vars):
            part = txids[i:i + self._max_vars]
            marks = ",".join("?" * len(part))
            rows = self._rd_rows(
                f"SELECT txid FROM txids WHERE txid IN ({marks})", part
            )
            self.txid_queries += 1
            found.update(t for (t,) in rows)
        return found

    def iter_blocks(self, start: int = 0):
        num = start
        while True:
            blk = self.get_block(num)
            if blk is None:
                return
            yield blk
            num += 1

    def _sync_locked(self, trigger: str) -> None:
        # caller holds self._io_lock
        if self._unsynced:
            self._count_fsync(trigger)
            # under whichever span the syncing thread is in: the
            # committer's ``commit``, the applier's ``apply.fence``
            with global_tracer().span("fsync", trigger=trigger,
                                      blocks=self._unsynced):
                self._fh.flush()
                _faults.fire("ledger.fsync.before")
                os.fsync(self._fh.fileno())
                _faults.fire("ledger.fsync.after")
            self._unsynced = 0
            self._oldest_unsynced = None
        self._synced_num = self._last_appended

    def sync(self) -> None:
        """Force-fsync any group-commit window still open."""
        with self._io_lock:
            self._sync_locked("forced")

    @property
    def synced_height(self) -> int:
        """Highest block number known durable + 1 (mirrors ``height``
        for the appended side) — the commit-engine postmortem reads
        appended vs synced vs applied off these watermarks."""
        return self._synced_num + 1

    def ensure_synced(self, num: int) -> None:
        """Durability fence: make every block up to ``num`` durable
        before returning.  The async apply engine's applier calls this
        in front of each state-DB apply so the durable savepoint can
        never get ahead of the block files; when the group-commit
        window already closed past ``num`` this is one lock op."""
        with self._io_lock:
            if num <= self._synced_num:
                return
            self._sync_locked("apply")

    def close(self):
        self.sync()
        self._fh.close()
        self._rd.close()
        self._wal.close()
        # the last connection to close writes back whatever is left
        # and removes the ``-wal``
        self._idx.close()
