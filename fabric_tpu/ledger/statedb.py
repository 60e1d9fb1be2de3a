"""Versioned state database: SPI + memory and sqlite backends.

Analog of the reference's statedb layer
(core/ledger/kvledger/txmgmt/statedb/statedb.go:36-76 ``VersionedDB``):
keyed (namespace, key) → (value, metadata, version), bulk reads, range
scans, savepoints.  Two backends mirror the reference's split:

* ``MemVersionedDB`` — in-process dict (test/bench fixture, the analog
  of statedb's mock+leveldb-in-memory usage);
* ``SqliteVersionedDB`` — durable embedded store (the goleveldb
  analog); rich JSON queries via sqlite's json functions stand in for
  the CouchDB backend (statecouchdb) without an external service —
  the reference itself documents CouchDB as a throughput liability
  (docs/source/performance.md:180-186).

The member on the commit path is ``get_versions_cols``: one gather a
block of the committed versions of its unique keys, as two arrays that
the validator's ``state_fill`` compares the block's reads with
(``peer/validator.py`` ``_flat_ver_ok``; ``state/residency.py`` gathers
its misses the same way).  ``get_versions_bulk`` is its dict form, kept
for the validator's host lane (``_committed_versions``; the reference
bulk-preload: txmgmt/validation/validator.go:27-78).
"""

from __future__ import annotations

import json
import sqlite3
import threading
import time
from bisect import bisect_left
from contextlib import contextmanager
from dataclasses import dataclass
from itertools import chain

from fabric_tpu.ledger.sqlrows import row_statements
from fabric_tpu.ledger.walcheckpoint import WalCheckpoint, writer_pragmas

Version = tuple[int, int]


@dataclass
class VersionedValue:
    value: bytes | None
    metadata: bytes | None
    version: Version


class UpdateBatch:
    """Accumulated writes of a block (analog statedb.UpdateBatch)."""

    def __init__(self):
        self.updates: dict = {}  # (ns, key) -> VersionedValue (value None = delete)
        self.has_meta = False    # any entry carries key metadata (SBE)

    def put(self, ns: str, key: str, value: bytes | None, version: Version, metadata: bytes | None = None):
        if metadata:
            self.has_meta = True
        self.updates[(ns, key)] = VersionedValue(value, metadata, version)

    def delete(self, ns: str, key: str, version: Version):
        self.put(ns, key, None, version)

    def items(self):
        return self.updates.items()

    def touches_namespace(self, ns: str) -> bool:
        """True when any entry writes ``ns`` — the lifecycle-barrier
        and post-commit scans use this instead of walking (and, for
        the columnar form, materializing) the full update dict."""
        return any(k[0] == ns for k in self.updates)

    @classmethod
    def merged(cls, batches):
        """One overlay view over a CHAIN of in-flight predecessor
        batches, oldest first — the depth-N commit pipeline's launch
        overlay (peer/pipeline.py).  Key resolution is newest-wins
        (``dict.update`` in chain order: exactly the value the LAST
        in-flight apply will land, so an overridden read equals a
        serialized read), ``has_meta`` is the union (a key-metadata
        write anywhere in the window must keep the successor's SBE
        machinery engaged), and iteration covers every key any
        predecessor touched (the lifecycle-write veto and range
        re-execution walk the whole window).

        Returns None for an empty chain and the batch ITSELF for a
        singleton — the depth-2 fast path stays pointer-identical to
        the single-overlay behavior every existing test pins."""
        batches = [b for b in batches if b is not None]
        if not batches:
            return None
        if len(batches) == 1:
            return batches[0]
        out = cls()
        for b in batches:
            out.updates.update(b.updates)
            if b.has_meta:
                out.has_meta = True
        return out


class ColumnarUpdateBatch(UpdateBatch):
    """Columnar UpdateBatch built straight off the validator's flat
    write slabs — no per-key Python tuples or VersionedValue objects
    on the commit path.

    Rows live in numpy arrays in FINAL APPLY ORDER (the concatenation,
    tx by tx, of each valid tx's (ns, key)-sorted writes — exactly the
    store order of ``_build_updates_flat``); key/namespace strings are
    indices into the block's shared unique-key table, and values are
    offset/length spans over the shared ``blob``.  The classic dict
    form stays available through the lazy ``updates`` property
    (identical content AND insertion order, so every overlay consumer
    — launch overlays, ``merged()``, the mem backend — behaves
    byte-for-byte like the dict batch), while
    ``SqliteVersionedDB.apply_updates`` consumes the slabs directly:
    one statement per namespace, zero-copy memoryview value slices.

    ``put``/``delete`` after construction (the pvt hashed-write phase,
    BTL purge) land in a small ``_extra`` override dict that shadows
    the slab rows everywhere.
    """

    def __init__(self, block_num: int, ns_names: list, ukeys: list,
                 ns_of, row_uid, row_del, row_voff, row_vlen,
                 row_txnum, blob: bytes, row_meta: list | None = None):
        # no super().__init__: ``updates`` is a lazy property here
        self.block_num = block_num
        self.ns_names = ns_names
        self.ukeys = ukeys
        self.ns_of = ns_of          # [n_keys] uid -> ns index
        self.row_uid = row_uid      # [R] apply-ordered key ids
        self.row_del = row_del      # [R] bool
        self.row_voff = row_voff    # [R] value span over blob
        self.row_vlen = row_vlen
        self.row_txnum = row_txnum  # [R] tx num (version minor)
        self.blob = blob
        # [R] the metadata each row stores (bytes, or None: none, or
        # cleared), or None where no row of the block carries any: a
        # value write carries the key's metadata along, a metadata
        # write replaces it
        self.row_meta = row_meta if row_meta and any(row_meta) else None
        self.has_meta = self.row_meta is not None
        self._extra: dict = {}      # post-build overrides
        self._updates: dict | None = None

    @property
    def updates(self):
        u = self._updates
        if u is None:
            # build into a local and publish last: readers on other
            # threads (the background applier vs. an overlay read) may
            # materialize concurrently — both build the same dict and
            # the single attribute store keeps it race-free
            u = self._materialize()
            self._updates = u
        return u

    def _materialize(self) -> dict:
        d: dict = {}
        ns_names, ukeys, ns_of = self.ns_names, self.ukeys, self.ns_of
        blob, bn = self.blob, self.block_num
        uid_l = self.row_uid.tolist()
        del_l = self.row_del.tolist()
        vo_l = self.row_voff.tolist()
        vl_l = self.row_vlen.tolist()
        tx_l = self.row_txnum.tolist()
        meta = self.row_meta or [None] * len(uid_l)
        for r, uid in enumerate(uid_l):
            if del_l[r]:
                val = md = None
            else:
                vo = vo_l[r]
                val, md = blob[vo:vo + vl_l[r]], meta[r]
            d[(ns_names[ns_of[uid]], ukeys[uid])] = VersionedValue(
                val, md, (bn, tx_l[r])
            )
        d.update(self._extra)
        return d

    def put(self, ns, key, value, version, metadata=None):
        if metadata:
            self.has_meta = True
        vv = VersionedValue(value, metadata, version)
        self._extra[(ns, key)] = vv
        if self._updates is not None:
            self._updates[(ns, key)] = vv

    def touches_namespace(self, ns: str) -> bool:
        if any(k[0] == ns for k in self._extra):
            return True
        try:
            idx = self.ns_names.index(ns)
        except ValueError:
            return False
        if not len(self.row_uid):
            return False
        import numpy as np

        return bool(np.any(np.asarray(self.ns_of)[self.row_uid] == idx))

    def sqlite_columns(self):
        """→ yields ``(deletes, rows)`` per namespace for the sqlite
        block path: ``deletes`` = [(ns, key)], ``rows`` = the table's
        six columns with zero-copy memoryview value slices.  Per-key
        last-wins dedupe (a later tx's write of the same key shadows
        the earlier row, exactly like the dict build: no key comes
        twice, in one list or across the two), and rows
        shadowed by ``_extra`` overrides are skipped — the caller
        applies the extras through the classic per-key path."""
        last: dict = {}  # uid -> last row index
        for r, uid in enumerate(self.row_uid.tolist()):
            last[uid] = r
        extras = self._extra
        ns_names, ukeys, ns_of = self.ns_names, self.ukeys, self.ns_of
        mv = memoryview(self.blob)
        bn = self.block_num
        meta = self.row_meta
        per_ns_del: dict = {}
        per_ns_row: dict = {}
        for uid, r in last.items():
            ns = ns_names[ns_of[uid]]
            key = ukeys[uid]
            if extras and (ns, key) in extras:
                continue
            if self.row_del[r]:
                per_ns_del.setdefault(ns, []).append((ns, key))
            else:
                vo = int(self.row_voff[r])
                per_ns_row.setdefault(ns, []).append(
                    (ns, key, mv[vo:vo + int(self.row_vlen[r])],
                     meta[r] if meta else None,
                     bn, int(self.row_txnum[r]))
                )
        for ns in sorted(set(per_ns_del) | set(per_ns_row)):
            yield per_ns_del.get(ns, ()), per_ns_row.get(ns, ())

    def extra_items(self):
        return self._extra.items()


class VersionedDB:
    """SPI (statedb.go:36-76)."""

    # True when the backend persists across process crashes — the
    # kvledger uses this to keep the block store's durability AHEAD of
    # the state savepoint (a durable savepoint past the block files
    # would break crash recovery's replay-forward assumption)
    durable: bool = True

    def open(self) -> None: ...
    def close(self) -> None: ...

    def get_state(self, ns: str, key: str) -> VersionedValue | None:
        raise NotImplementedError

    def get_version(self, ns: str, key: str) -> Version | None:
        vv = self.get_state(ns, key)
        return vv.version if vv else None

    def get_versions_bulk(self, keys: list[tuple[str, str]]) -> dict:
        """{(ns, key): Version} for present keys — the block-level
        gather used by MVCC preparation."""
        out = {}
        for ns, key in keys:
            v = self.get_version(ns, key)
            if v is not None:
                out[(ns, key)] = v
        return out

    def get_versions_cols(self, keys: list[tuple[str, str]], meta=None):
        """Column form of :meth:`get_versions_bulk` for the validator's
        ``state_fill`` hot path: → ``(present [U] bool, vers [U, 2]
        uint32)`` numpy arrays positionally aligned with ``keys``.  The
        dict round-trip of ``get_versions_bulk`` (build a dict, then
        re-walk every key to probe it) cost a second Python pass over
        every unique read key per block; backends override this with a
        single fused pass.

        ``meta``: a ``[U]`` bool array that flags the keys whose
        ``metadata`` is wanted too (the keys the block writes: a
        key-level endorsement policy lives there).  Given, the answer
        has a third member, a list aligned with ``keys``: the flagged
        key's metadata bytes, None where it has none, is absent or was
        not flagged."""
        import numpy as np

        U = len(keys)
        present = np.zeros(U, bool)
        vers = np.zeros((U, 2), np.uint32)
        got = self.get_versions_bulk(keys)
        if got:
            for i, k in enumerate(keys):
                v = got.get(k)
                if v is not None:
                    present[i] = True
                    vers[i] = v
        if meta is None:
            return present, vers
        metas = [None] * U
        for i in np.flatnonzero(meta).tolist():
            vv = self.get_state(*keys[i])
            if vv is not None:
                metas[i] = vv.metadata or None
        return present, vers, metas

    def iter_all(self):
        """Yield ((ns, key), VersionedValue) over the WHOLE state in
        (ns, key) order — deterministic for snapshot hashing
        (kvledger/snapshot.go export ordering)."""
        raise NotImplementedError

    def get_state_range(self, ns: str, start: str, end: str, limit: int = 0):
        """Yield (key, VersionedValue) for start <= key < end in key
        order ('' end = unbounded)."""
        raise NotImplementedError

    def execute_query(self, ns: str, query: dict, limit: int = 0):
        raise NotImplementedError("rich queries unsupported by this backend")

    def apply_updates(self, batch: UpdateBatch, savepoint: Version | None) -> None:
        raise NotImplementedError

    def savepoint(self) -> Version | None:
        raise NotImplementedError

    def checkpoint_if_due(self) -> None:
        """Write back, where the backend keeps a write-ahead log, what
        a group of blocks put into it: ``KVLedger.commit_block`` calls
        this on the committing thread once the block is out of its
        hands.  Nothing to do for a backend without one."""


class MemVersionedDB(VersionedDB):
    """In-memory backend.  Range/query iteration takes a lock against
    concurrent apply_updates: the commit pipeline overlaps the
    predecessor's state commit (committer thread) with the next
    block's launch, whose range re-execution walks these structures —
    per-key read SEMANTICS under that overlap are handled by the
    validator's overlay, the lock only guards the dict/cache
    iteration itself."""

    durable = False  # dies with the process: always replay-recovered

    def __init__(self):
        import threading

        self._data: dict = {}  # (ns,key) -> VersionedValue
        self._sorted_cache: dict = {}  # ns -> sorted key list (invalidated on write)
        self._savepoint: Version | None = None
        self._lock = threading.Lock()
        # number of keys carrying non-null metadata (key-level
        # endorsement policies): the validator's SBE gate — blocks on a
        # channel with NO key-level policies anywhere skip the
        # metadata bulk-lookup entirely
        self.meta_count = 0

    def get_state(self, ns, key):
        return self._data.get((ns, key))  # dict.get is atomic under the GIL

    def get_versions_cols(self, keys, meta=None):
        """Single fused pass (no intermediate dict): each lookup is one
        GIL-atomic ``dict.get`` — same concurrent-apply semantics as
        ``get_state``, the validator's overlay handles read ordering."""
        import numpy as np

        U = len(keys)
        present = np.zeros(U, bool)
        vers = np.zeros((U, 2), np.uint32)
        metas = None if meta is None else [None] * U
        get = self._data.get
        for i, k in enumerate(keys):
            vv = get(k)
            if vv is not None:
                present[i] = True
                vers[i] = vv.version
                if metas is not None and meta[i]:
                    metas[i] = vv.metadata or None
        return (present, vers) if metas is None else (present, vers, metas)

    def _sorted_keys(self, ns):
        keys = self._sorted_cache.get(ns)
        if keys is None:
            keys = sorted(k for (n, k) in self._data if n == ns)
            self._sorted_cache[ns] = keys
        return keys

    def iter_all(self):
        with self._lock:
            rows = [(k, self._data[k]) for k in sorted(self._data)]
        yield from rows

    def get_state_range(self, ns, start, end, limit=0):
        with self._lock:  # materialize under the lock, then yield
            keys = self._sorted_keys(ns)
            i = bisect_left(keys, start)
            rows = []
            while i < len(keys) and (not end or keys[i] < end):
                vv = self._data.get((ns, keys[i]))
                if vv is not None:
                    rows.append((keys[i], vv))
                i += 1
                if limit and len(rows) >= limit:
                    break
        yield from rows

    def execute_query(self, ns, query, limit=0):
        """CouchDB-selector-style equality matching over JSON values."""
        sel = query.get("selector", {})
        with self._lock:  # copy only the key list under the lock
            keys = list(self._sorted_keys(ns))
        n = 0
        for key in keys:
            vv = self._data.get((ns, key))  # atomic under the GIL
            if vv is None or vv.value is None:
                continue
            try:
                doc = json.loads(vv.value)
            except (ValueError, UnicodeDecodeError):
                continue
            if all(doc.get(f) == want for f, want in sel.items()):
                yield key, vv
                n += 1
                if limit and n >= limit:
                    return

    def apply_updates(self, batch, savepoint):
        with self._lock:
            for (ns, key), vv in batch.items():
                old = self._data.get((ns, key))
                if old is not None and old.metadata:
                    self.meta_count -= 1
                if vv.value is None:
                    self._data.pop((ns, key), None)
                else:
                    if vv.metadata:
                        self.meta_count += 1
                    self._data[(ns, key)] = vv
                self._sorted_cache.pop(ns, None)
        if savepoint is not None:
            self._savepoint = savepoint

    def savepoint(self):
        return self._savepoint


# The two statements of ``SqliteVersionedDB.apply_updates``' block path,
# as ``sqlrows.row_statements`` takes them.  The delete names its rows
# through the primary-key index and removes them by rowid: ``WHERE (ns,
# key) IN (VALUES ...)`` with two rows or more is planned as a scan of
# the whole table.  Its text STARTS with ``DELETE``: the ``sqlite3``
# module opens the block's transaction before a statement whose first
# word is INSERT, UPDATE, DELETE or REPLACE and before no other, so a
# ``WITH ... DELETE`` that came first in a block would commit alone.
_UPSERT_ROWS = dict(
    head="INSERT INTO state VALUES", width=6,
    tail=" ON CONFLICT(ns, key) DO UPDATE SET value=excluded.value,"
         " metadata=excluded.metadata, block=excluded.block,"
         " txnum=excluded.txnum",
)
# how many of a block's keys carry metadata before the block is
# applied: what ``meta_count`` loses to the block's upsert and delete
_COUNT_META_ROWS = dict(
    head="WITH d(ns, key) AS (VALUES", width=2,
    tail=") SELECT COUNT(*) FROM d CROSS JOIN state AS s"
         " ON s.ns = d.ns AND s.key = d.key"
         " WHERE s.metadata IS NOT NULL AND s.metadata != x''",
)
_DELETE_ROWS = dict(
    head="DELETE FROM state WHERE rowid IN (WITH d(ns, key) AS (VALUES",
    width=2,
    tail=") SELECT s.rowid FROM d CROSS JOIN state AS s"
         " ON s.ns = d.ns AND s.key = d.key)",
)


class SqliteVersionedDB(VersionedDB):
    """Durable backend over sqlite (WAL mode).

    Two connections to the one file that read and write, as the block
    store's index has (``BlockStore._idx`` / ``_rd``).  ``_conn``
    belongs to whoever writes: ``apply_updates`` (the applier thread,
    under the async engine), and the three iterators, whose cursors live
    as long as their caller walks them (an open cursor on a WAL reader
    pins its snapshot and stops checkpoints: see ``BlockStore._rd_rows``).
    ``_rd`` (``query_only``) answers the lookups: the block's version
    gather, ``get_state``, ``savepoint``.  In WAL mode a read on it
    never waits for the writer's transaction nor for its ``commit()``,
    and sees what was committed before the read began; what is
    submitted and not yet committed the async engine's pending overlay
    supplies (``ledger/committer.py``).  ``_rd_lock`` is for the
    readers among themselves: one transaction at a time on the
    connection, and two threads on one statement text would share its
    prepared statement.  A third connection (``_wal``) only writes the
    WAL back (``checkpoint_if_due``).
    """

    def __init__(self, path: str):
        self.path = path
        self._conn: sqlite3.Connection | None = None
        self._rd: sqlite3.Connection | None = None
        self._wal: WalCheckpoint | None = None
        self._rd_lock = threading.Lock()
        # seconds readers spent WAITING for ``_rd_lock`` (held by
        # another reader for one gather or one lookup).  Only a
        # contended acquire reads the clock; updated while holding the
        # lock.  The engine's ``sf.gather`` span carries the delta.
        self.rd_wait_s = 0.0
        # blocks ``apply_updates`` took by its block path (the others
        # took the per-key loop), and the statements that path sent to
        # ``state`` (one a namespace; two where it also deletes).  Plain
        # integers of the applier thread: ``apply.write`` carries the
        # block's share as ``path`` and ``stmts``.
        self.apply_fast_blocks = 0
        self.apply_statements = 0

    def open(self):
        self._conn = sqlite3.connect(self.path, check_same_thread=False)
        self._conn.execute("PRAGMA journal_mode=WAL")
        self._conn.execute("PRAGMA synchronous=NORMAL")
        writer_pragmas(self._conn)
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS state ("
            " ns TEXT NOT NULL, key TEXT NOT NULL,"
            " value BLOB, metadata BLOB,"
            " block INTEGER NOT NULL, txnum INTEGER NOT NULL,"
            " PRIMARY KEY (ns, key))"
        )
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS savepoint ("
            " id INTEGER PRIMARY KEY CHECK (id = 0),"
            " block INTEGER, txnum INTEGER)"
        )
        self._conn.commit()
        # SBE gate (see MemVersionedDB.meta_count)
        self.meta_count = self._conn.execute(
            "SELECT COUNT(*) FROM state WHERE metadata IS NOT NULL"
            " AND metadata != x''"
        ).fetchone()[0]
        # opened once the schema is committed.  ``isolation_level=None``:
        # the only transactions on it are the ones _reading opens
        self._rd = sqlite3.connect(self.path, check_same_thread=False,
                                   isolation_level=None)
        self._rd.execute("PRAGMA query_only=ON")
        # the write-back's connection of its own: on ``_conn`` a
        # checkpoint from the committer would wait for the applier
        self._wal = WalCheckpoint.own(self.path, "state")

    def close(self):
        # the reader first: the LAST connection to close checkpoints
        # the WAL and removes ``state.db-wal``, and that is the writer
        if self._rd:
            self._rd.close()
            self._rd = None
        if self._wal:
            self._wal.close()
            self._wal = None
        if self._conn:
            self._conn.close()
            self._conn = None

    @contextmanager
    def _rd_locked(self):
        """The read connection, under the readers' lock."""
        lock = self._rd_lock
        if not lock.acquire(False):
            t0 = time.perf_counter()
            lock.acquire()
            self.rd_wait_s += time.perf_counter() - t0
        try:
            yield self._rd
        finally:
            lock.release()

    def _rd_row(self, sql: str, args=()):
        """First row of one statement on the read connection, fetched
        to its end before the lock is released, so no cursor is left
        open on a snapshot."""
        with self._rd_locked() as rd:
            rows = rd.execute(sql, args).fetchall()
        return rows[0] if rows else None

    @contextmanager
    def _reading(self):
        """A cursor on the read connection inside ONE read transaction:
        every statement of the ``with`` body sees the same snapshot and
        the WAL's read lock is taken once (a ``SELECT`` of its own
        takes and drops it, once a key, and slows the writer beside
        it).  The transaction ends with the body, raised or not,
        so the connection never pins a snapshot between calls."""
        with self._rd_locked() as rd:
            cur = rd.cursor()
            cur.execute("BEGIN")
            try:
                yield cur
            finally:
                cur.close()  # resets a statement left mid-way
                rd.execute("COMMIT")

    def get_state(self, ns, key):
        row = self._rd_row(
            "SELECT value, metadata, block, txnum FROM state WHERE ns=? AND key=?",
            (ns, key),
        )
        if row is None:
            return None
        return VersionedValue(row[0], row[1], (row[2], row[3]))

    def get_versions_bulk(self, keys):
        out = {}
        with self._reading() as cur:
            for ns, key in keys:
                row = cur.execute(
                    "SELECT block, txnum FROM state WHERE ns=? AND key=?",
                    (ns, key),
                ).fetchone()
                if row:
                    out[(ns, key)] = (row[0], row[1])
        return out

    def get_versions_cols(self, keys, meta=None):
        """Fused column gather: one cursor, arrays filled in place —
        no per-key dict churn on the state_fill hot path.  A key
        flagged in ``meta`` is asked for its ``metadata`` in the same
        step; the others keep the two-column statement."""
        import numpy as np

        U = len(keys)
        present = np.zeros(U, bool)
        vers = np.zeros((U, 2), np.uint32)
        if meta is None or not meta.any():
            with self._reading() as cur:
                for i, (ns, key) in enumerate(keys):
                    row = cur.execute(
                        "SELECT block, txnum FROM state WHERE ns=? AND key=?",
                        (ns, key),
                    ).fetchone()
                    if row:
                        present[i] = True
                        vers[i] = row
            return (present, vers) if meta is None else (
                present, vers, [None] * U)
        metas = [None] * U
        plain = "SELECT block, txnum FROM state WHERE ns=? AND key=?"
        with_md = ("SELECT block, txnum, metadata FROM state"
                   " WHERE ns=? AND key=?")
        with self._reading() as cur:
            for i, (k, want) in enumerate(zip(keys, meta.tolist())):
                row = cur.execute(with_md if want else plain, k).fetchone()
                if row:
                    present[i] = True
                    vers[i] = row[:2]
                    if want:
                        metas[i] = row[2] or None
        return present, vers, metas

    def iter_all(self):
        q = ("SELECT ns, key, value, metadata, block, txnum FROM state "
             "ORDER BY ns, key")
        for ns, key, value, md, blk, txn in self._conn.execute(q):
            yield (ns, key), VersionedValue(value, md, (blk, txn))

    def get_state_range(self, ns, start, end, limit=0):
        q = "SELECT key, value, metadata, block, txnum FROM state WHERE ns=? AND key>=?"
        args = [ns, start]
        if end:
            q += " AND key<?"
            args.append(end)
        q += " ORDER BY key"
        if limit:
            q += f" LIMIT {int(limit)}"
        for key, value, md, blk, txn in self._conn.execute(q, args):
            yield key, VersionedValue(value, md, (blk, txn))

    def execute_query(self, ns, query, limit=0):
        """Rich queries via sqlite JSON1 (statecouchdb analog)."""
        sel = query.get("selector", {})
        clauses, args = [], [ns]
        for fld, want in sel.items():
            clauses.append("json_extract(value, ?) = ?")
            args.append(f"$.{fld}")
            args.append(want)
        q = "SELECT key, value, metadata, block, txnum FROM state WHERE ns=?"
        if clauses:
            q += " AND " + " AND ".join(clauses)
        q += " AND json_valid(value) ORDER BY key"
        if limit:
            q += f" LIMIT {int(limit)}"
        for key, value, md, blk, txn in self._conn.execute(q, args):
            yield key, VersionedValue(value, md, (blk, txn))

    def apply_updates(self, batch, savepoint):
        """One transaction a block: the rows, the savepoint, ``commit()``.

        A columnar batch goes as one statement a namespace
        (``sqlrows``): a multi-row upsert that rewrites a key that
        exists where it lies (sqlite >= 3.24), its ``metadata`` column
        with the rest (a value write carries the key's metadata along,
        a metadata write replaces it: the validator's
        ``_build_updates_flat``).  ``INSERT OR REPLACE`` would delete
        the row and insert a new one at the table's end, under a new
        rowid, and so dirty the old leaf, a new one and the key's leaf
        of the primary-key index; the update arm dirties one.  A reader
        sees the same ``value, metadata, block, txnum`` either way, and
        nothing reads a rowid.  ``meta_count`` stays exact: on a DB
        that holds metadata, one more statement a namespace counts the
        block's keys that carry some before they are written (a read;
        ``apply_statements`` counts the statements that write).
        Everything else (an ``UpdateBatch``, a columnar batch's
        ``_extra`` overrides) takes the per-key loop with its per-key
        metadata probe."""
        cur = self._conn.cursor()
        # meta_count == 0 ⇒ no existing row carries metadata, so the
        # decrement probe is skippable (keeps the common no-SBE channel
        # free of it)
        track = self.meta_count > 0
        rows_written = 0
        if isinstance(batch, ColumnarUpdateBatch):
            # values stay zero-copy slices of the validator's slab; no
            # dict materialization, no VersionedValue churn
            conn = self._conn
            for dels, rows in batch.sqlite_columns():
                if track:
                    for sql, params in row_statements(
                            conn, [r[:2] for r in chain(dels, rows)],
                            **_COUNT_META_ROWS):
                        self.meta_count -= cur.execute(
                            sql, params).fetchone()[0]
                if batch.row_meta is not None:
                    self.meta_count += sum(1 for r in rows if r[3])
                rows_written += len(dels) + len(rows)
                for sql, params in chain(
                    row_statements(conn, dels, **_DELETE_ROWS),
                    row_statements(conn, rows, **_UPSERT_ROWS),
                ):
                    cur.execute(sql, params)
                    self.apply_statements += 1
            self.apply_fast_blocks += 1
            items = batch.extra_items()
        else:
            items = batch.items()
        for (ns, key), vv in items:
            rows_written += 1
            if track:
                row = cur.execute(
                    "SELECT metadata FROM state WHERE ns=? AND key=?",
                    (ns, key),
                ).fetchone()
                if row is not None and row[0]:
                    self.meta_count -= 1
            if vv.value is None:
                cur.execute("DELETE FROM state WHERE ns=? AND key=?", (ns, key))
            else:
                if vv.metadata:
                    self.meta_count += 1
                cur.execute(
                    "INSERT OR REPLACE INTO state VALUES (?,?,?,?,?,?)",
                    (ns, key, vv.value, vv.metadata, vv.version[0], vv.version[1]),
                )
        if savepoint is not None:
            cur.execute(
                "INSERT OR REPLACE INTO savepoint VALUES (0,?,?)",
                (savepoint[0], savepoint[1]),
            )
        self._conn.commit()
        self._wal.note(rows_written)
        self._wal.backstop()

    def checkpoint_if_due(self):
        """``state.db``'s WAL, written back once a group of blocks
        wrote ``CHECKPOINT_ROWS`` rows, or where the last try left
        frames behind (``walcheckpoint``): on the calling thread,
        through the write-back's own connection, outside every
        transaction of the writer."""
        self._wal.if_due()

    def savepoint(self):
        row = self._rd_row("SELECT block, txnum FROM savepoint WHERE id=0")
        return (row[0], row[1]) if row else None
