"""When a store's sqlite WAL is written back into its database file.

Three stores of a ledger write a block's rows in one transaction each:
the block store's txid index, the state DB and the history DB.  Left to
sqlite, each writer connection holds 2 MB of pages, so a large block's
dirty pages spill to the WAL inside its statement, and every 1,000 WAL
pages sqlite writes them all a second time, into the database file,
inside whichever ``commit()`` crosses the line: on the thread that
writes, inside the block's own span.

Here each writer holds a block's pages (:func:`writer_pragmas`), and the
WAL is written back once a group of blocks by :class:`WalCheckpoint`:
a ``wal_checkpoint(PASSIVE)`` made outside every block's transaction by
a thread of the pipeline that has room for it (``KVLedger``: the
committer once the block is out of its hands, or for the state and
history DBs the applier once its apply is published, whichever does
less of the ledger's work a block), or by the writer itself where
nobody did for a whole multiple of the bound.  A checkpoint copies only
committed frames and syncs as sqlite's own does, so what a transaction
guarantees does not change.  Beside a writer that never rests the log
does not start over after a group checkpoint (the frames the writer
committed meanwhile are not yet written back when its next transaction
begins): it grows to sqlite's own threshold, whose checkpoint inside a
``commit()`` then copies what is left and lets the log start over.
"""

from __future__ import annotations

import sqlite3
import threading

from fabric_tpu.observe.tracer import global_tracer

#: page cache of a writer connection, in KiB.  Sized to what it has to
#: hold, not to the database: the pages ONE transaction dirties (about
#: one leaf a row once the tree has a few thousand leaves, plus the
#: interior pages above them) for the largest block the orderer's
#: ``BatchSize`` cuts, so that no dirty page spills to the WAL inside
#: the transaction and is written a second time at its commit.  A
#: 1000-tx block dirties about 4 MB of the txid index.
CACHE_KIB = 16 * 1024
#: rows written since the last WAL checkpoint past which
#: ``WalCheckpoint.if_due`` makes the next one.  Rows, not blocks: pages
#: follow rows (a leaf a row on a large tree, so a WAL of about 32 MB).
#: It bounds the WAL's size and nothing else.
CHECKPOINT_ROWS = 8000
#: the backstop, as a multiple of the bound: sqlite's own
#: ``wal_autocheckpoint`` in pages, and the rows past which the writer
#: checkpoints by itself because nobody called ``if_due``
BACKSTOP_FACTOR = 4


def writer_pragmas(conn: sqlite3.Connection, rows: int = CHECKPOINT_ROWS) -> None:
    """A writer connection that holds a block's pages and leaves the
    WAL's write-back to :class:`WalCheckpoint`: sqlite's own threshold,
    which fires inside a commit, stays as the backstop."""
    conn.execute(f"PRAGMA cache_size=-{CACHE_KIB}")
    conn.execute(f"PRAGMA wal_autocheckpoint={BACKSTOP_FACTOR * rows}")


class WalCheckpoint:
    """One store's WAL write-back: the trigger, the retry and the
    checkpoint.

    ``conn`` makes the checkpoints, under ``lock``; where the writer's
    own connection is it (the block store, whose writer and committer
    are one thread), ``lock`` is the writer's.  :meth:`own` opens a
    connection for nothing else, so a checkpoint on the committing
    thread never waits for the writer's statement on another thread.

    ``note(rows)`` is the writer's, once a transaction has committed;
    ``written`` counts every row it noted and is written by no other
    thread.  ``if_due`` (trigger ``group``) checkpoints once ``bound``
    rows were noted since the last checkpoint began, or where the last
    one left frames behind: PASSIVE waits for nobody and writes back
    what no reader's snapshot still needs (``busy``), and the WAL starts
    over only once a try went through.  ``backstop`` checkpoints where
    ``BACKSTOP_FACTOR`` times the bound went by without one, ``close``
    where anything was written since the last.

    Each checkpoint adds one to the counter ``counter`` with ``trigger``
    and ``labels``, and is the span ``span`` (``labels``, ``rows``,
    ``frames``, ``moved``, ``busy``) under the calling thread's block
    root: no part of the block's own commit."""

    def __init__(self, conn: sqlite3.Connection, lock, *, bound: int,
                 counter: tuple[str, str], span: str, **labels):
        self._conn = conn
        self._lock = lock
        self._owns = False
        self.bound = bound
        self._counter = counter
        self._ctr = None  # lazy registry counter
        self._span = span
        self._labels = labels
        self.written = 0
        # ``written`` when the last checkpoint began, and whether it
        # left frames behind: both written under ``lock``
        self._at = 0
        self.retry = False

    @classmethod
    def own(cls, path: str, db: str) -> WalCheckpoint:
        """The write-back of a ledger DB (``db``: ``state`` or
        ``history``) through a connection of its own to ``path``, closed
        by :meth:`close`: the span ``ledger.checkpoint`` and the counter
        ``ledger_wal_checkpoints_total{db, trigger}``."""
        conn = sqlite3.connect(path, check_same_thread=False,
                               isolation_level=None)
        conn.execute("PRAGMA synchronous=NORMAL")
        wal = cls(conn, threading.Lock(), bound=CHECKPOINT_ROWS,
                  counter=("ledger_wal_checkpoints_total",
                           "WAL checkpoints of the state and history DBs"
                           " by db and trigger"),
                  span="ledger.checkpoint", db=db)
        wal._owns = True
        return wal

    @property
    def rows(self) -> int:
        """Rows noted since the last checkpoint began."""
        return self.written - self._at

    def note(self, rows: int) -> None:
        # the writer's thread alone adds; a checkpoint only reads
        self.written += rows  # fabtpu: noqa(FT018)

    def if_due(self) -> None:
        if self._due():
            self._checkpoint("group")

    def _due(self) -> bool:
        return self.retry or self.rows >= self.bound

    def backstop(self) -> None:
        if self.rows >= BACKSTOP_FACTOR * self.bound:
            self._checkpoint("backstop")

    def close(self) -> None:
        """The last checkpoint, where anything is left; then the
        connection, where it is this object's own."""
        if self.rows or self.retry:
            self._checkpoint("close")
        if self._owns:
            self._conn.close()

    def _checkpoint(self, trigger: str) -> None:
        tracer = global_tracer()
        cur = tracer.current()
        with self._lock:
            if trigger == "group" and not self._due():
                return  # another thread's went through meanwhile
            ctr = self._ctr
            if ctr is None:
                from fabric_tpu.ops_metrics import global_registry

                ctr = self._ctr = global_registry().counter(*self._counter)
            ctr.add(1, trigger=trigger, **self._labels)
            at = self.written
            with tracer.span(self._span,
                             parent=cur.root if cur is not None else None,
                             **self._labels, rows=at - self._at) as csp:
                busy, frames, moved = self._conn.execute(
                    "PRAGMA wal_checkpoint(PASSIVE)").fetchone()
                self.retry = bool(busy) or moved < frames
                self._at = at
                if csp is not None:
                    csp.attrs.update(frames=frames, moved=moved,
                                     busy=int(self.retry))
