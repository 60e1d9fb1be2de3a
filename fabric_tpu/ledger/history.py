"""History database: key → commit positions (analog
core/ledger/kvledger/history — GetHistoryForKey support)."""

from __future__ import annotations

import sqlite3

from fabric_tpu.ledger.sqlrows import row_statements
from fabric_tpu.ledger.walcheckpoint import WalCheckpoint, writer_pragmas


class HistoryDB:
    def __init__(self, path: str):
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute("PRAGMA journal_mode=WAL")
        # history is DERIVED state: the kvledger recovery path replays
        # it from stored blocks (savepoint-gated), so a lost WAL tail
        # on crash self-heals — no per-commit fsync.  NORMAL, not OFF:
        # OFF can corrupt the DB file itself on power loss, and there
        # is no drop-and-rebuild path on open
        self._conn.execute("PRAGMA synchronous=NORMAL")
        writer_pragmas(self._conn)
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS hist ("
            " ns TEXT, key TEXT, block INTEGER, txnum INTEGER,"
            " PRIMARY KEY (ns, key, block, txnum))"
        )
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS savepoint ("
            " id INTEGER PRIMARY KEY CHECK (id = 0), block INTEGER)"
        )
        # the write-back's connection of its own: on ``_conn`` a
        # checkpoint from the committer would wait for the applier
        self._wal = WalCheckpoint.own(path, "history")

    def commit_block(self, block_num: int,
                     writes: list[tuple[str, str, int]]) -> tuple[int, int]:
        """writes: [(ns, key, txnum)] for VALID txs of the block, as one
        statement (``sqlrows``; sqlite >= 3.7.11 takes the multi-row
        ``VALUES``).  → ``(rows, statements)``, what the applier's
        ``apply.history`` span carries."""
        rows = [(ns, key, block_num, txnum) for ns, key, txnum in writes]
        stmts = 0
        for sql, params in row_statements(
            self._conn, rows,
            head="INSERT OR REPLACE INTO hist VALUES", width=4,
        ):
            self._conn.execute(sql, params)
            stmts += 1
        self._conn.execute(
            "INSERT OR REPLACE INTO savepoint VALUES (0,?)", (block_num,)
        )
        self._conn.commit()
        self._wal.note(len(rows))
        self._wal.backstop()
        return len(rows), stmts

    def checkpoint_if_due(self) -> None:
        """``history.db``'s WAL, written back as ``state.db``'s is
        (``SqliteVersionedDB.checkpoint_if_due``)."""
        self._wal.if_due()

    def get_history_for_key(self, ns: str, key: str):
        """Yield (block, txnum) newest-first (like the reference's
        history iterator)."""
        yield from self._conn.execute(
            "SELECT block, txnum FROM hist WHERE ns=? AND key=?"
            " ORDER BY block DESC, txnum DESC",
            (ns, key),
        )

    def savepoint(self) -> int | None:
        row = self._conn.execute("SELECT block FROM savepoint WHERE id=0").fetchone()
        return row[0] if row else None

    def close(self):
        self._wal.close()
        self._conn.close()
