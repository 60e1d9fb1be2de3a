"""The page traffic of the state and history DBs: a writer cache that
holds a block's pages, one WAL checkpoint a group of blocks made by the
committing thread through a connection of its own, a backstop where
nobody calls, and recovery from files whose WAL was not yet written
back.  The three stores of a ledger (the txid index, ``state.db``,
``history.db``) share the trigger-and-retry rule of ``walcheckpoint``."""

import os
import shutil
import sqlite3
import threading

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.ledger import blockstore, walcheckpoint
from fabric_tpu.ledger.blockstore import BlockStore
from fabric_tpu.ledger.history import HistoryDB
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import SqliteVersionedDB, UpdateBatch
from fabric_tpu.observe import global_tracer
from fabric_tpu.ops_metrics import global_registry
from tests.test_ledger_storage import _block

TRIGGERS = ("group", "backstop", "close")


def _checkpoints(db: str) -> dict:
    ctr = global_registry().counter(
        "ledger_wal_checkpoints_total",
        "WAL checkpoints of the state and history DBs by db and trigger")
    return {t: ctr.value(db=db, trigger=t) for t in TRIGGERS}


def _since(db: str, before: dict) -> dict:
    return {t: int(v - before[t]) for t, v in _checkpoints(db).items()}


def _wal(path: str) -> str:
    return path + "-wal"


def _open(kind: str, path: str):
    if kind == "state":
        db = SqliteVersionedDB(path)
        db.open()
        return db
    return HistoryDB(path)


def _rows(db, num: int, n: int) -> None:
    """Block ``num`` writes ``n`` fresh rows into ``db``, in its own
    transaction, as the applier does."""
    if isinstance(db, SqliteVersionedDB):
        batch = UpdateBatch()
        for i in range(n):
            batch.put("ns", f"k{num}-{i}", b"v" * 20, (num, i))
        db.apply_updates(batch, (num, 0))
    else:
        db.commit_block(num, [("ns", f"k{num}-{i}", i) for i in range(n)])


@pytest.fixture
def armed():
    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=64)
    yield tracer
    tracer.configure(ring_blocks=was)


# ---------------------------------------------------------------------------
# the writer cache


@pytest.mark.parametrize("kind", ["state", "history"])
def test_the_writer_holds_a_blocks_pages_and_the_reader_keeps_sqlites_default(
        tmp_path, kind):
    db = _open(kind, str(tmp_path / f"{kind}.db"))
    try:
        w = db._conn
        # one constant for the three writers: the index's 16 MB
        assert blockstore._IDX_CACHE_KIB == walcheckpoint.CACHE_KIB == 16384
        assert w.execute("PRAGMA cache_size").fetchone()[0] == -16384
        assert w.execute("PRAGMA wal_autocheckpoint").fetchone()[0] == (
            blockstore._BACKSTOP_FACTOR * blockstore._CHECKPOINT_ROWS)
        assert w.execute("PRAGMA synchronous").fetchone()[0] == 1  # NORMAL
        # the checkpoint's own connection syncs as the writer does
        ck = db._wal._conn
        assert ck is not w
        assert ck.execute("PRAGMA synchronous").fetchone()[0] == 1
        if kind == "state":
            assert db._rd.execute("PRAGMA cache_size").fetchone()[0] == -2000
            assert db._rd.execute(
                "PRAGMA wal_autocheckpoint").fetchone()[0] == 1000
    finally:
        db.close()


# ---------------------------------------------------------------------------
# one checkpoint a group of blocks, on the committing thread


def _traced_commit(tracer, lg, num, prev, rows):
    """Block ``num`` through ``KVLedger.commit_block`` under a traced
    ``commit``: ``rows`` state puts and as many history rows."""
    blk = _block(num, prev, [b"d%d" % num])
    batch = UpdateBatch()
    for i in range(rows):
        batch.put("ns", f"k{num}-{i}", b"v%d" % num, (num, i))
    hist = [("ns", f"k{num}-{i}", i) for i in range(rows)]
    root = tracer.begin_block(num)
    with tracer.span("commit", parent=root):
        lg.commit_block(blk, bytes([0]), batch, hist)
    tracer.finish_block(root)
    return root, pu.block_header_hash(blk.header)


def _statements(conn) -> list:
    """(thread, statement) of every statement ``conn`` runs from now."""
    seen = []
    conn.set_trace_callback(
        lambda sql: seen.append((threading.current_thread().name, sql)))
    return seen


@pytest.mark.parametrize("engine", [False, True],
                         ids=["serial_apply", "async_engine"])
def test_a_checkpoint_runs_on_the_committing_thread_once_8000_rows_were_written(
        tmp_path, armed, engine):
    """Blocks of 1,000 rows: no ``group`` checkpoint of ``state.db`` or
    ``history.db`` before 8,000 rows, then one of each, under the
    block's root, on the committing thread (the applier, which writes
    the rows, is the busier of the two here) and through the store's
    own checkpoint connection, never the writer's; ``ledger.checkpoint``
    carries ``db``, ``rows``, ``frames``, ``moved`` and ``busy``, the
    counter ``db`` and ``trigger``."""
    lg = KVLedger(str(tmp_path / "ledger"), async_commit=engine)
    inner = lg.engine._inner if engine else lg.state
    seen = {"state": _statements(inner._wal._conn),
            "history": _statements(lg.history._wal._conn),
            "state_writer": _statements(inner._conn),
            "history_writer": _statements(lg.history._conn)}
    before = {db: _checkpoints(db) for db in ("state", "history")}
    roots, prev, first = [], b"", {}
    me = threading.current_thread().name
    try:
        for num in range(10):
            root, prev = _traced_commit(armed, lg, num, prev, 1000)
            roots.append(root)
            # the async applier has noted the block before the next
            # commit asks: the first checkpoint falls on block 7 or 8
            lg.drain_state()
            for db in ("state", "history"):
                if db not in first and _since(db, before[db])["group"]:
                    first[db] = num
    finally:
        lg.close()
    for db in ("state", "history"):
        assert first[db] in ((7, 8) if engine else (7,)), first
        assert _since(db, before[db]) == {"group": 1, "backstop": 0,
                                          "close": 1}
    spans = [(num, c) for num, r in enumerate(roots) for c in r.children
             if c.name == "ledger.checkpoint"]
    assert sorted((num, c.attrs["db"]) for num, c in spans) == sorted(
        (first[db], db) for db in ("state", "history"))
    for num, ck in spans:
        commit = next(c for c in roots[num].children if c.name == "commit")
        enqueue = next(c for c in commit.children
                       if c.name == "commit.enqueue")
        assert ck.thread == commit.thread == me
        assert enqueue.t1 <= ck.t0 and ck.t1 <= commit.t1
        assert set(ck.attrs) == {"db", "rows", "frames", "moved", "busy"}
        assert ck.attrs["rows"] >= 8000 and ck.attrs["busy"] == 0
        assert ck.attrs["moved"] == ck.attrs["frames"] > 0
    for db in ("state", "history"):
        ran = [(t, s) for t, s in seen[db] if "wal_checkpoint" in s]
        assert ran[0] == (me, "PRAGMA wal_checkpoint(PASSIVE)")
        assert not [s for _, s in seen[f"{db}_writer"]
                    if "wal_checkpoint" in s]


@pytest.mark.parametrize("busier", ["applier", "committer"])
def test_the_thread_with_less_ledger_work_a_block_writes_back(
        tmp_path, armed, busier):
    """Under the apply engine the state and history checkpoints go to
    the one of committer and applier that spends less of the ledger's
    work on a block: made 20 ms slower a block, the applier leaves them
    to the committer, and the committer to the applier, which makes
    them once its apply is published, under the block's root.  The
    index's stays the committer's."""
    lg = KVLedger(str(tmp_path / "ledger"), async_commit=True)
    slow = lg.engine._inner if busier == "applier" else lg.blocks
    name = "apply_updates" if busier == "applier" else "add_block"
    fast = getattr(slow, name)

    def slowed(*a, **kw):
        threading.Event().wait(0.02)
        return fast(*a, **kw)

    setattr(slow, name, slowed)
    roots, prev = [], b""
    try:
        for num in range(10):
            root, prev = _traced_commit(armed, lg, num, prev, 1000)
            roots.append(root)
            lg.drain_state()
    finally:
        lg.close()
    assert lg._applier_writes_back() == (busier == "committer")
    spans = [c for r in roots for c in r.children
             if c.name == "ledger.checkpoint"]
    assert sorted(c.attrs["db"] for c in spans) == ["history", "state"]
    want = (threading.current_thread().name if busier == "applier"
            else "fabtpu-state-applier")
    assert {c.thread for c in spans} == {want}


def test_a_readers_snapshot_makes_it_busy_and_the_next_block_retries(
        tmp_path, armed):
    path = str(tmp_path / "state.db")
    db = _open("state", path)
    before = _checkpoints("state")
    spans = []

    def block(num, n):
        root = armed.begin_block(num)
        with armed.span("commit", parent=root):
            _rows(db, num, n)
            db.checkpoint_if_due()
        armed.finish_block(root)
        spans.extend(c.attrs for c in root.children
                     if c.name == "ledger.checkpoint")

    try:
        block(0, 4000)
        # a reader's snapshot from before block 1: its frames stay
        db._rd.execute("BEGIN")
        assert db._rd.execute("SELECT COUNT(*) FROM state").fetchone() == (
            4000,)
        block(1, 4000)                      # 8,000 rows: due, held back
        assert db._wal.retry and len(spans) == 1
        db._rd.execute("COMMIT")
        block(2, 10)                        # not due: tried again
        assert not db._wal.retry and len(spans) == 2
        block(3, 10)                        # nothing left to try
        assert len(spans) == 2
    finally:
        db.close()
    first, second = spans
    assert first["busy"] == 1 and 0 < first["moved"] < first["frames"]
    assert first["rows"] == 8000
    assert second["busy"] == 0 and second["moved"] == second["frames"]
    assert second["rows"] == 10
    # the WAL started over behind the retry: block 3's frames were
    # written from its start, so the close found a short log
    got = _since("state", before)
    assert got == {"group": 2, "backstop": 0, "close": 1}
    assert not os.path.exists(_wal(path))


@pytest.mark.parametrize("kind", ["state", "history"])
def test_the_backstop_bounds_the_wal_where_nobody_calls(tmp_path, kind):
    """Nobody calls ``checkpoint_if_due``: the writer checkpoints by
    itself once ``BACKSTOP_FACTOR`` times the bound went by, the WAL
    starts over, and its file grows no further."""
    path = str(tmp_path / f"{kind}.db")
    db = _open(kind, path)
    before, sizes = _checkpoints(kind), []
    bound = walcheckpoint.BACKSTOP_FACTOR * walcheckpoint.CHECKPOINT_ROWS
    try:
        for num in range(12):
            _rows(db, num, 4000)
            sizes.append(os.path.getsize(_wal(path)))
            if (num + 1) * 4000 < bound:
                assert _since(kind, before)["backstop"] == 0
        assert db._wal.rows == 12 * 4000 - bound
    finally:
        db.close()
    assert _since(kind, before) == {"group": 0, "backstop": 1, "close": 1}
    at = bound // 4000 - 1                  # the block that crossed it
    assert max(sizes[at + 1:]) <= sizes[at] == max(sizes)


@pytest.mark.parametrize("kind", ["state", "history"])
def test_close_leaves_no_wal(tmp_path, kind):
    path = str(tmp_path / f"{kind}.db")
    db = _open(kind, path)
    before = _checkpoints(kind)
    _rows(db, 0, 50)
    assert os.path.getsize(_wal(path)) > 0
    db.close()
    assert _since(kind, before) == {"group": 0, "backstop": 0, "close": 1}
    assert not os.path.exists(_wal(path))
    # a DB that wrote nothing has nothing to write back at its close
    before = _checkpoints(kind)
    _open(kind, path).close()
    assert _since(kind, before) == {"group": 0, "backstop": 0, "close": 0}
    assert not os.path.exists(_wal(path))


# ---------------------------------------------------------------------------
# one rule for the three stores


class _Store:
    """One of a ledger's three stores, written a block at a time, with
    a reader of its own file and the counter of its group checkpoints."""

    def __init__(self, kind: str, root: str):
        self.kind, self.prev = kind, b""
        if kind == "index":
            self.db = BlockStore(os.path.join(root, "chains"))
            self.path = os.path.join(root, "chains", "index.db")
            self.table = "txids"
        else:
            self.path = os.path.join(root, f"{kind}.db")
            self.db = _open(kind, self.path)
            self.table = "state" if kind == "state" else "hist"
        self.reader = sqlite3.connect(self.path, isolation_level=None)
        self.num = 0

    def write(self, n: int) -> None:
        if self.kind == "index":
            blk = _block(self.num, self.prev, [b"x"])
            pu.set_tx_filter(blk, bytes(n))
            self.db.add_block(blk, txids=[(f"t{self.num}-{i}", i)
                                          for i in range(n)])
            self.prev = pu.block_header_hash(blk.header)
        else:
            _rows(self.db, self.num, n)
        self.num += 1

    def groups(self) -> float:
        if self.kind == "index":
            return global_registry().counter(
                "blockstore_index_checkpoints_total",
                "WAL checkpoints of the block index by trigger",
            ).value(trigger="group")
        return _checkpoints(self.kind)["group"]

    def close(self) -> None:
        self.reader.close()
        self.db.close()


@pytest.mark.parametrize("kind", ["index", "state", "history"])
def test_the_three_stores_share_one_trigger_and_retry_rule(
        tmp_path, monkeypatch, kind):
    """With a bound of 4 rows: not due at 3, due at 4; a reader's
    snapshot makes the next one leave frames behind, and the call after
    tries again with nothing new written; then nothing is due."""
    monkeypatch.setattr(blockstore, "_CHECKPOINT_ROWS", 4)
    monkeypatch.setattr(walcheckpoint, "CHECKPOINT_ROWS", 4)
    st = _Store(kind, str(tmp_path))
    made, retry = [], []

    def block(n):
        was = st.groups()
        st.write(n)
        st.db.checkpoint_if_due()
        made.append(int(st.groups() - was))
        retry.append(st.db._wal.retry)

    try:
        assert st.db._wal.bound == 4
        block(3)
        block(1)
        st.reader.execute("BEGIN")
        st.reader.execute(f"SELECT COUNT(*) FROM {st.table}").fetchone()
        block(4)
        st.reader.execute("COMMIT")
        block(0)
        block(1)
    finally:
        st.close()
    assert made == [0, 1, 1, 1, 0]
    assert retry == [False, False, True, False, False]


# ---------------------------------------------------------------------------
# a crash with the WAL not yet written back


def _stream(lg, n, rows):
    """``n`` blocks of ``rows`` rows: a value, a version, metadata on
    every third key, one delete a block, history, the savepoint."""
    prev = b""
    for num in range(n):
        blk = _block(num, prev, [b"d%d" % num])
        batch = UpdateBatch()
        for i in range(rows):
            batch.put("ns", f"k{num}-{i}", b"v%d-%d" % (num, i), (num, i),
                      metadata=b"m%d" % i if i % 3 == 0 else None)
        if num:
            batch.delete("ns", f"k{num - 1}-1", (num, 0))
        lg.commit_block(blk, bytes([0]), batch,
                        [("ns", f"k{num}-{i}", i) for i in range(rows)])
        # the async applier has noted the block before the next commit
        # asks whether a checkpoint is due
        lg.drain_state()
        prev = pu.block_header_hash(blk.header)


def _everything(lg) -> dict:
    lg.drain_state()
    return {
        "height": lg.height,
        "commit_hash": lg.commit_hash,
        "state": sorted((k, vv.value, vv.metadata, vv.version)
                        for k, vv in lg.state.iter_all()),
        "savepoint": lg.state.savepoint(),
        "history_savepoint": lg.history.savepoint(),
        "history": {k: list(lg.history.get_history_for_key("ns", k))
                    for k in ("k0-0", "k3-1", "k4-2", "k5-0")},
    }


@pytest.mark.parametrize("engine", [False, True],
                         ids=["serial_apply", "async_engine"])
def test_a_copy_taken_before_close_reads_everything_back(tmp_path, engine):
    """A ledger directory copied while it is open (a crash's shape):
    the state and history WALs hold the blocks since the last group
    checkpoint, and the main files the rest.  Reopened, the copy reads
    every value, version, metadata row, history entry and savepoint
    back, with no replay."""
    live, copy = str(tmp_path / "live"), str(tmp_path / "copy")
    lg = KVLedger(live, async_commit=engine)
    before = {db: _checkpoints(db) for db in ("state", "history")}
    # one group checkpoint at block 5 or 6, and blocks 6-7 or 7 after it
    _stream(lg, 8, 1500)
    want = _everything(lg)
    for db in ("state", "history"):
        assert _since(db, before[db])["group"] == 1
        assert os.path.getsize(os.path.join(live, f"{db}.db-wal")) > 0
    shutil.copytree(live, copy)
    lg.close()

    lg2 = KVLedger(copy)
    try:
        assert lg2.recover(lambda blk: pytest.fail("nothing to replay")) == 0
        assert _everything(lg2) == want
        assert want["savepoint"] == (7, 0) and want["history_savepoint"] == 7
        assert len(want["state"]) == 8 * 1500 - 7
    finally:
        lg2.close()
