"""The state DB's read connection and the order of the engine's gather.

Two things are pinned here:

1. ``AsyncApplyEngine``'s version gathers snapshot the pending queue
   BEFORE they ask the inner DB, so an apply that ends while the query
   runs is still in the snapshot and the overlay carries its writes
   (``get_versions_cols`` asked first and snapshotted after until PR
   30: an entry applied and popped in between was in neither);
2. ``SqliteVersionedDB`` answers its lookups on a read connection of
   its own: never behind the writer's open transaction, one read
   snapshot a gather, no transaction nor cursor left open behind a
   call (so checkpoints keep ``state.db-wal`` bounded), readers
   serialized among themselves, and ``close()`` still leaves a
   checkpointed file.
"""

import os
import sqlite3
import sys
import threading

import pytest

from fabric_tpu.ledger.committer import AsyncApplyEngine
from fabric_tpu.ledger.statedb import (
    MemVersionedDB,
    SqliteVersionedDB,
    UpdateBatch,
)

K1, K2, K3, K4 = ("ns", "k1"), ("ns", "k2"), ("ns", "k3"), ("ns", "k4")


def _batch(num, puts=(), dels=()):
    b = UpdateBatch()
    for i, (ns, k) in enumerate(puts):
        b.put(ns, k, b"v%d" % num, (num, i))
    for ns, k in dels:
        b.delete(ns, k, (num, 0))
    return b


def _cols_as_dict(keys, cols):
    present, vers = cols
    return {k: tuple(int(x) for x in vers[i])
            for i, k in enumerate(keys) if present[i]}


def _gather(db, form, keys):
    """Either form of the gather, as ``{key: version}``."""
    if form == "bulk":
        return db.get_versions_bulk(keys)
    return _cols_as_dict(keys, db.get_versions_cols(keys))


# ---------------------------------------------------------------------------
# 1. the engine: snapshot first, query second


class _RacyDB(MemVersionedDB):
    """Inner DB whose applies park on a gate, and whose gathers read
    the pre-apply versions, THEN let the applier land the queued entry
    and wait until the engine has popped it, and only then return:
    the worst interleaving of a reader with a snapshot of its own."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()
        self.engine = None
        self.racing = None  # block number whose apply the gather races

    def apply_updates(self, batch, savepoint):
        assert self.gate.wait(30.0), "apply gate never opened"
        MemVersionedDB.apply_updates(self, batch, savepoint)

    def _race(self):
        if self.racing is not None:
            num, self.racing = self.racing, None
            self.gate.set()
            assert self.engine.wait_applied(num, timeout=30.0)
            assert self.engine.stats()["queue_depth"] == 0

    def get_versions_cols(self, keys):
        stale = super().get_versions_cols(keys)
        self._race()
        return stale

    def get_versions_bulk(self, keys):
        stale = super().get_versions_bulk(keys)
        self._race()
        return stale


@pytest.mark.parametrize("form", ["cols", "bulk"])
@pytest.mark.parametrize("deletes", [False, True],
                         ids=["puts", "puts_and_deletes"])
def test_an_apply_that_ends_inside_the_gather_is_still_answered(form,
                                                                deletes):
    inner = _RacyDB()
    MemVersionedDB.apply_updates(
        inner, _batch(1, puts=[K1, K2, K4]), (1, 0))
    eng = AsyncApplyEngine(inner)
    inner.engine = eng
    try:
        eng.submit(2, _batch(2, puts=[K1, K3],
                             dels=[K2] if deletes else ()), (2, 0))
        inner.racing = 2
        got = _gather(eng, form, [K1, K2, K3, K4])
        assert inner.racing is None  # the race was run
        want = {K1: (2, 0), K2: (1, 1), K3: (2, 1), K4: (1, 2)}
        if deletes:
            del want[K2]
        assert got == want
        # and with nothing queued the inner DB's answer stands alone
        assert _gather(eng, form, [K1, K2, K3, K4]) == want
    finally:
        inner.gate.set()
        eng.close()


# ---------------------------------------------------------------------------
# 2. the reader's contract, on a real sqlite state DB


@pytest.fixture
def db(tmp_path):
    d = SqliteVersionedDB(str(tmp_path / "state.db"))
    d.open()
    yield d
    d.close()


class _MidWriteSqlite(SqliteVersionedDB):
    """Its apply writes the batch's first row, parks with the writer's
    transaction OPEN, and finishes (the real apply, the real commit)
    when the gate opens."""

    def __init__(self, path):
        super().__init__(path)
        self.mid = threading.Event()
        self.gate = threading.Event()

    def apply_updates(self, batch, savepoint):
        (ns, key), vv = next(iter(batch.items()))
        self._conn.execute(
            "INSERT OR REPLACE INTO state VALUES (?,?,?,?,?,?)",
            (ns, key, vv.value, None, vv.version[0], vv.version[1]))
        assert self._conn.in_transaction
        self.mid.set()
        assert self.gate.wait(30.0), "apply gate never opened"
        super().apply_updates(batch, savepoint)


@pytest.mark.parametrize("form", ["cols", "bulk"])
def test_a_gather_beside_an_open_write_reads_what_is_committed(tmp_path,
                                                               form):
    inner = _MidWriteSqlite(str(tmp_path / "state.db"))
    inner.open()
    SqliteVersionedDB.apply_updates(inner, _batch(1, puts=[K1, K2]), (1, 0))
    eng = AsyncApplyEngine(inner)
    keys = [K1, K2, K3]
    try:
        eng.submit(2, _batch(2, puts=[K1, K3]), (2, 0))
        assert inner.mid.wait(30.0)
        # the DB itself: the committed versions, without waiting for
        # the writer (one connection would answer (2, 0) for K1: its
        # own uncommitted row)
        assert _gather(inner, form, keys) == {K1: (1, 0), K2: (1, 1)}
        assert inner.get_state(*K1).version == (1, 0)
        assert inner.savepoint() == (1, 0)
        # through the engine the pending batch supplies what it holds
        want = {K1: (2, 0), K2: (1, 1), K3: (2, 1)}
        assert _gather(eng, form, keys) == want
        assert eng.get_state(*K1).version == (2, 0)
        assert eng.savepoint() == (2, 0)
        inner.gate.set()
        eng.drain()
        assert _gather(inner, form, keys) == want
        assert inner.savepoint() == (2, 0)
    finally:
        inner.gate.set()
        eng.close()


_UNBINDABLE = ("ns", object())


@pytest.mark.parametrize("call", [
    lambda d: d.get_versions_cols([K1, K2, K3]),
    lambda d: d.get_versions_bulk([K1, K2, K3]),
    lambda d: d.get_versions_cols([K1, _UNBINDABLE, K2]),
    lambda d: d.get_versions_bulk([K1, _UNBINDABLE, K2]),
    lambda d: d.get_state(*K1),
    lambda d: d.get_state(*_UNBINDABLE),
    lambda d: d.savepoint(),
], ids=["cols", "bulk", "cols_raises", "bulk_raises", "get_state",
        "get_state_raises", "savepoint"])
def test_no_call_leaves_the_reader_in_a_transaction(db, call, request):
    db.apply_updates(_batch(1, puts=[K1, K2]), (1, 0))
    if request.node.callspec.id.endswith("raises"):
        with pytest.raises(sqlite3.Error):
            call(db)
    else:
        call(db)
    assert not db._rd.in_transaction
    assert not db._rd_lock.locked()
    # so the next read is of a NEW snapshot: it sees a later commit
    db.apply_updates(_batch(2, puts=[K1]), (2, 0))
    assert db.get_versions_bulk([K1, K2]) == {K1: (2, 0), K2: (1, 1)}
    assert db.savepoint() == (2, 0)


def test_the_reader_cannot_write(db):
    with pytest.raises(sqlite3.OperationalError, match="readonly"):
        db._rd.execute("DELETE FROM state")


def _wal_after_applies(path, gather_between, blocks=40, writes=2000):
    """Size of ``state.db-wal`` after each of ``blocks`` applies of
    ``writes`` rows over the same keys, with or without a gather of a
    tenth of them in between."""
    d = SqliteVersionedDB(path)
    d.open()
    keys = [("ns", "key%06d" % i) for i in range(writes)]
    sizes = []
    try:
        for num in range(blocks):
            b = UpdateBatch()
            for i, (ns, k) in enumerate(keys):
                b.put(ns, k, b"x" * 200, (num, i))
            d.apply_updates(b, (num, 0))
            sizes.append(os.path.getsize(path + "-wal"))
            if gather_between and num:
                present, vers = d.get_versions_cols(keys[::10])
                assert present.all() and (vers[:, 0] == num).all()
                assert d.get_state(*keys[0]).version == (num, 0)
    finally:
        d.close()
    return sizes


def test_gathers_between_applies_do_not_grow_the_wal(tmp_path):
    """A reader that kept a snapshot (an open cursor, an unfinished
    transaction) would stop every checkpoint short of it, and the WAL
    would grow by a block's frames with every apply."""
    alone = _wal_after_applies(str(tmp_path / "alone.db"), False)
    beside = _wal_after_applies(str(tmp_path / "beside.db"), True)
    one_block = alone[0]  # the first apply's frames: nothing to reuse yet
    assert one_block > 0
    assert beside[-1] <= alone[-1] + one_block, (alone, beside)
    assert max(beside) <= max(alone) + one_block, (alone, beside)
    # closed, each left a checkpointed file and no WAL behind
    for name in ("alone.db", "beside.db"):
        assert not os.path.exists(str(tmp_path / name) + "-wal")


@pytest.mark.parametrize("readers", [2, 12])
def test_concurrent_gathers_each_read_one_snapshot(db, readers):
    """Several threads gather at once while a writer rewrites every key
    a block: each gather finishes, and sees ONE block's versions (all
    its lookups inside one read transaction), never a mix."""
    keys = [("ns", "key%04d" % i) for i in range(300)]

    def apply(num):
        b = UpdateBatch()
        for i, (ns, k) in enumerate(keys):
            b.put(ns, k, b"v", (num, i))
        db.apply_updates(b, (num, 0))

    apply(0)
    stop = threading.Event()
    errors, rounds = [], [0] * readers

    def reader(slot):
        try:
            last = 0
            while not stop.is_set() and rounds[slot] < 60:
                form = "cols" if (slot + rounds[slot]) % 2 else "bulk"
                got = _gather(db, form, keys)
                blocks = {v[0] for v in got.values()}
                assert len(got) == len(keys) and len(blocks) == 1, blocks
                assert [v[1] for v in got.values()] == list(range(len(keys)))
                num = blocks.pop()
                assert num >= last  # snapshots never go back
                last = num
                rounds[slot] += 1
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader, args=(i,), daemon=True)
               for i in range(readers)]
    try:
        for t in threads:
            t.start()
        num = 0
        while any(t.is_alive() for t in threads) and num < 400:
            num += 1
            apply(num)
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        sys.setswitchinterval(was)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert all(n > 0 for n in rounds), rounds
    assert not db._rd.in_transaction and not db._rd_lock.locked()


def test_close_then_reopen_reads_back_what_was_applied(tmp_path):
    path = str(tmp_path / "state.db")
    d = SqliteVersionedDB(path)
    d.open()
    d.apply_updates(_batch(1, puts=[K1, K2, K3]), (1, 0))
    d.apply_updates(_batch(2, puts=[K1], dels=[K2]), (2, 0))
    assert d.get_versions_bulk([K1, K2, K3]) == {K1: (2, 0), K3: (1, 2)}
    d.close()
    assert d._rd is None and d._conn is None
    d.close()  # idempotent
    # the writer closed last: the WAL was checkpointed and removed, so
    # the file alone is the state (the benchmark's preload cache and
    # chip_smoke copy it)
    assert not os.path.exists(path + "-wal")
    again = SqliteVersionedDB(path)
    again.open()
    try:
        assert again.get_versions_bulk([K1, K2, K3]) == {
            K1: (2, 0), K3: (1, 2)}
        assert again.get_state(*K1).value == b"v2"
        assert again.get_state(*K2) is None
        assert again.savepoint() == (2, 0)
        assert sorted(k for k, _ in again.iter_all()) == [K1, K3]
    finally:
        again.close()


# ---------------------------------------------------------------------------
# 3. what ``sf.gather`` says about the reader


@pytest.mark.parametrize("backend", ["sqlite", "mem"])
@pytest.mark.parametrize("form", ["cols", "bulk"])
def test_sf_gather_says_whether_a_read_connection_answered(tmp_path,
                                                           backend, form):
    from fabric_tpu.observe import global_tracer

    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=4)
    inner = (SqliteVersionedDB(str(tmp_path / "state.db"))
             if backend == "sqlite" else MemVersionedDB())
    inner.open()
    inner.apply_updates(_batch(1, puts=[K1, K2]), (1, 0))
    eng = AsyncApplyEngine(inner)
    try:
        root = tracer.begin_block(7)
        with tracer.span("launch", parent=root) as launch:
            got = _gather(eng, form, [K1, K3])
        tracer.finish_block(root)
        # disarmed (no current span): the same answer, no span
        assert _gather(eng, form, [K1, K3]) == got == {K1: (1, 0)}
    finally:
        eng.close()
        tracer.configure(ring_blocks=was)
    names = sorted(c.name for c in launch.children)
    assert names == ["sf.gather", "sf.pending"]
    gsp = next(c for c in launch.children if c.name == "sf.gather")
    if backend == "sqlite":
        assert gsp.attrs == {"keys": 2, "reader": 1, "rd_wait_ms": 0.0}
    else:
        assert gsp.attrs == {"keys": 2}
    psp = next(c for c in launch.children if c.name == "sf.pending")
    assert psp.attrs == {"pending": 0}


def test_rd_wait_counts_only_a_contended_acquire(db):
    db.apply_updates(_batch(1, puts=[K1]), (1, 0))
    db.get_versions_cols([K1])
    assert db.rd_wait_s == 0.0
    held = threading.Event()

    def holder():
        with db._rd_locked():
            held.set()
            threading.Event().wait(0.05)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert held.wait(30.0)
    present, _vers = db.get_versions_cols([K1])
    t.join(30.0)
    assert not t.is_alive() and present.all()
    assert 0.0 < db.rd_wait_s < 30.0
