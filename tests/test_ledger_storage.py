"""Ledger storage tests: statedb backends, block store recovery,
history, kvledger commit-hash chain + crash recovery (scenarios
modeled on the reference's blkstorage/kvledger test coverage)."""

import math
import os
import random
import sqlite3
import threading
import time

import numpy as np
import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.ledger.blockstore import BlockStore
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger import statedb
from fabric_tpu.ledger.history import HistoryDB
from fabric_tpu.ledger.sqlrows import row_statements
from fabric_tpu.ledger.statedb import (
    ColumnarUpdateBatch,
    MemVersionedDB,
    SqliteVersionedDB,
    UpdateBatch,
)
from fabric_tpu.protos import common_pb2


@pytest.fixture(params=["mem", "sqlite"])
def db(request, tmp_path):
    if request.param == "mem":
        d = MemVersionedDB()
    else:
        d = SqliteVersionedDB(str(tmp_path / "state.db"))
    d.open()
    yield d
    d.close()


def test_statedb_basic(db):
    b = UpdateBatch()
    b.put("ns1", "k1", b"v1", (1, 0))
    b.put("ns1", "k2", b"v2", (1, 1))
    b.put("ns2", "k1", b"other", (1, 2))
    db.apply_updates(b, (1, 0))
    assert db.get_state("ns1", "k1").value == b"v1"
    assert db.get_version("ns1", "k2") == (1, 1)
    assert db.get_state("ns1", "zz") is None
    assert db.savepoint() == (1, 0)
    vers = db.get_versions_bulk([("ns1", "k1"), ("ns1", "nope"), ("ns2", "k1")])
    assert vers == {("ns1", "k1"): (1, 0), ("ns2", "k1"): (1, 2)}
    # delete
    b2 = UpdateBatch()
    b2.delete("ns1", "k1", (2, 0))
    db.apply_updates(b2, (2, 0))
    assert db.get_state("ns1", "k1") is None


def test_statedb_range_and_rich_query(db):
    b = UpdateBatch()
    for i in range(10):
        b.put("ns", f"key{i}", b'{"color":"%s","size":%d}' % (b"red" if i % 2 else b"blue", i), (1, i))
    db.apply_updates(b, (1, 0))
    got = [k for k, _ in db.get_state_range("ns", "key2", "key6")]
    assert got == ["key2", "key3", "key4", "key5"]
    got = [k for k, _ in db.get_state_range("ns", "key8", "")]
    assert got == ["key8", "key9"]
    got = [k for k, _ in db.get_state_range("ns", "key0", "key9", limit=3)]
    assert got == ["key0", "key1", "key2"]
    rich = [k for k, _ in db.execute_query("ns", {"selector": {"color": "red"}})]
    assert rich == [f"key{i}" for i in range(10) if i % 2]


# ---------------------------------------------------------------------------
# the sqlite backend's block path: one statement a table a block, a key
# that exists rewritten where it lies (``SqliteVersionedDB.apply_updates``)


def _columnar(block_num, ops):
    """A ``ColumnarUpdateBatch`` as the validator builds one, from
    ``ops`` = [(ns, key, value | None, txnum)] in apply order (None
    deletes; a key may come more than once, the last wins).  A fifth
    member is the row's metadata (key-level endorsement)."""
    ns_names = sorted({op[0] for op in ops})
    uids, ukeys, ns_of = {}, [], []
    row_uid, row_del, voff, vlen, txnums = [], [], [], [], []
    blob = bytearray()
    metas = [op[4] if len(op) > 4 else None for op in ops]
    for ns, key, value, txnum in (op[:4] for op in ops):
        uid = uids.setdefault((ns, key), len(uids))
        if uid == len(ukeys):
            ukeys.append(key)
            ns_of.append(ns_names.index(ns))
        row_uid.append(uid)
        row_del.append(value is None)
        voff.append(len(blob))
        vlen.append(len(value or b""))
        blob += value or b""
        txnums.append(txnum)
    return ColumnarUpdateBatch(
        block_num, ns_names, ukeys, np.array(ns_of, np.int64),
        np.array(row_uid, np.int64), np.array(row_del, bool),
        np.array(voff, np.int64), np.array(vlen, np.int64),
        np.array(txnums, np.int64), bytes(blob), metas)


@pytest.fixture
def sq(tmp_path):
    d = SqliteVersionedDB(str(tmp_path / "state.db"))
    d.open()
    yield d
    d.close()


def _state_statements(conn):
    """→ the list that collects what ``conn`` is sent for ``state``."""
    sent = []
    conn.set_trace_callback(
        lambda q: sent.append(q) if " state" in q else None)
    return sent


def _set_max_vars(conn, n):
    conn.setlimit(sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER, n)


@pytest.mark.parametrize("seed", [7, 2026])
def test_sqlite_block_path_lands_what_the_mem_backend_lands(sq, seed):
    """30 seeded columnar blocks over two namespaces (rewrites of keys
    that exist, fresh keys, a key written twice in a block, deletes of
    present and of absent keys, a post-build override) leave the sqlite
    backend where the same batches leave ``MemVersionedDB``."""
    rng = random.Random(seed)
    mem = MemVersionedDB()
    mem.open()
    pool = [(ns, f"k{i:02d}") for ns in ("cc", "lscc") for i in range(24)]
    for num in range(1, 31):
        ops = []
        for txnum in range(rng.randint(1, 12)):
            for ns, key in rng.sample(pool, rng.randint(1, 3)):
                value = (None if rng.random() < 0.2
                         else rng.randbytes(rng.randint(0, 40)))
                ops.append((ns, key, value, txnum))
            ops.append(("cc", f"w{num}_{txnum}", b"fresh", txnum))
        ns, key = rng.choice(pool)
        ops.append((ns, key, b"first", 98))   # twice in the block
        ops.append((ns, key, b"second", 99))
        batches = [_columnar(num, ops), _columnar(num, ops)]
        if num % 5 == 0:
            for b in batches:                 # the pvt / BTL phase
                b.put("cc", "k03", b"override", (num, 100))
                b.delete("lscc", "k05", (num, 101))
        sq.apply_updates(batches[0], (num, 0))
        mem.apply_updates(batches[1], (num, 0))
        assert list(sq.iter_all()) == list(mem.iter_all())
        assert sq.savepoint() == mem.savepoint() == (num, 0)
    assert sq.get_state(ns, key).value == b"second"
    assert sq.apply_fast_blocks == 30
    assert sq.meta_count == mem.meta_count == 0


def test_sqlite_block_path_rewrites_a_key_where_it_lies(sq):
    """The upsert's update arm keeps the row (``INSERT OR REPLACE``
    deleted it and inserted another at the table's end): a rewritten
    key keeps its rowid, a fresh key gets the next one."""
    def rowids():
        return dict(sq._conn.execute("SELECT key, rowid FROM state"))

    sq.apply_updates(_columnar(1, [("ns", "a", b"a1", 0),
                                   ("ns", "b", b"b1", 1)]), (1, 0))
    before = rowids()
    sq.apply_updates(_columnar(2, [("ns", "a", b"a2-longer", 0),
                                   ("ns", "c", b"c2", 1)]), (2, 0))
    after = rowids()
    assert after["a"] == before["a"] and after["b"] == before["b"]
    assert after["c"] > max(before.values())
    vv = sq.get_state("ns", "a")
    assert (vv.value, vv.metadata, vv.version) == (b"a2-longer", None, (2, 0))
    assert sq.get_state("ns", "b").version == (1, 1)


@pytest.mark.parametrize("max_vars", [None, 13, 6])
def test_sqlite_block_path_is_one_statement_a_table(tmp_path, sq, max_vars):
    """A one-namespace block is one statement (``apply_statements``
    counts them), cut only where sqlite takes no more variables: then
    several, and the same rows."""
    ops = [("ns", f"k{i}", b"v%d" % i, i) for i in range(7)]
    sq.apply_updates(_columnar(1, ops[:3]), (1, 0))   # some keys exist
    assert sq.apply_statements == 1
    want = SqliteVersionedDB(str(tmp_path / "want.db"))
    want.open()
    want.apply_updates(_columnar(1, ops[:3]), (1, 0))
    want.apply_updates(_columnar(2, ops), (2, 0))
    if max_vars is not None:
        _set_max_vars(sq._conn, max_vars)
    sent = _state_statements(sq._conn)
    sq.apply_updates(_columnar(2, ops), (2, 0))
    statements = (1 if max_vars is None
                  else math.ceil(len(ops) / (max_vars // 6)))
    assert sq.apply_statements == 1 + statements
    assert len(sent) == statements
    # (the trace shows a statement with its values filled in)
    assert all(q.startswith("INSERT INTO state VALUES (")
               and "ON CONFLICT(ns, key) DO UPDATE" in q for q in sent)
    assert list(sq.iter_all()) == list(want.iter_all())
    want.close()
    # deletes are the table's second statement, cut the same way
    del sent[:]
    dels = [("ns", f"k{i}", None, 0) for i in range(1, 6)]
    sq.apply_updates(_columnar(3, dels + [("ns", "z", b"z", 1)]), (3, 0))
    n_del = 1 if max_vars is None else math.ceil(5 / (max_vars // 2))
    assert len(sent) == n_del + 1
    assert sum("DELETE FROM state" in q for q in sent) == n_del
    assert [k for (_ns, k), _vv in sq.iter_all()] == ["k0", "k6", "z"]
    assert sq.apply_fast_blocks == 3


def test_sqlite_block_delete_goes_by_the_primary_key_index(sq):
    """The delete finds its rows through the primary-key index; a scan
    of ``state`` would read a million rows a block."""
    sq.apply_updates(_columnar(1, [("ns", f"k{i}", b"v", i)
                                   for i in range(50)]), (1, 0))
    dels = [("ns", f"k{i}") for i in range(0, 50, 5)]
    (sql, params), = row_statements(sq._conn, dels, **statedb._DELETE_ROWS)
    plan = [row[3] for row in sq._conn.execute(
        "EXPLAIN QUERY PLAN " + sql, params)]
    sq.apply_updates(_columnar(2, [(ns, key, None, 0)
                                   for ns, key in dels]), (2, 0))
    assert not any(step.startswith("SCAN s") for step in plan), plan
    assert any("SEARCH s USING COVERING INDEX sqlite_autoindex_state_1"
               in step for step in plan), plan
    assert len(list(sq.iter_all())) == 40


def test_sqlite_metadata_rides_the_block_path(sq):
    """Key-level endorsement metadata is a column of the block's one
    statement: a columnar batch takes the block path whatever the DB
    tracks, a post-build override (a metadata-only write, the pvt phase)
    and an ``UpdateBatch`` the per-key loop, and ``meta_count`` stays
    right through both."""
    sq.apply_updates(_columnar(1, [("ns", "a", b"a", 0),
                                   ("ns", "m", b"m", 1)]), (1, 0))
    assert sq.apply_fast_blocks == 1 and sq.meta_count == 0
    cb = _columnar(2, [("ns", "b", b"b", 0, b"owner")])
    cb.put("ns", "m", b"m2", (2, 1), metadata=b"policy")
    sq.apply_updates(cb, (2, 0))
    assert sq.apply_fast_blocks == 2 and sq.apply_statements == 2
    assert sq.meta_count == 2
    assert sq.get_state("ns", "m").metadata == b"policy"
    assert sq.get_state("ns", "b").metadata == b"owner"
    # a plain row over a key that had metadata takes it away (the
    # validator carries it along where it is to stay), a delete too
    sq.apply_updates(_columnar(3, [("ns", "m", b"m3", 0),
                                   ("ns", "b", None, 1),
                                   ("ns", "a", b"a3", 2, b"late")]), (3, 0))
    assert sq.apply_fast_blocks == 3
    assert sq.meta_count == 1 and sq.apply_statements == 4
    assert sq.get_state("ns", "m").metadata is None
    assert sq.get_state("ns", "b") is None
    assert sq.get_state("ns", "a").metadata == b"late"
    ub = UpdateBatch()
    ub.put("ns", "a", b"a4", (4, 0))
    sq.apply_updates(ub, (4, 0))
    assert sq.apply_fast_blocks == 3 and sq.meta_count == 0


@pytest.mark.parametrize("seed", [34, 3400])
def test_sqlite_block_path_with_a_metadata_column_equals_mem(sq, seed):
    """30 seeded columnar blocks whose rows set, carry along, replace
    and clear metadata, and delete keys that hold some: sqlite by one
    statement a table a block, ``MemVersionedDB`` key by key, the same
    state and the same exact ``meta_count`` after every block."""
    rng = random.Random(seed)
    mem = MemVersionedDB()
    mem.open()
    pool = [(ns, f"k{i:02d}") for ns in ("cc", "lscc") for i in range(16)]
    owners = [b"org1", b"org2", b"org3", None, None]
    for num in range(1, 31):
        ops = []
        for txnum in range(rng.randint(1, 10)):
            for ns, key in rng.sample(pool, rng.randint(1, 3)):
                value = (None if rng.random() < 0.2
                         else rng.randbytes(rng.randint(0, 40)))
                ops.append((ns, key, value, txnum, rng.choice(owners)))
            ops.append(("cc", f"w{num}_{txnum}", b"fresh", txnum,
                        rng.choice(owners)))
        batches = [_columnar(num, ops), _columnar(num, ops)]
        if num % 7 == 0:
            for b in batches:     # a metadata-only write: an override
                b.put("cc", "k03", b"kept", (num, 100), metadata=b"org2")
        stmts0 = sq.apply_statements
        sq.apply_updates(batches[0], (num, 0))
        mem.apply_updates(batches[1], (num, 0))
        assert list(sq.iter_all()) == list(mem.iter_all())
        want = sum(1 for _k, vv in mem.iter_all() if vv.metadata)
        assert sq.meta_count == mem.meta_count == want
        # a namespace's rows in one upsert, and one delete where it deletes
        assert 1 <= sq.apply_statements - stmts0 <= 4
    assert sq.apply_fast_blocks == 30 and want > 0


def test_sqlite_counts_metadata_only_where_it_holds_some(sq):
    """The counting statement runs on a DB that holds metadata and on
    no other: a channel that never set a parameter sends ``state`` the
    block's one statement and nothing else."""
    sent = _state_statements(sq._conn)
    sq.apply_updates(_columnar(1, [("ns", "a", b"a", 0),
                                   ("ns", "b", b"b", 1)]), (1, 0))
    assert len(sent) == 1 and sent[0].startswith("INSERT INTO state")
    sq.apply_updates(_columnar(2, [("ns", "a", b"a2", 0, b"org1")]), (2, 0))
    assert len(sent) == 2 and sq.meta_count == 1
    sq.apply_updates(_columnar(3, [("ns", "a", b"a3", 0, b"org2"),
                                   ("ns", "c", b"c", 1)]), (3, 0))
    assert [q.split()[0] for q in sent[2:]] == ["WITH", "INSERT"]
    assert "COUNT(*)" in sent[2] and sq.meta_count == 1
    assert sq.apply_statements == 3     # the statements that write


@pytest.mark.parametrize("backend", ["mem", "sqlite", "engine"])
def test_the_version_gather_brings_the_metadata_of_flagged_keys(
        tmp_path, backend):
    """``get_versions_cols(keys, meta)``: present and version of every
    key as ever, and the metadata of the flagged ones; the apply
    engine's pending entries win for it as for versions, a pending
    delete clears it."""
    from fabric_tpu.ledger.committer import AsyncApplyEngine

    inner = (SqliteVersionedDB(str(tmp_path / "s.db"))
             if backend == "sqlite" else MemVersionedDB())
    inner.open()
    ub = UpdateBatch()
    ub.put("ns", "a", b"a", (1, 0), metadata=b"org1")
    ub.put("ns", "b", b"b", (1, 1), metadata=b"org2")
    ub.put("ns", "c", b"c", (1, 2))
    ub.put("ns", "d", b"d", (1, 3), metadata=b"org3")
    inner.apply_updates(ub, (1, 0))
    keys = [("ns", k) for k in "abcdx"]
    db, want = inner, [b"org1", None, None, b"org3", None]
    gate = None
    if backend == "engine":
        import threading

        gate, real = threading.Event(), inner.apply_updates
        inner.apply_updates = lambda b, sp: (gate.wait(30.0), real(b, sp))
        db = AsyncApplyEngine(inner)
        pend = UpdateBatch()
        pend.put("ns", "a", b"a2", (2, 0), metadata=b"org2")
        pend.delete("ns", "d", (2, 1))
        pend.put("ns", "x", b"x", (2, 2))
        db.submit(2, pend, (2, 0))
        want = [b"org2", None, None, None, None]
    try:
        flag = np.array([True, False, True, True, True])
        present, vers, metas = db.get_versions_cols(keys, flag)
        assert metas == want       # b is not flagged, c holds none
        plain = db.get_versions_cols(keys)
        assert len(plain) == 2
        assert (plain[0] == present).all() and (plain[1] == vers).all()
        if backend == "engine":
            assert present.tolist() == [True, True, True, False, True]
            assert tuple(vers[0]) == (2, 0)
        else:
            assert present.tolist() == [True, True, True, True, False]
    finally:
        if gate is not None:
            gate.set()
        db.close()


def test_sqlite_block_is_one_transaction(sq):
    """A statement that raises part-way (a row sqlite cannot bind, in
    the block's second statement) leaves nothing of the block once the
    connection rolls back: rows, savepoint and all, for the reader at
    once and for the writer after the rollback."""
    sq.apply_updates(_columnar(1, [("ns", "a", b"a1", 0)]), (1, 0))
    before = list(sq.iter_all())
    _set_max_vars(sq._conn, 12)              # two rows a statement
    bad = _columnar(2, [("ns", "a", b"a2", 0), ("ns", "b", b"b2", 1),
                        ("ns", "c", b"c2", 2), ("ns", "d", b"d2", 3)])
    bad.ukeys[3] = object()
    with pytest.raises(sqlite3.Error):
        sq.apply_updates(bad, (2, 0))
    assert sq.apply_statements == 2          # the first one ran
    assert sq._conn.in_transaction
    assert sq.get_state("ns", "a").value == b"a1"
    assert sq.get_state("ns", "b") is None and sq.savepoint() == (1, 0)
    sq._conn.rollback()
    assert list(sq.iter_all()) == before and sq.savepoint() == (1, 0)
    sq.apply_updates(_columnar(2, [("ns", "b", b"b2", 0)]), (2, 0))
    assert sq.get_state("ns", "b").value == b"b2"


@pytest.mark.parametrize("first", ["delete", "upsert"])
def test_sqlite_block_opens_its_transaction_at_its_first_statement(sq, first):
    """Whichever statement a block sends first, a delete or an upsert,
    the ``sqlite3`` module opens the transaction before it (it does so
    for a text that STARTS with INSERT, UPDATE, DELETE or REPLACE): the
    delete must not commit alone ahead of the block."""
    sq.apply_updates(_columnar(1, [("ns", "a", b"a1", 0),
                                   ("ns", "b", b"b1", 1)]), (1, 0))
    sent = []

    def note(q):
        sent.append((q.split(" (")[0], sq._conn.in_transaction))

    ops = [("ns", "c", b"c2", 1)]
    if first == "delete":
        ops.insert(0, ("ns", "a", None, 0))
    sq._conn.set_trace_callback(note)
    sq.apply_updates(_columnar(2, ops), (2, 0))
    sq._conn.set_trace_callback(None)
    want = [("BEGIN ", False)]
    if first == "delete":
        want.append(("DELETE FROM state WHERE rowid IN", True))
    want += [("INSERT INTO state VALUES", True),
             ("INSERT OR REPLACE INTO savepoint VALUES", True),
             ("COMMIT", True)]
    assert sent == want
    assert not sq._conn.in_transaction


def test_sqlite_block_delete_is_inside_the_blocks_transaction(sq):
    """A block whose first statement deletes: the upsert after it
    raises, and the rollback brings the deleted row back with the old
    savepoint; committed, the reader sees the key up to ``commit()``
    and not after."""
    keys = [("ns", "a"), ("ns", "b"), ("ns", "c")]
    sq.apply_updates(_columnar(1, [("ns", "a", b"a1", 0),
                                   ("ns", "b", b"b1", 1)]), (1, 0))
    before = list(sq.iter_all())
    bad = _columnar(2, [("ns", "a", None, 0), ("ns", "c", b"c2", 1)])
    bad.ukeys[1] = object()
    with pytest.raises(sqlite3.Error):
        sq.apply_updates(bad, (2, 0))
    assert sq.apply_statements == 1 + 1       # the delete ran
    assert sq._conn.in_transaction
    assert sq.get_state("ns", "a").value == b"a1"   # the reader's view
    sq._conn.rollback()
    assert list(sq.iter_all()) == before and sq.savepoint() == (1, 0)
    seen = []

    def at_commit(q):
        if q == "COMMIT":
            seen.append((sq.get_versions_bulk(keys), sq.savepoint()))

    sq._conn.set_trace_callback(at_commit)
    sq.apply_updates(_columnar(2, [("ns", "a", None, 0),
                                   ("ns", "c", b"c2", 1)]), (2, 0))
    sq._conn.set_trace_callback(None)
    assert seen == [({("ns", "a"): (1, 0), ("ns", "b"): (1, 1)}, (1, 0))]
    assert sq.get_versions_bulk(keys) == {("ns", "b"): (1, 1),
                                          ("ns", "c"): (2, 1)}
    assert sq.savepoint() == (2, 0)


def test_sqlite_reader_sees_a_block_at_its_commit(sq):
    """The read connection sees none of a block's rows while the block's
    statement has run and ``commit()`` has not, and all of them after."""
    keys = [("ns", "a"), ("ns", "b"), ("ns", "c")]
    sq.apply_updates(_columnar(1, [("ns", "a", b"a1", 0)]), (1, 0))
    seen = []

    def at_commit(q):
        if q == "COMMIT":
            assert sq._conn.in_transaction
            seen.append((sq.get_versions_bulk(keys), sq.savepoint()))

    sq._conn.set_trace_callback(at_commit)
    sq.apply_updates(_columnar(2, [("ns", "a", b"a2", 0),
                                   ("ns", "b", b"b2", 1),
                                   ("ns", "c", b"c2", 2)]), (2, 0))
    sq._conn.set_trace_callback(None)
    assert seen == [({("ns", "a"): (1, 0)}, (1, 0))]
    assert sq.get_versions_bulk(keys) == {
        ("ns", "a"): (2, 0), ("ns", "b"): (2, 1), ("ns", "c"): (2, 2)}
    assert sq.savepoint() == (2, 0)


def test_sqlite_reader_sees_a_blocks_metadata_at_its_commit(sq):
    """As above for the metadata column: while the block's statement
    has run and ``commit()`` has not, the gather on the read connection
    answers the old parameter; after it, the new one."""
    keys = [("ns", "a"), ("ns", "b")]
    flag = np.array([True, True])
    sq.apply_updates(_columnar(1, [("ns", "a", b"a1", 0, b"org1")]), (1, 0))
    seen = []

    def at_commit(q):
        if q == "COMMIT":
            assert sq._conn.in_transaction
            seen.append((sq.get_versions_cols(keys, flag)[2], sq.meta_count))

    sq._conn.set_trace_callback(at_commit)
    sq.apply_updates(_columnar(2, [("ns", "a", b"a2", 0, b"org2"),
                                   ("ns", "b", b"b2", 1, b"org3")]), (2, 0))
    sq._conn.set_trace_callback(None)
    assert seen == [([b"org1", None], 2)]
    assert sq.get_versions_cols(keys, flag)[2] == [b"org2", b"org3"]


# ---------------------------------------------------------------------------
# the history DB: a block's rows in one statement


@pytest.mark.parametrize("max_vars", [None, 9])
def test_history_commits_a_block_in_one_statement(tmp_path, max_vars):
    h = HistoryDB(str(tmp_path / "history.db"))
    if max_vars is not None:
        _set_max_vars(h._conn, max_vars)      # two rows of four
    sent = []
    h._conn.set_trace_callback(
        lambda q: sent.append(q) if " hist " in q else None)
    writes = [("ns", "a", 0), ("ns", "b", 0), ("ns", "a", 3),
              ("zz", "a", 4), ("ns", "c", 4)]
    statements = 1 if max_vars is None else 3
    assert h.commit_block(1, writes) == (5, statements)
    assert len(sent) == statements
    assert all(q.startswith("INSERT OR REPLACE INTO hist VALUES (")
               for q in sent)
    assert h.commit_block(2, [("ns", "a", 1)]) == (1, 1)
    # the same block again (recovery's replay) leaves one row a write
    assert h.commit_block(1, writes) == (5, statements)
    assert h._conn.execute("SELECT COUNT(*) FROM hist").fetchone() == (6,)
    # newest first
    assert list(h.get_history_for_key("ns", "a")) == [(2, 1), (1, 3), (1, 0)]
    assert list(h.get_history_for_key("zz", "a")) == [(1, 4)]
    assert list(h.get_history_for_key("ns", "nope")) == []
    assert h.savepoint() == 1
    # a block with no write moves the savepoint alone
    assert h.commit_block(3, []) == (0, 0)
    assert h.savepoint() == 3
    h.close()


def _block(num, prev, payloads, channel="ch"):
    blk = pu.new_block(num, prev)
    for i, p in enumerate(payloads):
        ch = pu.make_channel_header(
            common_pb2.HeaderType.ENDORSER_TRANSACTION, channel, tx_id=f"tx{num}-{i}"
        )
        sh = pu.make_signature_header(b"creator", b"n")
        payload = pu.make_payload(ch, sh, p)
        env = common_pb2.Envelope(payload=payload.SerializeToString(), signature=b"s")
        blk.data.data.append(env.SerializeToString())
    return pu.finalize_block(blk)


def test_blockstore_append_get_and_txids(tmp_path):
    bs = BlockStore(str(tmp_path / "chains"))
    assert bs.height == 0
    prev = b""
    for n in range(5):
        blk = _block(n, prev, [b"a", b"b"])
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    assert bs.height == 5
    b3 = bs.get_block(3)
    assert b3.header.number == 3
    assert bs.get_block_by_hash(pu.block_header_hash(b3.header)).header.number == 3
    assert bs.get_tx_loc("tx3-1") == (3, 1, 254)
    assert bs.tx_exists("tx0-0") and not bs.tx_exists("nope")
    with pytest.raises(ValueError):
        bs.add_block(_block(9, b"", [b"x"]))
    bs.close()


def test_blockstore_index_reads_survive_concurrent_threads(tmp_path):
    """The validator's dup-txid lookups, the gateway's status reads and
    the committer's ``height`` share the index's read connection;
    threads running the same statement on one connection used to fail
    with sqlite3.InterfaceError."""
    import sys
    import threading
    import time

    bs = BlockStore(str(tmp_path / "chains"))
    errors, stop = [], threading.Event()

    def reader():
        i = 0
        while not stop.is_set():
            try:
                bs.get_tx_loc(f"tx{i % 64}-0")
                bs.existing_txids([f"tx{i % 64}-0", "nope"])
                bs.height
            except Exception as e:  # the invariant under test
                errors.append(repr(e))
                return
            i += 1

    threads = [threading.Thread(target=reader)
               for _ in range(2 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        prev, n, deadline = b"", 0, time.monotonic() + 1.5
        while time.monotonic() < deadline and not errors:
            blk = _block(n, prev, [b"a"])
            bs.add_block(blk)
            prev = pu.block_header_hash(blk.header)
            n += 1
    finally:
        stop.set()
        for t in threads:
            t.join(10)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert bs.height == n and n > 0
    bs.close()


def _hold(lock, seconds):
    """A thread that takes ``lock`` now and keeps it for ``seconds``."""
    held = threading.Event()

    def run():
        with lock:
            held.set()
            time.sleep(seconds)

    t = threading.Thread(target=run)
    t.start()
    assert held.wait(10)
    return t


@pytest.mark.parametrize("side", ["reader", "writer"])
def test_blockstore_idx_lock_wait_is_counted_by_side(tmp_path, side):
    """Each side counts the wait for the lock it really has: a lookup
    that finds the READ connection's lock held (another reader is
    inside its statement) adds to ``idx_wait_reader_s``, an
    ``add_block`` that finds the WRITER's lock held adds to
    ``idx_wait_writer_s``; neither takes the other's lock, and a free
    lock reads no clock and leaves both as they were."""
    bs = BlockStore(str(tmp_path / "chains"))
    b0 = _block(0, b"", [b"a", b"b"])
    bs.add_block(b0)
    assert bs.tx_exists("tx0-1") and not bs.tx_exists("nope")
    assert bs.existing_txids(["tx0-0", "nope"]) == {"tx0-0"}
    assert bs.height == 1
    assert (bs.idx_wait_reader_s, bs.idx_wait_writer_s) == (0.0, 0.0)
    hold_s = 0.2
    if side == "reader":
        t = _hold(bs._rd_lock, hold_s)
        assert bs.existing_txids(["tx0-0"]) == {"tx0-0"}
        got, other = bs.idx_wait_reader_s, bs.idx_wait_writer_s
    else:
        b1 = _block(1, pu.block_header_hash(b0.header), [b"c"])
        index_block, started = bs._index_block, []

        def index_block_late(*a, **kw):
            started.append(bs.idx_wait_writer_s)
            return index_block(*a, **kw)

        bs._index_block = index_block_late
        # add_block takes the file lock before the index lock: park it
        # there, past its height lookup, until the index lock is held
        with bs._io_lock:
            adder = threading.Thread(target=bs.add_block, args=(b1,))
            adder.start()
            time.sleep(0.05)
            t = _hold(bs._idx_lock, hold_s)
        adder.join(10)
        assert not adder.is_alive() and bs.get_tx_loc("tx1-0") == (1, 0, 254)
        # counted while holding the lock, before the index insert
        assert started == [bs.idx_wait_writer_s]
        got, other = bs.idx_wait_writer_s, bs.idx_wait_reader_s
    t.join(10)
    assert 0.5 * hold_s < got < 10 * hold_s
    assert other == 0.0
    bs.close()


def _chain(bs, n_blocks, payloads=(b"a", b"b"), start=0, prev=b""):
    for n in range(start, start + n_blocks):
        blk = _block(n, prev, list(payloads))
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    return prev


@pytest.mark.parametrize("case", ["empty", "none_known", "all_known",
                                  "more_than_one_statement", "bootstrapped"])
def test_blockstore_existing_txids_is_the_committed_subset(tmp_path, case):
    """``existing_txids`` answers a block's whole duplicate check:
    exactly the txids the index holds, whatever the size of the
    question, txids a snapshot brought included; never a statement a
    tx (``txid_queries`` counts them)."""
    bs = BlockStore(str(tmp_path / "chains"))
    if case == "bootstrapped":
        bs.bootstrap_from_snapshot(7, b"h" * 32, [("snap-a", 0), ("snap-b", 11)])
        assert bs.height == 7
        known = {"snap-a", "snap-b"}
    else:
        _chain(bs, 3)
        known = {f"tx{n}-{i}" for n in range(3) for i in range(2)}
    asked, want, statements = {
        "empty": ([], set(), 0),
        "none_known": (["nope", "tx3-0", ""], set(), 1),
        "all_known": (sorted(known), known, 1),
        # more variables than one statement takes: the question is cut
        "more_than_one_statement": (
            [f"absent{i}" for i in range(4)] + sorted(known), known, 3),
        "bootstrapped": (["snap-b", "tx0-0", "snap-a"], known, 1),
    }[case]
    if case == "more_than_one_statement":
        bs._max_vars = 4
    assert bs.existing_txids(asked) == want
    assert bs.existing_txids(iter(asked)) == want       # any iterable
    assert bs.txid_queries == 2 * statements
    assert all(bs.tx_exists(t) for t in want)
    bs.close()


@pytest.mark.parametrize("statements", ["one", "several"])
def test_blockstore_indexes_a_block_in_block_wide_statements(tmp_path,
                                                             statements):
    """``add_block`` hands a block's txid rows to sqlite in one
    statement (cut only where a statement takes no more variables), and
    indexes what a statement a row did: every txid at its position with
    its code, the first of a repeated txid winning, also on the rebuild
    from the files."""
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    if statements == "several":
        bs._max_vars = 9               # two rows of four a statement
    sent = []
    bs._idx.set_trace_callback(sent.append)
    blk = _block(0, b"", [b"a", b"b", b"c", b"d", b"e"])
    txids = [("tx0-0", 0), ("tx0-1", 1), ("tx0-0", 2), ("", 3), ("tx0-4", 4)]
    pu.set_tx_filter(blk, bytes([0, 11, 9, 0, 254]))
    bs.add_block(blk, txids=txids)
    bs._idx.set_trace_callback(None)
    inserts = [q for q in sent if q.startswith("INSERT OR IGNORE INTO txids")]
    assert len(inserts) == (1 if statements == "one" else 2)
    want = {"tx0-0": (0, 0, 0), "tx0-1": (0, 1, 11), "tx0-4": (0, 4, 254)}
    assert {t: bs.get_tx_loc(t) for t in want} == want
    assert bs.existing_txids(["tx0-0", "tx0-1", "tx0-2", "tx0-4", ""]) == set(want)
    bs.close()
    # the rebuild parses the envelopes: every position has its own txid
    os.remove(os.path.join(path, "index.db"))
    bs2 = BlockStore(path)
    assert [bs2.get_tx_loc(f"tx0-{i}") for i in range(5)] == [
        (0, 0, 0), (0, 1, 11), (0, 2, 9), (0, 3, 0), (0, 4, 254)]
    bs2.close()


def test_blockstore_add_block_is_visible_to_a_reader_thread(tmp_path):
    """The read connection sees a block's txids as soon as ``add_block``
    has returned, from whichever thread asks (each statement is a read
    of its own: nothing pins an older snapshot)."""
    bs = BlockStore(str(tmp_path / "chains"))
    added, asked, answers = threading.Event(), threading.Event(), []

    def reader():
        answers.append(bs.existing_txids(["tx0-0", "tx0-1"]))
        asked.set()
        assert added.wait(10)
        answers.append(bs.existing_txids(["tx0-0", "tx0-1"]))
        answers.append(bs.height)

    t = threading.Thread(target=reader)
    t.start()
    assert asked.wait(10)
    _chain(bs, 1)
    added.set()
    t.join(10)
    assert not t.is_alive()
    assert answers == [set(), {"tx0-0", "tx0-1"}, 1]
    bs.close()


def test_blockstore_reader_does_not_wait_for_the_writer(tmp_path):
    """A lookup finishes while another thread holds the writer's lock
    with the next block's rows inserted and not yet committed (where
    the committer spends its ``commit.index``); it sees the index as of
    the last commit, and the new rows once they are committed."""
    bs = BlockStore(str(tmp_path / "chains"))
    prev = _chain(bs, 1)
    inserted, release = threading.Event(), threading.Event()

    def writer():
        with bs._idx_lock:
            bs._index_block(_block(1, prev, [b"c"]), 0, 0)
            inserted.set()
            assert release.wait(10)
            bs._idx.commit()

    w = threading.Thread(target=writer)
    w.start()
    assert inserted.wait(10)
    answers = []

    def reader():
        answers.append(bs.existing_txids(["tx0-0", "tx1-0"]))
        answers.append(bs.tx_exists("tx0-1"))
        answers.append(bs.height)

    r = threading.Thread(target=reader)
    r.start()
    r.join(5)
    waited = r.is_alive()
    release.set()
    w.join(10)
    r.join(10)
    assert not waited and not w.is_alive() and not r.is_alive()
    assert answers == [{"tx0-0"}, True, 1]
    assert bs.existing_txids(["tx0-0", "tx1-0"]) == {"tx0-0", "tx1-0"}
    assert bs.height == 2
    assert bs.idx_wait_reader_s == 0.0
    bs.close()


def test_blockstore_lookups_do_not_pin_the_wal(tmp_path):
    """Every read cursor is fetched to its end, so no reader pins a
    WAL snapshot: each lookup sees the block just added, checkpoints
    finish, and ``index.db-wal`` is reused, not grown, across 50
    blocks of lookups between inserts.  (A cursor left open on the
    read connection holds its snapshot: the next lookups answer from
    before the insert, and the WAL grows with every block.)"""
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    # checkpoint every few pages, so 50 small blocks see many
    bs._idx.execute("PRAGMA wal_autocheckpoint=4")
    wal = os.path.join(path, "index.db-wal")
    payloads = [b"p"] * 40
    prev, sizes = b"", []
    for n in range(50):
        prev = _chain(bs, 1, payloads, start=n, prev=prev)
        txids = [f"tx{n}-{i}" for i in range(40)]
        assert bs.existing_txids(txids + ["nope"]) == set(txids)
        assert bs.tx_exists(txids[0]) and bs.get_block(n) is not None
        assert bs.height == n + 1
        sizes.append(os.path.getsize(wal))
    assert max(sizes[25:]) <= max(sizes[:25])
    bs.close()


def test_blockstore_read_connection_follows_recovery_and_close(tmp_path):
    """The read connection is opened after ``_recover``: a store whose
    index was lost answers from the rebuilt one.  ``close()`` closes
    it (the last connection gone, sqlite removes the WAL), and a
    reopened store answers as before."""
    import sqlite3

    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    _chain(bs, 3)
    asked = ["tx0-0", "tx2-1", "tx3-0", "nope"]
    want = {"tx0-0", "tx2-1"}
    assert bs.existing_txids(asked) == want
    rd = bs._rd
    bs.close()
    with pytest.raises(sqlite3.ProgrammingError):
        rd.execute("SELECT 1")
    assert not os.path.exists(os.path.join(path, "index.db-wal"))
    bs2 = BlockStore(path)
    assert bs2.existing_txids(asked) == want and bs2.height == 3
    with pytest.raises(sqlite3.OperationalError):   # query_only
        bs2._rd.execute("DELETE FROM txids")
    bs2.close()
    os.remove(os.path.join(path, "index.db"))
    bs3 = BlockStore(path)
    assert bs3.existing_txids(asked) == want and bs3.height == 3
    assert bs3.get_tx_loc("tx2-1") == (2, 1, 254)
    bs3.close()


@pytest.mark.parametrize("trigger", ["group", "lag", "forced", "apply"])
def test_blockstore_fsync_span_carries_its_trigger(tmp_path, trigger):
    """Each real fsync is one ``fsync`` span under the syncing thread's
    current span, with what closed the window and how many blocks it
    held; a sync with nothing to sync records none."""
    from fabric_tpu.observe import global_tracer

    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=4)
    bs = BlockStore(str(tmp_path / "chains"),
                    group_commit=2 if trigger == "group" else 8,
                    group_max_lag_s=0.0 if trigger == "lag" else 60.0)
    try:
        root = tracer.begin_block(0)
        with tracer.span("commit", parent=root) as commit:
            prev = b""
            for n in range(2):
                blk = _block(n, prev, [b"a"])
                bs.add_block(blk)
                prev = pu.block_header_hash(blk.header)
            if trigger == "forced":
                bs.sync()
            elif trigger == "apply":
                bs.ensure_synced(0)
            bs.sync()           # nothing left: no second span
            bs.ensure_synced(1)
        tracer.finish_block(root)
    finally:
        bs.close()
        tracer.configure(ring_blocks=was)
    got = [(c.attrs["trigger"], c.attrs["blocks"])
           for c in commit.children if c.name == "fsync"]
    want = {"group": [("group", 2)], "lag": [("lag", 1), ("lag", 1)],
            "forced": [("forced", 2)], "apply": [("apply", 2)]}[trigger]
    assert got == want
    assert [c.name for c in commit.children].count("commit.index") == 2


def test_blockstore_reopen_and_torn_write_recovery(tmp_path):
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    prev = b""
    for n in range(3):
        blk = _block(n, prev, [b"p"])
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    bs.close()
    # simulate crash mid-append: torn record at the tail
    seg = os.path.join(path, "blocks_000000.bin")
    with open(seg, "ab") as f:
        f.write(b"\xff\xff\x00\x00garbage")
    bs2 = BlockStore(path)
    assert bs2.height == 3
    assert bs2.get_block(2).header.number == 2
    # still appendable after recovery
    bs2.add_block(_block(3, prev, [b"q"]))
    assert bs2.height == 4
    bs2.close()


def test_blockstore_group_commit_index_clamp(tmp_path):
    """Group commit lets the sqlite index run durably ahead of an
    unsynced segment tail; after a crash truncates the tail, _recover
    must clamp the index BACK to the files (the files are the source
    of truth in both directions)."""
    path = str(tmp_path / "chains")
    bs = BlockStore(path, group_commit=8)
    prev = b""
    offs = []
    for n in range(5):
        blk = _block(n, prev, [b"p%d" % n])
        offs.append(os.path.getsize(os.path.join(path, "blocks_000000.bin"))
                    if n else 0)
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    # crash inside the group window: blocks 3-4's bytes never hit disk
    bs._fh.close()
    bs._idx.close()
    seg = os.path.join(path, "blocks_000000.bin")
    with open(seg, "r+b") as f:
        f.truncate(offs[3])
    bs2 = BlockStore(path)
    assert bs2.height == 3  # index clamped to the surviving files
    assert bs2.get_block(2) is not None
    assert bs2.get_block(3) is None
    assert bs2.get_tx_loc("tx3-0") is None  # txid rows clamped too
    # the chain continues from the clamped tip
    prev3 = pu.block_header_hash(bs2.get_block(2).header)
    bs2.add_block(_block(3, prev3, [b"re-delivered"]))
    assert bs2.height == 4
    reblk = bs2.get_block(3)
    assert reblk.header.number == 3
    assert b"re-delivered" in reblk.data.data[0]
    bs2.close()


def test_blockstore_index_rebuild(tmp_path):
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    prev = b""
    for n in range(3):
        blk = _block(n, prev, [b"p"])
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    bs.close()
    os.remove(os.path.join(path, "index.db"))
    bs2 = BlockStore(path)
    assert bs2.height == 3
    assert bs2.get_tx_loc("tx1-0") is not None
    bs2.close()


def _commit_n(ledger, n, start=0, prev=None):
    prev = prev if prev is not None else b""
    for num in range(start, start + n):
        blk = _block(num, prev, [b"data%d" % num])
        batch = UpdateBatch()
        batch.put("ns", f"k{num}", b"v%d" % num, (num, 0))
        ledger.commit_block(blk, bytes([0]), batch, [("ns", f"k{num}", 0)])
        prev = pu.block_header_hash(blk.header)
    return prev


def test_kvledger_commit_and_hash_chain(tmp_path):
    led = KVLedger(str(tmp_path / "ledger"))
    _commit_n(led, 3)
    assert led.height == 3
    assert led.state.get_state("ns", "k1").value == b"v1"
    assert list(led.history.get_history_for_key("ns", "k2")) == [(2, 0)]
    h1 = led.commit_hash
    assert h1 and len(h1) == 32
    blk2 = led.blocks.get_block(2)
    assert blk2.metadata.metadata[common_pb2.BlockMetadataIndex.COMMIT_HASH] == h1
    led.close()
    # reopen: commit hash reloaded from last block
    led2 = KVLedger(str(tmp_path / "ledger"))
    assert led2.commit_hash == h1
    led2.close()


def test_kvledger_crash_recovery_replays_state(tmp_path):
    led = KVLedger(str(tmp_path / "ledger"))
    prev = _commit_n(led, 2)
    # crash: block 2 reaches the block store but not the state db
    blk = _block(2, prev, [b"late"])
    pu.set_tx_filter(blk, bytes([0]))
    blk.metadata.metadata[common_pb2.BlockMetadataIndex.COMMIT_HASH] = b"x" * 32
    led.blocks.add_block(blk)
    led.close()

    led2 = KVLedger(str(tmp_path / "ledger"))
    assert led2.height == 3
    assert led2.state.savepoint() == (1, 0)  # behind

    def replayer(block):
        batch = UpdateBatch()
        num = block.header.number
        batch.put("ns", f"k{num}", b"replayed", (num, 0))
        return bytes([0]), batch, [("ns", f"k{num}", 0)]

    replayed = led2.recover(replayer)
    assert replayed == 1
    assert led2.state.get_state("ns", "k2").value == b"replayed"
    assert led2.state.savepoint() == (2, 0)
    led2.close()


def test_pvtdata_store_roundtrip_and_expiry(tmp_path):
    led = KVLedger(str(tmp_path / "ledger"))
    prev = b""
    blk = _block(0, prev, [b"x"])
    batch = UpdateBatch()
    led.commit_block(
        blk, bytes([0]), batch, None,
        pvt_data={(0, "ns", "collA"): (b"pvt-rwset", 5)},
    )
    assert led.pvtdata.get_pvt_data(0) == {(0, "ns", "collA"): b"pvt-rwset"}
    assert led.pvtdata.purge_expired(4) == []
    purged = led.pvtdata.purge_expired(5)
    assert [r[:4] for r in purged] == [(0, 0, "ns", "collA")]
    assert purged[0][4] == b"pvt-rwset"
    assert led.pvtdata.get_pvt_data(0) == {}
    led.close()
