"""The page traffic of the block store's txid index: one tree a txid
(``WITHOUT ROWID``, an older store rebuilt once at open), one WAL
checkpoint a group of blocks made by the writer after the block is out
of its hands, and recovery of an index that the files ran ahead of or
fell behind.  The index is derived state: every test here holds it to
the segment files and to what it answered before."""

import hashlib
import logging
import os
import shutil
import sqlite3

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.ledger import blockstore
from fabric_tpu.ledger.blockstore import BlockStore
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import UpdateBatch
from fabric_tpu.ops_metrics import global_registry
from fabric_tpu.protos import common_pb2
from tests.test_ledger_storage import _block, _commit_n

OLD_TXIDS = ("CREATE TABLE txids ("
             " txid TEXT PRIMARY KEY, num INTEGER, txnum INTEGER, code INTEGER)")
DUP_QUERY = "SELECT txid FROM txids WHERE txid IN (?,?,?)"
LOC_QUERY = "SELECT num, txnum, code FROM txids WHERE txid=?"


def _checkpoints() -> dict:
    ctr = global_registry().counter(
        "blockstore_index_checkpoints_total",
        "WAL checkpoints of the block index by trigger")
    return {t: ctr.value(trigger=t) for t in ("group", "backstop", "close")}


def _since(before: dict) -> dict:
    return {t: int(v - before[t]) for t, v in _checkpoints().items()}


def _txids_sql(path: str) -> str:
    conn = sqlite3.connect(os.path.join(path, "index.db"))
    try:
        return conn.execute(
            "SELECT sql FROM sqlite_master WHERE name='txids'").fetchone()[0]
    finally:
        conn.close()


def _wal(path: str) -> str:
    return os.path.join(path, "index.db-wal")


# ---------------------------------------------------------------------------
# one tree a txid


def test_a_fresh_stores_txids_is_without_rowid(tmp_path):
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    bs.close()
    assert "WITHOUT ROWID" in _txids_sql(path).upper()
    conn = sqlite3.connect(os.path.join(path, "index.db"))
    # the table is its primary key's tree and no second tree stands
    # beside it: a rowid table lists ``sqlite_autoindex_txids_1`` here
    # with a root page of its own
    assert conn.execute(
        "SELECT type, name FROM sqlite_master WHERE tbl_name='txids'"
        " AND rootpage > 0").fetchall() == [("table", "txids")]
    conn.close()


def _joined_store(path: str) -> tuple:
    """A snapshot-joined store that went on to commit blocks: txids of
    the snapshot (in no file, each with the code it was committed
    under) and txids of blocks 7-9.  → (store, last header hash)."""
    bs = BlockStore(path)
    bs.bootstrap_from_snapshot(
        7, b"\x07" * 32,
        [(f"snap-{i:03d}", (0, 11, 254)[i % 3]) for i in range(40)],
        commit_hash=b"\x0c" * 32)
    prev = b"\x07" * 32
    for n in range(7, 10):
        blk = _block(n, prev, [b"a", b"b", b"c"])
        pu.set_tx_filter(blk, bytes([0, 10, 0]))
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    return bs, prev


def _answers(bs: BlockStore) -> dict:
    asked = ([f"snap-{i:03d}" for i in range(0, 44, 3)]
             + [f"tx{n}-{i}" for n in range(6, 11) for i in range(3)])
    return {
        "existing": bs.existing_txids(asked),
        "locs": {t: bs.get_tx_loc(t) for t in asked},
        "codes": list(bs.iter_txid_codes()),
        "txids": list(bs.iter_txids()),
        "height": bs.height,
        "bootstrap": bs.bootstrap_info(),
    }


def _to_the_old_table(path: str) -> None:
    """``index.db`` as a store of before this table held it: the same
    rows under the old ``CREATE TABLE``, written by raw SQL."""
    conn = sqlite3.connect(os.path.join(path, "index.db"))
    rows = conn.execute("SELECT * FROM txids").fetchall()
    conn.execute("DROP TABLE txids")
    conn.execute(OLD_TXIDS)
    conn.executemany("INSERT INTO txids VALUES (?,?,?,?)", rows[::-1])
    conn.commit()
    conn.close()
    assert "WITHOUT ROWID" not in _txids_sql(path).upper()


def test_a_store_with_the_old_table_is_rebuilt_once_and_loses_nothing(
        tmp_path, caplog):
    path = str(tmp_path / "chains")
    bs, prev = _joined_store(path)
    want = _answers(bs)
    bs.close()
    assert len(want["codes"]) == 40 + 9 and want["height"] == 10
    assert want["locs"]["snap-003"] == (-1, -1, 0)
    assert want["locs"]["tx8-1"] == (8, 1, 10)
    _to_the_old_table(path)

    with caplog.at_level(logging.INFO, logger="fabric_tpu.ledger"):
        bs2 = BlockStore(path)
    said = [r.getMessage() for r in caplog.records
            if "rebuilt WITHOUT ROWID" in r.getMessage()]
    assert len(said) == 1 and "49 rows" in said[0]
    assert _answers(bs2) == want
    # it goes on as any store: a block more, its txids found
    blk = _block(10, prev, [b"z"])
    bs2.add_block(blk)
    assert bs2.get_tx_loc("tx10-0") == (10, 0, 254)
    bs2.close()
    assert "WITHOUT ROWID" in _txids_sql(path).upper()
    conn = sqlite3.connect(os.path.join(path, "index.db"))
    assert [r[0] for r in conn.execute(
        "SELECT name FROM sqlite_master WHERE type='table'"
        " ORDER BY name")] == ["blocks", "bootstrap", "txids"]
    conn.close()

    caplog.clear()
    with caplog.at_level(logging.INFO, logger="fabric_tpu.ledger"):
        bs3 = BlockStore(path)
    assert not [r for r in caplog.records
                if "rebuilt" in r.getMessage()]
    assert bs3.height == 11 and len(list(bs3.iter_txids())) == 50
    bs3.close()


@pytest.mark.parametrize("query, args", [
    (DUP_QUERY, ("tx0-0", "tx1-1", "nope")),
    (LOC_QUERY, ("tx1-0",)),
    ("SELECT txid, code FROM txids ORDER BY txid", ()),
], ids=["duplicate_check", "get_tx_loc", "snapshot_export"])
def test_the_index_queries_go_by_the_primary_key(tmp_path, query, args):
    """One tree: each lookup descends the primary key, and the snapshot
    export is a plain scan of it, with no sort and no second tree."""
    bs = BlockStore(str(tmp_path / "chains"))
    prev = b""
    for n in range(2):
        blk = _block(n, prev, [b"a", b"b"])
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    plan = " ".join(r[3] for r in bs._rd.execute(
        "EXPLAIN QUERY PLAN " + query, args))
    bs.close()
    if args:
        assert "USING PRIMARY KEY (txid=?)" in plan, plan
    else:
        assert plan == "SCAN txids", plan
    assert "autoindex" not in plan and "TEMP B-TREE" not in plan


def test_a_replayed_txid_is_ignored_and_keeps_its_first_code(tmp_path):
    bs = BlockStore(str(tmp_path / "chains"))
    b0 = _block(0, b"", [b"a", b"b"])
    pu.set_tx_filter(b0, bytes([0, 11]))
    bs.add_block(b0, txids=[("once", 0), ("twice", 1)])
    b1 = _block(1, pu.block_header_hash(b0.header), [b"c", b"d"])
    pu.set_tx_filter(b1, bytes([0, 0]))
    bs.add_block(b1, txids=[("twice", 0), ("fresh", 1)])
    assert bs.get_tx_loc("twice") == (0, 1, 11)
    assert bs.get_tx_loc("fresh") == (1, 1, 0)
    assert sorted(bs.iter_txid_codes()) == [
        ("fresh", 0), ("once", 0), ("twice", 11)]
    bs.close()


# ---------------------------------------------------------------------------
# one checkpoint a group of blocks


def _wide_block(num: int, prev: bytes, rows: int):
    """A block of one small envelope whose pre-parsed txids are ``rows``
    sha256 hex strings: the index's load without the envelopes'."""
    blk = _block(num, prev, [b"x"])
    pu.set_tx_filter(blk, bytes(rows))
    txids = [(hashlib.sha256(b"%d-%d" % (num, i)).hexdigest(), i)
             for i in range(rows)]
    return blk, txids


def test_the_wal_is_bounded_and_checkpointed_once_a_group(tmp_path):
    """200 blocks of 1000 rows: one ``group`` checkpoint per
    ``_CHECKPOINT_ROWS`` rows, the backstop never, and the WAL under
    twice the bound (a leaf a row is the worst a row can dirty)."""
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    page = bs._idx.execute("PRAGMA page_size").fetchone()[0]
    bound = blockstore._CHECKPOINT_ROWS * (page + 24)
    before, prev, largest = _checkpoints(), b"", 0
    for n in range(200):
        blk, txids = _wide_block(n, prev, 1000)
        bs.add_block(blk, txids=txids)
        bs.checkpoint_if_due()
        prev = pu.block_header_hash(blk.header)
        largest = max(largest, os.path.getsize(_wal(path)))
    got = _since(before)
    assert got["backstop"] == 0 and got["close"] == 0
    assert got["group"] == 200 * 1000 // blockstore._CHECKPOINT_ROWS
    assert largest < 2 * bound
    assert bs.existing_txids([t for t, _ in txids[:5]] + ["nope"]) == {
        t for t, _ in txids[:5]}
    bs.close()


def test_the_backstop_checkpoints_where_nobody_calls(tmp_path, monkeypatch):
    """A caller that appends and never calls ``checkpoint_if_due``: the
    store checkpoints by itself once the bound is passed
    ``_BACKSTOP_FACTOR`` times over, and says which trigger it was."""
    monkeypatch.setattr(blockstore, "_CHECKPOINT_ROWS", 6)
    bs = BlockStore(str(tmp_path / "chains"))
    (auto,) = bs._idx.execute("PRAGMA wal_autocheckpoint").fetchone()
    assert auto == 6 * blockstore._BACKSTOP_FACTOR
    before, prev = _checkpoints(), b""
    for n in range(8):                      # 3 rows a block: 24 at block 7
        blk = _block(n, prev, [b"a", b"b", b"c"])
        bs.add_block(blk)
        prev = pu.block_header_hash(blk.header)
    assert _since(before) == {"group": 0, "backstop": 1, "close": 0}
    assert bs._ckpt_rows == 0
    bs.close()


def test_the_write_connection_holds_a_blocks_pages_and_decides_no_checkpoint(
        tmp_path):
    bs = BlockStore(str(tmp_path / "chains"))
    assert bs._idx.execute("PRAGMA cache_size").fetchone()[0] == (
        -blockstore._IDX_CACHE_KIB)
    assert bs._idx.execute("PRAGMA wal_autocheckpoint").fetchone()[0] == (
        blockstore._BACKSTOP_FACTOR * blockstore._CHECKPOINT_ROWS)
    assert bs._idx.execute("PRAGMA synchronous").fetchone()[0] == 1  # NORMAL
    assert bs._rd.execute("PRAGMA cache_size").fetchone()[0] == -2000
    bs.close()


@pytest.mark.parametrize("engine", [True, False],
                         ids=["async_engine", "serial_apply"])
def test_the_checkpoint_comes_after_the_hand_over_and_outside_commit_index(
        tmp_path, monkeypatch, engine):
    """A traced commit through ``KVLedger``: ``commit.checkpoint`` hangs
    under the block's root, begins once ``commit.enqueue`` has ended and
    overlaps no ``commit.index``; ``commit.index`` keeps its two
    attributes and ``commit`` its three children."""
    from fabric_tpu.observe import global_tracer

    monkeypatch.setattr(blockstore, "_CHECKPOINT_ROWS", 3)
    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=16)
    lg = KVLedger(str(tmp_path / "ledger"), async_commit=engine)
    roots, prev, before = [], b"", _checkpoints()
    try:
        for num in range(7):
            blk = _block(num, prev, [b"data%d" % num])
            prev = pu.block_header_hash(blk.header)
            batch = UpdateBatch()
            batch.put("ns", f"k{num}", b"v", (num, 0))
            root = tracer.begin_block(num)
            roots.append(root)
            with tracer.span("commit", parent=root):
                lg.commit_block(blk, bytes([0]), batch,
                                [("ns", f"k{num}", 0)])
            tracer.finish_block(root)
        lg.drain_state()
    finally:
        lg.close()
        tracer.configure(ring_blocks=was)
    assert _since(before) == {"group": 2, "backstop": 0, "close": 1}
    index_spans, with_checkpoint = [], []
    for num, root in enumerate(roots):
        commit = next(c for c in root.children if c.name == "commit")
        by = {c.name: c for c in commit.children}
        # (the serial apply syncs the files first: an ``fsync`` between)
        assert [n for n in by if n != "fsync"] == [
            "commit.index", "commit.append", "commit.enqueue"]
        assert by["commit.index"].attrs == {"idx_wait_ms": 0.0, "txids": 1}
        index_spans.append(by["commit.index"])
        for ck in (c for c in root.children
                   if c.name == "commit.checkpoint"):
            with_checkpoint.append(num)
            assert ck.t0 >= by["commit.enqueue"].t1
            assert ck.t1 <= commit.t1 and ck.thread == commit.thread
            assert ck.attrs["rows"] == 3 and ck.attrs["busy"] == 0
            assert ck.attrs["moved"] == ck.attrs["frames"] > 0
            assert not [i for i in index_spans
                        if i.t0 < ck.t1 and ck.t0 < i.t1]
    assert with_checkpoint == [2, 5]


def test_a_readers_open_cursor_makes_one_checkpoint_busy_and_the_next_finishes(
        tmp_path, monkeypatch):
    from fabric_tpu.observe import global_tracer

    monkeypatch.setattr(blockstore, "_CHECKPOINT_ROWS", 4)
    path = str(tmp_path / "chains")
    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=16)
    bs = BlockStore(path)
    other = sqlite3.connect(os.path.join(path, "index.db"))
    roots, prev, before = [], b"", _checkpoints()

    def add(num, payloads):
        nonlocal prev
        blk = _block(num, prev, payloads)
        root = tracer.begin_block(num)
        roots.append(root)
        with tracer.span("commit", parent=root):
            bs.add_block(blk)
            bs.checkpoint_if_due()
        tracer.finish_block(root)
        prev = pu.block_header_hash(blk.header)

    try:
        add(0, [b"a", b"b"])
        # a reader's snapshot from before block 1: its frames stay
        cur = other.execute("SELECT txid FROM txids")
        assert cur.fetchone() is not None
        add(1, [b"a", b"b"])                 # 4 rows: due, and kept from
        assert bs._ckpt_retry                # finishing
        cur.close()
        add(2, [b"a"])                       # 1 row: not due, tried again
        assert not bs._ckpt_retry
        add(3, [b"a"])                       # nothing left to try
    finally:
        other.close()
        bs.close()
        tracer.configure(ring_blocks=was)
    assert _since(before) == {"group": 2, "backstop": 0, "close": 1}
    cks = [[c.attrs for c in r.children if c.name == "commit.checkpoint"]
           for r in roots]
    assert [len(c) for c in cks] == [0, 1, 1, 0]
    first, second = cks[1][0], cks[2][0]
    assert first["busy"] == 1 and first["moved"] < first["frames"]
    assert first["rows"] == 4
    assert second["busy"] == 0 and second["moved"] == second["frames"]
    assert second["rows"] == 1


def test_close_checkpoints_and_leaves_no_wal(tmp_path):
    path = str(tmp_path / "chains")
    bs = BlockStore(path)
    before, prev = _checkpoints(), b""
    for n in range(3):
        blk = _block(n, prev, [b"a"])
        bs.add_block(blk)
        bs.checkpoint_if_due()
        prev = pu.block_header_hash(blk.header)
    assert os.path.getsize(_wal(path)) > 0
    bs.close()
    assert _since(before) == {"group": 0, "backstop": 0, "close": 1}
    assert not os.path.exists(_wal(path))
    # a store that wrote nothing has nothing to checkpoint at its close
    before = _checkpoints()
    bs2 = BlockStore(path)
    assert bs2.height == 3
    bs2.close()
    assert _since(before) == {"group": 0, "backstop": 0, "close": 0}
    assert not os.path.exists(_wal(path))


def test_the_orderers_store_checkpoints_where_its_append_ends(
        tmp_path, monkeypatch):
    """``OrderingChain`` has no apply engine and syncs every block
    (``group_commit=1``): it calls ``checkpoint_if_due`` itself."""
    from fabric_tpu.ordering.blockcutter import BatchConfig
    from fabric_tpu.ordering.chain import OrderingChain
    from tests.test_ordering import _wait_for, run

    monkeypatch.setattr(blockstore, "_CHECKPOINT_ROWS", 2)

    def env(i: int) -> bytes:
        ch = pu.make_channel_header(
            common_pb2.HeaderType.ENDORSER_TRANSACTION, "chz",
            tx_id=f"otx-{i}")
        sh = pu.make_signature_header(b"creator", b"nonce-%d" % i)
        return common_pb2.Envelope(
            payload=pu.make_payload(ch, sh, b"p").SerializeToString(),
            signature=b"sig").SerializeToString()

    async def scenario():
        chain = OrderingChain(
            "chz", "solo", ["solo"], str(tmp_path / "ord"),
            send_cb=lambda p, m: None,
            config=BatchConfig(max_message_count=1))
        assert chain.blocks.group_commit == 1
        chain.start()
        try:
            assert await _wait_for(lambda: chain.raft.state == "leader")
            for i in range(5):
                await chain.broadcast(env(i))
            assert await _wait_for(lambda: chain.height == 5)
            assert chain.blocks.existing_txids(
                [f"otx-{i}" for i in range(6)]) == {
                    f"otx-{i}" for i in range(5)}
            assert chain.blocks._ckpt_rows == 1
        finally:
            chain.stop()

    before = _checkpoints()
    run(scenario())
    got = _since(before)
    assert got["group"] == 2 and got["backstop"] == 0


# ---------------------------------------------------------------------------
# recovery: the files are the truth in both directions


def _file_txids(lo: int, hi: int) -> list:
    return [f"tx{n}-0" for n in range(lo, hi)]


@pytest.mark.parametrize("wal", ["kept", "deleted"])
@pytest.mark.parametrize("tail", ["whole", "truncated"])
def test_a_copy_taken_mid_run_reopens_to_what_its_files_hold(
        tmp_path, monkeypatch, wal, tail):
    """A ledger directory copied while its store is open (a crash's
    shape), with ``index.db-wal`` or without it (power loss under
    ``synchronous=NORMAL``: what no checkpoint wrote back is gone, a
    whole group of blocks), the segment whole or cut inside block 9 (an
    unsynced tail).  Reopened, it holds the files' height and commit
    hash, every txid of the files and none beyond them: an index behind
    the files is re-indexed forward, one ahead of them clamped back."""
    monkeypatch.setattr(blockstore, "_CHECKPOINT_ROWS", 4)
    live, copy = str(tmp_path / "live"), str(tmp_path / "copy")
    lg = KVLedger(live)
    prev, hashes, sizes = b"", [], []
    seg = os.path.join(live, "chains", "blocks_000000.bin")
    for num in range(11):      # checkpoints after blocks 3 and 7
        prev = _commit_n(lg, 1, start=num, prev=prev)
        hashes.append(lg.commit_hash)
        sizes.append(os.path.getsize(seg))
    assert os.path.getsize(_wal(os.path.join(live, "chains"))) > 0
    shutil.copytree(live, copy)
    lg.close()

    chains = os.path.join(copy, "chains")
    if wal == "deleted":
        os.remove(_wal(chains))
        os.remove(os.path.join(chains, "index.db-shm"))
        conn = sqlite3.connect(os.path.join(chains, "index.db"))
        (behind,) = conn.execute("SELECT MAX(num) FROM blocks").fetchone()
        conn.close()
        assert behind == 7    # the last group's rows never left the WAL
    height = 11
    if tail == "truncated":
        with open(os.path.join(chains, "blocks_000000.bin"), "r+b") as f:
            f.truncate(sizes[8] + 9)        # inside block 9's record
        height = 9

    lg2 = KVLedger(copy)
    try:
        assert lg2.height == height
        assert lg2.commit_hash == hashes[height - 1]
        assert lg2.blocks.existing_txids(_file_txids(0, 12)) == set(
            _file_txids(0, height))
        assert list(lg2.blocks.iter_txids()) == sorted(
            _file_txids(0, height))
        assert lg2.blocks.get_block(height) is None
        blk = lg2.blocks.get_block(height - 1)
        # and the chain goes on from there
        _commit_n(lg2, 1, start=height,
                  prev=pu.block_header_hash(blk.header))
        assert lg2.blocks.get_tx_loc(f"tx{height}-0") == (height, 0, 0)
    finally:
        lg2.close()
