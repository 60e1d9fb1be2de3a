"""Ledger storage tests: statedb backends, block store recovery,
history, kvledger commit-hash chain + crash recovery (scenarios
modeled on the reference's blkstorage/kvledger test coverage)."""

import os
import threading
import time

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.ledger.blockstore import BlockStore
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import (
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
    the committer share one index connection; threads running the same
    statement used to fail with sqlite3.InterfaceError."""
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
    """A lookup (``tx_exists``) that finds the index lock held adds its
    wait to ``idx_wait_reader_s``, an ``add_block`` to
    ``idx_wait_writer_s``; a free lock reads no clock and leaves both
    as they were."""
    bs = BlockStore(str(tmp_path / "chains"))
    b0 = _block(0, b"", [b"a", b"b"])
    bs.add_block(b0)
    assert bs.tx_exists("tx0-1") and not bs.tx_exists("nope")
    assert bs.height == 1
    assert (bs.idx_wait_reader_s, bs.idx_wait_writer_s) == (0.0, 0.0)
    hold_s = 0.2
    if side == "reader":
        t = _hold(bs._idx_lock, hold_s)
        assert bs.tx_exists("tx0-0")
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
