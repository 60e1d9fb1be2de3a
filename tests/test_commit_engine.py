"""Async group-commit storage engine (fabric_tpu/ledger/committer.py):
the decoupled committer's differential battery.

Layers:

1. AsyncApplyEngine unit semantics — read-your-writes through the
   pending overlay (point reads, bulk/column version gathers, range
   scans, rich queries with pending-rewrite suppression), bounded-
   queue backpressure, fail-stop error latch;
2. columnar write batches — ``ColumnarUpdateBatch`` dict equivalence
   (content AND order), post-build overrides, and the sqlite
   executemany fast path landing byte-identical state;
3. crash recovery — the applier killed at EVERY queue depth via the
   ``ledger.apply.before`` fault point, reopened serial, replayed from
   the chain files: state byte-identical to the synchronous oracle,
   savepoint reconciled to the block height;
4. the depth-3 CommitPipeline differential: async ON vs OFF produce
   identical verdicts and final state (the toy validator reads
   through the engine, so MVCC preloads exercise the overlay).
"""

import threading
import time

import numpy as np
import pytest

from fabric_tpu import faults
from fabric_tpu import protoutil as pu
from fabric_tpu.ledger.committer import AsyncApplyEngine
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import (
    ColumnarUpdateBatch,
    MemVersionedDB,
    SqliteVersionedDB,
    UpdateBatch,
)
from fabric_tpu.protos import common_pb2


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


class _GatedDB(MemVersionedDB):
    """Inner backend whose applies park on a gate — the pending
    overlay becomes deterministic to probe."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def seed(self, batch, savepoint):
        MemVersionedDB.apply_updates(self, batch, savepoint)

    def apply_updates(self, batch, savepoint):
        assert self.gate.wait(30.0), "apply gate never opened"
        MemVersionedDB.apply_updates(self, batch, savepoint)


def _b(num, puts=(), dels=()):
    b = UpdateBatch()
    for i, (ns, k, v) in enumerate(puts):
        b.put(ns, k, v, (num, i))
    for ns, k in dels:
        b.delete(ns, k, (num, 0))
    return b


# ---------------------------------------------------------------------------
# 1. engine unit semantics


def test_overlay_read_your_writes_point_and_versions():
    inner = _GatedDB()
    inner.open()
    s = UpdateBatch()
    s.put("ns", "a", b"old", (0, 0))
    s.put("ns", "gone", b"x", (0, 1))
    inner.seed(s, (0, 0))
    eng = AsyncApplyEngine(inner, queue_blocks=8)
    eng.submit(1, _b(1, puts=[("ns", "a", b"new1"), ("ns", "b", b"b1")],
                     dels=[("ns", "gone")]), (1, 0))
    eng.submit(2, _b(2, puts=[("ns", "a", b"new2")]), (2, 0))
    # newest pending batch wins; deletes read as absent
    assert eng.get_state("ns", "a").value == b"new2"
    assert eng.get_state("ns", "b").value == b"b1"
    assert eng.get_state("ns", "gone") is None
    keys = [("ns", "a"), ("ns", "gone"), ("ns", "b"), ("ns", "nope")]
    assert eng.get_versions_bulk(keys) == {
        ("ns", "a"): (2, 0), ("ns", "b"): (1, 1),
    }
    present, vers = eng.get_versions_cols(keys)
    assert present.tolist() == [True, False, True, False]
    assert vers[0].tolist() == [2, 0] and vers[2].tolist() == [1, 1]
    # savepoint reads ahead to the newest queued batch
    assert eng.savepoint() == (2, 0)
    assert eng.stats()["queue_depth"] == 2
    # drain: the applied state serves the SAME answers
    inner.gate.set()
    eng.drain()
    assert eng.get_state("ns", "a").value == b"new2"
    assert eng.get_state("ns", "gone") is None
    assert inner.savepoint() == (2, 0)
    st = eng.stats()
    assert st["queue_depth"] == 0 and st["applied_num"] == 2
    assert st["applies_total"] == 2
    eng.close()


def test_overlay_range_scan_and_query_suppression():
    inner = _GatedDB()
    inner.open()
    s = UpdateBatch()
    for i in range(6):
        color = b"red" if i in (1, 2, 5) else b"blue"
        s.put("ns", f"key{i}", b'{"color":"%s"}' % color, (0, i))
    inner.seed(s, (0, 0))
    eng = AsyncApplyEngine(inner, queue_blocks=8)
    pend = UpdateBatch()
    pend.put("ns", "key2", b'{"color":"blue"}', (1, 0))  # rewrite
    pend.delete("ns", "key3", (1, 1))
    pend.put("ns", "key6", b'{"color":"red"}', (1, 2))   # new row
    eng.submit(1, pend, (1, 0))

    def rng(*a, **kw):
        return [(k, vv.value) for k, vv in eng.get_state_range(*a, **kw)]

    assert rng("ns", "key1", "key5") == [
        ("key1", b'{"color":"red"}'),
        ("key2", b'{"color":"blue"}'),   # pending rewrite wins
        ("key4", b'{"color":"blue"}'),   # key3: pending delete
    ]
    # limit counts OUTPUT rows, not inner rows eaten by suppression
    assert [k for k, _ in rng("ns", "key2", "", limit=2)] == [
        "key2", "key4",
    ]
    # rich query: the pending rewrite of key2 no longer matches red and
    # must SUPPRESS the committed (still-matching) row; pending key6
    # matches and merges in key order
    got = [k for k, _ in eng.execute_query(
        "ns", {"selector": {"color": "red"}})]
    assert got == ["key1", "key5", "key6"]
    inner.gate.set()
    eng.drain()
    # applied: identical answers with an empty queue
    assert [k for k, _ in eng.execute_query(
        "ns", {"selector": {"color": "red"}})] == ["key1", "key5", "key6"]
    eng.close()


def test_backpressure_parks_submitter_at_capacity():
    inner = _GatedDB()
    inner.open()
    eng = AsyncApplyEngine(inner, queue_blocks=2)
    eng.submit(0, _b(0, puts=[("ns", "k0", b"v")]), (0, 0))
    eng.submit(1, _b(1, puts=[("ns", "k1", b"v")]), (1, 0))
    entered = threading.Event()

    def third():
        eng.submit(2, _b(2, puts=[("ns", "k2", b"v")]), (2, 0))
        entered.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert not entered.wait(0.3), "bounded queue admitted past capacity"
    inner.gate.set()
    assert entered.wait(10.0)
    t.join(10.0)
    eng.drain()
    assert eng.stats()["backpressure_total"] >= 1
    assert inner.get_state("ns", "k2").value == b"v"
    eng.close()


def test_fail_stop_latch_reraises_at_submit_and_drain():
    inner = MemVersionedDB()
    inner.open()
    eng = AsyncApplyEngine(inner, queue_blocks=4)
    faults.configure("ledger.apply.before:raise:n=1")
    eng.submit(0, _b(0, puts=[("ns", "k0", b"v")]), (0, 0))
    with pytest.raises(RuntimeError):
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            eng.submit(1, _b(1, puts=[("ns", "k1", b"v")]), (1, 0))
            time.sleep(0.02)
        pytest.fail("latched applier failure never re-raised")
    assert eng.stats()["failed"]
    with pytest.raises(RuntimeError):
        eng.drain()
    eng.abort()


def test_a_failed_write_back_after_an_apply_latches_like_a_failed_apply():
    """``after_apply`` runs once the block is published; where it
    raises, the applier stops and the error re-raises at the next
    ``drain``/``submit``, as a failed apply's does."""
    inner = MemVersionedDB()
    inner.open()
    seen = []

    def after(root):
        seen.append(root)
        raise OSError("disk full")

    eng = AsyncApplyEngine(inner, queue_blocks=4, after_apply=after)
    eng.submit(0, _b(0, puts=[("ns", "k0", b"v")]), (0, 0))
    with pytest.raises(RuntimeError) as got:
        eng.drain()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            eng.submit(1, _b(1, puts=[("ns", "k1", b"v")]), (1, 0))
            time.sleep(0.02)
    assert isinstance(got.value.__cause__, OSError)
    assert seen == [None] and eng.stats()["failed"]
    assert inner.get_state("ns", "k0").value == b"v"
    eng.abort()


# ---------------------------------------------------------------------------
# 2. columnar write batches


def _columnar():
    """Hand-built slab batch: rows in apply order with a same-key
    rewrite (uid 0 written twice — last wins) and one delete."""
    blob = b"AAABBCCCC"
    return ColumnarUpdateBatch(
        5,
        ["ns", "zz"], ["a", "b", "c"], np.array([0, 0, 1]),
        np.array([0, 1, 0, 2]),            # uids: a, b, a again, c
        np.array([False, False, False, True]),
        np.array([0, 3, 5, 0]), np.array([3, 2, 4, 0]),
        np.array([0, 0, 1, 2], np.int64), blob,
    )


def _columnar_oracle():
    o = UpdateBatch()
    o.put("ns", "a", b"AAA", (5, 0))
    o.put("ns", "b", b"BB", (5, 0))
    o.put("ns", "a", b"CCCC", (5, 1))   # rewrite shadows
    o.delete("zz", "c", (5, 2))
    return o


def test_columnar_batch_matches_dict_form():
    cb, o = _columnar(), _columnar_oracle()
    assert list(cb.updates.items()) == list(o.updates.items())
    assert cb.touches_namespace("ns") and cb.touches_namespace("zz")
    assert not cb.touches_namespace("other")
    # post-build override shadows the slab rows everywhere
    cb.put("ns", "a", b"extra", (5, 9))
    assert cb.updates[("ns", "a")].value == b"extra"
    skipped = {k for dels, rows in cb.sqlite_columns()
               for k in ([d[1] for d in dels] + [r[1] for r in rows])}
    assert "a" not in skipped            # extras-shadowed slab row
    assert dict(cb.extra_items())[("ns", "a")].value == b"extra"
    assert cb.touches_namespace("pvt") is False
    cb.put("pvt", "h", b"x", (5, 9))
    assert cb.touches_namespace("pvt")


def test_columnar_sqlite_fast_path_byte_identical(tmp_path):
    fast = SqliteVersionedDB(str(tmp_path / "fast.db"))
    slow = SqliteVersionedDB(str(tmp_path / "slow.db"))
    fast.open()
    slow.open()
    # pre-existing row the columnar delete must remove
    pre = UpdateBatch()
    pre.put("zz", "c", b"stale", (1, 0))
    fast.apply_updates(pre, (1, 0))
    slow.apply_updates(pre, (1, 0))
    cb, o = _columnar(), _columnar_oracle()
    cb.put("ns", "d", b"late", (5, 3))   # extras ride the classic path
    o.put("ns", "d", b"late", (5, 3))
    fast.apply_updates(cb, (5, 0))       # isinstance → executemany path
    slow.apply_updates(o, (5, 0))
    assert sorted(fast.iter_all()) == sorted(slow.iter_all())
    assert fast.savepoint() == slow.savepoint() == (5, 0)
    fast.close()
    slow.close()


# ---------------------------------------------------------------------------
# 3. crash recovery at every queue depth


def _block(num, prev, payloads, channel="ch"):
    blk = pu.new_block(num, prev)
    for i, p in enumerate(payloads):
        ch = pu.make_channel_header(
            common_pb2.HeaderType.ENDORSER_TRANSACTION, channel,
            tx_id=f"tx{num}-{i}",
        )
        sh = pu.make_signature_header(b"creator", b"n")
        payload = pu.make_payload(ch, sh, p)
        env = common_pb2.Envelope(
            payload=payload.SerializeToString(), signature=b"s"
        )
        blk.data.data.append(env.SerializeToString())
    return pu.finalize_block(blk)


def _commit_stream(lg, n):
    prev = b""
    for num in range(n):
        blk = _block(num, prev, [b"data%d" % num])
        prev = pu.block_header_hash(blk.header)
        batch = UpdateBatch()
        batch.put("ns", f"k{num}", b"v%d" % num, (num, 0))
        if num:
            batch.delete("ns", f"k{num - 1}", (num, 0))
        lg.commit_block(blk, bytes([0]), batch, [("ns", f"k{num}", 0)])


def _replayer(block):
    num = block.header.number
    batch = UpdateBatch()
    batch.put("ns", f"k{num}", b"v%d" % num, (num, 0))
    if num:
        batch.delete("ns", f"k{num - 1}", (num, 0))
    return bytes([0]), batch, [("ns", f"k{num}", 0)]


def _dump(state):
    return sorted(
        (ns, key, vv.value, vv.metadata, vv.version)
        for (ns, key), vv in state.iter_all()
    )


def test_crash_recovery_differential_every_depth(tmp_path):
    n_blocks = 8
    oracle = KVLedger(str(tmp_path / "oracle"))
    _commit_stream(oracle, n_blocks)
    want = _dump(oracle.state)
    want_hist = list(oracle.history.get_history_for_key("ns", "k5"))
    oracle.close()

    for kill_at in range(1, 5):
        d = str(tmp_path / f"async{kill_at}")
        faults.configure(
            f"ledger.apply.before:raise:after={kill_at}:n=1"
        )
        lg = KVLedger(d, async_commit=True, apply_queue_blocks=4)
        try:
            _commit_stream(lg, n_blocks)
        except RuntimeError:
            pass  # the latched apply failure surfacing at a submit
        # die mid-queue: drop the pending tail, no graceful drain
        lg.engine.abort()
        lg.blocks.close()
        lg.history.close()
        lg.pvtdata.close()
        faults.reset()

        lg2 = KVLedger(d)  # reopen SERIAL
        assert lg2.height >= kill_at
        sp = lg2.state.savepoint()
        assert sp is not None and sp[0] + 1 < lg2.height, (
            f"kill_at={kill_at}: savepoint {sp} not behind height "
            f"{lg2.height}"
        )
        replayed = lg2.recover(_replayer)
        assert replayed == lg2.height - (sp[0] + 1)
        assert lg2.state.savepoint() == (lg2.height - 1, 0)
        if lg2.height == n_blocks:
            # full chain survived in the block files: state must be
            # BYTE-identical to the synchronous oracle
            assert _dump(lg2.state) == want
            assert list(
                lg2.history.get_history_for_key("ns", "k5")
            ) == want_hist
        lg2.close()


def test_async_end_to_end_commit_reopen(tmp_path):
    d = str(tmp_path / "ledger")
    lg = KVLedger(d, async_commit=True, apply_queue_blocks=2)
    _commit_stream(lg, 6)
    # read-your-writes straight after the last commit
    assert lg.state.get_state("ns", "k5").value == b"v5"
    assert lg.state.get_state("ns", "k4") is None
    assert lg.state.savepoint() == (5, 0)
    assert set(lg.last_commit_timings) == {"ledger_append", "state_apply"}
    lg.close()  # drains
    lg2 = KVLedger(d)
    assert lg2.height == 6
    assert lg2.state.savepoint() == (5, 0)
    assert lg2.state.get_state("ns", "k5").value == b"v5"
    lg2.close()


@pytest.mark.parametrize("db", ["state", "history"])
def test_a_crash_after_the_committers_checkpoint_recovers(
        tmp_path, monkeypatch, db):
    """The committer writes ``<db>.db``'s WAL back (a group checkpoint,
    here every 3 rows), then the applier dies at the next block, and
    the directory is copied as the crash left it (WAL, main file and
    block files).  Reopened serial and replayed from the block files,
    it lands the height, commit hash, state and history the
    synchronous oracle lands."""
    import shutil

    from fabric_tpu.ledger import walcheckpoint
    from fabric_tpu.ops_metrics import global_registry

    monkeypatch.setattr(walcheckpoint, "CHECKPOINT_ROWS", 3)
    groups = global_registry().counter(
        "ledger_wal_checkpoints_total",
        "WAL checkpoints of the state and history DBs by db and trigger")
    blocks, prev = [], b""
    for num in range(12):   # headers carry a timestamp: built once
        blocks.append(_block(num, prev, [b"data%d" % num]))
        prev = pu.block_header_hash(blocks[-1].header)

    def commit(lg, blk):
        _, batch, hist = _replayer(blk)
        lg.commit_block(common_pb2.Block.FromString(
            blk.SerializeToString()), bytes([0]), batch, hist)

    live, copy = str(tmp_path / "live"), str(tmp_path / "copy")
    lg = KVLedger(live, async_commit=True, apply_queue_blocks=4)
    inner = lg.engine._inner
    fast = inner.apply_updates

    def slowed(*a):     # the busier thread: the committer writes back
        threading.Event().wait(0.01)
        return fast(*a)

    inner.apply_updates = slowed
    before = groups.value(db=db, trigger="group")
    n_blocks = None
    try:
        for num, blk in enumerate(blocks):
            commit(lg, blk)
            if n_blocks is not None:
                break
            lg.drain_state()
            if groups.value(db=db, trigger="group") > before:
                assert not lg._applier_writes_back()
                # between this commit's checkpoint and the next apply
                faults.configure("ledger.apply.before:raise:n=1")
                n_blocks = num + 2
        with pytest.raises(RuntimeError):
            lg.drain_state()
        shutil.copytree(live, copy)
    finally:
        lg.engine.abort()
        lg.blocks.close()
        lg.history.close()
        lg.pvtdata.close()
        faults.reset()
    assert n_blocks is not None and n_blocks < 12

    oracle = KVLedger(str(tmp_path / "oracle"))
    for blk in blocks[:n_blocks]:
        commit(oracle, blk)
    lg2 = KVLedger(copy)
    try:
        assert lg2.height == oracle.height == n_blocks
        assert lg2.commit_hash == oracle.commit_hash
        sp = lg2.state.savepoint()
        assert sp is not None and sp[0] < n_blocks - 1
        assert lg2.recover(_replayer) == n_blocks - 1 - sp[0]
        assert lg2.state.savepoint() == (n_blocks - 1, 0)
        assert _dump(lg2.state) == _dump(oracle.state)
        for num in range(n_blocks):
            assert list(lg2.history.get_history_for_key(
                "ns", f"k{num}")) == list(
                    oracle.history.get_history_for_key("ns", f"k{num}"))
    finally:
        lg2.close()
        oracle.close()


# ---------------------------------------------------------------------------
# 4. depth-3 pipeline differential: async ON vs OFF


def test_pipeline_depth3_differential_async_vs_serial(tmp_path):
    from test_commit_pipeline import ToyValidator, _stream

    from fabric_tpu.peer.pipeline import CommitPipeline

    blocks = _stream(5, 6)

    def run(async_on):
        state = MemVersionedDB()
        lg = KVLedger(
            str(tmp_path / ("async" if async_on else "serial")),
            state_db=state, async_commit=async_on,
            apply_queue_blocks=2,
        )
        # the validator reads through lg.state: under the async engine
        # that is the pending overlay — MVCC verdicts must not change
        v = ToyValidator(lg.state)
        filters = []

        def commit_fn(res):
            lg.commit_block(res.block, res.tx_filter, res.batch,
                            res.history, None, res.txids)

        with CommitPipeline(v, commit_fn, depth=3) as pipe:
            for b in blocks:
                r = pipe.submit(b)
                if r is not None:
                    filters.append(
                        (r.block.header.number, list(r.tx_filter))
                    )
            r = pipe.flush()
            if r is not None:
                filters.append((r.block.header.number, list(r.tx_filter)))
        lg.drain_state()
        snap = dict(state._data)
        sp = lg.state.savepoint()
        height = lg.height
        lg.close()
        filters.sort()
        return filters, snap, sp, height

    fa, sa, spa, ha = run(True)
    fs, ss, sps, hs = run(False)
    assert fa == fs
    assert sa == ss
    assert spa == sps and ha == hs == 5
    # sanity: verdicts actually exercised both lanes
    assert any(
        c != 0 for _n, flt in fa for c in flt
    ) and any(c == 0 for _n, flt in fa for c in flt)


# ---------------------------------------------------------------------------
# 5. the applier on the block's span tree


class _GatedSqlite(SqliteVersionedDB):
    """The durable backend with its applies parked on a gate: every
    block's tree is finished before its apply can end."""

    def __init__(self, path):
        super().__init__(path)
        self.gate = threading.Event()

    def apply_updates(self, batch, savepoint):
        assert self.gate.wait(30.0), "apply gate never opened"
        super().apply_updates(batch, savepoint)


def _traced_pipeline_run(tmp_path, ring_blocks, n_blocks=4):
    """``n_blocks`` toy blocks through a depth-2 pipeline over a sqlite
    ledger with the async engine, the process tracer at ``ring_blocks``.
    → (roots a listener collected, what the gate saw before it opened:
    the queued entries' roots and the ``apply`` spans that had ended,
    each block's write count)."""
    from test_commit_pipeline import ToyValidator, _stream

    from fabric_tpu import observe
    from fabric_tpu.peer.pipeline import CommitPipeline

    tracer = observe.global_tracer()
    was, roots, writes = tracer.ring_blocks, [], {}
    observe.configure(ring_blocks=ring_blocks)
    tracer.add_listener(roots.append)
    inner = _GatedSqlite(str(tmp_path / "state.db"))
    lg = KVLedger(str(tmp_path / "lg"), state_db=inner, async_commit=True,
                  apply_queue_blocks=2 * n_blocks)
    try:
        def commit_fn(res):
            writes[res.block.header.number] = len(res.batch.updates)
            lg.commit_block(res.block, res.tx_filter, res.batch,
                            res.history, None, res.txids)

        with CommitPipeline(ToyValidator(lg.state), commit_fn,
                            depth=2) as pipe:
            for b in _stream(n_blocks, 4):
                pipe.submit(b)
            pipe.flush()
            # every commit has returned and every tree is finished;
            # no apply can have ended
            parked = {
                "entry_roots": [e.root for e in lg.engine._pending()],
                "ended": [sp.name for r in roots for sp in r.children
                          if sp.name == "apply" and sp.t1 is not None],
                "finished": [r.t1 is not None for r in roots],
            }
            inner.gate.set()
            lg.drain_state()
    finally:
        inner.gate.set()
        lg.close()
        tracer.remove_listener(roots.append)
        observe.configure(ring_blocks=was)
    return roots, parked, writes


def test_applier_spans_arrive_under_the_finished_block(tmp_path):
    roots, parked, writes = _traced_pipeline_run(tmp_path, 16)
    assert [r.attrs["block"] for r in roots] == [0, 1, 2, 3]
    # attached after finish_block: the trees were finished, their
    # entries still queued under their own roots, and no apply done
    assert parked["finished"] == [True] * 4 and parked["ended"] == []
    assert parked["entry_roots"] == roots
    for r in roots:
        applies = [c for c in r.children if c.name == "apply"]
        assert len(applies) == 1, [c.name for c in r.children]
        ap = applies[0]
        assert ap.thread == "fabtpu-state-applier" and ap.root is r
        assert ap.t1 is not None and ap.t1 > r.t1  # late, and t1 kept
        assert ap.attrs["queued_ms"] >= 0.0
        kids = {c.name: c for c in ap.children}
        assert list(kids) == ["apply.fence", "apply.write"]
        for c in kids.values():
            assert c.thread == ap.thread
            assert ap.t0 <= c.t0 <= c.t1 <= ap.t1
        assert kids["apply.write"].attrs["writes"] == writes[
            r.attrs["block"]]
    # the fence of the first apply closed the blockstore's window
    fsyncs = [c for r in roots for ap in r.children if ap.name == "apply"
              for f in ap.children if f.name == "apply.fence"
              for c in f.children]
    assert fsyncs and {c.name for c in fsyncs} == {"fsync"}
    assert {c.attrs["trigger"] for c in fsyncs} == {"apply"}


def test_disarmed_tracer_leaves_the_applier_untraced(tmp_path):
    roots, parked, _writes = _traced_pipeline_run(tmp_path, 0)
    assert roots == []
    assert parked["entry_roots"] == [None] * 4


def test_apply_history_span_and_backpressure_on_commit_enqueue(tmp_path):
    """``commit_block`` under a span: ``commit.append`` and
    ``commit.enqueue`` share its clock reads, ``commit.index`` sits
    inside the append, a parked enqueue says for how long, and the
    history commit is the apply's third child."""
    from fabric_tpu.observe import global_tracer

    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=8)
    inner = _GatedSqlite(str(tmp_path / "state.db"))
    lg = KVLedger(str(tmp_path / "lg"), state_db=inner, async_commit=True,
                  apply_queue_blocks=1)
    roots, prev = [], b""
    try:
        opener = threading.Timer(0.15, inner.gate.set)
        for num in range(2):
            blk = _block(num, prev, [b"data%d" % num])
            prev = pu.block_header_hash(blk.header)
            batch = UpdateBatch()
            batch.put("ns", f"k{num}", b"v", (num, 0))
            root = tracer.begin_block(num)
            roots.append(root)
            if num == 1:
                opener.start()  # block 0 holds the one queue slot
            with tracer.span("commit", parent=root):
                lg.commit_block(blk, bytes([0]), batch,
                                [("ns", f"k{num}", 0)])
            tracer.finish_block(root)
        lg.drain_state()
    finally:
        inner.gate.set()
        lg.close()
        tracer.configure(ring_blocks=was)
    for num, root in enumerate(roots):
        commit = next(c for c in root.children if c.name == "commit")
        by = {c.name: c for c in commit.children}
        assert list(by) == ["commit.index", "commit.append",
                            "commit.enqueue"]
        app, enq, idx = (by["commit.append"], by["commit.enqueue"],
                         by["commit.index"])
        assert app.t1 == enq.t0  # one clock read for the boundary
        assert app.t0 <= idx.t0 <= idx.t1 <= app.t1
        assert idx.attrs == {"idx_wait_ms": 0.0, "txids": 1}
        assert lg.last_commit_timings["state_apply"] > 0
        if num == 0:
            assert enq.attrs["backpressure_ms"] == 0.0
        else:  # parked until the gate opened
            assert 50.0 < enq.attrs["backpressure_ms"] <= (
                enq.t1 - enq.t0) * 1000.0
        ap = next(c for c in root.children if c.name == "apply")
        assert [c.name for c in ap.children] == [
            "apply.fence", "apply.write", "apply.history"]
        assert ap.children[1].attrs == {
            "writes": 1, "stmts": 0, "path": "per_key"}  # an UpdateBatch
        assert ap.children[2].attrs == {"rows": 1, "stmts": 1}


@pytest.mark.parametrize("armed", [False, True])
def test_apply_never_builds_a_columnar_batchs_dict(tmp_path, armed):
    """The sqlite fast path applies a columnar batch from its slabs; the
    ``apply.write`` span counts its rows the same way.  Building the
    lazy dict to count them cost 6 ms a 1000-tx block (``rw_paced`` on
    the chip, PR 25), traced or not."""
    from fabric_tpu.observe import global_tracer

    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=4 if armed else 0)
    inner = SqliteVersionedDB(str(tmp_path / "state.db"))
    inner.open()
    eng = AsyncApplyEngine(inner)
    cb = _columnar()
    cb.put("ns", "d", b"late", (5, 3))   # one post-build override
    try:
        root = tracer.begin_block(5)
        with tracer.span("commit", parent=root):
            eng.submit(5, cb, (5, 0))
        tracer.finish_block(root)
        eng.drain()
        assert cb._updates is None
        assert inner.get_state("ns", "a").value == b"CCCC"
    finally:
        eng.close()
        tracer.configure(ring_blocks=was)
    if armed:
        ap = next(c for c in root.children if c.name == "apply")
        write = next(c for c in ap.children if c.name == "apply.write")
        # slab rows + override; an upsert for ``ns``, a delete for ``zz``
        assert write.attrs == {"writes": 4 + 1, "stmts": 2, "path": "block"}
    else:
        assert root is None


class _WatchedDB:
    """A state DB behind a proxy that notes which of its attributes the
    engine reads."""

    def __init__(self, db):
        self._db = db
        self.read = set()

    def __getattr__(self, name):
        self.read.add(name)
        return getattr(self._db, name)


@pytest.mark.parametrize("armed", [False, True])
def test_apply_spans_say_how_the_block_was_written(tmp_path, armed):
    """A traced block's ``apply.write`` carries ``writes``, the
    statements its block path sent the state table (``stmts``, 1 for a
    one-namespace block) and ``path``; ``apply.history`` carries what
    the history DB's ``commit_block`` returns.  A backend that counts no
    statements gets neither, and disarmed nothing is read."""
    from fabric_tpu.ledger.history import HistoryDB
    from fabric_tpu.observe import global_tracer

    tracer = global_tracer()
    was = tracer.ring_blocks
    tracer.configure(ring_blocks=4 if armed else 0)
    sq = SqliteVersionedDB(str(tmp_path / "state.db"))
    sq.open()
    hist = HistoryDB(str(tmp_path / "history.db"))
    blob = b"AAABB"
    one_ns = ColumnarUpdateBatch(
        1, ["ns"], ["a", "b"], np.array([0, 0]), np.array([0, 1]),
        np.array([False, False]), np.array([0, 3]), np.array([3, 2]),
        np.array([0, 1], np.int64), blob)
    per_key = UpdateBatch()
    per_key.put("ns", "c", b"c", (2, 0))
    mem_batch = UpdateBatch()
    mem_batch.put("ns", "c", b"c", (1, 0))
    hw = [("ns", "a", 0), ("ns", "b", 1)]
    watched = [_WatchedDB(sq), _WatchedDB(MemVersionedDB())]
    roots = []
    try:
        for inner, blocks in zip(watched, (
            [(1, one_ns, lambda: hist.commit_block(1, hw)),
             (2, per_key, lambda: None)],
            [(1, mem_batch, None)],
        )):
            eng = AsyncApplyEngine(inner)
            try:
                for num, batch, post in blocks:
                    root = tracer.begin_block(num)
                    roots.append(root)
                    with tracer.span("commit", parent=root):
                        eng.submit(num, batch, (num, 0), post_apply=post)
                    tracer.finish_block(root)
                eng.drain()
                assert inner.get_state("ns", "c").value == b"c"
            finally:
                eng.close()               # and the DB behind it
        assert list(hist.get_history_for_key("ns", "b")) == [(1, 1)]
    finally:
        hist.close()
        tracer.configure(ring_blocks=was)
    counters = {"apply_statements", "apply_fast_blocks"}
    if not armed:
        assert roots == [None] * 3
        assert not counters & (watched[0].read | watched[1].read)
        return
    assert counters <= watched[0].read
    spans = []
    for root in roots:
        ap = next(c for c in root.children if c.name == "apply")
        spans.append({c.name: c.attrs for c in ap.children})
    assert spans == [
        {"apply.write": {"writes": 2, "stmts": 1, "path": "block"},
         "apply.history": {"rows": 2, "stmts": 1}},
        {"apply.write": {"writes": 1, "stmts": 0, "path": "per_key"},
         "apply.history": {}},
        {"apply.write": {"writes": 1}},
    ]
