"""Pipelined commit path: validate_launch/validate_finish with the
predecessor-overlay, in-flight dup-txid checks, and the committer-thread
overlap — the depth-2 pipeline the production CommitPipeline
(peer/pipeline.py) drives for both the node's deliver loop and
the benchmark's harness, pinned against the serial validate() verdicts.  The
crypto-free pipeline-engine semantics live in
tests/test_commit_pipeline.py."""

import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.crypto import cryptogen
from fabric_tpu.crypto import policy as pol
from fabric_tpu.crypto.msp import MSPManager
from fabric_tpu.ledger.rwset import TxRWSet
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
from fabric_tpu.peer import txassembly as txa
from fabric_tpu.peer.pipeline import CommitPipeline
from fabric_tpu.peer.validator import BlockValidator, NamespaceInfo, PolicyProvider
from fabric_tpu.protos import common_pb2, transaction_pb2

C = transaction_pb2.TxValidationCode
CHANNEL, CC = "pipechan", "pipecc"


@pytest.fixture(scope="module")
def net():
    org1 = cryptogen.generate_org("Org1MSP", "org1.example.com", peers=1, users=1)
    org2 = cryptogen.generate_org("Org2MSP", "org2.example.com", peers=1)
    policy = pol.from_dsl("OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer')")
    return {
        "mgr": MSPManager({"Org1MSP": org1.msp(), "Org2MSP": org2.msp()}),
        "client": cryptogen.signing_identity(org1, "User1@org1.example.com"),
        "peers": [
            cryptogen.signing_identity(org1, "peer0.org1.example.com"),
            cryptogen.signing_identity(org2, "peer0.org2.example.com"),
        ],
        "prov": PolicyProvider({CC: NamespaceInfo(policy=policy)}),
    }


def _tx(net, reads=(), writes=(), deletes=(), ranges=()):
    _, _, prop = txa.create_signed_proposal(net["client"], CHANNEL, CC, [b"i"])
    tx = TxRWSet()
    ns = tx.ns_rwset(CC)
    for k, ver in reads:
        ns.reads[k] = ver
    for k, v in writes:
        ns.writes[k] = v
    for k in deletes:
        ns.writes[k] = None
    for start, end, results in ranges:
        ns.range_queries.append((start, end, list(results)))
    rw = tx.to_proto().SerializeToString()
    resps = [txa.create_proposal_response(prop, rw, e, CC) for e in net["peers"]]
    return txa.assemble_transaction(prop, resps, net["client"])


def _block(num, prev, envs, pad_net=None):
    raw = [e.SerializeToString() for e in envs]
    if pad_net is not None:
        while len(raw) < 16:  # engage the native fast path
            raw.append(_tx(
                pad_net, writes=[(f"pad{num}_{len(raw)}", b"x")]
            ).SerializeToString())
    blk = pu.new_block(num, prev)
    for r in raw:
        blk.data.data.append(r)
    return pu.finalize_block(blk)


def _state(net):
    db = MemVersionedDB()
    seed = UpdateBatch()
    seed.put(CC, "s1", b"v", (1, 0))
    seed.put(CC, "s2", b"v", (1, 0))
    seed.put(CC, "dkey", b"v", (1, 0))
    db.apply_updates(seed, (1, 0))
    return db


def test_overlay_versions_and_dup_txid(net):
    """launch(n+1) with block n's UpdateBatch as overlay (commit NOT
    yet applied) must reach the same verdicts as committing n first:
    cross-block read-your-predecessor versions, stale reads of keys a
    VALID predecessor tx rewrote, deletes, and duplicate txids."""
    env_w = _tx(net, reads=[("s1", (1, 0))], writes=[("w1", b"1"), ("s2", b"n")])
    env_del = _tx(net, deletes=["dkey"], reads=[("dkey", (1, 0))])
    b2 = _block(2, b"p2", [env_w, env_del], pad_net=net)

    # block 3: reads that depend on block 2's outcome + a replayed env
    env_ok = _tx(net, reads=[("w1", (2, 0))], writes=[("x", b"1")])
    env_stale = _tx(net, reads=[("s2", (1, 0))], writes=[("y", b"1")])
    env_gone = _tx(net, reads=[("dkey", (1, 0))], writes=[("z", b"1")])
    b3 = _block(3, b"p3", [env_ok, env_stale, env_gone, env_w], pad_net=net)

    for mode in ("overlay", "committed"):
        state = _state(net)
        v = BlockValidator(net["mgr"], net["prov"], state)
        p2 = v.validate_launch(b2)
        flt2, batch2, _ = v.validate_finish(p2)
        assert flt2[0] == C.VALID and flt2[1] == C.VALID
        if mode == "committed":
            state.apply_updates(batch2, (2, 0))
            overlay, extra = None, None
        else:
            overlay, extra = batch2, p2.txids  # commit still "in flight"
        p3 = v.validate_launch(b3, overlay=overlay, extra_txids=extra)
        flt3, _, _ = v.validate_finish(p3)
        assert flt3[0] == C.VALID, mode            # sees (2,0) via overlay
        assert flt3[1] == C.MVCC_READ_CONFLICT, mode  # s2 rewritten by b2
        assert flt3[2] == C.MVCC_READ_CONFLICT, mode  # dkey deleted by b2
        if mode == "overlay":
            assert flt3[3] == C.DUPLICATE_TXID     # via extra_txids
        # committed mode: without a block store the replayed env is not
        # detectable — the store-backed path is covered in test_e2e


def test_overlay_range_phantom(net):
    """A key written by the in-flight predecessor inside a recorded
    range (and absent from its results) must yield
    PHANTOM_READ_CONFLICT — the overlay arm of range re-execution."""
    env_w = _tx(net, writes=[("r5", b"new")])
    b2 = _block(2, b"p2", [env_w], pad_net=net)
    env_rq = _tx(
        net, writes=[("q", b"1")],
        ranges=[("r0", "r9", [("r1", (1, 0))])],  # r5 not in results
    )
    env_rq_ok = _tx(
        net, writes=[("q2", b"1")],
        ranges=[("t0", "t9", [])],  # disjoint range: unaffected
    )
    b3 = _block(3, b"p3", [env_rq, env_rq_ok], pad_net=net)

    state = _state(net)
    seed = UpdateBatch()
    seed.put(CC, "r1", b"v", (1, 0))
    state.apply_updates(seed, (1, 0))
    v = BlockValidator(net["mgr"], net["prov"], state)
    p2 = v.validate_launch(b2)
    flt2, batch2, _ = v.validate_finish(p2)
    assert flt2[0] == C.VALID
    p3 = v.validate_launch(b3, overlay=batch2, extra_txids=p2.txids)
    flt3, _, _ = v.validate_finish(p3)
    assert flt3[0] == C.PHANTOM_READ_CONFLICT
    assert flt3[1] == C.VALID


def test_pipelined_stream_matches_serial(net):
    """Full depth-2 pipelined drive (prefetch + committer threads, as
    CommitPipeline runs them) over a dependent stream — filters and final state must
    equal the serial validate()+commit run.  Blocks with range queries
    ride along, exercising the state-DB iteration lock against the
    concurrent apply_updates."""
    def build_blocks():
        blocks, prev = [], b"genesis"
        for n in range(2, 8):
            envs = [
                _tx(net, reads=[(f"k{n-1}", (n - 1, 0))] if n > 2 else (),
                    writes=[(f"k{n}", b"v")]),
                _tx(net, writes=[(f"m{n}", b"v")],
                    ranges=[(f"k{n-1}", f"k{n-1}~", [])] if n % 2 == 0 else ()),
            ]
            blk = _block(n, prev, envs, pad_net=net)
            prev = pu.block_header_hash(blk.header)
            blocks.append(blk)
        return blocks

    def fresh():
        state = MemVersionedDB()
        seed = UpdateBatch()
        seed.put(CC, "k1", b"v", (1, 0))
        state.apply_updates(seed, (1, 0))
        return state, BlockValidator(net["mgr"], net["prov"], state)

    blocks = build_blocks()

    # serial reference
    state_s, v_s = fresh()
    serial_filters = []
    for n, b in enumerate(blocks, start=2):
        flt, batch, _ = v_s.validate(b)
        state_s.apply_updates(batch, (n, 0))
        serial_filters.append(flt)

    # pipelined run with a real committer thread (delayed apply to
    # widen the race window the overlay must cover)
    state_p, v_p = fresh()
    filters = []
    with ThreadPoolExecutor(1) as committer:
        prev_pend = overlay = extra = None
        commit_fut = None
        prev_num = None

        def commit(batch, num):
            time.sleep(0.01)  # hold the commit in flight
            state_p.apply_updates(batch, (num, 0))

        for n, b in enumerate(blocks, start=2):
            if prev_pend is not None:
                flt, batch, _ = v_p.validate_finish(prev_pend)
                filters.append(flt)
                if commit_fut is not None:
                    commit_fut.result()
                commit_fut = committer.submit(commit, batch, prev_num)
                overlay, extra = batch, prev_pend.txids
            prev_pend = v_p.validate_launch(b, overlay=overlay, extra_txids=extra)
            prev_num = n
        flt, batch, _ = v_p.validate_finish(prev_pend)
        filters.append(flt)
        if commit_fut is not None:
            commit_fut.result()
        state_p.apply_updates(batch, (prev_num, 0))

    assert [list(f) for f in filters] == [list(f) for f in serial_filters]
    assert dict(state_p._data) == dict(state_s._data)


def _tx_ns(net, ns_writes: dict):
    """A tx writing into explicit namespaces (e.g. _lifecycle)."""
    _, _, prop = txa.create_signed_proposal(net["client"], CHANNEL, CC, [b"i"])
    tx = TxRWSet()
    for ns_name, writes in ns_writes.items():
        ns = tx.ns_rwset(ns_name)
        for k, v in writes:
            ns.writes[k] = v
    rw = tx.to_proto().SerializeToString()
    resps = [txa.create_proposal_response(prop, rw, e, CC) for e in net["peers"]]
    return txa.assemble_transaction(prop, resps, net["client"])


def _drive_pipeline(net, blocks, prov=None, depth=2, commit_sleep=0.01):
    """Run ``blocks`` through the production CommitPipeline with a
    delayed committer (widening the race window the overlay must
    cover).  → (filters, final state dict, launch log, commit log)."""
    state = _state(net)
    v = BlockValidator(net["mgr"], prov or net["prov"], state)
    committed: list = []
    launches: list = []

    orig_launch = v.validate_launch

    def launch(b, pre=None, overlay=None, extra_txids=None):
        launches.append((
            b.header.number, overlay is not None, list(committed),
        ))
        return orig_launch(b, pre=pre, overlay=overlay,
                           extra_txids=extra_txids)

    v.validate_launch = launch

    def commit_fn(res):
        time.sleep(commit_sleep)  # hold the commit in flight
        state.apply_updates(res.batch, (res.block.header.number, 0))
        committed.append(res.block.header.number)

    filters = []
    with CommitPipeline(v, commit_fn, depth=depth) as pipe:
        for b in blocks:
            r = pipe.submit(b)
            if r is not None:
                filters.append((r.block.header.number, list(r.tx_filter)))
        r = pipe.flush()
        if r is not None:
            filters.append((r.block.header.number, list(r.tx_filter)))
    filters.sort()
    return filters, dict(state._data), launches, committed


def test_commit_pipeline_matches_serial(net):
    """The production CommitPipeline over a dependent stream (block
    n+1 reading a key block n wrote, range queries riding along) must
    produce the serial validate()+commit filters and state."""
    def build_blocks():
        blocks, prev = [], b"genesis"
        for n in range(2, 8):
            envs = [
                _tx(net, reads=[(f"k{n-1}", (n - 1, 0))] if n > 2 else (),
                    writes=[(f"k{n}", b"v")]),
                _tx(net, writes=[(f"m{n}", b"v")],
                    ranges=[(f"k{n-1}", f"k{n-1}~", [])] if n % 2 == 0 else ()),
            ]
            blk = _block(n, prev, envs, pad_net=net)
            prev = pu.block_header_hash(blk.header)
            blocks.append(blk)
        return blocks

    blocks = build_blocks()

    # serial reference
    state_s = _state(net)
    v_s = BlockValidator(net["mgr"], net["prov"], state_s)
    serial = []
    for n, b in enumerate(blocks, start=2):
        flt, batch, _ = v_s.validate(b)
        state_s.apply_updates(batch, (n, 0))
        serial.append((n, list(flt)))

    filters, state_p, launches, _ = _drive_pipeline(net, blocks)
    assert filters == serial
    assert state_p == dict(state_s._data)
    # depth-2 actually overlapped: every non-first launch carried the
    # predecessor's batch as overlay
    assert [ov for _, ov, _ in launches] == [False] + [True] * 5

    # serial mode through the same engine: identical verdicts, no
    # overlays anywhere
    filters1, state1, launches1, _ = _drive_pipeline(net, blocks, depth=1)
    assert filters1 == serial and state1 == state_p
    assert all(not ov for _, ov, _ in launches1)


def test_commit_pipeline_depth3_matches_serial(net):
    """Depth-3 over the FULL BlockValidator: a stream whose RW
    dependencies span BOTH in-flight predecessors (k→k+1 and k→k+2
    fresh reads, a hot key overwritten every block and read at the
    immediate predecessor's version — merged-overlay newest-wins)
    must equal the serial oracle in filters and state, and depth 4
    rides along."""
    def build_blocks(lo=2, hi=9):
        blocks, prev = [], b"genesis"
        for n in range(lo, hi):
            reads = []
            if n > lo:
                reads.append((f"k{n-1}", (n - 1, 1)))
                reads.append(("hot", (n - 1, 1)))
            if n > lo + 1:
                reads.append((f"q{n-2}", (n - 2, 1)))
            envs = [
                # reader FIRST: its hot read validates against the
                # predecessor's version, not this block's own writer
                _tx(net, reads=reads),
                _tx(net, writes=[(f"k{n}", b"v"), (f"q{n}", b"v"),
                                 ("hot", b"h%d" % n)]),
            ]
            blk = _block(n, prev, envs, pad_net=net)
            prev = pu.block_header_hash(blk.header)
            blocks.append(blk)
        return blocks

    blocks = build_blocks()

    # serial reference
    state_s = _state(net)
    v_s = BlockValidator(net["mgr"], net["prov"], state_s)
    serial = []
    for n, b in enumerate(blocks, start=2):
        flt, batch, _ = v_s.validate(b)
        state_s.apply_updates(batch, (n, 0))
        serial.append((n, list(flt)))
    # every lane VALID: the conflict chains are all fresh by design
    assert all(all(c == 0 for c in flt) for _, flt in serial)

    for depth in (3, 4):
        filters, state_p, launches, _ = _drive_pipeline(
            net, blocks, depth=depth
        )
        assert filters == serial, f"depth {depth}"
        assert state_p == dict(state_s._data), f"depth {depth}"
        assert [ov for _, ov, _ in launches] == [False] + [True] * 6


def test_commit_pipeline_depth3_merged_overlay_forced(net):
    """Deterministic merged-overlay proof on the full validator: the
    commits of BOTH predecessors are gated closed while block 4
    launches, so its k→k+1, k→k+2 and hot-key reads can resolve only
    through the merged overlay chain."""
    import threading

    blocks, prev = [], b"genesis"
    for n in (2, 3, 4):
        reads = []
        if n > 2:
            reads.append((f"k{n-1}", (n - 1, 1)))
            reads.append(("hot", (n - 1, 1)))
        if n > 3:
            reads.append((f"q{n-2}", (n - 2, 1)))
        envs = [
            _tx(net, reads=reads),
            _tx(net, writes=[(f"k{n}", b"v"), (f"q{n}", b"v"),
                             ("hot", b"h%d" % n)]),
        ]
        blk = _block(n, prev, envs, pad_net=net)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)

    state = _state(net)
    v = BlockValidator(net["mgr"], net["prov"], state)
    gate = threading.Event()
    committed: list = []

    def commit_fn(res):
        if res.block.header.number < 4:
            assert gate.wait(60.0), "commit gate never opened"
        state.apply_updates(res.batch, (res.block.header.number, 0))
        committed.append(res.block.header.number)

    filters = []
    with CommitPipeline(v, commit_fn, depth=3) as pipe:
        for b in blocks:
            r = pipe.submit(b)
            if r is not None:
                filters.append((r.block.header.number, list(r.tx_filter)))
        # block 4 is launched; blocks 2 and 3 are still uncommitted
        assert committed == []
        gate.set()
        r = pipe.flush()
        if r is not None:
            filters.append((r.block.header.number, list(r.tx_filter)))
    filters.sort()
    assert committed == [2, 3, 4]
    # every read resolved fresh through the merged chain
    assert all(all(c == 0 for c in flt) for _, flt in filters)


def test_commit_pipeline_lifecycle_barrier(net):
    """A block writing ``_lifecycle`` must commit FULLY before its
    successor launches, and the successor launches with the overlay
    dropped — then pipelining resumes."""
    prov = PolicyProvider({
        CC: net["prov"].infos[CC],
        "_lifecycle": net["prov"].infos[CC],
    })
    blocks, prev = [], b"genesis"
    envs_by_n = {
        2: [_tx(net, writes=[("a2", b"v")])],
        3: [_tx_ns(net, {
            "_lifecycle": [("namespaces/fields/cc1/Definition", b"d")],
            CC: [("a3", b"v")],
        })],
        4: [_tx(net, reads=[("a3", (3, 0))], writes=[("a4", b"v")])],
        5: [_tx(net, writes=[("a5", b"v")])],
    }
    for n in range(2, 6):
        blk = _block(n, prev, envs_by_n[n], pad_net=net)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)

    filters, state_p, launches, committed = _drive_pipeline(
        net, blocks, prov=prov
    )
    assert all(c == 0 for _, flt in filters for c in flt)
    info = {n: (ov, done) for n, ov, done in launches}
    # successor of the barrier: overlay dropped AND block 3 fully
    # committed before launch
    assert info[4][0] is False
    assert 3 in info[4][1]
    # pipelining resumed after the barrier
    assert info[5][0] is True
    assert committed == [2, 3, 4, 5]


def test_commit_pipeline_resident_state_matches_serial(net):
    """ISSUE 14: the device-resident MVCC state path over the FULL
    BlockValidator ≡ the host state_fill oracle — a hot key re-read
    every block (residency hits), k→k+1 reads crossing the in-flight
    window, per-block stale lanes and deletes churning the cache —
    verdict- and state-identical at depths 2 and 3, plus a 32-slot
    eviction-churn variant."""
    from fabric_tpu.state import ResidencyManager

    def build_blocks(lo=2, hi=9):
        blocks, prev = [], b"genesis"
        for n in range(lo, hi):
            envs = [
                _tx(net, reads=[("s1", (1, 0))],
                    writes=[(f"a{n}", b"x")]),
                _tx(net,
                    reads=([(f"k{n-1}", (n - 1, 3))] if n > lo else []),
                    writes=[(f"b{n}", b"y")]),
                _tx(net, reads=[("s2", (9, 9))],
                    writes=[(f"c{n}", b"z")]),
                _tx(net, writes=[(f"k{n}", b"v")],
                    deletes=([f"k{n-2}"] if n > lo + 1 else [])),
            ]
            blk = _block(n, prev, envs, pad_net=net)
            prev = pu.block_header_hash(blk.header)
            blocks.append(blk)
        return blocks

    blocks = build_blocks()

    # serial host-oracle reference (state_resident OFF — the exact
    # existing path)
    state_s = _state(net)
    v_s = BlockValidator(net["mgr"], net["prov"], state_s)
    serial = []
    for n, b in enumerate(blocks, start=2):
        flt, batch, _ = v_s.validate(b)
        state_s.apply_updates(batch, (n, 0))
        serial.append((n, list(flt)))
    # the lanes are load-bearing: hot-hit VALID, stale MVCC, k→k+1 fresh
    for n, flt in serial:
        assert flt[0] == C.VALID
        assert flt[2] == C.MVCC_READ_CONFLICT
        if n > 2:
            assert flt[1] == C.VALID

    for depth, tiny in ((2, False), (3, False), (2, True)):
        state_p = _state(net)
        v_p = BlockValidator(
            net["mgr"], net["prov"], state_p,
            state_resident=True, state_resident_mb=1,
        )
        assert v_p.resident is not None
        if tiny:
            # eviction churn: a block asks for 18-20 keys (reads,
            # writes and the padding txs'), and a table smaller than
            # one block's set sends the block to the host path with
            # nothing admitted.  32 slots hold one block's set and not
            # two, so from the second block on every launch evicts,
            # never changing a verdict
            v_p.resident = ResidencyManager(slots=32, range_bits=4)
        filters = []

        def commit_fn(res, _state=state_p):
            _state.apply_updates(
                res.batch, (res.block.header.number, 0)
            )

        with CommitPipeline(v_p, commit_fn, depth=depth) as pipe:
            for b in blocks:
                r = pipe.submit(b)
                if r is not None:
                    filters.append(
                        (r.block.header.number, list(r.tx_filter))
                    )
            r = pipe.flush()
            if r is not None:
                filters.append(
                    (r.block.header.number, list(r.tx_filter))
                )
        filters.sort()
        assert filters == serial, (depth, tiny)
        assert dict(state_p._data) == dict(state_s._data), (depth, tiny)
        st = v_p.resident.stats()
        if tiny:
            assert st["evictions_total"] > 0
        else:
            assert st["hits_total"] > 0, (
                "the hot working set never hit the resident table"
            )
        v_p.close()


# ---------------------------------------------------------------------------
# what is inside launch, and the wait between submits, on the span tree


@pytest.fixture
def traced_roots():
    """The process tracer armed, a listener collecting finished roots."""
    from fabric_tpu import observe

    tracer = observe.global_tracer()
    was, roots = tracer.ring_blocks, []
    observe.configure(ring_blocks=16)
    tracer.add_listener(roots.append)
    yield roots
    tracer.remove_listener(roots.append)
    observe.configure(ring_blocks=was)


def _chained(net, n_blocks, replay_in=None):
    """Blocks 0..n-1 chained by hash, 16 txs each; block ``replay_in``
    carries an envelope of block 0 again."""
    blocks, prev, first = [], b"", None
    for n in range(n_blocks):
        envs = [_tx(net, writes=[(f"w{n}", b"1")])]
        first = first or envs[0]
        if n == replay_in:
            envs.append(first)
        blk = _block(n, prev, envs, pad_net=net)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)
    return blocks


@pytest.mark.parametrize("store", ["block_store", "in_flight_only"])
def test_dup_txid_span_is_a_child_of_launch(net, tmp_path, traced_roots,
                                            store):
    """``dup_txid`` hangs under ``launch`` and says how many txs it
    checked and how many it found: against the block store's index
    (block 0 committed long before block 2 launches), or against the
    in-flight predecessor's txid set alone."""
    from fabric_tpu.ledger.blockstore import BlockStore

    by_store = store == "block_store"
    blocks = _chained(net, 3, replay_in=2 if by_store else 1)
    bs = BlockStore(str(tmp_path / "chains")) if by_store else None
    state = _state(net)
    v = BlockValidator(net["mgr"], net["prov"], state, block_store=bs)
    filters = {}

    def commit_fn(res):
        num = res.block.header.number
        if bs is not None:
            pu.set_tx_filter(res.block, res.tx_filter)
            bs.add_block(res.block, txids=res.txids)
        state.apply_updates(res.batch, (num, 0))
        filters[num] = list(res.tx_filter)

    with CommitPipeline(v, commit_fn, depth=2) as pipe:
        for b in blocks:
            pipe.submit(b)
    if bs is not None:
        bs.close()
    v.close()
    dup_block = 2 if by_store else 1
    assert filters[dup_block].count(C.DUPLICATE_TXID) == 1
    assert [r.attrs["block"] for r in traced_roots] == [0, 1, 2]
    for r in traced_roots:
        launch = [c for c in r.children if c.name == "launch"]
        assert len(launch) == 1
        dups = [c for c in launch[0].children if c.name == "dup_txid"]
        # block 0 of the store-less run has nothing to check against
        if not by_store and r.attrs["block"] == 0:
            assert dups == []
            continue
        assert len(dups) == 1
        d = dups[0]
        assert d.thread == launch[0].thread
        assert launch[0].t0 <= d.t0 <= d.t1 <= launch[0].t1
        # every tx of these blocks is a well-formed endorser tx, still
        # undetermined when the launch looks it up
        assert d.attrs["lookups"] == len(
            blocks[r.attrs["block"]].data.data)
        assert d.attrs["hits"] == (r.attrs["block"] == dup_block)
        # one statement a block to the index; none without a store
        assert d.attrs["queries"] == (1 if by_store else 0)
        assert d.attrs["idx_wait_ms"] >= 0.0


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_dup_txid_check_is_exact_at_every_depth(net, tmp_path, traced_roots,
                                                depth):
    """The duplicate check asks the block store's index once a block,
    on a read connection the committer does not use, and still equals
    the serial oracle at every depth.  Block 5 repeats a txid of block
    4 (its commit in flight at depth >= 2), of block 3 (in flight at
    depth 3, just committed at depth 2), of block 2 (just committed at
    depth 3) and of block 0 (old), and carries one txid twice: the
    index or the in-flight window must know each, with the committer
    held back so that the launch really reads beside its insert."""
    import numpy as np

    from fabric_tpu.ledger.blockstore import BlockStore

    def fresh(tag):
        return _tx(net, writes=[(f"w{tag}", b"1")])

    envs = {n: [fresh(f"{n}_{i}") for i in range(16)] for n in range(5)}
    twice = fresh("5_twice")
    envs[5] = ([fresh("5_a"), envs[4][0], fresh("5_b"), envs[3][1],
                envs[0][2], twice, twice, envs[2][0]]
               + [fresh(f"5_{i}") for i in range(8)])
    want_dups = [1, 3, 4, 6, 7]
    blocks, prev = [], b""
    for n in range(6):
        blk = _block(n, prev, envs[n])
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)

    def rig(name):
        bs = BlockStore(str(tmp_path / name))
        state = _state(net)
        v = BlockValidator(net["mgr"], net["prov"], state, block_store=bs)
        return bs, state, v

    def commit(bs, state, block, flt, batch, txids=None):
        pu.set_tx_filter(block, flt)
        bs.add_block(block, txids=txids)
        state.apply_updates(batch, (block.header.number, 0))

    # the serial oracle: each block committed before the next is looked at
    bs_s, state_s, v_s = rig("serial")
    serial = []
    for b in blocks:
        b = common_pb2.Block.FromString(b.SerializeToString())
        flt, batch, _ = v_s.validate(b)
        commit(bs_s, state_s, b, flt, batch)
        serial.append(list(flt))
    bs_s.close()
    v_s.close()
    assert [i for i, c in enumerate(serial[5])
            if c == C.DUPLICATE_TXID] == want_dups
    assert all(c == C.VALID for f in serial[:5] for c in f)

    bs, state, v = rig("piped")
    launch, live_dups, filters = v.validate_launch, {}, {}

    def launch_and_look(b, **kw):
        pend = launch(b, **kw)
        assert pend.fb is not None      # the columnar parse engaged
        live_dups[b.header.number] = np.flatnonzero(
            np.asarray(pend.fb.codes) == int(C.DUPLICATE_TXID)).tolist()
        return pend

    v.validate_launch = launch_and_look

    def commit_fn(res):
        time.sleep(0.05)    # hold the commit, and its index insert, in flight
        commit(bs, state, res.block, res.tx_filter, res.batch, res.txids)
        filters[res.block.header.number] = list(res.tx_filter)

    with CommitPipeline(v, commit_fn, depth=depth) as pipe:
        for b in blocks:
            pipe.submit(b)
    assert bs.height == 6
    bs.close()
    v.close()
    assert [filters[n] for n in range(6)] == serial
    # fb.codes, the verdict array state_fill reads, in step at launch
    assert live_dups == {**{n: [] for n in range(5)}, 5: want_dups}
    assert [r.attrs["block"] for r in traced_roots[-6:]] == list(range(6))
    for r in traced_roots[-6:]:
        (lsp,) = [c for c in r.children if c.name == "launch"]
        (d,) = [c for c in lsp.children if c.name == "dup_txid"]
        n = r.attrs["block"]
        # the in-block repeat was settled by the parse: not looked up
        assert d.attrs["lookups"] == (15 if n == 5 else 16)
        assert d.attrs["hits"] == (4 if n == 5 else 0)
        assert d.attrs["queries"] == 1
        assert d.attrs["idx_wait_ms"] >= 0.0


def test_feed_wait_covers_the_gap_between_submits(net, traced_roots):
    """``feed_wait`` on a block's root runs from the previous submit's
    return to this submit's entry, so it ends where the root begins;
    after a flush it runs from the flush's return and says so; the
    idle that led to a flush lands on the block the flush finishes;
    the first block has none before it.  It is a wait:
    ``observe.overlap`` does not count it as host work."""
    from fabric_tpu.observe.overlap import NON_HOST

    blocks = _chained(net, 4)
    state = _state(net)
    v = BlockValidator(net["mgr"], net["prov"], state)
    marks = {}
    with CommitPipeline(
            v, lambda res: state.apply_updates(
                res.batch, (res.block.header.number, 0)),
            depth=2) as pipe:
        pipe.submit(blocks[0])
        marks["ret0"] = time.perf_counter()
        time.sleep(0.08)
        marks["in1"] = time.perf_counter()
        pipe.submit(blocks[1])
        pipe.submit(blocks[2])
        time.sleep(0.03)
        marks["flush_in"] = time.perf_counter()
        pipe.flush()
        marks["flush_ret"] = time.perf_counter()
        time.sleep(0.05)
        marks["in3"] = time.perf_counter()
        pipe.submit(blocks[3])
    v.close()
    by_block = {r.attrs["block"]: r for r in traced_roots}
    waits = {k: [c for c in r.children if c.name == "feed_wait"]
             for k, r in by_block.items()}
    assert [[sorted(w.attrs) for w in waits[k]] for k in range(4)] == [
        [], [[]], [[], ["before_flush"]],
        [["after_flush"], ["before_flush"]]]   # the pipe's exit flushes
    for k in (1, 2, 3):
        w, r = waits[k][0], by_block[k]
        assert w.t0 <= w.t1 <= r.t0 and w.thread == r.thread
    w1, w2, w3 = waits[1][0], waits[2][0], waits[3][0]
    # the sleep between two submits, and nothing of either submit
    assert w1.t0 <= marks["ret0"] and marks["in1"] <= w1.t1
    assert 0.08 <= w1.t1 - w1.t0 < 0.08 + 0.05
    assert w2.t1 - w2.t0 < 0.02
    # the idle before a flush lands on the block in hand, after its
    # launch and before its finish
    idle, r2 = waits[2][1], by_block[2]
    launch, finish = (next(c for c in r2.children if c.name == n)
                      for n in ("launch", "finish"))
    assert launch.t1 <= idle.t0 and idle.t1 <= finish.t0
    assert 0.03 <= idle.t1 - idle.t0 < 0.03 + 0.05
    assert idle.t1 <= marks["flush_in"] + 1e-3
    # after a flush: from the flush's return, not from submit(2)'s
    assert marks["flush_in"] < w3.t0 <= marks["flush_ret"]
    assert 0.05 <= w3.t1 - w3.t0 < 0.05 + 0.05
    assert "feed_wait" in NON_HOST
