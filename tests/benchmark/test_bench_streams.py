"""Every traffic mix's stream against the plain reference at a tiny size:
the plan-level reference, the OpenSSL reference on the blocks' bytes, and
(for the Zipf stream, whose verdicts depend on each other) the system
itself through ``CommitPipeline`` at depth 2.  Last, the seam through
which a deployment brings its own transactions: the listed cells' rows
pinned to the parent's, and a generator's own assembler through the
spawned workers."""

import shutil

import pytest

from bench_tiny import tiny_cell
from benchmark import harness, manifest, preload, reference, stream
from benchmark.reference import C

CELLS = ["rw_backlog", "insert_backlog", "rw_paced", "zipf_backlog",
         "long_chain_backlog"]


@pytest.fixture(scope="module")
def network():
    config, _ = tiny_cell("rw_backlog")
    net = stream.make_network(config)
    return net, stream.signers(net), stream.msp_manager(net)


def _provider(config):
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    return PolicyProvider({config["chaincode"]: NamespaceInfo(
        policy=pol.from_dsl(config["endorsement_policy"]))})


def _planner(config, traffic, seed):
    return manifest.load_module("generators", traffic["generator"]).planner(
        config, traffic, seed, manifest.reference_of(config))


@pytest.mark.parametrize("cell", CELLS)
def test_stream_agrees_with_both_references(cell, network):
    config, traffic = tiny_cell(cell)
    _net, (endorsers, client), mgr = network
    prov = _provider(config)
    planner, state, prev = _planner(config, traffic, 5), \
        reference.RefState(config), b""
    by_kind, first = {}, config.get("first_block", 0)
    for b in range(first, first + 4):
        rows = planner.rows(b)
        assert len(rows) == config["block_tx"]
        blk, prev = stream.chain(b, prev, stream.envelopes(
            rows, config, endorsers, client, 5, b, planner.replays(b)))
        assert blk.header.number == b
        want, done = reference.plan_codes(rows, b, state)
        full, full_done = reference.openssl_codes(
            blk, mgr, prov, state, config["chaincode"])
        assert full == want and full_done == done, f"block {b}"
        for (kind, _r, _w), code in zip(rows, want):
            by_kind.setdefault(kind, set()).add(code)
        # fresh keys and versions carry the real block number
        assert all(v[1][0] == b for v in done.values())
        state.commit(done, reference.block_txids(blk))
    # the verdict each kind of spoiled tx gets, by construction
    assert by_kind.get("bad_creator_signature", {C.BAD_CREATOR_SIGNATURE}) \
        == {C.BAD_CREATOR_SIGNATURE}
    assert by_kind.get("bad_endorsement_signature",
                       {C.ENDORSEMENT_POLICY_FAILURE}) \
        == {C.ENDORSEMENT_POLICY_FAILURE}
    assert by_kind.get("stale_read", {C.MVCC_READ_CONFLICT}) \
        == {C.MVCC_READ_CONFLICT}
    assert by_kind.get("duplicate_txid", {C.DUPLICATE_TXID}) \
        == {C.DUPLICATE_TXID}
    want_kinds = {"ok", *traffic["invalid_kinds"]}
    assert set(by_kind) == want_kinds
    if traffic["invalid_kinds"]:
        # nothing but the injected txs is invalid in these mixes
        assert by_kind["ok"] == {C.VALID}
        bad = round(config["block_tx"] * traffic["invalid_share"])
        assert sum(k != "ok" for k, _r, _w in rows) == bad


@pytest.mark.parametrize("cell", ["rw_backlog", "zipf_backlog"])
def test_a_block_is_a_function_of_the_seed_and_its_number(cell):
    config, traffic = tiny_cell(cell)
    in_order = _planner(config, traffic, 9)
    rows = [in_order.rows(b) for b in range(4)]
    assert _planner(config, traffic, 9).rows(3) == rows[3]
    assert _planner(config, traffic, 10).rows(0) != rows[0]
    with pytest.raises(ValueError):
        in_order.rows(1)


def test_generator_refuses_parameters_it_cannot_honour():
    config, traffic = tiny_cell("insert_backlog")
    with pytest.raises(ValueError, match="stale_read needs"):
        _planner(config, dict(traffic, invalid_kinds=["stale_read"]), 1)
    with pytest.raises(ValueError, match="no such pool"):
        _planner(config, dict(traffic, writes=["elsewhere"]), 1)
    with pytest.raises(ValueError, match="needs replay_back_blocks"):
        _planner(config, dict(traffic, invalid_kinds=["duplicate_txid"]), 1)
    config, traffic = tiny_cell("rw_backlog")
    planner = _planner(config, traffic, 1)
    with pytest.raises(ValueError, match="without_replacement pool"):
        planner.rows(200)  # 2000 keys cannot feed 200 blocks of 20 cold reads


def test_zipf_stream_through_the_pipeline_at_depth_two(tmp_path, network):
    config, traffic = tiny_cell("zipf_backlog")
    _net, (endorsers, client), mgr = network
    planner = _planner(config, traffic, 5)
    blocks, plans, prev = [], [], b""
    for b in range(6):
        rows = planner.rows(b)
        blk, prev = stream.chain(
            b, prev, stream.envelopes(rows, config, endorsers, client, 5, b))
        blocks.append(blk)
        plans.append(rows)

    ledger_dir = str(tmp_path / "ledger")
    start = {"prev_hash": b""}
    template, built, began = preload.ensure(str(tmp_path / "cache"), config, 5)
    assert built > 0 and began == start
    assert preload.ensure(str(tmp_path / "cache"), config, 5) == (
        template, 0.0, start)
    shutil.copytree(template, ledger_dir)
    rig = harness.Rig(ledger_dir, config, mgr, _provider(config))
    assert rig.pipe.depth == 2 and rig.lg.engine is not None
    try:
        for b in range(6):
            rig.feed(blocks, b, 6)
        rig.drain()
        state = reference.RefState(config)
        in_block = across = 0
        for b in range(6):
            want, done = reference.plan_codes(plans[b], b, state)
            assert rig.filters[b] == want, f"block {b}"
            assert rig.fused[b] and b in rig.applied and b in rig.ack
            written = set()
            for (_k, reads, writes), code in zip(plans[b], want):
                # a read that an earlier valid tx of this block overwrote
                in_block += any(k in written for k, _v in reads)
                # a read of what the block before wrote: only the async
                # applier's overlay may hold it when this block launches
                across += any(v is not None and v[0] == b - 1 and b > 0
                              for _k2, v in reads)
                if code == C.VALID:
                    written.update(k for k, _v in writes)
            state.commit(done)
        assert in_block > 10 and across > 10
        keys = sorted({k for rows in plans for _k, _r, w in rows
                       for k, _v in w})
        assert reference.state_mismatches(
            rig.lg.state, config["chaincode"], state, keys) == []
    finally:
        end = rig.close()
    assert end["height"] == 6
    from benchmark.run import check_reopened

    assert check_reopened(ledger_dir, config, end, state, keys) == []
    # and the reference notices a state that is not the one it left
    state.written[keys[0]] = (b"other", (5, 0))
    assert check_reopened(ledger_dir, config, end, state, keys)


# ---------------------------------------------------------------------------
# duplicate txids


def _insert_stream_with_replays(network, seed, n_blocks, replays_of):
    """Tiny insert blocks whose first txs replay earlier ones:
    ``replays_of(b)`` → {position: (block, position)} for block b, every
    source an ``ok`` tx.  → (config, blocks, plans)."""
    config, traffic = tiny_cell("insert_backlog")
    traffic = dict(traffic, invalid_share=0.0, invalid_kinds=[])
    _net, (endorsers, client), _mgr = network
    planner = _planner(config, traffic, seed)
    blocks, plans, prev = [], [], b""
    for b in range(n_blocks):
        rows, replays = planner.rows(b), replays_of(b)
        rows = [("duplicate_txid" if i in replays else kind, reads, writes)
                for i, (kind, reads, writes) in enumerate(rows)]
        blk, prev = stream.chain(b, prev, stream.envelopes(
            rows, config, endorsers, client, seed, b, replays))
        blocks.append(blk)
        plans.append(rows)
    return config, blocks, plans


def _through_the_rig(tmp_path, config, blocks, network, seed):
    """→ the tx filters the system gave, by block."""
    _net, _signers, mgr = network
    template, _built, _start = preload.ensure(
        str(tmp_path / "cache"), config, seed)
    ledger_dir = str(tmp_path / "ledger")
    shutil.copytree(template, ledger_dir)
    rig = harness.Rig(ledger_dir, config, mgr, _provider(config))
    try:
        for k in range(len(blocks)):
            rig.feed(blocks, k, len(blocks))
        rig.drain()
        return dict(rig.filters), rig.pipe.depth
    finally:
        rig.close()


@pytest.mark.parametrize("depth", [1, 2])
def test_a_replayed_txid_is_a_duplicate_at_every_distance(
        depth, tmp_path, network, monkeypatch):
    """Blocks 8 and 9 each replay one tx of every block 1 to 8 blocks
    back: in flight (distance 1 at depth 2), committed a block ago,
    several ago.  The system, the plan-level and the OpenSSL reference
    all say ``DUPLICATE_TXID`` for those eight and nothing else."""
    defaults = harness.peer_defaults()
    monkeypatch.setattr(harness, "peer_defaults",
                        lambda: dict(defaults, pipeline_depth=depth))

    def replays_of(b):
        return {d - 1: (b - d, 8 + d + (b - 8)) for d in range(1, 9)} \
            if b >= 8 else {}

    config, blocks, plans = _insert_stream_with_replays(
        network, 21, 10, replays_of)
    _net, _signers, mgr = network
    prov, state = _provider(config), reference.RefState(config)
    filters, ran_depth = _through_the_rig(tmp_path, config, blocks, network,
                                          21)
    assert ran_depth == depth
    for b, (blk, rows) in enumerate(zip(blocks, plans)):
        want, done = reference.plan_codes(rows, b, state)
        full, full_done = reference.openssl_codes(
            blk, mgr, prov, state, config["chaincode"])
        assert full == want and full_done == done, f"block {b}"
        assert filters[b] == want, f"block {b}"
        dup = [i for i, c in enumerate(want) if c == C.DUPLICATE_TXID]
        assert dup == (list(range(8)) if b >= 8 else [])
        assert all(c == C.VALID for c in want[8:])
        state.commit(done, reference.block_txids(blk))
    # the replay carries the original's txid and no write of it is kept
    assert reference.block_txids(blocks[9])[3] \
        == reference.block_txids(blocks[5])[8 + 4 + 1]
    assert not any(k.startswith(("w8_00000", "w9_00007"))
                   for k in state.written)


def test_the_planner_replays_ok_txs_one_to_eight_blocks_back():
    config, traffic = tiny_cell("long_chain_backlog")
    first = config["first_block"]
    planner, kinds, seen = _planner(config, traffic, 3), {}, set()
    for b in range(first, first + 40):
        rows = planner.rows(b)
        kinds[b] = [k for k, _r, _w in rows]
        replays = planner.replays(b)
        at = [i for i, k in enumerate(kinds[b]) if k == "duplicate_txid"]
        assert sorted(replays) == at
        assert len(at) == (0 if b == first else 2)
        assert len(set(replays.values())) == len(replays)
        for src, pos in replays.values():
            assert 1 <= b - src <= 8 and src >= first
            assert kinds[src][pos] == "ok"
            seen.add(b - src)
    assert seen == set(range(1, 9))
    # a function of the seed and the block's number
    again = _planner(config, traffic, 3)
    again.rows(first + 17)
    ahead = _planner(config, traffic, 3)
    for b in range(first, first + 18):
        ahead.rows(b)
    assert again.replays(first + 17) == ahead.replays(first + 17)


def test_nonces_and_txids_are_functions_of_seed_block_and_position(network):
    from fabric_tpu import protoutil as pu

    config, traffic = tiny_cell("insert_backlog")
    _net, (endorsers, client), _mgr = network
    rows = _planner(config, traffic, 4).rows(0)
    once = stream.chain(0, b"", stream.envelopes(
        rows, config, endorsers, client, 4, 0))[0]
    twice = stream.chain(0, b"", stream.envelopes(
        rows, config, endorsers, client, 4, 0))[0]
    assert reference.block_txids(once) == reference.block_txids(twice)
    assert reference.block_txids(once)[5] == pu.compute_tx_id(
        stream.nonce(4, 0, 5), client.serialized)
    assert len(stream.nonce(4, 0, 5)) == len(pu.random_nonce())
    other = stream.chain(0, b"", stream.envelopes(
        rows, config, endorsers, client, 5, 0))[0]
    assert not set(reference.block_txids(once)) & set(
        reference.block_txids(other))
    assert len(set(reference.block_txids(once))) == len(rows)


def test_a_peer_joined_from_a_snapshot_knows_a_pre_snapshot_txid(
        tmp_path, network):
    """A tiny join (height 30, 20-tx blocks) whose txids file also holds
    one txid made from the test's own client and a chosen nonce: the tx
    that carries it after the join is ``DUPLICATE_TXID``, and the ledger
    reopens at height 32 with the commit hash it closed with."""
    import hashlib
    import json
    import os
    import struct

    from fabric_tpu import protoutil as pu
    from fabric_tpu.ledger import snapshot

    from benchmark.run import check_reopened

    config, traffic = tiny_cell("long_chain_backlog")
    traffic = dict(traffic, invalid_share=0.0, invalid_kinds=[])
    first = config["first_block"]
    assert first == 30
    _net, (endorsers, client), mgr = network
    join = manifest.preload_of(config)
    snap = str(tmp_path / "snapshot")
    meta = join.write_snapshot(snap, config, 8)
    assert meta["height"] == first
    replayed = pu.compute_tx_id(stream.nonce(8, 12, 3), client.serialized)
    path = os.path.join(snap, snapshot.TXIDS_FILE)
    with open(path, "ab") as f:
        f.write(struct.pack("<I", 64) + replayed.encode()
                + struct.pack("<I", 1) + b"\0")
    with open(path, "rb") as f:
        meta["files"][snapshot.TXIDS_FILE] = hashlib.sha256(
            f.read()).hexdigest()
    with open(os.path.join(snap, snapshot.META_FILE), "w") as f:
        json.dump(meta, f)
    ledger_dir = str(tmp_path / "ledger")
    lg, _meta = snapshot.create_from_snapshot(snap, ledger_dir)
    assert lg.height == first
    assert lg.blocks.existing_txids([replayed, "0" * 64]) == {replayed}
    lg.close()

    planner = _planner(config, traffic, 8)
    blocks, plans = [], []
    prev = join.anchors(8)["last_block_hash"]
    for b, replays in ((first, {4: (12, 3)}), (first + 1, {})):
        rows = [("duplicate_txid" if i in replays else k, r, w)
                for i, (k, r, w) in enumerate(planner.rows(b))]
        blk, prev = stream.chain(b, prev, stream.envelopes(
            rows, config, endorsers, client, 8, b, replays))
        blocks.append(blk)
        plans.append(rows)
    rig = harness.Rig(ledger_dir, config, mgr, _provider(config))
    try:
        rig.feed(blocks, 0, 2)
        rig.feed(blocks, 1, 2)
        rig.drain()
        assert sorted(rig.applied) == sorted(rig.ack) == [first, first + 1]
        state = reference.RefState(config)
        state.commit({}, [replayed])
        for b, blk, rows in zip((first, first + 1), blocks, plans):
            want, done = reference.plan_codes(rows, b, state)
            assert reference.openssl_codes(
                blk, mgr, _provider(config), state,
                config["chaincode"]) == (want, done)
            assert rig.filters[b] == want
            assert [i for i, c in enumerate(want) if c != C.VALID] == (
                [4] if b == first else [])
            state.commit(done, reference.block_txids(blk))
        keys = sorted(k for rows in plans for _k, _r, w in rows for k, _v in w)
        assert reference.state_mismatches(
            rig.lg.state, config["chaincode"], state, keys) == []
    finally:
        end = rig.close()
    assert end["height"] == first + 2
    assert check_reopened(ledger_dir, config, end, state, keys) == []


def test_the_cache_prunes_one_configuration_and_no_other(tmp_path):
    """Each configuration keeps its newest few ledgers; a build for one
    never deletes another's (the joined ledger of a snapshot is 1.9 GB
    and a minute and a half to make)."""
    import os
    import time

    cache = str(tmp_path / "cache")
    built = []

    def build(tmp):
        os.makedirs(tmp)
        built.append(tmp)

    other, took = preload.cached(cache, "joined", "snap", build, keep=1)
    assert took > 0 and os.path.isdir(other)
    for seed in range(5):
        preload.cached(cache, "seeded", f"s{seed}", build)
        time.sleep(0.01)
    assert sorted(os.listdir(os.path.join(cache, "seeded"))) == [
        "s2", "s3", "s4"]
    assert os.listdir(os.path.join(cache, "joined")) == ["snap"]
    assert preload.cached(cache, "joined", "snap", build, keep=1) == (
        other, 0.0)
    assert len(built) == 6


def test_the_joined_ledger_is_the_configurations_not_the_runs(tmp_path):
    """The snapshot comes from ``snapshot_seed``: runs of any ``--seed``
    share one joined ledger, built once, and continue from its anchor."""
    from fabric_tpu.ledger.kvledger import KVLedger

    config, _traffic = tiny_cell("long_chain_backlog")
    join, cache = manifest.preload_of(config), str(tmp_path / "cache")
    first, built, start = join.ensure(cache, config, 1)
    assert built > 0 and set(start) == {"prev_hash", "build_parts_s"}
    again, built_again, start_again = join.ensure(cache, config, 2)
    assert (again, built_again) == (first, 0.0)
    assert start_again["prev_hash"] == start["prev_hash"] == join.anchors(
        config["snapshot_seed"])["last_block_hash"]
    lg = KVLedger(first, enable_history=bool(config["history_db"]))
    try:
        assert lg.height == config["first_block"] == 30
        assert lg.blocks.expected_prev_hash() == start["prev_hash"]
    finally:
        lg.close()
    moved = dict(config, snapshot_seed=config["snapshot_seed"] + 1)
    assert join.ensure(cache, moved, 1)[0] != first


def test_a_block_says_how_many_txids_it_replays(network, monkeypatch):
    """What a traced run holds the ``dup_txid`` span's ``hits`` to comes
    from the generator's ``replays``, not from a kind's name."""
    config, traffic = tiny_cell("long_chain_backlog")
    _net, (endorsers, client), _mgr = network
    for key, value in (("config", config), ("seed", 9),
                       ("generator", manifest.load_module(
                           "generators", traffic["generator"])),
                       ("planner", _planner(config, traffic, 9)),
                       ("endorsers", endorsers), ("client", client)):
        monkeypatch.setitem(stream._worker, key, value)
    counts = []
    for k in range(3):
        rows, envs, replayed = stream._make_block(k)
        assert len(envs) == len(rows) == 20
        assert replayed == sum(kind == "duplicate_txid"
                               for kind, _r, _w in rows)
        counts.append(replayed)
    assert counts == [0, 2, 2]
    # a mix without replays, from a generator that has the method
    plain = _planner(config, dict(traffic, invalid_kinds=[
        "bad_creator_signature"]), 9)
    monkeypatch.setitem(stream._worker, "planner", plain)
    assert stream._make_block(0)[2] == 0


@pytest.mark.parametrize("back", [1, 3])
def test_the_traffic_file_says_how_far_back_a_replay_reaches(back):
    config, traffic = tiny_cell("long_chain_backlog")
    assert traffic["replay_back_blocks"] == 8
    planner = _planner(config, dict(traffic, replay_back_blocks=back), 3)
    first, seen = config["first_block"], set()
    for b in range(first, first + 20):
        planner.rows(b)
        seen |= {b - src for src, _pos in planner.replays(b).values()}
    assert seen == set(range(1, back + 1))


# ---------------------------------------------------------------------------
# the assembler seam: a deployment may bring its own transactions


#: sha256 over ``repr(planner.rows(b))`` for b = 0, 1, 2 at ``ROW_SEED``,
#: from each listed cell's own files at their full size, taken at PR 33's
#: parent (f1252b1): the plan is a function of the seed.  Signing keys
#: are not (``cryptogen`` takes no seed), so rows are pinned and not
#: envelope bytes.  ``rw_paced`` reads ``rw_backlog``'s pools.
ROW_SEED = 2**31 + 33
ROW_DIGESTS = {
    "rw_backlog":
        "614cac81d0a7cf740b4c73dab598ad928d4f5906a967788ecb8047805bba9c7c",
    "insert_backlog":
        "ecf866c86bd2e3660f10194a67ce98674fbab98cc65505a9fc0b581b308b3918",
    "rw_paced":
        "614cac81d0a7cf740b4c73dab598ad928d4f5906a967788ecb8047805bba9c7c",
    "zipf_backlog":
        "fe120cf563bf773bba2308c65ac4b721b434e8e080b25643011202014593acff",
    "ragged_backlog":
        "29dbc0b17c06b911fabe1fdc376ac46031d57817ec3760976e96775ee2d0cde6",
}


@pytest.mark.parametrize("cell", sorted(ROW_DIGESTS))
def test_a_listed_cells_rows_are_the_parents_for_the_same_seed(cell):
    import hashlib

    _w, config, traffic = manifest.cell(manifest.load(), cell)
    planner = _planner(config, traffic, ROW_SEED)
    digest = hashlib.sha256()
    for b in range(3):
        digest.update(repr(planner.rows(b)).encode())
    assert digest.hexdigest() == ROW_DIGESTS[cell]


@pytest.mark.parametrize("cell", ["rw_backlog", "ragged_backlog"])
def test_a_listed_generators_blocks_are_built_by_stream_envelopes(
        cell, network, monkeypatch):
    """``pooled_kv`` and ``cutter_kv`` bring no assembler: a worker
    reaches ``stream.envelopes`` itself, with the arguments it always
    had."""
    config, traffic = tiny_cell(cell)
    generator = manifest.load_module("generators", traffic["generator"])
    assert not hasattr(generator, "envelopes")
    _net, (endorsers, client), _mgr = network
    planner = _planner(config, traffic, 9)
    for key, value in (("config", config), ("seed", 9),
                       ("generator", generator), ("planner", planner),
                       ("endorsers", endorsers), ("client", client)):
        monkeypatch.setitem(stream._worker, key, value)
    calls = []
    monkeypatch.setattr(stream, "envelopes",
                        lambda *args: calls.append(args) or ["envs"])
    rows, envs, replayed = stream._make_block(1)
    assert envs == ["envs"] and replayed == 0
    assert rows == _planner(config, traffic, 9).rows(1)
    assert calls == [(rows, config, endorsers, client, 9, 1, {})]


OWN_ROWS = '''
"""Rows the default assembler cannot read, and an assembler that says
what it was handed: in place of envelopes, one ``repr`` a block."""


class Planner:
    def __init__(self, config, traffic, seed, reference):
        self.first = int(config.get("first_block", 0))

    def rows(self, b):
        return [{"at": (b, i), "endorsing_orgs": (i % 3,), "sets": frozenset(
            {f"p{b}_{i}"})} for i in range(3)]

    def replays(self, b):
        return {2: (b - 1, 0)} if b > self.first else {}


def planner(config, traffic, seed, reference):
    return Planner(config, traffic, seed, reference)


def envelopes(*args):
    rows, config, endorsers, client, seed, block_num, replays = args
    return [repr((rows, config["name"], [e.msp_id for e in endorsers],
                  client.msp_id, seed, block_num, replays)).encode()]
'''


def test_a_generators_own_assembler_gets_what_the_default_gets(tmp_path):
    """Through ``BlockFactory``'s spawned workers: the module is found by
    path under the copy's root, its ``envelopes`` is handed the default's
    seven arguments, and its rows (a format of its own) come back as the
    planner made them, which is how they reach the reference."""
    import os

    from bench_tiny import make_root

    root = make_root(tmp_path)
    with open(os.path.join(root, "benchmark", "generators", "own_rows.py"),
              "w") as f:
        f.write(OWN_ROWS)
    config, _traffic = tiny_cell("rw_backlog")
    traffic = {"name": "own-rows", "generator": "own_rows", "loop": "backlog"}
    own = manifest.load_module("generators", "own_rows", root)
    seed = 2**31 + 7
    factory = stream.BlockFactory(root, config, traffic, seed,
                                  stream.make_network(config), workers=1)
    try:
        factory.extend(2)
        for k in range(2):
            rows, envs, replayed = factory.take(k)
            assert rows == own.planner(config, traffic, seed, None).rows(k)
            assert replayed == k
            assert envs == [repr((
                rows, config["name"], ["Org1MSP", "Org2MSP", "Org3MSP"],
                "Org1MSP", seed, k, {2: (0, 0)} if k else {})).encode()]
    finally:
        factory.close()
    # such a deployment's reference brings the ``written_keys`` of its
    # rows; the default's reads the default's, valid or not
    assert reference.written_keys(
        [("ok", (), (("a", b"1"), ("b", b"2"))),
         ("bad_creator_signature", (("r", None),), (("c", b"3"),))]) == {
             "a", "b", "c"}
