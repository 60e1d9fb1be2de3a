"""The closed set of device programs a channel needs (PR 28): the one
bucket rule (``utils/batching.block_shapes`` / ``channel_shapes``) against
the repo's own block cutter at its defaults, the shapes the commit path
really keys, and the warm-up ``peer/node.py`` runs when a channel opens."""

import time
import types

import pytest

from fabric_tpu.crypto import policy as pol
from fabric_tpu.ops import p256v3
from fabric_tpu.ordering.blockcutter import BatchConfig, BlockCutter
from fabric_tpu.utils.batching import (BlockShapes, block_shapes,
                                       channel_shapes, next_pow2)


@pytest.mark.parametrize("n", [1, 10, 87, 499, 500, 501, 1203])
def test_the_cutter_at_its_defaults_cuts_min_500_n(n):
    """n envelopes of 3.6 KB inside one timeout: the count cuts before
    the bytes do (500 x 3.6 KB = 1.8 MB < PreferredMaxBytes), and the
    timer cuts what is left."""
    cfg = BatchConfig()
    assert (cfg.max_message_count, cfg.preferred_max_bytes,
            cfg.absolute_max_bytes, cfg.batch_timeout_s) == (
                500, 2 * 1024 * 1024, 10 * 1024 * 1024, 2.0)
    cutter, env, cut = BlockCutter(cfg), bytes(3600), []
    for _ in range(n):
        batches, _pending = cutter.ordered(env)
        cut += batches
    if cutter.pending:
        cut.append(cutter.cut())  # the chain's BatchTimeout
    assert [len(b) for b in cut] == [500] * (n // 500) + (
        [n % 500] if n % 500 else [])
    assert len(cut[0]) == min(500, n)


def test_block_shapes_is_the_rule_every_site_pads_by():
    assert block_shapes() == BlockShapes(16, 16, 16, 4, (1, 1, 1))
    for n in (1, 15, 16, 17, 100, 512, 513, 1024, 1500, 3000, 3073):
        assert p256v3._bucket(n) == block_shapes(signatures=n).verify
        want = max(16, next_pow2(n)) if n <= 512 else -(-n // 512) * 512
        assert p256v3._bucket(n) == want
    got = block_shapes(txs=1000, entries=900, signatures=3000,
                       endorsements=2, reads=3, writes=5, ranges=0)
    assert got == BlockShapes(3072, 1024, 1024, 4, (4, 8, 1))
    assert block_shapes(endorsements=5).slots == 8


def test_static_mvcc_arrays_take_their_shapes_from_the_rule():
    from fabric_tpu.ops import mvcc

    txs = [mvcc.TxRWSet(reads=[(f"k{i}", (1, 0))] * (1 + i % 3),
                        writes=[f"w{i}", f"x{i}"], range_reads=[])
           for i in range(21)]
    static = mvcc.prepare_block_static(txs, bucketed=True)
    sh = block_shapes(txs=21, reads=3, writes=2)
    assert static.read_keys.shape == (sh.txs, sh.dims[0]) == (32, 4)
    assert static.dims == sh.dims == (4, 2, 1)
    assert mvcc.prepare_block_static(txs).read_keys.shape == (21, 3)


def test_channel_shapes_are_the_programs_of_every_block_up_to_n():
    every = channel_shapes(500, 3, 0, 2, 0)
    pairs = [(s.txs, s.verify, n) for s, n in every.items()]
    assert pairs == [
        (512, 1536, 500), (512, 1024, 341), (256, 1024, 256),
        (256, 512, 170), (128, 512, 128), (128, 256, 85), (64, 256, 64),
        (64, 128, 42), (32, 128, 32), (32, 64, 21), (16, 64, 16),
        (16, 32, 10), (16, 16, 5)]
    assert all(s.entries == s.txs and s.slots == 4 and s.dims == (1, 2, 1)
               for s in every)
    for n in range(1, 501):
        assert block_shapes(txs=n, entries=n, signatures=3 * n,
                            endorsements=2, writes=2) in every
    # the 1000-tx cells: the one pair of programs they always keyed
    assert next(iter(channel_shapes(1000, 3, 2, 2, 0))) == BlockShapes(
        3072, 1024, 1024, 4, (2, 2, 1))


def test_launch_rows_and_spans_say_bucket_and_txs():
    """The launch ledger's verify row carries the padded ``bucket``
    beside the real ``lanes``; a stage-2 row the block's ``txs`` beside
    its T bucket; disarmed, neither is recorded."""
    from fabric_tpu import observe
    from fabric_tpu.observe import ledger as launch_ledger

    assert launch_ledger.launch("verify", lanes=3) is None  # disarmed
    led = launch_ledger.acquire(ring=64)
    try:
        rec = p256v3._verify_rec(30, 0, None, False)
        rec.dispatched()
        rec.complete()
        rec = launch_ledger.launch("stage2", compiled=False, lanes=16,
                                   shape={"txs": 10})
        rec.complete()
        rows = led.rows()[-2:]
    finally:
        launch_ledger.release()
    assert (rows[0]["kernel"], rows[0]["lanes"], rows[0]["bucket"]) == (
        "verify", 30, 32)
    assert (rows[1]["kernel"], rows[1]["lanes"], rows[1]["txs"]) == (
        "stage2", 16, 10)
    # a chunked launch's chunks add up to the bucket its row says
    assert sum(pad for _lo, _hi, pad in p256v3._chunk_bounds(700, 256)) == (
        p256v3._bucket(700)) == 1024
    from fabric_tpu.ops_metrics import global_registry

    gauge = global_registry().metric("device_verify_programs")
    assert gauge is not None and sum(gauge.snapshot().values()) >= 1

    from fabric_tpu.peer import pipeline

    blk = types.SimpleNamespace(data=types.SimpleNamespace(data=[b""] * 7))
    pipeline._note_txs(None, blk)  # disarmed: a no-op
    tracer = observe.Tracer(ring_blocks=4)
    root = tracer.begin_block(3)
    pipeline._note_txs(root, blk)
    assert root.attrs == {"block": 3, "txs": 7}
    tracer.finish_block(root)


CHANNEL, CC = "warmchan", "warmcc"


@pytest.fixture(scope="module")
def material():
    """Two orgs and a genesis block from ``configtxgen`` whose orderer
    cuts at six messages (its default, 500, would have the CPU
    backend lower the whole verify ladder)."""
    from fabric_tpu.crypto import cryptogen
    from fabric_tpu.tools import configtxgen as cg

    orgs = [cryptogen.generate_org(f"Org{i}MSP", f"org{i}.example.com",
                                   peers=1, users=1) for i in (1, 2)]
    assert cg.Profile(CHANNEL).max_message_count == 500
    profile = cg.Profile(
        CHANNEL, max_message_count=6,
        application_orgs=[cg.OrgProfile(o.msp_id, o.msp()) for o in orgs])
    return {
        "genesis": cg.genesis_block(profile),
        "client": cryptogen.signing_identity(orgs[0],
                                             "User1@org1.example.com"),
        "admin": cryptogen.signing_identity(orgs[0],
                                            "Admin@org1.example.com"),
        "peers": [cryptogen.signing_identity(o, f"peer0.org{i}.example.com")
                  for i, o in zip((1, 2), orgs)]}


def _tx(material, ns, writes):
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.peer import txassembly as txa

    signer = material["client"]
    _signed, _tx_id, prop = txa.create_signed_proposal(
        signer, CHANNEL, ns, [b"invoke"])
    tx = TxRWSet()
    for k, v in writes:
        tx.ns_rwset(ns).writes[k] = v
    rw = tx.to_proto().SerializeToString()
    return txa.assemble_transaction(
        prop, [txa.create_proposal_response(prop, rw, e, ns)
               for e in material["peers"]], signer)


def _commit(ch, envs):
    import asyncio

    from fabric_tpu import protoutil as pu

    blk = pu.new_block(ch.height, pu.block_header_hash(
        ch.ledger.blocks.get_block(ch.height - 1).header))
    for env in envs:
        blk.data.data.append(env.SerializeToString())
    return list(asyncio.run(ch.commit_block(pu.finalize_block(blk))))


def test_a_tx_shape_is_read_off_a_committed_block(material, tmp_path):
    """What ``PeerChannel._warm_programs`` takes from the block store:
    nothing from a configuration block, and from a block of endorser
    txs the structure of the policy they are judged under (MAJORITY of
    two orgs: 2 of 2), the signatures a tx carries and the rwset dims
    as stage 2 pads them."""
    from fabric_tpu.peer import lifecycle as lc
    from fabric_tpu.peer.node import PeerChannel
    from fabric_tpu.peer.validator import TxShape

    ch = PeerChannel(CHANNEL, str(tmp_path / "peer"),
                     genesis_block=material["genesis"])
    try:
        cd = lc.ChaincodeDefinition(name=CC, sequence=1)
        _commit(ch, [_tx(material, lc.LIFECYCLE_NS, [
            (lc.definition_key(CC), cd.to_bytes())])])
        _commit(ch, [_tx(material, CC, [("a", b"1"), ("b", b"2"),
                                        ("c", b"3")]) for _ in range(2)])
        shapes = [ch.validator.tx_shapes(ch.ledger.blocks.get_block(n))
                  for n in range(3)]
    finally:
        ch.stop()
    assert shapes[0] == set()
    (one,), (two,) = shapes[1:]
    assert isinstance(two, TxShape) and one.policy == two.policy
    assert (two.policy.gates, two.policy.n_principals) == (((2, (0, 1)),), 2)
    assert (one.signatures, one.dims) == (3, (1, 1, 1))
    assert (two.signatures, two.dims) == (3, (1, 4, 1))


def _policy_update(ch, admin):
    """A CONFIG envelope that makes Org1's endorsement policy its
    admin's signature, signed by that admin."""
    from fabric_tpu import channelconfig as cc
    from fabric_tpu.protos import configtx_pb2
    from fabric_tpu.tools import configtxgen as cg

    bundle = ch.processor.bundle
    new = configtx_pb2.Config()
    new.CopyFrom(bundle.config)
    new.channel_group.groups["Application"].groups["Org1MSP"].policies[
        "Endorsement"].CopyFrom(cc.config_policy(pol.SignedBy(
            pol.Principal("Org1MSP", pol.ROLE_ADMIN))))
    signed = cg.sign_update(cg.compute_update(CHANNEL, bundle.config, new),
                            [admin])
    return cg.config_tx(CHANNEL, cc.authorize_update(bundle, signed), signed,
                        signer=admin)


def test_a_channel_warms_its_programs_when_it_opens(material, tmp_path,
                                                    monkeypatch):
    """A real channel joined from a genesis block: where a chip is the
    device lane its opening warms the verify ladder for
    ``BatchSize.max_message_count`` txs (a fresh join has no blocks to
    take stage-2 shapes from); opened again over the blocks it
    committed it warms their stage-2 programs too, and then no block of
    1..max txs lowers a program; a committed configuration update warms
    again; never on the CPU backend, for a sidecar's peer or without a
    configuration."""
    from benchmark.run import CompileWatch
    from fabric_tpu.ledger.statedb import SqliteVersionedDB
    from fabric_tpu.nodeconfig import PeerConfig
    from fabric_tpu.peer import lifecycle as lc
    from fabric_tpu.peer.node import PeerChannel
    from fabric_tpu.peer.validator import BlockValidator
    from fabric_tpu.protos import transaction_pb2
    from fabric_tpu.utils import xla_env

    VALID = transaction_pb2.TxValidationCode.VALID
    calls, real = [], BlockValidator.warmup

    def spy(self, max_tx, signatures_per_tx, seen):
        out = real(self, max_tx, signatures_per_tx, seen)
        calls.append((max_tx, signatures_per_tx, len(seen),
                      out["verify"], out["stage2"]))
        return out

    monkeypatch.setattr(BlockValidator, "warmup", spy)
    assert xla_env.on_accelerator() is False  # the tests' backend
    ch = PeerChannel(CHANNEL, str(tmp_path / "cpu"),
                     genesis_block=material["genesis"])
    ch.stop()
    assert calls == []
    monkeypatch.setattr(xla_env, "on_accelerator", lambda: True)
    state = str(tmp_path / "state.db")
    ch = PeerChannel(CHANNEL, str(tmp_path / "peer"),
                     state_db=SqliteVersionedDB(state),
                     genesis_block=material["genesis"])
    # 6 txs x (a creator + an endorsement of each of two orgs) = 18
    # signatures: the 16- and the 32-lane program; no block to take a
    # shape from
    assert calls == [(6, 3, 0, 2, 0)]
    cd = lc.ChaincodeDefinition(name=CC, sequence=1)
    assert _commit(ch, [_tx(material, lc.LIFECYCLE_NS, [
        (lc.definition_key(CC), cd.to_bytes())])]) == [VALID]
    assert _commit(ch, [_tx(material, CC, [("a", b"1"), ("b", b"2")])]) == [
        VALID]
    for gate in ("_device_lane", "processor"):
        kept = getattr(ch, gate)
        setattr(ch, gate, None if gate == "processor" else False)
        ch._warm_programs()
        setattr(ch, gate, kept)
    assert len(calls) == 1
    ch.stop()
    del calls[:]
    ch = PeerChannel(CHANNEL, str(tmp_path / "peer"),
                     state_db=SqliteVersionedDB(state),
                     genesis_block=material["genesis"])
    try:
        # two shapes in its blocks (the lifecycle's tx, the chaincode's:
        # one policy structure, 1 and 2 writes), each under both verify
        # programs; the blocks so far, of one tx, keyed the smaller
        assert calls == [(6, 3, 2, 2, 4)]
        with CompileWatch() as watch:
            t0 = time.perf_counter()
            for n in (6, 1, 5):
                assert _commit(ch, [_tx(material, CC, [
                    (f"k{n}.{i}", b"v"), (f"l{n}.{i}", b"w")])
                    for i in range(n)]) == [VALID] * n
            assert watch.lowered_between(t0, time.perf_counter()) == 0
        # a committed configuration update (Org1's endorsement policy;
        # the count stays) warms again, inside the commit
        assert _commit(ch, [_policy_update(ch, material["admin"])]) == [VALID]
        assert [c[:2] for c in calls] == [(6, 3), (6, 3)]
    finally:
        ch.stop()
    assert not any("warm" in f for f in PeerConfig.__dataclass_fields__)
