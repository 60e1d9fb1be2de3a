"""Gossip + private-data tests over real localhost sockets:
endorsement-time distribution into transient stores, commit-time
coordinator sourcing (transient hit AND pull path), missing-data
recording + background reconciliation, anti-entropy block transfer,
leader election (reference: gossip/privdata/{distributor,pull,
reconcile}.go, gossip/state/state.go:584, gossip/election)."""

import asyncio
import json

import pytest

from fabric_tpu.crypto import cryptogen
from fabric_tpu.crypto import policy as pol
from fabric_tpu.crypto.msp import MSPManager
from fabric_tpu.discovery import PeerInfo
from fabric_tpu.ordering.blockcutter import BatchConfig
from fabric_tpu.ordering.node import BroadcastClient, OrdererNode
from fabric_tpu.peer import txassembly as txa
from fabric_tpu.peer.chaincode import ChaincodeRuntime, KVContract
from fabric_tpu.peer.node import PeerNode
from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

CHANNEL = "pvtchan"
CC = "pvtcc"


def run(coro, timeout=120):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


async def _wait(cond, timeout=15.0):
    loop = asyncio.get_event_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return True
        await asyncio.sleep(0.03)
    return False


async def _mknet(tmp_path, n_peers=2):
    org1 = cryptogen.generate_org("Org1MSP", "org1.example.com", peers=2, users=1)
    org2 = cryptogen.generate_org("Org2MSP", "org2.example.com", peers=1)
    mgr = MSPManager({"Org1MSP": org1.msp(), "Org2MSP": org2.msp()})
    client = cryptogen.signing_identity(org1, "User1@org1.example.com")
    signers = [
        cryptogen.signing_identity(org1, "peer0.org1.example.com"),
        cryptogen.signing_identity(org2, "peer0.org2.example.com"),
    ]
    orgs = ["Org1MSP", "Org2MSP"]

    orderer = OrdererNode(
        "o0", str(tmp_path / "o0"), {},
        batch_config=BatchConfig(max_message_count=1, batch_timeout_s=0.1),
    )
    await orderer.start()
    orderer.cluster["o0"] = ("127.0.0.1", orderer.port)
    orderer.join_channel(CHANNEL)

    policy = pol.from_dsl("OutOf(1, 'Org1MSP.peer', 'Org2MSP.peer')")
    peers = []
    for i in range(n_peers):
        rt = ChaincodeRuntime()
        rt.register(CC, KVContract())
        node = PeerNode(f"p{i}", str(tmp_path / f"p{i}"), mgr, signers[i], rt)
        await node.start()
        # collA spans both orgs; collPriv is Org1-only (the eligibility
        # filter under test); undefined collections disseminate nowhere.
        # max_peer_count must be ≥ required_peer_count (the reference
        # validates this) and 0 means NO endorsement-time push —
        # reconciliation-only (distributor maximumPeerCount contract)
        prov = PolicyProvider({CC: NamespaceInfo(policy=policy, collections={
            "collA": {"member_orgs": ["Org1MSP", "Org2MSP"],
                      "required_peer_count": 1, "max_peer_count": 2,
                      "btl": 0},
            "collB": {"member_orgs": ["Org1MSP", "Org2MSP"],
                      "required_peer_count": 0, "max_peer_count": 2,
                      "btl": 0},
            "collPriv": {"member_orgs": ["Org1MSP"],
                         "required_peer_count": 0, "max_peer_count": 2,
                         "btl": 0},
            # pull-only lane: eligible members but max_peer_count 0 —
            # eager push must SKIP it entirely
            "collPullOnly": {"member_orgs": ["Org1MSP", "Org2MSP"],
                             "required_peer_count": 0, "max_peer_count": 0,
                             "btl": 0},
        })})
        ch = node.join_channel(CHANNEL, prov)
        peers.append(node)
    for i, node in enumerate(peers):
        for j, other in enumerate(peers):
            if i != j:
                node.registry.add(
                    PeerInfo(orgs[j % 2], "127.0.0.1", other.port)
                )
    return orderer, peers, client


def test_pvt_distribution_and_pull(tmp_path):
    async def scenario():
        orderer, peers, client = await _mknet(tmp_path)
        p0, p1 = peers
        try:
            p0.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p1.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p0.channels[CHANNEL].validator.warmup(5, 3, ())

            # endorse ONLY on p0 with transient value; p0 distributes
            # to p1's transient store at endorsement time
            from fabric_tpu.comm.rpc import RpcClient

            signed, tx_id, prop = txa.create_signed_proposal(
                client, CHANNEL, CC, [b"put_private", b"collA", b"secret-key"],
                transient={"value": b"secret-value"},
            )
            cli = RpcClient("127.0.0.1", p0.port)
            await cli.connect()
            raw = await cli.unary("Endorse", signed.SerializeToString())
            await cli.close()
            from fabric_tpu.protos import proposal_pb2

            pr = proposal_pb2.ProposalResponse()
            pr.ParseFromString(raw)
            assert pr.response.status == 200, pr.response.message

            # distribution reached p1's transient store
            assert await _wait(lambda: bool(
                p1.channels[CHANNEL].transient.get(tx_id)
            ))

            env = txa.assemble_transaction(prop, [pr], client)
            bc = BroadcastClient([("127.0.0.1", orderer.port)])
            res = await bc.broadcast(CHANNEL, env.SerializeToString())
            assert res["status"] == 200
            await bc.close()

            # BOTH peers commit the cleartext into pvt state
            def committed(p):
                vv = p.channels[CHANNEL].ledger.state.get_state(
                    f"{CC}$collA", "secret-key"
                )
                return vv is not None and vv.value == b"secret-value"

            assert await _wait(lambda: committed(p0) and committed(p1), 20)
            # hashed state matches on both, cleartext never hit the rwset
            import hashlib

            kh = hashlib.sha256(b"secret-key").digest().hex()
            for p in (p0, p1):
                hv = p.channels[CHANNEL].ledger.state.get_state(
                    f"{CC}$collA#hashed", kh
                )
                assert hv is not None
                assert hv.value == hashlib.sha256(b"secret-value").digest()

            # pull-only collection (max_peer_count 0): endorsement-time
            # push must SKIP it — p1's transient store stays empty for
            # this txid; the data still arrives post-commit via the
            # reconciler (reconciliation-only delivery)
            signed2, tx_id2, prop2 = txa.create_signed_proposal(
                client, CHANNEL, CC,
                [b"put_private", b"collPullOnly", b"po-key"],
                transient={"value": b"po-value"},
            )
            cli = RpcClient("127.0.0.1", p0.port)
            await cli.connect()
            raw = await cli.unary("Endorse", signed2.SerializeToString())
            await cli.close()
            pr2 = proposal_pb2.ProposalResponse()
            pr2.ParseFromString(raw)
            assert pr2.response.status == 200, pr2.response.message
            await asyncio.sleep(1.0)  # window an eager push would use
            assert not p1.channels[CHANNEL].transient.get(tx_id2)

            env2 = txa.assemble_transaction(prop2, [pr2], client)
            bc = BroadcastClient([("127.0.0.1", orderer.port)])
            res = await bc.broadcast(CHANNEL, env2.SerializeToString())
            assert res["status"] == 200
            await bc.close()

            def committed_po(p):
                vv = p.channels[CHANNEL].ledger.state.get_state(
                    f"{CC}$collPullOnly", "po-key"
                )
                return vv is not None and vv.value == b"po-value"

            assert await _wait(lambda: committed_po(p0), 20)
            assert await _wait(lambda: committed_po(p1), 25)
        finally:
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())


def test_missing_then_reconcile(tmp_path):
    async def scenario():
        orderer, peers, client = await _mknet(tmp_path)
        p0, p1 = peers
        try:
            # p1 gets NO distribution and cannot pull at commit time
            # (puller disabled) → records missing, then the reconciler
            # catches up once pulling is re-enabled
            p0.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p1.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p0.gossip_service._clients.clear()
            p0.registry.peers.clear()  # no distribution targets

            async def no_pull(*a):
                return None

            real_puller = p1.channels[CHANNEL].pvt_puller
            p1.channels[CHANNEL].pvt_puller = no_pull

            from fabric_tpu.comm.rpc import RpcClient
            from fabric_tpu.protos import proposal_pb2

            signed, tx_id, prop = txa.create_signed_proposal(
                client, CHANNEL, CC, [b"put_private", b"collB", b"k2"],
                transient={"value": b"v2"},
            )
            cli = RpcClient("127.0.0.1", p0.port)
            await cli.connect()
            raw = await cli.unary("Endorse", signed.SerializeToString())
            await cli.close()
            pr = proposal_pb2.ProposalResponse()
            pr.ParseFromString(raw)
            assert pr.response.status == 200

            env = txa.assemble_transaction(prop, [pr], client)
            bc = BroadcastClient([("127.0.0.1", orderer.port)])
            assert (await bc.broadcast(CHANNEL, env.SerializeToString()))["status"] == 200
            await bc.close()

            ch1 = p1.channels[CHANNEL]
            assert await _wait(lambda: ch1.height >= 1, 20)
            assert await _wait(
                lambda: bool(ch1.ledger.pvtdata.missing_data(ch1.height)), 10
            )
            assert ch1.ledger.state.get_state(f"{CC}$collB", "k2") is None

            # re-enable pulling and run the reconciler
            ch1.pvt_puller = real_puller
            p1.gossip_service.start_reconciler(CHANNEL, interval=0.2)
            assert await _wait(
                lambda: not ch1.ledger.pvtdata.missing_data(ch1.height), 15
            )
            vv = ch1.ledger.state.get_state(f"{CC}$collB", "k2")
            assert vv is not None and vv.value == b"v2"
        finally:
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())


def test_anti_entropy_catchup(tmp_path):
    async def scenario():
        orderer, peers, client = await _mknet(tmp_path)
        p0, p1 = peers
        try:
            # only p0 talks to the orderer (org leader); p1 relies on
            # anti-entropy pulls from p0
            p0.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p0.channels[CHANNEL].validator.warmup(5, 3, ())
            bc = BroadcastClient([("127.0.0.1", orderer.port)])
            for i in range(3):
                signed, tx_id, prop = txa.create_signed_proposal(
                    client, CHANNEL, CC, [b"put", b"k%d" % i, b"v%d" % i]
                )
                from fabric_tpu.comm.rpc import RpcClient
                from fabric_tpu.protos import proposal_pb2

                cli = RpcClient("127.0.0.1", p0.port)
                await cli.connect()
                raw = await cli.unary("Endorse", signed.SerializeToString())
                await cli.close()
                pr = proposal_pb2.ProposalResponse()
                pr.ParseFromString(raw)
                env = txa.assemble_transaction(prop, [pr], client)
                assert (await bc.broadcast(CHANNEL, env.SerializeToString()))["status"] == 200
            await bc.close()
            assert await _wait(lambda: p0.channels[CHANNEL].height >= 3, 20)

            assert p1.channels[CHANNEL].height == 0
            p1.gossip_service.start_anti_entropy(CHANNEL, interval=0.2)
            assert await _wait(lambda: p1.channels[CHANNEL].height >= 3, 20)
            c0, c1 = p0.channels[CHANNEL], p1.channels[CHANNEL]
            for k in range(3):
                assert (c0.ledger.blocks.get_block(k).SerializeToString()
                        == c1.ledger.blocks.get_block(k).SerializeToString())

            # leader election: deterministic lowest endpoint
            gs = p0.gossip_service
            me = ("127.0.0.1", p0.port)
            others = [PeerInfo("Org1MSP", "127.0.0.1", p1.port, height=3)]
            assert gs.elect_leader(others, me) == (me < ("127.0.0.1", p1.port))
        finally:
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())


def test_non_member_org_never_holds_cleartext(tmp_path):
    """collPriv is Org1-only: endorsement-time distribution must skip
    Org2's peer, a push targeting it must be refused, and a pull by an
    Org2 identity must be denied — collection confidentiality
    (distributor.go AccessFilter; ADVICE r3 high)."""
    async def scenario():
        orderer, peers, client = await _mknet(tmp_path)
        p0, p1 = peers  # p0 = Org1 peer, p1 = Org2 peer
        try:
            p0.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p1.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p0.channels[CHANNEL].validator.warmup(5, 3, ())

            from fabric_tpu.comm.rpc import RpcClient
            from fabric_tpu.protos import proposal_pb2

            signed, tx_id, prop = txa.create_signed_proposal(
                client, CHANNEL, CC,
                [b"put_private", b"collPriv", b"top-secret"],
                transient={"value": b"classified"},
            )
            cli = RpcClient("127.0.0.1", p0.port)
            await cli.connect()
            raw = await cli.unary("Endorse", signed.SerializeToString())
            pr = proposal_pb2.ProposalResponse()
            pr.ParseFromString(raw)
            assert pr.response.status == 200

            # p0 (member) holds the cleartext; p1 (non-member) must not
            assert p0.channels[CHANNEL].transient.get(tx_id)
            await asyncio.sleep(0.5)  # give any (wrong) push time to land
            assert not p1.channels[CHANNEL].transient.get(tx_id)

            # a direct PvtPush of collPriv data at p1 is refused
            import json as _json

            push = _json.dumps({
                "channel": CHANNEL, "txid": tx_id, "height": 0,
                "data": {f"{CC}\x00collPriv": {"top-secret": b"x".hex()}},
            }).encode()
            cli1 = RpcClient("127.0.0.1", p1.port)
            await cli1.connect()
            res = _json.loads(await cli1.unary("PvtPush", push))
            assert res["status"] == 403
            assert not p1.channels[CHANNEL].transient.get(tx_id)

            # commit the tx; p0 gets the pvt state, p1 records missing
            # and CANNOT reconcile it (its pulls are denied by org)
            env = txa.assemble_transaction(prop, [pr], client)
            bc = BroadcastClient([("127.0.0.1", orderer.port)])
            assert (await bc.broadcast(
                CHANNEL, env.SerializeToString()))["status"] == 200
            await bc.close()
            assert await _wait(
                lambda: p0.channels[CHANNEL].height >= 1
                and p1.channels[CHANNEL].height >= 1, 20)
            vv = p0.channels[CHANNEL].ledger.state.get_state(
                f"{CC}$collPriv", "top-secret")
            assert vv is not None and vv.value == b"classified"
            assert p1.channels[CHANNEL].ledger.state.get_state(
                f"{CC}$collPriv", "top-secret") is None

            # p1's signed pull is refused by p0 (org not a member)
            pull = p1.gossip_service.pull_pvt_for(CHANNEL)
            got = await pull(tx_id, 0, 0, CC, "collPriv")
            assert got is None
        finally:
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())


def test_btl_expiry_purges_state_and_store(tmp_path):
    """block_to_live: pvt data (store rows + cleartext state + hashed
    state) is purged once its BTL elapses (pvtstatepurgemgmt +
    pvtdatastorage expiry)."""
    import hashlib

    async def scenario():
        orderer, peers, client = await _mknet(tmp_path)
        p0, p1 = peers
        try:
            # tighten collA to btl=1: data expires 1 block after commit
            for p in peers:
                prov = p.channels[CHANNEL].validator.policies
                prov.infos[CC].collections["collA"]["btl"] = 1
            p0.channels[CHANNEL].start_deliver([("127.0.0.1", orderer.port)])
            p0.channels[CHANNEL].validator.warmup(5, 3, ())

            from fabric_tpu.comm.rpc import RpcClient
            from fabric_tpu.protos import proposal_pb2

            signed, tx_id, prop = txa.create_signed_proposal(
                client, CHANNEL, CC, [b"put_private", b"collA", b"ttl-key"],
                transient={"value": b"ephemeral"},
            )
            cli = RpcClient("127.0.0.1", p0.port)
            await cli.connect()
            raw = await cli.unary("Endorse", signed.SerializeToString())
            pr = proposal_pb2.ProposalResponse()
            pr.ParseFromString(raw)
            assert pr.response.status == 200
            env = txa.assemble_transaction(prop, [pr], client)
            bc = BroadcastClient([("127.0.0.1", orderer.port)])
            assert (await bc.broadcast(
                CHANNEL, env.SerializeToString()))["status"] == 200

            ch0 = p0.channels[CHANNEL]
            assert await _wait(lambda: ch0.height >= 1, 20)
            blk_n = ch0.height - 1
            assert ch0.ledger.state.get_state(
                f"{CC}$collA", "ttl-key") is not None
            assert ch0.ledger.pvtdata.get_pvt_data(blk_n)

            # drive 3 more (public) blocks past the BTL horizon
            # (expiringBlk = committingBlk + btl + 1: data committed at
            # block 1 with btl=1 expires when block 3 commits)
            for i in range(3):
                s2, t2, prop2 = txa.create_signed_proposal(
                    client, CHANNEL, CC, [b"put", f"pub{i}".encode(), b"v"]
                )
                cli2 = RpcClient("127.0.0.1", p0.port)
                await cli2.connect()
                raw2 = await cli2.unary("Endorse", s2.SerializeToString())
                await cli2.close()
                pr2 = proposal_pb2.ProposalResponse()
                pr2.ParseFromString(raw2)
                assert pr2.response.status == 200, pr2.response.message
                env2 = txa.assemble_transaction(prop2, [pr2], client)
                assert (await bc.broadcast(
                    CHANNEL, env2.SerializeToString()))["status"] == 200
            await bc.close()
            assert await _wait(lambda: ch0.height >= 4, 20)

            # expired: store row gone, cleartext state gone, hash gone
            assert not ch0.ledger.pvtdata.get_pvt_data(blk_n)
            assert ch0.ledger.state.get_state(
                f"{CC}$collA", "ttl-key") is None
            kh = hashlib.sha256(b"ttl-key").hexdigest()
            assert ch0.ledger.state.get_state(
                f"{CC}$collA#hashed", kh) is None
        finally:
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())


def test_dead_peer_excluded_from_election(tmp_path):
    """A peer whose probe failed must not win the org-leader election
    (liveness, gossip/discovery alive/dead expiration; ADVICE r3)."""
    async def scenario():
        orderer, peers, client = await _mknet(tmp_path)
        p0, p1 = peers
        try:
            gs = p0.gossip_service
            # register a bogus (dead) lowest-endpoint peer in p0's org
            dead = PeerInfo("Org1MSP", "127.0.0.1", 1)
            p0.registry.add(dead)
            me = ("127.0.0.1", p0.port)
            org_peers = p0.registry.peers.get("Org1MSP", [])
            # before any probe the dead peer still counts (alive=None)
            assert not gs.elect_leader(org_peers, me)
            await gs.probe_members()
            assert dead.alive is False
            # after the failed probe it is excluded → we win
            assert gs.elect_leader(org_peers, me)
        finally:
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())
