"""End-to-end network tests: client → endorsers → raft orderers →
peer commit pipeline → state, all over real localhost sockets.

The nwo-harness analog (integration/nwo + integration/e2e): a network
description (2 orgs × 1 peer, 3 orderers, one channel, KV chaincode)
is brought up in-process, then exercised through the same protocol
surfaces a real deployment uses."""

import asyncio
import json

import pytest

from fabric_tpu.comm.rpc import RpcClient
from fabric_tpu.crypto import cryptogen
from fabric_tpu.crypto import policy as pol
from fabric_tpu.crypto.msp import MSPManager
from fabric_tpu.ordering.blockcutter import BatchConfig
from fabric_tpu.ordering.node import BroadcastClient, OrdererNode
from fabric_tpu.peer import txassembly as txa
from fabric_tpu.peer.chaincode import ChaincodeRuntime, KVContract
from fabric_tpu.peer.node import PeerNode
from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider
from fabric_tpu.protos import proposal_pb2, transaction_pb2

C = transaction_pb2.TxValidationCode
CHANNEL = "e2echan"
CC = "kvcc"


def run(coro, timeout=90):
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
        await asyncio.sleep(0.02)
    return False


@pytest.fixture(scope="module")
def material():
    org1 = cryptogen.generate_org("Org1MSP", "org1.example.com", peers=1, users=1)
    org2 = cryptogen.generate_org("Org2MSP", "org2.example.com", peers=1)
    mgr = MSPManager({"Org1MSP": org1.msp(), "Org2MSP": org2.msp()})
    return {
        "mgr": mgr,
        "client": cryptogen.signing_identity(org1, "User1@org1.example.com"),
        "p1": cryptogen.signing_identity(org1, "peer0.org1.example.com"),
        "p2": cryptogen.signing_identity(org2, "peer0.org2.example.com"),
    }


class Network:
    """2 peers (one per org), 3 orderers, one channel, KV chaincode."""

    def __init__(self, material, tmp_path):
        self.m = material
        self.tmp = tmp_path
        self.orderers = []
        self.peers = []
        self.client = None

    async def up(self):
        cluster = {}
        for i in range(3):
            n = OrdererNode(
                f"o{i}", str(self.tmp / f"o{i}"), cluster,
                batch_config=BatchConfig(max_message_count=3, batch_timeout_s=0.2),
            )
            await n.start()
            cluster[n.id] = ("127.0.0.1", n.port)
            self.orderers.append(n)
        for n in self.orderers:
            n.cluster.update(cluster)
            n.join_channel(CHANNEL)

        policy = pol.from_dsl("AND('Org1MSP.peer', 'Org2MSP.peer')")
        orderer_addrs = list(cluster.values())
        for name, signer in (("peer1", self.m["p1"]), ("peer2", self.m["p2"])):
            runtime = ChaincodeRuntime()
            runtime.register(CC, KVContract())
            p = PeerNode(name, str(self.tmp / name), self.m["mgr"], signer, runtime)
            await p.start()
            prov = PolicyProvider({CC: NamespaceInfo(policy=policy)})
            ch = p.join_channel(CHANNEL, prov)
            ch.start_deliver(orderer_addrs)
            self.peers.append(p)
        # one warmup loads the verify kernel into the in-process jit
        # cache for BOTH peers (first-block commits must not eat it)
        self.peers[0].channels[CHANNEL].validator.warmup(5, 3, ())
        self.client = BroadcastClient(orderer_addrs)
        assert await _wait(lambda: any(
            n.chains[CHANNEL].raft.state == "leader" for n in self.orderers))

    async def down(self):
        if self.client:
            await self.client.close()
        for p in self.peers:
            await p.stop()
        for n in self.orderers:
            await n.stop()

    async def endorse(self, args, signer=None, transient=None):
        signer = signer or self.m["client"]
        signed, tx_id, prop = txa.create_signed_proposal(
            signer, CHANNEL, CC, args, transient=transient
        )
        responses = []
        for p in self.peers:
            cli = RpcClient("127.0.0.1", p.port)
            await cli.connect()
            raw = await cli.unary("Endorse", signed.SerializeToString())
            await cli.close()
            pr = proposal_pb2.ProposalResponse()
            pr.ParseFromString(raw)
            responses.append(pr)
        return prop, responses, tx_id

    async def submit(self, args, signer=None, endorsers=None):
        signer = signer or self.m["client"]
        prop, responses, tx_id = await self.endorse(args, signer)
        good = [r for r in responses if r.response.status < 400]
        use = good if endorsers is None else good[:endorsers]
        env = txa.assemble_transaction(prop, use, signer)
        res = await self.client.broadcast(CHANNEL, env.SerializeToString())
        assert res["status"] == 200, res
        return tx_id

    async def query(self, peer, key):
        cli = RpcClient("127.0.0.1", peer.port)
        await cli.connect()
        resp = json.loads(await cli.unary("Query", json.dumps(
            {"channel": CHANNEL, "ns": CC, "key": key}
        ).encode()))
        await cli.close()
        return bytes.fromhex(resp["value"]) if resp.get("value") else None

    async def heights(self):
        return [p.channels[CHANNEL].height for p in self.peers]

    async def wait_all(self, h, timeout=20):
        for p in self.peers:
            await p.channels[CHANNEL].wait_height(h, timeout)

    def tx_code(self, peer, tx_num_from_end=0):
        from fabric_tpu import protoutil as pu

        ch = peer.channels[CHANNEL]
        blk = ch.ledger.blocks.get_block(ch.height - 1)
        return list(pu.get_tx_filter(blk))


@pytest.mark.slow
def test_e2e_submit_endorse_order_commit(material, tmp_path):
    async def scenario():
        net = Network(material, tmp_path)
        await net.up()
        try:
            # happy path: put k1=v1, both endorsers
            await net.submit([b"put", b"k1", b"v1"])
            await net.submit([b"put", b"k2", b"v2"])
            await net.submit([b"put", b"acct-a", b"100"])
            await net.wait_all(1)
            await _wait(lambda: False, timeout=0.5)  # settle timeout batch
            # all peers converge and agree
            for p in net.peers:
                await _wait(
                    lambda p=p: None not in
                    (net.peers[0].channels[CHANNEL].ledger.state.get_state(CC, "acct-a"),),
                    timeout=10,
                )
            assert await _wait(lambda: all(
                p.channels[CHANNEL].ledger.state.get_state(CC, "k1") is not None
                for p in net.peers), timeout=10)
            for p in net.peers:
                assert (await net.query(p, "k1")) == b"v1"
                assert (await net.query(p, "k2")) == b"v2"
                assert (await net.query(p, "acct-a")) == b"100"

            # read-modify-write through chaincode; endorsed state matches
            await net.submit([b"transfer", b"acct-a", b"acct-b", b"30"])

            def _b_is_30(p):
                vv = p.channels[CHANNEL].ledger.state.get_state(CC, "acct-b")
                return vv is not None and vv.value == b"30"

            assert await _wait(
                lambda: all(_b_is_30(p) for p in net.peers), timeout=10)
            for p in net.peers:
                assert (await net.query(p, "acct-a")) == b"70"

            # identical chains on both peers
            h = min(await net.heights())
            c0 = net.peers[0].channels[CHANNEL]
            c1 = net.peers[1].channels[CHANNEL]
            for k in range(h):
                assert (c0.ledger.blocks.get_block(k).SerializeToString()
                        == c1.ledger.blocks.get_block(k).SerializeToString())
            assert c0.ledger.commit_hash == c1.ledger.commit_hash
        finally:
            await net.down()

    run(scenario())


@pytest.mark.slow
def test_e2e_policy_and_mvcc_rejections(material, tmp_path):
    async def scenario():
        net = Network(material, tmp_path)
        await net.up()
        try:
            await net.submit([b"put", b"bal", b"100"])
            assert await _wait(lambda: all(
                p.channels[CHANNEL].ledger.state.get_state(CC, "bal") is not None
                for p in net.peers), timeout=10)

            # under-endorsed tx (1 of 2 required orgs): committed as
            # ENDORSEMENT_POLICY_FAILURE, state unchanged
            h0 = net.peers[0].channels[CHANNEL].height
            await net.submit([b"put", b"bal", b"999"], endorsers=1)
            assert await _wait(lambda: net.peers[0].channels[CHANNEL].height > h0,
                               timeout=10)
            for p in net.peers:
                assert (await net.query(p, "bal")) == b"100"
            codes = net.tx_code(net.peers[0])
            assert C.ENDORSEMENT_POLICY_FAILURE in codes

            # double-spend race: two txs endorsed against the same
            # version; the second to order must MVCC-fail
            prop_a, resp_a, _ = await net.endorse([b"transfer", b"bal", b"x", b"60"])
            prop_b, resp_b, _ = await net.endorse([b"transfer", b"bal", b"y", b"70"])
            env_a = txa.assemble_transaction(prop_a, resp_a, net.m["client"])
            env_b = txa.assemble_transaction(prop_b, resp_b, net.m["client"])
            for env in (env_a, env_b):
                res = await net.client.broadcast(CHANNEL, env.SerializeToString())
                assert res["status"] == 200
            assert await _wait(lambda: all(
                (p.channels[CHANNEL].ledger.state.get_state(CC, "x") is not None
                 or p.channels[CHANNEL].ledger.state.get_state(CC, "y") is not None)
                for p in net.peers), timeout=10)
            await _wait(lambda: False, timeout=1.0)  # let both commit
            for p in net.peers:
                x = await net.query(p, "x")
                y = await net.query(p, "y")
                bal = await net.query(p, "bal")
                # exactly one transfer won
                assert (x, y, bal) in ((b"60", None, b"40"), (None, b"70", b"30"))
            # both peers agree on the winner
            assert (await net.query(net.peers[0], "x")) == (await net.query(net.peers[1], "x"))
        finally:
            await net.down()

    run(scenario())
