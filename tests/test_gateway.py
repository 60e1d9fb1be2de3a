"""Gateway + discovery tests over a real localhost network: evaluate,
endorse→sign→submit→commit-status round trip, chaincode events,
discovery peers/endorsers (reference: internal/pkg/gateway/*.go,
discovery/endorsement/endorsement.go:84)."""

import asyncio
import json

import pytest

from fabric_tpu.comm.rpc import RpcClient
from fabric_tpu.crypto import cryptogen
from fabric_tpu.crypto import policy as pol
from fabric_tpu.discovery import PeerInfo, layouts_for_policy
from fabric_tpu.ordering.blockcutter import BatchConfig
from fabric_tpu.ordering.node import OrdererNode
from fabric_tpu.peer.chaincode import ChaincodeRuntime, MarblesContract, KVContract
from fabric_tpu.peer.gateway import GatewayClient, GatewayError
from fabric_tpu.peer.node import PeerNode
from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

CHANNEL = "gwchan"
CC = "gwcc"


def run(coro, timeout=120):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def test_layouts_for_policy():
    rule = pol.from_dsl("AND('Org1MSP.peer', OR('Org2MSP.peer', 'Org3MSP.peer'))")
    lays = layouts_for_policy(rule)
    assert {"Org1MSP": 1, "Org2MSP": 1} in lays
    assert {"Org1MSP": 1, "Org3MSP": 1} in lays
    two_of_same = pol.from_dsl("OutOf(2, 'Org1MSP.peer', 'Org1MSP.peer')")
    assert layouts_for_policy(two_of_same) == [{"Org1MSP": 2}]


@pytest.mark.slow
def test_gateway_round_trip(tmp_path):
    async def scenario():
        org1 = cryptogen.generate_org("Org1MSP", "org1.example.com", peers=1, users=1)
        org2 = cryptogen.generate_org("Org2MSP", "org2.example.com", peers=1)
        from fabric_tpu.crypto.msp import MSPManager

        mgr = MSPManager({"Org1MSP": org1.msp(), "Org2MSP": org2.msp()})
        client = cryptogen.signing_identity(org1, "User1@org1.example.com")
        p1 = cryptogen.signing_identity(org1, "peer0.org1.example.com")
        p2 = cryptogen.signing_identity(org2, "peer0.org2.example.com")

        orderer = OrdererNode(
            "o0", str(tmp_path / "o0"), {},
            batch_config=BatchConfig(max_message_count=1, batch_timeout_s=0.1),
        )
        await orderer.start()
        orderer.cluster["o0"] = ("127.0.0.1", orderer.port)
        orderer.join_channel(CHANNEL)

        policy = pol.from_dsl("AND('Org1MSP.peer', 'Org2MSP.peer')")
        peers = []
        for name, signer in (("p1", p1), ("p2", p2)):
            rt = ChaincodeRuntime()
            rt.register(CC, KVContract())
            rt.register("marbles", MarblesContract())
            node = PeerNode(name, str(tmp_path / name), mgr, signer, rt)
            await node.start()
            prov = PolicyProvider({
                CC: NamespaceInfo(policy=policy),
                "marbles": NamespaceInfo(policy=policy),
            })
            ch = node.join_channel(CHANNEL, prov)
            ch.start_deliver([("127.0.0.1", orderer.port)])
            peers.append(node)
        # cross-register each peer in the other's registry
        peers[0].registry.add(PeerInfo("Org2MSP", "127.0.0.1", peers[1].port))
        peers[1].registry.add(PeerInfo("Org1MSP", "127.0.0.1", peers[0].port))
        peers[0].channels[CHANNEL].validator.warmup(5, 3, ())

        gw = GatewayClient("127.0.0.1", peers[0].port, client)
        try:
            # submit via the full gateway flow
            tx_id, status = await gw.submit_transaction(
                CHANNEL, CC, [b"put", b"city", b"zurich"]
            )
            assert status["code"] == 0 and status["code_name"] == "VALID"
            # read-your-writes honesty: the status distinguishes the
            # block being IN the ledger from its writes being READABLE
            assert isinstance(status["applied"], bool)
            assert status["applied_height"] >= 0
            assert status["durable_height"] >= status["block"]

            # evaluate reads the committed state without ordering
            resp = await gw.evaluate(CHANNEL, CC, [b"get", b"city"])
            assert resp.payload == b"zurich"

            # commit-status for an unknown tx times out with 408
            with pytest.raises(GatewayError) as ei:
                await gw._unwrap(await (await gw._client()).unary(
                    "GwCommitStatus",
                    json.dumps({"channel": CHANNEL, "tx_id": "nope",
                                "timeout": 0.3}).encode(),
                ))
            assert ei.value.status == 408

            # chaincode events stream
            tx2, status2 = await gw.submit_transaction(
                CHANNEL, "marbles", [b"create", b"m1", b"red", b"5", b"alice"]
            )
            assert status2["code"] == 0
            cli = RpcClient("127.0.0.1", peers[0].port)
            await cli.connect()
            stream = await cli.open_stream("GwChaincodeEvents")
            await stream.send(json.dumps(
                {"channel": CHANNEL, "chaincode": "marbles", "start": 0}
            ).encode())
            ev = json.loads(await asyncio.wait_for(stream.__anext__(), 10))
            assert ev["event_name"] == "marble_created"
            assert bytes.fromhex(ev["payload"]) == b"m1"
            await cli.close()

            # discovery: endorsers descriptor lists both orgs
            cli2 = RpcClient("127.0.0.1", peers[0].port)
            await cli2.connect()
            raw = await cli2.unary("Discover", json.dumps(
                {"query": "endorsers", "channel": CHANNEL, "chaincode": CC}
            ).encode())
            desc = json.loads(raw)
            assert desc["status"] == 200
            assert {"Org1MSP": 1, "Org2MSP": 1} in desc["descriptor"]["layouts"]
            await cli2.close()
        finally:
            await gw.close()
            for p in peers:
                await p.stop()
            await orderer.stop()

    run(scenario())
