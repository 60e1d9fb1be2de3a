"""End-to-end block-validation pipeline tests.

Builds real signed transactions (cryptogen identities → proposals →
endorsements → envelopes → block) and runs them through the TPU
pipeline, asserting the exact TRANSACTIONS_FILTER codes the reference
would produce (scenarios modeled on txvalidator v20 + txmgr tests).
"""

import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.crypto import cryptogen
from fabric_tpu.crypto import policy as pol
from fabric_tpu.crypto.msp import MSPManager
from fabric_tpu.ledger.rwset import TxRWSet
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
from fabric_tpu.peer import txassembly as txa
from fabric_tpu.peer.validator import BlockValidator, NamespaceInfo, PolicyProvider
from fabric_tpu.protos import common_pb2, transaction_pb2

C = transaction_pb2.TxValidationCode
CHANNEL = "testchan"
CC = "mycc"


@pytest.fixture(scope="module")
def net():
    org1 = cryptogen.generate_org("Org1MSP", "org1.example.com", peers=1, users=1)
    org2 = cryptogen.generate_org("Org2MSP", "org2.example.com", peers=1)
    mgr = MSPManager({"Org1MSP": org1.msp(), "Org2MSP": org2.msp()})
    return {
        "mgr": mgr,
        "client": cryptogen.signing_identity(org1, "User1@org1.example.com"),
        "p1": cryptogen.signing_identity(org1, "peer0.org1.example.com"),
        "p2": cryptogen.signing_identity(org2, "peer0.org2.example.com"),
    }


def _rwset(reads=(), writes=(), ns=CC):
    tx = TxRWSet()
    n = tx.ns_rwset(ns)
    for k, ver in reads:
        n.reads[k] = ver
    for k, v in writes:
        n.writes[k] = v
    return tx.to_proto().SerializeToString()


def _tx(net, endorsers, reads=(), writes=(), signer=None, ns=CC):
    signer = signer or net["client"]
    signed, tx_id, prop = txa.create_signed_proposal(signer, CHANNEL, ns, [b"invoke"])
    rw = _rwset(reads, writes, ns)
    responses = [
        txa.create_proposal_response(prop, rw, e, ns) for e in endorsers
    ]
    return txa.assemble_transaction(prop, responses, signer), tx_id


def _block(envs, num=0):
    blk = pu.new_block(num, b"prev")
    for env in envs:
        blk.data.data.append(env.SerializeToString())
    return pu.finalize_block(blk)


@pytest.fixture()
def validator(net):
    state = MemVersionedDB()
    b = UpdateBatch()
    b.put(CC, "existing", b"v", (1, 0))
    state.apply_updates(b, (1, 0))
    policy = pol.from_dsl("AND('Org1MSP.peer', 'Org2MSP.peer')")
    prov = PolicyProvider({CC: NamespaceInfo(policy=policy)})
    return BlockValidator(net["mgr"], prov, state)


def test_valid_and_policy_failure(net, validator):
    env_ok, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k1", b"v1")])
    env_one, _ = _tx(net, [net["p1"]], writes=[("k2", b"v2")])  # missing Org2
    blk = _block([env_ok, env_one])
    flt, batch, history = validator.validate(blk)
    assert list(flt) == [C.VALID, C.ENDORSEMENT_POLICY_FAILURE]
    assert (CC, "k1") in batch.updates and (CC, "k2") not in batch.updates
    assert history == [(CC, "k1", 0)]


def test_tampered_endorsement_rejected(net, validator):
    env, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k", b"v")])
    # corrupt one endorsement signature byte, then RE-SIGN the envelope
    # as the creator so only the endorsement check can fire (a stale
    # envelope signature would trip BAD_CREATOR_SIGNATURE first)
    payload = pu.unmarshal(common_pb2.Payload, env.payload)
    tx = pu.unmarshal(transaction_pb2.Transaction, payload.data)
    cap = pu.unmarshal(transaction_pb2.ChaincodeActionPayload, tx.actions[0].payload)
    sig = bytearray(cap.action.endorsements[1].signature)
    sig[-1] ^= 1
    cap.action.endorsements[1].signature = bytes(sig)
    tx.actions[0].payload = cap.SerializeToString()
    payload.data = tx.SerializeToString()
    env2 = pu.sign_envelope(payload, net["client"])
    flt, _, _ = validator.validate(_block([env2]))
    assert list(flt) == [C.ENDORSEMENT_POLICY_FAILURE]


def test_bad_creator_signature(net, validator):
    env, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k", b"v")])
    env.signature = env.signature[:-2] + bytes(2)
    flt, _, _ = validator.validate(_block([env]))
    assert list(flt) == [C.BAD_CREATOR_SIGNATURE]


def test_mvcc_conflict_between_block_txs(net, validator):
    envA, _ = _tx(net, [net["p1"], net["p2"]],
                  reads=[("existing", (1, 0))], writes=[("existing", b"new")])
    envB, _ = _tx(net, [net["p1"], net["p2"]],
                  reads=[("existing", (1, 0))], writes=[("other", b"x")])
    flt, batch, _ = validator.validate(_block([envA, envB]))
    assert list(flt) == [C.VALID, C.MVCC_READ_CONFLICT]
    assert (CC, "other") not in batch.updates


def test_stale_version_and_absent_reads(net, validator):
    env_stale, _ = _tx(net, [net["p1"], net["p2"]], reads=[("existing", (0, 0))])
    env_absent_ok, _ = _tx(net, [net["p1"], net["p2"]], reads=[("ghost", None)])
    flt, _, _ = validator.validate(_block([env_stale, env_absent_ok]))
    assert list(flt) == [C.MVCC_READ_CONFLICT, C.VALID]


def test_duplicate_txid_in_block(net, validator):
    env, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k", b"v")])
    flt, _, _ = validator.validate(_block([env, env]))
    assert list(flt) == [C.VALID, C.DUPLICATE_TXID]


@pytest.mark.parametrize("answered_by", ["index", "in_flight", "both"])
def test_duplicate_txid_one_question_to_the_index(net, validator, tmp_path,
                                                  answered_by):
    """A launch asks the block store once for the txids the in-flight
    set did not already answer, and marks what either knows: a
    committed txid, an in-flight one, and neither of two fresh ones;
    a tx the parse already settled is not asked about."""
    from fabric_tpu.ledger.blockstore import BlockStore

    e = [net["p1"], net["p2"]]
    (old, old_id), (flying, flying_id), (new1, new1_id), (new2, new2_id) = [
        _tx(net, e, writes=[(f"k{i}", b"v")]) for i in range(4)]
    bs = BlockStore(str(tmp_path / "chains"))
    b0 = _block([old], num=0)
    pu.set_tx_filter(b0, bytes([C.VALID]))
    bs.add_block(b0)
    asked, existing = [], bs.existing_txids

    def spy(txids):
        asked.append(list(txids))
        return existing(txids)

    bs.existing_txids = spy
    validator.blocks = bs if answered_by != "in_flight" else None
    extra = {flying_id} if answered_by != "index" else None
    blk = _block([new1, old, flying, new2, new2], num=1)
    pend = validator.validate_launch(blk, extra_txids=extra)
    flt, _, _ = validator.validate_finish(pend)
    dup = C.DUPLICATE_TXID
    assert list(flt) == {
        "index": [C.VALID, dup, C.VALID, C.VALID, dup],
        "in_flight": [C.VALID, C.VALID, dup, C.VALID, dup],
        "both": [C.VALID, dup, dup, C.VALID, dup],
    }[answered_by]
    assert asked == {
        "index": [[new1_id, old_id, flying_id, new2_id]],
        "in_flight": [],
        "both": [[new1_id, old_id, new2_id]],
    }[answered_by]
    bs.close()


def test_unknown_namespace_rejected(net, validator):
    env, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k", b"v")], ns="nope")
    flt, _, _ = validator.validate(_block([env]))
    assert list(flt) == [C.INVALID_CHAINCODE]


def test_invalid_creator_msp(net, validator):
    outsider_org = cryptogen.generate_org("MarsMSP", "mars.example.com", users=1)
    outsider = cryptogen.signing_identity(outsider_org, "User1@mars.example.com")
    env, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k", b"v")], signer=outsider)
    flt, _, _ = validator.validate(_block([env]))
    assert list(flt) == [C.BAD_CREATOR_SIGNATURE]


def test_config_tx_passes_through(net, validator):
    ch = pu.make_channel_header(common_pb2.HeaderType.CONFIG, CHANNEL)
    sh = pu.make_signature_header(net["client"].serialized, b"n")
    env = pu.sign_envelope(pu.make_payload(ch, sh, b""), net["client"])
    flt, _, _ = validator.validate(_block([env]))
    assert list(flt) == [C.VALID]


def test_garbage_envelope(net, validator):
    env = common_pb2.Envelope(payload=b"\x01\x02garbage")
    flt, _, _ = validator.validate(_block([env]))
    assert list(flt) == [C.BAD_PAYLOAD]


def _rwset_ranges(ranges, reads=(), writes=(), ns=CC):
    """rwset with range queries: ranges = [(start, end, [(key, ver)])]."""
    tx = TxRWSet()
    n = tx.ns_rwset(ns)
    for k, ver in reads:
        n.reads[k] = ver
    for k, v in writes:
        n.writes[k] = v
    for start, end, results in ranges:
        n.range_queries.append((start, end, list(results)))
    return tx.to_proto().SerializeToString()


def _tx_raw(net, endorsers, rwset_bytes, signer=None, ns=CC):
    signer = signer or net["client"]
    signed, tx_id, prop = txa.create_signed_proposal(signer, CHANNEL, ns, [b"invoke"])
    responses = [
        txa.create_proposal_response(prop, rwset_bytes, e, ns) for e in endorsers
    ]
    return txa.assemble_transaction(prop, responses, signer), tx_id


def test_repeated_endorsement_not_double_counted(net):
    """A client repeating one endorser's endorsement must not satisfy a
    2-of-same-org policy (round-1/2 bypass #2 regression)."""
    state = MemVersionedDB()
    policy = pol.from_dsl("OutOf(2, 'Org1MSP.member', 'Org1MSP.member')")
    prov = PolicyProvider({CC: NamespaceInfo(policy=policy)})
    v = BlockValidator(net["mgr"], prov, state)
    # same endorser twice → ONE signature toward the policy
    env_dup, _ = _tx(net, [net["p1"], net["p1"]], writes=[("k", b"v")])
    # two distinct Org1 members → satisfied
    env_ok, _ = _tx(net, [net["p1"], net["client"]], writes=[("k2", b"v")])
    flt, _, _ = v.validate(_block([env_dup, env_ok]))
    assert list(flt) == [C.ENDORSEMENT_POLICY_FAILURE, C.VALID]


def test_txid_binding(net, validator):
    """tx_id must equal sha256(nonce ‖ creator) — squatting rejected."""
    env, _ = _tx(net, [net["p1"], net["p2"]], writes=[("k", b"v")])
    payload = pu.unmarshal(common_pb2.Payload, env.payload)
    ch = pu.unmarshal(common_pb2.ChannelHeader, payload.header.channel_header)
    ch.tx_id = "f" * 64  # squat someone else's id space
    payload.header.channel_header = ch.SerializeToString()
    env2 = pu.sign_envelope(payload, net["client"])
    flt, _, _ = validator.validate(_block([env2]))
    assert list(flt) == [C.BAD_PROPOSAL_TXID]


def test_committed_state_range_phantom(net, validator):
    """A key committed inside a recorded range but missing from its
    results is a phantom even with NO in-block writer (the reference
    merges committed state into the range re-check)."""
    # validator fixture state has CC/"existing"@(1,0)
    ok_results = [("existing", (1, 0))]
    env_ok, _ = _tx_raw(net, [net["p1"], net["p2"]],
                        _rwset_ranges([("a", "z", ok_results)]))
    env_phantom, _ = _tx_raw(net, [net["p1"], net["p2"]],
                             _rwset_ranges([("a", "z", [])]))  # missed it
    flt, _, _ = validator.validate(_block([env_ok, env_phantom]))
    assert list(flt) == [C.VALID, C.PHANTOM_READ_CONFLICT]


def test_unbounded_range_phantom_in_block(net, validator):
    """end_key == '' scans to the namespace end: an in-block write far
    beyond any bounded guess must still phantom the range."""
    env_w, _ = _tx(net, [net["p1"], net["p2"]], writes=[("zzzz", b"v")])
    env_rq, _ = _tx_raw(
        net, [net["p1"], net["p2"]],
        _rwset_ranges([("existing", "", [("existing", (1, 0))])]),
    )
    flt, _, _ = validator.validate(_block([env_w, env_rq]))
    assert list(flt) == [C.VALID, C.PHANTOM_READ_CONFLICT]


def test_range_results_stale_version(net, validator):
    """Recorded range results carry versions; staleness fails the tx."""
    env, _ = _tx_raw(net, [net["p1"], net["p2"]],
                     _rwset_ranges([("a", "z", [("existing", (0, 0))])]))
    flt, _, _ = validator.validate(_block([env]))
    assert list(flt) == [C.MVCC_READ_CONFLICT]


def test_config_tx_garbage_rejected(net, validator):
    """CONFIG envelopes are not rubber-stamped: unparseable config
    payloads and bad signatures are rejected."""
    ch = pu.make_channel_header(common_pb2.HeaderType.CONFIG, CHANNEL)
    sh = pu.make_signature_header(net["client"].serialized, b"n")
    # block 1, not 0: genesis blocks are the admin-verified trust
    # anchor and bypass config validation (kvledger bootstrap)
    payload = pu.make_payload(ch, sh, b"\x01\x02\x03garbage-not-a-config")
    env = pu.sign_envelope(payload, net["client"])
    flt, _, _ = validator.validate(_block([env], num=1))
    assert list(flt) == [C.BAD_PAYLOAD]

    env2 = pu.sign_envelope(pu.make_payload(ch, sh, b""), net["client"])
    env2.signature = bytes(len(env2.signature))
    flt, _, _ = validator.validate(_block([env2], num=1))
    assert list(flt) == [C.BAD_CREATOR_SIGNATURE]


def test_device_signed_endorsements_validate_on_device(net, validator):
    """ISSUE 13 acceptance: endorse-on-device, validate-on-device.

    Proposal responses ESCC-signed by the batched device sign lane
    (RFC 6979 nonces, fixed-base comb kernel, verify-after-sign armed)
    flow through the UNCHANGED BlockValidator commit path and produce
    the exact verdicts of the all-CPU OpenSSL signing path."""
    from fabric_tpu.peer import signlane

    batchers, providers = [], []
    for peer in (net["p1"], net["p2"]):
        d = signlane.private_scalar(peer)
        b = signlane.SignBatcher(
            signlane.device_sign_backend(d, verify_after=True),
            batch_max=16, wait_ms=5.0,
        ).start()
        batchers.append(b)
        providers.append(signlane.BatchedSigner(peer, b))
    try:
        # deterministic nonces: the SAME bytes sign to the SAME DER
        assert (providers[0].sign(b"replay") ==
                providers[0].sign(b"replay"))
        env_ok, _ = _tx(net, providers, writes=[("dk1", b"v1")])
        env_one, _ = _tx(net, [providers[0]], writes=[("dk2", b"v2")])
        flt, batch, history = validator.validate(
            _block([env_ok, env_one])
        )
        assert list(flt) == [C.VALID, C.ENDORSEMENT_POLICY_FAILURE]
        assert (CC, "dk1") in batch.updates
        # the all-CPU signing path agrees verdict for verdict
        env_ok_cpu, _ = _tx(
            net, [net["p1"], net["p2"]], writes=[("dk1", b"v1")]
        )
        env_one_cpu, _ = _tx(net, [net["p1"]], writes=[("dk2", b"v2")])
        flt_cpu, _, _ = validator.validate(
            _block([env_ok_cpu, env_one_cpu])
        )
        assert list(flt) == list(flt_cpu)
    finally:
        for b in batchers:
            b.stop()
