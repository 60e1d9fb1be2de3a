"""Key-level (state-based) endorsement on the fused device path.

The stage-2 program's key-policy lanes against ``_validate_host``, the
exact host interpreter, on the same blocks and the same state: the same
filter, the same update batch (value, version, metadata of every key)
and the same history, tx by tx.  A written key that carries a
validation parameter is held to it INSTEAD of the chaincode's policy;
one without to the chaincode's.  Blocks of 16 txs and more, so that the
columnar parse and the native rwset walk (metadata writes on the flat
arrays) carry them; the small blocks of ``tests/test_sbe.py`` go the
generic builder's way.
"""

import threading

import numpy as np
import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.crypto import cryptogen
from fabric_tpu.crypto import policy as pol
from fabric_tpu.crypto.msp import MSPManager, policy_to_proto
from fabric_tpu.ledger.committer import AsyncApplyEngine
from fabric_tpu.ledger.rwset import (
    VALIDATION_PARAMETER, TxRWSet, decode_metadata, encode_metadata,
)
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
from fabric_tpu.ops_metrics import global_registry
from fabric_tpu.peer import txassembly as txa
from fabric_tpu.peer import validator as validator_mod
from fabric_tpu.peer.validator import (
    BlockValidator, NamespaceInfo, PolicyProvider,
)
from fabric_tpu.protos import transaction_pb2

C = transaction_pb2.TxValidationCode
CHANNEL, CC = "sbedev", "assetcc"
ORGS = ("Org1MSP", "Org2MSP", "Org3MSP")
NS_POLICY = "OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer')"


@pytest.fixture(scope="module")
def net():
    orgs = [cryptogen.generate_org(m, f"org{i + 1}.example.com", peers=1,
                                   users=1) for i, m in enumerate(ORGS)]
    return {
        "mgr": MSPManager({o.msp_id: o.msp() for o in orgs}),
        "client": cryptogen.signing_identity(
            orgs[0], "User1@org1.example.com"),
        "peers": [cryptogen.signing_identity(
            o, f"peer0.org{i + 1}.example.com") for i, o in enumerate(orgs)],
    }


def owner_policy(org: int, dsl=None) -> bytes:
    """The parameter of a key ``ORGS[org]`` owns."""
    return policy_to_proto(pol.from_dsl(
        dsl or f"OutOf(1, '{ORGS[org]}.peer')")).SerializeToString()


def param(org: int) -> dict:
    return {VALIDATION_PARAMETER: owner_policy(org)}


def stored(org: int, **more) -> bytes:
    return encode_metadata({**param(org), **more})


def tx(net, orgs, reads=(), writes=(), meta=None, spoil=False, cc=CC,
       more_ns=None, results=None):
    """One endorser tx: ``orgs`` endorse; ``writes`` of (key, value or
    None = delete); ``meta`` key → entries; ``more_ns`` namespace →
    writes, for a tx that touches a second namespace; ``results``: the
    rwset's bytes, handed in as they are."""
    signer = net["client"]
    _signed, _txid, prop = txa.create_signed_proposal(
        signer, CHANNEL, cc, [b"invoke"])
    rw = TxRWSet()
    n = rw.ns_rwset(cc)
    for k, ver in reads:
        n.reads[k] = ver
    for k, v in writes:
        n.writes[k] = v
    for k, entries in (meta or {}).items():
        n.metadata_writes[k] = dict(entries)
    for ns, ws in (more_ns or {}).items():
        for k, v in ws:
            rw.ns_rwset(ns).writes[k] = v
    raw = results or rw.to_proto().SerializeToString()
    env = txa.assemble_transaction(prop, [
        txa.create_proposal_response(prop, raw, net["peers"][o], cc)
        for o in orgs], signer)
    if spoil:
        env.signature = env.signature[:-4] + bytes(4)
    return env


def block(envs, num):
    blk = pu.new_block(num, b"prev")
    for env in envs:
        blk.data.data.append(env.SerializeToString())
    return pu.finalize_block(blk)


def filler(net, num, upto=16, start=0):
    """Txs on fresh keys under the chaincode's 2-of-3: they bring a
    block to the 16 envelopes the native parse starts at."""
    return [tx(net, (i % 3, (i + 1) % 3),
               writes=[(f"fill{num}_{i}", b"f%d" % i)])
            for i in range(start, upto)]


def seed_state(state, rows, version=(1, 0)):
    b = UpdateBatch()
    for key, value, md in rows:
        b.put(CC, key, value, version, metadata=md)
    state.apply_updates(b, version)


def validators(net, state, infos=None):
    prov = PolicyProvider(infos or {
        CC: NamespaceInfo(policy=pol.from_dsl(NS_POLICY))})
    return (BlockValidator(net["mgr"], prov, state),
            BlockValidator(net["mgr"], prov, state))


def host_moves(reason) -> float:
    m = global_registry().metric("key_policy_host_blocks_total")
    return 0.0 if m is None else float(sum(
        v for k, v in m.snapshot().items() if ("reason", reason) in k))


def both_paths(vd, vh, blk, overlay=None, fused=True):
    """The block through the fused device path of ``vd`` and through
    ``_validate_host`` of ``vh`` → the device's (filter, batch,
    history), after asserting the two agree on every tx and key."""
    pend = vd.validate_launch(blk, overlay=overlay)
    assert (pend.fetch2 is not None) == fused
    flt_d, batch_d, hist_d = vd.validate_finish(pend)
    txs, items, fetch, _msp, _dpre, fb, _hd = vh.preprocess(blk)
    flt_h, batch_h, hist_h = vh._validate_host(
        blk, txs, items, fetch, overlay=overlay, fb=fb)
    assert list(flt_d) == list(flt_h)
    got = {k: (vv.value, vv.version, vv.metadata or None)
           for k, vv in batch_d.updates.items()}
    want = {k: (vv.value, vv.version, vv.metadata or None)
            for k, vv in batch_h.updates.items()}
    assert got == want
    # apply order too; a metadata-only write is an override of the
    # columnar batch, which keeps no place among the rows
    late = set(getattr(batch_d, "_extra", ()))
    assert [k for k in batch_d.updates if k not in late] == [
        k for k in batch_h.updates if k not in late]
    assert sorted(hist_d) == sorted(hist_h)
    assert bool(batch_d.has_meta) == bool(batch_h.has_meta)
    return flt_d, batch_d, hist_d


# ---------------------------------------------------------------------------
# 1. committed parameters: the cell's five kinds in one block


def asset_block(net, num, version=(1, 0)):
    """update / transfer / create / bad creator / wrong org over keys
    a0..a8 (owner = index mod 3), each key once."""
    envs = [
        # the owner's endorsement alone: fails 2-of-3, passes its key
        tx(net, (0,), reads=[("a0", version)], writes=[("a0", b"u0")]),
        tx(net, (1,), reads=[("a1", version)], writes=[("a1", b"u1")]),
        # transfer: value and parameter, the old owner endorses
        tx(net, (2,), reads=[("a2", version)], writes=[("a2", b"t2")],
           meta={"a2": param(0)}),
        # create: a fresh key under the chaincode's 2-of-3
        tx(net, (0, 1), writes=[(f"new{num}", b"c")],
           meta={f"new{num}": param(2)}),
        tx(net, (0,), reads=[("a3", version)], writes=[("a3", b"x")],
           spoil=True),
        # the two non-owners: 2-of-3 is met, a4's policy (Org2) is not
        tx(net, (0, 2), reads=[("a4", version)], writes=[("a4", b"w")]),
        # a single endorsement on a key WITHOUT a parameter: 2-of-3 holds
        tx(net, (0,), writes=[("plain", b"p")]),
        # a stale read under a satisfied key policy
        tx(net, (2,), reads=[("a5", (0, 99))], writes=[("a5", b"s")]),
    ]
    want = [C.VALID, C.VALID, C.VALID, C.VALID, C.BAD_CREATOR_SIGNATURE,
            C.ENDORSEMENT_POLICY_FAILURE, C.ENDORSEMENT_POLICY_FAILURE,
            C.MVCC_READ_CONFLICT]
    return envs, want


def owned(n=9):
    return [(f"a{j}", b"v%d" % j, stored(j % 3)) for j in range(n)]


def test_committed_parameters_decide_instead_of_the_chaincode_policy(net):
    state = MemVersionedDB()
    seed_state(state, owned() + [("plain", b"p0", None)])
    vd, vh = validators(net, state)
    envs, want = asset_block(net, 2)
    flt, batch, hist = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2))
    assert list(flt)[:len(want)] == want
    assert all(c == C.VALID for c in list(flt)[len(want):])
    # a value write carries the parameter along, a transfer replaces it
    assert batch.updates[(CC, "a0")].metadata == stored(0)
    assert batch.updates[(CC, "a2")].metadata == stored(0)
    assert batch.updates[(CC, "new2")].metadata == stored(2)
    assert batch.updates[(CC, "fill2_9")].metadata is None
    assert (CC, "a4") not in batch.updates
    assert (CC, "a2", 2) in hist
    assert len(vd._key_plans) == 3   # Org1, Org2, Org3: met at this launch


def test_a_channel_without_parameters_keys_the_program_as_ever(net):
    """No metadata anywhere: no key lanes are built, none uploaded,
    and the stage-2 program's key has no key-policy part."""
    from fabric_tpu.peer import device_block

    state = MemVersionedDB()
    seed_state(state, [("plain", b"p0", None)])
    vd, vh = validators(net, state)
    before = set(device_block._PROGRAM_CACHE)
    pend = vd.validate_launch(block(filler(net, 3, 16), 3))
    assert pend.fetch2 is not None and pend.key_launch is None
    assert pend.dpre.key_lanes is None
    assert all(len(k) == 5 for k in set(device_block._PROGRAM_CACHE) - before)
    flt, batch, _ = vd.validate_finish(pend)
    assert set(flt) == {C.VALID} and not batch.has_meta


# ---------------------------------------------------------------------------
# 2. where the parameter comes from: overlay, pending apply


def test_a_parameter_in_the_in_flight_overlay_wins(net):
    """The predecessor block (in flight: handed in as the overlay)
    moved a0 to Org2 and cleared a1's parameter; the state still holds
    the old ones."""
    state = MemVersionedDB()
    seed_state(state, owned())
    vd, vh = validators(net, state)
    overlay = UpdateBatch()
    overlay.put(CC, "a0", b"moved", (2, 0), metadata=stored(1))
    overlay.put(CC, "a1", b"bare", (2, 1))
    overlay.delete(CC, "a2", (2, 2))
    envs = [
        tx(net, (0,), writes=[("a0", b"x")]),        # the old owner: fails
        tx(net, (1,), writes=[("a0", b"y")]),        # the new one: passes
        tx(net, (1,), writes=[("a1", b"z")]),        # bare now: needs 2-of-3
        tx(net, (0, 2), writes=[("a1", b"zz")]),
        tx(net, (2,), writes=[("a2", b"gone")]),     # deleted: 2-of-3
        tx(net, (0, 1), reads=[("a2", None)], writes=[("a2", b"back")]),
    ]
    flt, batch, _ = both_paths(
        vd, vh, block(envs + filler(net, 3, 16, len(envs)), 3), overlay)
    assert list(flt)[:6] == [
        C.ENDORSEMENT_POLICY_FAILURE, C.VALID, C.ENDORSEMENT_POLICY_FAILURE,
        C.VALID, C.ENDORSEMENT_POLICY_FAILURE, C.VALID]
    assert batch.updates[(CC, "a0")].metadata == stored(1)
    assert batch.updates[(CC, "a1")].metadata is None
    assert batch.updates[(CC, "a2")].metadata is None


class _Gated(MemVersionedDB):
    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def apply_updates(self, batch, savepoint):
        assert self.gate.wait(30.0)
        MemVersionedDB.apply_updates(self, batch, savepoint)


def test_a_parameter_in_a_pending_apply_wins(net):
    """The apply engine holds block 2 (acknowledged, not applied): its
    parameters answer the gather, a pending delete clears one."""
    inner = _Gated()
    MemVersionedDB.apply_updates(inner, _batch(owned()), (1, 0))
    eng = AsyncApplyEngine(inner)
    try:
        b2 = UpdateBatch()
        b2.put(CC, "a0", b"moved", (2, 0), metadata=stored(1))
        b2.delete(CC, "a1", (2, 1))
        eng.submit(2, b2, (2, 0))
        vd, vh = validators(net, eng)
        envs = [
            tx(net, (0,), writes=[("a0", b"x")]),
            tx(net, (1,), writes=[("a0", b"y")]),
            tx(net, (1,), writes=[("a1", b"z")]),
            tx(net, (1, 2), writes=[("a1", b"zz")]),
        ]
        flt, batch, _ = both_paths(
            vd, vh, block(envs + filler(net, 3, 16, len(envs)), 3))
        assert list(flt)[:4] == [
            C.ENDORSEMENT_POLICY_FAILURE, C.VALID,
            C.ENDORSEMENT_POLICY_FAILURE, C.VALID]
        assert batch.updates[(CC, "a0")].metadata == stored(1)
        assert batch.updates[(CC, "a1")].metadata is None
    finally:
        inner.gate.set()
        eng.close()


def _batch(rows, version=(1, 0)):
    b = UpdateBatch()
    for key, value, md in rows:
        b.put(CC, key, value, version, metadata=md)
    return b


# ---------------------------------------------------------------------------
# 3. what a write does to the metadata


def test_metadata_only_writes_cleared_parameters_and_other_entries(net):
    state = MemVersionedDB()
    seed_state(state, owned(6) + [
        ("tagged", b"t0", stored(0, color=b"red")),
        ("plain", b"p0", None)])
    vd, vh = validators(net, state)
    envs = [
        # metadata-only: the value is re-put under the new owner
        tx(net, (0,), meta={"a0": param(1)}),
        # ... and is a writer: a later read of it conflicts
        tx(net, (0, 1), reads=[("a0", (1, 0))], writes=[("r0", b"r")]),
        # metadata-only on an absent key: a no-op, and no writer
        tx(net, (0, 1), meta={"ghost": param(0)}),
        tx(net, (0, 1), reads=[("ghost", None)], writes=[("r1", b"r")]),
        # the owner clears a1's parameter (an empty entry list)
        tx(net, (1,), meta={"a1": {}}),
        # a value write keeps an entry that is no policy
        tx(net, (0,), writes=[("tagged", b"t1")]),
        # a value and metadata write that drops the parameter and
        # keeps a tag
        tx(net, (2,), writes=[("a2", b"n")], meta={"a2": {"color": b"blue"}}),
        # delete: the parameter goes with the row
        tx(net, (0,), writes=[("a3", None)]),
        # the wrong org may not clear a parameter either
        tx(net, (0, 2), meta={"a4": {}}),
        # a fresh value then, in a later tx, its metadata alone: the
        # parameter write is the later one, so no dependency
        tx(net, (0, 1), writes=[("late", b"l")]),
    ]
    flt, batch, hist = both_paths(
        vd, vh, block(envs + filler(net, 2, 18, len(envs)), 2))
    assert list(flt)[:10] == [
        C.VALID, C.MVCC_READ_CONFLICT, C.VALID, C.VALID, C.VALID, C.VALID,
        C.VALID, C.VALID, C.ENDORSEMENT_POLICY_FAILURE, C.VALID]
    u = batch.updates
    assert (u[(CC, "a0")].value, u[(CC, "a0")].version) == (b"v0", (2, 0))
    assert u[(CC, "a0")].metadata == stored(1)
    assert (CC, "ghost") not in u
    assert u[(CC, "a1")].value == b"v1" and u[(CC, "a1")].metadata is None
    assert decode_metadata(u[(CC, "tagged")].metadata) == {
        VALIDATION_PARAMETER: owner_policy(0), "color": b"red"}
    assert decode_metadata(u[(CC, "a2")].metadata) == {"color": b"blue"}
    assert u[(CC, "a3")].value is None
    assert (CC, "a0", 0) not in hist  # history records value writes only


def test_a_value_then_a_later_metadata_only_write_of_one_block(net):
    """An earlier tx creates the key, a later one sets its parameter
    alone: the later write finds the block's own value."""
    state = MemVersionedDB()
    seed_state(state, owned(3))
    vd, vh = validators(net, state)
    envs = [
        tx(net, (0, 1), writes=[("late", b"l")]),
        tx(net, (0, 1), meta={"late": param(2)}),
        tx(net, (0,), writes=[("a0", None)]),
    ]
    flt, batch, _ = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2))
    assert list(flt)[:3] == [C.VALID] * 3
    vv = batch.updates[(CC, "late")]
    assert (vv.value, vv.version, vv.metadata) == (b"l", (2, 1), stored(2))


# ---------------------------------------------------------------------------
# 4. parameters the lanes cannot simply carry


def test_an_unparseable_parameter_fails_closed(net):
    state = MemVersionedDB()
    junk = encode_metadata({VALIDATION_PARAMETER: b"\xff\xfenot a policy"})
    seed_state(state, owned(3) + [("junk", b"j", junk)])
    vd, vh = validators(net, state)
    envs = [tx(net, (0, 1, 2), writes=[("junk", b"x")]),
            tx(net, (0,), writes=[("a0", b"y")])]
    flt, batch, _ = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2))
    assert list(flt)[:2] == [C.ENDORSEMENT_POLICY_FAILURE, C.VALID]
    assert vd._key_pid_of[b"\xff\xfenot a policy"] == -2
    assert len(vd._key_plans) == 1


def test_a_consumption_unsafe_row_takes_the_exact_interpreter(net):
    """Org1's peer matches both principals of the key's policy: the
    count-based reduction is not exact, so the block is the host's."""
    state = MemVersionedDB()
    both = owner_policy(0, "OutOf(2, 'Org1MSP.peer', 'Org1MSP.member')")
    seed_state(state, owned(3) + [
        ("twice", b"t", encode_metadata({VALIDATION_PARAMETER: both}))])
    vd, vh = validators(net, state)
    envs = [tx(net, (0,), writes=[("twice", b"x")]),
            tx(net, (0,), writes=[("a0", b"y")])]
    before = host_moves("unsafe")
    pend = vd.validate_launch(
        block(envs + filler(net, 2, 16, len(envs)), 2))
    assert pend.fetch2 is not None      # launched, then found unsafe
    flt, batch, _ = vd.validate_finish(pend)
    assert host_moves("unsafe") == before + 1
    # one signature cannot satisfy two principals
    assert list(flt)[:2] == [C.ENDORSEMENT_POLICY_FAILURE, C.VALID]
    assert batch.updates[(CC, "a0")].metadata == stored(0)


def test_an_in_block_parameter_dependency_is_the_host_paths(net):
    """A later tx touches a key whose parameter an earlier tx of the
    block writes: the repo's in-block rule (the later tx is judged
    under the new parameter) stays on the host path, and is counted."""
    state = MemVersionedDB()
    seed_state(state, owned(3))
    vd, vh = validators(net, state)
    envs = [
        tx(net, (0,), writes=[("a0", b"t")], meta={"a0": param(1)}),
        tx(net, (0,), writes=[("a0", b"old owner")]),
        tx(net, (1,), writes=[("a0", b"new owner")]),
    ]
    before = host_moves("inblock_dependency")
    flt, batch, _ = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2), fused=False)
    assert host_moves("inblock_dependency") == before + 1
    assert list(flt)[:3] == [C.VALID, C.ENDORSEMENT_POLICY_FAILURE, C.VALID]
    assert batch.updates[(CC, "a0")].value == b"new owner"


def test_more_policies_than_the_table_holds_is_the_host_paths(net,
                                                              monkeypatch):
    monkeypatch.setattr(validator_mod, "MAX_KEY_POLICIES", 2)
    state = MemVersionedDB()
    seed_state(state, owned(3))
    vd, vh = validators(net, state)
    envs = [tx(net, (j,), writes=[(f"a{j}", b"x")]) for j in range(3)]
    before = host_moves("too_many_policies")
    flt, _, _ = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2), fused=False)
    assert host_moves("too_many_policies") == before + 1
    assert list(flt)[:3] == [C.VALID] * 3
    # a block that needs only the two it holds stays on the device
    envs = [tx(net, (j,), writes=[(f"a{j}", b"y")]) for j in range(2)]
    both_paths(vd, vh, block(envs + filler(net, 3, 16, len(envs)), 3))


def test_a_policy_first_met_at_a_launch_joins_the_table_once(net):
    """Block 2 is prefetched with an empty table and meets Org1's
    policy at its launch; block 3 is prefetched with it, meets Org2's
    at its launch, and both are judged under the right one."""
    state = MemVersionedDB()
    seed_state(state, owned(6))
    vd, vh = validators(net, state)
    envs = [tx(net, (0,), writes=[("a0", b"x")]),
            tx(net, (1,), writes=[("a3", b"wrong")])]
    flt, _, _ = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2))
    assert list(flt)[:2] == [C.VALID, C.ENDORSEMENT_POLICY_FAILURE]
    assert len(vd._key_plans) == 1
    envs = [tx(net, (0,), writes=[("a3", b"y")]),
            tx(net, (1,), writes=[("a1", b"z")]),
            tx(net, (0,), writes=[("a4", b"wrong")])]
    blk3 = block(envs + filler(net, 3, 16, len(envs)), 3)
    pre = vd.preprocess(blk3)
    assert len(pre[4].key_lanes.groups) == 1     # built on the prefetch side
    flt, _, _ = both_paths(vd, vh, blk3)
    assert list(flt)[:3] == [C.VALID, C.VALID, C.ENDORSEMENT_POLICY_FAILURE]
    assert len(vd._key_plans) == 2


def test_two_namespaces_each_key_under_its_own(net):
    """A tx that writes a key under a parameter in one namespace and a
    bare key in another needs the parameter for the first and the
    second namespace's own policy for the second."""
    state = MemVersionedDB()
    seed_state(state, owned(3))
    infos = {CC: NamespaceInfo(policy=pol.from_dsl(NS_POLICY)),
             "othercc": NamespaceInfo(policy=pol.from_dsl(
                 "OutOf(1, 'Org3MSP.peer')"))}
    vd, vh = validators(net, state, infos)
    envs = [
        # Org1 owns a0; othercc wants Org3: Org1 alone fails othercc
        tx(net, (0,), writes=[("a0", b"x")], more_ns={"othercc": [("o", b"1")]}),
        tx(net, (0, 2), writes=[("a0", b"y")], more_ns={"othercc": [("o", b"2")]}),
        # a namespace the tx only reads in: its verdict still counts
        tx(net, (1,), reads=[("a2", (1, 0))], more_ns={"othercc": [("p", b"3")]}),
    ]
    flt, _, _ = both_paths(
        vd, vh, block(envs + filler(net, 2, 16, len(envs)), 2))
    assert list(flt)[:3] == [
        C.ENDORSEMENT_POLICY_FAILURE, C.VALID, C.ENDORSEMENT_POLICY_FAILURE]


# ---------------------------------------------------------------------------
# 5. seeded random blocks, a chain of them over one state


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_seeded_random_blocks_agree_with_the_host_path(net, seed):
    """Three blocks of 20 random asset txs over 40 owned keys, each key
    once a block; the device's batch is applied, so later blocks see
    transferred owners, cleared parameters and deleted keys."""
    rng = np.random.default_rng(seed)
    state = MemVersionedDB()
    n_keys = 40
    seed_state(state, [(f"a{j}", b"v%d" % j, stored(j % 3))
                       for j in range(n_keys)])
    owner = {f"a{j}": j % 3 for j in range(n_keys)}   # None: no parameter
    version = {f"a{j}": (1, 0) for j in range(n_keys)}
    vd, vh = validators(net, state)
    for num in (2, 3, 4):
        keys = [f"a{j}" for j in rng.permutation(n_keys)[:20]]
        envs, plan = [], []
        for i, key in enumerate(keys):
            own = owner.get(key)
            alive = key in version
            kind = rng.choice(["update", "transfer", "wrong", "clear",
                               "meta_only", "delete", "bad_creator", "tag"])
            if own is None:
                ends = tuple(rng.permutation(3)[:rng.integers(1, 4)])
            elif kind == "wrong":
                ends = tuple(o for o in range(3) if o != own)
            else:
                ends = (own,)
            reads = [(key, version.get(key))] if rng.random() < 0.7 else []
            new = int(rng.integers(0, 3))
            if kind == "transfer":
                env = tx(net, ends, reads, [(key, b"t%d" % i)],
                         {key: param(new)})
            elif kind == "clear":
                env = tx(net, ends, reads, meta={key: {}})
            elif kind == "meta_only":
                env = tx(net, ends, reads, meta={key: param(new)})
            elif kind == "delete":
                env = tx(net, ends, reads, [(key, None)])
            elif kind == "tag":
                env = tx(net, ends, reads, [(key, b"g%d" % i)],
                         {key: {**param(new), "n": b"%d" % i}})
            else:
                env = tx(net, ends, reads, [(key, b"u%d" % i)],
                         spoil=kind == "bad_creator")
            envs.append(env)
            plan.append((key, kind, new, alive))
        flt, batch, _ = both_paths(vd, vh, block(envs, num))
        assert {C.VALID, C.ENDORSEMENT_POLICY_FAILURE} <= set(flt)
        state.apply_updates(batch, (num, 0))
        for i, (key, kind, new, alive) in enumerate(plan):
            if flt[i] != C.VALID:
                continue
            vv = state.get_state(CC, key)
            if vv is None:
                owner.pop(key, None), version.pop(key, None)
                continue
            version[key] = vv.version
            p = decode_metadata(vv.metadata).get(VALIDATION_PARAMETER)
            owner[key] = None if p is None else [
                owner_policy(o) for o in range(3)].index(p)
        want_meta = sum(1 for k, o in owner.items()
                        if k in version and state.get_state(CC, k).metadata)
        assert state.meta_count == want_meta


# ---------------------------------------------------------------------------
# 6. metadata writes on the native parser's flat arrays


def _flat(blk):
    from fabric_tpu.native import blockparse as nbp
    from fabric_tpu.native import mvccprep_py

    native = nbp.parse_envelopes(list(blk.data.data))
    return native, mvccprep_py.prep(native, native.ok.astype(bool))


def _from_flat(native, rwp, i):
    """Tx ``i`` of the flat arrays, as ``TxRWSet`` holds one: namespace →
    (reads, writes, metadata writes)."""
    ns_names, ukeys = rwp.ns_names(), rwp.ukey_strs()
    s, c = int(rwp.tx_ns_start[i]), int(rwp.tx_ns_count[i])
    out = {ns_names[j]: ({}, {}, {}) for j in rwp.ns_ids_flat[s:s + c]}

    def slot(uid, part):
        return out[ns_names[rwp.ns_of_ukey[uid]]][part], ukeys[uid]

    for r in range(int(rwp.r_start[i]), int(rwp.r_start[i] + rwp.r_count[i])):
        d, k = slot(rwp.r_uid[r], 0)
        d[k] = tuple(int(x) for x in rwp.r_ver[r]) if rwp.r_has_ver[r] else None
    for w in range(int(rwp.w_start[i]), int(rwp.w_start[i] + rwp.w_count[i])):
        d, k = slot(rwp.w_uid[w], 1)
        o, n = (int(x) for x in rwp.w_val_span[w])
        d[k] = None if rwp.w_is_del[w] else (rwp.blob[o:o + n] if o >= 0
                                             else b"")
    for m in range(int(rwp.m_start[i]), int(rwp.m_start[i] + rwp.m_count[i])):
        d, k = slot(rwp.m_uid[m], 2)
        o, n = (int(x) for x in rwp.m_ent_span[m])
        d[k] = decode_metadata(rwp.blob[o:o + n])
        assert bool(rwp.m_only[m]) == (k not in slot(rwp.m_uid[m], 1)[0])
    return out


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_native_and_python_parsers_agree_on_metadata_writes(net, seed):
    """Seeded rwsets with reads, writes, deletes and metadata writes
    (none, one or three entries; a key with and without a value write;
    two namespaces): the flat arrays hold what ``TxRWSet.from_bytes``
    holds, metadata-only rows are flagged, every tx stays on the flat
    path."""
    rng = np.random.default_rng(seed)
    names = [VALIDATION_PARAMETER, "color", "größe"]
    envs, raws = [], []
    for i in range(18):
        keys = [f"k{j}" for j in rng.permutation(12)[:4]]
        meta = {}
        for k in keys[:int(rng.integers(0, 3))] + [f"only{i}"] * int(
                rng.integers(0, 2)):
            meta[k] = {n: rng.bytes(int(rng.integers(0, 40)))
                       for n in names[:int(rng.integers(0, 4))]}
        e = tx(net, (0, 1),
               reads=[(k, (1, int(rng.integers(0, 9)))) for k in keys[2:]],
               writes=[(k, None if rng.random() < 0.2 else rng.bytes(8))
                       for k in keys[1:3]],
               meta=meta,
               more_ns={"othercc": [("o%d" % i, b"x")]} if i % 3 == 0 else None)
        envs.append(e)
        _ch, _sh, _cap, _prp, cca = pu.extract_action(e)
        raws.append(cca.results)
    native, rwp = _flat(block(envs, 2))
    assert rwp.status.tolist() == [0] * 18
    assert rwp.n_meta == sum(
        len(n.metadata_writes) for raw in raws
        for n in TxRWSet.from_bytes(raw).ns.values()) > 0
    for i, raw in enumerate(raws):
        want = {name: (dict(n.reads), dict(n.writes),
                       {k: dict(v) for k, v in n.metadata_writes.items()})
                for name, n in TxRWSet.from_bytes(raw).ns.items()}
        assert _from_flat(native, rwp, i) == want, f"tx {i}"


def _kv_rwset(metadata_write: bytes) -> bytes:
    """A TxReadWriteSet of one namespace whose KVRWSet holds one write
    and one KVMetadataWrite given as raw bytes."""
    from fabric_tpu.protos import rwset_pb2

    kv = rwset_pb2.KVRWSet()
    kv.writes.add(key="a0", value=b"v")
    body = kv.SerializeToString() + b"\x22" + bytes(
        [len(metadata_write)]) + metadata_write
    out = rwset_pb2.TxReadWriteSet()
    out.ns_rwset.add(namespace=CC, rwset=body)
    return out.SerializeToString()


@pytest.mark.parametrize("case,entries", [
    ("name_not_utf8", b"\x12\x05\x0a\x01\xff\x12\x00"),
    ("entry_cut_short", b"\x12\x09\x0a\x01n"),
    ("entry_inside_malformed", b"\x12\x03\x0a\x7f\x01"),
])
def test_a_malformed_metadata_entry_leaves_the_flat_path(net, case, entries):
    """What the Python parser refuses the native walk does not accept:
    the tx is marked for the Python path (status 1), its neighbours
    stay flat, and the block's verdicts are the Python parser's."""
    bad = tx(net, (0, 1), results=_kv_rwset(b"\x0a\x02a0" + entries))
    good = tx(net, (0, 1), results=_kv_rwset(
        b"\x0a\x02a0\x12\x04\x0a\x02ok"))
    with pytest.raises(Exception):
        _ch, _sh, _cap, _prp, cca = pu.extract_action(bad)
        TxRWSet.from_bytes(cca.results)
    blk = block([bad, good] + filler(net, 2, 16, 2), 2)
    _native, rwp = _flat(blk)
    assert rwp.status.tolist()[:2] == [1, 0]
    assert rwp.n_meta == 1 and rwp.m_only.tolist()[:1] == [0]
    state = MemVersionedDB()
    seed_state(state, owned(3))
    vd, vh = validators(net, state)
    flt, _batch_d, _ = vd.validate(blk)
    assert flt[0] == C.BAD_RWSET and flt[1] == C.VALID
