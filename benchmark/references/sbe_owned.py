"""The plain reference of a channel whose assets carry key-level
(state-based) endorsement policies, as ``benchmark/reference.py`` is the
plain reference of a channel without: what each block's tx filter and
the final state must be, worked out serially on the host.

The rule (Fabric's ``docs/source/endorsement-policies.rst``, "Setting
key-level endorsement policies"; ``statebased/validator_keylevel.go``):
a key a tx writes, or whose validation parameter it writes, is checked
under the key's committed parameter where it has one, for the value and
for the parameter alike, INSTEAD of the chaincode's policy; a key
without one under the chaincode's policy.  The state holds each key's
owner org beside its value and version; a preloaded key ``k<j>`` belongs
to org ``j mod orgs`` until a transfer says otherwise.

No key comes twice in a block (the generator draws assets without
replacement, and both levels raise if one does), so what an earlier tx
of a block wrote never decides a later one's policy here: the in-block
rule is not this reference's to state (``configs/fabric-sbe-assets.json``,
``assumed``).

Two levels, as the plain reference's: :func:`plan_codes` from the
generator's rows (``generators/sbe_assets.py``: ``(kind, orgs, reads,
writes, params)``), every block; :func:`openssl_codes` from a block's
bytes alone, with the policy walk a key at a time, on sampled blocks.
Independent of ``fabric_tpu/peer/validator.py`` and ``device_block.py``:
it uses ``crypto/policy.py``'s exact interpreter (``evaluate`` over a
match matrix), the MSP's identities with OpenSSL behind them, and the
protobuf helpers, as ``benchmark/reference.py`` does.  No ``jax``.
"""

import functools

from benchmark import reference as _plain
from benchmark.reference import C, _conflicts, block_txids, key_name  # noqa: F401


@functools.lru_cache(maxsize=None)
def owner_policy(owner: int) -> bytes:
    """The validation parameter of an asset ``Org<owner+1>MSP`` owns: a
    serialized SignaturePolicyEnvelope that one of that org's peers
    satisfies (what ``statebased.NewStateEP`` + ``AddOrgs(RoleTypePeer,
    org)`` gives for one org)."""
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.crypto.msp import policy_to_proto

    return policy_to_proto(pol.from_dsl(
        f"OutOf(1, 'Org{owner + 1}MSP.peer')")).SerializeToString()


class RefState(_plain.RefState):
    """``written``: key → (value, version, owner)."""

    def __init__(self, config):
        super().__init__(config)
        self.orgs = int(config["orgs"])

    def owner(self, key):
        """The org whose policy the key carries; None: it carries none
        (an absent key: the chaincode's policy decides)."""
        got = self.written.get(key)
        if got is not None:
            return got[2]
        return int(key[1:]) % self.orgs if self.preloaded(key) else None


def written_keys(rows) -> set:
    """Every key a block's txs write or set the parameter of, valid or
    not: what a run reads back."""
    return {key for _k, _o, _r, writes, params in rows
            for key, _x in (*writes, *params)}


def plan_codes(rows, block_num, state):
    """Serial validation of one block's plan → (filter bytes, writes as
    ``RefState.commit`` takes them: key → (value, version, owner))."""
    codes, done = [], {}
    for i, (kind, orgs, reads, writes, params) in enumerate(rows):
        keys = {k for k, _x in (*writes, *params)}
        if keys & set(done):
            raise ValueError(f"block {block_num}: two txs on one key")
        if kind == "bad_creator_signature":
            codes.append(C.BAD_CREATOR_SIGNATURE)
            continue
        # under a parameter: its owner must be among the endorsers;
        # under none: two distinct orgs (the chaincode's 2-of-3)
        held = [state.owner(k) for k in keys]
        if not all(o in orgs if o is not None else len(set(orgs)) >= 2
                   for o in held):
            codes.append(C.ENDORSEMENT_POLICY_FAILURE)
            continue
        if any(_conflicts(k, ver, done, state) for k, ver in reads):
            codes.append(C.MVCC_READ_CONFLICT)
            continue
        new = dict(params)
        for key, value in writes:
            done[key] = (value, (block_num, i),
                         new.get(key, state.owner(key)))
        codes.append(C.VALID)
    return bytes(codes), done


def openssl_codes(blk, mgr, prov, state, chaincode):
    """One block from its bytes alone → as :func:`plan_codes`: per tx
    the creator's signature, the duplicate check, each endorsement's
    signature (OpenSSL), then for EACH key the tx touches the policy
    that key is under, walked by the exact interpreter, then serial
    MVCC."""
    import numpy as np

    from fabric_tpu import protoutil as pu
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.crypto.msp import policy_from_proto
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, TxRWSet
    from fabric_tpu.protos import common_pb2, policies_pb2

    @functools.lru_cache(maxsize=None)
    def parsed(raw):
        policy = policy_from_proto(pu.unmarshal(
            policies_pb2.SignaturePolicyEnvelope, raw))
        return policy, pol.compile_plan(policy).principals

    def satisfied(policy, principals, idents, valid):
        m = pol.match_matrix(idents, principals)
        return pol.evaluate(policy, m & np.asarray(valid, bool)[:, None])

    chaincode_policy = prov.info(chaincode).policy
    under_none = (chaincode_policy,
                  pol.compile_plan(chaincode_policy).principals)
    by_bytes = {owner_policy(o): o for o in range(state.orgs)}
    codes, done, seen = [], {}, set()
    for txnum, env_bytes in enumerate(blk.data.data):
        env = pu.unmarshal(common_pb2.Envelope, env_bytes)
        ch, sh, cap, _prp, cca = pu.extract_action(env)
        creator = mgr.deserialize_identity(sh.creator)
        if not creator.is_valid or not creator.verify(env.payload,
                                                      env.signature):
            codes.append(C.BAD_CREATOR_SIGNATURE)
            continue
        if ch.tx_id in state.txids or ch.tx_id in seen:
            codes.append(C.DUPLICATE_TXID)
            continue
        seen.add(ch.tx_id)
        prp_bytes = cap.action.proposal_response_payload
        idents = [mgr.deserialize_identity(e.endorser)
                  for e in cap.action.endorsements]
        valid = [i.is_valid and i.verify(prp_bytes + e.endorser, e.signature)
                 for i, e in zip(idents, cap.action.endorsements)]
        rwset = TxRWSet.from_bytes(cca.results)
        if set(rwset.ns) != {chaincode}:
            raise ValueError(f"tx {txnum}: namespaces {sorted(rwset.ns)}, "
                             f"the stream writes only {chaincode!r}")
        n = rwset.ns[chaincode]
        keys = set(n.writes) | set(n.metadata_writes)
        if keys & set(done):
            raise ValueError(f"tx {txnum}: a second tx on one key")
        endorsed = all(
            satisfied(*(under_none if state.owner(k) is None
                        else parsed(owner_policy(state.owner(k)))),
                      idents, valid)
            for k in keys)
        if not endorsed:
            codes.append(C.ENDORSEMENT_POLICY_FAILURE)
            continue
        if any(_conflicts(k, ver, done, state)
               for k, ver in n.reads.items()):
            codes.append(C.MVCC_READ_CONFLICT)
            continue
        for k, v in n.writes.items():
            new = n.metadata_writes.get(k, {}).get(VALIDATION_PARAMETER)
            done[k] = (v, (blk.header.number, txnum),
                       state.owner(k) if new is None else by_bytes[new])
        codes.append(C.VALID)
    return bytes(codes), done


def state_mismatches(db, chaincode, state, keys):
    """Where the ledger's state differs from the reference's on
    ``keys``: value and version as the plain reference compares them,
    and the key's validation parameter.  → messages, empty when every
    key agrees."""
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, decode_metadata

    bad = []
    for key in keys:
        vv = db.get_state(chaincode, key)
        want, owner = state.written.get(key), state.owner(key)
        if want is None and not state.preloaded(key):
            if vv is not None:
                bad.append(f"{key}: an invalid tx's write is present at "
                           f"{tuple(vv.version)}")
            continue
        value, version = (want[:2] if want is not None
                          else (None, state.preload_version))
        if vv is None or tuple(vv.version) != tuple(version) or (
                value is not None and bytes(vv.value) != value):
            bad.append(f"{key}: want version {version}, have "
                       f"{None if vv is None else tuple(vv.version)}")
        elif decode_metadata(vv.metadata).get(
                VALIDATION_PARAMETER) != owner_policy(owner):
            bad.append(f"{key}: not under Org{owner + 1}MSP's parameter")
    return bad
