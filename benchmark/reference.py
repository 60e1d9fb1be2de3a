"""The plain reference: what a block stream's tx filters and final state
must be, worked out serially on the host and independent of the
validator under test.

Two levels, which must agree with each other and with the system:

* :func:`plan_codes` — from the generator's plan (the read and write
  sets it put into each tx, and which signatures it corrupted): serial
  MVCC in block order, the signature verdicts by construction.  Cheap,
  so every tx of every block is held to it.
* :func:`openssl_codes` — from a block's bytes alone: per tx parse →
  creator signature (OpenSSL) → endorsement signatures (OpenSSL) →
  policy walk → serial MVCC (the work of v20/validator.go:180 and
  validation/validator.go:81 on one thread).  About 0.6 s per 1000-tx
  block, so a seeded sample of blocks is held to it.

A tx that carries the txid of a tx of an earlier block of the stream is
``DUPLICATE_TXID`` (v20/validator.go:460-481 checkTxIdDupsLedger): by
construction at the plan level, and at the OpenSSL level because the
rolling :class:`RefState` holds every txid of the blocks committed to it
(:func:`block_txids`, from the blocks' bytes), looked up after a good
creator signature as the validator does.

A copy of ``chip_smoke.serial_reference`` (PR 21), per block and over a
rolling :class:`RefState`; a corrupted endorsement signature leaves one
good endorsement of the two the policy asks for and gives
``ENDORSEMENT_POLICY_FAILURE``.  Imports nothing of ``peer/validator.py``
(the caller hands in its ``PolicyProvider``) and no ``jax``: the
generator's workers import this module.
"""

from __future__ import annotations

from fabric_tpu.protos import transaction_pb2

C = transaction_pb2.TxValidationCode

#: what a generator may do to a tx, and the verdict that follows by
#: construction; ``ok`` and ``stale_read`` are decided by MVCC
SIGNATURE_KINDS = {
    "bad_creator_signature": C.BAD_CREATOR_SIGNATURE,
    "bad_endorsement_signature": C.ENDORSEMENT_POLICY_FAILURE,
}
#: ``duplicate_txid``: the tx carries the txid of an ``ok`` tx of one of
#: the blocks before its own, with writes and good signatures of its own
BY_CONSTRUCTION = {**SIGNATURE_KINDS, "duplicate_txid": C.DUPLICATE_TXID}
KINDS = ("ok", "stale_read", *BY_CONSTRUCTION)
#: the version a ``stale_read`` tx claims to have read: no tx has index
#: 1,000,000 and the preload sits at block 1, so it equals no real one
STALE_VERSION = (0, 1_000_000)


def key_name(j: int) -> str:
    """The j-th preloaded key."""
    return f"k{j:07d}"


class RefState:
    """The committed state as the reference leaves it: ``preload_keys``
    keys at ``preload_version`` until a valid tx rewrites them, what
    valid txs wrote, and the txid of every tx (valid or not) of the
    blocks committed to it."""

    def __init__(self, config: dict):
        self.preload_keys = int(config["preload_keys"])
        self.preload_version = tuple(config["preload_version"])
        self.written: dict = {}  # key → (value, version)
        self.txids: set = set()

    def preloaded(self, key: str) -> bool:
        return (len(key) == 8 and key[0] == "k" and key[1:].isdigit()
                and int(key[1:]) < self.preload_keys)

    def version(self, key: str):
        got = self.written.get(key)
        if got is not None:
            return got[1]
        return self.preload_version if self.preloaded(key) else None

    def commit(self, writes: dict, txids=()) -> None:
        self.written.update(writes)
        self.txids.update(txids)


def _conflicts(key: str, ver, done: dict, state: RefState) -> bool:
    """``validateKVRead`` (validation/validator.go): a read conflicts when
    an earlier valid tx of this block wrote the key, whatever version that
    gives it, or when the committed version is not the one read.  (The
    smoke's reference compares versions in both cases, which misses a
    write by tx 0 of block 1: its version (1, 0) is the preload's.)"""
    if key in done:
        return True
    return state.version(key) != (None if ver is None else tuple(ver))


def plan_codes(rows, block_num: int, state: RefState) -> tuple:
    """Serial MVCC over one block's plan → (filter bytes, writes).
    ``rows[i]`` is ``(kind, reads, writes)`` with ``reads`` a sequence of
    ``(key, version)`` and ``writes`` of ``(key, value)``; ``writes`` out
    is ``{key: (value, (block_num, i))}`` of the valid txs, in the form
    :meth:`RefState.commit` takes.  ``state`` is not changed."""
    codes, done = [], {}
    for i, (kind, reads, writes) in enumerate(rows):
        code = BY_CONSTRUCTION.get(kind)
        if code is None:
            code = C.VALID
            for key, ver in reads:
                if _conflicts(key, ver, done, state):
                    code = C.MVCC_READ_CONFLICT
                    break
        if code == C.VALID:
            for key, value in writes:
                done[key] = (value, (block_num, i))
        codes.append(code)
    return bytes(codes), done


def written_keys(rows) -> set:
    """Every key of every tx's writes in one block's plan, valid or not:
    what a run reads back after flush and after reopen (an invalid tx's
    write must be absent).  A configuration's own reference may define
    its own, say to add keys whose metadata alone was written."""
    return {key for _kind, _reads, writes in rows for key, _value in writes}


def block_txids(blk) -> list:
    """The txid every tx of a block carries in its channel header, from
    the block's bytes."""
    from fabric_tpu import protoutil as pu
    from fabric_tpu.protos import common_pb2

    out = []
    for env_bytes in blk.data.data:
        env = pu.unmarshal(common_pb2.Envelope, env_bytes)
        payload = pu.unmarshal(common_pb2.Payload, env.payload)
        out.append(pu.unmarshal(common_pb2.ChannelHeader,
                                payload.header.channel_header).tx_id)
    return out


def openssl_codes(blk, mgr, prov, state: RefState, chaincode: str) -> tuple:
    """One block through the reference commit path, from its bytes
    alone → (filter bytes, writes) as :func:`plan_codes` gives them."""
    import numpy as np

    from fabric_tpu import protoutil as pu
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.protos import common_pb2

    plans: dict = {}
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
        idents, valid = [], []
        for e in cap.action.endorsements:
            ident = mgr.deserialize_identity(e.endorser)
            idents.append(ident)
            valid.append(ident.is_valid and ident.verify(
                prp_bytes + e.endorser, e.signature))
        rwset = TxRWSet.from_bytes(cca.results)
        if set(rwset.ns) != {chaincode}:
            raise ValueError(f"tx {txnum}: namespaces {sorted(rwset.ns)}, "
                             f"the stream writes only {chaincode!r}")
        endorsed = True
        for ns_name in rwset.ns:
            info = prov.info(ns_name)
            plan = plans.get(ns_name)
            if plan is None:
                plan = plans[ns_name] = pol.compile_plan(info.policy)
            m = pol.match_matrix(idents, plan.principals)
            m = m & np.asarray(valid, bool)[:, None]
            endorsed = endorsed and pol.evaluate(info.policy, m)
        if not endorsed:
            codes.append(C.ENDORSEMENT_POLICY_FAILURE)
            continue
        n = rwset.ns[chaincode]
        if any(_conflicts(k, ver, done, state)
               for k, ver in n.reads.items()):
            codes.append(C.MVCC_READ_CONFLICT)
            continue
        for k, v in n.writes.items():
            done[k] = (v, (blk.header.number, txnum))
        codes.append(C.VALID)
    return bytes(codes), done


def state_mismatches(db, chaincode: str, state: RefState, keys) -> list:
    """Where the ledger's state differs from the reference's on ``keys``:
    a valid tx's write not read back at its committed version, an invalid
    tx's write present, a preloaded key not at its version.  → messages,
    empty when every key agrees."""
    bad = []
    for key in keys:
        vv = db.get_state(chaincode, key)
        want = state.written.get(key)
        if want is not None:
            value, version = want
            if (vv is None or bytes(vv.value) != value
                    or tuple(vv.version) != tuple(version)):
                bad.append(f"{key}: want version {version}, have "
                           f"{None if vv is None else tuple(vv.version)}")
        elif state.preloaded(key):
            if vv is None or tuple(vv.version) != state.preload_version:
                bad.append(f"{key}: preloaded key not at "
                           f"{state.preload_version}")
        elif vv is not None:
            bad.append(f"{key}: an invalid tx's write is present at "
                       f"{tuple(vv.version)}")
    return bad
