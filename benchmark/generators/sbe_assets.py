"""Asset txs under key-level endorsement, in the shapes of
fabric-samples' ``asset-transfer-sbe``, and the assembler that signs
them (``benchmark/stream.py``: a generator that brings ``envelopes``
owns its rows).

A row is ``(kind, orgs, reads, writes, params)``: the orgs whose peers
endorse (org *n* is ``endorsers[n]``), reads of ``(key, version)``,
writes of ``(key, value)``, and ``params`` of ``(key, owner)``: the keys
whose VALIDATION_PARAMETER the tx sets, and to which org's policy.
Kinds, in the shares the traffic file's ``mix`` gives of each block's
txs (positions permuted by (seed, block)):

``update``                 the owner's endorsement alone rewrites the value
``transfer``               value and parameter, the old owner endorses
``create``                 a fresh asset and its parameter, two orgs: the
                           chaincode's policy
``bad_creator_signature``  an update whose envelope signature is spoiled
``wrong_org_endorsement``  an update endorsed by the two orgs that do not
                           own the asset: the chaincode's 2-of-3 is met,
                           the key's policy is not

Every preloaded asset is drawn at most once in the whole stream (one
seeded permutation of the preload), so each is read cold at its
preloaded version and no block has two txs on one key.  Block ``b`` is a
function of (configuration, traffic, seed, b) alone: any worker makes
any block.  No ``jax``.
"""

import functools

import numpy as np

KINDS = ("update", "transfer", "create", "bad_creator_signature",
         "wrong_org_endorsement")
_PERM_TAG, _VALUE_TAG = 0x5BE, 0x5BE1


@functools.lru_cache(maxsize=None)
def policy_bytes(owner: int) -> bytes:
    """The parameter a tx writes for ``Org<owner+1>MSP``: one of that
    org's peers (``statebased.NewStateEP`` + ``AddOrgs(RoleTypePeer,
    org)``)."""
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.crypto.msp import policy_to_proto

    return policy_to_proto(pol.from_dsl(
        f"OutOf(1, 'Org{owner + 1}MSP.peer')")).SerializeToString()


def kinds_of_a_block(mix: dict, block_tx: int) -> list:
    """``block_tx`` kinds in the shares of ``mix``, each share rounded
    and what rounding leaves given to ``update``."""
    unknown = set(mix) - set(KINDS)
    if unknown:
        raise ValueError(f"mix names {sorted(unknown)}; the kinds are {KINDS}")
    counts = {k: round(float(mix.get(k, 0)) * block_tx) for k in KINDS}
    counts["update"] += block_tx - sum(counts.values())
    if min(counts.values()) < 0:
        raise ValueError(f"mix {mix} does not fit a block of {block_tx}")
    return [k for k in KINDS for _ in range(counts[k])]


class Planner:
    def __init__(self, config, traffic, seed, reference):
        self.seed, self.orgs = int(seed), int(config["orgs"])
        if self.orgs < 3:
            raise ValueError("wrong_org_endorsement needs two orgs that "
                             "do not own the asset")
        self.T, self.n_keys = int(config["block_tx"]), int(
            config["preload_keys"])
        self.first = int(config.get("first_block", 0))
        self.vb = int(config["value_bytes"])
        self.version = tuple(config["preload_version"])
        self.key_name = reference.key_name
        self.perm = np.random.default_rng(
            [self.seed, _PERM_TAG]).permutation(self.n_keys)
        self.kinds = kinds_of_a_block(traffic["mix"], self.T)

    def rows(self, b):
        T, k = self.T, b - self.first
        if (k + 1) * T > self.n_keys:
            raise ValueError(f"block {b} needs more than the "
                             f"{self.n_keys} preloaded assets")
        rng = np.random.default_rng([self.seed, _VALUE_TAG, b])
        kinds = [self.kinds[j] for j in rng.permutation(T)]
        values = rng.bytes(T * self.vb)
        rows = []
        for i, kind in enumerate(kinds):
            j = int(self.perm[k * T + i])
            key, owner = self.key_name(j), j % self.orgs
            value = values[i * self.vb:(i + 1) * self.vb]
            others = tuple(o for o in range(self.orgs) if o != owner)
            held = ((key, self.version),)
            if kind == "create":
                key = f"a{b}_{i:03d}"
                rows.append((kind, others[:2], (), ((key, value),),
                             ((key, i % self.orgs),)))
            elif kind == "transfer":
                rows.append((kind, (owner,), held, ((key, value),),
                             ((key, others[i % len(others)]),)))
            elif kind == "wrong_org_endorsement":
                rows.append((kind, others[:2], held, ((key, value),), ()))
            else:  # update, bad_creator_signature
                rows.append((kind, (owner,), held, ((key, value),), ()))
        return rows


def planner(config, traffic, seed, reference):
    return Planner(config, traffic, seed, reference)


def envelopes(rows, config, endorsers, client, seed, block_num,
              replays=None):
    """One block's rows → its serialized tx envelopes."""
    from benchmark import stream
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, TxRWSet
    from fabric_tpu.peer import txassembly as txa

    channel, cc = config["channel"], config["chaincode"]
    out = []
    for i, (kind, orgs, reads, writes, params) in enumerate(rows):
        prop = stream.proposal(client.serialized, channel, cc,
                               stream.nonce(seed, block_num, i))
        tx = TxRWSet()
        ns = tx.ns_rwset(cc)
        for key, ver in reads:
            ns.reads[key] = tuple(ver)
        for key, value in writes:
            ns.writes[key] = value
        for key, owner in params:
            ns.metadata_writes[key] = {
                VALIDATION_PARAMETER: policy_bytes(owner)}
        rw = tx.to_proto().SerializeToString()
        env = txa.assemble_transaction(prop, [
            txa.create_proposal_response(prop, rw, endorsers[o], cc)
            for o in orgs], client)
        if kind == "bad_creator_signature":
            env.signature = stream.spoil(env.signature)
        out.append(env.SerializeToString())
    return out
