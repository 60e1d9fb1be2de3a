"""From a generator's plan to signed blocks: the network's identities,
the envelope of one planned tx, and the pool of worker processes that
signs blocks while the parent imports ``jax`` and compiles.

Nothing here imports ``jax``: the workers must never open the chip, and
they start faster without it.  Signing a 1000-tx block (three ECDSA
signatures and a dozen protobuf messages per tx) takes a quarter of a
second on one core of the sandbox and one to two seconds on a core of
the chip's host while the others are busy, which is why it is farmed out.
"""

from __future__ import annotations

import concurrent.futures as cf
import multiprocessing
import os


def make_network(config: dict) -> dict:
    """The channel's orgs, one endorsing peer each, and a client, as PEM
    bytes a worker process can be handed.  ``cryptogen`` takes no seed."""
    from fabric_tpu.crypto import cryptogen

    orgs, endorsers = [], []
    for i in range(1, int(config["orgs"]) + 1):
        msp_id, domain = f"Org{i}MSP", f"org{i}.example.com"
        org = cryptogen.generate_org(msp_id, domain,
                                     peers=int(config["peers_per_org"]),
                                     users=1)
        orgs.append({"msp_id": msp_id, "ca_pem": org.ca.cert_pem})
        enr = org.nodes[f"peer0.{domain}"]
        endorsers.append((msp_id, enr.key_pem, enr.cert_pem))
        if i == 1:
            usr = org.users[f"User1@{domain}"]
            client = (msp_id, usr.key_pem, usr.cert_pem)
    return {"orgs": orgs, "endorsers": endorsers, "client": client}


def signers(network: dict) -> tuple:
    """→ (endorsers, client) as ``SigningIdentity`` objects."""
    from fabric_tpu.crypto.identity import SigningIdentity

    return ([SigningIdentity.from_pem(*e) for e in network["endorsers"]],
            SigningIdentity.from_pem(*network["client"]))


def msp_manager(network: dict):
    from fabric_tpu.crypto.msp import MSP, MSPManager

    return MSPManager({
        o["msp_id"]: MSP(msp_id=o["msp_id"], root_certs=[o["ca_pem"]],
                         node_ous=True)
        for o in network["orgs"]
    })


class _Unsigned:
    """The client as the creator of a proposal: a block carries the
    proposal and never the ``SignedProposal`` around it, so that
    signature (a quarter of the signing) is not made."""

    def __init__(self, signer):
        self.serialized = signer.serialized

    def sign(self, _message: bytes) -> bytes:
        return b""


def _spoil(sig: bytes) -> bytes:
    """A DER signature that still parses and no longer verifies."""
    return sig[:-4] + bytes(4)


def envelopes(rows, config: dict, endorsers, client) -> list:
    """One block's plan → its serialized tx envelopes.  Tx i is endorsed
    by peers i and i+1 (mod the orgs), ``endorsements_per_tx`` of them."""
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.peer import txassembly as txa

    channel, cc = config["channel"], config["chaincode"]
    n_end, n_org = int(config["endorsements_per_tx"]), len(endorsers)
    proposer = _Unsigned(client)
    out = []
    for i, (kind, reads, writes) in enumerate(rows):
        _, _, prop = txa.create_signed_proposal(proposer, channel, cc,
                                                [b"invoke"])
        tx = TxRWSet()
        ns = tx.ns_rwset(cc)
        for key, ver in reads:
            ns.reads[key] = None if ver is None else tuple(ver)
        for key, value in writes:
            ns.writes[key] = value
        rw = tx.to_proto().SerializeToString()
        resps = [txa.create_proposal_response(
            prop, rw, endorsers[(i + j) % n_org], cc) for j in range(n_end)]
        if kind == "bad_endorsement_signature":
            resps[0].endorsement.signature = _spoil(
                resps[0].endorsement.signature)
        env = txa.assemble_transaction(prop, resps, client)
        if kind == "bad_creator_signature":
            env.signature = _spoil(env.signature)
        out.append(env.SerializeToString())
    return out


def chain(block_num: int, prev_hash: bytes, envs):
    """Envelopes → the block that extends ``prev_hash`` → (block, hash)."""
    from fabric_tpu import protoutil as pu

    blk = pu.new_block(block_num, prev_hash)
    blk.data.data.extend(envs)
    blk = pu.finalize_block(blk)
    return blk, pu.block_header_hash(blk.header)


# -- the worker pool ---------------------------------------------------------

_worker: dict = {}


def _init_worker(root, generator, config, traffic, seed, network):
    import sys

    # the parent's path to the window (imports, the device, tracing and
    # lowering) and the state DB's child go first where cores are short
    os.nice(5)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import manifest

    _worker["config"] = config
    _worker["planner"] = manifest.load_module(
        "generators", generator, root).planner(config, traffic, seed)
    _worker["endorsers"], _worker["client"] = signers(network)


def _make_block(b: int) -> tuple:
    rows = _worker["planner"].rows(b)
    return rows, envelopes(rows, _worker["config"], _worker["endorsers"],
                           _worker["client"])


def default_workers() -> int:
    """Leave cores for the parent (tracing and lowering the ladder is one
    busy thread) and the preload child."""
    return max(1, min(10, (os.cpu_count() or 2) - 3))


class BlockFactory:
    """Signs blocks in worker processes, in block order.  ``extend(n)``
    asks for the stream to reach n blocks (or cuts what has not started
    back to n); ``take(b)`` waits for block b's plan and envelopes."""

    def __init__(self, root, config, traffic, seed, network,
                 workers: int | None = None):
        ctx = multiprocessing.get_context("spawn")
        self._pool = cf.ProcessPoolExecutor(
            workers or default_workers(), mp_context=ctx,
            initializer=_init_worker,
            initargs=(root, traffic["generator"], config, traffic, seed,
                      network),
        )
        self._futs: list = []

    def extend(self, n: int) -> None:
        while len(self._futs) > n and self._futs[-1].cancel():
            self._futs.pop()
        for b in range(len(self._futs), n):
            self._futs.append(self._pool.submit(_make_block, b))

    def __len__(self) -> int:
        return len(self._futs)

    def take(self, b: int) -> tuple:
        """→ (rows, envelopes) of block b."""
        return self._futs[b].result()

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)
