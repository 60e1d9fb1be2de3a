"""From a generator's plan to signed blocks: the network's identities,
the envelope of one planned tx, and the pool of worker processes that
signs blocks while the parent imports ``jax`` and compiles.

A tx's nonce, and so its txid (``sha256(nonce ‖ creator)``), is a function
of (seed, block, position): any worker can make any block, and a tx that
replays an earlier one (``duplicate_txid``) needs only that tx's block and
position to carry its txid.  The creator is the run's one client.

A deployment may bring its own transactions.  Where the traffic's
generator module (``generators/<name>.py``) defines

    envelopes(rows, config, endorsers, client, seed, block_num, replays)

a worker builds each block's envelopes with it, and with
:func:`envelopes` here where it defines none; the arguments are the same
either way.  Such a generator owns its row format: from then on only it,
its planner and the configuration's reference read a row (the default's
rows are ``(kind, reads, writes)``).  What its assembler may take from
here: :func:`proposal` and :func:`nonce` (the seeded txid a replay needs),
:func:`spoil` (a signature that still parses and no longer verifies), and
the order of ``endorsers`` as :func:`signers` gives it: org *n*'s peer is
``endorsers[n]``, ``Org1MSP`` first; ``client`` is ``Org1MSP``'s user.

Nothing here imports ``jax``: the workers must never open the chip, and
they start faster without it.  Signing a 1000-tx block (three ECDSA
signatures and a dozen protobuf messages per tx) takes a quarter of a
second on one core of the sandbox and one to two seconds on a core of
the chip's host while the others are busy, which is why it is farmed out.
"""

from __future__ import annotations

import concurrent.futures as cf
import hashlib
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


def nonce(seed: int, block_num: int, position: int) -> bytes:
    """The 24 bytes tx ``position`` of block ``block_num`` carries as its
    nonce (``protoutil.random_nonce`` draws as many from the OS)."""
    return hashlib.sha256(
        f"nonce|{int(seed)}|{int(block_num)}|{int(position)}".encode()
    ).digest()[:24]


def proposal(creator: bytes, channel: str, chaincode: str, tx_nonce: bytes):
    """The proposal of one invoke, as ``txassembly.create_signed_proposal``
    builds it but for the nonce, which is handed in, and the
    ``SignedProposal`` around it, which no block carries (that signature,
    a quarter of the signing, is not made)."""
    from fabric_tpu import protoutil as pu
    from fabric_tpu.protos import common_pb2, proposal_pb2

    ext = proposal_pb2.ChaincodeHeaderExtension()
    ext.chaincode_id.name = chaincode
    ch = pu.make_channel_header(
        common_pb2.HeaderType.ENDORSER_TRANSACTION, channel,
        tx_id=pu.compute_tx_id(tx_nonce, creator),
        extension=ext.SerializeToString())
    sh = pu.make_signature_header(creator, tx_nonce)
    spec = proposal_pb2.ChaincodeInvocationSpec()
    spec.chaincode_spec.type = proposal_pb2.ChaincodeSpec.EXTERNAL
    spec.chaincode_spec.chaincode_id.name = chaincode
    spec.chaincode_spec.input.args.append(b"invoke")
    cpp = proposal_pb2.ChaincodeProposalPayload(
        input=spec.SerializeToString())
    return proposal_pb2.Proposal(
        header=common_pb2.Header(
            channel_header=ch.SerializeToString(),
            signature_header=sh.SerializeToString(),
        ).SerializeToString(),
        payload=cpp.SerializeToString())


def spoil(sig: bytes) -> bytes:
    """A DER signature that still parses and no longer verifies."""
    return sig[:-4] + bytes(4)


def envelopes(rows, config: dict, endorsers, client, seed: int,
              block_num: int, replays=None) -> list:
    """One block's plan → its serialized tx envelopes.  Tx i is endorsed
    by peers i and i+1 (mod the orgs), ``endorsements_per_tx`` of them.
    ``replays`` maps a position to the (block, position) whose nonce, and
    so whose txid, that tx carries instead of its own."""
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.peer import txassembly as txa

    channel, cc = config["channel"], config["chaincode"]
    n_end, n_org = int(config["endorsements_per_tx"]), len(endorsers)
    creator, replays = client.serialized, replays or {}
    out = []
    for i, (kind, reads, writes) in enumerate(rows):
        prop = proposal(creator, channel, cc,
                        nonce(seed, *replays.get(i, (block_num, i))))
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
            resps[0].endorsement.signature = spoil(
                resps[0].endorsement.signature)
        env = txa.assemble_transaction(prop, resps, client)
        if kind == "bad_creator_signature":
            env.signature = spoil(env.signature)
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

    _worker["config"], _worker["seed"] = config, seed
    _worker["generator"] = manifest.load_module("generators", generator, root)
    _worker["planner"] = _worker["generator"].planner(
        config, traffic, seed, manifest.reference_of(config, root))
    _worker["endorsers"], _worker["client"] = signers(network)


def _make_block(k: int) -> tuple:
    """The k-th block of the stream → (rows, envelopes, how many of its
    txs carry the txid of an earlier one)."""
    planner, config = _worker["planner"], _worker["config"]
    b = int(config.get("first_block", 0)) + k
    rows = planner.rows(b)
    # a generator that replays txids says which, for the block it
    # planned last
    replays = planner.replays(b) if hasattr(planner, "replays") else None
    # the deployment's own assembler where its generator brings one
    build = getattr(_worker["generator"], "envelopes", envelopes)
    return (rows, build(rows, config, _worker["endorsers"],
                        _worker["client"], _worker["seed"], b, replays),
            len(replays or ()))


def default_workers() -> int:
    """Leave cores for the parent (tracing and lowering the ladder is one
    busy thread) and the preload child."""
    return max(1, min(10, (os.cpu_count() or 2) - 3))


class BlockFactory:
    """Signs blocks in worker processes, in block order.  ``extend(n)``
    asks for the stream to reach n blocks (or cuts what has not started
    back to n); ``take(k)`` waits for the plan and envelopes of the
    stream's k-th block, which is block ``first_block + k``."""

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

    def take(self, k: int) -> tuple:
        """→ (rows, envelopes, txids replayed) of the stream's k-th
        block; the last is what the system's duplicate check has to find
        there."""
        return self._futs[k].result()

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)
