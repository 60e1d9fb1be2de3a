"""On the chip: what a peer pays, and no longer pays, for the programs
of a channel at the orderer's defaults (PERF.md section 6, PR 28).

A channel whose genesis block ``tools/configtxgen`` wrote at its
defaults (BatchSize 500 messages; three orgs, a chaincode under the
channel's MAJORITY endorsement policy, 2 endorsements a tx) is joined
through ``PeerNode.join_channel``, which runs ``PeerChannel.
_warm_programs`` for real:

1. a fresh join: the verify ladder alone (no block to take a tx shape
   from); how long ``join_channel`` holds its caller;
2. the channel's first blocks (500, 87 and 10 txs, each twice) through
   ``commit_block``: what a stage-2 program lowered on first sight costs
   the block that meets it;
3. the same directory joined again, as a restarted peer does: the
   stage-2 programs of blocks like those the channel holds, at every
   size;
4. blocks of 1..500 txs through the deliver loop's own driver
   (``_run_deliver_pipelined``, ``CommitPipeline`` at depth 2): none may
   lower a program, every tx must be valid and applied.

Prints one JSON line last and writes it to ``--out``; exit 0 only if
step 4 lowered nothing and committed everything.

    chiprun --timeout 1800 -- python3 scripts/warm_channel.py
"""

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CHANNEL, CC = "warmchan", "warmcc"
FIRST = [500, 500, 87, 87, 10, 10]
SIZES = [1, 5, 10, 16, 21, 32, 42, 64, 85, 87, 128, 170, 256, 300, 341,
         499, 500]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu",
                    help="cpu: a try-out of this script; the CPU backend "
                         "then stands in for the chip (with a small "
                         "--max-tx)")
    ap.add_argument("--max-tx", type=int, default=None,
                    help="BatchSize.max_message_count (configtxgen's "
                         "default, 500, when left out)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "warm_channel.json"))
    args = ap.parse_args()

    from benchmark.run import CompileWatch
    from fabric_tpu import protoutil as pu
    from fabric_tpu.crypto import cryptogen
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.ledger.statedb import SqliteVersionedDB
    from fabric_tpu.peer import lifecycle as lc
    from fabric_tpu.peer import txassembly as txa
    from fabric_tpu.peer.node import PeerNode
    from fabric_tpu.peer.validator import BlockValidator
    from fabric_tpu.protos import common_pb2
    from fabric_tpu.tools import configtxgen as cg
    from fabric_tpu.utils.xla_env import claim_device, enable_compile_cache

    dev = claim_device("warm_channel")
    if dev["platform"] != args.platform:
        raise SystemExit(f"wanted {args.platform}, got {dev}")
    out = {"device": dev, "cache_dir": enable_compile_cache()}
    if args.platform == "cpu":
        from fabric_tpu.utils import xla_env

        xla_env.on_accelerator = lambda: True

    orgs = [cryptogen.generate_org(f"Org{i}MSP", f"org{i}.example.com",
                                   peers=1, users=1) for i in (1, 2, 3)]
    profile = cg.Profile(CHANNEL, application_orgs=[
        cg.OrgProfile(o.msp_id, o.msp()) for o in orgs])
    if args.max_tx is not None:
        profile.max_message_count = args.max_tx
    out["max_message_count"] = top = profile.max_message_count
    genesis = cg.genesis_block(profile)
    client = cryptogen.signing_identity(orgs[0], "User1@org1.example.com")
    peers = [cryptogen.signing_identity(o, f"peer0.org{i}.example.com")
             for i, o in zip((1, 2, 3), orgs)]

    def tx(ns, writes):
        _signed, _id, prop = txa.create_signed_proposal(
            client, CHANNEL, ns, [b"invoke"])
        rw = TxRWSet()
        for k, v in writes:
            rw.ns_rwset(ns).writes[k] = v
        raw = rw.to_proto().SerializeToString()
        return txa.assemble_transaction(
            prop, [txa.create_proposal_response(prop, raw, e, ns)
                   for e in peers[:2]], client)

    def block(ch, num, prev, size):
        """→ (block ``num`` of ``size`` txs of two fresh 200 B writes,
        its header's hash)."""
        blk = pu.new_block(num, prev)
        for i in range(min(size, top)):
            blk.data.data.append(tx(CC, [
                (f"a{num}.{i}", b"v" * 200),
                (f"b{num}.{i}", b"w" * 200)]).SerializeToString())
        blk = pu.finalize_block(blk)
        return blk, pu.block_header_hash(blk.header)

    def tip(ch):
        return pu.block_header_hash(
            ch.ledger.blocks.get_block(ch.height - 1).header)

    warmed, real = [], BlockValidator.warmup

    def spy(self, max_tx, signatures_per_tx, seen):
        got = real(self, max_tx, signatures_per_tx, seen)
        warmed.append(dict(got, max_tx=max_tx,
                           signatures_per_tx=signatures_per_tx,
                           tx_shapes=len(seen)))
        return got

    BlockValidator.warmup = spy
    tmp = tempfile.mkdtemp(prefix="warm_channel_")
    node = PeerNode("peer0", os.path.join(tmp, "peer0"),
                    None, peers[0])

    def join(watch):
        t0, n0 = time.perf_counter(), len(watch.events)
        hits, misses = watch.hits, watch.misses
        ch = node.join_channel(
            CHANNEL, genesis_block=genesis,
            state_db=SqliteVersionedDB(os.path.join(tmp, "state.db")))
        jax_s: dict = {}
        for part, _t, d in watch.events[n0:]:
            jax_s[part] = round(jax_s.get(part, 0.0) + d, 2)
        return ch, {
            "join_s": round(time.perf_counter() - t0, 3),
            "lowered": sum(e[0] == "lower" for e in watch.events[n0:]),
            "jax_s": jax_s,
            "cache_hits_misses": [watch.hits - hits, watch.misses - misses],
            "warmup": warmed[-1] if warmed else None}

    ok = False
    try:
        with CompileWatch() as watch:
            ch, out["fresh_join"] = join(watch)
            cd = lc.ChaincodeDefinition(name=CC, sequence=1)
            blk = pu.new_block(ch.height, tip(ch))
            blk.data.data.append(tx(lc.LIFECYCLE_NS, [(
                lc.definition_key(CC), cd.to_bytes())]).SerializeToString())
            flt = asyncio.run(ch.commit_block(pu.finalize_block(blk)))
            assert list(flt) == [0], list(flt)
            first = []
            for size in FIRST:
                blk, _h = block(ch, ch.height, tip(ch), size)
                t0 = time.perf_counter()
                flt = asyncio.run(ch.commit_block(blk))
                t1 = time.perf_counter()
                assert not any(flt), f"first block of {size}: {set(flt)}"
                first.append({"txs": len(blk.data.data),
                              "commit_s": round(t1 - t0, 3),
                              "lowered": watch.lowered_between(t0, t1)})
            out["first_blocks"] = first
            ch.stop()
            del warmed[:]
            ch, out["joined_again"] = join(watch)
            blocks, num, prev = [], ch.height, tip(ch)
            for size in SIZES:
                blk, prev = block(ch, num, prev, size)
                blocks.append(blk)
                num += 1

            async def stream():
                for b in blocks:
                    yield b

            t0 = time.perf_counter()
            asyncio.run(ch._run_deliver_pipelined(stream()))
            t1 = time.perf_counter()
            wrong = 0
            for b in blocks:
                got = ch.ledger.blocks.get_block(b.header.number)
                flags = bytes(got.metadata.metadata[
                    common_pb2.BlockMetadataIndex.TRANSACTIONS_FILTER])
                wrong += (len(flags) != len(b.data.data)) + sum(
                    f != 0 for f in flags)
            last = len(blocks[-1].data.data) - 1
            key = f"b{blocks[-1].header.number}.{last}"
            ch.ledger.drain_state()
            got = ch.ledger.state.get_state(CC, key)
            out["blocks"] = {
                "sizes": [len(b.data.data) for b in blocks],
                "seconds": round(t1 - t0, 3),
                "lowered": watch.lowered_between(t0, t1),
                "height": ch.height, "want_height": num,
                "txs_not_valid": wrong,
                "last_write_applied": got is not None
                and got.value == b"w" * 200}
            ch.stop()
        b = out["blocks"]
        ok = (b["lowered"] == 0 and b["height"] == b["want_height"]
              and not b["txs_not_valid"] and b["last_write_applied"])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
