"""The block store's ``fsync`` spans on a committer thread, summed,
median per block: the segment-file syncs the commit itself pays for.
The applier's fence syncs on its own thread and is not counted; a block
whose window the applier closed first reads 0 here.  None where the
program records no ``fsync`` span at all."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block, seen = [], False
    for r in obs.roots:
        syncs = [sp for sp in spans.walk(r) if sp.name == "fsync"]
        seen = seen or bool(syncs)
        per_block.append(sum((sp.t1 - sp.t0) * 1000.0 for sp in syncs
                             if "committer" in sp.thread))
    return spans.median_ms(per_block) if seen else None
