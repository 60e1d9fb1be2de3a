"""The ledger's ``commit.enqueue`` span, median per block: handing the
block's writes to the apply queue, with any wait at a full queue, and
the private-data purge.  None where the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["commit.enqueue"])
    return spans.median_ms(per_block) if any(per_block) else None
