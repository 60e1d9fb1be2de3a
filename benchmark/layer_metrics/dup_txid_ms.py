"""The validator's ``dup_txid`` span, median per block: the
duplicate-txid lookups on the block store's index, inside ``launch`` on
the caller thread.  None where the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.host_lane", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["dup_txid"])
    return spans.median_ms(per_block) if any(per_block) else None
