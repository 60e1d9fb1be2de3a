"""The block store's ``commit.index`` span, median per block: the wait
for the index lock, the txid-index insert and its sqlite commit, on the
committer thread.  None where the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["commit.index"])
    return spans.median_ms(per_block) if any(per_block) else None
