"""The pipeline's ``commit`` span, median per block: the ledger commit
(block append, index, enqueue of the state apply) and the sync before
the acknowledgement, on the committer thread."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    return spans.median_ms(spans.ms_per_block(obs.roots, ["commit"]))
