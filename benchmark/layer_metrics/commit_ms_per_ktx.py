"""The committer thread's cost of a thousand txs: the ``commit`` spans
(block append, index transaction, enqueue of the apply, the sync before
the acknowledgement) summed over the window's blocks, over the sum of
the blocks' ``txs``, times 1000."""

from benchmark import per_tx

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    return per_tx.ms_per_ktx(obs.roots, ["commit"])
