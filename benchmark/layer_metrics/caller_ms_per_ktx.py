"""The caller thread's cost of a thousand txs: the ``launch`` and
``finish`` spans summed over the window's blocks, over the sum of the
blocks' ``txs``, times 1000.  Beside ``insert_backlog``'s per-block
medians (1000-tx blocks) it says what the caller pays per block rather
than per tx; the largest of the three ``*_ms_per_ktx`` bounds the cell's
tx/s."""

from benchmark import per_tx

LAYER, UNIT, SOURCE, MOVES = ("commit_pipeline", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    return per_tx.ms_per_ktx(obs.roots, ["launch", "finish"])
