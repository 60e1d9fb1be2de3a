"""What the txid index's WAL checkpoints cost a block: the
``commit.checkpoint`` spans of the window (the block store writing the
index's WAL back into ``index.db``, on the thread that commits, after
the block went to the applier and outside ``commit.index``) summed,
over the window's blocks.  A mean and not a median: the span is on one
block in about eight, so a median would read 0; beside
``commit_index_ms`` it says where the time the index transaction no
longer holds went.  None where the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["commit.checkpoint"])
    return sum(per_block) / len(per_block) if any(per_block) else None
