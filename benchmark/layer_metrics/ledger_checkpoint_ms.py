"""What the state and history DBs' WAL checkpoints cost a block: the
``ledger.checkpoint`` spans of the window (a DB's WAL written back into
its file, under the root of the block on whose thread it ran: the
committer's after ``commit.enqueue``, the applier's once its apply is
published, or the caller's where it commits a tail) summed over every
thread, over the window's blocks.  A mean and not a median, as
``commit_checkpoint_ms``: the span is on one block in four or five.
None where the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["ledger.checkpoint"])
    return sum(per_block) / len(per_block) if any(per_block) else None
