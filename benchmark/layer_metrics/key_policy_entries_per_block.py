"""Key entries a block's stage-2 program judged under a committed
validation parameter and not under the chaincode's policy: the
``entries`` of the validator's ``sf.key_lanes`` span, median per block.
Fixed by the stream (``sbe_backlog``: the 800 txs of a block that touch
a preloaded asset), so it must not move.  None where the program
records no such attribute."""

from benchmark import spans, timeline

LAYER, UNIT, SOURCE, MOVES = ("workload_check", "1", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.attr_per_block(obs.roots, "sf.key_lanes", "entries")
    return timeline.median(list(per_block.values())) if per_block else None
