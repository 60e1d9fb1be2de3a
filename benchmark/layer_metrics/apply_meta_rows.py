"""Rows of a block's state apply that carry metadata (a key-level
endorsement policy rides there): the ``meta_rows`` of the applier's
``apply.write`` span, median per block.  Fixed by the stream
(``sbe_backlog``: every row a valid tx writes, 900 a block), so it
must not move.  None where the program records no such attribute."""

from benchmark import spans, timeline

LAYER, UNIT, SOURCE, MOVES = ("ledger", "1", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.attr_per_block(obs.roots, "apply.write", "meta_rows")
    return timeline.median(list(per_block.values())) if per_block else None
