"""Txs the duplicate check found in the index or among the in-flight
predecessors' txids: the ``hits`` of the validator's ``dup_txid`` span,
median per block.  Fixed by the stream (its ``duplicate_txid`` txs a
block) and checked by ``correct`` block by block: it must not move.
None where the program records no such attribute."""

from benchmark import spans, timeline

LAYER, UNIT, SOURCE, MOVES = ("workload_check", "tx", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.attr_per_block(obs.roots, "dup_txid", "hits")
    return timeline.median(list(per_block.values())) if per_block else None
