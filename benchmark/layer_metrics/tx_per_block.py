"""Txs per block over the window: the mean of the ``txs`` the program
writes on each block's root span.  Fixed by the stream (a cycle of 64
sizes whose mean is 165.4375, to within the part of a cycle the window's
edge cuts), so it must not move: a PR that moves it changed the blocks,
not the system.  None where the program's root spans say no ``txs``."""

from benchmark import per_tx

LAYER, UNIT, SOURCE, MOVES = ("workload_check", "tx", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    txs = per_tx.root_txs(obs.roots)
    return sum(txs.values()) / len(txs) if txs else None
