"""VALID txs over txs committed in the window.  Fixed by the stream and
checked by ``correct``; it repeats exactly for a seed and a block
range, and must not move: a PR that moves it changed verdicts."""

LAYER, UNIT, SOURCE, MOVES = ("workload_check", "%", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    n = sum(obs.block_txs)
    return obs.n_valid / n * 100.0 if n else None
