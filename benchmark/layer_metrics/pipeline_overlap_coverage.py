"""The share of each block's ``device_wait`` that host work of its
neighbours (within depth - 1 blocks) covers, mean over the window's
blocks: how much of the device lane the pipeline hides."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("commit_pipeline", "%", "program_span",
                              "commit_tx_per_s")


def read(obs):
    share = spans.overlap_coverage(obs.roots, window=max(1, obs.depth - 1))
    return None if share is None else share * 100.0
