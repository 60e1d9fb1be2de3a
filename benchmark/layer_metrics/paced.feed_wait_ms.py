"""The pipeline's ``feed_wait`` span, median per block: how long the
caller thread was outside the pipeline before it brought this block,
waiting for the stream.  In a paced cell that is the slack a block's
trip leaves.  None where the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = "entry", "ms", "program_span", "tx_commit_p50_ms"


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["feed_wait"])
    return spans.median_ms(per_block) if any(per_block) else None
