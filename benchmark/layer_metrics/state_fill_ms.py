"""The validator's ``state_fill`` span, median per block: the
committed-version reads on the state DB (and the launch vector built
from them), on the caller thread."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.host_lane", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    return spans.median_ms(spans.ms_per_block(obs.roots, ["state_fill"]))
