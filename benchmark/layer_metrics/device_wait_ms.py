"""The validator's ``device_wait`` span, median per block: how long the
caller thread waited for the stage-2 output when it asked."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.device_lane", "ms",
                              "program_span", "commit_tx_per_s")


def read(obs):
    return spans.median_ms(spans.ms_per_block(obs.roots, ["device_wait"]))
