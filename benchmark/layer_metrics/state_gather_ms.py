"""The apply engine's ``sf.gather`` span, median per block: the state
DB's own committed-version query inside ``state_fill``, without the
walk over the pending applies or the launch vector.  None where the
program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.host_lane", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["sf.gather"])
    return spans.median_ms(per_block) if any(per_block) else None
