"""The validator's ``sf.key_lanes`` span, median per block: inside
``state_fill``, on the caller's thread, the mapping of the block's key
entries (a tx and a key it writes) to the policy of each key's
committed validation parameter, and the upload of that one int32 an
entry beside the launch vector.  None where the program records no
such span: a program without key-policy lanes, or a channel whose state
holds no parameter."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.host_lane", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["sf.key_lanes"])
    return spans.median_ms(per_block) if any(per_block) else None
