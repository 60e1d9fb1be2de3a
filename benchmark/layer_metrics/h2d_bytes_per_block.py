"""Bytes uploaded per block, from the launch ledger's rows (verify
frame + stage-2 launch vector), median per block.  A count: it repeats
exactly for a shape."""

from benchmark import timeline

LAYER, UNIT, SOURCE, MOVES = ("validator.device_lane", "B",
                              "program_counter", "commit_tx_per_s")


def read(obs):
    per_block = {}
    for r in obs.launch_rows:
        per_block[r["block"]] = per_block.get(r["block"], 0) + r["h2d_bytes"]
    return timeline.median(list(per_block.values())) if per_block else None
