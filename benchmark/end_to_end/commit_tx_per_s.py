"""Closed loop: the transactions (valid and invalid) of the blocks whose
commit was acknowledged and whose state was applied inside the window,
over the window's length; a block's txs are counted on the block.  The
window opens at the apply of the last
ramp block and closes at the last apply inside ``--seconds``."""

from benchmark import timeline

UNIT, SOURCE = "tx/s", "host_clock"


def read(obs):
    if obs.loop != "backlog":
        return None
    return timeline.tx_per_s(obs.t_open, obs.t_close, obs.block_txs)
