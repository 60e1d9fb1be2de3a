"""Process start to the opening of the window: imports, the network's
keys, the state DB, the block stream, the first dispatch of each shape
(trace + lower + compile or cache load), the warm-up and ramp blocks."""

UNIT, SOURCE = "s", "host_clock"


def read(obs):
    return obs.t_open - obs.t_start
