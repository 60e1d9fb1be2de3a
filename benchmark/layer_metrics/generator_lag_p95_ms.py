"""How late the harness itself released a block against its due time
(the overshoot of its own sleep), 95th percentile over the window's
blocks.  A sanity figure for ``tx_commit_*``: a starved generator is
not a fast peer."""

from benchmark import timeline

LAYER, UNIT, SOURCE, MOVES = "entry", "ms", "host_clock", "tx_commit_p50_ms"


def read(obs):
    if not obs.lag_s:
        return None
    return timeline.percentile(obs.lag_s, 95.0) * 1000.0
