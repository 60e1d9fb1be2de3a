"""From a block's commit acknowledged to its state applied and
readable, on the harness's clock, median over the window's blocks:
how far the async applier trails."""

from benchmark import timeline

LAYER, UNIT, SOURCE, MOVES = "ledger", "ms", "host_clock", "tx_commit_p50_ms"


def read(obs):
    lags = [(obs.applied[b] - obs.ack[b]) * 1000.0
            for b in range(obs.first, obs.last + 1)
            if b in obs.applied and b in obs.ack]
    return timeline.median(lags) if lags else None
