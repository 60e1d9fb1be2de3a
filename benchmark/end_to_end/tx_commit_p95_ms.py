"""Open loop: over every tx of every block due in the window, from the
moment the tx was due to arrive to the moment its block's state was
applied and readable; the 95th percentile.  None when a block of the
window was not applied: a tx that failed has no latency to report."""

from benchmark import timeline

UNIT, SOURCE = "ms", "host_clock"


def read(obs):
    if obs.tx_latencies_ms is None:
        return None
    return timeline.percentile(obs.tx_latencies_ms, 95.0)
