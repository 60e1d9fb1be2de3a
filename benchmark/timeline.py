"""The clock arithmetic of a run, free of the system under test: when a
block is due, which blocks count, what a tx waited, how many txs a
second were committed.  Pure functions over timestamps (seconds on one
monotonic clock), so that made-up timelines can pin them.
"""

from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation between order
    statistics (numpy's default), as a plain float."""
    if len(values) == 0:
        raise ValueError("percentile of nothing")
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values) -> float:
    return percentile(values, 50.0)


# -- open loop ---------------------------------------------------------------


def paced_blocks(seconds: float, rate_tx_per_s: float, block_tx: int) -> int:
    """How many full blocks fall due inside a window of ``seconds``: block
    k (from 0) is released when its last tx is due, (k+1)·T/R after the
    window opens."""
    return int(math.floor(seconds * rate_tx_per_s / block_tx + 1e-9))


def block_due(t0: float, k: int, rate_tx_per_s: float, block_tx: int) -> float:
    """When block k's last tx is due: the moment a block cutter with
    ``MaxMessageCount = block_tx`` would cut it."""
    return t0 + (k + 1) * block_tx / rate_tx_per_s


def tx_latencies_ms(t0: float, applied, rate_tx_per_s: float,
                    block_tx: int) -> np.ndarray:
    """Per tx, from the moment it was due to arrive to the moment its
    block's state was applied.  Tx n (from 1) is due at ``t0 + n/R``
    whatever the generator or the system did since: a stalled block makes
    every tx due behind it wait, and that wait is counted.  ``applied[k]``
    is block k's apply time."""
    applied = np.asarray(applied, np.float64)
    n = np.arange(1, len(applied) * block_tx + 1, dtype=np.float64)
    due = t0 + n / rate_tx_per_s
    return (np.repeat(applied, block_tx) - due) * 1000.0


# -- closed loop -------------------------------------------------------------


def backlog_window(t_open: float, seconds: float, applied) -> tuple:
    """The measured window of a backlog run → (t_close, n_blocks).  It
    opens at ``t_open``, the apply of the last ramp block, and closes at
    the last apply inside ``seconds``; ``applied`` holds the apply times
    of the blocks after the ramp, in order.  Both ends sit on an apply,
    so the count of blocks between them is not rounded to a whole block
    the way a fixed window would round it."""
    inside = [t for t in applied if t_open < t <= t_open + seconds]
    if not inside:
        raise ValueError(f"no block was applied inside {seconds} s")
    return inside[-1], len(inside)


def tx_per_s(t_open: float, t_close: float, n_blocks: int,
             block_tx: int) -> float:
    return n_blocks * block_tx / (t_close - t_open)
