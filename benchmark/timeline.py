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


def paced_blocks(seconds: float, rate_tx_per_s: float, block_txs) -> int:
    """How many of the stream's blocks fall due inside a window of
    ``seconds``: block k (from 0) is released when its last tx is due,
    ``sum(block_txs[:k+1])/R`` after the window opens.  ``block_txs``
    gives the blocks' tx counts in order (any iterable: an endless one
    of the nominal size says how many blocks to ask a generator for)."""
    n = total = 0
    for txs in block_txs:
        total += txs
        if total > seconds * rate_tx_per_s + 1e-9 * txs:
            break
        n += 1
    return n


def block_due(t0: float, k: int, rate_tx_per_s: float, block_txs) -> float:
    """When block k's last tx is due: the moment a block cutter that
    cuts on the message count alone would cut it."""
    return t0 + sum(block_txs[:k + 1]) / rate_tx_per_s


def tx_latencies_ms(t0: float, applied, rate_tx_per_s: float,
                    block_txs) -> np.ndarray:
    """Per tx, from the moment it was due to arrive to the moment its
    block's state was applied.  Tx n (from 1) is due at ``t0 + n/R``
    whatever the generator or the system did since: a stalled block makes
    every tx due behind it wait, and that wait is counted.  ``applied[k]``
    is block k's apply time and ``block_txs[k]`` its tx count."""
    applied = np.asarray(applied, np.float64)
    counts = np.asarray(block_txs[:len(applied)], np.int64)
    n = np.arange(1, int(counts.sum()) + 1, dtype=np.float64)
    due = t0 + n / rate_tx_per_s
    return (np.repeat(applied, counts) - due) * 1000.0


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


def tx_per_s(t_open: float, t_close: float, block_txs) -> float:
    """``block_txs``: the tx counts of the blocks applied in the window."""
    return sum(block_txs) / (t_close - t_open)
