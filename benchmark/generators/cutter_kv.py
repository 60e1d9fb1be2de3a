"""``pooled_kv``'s txs in the blocks an orderer's block cutter cuts: a
block holds ``min(max_message_count, arrivals inside one batch_timeout)``
txs, so its size follows the clients' arrival rate.

The planner is ``pooled_kv``'s (imported, not copied): pools, reads,
writes, ``invalid_share`` and ``invalid_kinds`` mean what they mean
there, with the share taken of each block's own size.  What this file
adds is the size of block b, from the traffic file:

``block_sizes``     ``{"cycle", "rate_lo_tx_per_s", "rate_hi_tx_per_s"}``:
                    a cycle of ``cycle`` sizes, the blocks a
                    ``batch_timeout_s`` timer and a ``max_message_count``
                    cap (the configuration's) cut at ``cycle`` arrival
                    rates spaced evenly in the logarithm between the two:

                        n_k = min(cap, round(batch_timeout_s · lo ·
                                             (hi/lo)^((k + 0.5)/cycle)))

                    After the warm-up every run of ``cycle`` consecutive
                    blocks is a permutation of the cycle drawn from
                    (seed, cycle number): any two windows hold the same
                    mix of sizes to within one cycle.
``warmup_sizes``    the sizes of the stream's first ``warmup_blocks``
                    blocks: one for each pair of programs (verify lanes,
                    stage-2 shapes) the cycle's sizes reach, largest
                    first, so that the warm-up compiles every program
                    and the window lowers none.

``cap`` is the smaller of the configuration's ``max_message_count`` and
``block_tx`` (the same number in the file; a test's copy shrinks
``block_tx`` alone).  The byte limits never cut: at 3.6 KB a tx, 500
messages are 1.8 MB, under ``preferred_max_bytes``.  Block b is still a
function of (configuration, traffic, seed, b).  No ``jax``.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark import manifest

_SIZE_TAG = 0xB10C
#: by path, from the checkout this file lies in: ``generators`` is a
#: directory of files found by name, not a package
pooled_kv = manifest.load_module(
    "generators", "pooled_kv", os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def largest_block(config: dict) -> int:
    return min(int(config["max_message_count"]), int(config["block_tx"]))


def cycle_sizes(config: dict, traffic: dict) -> list:
    """The cycle's sizes in the order of their arrival rates."""
    rule = traffic["block_sizes"]
    n, lo = int(rule["cycle"]), float(rule["rate_lo_tx_per_s"])
    ratio = float(rule["rate_hi_tx_per_s"]) / lo
    cap, timeout = largest_block(config), float(config["batch_timeout_s"])
    return [max(1, min(cap, round(timeout * lo * ratio ** ((k + 0.5) / n))))
            for k in range(n)]


class Planner(pooled_kv.Planner):
    """``pooled_kv.Planner`` whose block b has ``size(b)`` txs."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        super().__init__(config, traffic, seed, reference)
        if any(not p.repeats for p in self.pools.values()):
            raise ValueError("a without_replacement pool counts its draws "
                             "in blocks of one size")
        self.cycle = cycle_sizes(config, traffic)
        cap = largest_block(config)
        self.warm = [min(cap, int(s)) for s in traffic["warmup_sizes"]]
        if len(self.warm) != int(traffic["warmup_blocks"]):
            raise ValueError("warmup_sizes must give warmup_blocks sizes")
        self.share = float(traffic.get("invalid_share", 0))

    def size(self, b: int) -> int:
        k = b - self.first_block
        if k < len(self.warm):
            return self.warm[k]
        turn, at = divmod(k - len(self.warm), len(self.cycle))
        order = np.random.default_rng(
            [self.seed, _SIZE_TAG, turn]).permutation(len(self.cycle))
        return self.cycle[int(order[at])]

    def _plan(self, b: int) -> list:
        self.T = self.size(b)
        self.n_bad = round(self.T * self.share)
        return super()._plan(b)


def planner(config: dict, traffic: dict, seed: int, reference) -> Planner:
    return Planner(config, traffic, seed, reference)
