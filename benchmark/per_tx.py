"""Per-tx readings for a stream whose blocks differ in size: a median
per block describes one block of median size, so the cost of a thousand
txs is summed over the window instead.  Needs the ``txs`` the program
writes on a block's root span (``peer/pipeline.py``, PR 28); on a program
without it everything here reads None."""

from __future__ import annotations

from benchmark import spans


def root_txs(roots) -> dict:
    """block → the ``txs`` its root span carries, for the roots that
    carry one."""
    return {spans.block_of(r): int(r.attrs["txs"]) for r in roots
            if "txs" in r.attrs}


def ms_per_ktx(roots, names):
    """Milliseconds the spans called one of ``names`` took per thousand
    txs, summed over the blocks whose root says its ``txs``; None where
    no root does or no such span was recorded."""
    said = [r for r in roots if "txs" in r.attrs]
    txs = sum(int(r.attrs["txs"]) for r in said)
    total = sum(spans.ms_per_block(said, names))
    return total / txs * 1000.0 if txs and total else None
