"""How much ``commit.index`` grows along the window's chain: the mean of
the span over the last fifth of the window's blocks over that of the
first fifth.  1 is a flat index; what a page cache sized to the index,
``WITHOUT ROWID`` or one index transaction per group of blocks must
bring down.  Means and not medians: on a long chain the span is
two-valued (every second block pays a WAL checkpoint), and the median of
a fifth then reads whichever value has one block more.  None where the
program records no such span or the window holds fewer than five
blocks."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "x", "program_span",
                              "commit_tx_per_s")


def read(obs):
    roots = sorted(obs.roots, key=spans.block_of)
    per_block = spans.ms_per_block(roots, ["commit.index"])
    fifth = len(per_block) // 5
    if not fifth or not any(per_block):
        return None
    first = sum(per_block[:fifth])
    return sum(per_block[-fifth:]) / first if first > 0 else None
