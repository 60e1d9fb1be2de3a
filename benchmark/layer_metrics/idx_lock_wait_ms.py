"""What waited for the block store's index lock, median per block: the
``idx_wait_ms`` the program counts on ``dup_txid`` (readers, while the
caller's lookups ran) and on ``commit.index`` (the committer's insert),
summed.  A contended acquire is all the program times, so an
uncontended block reads exactly 0."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_counter",
                              "commit_tx_per_s")
CARRIERS = ("dup_txid", "commit.index")


def read(obs):
    per_block, seen = [], False
    for r in obs.roots:
        waits = [sp.attrs["idx_wait_ms"] for sp in spans.walk(r)
                 if sp.name in CARRIERS and "idx_wait_ms" in sp.attrs]
        seen = seen or bool(waits)
        per_block.append(sum(waits))
    return spans.median_ms(per_block) if seen else None
