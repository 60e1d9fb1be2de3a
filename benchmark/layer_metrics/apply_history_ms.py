"""The applier's ``apply.history`` span, median per block: the history
DB's commit, after the state write on the applier thread.  None where
the program records no such span."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("ledger", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    per_block = spans.ms_per_block(obs.roots, ["apply.history"])
    return spans.median_ms(per_block) if any(per_block) else None
