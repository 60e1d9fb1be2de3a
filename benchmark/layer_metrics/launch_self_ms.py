"""The ``launch`` span minus its child spans, median per block: what
the caller thread does between the prefetch and the stage-2 dispatch
that no span labels (the duplicate-txid lookups run there)."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("commit_pipeline", "ms", "program_span",
                              "commit_tx_per_s")


def read(obs):
    return spans.median_ms(spans.self_ms_per_block(obs.roots, "launch"))
