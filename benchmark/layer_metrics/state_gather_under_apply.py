"""The share of the window's ``sf.gather`` time during which an
``apply.write`` of any block was running on the applier thread: how
much of the caller's state query shares the state DB's one connection
with a write.  0 where no gather met a write; None where the program
records no ``sf.gather``."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.host_lane", "%", "program_span",
                              "commit_tx_per_s")


def intervals(roots, name):
    return spans.union([(sp.t0, sp.t1) for r in roots
                        for sp in spans.walk(r) if sp.name == name])


def read(obs):
    gather = intervals(obs.roots, "sf.gather")
    total = sum(t1 - t0 for t0, t1 in gather)
    if total <= 0:
        return None
    under = spans.overlap_len(gather, intervals(obs.roots, "apply.write"))
    return under / total * 100.0
