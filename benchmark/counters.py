"""The program's counters and gauges block by block, for the per-layer
readers: ``obs.counters`` maps a block number to what every counter and
gauge of ``ops_metrics.global_registry()`` held once that block's tree
was finished, ``(name, labels) → value`` (``labels``: the registry's own
key, the sorted ``(label, value)`` pairs).  Taken in traced runs only,
by a tracer listener (``Snapshots``), for the window's blocks and the
one before them, whose snapshot is where the window's change starts.
A counter the applier adds to for a block may move after that block's
tree is finished, so a change over the window can hold a block's worth
of the applier's counts from before the window and miss its last
block's."""

from __future__ import annotations


def snapshot() -> dict:
    """``(name, labels) → value`` of every counter and gauge the
    program's registry holds now (histograms are left out)."""
    from fabric_tpu.ops_metrics import Counter, Gauge, global_registry

    out = {}
    for name, m in global_registry().metrics():
        if isinstance(m, (Counter, Gauge)):
            for labels, value in m.snapshot().items():
                out[(name, labels)] = value
    return out


class Snapshots:
    """A tracer listener: block number → :func:`snapshot` when that
    block's tree is finished (``Tracer.finish_block``)."""

    def __init__(self):
        self.by_block: dict = {}

    def __call__(self, root) -> None:
        self.by_block[int(root.attrs["block"])] = snapshot()

    def window(self, first: int, last: int) -> dict:
        """What ``obs.counters`` holds: blocks ``first - 1 .. last``."""
        return {b: self.by_block[b] for b in range(first - 1, last + 1)
                if b in self.by_block}


def _total(values: dict, name: str, labels: dict):
    got = [v for (n, key), v in values.items()
           if n == name and labels.items() <= dict(key).items()]
    return sum(got) if got else None


def change(obs, name: str, **labels):
    """What counter ``name`` went up by over the window: its value once
    the window's last block was finished less its value once the block
    before the window's first was, summed over the label variants that
    carry ``labels`` (all of them where none is given).  A variant first
    seen inside the window starts from 0.  None where the program has no
    such counter, or the run took no snapshots (``--trace 0``)."""
    end = obs.counters.get(obs.last)
    start = obs.counters.get(obs.first - 1)
    if end is None or start is None:
        return None
    after = _total(end, name, labels)
    if after is None:
        return None
    return after - (_total(start, name, labels) or 0.0)
