"""Reductions over the program's block trees (``observe/tracer.py``
``Span``: ``name``, ``t0``, ``t1``, ``thread``, ``children``,
``attrs["block"]`` on a root), read through ``Tracer.add_listener`` so
that no ring size limits the window.  The arithmetic is the benchmark's
own: interval unions, a span's self time, the coverage of ``device_wait``
by neighbouring blocks' host work (a copy of ``observe/overlap.py``
``coverage_from_spans``, which the program may change; unlike it, the
launch ledger's ``dev:*`` estimates are not counted as host work).
"""

from __future__ import annotations

from benchmark.timeline import median

#: span names that are not host work (``observe/overlap.py`` NON_HOST):
#: containers and waits, ``feed_wait`` (the caller waiting for the next
#: block of the stream) among them
NON_HOST = {"block", "finish", "device_wait", "commit_wait",
            "prefetch_wait", "queue_wait", "feed_wait"}


def walk(root):
    """Every finished span of a tree, the root first."""
    stack = [root]
    while stack:
        sp = stack.pop()
        if sp.t1 is not None:
            yield sp
        stack.extend(sp.children)


def block_of(root) -> int:
    return int(root.attrs["block"])


def ms_per_block(roots, names) -> list:
    """Per block tree, the summed milliseconds of the spans called one
    of ``names``."""
    names = set(names)
    return [sum((sp.t1 - sp.t0) * 1000.0 for sp in walk(r)
                if sp.name in names) for r in roots]


def self_ms_per_block(roots, name: str) -> list:
    """Per block tree, the milliseconds of the spans called ``name`` that
    none of their child spans covers."""
    out = []
    for r in roots:
        total = 0.0
        for sp in walk(r):
            if sp.name == name:
                kids = union([(c.t0, c.t1) for c in sp.children
                              if c.t1 is not None and c.thread == sp.thread])
                total += (sp.t1 - sp.t0) - overlap_len(kids,
                                                       [(sp.t0, sp.t1)])
        out.append(total * 1000.0)
    return out


def attr_per_block(roots, name: str, attr: str) -> dict:
    """block → the sum of ``attrs[attr]`` over its spans called ``name``,
    for the blocks that have such a span with such an attribute."""
    out = {}
    for r in roots:
        got = [sp.attrs[attr] for sp in walk(r)
               if sp.name == name and attr in sp.attrs]
        if got:
            out[block_of(r)] = sum(got)
    return out


def median_ms(per_block):
    return median(per_block) if per_block else None


def union(ivals) -> list:
    """Sorted disjoint union of [t0, t1) intervals."""
    out: list = []
    for t0, t1 in sorted(i for i in ivals if i[1] > i[0]):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def overlap_len(a, b) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def overlap_coverage(roots, window: int = 1):
    """Mean over blocks of the share of ``device_wait(k)`` that host work
    of blocks within ``window`` of k covers; None when no block has a
    ``device_wait`` and a neighbour.  ``window`` is depth − 1."""
    dev, host = {}, {}
    for r in roots:
        k = block_of(r)
        for sp in walk(r):
            if sp.name == "device_wait":
                dev.setdefault(k, []).append((sp.t0, sp.t1))
            elif sp.name not in NON_HOST and not _on_device(sp):
                host.setdefault(k, []).append((sp.t0, sp.t1))
    known = set(dev) | set(host)
    shares = []
    for k, ivals in dev.items():
        near = [j for j in known if j != k and abs(j - k) <= window]
        dk = union(ivals)
        total = sum(t1 - t0 for t0, t1 in dk)
        if not near or total <= 0:
            continue
        cover = union([iv for j in near for iv in host.get(j, ())])
        shares.append(overlap_len(dk, cover) / total)
    return sum(shares) / len(shares) if shares else None


def self_intervals(roots) -> dict:
    """``"<thread>:<span>"`` → the disjoint intervals in which that span
    ran on that thread and none of its children did, over all trees (the
    root container left out): what the host was doing, instant by
    instant, for attributing device-idle gaps."""
    out: dict = {}
    for r in roots:
        for sp in walk(r):
            if sp is r or _on_device(sp):
                continue
            kids = union([(c.t0, c.t1) for c in sp.children
                          if c.t1 is not None and c.thread == sp.thread])
            own, at = [], sp.t0
            for k0, k1 in kids:
                if k0 > at:
                    own.append((at, min(k0, sp.t1)))
                at = max(at, k1)
            if at < sp.t1:
                own.append((at, sp.t1))
            out.setdefault(f"{_role(sp.thread)}:{sp.name}", []).extend(own)
    return {k: union(v) for k, v in out.items()}


def _on_device(sp) -> bool:
    """The launch ledger files its ``dev:*`` estimates under the span
    that dispatched, on a made-up ``device:<lane>`` thread: not host work,
    and no child of the host span it hangs under."""
    return sp.thread.startswith("device:")


def _role(thread: str) -> str:
    """A thread's role without its pool's running number."""
    for role in ("prefetch", "committer", "applier"):
        if role in thread:
            return role
    return "caller"
