#!/usr/bin/env python3
"""Text waterfall for block-commit traces — Perfetto for containers
with no browser.

Input (auto-detected):
  * Chrome trace-event JSON written by ``Tracer.export_chrome``
    ({"traceEvents": [...]}), or
  * a ``/trace`` endpoint dump (``curl :PORT/trace > dump.json`` —
    either the index payload or a single ``?block=N`` tree).

Usage:
  python scripts/traceview.py trace.json [--block N] [--width 48]

Per block, prints one line per span: an ASCII bar positioned on the
block's [0, total] time axis, the span name (indented by tree depth
where the dump carries the tree), start/duration in ms, and the
thread/worker that ran it — the overlap question ("did prefetch(k+1)
run while commit(k) fsynced?") is answered by bars on different
thread rows sharing a time range across consecutive blocks.

The launch ledger's device-lane child spans (observe/ledger.py) ride
``device:<lane>`` rows with distinct bar glyphs — ``%`` for
``dev:compile``, ``~`` for ``dev:queue``, ``=`` for ``dev:execute`` —
so a cold-compile stall is visually distinct from kernel execute.

Merged MULTI-PROCESS dumps (a peer tree with the sidecar's stitched
request subtree, or a Chrome export with several process_name rows)
render with per-process labels — ``[sidecar:fabtpu-sidecar-dev_0]``
vs ``[MainThread]`` — and a ``~ clock offset`` annotation under each
stitched subtree stating the estimated remote-clock offset and the
round-trip bound on its error, so a browserless host can read the
cross-process waterfall AND how far to trust its alignment.
"""

from __future__ import annotations

import argparse
import json
import sys


#: bar glyphs for the launch ledger's device-lane spans: a compile
#: stall must read differently from queue wait and execute at a glance
_DEV_BARS = {"dev:compile": "%", "dev:queue": "~", "dev:execute": "="}


def _bar(start: float, dur: float, total: float, width: int,
         char: str = "#") -> str:
    """[start, start+dur) rendered on a width-char axis of [0, total)."""
    if total <= 0:
        return " " * width
    lo = int(start / total * width)
    hi = int((start + dur) / total * width)
    lo = max(0, min(lo, width - 1))
    hi = max(lo + 1, min(hi, width))
    return " " * lo + char * (hi - lo) + " " * (width - hi)


def _line(depth: int, name: str, start: float, dur: float, total: float,
          thread: str, width: int) -> str:
    label = "  " * depth + name
    return "  %s %-28s %8.2f +%8.2f ms  [%s]" % (
        _bar(start, dur, total, width, _DEV_BARS.get(name, "#")),
        label[:28], start, dur, thread,
    )


# -- /trace dump form (span trees) ------------------------------------------


def render_tree(block: dict, width: int = 48) -> str:
    """One /trace block tree → waterfall text."""
    total = float(block.get("dur_ms", 0.0))
    attrs = block.get("attrs", {})
    extra = "".join(
        f" {k}={v}" for k, v in sorted(attrs.items()) if k != "block"
    )
    out = ["block %s  total %.2f ms%s" % (block.get("block"), total, extra)]

    def walk(span: dict, depth: int) -> None:
        row = span.get("thread", "?")
        if span.get("proc"):
            row = f"{span['proc']}:{row}"
        out.append(_line(depth, span.get("name", "?"),
                         float(span.get("start_ms", 0.0)),
                         float(span.get("dur_ms", 0.0)),
                         total, row, width))
        off = (span.get("attrs") or {}).get("clock_offset_ms")
        if off is not None:
            out.append("  %s ~ clock offset %.3f ms (rtt %.3f ms)" % (
                " " * width, float(off),
                float((span.get("attrs") or {}).get("rtt_ms", 0.0)),
            ))
        for ev in span.get("events", ()):
            out.append("  %s ! %s" % (
                " " * width,
                ev.get("name", "?") + " @ %.2f ms" % ev.get("at_ms", 0.0),
            ))
        for c in span.get("children", ()):
            walk(c, depth + 1)

    walk(block, 0)
    return "\n".join(out)


def render_trace_dump(data: dict, width: int = 48,
                      block: int | None = None) -> str:
    if "name" in data and "block" in data:  # a single ?block=N tree
        return render_tree(data, width)
    trees = {b.get("block"): b for b in data.get("recent_blocks", ())}
    for b in data.get("slow_blocks", ()):
        trees.setdefault(b.get("block"), b)
    if block is not None:
        if block not in trees:
            return (f"block {block} not in dump (have: "
                    f"{sorted(k for k in trees if k is not None)})")
        return render_tree(trees[block], width)
    return "\n\n".join(
        render_tree(trees[k], width) for k in sorted(trees)
    ) or "no block trees in dump"


# -- Chrome trace-event form ------------------------------------------------


def render_chrome(data: dict, width: int = 48,
                  block: int | None = None) -> str:
    events = data.get("traceEvents", data if isinstance(data, list) else [])
    # thread rows are keyed (pid, tid) — tids repeat across processes
    # in a multi-process export; process_name rows label the pids
    procs = {
        e.get("pid", 0): e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    threads = {
        (e.get("pid", 0), e["tid"]): e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    by_block: dict[int, list] = {}
    for e in events:
        if e.get("ph") not in ("X", "i"):
            continue
        b = e.get("args", {}).get("block")
        if b is None:
            continue
        by_block.setdefault(int(b), []).append(e)
    if block is not None:
        by_block = {block: by_block.get(block, [])}
    out = []
    for b in sorted(by_block):
        evs = sorted(by_block[b], key=lambda e: e["ts"])
        roots = [e for e in evs if e.get("name") == "block"]
        if not roots:
            continue
        base, total = roots[0]["ts"], roots[0].get("dur", 0.0) / 1000.0
        lines = ["block %d  total %.2f ms" % (b, total)]
        for e in evs:
            pid = e.get("pid", 0)
            thread = threads.get((pid, e.get("tid")),
                                 str(e.get("tid")))
            proc = procs.get(pid, "")
            if proc and proc != "local":
                thread = f"{proc}:{thread}"
            start = (e["ts"] - base) / 1000.0
            if e["ph"] == "i":
                lines.append("  %s ! %s @ %.2f ms" % (
                    " " * width, e.get("name", "?"), start,
                ))
                continue
            lines.append(_line(0, e.get("name", "?"), start,
                               e.get("dur", 0.0) / 1000.0, total, thread,
                               width))
            off = e.get("args", {}).get("clock_offset_ms")
            if off is not None:
                lines.append(
                    "  %s ~ clock offset %.3f ms (rtt %.3f ms)" % (
                        " " * width, float(off),
                        float(e.get("args", {}).get("rtt_ms", 0.0)),
                    )
                )
        out.append("\n".join(lines))
    return "\n\n".join(out) or "no block events in trace"


def render(data, width: int = 48, block: int | None = None) -> str:
    if isinstance(data, dict) and "traceEvents" in data:
        return render_chrome(data, width, block)
    if isinstance(data, list):
        return render_chrome({"traceEvents": data}, width, block)
    return render_trace_dump(data, width, block)


# -- pipeline overlap coverage ----------------------------------------------


def render_coverage(data, window: int = 2) -> str:
    """Per-block device_wait coverage by neighbor-block host stages
    (observe/overlap.py) — the deep-pipelining acceptance number as a
    text table, from either input form."""
    import os
    import sys as _sys

    _sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from fabric_tpu.observe import overlap

    if isinstance(data, dict) and "traceEvents" in data:
        cov = overlap.coverage_from_spans(
            overlap.spans_from_chrome(data["traceEvents"]), window=window
        )
    elif isinstance(data, list):
        cov = overlap.coverage_from_spans(
            overlap.spans_from_chrome(data), window=window
        )
    else:
        cov = overlap.coverage_from_trace_dump(data, window=window)
        if cov is None:
            return ("no t0_s anchors in dump — re-capture from a "
                    "/trace endpoint that emits them")
    lines = [
        "pipeline overlap coverage (window ±%d): mean %s  p50 %s  "
        "min %s over %d block(s)" % (
            cov["window"], cov["mean"], cov["p50"], cov["min"],
            cov["blocks_measured"],
        )
    ]
    for b in cov["per_block"]:
        lines.append(
            "  block %-6s device_wait %8.2f ms  covered %8.2f ms  "
            "(%.1f%%)" % (
                b["block"], b["device_wait_ms"], b["covered_ms"],
                b["coverage"] * 100.0,
            )
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="chrome trace JSON or /trace dump")
    ap.add_argument("--block", type=int, default=None,
                    help="render one block only")
    ap.add_argument("--width", type=int, default=48,
                    help="waterfall bar width (chars)")
    ap.add_argument("--coverage", action="store_true",
                    help="print the pipeline overlap-coverage table "
                         "(device_wait hidden by neighbor host stages) "
                         "instead of the waterfall")
    ap.add_argument("--window", type=int, default=2,
                    help="coverage neighbor window in blocks "
                         "(depth−1; default 2 = depth-3)")
    args = ap.parse_args(argv)
    with open(args.path) as f:
        data = json.load(f)
    if args.coverage:
        print(render_coverage(data, window=args.window))
    else:
        print(render(data, width=args.width, block=args.block))
    return 0


if __name__ == "__main__":
    sys.exit(main())
