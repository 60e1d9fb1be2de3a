"""A temporary copy of the benchmark's files cut to a size the CPU
runs in seconds: 20-tx blocks (60 signatures, the 64-lane bucket the
suite's compile cache already holds), a few thousand keys, a snapshot
taken at height 30 (600 txids)."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_TX = 20
TINY_HEIGHT = 30
#: blocks a second of window a tiny backlog stream is signed for: twice
#: what an idle CPU commits of 20-tx blocks
TINY_HINT = 16


def _rewrite(path, change):
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def shrink_config(c):
    c["block_tx"] = TINY_TX
    if "preload_keys" in c:
        c["preload_keys"] = min(c["preload_keys"], 2000)
    if c.get("preload") == "snapshot_join":
        c["first_block"] = TINY_HEIGHT


def shrink_traffic(t):
    for pool in t.get("pools", {}).values():
        if pool["draw"] == "uniform":
            pool["count"] = 16
        elif pool["draw"] == "zipf":
            pool["count"] = 200
        else:
            pool["first"] = 16
    if "rate_tx_per_s" in t:
        t["rate_tx_per_s"] = 40
    # the hint leads, as in a full-size cell: the stream is signed during
    # the warm-up, and a 2 s window holds what the pipeline and the apply
    # queue take ahead of the applies besides what it commits, a dozen
    # blocks on an idle machine
    t["stream_hint_blocks_per_s"] = TINY_HINT
    if "duplicate_txid" in t.get("invalid_kinds", ()):
        # two of a tiny block's txs are invalid: six, so that each of
        # the three kinds comes twice
        t["invalid_share"] = 0.3


def listed() -> dict:
    """The repository's manifest with the cells that were measured and
    not listed (``benchmark/cells/<cell>.json`` holds the entries such a
    cell needs) added as those files say: what ``BENCHMARK.json`` becomes
    when a later PR lists them."""
    from benchmark import manifest

    man = manifest.load()
    cells = os.path.join(REPO, "benchmark", "cells")
    for name in sorted(os.listdir(cells)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(cells, name)) as f:
            add = json.load(f)
        man["configs"].append(add["config"])
        man["workloads"].append(add["workload"])
        man["per_layer"] += add["per_layer"]
        for m in man["end_to_end"] + man["per_layer"]:
            if m["name"] in add["joins"]:
                m["workloads"].append(add["workload"]["name"])
    return man


def make_root(dst) -> str:
    """``BENCHMARK.json`` (with the cells of :func:`listed`) and
    ``benchmark/`` copied under ``dst``, every configuration and traffic
    mix shrunk in the copy."""
    dst = str(dst)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(listed(), f, indent=1)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(dst, "benchmark"),
        ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__"))
    for sub, change in (("configs", shrink_config),
                        ("traffic", shrink_traffic)):
        d = os.path.join(dst, "benchmark", sub)
        for name in os.listdir(d):
            _rewrite(os.path.join(d, name), change)
    return dst


def tiny_cell(name):
    """→ (configuration, traffic) of a cell of the repository's manifest
    (or of :func:`listed`), shrunk."""
    from benchmark import manifest

    _w, config, traffic = manifest.cell(listed(), name)
    shrink_config(config)
    shrink_traffic(traffic)
    return config, traffic
