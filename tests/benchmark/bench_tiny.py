"""A temporary copy of the benchmark's files cut to a size the CPU
runs in seconds: 20-tx blocks (60 signatures, the 64-lane bucket the
suite's compile cache already holds), a few thousand keys."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_TX = 20


def _rewrite(path, change):
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def shrink_config(c):
    c["block_tx"] = TINY_TX
    c["preload_keys"] = min(c["preload_keys"], 2000)


def shrink_traffic(t):
    for pool in t["pools"].values():
        if pool["draw"] == "uniform":
            pool["count"] = 16
        elif pool["draw"] == "zipf":
            pool["count"] = 200
        else:
            pool["first"] = 16
    if "rate_tx_per_s" in t:
        t["rate_tx_per_s"] = 40
    t["stream_hint_blocks_per_s"] = 2


def make_root(dst) -> str:
    """``BENCHMARK.json`` and ``benchmark/`` copied under ``dst``, every
    configuration and traffic mix shrunk in the copy."""
    dst = str(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
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
    """→ (configuration, traffic) of a cell of the repository's manifest,
    shrunk."""
    from benchmark import manifest

    _w, config, traffic = manifest.cell(manifest.load(), name)
    shrink_config(config)
    shrink_traffic(traffic)
    return config, traffic
