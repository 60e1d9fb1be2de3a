"""The state a run starts from: ``preload_keys`` keys of ``value_bytes``
seeded bytes at ``preload_version``, loaded through the ledger's own
state DB (whatever ``KVLedger`` opens by default) before any block.
This is the preload of a configuration that names none; one that gives
``"preload": "<name>"`` brings ``benchmark/preloads/<name>.py`` with an
``ensure`` of the same shape (:func:`ensure_named` finds it).

Loading a million keys takes 8 to 13 s, so a ledger directory is built
once per (configuration, seed) under ``benchmark/.cache/<configuration>/``
and copied into each run's fresh directory; the newest few of that
configuration are kept.  Runs in a child process while the parent imports
``jax``.  No ``jax`` here.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmark.reference import key_name

KEEP = 3
_CHUNK = 100_000


def _load(ledger_dir: str, config: dict, seed: int) -> None:
    from fabric_tpu.ledger.kvledger import KVLedger
    from fabric_tpu.ledger.statedb import UpdateBatch

    rng = np.random.default_rng([int(seed), 0x57A7E])
    n_keys, vb = int(config["preload_keys"]), int(config["value_bytes"])
    cc, version = config["chaincode"], tuple(config["preload_version"])
    lg = KVLedger(ledger_dir, enable_history=bool(config["history_db"]))
    try:
        for lo in range(0, n_keys, _CHUNK):
            n = min(_CHUNK, n_keys - lo)
            buf = rng.bytes(n * vb)
            batch = UpdateBatch()
            for j in range(n):
                batch.put(cc, key_name(lo + j), buf[j * vb:(j + 1) * vb],
                          version)
            # no savepoint: nothing was committed yet
            lg.state.apply_updates(batch, None)
    finally:
        lg.close()


def cached(cache_dir: str, family: str, tag: str, build,
           keep: int = KEEP) -> tuple:
    """→ (``cache_dir/family/tag``, the seconds ``build(dirpath)`` took
    to make it: 0 where the cache held it).  ``family`` is the
    configuration's name: the newest ``keep`` directories of that
    configuration stay, and no other configuration's are touched."""
    t0 = time.perf_counter()
    cache_dir = os.path.join(cache_dir, family)
    want = os.path.join(cache_dir, tag)
    if not os.path.isdir(want):
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{want}.{os.getpid()}.tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        build(tmp)
        os.replace(tmp, want)
        built = time.perf_counter() - t0
    else:
        built = 0.0
    os.utime(want)
    kept = sorted((e for e in os.scandir(cache_dir) if e.is_dir()),
                  key=lambda e: e.stat().st_mtime, reverse=True)
    for e in kept[keep:]:
        shutil.rmtree(e.path, ignore_errors=True)
    return want, built


def ensure(cache_dir: str, config: dict, seed: int) -> tuple:
    """→ (the preloaded ledger directory for (configuration, seed), the
    seconds it took to build: 0 where the cache held it, what the stream
    continues from: the ``prev_hash`` its first block extends)."""
    tag = (f"s{int(seed)}-{int(config['preload_keys'])}"
           f"x{int(config['value_bytes'])}")
    want, built = cached(cache_dir, config["name"], tag,
                         lambda tmp: _load(tmp, config, seed))
    return want, built, {"prev_hash": b""}


def ensure_named(root: str, cache_dir: str, config: dict, seed: int) -> tuple:
    """The ``ensure`` of the configuration's own preload, found by name
    under ``root``: what a child process is handed, since a module
    loaded by path cannot be."""
    from benchmark import manifest

    return manifest.preload_of(config, root).ensure(cache_dir, config, seed)
