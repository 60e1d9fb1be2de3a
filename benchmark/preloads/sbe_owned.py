"""The state of a channel of owned assets: ``preload_keys`` keys of
``value_bytes`` seeded bytes at ``preload_version``, each under the
key-level endorsement policy of its owner org (key index mod the orgs),
loaded through the ledger's own state DB before any block, as
``benchmark/preload.py`` loads a state without parameters.  The
parameter's bytes are the configuration's reference's
(``references/<name>.py`` ``owner_policy``).  No ``jax`` here: this runs
in a child process while the parent imports it."""

import os

import numpy as np

from benchmark import preload

_CHUNK = 100_000
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(ledger_dir, config, seed):
    from benchmark import manifest
    from fabric_tpu.ledger.kvledger import KVLedger
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, encode_metadata
    from fabric_tpu.ledger.statedb import UpdateBatch

    ref = manifest.reference_of(config, ROOT)
    orgs = int(config["orgs"])
    owned = [encode_metadata({VALIDATION_PARAMETER: ref.owner_policy(o)})
             for o in range(orgs)]
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
                batch.put(cc, ref.key_name(lo + j), buf[j * vb:(j + 1) * vb],
                          version, metadata=owned[(lo + j) % orgs])
            # no savepoint: nothing was committed yet
            lg.state.apply_updates(batch, None)
    finally:
        lg.close()


def ensure(cache_dir, config, seed):
    """→ (the preloaded ledger directory for (configuration, seed), the
    seconds it took to build: 0 where the cache held it, what the stream
    continues from), as ``benchmark/preload.py``'s."""
    tag = (f"s{int(seed)}-{int(config['preload_keys'])}"
           f"x{int(config['value_bytes'])}")
    want, built = preload.cached(cache_dir, config["name"], tag,
                                 lambda tmp: _load(tmp, config, seed))
    return want, built, {"prev_hash": b""}
