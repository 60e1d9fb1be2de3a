"""The general block-stream generator: every tx reads and writes keys
drawn from named pools, and a seeded share of txs is made invalid.

A traffic file (``benchmark/traffic/<mix>.json``) gives the parameters:

``pools``           name → ``{"draw", "first", "count", ...}`` over the
                    indices of the configuration's preloaded keys
                    (``count`` left out: up to ``preload_keys``).  Draws:
                    ``uniform``; ``without_replacement`` (each index at
                    most once in the whole stream, so such a key is read
                    cold and never again); ``zipf`` (bounded, ``theta``,
                    rank r with weight r^-theta, ranks ``scrambled`` over
                    the pool by a seeded permutation as YCSB does).
``reads``           one pool name per read of a tx.
``writes``          per write of a tx: a pool name, ``fresh`` (a key no
                    tx named before) or ``read:<j>`` (rewrite the key of
                    read j).  Values are ``value_bytes`` seeded bytes.
``invalid_share``   share of each block's txs made invalid, at seeded
                    positions, by ``invalid_kinds`` in turn:
                    ``bad_creator_signature``, ``bad_endorsement_signature``
                    (one of the two endorsements), ``stale_read`` (the
                    first read claims a version that never existed),
                    ``duplicate_txid`` (the tx carries the txid of an
                    ``ok`` tx 1 to ``replay_back_blocks`` blocks before
                    its own, block and position seeded, no two of a
                    block the same; writes and signatures are its own
                    and good.  The stream's first block has nothing to
                    replay: its share stays ``ok``).
``replay_back_blocks``  how far back a ``duplicate_txid`` tx finds its
                    original; a mix with that kind must give it.  The
                    three states a peer at its defaults can hold the
                    original in: at pipeline depth 2 a distance of 1 is
                    still in flight, the apply queue's four blocks behind
                    it are committed and may not be applied, the rest are
                    applied.

Every read carries the version the plain reference's state holds at the
end of the previous block, so a tx is simulated as a real endorser would
have simulated it just before the block was cut, and what contention
makes invalid is in-block.  Block ``b`` is a function of (configuration,
traffic, seed, b) alone: any worker can make any block.  Blocks are
numbered from the configuration's ``first_block`` (0 where it gives
none), and fresh keys and versions carry the real number.  The plain
reference is handed in (``manifest.reference_of``).  No ``jax``.
"""

from __future__ import annotations

import numpy as np

_PERM_TAG, _SCRAMBLE_TAG, _REPLAY_TAG = 0xC01D, 0x5C4A, 0xD0B1


class _Pool:
    def __init__(self, spec: dict, preload_keys: int, seed: int, tag: int):
        self.draw = spec["draw"]
        self.first = int(spec.get("first", 0))
        self.count = int(spec.get("count", preload_keys - self.first))
        if self.first + self.count > preload_keys or self.count <= 0:
            raise ValueError(f"pool {spec} does not fit {preload_keys} "
                             "preloaded keys")
        if self.draw == "without_replacement":
            self.perm = np.random.default_rng(
                [seed, _PERM_TAG, tag]).permutation(self.count)
        elif self.draw == "zipf":
            w = np.arange(1, self.count + 1, dtype=np.float64) ** -float(
                spec["theta"])
            self.cdf = np.cumsum(w / w.sum())
            self.scramble = (np.random.default_rng(
                [seed, _SCRAMBLE_TAG, tag]).permutation(self.count)
                if spec.get("scrambled") else None)
        elif self.draw != "uniform":
            raise ValueError(f"pool draw {self.draw!r}: not one of uniform, "
                             "without_replacement, zipf")

    @property
    def repeats(self) -> bool:
        return self.draw != "without_replacement"

    def indices(self, rng, n: int, taken: int) -> np.ndarray:
        """``n`` key indices; ``taken`` is how many this pool handed out
        before (what ``without_replacement`` continues from)."""
        if self.draw == "uniform":
            return self.first + rng.integers(0, self.count, n)
        if self.draw == "zipf":
            rank = np.minimum(np.searchsorted(self.cdf, rng.random(n)),
                              self.count - 1)
            if self.scramble is not None:
                rank = self.scramble[rank]
            return self.first + rank
        if taken + n > self.count:
            raise ValueError(
                f"the stream needs more than the {self.count} keys its "
                "without_replacement pool holds")
        return self.first + self.perm[taken:taken + n]


class Planner:
    """``rows(b)`` → block b's plan: per tx ``(kind, reads, writes)`` as
    :func:`benchmark.reference.plan_codes` takes them."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference):
        self.seed, self.ref = int(seed), reference
        self.first_block = int(config.get("first_block", 0))
        self.T = int(config["block_tx"])
        self.value_bytes = int(config["value_bytes"])
        self.reads = list(traffic["reads"])
        self.writes = list(traffic["writes"])
        self.kinds = list(traffic.get("invalid_kinds", []))
        self.n_bad = round(self.T * float(traffic.get("invalid_share", 0)))
        self.replay_back = int(traffic.get("replay_back_blocks", 0))
        if self.n_bad and not self.kinds:
            raise ValueError("invalid_share without invalid_kinds")
        for k in self.kinds:
            if k not in reference.KINDS or k == "ok":
                raise ValueError(f"invalid kind {k!r}")
        if "stale_read" in self.kinds and not self.reads:
            raise ValueError("stale_read needs a tx that reads")
        if "duplicate_txid" in self.kinds and self.replay_back < 1:
            raise ValueError("duplicate_txid needs replay_back_blocks of 1 "
                             "or more")
        self.pools = {
            name: _Pool(spec, int(config["preload_keys"]), self.seed, tag)
            for tag, (name, spec) in enumerate(sorted(
                traffic.get("pools", {}).items()))
        }
        for w in self.writes:
            if w.startswith("read:"):
                if int(w[5:]) >= len(self.reads):
                    raise ValueError(f"write {w!r}: the tx has no such read")
            elif w != "fresh" and w not in self.pools:
                raise ValueError(f"write {w!r}: no such pool")
        # how many draws of each pool a tx makes, for without_replacement
        self.per_tx = {name: self.reads.count(name) + self.writes.count(name)
                       for name in self.pools}
        self.state = reference.RefState(config)
        # indices a later tx can draw again: only their versions are kept
        self._repeat = [(p.first, p.first + p.count)
                        for p in self.pools.values() if p.repeats]
        self._next = self.first_block
        # block → positions of its ``ok`` txs, for the last
        # ``replay_back`` blocks; block → what its ``duplicate_txid`` txs
        # replay
        self._ok: dict = {}
        self._replays: dict = {}

    def _plan(self, b: int) -> list:
        rng = np.random.default_rng([self.seed, b])
        T, state = self.T, self.state
        key_name, slot_of = self.ref.key_name, dict.fromkeys(self.pools, 0)

        def keys_from(name: str) -> list:
            pool, slot = self.pools[name], slot_of[name]
            slot_of[name] += 1
            taken = ((b - self.first_block) * self.per_tx[name] + slot) * T
            return [key_name(int(j)) for j in pool.indices(rng, T, taken)]

        read_keys = [keys_from(name) for name in self.reads]
        write_keys = []
        for slot, w in enumerate(self.writes):
            if w == "fresh":
                write_keys.append([f"w{b}_{i:05d}_{slot}" for i in range(T)])
            elif w.startswith("read:"):
                write_keys.append(read_keys[int(w[5:])])
            else:
                write_keys.append(keys_from(w))
        kinds = ["ok"] * T
        if self.n_bad:
            for n, i in enumerate(
                    rng.choice(T, self.n_bad, replace=False).tolist()):
                kinds[i] = self.kinds[n % len(self.kinds)]
        self._replays = {b: self._pick_replays(b, kinds)}
        self._ok[b] = [i for i, k in enumerate(kinds) if k == "ok"]
        self._ok.pop(b - self.replay_back, None)
        vb = self.value_bytes
        blob = rng.bytes(T * len(self.writes) * vb)
        rows, at = [], 0
        for i in range(T):
            # a dict, as a chaincode's rwset is: a key drawn twice is
            # read (or written) once
            reads = {col[i]: state.version(col[i]) for col in read_keys}
            if kinds[i] == "stale_read":
                reads[read_keys[0][i]] = self.ref.STALE_VERSION
            writes = {}
            for col in write_keys:
                writes[col[i]] = blob[at:at + vb]
                at += vb
            rows.append((kinds[i], tuple(reads.items()),
                         tuple(writes.items())))
        return rows

    def _pick_replays(self, b: int, kinds: list) -> dict:
        """position → (block, position) of the ``ok`` tx it replays, for
        each ``duplicate_txid`` tx of ``kinds``; in the stream's first
        block, where there is none, such a tx is made ``ok``."""
        at = [i for i, k in enumerate(kinds) if k == "duplicate_txid"]
        if not at:
            return {}
        if not self._ok:
            for i in at:
                kinds[i] = "ok"
            return {}
        rng = np.random.default_rng([self.seed, b, _REPLAY_TAG])
        back = min(self.replay_back, len(self._ok))
        out, taken = {}, set()
        for i in at:
            while True:
                src = b - int(rng.integers(1, back + 1))
                pos = self._ok[src][int(rng.integers(len(self._ok[src])))]
                if (src, pos) not in taken:
                    break
            taken.add((src, pos))
            out[i] = (src, pos)
        return out

    def replays(self, b: int) -> dict:
        """What block b's ``duplicate_txid`` txs replay; b is the block
        :meth:`rows` planned last."""
        return self._replays[b]

    def _repeatable(self, key: str) -> bool:
        if not self.state.preloaded(key):
            return False
        j = int(key[1:])
        return any(lo <= j < hi for lo, hi in self._repeat)

    def rows(self, b: int) -> list:
        """Blocks are planned in order, because reads carry the versions
        the reference left; asking for a later block plans the ones
        between (planning is cheap, signing is not)."""
        if b < self._next:
            raise ValueError(f"block {b} asked for after block "
                             f"{self._next - 1}")
        while True:
            rows = self._plan(self._next)
            _codes, done = self.ref.plan_codes(rows, self._next, self.state)
            self.state.commit({k: v for k, v in done.items()
                               if self._repeatable(k)})
            self._next += 1
            if self._next > b:
                return rows


def planner(config: dict, traffic: dict, seed: int, reference) -> Planner:
    return Planner(config, traffic, seed, reference)
