#!/usr/bin/env python3
"""The quickest proof that the block-commit path still starts on the chip.

One process (the chip belongs to one process at a time), the default
``PeerConfig`` knobs (depth 2, no chunk/mesh/coalesce/resident; tracer,
launch ledger and tx-flow journal on; lane guard off), data made from
``--seed``:

* phase 0 — the machine: platform, device kind and count, versions,
  where the compile cache lives, the three native libraries built from
  the tree's ``.cpp`` files and loaded;
* phase 1 — the deployment BASELINE.json calls config #2 at its own
  width: 3 orgs, 2-of-3 ECDSA-P256 policy, 1000-tx blocks (3000
  signatures, the 3072-lane verify bucket), 10 % invalid (bad creator
  signatures and stale reads), through ``CommitPipeline`` →
  ``BlockValidator`` → ``KVLedger.commit_block`` on a ledger directory
  with the default sqlite state DB, async commit and history on,
  preloaded with 1,000,000 keys × 200 B.  Every tx filter must equal
  an independent serial reference (OpenSSL verify + policy walk +
  serial MVCC), every acknowledged write must read back after a flush
  and again after a reopen, and the launch ledger must show one verify
  and one stage-2 launch per block on the claimed device;
* phase 2 — a served leg: one orderer (500-message / 2 s blocks) and
  two peers over loopback sockets in this process, a few hundred
  transactions through ``GatewayClient.submit_transaction``, each VALID
  and applied, then evaluated back;
* ``--all-programs`` — every device program the repo ships that the
  default path does not reach, once, at its production shape, against
  its oracle.

Run as a script it refuses any platform but ``tpu``.  A failed check
raises; nothing catches a phase's failure and exits 0.  The signing
keys, nonces and ECDSA ``k`` values come from OS entropy (the normal
cryptogen / OpenSSL entry points take no seed); they move no verdict.
The last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import hashlib
import importlib.metadata
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

CHANNEL, CC = "smokechan", "smokecc"
#: version every preloaded key carries
PRELOAD_VERSION = (1, 0)
#: phase-1 counters that must stay 0 — each one is a path that would
#: let a block commit without the device doing the work
ZERO_COUNTERS = (
    "fallback_blocks_total", "validator_degraded",
    "mesh_shard_fallback_total", "commit_pipeline_stage_failures_total",
)


class SmokeFailure(RuntimeError):
    """A hard check did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run is cut to.  The defaults are the full width; the
    tier-1 test passes a tiny one so the script cannot rot between
    chip runs."""

    n_tx: int = 1000                 # txs per phase-1 block
    n_blocks: int = 20
    preload_keys: int = 1_000_000
    value_bytes: int = 200
    hot_keys: int = 4096             # read-only working set re-read by every block
    served_waves: tuple = (40, 100, 200)   # concurrent submits per wave
    served_deadline_s: float = 600.0
    sign_lanes: int = 1000           # --all-programs: sign comb
    sha_msgs: int = 4096             # --all-programs: sha256
    verify_chunk: int = 1024         # --all-programs: chunked verify


# -- phase 0 ----------------------------------------------------------------


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def phase0_machine(device: dict) -> dict:
    import jax
    import jaxlib

    from fabric_tpu import native
    from fabric_tpu.utils.xla_env import enable_compile_cache

    t_start = time.time()
    cache_dir = enable_compile_cache()
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None  # a CPU-only installation: informational
    say(f"[phase 0] platform={device['platform']} "
        f"device_kind={device['kind']} devices={device['count']}")
    say(f"[phase 0] jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    entries = cache_entries(cache_dir)
    say(f"[phase 0] compile cache: {cache_dir} "
        f"({'from JAX_COMPILATION_CACHE_DIR' if from_env else 'checkout default'}"
        f", {entries} entries)")
    libs = {}
    # ecprep_lib() itself fails on a library without ec_prepare_pack
    for name, loader in (("blockparse", native.blockparse_lib),
                         ("mvccprep", native.mvccprep_lib),
                         ("ecprep", native.ecprep_lib)):
        require(loader() is not None, f"native {name} did not build/load")
        so = os.path.join(os.path.dirname(native.__file__), "_build",
                          f"lib{name}.so")
        libs[name] = {"built_this_run": os.path.getmtime(so) >= t_start - 1}
    say(f"[phase 0] native libraries loaded: {libs}")
    return {"cache_dir": cache_dir, "cache_from_env": from_env,
            "cache_entries_at_start": entries, "native": libs}


# -- the workload -------------------------------------------------------------


def build_network():
    """3 orgs, one peer identity each, a client, the 2-of-3 policy."""
    from fabric_tpu.crypto import cryptogen, policy as pol
    from fabric_tpu.crypto.msp import MSPManager
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    orgs = [
        cryptogen.generate_org(f"Org{i}MSP", f"org{i}.example.com",
                               peers=1, users=1)
        for i in (1, 2, 3)
    ]
    mgr = MSPManager({o.msp().msp_id: o.msp() for o in orgs})
    endorsers = [
        cryptogen.signing_identity(o, f"peer0.org{i}.example.com")
        for i, o in zip((1, 2, 3), orgs)
    ]
    client = cryptogen.signing_identity(orgs[0], "User1@org1.example.com")
    policy = pol.from_dsl(
        "OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer')"
    )
    prov = PolicyProvider({CC: NamespaceInfo(policy=policy)})
    return mgr, prov, endorsers, client


def key_name(j: int) -> str:
    return f"k{j:07d}"


def preload_state(path: str, size: Size, rng) -> None:
    """``size.preload_keys`` keys × ``size.value_bytes`` random bytes
    into a fresh sqlite state DB, through the backend's own
    ``apply_updates`` (no savepoint: nothing was committed yet)."""
    from fabric_tpu.ledger.statedb import SqliteVersionedDB, UpdateBatch

    db = SqliteVersionedDB(path)
    db.open()
    try:
        vb, chunk = size.value_bytes, 100_000
        for lo in range(0, size.preload_keys, chunk):
            n = min(chunk, size.preload_keys - lo)
            buf = rng.bytes(n * vb)
            batch = UpdateBatch()
            for j in range(n):
                batch.put(CC, key_name(lo + j), buf[j * vb:(j + 1) * vb],
                          PRELOAD_VERSION)
            db.apply_updates(batch, None)
    finally:
        db.close()


def build_blocks(size: Size, rng, endorsers, client):
    """→ (blocks, plan): ``plan[b][i]`` is ``(kind, seed_key,
    fresh_key)`` with kind ``ok`` | ``bad_sig`` | ``stale``.  Tx i of
    block b reads one cold preloaded key (its own, never shared) and
    one key of the hot read-only set, rewrites the cold key and writes
    a fresh one, over a state the size a deployment holds."""
    from fabric_tpu import protoutil as pu
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.peer import txassembly as txa

    total = size.n_tx * size.n_blocks
    require(size.preload_keys >= size.hot_keys + total,
            "preload too small for the stream's cold reads")
    # keys [0, hot) are the hot set; cold reads are drawn without
    # replacement from the rest
    cold = size.hot_keys + rng.choice(
        size.preload_keys - size.hot_keys, total, replace=False
    )
    n_bad = size.n_tx // 10
    blocks, plan, prev = [], [], b""
    for b in range(size.n_blocks):
        bad = rng.choice(size.n_tx, n_bad, replace=False)
        kinds = ["ok"] * size.n_tx
        for slot, i in enumerate(bad.tolist()):
            kinds[i] = "bad_sig" if slot % 2 == 0 else "stale"
        hot = rng.integers(0, size.hot_keys, size.n_tx)
        blk = pu.new_block(b, prev)
        rows = []
        for i, kind in enumerate(kinds):
            seed_key = key_name(int(cold[b * size.n_tx + i]))
            fresh_key = f"w{b}_{i:05d}"
            _, _, prop = txa.create_signed_proposal(
                client, CHANNEL, CC, [b"invoke"]
            )
            tx = TxRWSet()
            ns = tx.ns_rwset(CC)
            ns.reads[seed_key] = (9, 9) if kind == "stale" else PRELOAD_VERSION
            ns.reads[key_name(int(hot[i]))] = PRELOAD_VERSION
            ns.writes[fresh_key] = b"value-%d-%d" % (b, i)
            ns.writes[seed_key] = b"updated-%d" % b
            rw = tx.to_proto().SerializeToString()
            two = (endorsers[i % 3], endorsers[(i + 1) % 3])
            resps = [txa.create_proposal_response(prop, rw, e, CC)
                     for e in two]
            env = txa.assemble_transaction(prop, resps, client)
            if kind == "bad_sig":
                env.signature = env.signature[:-4] + bytes(4)
            blk.data.data.append(env.SerializeToString())
            rows.append((kind, seed_key, fresh_key))
        blk = pu.finalize_block(blk)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)
        plan.append(rows)
    return blocks, plan


def serial_reference(blocks, mgr, prov, state):
    """The reference commit path, serially on the host and independent
    of the validator: per tx parse → creator signature (OpenSSL) →
    endorsement signatures (OpenSSL) → consumption policy walk →
    serial MVCC against ``state`` plus the writes of earlier valid txs
    (v20/validator.go:180 + validation/validator.go:81, one thread).
    → (filters, writes) with ``writes[(ns, key)] = (value, version)``
    as the chain leaves them."""
    import numpy as np

    from fabric_tpu import protoutil as pu
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.ledger.rwset import TxRWSet
    from fabric_tpu.protos import common_pb2, transaction_pb2

    C = transaction_pb2.TxValidationCode
    plans: dict = {}
    writes: dict = {}
    filters = []
    for blk in blocks:
        codes = []
        for txnum, env_bytes in enumerate(blk.data.data):
            env = pu.unmarshal(common_pb2.Envelope, env_bytes)
            _ch, sh, cap, _prp, cca = pu.extract_action(env)
            creator = mgr.deserialize_identity(sh.creator)
            if not creator.is_valid or not creator.verify(
                    env.payload, env.signature):
                codes.append(C.BAD_CREATOR_SIGNATURE)
                continue
            prp_bytes = cap.action.proposal_response_payload
            idents, valid = [], []
            for e in cap.action.endorsements:
                ident = mgr.deserialize_identity(e.endorser)
                idents.append(ident)
                valid.append(ident.is_valid and ident.verify(
                    prp_bytes + e.endorser, e.signature))
            rwset = TxRWSet.from_bytes(cca.results)
            endorsed = True
            for ns_name in rwset.ns:
                info = prov.info(ns_name)
                plan = plans.get(ns_name)
                if plan is None:
                    plan = plans[ns_name] = pol.compile_plan(info.policy)
                m = pol.match_matrix(idents, plan.principals)
                m = m & np.asarray(valid, bool)[:, None]
                endorsed = endorsed and pol.evaluate(info.policy, m)
            if not endorsed:
                codes.append(C.ENDORSEMENT_POLICY_FAILURE)
                continue
            conflict = False
            for ns_name, n in rwset.ns.items():
                for k, ver in n.reads.items():
                    done = writes.get((ns_name, k))
                    have = (done[1] if done is not None
                            else state.get_version(ns_name, k))
                    conflict = conflict or have != ver
            if conflict:
                codes.append(C.MVCC_READ_CONFLICT)
                continue
            for ns_name, n in rwset.ns.items():
                for k, v in n.writes.items():
                    writes[(ns_name, k)] = (v, (blk.header.number, txnum))
            codes.append(C.VALID)
        filters.append(bytes(codes))
    return filters, writes


def copy_blocks(blocks):
    from fabric_tpu.protos import common_pb2

    out = []
    for blk in blocks:
        b = common_pb2.Block()
        b.CopyFrom(blk)
        out.append(b)
    return out


# -- reading the instruments ---------------------------------------------------


def counter_total(name: str) -> float:
    """Sum over every label variant; 0 for a metric nothing registered
    (the lane guard's counters do not exist while the guard is off)."""
    from fabric_tpu.ops_metrics import global_registry

    m = global_registry().metric(name)
    return 0.0 if m is None else float(sum(m.snapshot().values()))


class CounterWatch:
    """Counters that must not move while a phase runs.  The registry
    is process-wide, so a phase is held to what it added, not to what
    the process (a test session, an earlier phase) counted before."""

    def __init__(self, names):
        self.before = {n: counter_total(n) for n in names}

    def require_unmoved(self, where: str) -> dict:
        moved = {n: counter_total(n) - b for n, b in self.before.items()}
        require(not any(moved.values()),
                f"{where}: counters moved {moved}")
        return moved


def compiled_shapes(rows) -> list:
    """Launch-ledger rows that missed the program cache: the kernel,
    its lanes, the padded shape and the seconds the dispatch call took
    (trace + compile, or the load from the persistent cache)."""
    from fabric_tpu.ops import p256v3

    out = []
    for r in rows:
        if r["cache"] != "miss":
            continue
        shape = (p256v3._bucket(r["lanes"]) if r["kernel"] == "verify"
                 else r["lanes"])
        out.append({"kernel": r["kernel"], "lanes": r["lanes"],
                    "shape": shape,
                    "compile_s": round(r["compile_ms"] / 1000.0, 3)})
    return out


def require_launches(rows, n_blocks: int, each: int, where: str) -> None:
    """Every block made ``each`` verify and ``each`` stage-2 launches
    (one per validator that committed it): no block rode a host path,
    none was split or retried."""
    for num in range(n_blocks):
        mine = [r["kernel"] for r in rows if r.get("block") == str(num)]
        require(mine.count("verify") == each and mine.count("stage2") == each,
                f"{where} block {num}: launches {mine}, want {each} verify "
                f"and {each} stage2")


def stage_ms_median(roots) -> dict:
    """Median ms per block of every span name in the tracer's block
    trees (a name's spans summed within a block) — ``device_wait`` is
    the host's wait on the device, ``dev:execute`` the launch ledger's
    estimate of device time."""
    per_block = []

    def walk(sp, acc):
        acc[sp.name] = acc.get(sp.name, 0.0) + sp.dur * 1000.0
        for c in sp.children:
            walk(c, acc)
        return acc

    for root in roots:
        per_block.append(walk(root, {}))
    names = sorted({n for acc in per_block for n in acc})
    return {n: round(statistics.median(acc.get(n, 0.0)
                                       for acc in per_block), 3)
            for n in names}


class CompileMeter:
    """Sums what jax itself reports about compilation: backend compile
    seconds, and persistent-cache hits and misses."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = self.misses = 0

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        """What accumulated since the last ``take``."""
        out = {"backend_compile_s": round(self.compile_s, 3),
               "persistent_cache_hits": self.hits,
               "persistent_cache_misses": self.misses}
        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        return out


# -- phase 1 ------------------------------------------------------------------


def run_stream(blocks, mgr, prov, ledger_dir: str, platform: str,
               after_flush=None, **validator_kw):
    """The phase-1 stream through the production commit path on the
    ledger at ``ledger_dir`` (its state DB already preloaded).
    → (filters, stats).  ``after_flush(state)`` runs on the
    live ledger's state once the pipeline has flushed.
    ``validator_kw`` is empty on the default path; ``--all-programs``
    passes ``state_resident``."""
    from fabric_tpu import observe
    from fabric_tpu.ledger.kvledger import KVLedger
    from fabric_tpu.observe import ledger as launch_ledger
    from fabric_tpu.observe import txflow
    from fabric_tpu.peer.pipeline import CommitPipeline
    from fabric_tpu.peer.validator import BlockValidator

    n = len(blocks)
    lg = KVLedger(ledger_dir, enable_history=True, async_commit=True)
    led = launch_ledger.acquire(ring=max(256, 8 * n))
    t_begin = led.clock()
    txflow.acquire()
    v = BlockValidator(mgr, prov, lg.state, block_store=lg.blocks,
                       **validator_kw)
    filters: list = [None] * n
    devices: list = [None] * n
    commit_t: list = [0.0] * n

    def commit_fn(res):
        num = res.block.header.number
        lg.commit_block(res.block, res.tx_filter, res.batch, res.history,
                        None, res.txids, res.pend.hd_bytes)
        filters[num] = bytes(res.tx_filter)
        devices[num] = res.pend.fetch.device_out.devices()
        commit_t[num] = time.perf_counter()

    try:
        t0 = time.perf_counter()
        with CommitPipeline(v, commit_fn, depth=2, channel=CHANNEL) as pipe:
            for blk in copy_blocks(blocks):
                pipe.submit(blk)
            pipe.flush()
        lg.drain_state()
        wall = time.perf_counter() - t0
        if after_flush is not None:
            after_flush(lg.state)
        rows = [r for r in led.rows() if r["t_s"] >= t_begin]
        # this stream's trees are the ring's newest; blocks 0 and 1
        # carry the first-shape compiles
        stages = stage_ms_median(
            observe.global_tracer().recent_roots()[-n:][2:]
        )
        height, commit_hash = lg.height, lg.commit_hash
        resident = None if v.resident is None else v.resident.stats()
    finally:
        v.close()
        txflow.release()
        launch_ledger.release()
        lg.close()

    require(height == n, f"ledger height {height} != {n} blocks")
    require_launches(rows, n, 1, "phase 1")
    for num in range(n):
        require(devices[num] and all(
            d.platform == platform for d in devices[num]),
            f"block {num}: verify output on {devices[num]}, "
            f"want {platform}")
    # steady state starts once both first-shape compiles are behind:
    # block 0 compiled, block 1 was prefetched under it
    warm = (None if n < 4 else
            (n - 2) / (commit_t[n - 1] - commit_t[1]))
    stats = {
        "blocks": n, "wall_s": round(wall, 3),
        "compiled": compiled_shapes(rows),
        "warm_blocks_per_s": None if warm is None else round(warm, 3),
        "stage_ms_per_block_median": stages,
        "height": height, "commit_hash": commit_hash.hex(),
    }
    if resident is not None:
        stats["resident"] = resident
    return filters, stats


def check_state(state, plan, writes) -> None:
    """Every valid tx's writes at the committed version, no invalid
    tx's write."""
    for (ns, key), (value, version) in writes.items():
        vv = state.get_state(ns, key)
        require(vv is not None and bytes(vv.value) == value
                and tuple(vv.version) == tuple(version),
                f"valid write {ns}/{key} not read back at {version}")
    for rows in plan:
        for kind, seed_key, fresh_key in rows:
            if kind == "ok":
                continue
            require(state.get_state(CC, fresh_key) is None,
                    f"invalid tx's write {fresh_key} is present")
            vv = state.get_state(CC, seed_key)
            require(vv is not None
                    and tuple(vv.version) == PRELOAD_VERSION,
                    f"invalid tx's rewrite of {seed_key} is present")


def check_reopened(ledger_dir: str, plan, writes, stats) -> None:
    """Reopen the ledger directory: same height, same commit hash,
    same state."""
    from fabric_tpu.ledger.kvledger import KVLedger

    lg = KVLedger(ledger_dir, enable_history=True)
    try:
        require(lg.height == stats["height"],
                f"reopened height {lg.height} != {stats['height']}")
        require(lg.commit_hash.hex() == stats["commit_hash"],
                "reopened commit hash differs")
        check_state(lg.state, plan, writes)
    finally:
        lg.close()


def phase1_commit_path(size: Size, seed: int, workdir: str,
                       platform: str) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    watch = CounterWatch(ZERO_COUNTERS)
    t0 = time.perf_counter()
    preload = os.path.join(workdir, "preload.db")
    preload_state(preload, size, rng)
    say(f"[phase 1] preloaded {size.preload_keys} keys x "
        f"{size.value_bytes} B in {time.perf_counter() - t0:.1f}s "
        f"({os.path.getsize(preload) / 1e6:.0f} MB)")
    t0 = time.perf_counter()
    mgr, prov, endorsers, client = build_network()
    blocks, plan = build_blocks(size, rng, endorsers, client)
    say(f"[phase 1] built {size.n_blocks} blocks x {size.n_tx} tx "
        f"(10% invalid) in {time.perf_counter() - t0:.1f}s")

    ledger_dir = os.path.join(workdir, "ledger")
    os.makedirs(ledger_dir)
    shutil.copy(preload, os.path.join(ledger_dir, "state.db"))

    from fabric_tpu.ledger.statedb import SqliteVersionedDB

    t0 = time.perf_counter()
    ref_db = SqliteVersionedDB(preload)
    ref_db.open()
    try:
        want, writes = serial_reference(blocks, mgr, prov, ref_db)
    finally:
        ref_db.close()
    n_valid = sum(f.count(0) for f in want)
    say(f"[phase 1] serial reference: {n_valid} valid of "
        f"{size.n_tx * size.n_blocks} in {time.perf_counter() - t0:.1f}s")
    require(n_valid == (size.n_tx - size.n_tx // 10) * size.n_blocks,
            "serial reference disagrees with the stream's construction")

    got, stats = run_stream(
        blocks, mgr, prov, ledger_dir, platform,
        after_flush=lambda state: check_state(state, plan, writes),
    )
    for num, (g, w) in enumerate(zip(got, want)):
        require(g == w, f"block {num}: tx filter differs from the serial "
                        f"reference at {[i for i in range(len(w)) if g[i] != w[i]][:8]}")
    check_reopened(ledger_dir, plan, writes, stats)
    stats["counters"] = watch.require_unmoved("phase 1")
    say(f"[phase 1] ok: {json.dumps(stats)}")
    return {"stats": stats, "blocks": blocks, "want": want, "mgr": mgr,
            "prov": prov, "preload": preload}


# -- phase 2 ------------------------------------------------------------------


async def _served_leg(size: Size, workdir: str) -> dict:
    """The in-process network of tests/test_gateway.py: one orderer
    with the default block cutter (500 messages / 2 s), two peers with
    the default knobs over loopback sockets."""
    from fabric_tpu.crypto import cryptogen, policy as pol
    from fabric_tpu.crypto.msp import MSPManager
    from fabric_tpu.discovery import PeerInfo
    from fabric_tpu.observe import ledger as launch_ledger
    from fabric_tpu.ordering.blockcutter import BatchConfig
    from fabric_tpu.ordering.node import OrdererNode
    from fabric_tpu.peer.chaincode import ChaincodeRuntime, KVContract
    from fabric_tpu.peer.gateway import GatewayClient
    from fabric_tpu.peer.node import PeerNode
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    org1 = cryptogen.generate_org("Org1MSP", "org1.example.com",
                                  peers=1, users=1)
    org2 = cryptogen.generate_org("Org2MSP", "org2.example.com", peers=1)
    mgr = MSPManager({"Org1MSP": org1.msp(), "Org2MSP": org2.msp()})
    client = cryptogen.signing_identity(org1, "User1@org1.example.com")
    signers = (cryptogen.signing_identity(org1, "peer0.org1.example.com"),
               cryptogen.signing_identity(org2, "peer0.org2.example.com"))

    orderer = OrdererNode(
        "o0", os.path.join(workdir, "o0"), {},
        batch_config=BatchConfig(max_message_count=500, batch_timeout_s=2),
    )
    await orderer.start()
    orderer.cluster["o0"] = ("127.0.0.1", orderer.port)
    orderer.join_channel(CHANNEL)
    policy = pol.from_dsl("AND('Org1MSP.peer', 'Org2MSP.peer')")
    peers = []
    gw = None
    try:
        for name, signer in zip(("p1", "p2"), signers):
            rt = ChaincodeRuntime()
            rt.register(CC, KVContract())
            node = PeerNode(name, os.path.join(workdir, name), mgr,
                            signer, rt)
            await node.start()
            peers.append(node)
            ch = node.join_channel(
                CHANNEL, PolicyProvider({CC: NamespaceInfo(policy=policy)})
            )
            ch.start_deliver([("127.0.0.1", orderer.port)])
        peers[0].registry.add(PeerInfo("Org2MSP", "127.0.0.1", peers[1].port))
        peers[1].registry.add(PeerInfo("Org1MSP", "127.0.0.1", peers[0].port))

        led = launch_ledger.global_ledger()
        t_begin = led.clock()
        gw = GatewayClient("127.0.0.1", peers[0].port, client)
        written: dict = {}

        async def submit(k: int) -> None:
            key, value = b"asset%05d" % k, b"owner-%d" % k
            _tx, status = await gw.submit_transaction(
                CHANNEL, CC, [b"put", key, value]
            )
            require(status["code_name"] == "VALID",
                    f"tx {k}: commit_status {status}")
            while not status["applied"]:
                # the block is in the ledger but its writes trail on
                # the applier; ask again until they are readable
                await asyncio.sleep(0.05)
                status = await gw.commit_status(CHANNEL, status["tx_id"],
                                                timeout=5.0)
            written[key] = value

        k = 0
        for wave in size.served_waves:
            t0 = time.perf_counter()
            await asyncio.gather(*(submit(k + j) for j in range(wave)))
            k += wave
            say(f"[phase 2] wave of {wave} submits committed VALID and "
                f"applied in {time.perf_counter() - t0:.1f}s")
        for key in list(written)[:: max(1, len(written) // 8)]:
            resp = await gw.evaluate(CHANNEL, CC, [b"get", key])
            require(resp.payload == written[key],
                    f"evaluate {key!r} returned {resp.payload!r}")

        height = peers[0].channels[CHANNEL].height
        await peers[1].channels[CHANNEL].wait_height(height, timeout=60.0)
        hashes = {p.channels[CHANNEL].ledger.commit_hash.hex()
                  for p in peers}
        require(len(hashes) == 1, f"peers disagree on commit hash {hashes}")
        rows = [r for r in led.rows() if r["t_s"] >= t_begin]
        require_launches(rows, height, len(peers), "phase 2")
        return {"txs": k, "blocks": height,
                "compiled": compiled_shapes(rows),
                "commit_hash": hashes.pop()}
    finally:
        if gw is not None:
            await gw.close()
        for p in peers:
            await p.stop()
        await orderer.stop()


def phase2_served(size: Size, workdir: str) -> dict:
    served = os.path.join(workdir, "served")
    os.makedirs(served)
    watch = CounterWatch(ZERO_COUNTERS + ("deliver_reconnects_total",))
    threads_before = set(threading.enumerate())
    stats = asyncio.run(asyncio.wait_for(
        _served_leg(size, served), size.served_deadline_s
    ))
    stats["counters"] = watch.require_unmoved("phase 2")
    # the nodes are stopped: their pipeline and feeder threads must be
    # gone (a short grace for threads already on their way out)
    deadline = time.perf_counter() + 5.0
    while True:
        left = sorted(t.name for t in threading.enumerate()
                      if t not in threads_before
                      and t.name.startswith("fabtpu"))
        if not left or time.perf_counter() > deadline:
            break
        time.sleep(0.05)
    require(not left, f"phase 2 left threads running: {left}")
    say(f"[phase 2] ok: {json.dumps(stats)}")
    return stats


# -- --all-programs -------------------------------------------------------------


def _signed_items(n: int, rng) -> tuple:
    """``n`` (digest, r, s, qx, qy) tuples signed by OpenSSL with low-S
    normalisation, every 7th lane's digest flipped → (items,
    expected)."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from fabric_tpu.crypto import ec_ref

    keys = [ec.generate_private_key(ec.SECP256R1()) for _ in range(4)]
    pubs = [k.public_key().public_numbers() for k in keys]
    items, want = [], []
    for i in range(n):
        msg = rng.bytes(48)
        k = i % len(keys)
        r, s = decode_dss_signature(
            keys[k].sign(msg, ec.ECDSA(hashes.SHA256()))
        )
        s = ec_ref.N - s if s > ec_ref.HALF_N else s
        ok = i % 7 != 3
        e = ec_ref.digest_int(msg) ^ (0 if ok else 1)
        items.append((e, r, s, pubs[k].x, pubs[k].y))
        want.append(ok)
    return items, want


def all_programs(size: Size, seed: int, workdir: str, platform: str,
                 p1: dict) -> dict:
    """Each device program the default path does not reach, once, at
    its production shape, against its oracle.  Any mismatch raises."""
    import numpy as np

    from fabric_tpu.crypto import ec_ref
    from fabric_tpu.ops import p256sign, p256v3, sha256

    rng = np.random.default_rng(seed + 1)
    watch = CounterWatch(ZERO_COUNTERS)
    out = {}
    n_sig = 3 * size.n_tx

    def timed(name, fn):
        t0 = time.perf_counter()
        fn()
        out[name] = {"ok": True, "s": round(time.perf_counter() - t0, 2)}
        say(f"[all-programs] {name}: ok in {out[name]['s']}s")

    items, want = _signed_items(n_sig, rng)
    base = p256v3.verify_launch(items)()
    require(base == want, "default verify_launch disagrees with the "
                          "signatures' construction")
    # information: where one warm launch of the default program spends
    # its wall — the launch call (host staging + dispatch), the
    # dispatch of a program that consumes its output (what stage 2
    # does), and the wait for that to be ready
    import jax.numpy as jnp

    t0 = time.perf_counter()
    handle = p256v3.verify_launch(items)
    t1 = time.perf_counter()
    dependent = jnp.logical_not(handle.device_out)
    t2 = time.perf_counter()
    dependent.block_until_ready()
    t3 = time.perf_counter()
    out["verify_default_warm"] = {
        "lanes": n_sig, "launch_s": round(t1 - t0, 4),
        "dependent_dispatch_s": round(t2 - t1, 4),
        "ready_s": round(t3 - t2, 4),
    }
    say(f"[all-programs] default verify, warm: "
        f"{json.dumps(out['verify_default_warm'])}")

    def recode():
        got = p256v3.verify_launch(items, recode_device=True)()
        require(got == base, "recode_device verdicts differ from host recode")

    def chunked():
        got = p256v3.verify_launch(items, chunk=size.verify_chunk)()
        require(got == base, "chunked verdicts differ from monolithic")

    def coalesced():
        items2, want2 = _signed_items(n_sig, rng)
        h = p256v3.verify_launch_many([items, items2])
        require(h[0]() == base and h[1]() == want2,
                "coalesced verdicts differ from per-block launches")

    def sign():
        d = int.from_bytes(rng.bytes(32), "big") % (ec_ref.N - 1) + 1
        digests = [ec_ref.digest_int(rng.bytes(32))
                   for _ in range(size.sign_lanes)]
        require(p256sign.sign_digests(digests, d)
                == p256sign.sign_host(digests, d),
                "device sign comb is not bit-equal to RFC 6979 ec_ref")

    def resident():
        ledger_dir = os.path.join(workdir, "ledger_resident")
        os.makedirs(ledger_dir)
        shutil.copy(p1["preload"], os.path.join(ledger_dir, "state.db"))
        got, stats = run_stream(
            p1["blocks"], p1["mgr"], p1["prov"], ledger_dir, platform,
            state_resident=True,
        )
        require(got == p1["want"],
                "resident-state filters differ from the serial reference")
        st = stats["resident"]
        require(st["enabled"] and st["hits_total"] > 0
                and st["h2d_bytes_total"] > 0,
                f"resident table did not serve the stream: {st}")
        out["resident_stats"] = {k: st[k] for k in (
            "hits_total", "misses_total", "evictions_total",
            "h2d_bytes_total", "resident_keys")}

    def sha():
        msgs = [rng.bytes(int(n)) for n in
                rng.integers(1, 200, size.sha_msgs)]
        require(sha256.sha256_host(msgs)
                == [hashlib.sha256(m).digest() for m in msgs],
                "sha256 kernel differs from hashlib")

    timed("verify_recode_device", recode)
    timed("verify_chunked", chunked)
    timed("verify_launch_many_2_blocks", coalesced)
    timed("sign_comb", sign)
    timed("resident_state_stage2", resident)
    timed("sha256_blocks", sha)
    watch.require_unmoved("--all-programs")
    return out


# -- driver ---------------------------------------------------------------------


def run(size: Size, seed: int, device: dict, workdir: str,
        programs: bool = False) -> dict:
    """Phases 0–2 (and ``--all-programs``) on the device ``device``
    names; raises on the first check that does not hold."""
    from fabric_tpu import observe

    result = {"device": device, "seed": seed,
              "size": dataclasses.asdict(size)}
    # the tracer is process-wide: run at its default ring whatever an
    # earlier user of the process left, and hand it back as found
    ring_before = observe.global_tracer().ring_blocks
    observe.configure(ring_blocks=observe.DEFAULT_RING_BLOCKS)
    try:
        with CompileMeter() as meter:
            machine = result["machine"] = phase0_machine(device)
            p1 = phase1_commit_path(size, seed, workdir, device["platform"])
            result["phase1"] = p1["stats"]
            result["phase2"] = phase2_served(size, workdir)
            result["compile"] = meter.take()
            say(f"[compile] phases 0-2: {json.dumps(result['compile'])}")
            if programs:
                result["all_programs"] = all_programs(
                    size, seed, workdir, device["platform"], p1
                )
                result["all_programs"]["compile"] = meter.take()
    finally:
        observe.configure(ring_blocks=ring_before)
    machine["cache_entries_at_end"] = cache_entries(machine["cache_dir"])
    say(f"[phase 0] compile cache entries at end: "
        f"{machine['cache_entries_at_end']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--all-programs", action="store_true",
                    help="also run every device program the default "
                         "path does not reach, against its oracle")
    args = ap.parse_args(argv)

    from fabric_tpu.utils.xla_env import claim_device

    t0 = time.perf_counter()
    device = claim_device("chip_smoke")
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs platform 'tpu', jax found "
              f"{device['platform']!r} ({device['kind']} x{device['count']})",
              file=sys.stderr)
        return 2
    workdir = tempfile.mkdtemp(prefix="chip_smoke.")
    try:
        result = run(Size(), args.seed, device, workdir,
                     programs=args.all_programs)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(result, f, indent=1)
    say(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
