"""A later PR brings its own cell and metrics as new files and new
entries, and edits no file that is there: a throw-away configuration,
traffic mix and metrics are added to a temporary copy of the benchmark
and run through the harness as the manifest's own cells are.  So is a
deployment that is not the one the harness already runs at other
numbers: a preload of its own (a ledger that starts at block 7), a plain
reference of its own (other key names), and a generator whose blocks
differ in size, in both loops.  And so is one whose transactions are not
the harness's either: key-level endorsement, with an assembler of its own
(the owner's endorsement alone, validation-parameter writes) and a row
format only its own files read."""

import json
import os
import textwrap

import pytest
from bench_tiny import TINY_HINT, make_root
from benchmark import run
from fabric_tpu.utils.xla_env import claim_device


def _tree(root):
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        if ".cache" in dirpath or ".work" in dirpath:
            continue
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_a_new_cell_and_its_metrics_are_files_and_entries(tmp_path):
    root = make_root(tmp_path)
    before = _tree(root)
    bench = os.path.join(root, "benchmark")

    with open(os.path.join(bench, "configs", "throwaway-2org.json"), "w") as f:
        json.dump({
            "name": "throwaway-2org", "source": "https://example.org/spec",
            "channel": "tmpchan", "chaincode": "tmpcc", "orgs": 2,
            "peers_per_org": 1,
            "endorsement_policy": "AND('Org1MSP.peer', 'Org2MSP.peer')",
            "endorsements_per_tx": 2, "signatures_per_tx": 3,
            "block_tx": 20, "value_bytes": 64, "history_db": False,
            "preload_keys": 500, "preload_version": [1, 0], "reduced": {},
        }, f)
    with open(os.path.join(bench, "traffic", "uniform-backlog.json"), "w") as f:
        json.dump({
            "name": "uniform-backlog", "generator": "pooled_kv",
            "loop": "backlog",
            "pools": {"all": {"draw": "uniform", "first": 0, "count": 500}},
            "reads": ["all", "all"], "writes": ["all", "fresh"],
            "invalid_share": 0.1,
            "invalid_kinds": ["bad_endorsement_signature"],
            "warmup_blocks": 3, "stream_hint_blocks_per_s": TINY_HINT,
        }, f)
    with open(os.path.join(bench, "end_to_end", "blocks_per_s.py"), "w") as f:
        f.write(textwrap.dedent('''
            """Blocks applied in the window per second."""
            UNIT, SOURCE = "1/s", "host_clock"


            def read(obs):
                return (obs.last - obs.first + 1) / (obs.t_close - obs.t_open)
            '''))
    with open(os.path.join(bench, "layer_metrics", "blocks_seen.py"), "w") as f:
        f.write(textwrap.dedent('''
            """Block trees the tracer finished in the window."""
            LAYER, UNIT, SOURCE, MOVES = ("commit_pipeline", "1",
                                          "program_span", "blocks_per_s")


            def read(obs):
                return len(obs.roots) or None
            '''))
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["configs"].append({
        "name": "throwaway-2org", "source": "https://example.org/spec",
        "file": "benchmark/configs/throwaway-2org.json", "reduced": [],
        "why": "a test's"})
    man["workloads"].append({
        "name": "throwaway", "config": "throwaway-2org",
        "traffic": "uniform-backlog", "chips": 1, "why": "a test's"})
    man["end_to_end"].append({
        "name": "blocks_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["throwaway"]})
    man["per_layer"].append({
        "name": "blocks_seen", "unit": "1", "better": "higher",
        "source": "program_span", "layer": "commit_pipeline",
        "moves": "blocks_per_s", "workloads": ["throwaway"]})
    with open(man_path, "w") as f:
        json.dump(man, f)

    assert claim_device("test_bench")["platform"] == "cpu"
    res = run.run_cell(root, "throwaway", seed=3, seconds=2.0, trace=False,
                       platform="cpu", workers=2)
    assert res["correct"], res["problems"]
    assert res["failed"] == 0 and res["attempted"] >= 2 * 20
    # what the manifest lists for the cell, and nothing else
    assert set(res["metrics"]) == {"setup_s", "blocks_per_s"}
    assert res["metrics"]["blocks_per_s"]["value"] > 0
    assert res["metrics"]["blocks_per_s"]["unit"] == "1/s"
    assert res["device"]["platform"] == "cpu"
    valid = res["window"]["valid_tx"]
    n_blocks = res["window"]["last_block"] - res["window"]["first_block"] + 1
    assert 0 < valid <= n_blocks * 18   # 10 % spoiled, and some contention

    # an existing cell of the same copy still runs, paced this time
    res = run.run_cell(root, "rw_paced", seed=4, seconds=2.0, trace=False,
                       platform="cpu", workers=2)
    assert res["correct"], res["problems"]
    assert set(res["metrics"]) == {"setup_s", "tx_commit_p50_ms",
                                   "tx_commit_p95_ms"}
    assert res["attempted"] == 4 * 20    # 2 s at 40 tx/s, blocks of 20

    after = _tree(root)
    changed = {p for p in before if before[p] != after.get(p)}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(before) == {
        "benchmark/configs/throwaway-2org.json",
        "benchmark/traffic/uniform-backlog.json",
        "benchmark/end_to_end/blocks_per_s.py",
        "benchmark/layer_metrics/blocks_seen.py"}
    # nothing is left behind: no work directory, no thread, no child
    assert not os.listdir(os.path.join(bench, ".work"))


PRELOAD = '''
"""A ledger that starts at block ``first_block`` with its keys named as
the configuration's own reference names them."""
import hashlib

from benchmark import manifest, preload


def _build(ledger_dir, config, seed, root):
    from fabric_tpu.ledger.kvledger import KVLedger
    from fabric_tpu.ledger.statedb import UpdateBatch

    ref = manifest.reference_of(config, root)
    lg = KVLedger(ledger_dir, enable_history=bool(config["history_db"]))
    try:
        batch = UpdateBatch()
        for j in range(int(config["preload_keys"])):
            batch.put(config["chaincode"], ref.key_name(j), b"v%d" % j,
                      tuple(config["preload_version"]))
        lg.state.apply_updates(batch, (int(config["first_block"]) - 1, 0))
        lg.blocks.bootstrap_from_snapshot(
            int(config["first_block"]), prev_hash(seed), [],
            commit_hash=b"c" * 32)
    finally:
        lg.close()


def prev_hash(seed):
    return hashlib.sha256(b"throwaway%d" % seed).digest()


def ensure(cache_dir, config, seed):
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    want, built = preload.cached(
        cache_dir, config["name"], f"s{seed}",
        lambda tmp: _build(tmp, config, seed, root))
    return want, built, {"prev_hash": prev_hash(seed)}
'''

REFERENCE = '''
"""The plain reference with preloaded keys of another name."""
from benchmark import reference as _plain
from benchmark.reference import *  # noqa: F401,F403
from benchmark.reference import block_txids, openssl_codes  # noqa: F401
from benchmark.reference import plan_codes, state_mismatches  # noqa: F401


def key_name(j):
    return f"q{j:07d}"


class RefState(_plain.RefState):
    def preloaded(self, key):
        return (len(key) == 8 and key[0] == "q" and key[1:].isdigit()
                and int(key[1:]) < self.preload_keys)
'''

GENERATOR = '''
"""Blocks of 20, 17 and 19 txs in turn (one verify bucket, one stage-2
bucket): each tx reads a preloaded key of its own at the preload's
version and writes a fresh one; the second tx of a block is spoiled."""
SIZES = (20, 17, 19)


class Planner:
    def __init__(self, config, traffic, seed, reference):
        self.first = int(config.get("first_block", 0))
        self.version = tuple(config["preload_version"])
        self.key_name = reference.key_name

    def rows(self, b):
        k = b - self.first
        rows = []
        for i in range(SIZES[k % len(SIZES)]):
            rows.append(("bad_creator_signature" if i == 1 else "ok",
                         ((self.key_name(k * 20 + i), self.version),),
                         ((f"n{b}_{i}", b"x" * 8),)))
        return rows


def planner(config, traffic, seed, reference):
    return Planner(config, traffic, seed, reference)
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text).lstrip())


def test_a_new_deployment_brings_preload_reference_and_block_sizes(
        tmp_path, capsys):
    root = make_root(tmp_path)
    before = _tree(root)
    bench = os.path.join(root, "benchmark")
    _write(os.path.join(bench, "preloads", "at_block_seven.py"), PRELOAD)
    _write(os.path.join(bench, "references", "q_keys.py"), REFERENCE)
    _write(os.path.join(bench, "generators", "ragged.py"), GENERATOR)
    with open(os.path.join(bench, "configs", "throwaway-ragged.json"),
              "w") as f:
        json.dump({
            "name": "throwaway-ragged", "source": "https://example.org/spec",
            "channel": "tmpchan", "chaincode": "tmpcc", "orgs": 3,
            "peers_per_org": 1,
            "endorsement_policy":
                "OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer')",
            "endorsements_per_tx": 2, "signatures_per_tx": 3,
            "block_tx": 20, "value_bytes": 8, "history_db": True,
            "preload_keys": 2000, "preload_version": [1, 0],
            "first_block": 7, "preload": "at_block_seven",
            "reference": "q_keys", "reduced": {},
        }, f)
    for loop, extra in (("backlog",
                         {"stream_hint_blocks_per_s": TINY_HINT}),
                        ("paced", {"rate_tx_per_s": 40})):
        with open(os.path.join(bench, "traffic", f"ragged-{loop}.json"),
                  "w") as f:
            json.dump({"name": f"ragged-{loop}", "generator": "ragged",
                       "loop": loop, "warmup_blocks": 3, **extra}, f)
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["configs"].append({
        "name": "throwaway-ragged", "source": "https://example.org/spec",
        "file": "benchmark/configs/throwaway-ragged.json", "reduced": [],
        "why": "a test's"})
    for loop in ("backlog", "paced"):
        man["workloads"].append({
            "name": f"ragged_{loop}", "config": "throwaway-ragged",
            "traffic": f"ragged-{loop}", "chips": 1, "why": "a test's"})
    for m in man["end_to_end"]:
        if m["name"] == "commit_tx_per_s":
            m["workloads"].append("ragged_backlog")
        elif m["name"].startswith("tx_commit_"):
            m["workloads"].append("ragged_paced")
    with open(man_path, "w") as f:
        json.dump(man, f)

    assert claim_device("test_bench")["platform"] == "cpu"
    res = run.run_cell(root, "ragged_backlog", seed=5, seconds=2.0,
                       trace=False, platform="cpu", workers=2)
    assert res["correct"], res["problems"]
    # the stream is never shorter than the hint says, however slow the
    # warm-up was: 3 warm-up and 2 ramp blocks, and the hint's blocks a
    # second for 2 s
    assert (f"stream: blocks 7..{7 + 3 + 2 + 2 * TINY_HINT - 1} of 17..20 tx"
            in capsys.readouterr().out)
    w = res["window"]
    # three warm-up blocks and two ramp blocks after block 7
    assert w["first_block"] == 7 + 3 + 2 and res["failed"] == 0
    sizes = [(20, 17, 19)[(b - 7) % 3]
             for b in range(w["first_block"], w["last_block"] + 1)]
    # (a loaded host fits one or two blocks into the window, an idle one
    # more: the sizes the window holds are the stream's, 17 to 20, and the
    # paced run below holds all three)
    assert sizes and set(sizes) <= {20, 17, 19}
    # every block but its spoiled tx is valid, whatever its size
    assert w["valid_tx"] == sum(sizes) - len(sizes)
    assert res["metrics"]["commit_tx_per_s"]["value"] == pytest.approx(
        sum(sizes) / w["seconds"])
    assert res["attempted"] >= sum(sizes)

    res = run.run_cell(root, "ragged_paced", seed=6, seconds=2.0,
                       trace=False, platform="cpu", workers=2)
    assert res["correct"], res["problems"]
    w = res["window"]
    # 80 txs fall due in 2 s at 40 tx/s: 20 + 17 + 19 + 20 = 76 do, in
    # whole blocks, and the next block's last tx does not
    assert (w["first_block"], w["last_block"]) == (10, 13)
    assert res["attempted"] == 76 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "tx_commit_p50_ms",
                                   "tx_commit_p95_ms"}

    after = _tree(root)
    assert {p for p in before if before[p] != after.get(p)} == {
        "BENCHMARK.json"}
    assert set(after) - set(before) == {
        "benchmark/configs/throwaway-ragged.json",
        "benchmark/traffic/ragged-backlog.json",
        "benchmark/traffic/ragged-paced.json",
        "benchmark/generators/ragged.py",
        "benchmark/preloads/at_block_seven.py",
        "benchmark/references/q_keys.py"}
    assert not os.listdir(os.path.join(bench, ".work"))


# ---------------------------------------------------------------------------
# the witness: key-level endorsement, as files

SBE_PRELOAD = '''
"""Every preloaded key carries a VALIDATION_PARAMETER: one peer of its
owner's org, owner = key index mod the orgs."""
from benchmark import manifest, preload


def _build(ledger_dir, config, seed, root):
    from fabric_tpu.ledger.kvledger import KVLedger
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, encode_metadata
    from fabric_tpu.ledger.statedb import UpdateBatch

    ref = manifest.reference_of(config, root)
    orgs = int(config["orgs"])
    lg = KVLedger(ledger_dir, enable_history=bool(config["history_db"]))
    try:
        batch = UpdateBatch()
        for j in range(int(config["preload_keys"])):
            batch.put(config["chaincode"], ref.key_name(j), b"v%d" % j,
                      tuple(config["preload_version"]),
                      metadata=encode_metadata({
                          VALIDATION_PARAMETER: ref.owner_policy(j % orgs)}))
        lg.state.apply_updates(batch, None)
    finally:
        lg.close()


def ensure(cache_dir, config, seed):
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    want, built = preload.cached(
        cache_dir, config["name"], f"s{seed}",
        lambda tmp: _build(tmp, config, seed, root))
    return want, built, {"prev_hash": b""}
'''

SBE_GENERATOR = '''
"""Asset txs under key-level endorsement.  A row is
``(kind, orgs, reads, writes, params)``: the orgs whose peers endorse,
and ``params`` the ``(key, owner)`` whose VALIDATION_PARAMETER the tx
sets.  Kinds: ``update`` (the owner's endorsement alone rewrites the
value), ``transfer`` (value and parameter, the old owner endorses),
``create`` (a fresh key and its parameter, two orgs: the chaincode's
policy), ``bad_creator_signature``, ``wrong_org_endorsement`` (the two
non-owners endorse: the chaincode's 2-of-3 is met, the key's policy is
not).  Every preloaded key is drawn at most once in the stream, so no
block has two txs on one key."""
import functools

import numpy as np

MIX = (("update", 8), ("transfer", 4), ("create", 4),
       ("bad_creator_signature", 2), ("wrong_org_endorsement", 2))


@functools.lru_cache(maxsize=None)
def policy_bytes(owner):
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.crypto.msp import policy_to_proto

    return policy_to_proto(pol.from_dsl(
        f"OutOf(1, 'Org{owner + 1}MSP.peer')")).SerializeToString()


class Planner:
    def __init__(self, config, traffic, seed, reference):
        self.seed, self.orgs = int(seed), int(config["orgs"])
        self.T, self.n_keys = int(config["block_tx"]), int(
            config["preload_keys"])
        self.version = tuple(config["preload_version"])
        self.key_name = reference.key_name
        self.perm = np.random.default_rng([self.seed, 0x5BE]).permutation(
            self.n_keys)
        self.kinds = [k for k, n in MIX for _ in range(n)]
        if len(self.kinds) != self.T:
            raise ValueError("the mix is written for blocks of 20")

    def rows(self, b):
        rng = np.random.default_rng([self.seed, b])
        kinds = [self.kinds[j] for j in rng.permutation(self.T)]
        if (b + 1) * self.T > self.n_keys:
            raise ValueError("the stream needs more preloaded keys")
        rows = []
        for i, kind in enumerate(kinds):
            j = int(self.perm[b * self.T + i])
            key, owner, value = self.key_name(j), j % self.orgs, rng.bytes(16)
            others = tuple(o for o in range(self.orgs) if o != owner)
            held = ((key, self.version),)
            if kind == "create":
                key = f"a{b}_{i:03d}"
                rows.append((kind, others[:2], (), ((key, value),),
                             ((key, i % self.orgs),)))
            elif kind == "transfer":
                rows.append((kind, (owner,), held, ((key, value),),
                             ((key, others[0]),)))
            elif kind == "wrong_org_endorsement":
                rows.append((kind, others, held, ((key, value),), ()))
            else:  # update, bad_creator_signature
                rows.append((kind, (owner,), held, ((key, value),), ()))
        return rows


def planner(config, traffic, seed, reference):
    return Planner(config, traffic, seed, reference)


def envelopes(rows, config, endorsers, client, seed, block_num,
              replays=None):
    from benchmark import stream
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, TxRWSet
    from fabric_tpu.peer import txassembly as txa

    channel, cc = config["channel"], config["chaincode"]
    out = []
    for i, (kind, orgs, reads, writes, params) in enumerate(rows):
        prop = stream.proposal(client.serialized, channel, cc,
                               stream.nonce(seed, block_num, i))
        tx = TxRWSet()
        ns = tx.ns_rwset(cc)
        for key, ver in reads:
            ns.reads[key] = tuple(ver)
        for key, value in writes:
            ns.writes[key] = value
        for key, owner in params:
            ns.metadata_writes[key] = {
                VALIDATION_PARAMETER: policy_bytes(owner)}
        rw = tx.to_proto().SerializeToString()
        env = txa.assemble_transaction(prop, [
            txa.create_proposal_response(prop, rw, endorsers[o], cc)
            for o in orgs], client)
        if kind == "bad_creator_signature":
            env.signature = stream.spoil(env.signature)
        out.append(env.SerializeToString())
    return out
'''

SBE_REFERENCE = '''
"""The plain reference of a channel whose keys carry key-level
endorsement policies: a key with a validation parameter is checked
under it, for its value and for the parameter alike; a key without one
under the chaincode's policy.  The state holds each key's owner beside
its value and version.  No key comes twice in a block (the generator's
rule), so what an earlier tx of the block wrote never decides a later
one's policy here."""
from benchmark.reference import C, _conflicts, block_txids, key_name  # noqa: F401
from benchmark import reference as _plain


def owner_policy(owner):
    """The parameter of a key ``Org<owner+1>MSP`` owns, as bytes."""
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.crypto.msp import policy_to_proto

    return policy_to_proto(pol.from_dsl(
        f"OutOf(1, 'Org{owner + 1}MSP.peer')")).SerializeToString()


class RefState(_plain.RefState):
    """``written``: key → (value, version, owner)."""

    def __init__(self, config):
        super().__init__(config)
        self.orgs = int(config["orgs"])

    def owner(self, key):
        got = self.written.get(key)
        if got is not None:
            return got[2]
        return int(key[1:]) % self.orgs if self.preloaded(key) else None


def written_keys(rows):
    return {key for _k, _o, _r, writes, params in rows
            for key, _x in (*writes, *params)}


def plan_codes(rows, block_num, state):
    codes, done = [], {}
    for i, (kind, orgs, reads, writes, params) in enumerate(rows):
        keys = {k for k, _x in (*writes, *params)}
        if keys & set(done):
            raise ValueError(f"block {block_num}: two txs on one key")
        if kind == "bad_creator_signature":
            codes.append(C.BAD_CREATOR_SIGNATURE)
            continue
        held = [state.owner(k) for k in keys]
        if not all(o in orgs if o is not None else len(set(orgs)) >= 2
                   for o in held):
            codes.append(C.ENDORSEMENT_POLICY_FAILURE)
            continue
        if any(_conflicts(k, ver, done, state) for k, ver in reads):
            codes.append(C.MVCC_READ_CONFLICT)
            continue
        new = dict(params)
        for key, value in writes:
            done[key] = (value, (block_num, i),
                         new.get(key, state.owner(key)))
        codes.append(C.VALID)
    return bytes(codes), done


def openssl_codes(blk, mgr, prov, state, chaincode):
    """From the block's bytes alone, as the plain reference's, with the
    policy walk a key at a time."""
    import numpy as np

    from fabric_tpu import protoutil as pu
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.crypto.msp import policy_from_proto
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, TxRWSet
    from fabric_tpu.protos import common_pb2, policies_pb2

    def satisfied(policy, idents, valid):
        m = pol.match_matrix(idents, pol.compile_plan(policy).principals)
        return pol.evaluate(policy, m & np.asarray(valid, bool)[:, None])

    def parsed(raw):
        return policy_from_proto(pu.unmarshal(
            policies_pb2.SignaturePolicyEnvelope, raw))

    by_bytes = {owner_policy(o): o for o in range(state.orgs)}
    codes, done, seen = [], {}, set()
    for txnum, env_bytes in enumerate(blk.data.data):
        env = pu.unmarshal(common_pb2.Envelope, env_bytes)
        ch, sh, cap, _prp, cca = pu.extract_action(env)
        creator = mgr.deserialize_identity(sh.creator)
        if not creator.is_valid or not creator.verify(env.payload,
                                                      env.signature):
            codes.append(C.BAD_CREATOR_SIGNATURE)
            continue
        if ch.tx_id in state.txids or ch.tx_id in seen:
            codes.append(C.DUPLICATE_TXID)
            continue
        seen.add(ch.tx_id)
        prp_bytes = cap.action.proposal_response_payload
        idents = [mgr.deserialize_identity(e.endorser)
                  for e in cap.action.endorsements]
        valid = [i.is_valid and i.verify(prp_bytes + e.endorser, e.signature)
                 for i, e in zip(idents, cap.action.endorsements)]
        rwset = TxRWSet.from_bytes(cca.results)
        if set(rwset.ns) != {chaincode}:
            raise ValueError(f"tx {txnum}: namespaces {sorted(rwset.ns)}")
        n = rwset.ns[chaincode]
        keys = set(n.writes) | set(n.metadata_writes)
        if keys & set(done):
            raise ValueError(f"tx {txnum}: a second tx on one key")
        endorsed = all(
            satisfied(prov.info(chaincode).policy if state.owner(k) is None
                      else parsed(owner_policy(state.owner(k))),
                      idents, valid)
            for k in keys)
        if not endorsed:
            codes.append(C.ENDORSEMENT_POLICY_FAILURE)
            continue
        if any(_conflicts(k, ver, done, state)
               for k, ver in n.reads.items()):
            codes.append(C.MVCC_READ_CONFLICT)
            continue
        for k, v in n.writes.items():
            new = n.metadata_writes.get(k, {}).get(VALIDATION_PARAMETER)
            done[k] = (v, (blk.header.number, txnum),
                       state.owner(k) if new is None else by_bytes[new])
        codes.append(C.VALID)
    return bytes(codes), done


def state_mismatches(db, chaincode, state, keys):
    """Value and version as the plain reference compares them, and the
    key's validation parameter."""
    from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER, decode_metadata

    bad = []
    for key in keys:
        vv = db.get_state(chaincode, key)
        want, owner = state.written.get(key), state.owner(key)
        if want is None and not state.preloaded(key):
            if vv is not None:
                bad.append(f"{key}: an invalid tx's write is present")
            continue
        value, version = (want[:2] if want is not None
                          else (None, state.preload_version))
        if vv is None or tuple(vv.version) != tuple(version) or (
                value is not None and bytes(vv.value) != value):
            bad.append(f"{key}: want version {version}, have "
                       f"{None if vv is None else tuple(vv.version)}")
        elif decode_metadata(vv.metadata).get(
                VALIDATION_PARAMETER) != owner_policy(owner):
            bad.append(f"{key}: not under Org{owner + 1}MSP's parameter")
    return bad
'''


def test_a_key_level_endorsement_deployment_arrives_as_files(tmp_path,
                                                              capsys):
    """The deployment the assembler seam is for, tiny: its transactions
    (the owner's endorsement alone, validation-parameter writes), its
    preload, its reference, under names of its own beside the
    repository's ``fabric-sbe-assets``.  The program judges every tx as
    the reference does, and every block on the fused device path, as it
    has since key-policy lanes joined the stage-2 program."""
    import re

    root = make_root(tmp_path)
    before = _tree(root)
    bench = os.path.join(root, "benchmark")
    _write(os.path.join(bench, "preloads", "throwaway_sbe.py"), SBE_PRELOAD)
    _write(os.path.join(bench, "references", "throwaway_sbe.py"),
           SBE_REFERENCE)
    _write(os.path.join(bench, "generators", "throwaway_sbe.py"),
           SBE_GENERATOR)
    with open(os.path.join(bench, "configs", "throwaway-sbe.json"),
              "w") as f:
        json.dump({
            "name": "throwaway-sbe", "source": "https://example.org/spec",
            "channel": "tmpchan", "chaincode": "tmpcc", "orgs": 3,
            "peers_per_org": 1,
            "endorsement_policy":
                "OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer')",
            "block_tx": 20, "value_bytes": 16, "history_db": True,
            "preload_keys": 2000, "preload_version": [1, 0],
            "preload": "throwaway_sbe", "reference": "throwaway_sbe",
            "reduced": {},
        }, f)
    # no ``pools``, no ``invalid_kinds``: the generator owns its mix
    with open(os.path.join(bench, "traffic", "throwaway-sbe-backlog.json"),
              "w") as f:
        json.dump({"name": "throwaway-sbe-backlog",
                   "generator": "throwaway_sbe",
                   "loop": "backlog", "warmup_blocks": 3,
                   "stream_hint_blocks_per_s": TINY_HINT}, f)
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["configs"].append({
        "name": "throwaway-sbe", "source": "https://example.org/spec",
        "file": "benchmark/configs/throwaway-sbe.json", "reduced": [],
        "why": "a test's"})
    man["workloads"].append({
        "name": "throwaway_sbe_backlog", "config": "throwaway-sbe",
        "traffic": "throwaway-sbe-backlog", "chips": 1, "why": "a test's"})
    for m in man["end_to_end"]:
        if m["name"] == "commit_tx_per_s":
            m["workloads"].append("throwaway_sbe_backlog")
    with open(man_path, "w") as f:
        json.dump(man, f)

    assert claim_device("test_bench")["platform"] == "cpu"
    res = run.run_cell(root, "throwaway_sbe_backlog", seed=2**31 + 9,
                       seconds=2.0,
                       trace=False, platform="cpu", workers=2)
    said = capsys.readouterr().out
    checked = int(re.search(r"checked (\d+) blocks", said).group(1))
    w = res["window"]
    assert w["first_block"] == 3 + 2 and res["failed"] == 0
    assert checked == w["first_block"] + res["attempted"] // 20 >= 6
    # the program's verdicts are the reference's, tx by tx and key by
    # key (value, version, parameter), after flush and after reopen:
    # 8 updates, 4 transfers and 4 creates of a block's 20 are valid
    n_blocks = w["last_block"] - w["first_block"] + 1
    assert w["valid_tx"] == 16 * n_blocks
    compared = {name: c["value"] for name, c in res["compared"].items()}
    # and every block of the stream rode the fused device path: no count
    # of ``correct`` moved
    assert compared["blocks_not_from_fused_device_path"] == 0
    assert not any(compared.values()), (compared, res["problems"])
    assert res["correct"] is True and not res["problems"]

    after = _tree(root)
    assert {p for p in before if before[p] != after.get(p)} == {
        "BENCHMARK.json"}
    assert set(after) - set(before) == {
        "benchmark/configs/throwaway-sbe.json",
        "benchmark/traffic/throwaway-sbe-backlog.json",
        "benchmark/generators/throwaway_sbe.py",
        "benchmark/preloads/throwaway_sbe.py",
        "benchmark/references/throwaway_sbe.py"}
    assert not os.listdir(os.path.join(bench, ".work"))


def test_the_tiny_copy_shrinks_files_that_leave_optional_keys_out():
    """A deployment whose generator owns its mix has no ``pools`` and no
    ``invalid_kinds``, and one whose preload owns its keys no
    ``preload_keys``: ``make_root`` shrinks what is there and adds
    nothing but the hint."""
    from bench_tiny import TINY_TX, shrink_config, shrink_traffic

    traffic = {"name": "own-mix", "generator": "own", "loop": "backlog"}
    shrink_traffic(traffic)
    assert traffic == {"name": "own-mix", "generator": "own",
                       "loop": "backlog",
                       "stream_hint_blocks_per_s": TINY_HINT}
    config = {"name": "own-keys", "block_tx": 500}
    shrink_config(config)
    assert config == {"name": "own-keys", "block_tx": TINY_TX}
