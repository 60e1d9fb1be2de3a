"""A later PR brings its own cell and metrics as new files and new
entries, and edits no file that is there: a throw-away configuration,
traffic mix and metrics are added to a temporary copy of the benchmark
and run through the harness as the manifest's own cells are.  So is a
deployment that is not the one the harness already runs at other
numbers: a preload of its own (a ledger that starts at block 7), a plain
reference of its own (other key names), and a generator whose blocks
differ in size, in both loops."""

import json
import os
import textwrap

import pytest
from bench_tiny import make_root
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
            "warmup_blocks": 3, "stream_hint_blocks_per_s": 3,
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
    for loop, extra in (("backlog", {"stream_hint_blocks_per_s": 6}),
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
    # warm-up was: 3 warm-up and 2 ramp blocks, and 6 a second for 2 s
    assert "stream: blocks 7..23 of 17..20 tx" in capsys.readouterr().out
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
