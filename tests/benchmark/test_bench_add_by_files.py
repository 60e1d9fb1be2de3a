"""A later PR brings its own cell and metrics as new files and new
entries, and edits no file that is there: a throw-away configuration,
traffic mix and metrics are added to a temporary copy of the benchmark
and run through the harness as the manifest's own cells are."""

import json
import os
import textwrap

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
