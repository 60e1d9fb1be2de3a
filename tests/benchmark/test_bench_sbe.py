"""``fabric-sbe-assets`` / ``sbe_backlog`` at a tiny size on the CPU: the
configuration's file against its sibling's, the reference's two levels
against each other, the generator's mix, signatures and key entries a
block, its rows pinned, the cell through ``run.run_cell`` with every
block from the fused device path (what the witness of PR 33,
``test_a_key_level_endorsement_deployment_arrives_as_files``, showed
the other way round: 11 of 11 blocks on the host path), and the three
readers the cell brings."""

import hashlib
import json
import os
import re
import types

import pytest

from bench_tiny import REPO, TINY_TX, make_root, tiny_cell
from benchmark import manifest, run, stream
from benchmark.reference import C
from fabric_tpu.observe import Span
from fabric_tpu.utils.xla_env import claim_device

CELL = "sbe_backlog"
SEED = 2**31 + 34


def _files():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "fabric-sbe-assets.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "fabric-2of3-sqlite1m.json")) as f:
        sibling = json.load(f)
    return config, sibling


def test_the_configuration_keeps_every_shape_of_its_sibling():
    config, sibling = _files()
    kept = ("channel", "chaincode", "orgs", "peers_per_org",
            "endorsement_policy", "block_tx", "value_bytes", "state_db",
            "block_store", "history_db", "preload_keys", "preload_version",
            "channels", "verify_orderer_block_signature", "chips")
    assert {k: config[k] for k in kept} == {k: sibling[k] for k in kept}
    assert config["reduced"] == sibling["reduced"]
    # the five guarantees word for word, then its own two
    assert config["guarantees"][:5] == sibling["guarantees"]
    assert len(config["guarantees"]) == 7
    assert "validation parameter" in config["guarantees"][6]
    assert set(config["assumed"]) >= {"owners", "in_block_parameter_rule",
                                      "mix", "state_db", "signing_keys"}
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "sbe-backlog", 1)
    # the sixth cell; no cell up to it takes four chips (what later PRs
    # append comes after it)
    names = [w["name"] for w in man["workloads"]]
    assert names.index(CELL) == 5
    assert all(w["chips"] == 1 for w in man["workloads"][:6])


def test_the_cell_lists_what_its_control_lists_and_its_own_three():
    man = manifest.load()
    mine = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    control = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", "rw_backlog")}
    own = {"key_lanes_ms", "key_policy_entries_per_block", "apply_meta_rows"}
    assert mine == control | own and not control & own
    # these three, in this order, right after ``ragged_backlog``'s last
    # (what later PRs append comes after them)
    names = [m["name"] for m in man["per_layer"]]
    at = names.index("apply_ms_per_ktx") + 1
    assert names[at:at + 3] == [
        "key_lanes_ms", "key_policy_entries_per_block", "apply_meta_rows"]
    for m in man["per_layer"][at:at + 3]:
        assert m["workloads"] == [CELL]
        mod = manifest.load_module("layer_metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
    assert {m["name"] for m in manifest.metrics_of(
        man, "end_to_end", CELL)} == {"commit_tx_per_s", "setup_s"}


def test_the_reference_imports_nothing_of_the_code_under_test():
    for rel in ("references/sbe_owned.py", "generators/sbe_assets.py",
                "preloads/sbe_owned.py"):
        with open(os.path.join(REPO, "benchmark", rel)) as f:
            text = f.read()
        imports = [ln for ln in text.splitlines()
                   if re.match(r"\s*(from|import)\s", ln)]
        assert not any("peer.validator" in ln or "device_block" in ln
                       or "peer import validator" in ln for ln in imports)
        assert not any(re.search(r"\bjax\b", ln) for ln in imports)


# ---------------------------------------------------------------------------
# the stream


@pytest.fixture(scope="module")
def tiny():
    config, traffic = tiny_cell(CELL)
    net = stream.make_network(config)
    reference = manifest.reference_of(config)
    gen = manifest.load_module("generators", traffic["generator"])
    return types.SimpleNamespace(
        config=config, traffic=traffic, net=net, reference=reference,
        gen=gen, signers=stream.signers(net), mgr=stream.msp_manager(net),
        planner=gen.planner(config, traffic, SEED, reference))


def _provider(config):
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    return PolicyProvider({config["chaincode"]: NamespaceInfo(
        policy=pol.from_dsl(config["endorsement_policy"]))})


def test_the_mix_at_the_benchmarks_size_and_at_the_tests():
    gen = manifest.load_module("generators", "sbe_assets")
    _w, _config, traffic = manifest.cell(manifest.load(), CELL)
    kinds = gen.kinds_of_a_block(traffic["mix"], 1000)
    assert {k: kinds.count(k) for k in gen.KINDS} == {
        "update": 500, "transfer": 200, "create": 200,
        "bad_creator_signature": 50, "wrong_org_endorsement": 50}
    tiny_kinds = gen.kinds_of_a_block(traffic["mix"], TINY_TX)
    assert {k: tiny_kinds.count(k) for k in gen.KINDS} == {
        "update": 10, "transfer": 4, "create": 4,
        "bad_creator_signature": 1, "wrong_org_endorsement": 1}
    with pytest.raises(ValueError):
        gen.kinds_of_a_block({"update": 0.5, "mint": 0.5}, 20)


def test_a_block_of_the_stream_signatures_entries_and_no_key_twice(tiny):
    """Per block: one or two endorsements a tx (2.25 signatures a tx),
    four in five txs on a preloaded asset under its owner's parameter,
    every asset once in the stream."""
    from fabric_tpu import protoutil as pu
    from fabric_tpu.protos import common_pb2

    endorsers, client = tiny.signers
    seen: set = set()
    for b in range(3):
        rows = tiny.planner.rows(b)
        assert len(rows) == TINY_TX
        keys = [k for _k, _o, _r, writes, params in rows
                for k in {key for key, _x in (*writes, *params)}]
        assert len(keys) == len(set(keys)) == TINY_TX
        assert not seen & set(keys)
        seen |= set(keys)
        state = tiny.reference.RefState(tiny.config)
        owned = [k for k in keys if state.owner(k) is not None]
        assert len(owned) == 16            # 800 of 1000
        assert sum(len(p) for *_x, p in rows) == 8   # 400 parameter writes
        for kind, orgs, reads, writes, params in rows:
            (key, value), = writes
            assert len(value) == tiny.config["value_bytes"]
            if kind == "create":
                assert not reads and len(orgs) == 2
                assert not state.preloaded(key)
            else:
                assert reads == ((key, tuple(tiny.config["preload_version"])),)
                own = state.owner(key)
                assert (orgs == (own,)) == (kind != "wrong_org_endorsement")
                assert (own not in orgs) == (kind == "wrong_org_endorsement")
        envs = tiny.gen.envelopes(rows, tiny.config, endorsers, client,
                                  SEED, b)
        n_sig = 0
        for raw in envs:
            env = pu.unmarshal(common_pb2.Envelope, raw)
            _ch, _sh, cap, _prp, _cca = pu.extract_action(env)
            n_sig += 1 + len(cap.action.endorsements)
        assert n_sig == 45                 # 2,250 of 1000 txs
    # any worker makes any block: a second planner, the same rows
    again = tiny.gen.planner(tiny.config, tiny.traffic, SEED,
                             tiny.reference)
    assert again.rows(2) == tiny.planner.rows(2)
    assert again.rows(1) != tiny.planner.rows(2)


def test_the_references_two_levels_agree_and_give_the_mixs_verdicts(tiny):
    endorsers, client = tiny.signers
    ref, config = tiny.reference, tiny.config
    prov, state, prev = _provider(config), ref.RefState(config), b""
    for b in range(4):
        rows = tiny.planner.rows(b)
        blk, prev = stream.chain(b, prev, tiny.gen.envelopes(
            rows, config, endorsers, client, SEED, b))
        want, done = ref.plan_codes(rows, b, state)
        full, full_done = ref.openssl_codes(blk, tiny.mgr, prov, state,
                                            config["chaincode"])
        assert full == want and full_done == done, f"block {b}"
        by_kind = {}
        for (kind, *_r), code in zip(rows, want):
            by_kind.setdefault(kind, set()).add(code)
        assert by_kind == {
            "update": {C.VALID}, "transfer": {C.VALID}, "create": {C.VALID},
            "bad_creator_signature": {C.BAD_CREATOR_SIGNATURE},
            "wrong_org_endorsement": {C.ENDORSEMENT_POLICY_FAILURE}}
        assert ref.written_keys(rows) == {
            k for _k, _o, _r, writes, _p in rows for k, _v in writes}
        state.commit(done, ref.block_txids(blk))
    # a transfer moved its asset: the reference holds the new owner
    moved = [(k, o) for rows in map(tiny.planner.rows, range(4))
             for kind, _o, _r, _w, params in rows if kind == "transfer"
             for k, o in params]
    assert moved and all(state.owner(k) == o for k, o in moved)
    assert all(state.owner(k) != int(k[1:]) % 3 for k, _o in moved)
    # a second tx on one key in a block is the stream's fault, and said
    rows = tiny.planner.rows(5)
    with pytest.raises(ValueError, match="two txs on one key"):
        ref.plan_codes(rows + rows[:1], 5, state)


#: sha256 over ``repr(planner.rows(b))`` for b = 0, 1, 2 at seed 7 of the
#: tiny cell: the stream is a function of (configuration, traffic, seed,
#: block) and of nothing else
ROW_DIGEST = "fb34b442bcaff4400834427b36c4c50ba60a550b28cf4246fe9f99c6ae41f5c5"


def test_the_rows_are_pinned():
    config, traffic = tiny_cell(CELL)
    planner = manifest.load_module("generators", "sbe_assets").planner(
        config, traffic, 7, manifest.reference_of(config))
    digest = hashlib.sha256()
    for b in range(3):
        digest.update(repr(planner.rows(b)).encode())
    assert digest.hexdigest() == ROW_DIGEST


# ---------------------------------------------------------------------------
# the cell, through the harness


def test_sbe_backlog_runs_tiny_with_every_block_from_the_device_path(
        tmp_path, capsys):
    root = make_root(tmp_path)
    assert claim_device("test_bench")["platform"] == "cpu"
    res = run.run_cell(root, CELL, seed=SEED, seconds=2.0, trace=False,
                       platform="cpu", workers=2)
    said = capsys.readouterr().out
    checked = int(re.search(r"checked (\d+) blocks", said).group(1))
    compared = {name: c["value"] for name, c in res["compared"].items()}
    assert not any(compared.values()), (compared, res["problems"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "commit_tx_per_s"}
    w = res["window"]
    n_blocks = w["last_block"] - w["first_block"] + 1
    assert checked >= w["first_block"] + n_blocks >= 5 + 2 + 1
    # 10 updates, 4 transfers and 4 creates of a block's 20 are valid
    assert w["valid_tx"] == 18 * n_blocks
    assert res["attempted"] % TINY_TX == 0
    assert not os.listdir(os.path.join(root, "benchmark", ".work"))


def test_the_rigs_trees_carry_what_the_new_readers_read(tmp_path):
    """Six tiny blocks through the harness's rig with the tracer armed:
    every block from the fused device path; ``sf.gather`` says how many
    keys' metadata it was asked and how many held some, ``sf.key_lanes``
    how many entries a committed parameter decided, ``apply.write`` one
    statement, the block path, and its rows with metadata; the three
    readers read them."""
    import shutil

    from benchmark import harness, spans
    from fabric_tpu import observe

    config, traffic = tiny_cell(CELL)
    net = stream.make_network(config)
    endorsers, client = stream.signers(net)
    gen = manifest.load_module("generators", traffic["generator"])
    planner = gen.planner(config, traffic, 11, manifest.reference_of(config))
    blocks, prev = [], b""
    for b in range(6):
        blk, prev = stream.chain(b, prev, gen.envelopes(
            planner.rows(b), config, endorsers, client, 11, b))
        blocks.append(blk)
    template, _built, _start = manifest.preload_of(config).ensure(
        str(tmp_path / "cache"), config, 11)
    ledger_dir = str(tmp_path / "ledger")
    shutil.copytree(template, ledger_dir)
    tracer = observe.global_tracer()
    was, roots = tracer.ring_blocks, []
    observe.configure(ring_blocks=observe.DEFAULT_RING_BLOCKS)
    tracer.add_listener(roots.append)
    rig = harness.Rig(ledger_dir, config, stream.msp_manager(net),
                      _provider(config))
    try:
        assert rig.lg.state.meta_count == config["preload_keys"]
        for b in range(6):
            rig.feed(blocks, b, 6)
        rig.drain()
        assert all(rig.fused[b] for b in range(6))
        # 4 creates a block with a parameter each; transfers keep theirs
        assert rig.lg.state.meta_count == config["preload_keys"] + 6 * 4
    finally:
        rig.close()
        tracer.remove_listener(roots.append)
        observe.configure(ring_blocks=was)
    for r in roots:
        by_name = {sp.name: sp for sp in spans.walk(r)}
        gather, lanes = by_name["sf.gather"], by_name["sf.key_lanes"]
        assert (gather.attrs["keys"], gather.attrs["meta_keys"]) == (20, 20)
        assert gather.attrs["params"] == 16
        assert (lanes.attrs["entries"], lanes.attrs["lanes"]) == (16, 20)
        assert lanes.attrs["policies"] == 3 and lanes.attrs["bytes"] == 4 * 32
        fill = by_name["state_fill"]
        assert fill.t0 <= lanes.t0 and lanes.t1 <= fill.t1
        write = by_name["apply.write"]
        assert write.attrs == {"writes": 18, "meta_rows": 18, "stmts": 1,
                               "path": "block"}
    obs = types.SimpleNamespace(roots=roots)
    read = lambda name: manifest.load_module(  # noqa: E731
        "layer_metrics", name).read(obs)
    assert read("key_policy_entries_per_block") == 16
    assert read("apply_meta_rows") == 18
    assert 0.0 < read("key_lanes_ms") < read("state_fill_ms")


# ---------------------------------------------------------------------------
# the three readers


CALLER, APPLIER = "MainThread", "fabtpu-state-applier"


def sp(name, t0, t1, thread=CALLER, kids=(), **attrs):
    s = Span(name, t0, thread, attrs)
    s.t1 = t1
    s.children.extend(kids)
    return s


def _trees(lanes=True):
    out = []
    for k, (ms, entries, meta_rows) in enumerate(
            [(2.0, 800, 900), (4.0, 800, 900), (9.0, 799, 901)]):
        at = 2.0 * k
        launch = [sp("state_fill", at + 0.1, at + 0.3)]
        write = dict(writes=900)
        if lanes:
            launch.append(sp("sf.key_lanes", at + 0.2, at + 0.2 + ms / 1e3,
                             entries=entries, lanes=1000, policies=3,
                             bytes=4096))
            write["meta_rows"] = meta_rows
        out.append(sp("block", at, at + 1.0, block=7 + k, kids=[
            sp("launch", at + 0.1, at + 0.4, kids=launch),
            sp("apply", at + 0.5, at + 0.9, APPLIER, kids=[
                sp("apply.write", at + 0.5, at + 0.8, APPLIER, **write)])]))
    return out


def _read(name, roots):
    return manifest.load_module("layer_metrics", name).read(
        types.SimpleNamespace(roots=roots))


@pytest.mark.parametrize("name,want", [
    ("key_lanes_ms", 4.0), ("key_policy_entries_per_block", 800),
    ("apply_meta_rows", 900)])
def test_a_new_reader_on_recorded_spans(name, want):
    assert _read(name, _trees()) == pytest.approx(want)
    # a program without the lanes (the parent commit): nothing, no raise
    assert _read(name, _trees(lanes=False)) is None
    assert _read(name, []) is None
