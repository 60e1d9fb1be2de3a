"""``fabric-default-cutter`` and its cell ``ragged_backlog`` (PR 28): the
generator's size rule and its permuted cycles, the warm-up sizes against
the programs the cycle can reach, the six readers on made-up
observations, a warmed validator that lowers nothing for any block of
1..n txs, and the cell run tiny from its own files."""

import json
import os
import shutil
import time
import types

import pytest

import manifest_pins
from bench_tiny import make_root, tiny_cell
from benchmark import harness, manifest, preload, reference, run, stream
from fabric_tpu.observe import Span
from fabric_tpu.utils.batching import block_shapes, channel_shapes

CELL, NEW = manifest_pins.CELL, manifest_pins.NEW


def full_cell():
    _w, config, traffic = manifest.cell(manifest.load(), CELL)
    return config, traffic


def cutter():
    return manifest.load_module("generators", "cutter_kv")


def _planner(config, traffic, seed):
    return cutter().planner(config, traffic, seed,
                            manifest.reference_of(config))


@pytest.fixture(scope="module")
def network():
    config, _ = tiny_cell(CELL)
    net = stream.make_network(config)
    return net, stream.signers(net), stream.msp_manager(net)


def _provider(config):
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    return PolicyProvider({config["chaincode"]: NamespaceInfo(
        policy=pol.from_dsl(config["endorsement_policy"]))})


# -- (a) the generator --------------------------------------------------------


def test_the_cycle_is_what_a_timer_and_a_cap_cut_at_log_spaced_rates():
    config, traffic = full_cell()
    sizes = cutter().cycle_sizes(config, traffic)
    assert len(sizes) == 64 and sizes == sorted(sizes)
    assert sizes[:4] == [10, 11, 12, 13] and sizes[-9:-7] == [447, 479]
    assert sizes.count(500) == 7 and max(sizes) == config["block_tx"] == 500
    assert sum(sizes) / 64 == pytest.approx(165.4375)
    full = sum(s for s in sizes if s == 500) / sum(sizes)
    assert full == pytest.approx(1 / 3, abs=0.01)
    # the lanes launched under today's buckets that are real signatures
    per = config["signatures_per_tx"]
    fill = (sum(per * s for s in sizes)
            / sum(block_shapes(signatures=per * s).verify for s in sizes))
    assert fill == pytest.approx(0.808, abs=0.001)
    # the configuration is its sibling's but for the cutter
    _w, sibling, _t = manifest.cell(manifest.load(), "insert_backlog")
    assert sibling["guarantees"] == config["guarantees"]
    same = [k for k in sibling if k not in (
        "name", "source", "source_detail", "deployment", "block_tx",
        "shapes_kept", "assumed")]
    assert all(sibling[k] == config[k] for k in same)
    assert (config["max_message_count"], config["preferred_max_bytes"],
            config["absolute_max_bytes"], config["batch_timeout_s"]) == (
                500, 2 * 1024 * 1024, 10 * 1024 * 1024, 2)
    assert "reference" not in config and "preload" not in config


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_every_cycle_is_a_seeded_permutation_and_the_warm_up_is_as_given(
        seed):
    config, traffic = full_cell()
    p = _planner(dict(config, preload_keys=2000), traffic, seed)
    warm = traffic["warmup_blocks"]
    assert [p.size(b) for b in range(warm)] == traffic["warmup_sizes"]
    want = sorted(cutter().cycle_sizes(config, traffic))
    turns = [[p.size(warm + 64 * c + i) for i in range(64)]
             for c in range(3)]
    assert all(sorted(t) == want for t in turns)
    assert turns[0] != turns[1] != turns[2] and turns[0] != want
    other = _planner(dict(config, preload_keys=2000), traffic, seed + 1)
    assert [other.size(warm + i) for i in range(64)] != turns[0]
    # any window of whole cycles holds the same txs; one cut anywhere
    # differs by less than a cycle's worth
    flat = [s for t in turns for s in t]
    assert abs(sum(flat[5:5 + 128]) - 2 * sum(want)) < sum(want)


def test_a_block_is_a_function_of_the_seed_and_its_number():
    config, traffic = tiny_cell(CELL)
    a, b = _planner(config, traffic, 9), _planner(config, traffic, 9)
    b.rows(14)  # plans the blocks between
    assert a.rows(13) != a.rows(14)
    b15 = b.rows(15)
    assert a.rows(15) == b15 and len(a.rows(16)) == a.size(16)
    assert _planner(config, traffic, 10).rows(15) != b15
    sizes = {a.size(k) for k in range(12, 12 + 64)}
    assert min(sizes) == 10 and max(sizes) == config["block_tx"] == 20
    bad = dict(traffic, warmup_sizes=traffic["warmup_sizes"][:-1])
    with pytest.raises(ValueError, match="warmup_sizes"):
        _planner(config, bad, 1)
    cold = dict(traffic, reads=["cold"], pools={"cold": {
        "draw": "without_replacement", "first": 16}})
    with pytest.raises(ValueError, match="without_replacement"):
        _planner(config, cold, 1)


def test_stream_agrees_with_both_references(network):
    """As ``test_bench_streams`` checks its cells, on blocks of the
    cycle's own sizes (warm-up blocks and the first of the cycle)."""
    config, traffic = tiny_cell(CELL)
    _net, (endorsers, client), mgr = network
    prov = _provider(config)
    planner, state, prev = _planner(config, traffic, 5), \
        reference.RefState(config), b""
    kinds = set()
    for b in range(0, 16):
        rows = planner.rows(b)
        assert len(rows) == planner.size(b)
        assert sum(k != "ok" for k, _r, _w in rows) == round(0.1 * len(rows))
        blk, prev = stream.chain(b, prev, stream.envelopes(
            rows, config, endorsers, client, 5, b, planner.replays(b)))
        want, done = reference.plan_codes(rows, b, state)
        if b in (0, 9, 11, 12, 15):
            full, full_done = reference.openssl_codes(
                blk, mgr, prov, state, config["chaincode"])
            assert full == want and full_done == done, f"block {b}"
        kinds.update(k for k, _r, _w in rows)
        state.commit(done, reference.block_txids(blk))
    assert kinds == {"ok", "bad_creator_signature",
                     "bad_endorsement_signature"}


# -- (c) the warm-up sizes reach every program the cycle can ------------------


def _programs(config, size):
    per = config["signatures_per_tx"]
    return block_shapes(txs=size, entries=size, signatures=per * size,
                        endorsements=per - 1, writes=2)


def test_every_size_of_the_cycle_keys_programs_a_warm_up_size_keys():
    config, traffic = full_cell()
    warmed = {_programs(config, s) for s in traffic["warmup_sizes"]}
    reached = {_programs(config, s)
               for s in cutter().cycle_sizes(config, traffic)}
    assert reached == warmed and len(warmed) == 12
    assert sorted({p.verify for p in warmed}) == [32, 64, 128, 256, 512,
                                                  1024, 1536]
    # one size a program, the largest that has it, largest first: what
    # the enumeration gives for blocks of 10..500 txs
    assert len(traffic["warmup_sizes"]) == traffic["warmup_blocks"] == 12
    every = channel_shapes(500, config["signatures_per_tx"], 0, 2, 0)
    assert traffic["warmup_sizes"] == [n for n in every.values() if n >= 10]
    # and the 1000-tx cells key the two programs they keyed before
    big = block_shapes(txs=1000, entries=1000, signatures=3000,
                       endorsements=2, reads=2, writes=2)
    assert (big.verify, big.txs, big.entries, big.slots, big.dims) == (
        3072, 1024, 1024, 4, (2, 2, 1))


# -- (d) a warmed validator lowers nothing ------------------------------------


def test_after_the_warm_up_no_block_of_1_to_n_txs_lowers_a_program(
        tmp_path, network):
    n = 20
    config, traffic = tiny_cell(CELL)
    _net, (endorsers, client), mgr = network
    planner = _planner(config, traffic, 3)
    planner.size = lambda b: b + 1
    blocks, plans, prev = [], [], b""
    for b in range(n):
        rows = planner.rows(b)
        blk, prev = stream.chain(b, prev, stream.envelopes(
            rows, config, endorsers, client, 3, b))
        blocks.append(blk)
        plans.append(rows)
    assert [len(b.data.data) for b in blocks] == list(range(1, n + 1))
    template, _built, _start = preload.ensure(
        str(tmp_path / "cache"), config, 3)
    ledger_dir = str(tmp_path / "ledger")
    shutil.copytree(template, ledger_dir)
    rig = harness.Rig(ledger_dir, config, mgr, _provider(config))
    assert rig.pipe.depth == 2
    try:
        with run.CompileWatch() as watch:
            # as ``peer/node.py`` does when a channel opens: what the
            # blocks it holds look like, from one of them
            seen = rig.v.tx_shapes(blocks[-1])
            assert [(s.signatures, s.dims) for s in seen] == [(3, (1, 2, 1))]
            warmed = rig.v.warmup(n, 4, seen)
            # the verify family up to n x (1 creator + 3 principals)
            # signatures, and the (T, lanes) pairs of 1..n txs at 3 each
            assert (warmed["verify"], warmed["stage2"]) == (4, 4)
            t0 = time.perf_counter()
            for k in range(n):
                rig.feed(blocks, k, n)
            rig.drain()
            assert watch.lowered_between(t0, time.perf_counter()) == 0
        state = reference.RefState(config)
        for b in range(n):
            want, done = reference.plan_codes(plans[b], b, state)
            assert rig.filters[b] == want and rig.fused[b], f"block {b}"
            state.commit(done)
        keys = sorted({k for rows in plans for _k, _r, w in rows
                       for k, _v in w})
        assert reference.state_mismatches(
            rig.lg.state, config["chaincode"], state, keys) == []
    finally:
        rig.close()


# -- (e) the six readers -------------------------------------------------------

CALLER, PREFETCH, COMMITTER, APPLIER = (
    "MainThread", "fabtpu-prefetch_0", "fabtpu-committer_0",
    "fabtpu-state-applier")


def sp(name, t0, t1, thread=CALLER, kids=(), **attrs):
    s = Span(name, t0, thread, attrs)
    s.t1 = t1
    s.children.extend(kids)
    return s


def block(num, at, txs, lanes=None, bucket=None):
    """A block's tree from ``at``: 10 ms of ``sig_prepare_launch`` on the
    prefetch thread, ``launch`` 20 ms and ``finish`` 5 ms on the caller,
    ``commit`` 30 ms, ``apply`` 12 ms."""
    sig = {} if lanes is None else {"lanes": lanes, "bucket": bucket}
    attrs = {"block": num} if txs is None else {"block": num, "txs": txs}
    return sp("block", at, at + 0.1, kids=[
        sp("prefetch", at, at + 0.02, PREFETCH, kids=[
            sp("sig_prepare_launch", at + 0.005, at + 0.015, PREFETCH,
               **sig)]),
        sp("launch", at + 0.02, at + 0.04),
        sp("finish", at + 0.04, at + 0.045),
        sp("commit", at + 0.05, at + 0.08, COMMITTER),
        sp("apply", at + 0.085, at + 0.097, APPLIER)], **attrs)


def obs_of(roots, rows=(), modules=None, offset=100.0):
    trace = None if modules is None else types.SimpleNamespace(
        modules=modules)
    return types.SimpleNamespace(
        roots=roots, launch_rows=list(rows), device_trace=trace,
        capture_window=(offset, offset + 1.0, offset),
        device_kind="TPU v5 lite")


def read(name, obs):
    return manifest.load_module("layer_metrics", name).read(obs)


def test_readers_on_made_up_observations():
    from benchmark import roofline

    roots = [block(5, 0.0, 10, 30, 32), block(6, 0.2, 490, 1470, 1536)]
    rows = [{"kernel": "verify", "block": "5", "lanes": 30, "bucket": 32},
            {"kernel": "stage2", "block": "5", "lanes": 16, "txs": 10},
            {"kernel": "verify", "block": "6", "lanes": 1470,
             "bucket": 1536}]
    # the two launches' executions, 8 and 16 ms, and a stage-2 run
    mods = [(0, "jit_verify_batch_packed(11)", 100.012, 0.008),
            (0, "jit_stage2(7)", 100.03, 0.0001),
            (0, "jit_verify_batch_packed(12)", 100.213, 0.016)]
    obs = obs_of(roots, rows, mods)
    assert read("tx_per_block", obs) == 250.0
    assert read("verify_lane_fill", obs) == pytest.approx(
        1500 / 1568 * 100)
    assert read("caller_ms_per_ktx", obs) == pytest.approx(50.0 / 500 * 1000)
    assert read("commit_ms_per_ktx", obs) == pytest.approx(60.0 / 500 * 1000)
    assert read("apply_ms_per_ktx", obs) == pytest.approx(24.0 / 500 * 1000)
    least = sum(roofline.least_seconds(roofline.verify_work(n),
                                       "TPU v5 lite")[0] for n in (30, 1470))
    assert read("verify_roofline_sum", obs) == pytest.approx(
        least / 0.024 * 100)
    # the device's clock 6 ms ahead of the host's: the same window
    skewed = [(d, n, s - 0.006, t) for d, n, s, t in mods]
    assert read("verify_roofline_sum", obs_of(roots, rows, skewed)) == (
        pytest.approx(least / 0.024 * 100))
    # a launch and an execution after the capture's end count for nothing
    late = roots + [block(7, 1.2, 100, 300, 512)]
    more = mods + [(0, "jit_verify_batch_packed(13)", 101.3, 0.005)]
    assert read("verify_roofline_sum", obs_of(late, rows, more)) == (
        pytest.approx(least / 0.024 * 100))
    # no verify execution inside the capture: no number
    assert read("verify_roofline_sum", obs_of(roots, rows, mods[1:2])) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_attributes(name):
    """The parent's program: no ``txs`` on the root, no ``lanes`` on
    ``sig_prepare_launch``, no ``bucket`` on a verify row."""
    roots = [block(5, 0.0, None), block(6, 0.2, None)]
    rows = [{"kernel": "verify", "block": "5", "lanes": 30},
            {"kernel": "stage2", "block": "5", "lanes": 16}]
    mods = [(0, "jit_verify_batch_packed(11)", 100.012, 0.008)]
    assert read(name, obs_of(roots, rows, mods)) is None
    assert read(name, obs_of([], [], None)) is None


def test_the_cell_and_its_metrics_are_in_the_manifest():
    """What PR 28 and its predecessors brought is there, first and in
    its order; what came after is admitted (``manifest_pins``)."""
    man = manifest.load()
    for pin in manifest_pins.PINS:
        pin(man)
    assert os.path.isfile(os.path.join(
        manifest.ROOT, "benchmark", "cells", CELL + ".md"))


# -- (f) the cell, tiny, from its own files ------------------------------------


def test_the_cell_runs_tiny_from_its_own_files(tmp_path, capsys):
    import jax

    from fabric_tpu.utils.xla_env import claim_device

    root = make_root(tmp_path)
    assert claim_device("test_bench")["platform"] == "cpu"
    # the warm-up lowers its programs, as in a run's own process, even
    # where an earlier test of this one traced them
    jax.clear_caches()
    res = run.run_cell(root, CELL, seed=2**31 + 5, seconds=2.0,
                       trace=False, platform="cpu", workers=2)
    assert res["correct"], res["problems"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"commit_tx_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in res["compared"].values())
    # twelve warm-up blocks, each of which lowers a program, the probe
    # that times the rate instead (one whole cycle), two ramp blocks, then
    # the window; the copy's blocks are cut to 20 txs
    w = res["window"]
    assert w["probe_blocks"] == 64
    assert w["rate_blocks"] == [12 + 1, 12 + 64 - 1]
    assert w["first_block"] == 12 + 64 + 2
    assert "of 10..20 tx" in capsys.readouterr().out
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert any(x["name"] == CELL for x in json.load(f)["workloads"])
    assert not os.listdir(os.path.join(root, "benchmark", ".work"))
