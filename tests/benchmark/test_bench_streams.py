"""Every traffic mix's stream against the plain reference at a tiny size:
the plan-level reference, the OpenSSL reference on the blocks' bytes, and
(for the Zipf stream, whose verdicts depend on each other) the system
itself through ``CommitPipeline`` at depth 2."""

import shutil

import pytest

from bench_tiny import tiny_cell
from benchmark import harness, manifest, preload, reference, stream
from benchmark.reference import C

CELLS = ["rw_backlog", "insert_backlog", "rw_paced", "zipf_backlog"]


@pytest.fixture(scope="module")
def network():
    config, _ = tiny_cell("rw_backlog")
    net = stream.make_network(config)
    return net, stream.signers(net), stream.msp_manager(net)


def _provider(config):
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    return PolicyProvider({config["chaincode"]: NamespaceInfo(
        policy=pol.from_dsl(config["endorsement_policy"]))})


def _planner(config, traffic, seed):
    return manifest.load_module("generators", traffic["generator"]).planner(
        config, traffic, seed)


@pytest.mark.parametrize("cell", CELLS)
def test_stream_agrees_with_both_references(cell, network):
    config, traffic = tiny_cell(cell)
    _net, (endorsers, client), mgr = network
    prov = _provider(config)
    planner, state, prev = _planner(config, traffic, 5), \
        reference.RefState(config), b""
    by_kind = {}
    for b in range(4):
        rows = planner.rows(b)
        assert len(rows) == config["block_tx"]
        blk, prev = stream.chain(
            b, prev, stream.envelopes(rows, config, endorsers, client))
        want, done = reference.plan_codes(rows, b, state)
        full, full_done = reference.openssl_codes(
            blk, mgr, prov, state, config["chaincode"])
        assert full == want and full_done == done, f"block {b}"
        for (kind, _r, _w), code in zip(rows, want):
            by_kind.setdefault(kind, set()).add(code)
        state.commit(done)
    # the verdict each kind of spoiled tx gets, by construction
    assert by_kind.get("bad_creator_signature", {C.BAD_CREATOR_SIGNATURE}) \
        == {C.BAD_CREATOR_SIGNATURE}
    assert by_kind.get("bad_endorsement_signature",
                       {C.ENDORSEMENT_POLICY_FAILURE}) \
        == {C.ENDORSEMENT_POLICY_FAILURE}
    assert by_kind.get("stale_read", {C.MVCC_READ_CONFLICT}) \
        == {C.MVCC_READ_CONFLICT}
    want_kinds = {"ok", *traffic["invalid_kinds"]}
    assert set(by_kind) == want_kinds
    if traffic["invalid_kinds"]:
        # nothing but the injected txs is invalid in these mixes
        assert by_kind["ok"] == {C.VALID}
        bad = round(config["block_tx"] * traffic["invalid_share"])
        assert sum(k != "ok" for k, _r, _w in rows) == bad


@pytest.mark.parametrize("cell", ["rw_backlog", "zipf_backlog"])
def test_a_block_is_a_function_of_the_seed_and_its_number(cell):
    config, traffic = tiny_cell(cell)
    in_order = _planner(config, traffic, 9)
    rows = [in_order.rows(b) for b in range(4)]
    assert _planner(config, traffic, 9).rows(3) == rows[3]
    assert _planner(config, traffic, 10).rows(0) != rows[0]
    with pytest.raises(ValueError):
        in_order.rows(1)


def test_generator_refuses_parameters_it_cannot_honour():
    config, traffic = tiny_cell("insert_backlog")
    with pytest.raises(ValueError, match="stale_read needs"):
        _planner(config, dict(traffic, invalid_kinds=["stale_read"]), 1)
    with pytest.raises(ValueError, match="no such pool"):
        _planner(config, dict(traffic, writes=["elsewhere"]), 1)
    config, traffic = tiny_cell("rw_backlog")
    planner = _planner(config, traffic, 1)
    with pytest.raises(ValueError, match="without_replacement pool"):
        planner.rows(200)  # 2000 keys cannot feed 200 blocks of 20 cold reads


def test_zipf_stream_through_the_pipeline_at_depth_two(tmp_path, network):
    config, traffic = tiny_cell("zipf_backlog")
    _net, (endorsers, client), mgr = network
    planner = _planner(config, traffic, 5)
    blocks, plans, prev = [], [], b""
    for b in range(6):
        rows = planner.rows(b)
        blk, prev = stream.chain(
            b, prev, stream.envelopes(rows, config, endorsers, client))
        blocks.append(blk)
        plans.append(rows)

    ledger_dir = str(tmp_path / "ledger")
    template, built = preload.ensure(str(tmp_path / "cache"), config, 5)
    assert built > 0
    assert preload.ensure(str(tmp_path / "cache"), config, 5) == (template, 0.0)
    shutil.copytree(template, ledger_dir)
    rig = harness.Rig(ledger_dir, config, mgr, _provider(config))
    assert rig.pipe.depth == 2 and rig.lg.engine is not None
    try:
        for b in range(6):
            rig.feed(blocks, b, 6)
        rig.drain()
        state = reference.RefState(config)
        in_block = across = 0
        for b in range(6):
            want, done = reference.plan_codes(plans[b], b, state)
            assert rig.filters[b] == want, f"block {b}"
            assert rig.fused[b] and b in rig.applied and b in rig.ack
            written = set()
            for (_k, reads, writes), code in zip(plans[b], want):
                # a read that an earlier valid tx of this block overwrote
                in_block += any(k in written for k, _v in reads)
                # a read of what the block before wrote: only the async
                # applier's overlay may hold it when this block launches
                across += any(v is not None and v[0] == b - 1 and b > 0
                              for _k2, v in reads)
                if code == C.VALID:
                    written.update(k for k, _v in writes)
            state.commit(done)
        assert in_block > 10 and across > 10
        keys = sorted({k for rows in plans for _k, _r, w in rows
                       for k, _v in w})
        assert reference.state_mismatches(
            rig.lg.state, config["chaincode"], state, keys) == []
    finally:
        end = rig.close()
    assert end["height"] == 6
    from benchmark.run import check_reopened

    assert check_reopened(ledger_dir, config, end, state, keys) == []
    # and the reference notices a state that is not the one it left
    state.written[keys[0]] = (b"other", (5, 0))
    assert check_reopened(ledger_dir, config, end, state, keys)
