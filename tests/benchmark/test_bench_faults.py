"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (the tests name ``cpu``), a tiny
``long_chain_backlog`` goes through ``run.run_cell``, and ``correct``
must come out false for each fault the cell can have, and true with
none."""

import pytest

from bench_tiny import make_root
from benchmark import harness, run
from fabric_tpu.utils.xla_env import claim_device


def _duplicate_check_misses(monkeypatch):
    """A stub store: the index answers that it holds no txid, so only the
    in-flight predecessors' txids catch a replay."""
    from fabric_tpu.ledger.blockstore import BlockStore

    monkeypatch.setattr(BlockStore, "existing_txids",
                        lambda self, txids: set())


def _a_verdict_is_altered(monkeypatch):
    """The last tx of every block is committed as valid, whatever the
    validator said."""
    commit = harness.Rig._commit

    def altered(self, res):
        res.tx_filter = (bytes(res.tx_filter[:-1])
                         + bytes([res.tx_filter[-1] ^ 1]))
        commit(self, res)

    monkeypatch.setattr(harness.Rig, "_commit", altered)


FAULTS = {"none": None, "duplicate_check_misses": _duplicate_check_misses,
          "a_verdict_is_altered": _a_verdict_is_altered}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    assert claim_device("test_bench")["platform"] == "cpu"
    return make_root(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_correct_is_false_with_the_timed_path_broken(fault, root,
                                                      monkeypatch):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    res = run.run_cell(root, "long_chain_backlog", seed=6, seconds=2.0,
                       trace=False, platform="cpu", workers=2)
    compared = res["compared"]
    assert list(res)[-1] == "compared"
    assert all(c["limit"] == 0 for c in compared.values())
    wrong = {name for name, c in compared.items() if c["value"]}
    if fault == "none":
        assert res["correct"] and not wrong, res["problems"]
        # the stream does replay txids, from block 30 on
        assert res["window"]["first_block"] == 30 + 5 + 2
        assert res["attempted"] % 20 == 0 and res["failed"] == 0
        return
    assert res["correct"] is False
    assert "blocks_whose_filter_differs_from_reference" in wrong
    if fault == "duplicate_check_misses":
        # the replay went through as valid: its writes are in the state
        assert any("DUPLICATE" in p or "filter differs" in p
                   for p in res["problems"])
