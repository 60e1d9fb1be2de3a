"""``obs.counters``: the program's counters and gauges block by block, as
a per-layer reader gets them (``benchmark/counters.py``).  A reader of a
counter's change over the window, on made-up snapshots; the snapshot of
the program's own registry; and an untraced run, which takes none.  The
rig's blocks read through a reader file: ``test_bench_span_readers.py``."""

import types

import pytest

from bench_tiny import make_root
from benchmark import counters, run
from fabric_tpu import ops_metrics
from fabric_tpu.ops_metrics import Registry
from fabric_tpu.utils.xla_env import claim_device

NAME = "made_up_checkpoints_total"


def obs_of(by_block, first=10, last=12):
    return types.SimpleNamespace(first=first, last=last, counters=by_block)


def labels(**kw):
    return tuple(sorted(kw.items()))


#: blocks 9 (the one before the window) to 12: a counter by db and
#: trigger, whose ``history`` variant first shows inside the window
WINDOW = {
    9: {(NAME, labels(db="state", trigger="group")): 4.0,
        ("other_total", ()): 1.0},
    10: {(NAME, labels(db="state", trigger="group")): 5.0,
         ("other_total", ()): 1.0},
    11: {(NAME, labels(db="state", trigger="group")): 5.0,
         (NAME, labels(db="history", trigger="group")): 1.0},
    12: {(NAME, labels(db="state", trigger="group")): 7.0,
         (NAME, labels(db="state", trigger="backstop")): 1.0,
         (NAME, labels(db="history", trigger="group")): 2.0,
         ("other_total", ()): 1.0},
}


@pytest.mark.parametrize("want,kw", [
    (6.0, {}),                                    # 7 + 1 + 2 - 4
    (4.0, {"db": "state"}),                       # 7 + 1 - 4
    (5.0, {"trigger": "group"}),                  # 7 + 2 - 4
    (2.0, {"db": "history"}),                     # first seen in the window
    (1.0, {"db": "state", "trigger": "backstop"}),
])
def test_a_counters_change_over_the_window(want, kw):
    assert counters.change(obs_of(WINDOW), NAME, **kw) == want
    assert counters.change(obs_of(WINDOW), "other_total") == 0.0


def test_a_made_up_reader_reads_the_change_and_nothing_where_none_is():
    def read(obs):
        return counters.change(obs, NAME, db="state")

    assert read(obs_of(WINDOW)) == 4.0
    # the program has no such counter, or no variant with such labels
    assert counters.change(obs_of(WINDOW), "no_such_total") is None
    assert counters.change(obs_of(WINDOW), NAME, db="index") is None
    # no snapshot at the window's end or before its first block, or none
    # at all (an untraced run)
    assert read(obs_of({k: v for k, v in WINDOW.items() if k != 12})) is None
    assert read(obs_of({k: v for k, v in WINDOW.items() if k != 9})) is None
    assert read(obs_of({})) is None


def test_the_snapshot_holds_the_registrys_counters_and_gauges(monkeypatch):
    reg = Registry()
    monkeypatch.setattr(ops_metrics, "global_registry", lambda: reg)
    reg.counter("made_up_total").add(3, lane="a")
    reg.counter("made_up_total").add(1, lane="b")
    reg.gauge("made_up_gauge").set(2.5)
    reg.histogram("made_up_seconds").observe(0.1)
    assert counters.snapshot() == {
        ("made_up_total", (("lane", "a"),)): 3.0,
        ("made_up_total", (("lane", "b"),)): 1.0,
        ("made_up_gauge", ()): 2.5}
    # a listener's snapshot when a block's tree is finished, by block
    seen = counters.Snapshots()
    for b in (3, 4, 5):
        seen(types.SimpleNamespace(attrs={"block": b}))
    assert sorted(seen.by_block) == [3, 4, 5]
    assert sorted(seen.window(5, 5)) == [4, 5]


def test_an_untraced_run_takes_no_snapshot(tmp_path, monkeypatch):
    taken = []
    monkeypatch.setattr(counters, "snapshot",
                        lambda: taken.append(1) or {})
    root = make_root(tmp_path)
    assert claim_device("test_bench")["platform"] == "cpu"
    res = run.run_cell(root, "rw_paced", seed=2**31 + 42, seconds=2.0,
                       trace=False, platform="cpu", workers=2)
    assert res["correct"], res["problems"]
    assert res["attempted"] > 0 and taken == []
