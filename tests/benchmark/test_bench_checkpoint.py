"""``commit_checkpoint_ms`` and ``ledger_checkpoint_ms``: the index's and
the state and history DBs' WAL checkpoints as a mean over the window's
blocks, on made-up trees, on trees without the span (the parent's: the
reader reads nothing and does not raise), and their entries in the
manifest, each pinned to the entry before it and not to the end."""

import types

import pytest

from benchmark import manifest
from fabric_tpu.observe import Span

NAME = "commit_checkpoint_ms"
BACKLOG = ["rw_backlog", "insert_backlog", "zipf_backlog", "ragged_backlog",
           "sbe_backlog"]
COMMITTER = "fabtpu-committer_0"


def sp(name, t0, t1, kids=(), **attrs):
    s = Span(name, t0, COMMITTER, attrs)
    s.t1 = t1
    s.children.extend(kids)
    return s


def block(num, at, checkpoint_ms=None):
    """A block whose ``commit`` holds a 40 ms index insert; where
    ``checkpoint_ms`` is given, a ``commit.checkpoint`` of that length
    under the ROOT, after the enqueue."""
    kids = [sp("commit", at, at + 0.3, kids=[
        sp("commit.index", at + 0.01, at + 0.05, idx_wait_ms=0.0, txids=9),
        sp("commit.append", at, at + 0.06),
        sp("commit.enqueue", at + 0.06, at + 0.07, backpressure_ms=0.0)])]
    if checkpoint_ms is not None:
        kids.append(sp("commit.checkpoint", at + 0.07,
                       at + 0.07 + checkpoint_ms / 1000.0,
                       rows=8000, frames=7000, moved=7000, busy=0))
    return sp("block", at, at + 0.3, kids=kids, block=num, txs=9)


def read(roots):
    return manifest.load_module("layer_metrics", NAME).read(
        types.SimpleNamespace(roots=roots))


def test_it_is_the_windows_checkpoint_time_over_the_windows_blocks():
    roots = [block(n, float(n), {3: 160.0, 11: 200.0}.get(n))
             for n in range(16)]
    assert read(roots) == pytest.approx(360.0 / 16)
    # the median of the same window reads 0: why it is a mean
    from benchmark import spans

    per_block = spans.ms_per_block(roots, ["commit.checkpoint"])
    assert spans.median_ms(per_block) == 0.0
    # commit_index_ms does not see the checkpoint: it left the span
    assert manifest.load_module("layer_metrics", "commit_index_ms").read(
        types.SimpleNamespace(roots=roots)) == pytest.approx(40.0)


@pytest.mark.parametrize("roots", [[], [block(n, float(n)) for n in range(8)]],
                         ids=["no_blocks", "a_program_without_the_span"])
def test_it_reads_nothing_where_no_checkpoint_was_recorded(roots):
    assert read(roots) is None


def _entry(man, name, after):
    """``name``'s entry, which stands right after ``after``'s."""
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(name)
    assert names[at - 1] == after, names[at - 1]
    return man["per_layer"][at]


def test_its_entry_is_appended_for_the_five_backlog_cells():
    man = manifest.load()
    entry = _entry(man, NAME, after="apply_meta_rows")
    assert entry["workloads"] == BACKLOG
    mod = manifest.load_module("layer_metrics", NAME)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        "ledger", "ms", "program_span", "commit_tx_per_s")
    assert entry["better"] == "lower"
    for cell in BACKLOG:
        assert NAME in {m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell)}
    assert NAME not in {m["name"] for m in manifest.metrics_of(
        man, "per_layer", "rw_paced")}


# -- ``ledger_checkpoint_ms``: the state and history DBs' checkpoints ---------

LEDGER = "ledger_checkpoint_ms"
APPLIER, CALLER = "fabtpu-state-applier", "MainThread"


def ledger_block(num, at, checkpoints=()):
    """``block`` with a ``ledger.checkpoint`` under the root for each
    ``(thread, ms, db)`` of ``checkpoints``: on the committer after its
    enqueue, on the applier after the block's apply, on the caller where
    it commits a tail."""
    root = block(num, at)
    for thread, ms, db in checkpoints:
        t0 = at + (0.5 if thread == APPLIER else 0.07)
        ck = Span("ledger.checkpoint", t0, thread,
                  {"db": db, "rows": 8000, "frames": 900, "moved": 900,
                   "busy": 0})
        ck.t1 = t0 + ms / 1000.0
        root.children.append(ck)
    return root


@pytest.mark.parametrize("thread", [COMMITTER, APPLIER, CALLER],
                         ids=["committer", "applier", "caller"])
@pytest.mark.parametrize("name", [LEDGER, "paced." + LEDGER])
def test_the_ledgers_checkpoints_are_a_mean_over_the_windows_blocks(
        name, thread):
    """Whichever thread wrote the WALs back, its spans count: 40 + 25 ms
    on block 2, 30 on block 7, over ten blocks.  The index's
    ``commit.checkpoint`` is not read."""
    roots = [ledger_block(n, float(n), {
        2: [(thread, 40.0, "state"), (thread, 25.0, "history")],
        7: [(thread, 30.0, "state")]}.get(n, ())) for n in range(10)]
    roots[4] = block(4, 4.0, checkpoint_ms=500.0)
    got = manifest.load_module("layer_metrics", name).read(
        types.SimpleNamespace(roots=roots))
    assert got == pytest.approx(95.0 / 10)


def test_a_window_without_the_span_reads_nothing():
    roots = [block(n, float(n), 160.0 if n == 3 else None) for n in range(8)]
    for name in (LEDGER, "paced." + LEDGER):
        mod = manifest.load_module("layer_metrics", name)
        assert mod.read(types.SimpleNamespace(roots=roots)) is None
        assert mod.read(types.SimpleNamespace(roots=[])) is None


@pytest.mark.parametrize("name,after,cells,moves", [
    (LEDGER, NAME, BACKLOG, "commit_tx_per_s"),
    ("paced." + LEDGER, LEDGER, ["rw_paced"], "tx_commit_p50_ms"),
], ids=["backlog", "paced"])
def test_the_ledgers_entries_follow_the_index_checkpoints(name, after, cells,
                                                           moves):
    man = manifest.load()
    entry = _entry(man, name, after=after)
    assert entry["workloads"] == cells and entry["better"] == "lower"
    mod = manifest.load_module("layer_metrics", name)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        "ledger", "ms", "program_span", moves)
