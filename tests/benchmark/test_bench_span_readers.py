"""The readers of the spans inside ``launch``, ``state_fill``, ``commit``
and the apply: each on made-up trees whose answers are known by hand,
then all of them on the trees a few tiny blocks leave when they go
through the harness's rig with the tracer armed (no profiler capture:
a traced ``run_cell`` is the chip's to run), and a reader of the
program's counters (``benchmark/counters.py``) on the same blocks."""

import os
import shutil
import textwrap
import types

import pytest

from bench_tiny import tiny_cell
from benchmark import counters, harness, manifest, preload, spans, stream
from fabric_tpu.observe import Span

CALLER, COMMITTER, APPLIER = ("MainThread", "fabtpu-committer_0",
                              "fabtpu-state-applier")
BACKLOG = ["dup_txid_ms", "state_gather_ms", "state_gather_under_apply",
           "commit_index_ms", "commit_enqueue_ms", "apply_write_ms",
           "apply_history_ms", "ledger_checkpoint_ms"]
PACED = ["paced.dup_txid_ms", "paced.apply_write_ms",
         "paced.apply_history_ms", "paced.feed_wait_ms",
         "paced.ledger_checkpoint_ms"]


def sp(name, t0, t1, thread=CALLER, kids=(), **attrs):
    s = Span(name, t0, thread, attrs)
    s.t1 = t1
    s.children.extend(kids)
    return s


def block(num, at, gather, write, checkpoint_ms=None, fed=None):
    """One block's tree starting at ``at``: ``launch`` with a 100·(num-9)
    ms ``dup_txid``, ``commit`` on the committer with an 80 ms index
    insert, a 30 ms enqueue and two fsyncs (100 ms on the committer,
    40 ms under the applier's fence), and the ``apply`` late on the
    applier; where ``checkpoint_ms`` is given, the state DB's
    ``ledger.checkpoint`` of that length on the committer after the
    enqueue.  ``gather`` and ``write`` are absolute intervals."""
    dup = 0.1 * (num - 9)
    launch = sp("launch", at + 0.1, at + 0.9, kids=[
        sp("dup_txid", at + 0.1, at + 0.1 + dup, lookups=20, hits=0,
           idx_wait_ms=0.0),
        sp("sf.gather", *gather, keys=58),
        sp("sf.pending", gather[1], gather[1] + 0.01, pending=1),
        sp("state_fill", gather[0] - 0.02, gather[1] + 0.03),
        sp("stage2_dispatch", at + 0.85, at + 0.9)])
    commit = sp("commit", at + 1.0, at + 1.4, COMMITTER, kids=[
        sp("commit.index", at + 1.02, at + 1.10, COMMITTER,
           idx_wait_ms=0.0, txids=20),
        sp("commit.append", at + 1.0, at + 1.12, COMMITTER),
        sp("commit.enqueue", at + 1.12, at + 1.15, COMMITTER,
           backpressure_ms=0.0),
        sp("fsync", at + 1.2, at + 1.3, COMMITTER, trigger="forced",
           blocks=1)])
    apply = sp("apply", write[0] - 0.05, write[1] + 0.15, APPLIER, kids=[
        sp("apply.fence", write[0] - 0.05, write[0], APPLIER, kids=[
            sp("fsync", write[0] - 0.05, write[0] - 0.01, APPLIER,
               trigger="apply", blocks=1)]),
        sp("apply.write", *write, APPLIER, writes=40),
        sp("apply.history", write[1], write[1] + 0.15, APPLIER)],
        queued_ms=5.0)
    kids = [launch, commit, apply]
    if checkpoint_ms is not None:
        kids.append(sp("ledger.checkpoint", at + 1.15,
                       at + 1.15 + checkpoint_ms / 1000.0, COMMITTER,
                       db="state", rows=8000, frames=900, moved=900, busy=0))
    if fed is not None:
        kids.insert(0, sp("feed_wait", at - fed, at))
    return sp("block", at, at + 1.4, kids=kids, block=num)


def made_up(overlapping: bool):
    """Blocks 10 and 11.  Overlapping: block 11's 200 ms gather meets
    the last 50 ms of block 10's write.  Disjoint: it starts after."""
    w10 = (1.5, 2.45) if overlapping else (1.5, 2.3)
    return [
        block(10, 0.0, gather=(0.3, 0.4), write=w10, checkpoint_ms=60.0),
        block(11, 2.0, gather=(2.4, 2.6), write=(3.5, 3.9), fed=0.25),
    ]


def obs_of(roots):
    return types.SimpleNamespace(roots=roots)


def read(name, roots):
    return manifest.load_module("layer_metrics", name).read(obs_of(roots))


@pytest.mark.parametrize("name,want", [
    ("dup_txid_ms", 150.0),               # 100 and 200
    ("paced.dup_txid_ms", 150.0),
    ("state_gather_ms", 150.0),           # 100 and 200
    ("state_gather_under_apply", 50.0 / 300.0 * 100.0),
    ("commit_index_ms", 80.0),
    ("commit_enqueue_ms", 30.0),
    ("apply_write_ms", (950.0 + 400.0) / 2),
    ("paced.apply_write_ms", (950.0 + 400.0) / 2),
    ("apply_history_ms", 150.0),
    ("paced.apply_history_ms", 150.0),
    ("paced.feed_wait_ms", 125.0),        # none, and 250
    ("ledger_checkpoint_ms", 30.0),       # 60 and none: a mean
    ("paced.ledger_checkpoint_ms", 30.0),
])
def test_reader_on_made_up_trees(name, want):
    assert read(name, made_up(overlapping=True)) == pytest.approx(want)


def test_gather_that_meets_no_write_reads_zero_not_nothing():
    assert read("state_gather_under_apply", made_up(False)) == 0.0
    # and the other readers do not care
    assert read("state_gather_ms", made_up(False)) == pytest.approx(150.0)


def _chain(index_ms, hits=None):
    """One tree a block, from block 40: ``commit.index`` of
    ``index_ms[k]`` ms, ``dup_txid`` with ``hits[k]`` hits."""
    out = []
    for k, ms in enumerate(index_ms):
        at = 2.0 * k
        out.append(sp("block", at, at + 1.4, block=40 + k, kids=[
            sp("launch", at + 0.1, at + 0.5, kids=[
                sp("dup_txid", at + 0.1, at + 0.2, lookups=20,
                   hits=hits[k] if hits else 0)]),
            sp("commit", at + 1.0, at + 1.4, COMMITTER, kids=[
                sp("commit.index", at + 1.0, at + 1.0 + ms / 1000.0,
                   COMMITTER, txids=20)])]))
    return out


def test_index_growth_is_the_last_fifth_over_the_first():
    # eleven blocks: fifths of two; means 25 and 155
    ms = [20, 30, 60, 80, 100, 110, 120, 130, 140, 150, 160]
    assert read("commit_index_growth", _chain(ms)) == pytest.approx(155 / 25)
    # in any order the tracer finished them
    assert read("commit_index_growth", _chain(ms)[::-1]) == pytest.approx(
        155 / 25)
    assert read("commit_index_growth", _chain([50] * 10)) == pytest.approx(1.0)
    # a flat index that pays a checkpoint every second block is flat,
    # whichever of the two values a fifth holds once more
    assert read("commit_index_growth", _chain([115, 223] * 8 + [115])) \
        == pytest.approx((115 * 2 + 223) / (115 + 223 + 115))
    # under five blocks there is no fifth
    assert read("commit_index_growth", _chain(ms[:4])) is None


def test_duplicate_hits_are_the_median_of_the_spans_counts():
    assert read("dup_txid_hits", _chain([50] * 5, [33, 34, 33, 34, 33])) == 33
    assert read("dup_txid_hits", _chain([50] * 4)) == 0
    assert spans.attr_per_block(_chain([50] * 3, [1, 0, 2]), "dup_txid",
                                "hits") == {40: 1, 41: 0, 42: 2}


def test_the_applier_is_a_role_and_feed_wait_is_no_host_work():
    roots = made_up(overlapping=True)
    own = spans.self_intervals(roots)
    assert "applier:apply.write" in own and "applier:apply.history" in own
    assert not any(k.startswith("caller:apply") for k in own)
    assert "committer:commit.index" in own and "caller:dup_txid" in own
    # a caller that waits for the stream covers no block's device_wait
    wait = sp("block", 0.0, 1.0, block=1, kids=[
        sp("finish", 0.2, 0.6, kids=[sp("device_wait", 0.2, 0.6)])])
    fed = sp("block", 0.6, 1.5, block=2, kids=[
        sp("feed_wait", 0.1, 0.6), sp("launch", 0.6, 0.8)])
    assert spans.overlap_coverage([wait, fed]) == 0.0
    fed.children[0].name = "prefetch"
    assert spans.overlap_coverage([wait, fed]) == pytest.approx(1.0)


@pytest.mark.parametrize("name", BACKLOG + PACED + ["commit_index_growth",
                                                    "dup_txid_hits"])
def test_reader_finds_nothing_in_a_program_without_the_spans(name):
    """The parent commit's trees: the reader returns None and does not
    raise, so the result line leaves the metric out."""
    old = [sp("block", 0.0, 1.0, block=3, kids=[
        sp("launch", 0.1, 0.5, kids=[sp("state_fill", 0.2, 0.4)]),
        sp("commit", 0.6, 0.9, COMMITTER)])]
    assert read(name, old) is None
    assert read(name, []) is None


# ---------------------------------------------------------------------------
# the rig's own trees


N_BLOCKS = 6
#: rows a state or history DB writes between two of its WAL checkpoints
#: in the rig: a tiny block's, so that six blocks hold some (a deployment
#: checkpoints every 8,000 rows, some eight 1000-tx blocks)
TINY_CHECKPOINT_ROWS = 30


@pytest.fixture(scope="module")
def rig_run(tmp_path_factory):
    """``N_BLOCKS`` tiny ``rw_backlog`` blocks through the rig, the
    process tracer armed, listeners collecting the roots and the
    program's counters as ``benchmark/run.py`` does."""
    from fabric_tpu import observe
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.ledger import walcheckpoint
    from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider

    tmp = tmp_path_factory.mktemp("span_readers")
    config, traffic = tiny_cell("rw_backlog")
    net = stream.make_network(config)
    endorsers, client = stream.signers(net)
    planner = manifest.load_module(
        "generators", traffic["generator"]).planner(
            config, traffic, 11, manifest.reference_of(config))
    blocks, prev = [], b""
    for b in range(N_BLOCKS):
        blk, prev = stream.chain(b, prev, stream.envelopes(
            planner.rows(b), config, endorsers, client, 11, b))
        blocks.append(blk)
    template, _built, _start = preload.ensure(str(tmp / "cache"), config, 11)
    ledger_dir = str(tmp / "ledger")
    shutil.copytree(template, ledger_dir)
    prov = PolicyProvider({config["chaincode"]: NamespaceInfo(
        policy=pol.from_dsl(config["endorsement_policy"]))})

    tracer = observe.global_tracer()
    was, roots = tracer.ring_blocks, []
    snapshots = counters.Snapshots()
    observe.configure(ring_blocks=observe.DEFAULT_RING_BLOCKS)
    tracer.add_listener(roots.append)
    tracer.add_listener(snapshots)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(walcheckpoint, "CHECKPOINT_ROWS", TINY_CHECKPOINT_ROWS)
        rig = harness.Rig(ledger_dir, config, stream.msp_manager(net), prov)
    try:
        for b in range(N_BLOCKS):
            rig.feed(blocks, b, N_BLOCKS)
        rig.drain()
        assert all(rig.fused[b] for b in range(N_BLOCKS))
    finally:
        rig.close()
        tracer.remove_listener(roots.append)
        tracer.remove_listener(snapshots)
        observe.configure(ring_blocks=was)
    return types.SimpleNamespace(roots=roots, snapshots=snapshots)


@pytest.fixture(scope="module")
def rig_roots(rig_run):
    return rig_run.roots


@pytest.mark.parametrize("name", BACKLOG + PACED)
def test_reader_returns_a_number_on_the_rigs_trees(name, rig_roots):
    value = read(name, rig_roots)
    assert isinstance(value, float) and value >= 0.0
    if name == "state_gather_under_apply":
        assert value <= 100.0
    else:
        assert value > 0.0


def test_rig_trees_keep_their_shape_and_launch_adds_up(rig_roots):
    assert [spans.block_of(r) for r in rig_roots] == list(range(N_BLOCKS))
    self_ms = spans.self_ms_per_block(rig_roots, "launch")
    dup_ms = spans.ms_per_block(rig_roots, ["dup_txid"])
    for r, own, dup in zip(rig_roots, self_ms, dup_ms):
        names = [s.name for s in spans.walk(r)]
        for one in ("launch", "state_fill", "commit", "dup_txid", "apply",
                    "apply.write", "commit.index", "commit.append",
                    "commit.enqueue"):
            assert names.count(one) == 1, (spans.block_of(r), one)
        launch = next(s for s in r.children if s.name == "launch")
        dup_sp = next(s for s in launch.children if s.name == "dup_txid")
        # 20 well-formed txs, none of them config
        assert dup_sp.attrs["lookups"] == 20 and dup_sp.attrs["hits"] == 0
        others = spans.union([(c.t0, c.t1) for c in launch.children
                              if c is not dup_sp
                              and c.thread == launch.thread])
        rest = (launch.t1 - launch.t0) - spans.overlap_len(
            others, [(launch.t0, launch.t1)])
        assert own + dup == pytest.approx(rest * 1000.0, abs=1e-6)
        # the gather sits inside state_fill, on the caller's thread
        fill = next(s for s in launch.children if s.name == "state_fill")
        for g in (s for s in launch.children if s.name.startswith("sf.")):
            assert fill.t0 <= g.t0 <= g.t1 <= fill.t1
            assert g.thread == launch.thread
        apply = next(s for s in r.children if s.name == "apply")
        assert apply.thread == APPLIER



COUNTER_READER = '''
"""The state DB's WAL checkpoints in the window, from the program's
counter."""
from benchmark import counters

LAYER, UNIT, SOURCE, MOVES = ("ledger", "1", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    return counters.change(obs, "ledger_wal_checkpoints_total", db="state")
'''


def test_a_reader_file_reads_a_program_counters_change(rig_run, tmp_path):
    """A reader added as a file reads what the program counted over the
    window, the block before it as the baseline: here the state DB's
    checkpoints, which the rig's trees show as spans."""
    d = tmp_path / "benchmark" / "layer_metrics"
    os.makedirs(d)
    (d / "made_up_wal_checkpoints.py").write_text(
        textwrap.dedent(COUNTER_READER))
    mod = manifest.load_module("layer_metrics", "made_up_wal_checkpoints",
                               str(tmp_path))
    by_block = rig_run.snapshots.by_block
    assert sorted(by_block) == list(range(N_BLOCKS))
    first, last = 1, N_BLOCKS - 1
    obs = types.SimpleNamespace(
        first=first, last=last,
        counters=rig_run.snapshots.window(first, last))
    assert sorted(obs.counters) == list(range(N_BLOCKS))
    spans_seen = sum(1 for r in rig_run.roots for sp in spans.walk(r)
                     if sp.name == "ledger.checkpoint"
                     and sp.attrs["db"] == "state")
    got = mod.read(obs)
    assert got == int(got) and 1 <= got <= spans_seen
    # the registry's own reading, from the same two snapshots
    key = [k for k in by_block[last]
           if k[0] == "ledger_wal_checkpoints_total"
           and ("db", "state") in k[1]]
    assert got == sum(by_block[last][k] - by_block[first - 1].get(k, 0.0)
                      for k in key)
    # a counter the program does not have, or a label no variant carries
    assert counters.change(obs, "no_such_counter_total") is None
    assert counters.change(obs, "ledger_wal_checkpoints_total",
                           db="no_such_db") is None
