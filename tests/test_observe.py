"""Block-commit span tracer tests (fabric_tpu.observe): span-tree
shape through a real depth-2 CommitPipeline run over the crypto-free
DeviceToyValidator, ring-buffer eviction, slow-block watchdog, Chrome
trace-event schema, cross-thread span adoption (host pool workers),
the /trace operations-server endpoint, the locked ops_metrics read
accessors, and the traceview text waterfall."""

import asyncio
import json
import logging
import os
import sys
import threading
import urllib.error
import urllib.request

import pytest

# scripts/ is not a package: make traceview importable for its tests
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "scripts")
)

from fabric_tpu import observe
from fabric_tpu.observe import Span, Tracer  # noqa: F401
from fabric_tpu.ledger.statedb import MemVersionedDB
from fabric_tpu.peer.pipeline import CommitPipeline


class _Clock:
    """Deterministic perf_counter stand-in for watchdog tests."""

    def __init__(self):
        self.t = 0.0

    def advance(self, dt: float) -> None:
        self.t += dt

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# core span mechanics


def test_span_nesting_and_thread_local_current():
    tr = Tracer(ring_blocks=4, slow_factor=0)
    root = tr.begin_block(1, channel="c")
    with tr.span("launch", parent=root) as sp:
        # the launch span became this thread's current: a parentless
        # retro add() lands under it (how validator._t plugs in)
        assert tr.current() is sp
        tr.add("state_fill", 0.0, 0.001)
        tr.event("note", detail="x")
    assert tr.current() is None
    tr.finish_block(root)
    (tree,) = tr.blocks()
    assert tree["block"] == 1
    (launch,) = tree["children"]
    assert launch["name"] == "launch"
    assert [c["name"] for c in launch["children"]] == ["state_fill"]
    assert launch["events"][0]["name"] == "note"


def test_disabled_tracer_is_noop():
    tr = Tracer(ring_blocks=0)
    assert not tr.enabled
    root = tr.begin_block(5)
    assert root is None
    with tr.span("x", parent=root) as sp:
        assert sp is None
        tr.add("y", 0.0, 1.0)  # parentless: dropped
        tr.event("z")
    tr.finish_block(root)
    assert tr.blocks() == [] and tr.slow_blocks() == []


def test_explicit_handle_crosses_executor_threads():
    """contextvars don't follow ThreadPoolExecutor tasks — the span
    handle passed + attach() is the supported crossing."""
    from concurrent.futures import ThreadPoolExecutor

    tr = Tracer(ring_blocks=4, slow_factor=0)
    root = tr.begin_block(2)

    def task():
        assert tr.current() is None  # nothing followed implicitly
        tok = tr.attach(root)
        try:
            with tr.span("worker-stage"):
                pass
        finally:
            tr.detach(tok)
        return threading.current_thread().name

    with ThreadPoolExecutor(1, thread_name_prefix="tw") as ex:
        worker_name = ex.submit(task).result()
    tr.finish_block(root)
    (child,) = root.children
    assert child.name == "worker-stage" and child.thread == worker_name


def test_ring_eviction():
    tr = Tracer(ring_blocks=2, slow_factor=0)
    for n in range(3):
        tr.finish_block(tr.begin_block(n))
    assert [b["block"] for b in tr.blocks()] == [1, 2]
    assert tr.block(0) is None
    assert tr.block(2)["block"] == 2


def test_watchdog_flags_slow_block(caplog):
    clk = _Clock()
    tr = Tracer(ring_blocks=32, slow_factor=3.0, clock=clk)
    for n in range(9):  # arm the median (8+ samples) at 10 ms/block
        root = tr.begin_block(n)
        clk.advance(0.010)
        tr.finish_block(root)
    assert tr.slow_blocks() == []
    with caplog.at_level(logging.WARNING, logger="fabric_tpu.observe"):
        root = tr.begin_block(9)
        with tr.span("finish", parent=root):
            clk.advance(0.500)  # 50x the trailing median
        tr.finish_block(root)
    (slow,) = tr.slow_blocks()
    assert slow["block"] == 9 and slow["attrs"]["slow"] is True
    assert any("slow block 9" in r.getMessage()
               and "finish" in r.getMessage()
               for r in caplog.records)
    # a watchdog of 0 never flags
    clk2 = _Clock()
    tr2 = Tracer(ring_blocks=32, slow_factor=0, clock=clk2)
    for n in range(12):
        root = tr2.begin_block(n)
        clk2.advance(10.0 if n == 11 else 0.01)
        tr2.finish_block(root)
    assert tr2.slow_blocks() == []


def test_configure_resize_keeps_recent_trees():
    tr = Tracer(ring_blocks=8, slow_factor=0)
    for n in range(5):
        tr.finish_block(tr.begin_block(n))
    tr.configure(ring_blocks=2)
    assert [b["block"] for b in tr.blocks()] == [3, 4]
    tr.configure(ring_blocks=0)
    assert not tr.enabled and tr.begin_block(9) is None


# ---------------------------------------------------------------------------
# the real thing: a depth-2 pipelined run over the device toy validator


@pytest.fixture(scope="module")
def toy_run():
    """One depth-2 CommitPipeline run (5 blocks, real device verifies,
    bad-sig lanes) captured by a fresh tracer."""
    from test_multidevice import DeviceToyValidator, _device_stream
    from fabric_tpu.crypto import ec_ref

    tr = Tracer(ring_blocks=16, slow_factor=0)
    key = ec_ref.SigningKey.generate()
    blocks = _device_stream(key, n_blocks=5, n_tx=8)
    state = MemVersionedDB()
    v = DeviceToyValidator(state)
    filters = []

    def commit_fn(res):
        state.apply_updates(res.batch, (res.block.header.number, 0))
        filters.append((res.block.header.number, list(res.tx_filter)))

    with CommitPipeline(v, commit_fn, depth=2, tracer=tr) as pipe:
        for b in blocks:
            pipe.submit(b)
    return tr, sorted(filters)


def test_pipeline_span_tree_shape(toy_run):
    """Every committed block leaves one finalized tree whose
    prefetch/launch/finish/commit children are complete, nested inside
    the root's window, and placed on the right threads."""
    tr, filters = toy_run
    assert len(filters) == 5  # nothing lost to tracing
    roots = list(tr._ring)
    assert [r.attrs["block"] for r in roots] == [0, 1, 2, 3, 4]
    for r in roots:
        names = [c.name for c in r.children]
        for want in ("prefetch", "prefetch_wait", "launch", "finish",
                     "commit_wait", "commit"):
            assert names.count(want) == 1, (r.attrs, want, names)
        for c in r.children:
            assert c.t1 is not None, (r.attrs, c.name)
            if c.name == "feed_wait" and "before_flush" not in c.attrs:
                # the wait for the feeder ends where the root begins
                assert c.t0 <= c.t1 <= r.t0 + 1e-6
                continue
            assert c.t0 >= r.t0 - 1e-6 and c.t1 <= r.t1 + 1e-6
        # every block but the first was submitted straight after the
        # one before (a feed_wait each, none of them after a flush);
        # the last waited, launched, for the flush that closed the pipe
        waits = [c.attrs for c in r.children if c.name == "feed_wait"]
        assert waits == ([{}] * (r.attrs["block"] > 0)
                         + [{"before_flush": True}] * ("tail" in r.attrs))
        by = {c.name: c for c in r.children}
        # prefetch ran on the prefetch thread; pipelined commits on the
        # committer thread (the tail flushes inline on the caller)
        assert by["prefetch"].thread.startswith("fabtpu-prefetch")
        if "tail" not in r.attrs:
            assert by["commit"].thread.startswith("fabtpu-committer")
        # stage order within the block: launch → finish → commit
        assert by["launch"].t0 <= by["finish"].t0 <= by["commit"].t0
    # the tail block is annotated as such
    assert roots[-1].attrs.get("tail") is True


def test_pipeline_overlap_visible(toy_run):
    """The depth-2 win on the timeline: block k+1's prefetch begins
    while block k is still in flight (strictly before k's commit
    completes) — impossible under depth-1, where root k finalizes
    before submit(k+1) runs."""
    tr, _ = toy_run
    roots = list(tr._ring)
    for prev, cur in zip(roots, roots[1:]):
        prefetch = next(c for c in cur.children if c.name == "prefetch")
        commit = next(c for c in prev.children if c.name == "commit")
        assert prefetch.t0 < commit.t1, (prev.attrs, cur.attrs)
        assert prefetch.t0 < prev.t1


def test_chrome_export_schema_and_overlap(toy_run, tmp_path):
    """The export is Chrome-trace-event JSON Perfetto can load: X/i
    events with ts/dur/pid/tid + thread_name metadata rows, block
    numbers in args — and the prefetch(k+1)-before-commit(k)-ends
    overlap is readable straight off the event timestamps."""
    tr, _ = toy_run
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    meta = [e for e in events if e.get("ph") == "M"]
    names = {e["args"]["name"] for e in meta}
    assert any(n.startswith("fabtpu-prefetch") for n in names)
    assert any(n.startswith("fabtpu-committer") for n in names)
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no complete events"
    for e in xs:
        for k in ("name", "ts", "dur", "pid", "tid", "args"):
            assert k in e, (k, e)
    by_block: dict = {}
    for e in xs:
        by_block.setdefault(e["args"]["block"], []).append(e)
    assert sorted(by_block) == [0, 1, 2, 3, 4]
    for k in range(4):
        commit_k = next(e for e in by_block[k] if e["name"] == "commit")
        pre_k1 = next(e for e in by_block[k + 1]
                      if e["name"] == "prefetch")
        assert pre_k1["ts"] < commit_k["ts"] + commit_k["dur"]

    # the text waterfall renders the same file without a browser
    import traceview

    text = traceview.render(data)
    assert "block 3" in text and "prefetch" in text and "#" in text
    one = traceview.render(data, block=2)
    assert "block 2" in one and "block 3" not in one


def test_traceview_renders_trace_dump(toy_run):
    import traceview

    tr, _ = toy_run
    dump = {
        "slow_blocks": tr.slow_blocks(),
        "recent_blocks": tr.blocks(4),
    }
    text = traceview.render(dump)
    assert "block 4" in text and "commit" in text
    single = traceview.render(tr.block(3))
    assert single.startswith("block 3") and "finish" in single


def _late_tree():
    """A finished block whose applier spans arrived after
    ``finish_block`` and whose ``feed_wait`` ends where it begins."""
    clk = _Clock()
    tr = Tracer(ring_blocks=4, slow_factor=0, clock=clk)
    clk.advance(1.0)
    root = tr.begin_block(7)
    tr.add("feed_wait", clk.t - 0.4, clk.t, parent=root)
    with tr.span("commit", parent=root):
        clk.advance(0.05)
    tr.finish_block(root)
    dur = root.dur
    clk.advance(0.1)
    with tr.span("apply", parent=root, queued_ms=100.0):
        with tr.span("apply.write", writes=3):
            clk.advance(0.2)
    return tr, root, dur


@pytest.mark.parametrize("surface", ["format_block", "trace_dump", "chrome"])
def test_late_and_early_children_render(surface):
    """Children outside the root's extent (the applier's, after it; the
    feed wait, before it) show on every surface, and the root's own
    duration stays the commit's."""
    import traceview

    from fabric_tpu.observe import format_block

    tr, root, dur = _late_tree()
    assert root.dur == dur == pytest.approx(0.05)
    if surface == "format_block":
        text = format_block(root)
    elif surface == "trace_dump":
        assert tr.block(7)["dur_ms"] == pytest.approx(50.0)
        text = traceview.render(tr.block(7))
    else:
        text = traceview.render({"traceEvents": tr.chrome_events()})
    for name in ("feed_wait", "commit", "apply", "apply.write"):
        assert name in text, (surface, name)
    if surface != "format_block":
        assert text.startswith("block 7  total 50.00 ms")


@pytest.mark.parametrize("name,covers", [("feed_wait", False),
                                         ("apply", True)])
def test_overlap_counts_the_applier_but_not_the_feed_wait(name, covers):
    """A neighbour's ``apply`` hides a block's device wait (host work
    on another thread); its ``feed_wait`` does not (nobody works)."""
    from fabric_tpu.observe import coverage_from_spans

    rows = [(1, "device_wait", 1.0, 2.0), (2, name, 0.5, 2.5),
            (2, "launch", 3.0, 3.1)]
    got = coverage_from_spans(rows, window=1)
    assert got["blocks_measured"] == 1
    assert got["mean"] == (1.0 if covers else 0.0)


# ---------------------------------------------------------------------------
# host pool workers adopt the submitting thread's span


def test_hostpool_worker_spans_cross_thread():
    from fabric_tpu.parallel.hostpool import HostStagePool

    tr = observe.global_tracer()
    root = tr.begin_block(991)
    assert root is not None  # global default is always-on
    tok = tr.attach(root)
    try:
        with HostStagePool(2) as pool:
            assert pool.map(lambda x: x * 2, [1, 2, 3],
                            stage="unit") == [2, 4, 6]
    finally:
        tr.detach(tok)
    tasks = [c for c in root.children if c.name == "unit"]
    assert len(tasks) == 3
    assert all(c.thread.startswith("fabtpu-hoststage") for c in tasks)
    assert all("worker" in c.attrs for c in tasks)


# ---------------------------------------------------------------------------
# /trace endpoint round-trip


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.status, json.loads(r.read())


def test_trace_endpoint_roundtrip(toy_run):
    from fabric_tpu.ops_metrics import Registry
    from fabric_tpu.opsserver import HealthRegistry, OperationsServer

    tr, _ = toy_run
    reg = Registry()
    reg.histogram("validator_stage_seconds").observe(0.01, stage="finish")

    async def scenario():
        srv = await OperationsServer(
            port=0, registry=reg, health=HealthRegistry(), tracer=tr
        ).start()
        try:
            loop = asyncio.get_event_loop()
            st, idx = await loop.run_in_executor(
                None, _get, srv.port, "/trace"
            )
            assert st == 200 and idx["enabled"]
            assert idx["blocks_in_ring"] == [0, 1, 2, 3, 4]
            assert [b["block"] for b in idx["recent_blocks"]] == [1, 2, 3, 4]
            # the summary reads histograms through the LOCKED snapshot
            summ = idx["summary"]["validator_stage_seconds"]
            assert summ["stage=finish"]["count"] == 1
            # the deep-pipelining acceptance number rides the index
            cov = idx["pipeline_overlap_coverage"]
            assert cov["window"] == 2
            assert set(cov) >= {"blocks_measured", "mean", "p50", "min"}
            # and ?overlap_window= adjusts the neighbor window
            st, idx1 = await loop.run_in_executor(
                None, _get, srv.port, "/trace?overlap_window=1"
            )
            assert idx1["pipeline_overlap_coverage"]["window"] == 1
            st, tree = await loop.run_in_executor(
                None, _get, srv.port, "/trace?block=3"
            )
            assert st == 200 and tree["block"] == 3
            assert {c["name"] for c in tree["children"]} >= {
                "prefetch", "launch", "finish", "commit"
            }
            try:
                await loop.run_in_executor(
                    None, _get, srv.port, "/trace?block=77"
                )
                raise AssertionError("expected 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            await srv.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(scenario(), 30))
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# ops_metrics locked read accessors


def test_metrics_locked_accessors():
    from fabric_tpu.ops_metrics import Registry

    reg = Registry()
    c = reg.counter("c_total")
    c.add(2, channel="a")
    c.add(3, channel="a")
    assert c.value(channel="a") == 5.0
    assert c.snapshot() == {(("channel", "a"),): 5.0}
    g = reg.gauge("g")
    g.set(7, channel="a")
    assert g.value(channel="a") == 7.0
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0, float("inf")))
    assert h.value(stage="x") is None
    h.observe(0.05, stage="x")
    h.observe(0.5, stage="x")
    snap = h.value(stage="x")
    assert snap["count"] == 2 and snap["counts"] == [1, 2, 2]
    assert abs(snap["sum"] - 0.55) < 1e-9
    assert reg.metric("h_seconds") is h and reg.metric("nope") is None

    # render still emits the same exposition format off the snapshots
    text = reg.render()
    assert 'c_total{channel="a"} 5.0' in text
    assert 'h_seconds_bucket{stage="x",le="0.1"} 1' in text
    assert 'h_seconds_count{stage="x"} 2' in text


def test_metrics_concurrent_read_write_smoke():
    """Readers (render / value / snapshot) race writers without
    torn/failed reads — the bug was unlocked reads of ``_values``."""
    from fabric_tpu.ops_metrics import Registry

    reg = Registry()
    c = reg.counter("rw_total")
    h = reg.histogram("rw_seconds")
    stop = threading.Event()
    errors = []

    def writer(i):
        try:
            n = 0
            while not stop.is_set():
                # fresh label keys force dict growth mid-read
                c.add(1, worker=str(i), n=str(n % 97))
                h.observe(0.001, worker=str(i), n=str(n % 97))
                n += 1
        except Exception as e:  # surface, don't swallow
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            reg.render()
            c.value(worker="0", n="1")
            h.snapshot()
    except Exception as e:
        errors.append(e)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors


# ---------------------------------------------------------------------------
# namespaced rings + multi-process export (ISSUE 9)


def test_namespaced_rings_are_independent():
    tr = Tracer(ring_blocks=2, slow_factor=0)
    for n in range(3):
        tr.finish_block(tr.begin_block(n, channel="c"))
    for n in range(5):
        tr.finish_block(tr.begin_block(n, ns="sidecar", channel="s"))
    # the sidecar storm evicted only its own ring
    assert [b["block"] for b in tr.blocks()] == [1, 2]
    assert [b["block"] for b in tr.blocks(ns="sidecar")] == [3, 4]
    assert tr.block(1)["attrs"]["channel"] == "c"
    assert tr.block(4, ns="sidecar")["attrs"]["ns"] == "sidecar"
    assert tr.block(4) is None  # no cross-namespace shadowing
    assert tr.namespaces() == {"": 2, "sidecar": 2}
    # a resize keeps both rings (truncated)
    tr.configure(ring_blocks=1)
    assert tr.namespaces() == {"": 1, "sidecar": 1}


def test_watchdog_medians_are_per_namespace(caplog):
    """Sub-ms sidecar requests must not drag the block-commit median
    down (which would flag every normal block as slow), and vice
    versa."""
    clk = _Clock()
    tr = Tracer(ring_blocks=64, slow_factor=3.0, clock=clk)
    for n in range(10):  # blocks at a steady 100 ms
        root = tr.begin_block(n)
        clk.advance(0.100)
        tr.finish_block(root)
    for n in range(20):  # requests at a steady 1 ms, separate ns
        root = tr.begin_block(n, ns="sidecar")
        clk.advance(0.001)
        tr.finish_block(root)
    with caplog.at_level(logging.WARNING, logger="fabric_tpu.observe"):
        root = tr.begin_block(99)  # another normal 100 ms block
        clk.advance(0.100)
        tr.finish_block(root)
    # against the BLOCK median (100 ms) this is not slow; against a
    # polluted mixed median (~1 ms) it would have been 100x
    assert tr.slow_blocks() == []


def test_root_propagates_to_leaf_spans():
    tr = Tracer(ring_blocks=4, slow_factor=0)
    root = tr.begin_block(5)
    assert root.root is root
    with tr.span("launch", parent=root) as launch:
        assert launch.root is root
        with tr.span("inner") as inner:
            assert inner.root is root
        tr.add("retro", 0.0, 0.001)
    assert root.children[0].children[0].root is root
    tr.finish_block(root)


def test_span_from_dict_roundtrip_with_offset():
    from fabric_tpu.observe import span_from_dict

    tr = Tracer(ring_blocks=4, slow_factor=0)
    root = tr.begin_block(3, channel="x")
    with tr.span("dispatch", parent=root, n=2):
        pass
    tr.event("note", parent=root)
    tr.finish_block(root)
    d = root.to_dict(0.0)  # absolute times, the wire form
    sp = span_from_dict(d, offset_s=10.0, proc="sidecar")
    assert sp.proc == "sidecar" and sp.children[0].proc == "sidecar"
    assert sp.t0 == pytest.approx(root.t0 - 10.0, abs=1e-3)
    assert sp.children[0].name == "dispatch"
    assert sp.children[0].attrs == {"n": 2}
    assert sp.events[0][0] == "note"
    assert sp.children[0].t0 == pytest.approx(
        root.children[0].t0 - 10.0, abs=1e-3
    )


def test_traceview_renders_multiprocess_dump():
    """Satellite: merged peer+sidecar trees render with per-process
    labels and the clock-offset annotation, both input forms."""
    import traceview
    from fabric_tpu.observe import span_from_dict

    tr = Tracer(ring_blocks=4, slow_factor=0)
    root = tr.begin_block(11, channel="chanA")
    with tr.span("sig_prepare_launch", parent=root):
        pass
    # a stitched remote subtree, the client shape
    remote_src = Tracer(ring_blocks=4, slow_factor=0)
    rroot = remote_src.begin_block(1, ns="sidecar",
                                   channel="sidecar:chanA")
    remote_src.add("queue_wait", rroot.t0, rroot.t0 + 0.001,
                   parent=rroot)
    remote_src.add("dispatch", rroot.t0 + 0.001, rroot.t0 + 0.003,
                   parent=rroot)
    remote_src.end(rroot)
    sp = span_from_dict(rroot.to_dict(0.0), offset_s=-0.002,
                        proc="sidecar")
    sp.name = "sidecar_request"
    sp.attrs["clock_offset_ms"] = -2.0
    sp.attrs["rtt_ms"] = 0.4
    root.children.append(sp)
    tr.finish_block(root)

    # /trace-dump form
    text = traceview.render(tr.block(11))
    assert "sidecar:" in text            # per-process row label
    assert "clock offset -2.000 ms" in text
    assert "queue_wait" in text and "dispatch" in text

    # Chrome form: distinct pid + process_name metadata
    data = {"traceEvents": tr.chrome_events()}
    text = traceview.render(data, block=11)
    assert "sidecar:" in text
    assert "clock offset -2.000 ms" in text
    assert "sig_prepare_launch" in text


# ---------------------------------------------------------------------------
# overlap-coverage analyzer (observe/overlap.py)


def _cov_rows():
    """Hand-built timeline: block 1's device_wait [10.00, 10.10);
    block 0's commit covers [10.00, 10.05), block 3's prefetch (a
    DISTANCE-2 neighbor) [10.05, 10.08) — union coverage 0.8 at
    window 2, 0.5 at window 1.  Block 6 sits outside every window.
    Non-host spans (commit_wait) and SAME-block host work must not
    count."""
    return [
        (0, "commit", 10.00, 10.05),
        (1, "device_wait", 10.00, 10.10),
        (1, "host_parse", 10.00, 10.10),    # own block: never counts
        (3, "prefetch", 10.05, 10.08),
        (3, "commit_wait", 10.00, 10.20),   # pure wait: never counts
        (6, "device_wait", 20.00, 20.10),   # no in-window neighbor
    ]


def test_overlap_coverage_math():
    from fabric_tpu.observe import overlap

    cov = overlap.coverage_from_spans(_cov_rows(), window=2)
    assert cov["window"] == 2
    per = {b["block"]: b for b in cov["per_block"]}
    assert per[1]["coverage"] == pytest.approx(0.8)
    assert per[1]["device_wait_ms"] == pytest.approx(100.0)
    assert per[1]["covered_ms"] == pytest.approx(80.0)
    # block 6 has NO in-window neighbor at all → skipped entirely
    assert 6 not in per
    assert cov["blocks_measured"] == 1
    assert cov["min"] == pytest.approx(0.8)

    # window 1: block 0's commit is the only neighbor of block 1 —
    # block 3's prefetch falls out of the window
    cov1 = overlap.coverage_from_spans(_cov_rows(), window=1)
    per1 = {b["block"]: b for b in cov1["per_block"]}
    assert per1[1]["coverage"] == pytest.approx(0.5)
    assert cov1["blocks_measured"] == 1


def test_overlap_coverage_union_no_double_count():
    """Nested/overlapping host spans union — a container span plus
    its children must not count twice."""
    from fabric_tpu.observe import overlap

    rows = [
        (1, "device_wait", 0.0, 1.0),
        (0, "commit", 0.0, 0.6),
        (0, "ledger_commit", 0.0, 0.5),   # nested inside commit
        (0, "fsync", 0.5, 0.6),           # ditto
    ]
    cov = overlap.coverage_from_spans(rows, window=1)
    assert cov["per_block"][0]["coverage"] == pytest.approx(0.6)


def _device_wait_tracer():
    """A tracer whose trees carry device_wait spans with a known
    overlap shape — 3 blocks, each block's device_wait half-covered by
    its predecessor's commit."""
    clk = _Clock()
    tr = Tracer(ring_blocks=8, slow_factor=0, clock=clk)
    for n in range(3):
        base = 10.0 * n
        root = tr.begin_block(n)
        root.t0 = base
        tr.add("launch", base, base + 1.0, parent=root)
        tr.add("device_wait", base + 1.0, base + 5.0, parent=root)
        if n + 1 < 3:
            # predecessor's commit overlaps HALF the successor's wait
            tr.add("commit", base + 11.0, base + 13.0, parent=root)
        root.t1 = base + 9.0
        tr.finish_block(root)
    return tr


def test_overlap_coverage_all_three_input_forms():
    """The live-roots, /trace-dump (t0_s anchored), and Chrome-event
    forms of the SAME flight recorder must agree."""
    from fabric_tpu.observe import overlap

    tr = _device_wait_tracer()
    live = overlap.coverage_from_roots(tr.recent_roots(), window=2)
    dump = overlap.coverage_from_trace_dump(
        {"recent_blocks": tr.blocks(), "slow_blocks": []}, window=2
    )
    chrome = overlap.coverage_from_spans(
        overlap.spans_from_chrome(tr.chrome_events()), window=2
    )
    assert live["blocks_measured"] == dump["blocks_measured"] \
        == chrome["blocks_measured"] > 0
    # block 1's wait [11, 15] is covered by block 0's commit [11, 13]
    per = {b["block"]: b for b in live["per_block"]}
    assert per[1]["coverage"] == pytest.approx(0.5)
    for a, b in ((live, dump), (live, chrome)):
        for x, y in zip(a["per_block"], b["per_block"]):
            assert x["block"] == y["block"]
            assert x["coverage"] == pytest.approx(y["coverage"],
                                                  abs=1e-3)

    # a dump with no t0_s anchors (pre-upgrade capture) returns None
    old = [{k: v for k, v in b.items() if k != "t0_s"}
           for b in tr.blocks()]
    assert overlap.coverage_from_trace_dump(
        {"recent_blocks": old, "slow_blocks": []}
    ) is None


def test_traceview_coverage_table():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "traceview", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "traceview.py",
        ),
    )
    traceview = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(traceview)

    tr = _device_wait_tracer()
    dump = {"recent_blocks": tr.blocks(), "slow_blocks": [],
            "blocks_in_ring": [b["block"] for b in tr.blocks()]}
    text = traceview.render_coverage(dump, window=2)
    assert "pipeline overlap coverage" in text
    assert "device_wait" in text
    chrome = {"traceEvents": tr.chrome_events()}
    text2 = traceview.render_coverage(chrome, window=2)
    assert "pipeline overlap coverage" in text2
