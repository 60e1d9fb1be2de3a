"""Flight-data recorder battery (fabric_tpu.observe.timeseries +
.blackbox) — crypto-free, injected clock.

Layers:

* sampler delta semantics for all three metric kinds (counter deltas,
  gauge levels, histogram interval {n, sum, p99}), ring retention and
  live resize, counter-reset clamping, and the OFF contract — no
  sampler thread exists and no global state is built;
* black-box trigger edges: DeviceLaneGuard degrade latch, autopilot
  SHED decision, SLO fast burn, CommitPipeline ``_fail_closed``, and
  the injected-crash last-gasp path via a CHILD process;
* bundle bounds: per-kind rate limiting and the size cap's honest
  ``truncated`` section list;
* ``/vitals`` round-trip over a live OperationsServer (index +
  ?metric + ?incident + 404s + unarmed honesty).
"""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import pytest

from fabric_tpu.observe import blackbox, timeseries
from fabric_tpu.observe.timeseries import MetricsSampler
from fabric_tpu.ops_metrics import Registry


class Clock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test leaves the process-global recorder OFF — the default
    contract the acceptance pins."""
    yield
    timeseries.configure(0)
    blackbox.configure(enabled=False)


def _sampler(clk, retention=8):
    reg = Registry()
    return reg, MetricsSampler(interval_s=1.0, retention=retention,
                               registry=reg, clock=clk)


# ---------------------------------------------------------------------------
# sampler delta semantics


def test_counter_series_records_deltas_not_monotones():
    clk = Clock()
    reg, s = _sampler(clk)
    c = reg.counter("reqs_total", "t")
    c.add(5, tenant="a")
    s.sample()
    clk.advance(1.0)
    c.add(2, tenant="a")
    s.sample()
    clk.advance(1.0)
    s.sample()  # idle interval → delta 0
    pts = s.series()["reqs_total"]["tenant=a"]["points"]
    assert [v for _t, v in pts] == [5.0, 2.0, 0.0]
    # rate over the trailing window divides deltas by elapsed time
    assert s.rate("reqs_total", tenant="a") == pytest.approx(1.0)

def test_counter_reset_clamps_to_new_level():
    clk = Clock()
    reg, s = _sampler(clk)
    c = reg.counter("x_total", "t")
    c.add(10)
    s.sample()
    # a "reset" (negative delta) records the new raw level, never a
    # negative rate
    with c._lock:
        c._values[()] = 3.0
    clk.advance(1.0)
    s.sample()
    pts = s.series()["x_total"]["_"]["points"]
    assert [v for _t, v in pts] == [10.0, 3.0]


def test_gauge_series_records_levels():
    clk = Clock()
    reg, s = _sampler(clk)
    g = reg.gauge("depth", "t")
    g.set(3, tenant="a")
    s.sample()
    clk.advance(1.0)
    g.set(1, tenant="a")
    s.sample()
    pts = s.series()["depth"]["tenant=a"]["points"]
    assert [v for _t, v in pts] == [3.0, 1.0]


def test_histogram_series_records_interval_deltas_and_p99():
    clk = Clock()
    reg, s = _sampler(clk)
    h = reg.histogram("lat_s", "t")
    h.observe(0.002)
    h.observe(0.3)
    s.sample()
    clk.advance(1.0)
    h.observe(0.004)
    s.sample()
    clk.advance(1.0)
    s.sample()
    pts = [p for _t, p in s.series()["lat_s"]["_"]["points"]]
    # first interval: both observations; p99 covers the slow one
    assert pts[0]["n"] == 2 and pts[0]["sum"] == pytest.approx(0.302)
    assert pts[0]["p99"] == 0.5
    # second interval: ONLY the new observation — not the cumulative
    assert pts[1]["n"] == 1 and pts[1]["sum"] == pytest.approx(0.004)
    assert pts[1]["p99"] == 0.005
    # idle interval: empty, p99 None (no traffic is not a latency)
    assert pts[2] == {"n": 0, "sum": 0.0, "p99": None}
    # the report's sparkline carries interval p99s
    rep = s.report()["metrics"]["lat_s"]["_"]
    assert rep["kind"] == "histogram" and rep["spark"] == [0.5, 0.005]


# ---------------------------------------------------------------------------
# retention, resize, validation, OFF contract


def test_ring_retention_and_live_resize():
    clk = Clock()
    reg, s = _sampler(clk, retention=4)
    g = reg.gauge("v", "t")
    for i in range(7):
        g.set(i)
        s.sample()
        clk.advance(1.0)
    pts = s.series()["v"]["_"]["points"]
    assert len(pts) == 4 and [v for _t, v in pts] == [3.0, 4.0, 5.0, 6.0]
    s.configure(retention=2)
    pts = s.series()["v"]["_"]["points"]
    assert [v for _t, v in pts] == [5.0, 6.0]
    # and the next samples respect the new bound
    g.set(9)
    s.sample()
    assert len(s.series()["v"]["_"]["points"]) == 2


def test_sampler_validation():
    with pytest.raises(ValueError):
        MetricsSampler(interval_s=-1, registry=Registry())
    with pytest.raises(ValueError):
        MetricsSampler(retention=0, registry=Registry())
    _reg, s = _sampler(Clock())
    with pytest.raises(ValueError):
        s.configure(retention=0)


def test_recorder_off_means_no_thread_and_no_global():
    """The acceptance's OFF half: interval 0 builds nothing."""
    assert timeseries.configure(0) is None
    assert timeseries.global_sampler() is None
    assert not any(
        t.name == "fabtpu-vitals" for t in threading.enumerate()
    )
    # and arming then disarming stops the thread
    s = timeseries.configure(0.05, retention=4, registry=Registry())
    assert s is not None and timeseries.global_sampler() is s
    assert any(t.name == "fabtpu-vitals" for t in threading.enumerate())
    timeseries.configure(0)
    assert timeseries.global_sampler() is None
    for t in threading.enumerate():
        assert t.name != "fabtpu-vitals" or not t.is_alive()


def test_acquire_release_refcounts_colocated_holders(tmp_path):
    """Two colocated nodes share ONE sampler and ONE recorder; the
    first stop() — creator or not — must not strand the survivor,
    and the last one out disarms.  (PeerNode start/stop pairs
    acquire/release.)"""
    s1 = timeseries.acquire(0.05, retention=4, registry=Registry())
    s2 = timeseries.acquire(0.05, retention=4)
    assert s1 is s2 and timeseries.global_sampler() is s1
    b1 = blackbox.acquire(out_dir=str(tmp_path), sampler=s1)
    b2 = blackbox.acquire(out_dir=str(tmp_path / "other"))
    # second acquire REUSES the live recorder (first-arm wins for the
    # out_dir wiring — replacing would discard b1's incident index)
    assert b1 is b2 and blackbox.global_blackbox() is b1
    timeseries.release()           # first node stops...
    blackbox.release()
    assert timeseries.global_sampler() is s1   # ...survivor keeps both
    assert blackbox.global_blackbox() is b1
    timeseries.release()           # last one out disarms
    blackbox.release()
    assert timeseries.global_sampler() is None
    assert blackbox.global_blackbox() is None
    # the hard OFF (configure) zeroes the refcount for the next test
    s3 = timeseries.acquire(0.05, retention=4, registry=Registry())
    assert s3 is not None
    timeseries.configure(0)
    assert timeseries.global_sampler() is None
    timeseries.release()           # over-release after hard OFF: no-op
    assert timeseries.global_sampler() is None
    # interval<=0 acquires nothing and holds nothing
    assert timeseries.acquire(0) is None
    timeseries.release()


def test_nodeconfig_validates_vitals_knobs():
    from fabric_tpu.nodeconfig import ConfigError, load_peer_config

    base = {"id": "p", "data_dir": "/tmp/x", "msp_id": "m",
            "msp_dir": "/tmp/m"}
    with pytest.raises(ConfigError, match="vitals_interval_s"):
        load_peer_config({**base, "vitals_interval_s": -1}, environ={})
    with pytest.raises(ConfigError, match="vitals_retention"):
        load_peer_config({**base, "vitals_retention": 0}, environ={})
    cfg = load_peer_config(
        {**base, "vitals_interval_s": 2.5, "vitals_retention": 32,
         "blackbox_dir": "/tmp/bb"}, environ={},
    )
    assert cfg.vitals_interval_s == 2.5
    assert cfg.vitals_retention == 32
    assert cfg.blackbox_dir == "/tmp/bb"


# ---------------------------------------------------------------------------
# black-box trigger edges


def test_degrade_latch_produces_exactly_one_bundle(tmp_path):
    """THE acceptance edge: a SEEDED fault that latches the degrade
    guard produces exactly one bundle carrying the decision log, the
    metric trails, and the trace trees."""
    from fabric_tpu import faults
    from fabric_tpu.control import Autopilot, Signals
    from fabric_tpu.observe import Tracer
    from fabric_tpu.peer.degrade import DeviceLaneGuard

    clk = Clock()
    reg, s = _sampler(clk)
    reg.counter("fallback_seen_total", "t").add(3, channel="ch1")
    s.sample()
    tr = Tracer(ring_blocks=4, slow_factor=0)
    tr.finish_block(tr.begin_block(7, channel="ch1"))
    # an autopilot with one prior actuation in its log — the bundle
    # must carry the decision history, not just the moment
    ap = Autopilot(None, lambda k, v: None,
                   tracer=Tracer(ring_blocks=4, slow_factor=0,
                                 clock=clk),
                   clock=clk, registry=reg)
    d = ap.tick(Signals(queue_age_p99_ms={"t1": 500.0}, clock_s=clk()))
    assert d is not None and d.knob == "coalesce_blocks"
    bb = blackbox.configure(
        out_dir=str(tmp_path), sampler=s, tracer=tr, autopilot=ap,
        clock=clk, registry=reg,
    )
    guard = DeviceLaneGuard(fail_threshold=2, retries=1,
                            channel="ch1", registry=reg, clock=clk,
                            sleep=lambda _s: None)
    plan = faults.FaultPlan("validator.verify_launch:raise:n=4",
                            seed=7)
    faults.install(plan)
    try:
        # one guarded launch = 2 seeded failed attempts → the latch
        out = guard.run_launch(lambda: "device",
                               lambda: "cpu-fallback")
        assert out == "cpu-fallback" and guard.degraded
    finally:
        faults.reset()
    idx = bb.bundles()
    assert len(idx) == 1 and idx[0]["kind"] == "degrade_latch"
    assert idx[0]["detail"]["channel"] == "ch1"
    assert idx[0]["detail"]["consecutive_failures"] == 2
    bundle = bb.bundle(idx[0]["seq"])
    # decision log + trails + trace trees all rode along
    assert bundle["autopilot"]["decisions"][0]["knob"] == (
        "coalesce_blocks"
    )
    assert "fallback_seen_total" in bundle["vitals"]
    assert bundle["traces"]["_"][0]["block"] == 7
    # the seeded plan's own stats are in the bundle too
    assert bundle["faults"]["validator.verify_launch"][0]["fired"] == 2
    # a SECOND latch inside the rate-limit window records nothing new
    guard.record_success()
    guard.record_failure(RuntimeError("again"))
    guard.record_failure(RuntimeError("again"))
    assert len(bb.bundles()) == 1
    # and the bundle landed on disk, bounded-name form
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["blackbox-0001-degrade_latch.json"]
    on_disk = json.loads((tmp_path / files[0]).read_text())
    assert on_disk["kind"] == "degrade_latch"


def test_autopilot_shed_decision_records_bundle():
    from fabric_tpu.control import Autopilot, Signals
    from fabric_tpu.observe import Tracer

    clk = Clock()
    reg, s = _sampler(clk)
    ap = Autopilot(
        None, lambda k, v: None, set_shed=lambda t, on: None,
        tracer=Tracer(ring_blocks=8, slow_factor=0, clock=clk),
        clock=clk, registry=reg,
    )
    bb = blackbox.configure(sampler=s, autopilot=ap, clock=clk,
                            registry=reg)
    d = ap.tick(Signals(burn={("lat", "sidecar:noisy"): 9.0},
                        clock_s=clk()))
    assert (d.knob, d.direction) == ("shed", "on")
    idx = bb.bundles()
    assert len(idx) == 1 and idx[0]["kind"] == "autopilot_shed"
    assert idx[0]["detail"]["tenant"] == "noisy"
    # the decision log itself is in the bundle (explicit source)
    bundle = bb.bundle(idx[0]["seq"])
    assert bundle["autopilot"]["decisions"][0]["knob"] == "shed"


def test_slo_fast_burn_records_bundle():
    from fabric_tpu.observe.slo import Objective, SloEngine

    clk = Clock()
    reg, s = _sampler(clk)
    bb = blackbox.configure(sampler=s, clock=clk, registry=reg)
    eng = SloEngine(
        [Objective(name="lat", kind="latency", ms=10.0,
                   windows=(60.0,), min_events=1)],
        clock=clk, registry=reg,
    )
    for _ in range(3):
        eng.record(eng.objectives[0], "ch1", good=False)
    idx = bb.bundles()
    assert len(idx) == 1 and idx[0]["kind"] == "slo_fast_burn"
    assert idx[0]["detail"]["slo"] == "lat"
    assert idx[0]["detail"]["channel"] == "ch1"


def test_pipeline_fail_closed_records_bundle():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_commit_pipeline import ToyValidator, _stream

    from fabric_tpu.ledger.statedb import MemVersionedDB
    from fabric_tpu.peer.pipeline import CommitPipeline

    clk = Clock()
    reg, s = _sampler(clk)
    bb = blackbox.configure(sampler=s, clock=clk, registry=reg)
    blocks = _stream(n_blocks=3)
    v = ToyValidator(MemVersionedDB())

    def commit_fn(res):
        raise RuntimeError("committer wedged")

    pipe = CommitPipeline(v, commit_fn, depth=2, channel="ch1")
    with pytest.raises(RuntimeError):
        for b in blocks:
            pipe.submit(b)
        pipe.flush()
    idx = bb.bundles()
    assert len(idx) == 1 and idx[0]["kind"] == "pipeline_fail_closed"
    assert idx[0]["detail"]["channel"] == "ch1"
    assert idx[0]["detail"]["stage"] == "commit"
    # pipe latched closed exactly as before (the edge observes, never
    # changes containment semantics)
    with pytest.raises(RuntimeError, match="closed"):
        pipe.submit(blocks[0])


def test_injected_crash_dumps_bundle_in_child(tmp_path):
    """The last-gasp path: a FaultPlan ``crash`` fault hard-exits the
    child with 86, but not before the armed recorder writes its
    bundle (the one edge atexit can never see)."""
    script = r"""
import sys
from fabric_tpu import faults
from fabric_tpu.observe import blackbox
blackbox.configure(out_dir=sys.argv[1])
faults.configure("toy.point:crash")
faults.fire("toy.point")
raise SystemExit("unreachable: the crash fault must exit first")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 86, (proc.stdout, proc.stderr)
    files = [p for p in tmp_path.iterdir()
             if p.name.endswith("injected_crash.json")]
    assert len(files) == 1, list(tmp_path.iterdir())
    bundle = json.loads(files[0].read_text())
    assert bundle["kind"] == "injected_crash"
    assert bundle["detail"]["point"] == "toy.point"
    # the chaos plan's own stats made it into the bundle
    assert bundle["faults"]["toy.point"][0]["fired"] == 1


def test_atexit_flushes_fault_stats_for_bundle_less_chaos_run(tmp_path):
    """A chaos-armed process that fired faults but recorded no
    incident bundle still leaves ONE stats bundle at clean exit."""
    script = r"""
import sys
from fabric_tpu import faults
from fabric_tpu.observe import blackbox
blackbox.configure(out_dir=sys.argv[1])
faults.configure("toy.point:raise:n=1")
try:
    faults.fire("toy.point")
except faults.InjectedFault:
    pass
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    files = [p for p in tmp_path.iterdir()
             if p.name.endswith("fault_stats_at_exit.json")]
    assert len(files) == 1, list(tmp_path.iterdir())


# ---------------------------------------------------------------------------
# bundle bounds


def test_rate_limit_is_per_kind_and_expires():
    clk = Clock()
    reg, s = _sampler(clk)
    bb = blackbox.BlackBox(sampler=s, clock=clk, registry=reg,
                           min_interval_s=30.0)
    assert bb.record("degrade_latch") is not None
    assert bb.record("degrade_latch") is None        # limited
    assert bb.record("autopilot_shed") is not None   # other kind flows
    clk.advance(31.0)
    assert bb.record("degrade_latch") is not None    # window expired
    assert reg.counter(
        "blackbox_rate_limited_total", ""
    ).value(kind="degrade_latch") == 1


def test_size_bound_drops_sections_honestly():
    from fabric_tpu.observe import Tracer

    clk = Clock()
    reg, s = _sampler(clk, retention=256)
    g = reg.gauge("wide", "t")
    for i in range(400):  # many label variants × many points
        g.set(i, series=f"s{i % 40}")
    for _ in range(64):
        s.sample()
        clk.advance(1.0)
    bb = blackbox.BlackBox(sampler=s, clock=clk, registry=reg,
                           tracer=Tracer(ring_blocks=0),
                           max_bytes=20_000)
    bundle = bb.record("degrade_latch", channel="ch1")
    assert len(json.dumps(bundle)) <= 20_000
    assert "vitals" in bundle.get("truncated", [])
    assert bundle["detail"]["channel"] == "ch1"  # the header survives
    # index names the truncation
    assert bb.bundles()[0]["truncated"] == bundle["truncated"]


def test_restart_resumes_seq_and_prunes_prior_run_files(tmp_path):
    """A restarted recorder (the crash-then-restart flow it exists
    for) must never overwrite the crashed run's bundles, and the disk
    cap must count prior-run files."""
    clk = Clock()
    reg, s = _sampler(clk)
    kw = dict(sampler=s, clock=clk, registry=reg, max_bundles=3,
              min_interval_s=0.0, out_dir=str(tmp_path))
    bb1 = blackbox.BlackBox(**kw)
    bb1.record("degrade_latch")
    bb1.record("injected_crash")
    first_run = sorted(p.name for p in tmp_path.iterdir())
    assert first_run == ["blackbox-0001-degrade_latch.json",
                        "blackbox-0002-injected_crash.json"]
    # "restart": a fresh recorder over the same directory
    bb2 = blackbox.BlackBox(**kw)
    bb2.record("degrade_latch")
    bb2.record("autopilot_shed")
    names = sorted(p.name for p in tmp_path.iterdir())
    # seq resumed past the prior run, nothing overwritten, and the
    # oldest prior-run file was pruned to honor max_bundles=3
    assert names == ["blackbox-0002-injected_crash.json",
                     "blackbox-0003-degrade_latch.json",
                     "blackbox-0004-autopilot_shed.json"]


def test_bundle_ring_is_bounded(tmp_path):
    clk = Clock()
    reg, s = _sampler(clk)
    bb = blackbox.BlackBox(sampler=s, clock=clk, registry=reg,
                           max_bundles=3, min_interval_s=0.0,
                           out_dir=str(tmp_path))
    for i in range(6):
        clk.advance(1.0)
        assert bb.record(f"kind{i}") is not None
    idx = bb.bundles()
    assert [b["kind"] for b in idx] == ["kind3", "kind4", "kind5"]
    assert len(list(tmp_path.iterdir())) == 3  # disk bounded too


# ---------------------------------------------------------------------------
# /vitals round-trip


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.status, json.loads(r.read())


def test_vitals_endpoint_roundtrip():
    import asyncio

    from fabric_tpu.opsserver import HealthRegistry, OperationsServer

    clk = Clock()
    reg, s = _sampler(clk)
    c = reg.counter("reqs_total", "t")
    c.add(4, tenant="a")
    s.sample()
    clk.advance(1.0)
    c.add(1, tenant="a")
    s.sample()
    bb = blackbox.BlackBox(sampler=s, clock=clk, registry=reg)
    bb.record("degrade_latch", channel="ch1")

    async def scenario():
        srv = await OperationsServer(
            port=0, registry=reg, health=HealthRegistry(),
            vitals=s, blackbox=bb,
        ).start()
        try:
            loop = asyncio.get_event_loop()
            st, idx = await loop.run_in_executor(
                None, _get, srv.port, "/vitals"
            )
            assert st == 200 and idx["enabled"]
            assert idx["samples"] == 2
            spark = idx["metrics"]["reqs_total"]["tenant=a"]
            assert spark["kind"] == "counter"
            assert spark["spark"] == [4.0, 1.0]
            assert [b["kind"] for b in idx["incidents"]] == [
                "degrade_latch"
            ]
            st, m = await loop.run_in_executor(
                None, _get, srv.port, "/vitals?metric=reqs_total"
            )
            assert st == 200
            pts = m["series"]["tenant=a"]["points"]
            assert [v for _t, v in pts] == [4.0, 1.0]
            st, b = await loop.run_in_executor(
                None, _get, srv.port, "/vitals?incident=1"
            )
            assert st == 200 and b["kind"] == "degrade_latch"
            for bad in ("/vitals?metric=nope", "/vitals?incident=99"):
                try:
                    await loop.run_in_executor(
                        None, _get, srv.port, bad
                    )
                    raise AssertionError(f"expected 404 for {bad}")
                except urllib.error.HTTPError as e:
                    assert e.code == 404
        finally:
            await srv.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(scenario(), 30))
    finally:
        loop.close()


def test_vitals_metric_label_filter_and_exemplars():
    """/vitals?metric=N&label=k=v keeps only the matching variants —
    one metric with many label variants no longer returns every ring.
    404 semantics unchanged for unknown metrics; an exemplar-armed
    histogram's rings ride the payload."""
    import asyncio

    from fabric_tpu.opsserver import HealthRegistry, OperationsServer

    clk = Clock()
    reg, s = _sampler(clk)
    c = reg.counter("reqs_total", "t")
    c.add(4, tenant="a")
    c.add(9, tenant="b")
    h = reg.histogram("stage_seconds", "t", exemplars=2)
    h.observe(0.5, exemplar="blk7", stage="launch")
    s.sample()

    async def scenario():
        srv = await OperationsServer(
            port=0, registry=reg, health=HealthRegistry(), vitals=s,
        ).start()
        try:
            loop = asyncio.get_event_loop()
            st, m = await loop.run_in_executor(
                None, _get, srv.port,
                "/vitals?metric=reqs_total&label=tenant=a",
            )
            assert st == 200
            assert list(m["series"]) == ["tenant=a"]
            # no filter still returns every variant (unchanged)
            st, m2 = await loop.run_in_executor(
                None, _get, srv.port, "/vitals?metric=reqs_total"
            )
            assert sorted(m2["series"]) == ["tenant=a", "tenant=b"]
            # exemplar-armed histogram: rings ride the payload
            st, m3 = await loop.run_in_executor(
                None, _get, srv.port, "/vitals?metric=stage_seconds"
            )
            assert m3["exemplars"]["stage=launch"] == [[0.5, "blk7"]]
            # 404s: unknown metric (unchanged), and a label matching
            # no variant of a known metric
            for bad in ("/vitals?metric=nope&label=tenant=a",
                        "/vitals?metric=reqs_total&label=tenant=zz"):
                try:
                    await loop.run_in_executor(None, _get, srv.port, bad)
                    raise AssertionError(f"expected 404 for {bad}")
                except urllib.error.HTTPError as e:
                    assert e.code == 404
        finally:
            await srv.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(scenario(), 30))
    finally:
        loop.close()


def test_vitals_endpoint_unarmed_is_honest():
    import asyncio

    from fabric_tpu.opsserver import HealthRegistry, OperationsServer

    async def scenario():
        srv = await OperationsServer(
            port=0, registry=Registry(), health=HealthRegistry(),
        ).start()
        try:
            loop = asyncio.get_event_loop()
            st, idx = await loop.run_in_executor(
                None, _get, srv.port, "/vitals"
            )
            assert st == 200
            assert idx["enabled"] is False
            assert idx["incidents"] == []
            assert "metrics" not in idx
        finally:
            await srv.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(scenario(), 30))
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# recorder armed over a real crypto-free pipeline run: delta-correct
# series for all three kinds off live traffic (the acceptance's ON half)


def test_recorder_over_live_pipeline_run():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_commit_pipeline import ToyValidator, _stream

    from fabric_tpu.ledger.statedb import MemVersionedDB
    from fabric_tpu.observe import Tracer
    from fabric_tpu.ops_metrics import global_registry
    from fabric_tpu.peer.pipeline import CommitPipeline

    reg = global_registry()  # the pipeline publishes here
    s = MetricsSampler(interval_s=1.0, retention=64, registry=reg)
    s.sample()  # baseline pass: later deltas cover ONLY this run
    state = MemVersionedDB()
    v = ToyValidator(state)
    committed = []

    def commit_fn(res):
        state.apply_updates(res.batch, (res.block.header.number, 0))
        committed.append(res.block.header.number)

    tr = Tracer(ring_blocks=8, slow_factor=0)
    with CommitPipeline(v, commit_fn, depth=2, channel="vit",
                        tracer=tr) as pipe:
        for b in _stream(n_blocks=4):
            pipe.submit(b)
    assert committed and sorted(committed) == [0, 1, 2, 3]
    s.sample()
    series = s.series()
    # counter: the pipelined-block count delta equals this run's blocks
    ctr = series["commit_pipeline_blocks_total"]
    run_total = sum(
        v for labels, sr in ctr.items()
        if "channel=vit" in labels for _t, v in sr["points"]
    )
    assert run_total == 4
    # gauge: inflight ended drained at 0
    g = series["commit_pipeline_inflight"]["channel=vit"]
    assert g["kind"] == "gauge" and g["points"][-1][1] == 0.0
    # histogram: stage seconds saw exactly this run's finish count
    h = series["commit_pipeline_stage_seconds"]
    fin = [sr for labels, sr in h.items()
           if "channel=vit" in labels and "stage=finish" in labels]
    assert len(fin) == 1
    assert sum(p["n"] for _t, p in fin[0]["points"]) == 4


# ---------------------------------------------------------------------------
# blackbox_view renders a bundle as a text postmortem


def test_blackbox_view_renders_postmortem(tmp_path):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    import blackbox_view

    from fabric_tpu.control import Autopilot, Signals
    from fabric_tpu.observe import Tracer

    clk = Clock()
    reg, s = _sampler(clk)
    c = reg.counter("reqs_total", "t")
    for i in range(5):
        c.add(i + 1, tenant="a")
        s.sample()
        clk.advance(1.0)
    tr = Tracer(ring_blocks=4, slow_factor=0, clock=clk)
    tr.finish_block(tr.begin_block(3, channel="ch1"))
    ap = Autopilot(None, lambda k, v: None,
                   set_shed=lambda t, on: None, tracer=tr, clock=clk,
                   registry=reg)
    ap.tick(Signals(burn={("lat", "sidecar:noisy"): 9.0},
                    clock_s=clk()))
    bb = blackbox.BlackBox(sampler=s, tracer=tr, autopilot=ap,
                           clock=clk, registry=reg,
                           out_dir=str(tmp_path))
    bundle = bb.record("autopilot_shed", tenant="noisy")
    text = blackbox_view.render_bundle(bundle)
    assert "incident: autopilot_shed" in text
    assert "reqs_total{tenant=a}" in text
    assert "shed" in text and "burn" in text
    assert "block 3" in text  # the trace waterfall rode along
    # the CLI end of it renders the on-disk file too
    path = next(tmp_path.iterdir())
    rc = blackbox_view.main([str(path), "--no-traces"])
    assert rc == 0
