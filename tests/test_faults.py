"""Chaos-hardening battery (fabric_tpu.faults + peer.degrade +
utils.backoff): fault-plan mechanics, the device-lane degradation
state machine, and the two acceptance differentials —

* a seeded FaultPlan (device faults + a host-pool worker fault + one
  injected mid-stream disconnect + a commit fault) driven through a
  depth-2 CommitPipeline commits the EXACT block/tx accept-set of a
  fault-free serial run (crypto-free toy validator);
* a kill-mid-fsync child process leaves a ledger that reopens at a
  consistent height, replays state, and keeps accepting blocks.
"""

import json
import os
import subprocess
import sys
import textwrap
from dataclasses import dataclass

import pytest

from fabric_tpu import faults
from fabric_tpu import protoutil as pu
from fabric_tpu.faults import FaultPlan, FaultSpecError, InjectedFault
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
from fabric_tpu.peer.degrade import DeviceLaneGuard
from fabric_tpu.peer.pipeline import CommitPipeline
from fabric_tpu.utils.backoff import Backoff

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with no armed global plan."""
    faults.reset()
    yield
    faults.reset()


# -- FaultPlan mechanics ----------------------------------------------------


class TestFaultPlan:
    def test_parse_errors_name_the_problem(self):
        for bad in ("point-only", "p:unknownkind", "p:raise:p=2",
                    "p:raise:bogus=1", "p:latency", "p:raise:n=x"):
            with pytest.raises(FaultSpecError):
                FaultPlan(bad)

    def test_raise_budget_and_after(self):
        p = FaultPlan("x:raise:n=2:after=1")
        p.fire("x")  # after=1: first arrival passes
        with pytest.raises(InjectedFault):
            p.fire("x")
        with pytest.raises(InjectedFault):
            p.fire("x")
        p.fire("x")  # budget n=2 exhausted
        assert p.fired("x") == 2
        s = p.stats()["x"][0]
        assert s == {"kind": "raise", "arrivals": 4, "fired": 2}

    def test_unmatched_points_never_trigger(self):
        p = FaultPlan("x:raise")
        p.fire("y")  # no rule for y
        assert p.fired() == 0

    def test_probability_is_seed_deterministic(self):
        def run(seed):
            p = FaultPlan("x:raise:p=0.5", seed=seed)
            hits = []
            for _ in range(32):
                try:
                    p.fire("x")
                    hits.append(0)
                except InjectedFault:
                    hits.append(1)
            return hits

        a, b = run(7), run(7)
        assert a == b
        assert 0 < sum(a) < 32  # actually probabilistic
        assert run(8) != a      # and seed-sensitive

    def test_probability_replay_survives_other_points_interleaving(self):
        """Each rule draws from its OWN seeded RNG: arrivals at OTHER
        points (whose thread interleaving varies run to run) must not
        shift which of THIS point's arrivals fire."""
        def run(noise_every):
            p = FaultPlan("x:raise:p=0.5;y:raise:p=0.5", seed=7)
            hits = []
            for i in range(32):
                if noise_every and i % noise_every == 0:
                    try:
                        p.fire("y")  # a differently-interleaved thread
                    except InjectedFault:
                        pass
                try:
                    p.fire("x")
                    hits.append(0)
                except InjectedFault:
                    hits.append(1)
            return hits

        assert run(0) == run(1) == run(3)

    def test_latency_sleeps(self):
        import time

        p = FaultPlan("x:latency:ms=30:n=1")
        t0 = time.perf_counter()
        p.fire("x")
        assert time.perf_counter() - t0 >= 0.025
        p.fire("x")  # budget spent: no sleep

    def test_afire_latency_keeps_the_event_loop_live(self):
        """The async hook must asyncio.sleep a latency fault so other
        tasks keep running, and still raise the raising kinds."""
        import asyncio

        faults.configure("d.read:latency:ms=60:n=1;d.cut:disconnect")
        ticks = []

        async def ticker():
            for _ in range(8):
                ticks.append(1)
                await asyncio.sleep(0.005)

        async def scenario():
            t = asyncio.ensure_future(ticker())
            await faults.afire("d.read")   # 60ms latency, loop live
            with pytest.raises(ConnectionResetError):
                await faults.afire("d.cut")
            await t

        asyncio.new_event_loop().run_until_complete(
            asyncio.wait_for(scenario(), 10))
        # the ticker made progress DURING the injected latency — a
        # blocking time.sleep would have frozen it at 1 tick
        assert len(ticks) == 8
        assert faults.plan().fired("d.read") == 1

    def test_disconnect_and_truncate_raise_connection_errors(self):
        p = FaultPlan("a:disconnect;b:truncate")
        with pytest.raises(ConnectionResetError):
            p.fire("a")
        with pytest.raises(ConnectionResetError, match="truncated"):
            p.fire("b")

    def test_shield_suppresses_recovery_path(self):
        faults.configure("x:raise")
        with pytest.raises(InjectedFault):
            faults.fire("x")
        with faults.shield():
            faults.fire("x")  # recovery path: no trigger
            with faults.shield():
                faults.fire("x")  # nesting
            faults.fire("x")
        with pytest.raises(InjectedFault):
            faults.fire("x")  # shield released

    def test_global_configure_and_reset(self):
        assert faults.plan() is None
        faults.fire("anything")  # no plan: free no-op
        p = faults.configure("x:raise:n=1")
        assert faults.plan() is p
        with pytest.raises(InjectedFault):
            faults.fire("x")
        faults.reset()
        assert faults.plan() is None

    def test_configure_defaults_seed_from_env(self, monkeypatch):
        """A peer re-arming the plan from nodeconfig ``faults`` must
        keep the FABTPU_FAULTS_SEED determinism, not drop it."""
        monkeypatch.setenv(faults.ENV_SEED, "41")
        p = faults.configure("x:raise:p=0.5")
        assert p.seed == 41
        monkeypatch.delenv(faults.ENV_SEED)
        assert faults.configure("x:raise").seed is None
        assert faults.configure("x:raise", seed=9).seed == 9

    def test_env_spec_arms_child_processes(self, tmp_path):
        script = textwrap.dedent(f"""\
            import sys
            sys.path.insert(0, {REPO!r})
            from fabric_tpu import faults
            try:
                faults.fire("child.point")
                print("NOFIRE")
            except faults.InjectedFault:
                print("FIRED")
        """)
        path = tmp_path / "child.py"
        path.write_text(script)
        env = dict(os.environ, FABTPU_FAULTS="child.point:raise",
                   JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, str(path)], env=env, capture_output=True,
            text=True, timeout=60,
        )
        assert "FIRED" in out.stdout, (out.stdout, out.stderr)

    def test_injected_counter_rides_registry(self):
        from fabric_tpu.ops_metrics import global_registry

        ctr = global_registry().counter("faults_injected_total")
        before = ctr.value(point="m.count", kind="raise")
        faults.configure("m.count:raise:n=2")
        for _ in range(3):
            try:
                faults.fire("m.count")
            except InjectedFault:
                pass
        assert ctr.value(point="m.count", kind="raise") == before + 2


# -- Backoff ---------------------------------------------------------------


class TestBackoff:
    def test_growth_cap_and_jitter_bounds(self):
        import random

        bo = Backoff(base=0.1, cap=1.0, factor=2.0, jitter=0.5,
                     rng=random.Random(3))
        seen = [bo.next() for _ in range(8)]
        # each delay within [peek*(1-jitter), peek] of its attempt
        expect = [min(1.0, 0.1 * 2 ** i) for i in range(8)]
        for d, e in zip(seen, expect):
            assert e * 0.5 <= d <= e + 1e-12
        assert bo.peek() == 1.0  # capped

    def test_long_outage_never_overflows(self):
        """~2000 consecutive failures (a multi-hour orderer outage at
        cap cadence) must keep returning cap, not raise OverflowError
        out of factor**attempt and kill the reconnect loop for good."""
        bo = Backoff(base=0.2, cap=15.0, jitter=0.0)
        for _ in range(2000):
            d = bo.next()
            assert 0.2 <= d <= 15.0
        assert bo.attempt == 2000
        assert bo.peek() == 15.0
        bo.reset()
        assert bo.next() == 0.2

    def test_reset_returns_to_base(self):
        bo = Backoff(base=0.2, cap=5.0, jitter=0.0)
        assert bo.next() == 0.2
        assert bo.next() == 0.4
        bo.reset()
        assert bo.attempt == 0
        assert bo.next() == 0.2

    def test_validation(self):
        for kw in ({"base": 0}, {"base": 1, "cap": 0.5},
                   {"factor": 0.5}, {"jitter": 2.0}):
            with pytest.raises(ValueError):
                Backoff(**kw)


# -- DeviceLaneGuard --------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _guard(**kw):
    from fabric_tpu.ops_metrics import Registry

    clock = kw.pop("clock", None) or _Clock()
    reg = Registry()  # isolated: assertions read exact counts
    g = DeviceLaneGuard(
        registry=reg, clock=clock, sleep=lambda s: None,
        backoff=Backoff(base=0.001, cap=0.002, jitter=0.0),
        channel="t", **kw,
    )
    return g, reg, clock


def _ctr(reg, name):
    m = reg.metric(name)
    return m.value(channel="t") if m else 0.0


class TestDeviceLaneGuard:
    def test_threshold_zero_is_a_construction_error(self):
        with pytest.raises(ValueError):
            _guard(fail_threshold=0)

    def test_retry_then_success(self):
        g, reg, _ = _guard(retries=2, fail_threshold=5)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            return "device"

        assert g.run_launch(flaky, lambda: "cpu", eager=True) == "device"
        assert calls["n"] == 3
        assert _ctr(reg, "device_verify_retries_total") == 2
        assert not g.degraded
        assert g.consecutive_failures == 0  # success reset

    def test_exhausted_retries_route_to_fallback(self):
        g, reg, _ = _guard(retries=1, fail_threshold=10)

        def dead():
            raise RuntimeError("boom")

        assert g.run_launch(dead, lambda: "cpu", eager=True) == "cpu"
        assert _ctr(reg, "fallback_blocks_total") == 1
        assert not g.degraded  # threshold 10 not reached

    def test_latch_fallback_probe_and_recovery(self):
        g, reg, clock = _guard(retries=0, fail_threshold=2,
                               recovery_s=10.0)
        state = {"dead": True}

        def lane():
            if state["dead"]:
                raise RuntimeError("device gone")
            return "device"

        gauge = reg.metric("validator_degraded")
        # two consecutive failures latch degraded
        assert g.run_launch(lane, lambda: "cpu", eager=True) == "cpu"
        assert not g.degraded
        assert g.run_launch(lane, lambda: "cpu", eager=True) == "cpu"
        assert g.degraded
        assert gauge.value(channel="t") == 1
        # degraded: straight to fallback, NO device attempt
        before = state.copy()
        clock.t += 5.0  # < recovery_s: not yet probing
        assert g.run_launch(lane, lambda: "cpu", eager=True) == "cpu"
        assert _ctr(reg, "fallback_blocks_total") == 3
        # probe due, device still dead: stays degraded, block on CPU
        clock.t += 10.0
        assert g.run_launch(lane, lambda: "cpu", eager=True) == "cpu"
        assert g.degraded
        # next probe finds the device back: lane re-arms
        state["dead"] = False
        clock.t += 10.0
        assert g.run_launch(lane, lambda: "cpu", eager=True) == "device"
        assert not g.degraded
        assert gauge.value(channel="t") == 0
        assert g.degraded_seconds() == pytest.approx(25.0)

    def test_shielded_fallback_survives_persistent_fault(self):
        # a persistent fault at the SHARED ops entry point must not
        # chase the CPU fallback — faults.shield() around fallback_fn
        faults.configure("validator.verify_launch:raise")
        g, reg, _ = _guard(retries=0, fail_threshold=1)

        def cpu():
            faults.fire("validator.verify_launch")  # shared entry
            return "cpu"

        assert g.run_launch(lambda: "device", cpu, eager=True) == "cpu"
        assert g.degraded

    def test_deadline_counts_toward_latch(self):
        clock = _Clock()
        g, reg, _ = _guard(retries=0, fail_threshold=2,
                           deadline_ms=50.0, clock=clock)

        def slow():
            clock.t += 0.2  # 200ms > 50ms deadline
            return "device"

        # result still used, but each over-deadline attempt counts
        assert g.run_launch(slow, lambda: "cpu", eager=True) == "device"
        assert g.consecutive_failures == 1
        assert not g.degraded
        assert g.run_launch(slow, lambda: "cpu", eager=True) == "device"
        assert g.degraded  # latched by slowness alone


# -- the REAL validator's device lane (crypto-free via ec_ref) --------------


def _ecref_items():
    """5 deterministic P-256 signature tuples (4 valid, 1 corrupted)
    from the pure-Python oracle — no `cryptography` needed."""
    from fabric_tpu.crypto import ec_ref

    k = ec_ref.SigningKey(d=0x1F2E3D4C5B6A79885746352413021100DEADBEEF)
    items = []
    for i in range(5):
        e = ec_ref.digest_int(b"payload-%d" % i)
        r, s = k.sign_digest(e, k=0xA5A5A5A5 + 977 * i)
        if i == 4:
            r ^= 1  # corrupt: must reject on EVERY lane
        items.append((e, r, s, *k.public))
    return items, [True, True, True, True, False]


def _real_validator(**kw):
    # peer.validator imports crypto.identity → needs `cryptography`
    # (the seed condition); the crypto-free differential below covers
    # the same machinery through the toy validator on bare containers
    pytest.importorskip("cryptography")
    from fabric_tpu.peer.validator import BlockValidator, PolicyProvider

    return BlockValidator(
        msp_manager=None, policy_provider=PolicyProvider({}),
        state_db=MemVersionedDB(), channel="lane", **kw,
    )


class TestValidatorDeviceLane:
    def test_guarded_device_lane_verdicts(self):
        items, want = _ecref_items()
        v = _real_validator(device_fail_threshold=3, device_retries=0)
        h = v._verify_launch_guarded(items)
        assert hasattr(h, "device_out")  # device lane, guarded wrapper
        assert [bool(x) for x in h()] == want
        assert not v.device_guard.degraded

    def test_persistent_launch_fault_latches_cpu_fallback(self):
        items, want = _ecref_items()
        v = _real_validator(device_fail_threshold=1, device_retries=0)
        faults.configure("validator.verify_launch:raise")
        h = v._verify_launch_guarded(items)
        assert getattr(h, "device_out", None) is None  # host MVCC path
        assert [bool(x) for x in h()] == want          # verdicts equal
        assert v.device_guard.degraded

    def test_fetch_side_failure_reverifies_on_cpu(self):
        items, want = _ecref_items()
        v = _real_validator(device_fail_threshold=2, device_retries=0)
        from fabric_tpu.peer.validator import _GuardedHandle

        class DeadHandle:
            device_out = object()
            n_real = len(items)

            def __call__(self):
                raise RuntimeError("device died after launch")

        g = _GuardedHandle(DeadHandle(), v.device_guard, v, items)
        assert [bool(x) for x in g()] == want  # CPU re-verify, correct
        assert v.device_guard.consecutive_failures == 1

    def test_last_ditch_ecref_when_host_lane_dies(self, monkeypatch):
        items, want = _ecref_items()
        v = _real_validator(device_fail_threshold=1, device_retries=0)
        from fabric_tpu.ops import p256v3

        calls = []

        def dead(*a, **kw):
            calls.append(a)
            raise RuntimeError("jax runtime gone")

        monkeypatch.setattr(p256v3, "verify_host", dead)
        assert [bool(x) for x in v._host_verify_fallback(items)] == want
        assert calls  # the verdicts above are ec_ref's


# -- /healthz surfaces a degraded lane (end-to-end, crypto-free) ------------


def test_healthz_reflects_degraded_lane():
    """The node registers a ``device_verify_lane`` health check over
    its channels' guards; a degraded lane must flip /healthz to 503
    with an explanatory reason, and recovery must flip it back."""
    import asyncio
    import urllib.error
    import urllib.request

    from fabric_tpu.opsserver import HealthRegistry, OperationsServer

    g, _, clock = _guard(retries=0, fail_threshold=1, recovery_s=10.0)
    guards = {"chan0": g}

    def _device_lanes():  # the PeerNode.start checker, in miniature
        for cid, gd in guards.items():
            if gd is not None and gd.degraded:
                return (
                    f"channel {cid}: device verify lane DEGRADED — "
                    "committing via CPU fallback, recovery probe armed"
                )
        return None

    health = HealthRegistry()
    health.register("device_verify_lane", _device_lanes)

    def _get(port):
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz"
            ) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    async def scenario():
        loop = asyncio.get_event_loop()
        srv = await OperationsServer(port=0, health=health).start()
        try:
            st, body = await loop.run_in_executor(None, _get, srv.port)
            assert st == 200 and body["status"] == "OK"
            # latch the lane degraded
            g.run_launch(lambda: (_ for _ in ()).throw(
                RuntimeError("dead")), lambda: "cpu", eager=True)
            assert g.degraded
            st, body = await loop.run_in_executor(None, _get, srv.port)
            assert st == 503
            (check,) = body["failed_checks"]
            assert check["component"] == "device_verify_lane"
            assert "DEGRADED" in check["reason"]
            assert "chan0" in check["reason"]
            # recovery probe succeeds → healthy again
            clock.t += 20.0
            assert g.run_launch(lambda: "device", lambda: "cpu",
                                eager=True) == "device"
            st, body = await loop.run_in_executor(None, _get, srv.port)
            assert st == 200
        finally:
            await srv.stop()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(asyncio.wait_for(scenario(), 30))
    finally:
        loop.close()


# -- chaos differential through the depth-2 CommitPipeline ------------------


@dataclass
class ToyPtx:
    txid: str
    idx: int
    is_config: bool = False


@dataclass
class ToyPending:
    block: object
    txs: list
    raw: list
    sigs: list
    overlay: object
    extra: object
    hd_bytes: bytes = None

    @property
    def txids(self):
        return {p.txid for p in self.txs if p.txid}


class ChaosToyValidator:
    """The toy-validator protocol with an explicit DEVICE LANE: the
    signature phase runs through a DeviceLaneGuard (so the
    ``validator.verify_launch`` injection point, retries, degraded CPU
    fallback and recovery probes are all in play) and the parse phase
    optionally shards over a HostStagePool (so ``hostpool.task``
    worker faults hit the prefetch stage).  Device lane and CPU lane
    compute the same verdicts — the differential proves chaos changes
    WHERE work runs, never WHAT commits.

    tx wire form: {"id", "sig"?: false, "config"?, "reads": {k: [b,t]},
    "writes": {k: v}} — "_lifecycle/"-prefixed keys write the barrier
    namespace."""

    VALID, DUP, BADSIG, MVCC = 0, 2, 8, 11

    def __init__(self, state, guard=None, pool=None):
        self.state = state
        self.guard = guard
        self.pool = pool
        self.lanes: list = []  # "device" | "cpu" per preprocess

    def preprocess(self, block):
        datas = list(block.data.data)
        if self.pool is not None:
            raw = self.pool.map(
                lambda d: json.loads(bytes(d)), datas, stage="parse"
            )
        else:
            raw = [json.loads(bytes(d)) for d in datas]

        def device_lane():
            return ("device", [bool(t.get("sig", True)) for t in raw])

        def cpu_lane():
            return ("cpu", [bool(t.get("sig", True)) for t in raw])

        if self.guard is not None:
            lane, sigs = self.guard.run_launch(
                device_lane, cpu_lane, eager=True
            )
        else:
            lane, sigs = device_lane()
        self.lanes.append(lane)
        return raw, sigs

    def validate_launch(self, block, pre=None, overlay=None,
                        extra_txids=None):
        raw, sigs = pre if pre is not None else self.preprocess(block)
        txs = [
            ToyPtx(t["id"], i, bool(t.get("config")))
            for i, t in enumerate(raw)
        ]
        return ToyPending(block, txs, raw, sigs, overlay, extra_txids)

    def _version(self, ns, key, overlay):
        if overlay is not None:
            vv = overlay.updates.get((ns, key))
            if vv is not None:
                return None if vv.value is None else list(vv.version)
        vv = self.state.get_state(ns, key)
        return None if vv is None else list(vv.version)

    @staticmethod
    def _ns(key):
        return "_lifecycle" if key.startswith("_lifecycle/") else "ns"

    def validate_finish(self, pend):
        codes = []
        batch = UpdateBatch()
        num = pend.block.header.number
        seen = set(pend.extra or ())
        for ptx, t, sig_ok in zip(pend.txs, pend.raw, pend.sigs):
            if ptx.txid in seen:
                codes.append(self.DUP)
                continue
            seen.add(ptx.txid)
            if not sig_ok:
                codes.append(self.BADSIG)
                continue
            ok = all(
                self._version(self._ns(k), k, pend.overlay) == want
                for k, want in t.get("reads", {}).items()
            )
            if not ok:
                codes.append(self.MVCC)
                continue
            codes.append(self.VALID)
            for k, val in t.get("writes", {}).items():
                batch.put(self._ns(k), k, val.encode(), (num, ptx.idx))
        return bytes(codes), batch, []


def _chaos_stream(n_blocks=12, n_tx=6):
    """Dependent stream with an overlay lane, a stale lane, a bad-sig
    lane, and one mid-stream lifecycle BARRIER block."""
    blocks, prev = [], b""
    for n in range(n_blocks):
        txs = []
        for i in range(n_tx):
            t = {"id": f"tx{n}_{i}", "writes": {f"k{n}_{i}": f"v{n}"}}
            if n > 0 and i == 0:
                t["reads"] = {f"k{n-1}_0": [n - 1, 0]}  # via overlay
            if n > 0 and i == 1:
                t["reads"] = {f"k{n-1}_1": [0, 0]}      # stale → MVCC
            if i == 2 and n % 3 == 1:
                t["sig"] = False                         # bad signature
            txs.append(t)
        if n == 5:
            txs[-1]["writes"]["_lifecycle/cc1"] = "defn"  # barrier
        blk = pu.new_block(n, prev)
        for t in txs:
            blk.data.data.append(json.dumps(t).encode())
        blk = pu.finalize_block(blk)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)
    return blocks


def _drive_chaotic(blocks, make_validator, depth=2, max_restarts=300):
    """The deliver driver's containment loop, in miniature: submit the
    stream; a pipeline stage exception drains the (fail-closed) pipe,
    rebuilds it, and resumes from the last COMMITTED height — exactly
    what _run_deliver_pipelined does via stream reconnect."""
    state = MemVersionedDB()
    v = make_validator(state)
    filters: dict[int, list] = {}
    height = [0]

    def commit_fn(res):
        num = res.block.header.number
        assert num == height[0], "commit out of order"
        assert num not in filters, "block committed twice"
        state.apply_updates(res.batch, (num, 0))
        filters[num] = list(res.tx_filter)
        height[0] = num + 1

    restarts = 0
    pipe = CommitPipeline(v, commit_fn, depth=depth)
    while True:
        try:
            for blk in blocks[height[0]:]:
                if blk.header.number < height[0]:
                    continue  # replayed (committed while we restarted)
                pipe.submit(blk)
            pipe.flush()
            break
        except Exception:
            restarts += 1
            assert restarts < max_restarts, "chaos run cannot converge"
            pipe.close(flush=False)
            pipe = CommitPipeline(v, commit_fn, depth=depth)
    pipe.close()
    return filters, dict(state._data), v, restarts


def test_chaos_differential_matches_fault_free_serial():
    """THE acceptance criterion: device-launch faults (probabilistic,
    seeded), one host-pool worker fault, one injected mid-stream
    pipeline disconnect and one commit-stage fault, driven through a
    depth-2 CommitPipeline with retry/fallback/containment — the
    committed block/tx accept-set equals a fault-free depth-1 run."""
    from fabric_tpu.parallel.hostpool import HostStagePool

    blocks = _chaos_stream(12, 6)

    # fault-free serial oracle
    f_serial, s_serial, v0, r0 = _drive_chaotic(
        blocks, lambda st: ChaosToyValidator(st), depth=1
    )
    assert r0 == 0
    assert sorted(f_serial) == list(range(12))

    plan = FaultPlan(
        "validator.verify_launch:raise:p=0.6;"
        "hostpool.task:raise:n=1:after=6;"
        "pipeline.prefetch:raise:n=1:after=4;"   # the mid-stream cut
        "pipeline.commit:raise:n=1:after=2",
        seed=20260803,
    )
    faults.install(plan)
    pool = HostStagePool(2)
    try:
        def make_validator(st):
            g = DeviceLaneGuard(
                retries=1, fail_threshold=2, recovery_s=0.0,
                backoff=Backoff(base=0.001, cap=0.002, jitter=0.0),
                sleep=lambda s: None, channel="chaos",
            )
            return ChaosToyValidator(st, guard=g, pool=pool)

        f_chaos, s_chaos, v, restarts = _drive_chaotic(
            blocks, make_validator, depth=2
        )
    finally:
        pool.shutdown()
        faults.reset()

    # the differential: EXACT accept set and final state
    assert f_chaos == f_serial
    assert s_chaos == s_serial
    # and the chaos actually bit: device faults fired, blocks rode the
    # CPU lane, the pipe was torn down and resumed at least once
    assert plan.fired("validator.verify_launch") > 0
    assert plan.fired("pipeline.prefetch") == 1
    assert plan.fired("pipeline.commit") == 1
    assert plan.fired("hostpool.task") == 1
    assert "cpu" in v.lanes and "device" in v.lanes
    assert restarts >= 2  # prefetch cut + commit fault (+ pool fault)


def test_chaos_latency_faults_change_nothing():
    """Latency-only chaos (slow device, slow commit) must not change
    verdicts, state, or require any restart."""
    blocks = _chaos_stream(6, 4)
    f_serial, s_serial, _, _ = _drive_chaotic(
        blocks, lambda st: ChaosToyValidator(st), depth=1
    )
    faults.install(FaultPlan(
        "validator.verify_launch:latency:ms=5:p=0.5;"
        "pipeline.commit:latency:ms=5:p=0.5", seed=11,
    ))
    try:
        f, s, _, restarts = _drive_chaotic(
            blocks,
            lambda st: ChaosToyValidator(st, guard=DeviceLaneGuard(
                retries=1, fail_threshold=3, recovery_s=0.0,
                deadline_ms=1.0,  # every slow launch counts a failure
                backoff=Backoff(base=0.001, cap=0.002, jitter=0.0),
                sleep=lambda s_: None, channel="lat",
            )),
            depth=2,
        )
    finally:
        faults.reset()
    assert restarts == 0
    assert f == f_serial and s == s_serial


# -- crash consistency: kill mid-fsync, replay on restart -------------------


_CRASH_CHILD = """\
import json, sys
sys.path.insert(0, {repo!r})
from fabric_tpu import protoutil as pu
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch

lg = KVLedger(sys.argv[1], state_db=MemVersionedDB(),
              enable_history=False)
lg.blocks.group_commit = 4
prev = b""
for n in range(int(sys.argv[2])):
    blk = pu.new_block(n, prev)
    blk.data.data.append(
        json.dumps({{"id": "tx%d" % n, "key": "k%d" % n}}).encode()
    )
    blk = pu.finalize_block(blk)
    batch = UpdateBatch()
    batch.put("ns", "k%d" % n, b"v%d" % n, (n, 0))
    lg.commit_block(blk, bytes([0]), batch, [], None, [("tx%d" % n, 0)])
    prev = pu.block_header_hash(blk.header)
print("HEIGHT", lg.height)
lg.close()
"""


def _run_crash_child(tmp_path, n_blocks, fault_spec):
    script = tmp_path / "crash_child.py"
    script.write_text(_CRASH_CHILD.format(repo=REPO))
    ledger_dir = str(tmp_path / "ledger")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FABTPU_FAULTS", None)
    if fault_spec:
        env["FABTPU_FAULTS"] = fault_spec
    out = subprocess.run(
        [sys.executable, str(script), ledger_dir, str(n_blocks)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    return ledger_dir, out


def _reopen_and_verify(ledger_dir, expect_height, indexed_txids=None):
    """Reopen the crashed ledger: consistent height, linked chain,
    state replay via recover(), and the store still accepts blocks.
    ``indexed_txids``: blocks whose txid-index rows must have survived
    (the recovery re-index parses real envelopes, not these toy JSON
    payloads, so a tail block re-indexed from the FILES keeps its
    block row but not its toy txids)."""
    from fabric_tpu.ledger.kvledger import KVLedger

    lg = KVLedger(ledger_dir, state_db=MemVersionedDB(),
                  enable_history=False)
    try:
        assert lg.height == expect_height
        prev = b""
        for n in range(lg.height):
            blk = lg.blocks.get_block(n)
            assert blk is not None, f"block {n} unreadable"
            assert blk.header.previous_hash == prev
            if n < (expect_height if indexed_txids is None
                    else indexed_txids):
                assert lg.blocks.tx_exists(f"tx{n}")
            prev = pu.block_header_hash(blk.header)
        assert lg.blocks.get_block(lg.height) is None
        # state replays forward from the block files (mem state starts
        # empty: savepoint None → full replay)
        def replayer(block):
            t = json.loads(bytes(block.data.data[0]))
            batch = UpdateBatch()
            batch.put("ns", t["key"], b"r", (block.header.number, 0))
            return bytes([0]), batch, []

        replayed = lg.recover(replayer)
        assert replayed == expect_height
        for n in range(expect_height):
            assert lg.state.get_state("ns", f"k{n}") is not None
        # and the channel keeps accepting: commit the next block
        h = lg.height
        blk = pu.new_block(h, prev)
        blk.data.data.append(json.dumps({"id": f"tx{h}"}).encode())
        blk = pu.finalize_block(blk)
        lg.commit_block(blk, bytes([0]), UpdateBatch(), [], None,
                        [(f"tx{h}", 0)])
        assert lg.height == h + 1
    finally:
        lg.close()


@pytest.mark.parametrize("hook", ["before", "after"])
def test_kill_mid_fsync_replays_to_consistent_height(tmp_path, hook):
    """Child commits 12 blocks (group_commit=4) and is hard-killed at
    its SECOND fsync (os._exit inside the hook — nothing flushed, no
    atexit): block 7's record is on disk but unindexed.  Reopen must
    re-index forward to height 8, link the chain, replay state, and
    accept block 8."""
    ledger_dir, out = _run_crash_child(
        tmp_path, 12, f"ledger.fsync.{hook}:crash:after=1"
    )
    assert out.returncode == 86, (out.stdout, out.stderr)
    assert "HEIGHT" not in out.stdout  # died mid-stream, as intended
    _reopen_and_verify(ledger_dir, expect_height=8, indexed_txids=7)


def test_torn_tail_after_crash_truncates_and_recovers(tmp_path):
    """The unsynced tail a crash can tear: chop the last segment file
    mid-record (what a power loss does to the un-fsynced window) —
    _recover must truncate to the last complete record, clamp the
    index back to the files, and the ledger must keep accepting."""
    ledger_dir, out = _run_crash_child(
        tmp_path, 12, "ledger.fsync.before:crash:after=1"
    )
    assert out.returncode == 86
    seg = os.path.join(ledger_dir, "chains", "blocks_000000.bin")
    size = os.path.getsize(seg)
    with open(seg, "r+b") as f:
        f.truncate(size - 7)  # mid-record: block 7's tail is torn
    _reopen_and_verify(ledger_dir, expect_height=7)


def test_no_fault_child_is_clean(tmp_path):
    """The same child with NO fault plan commits all 12 blocks — pins
    that the harness itself (env spec, group commit) is inert."""
    ledger_dir, out = _run_crash_child(tmp_path, 12, "")
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "HEIGHT 12" in out.stdout
    _reopen_and_verify(ledger_dir, expect_height=12)


# -- crash consistency under the PIPELINED windowed fsync --------------------

_PIPE_CRASH_CHILD = """\
import json, sys
sys.path.insert(0, {repo!r})
from fabric_tpu import protoutil as pu
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
from fabric_tpu.peer.pipeline import CommitPipeline


class V:  # minimal validator protocol over 1-tx JSON blocks
    def preprocess(self, block):
        return [json.loads(bytes(d)) for d in block.data.data]

    def validate_launch(self, block, pre=None, overlay=None,
                        extra_txids=None):
        raw = pre if pre is not None else self.preprocess(block)
        return type("P", (), {{
            "block": block, "raw": raw, "txs": [],
            "txids": {{t["id"] for t in raw}},
        }})()

    def validate_finish(self, pend):
        batch = UpdateBatch()
        num = pend.block.header.number
        for i, t in enumerate(pend.raw):
            batch.put("ns", t["key"], b"v", (num, i))
        return bytes([0] * len(pend.raw)), batch, []


lg = KVLedger(sys.argv[1], state_db=MemVersionedDB(),
              enable_history=False)
lg.blocks.group_commit = 4
depth = int(sys.argv[3])
mode = sys.argv[4]  # "honor" = node discipline; "windowed" = pure
                    # group-commit batching (no forced per-block sync)


def commit_fn(res):
    lg.commit_block(res.block, res.tx_filter, res.batch, res.history,
                    None, [(t["id"], i)
                           for i, t in enumerate(res.pend.raw)])
    # the node's windowed-fsync discipline: mid-window DEEP-pipelined
    # commits defer; everything else forces the window closed
    if mode == "honor" and not res.defer_sync:
        lg.blocks.sync()


prev = b""
blocks = []
for n in range(int(sys.argv[2])):
    blk = pu.new_block(n, prev)
    blk.data.data.append(
        json.dumps({{"id": "tx%d" % n, "key": "k%d" % n}}).encode()
    )
    blk = pu.finalize_block(blk)
    prev = pu.block_header_hash(blk.header)
    blocks.append(blk)
with CommitPipeline(V(), commit_fn, depth=depth) as pipe:
    for blk in blocks:
        pipe.submit(blk)
print("HEIGHT", lg.height)
lg.close()
"""


def _run_pipe_crash_child(tmp_path, n_blocks, depth, fault_spec,
                          mode="honor"):
    script = tmp_path / "pipe_crash_child.py"
    script.write_text(_PIPE_CRASH_CHILD.format(repo=REPO))
    ledger_dir = str(tmp_path / "ledger")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FABTPU_FAULTS", None)
    if fault_spec:
        env["FABTPU_FAULTS"] = fault_spec
    out = subprocess.run(
        [sys.executable, str(script), ledger_dir, str(n_blocks),
         str(depth), mode],
        env=env, capture_output=True, text=True, timeout=120,
    )
    return ledger_dir, out


@pytest.mark.parametrize("hook", ["before", "after"])
def test_pipelined_windowed_fsync_crash_replays_depth3(tmp_path, hook):
    """THE windowed-fsync durability re-pin at depth 3: mid-window
    commits carry defer_sync=True, the node discipline skips their
    forced fsync, and group_commit=4 batches the window — a hard kill
    at the SECOND group fsync must reopen at the last group-commit
    boundary (height 8: block 7's record on disk but unindexed), link
    the chain, replay state forward, and keep accepting blocks."""
    ledger_dir, out = _run_pipe_crash_child(
        tmp_path, 12, 3, f"ledger.fsync.{hook}:crash:after=1"
    )
    assert out.returncode == 86, (out.stdout, out.stderr)
    assert "HEIGHT" not in out.stdout  # died mid-stream, as intended
    _reopen_and_verify(ledger_dir, expect_height=8, indexed_txids=7)


def test_pipelined_depth2_keeps_classic_per_block_durability(tmp_path):
    """Depth 2 NEVER defers (defer_sync is a depth ≥ 3 behavior): the
    honor-discipline child force-fsyncs every commit, so the same
    crash plan fires at the SECOND per-block sync and only blocks 0–1
    are on disk — the default config's acknowledged-durability
    semantics are exactly the pre-depth-N ones."""
    ledger_dir, out = _run_pipe_crash_child(
        tmp_path, 12, 2, "ledger.fsync.before:crash:after=1"
    )
    assert out.returncode == 86, (out.stdout, out.stderr)
    _reopen_and_verify(ledger_dir, expect_height=2)


@pytest.mark.parametrize("hook", ["before", "after"])
def test_pipelined_windowed_fsync_crash_depth2_group_knob(tmp_path,
                                                          hook):
    """The depth-2 windowed story rides the group_commit KNOB, not
    defer_sync: a committer that opts out of forced per-block syncs
    entirely (mode=windowed) batches fsyncs every 4 blocks at depth 2
    too, and the kill-mid-group replay holds there as well."""
    ledger_dir, out = _run_pipe_crash_child(
        tmp_path, 12, 2, f"ledger.fsync.{hook}:crash:after=1",
        mode="windowed",
    )
    assert out.returncode == 86, (out.stdout, out.stderr)
    _reopen_and_verify(ledger_dir, expect_height=8, indexed_txids=7)


@pytest.mark.parametrize("depth", [2, 3])
def test_pipelined_windowed_fsync_clean_run(tmp_path, depth):
    """No fault: the pipelined honor-discipline child commits all 12
    blocks and the TAIL commit closes any open window (the stream's
    last block arrives with defer_sync=False), so everything is
    durable at exit even before close()."""
    ledger_dir, out = _run_pipe_crash_child(tmp_path, 12, depth, "")
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "HEIGHT 12" in out.stdout
    _reopen_and_verify(ledger_dir, expect_height=12)
