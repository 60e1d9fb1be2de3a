"""The reduction from a capture to numbers, on a cut of a real one: the
first block of ``rw_backlog`` on the v5e (PR 22), whose events were read
by hand from a dump of the capture before the reduction was written:

    XLA Modules  jit_stage2               start 122493777 ns  dur    95605 ns
                 jit_verify_batch_packed  start 164725488 ns  dur 26904942 ns
                 jit_stage2               start 492481630 ns  dur    95056 ns
    host         bench.anchor at 46812740 ns, fabtpu.stage2_dispatch at
                 123576489 ns, fabtpu.verify_dispatch at 164645149 ns

and on made-up span trees for the arithmetic over the program's spans."""

import gzip
import os
import types

import pytest

from benchmark import manifest, spans, trace_reduce
from fabric_tpu.observe.tracer import Span

FIXTURE = os.path.join(manifest.ROOT, "benchmark", "fixtures",
                       "rw_backlog_one_block.xplane.pb.gz")
T0, T1 = 0.04681274, 0.4930


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "cut.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return trace_reduce.read(str(path))


def test_modules_and_annotations_are_found_by_name(capture):
    assert capture.devices == [0]
    assert [(trace_reduce.module_name(n), round(s * 1e9), round(d * 1e9))
            for _dev, n, s, d in capture.modules] == [
        ("jit_stage2", 122493777, 95605),
        ("jit_verify_batch_packed", 164725488, 26904942),
        ("jit_stage2", 492481630, 95056)]
    assert trace_reduce.module_runs(
        capture, "jit_verify_batch_packed", T0, T1) == [
        pytest.approx(0.026904942)]
    assert trace_reduce.module_runs(capture, "jit_stage2", T0, 0.2) == [
        pytest.approx(95605e-9)]
    assert capture.host["bench.anchor"][0][0] == pytest.approx(T0)
    assert capture.host["fabtpu.verify_dispatch"][0][0] == pytest.approx(
        0.164645149)
    assert set(capture.host) == {
        "bench.anchor", "bench.submit", "bench.commit",
        "fabtpu.verify_dispatch", "fabtpu.stage2_dispatch"}


def test_busy_time_is_the_union_of_the_operations(capture):
    busy = trace_reduce.busy_seconds(capture, T0, T1)
    modules = sum(d for _dev, _n, _s, d in capture.modules)
    # 22,154 operation events, most of them inside the ladder's while:
    # their union is the three modules less the nanoseconds between ops
    assert len(capture.ops) == 22154
    assert busy == pytest.approx(0.027093505, abs=1e-9)
    assert modules - 5e-6 < busy <= modules
    # half a window holds half the ladder
    mid = 0.164725488 + 0.026904942 / 2
    assert trace_reduce.busy_seconds(capture, T0, mid) == pytest.approx(
        95605e-9 + 0.026904942 / 2, rel=2e-3)
    gaps = trace_reduce.idle_gaps(capture, T0, T1)
    assert sum(b - a for a, b in gaps) == pytest.approx(T1 - T0 - busy)
    assert max(gaps, key=lambda g: g[1] - g[0]) == pytest.approx(
        (0.191630433, 0.49248163), abs=1e-6)


def test_operations_are_summed_by_kind_under_their_module(capture):
    top = trace_reduce.top_ops(capture, T0, T1, n=4)
    assert [k for k, _s in top] == [
        "jit_verify_batch_packed/multiply_reduce_fusion",
        "jit_verify_batch_packed/select_subtract_fusion",
        "jit_verify_batch_packed/fusion",
        "jit_verify_batch_packed/convolution_convert_fusion"]
    assert top[0][1] == pytest.approx(0.008399947, abs=1e-8)
    everything = trace_reduce.top_ops(capture, T0, T1, n=10_000)
    # self times: the while that holds the ladder adds almost nothing of
    # its own, and the kinds add up to the busy time
    assert dict(everything)["jit_verify_batch_packed/while"] < 1e-4
    assert sum(s for _k, s in everything) == pytest.approx(
        trace_reduce.busy_seconds(capture, T0, T1), rel=1e-3)
    assert trace_reduce.op_kind(
        "%multiply_reduce_fusion.213 = (f32[6,3072]{1,0}) fusion(...)") \
        == "multiply_reduce_fusion"
    assert trace_reduce.op_kind("%copy-start.98 = (s32[3072,64]) copy-start()") \
        == "copy-start"


def test_gaps_are_attributed_to_what_the_host_was_doing():
    gaps = [(0.0, 1.0), (2.0, 2.5)]
    host = {"caller:state_fill": [(0.2, 0.9)],
            "prefetch:host_parse": [(0.8, 1.2), (2.0, 2.1)]}
    got = dict(trace_reduce.attribute_gaps(gaps, host))
    assert got == pytest.approx({"caller:state_fill": 0.7,
                                 "prefetch:host_parse": 0.3,
                                 "(no span)": 0.6})
    assert trace_reduce.attribute_gaps(gaps, host, n=1) == [
        ["caller:state_fill", pytest.approx(0.7)]]


def test_kernel_readers_and_the_roofline_share(capture):
    obs = types.SimpleNamespace(
        device_trace=capture, capture_window=(T0, T1, 0.0), block_tx=1000,
        config={"signatures_per_tx": 3}, device_kind="TPU v5 lite",
        launch_rows=[{"block": "7", "kernel": "verify", "lanes": 3000},
                     {"block": "7", "kernel": "stage2", "lanes": 1000},
                     {"block": "8", "kernel": "verify", "lanes": 3000}])
    read = lambda name: manifest.load_module("layer_metrics", name).read(obs)
    assert read("verify_kernel_ms") == pytest.approx(26.904942)
    assert read("stage2_kernel_ms") == pytest.approx((95605 + 95056) / 2e6)
    # 3000 signatures x 5265 muls x 12,696 FLOP at 197 TFLOP/s = 1.0179 ms
    assert read("verify_roofline") == pytest.approx(
        1.0179 / 26.904942 * 100, rel=1e-4)
    # the lanes are the block's own: a block of 100 txs needs a tenth of
    # the operations, whatever the configuration's nominal size
    obs.launch_rows = [{"block": "7", "kernel": "verify", "lanes": 300}]
    assert read("verify_roofline") == pytest.approx(
        0.10179 / 26.904942 * 100, rel=1e-4)
    obs.launch_rows = []
    assert read("verify_roofline") is None
    obs.device_trace = None
    assert read("verify_kernel_ms") is None and read("verify_roofline") is None


def _span(name, t0, t1, thread="MainThread", children=(), **attrs):
    sp = Span(name, t0, thread, attrs)
    sp.t1 = t1
    sp.children = list(children)
    return sp


def _block(k, t):
    """One block's tree, ``t`` its submit time: prefetch on its thread,
    launch and finish on the caller's, commit on the committer's; the
    launch ledger's estimate hangs under ``launch`` on a made-up thread."""
    return _span("block", t, t + 0.5, block=k, children=[
        _span("prefetch", t, t + 0.05, "fabtpu-prefetch_0", children=[
            _span("host_parse", t, t + 0.03, "fabtpu-prefetch_0"),
            _span("sig_prepare_launch", t + 0.03, t + 0.04,
                  "fabtpu-prefetch_0")]),
        _span("launch", t + 0.1, t + 0.4, children=[
            _span("state_fill", t + 0.15, t + 0.35),
            _span("stage2_dispatch", t + 0.35, t + 0.36),
            _span("dev:execute", t + 0.1, t + 0.45, "device:dev")]),
        _span("finish", t + 0.4, t + 0.45, children=[
            _span("device_wait", t + 0.40, t + 0.42),
            _span("postprocess", t + 0.42, t + 0.45)]),
        _span("commit", t + 0.45, t + 0.5, "fabtpu-committer_0"),
    ])


def test_span_arithmetic_on_made_up_trees():
    roots = [_block(k, 10.0 + 0.41 * k) for k in range(3)]
    assert spans.ms_per_block(roots, ["state_fill"]) == pytest.approx(
        [200.0] * 3)
    assert spans.ms_per_block(roots, ["host_parse", "postprocess"]) == \
        pytest.approx([60.0] * 3)
    # launch 300 ms less state_fill 200 and stage2_dispatch 10; the
    # device-lane estimate under it is no child of the host span
    assert spans.self_ms_per_block(roots, "launch") == pytest.approx(
        [90.0] * 3)
    # device_wait(k) = [t+.40, t+.42]; block k+1 was submitted at t+.41,
    # so its prefetch covers the second half; dev:* spans cover nothing
    assert spans.overlap_coverage(roots, window=1) == pytest.approx(
        (0.5 + 0.5 + 0.0) / 3)
    own = spans.self_intervals(roots[:1])
    assert own["caller:launch"] == pytest.approx([(10.1, 10.15),
                                                  (10.36, 10.4)])
    assert own["prefetch:prefetch"] == pytest.approx([(10.04, 10.05)])
    assert own["committer:commit"] == pytest.approx([(10.45, 10.5)])
    assert not any(k.endswith("dev:execute") or k.endswith(":block")
                   for k in own)


def test_span_readers_take_the_median_over_blocks():
    roots = [_block(k, 10.0 + 0.41 * k) for k in range(3)]
    obs = types.SimpleNamespace(
        roots=roots, depth=2, first=0, last=2, block_tx=1000,
        block_txs=[1000, 1000, 1000], n_valid=2700,
        ack={0: 1.0, 1: 2.0, 2: 3.0}, applied={0: 1.1, 1: 2.3, 2: 3.2},
        launch_rows=[{"block": "0", "h2d_bytes": 1929216},
                     {"block": "0", "h2d_bytes": 12288},
                     {"block": "1", "h2d_bytes": 1929216},
                     {"block": "1", "h2d_bytes": 12288}],
        lag_s=[0.0, 0.001, 0.0005])
    read = lambda name: manifest.load_module("layer_metrics", name).read(obs)
    assert read("state_fill_ms") == pytest.approx(200.0)
    assert read("paced.state_fill_ms") == pytest.approx(200.0)
    assert read("launch_self_ms") == pytest.approx(90.0)
    assert read("host_lane_rest_ms") == pytest.approx(30 + 10 + 10 + 30)
    assert read("device_wait_ms") == pytest.approx(20.0)
    assert read("ledger_commit_ms") == pytest.approx(50.0)
    assert read("pipeline_overlap_coverage") == pytest.approx(100 / 3)
    assert read("h2d_bytes_per_block") == 1941504
    assert read("apply_lag_ms") == pytest.approx(200.0)
    assert read("valid_share") == pytest.approx(90.0)
    assert read("generator_lag_p95_ms") == pytest.approx(0.95)
    obs.roots, obs.launch_rows, obs.lag_s = [], [], None
    for name in ("state_fill_ms", "launch_self_ms", "h2d_bytes_per_block",
                 "pipeline_overlap_coverage", "generator_lag_p95_ms"):
        assert read(name) is None, name
