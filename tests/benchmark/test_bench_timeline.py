"""The clock arithmetic on made-up timestamps, and the paced loop on a
made-up clock: a stalled block must raise the latency of the blocks due
behind it.  A block's txs are counted on the block: the made-up blocks
carry their own sizes, and streams whose blocks are all one size must
read what they read when the size was a constant."""

import itertools
import math
import types

import numpy as np
import pytest

from benchmark import harness, timeline


def test_percentiles_interpolate_between_order_statistics():
    v = [10.0, 20.0, 30.0, 40.0]
    assert timeline.median(v) == 25.0
    assert timeline.percentile(v, 95.0) == pytest.approx(38.5)
    assert timeline.percentile([7.0], 95.0) == 7.0
    with pytest.raises(ValueError):
        timeline.percentile([], 50.0)


def test_due_times_and_the_count_of_paced_blocks():
    # 1000 tx/s, 100-tx blocks: block k is cut (k+1)/10 s after t0
    assert timeline.block_due(5.0, 0, 1000.0, [100] * 10) == pytest.approx(5.1)
    assert timeline.block_due(5.0, 9, 1000.0, [100] * 10) == pytest.approx(6.0)
    assert timeline.paced_blocks(1.0, 1000.0, [100] * 20) == 10
    assert timeline.paced_blocks(0.99, 1000.0, [100] * 20) == 9
    assert timeline.paced_blocks(27.0, 1900.0, [1000] * 60) == 51
    # an endless stream of the nominal size: how many blocks to ask for
    assert timeline.paced_blocks(27.0, 1900.0, itertools.repeat(1000)) == 51
    # a stream shorter than the window: all of it
    assert timeline.paced_blocks(27.0, 1900.0, [1000] * 7) == 7


@pytest.mark.parametrize("seconds,rate,size", [
    (30.0, 1800.0, 1000), (2.0, 40.0, 20), (10.0, 1800.0, 1000),
    (7.3, 333.0, 17), (30.0, 2000.0, 1000), (1.0, 1000.0, 100)])
def test_equal_blocks_read_what_a_constant_block_size_read(seconds, rate,
                                                           size):
    """The formulas of PR 22, which multiplied by ``block_tx``."""
    sizes = [size] * (2 + int(seconds * rate / size))
    assert timeline.paced_blocks(seconds, rate, sizes) == int(
        math.floor(seconds * rate / size + 1e-9))
    for k in (0, 1, len(sizes) - 1):
        assert timeline.block_due(3.0, k, rate, sizes) == (
            3.0 + (k + 1) * size / rate)
    applied = [3.0 + 0.37 * (k + 1) for k in range(4)]
    n = np.arange(1, 4 * size + 1, dtype=np.float64)
    old = (np.repeat(np.asarray(applied), size) - (3.0 + n / rate)) * 1000.0
    assert timeline.tx_latencies_ms(3.0, applied, rate, sizes).tolist() \
        == old.tolist()
    assert timeline.tx_per_s(3.0, 4.7, sizes[:4]) == 4 * size / (4.7 - 3.0)


def test_blocks_of_different_sizes_fall_due_with_their_last_tx():
    sizes = [10, 500, 30, 460]          # 1000 tx in all
    # at 1000 tx/s block k is cut when its last tx is due
    assert [timeline.block_due(0.0, k, 1000.0, sizes) for k in range(4)] \
        == pytest.approx([0.01, 0.51, 0.54, 1.0])
    assert timeline.paced_blocks(0.53, 1000.0, sizes) == 2
    assert timeline.paced_blocks(0.54, 1000.0, sizes) == 3
    assert timeline.paced_blocks(5.0, 1000.0, sizes) == 4
    # a tx waits from its own due time to its block's apply
    lat = timeline.tx_latencies_ms(0.0, [0.02, 0.6], 1000.0, sizes)
    assert len(lat) == 510
    assert lat[0] == pytest.approx(19.0) and lat[9] == pytest.approx(10.0)
    assert lat[10] == pytest.approx(589.0) and lat[-1] == pytest.approx(90.0)
    # the rate counts the txs the applied blocks held
    assert timeline.tx_per_s(0.0, 2.0, sizes) == 500.0
    assert timeline.tx_per_s(0.0, 2.0, sizes[:2]) == 255.0


def test_tx_latency_runs_from_the_due_time():
    # 2 blocks of 4 tx at 4 tx/s: txs due at 0.25 .. 2.0; blocks applied
    # at 1.5 and 2.25
    lat = timeline.tx_latencies_ms(0.0, [1.5, 2.25], 4.0, [4, 4])
    assert lat.tolist() == pytest.approx(
        [1250, 1000, 750, 500, 1000, 750, 500, 250])
    # the last tx of a block waits only for the block's own trip
    assert lat[3] == pytest.approx((1.5 - 1.0) * 1000)


def test_backlog_window_ends_on_an_apply_and_counts_whole_blocks():
    applied = [10.4, 10.8, 11.3, 11.7, 12.2]
    t_close, n = timeline.backlog_window(10.0, 2.0, applied)
    assert (t_close, n) == (11.7, 4)
    assert timeline.tx_per_s(10.0, t_close, [1000] * n) == pytest.approx(
        4000 / 1.7)
    # a fixed 2 s window would have rounded the same run to 2000 tx/s
    with pytest.raises(ValueError):
        timeline.backlog_window(10.0, 0.3, applied)


class FakeRig:
    """A serial pipeline on a made-up clock: ``feed`` takes the block's
    service time and the block is applied when it returns."""

    def __init__(self, service):
        self.now = 100.0
        self.service = service
        self.clock = lambda: self.now
        self.pipe = types.SimpleNamespace(inflight=0, coalesce_blocks=0)
        self.released, self.applied, self.flushes = {}, {}, 0

    def sleep(self, dt):
        assert dt >= 0
        self.now += dt

    def feed(self, blocks, lo, hi):
        b = blocks[lo].header.number
        self.released[b] = self.now
        self.now += self.service[b]
        self.applied[b] = self.now
        self.pipe.inflight = 1
        return 1

    def flush(self):
        self.flushes += 1
        self.pipe.inflight = 0

    def drain(self):
        self.flush()


def _blocks(sizes, first=0):
    """Made-up blocks: a number and as many txs as ``sizes`` says."""
    return [types.SimpleNamespace(
        header=types.SimpleNamespace(number=first + k),
        data=types.SimpleNamespace(data=[b""] * txs))
        for k, txs in enumerate(sizes)]


def _paced(service, rate=100.0, block_tx=10):
    rig = FakeRig(service)
    n = len(service)
    ran = harness.run_paced(rig, _blocks([block_tx] * n), 0, n, rate,
                            idle_flush_s=0.05, sleep=rig.sleep)
    applied = [rig.applied[b] for b in range(n)]
    lat = timeline.tx_latencies_ms(ran["t_open"], applied, rate,
                                   [block_tx] * n)
    return rig, ran, lat.reshape(n, block_tx)


def test_paced_loop_releases_on_the_schedule_and_flushes_a_quiet_tail():
    # a block every 0.1 s, each served in 0.02 s: the stream is quiet for
    # longer than idle_flush_s after every block
    rig, ran, lat = _paced([0.02] * 5)
    t0 = ran["t_open"]
    assert [rig.released[b] - t0 for b in range(5)] == pytest.approx(
        [0.1, 0.2, 0.3, 0.4, 0.5])
    assert ran["lag_s"] == pytest.approx([0.0] * 5)
    assert rig.flushes >= 5
    # a block's last tx waits for its trip, its first for the block to fill
    assert lat[:, -1] == pytest.approx([20.0] * 5)
    assert lat[:, 0] == pytest.approx([110.0] * 5)


def test_a_stalled_block_raises_the_latency_of_the_blocks_due_behind_it():
    calm = _paced([0.02] * 10)[2]
    rig, ran, lat = _paced([0.02, 0.02, 0.45] + [0.02] * 7)
    t0 = ran["t_open"]
    # block 2 holds the loop from 0.3 to 0.75: blocks 3..6 fell due
    # meanwhile and are released late, all at once
    assert rig.released[3] - t0 == pytest.approx(0.75)
    assert rig.released[6] - t0 == pytest.approx(0.81)
    # that lateness is the system's, not the generator's
    assert ran["lag_s"] == pytest.approx([0.0] * 10)
    # measured from when it was released, block 3 looks as fast as ever...
    assert rig.applied[3] - rig.released[3] == pytest.approx(0.02)
    # ...measured from when its txs were due, it waited for the stall
    assert np.median(lat[3]) == pytest.approx(np.median(calm[3]) + 350.0)
    assert all(np.median(lat[b]) > np.median(calm[b]) + 50 for b in (3, 4, 5, 6))
    # the backlog is worked off by block 8, due at 0.9
    assert np.median(lat[9]) == pytest.approx(np.median(calm[9]))
    assert timeline.percentile(lat.ravel(), 95) > timeline.percentile(
        calm.ravel(), 95) + 200


def test_a_late_wake_up_is_the_generators_lag():
    rig = FakeRig([0.01] * 3)
    overshoot = iter([0.0, 0.0, 0.004, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0])

    def sleepy(dt):
        rig.now += dt + next(overshoot)

    ran = harness.run_paced(rig, _blocks([10] * 3), 0, 3, 100.0,
                            idle_flush_s=0.05, sleep=sleepy)
    assert max(ran["lag_s"]) == pytest.approx(0.004)
    assert sorted(ran["lag_s"])[:2] == pytest.approx([0.0, 0.0])


def test_paced_loop_releases_ragged_blocks_when_their_last_tx_is_due():
    """Blocks of 5, 20, 5 and 10 txs at 100 tx/s, numbered from 7 as
    after a preload that left the ledger there; two warm-up blocks go
    before them in the stream."""
    sizes = [5, 20, 5, 10]
    rig = FakeRig({7 + 2 + k: 0.01 for k in range(4)})
    ran = harness.run_paced(rig, _blocks([10, 10] + sizes, first=7), 2, 4,
                            100.0, idle_flush_s=0.5, sleep=rig.sleep)
    t0 = ran["t_open"]
    assert (ran["first"], ran["submitted"]) == (9, 13)
    assert [rig.released[b] - t0 for b in range(9, 13)] == pytest.approx(
        [0.05, 0.25, 0.30, 0.40])
    assert ran["lag_s"] == pytest.approx([0.0] * 4)


def test_a_stall_releases_every_ragged_block_that_fell_due_meanwhile():
    sizes = [10, 2, 2, 2, 30]
    rig = FakeRig({0: 0.2, 1: 0.01, 2: 0.01, 3: 0.01, 4: 0.01})
    rig.pipe.coalesce_blocks = 8
    taken = []

    def feed(blocks, lo, hi):
        taken.append(hi - lo)
        return FakeRig.feed(rig, blocks, lo, hi)

    rig.feed = feed
    harness.run_paced(rig, _blocks(sizes), 0, 5, 100.0, idle_flush_s=0.5,
                      sleep=rig.sleep)
    # block 0 holds the loop until 0.3: blocks 1..3 (due 0.12, 0.14,
    # 0.16) are all ready then, block 4 (due 0.46) is not
    assert taken[:2] == [1, 3]
