"""How long a backlog stream is made (``run.CellRun.size_stream``), on a
made-up rig whose clock advances only as it is fed: a block whose program
has not run before lowers it and compiles for seconds, any block commits
in a time of its own.  The stream then goes through
``harness.run_backlog`` as a run's does."""

import contextlib
import math
import types

import pytest

from benchmark import harness, manifest, run

COMPILE_S = 5.0
#: the cutter's warm-up: twelve sizes, each the largest of its program
CUTTER_WARM = [500, 341, 256, 170, 128, 85, 64, 42, 32, 21, 16, 10]


def program(size):
    """The program a block of ``size`` txs runs: a cutter's size runs the
    one of the smallest warm-up size that holds it."""
    return min((w for w in CUTTER_WARM if w >= size), default=size)


class FakeRig:
    """``feed`` releases a block, lowers and compiles its program where
    that is new, and commits and applies it ``period(size)`` seconds
    later."""

    def __init__(self, watch, period):
        self.now, self.watch, self.period = 0.0, watch, period
        self.seen: set = set()
        self.released, self.ack, self.applied = {}, {}, {}

    def clock(self):
        return self.now

    def feed(self, blocks, lo, hi):
        blk = blocks[lo]
        num, size = blk.header.number, len(blk.data.data)
        self.released[num] = self.now
        if program(size) not in self.seen:
            self.seen.add(program(size))
            self.now += COMPILE_S
            self.watch.events.append(("lower", self.now, COMPILE_S))
        self.now += self.period(size)
        self.ack[num] = self.applied[num] = self.now
        return 1

    def drain(self):
        pass


class FakeFactory:
    def __init__(self):
        self.n = 0

    def extend(self, n):
        self.n = n

    def __len__(self):
        return self.n


class FakeRun(run.CellRun):
    """What ``size_stream`` reads of a run, and blocks of ``size(k)`` txs
    that are only their sizes."""

    def __init__(self, size, period, traffic, seconds=30.0, base=0):
        self.size, self.base, self.seconds = size, base, seconds
        self.loop = "backlog"
        self.warm = int(traffic["warmup_blocks"])
        self.probe = int(traffic.get("block_sizes", {}).get("cycle", 0))
        self.hint = float(traffic["stream_hint_blocks_per_s"])
        self.compiles = run.CompileWatch()
        self.rig = FakeRig(self.compiles, period)
        self.factory = FakeFactory()
        self.factory.extend(self.warm + self.probe + run.RAMP_BLOCKS
                            + run.stream_blocks(self.hint, 0.0, seconds))
        self.blocks, self.block_txs = [], {}
        self.parts, self._mark = {}, 0.0
        self.cleanup = contextlib.ExitStack()

    def assemble(self, upto):
        for k in range(len(self.blocks), upto):
            num = self.base + k
            self.blocks.append(types.SimpleNamespace(
                header=types.SimpleNamespace(number=num),
                data=types.SimpleNamespace(data=[b""] * self.size(k))))
            self.block_txs[num] = self.size(k)

    def warm_up_and_size(self):
        self.assemble(self.warm)
        self.feed_through(0, self.warm)
        self.size_stream()
        self.assemble(len(self.factory))
        self.cleanup.close()


def cutter_sizes(seed=3):
    """Twelve warm-up sizes, then permuted cycles of 64 of mean 165."""
    import random

    cycle = [min(500, round(10 * 80 ** ((k + 0.5) / 64))) for k in range(64)]
    rng, turns = random.Random(seed), {}

    def size(k):
        if k < len(CUTTER_WARM):
            return CUTTER_WARM[k]
        turn, at = divmod(k - len(CUTTER_WARM), 64)
        if turn not in turns:
            turns[turn] = rng.sample(cycle, 64)
        return turns[turn][at]

    return size, sum(cycle) / 64


def _cutter_traffic():
    _w, _c, traffic = manifest.cell(manifest.load(), "ragged_backlog")
    return traffic


@pytest.mark.parametrize("faster", [1.5, 3.0])
def test_a_rig_faster_than_the_hint_does_not_run_dry(faster):
    traffic = _cutter_traffic()
    hint = traffic["stream_hint_blocks_per_s"]
    size, mean = cutter_sizes()

    def period(txs):
        """``faster`` times the hint's blocks a second, each block in
        proportion to its size"""
        return txs / (faster * hint * mean)

    r = FakeRun(size, period, traffic)
    r.warm_up_and_size()
    # every warm-up block lowered a program: the probe, one whole cycle,
    # gave the rate
    assert r.lead == r.warm + traffic["block_sizes"]["cycle"]
    assert r.stream_rate == pytest.approx(faster * hint, rel=0.02)
    ran = harness.run_backlog(r.rig, r.blocks, r.lead, run.RAMP_BLOCKS,
                              r.seconds)
    assert r.rig.now >= ran["t_open"] + r.seconds
    assert ran["submitted"] < len(r.blocks)

    # the rate the warm-up showed, compiles and all, would have left the
    # hint to size the stream, and the stream would have run dry
    ack = r.rig.ack
    warm_rate = (r.warm - 2) / (ack[r.warm - 1] - ack[1])
    assert run.STREAM_MARGIN * warm_rate < hint
    old = r.blocks[:r.warm + run.RAMP_BLOCKS
                   + run.stream_blocks(hint, warm_rate, r.seconds)]
    old_rig = FakeRig(run.CompileWatch(), period)
    old_rig.seen = set(r.rig.seen)
    with pytest.raises(harness.StreamDry):
        harness.run_backlog(old_rig, old, r.warm, run.RAMP_BLOCKS, r.seconds)


@pytest.mark.parametrize("cell", ["rw_backlog", "insert_backlog",
                                  "zipf_backlog", "sbe_backlog"])
@pytest.mark.parametrize("seed", [1, 2])
def test_a_1000_tx_cells_stream_is_as_long_as_it_was(cell, seed):
    """Their warm-up blocks after the second lower nothing: the rate is
    taken over the same blocks by the same arithmetic as before the
    probe existed, and the stream has the same number of blocks."""
    import random

    _w, config, traffic = manifest.cell(manifest.load(), cell)
    assert "block_sizes" not in traffic and config["block_tx"] == 1000
    rng = random.Random(seed)
    r = FakeRun(lambda _k: 1000, lambda _txs: rng.uniform(0.05, 0.4),
                traffic, base=rng.randrange(99))
    r.warm_up_and_size()
    warm, base, ack = r.warm, r.base, r.rig.ack
    assert r.lead == warm and r.rate_from == (base + 1, base + warm)
    # ``run.py`` before the probe: blocks 2..warm-1 of the warm-up
    warm_rate = (warm - 2) / (ack[base + warm - 1] - ack[base + 1])
    assert r.stream_rate == warm_rate
    assert len(r.blocks) == warm + run.RAMP_BLOCKS + math.ceil(
        max(r.hint, run.STREAM_MARGIN * warm_rate) * r.seconds)


def test_the_probe_falls_before_the_ramp_and_outside_the_window():
    traffic = _cutter_traffic()
    size, mean = cutter_sizes(seed=8)
    r = FakeRun(size, lambda txs: txs / (40 * mean), traffic, base=7)
    r.warm_up_and_size()
    n = traffic["block_sizes"]["cycle"]
    probe = range(r.base + r.warm, r.base + r.warm + n)
    assert r.rate_from == (probe[0], probe[-1] + 1)
    # the probe is one whole cycle: every size of the stream, once
    assert (sorted(r.block_txs[b] for b in probe)
            == sorted(size(r.warm + 64 + i) for i in range(64)))
    ran = harness.run_backlog(r.rig, r.blocks, r.lead, run.RAMP_BLOCKS,
                              r.seconds)
    assert ran["first"] == probe[-1] + 1 + run.RAMP_BLOCKS
    assert all(r.rig.applied[b] < ran["t_open"] for b in probe)
    # the probe blocks are in the chain, fed once, in order
    assert sorted(r.rig.released) == list(
        range(r.base, ran["submitted"]))


def test_a_warm_up_that_lowers_in_every_block_is_timed_on_one_cycle():
    """Whatever the traffic's cycle of block sizes is, the probe is one
    whole turn of it, fed after the warm-up; none of its blocks lowers."""
    traffic = {"warmup_blocks": 5, "stream_hint_blocks_per_s": 4,
               "block_sizes": {"cycle": 6}}
    r = FakeRun(lambda k: 1000 - k if k < 5 else 1000, lambda _txs: 0.1,
                traffic)
    r.warm_up_and_size()
    assert r.lead == 5 + 6 and r.rate_from == (5, 5 + 6)
    assert r.stream_rate == pytest.approx(10.0)
    assert len(r.blocks) == r.lead + run.RAMP_BLOCKS + math.ceil(
        run.STREAM_MARGIN * 10.0 * r.seconds)


def test_a_warm_up_that_lowers_in_every_block_without_a_cycle_fails():
    """No block of the warm-up times the program and the traffic has no
    cycle to probe: the run stops in set-up, as a warm-up under three
    blocks does, rather than size its stream from the compiler's pace."""
    traffic = {"warmup_blocks": 5, "stream_hint_blocks_per_s": 4}
    r = FakeRun(lambda k: 1000 - k if k < 5 else 1000, lambda _txs: 0.1,
                traffic)
    with pytest.raises(run.RunFailed, match="no cycle"):
        r.warm_up_and_size()
