#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
with ``--trace 1``).  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, taken with the tracer disarmed and neither the
launch ledger nor the tx-flow journal acquired; with ``--trace 1`` all
three are armed, the program's counters are read as each block's tree
is finished (``counters.py``), a ``jax.profiler`` capture covers a few
seconds of the window, and the metrics are the cell's per-layer
metrics.  Any platform but ``tpu``, or fewer chips than the cell asks
for, exits non-zero and prints no result; so does a run that could not
be measured (a stream that ran dry).  A run whose outputs are wrong
prints ``"correct": false``.

Set-up, in the order it is paid (``setup_s`` is all of it): a child
that builds what the ledger starts from (the configuration's preload:
the state DB, and for a peer joined from a snapshot the txid index at
the snapshot's height), the network's keys and worker processes that
sign the block stream, all started before this process imports ``jax``;
the native libraries (built on a checkout's first run); the device and
the compile cache; the warm-up blocks through the pipeline itself, which
trace, lower and compile (or load) the cell's programs; in a backlog run
where every warm-up block lowered a program, a probe: the stream's next
whole cycle of block sizes, timed; the rest of the stream, sized from a
rate taken on blocks that lowered no program; the flush of what set-up
wrote.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures as cf  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: counters that must not move: each is a path that would let a block
#: commit without the device doing the work (chip_smoke.ZERO_COUNTERS)
ZERO_COUNTERS = ("fallback_blocks_total", "validator_degraded",
                 "mesh_shard_fallback_total",
                 "commit_pipeline_stage_failures_total")
#: blocks submitted before the window opens in a backlog run, after the
#: warm-up: the pipeline and the apply queue are then full
RAMP_BLOCKS = 2
#: how much longer than rate x seconds a backlog stream is made.  More
#: than it looks: the rate is that of a short chain, and every cell is a
#: fifth slower by the end of a window (PERF.md, section 6); and a stream may
#: end after four fifths of the window.  The traffic's
#: ``stream_hint_blocks_per_s`` is the other bound, and the larger of the
#: two holds: the workers sign that many blocks a second of window before
#: any rate is known, and the stream is never cut below it.
STREAM_MARGIN = 1.15
#: blocks held to the OpenSSL reference and read back: the first and the
#: last of the window and this many between
SAMPLED_BETWEEN = 2
#: what ``correct`` compares, each a count of faults with the limit 0 (the
#: comparisons are exact).  A result carries every one beside its limit.
COMPARED = (
    "blocks_whose_filter_differs_from_reference",
    "blocks_where_the_two_references_disagree",
    "blocks_not_from_fused_device_path",
    "keys_wrong_after_flush",
    "keys_wrong_after_reopen",
    "ledger_height_off_by",
    "reopened_height_or_commit_hash_differs",
    "fallback_counters_moved",
    "programs_lowered_in_window",
    "tx_not_committed_and_applied",
    "blocks_without_one_verify_and_one_stage2_launch",
    "blocks_whose_duplicate_hits_differ_from_stream",
    "captures_without_device_work",
)
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
}


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - _T_START:7.2f}s] {msg}", flush=True)


class RunFailed(RuntimeError):
    """The run cannot give a result."""


def counter_total(name: str) -> float:
    from fabric_tpu.ops_metrics import global_registry

    m = global_registry().metric(name)
    return 0.0 if m is None else float(sum(m.snapshot().values()))


class CompileWatch:
    """When jax traced, lowered and compiled (or loaded) a program, on
    the harness's clock, and the persistent cache's hits and misses."""

    def __init__(self):
        self.events: list = []   # (part, t_end, seconds)
        self.hits = self.misses = 0

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)

    def _dur(self, event, duration, **_kw):
        part = COMPILE_EVENTS.get(event)
        if part is not None:
            self.events.append((part, time.perf_counter(), duration))

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def lowered_between(self, t0: float, t1: float) -> int:
        """Programs lowered inside [t0, t1]: each is a shape the warm-up
        did not reach."""
        return sum(1 for part, t, _d in self.events
                   if part == "lower" and t0 <= t <= t1)

    def seconds(self) -> dict:
        out = dict.fromkeys(COMPILE_EVENTS.values(), 0.0)
        for part, _t, d in self.events:
            out[part] += d
        return {k: round(v, 2) for k, v in out.items()}


class Capture(threading.Thread):
    """A ``jax.profiler`` capture of the window's last few seconds, from a
    thread of its own so that the feed loop never waits for it.  The last,
    because stopping a capture serialises it (a quarter of a minute for
    four seconds of this program, with the interpreter held for much of
    it): that then falls after the window and disturbs no block.  Two
    ``bench.anchor`` annotations bracket the capture; each is also read on
    the harness's clock, which places the program's spans on the
    profiler's clock.  ``span``: the capture's length where the traffic
    gives one (``capture_s``: a cell that launches many times a second
    serialises for longer than a run may last)."""

    def __init__(self, dirpath: str, seconds: float, opened,
                 span: float | None = None):
        super().__init__(name="bench-capture", daemon=True)
        self.dir, self.opened = dirpath, opened
        self.span = min(4.0, seconds / 3.0) if span is None else span
        self.lead = max(0.0, seconds - self.span - 0.5)
        self.anchors: list = []
        self.error = None

    def _anchor(self) -> None:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.anchor"):
            self.anchors.append(time.perf_counter())

    def run(self) -> None:
        import jax

        try:
            if not self.opened.wait(timeout=900.0):
                raise TimeoutError("the window never opened")
            time.sleep(self.lead)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                self._anchor()
                time.sleep(self.span)
                self._anchor()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # surfaced by the caller: the run fails
            self.error = e

    def window(self, device_trace) -> tuple:
        """→ (t0, t1, offset): the captured interval on the profiler's
        clock, and profiler time minus harness time."""
        got = device_trace.host.get("bench.anchor", [])
        if len(got) != 2 or len(self.anchors) != 2:
            raise RunFailed(f"capture has {len(got)} anchors, wants 2")
        (a0, _), (a1, d1) = got
        offset = ((a0 - self.anchors[0]) + (a1 - self.anchors[1])) / 2.0
        return a0, a1 + d1, offset


def compile_free_from(released: dict, lowered, first: int, end: int) -> int:
    """The first of blocks ``first..end-1`` released after the last
    lowering ended (``lowered``: when each one did); ``end`` if none was.
    From there on no block lowered a program: a rate taken over them is
    the program's, not the compiler's."""
    last = max(lowered, default=-math.inf)
    return next((b for b in range(first, end) if released[b] > last), end)


def blocks_per_s(ack: dict, block_txs: dict, first: int, end: int) -> float:
    """The rate of blocks ``first+1..end-1``, from the acknowledgement of
    block ``first`` to that of block ``end-1``, counted in blocks of the
    mean size of ``first..end-1``: where sizes vary (a cutter's cycle),
    the txs a second over the blocks the interval holds, divided by that
    mean; where they do not, the count of blocks over the interval."""
    timed = [block_txs[b] for b in range(first + 1, end)]
    mean = sum(block_txs[b] for b in range(first, end)) / (end - first)
    return (len(timed) / (ack[end - 1] - ack[first])
            * (sum(timed) / len(timed) / mean))


def stream_blocks(hint: float, rate: float, seconds: float) -> int:
    """The blocks a backlog stream holds after the ramp."""
    return math.ceil(max(hint, STREAM_MARGIN * rate) * seconds)


def sample_blocks(first: int, last: int, seed: int) -> list:
    import numpy as np

    between = list(range(first + 1, last))
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    picked = (rng.choice(between, min(SAMPLED_BETWEEN, len(between)),
                         replace=False).tolist() if between else [])
    return sorted({first, last, *picked})


def check_reopened(ledger_dir: str, config: dict, end: dict, state,
                   keys, reference=None) -> list:
    """Reopen the ledger directory: same height, same commit hash, the
    same state on ``keys``."""
    from fabric_tpu.ledger.kvledger import KVLedger

    from benchmark import manifest

    reference = reference or manifest.reference_of(config)
    lg = KVLedger(ledger_dir, enable_history=bool(config["history_db"]))
    try:
        out = []
        if lg.height != end["height"]:
            out.append(f"reopened height {lg.height} != {end['height']}")
        if (lg.commit_hash or b"").hex() != end["commit_hash"]:
            out.append("reopened commit hash differs")
        out += [f"after reopen: {m}" for m in reference.state_mismatches(
            lg.state, config["chaincode"], state, keys)[:8]]
        return out
    finally:
        lg.close()


def read_metrics(root: str, kind: str, entries, obs, must: bool) -> dict:
    """Each metric's reader (``benchmark/<kind>/<name>.py``) over the
    run's observations.  A reader that finds nothing to read returns
    None and the metric is left out, unless it ``must`` be there."""
    from benchmark import manifest

    out = {}
    for m in entries:
        value = manifest.load_module(kind, m["name"], root).read(obs)
        if value is None:
            if must:
                raise RunFailed(f"{m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class CellRun:
    """One run of one cell, in the order a run goes: :meth:`prepare`
    (children first, then ``jax`` and the device), :meth:`warm_up`,
    :meth:`measure`, :meth:`check`, :meth:`report`."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, platform: str, workers, t_start: float):
        from benchmark import manifest, timeline

        self.root, self.workload, self.seed = root, workload, int(seed)
        self.seconds, self.trace, self.platform = seconds, trace, platform
        self.workers, self.t_start = workers, t_start
        self.parts: dict = {}   # where set-up's seconds go
        self._mark = t_start
        self.man = manifest.load(root)
        self.cell, self.config, self.traffic = manifest.cell(
            self.man, workload, root)
        self.loop = self.traffic["loop"]
        if self.loop not in ("backlog", "paced"):
            raise RunFailed(f"traffic {self.cell['traffic']!r}: loop "
                            f"{self.loop!r}")
        #: the configuration's nominal block size: what sizes the stream
        #: before a block of it exists.  A block's txs are counted on
        #: the block
        self.T = int(self.config["block_tx"])
        self.base = int(self.config.get("first_block", 0))
        self.reference = manifest.reference_of(self.config, root)
        self.warm = int(self.traffic["warmup_blocks"])
        if self.warm < 3:
            raise RunFailed("warmup_blocks under 3 gives no warm rate")
        #: the probe's length, where every warm-up block lowers a program
        #: and none of them gives a rate: one whole cycle of the traffic's
        #: block sizes, so that it holds each size once (0: no cycle)
        self.probe = int(self.traffic.get("block_sizes", {}).get("cycle", 0))
        if self.loop == "paced":
            self.rate = float(self.traffic["rate_tx_per_s"])
        self.here = os.path.join(root, manifest.HERE)
        self.work = os.path.join(self.here, ".work",
                                 f"{workload}-{os.getpid()}")
        self.blocks, self.plans, self._prev = [], [], b""
        self.block_txs: dict = {}   # block number → its txs
        self.replayed: dict = {}    # block number → txids it replays
        self.roots: list = []
        self.rig = self.factory = self.loader = self.capture = None
        self.snapshots = None   # the program's counters, traced runs only
        self.cleanup = contextlib.ExitStack()
        self.problems: list = []
        self.compared = dict.fromkeys(COMPARED, 0)

    def fault(self, what: str, message: str, by: int = 1) -> None:
        """One more of ``what`` (a name of ``COMPARED``) is wrong."""
        self.compared[what] += by
        self.problems.append(message)

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = round(self.parts.get(name, 0.0) + now
                                 - self._mark, 2)
        self._mark = now

    def close(self) -> None:
        if self.rig is not None:
            with contextlib.suppress(Exception):
                self.rig.close()
        self.cleanup.close()

    # -- set-up --------------------------------------------------------------

    def prepare(self) -> None:
        from benchmark import counters, preload, stream, timeline

        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        self.cleanup.callback(shutil.rmtree, self.work, ignore_errors=True)
        # the preload first: loading a million keys (or ten million
        # txids) is the longest thing a child does, and the warm-up
        # cannot start without it
        self.loader = cf.ProcessPoolExecutor(
            1, mp_context=multiprocessing.get_context("spawn"))
        self.cleanup.callback(self.loader.shutdown, cancel_futures=True)
        loaded = self.loader.submit(
            preload.ensure_named, self.root,
            os.path.join(self.here, ".cache"), self.config, self.seed)
        self.network = stream.make_network(self.config)
        self.factory = stream.BlockFactory(
            self.root, self.config, self.traffic, self.seed, self.network,
            workers=self.workers)
        self.cleanup.callback(self.factory.close)
        if self.loop == "paced":
            # one more than fall due at the nominal size: the block that
            # shows the window is full
            self.factory.extend(self.warm + 1 + timeline.paced_blocks(
                self.seconds, self.rate, itertools.repeat(self.T)))
        else:
            self.hint = float(self.traffic.get("stream_hint_blocks_per_s", 0))
            self.factory.extend(self.warm + self.probe + RAMP_BLOCKS
                                + stream_blocks(self.hint, 0.0, self.seconds))
        self.lap("keys_and_children")

        from fabric_tpu import native

        for name in ("blockparse", "mvccprep", "ecprep"):
            if getattr(native, name + "_lib")() is None:
                raise RunFailed(f"native library {name} did not build")
        self.lap("native_libraries")

        from fabric_tpu import observe
        from fabric_tpu.crypto import policy as pol
        from fabric_tpu.observe import ledger as launch_ledger
        from fabric_tpu.observe import txflow
        from fabric_tpu.peer.validator import NamespaceInfo, PolicyProvider
        from fabric_tpu.utils.xla_env import (claim_device,
                                              enable_compile_cache)

        self.device = claim_device("benchmark")
        if self.device["platform"] != self.platform:
            raise RunFailed(
                f"needs platform {self.platform!r}, jax found "
                f"{self.device['platform']!r} ({self.device['kind']} "
                f"x{self.device['count']})")
        if self.device["count"] < int(self.cell["chips"]):
            raise RunFailed(
                f"cell {self.workload!r} needs {self.cell['chips']} chips, "
                f"jax found {self.device['count']}")
        say(f"{self.workload}: {self.device['platform']} "
            f"{self.device['kind']} x{self.device['count']}, compile cache "
            f"{enable_compile_cache()}")
        self.lap("import_jax_and_claim_device")

        self.ledger_dir = os.path.join(self.work, "ledger")
        template, built, start = loaded.result()
        self.lap("preload_wait")
        shutil.copytree(template, self.ledger_dir)
        self.lap("preload_copy")
        self.parts["preload_build_in_child"] = round(built, 2)
        for part, took in start.get("build_parts_s", {}).items():
            self.parts[f"preload_build.{part}"] = round(took, 2)
        self._prev = start["prev_hash"]
        # set-up has just written the preload once or twice over.  Left
        # to the kernel, those pages are written back half a minute later,
        # a few seconds into the window.  Without this flush one block of
        # a paced run in six waited over a second longer for its state
        # (2 runs of 13); with it none of 12 did (PERF.md, PR 22).
        self.flushed = threading.Thread(target=os.sync, name="bench-sync",
                                        daemon=True)
        self.flushed.start()

        self.mgr = stream.msp_manager(self.network)
        self.prov = PolicyProvider({self.config["chaincode"]: NamespaceInfo(
            policy=pol.from_dsl(self.config["endorsement_policy"]))})
        tracer = observe.global_tracer()
        self.cleanup.callback(observe.configure,
                              ring_blocks=tracer.ring_blocks)
        self.led = self.annotate = None
        if self.trace:
            from jax.profiler import TraceAnnotation

            self.annotate = TraceAnnotation
            observe.configure(ring_blocks=observe.DEFAULT_RING_BLOCKS)
            tracer.add_listener(self.roots.append)
            self.cleanup.callback(tracer.remove_listener, self.roots.append)
            self.snapshots = counters.Snapshots()
            tracer.add_listener(self.snapshots)
            self.cleanup.callback(tracer.remove_listener, self.snapshots)
            self.led = launch_ledger.acquire(ring=1 << 16)
            self.cleanup.callback(launch_ledger.release)
            txflow.acquire()
            self.cleanup.callback(txflow.release)
        else:
            observe.configure(ring_blocks=0)
        self.zero_before = {n: counter_total(n) for n in ZERO_COUNTERS}
        self.compiles = self.cleanup.enter_context(CompileWatch())

    def assemble(self, upto: int) -> None:
        """Chain the stream's blocks up to the ``upto``-th as the workers
        deliver them, from the block and the hash the preload left."""
        from benchmark import stream

        for k in range(len(self.blocks), upto):
            rows, envs, replayed = self.factory.take(k)
            blk, self._prev = stream.chain(self.base + k, self._prev, envs)
            self.blocks.append(blk)
            self.plans.append(rows)
            self.block_txs[self.base + k] = len(blk.data.data)
            self.replayed[self.base + k] = replayed

    def warm_up(self) -> None:
        """The cell's own shapes, through the pipeline itself; then the
        rest of the stream, as :meth:`size_stream` sizes it."""
        from benchmark import harness, timeline

        self.rig = harness.Rig(self.ledger_dir, self.config, self.mgr,
                               self.prov, annotate=self.annotate)
        rig, warm, base = self.rig, self.warm, self.base
        if rig.lg.height != base:
            raise RunFailed(f"the ledger opens at height {rig.lg.height}, "
                            f"the stream starts at block {base}")
        self.assemble(warm)
        self.lap("first_blocks_wait")
        self.feed_through(0, warm)
        self.lap("warmup_blocks")
        say(f"warm-up: {warm} blocks; jax {self.compiles.seconds()}, "
            f"persistent cache {self.compiles.hits} hits / "
            f"{self.compiles.misses} misses")
        self.size_stream()
        self.assemble(len(self.factory))
        if self.loop == "paced":
            # blocks smaller than the nominal size: more of them fall due
            while True:
                sizes = [len(b.data.data) for b in self.blocks[warm:]]
                self.n_due = timeline.paced_blocks(self.seconds, self.rate,
                                                   sizes)
                if self.n_due < len(sizes):
                    break
                self.factory.extend(len(self.factory) + warm)
                self.assemble(len(self.factory))
        self.factory.close()
        self.loader.shutdown()
        # the stream's millions of small objects must not be walked by a
        # collection inside the window
        gc.collect()
        gc.freeze()
        self.cleanup.callback(gc.unfreeze)
        self.lap("rest_of_stream_wait")
        self.flushed.join()
        self.lap("flush_of_setup_writes_wait")
        sizes = set(self.block_txs.values())
        say(f"stream: blocks {base}..{base + len(self.blocks) - 1} of "
            f"{min(sizes)}..{max(sizes)} tx")

    def feed_through(self, lo: int, hi: int) -> None:
        """Feed the stream's blocks ``lo..hi-1`` and drain the pipeline."""
        k = lo
        while k < hi:
            k += self.rig.feed(self.blocks, k, hi)
        self.rig.drain()

    def size_stream(self) -> None:
        """The rate that sizes a backlog stream, taken on blocks that
        lowered no program: the warm-up's last ones or, where every
        warm-up block lowered one, a probe of the stream's next
        ``self.probe`` blocks, fed before the ramp; then the rest of the
        stream, ``stream_blocks`` after the ramp.  ``lead``: the blocks
        fed before the ramp."""
        rig, base, warm = self.rig, self.base, self.warm
        lowered = [t for part, t, _d in self.compiles.events
                   if part == "lower"]
        # the first block compiled and the second was parsed under it:
        # the rate starts at the second's acknowledgement at the earliest
        k = compile_free_from(rig.released, lowered, base + 2, base + warm)
        self.lead, self.rate_from = warm, (k - 1, base + warm)
        if self.loop == "backlog" and k == base + warm:
            n = self.probe
            if n < 2:
                raise RunFailed("every warm-up block lowered a program and "
                                "the traffic has no cycle of block sizes "
                                "to time instead")
            self.factory.extend(max(len(self.factory), warm + n))
            # the probe runs as the window will: the workers done with
            # what they were asked for first, and the stream's objects
            # out of the collector's walks
            self.assemble(len(self.factory))
            gc.collect()
            gc.freeze()
            self.cleanup.callback(gc.unfreeze)
            self.lap("rest_of_stream_wait")
            self.feed_through(warm, warm + n)
            self.lead, self.rate_from = warm + n, (base + warm,
                                                   base + warm + n)
            self.lap("probe_blocks")
        lo, hi = self.rate_from
        self.stream_rate = (blocks_per_s(rig.ack, self.block_txs, lo, hi)
                            if hi - lo >= 2 else None)
        if self.loop == "backlog":
            self.factory.extend(self.lead + RAMP_BLOCKS + stream_blocks(
                self.hint, self.stream_rate, self.seconds))
        if self.stream_rate is not None:
            lowered = self.compiles.lowered_between(rig.released[lo + 1],
                                                    rig.ack[hi - 1])
            say(f"rate: {self.stream_rate:.2f} blocks/s over blocks "
                f"{lo + 1}..{hi - 1} ({self.lead - warm} probe blocks, "
                f"{lowered} programs lowered); stream of "
                f"{len(self.factory)} blocks")

    # -- the window ----------------------------------------------------------

    def measure(self) -> None:
        from fabric_tpu.peer.node import PeerChannel

        from benchmark import harness, timeline

        rig, opened = self.rig, threading.Event()
        if self.trace:
            self.capture = Capture(os.path.join(self.work, "trace"),
                                   self.seconds, opened,
                                   self.traffic.get("capture_s"))
            self.capture.start()
        if self.loop == "backlog":
            ran = harness.run_backlog(
                rig, self.blocks, self.lead, RAMP_BLOCKS, self.seconds,
                on_open=lambda _t: opened.set())
        else:
            ran = harness.run_paced(
                rig, self.blocks, self.warm, self.n_due, self.rate,
                PeerChannel.PIPELINE_IDLE_FLUSH_S,
                on_open=lambda _t: opened.set())
        self.t_end = time.perf_counter()
        if self.capture is not None:
            self.capture.join()
        self.t_open, self.first = ran["t_open"], ran["first"]
        self.submitted, self.lag_s = ran["submitted"], ran.get("lag_s")
        self.ack, self.applied = dict(rig.ack), dict(rig.applied)
        self.latencies = None
        if self.loop == "backlog":
            self.t_close, n_in = timeline.backlog_window(
                self.t_open, self.seconds,
                [self.applied[b] for b in range(self.first, self.submitted)
                 if b in self.applied])
            self.last = self.first + n_in - 1
        else:
            self.last = self.first + self.n_due - 1
            done = [self.applied[b] for b in range(self.first, self.last + 1)
                    if b in self.applied]
            self.t_close = max(done, default=self.t_open + self.seconds)
            if len(done) == self.n_due:
                self.latencies = timeline.tx_latencies_ms(
                    self.t_open, done, self.rate,
                    [self.block_txs[b] for b in range(self.first,
                                                      self.last + 1)])
        self.parts["ramp_to_window_open"] = round(self.t_open - self._mark, 2)
        say(f"window: blocks {self.first}..{self.last} in "
            f"{self.t_close - self.t_open:.2f} s; "
            f"{self.submitted - self.first} submitted, drained "
            f"{self.t_end - self.t_open:.2f} s after it opened")

    # -- correct? (outside the window) ---------------------------------------

    def check(self) -> None:
        from fabric_tpu.protos import transaction_pb2

        from benchmark import reference as plain_reference
        from benchmark import spans

        rig, config, reference = self.rig, self.config, self.reference
        # a configuration's own reference may name the keys its rows
        # write (it owns the row format); one that does not gets the
        # default's
        written_keys = getattr(reference, "written_keys",
                               plain_reference.written_keys)
        first, last, submitted = self.first, self.last, self.submitted
        base, txs = self.base, self.block_txs
        VALID = transaction_pb2.TxValidationCode.VALID
        problems, fault = self.problems, self.fault
        self.attempted = sum(txs[b] for b in range(first, submitted))
        self.failed = sum(txs[b] for b in range(first, submitted)
                          if b not in self.ack or b not in self.applied)
        state = reference.RefState(config)
        sampled = sample_blocks(first, last, self.seed)
        touched: set = set()
        self.n_valid = 0
        for b in range(base, submitted):
            plan, blk = self.plans[b - base], self.blocks[b - base]
            want, done = reference.plan_codes(plan, b, state)
            if b in sampled:
                full, full_done = reference.openssl_codes(
                    blk, self.mgr, self.prov, state, config["chaincode"])
                if full != want or full_done != done:
                    fault("blocks_where_the_two_references_disagree",
                          f"block {b}: the OpenSSL reference and the "
                          "plan-level reference disagree")
                touched.update(written_keys(plan))
            got = rig.filters.get(b)
            if got is not None:
                if got != want:
                    diff = ([i for i in range(min(len(got), len(want)))
                             if got[i] != want[i]][:8]
                            or f"{len(got)} verdicts for {len(want)} txs")
                    fault("blocks_whose_filter_differs_from_reference",
                          f"block {b}: tx filter differs from the reference "
                          f"at {diff}")
                if first <= b <= last:
                    self.n_valid += got.count(VALID)
                if not rig.fused.get(b):
                    fault("blocks_not_from_fused_device_path",
                          f"block {b}: verdicts not from the fused device "
                          "path")
                elif rig.devices.get(b) != [self.platform]:
                    fault("blocks_not_from_fused_device_path",
                          f"block {b}: verify output on "
                          f"{rig.devices.get(b)}")
            state.commit(done, reference.block_txids(blk))
        keys = sorted(touched)
        for m in reference.state_mismatches(
                rig.lg.state, config["chaincode"], state, keys):
            fault("keys_wrong_after_flush", f"after flush: {m}")
        self.launch_rows = []
        if self.trace:
            self.launch_rows = [r for r in self.led.rows()
                                if str(r.get("block", "")).isdigit()]
            for b in range(first, last + 1):
                mine = [r["kernel"] for r in self.launch_rows
                        if r["block"] == str(b)]
                if mine.count("verify") != 1 or mine.count("stage2") != 1:
                    fault("blocks_without_one_verify_and_one_stage2_launch",
                          f"block {b}: launches {mine}, want one verify and "
                          "one stage2")
            # the duplicate check found the stream's replays and no other
            hits = spans.attr_per_block(self.roots, "dup_txid", "hits")
            for b in range(first, last + 1):
                if b in hits and hits[b] != self.replayed[b]:
                    fault("blocks_whose_duplicate_hits_differ_from_stream",
                          f"block {b}: the duplicate check hit {hits[b]} "
                          f"txs, the stream replays {self.replayed[b]}")
        self.depth = rig.pipe.depth
        end = rig.close()
        self.rig = None
        if end["height"] != submitted:
            fault("ledger_height_off_by",
                  f"ledger height {end['height']} != {submitted}: first "
                  f"block {base} and {submitted - base} blocks submitted",
                  by=abs(end["height"] - submitted))
        for m in check_reopened(self.ledger_dir, config, end, state, keys,
                                reference):
            fault("keys_wrong_after_reopen" if m.startswith("after reopen")
                  else "reopened_height_or_commit_hash_differs", m)
        moved = {n: counter_total(n) - v
                 for n, v in self.zero_before.items()}
        if any(moved.values()):
            fault("fallback_counters_moved",
                  f"fallback counters moved: {moved}",
                  by=sum(1 for v in moved.values() if v))
        lowered = self.compiles.lowered_between(self.t_open, self.t_close)
        if lowered:
            fault("programs_lowered_in_window",
                  f"{lowered} programs were lowered inside the window",
                  by=lowered)
        if self.failed:
            fault("tx_not_committed_and_applied",
                  f"{self.failed} tx were not committed and applied",
                  by=self.failed)
        say(f"checked {submitted - base} blocks against the reference, "
            f"{len(sampled)} of them with OpenSSL and {len(keys)} keys "
            f"read back twice, in {time.perf_counter() - self.t_end:.1f} s: "
            f"{problems[:6] or 'ok'}")

    # -- metrics -------------------------------------------------------------

    def report(self, keep_trace: str | None = None) -> dict:
        import jax

        from benchmark import manifest, spans, trace_reduce

        first, last, device = self.first, self.last, dict(self.device)
        device_trace = window = None
        if self.capture is not None:
            if self.capture.error is not None:
                raise RunFailed("profiler capture failed: "
                                f"{self.capture.error!r}")
            xplane = trace_reduce.find_xplane(self.capture.dir)
            device_trace = trace_reduce.read(xplane)
            window = self.capture.window(device_trace)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(
                    keep_trace, f"{self.workload}.xplane.pb"))
        obs = types.SimpleNamespace(
            cell=self.workload, config=self.config, traffic=self.traffic,
            loop=self.loop, seconds=self.seconds, block_tx=self.T,
            block_txs=[self.block_txs[b] for b in range(first, last + 1)],
            depth=self.depth, device_kind=device["kind"],
            t_start=self.t_start, t_open=self.t_open, t_close=self.t_close,
            first=first, last=last, ack=self.ack, applied=self.applied,
            lag_s=self.lag_s, tx_latencies_ms=self.latencies,
            n_valid=self.n_valid,
            roots=[r for r in self.roots
                   if first <= spans.block_of(r) <= last],
            launch_rows=[r for r in self.launch_rows
                         if first <= int(r["block"]) <= last],
            device_trace=device_trace, capture_window=window,
            counters=({} if self.snapshots is None
                      else self.snapshots.window(first, last)),
        )
        if self.snapshots is not None:
            say(f"counters: {len(obs.counters)} snapshots for blocks "
                f"{first - 1}..{last}, {len(obs.counters.get(last, ()))} "
                "values in the last")
        e2e = manifest.metrics_of(self.man, "end_to_end", self.workload)
        if self.trace:
            reported = {m["name"] for m in e2e}
            entries = [m for m in manifest.metrics_of(
                self.man, "per_layer", self.workload)
                if m["moves"] in reported]
            metrics = read_metrics(self.root, "layer_metrics", entries, obs,
                                   must=False)
        else:
            metrics = read_metrics(self.root, "end_to_end", e2e, obs,
                                   must=True)
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        device["memory_peak_bytes"] = int(max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0))
        result = {"correct": True, "attempted": self.attempted,
                  "failed": self.failed, "metrics": metrics,
                  "device": device}
        if self.trace:
            t0, t1, offset = window
            device["busy_s"] = trace_reduce.busy_seconds(device_trace, t0, t1)
            device["window_s"] = t1 - t0
            if device["busy_s"] <= 0:
                self.fault("captures_without_device_work",
                           "no operation ran on the device inside the "
                           "capture")
            host = {label: [(a + offset, b + offset) for a, b in ivals]
                    for label, ivals in
                    spans.self_intervals(self.roots).items()}
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(device_trace, t0, t1),
                "idle_gaps": trace_reduce.attribute_gaps(
                    trace_reduce.idle_gaps(device_trace, t0, t1), host),
            }
        result["correct"] = not any(self.compared.values())
        # beside what the driver reads: why a run was not correct, where
        # set-up's seconds went, and when each block after the ramp was
        # applied (what any other window length would have given)
        result["problems"] = self.problems[:20]
        result["setup_parts_s"] = self.parts
        result["window"] = {
            "first_block": first, "last_block": last,
            "seconds": self.t_close - self.t_open, "valid_tx": self.n_valid,
            "rate_blocks_per_s": self.stream_rate,
            "rate_blocks": [self.rate_from[0] + 1, self.rate_from[1] - 1],
            "probe_blocks": self.lead - self.warm,
            "stream_blocks": len(self.blocks),
            "applied_s": [round(self.applied[b] - self.t_open, 4)
                          for b in range(first, self.submitted)
                          if b in self.applied],
        }
        if self.trace:
            # the pipeline's stages block by block: what grows with the
            # chain shows here and not in a median
            result["window"]["per_block_ms"] = {
                name: [round(v, 2) for v in spans.ms_per_block(
                    obs.roots, [name])]
                for name in ("prefetch", "launch", "state_fill", "finish",
                             "commit")}
        # last: each number ``correct`` compared, beside its limit
        result["compared"] = {name: {"value": n, "limit": 0}
                              for name, n in self.compared.items()}
        return result


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, platform: str = "tpu", workers=None,
             t_start: float | None = None,
             keep_trace: str | None = None) -> dict:
    """One run of one cell → the result object.  ``platform`` is what jax
    must report (the tests name ``cpu``); ``keep_trace`` is a directory
    that gets a copy of the raw capture."""
    run = CellRun(root, workload, seed, seconds, trace, platform, workers,
                  time.perf_counter() if t_start is None else t_start)
    try:
        run.prepare()
        run.warm_up()
        run.measure()
        run.check()
        return run.report(keep_trace)
    finally:
        run.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, copy the raw .xplane.pb here")
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=_T_START,
                          keep_trace=args.keep_trace)
    except RunFailed as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
