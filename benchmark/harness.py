"""The system under test and the two loops that feed it.

The rig is what a deployed peer's deliver loop drives (``peer/node.py``
``_run_deliver_pipelined``): ``CommitPipeline`` → ``BlockValidator`` →
``KVLedger.commit_block`` + ``BlockStore.sync()``, every knob at its
``PeerConfig`` default, whatever that is at the commit under test.  The
harness adds clocks on its edges (release, commit acknowledged, state
applied) and nothing inside.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import threading
import time

from benchmark import timeline

#: blocks the deliver loop can hold ready beside the one in hand
#: (``asyncio.Queue(maxsize=4)`` in ``_run_deliver_pipelined``): what a
#: coalescing pipeline may be handed at once
DELIVER_QUEUE = 4


def peer_defaults() -> dict:
    from fabric_tpu.nodeconfig import PeerConfig

    return {f.name: f.default for f in dataclasses.fields(PeerConfig)
            if f.default is not dataclasses.MISSING}


def knobs(fn, defaults: dict, **rename) -> dict:
    """The arguments of ``fn`` that are ``PeerConfig`` fields, at the
    fields' defaults: a PR that changes a default is thereby measured,
    and one that deletes a knob breaks nothing here."""
    out = {}
    for p in inspect.signature(fn).parameters:
        field = rename.get(p, p)
        if field in defaults:
            out[p] = defaults[field]
    return out


class Rig:
    """One ledger directory, one validator, one pipeline, and when each
    block was released, acknowledged and applied."""

    def __init__(self, ledger_dir: str, config: dict, mgr, prov,
                 annotate=None, clock=time.perf_counter):
        """``ledger_dir`` holds what the configuration's preload left:
        the ledger's height there is the number of the first block the
        rig will be fed."""
        from fabric_tpu.ledger.kvledger import KVLedger
        from fabric_tpu.peer.pipeline import CommitPipeline
        from fabric_tpu.peer.validator import BlockValidator

        d = peer_defaults()
        self.clock = clock
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.lg = KVLedger(ledger_dir,
                           enable_history=bool(config["history_db"]),
                           **knobs(KVLedger.__init__, d))
        if "group_commit" in d:
            self.lg.blocks.group_commit = d["group_commit"]
        self.v = BlockValidator(mgr, prov, self.lg.state,
                                block_store=self.lg.blocks,
                                **knobs(BlockValidator.__init__, d))
        self.pipe = CommitPipeline(
            self.v, self._commit, channel=config["channel"],
            **knobs(CommitPipeline.__init__, d, depth="pipeline_depth"))
        self.filters: dict = {}
        self.fused: dict = {}      # block → its verdicts came from stage 2
        self.devices: dict = {}    # block → platforms of the verify output
        self.released: dict = {}
        self.ack: dict = {}
        self.applied: dict = {}
        self._stop = threading.Event()
        self._watcher = None
        if self.lg.engine is not None:
            self._watcher = threading.Thread(
                target=self._watch_applied, args=(self.lg.height,),
                name="bench-applied", daemon=True)
            self._watcher.start()

    def _commit(self, res) -> None:
        """The pipeline's ``commit_fn``: the ledger commit, then the sync
        that must precede the acknowledgement, as ``_commit_inner`` does
        (a deep pipeline's mid-window commits defer it)."""
        num = res.block.header.number
        with self.annotate("bench.commit"):
            self.lg.commit_block(res.block, res.tx_filter, res.batch,
                                 res.history, None, res.txids,
                                 res.pend.hd_bytes)
            if not res.defer_sync:
                self.lg.blocks.sync()
        self.ack[num] = self.clock()
        if self.lg.engine is None:
            self.applied[num] = self.ack[num]
        self.filters[num] = bytes(res.tx_filter)
        self.fused[num] = getattr(res.pend, "fetch2", None) is not None
        out = getattr(res.pend.fetch, "device_out", None)
        self.devices[num] = (sorted({d.platform for d in out.devices()})
                             if out is not None else [])

    def _watch_applied(self, num: int) -> None:
        while not self._stop.is_set():
            if self.lg.engine.wait_applied(num, timeout=0.2):
                self.applied[num] = self.clock()
                num += 1

    def feed(self, blocks, lo: int, hi: int) -> int:
        """Hand the pipeline the next block of ``blocks[lo:hi]`` (those
        that are ready), or as many as it coalesces, as the deliver loop
        does.  → how many it took."""
        k = int(getattr(self.pipe, "coalesce_blocks", 0) or 0)
        n = min(hi - lo, k, 1 + DELIVER_QUEUE) if k >= 2 else 1
        group = blocks[lo:lo + max(1, n)]
        t = self.clock()
        for blk in group:
            self.released[blk.header.number] = t
        with self.annotate("bench.submit"):
            if len(group) == 1:
                self.pipe.submit(group[0])
            else:
                self.pipe.submit_many(group)
        return len(group)

    def flush(self) -> None:
        with self.annotate("bench.flush"):
            self.pipe.flush()

    def drain(self) -> None:
        """Flush the pipeline and wait until every block's state is
        applied and seen by the watcher."""
        self.flush()
        self.lg.drain_state()
        deadline = self.clock() + 30.0
        while (len(self.applied) < len(self.ack)
               and self.clock() < deadline):
            time.sleep(0.001)

    def close(self) -> dict:
        """Stop everything → the ledger's height and commit hash."""
        try:
            self.pipe.close(flush=False)
        finally:
            self._stop.set()
            if self._watcher is not None:
                self._watcher.join(timeout=5.0)
            self.v.close()
            end = {"height": self.lg.height,
                   "commit_hash": (self.lg.commit_hash or b"").hex()}
            self.lg.close()
        return end


class StreamDry(RuntimeError):
    """The stream ended before four fifths of the window had passed."""


def run_backlog(rig: Rig, blocks, first: int, ramp: int, seconds: float,
                on_open=None) -> dict:
    """Closed loop: every block is ready at once.  Submits from
    ``blocks[first]``; the window opens at the apply of the ``ramp``-th
    block (the pipeline is then full), and submission stops ``seconds``
    later or at the end of the stream.  ``first`` and ``ramp`` count
    blocks of the stream; what comes back is in block numbers."""
    clock, n = rig.clock, len(blocks)
    base = blocks[0].header.number
    last_ramp = base + first + ramp - 1
    t_open, k = None, first
    while k < n:
        if t_open is None:
            t_open = rig.applied.get(last_ramp)
            if t_open is not None and on_open is not None:
                on_open(t_open)
        if t_open is not None and clock() >= t_open + seconds:
            break
        k += rig.feed(blocks, k, n)
    t_stop = clock()
    rig.drain()
    t_open = rig.applied.get(last_ramp) if t_open is None else t_open
    if t_open is None or t_stop < t_open + 0.8 * seconds:
        when = ("before the window opened" if t_open is None
                else f"{t_stop - t_open:.1f} s into the window")
        raise StreamDry(f"the stream of {n} blocks ended {when}, short of "
                        f"four fifths of {seconds} s")
    return {"t_open": t_open, "first": last_ramp + 1, "submitted": base + k}


def run_paced(rig: Rig, blocks, first: int, n_due: int, rate: float,
              idle_flush_s: float, on_open=None, sleep=time.sleep) -> dict:
    """Open loop: ``blocks[first + k]`` is released when its last tx is
    due, the txs of blocks ``first..first+k`` over ``rate`` after the
    window opens, whether or not earlier blocks have finished.  While
    the stream is quiet for
    ``idle_flush_s`` with a block in flight, the tail is flushed, as the
    deliver loop does (``PeerChannel.PIPELINE_IDLE_FLUSH_S``).  ``lag[k]``
    is how late the harness itself was in releasing block k: the
    overshoot of its own sleep, never the time a block waited for a busy
    pipeline, which is the system's and counted in the tx latency.
    ``first`` counts blocks of the stream; what comes back is in block
    numbers."""
    clock = rig.clock
    base = blocks[0].header.number
    sizes = [len(b.data.data) for b in blocks[first:first + n_due]]
    t0 = clock()
    dues = [timeline.block_due(t0, k, rate, sizes) for k in range(n_due)]
    if on_open is not None:
        on_open(t0)
    lag, k = [], 0
    while k < n_due:
        due = dues[k]
        now = clock()
        late = 0.0
        if now < due:
            if rig.pipe.inflight and due - now > idle_flush_s:
                sleep(idle_flush_s)
                rig.flush()
                continue
            sleep(due - now)
            now = clock()
            late = max(0.0, now - due)
        ready = 1
        while k + ready < n_due and dues[k + ready] <= now:
            ready += 1
        took = rig.feed(blocks, first + k, first + k + ready)
        lag.extend([late] + [0.0] * (took - 1))
        k += took
    sleep(idle_flush_s)
    rig.drain()
    return {"t_open": t0, "first": base + first,
            "submitted": base + first + k, "lag_s": lag}
