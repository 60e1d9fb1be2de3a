"""The production commit pipeline: depth-N block overlap as a
reusable subsystem shared by the peer node's deliver loop and the
benchmark's harness (benchmark/harness.py).

Shape at depth 3 (the TPU analog of the reference peer's deliver
prefetch + committer overlap, gossip/state/state.go:540 + the
validator pool, v20/validator.go:193):

    prefetch thread   preprocess(block n+1)      host parse + async
                                                 device verify launch
    caller thread     validate_finish(block n-1) device sync → filter
                      validate_launch(block n)   overlay = merged
                                                 batches of n-1, n-2
    committer thread  commit(block n-1)          ledger commit
                      commit(block n-2)          …still fsyncing

While block n sits on device and up to ``depth - 1`` predecessors'
ledger commits drain in order on the committer thread, the prefetch
thread parses block n+1.  The in-flight predecessors' UpdateBatches
ride along as a MERGED *overlay* on block n's launch (newest-wins key
resolution — ``ledger.statedb.UpdateBatch.merged`` — feeding the
committed-version fill, range re-execution and SBE probes), and the
duplicate-txid window widens to every in-flight predecessor's txid
set, so launch(n) never waits for any predecessor's fsync.  Depth 2
degrades to the classic single-overlay overlap (pointer-identical
batch, same wait points); the overlay equivalence at every depth is
pinned by tests/test_pipeline.py and tests/test_commit_pipeline.py.

At depth ≥ 3, commits handed to the committer thread are marked
``defer_sync``: the ledger's group-commit machinery batches their
fsyncs across the pipeline window, and the window closes (forced
sync) at every barrier, stream-idle flush, and tail — the
crash-replay story is the blockstore's (ledger/blockstore.py
group_commit): a kill mid-window reopens at the last synced boundary
and replays forward.  Depth 2 never defers — the default config keeps
the classic per-block acknowledged-durability fsync exactly.

Lifecycle/config barrier: blocks that rotate validation inputs —
CONFIG txs (MSP/policy object rotation) and blocks writing the
``_lifecycle`` namespace (state-backed chaincode definitions feed the
preprocess-time policy plans) — must commit FULLY before the next
block launches, with the overlay dropped.  ``CommitPipeline`` owns
that rule so no caller can get it wrong (validate_launch also refuses
a lifecycle-writing overlay as a backstop).

``depth=1`` degrades to the strict serial launch→finish→commit order —
the correctness oracle, kept behind the ``pipeline_depth`` node config
knob.

Overlap telemetry rides the process metrics registry
(fabric_tpu.ops_metrics) so the bench breakdown and production
telemetry agree:

* ``commit_pipeline_stage_seconds{stage=...}`` — prefetch_wait /
  finish / commit_wait / launch per block,
* ``commit_pipeline_overlap_ratio`` — 1 − blocked/total per block
  (1.0 = the pipeline never stalled on prefetch or the committer),
* ``commit_pipeline_inflight`` — blocks in flight (launched or
  committing),
* ``commit_pipeline_blocks_total{mode=...}`` — pipelined / barrier /
  serial block counts.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from concurrent.futures import TimeoutError as _CfTimeout

from fabric_tpu import faults as _faults
from fabric_tpu.observe import txflow as _txflow

_log = logging.getLogger("fabric_tpu.pipeline")

#: seconds between "still waiting" warnings while blocked on a worker
#: thread — bounded-wait discipline (FT009): a wedged fsync or a hung
#: prefetch must be VISIBLE in logs, not a silently frozen feeder
WAIT_WARN_S = 60.0


def _note_txs(root, block) -> None:
    """``txs`` on a block's root span: how many envelopes it holds, so
    that a span tree gives per-tx costs on a channel whose orderer cuts
    blocks of every size.  Nothing is counted off the traced paths."""
    if root is not None:
        root.attrs["txs"] = len(block.data.data)


def _wait_result(fut, what: str, channel: str = ""):
    """``fut.result()`` as a bounded poll: same blocking semantics (a
    legitimately slow commit still completes), but a warning fires
    every ``WAIT_WARN_S`` so a wedged worker thread is diagnosable."""
    waited = 0.0
    while True:
        try:
            return fut.result(timeout=WAIT_WARN_S)
        except _CfTimeout:
            if fut.done():
                # the future completed in the race window while our
                # poll timeout propagated (or, py3.11+, the WORK itself
                # raised builtin TimeoutError) — a done future answers
                # non-blocking with the real value or the real error,
                # never with our poll timeout
                return fut.result()
            waited += WAIT_WARN_S
            _log.warning(
                "%s: still waiting on the %s worker after %.0fs — "
                "thread wedged? (/debug/stacks names it)",
                channel or "pipeline", what, waited,
            )


@dataclass
class CommittedBlock:
    """One block through the pipeline: the validated triple plus the
    PendingBlock handle (``pend.txs`` carries the parsed records for
    post-commit consumers; ``pend.hd_bytes`` the pre-serialized
    header+data for the ledger)."""

    block: object
    pend: object
    tx_filter: bytes
    batch: object
    history: list
    barrier: bool = False
    # True when this commit runs on the committer thread of a DEPTH ≥ 3
    # pipeline with more of the window behind it: the commit_fn may
    # SKIP its forced per-block fsync and let the blockstore's
    # group-commit batch the syncs across the window (force-closed at
    # every barrier, idle flush, and tail — those commits carry False,
    # and depth ≤ 2 never defers: classic durability unchanged)
    defer_sync: bool = False
    # filled by the pipeline for telemetry (seconds)
    stage_s: dict = field(default_factory=dict)
    # this block's tracer root span (fabric_tpu.observe) — commit_fn
    # implementations hang their ledger-commit/fsync spans off it
    # explicitly (the commit may hop to an event-loop thread, where
    # the committer thread's span attachment cannot follow)
    root_span: object = None

    @property
    def txids(self) -> list:
        """[(txid, idx)] for the ledger's txid index."""
        return [(p.txid, p.idx) for p in self.pend.txs if p.txid]

    @property
    def n_valid(self) -> int:
        return sum(1 for c in self.tx_filter if c == 0)


class _SliceFuture:
    """One block's slice of a coalesced prefetch future — quacks like
    the per-block Future ``_launch_next`` expects."""

    __slots__ = ("fut", "i")

    def __init__(self, fut, i: int):
        self.fut = fut
        self.i = i

    def result(self, timeout=None):
        return self.fut.result(timeout)[self.i]

    def done(self) -> bool:
        return self.fut.done()


def _is_barrier(pend, batch) -> bool:
    """True for blocks that rotate validation inputs: commit fully,
    drop the overlay, before the successor may launch."""
    return batch.touches_namespace("_lifecycle") or any(
        p.is_config for p in pend.txs
    )


@dataclass
class _InflightCommit:
    """One predecessor whose ledger commit is in flight on the
    committer thread — its batch joins the merged launch overlay and
    its txids the widened dup window until the commit is drained."""

    fut: object       # committer-thread Future
    batch: object     # the block's UpdateBatch (overlay chain member)
    txids: object     # the block's txid set (dup-window member)
    number: int


class CommitPipeline:
    """Streaming depth-N commit pipeline over a BlockValidator.

    ``depth`` is the number of blocks in flight: 1 = strict serial
    (the correctness oracle), 2 = the classic overlap (one launched +
    one committing, single-batch overlay), N ≥ 3 = a deep window where
    up to N−1 predecessors' commits drain on the committer thread
    while the newest block launches under a MERGED overlay of their
    batches (``UpdateBatch.merged``, newest-wins) and a dup-txid
    window spanning all of them — block n on device while n−1 commits
    and n−2 fsyncs.  The committer thread serializes commits in block
    order at every depth; a barrier (or flush) drains the whole
    window before proceeding.

    ``submit(block)`` feeds the next block in height order and returns
    the COMPLETED predecessor (its commit handed to the committer
    thread — or fully flushed for barriers/serial mode), or None while
    the pipe fills.  ``flush()`` drains the in-flight tail.  Use as a
    context manager, or call ``close()``; both flush unless told not
    to.

    ``commit_fn(res: CommittedBlock)`` runs on the committer thread
    (inline for barriers and in serial mode) and must perform the
    ledger commit; commits are serialized in block order and a commit
    failure surfaces at the next ``submit``/``flush``.  At depth ≥ 3,
    pipelined commits carry ``res.defer_sync=True`` — a commit_fn may
    skip its forced per-block fsync for those and let the blockstore's
    group-commit batch the syncs over the window (barrier/tail/idle
    commits carry False, closing the window; depth ≤ 2 never defers).

    ``prefetch_fn(block)`` (default ``validator.preprocess``) runs on
    the prefetch thread.  ``pre_launch_fn(block)`` runs on the CALLER
    thread immediately before the block's launch — the node hangs
    orderer block-signature verification here, NOT on the prefetch
    thread, because the barrier guarantees a predecessor CONFIG block
    has fully committed (bundle rotated) by launch time, while
    prefetch overlaps that commit and would verify against the
    pre-rotation orderer set.

    ``coalesce_blocks`` ≥ 2 turns on multi-block launch coalescing:
    ``submit_many`` stages up to that many waiting blocks' signature
    batches as ONE concatenated verify dispatch
    (validator.preprocess_many → ops.p256v3.verify_launch_many),
    amortizing the ladder's dispatch latency over the backlog; each
    block then flows through the normal depth-2 launch/finish/commit
    machinery on its own slice of the device output, so overlays,
    barriers and dup-txid windows behave exactly as with per-block
    prefetch.  Needs a real accelerator to win (like ``verify_chunk``);
    off (0) by default.
    """

    def __init__(self, validator, commit_fn, depth: int = 2,
                 prefetch_fn=None, pre_launch_fn=None, registry=None,
                 channel: str = "", coalesce_blocks: int = 0,
                 tracer=None, replay: bool = False):
        self.validator = validator
        self.commit_fn = commit_fn
        # replay pipelines (peer/replay.py) tag their tx-flow
        # inclusion stamps so catch-up blocks record inclusion→apply
        # only and never inherit a colliding live flow's endorse legs
        self.replay = bool(replay)
        if tracer is None:
            from fabric_tpu.observe import global_tracer

            tracer = global_tracer()
        # span tracer (fabric_tpu.observe): one root span per block
        # (submit → commit complete) with prefetch/launch/finish/commit
        # children across the three threads — the flight recorder and
        # /trace read what this records
        self.tracer = tracer
        # 1 = serial oracle; N ≥ 2 = up to N−1 in-flight predecessor
        # commits, their batches merged into the launch overlay
        self.depth = max(1, int(depth))
        self.prefetch_fn = prefetch_fn or validator.preprocess
        self.pre_launch_fn = pre_launch_fn
        self.coalesce_blocks = int(coalesce_blocks)
        # coalescing rides the validator's preprocess_many; a CUSTOM
        # prefetch_fn has no coalesced form, so submit_many degrades
        # to per-block submits there
        self._prefetch_many_fn = (
            getattr(validator, "preprocess_many", None)
            if prefetch_fn is None else None
        )
        self.channel = channel
        if registry is None:
            from fabric_tpu.ops_metrics import global_registry

            registry = global_registry()
        self._stage_hist = registry.histogram(
            "commit_pipeline_stage_seconds",
            "per-block commit pipeline stage time (s)",
        )
        self._overlap_hist = registry.histogram(
            "commit_pipeline_overlap_ratio",
            "1 - blocked/total per pipelined block",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0,
                     float("inf")),
        )
        self._inflight_gauge = registry.gauge(
            "commit_pipeline_inflight", "blocks launched or committing"
        )
        self._blocks_ctr = registry.counter(
            "commit_pipeline_blocks_total", "blocks through the pipeline"
        )
        self._stage_fail_ctr = registry.counter(
            "commit_pipeline_stage_failures_total",
            "pipeline stage exceptions by stage",
        )
        # (block_number, stage) of the most recent stage failure — the
        # deliver driver reads this to log WHICH block was quarantined
        # when it drains the pipe and resumes from committed height
        self.last_failure: tuple | None = None
        self._prefetch = ThreadPoolExecutor(
            1, thread_name_prefix="fabtpu-prefetch"
        )
        self._committer = ThreadPoolExecutor(
            1, thread_name_prefix="fabtpu-committer"
        )
        self._pre: tuple | None = None   # (block, prefetch Future, root)
        self._launched = None                # PendingBlock in flight
        self._launched_root = None           # its tracer root span
        # in-flight predecessor commits, oldest first: at most depth−1
        # deep; their batches form the merged launch overlay and their
        # txids the widened dup window
        self._commits: deque[_InflightCommit] = deque()
        # set when a barrier flushed AFTER the next block was already
        # staged on the prefetch thread — that prefetch ran against
        # pre-barrier state and must be redone (see _launch_next)
        self._stale_prefetch = False
        # the in-flight block's own launch duration, attached to its
        # CommittedBlock at finish so per-block metrics keep covering
        # launch+finish under pipelining (prefetch parse overlaps the
        # predecessor and is deliberately excluded)
        self._launch_s = 0.0
        # runtime re-knobbing (the traffic autopilot's actuators):
        # set_depth/set_coalesce_blocks latch a pending value that is
        # applied at the NEXT submit boundary — never mid-window, so a
        # block's launch/finish/commit always runs under one knob
        # vector.  GIL-atomic attribute writes; no lock needed.
        self._pending_depth: int | None = None
        self._pending_coalesce: int | None = None
        # when the last submit*/flush handed control back to the feeder
        # (tracer armed only): the next block's ``feed_wait`` starts here
        self._fed_at: float | None = None
        self._fed_by_flush = False
        self._closed = False

    # -- runtime re-knobbing (autopilot actuators) -------------------------

    def set_depth(self, depth: int) -> None:
        """Request a new pipeline depth, applied at the next submit
        boundary (never mid-window).  A serial pipe (depth 1) stays
        serial — the pipelined/serial boundary owns thread lifecycles
        and cannot be crossed at runtime — and a pipelined pipe never
        drops below 2 for the same reason; deeper→shallower simply
        drains the excess window at the next finish."""
        if self.depth <= 1:
            return
        self._pending_depth = max(2, int(depth))

    def set_coalesce_blocks(self, n: int) -> None:
        """Request a new multi-block coalescing group size, applied at
        the next submit boundary.  Coalescing needs the validator's
        ``preprocess_many``; without it the knob stays inert exactly
        as at construction."""
        n = int(n)
        self._pending_coalesce = 0 if n < 2 else n

    def _apply_pending_knobs(self) -> None:
        """Block boundary: adopt any latched knob values.  Called at
        the top of submit/submit_many, where no block is mid-stage on
        the caller thread."""
        d = self._pending_depth
        if d is not None:
            self._pending_depth = None
            if d != self.depth:
                self.depth = d
        c = self._pending_coalesce
        if c is not None:
            self._pending_coalesce = None
            if c != self.coalesce_blocks:
                self.coalesce_blocks = c

    # -- failure containment ----------------------------------------------

    def _note_stage_failure(self, stage: str, block_num) -> None:
        """Record a stage exception (counter + quarantine pointer) on
        its way out; the exception itself keeps propagating."""
        self.last_failure = (block_num, stage)
        self._stage_fail_ctr.add(1, channel=self.channel, stage=stage)
        _log.warning(
            "%s: pipeline %s stage failed for block %s — pipe will "
            "drain and fail closed; resume from committed height",
            self.channel or "pipeline", stage, block_num,
        )

    def _fail_closed(self) -> None:
        """A stage exception left the pipe mid-flight: drop the
        in-flight state, drain both worker threads, and latch closed so
        the NEXT submit raises 'pipeline is closed' cleanly instead of
        tripping internal asserts.  The caller (deliver driver, bench
        chaos harness) rebuilds a fresh pipeline and resumes from the
        last committed height — the replay check skips what already
        landed.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        # incident edge: a quarantined block is the attribution case
        # the flight-data recorder exists for — bundle the trailing
        # series + trace trees before the in-flight state is dropped
        from fabric_tpu.observe import blackbox

        failure = self.last_failure
        blackbox.notify(
            "pipeline_fail_closed", channel=self.channel,
            block=failure[0] if failure else None,
            stage=failure[1] if failure else None,
        )
        self._pre = None
        self._launched = None
        self._launched_root = None
        # still-pending committer tasks finish inside shutdown's wait;
        # their errors (if any) were either surfaced already or are
        # superseded by the failure that got us here
        self._commits.clear()
        self._stale_prefetch = False
        self._fed_at = None
        self._prefetch.shutdown(wait=True)
        self._committer.shutdown(wait=True)
        self._inflight_gauge.set(0, channel=self.channel)

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # on an exception unwind, don't let a flush failure mask it
        self.close(flush=exc_type is None)
        return False

    def close(self, flush: bool = True):
        """Shut the worker threads down; with ``flush`` (default) the
        in-flight tail commits first."""
        if self._closed:
            return None
        res = None
        try:
            if flush:
                res = self.flush()
        finally:
            self._closed = True
            self._prefetch.shutdown(wait=True)
            self._committer.shutdown(wait=True)
            self._inflight_gauge.set(0, channel=self.channel)
        return res

    @property
    def inflight(self) -> int:
        """Blocks accepted but not yet fully committed (prefetched,
        launched, or draining on the committer thread) — feeds the
        ``commit_pipeline_inflight`` gauge and the deliver driver's
        idle-flush decision.  (Replay protection in the deliver loop
        tracks the next expected block number directly; it does not
        consume this.)"""
        return ((self._pre is not None) + (self._launched is not None)
                + len(self._commits))

    # -- the in-flight commit window ---------------------------------------

    def _drain_commits(self, keep: int) -> None:
        """Wait out in-flight predecessor commits (oldest first) until
        at most ``keep`` remain.  Records are POPPED before waiting so
        a commit error surfaces exactly once; 0 = full drain (barrier,
        tail, flush)."""
        while len(self._commits) > keep:
            rec = self._commits.popleft()
            _wait_result(rec.fut, "committer", self.channel)

    def _launch_overlay(self):
        """(overlay, extra_txids) for the next launch, derived from the
        in-flight commit window: a singleton window hands the batch and
        txid set through UNMERGED (the depth-2 fast path — pointer
        identity preserved); deeper windows merge newest-wins and union
        the dup-txid sets."""
        if not self._commits:
            return None, None
        if len(self._commits) == 1:
            rec = self._commits[0]
            return rec.batch, rec.txids
        from fabric_tpu.ledger.statedb import UpdateBatch

        recs = list(self._commits)
        return (
            UpdateBatch.merged([r.batch for r in recs]),
            set().union(*(set(r.txids) for r in recs)),
        )

    # -- feed_wait: the pipeline idle, waiting for its feeder ----------------

    def _mark_fed(self, by_flush: bool = False) -> None:
        """Last act of submit*/flush: from here until the next call the
        pipeline waits for its feeder.  One clock read per call, and
        only while the tracer is armed."""
        self._fed_at = (time.perf_counter() if self.tracer.enabled
                        else None)
        self._fed_by_flush = by_flush

    def _add_feed_wait(self, root, t_in: float, **attrs) -> None:
        """``feed_wait``: the caller thread was outside the pipeline,
        waiting for the deliver stream or the generator, from the last
        submit*/flush return (:meth:`_mark_fed`) to this call's entry
        ``t_in``.  A retro span: on the NEW block's root at a submit,
        where it ends as the root begins (``after_flush`` when the
        call before was a flush, so the wait starts at that flush's
        return and holds none of its work); on the block in hand at a
        flush (``before_flush``: the idle timeout that led to it).
        The first block of a pipeline, and the first after a failure,
        have none."""
        t_fed, self._fed_at = self._fed_at, None
        if t_fed is not None:
            if self._fed_by_flush:
                attrs["after_flush"] = True
            self.tracer.add("feed_wait", t_fed, t_in, parent=root, **attrs)

    # -- the pipeline ------------------------------------------------------

    def submit(self, block):
        """Feed the next block (height order).  Depth-2: returns the
        predecessor's CommittedBlock (commit in flight on the
        committer thread unless it was a barrier) or None while the
        pipe fills.  Serial (depth=1): validates AND commits ``block``
        inline, returning its CommittedBlock.

        A stage exception FAILS THE PIPE CLOSED (see ``_fail_closed``):
        it surfaces here exactly once, the worker threads drain, and
        the next submit raises 'pipeline is closed' — callers rebuild a
        fresh pipeline and resume from the last committed height."""
        if self._closed:
            raise RuntimeError("pipeline is closed")
        self._apply_pending_knobs()
        try:
            t_sub = time.perf_counter()
            if self.depth == 1:
                out = self._submit_serial(block, t_sub)
                self._mark_fed()
                return out
            # stage the new block on the prefetch thread FIRST: its
            # host parse + device verify launch overlap the
            # predecessor's device sync below
            assert self._pre is None, (
                "submit() before the previous returned"
            )
            root = self.tracer.begin_block(block.header.number,
                                           channel=self.channel)
            _note_txs(root, block)
            self._add_feed_wait(root, t_sub)
            self._pre = (
                block,
                self._prefetch.submit(self._prefetch_traced, block, root),
                root,
            )
            self._inflight_gauge.set(self.inflight, channel=self.channel)

            out = None
            if self._launched is not None:
                out = self._finish_and_commit(self._launched)
            self._launch_next(
                out.stage_s if out is not None else {}, t_sub
            )
            self._mark_fed()
            return out
        except BaseException:
            self._fail_closed()
            raise

    def _prefetch_traced(self, block, root):
        """Prefetch-thread task: the explicit span handle crosses the
        executor boundary here (contextvars would not), and the span's
        attachment makes the validator's parse/device_pre stage timers
        and any host-pool worker tasks nest under it."""
        with self.tracer.span("prefetch", parent=root):
            _faults.fire("pipeline.prefetch")
            return self.prefetch_fn(block)

    def _prefetch_many_traced(self, group, root, n):
        with self.tracer.span("prefetch", parent=root, coalesced=n):
            _faults.fire("pipeline.prefetch")
            return self._prefetch_many_fn(group)

    def _resident_commit(self, res) -> None:
        """Device-resident state (fabric_tpu/state): apply the
        committed block's write-set delta to the validator's resident
        version table AT the commit boundary — strictly before the
        commit future resolves, so a successor launch whose overlay no
        longer covers this block has happens-before ordering with the
        scatter (the coherence contract in state/residency.py), while
        a launch whose overlay still covers it forces the same keys
        onto overlay-valued host lanes either way.  Validators without
        the hook (toy validators, custom prefetchers) skip free."""
        fn = getattr(self.validator, "resident_commit", None)
        if fn is not None:
            fn(res.batch)

    def _run_commit(self, res) -> None:
        """The ONE commit body shared by all three commit sites
        (pipelined committer thread, serial mode, barrier/tail
        inline): stamp tx-flow inclusion + verdicts, then the ledger
        commit and the resident-state scatter.  The inclusion stamp
        lands BEFORE commit_fn so the ledger's durable/apply fences
        find the block's flows already open."""
        if _txflow.enabled():
            num = res.block.header.number
            txs = [(p.txid, int(res.tx_filter[p.idx]))
                   for p in res.pend.txs if p.txid]
            _txflow.block_included(num, txs, channel=self.channel,
                                   replay=self.replay)
        self.commit_fn(res)
        self._resident_commit(res)

    def _commit_traced(self, res, root):
        """Committer-thread task: commit under its span, then finalize
        the block's root — ring append + slow-block watchdog run here,
        off the caller thread's critical path."""
        try:
            with self.tracer.span("commit", parent=root):
                _faults.fire("pipeline.commit")
                self._run_commit(res)
        except BaseException:
            self._note_stage_failure("commit", res.block.header.number)
            raise
        finally:
            self.tracer.finish_block(root)

    def submit_many(self, blocks) -> list:
        """Feed several height-ordered blocks, coalescing their verify
        dispatches in groups of ``coalesce_blocks`` (see the class
        docstring).  Returns the CommittedBlocks COMPLETED by these
        submissions — the in-flight tail stays in the pipe until the
        next submit or ``flush``.  Degrades to per-block ``submit``
        when coalescing is off, the pipe is serial, or the validator
        has no ``preprocess_many``."""
        blocks = list(blocks)
        self._apply_pending_knobs()
        k = self.coalesce_blocks
        if (self.depth == 1 or k < 2 or len(blocks) < 2
                or self._prefetch_many_fn is None):
            return [
                r for r in (self.submit(b) for b in blocks) if r is not None
            ]
        if self._closed:
            raise RuntimeError("pipeline is closed")
        try:
            out = self._submit_many_coalesced(blocks, k,
                                              time.perf_counter())
            self._mark_fed()
            return out
        except BaseException:
            self._fail_closed()
            raise

    def _submit_many_coalesced(self, blocks, k, t_in: float) -> list:
        out = []
        i = 0
        while i < len(blocks):
            group = blocks[i:i + k]
            i += len(group)
            if len(group) == 1:
                r = self.submit(group[0])
                if r is not None:
                    out.append(r)
                continue
            # ONE prefetch-thread call stages every block in the group
            # and launches their signature batches as one coalesced
            # device dispatch; each block then takes the normal path
            # on its own slice of the device output.  The group's
            # prefetch span hangs off the LEADER's root; every member
            # root records its membership so /trace shows which blocks
            # shared the dispatch.
            lead = group[0].header.number
            roots = []
            for b in group:
                r = self.tracer.begin_block(b.header.number,
                                            channel=self.channel)
                _note_txs(r, b)
                self.tracer.set_attrs(r, coalesce_group=int(lead),
                                      coalesce_size=len(group))
                roots.append(r)
            # the call's one wait lands on its first block's root:
            # taking it clears the mark, so later groups add nothing
            self._add_feed_wait(roots[0], t_in)
            fut = self._prefetch.submit(
                self._prefetch_many_traced, group, roots[0], len(group)
            )
            # barrier taint: the WHOLE group was staged just now, so a
            # barrier committing anywhere during this loop (an in-group
            # config/lifecycle block, or the previous group's tail
            # finishing at j=0) makes every REMAINING slice stale —
            # _finish_and_commit's flag only covers the immediate
            # successor, so latch it and force the per-block redo for
            # the rest of the group (barriers are rare; the serial
            # redo is the correctness price, same as per-block mode)
            stale_group = False
            for j, block in enumerate(group):
                t_sub = time.perf_counter()
                assert self._pre is None, (
                    "submit_many() before the previous returned"
                )
                self._pre = (block, _SliceFuture(fut, j), roots[j])
                self._inflight_gauge.set(self.inflight,
                                         channel=self.channel)
                res = None
                if self._launched is not None:
                    res = self._finish_and_commit(self._launched)
                if self._stale_prefetch:
                    stale_group = True
                elif stale_group:
                    self._stale_prefetch = True
                self._launch_next(
                    res.stage_s if res is not None else {}, t_sub
                )
                if res is not None:
                    out.append(res)
        return out

    def flush(self):
        """Drain: finish + commit the last launched block and wait for
        every committer-thread commit.  Returns the final
        CommittedBlock (or None if nothing was in flight).  A stage
        or commit exception fails the pipe closed and surfaces ONCE
        (see ``submit``)."""
        try:
            # a flush of nothing leaves the mark where it was
            busy = self._launched is not None
            if busy and self._fed_at is not None:
                self._add_feed_wait(self._launched_root,
                                    time.perf_counter(), before_flush=True)
            out = self._flush_inner()
            if busy:
                self._mark_fed(by_flush=True)
            return out
        except BaseException:
            self._fail_closed()
            raise

    def _flush_inner(self):
        out = None
        if self._launched is not None:
            out = self._finish_and_commit(self._launched, tail=True)
            self._launched = None
        if self._pre is not None:
            # a prefetched block with no successor: run it serially
            block, fut, root = self._pre
            self._pre = None
            try:
                pre = _wait_result(fut, "prefetch", self.channel)
                if self._stale_prefetch:
                    # prefetched before its barrier predecessor
                    # committed
                    self._stale_prefetch = False
                    self.tracer.event("stale_prefetch_reparse",
                                      parent=root)
                    with self.tracer.span("re-prefetch", parent=root):
                        pre = self.prefetch_fn(block)
            except BaseException:
                self._note_stage_failure("prefetch", block.header.number)
                raise
            try:
                with self.tracer.span("launch", parent=root) as lsp:
                    _faults.fire("pipeline.launch")
                    if self.pre_launch_fn is not None:
                        self.pre_launch_fn(block)
                    overlay, extra = self._launch_overlay()
                    t0 = time.perf_counter()
                    pend = self.validator.validate_launch(
                        block, pre=pre, overlay=overlay,
                        extra_txids=extra,
                    )
                    self._launch_s = time.perf_counter() - t0
                    self.tracer.set_attrs(
                        lsp, device=getattr(pend, "fetch2", None)
                        is not None,
                    )
            except BaseException:
                self._note_stage_failure("launch", block.header.number)
                raise
            self._launched_root = root
            out = self._finish_and_commit(pend, tail=True)
        # records are popped before waiting inside the drain: a commit
        # error must surface exactly once, not re-raise at close()
        self._drain_commits(0)
        # nothing is prefetched past this point: a barrier flushed as
        # the tail must not make the NEXT submit discard and redo its
        # (post-barrier) prefetch
        self._stale_prefetch = False
        self._inflight_gauge.set(0, channel=self.channel)
        return out

    def _submit_serial(self, block, t_sub: float) -> CommittedBlock:
        tr = self.tracer
        root = tr.begin_block(block.header.number, channel=self.channel,
                              mode="serial")
        _note_txs(root, block)
        self._add_feed_wait(root, t_sub)
        t0 = time.perf_counter()
        stage = "launch"  # failure label tracks the stage under way
        try:
            with tr.span("launch", parent=root):
                _faults.fire("pipeline.launch")
                if self.pre_launch_fn is not None:
                    self.pre_launch_fn(block)
                with tr.span("prefetch"):  # inline in serial mode
                    stage = "prefetch"
                    _faults.fire("pipeline.prefetch")
                    pre = self.prefetch_fn(block)
                    stage = "launch"
                pend = self.validator.validate_launch(block, pre=pre)
            stage = "finish"
            with tr.span("finish", parent=root):
                flt, batch, history = self.validator.validate_finish(pend)
        except BaseException:
            self._note_stage_failure(stage, block.header.number)
            raise
        t1 = time.perf_counter()
        res = CommittedBlock(
            block=block, pend=pend, tx_filter=flt, batch=batch,
            history=history, barrier=_is_barrier(pend, batch),
            stage_s={"finish": t1 - t0}, root_span=root,
        )
        try:
            with tr.span("commit", parent=root):
                _faults.fire("pipeline.commit")
                self._run_commit(res)
        except BaseException:
            self._note_stage_failure("commit", block.header.number)
            raise
        finally:
            tr.finish_block(root)
        res.stage_s["commit_wait"] = time.perf_counter() - t1
        self._blocks_ctr.add(1, channel=self.channel, mode="serial")
        return res

    def _finish_and_commit(self, pend, tail: bool = False):
        """Sync the device for ``pend``, serialize behind enough of the
        in-flight commit window (all of it for barriers/tails; enough
        to keep at most depth−1 commits in flight otherwise), then
        either commit inline (barrier/tail) or hand the commit to the
        committer thread and join the batch to the successors' merged
        overlay window."""
        root = self._launched_root
        self._launched_root = None
        t0 = time.perf_counter()
        try:
            with self.tracer.span("finish", parent=root):
                flt, batch, history = self.validator.validate_finish(pend)
        except BaseException:
            self._note_stage_failure("finish", pend.block.header.number)
            raise
        t1 = time.perf_counter()
        barrier = _is_barrier(pend, batch)
        # depth 2: wait THE predecessor commit (the classic overlap);
        # depth N: only block once N−1 commits are already in flight —
        # a slow fsync deep in the window no longer stalls this launch
        self._drain_commits(
            0 if (barrier or tail) else max(0, self.depth - 2)
        )
        t2 = time.perf_counter()
        self.tracer.add("commit_wait", t1, t2, parent=root)
        res = CommittedBlock(
            block=pend.block, pend=pend, tx_filter=flt, batch=batch,
            history=history, barrier=barrier,
            # fsync deferral is a DEPTH ≥ 3 behavior: at the default
            # depth 2 every commit keeps the classic forced per-block
            # fsync, so acknowledged-durability semantics are exactly
            # the pre-depth-N ones on unchanged configs
            defer_sync=self.depth >= 3 and not (barrier or tail),
            stage_s={"launch": self._launch_s, "finish": t1 - t0,
                     "commit_wait": t2 - t1},
            root_span=root,
        )
        self._launch_s = 0.0
        self._stage_hist.observe(t1 - t0, channel=self.channel,
                                 stage="finish")
        self._stage_hist.observe(t2 - t1, channel=self.channel,
                                 stage="commit_wait")
        if barrier or tail:
            # barrier: rotated validation inputs must be fully
            # committed (and the overlay window dropped) before any
            # launch; tail: nothing left to overlap with.  Either way
            # the fsync window closes here (defer_sync=False).
            self.tracer.set_attrs(
                root, **({"barrier": True} if barrier else {"tail": True})
            )
            try:
                with self.tracer.span("commit", parent=root):
                    _faults.fire("pipeline.commit")
                    self._run_commit(res)
            except BaseException:
                self._note_stage_failure(
                    "commit", res.block.header.number
                )
                raise
            finally:
                self.tracer.finish_block(root)
            if barrier:
                self._stale_prefetch = True
        else:
            fut = self._committer.submit(self._commit_traced, res, root)
            self._commits.append(_InflightCommit(
                fut=fut, batch=batch, txids=pend.txids,
                number=pend.block.header.number,
            ))
        self._blocks_ctr.add(
            1, channel=self.channel,
            mode="barrier" if barrier else "pipelined",
        )
        self._launched = None
        return res

    def _launch_next(self, prev_stage_s: dict, t_sub: float) -> None:
        block, fut, root = self._pre
        self._pre = None
        t0 = time.perf_counter()
        try:
            # host parse ran while the device synced
            pre = _wait_result(fut, "prefetch", self.channel)
            if self._stale_prefetch:
                # this block was staged on the prefetch thread BEFORE
                # its barrier predecessor committed, so its parse/
                # policy plans saw pre-barrier state — and
                # validate_launch's staleness backstop is an identity
                # check that state-backed policy providers (lifecycle
                # caches rotate IN PLACE) never trip.  Redo the parse
                # against post-barrier state; barriers are rare, the
                # serial redo is the correctness price.
                self._stale_prefetch = False
                self.tracer.event("stale_prefetch_reparse", parent=root)
                with self.tracer.span("re-prefetch", parent=root):
                    pre = self.prefetch_fn(block)
        except BaseException:
            self._note_stage_failure("prefetch", block.header.number)
            raise
        t1 = time.perf_counter()
        self.tracer.add("prefetch_wait", t0, t1, parent=root)
        try:
            with self.tracer.span("launch", parent=root) as lsp:
                _faults.fire("pipeline.launch")
                if self.pre_launch_fn is not None:
                    # caller thread, AFTER any predecessor barrier
                    # flushed — the node verifies orderer block
                    # signatures here against the post-rotation bundle
                    self.pre_launch_fn(block)
                overlay, extra = self._launch_overlay()
                self._launched = self.validator.validate_launch(
                    block, pre=pre, overlay=overlay,
                    extra_txids=extra,
                )
                # attribution aid for /trace + the device ledger's
                # exemplars: a block silently riding the host path
                # (no fused stage-2 dispatch) must be visible
                self.tracer.set_attrs(
                    lsp, device=getattr(self._launched, "fetch2", None)
                    is not None,
                )
        except BaseException:
            self._note_stage_failure("launch", block.header.number)
            raise
        self._launched_root = root
        t2 = time.perf_counter()
        self._launch_s = t2 - t1
        self._inflight_gauge.set(self.inflight, channel=self.channel)
        self._stage_hist.observe(t1 - t0, channel=self.channel,
                                 stage="prefetch_wait")
        self._stage_hist.observe(t2 - t1, channel=self.channel,
                                 stage="launch")
        total = t2 - t_sub
        if prev_stage_s and total > 0:
            blocked = (t1 - t0) + prev_stage_s.get("commit_wait", 0.0)
            self._overlap_hist.observe(
                max(0.0, 1.0 - blocked / total), channel=self.channel
            )
