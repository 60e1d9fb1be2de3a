"""Asynchronous group-commit storage engine: state apply off the
block critical path.

Analog of the reference committer's split (core/ledger/kvledger
kvLedger.commit): the BLOCK-STORE append is the durability boundary —
a block is committed once it is in the chain files — while the
state-DB apply merely *trails* it and is reconstructible from those
files through the savepoint/replay machinery (recoverDBs,
kv_ledger.go:357).  Our serial engine paid the full SQLite apply on
the commit critical path anyway; :class:`AsyncApplyEngine` moves it to
an ordered background queue drained by one dedicated applier thread so
the host side of a committed block approaches pure dispatch: append +
enqueue.

The engine is itself a :class:`~fabric_tpu.ledger.statedb.VersionedDB`
wrapping the real backend, which is what makes the move safe:

* **ordering** — one FIFO queue, one applier: batches land in commit
  order, each under its own ``(block, 0)`` savepoint, exactly as the
  serial engine would have landed them;
* **read-your-writes** — every read (``get_state``, the bulk/column
  version gathers, range scans, rich queries) consults the pending
  overlay (newest batch first) in front of the inner DB, so MVCC
  preloads, lifecycle queries and the resident-cache commit scatter
  observe *identical* state to the synchronous engine — verdicts are
  bit-equal by construction, not by luck;
* **durability fence** — before applying block N against a *durable*
  backend the applier calls ``blocks.ensure_synced(N)``: the durable
  savepoint can never get ahead of the block files (the invariant the
  serial engine enforced with an inline ``sync()`` per commit — moved
  here, it also pulls those per-commit fsyncs off the critical path);
* **backpressure** — the queue is bounded in BLOCKS; ``submit`` parks
  the committer at the block boundary until the applier catches up, so
  lag is never unbounded and crash-recovery replay stays short;
* **crash recovery** — a crash loses at most the queued tail; on
  reopen the state savepoint trails the block height and
  ``KVLedger.recover`` replays the gap from the chain files.  The
  ``ledger.apply.before``/``ledger.apply.after`` fault points let the
  differential battery kill the applier at every queue depth.

A failed apply latches: the applier stops (ordered apply cannot skip),
and the error re-raises at the next ``submit``/``drain`` — fail-stop,
never fail-skip.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque

from fabric_tpu import faults as _faults
from fabric_tpu.ledger.statedb import VersionedDB
from fabric_tpu.observe import txflow as _txflow
from fabric_tpu.observe.tracer import global_tracer

_log = logging.getLogger("fabric_tpu.ledger.committer")


class _Pending:
    """One queued block apply."""

    __slots__ = ("num", "batch", "sp", "post_apply", "enqueued_at",
                 "root", "traced_at")

    def __init__(self, num, batch, sp, post_apply, enqueued_at):
        self.num = num
        self.batch = batch
        self.sp = sp
        self.post_apply = post_apply
        self.enqueued_at = enqueued_at
        # the tracer root of the block whose commit enqueued this (None
        # when disarmed or enqueued outside a traced commit), and the
        # enqueue time on the tracer's clock: the applier records its
        # ``apply`` span under that root
        self.root = None
        self.traced_at = 0.0


def _n_writes(batch) -> int:
    """Rows ``batch`` writes.  A columnar batch is counted by its slab
    rows: ``len(batch.updates)`` would build its lazy dict, which the
    sqlite fast path never needs (6 ms a 1000-tx block, on the applier
    thread: ``rw_paced`` showed it with the tracer disarmed, PR 25)."""
    rows = getattr(batch, "row_uid", None)
    if rows is None:
        return len(batch.updates)
    return len(rows) + len(batch._extra)


def _n_meta_rows(batch) -> int:
    """Rows of ``batch`` that store metadata (a key-level endorsement
    policy's carrier), counted as :func:`_n_writes` counts rows."""
    if getattr(batch, "row_uid", None) is None:
        return sum(1 for vv in batch.updates.values() if vv.metadata)
    return (sum(1 for md in batch.row_meta or () if md)
            + sum(1 for vv in batch._extra.values() if vv.metadata))


def _merge_overlay(inner_iter, ov: dict):
    """Merge a sorted ``(key, VersionedValue)`` iterator with an
    overlay dict ``{key: VersionedValue | None}`` (None = the overlay
    suppresses the row: a pending delete, or a pending rewrite that no
    longer matches the caller's predicate).  Overlay wins on key
    collision; output stays in key order."""
    ks = sorted(ov)
    i, n = 0, len(ks)
    for key, vv in inner_iter:
        while i < n and ks[i] < key:
            o = ov[ks[i]]
            if o is not None:
                yield ks[i], o
            i += 1
        if i < n and ks[i] == key:
            o = ov[ks[i]]
            i += 1
            if o is not None:
                yield key, o
        else:
            yield key, vv
    while i < n:
        o = ov[ks[i]]
        if o is not None:
            yield ks[i], o
        i += 1


class AsyncApplyEngine(VersionedDB):
    """Ordered background applier in front of a real VersionedDB.

    The inner backend must already be open; ``close()`` drains the
    queue, joins the applier and closes the inner DB.  The applier
    thread starts lazily on the first ``submit`` so idle ledgers
    (tests open hundreds) never park a thread.
    """

    def __init__(self, inner: VersionedDB, blocks=None,
                 queue_blocks: int = 4, name: str = "state-applier",
                 after_apply=None):
        """``after_apply(root)``: run on the applier thread once each
        block's apply is published (out of the queue, its waiters
        woken), with the tracer root of the block (or None)."""
        self._inner = inner
        self._after_apply = after_apply
        self._blocks = blocks  # durability fence (BlockStore), optional
        self._capacity = max(1, int(queue_blocks))
        self._name = name
        self._cond = threading.Condition()
        self._queue: deque[_Pending] = deque()
        self._thread: threading.Thread | None = None
        self._closing = False
        self._error: BaseException | None = None
        self._applied_num = -1
        self._applies_total = 0
        self._apply_s_total = 0.0
        self._backpressure_total = 0
        # seconds submit spent parked at a full queue, all calls: the
        # ledger's ``commit.enqueue`` span carries the delta
        self.backpressure_s = 0.0
        self._metrics = None  # lazy (gauge, hist, counter) bundle
        # mirrored so KVLedger's getattr(state, "durable") keeps working
        self.durable = getattr(inner, "durable", True)

    # -- write side --------------------------------------------------------

    def submit(self, num: int, batch, savepoint, post_apply=None) -> None:
        """Enqueue one block's batch for ordered background apply.
        Blocks at the block boundary while the queue is at capacity
        (the backpressure latch).  ``post_apply`` (optional, no-arg)
        runs on the applier thread after the batch lands — the
        history-DB commit rides here, and returns ``(rows, statements)``
        for its ``apply.history`` span (or None)."""
        # on the committer thread, inside the pipeline's ``commit``
        # span: its root is the block's
        entry = _Pending(num, batch, savepoint, post_apply,
                         time.monotonic())
        tracer = global_tracer()
        cur = tracer.current()
        if cur is not None:
            entry.root, entry.traced_at = cur.root, tracer.clock()
        with self._cond:
            self._raise_if_failed()
            waited = False
            while (len(self._queue) >= self._capacity
                   and self._error is None and not self._closing):
                if not waited:
                    waited, t_parked = True, time.perf_counter()
                self._cond.wait()
            self._raise_if_failed()
            if waited:
                self._backpressure_total += 1
                self.backpressure_s += time.perf_counter() - t_parked
            self._queue.append(entry)
            if self._thread is None:
                t = threading.Thread(target=self._apply_loop,
                                     name=f"fabtpu-{self._name}",
                                     daemon=True)
                self._thread = t
                t.start()
            self._cond.notify_all()

    def apply_updates(self, batch, savepoint) -> None:
        """VersionedDB SPI: enqueue, preserving order with every
        in-flight commit (recovery replay and the pvt BTL purge come
        through here)."""
        self.submit(savepoint[0] if savepoint else -1, batch, savepoint)

    def _raise_if_failed(self):
        # callers hold self._cond
        if self._error is not None:
            raise RuntimeError(
                "state applier failed; the apply queue is fail-stop"
            ) from self._error

    def _apply_loop(self):
        while True:
            with self._cond:
                while (not self._queue and not self._closing
                       and self._error is None):
                    self._cond.wait()
                if self._error is not None or (self._closing
                                               and not self._queue):
                    return
                entry = self._queue[0]  # stays queued: overlay serves it
            try:
                dur = self._apply_one(entry)
            except BaseException as e:  # latch: ordered apply can't skip
                self._fail(e, "state apply of block %d", entry.num)
                return
            with self._cond:
                # abort() may have dropped the queue mid-apply
                if self._queue and self._queue[0] is entry:
                    self._queue.popleft()
                self._applied_num = entry.num
                self._applies_total += 1
                self._apply_s_total += dur
                self._cond.notify_all()
            self._observe(dur)
            if self._after_apply is not None:
                try:
                    self._after_apply(entry.root)
                except BaseException as e:
                    self._fail(e, "write-back after block %d", entry.num)
                    return

    def _fail(self, e: BaseException, what: str, num: int) -> None:
        _log.error(what + " failed: %s", num, e)
        with self._cond:
            self._error = e
            self._cond.notify_all()

    def _apply_one(self, entry: _Pending) -> float:
        # the block's tree was finished when its commit returned: these
        # spans arrive late (Tracer.finish_block states the contract).
        # With no root every span below is a no-op on a None parent.
        tracer = global_tracer()
        with tracer.span("apply", parent=entry.root) as asp:
            if asp is not None:
                asp.attrs["queued_ms"] = (asp.t0 - entry.traced_at) * 1000.0
            _faults.fire("ledger.apply.before", block=entry.num)
            if self._blocks is not None and getattr(self._inner, "durable",
                                                    True):
                # a DURABLE savepoint must never get ahead of the block
                # files (see module docstring) — fence before the apply
                with tracer.span("apply.fence"):
                    self._blocks.ensure_synced(entry.num)
                _txflow.block_durable(entry.num)
            t0 = time.perf_counter()
            # ``apply.write``: ``writes`` is the block's rows and
            # ``meta_rows`` those of them that store metadata (absent
            # where none does); where the
            # inner DB counts its statements (``SqliteVersionedDB``),
            # ``stmts`` is what its block path sent to the state table
            # for this block (1: one namespace, no delete) and ``path``
            # says which way the block went, ``block`` or ``per_key``.
            # ``apply.history``: ``rows`` and ``stmts`` as the history
            # DB's ``commit_block`` returns them.
            inner = self._inner
            with tracer.span("apply.write") as wsp:
                if wsp is not None:
                    stmts0 = getattr(inner, "apply_statements", None)
                    fast0 = getattr(inner, "apply_fast_blocks", 0)
                inner.apply_updates(entry.batch, entry.sp)
            if wsp is not None:
                wsp.attrs["writes"] = _n_writes(entry.batch)
                if entry.batch.has_meta:
                    wsp.attrs["meta_rows"] = _n_meta_rows(entry.batch)
                if stmts0 is not None:
                    wsp.attrs["stmts"] = inner.apply_statements - stmts0
                    wsp.attrs["path"] = (
                        "block" if inner.apply_fast_blocks > fast0
                        else "per_key")
            if entry.post_apply is not None:
                with tracer.span("apply.history") as hsp:
                    sent = entry.post_apply()
                if hsp is not None and sent is not None:
                    hsp.attrs["rows"], hsp.attrs["stmts"] = sent
            dur = time.perf_counter() - t0
            # the decoupled path's visibility edge: the block's writes
            # (and history) became readable HERE, on the applier thread
            _txflow.block_applied(entry.num)
            _faults.fire("ledger.apply.after", block=entry.num)
        return dur

    # -- read side: pending overlay in front of the inner DB ---------------

    def _pending(self) -> list[_Pending]:
        with self._cond:
            return list(self._queue)

    def get_state(self, ns, key):
        for entry in reversed(self._pending()):
            vv = entry.batch.updates.get((ns, key))
            if vv is not None:
                return None if vv.value is None else vv
        return self._inner.get_state(ns, key)

    # The two version gathers record ``sf.gather`` (the inner DB's
    # query) and ``sf.pending`` (the overlay walk) under the calling
    # thread's current span, the validator's ``launch``: two spans a
    # call, nothing per key, no-ops off a traced path.
    #
    # Both take the pending snapshot BEFORE the inner DB is asked.  An
    # entry leaves the queue only after its apply has committed, so an
    # apply that ends while the query runs is still in the snapshot,
    # and the overlay, which wins, carries what it wrote.  The other
    # order loses it: the query's snapshot predates the commit and the
    # queue no longer holds the entry.

    def _inner_gather(self, gather, keys, meta=None):
        """``gather(keys)`` on the inner DB inside ``sf.gather``.  Where
        the inner DB answers on a read connection of its own
        (``SqliteVersionedDB.rd_wait_s``) the span says so: ``reader``
        1, and ``rd_wait_ms``, what readers waited for that
        connection's lock over the span.  Where the metadata of some
        keys is asked too (``meta``, a bool array), ``meta_keys`` is
        how many and ``params`` how many of them the inner DB holds
        metadata for: a key-level endorsement policy's carrier."""
        with global_tracer().span("sf.gather", keys=len(keys)) as gsp:
            waited0 = getattr(self._inner, "rd_wait_s", None)
            out = gather(keys) if meta is None else gather(keys, meta)
            if gsp is not None and waited0 is not None:
                gsp.attrs.update(
                    reader=1,
                    rd_wait_ms=(self._inner.rd_wait_s - waited0) * 1000.0)
            if gsp is not None and meta is not None:
                gsp.attrs.update(
                    meta_keys=int(meta.sum()),
                    params=sum(1 for m in out[2] if m))
            return out

    def get_versions_bulk(self, keys):
        tracer = global_tracer()
        out, rest = {}, []
        with tracer.span("sf.pending") as psp:
            pend = self._pending()
            tracer.set_attrs(psp, pending=len(pend))
            if pend:
                for k in keys:
                    for entry in reversed(pend):
                        vv = entry.batch.updates.get(k)
                        if vv is not None:
                            if vv.value is not None:
                                out[k] = vv.version
                            break
                    else:
                        rest.append(k)
        if not pend:
            return self._inner_gather(self._inner.get_versions_bulk, keys)
        if rest:
            out.update(
                self._inner_gather(self._inner.get_versions_bulk, rest))
        return out

    def get_versions_cols(self, keys, meta=None):
        """As the inner DB's, pending applies winning; for a key
        flagged in ``meta`` the pending entry's metadata wins with its
        version (a pending delete clears it)."""
        tracer = global_tracer()
        pend = self._pending()
        present, vers, *rest = self._inner_gather(
            self._inner.get_versions_cols, keys, meta)
        metas = rest[0] if rest else None
        with tracer.span("sf.pending") as psp:
            tracer.set_attrs(psp, pending=len(pend))
            if pend:
                for i, k in enumerate(keys):
                    for entry in reversed(pend):
                        vv = entry.batch.updates.get(k)
                        if vv is not None:
                            if vv.value is None:
                                present[i] = False
                                vers[i] = 0
                            else:
                                present[i] = True
                                vers[i] = vv.version
                            if metas is not None and meta[i]:
                                metas[i] = (vv.metadata or None
                                            if vv.value is not None
                                            else None)
                            break
        return (present, vers) if metas is None else (present, vers, metas)

    def _overlay_for(self, ns, pend, keep):
        """{key: vv-or-None} for every pending write in ``ns``;
        ``keep(vv)`` False maps to None (suppress the row)."""
        ov = {}
        for entry in pend:  # oldest → newest: newest wins
            for (n, k), vv in entry.batch.updates.items():
                if n == ns:
                    ov[k] = vv if keep(vv) else None
        return ov

    def get_state_range(self, ns, start, end, limit=0):
        pend = self._pending()
        if not pend:
            yield from self._inner.get_state_range(ns, start, end, limit)
            return
        ov = self._overlay_for(
            ns, pend,
            lambda vv: vv.value is not None,
        )
        ov = {k: v for k, v in ov.items()
              if k >= start and (not end or k < end)}
        # pending deletes/rewrites can drop at most len(ov) inner rows
        inner_limit = (limit + len(ov)) if limit else 0
        n = 0
        for key, vv in _merge_overlay(
                self._inner.get_state_range(ns, start, end, inner_limit),
                ov):
            yield key, vv
            n += 1
            if limit and n >= limit:
                return

    def execute_query(self, ns, query, limit=0):
        pend = self._pending()
        if not pend:
            yield from self._inner.execute_query(ns, query, limit)
            return
        import json

        sel = query.get("selector", {})

        def match(vv):
            if vv.value is None:
                return False
            try:
                doc = json.loads(vv.value)
            except (ValueError, UnicodeDecodeError):
                return False
            return all(doc.get(f) == want for f, want in sel.items())

        # a pending rewrite that no longer matches must SUPPRESS the
        # committed row (the inner DB would still match it)
        ov = self._overlay_for(ns, pend, match)
        inner_limit = (limit + len(ov)) if limit else 0
        n = 0
        for key, vv in _merge_overlay(
                self._inner.execute_query(ns, query, inner_limit), ov):
            yield key, vv
            n += 1
            if limit and n >= limit:
                return

    def iter_all(self):
        # snapshot export wants the WHOLE committed state: barrier
        self.drain()
        yield from self._inner.iter_all()

    def savepoint(self):
        with self._cond:
            for entry in reversed(self._queue):
                if entry.sp is not None:
                    return entry.sp
        return self._inner.savepoint()

    def checkpoint_if_due(self) -> None:
        """The inner DB's, on the calling thread."""
        self._inner.checkpoint_if_due()

    @property
    def meta_count(self):
        """SBE gate: conservative — a pending batch carrying metadata
        counts before the inner DB has seen it."""
        with self._cond:
            pend = sum(1 for e in self._queue
                       if getattr(e.batch, "has_meta", False))
        return self._inner.meta_count + pend

    # -- lifecycle / introspection -----------------------------------------

    def drain(self) -> None:
        """Barrier: block until every queued batch has applied; raises
        if the applier latched a failure."""
        with self._cond:
            while self._queue and self._error is None:
                self._cond.wait(0.5)
            self._raise_if_failed()

    def wait_applied(self, num: int, timeout: float = 30.0) -> bool:
        """Block until block ``num`` has applied (or timeout)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while (self._applied_num < num and self._error is None
                   and time.monotonic() < deadline):
                self._cond.wait(0.2)
            self._raise_if_failed()
            return self._applied_num >= num

    def stats(self) -> dict:
        """Queue telemetry for /vitals, bench extras, the autopilot's
        apply-age signal and the blackbox postmortem."""
        with self._cond:
            depth = len(self._queue)
            oldest = self._queue[0].enqueued_at if self._queue else None
            out = {
                "queue_depth": depth,
                "queue_capacity": self._capacity,
                "oldest_age_ms": ((time.monotonic() - oldest) * 1000.0
                                  if oldest is not None else 0.0),
                "applied_num": self._applied_num,
                "applies_total": self._applies_total,
                "apply_ms_total": self._apply_s_total * 1000.0,
                "backpressure_total": self._backpressure_total,
                "failed": self._error is not None,
            }
        return out

    def _observe(self, dur: float) -> None:
        m = self._metrics
        if m is None:
            from fabric_tpu.ops_metrics import global_registry

            reg = global_registry()
            m = self._metrics = (
                reg.gauge("commit_apply_queue_depth",
                          "pending state-apply batches"),
                reg.histogram("commit_state_apply_seconds",
                              "background state-DB apply per block"),
                reg.counter("commit_state_applies_total",
                            "state batches applied in the background"),
            )
        gauge, hist, ctr = m
        with self._cond:
            gauge.set(float(len(self._queue)))
        hist.observe(dur)
        ctr.add(1)

    def abort(self) -> None:
        """Crash-simulation seam for the differential battery: DROP the
        pending queue without applying, stop the applier and close the
        inner DB — the state the process would leave behind had it
        died mid-queue.  Never called on a live peer."""
        with self._cond:
            self._queue.clear()
            self._closing = True
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=10.0)
        self._inner.close()

    def close(self) -> None:
        abandoned = 0
        with self._cond:
            while self._queue and self._error is None:
                self._cond.wait(0.5)
            abandoned = len(self._queue)
            self._closing = True
            self._cond.notify_all()
            err = self._error
            t = self._thread
        if t is not None:
            t.join(timeout=10.0)
        self._inner.close()
        if err is not None:
            _log.error(
                "state applier closed after a latched failure; %d "
                "queued batches abandoned (recover() replays them "
                "from the block files on reopen): %s", abandoned, err,
            )
