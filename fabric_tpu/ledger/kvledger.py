"""The per-channel ledger: block store + state + history orchestration.

Analog of the reference's kvledger (core/ledger/kvledger/kv_ledger.go):
``commit_block`` mirrors kvLedger.commit (:612-731) — already-validated
block + its TRANSACTIONS_FILTER and prepared update batch go through:

  1. commit-hash chaining (:650) — sha256(prev_commit_hash ‖
     block-header hash ‖ tx filter), stored in the COMMIT_HASH
     metadata slot so peers can cross-check state equality;
  2. block+pvtdata store append (the source of truth);
  3. state-DB apply with the block height as savepoint;
  4. history-DB apply.

Crash recovery mirrors recoverDBs (:357): on open, state/history DBs
behind the block store are caught up by replaying stored blocks
through a replay callback (the committer's re-validation path), so a
crash between steps 2-4 self-heals.

Validation itself lives in fabric_tpu.peer.validator (the TPU
pipeline); the ledger takes its outputs, keeping the layering of the
reference (txmgr validates, kvledger orchestrates).
"""

from __future__ import annotations

import hashlib
import logging
import os

from fabric_tpu import protoutil
from fabric_tpu.ledger.blockstore import BlockStore
from fabric_tpu.ledger.history import HistoryDB
from fabric_tpu.ledger.pvtdata import PvtDataStore
from fabric_tpu.ledger.statedb import SqliteVersionedDB, UpdateBatch, VersionedDB
from fabric_tpu.observe import txflow as _txflow
from fabric_tpu.observe.tracer import global_tracer
from fabric_tpu.protos import common_pb2

_log = logging.getLogger("fabric_tpu.ledger")


class KVLedger:
    def __init__(
        self,
        ledger_dir: str,
        state_db: VersionedDB | None = None,
        enable_history: bool = True,
        async_commit: bool = False,
        apply_queue_blocks: int = 4,
    ):
        """``async_commit``: state-DB apply trails the block append on
        the background applier (ledger/committer.py) — reads stay
        consistent through the engine's pending overlay, the bounded
        queue (``apply_queue_blocks``) backpressures at the block
        boundary.  The peer turns this ON by default (nodeconfig
        ``async_commit``), and so does the benchmark's rig;
        the library default stays serial so direct KVLedger users get
        apply-on-return semantics unless they opt in."""
        os.makedirs(ledger_dir, exist_ok=True)
        self.dir = ledger_dir
        self.blocks = BlockStore(os.path.join(ledger_dir, "chains"))
        inner = state_db or SqliteVersionedDB(os.path.join(ledger_dir, "state.db"))
        inner.open()
        self.engine = None
        if async_commit:
            from fabric_tpu.ledger.committer import AsyncApplyEngine

            self.engine = AsyncApplyEngine(
                inner, blocks=self.blocks,
                queue_blocks=apply_queue_blocks,
                after_apply=self._after_apply,
            )
        self.state = self.engine if self.engine is not None else inner
        self._reconcile_on_open()
        self.history = (
            HistoryDB(os.path.join(ledger_dir, "history.db")) if enable_history else None
        )
        self.pvtdata = PvtDataStore(os.path.join(ledger_dir, "pvtdata.db"))
        self._commit_hash: bytes | None = self._load_last_commit_hash()
        # per-commit critical-path decomposition (ledger_append = block
        # store + pvtdata, state_apply = state/history/purge — under
        # the async engine the latter is enqueue + backpressure only)
        self.last_commit_timings: dict = {}
        self._commit_hists = None  # lazy registry histograms
        # (seconds, blocks) of the ledger's own work on the committing
        # thread since open: ``commit_block`` up to the hand-over,
        # without its wait at a full apply queue (``_applier_writes_back``)
        self._commit_load = (0.0, 0)

    def _reconcile_on_open(self) -> None:
        """Height/savepoint reconciliation (recoverDBs preamble): the
        savepoint BEHIND the block height is the normal crash shape —
        recover() replays the gap from the chain files.  A savepoint
        AHEAD of the files (a crash-truncated block tail under a
        durable state DB) cannot be replayed from anywhere; flag it
        loudly — redelivery from ordering re-commits the missing
        blocks and the savepoint self-heals by overwrite."""
        try:
            sp = self.state.savepoint()
        except Exception as e:
            _log.debug("savepoint unreadable at open (fresh or "
                       "still-initializing state DB): %s", e)
            return
        height = self.blocks.height
        if sp is not None and sp[0] + 1 > height:
            _log.warning(
                "state savepoint %s is ahead of block height %d; "
                "awaiting block redelivery to reconcile", sp, height,
            )

    # -- commit hash chain -------------------------------------------------

    def _load_last_commit_hash(self) -> bytes | None:
        h = self.blocks.height
        if h == 0:
            return None
        blk = self.blocks.get_block(h - 1)
        if blk is None:
            # snapshot-bootstrapped store with no post-snapshot blocks
            # yet: the chain anchor persists in the bootstrap record
            boot = self.blocks.bootstrap_info()
            return (boot[2] or None) if boot else None
        idx = common_pb2.BlockMetadataIndex.COMMIT_HASH
        if len(blk.metadata.metadata) > idx and blk.metadata.metadata[idx]:
            return blk.metadata.metadata[idx]
        return None

    def _next_commit_hash(self, block: common_pb2.Block, tx_filter: bytes) -> bytes:
        return hashlib.sha256(
            (self._commit_hash or b"")
            + protoutil.block_header_hash(block.header)
            + bytes(tx_filter)
        ).digest()

    # -- commit (kv_ledger.go:612) ----------------------------------------

    def commit_block(
        self,
        block: common_pb2.Block,
        tx_filter: bytes,
        batch: UpdateBatch,
        history_writes: list | None = None,
        pvt_data: dict | None = None,
        txids: list | None = None,
        hd_bytes: bytes | None = None,
    ) -> None:
        import time as _time

        num = block.header.number
        if num != self.blocks.height:
            raise ValueError(f"commit out of order: {num} vs height {self.blocks.height}")
        protoutil.set_tx_filter(block, tx_filter)
        commit_hash = self._next_commit_hash(block, tx_filter)
        idx = common_pb2.BlockMetadataIndex.COMMIT_HASH
        while len(block.metadata.metadata) <= idx:
            block.metadata.metadata.append(b"")
        block.metadata.metadata[idx] = commit_hash

        t0 = _time.perf_counter()
        self.blocks.add_block(block, txids=txids, hd_bytes=hd_bytes)
        if pvt_data:
            self.pvtdata.commit_block(num, pvt_data)
        t1 = _time.perf_counter()
        # seconds parked at a full apply queue so far (0.0 with no engine)
        parked0 = getattr(self.engine, "backpressure_s", 0.0)
        if self.engine is not None:
            # decoupled committer: the block is committed (appended);
            # state apply trails on the applier thread, which also
            # enforces the durability fence (ensure_synced) and runs
            # the history commit post-apply.  Cost here is enqueue +
            # any backpressure wait.
            post_apply = None
            if self.history is not None and history_writes:
                hist = self.history

                def post_apply(hist=hist, num=num, hw=history_writes):
                    return hist.commit_block(num, hw)

            self.engine.submit(num, batch, (num, 0), post_apply=post_apply)
        else:
            if getattr(self.state, "durable", True):
                # a DURABLE state savepoint must never get ahead of the
                # block files (recover() replays forward from the
                # savepoint; a savepoint past a crash-truncated store
                # would skip replay and fork the peer) — close the
                # group window before the state commit.  Non-durable
                # backends (mem) recover by full replay, so they keep
                # the amortized-fsync fast path.
                self.blocks.sync()
                _txflow.block_durable(num)
            self.state.apply_updates(batch, (num, 0))
            if self.history is not None and history_writes:
                self.history.commit_block(num, history_writes)
            # serial path: writes are readable the moment apply (+
            # history) returns on the committer's own thread
            _txflow.block_applied(num)
        self._purge_expired_pvt(num)
        t2 = _time.perf_counter()
        self._commit_hash = commit_hash
        self.last_commit_timings = {
            "ledger_append": t1 - t0,
            "state_apply": t2 - t1,
        }
        parked = getattr(self.engine, "backpressure_s", 0.0) - parked0
        spent, blocks = self._commit_load
        self._commit_load = (spent + t2 - t0 - parked, blocks + 1)
        # the same three clock reads as spans, under the committing
        # thread's ``commit`` (no-ops off a traced commit)
        tracer = global_tracer()
        tracer.add("commit.append", t0, t1)
        tracer.add("commit.enqueue", t1, t2, backpressure_ms=parked * 1000.0)
        hists = self._commit_hists
        if hists is None:
            from fabric_tpu.ops_metrics import global_registry

            reg = global_registry()
            hists = self._commit_hists = (
                reg.histogram("ledger_append_seconds",
                              "block-store append on the commit path"),
                reg.histogram("ledger_state_apply_seconds",
                              "state apply (or enqueue) on the commit path"),
            )
        hists[0].observe(t1 - t0)
        hists[1].observe(t2 - t1)
        # the block is with the apply engine (or applied): the WALs are
        # written back here, one group of blocks at a time, and not
        # inside a later block's ``commit.index`` or the applier's
        # ``commit()``; the state's and history's here only where the
        # applier is the busier of the two threads
        self.blocks.checkpoint_if_due()
        if not self._applier_writes_back():
            self._write_back()

    def _applier_writes_back(self) -> bool:
        """Whether the applier, and not the committer, writes back the
        state and history WALs.  The checkpoint takes a thread off the
        pipeline for as long as it copies and syncs, so it goes to the
        one of the two that does less of the ledger's work a block, on
        the mean since open: the committer's ``commit_block`` without
        its wait at a full apply queue, against the applier's apply.
        Without an apply engine the committer applies and writes back."""
        if self.engine is None:
            return False
        applied = self.engine.stats()
        spent, blocks = self._commit_load
        return blocks > 0 and applied["apply_ms_total"] * blocks < (
            spent * 1000.0 * applied["applies_total"])

    def _write_back(self) -> None:
        self.state.checkpoint_if_due()
        if self.history is not None:
            self.history.checkpoint_if_due()

    def _after_apply(self, root) -> None:
        """The applier, once a block's apply is published: the write-
        back, under that block's tracer root, where it is the
        applier's."""
        if self._applier_writes_back():
            tracer = global_tracer()
            token = tracer.attach(root)
            try:
                self._write_back()
            finally:
                tracer.detach(token)

    def _purge_expired_pvt(self, num: int) -> None:
        """BTL expiry at the block boundary (pvtstatepurgemgmt analog):
        expired collections leave the pvtdata store AND the private
        state — both the cleartext namespace and the key-hash
        namespace (the hashes on the public rwset stay in the block
        history, but live state must not outlive block_to_live)."""
        import hashlib

        from fabric_tpu.ledger.pvtdata import decode_kv
        from fabric_tpu.ledger.statedb import UpdateBatch

        purged = self.pvtdata.purge_expired(num)
        if not purged:
            return
        batch = UpdateBatch()
        for blk_n, txnum, ns, coll, rwset in purged:
            try:
                kv = decode_kv(rwset)
            except Exception as e:
                _log.warning(
                    "pvt purge: undecodable rwset for %s/%s at block "
                    "%d tx %d: %s", ns, coll, blk_n, txnum, e,
                )
                continue
            hns = f"{ns}${coll}"
            for key in kv:
                # only purge if the LIVE state still carries this (or an
                # older) write: a later re-write has its own, later BTL
                # horizon and must survive (per-key expiry semantics of
                # pvtstatepurgemgmt)
                vv = self.state.get_state(hns, key)
                if vv is None or vv.version[0] > blk_n:
                    continue
                batch.delete(hns, key, (num, 0))
                kh = hashlib.sha256(
                    key.encode() if isinstance(key, str) else key
                ).hexdigest()
                batch.delete(f"{hns}#hashed", kh, (num, 0))
        if batch.updates:
            # re-assert the block's savepoint (passing None would reset
            # it on the mem backend and force a full recovery replay)
            self.state.apply_updates(batch, (num, 0))

    # -- recovery (kv_ledger.go:357 recoverDBs) ---------------------------

    def recover(self, replayer) -> int:
        """replayer(block) -> (tx_filter, UpdateBatch, history_writes);
        re-derives state for blocks the state DB is missing.  Returns
        the number of replayed blocks."""
        height = self.blocks.height
        sp = self.state.savepoint()
        start = (sp[0] + 1) if sp else 0
        replayed = 0
        for num in range(start, height):
            block = self.blocks.get_block(num)
            tx_filter, batch, history_writes = replayer(block)
            self.state.apply_updates(batch, (num, 0))
            if self.history is not None and history_writes:
                hsp = self.history.savepoint()
                if hsp is None or hsp < num:
                    self.history.commit_block(num, history_writes)
            replayed += 1
        # replay applies ride the normal queue under the async engine;
        # recovery is a barrier — callers read state right after
        self.drain_state()
        return replayed

    def drain_state(self) -> None:
        """Barrier on the async apply queue (no-op for the serial
        engine): returns once every enqueued batch has applied."""
        if self.engine is not None:
            self.engine.drain()

    def state_digest(self) -> str:
        """Content hash of the committed state (ledger/snapshot.py
        ``state_digest``), behind the async-apply drain barrier — the
        catch-up differential's equality oracle: snapshot-then-replay
        vs replay-from-genesis compare equal iff their committed
        records are byte-identical."""
        from fabric_tpu.ledger.snapshot import state_digest

        self.drain_state()
        return state_digest(self.state)

    @property
    def height(self) -> int:
        return self.blocks.height

    @property
    def commit_hash(self) -> bytes | None:
        return self._commit_hash

    def bootstrap_commit_hash(self, h: bytes | None) -> None:
        """Seed the commit-hash chain when joining from a snapshot
        (the chain continues from the snapshot's last commit hash)."""
        self._commit_hash = h

    def close(self):
        try:
            # state first: the async engine drains here, and its
            # applier fences against self.blocks / commits history —
            # both must still be open
            self.state.close()
        finally:
            self.blocks.close()
            if self.history is not None:
                self.history.close()
            self.pvtdata.close()
