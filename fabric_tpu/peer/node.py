"""Peer node assembly: ledger + validator + endorser + commit driver
+ client services, as one process.

The analog of internal/peer/node/start.go:190-930 `serve()` compressed
to the components this framework has: a KVLedger per channel, the
TPU-batched BlockValidator on the commit path, the endorser service,
and a deliver-client loop that pulls blocks from the ordering service
and drives StoreBlock (the gossip/privdata coordinator's role,
coordinator.go:151 — gossip dissemination itself is replaced by every
peer pulling from the orderer, which the reference also supports via
useLeaderElection=false + org leaders).

Services exposed over fabric_tpu.comm RPC:
* ``Endorse``      — SignedProposal → ProposalResponse (unary).
* ``DeliverBlocks``— committed-block stream with TRANSACTIONS_FILTER
                     metadata set (client event stream analog).
* ``Query``        — read-only state access (qscc-style convenience).
"""

from __future__ import annotations

import asyncio
import json
import logging

from google.protobuf.message import DecodeError

from fabric_tpu import protoutil
from fabric_tpu.comm.rpc import RpcServer
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import MemVersionedDB
from fabric_tpu.observe import txflow as _txflow
from fabric_tpu.ordering.node import DeliverClient
from fabric_tpu.peer.chaincode import ChaincodeRuntime
from fabric_tpu.peer.endorser import Endorser
from fabric_tpu.peer.validator import BlockValidator, PolicyProvider
from fabric_tpu.protos import common_pb2, proposal_pb2

_log = logging.getLogger("fabric_tpu.peer")

#: how many of a channel's last committed blocks ``_warm_programs``
#: looks at for the tx shapes its stage-2 programs are warmed for: half
#: a minute of a channel that cuts one every second
WARM_FROM_BLOCKS = 32


class PeerChannel:
    """One channel's ledger + validator + commit loop on this peer.

    With ``genesis_block`` (the production path — the reference
    requires the channel's genesis/config block at join,
    core/peer/peer.go:235), the channel derives its trust anchor from
    it: channelconfig Bundle → MSPs + policy tree, a lifecycle-backed
    policy provider over the channel's OWN state, and a config-tx
    processor.  The genesis block commits locally WITHOUT validation
    (the admin vouches for it out-of-band) and the deliver loop then
    starts at height 1, so a malicious orderer can never substitute a
    different block 0.  Without a genesis block (dev mode) the caller
    wires msp/provider explicitly and the first delivered block is
    trusted — test-network semantics only."""

    def __init__(self, channel_id: str, data_dir: str, msp_manager=None,
                 policy_provider: PolicyProvider | None = None, state_db=None,
                 config_processor=None, genesis_block=None,
                 snapshot_dir: str | None = None, pipeline_depth: int = 2,
                 verify_chunk: int = 0, mesh_devices: int = 0,
                 mesh_topology=None,
                 coalesce_blocks: int = 0, host_stage_workers: int = 0,
                 recode_device: bool = False,
                 trace_ring_blocks: int | None = None,
                 trace_slow_factor: float | None = None,
                 device_fail_threshold: int = 0,
                 device_retries: int = 2,
                 device_recovery_s: float = 30.0,
                 verify_deadline_ms: float = 0.0,
                 state_resident: bool = False,
                 state_resident_mb: int = 64,
                 state_resident_range_bits: int = 12,
                 sidecar_endpoint: str = "",
                 sidecar_weight: float = 1.0,
                 sidecar_recovery_s: float = 5.0,
                 sidecar_ssl=None,
                 async_commit: bool = True,
                 apply_queue_blocks: int = 4):
        self.id = channel_id
        # block-commit span tracer knobs (nodeconfig trace_ring_blocks
        # / trace_slow_factor): configure the process-global tracer the
        # CommitPipeline, validator stage timers, host pool workers and
        # the operations server's /trace endpoint all share
        from fabric_tpu import observe

        observe.configure(ring_blocks=trace_ring_blocks,
                          slow_factor=trace_slow_factor)
        self.tracer = observe.global_tracer()
        # commit-path knobs (nodeconfig pipeline_depth / verify_chunk /
        # coalesce_blocks): depth 2 = CommitPipeline overlap on the
        # deliver loop, N ≥ 3 = deep window (merged multi-batch launch
        # overlays, widened dup-txid window, fsyncs deferred to the
        # blockstore group commit), 1 = strict serial commit_block per
        # block; coalesce_blocks ≥ 2 = multi-block verify-dispatch
        # coalescing over the deliver backlog
        # (CommitPipeline.submit_many)
        self.pipeline_depth = int(pipeline_depth)
        self.coalesce_blocks = int(coalesce_blocks)
        snap_meta = None
        if snapshot_dir is not None:
            from fabric_tpu.ledger.snapshot import create_from_snapshot

            self.ledger, snap_meta = create_from_snapshot(
                snapshot_dir, data_dir, state_db=state_db or MemVersionedDB(),
                async_commit=async_commit,
                apply_queue_blocks=apply_queue_blocks,
            )
        else:
            # async group-commit storage engine (nodeconfig
            # ``async_commit``, default ON): state apply trails the
            # block append on the ledger's applier thread
            self.ledger = KVLedger(data_dir, state_db=state_db or MemVersionedDB(),
                                   async_commit=async_commit,
                                   apply_queue_blocks=apply_queue_blocks)
        config = None
        if genesis_block is not None:
            from fabric_tpu.protos import configtx_pb2

            env = protoutil.unmarshal(
                common_pb2.Envelope, genesis_block.data.data[0]
            )
            payload = protoutil.unmarshal(common_pb2.Payload, env.payload)
            cfg_env = protoutil.unmarshal(configtx_pb2.ConfigEnvelope, payload.data)
            config = cfg_env.config
        elif snap_meta is not None and snap_meta.get("config"):
            from fabric_tpu.protos import configtx_pb2

            config = protoutil.unmarshal(
                configtx_pb2.Config, bytes.fromhex(snap_meta["config"])
            )
        if config is not None:
            from fabric_tpu import channelconfig as chancfg
            from fabric_tpu.peer.lifecycle import LifecyclePolicyProvider

            bundle = chancfg.Bundle(channel_id, config)
            config_processor = config_processor or chancfg.ConfigTxProcessor(bundle)
            self.processor = config_processor
            msp_manager = bundle.msp_manager
            if policy_provider is None:
                policy_provider = LifecyclePolicyProvider(
                    self.ledger.state,
                    ref_resolver=lambda name: (
                        self.processor.bundle.application_policy_ast(name)
                    ),
                )
            if genesis_block is not None and self.ledger.blocks.height == 0:
                from fabric_tpu.ledger.statedb import UpdateBatch

                gb = common_pb2.Block()
                gb.CopyFrom(genesis_block)
                self.ledger.commit_block(
                    gb, bytes([0]), UpdateBatch(), []
                )
            # ACLs over the live bundle (rotates with config updates)
            from fabric_tpu.peer.acl import ACLProvider, PROPOSE

            self.acl = ACLProvider(
                lambda: getattr(self.processor, "bundle", None)
            )
            # the _lifecycle system contract scoped to THIS channel's
            # org set (system-chaincode deploy, start.go:765)
            from fabric_tpu.peer.lifecycle import LIFECYCLE_NS, LifecycleContract

            self.syscc = {
                LIFECYCLE_NS: LifecycleContract(
                    org_lister=lambda: self.processor.bundle.application_orgs()
                )
            }
        else:
            self.processor = config_processor
            self.syscc = {}
            if config_processor is not None and hasattr(config_processor, "bundle"):
                from fabric_tpu.peer.acl import ACLProvider

                self.acl = ACLProvider(
                    lambda: getattr(self.processor, "bundle", None)
                )
            else:
                self.acl = None  # dev mode: no policy source, no ACLs
        if msp_manager is None or policy_provider is None:
            raise ValueError(
                "join without genesis_block/snapshot requires explicit "
                "msp_manager and policy_provider"
            )
        validator_kw = dict(
            block_store=self.ledger.blocks, config_processor=config_processor,
            verify_chunk=verify_chunk, mesh_devices=mesh_devices,
            mesh_topology=mesh_topology,
            host_stage_workers=host_stage_workers,
            recode_device=recode_device,
            device_fail_threshold=device_fail_threshold,
            device_retries=device_retries,
            device_recovery_s=device_recovery_s,
            verify_deadline_ms=verify_deadline_ms,
            state_resident=state_resident,
            state_resident_mb=state_resident_mb,
            state_resident_range_bits=state_resident_range_bits,
            channel=channel_id,
        )
        if sidecar_endpoint:
            # nodeconfig ``sidecar_endpoint``: the channel's signature
            # batches ship to a shared validation sidecar
            # (fabric_tpu/sidecar) instead of owning a local device
            # lane; sidecar loss latches the local CPU fallback and
            # re-attaches via recovery probes, so the channel stays
            # live through sidecar restarts
            from fabric_tpu.sidecar.validator import SidecarValidator

            self.validator = SidecarValidator(
                msp_manager, policy_provider, self.ledger.state,
                sidecar_endpoint=sidecar_endpoint,
                sidecar_weight=sidecar_weight,
                sidecar_recovery_s=sidecar_recovery_s,
                sidecar_ssl=sidecar_ssl,
                **validator_kw,
            )
        else:
            self.validator = BlockValidator(
                msp_manager, policy_provider, self.ledger.state,
                **validator_kw,
            )
        if snapshot_dir is not None:
            # snapshot join + resident cache (PR 14): warm the device
            # table straight from the snapshot's key ranges instead of
            # faulting the working set in miss-by-miss over the first
            # replayed blocks (ledger/snapshot.py warm_resident; a
            # no-op when the resident knob is off or capacity is hit)
            res = getattr(self.validator, "resident", None)
            if res is not None:
                from fabric_tpu.ledger.snapshot import warm_resident

                warmed = warm_resident(res, snapshot_dir)
                if warmed:
                    _log.info("%s: resident cache warmed with %d keys "
                              "from snapshot", channel_id, warmed)
        # the device programs this channel's blocks can key, compiled
        # before its first block (a peer with a sidecar owns no lane)
        self._device_lane = not sidecar_endpoint
        self._warm_programs()
        from fabric_tpu.peer.coordinator import PvtDataCoordinator
        from fabric_tpu.peer.transient import TransientStore

        self.transient = TransientStore(f"{data_dir}/transient.db")
        self.pvt_puller = None  # async callable injected by the gossip layer

        async def _pull(*a):
            if self.pvt_puller is None:
                return None
            return await self.pvt_puller(*a)

        self.coordinator = PvtDataCoordinator(self.transient, puller=_pull)
        from fabric_tpu.ledger.confighistory import ConfigHistoryDB

        self.confighistory = ConfigHistoryDB(f"{data_dir}/confighistory.db")
        self.transient_retention = 50  # blocks (core.yaml transientstore)
        from fabric_tpu.utils.locks import AsyncRWLock

        # endorsement vs commit: simulations take the SHARED side, the
        # committer the exclusive one (lockbased_txmgr RW semantics,
        # endorser.go:379-401) — endorsements run in parallel with each
        # other and only serialize against block commits
        self.commit_lock = AsyncRWLock()
        self._height_changed = asyncio.Event()
        self._deliver_task: asyncio.Task | None = None
        # the live CommitPipeline while the deliver driver runs — the
        # traffic autopilot actuates runtime knobs through it
        # (apply_knob); None between deliver sessions
        self.pipe = None

    def _warm_programs(self) -> None:
        """``BlockValidator.warmup`` for this channel: every verify
        program up to the largest block its orderer cuts
        (``BatchSize.max_message_count`` of the channel's configuration,
        each tx with its creator's signature and one endorsement from
        every principal of the widest application policy), and the
        stage-2 programs of blocks that look like the last
        ``WARM_FROM_BLOCKS`` the channel committed, at every size up to
        that count.  Without it a live peer lowers and compiles a
        program inside its commit path each time a block's size class
        first appears, a stall of seconds to tens of seconds, some
        nineteen times after a start on a channel at the orderer's
        defaults.  A freshly joined channel has no blocks to look at:
        its verify programs are warmed and each stage-2 program is
        lowered where its first block commits, about a second each.

        Called when the channel opens and after every committed
        configuration update (a raised count, another policy): what is
        compiled already costs a launch, a third of a second in all.
        SYNCHRONOUS: the caller (``Peer.join_channel``, the commit of a
        configuration block) waits two to four minutes where the
        programs are new to the process, and a peer that joins on its
        event loop serves nothing meanwhile.  Only where a chip is the
        device lane (``xla_env.on_accelerator``), never for a sidecar's
        peer; a channel without a configuration (dev mode) has no such
        count."""
        from fabric_tpu.crypto import policy as pol
        from fabric_tpu.protos import orderer_pb2
        from fabric_tpu.utils.xla_env import on_accelerator

        bundle = getattr(self.processor, "bundle", None)
        if bundle is None or not self._device_lane or not on_accelerator():
            return
        size = bundle.orderer_value("BatchSize", orderer_pb2.BatchSize)
        max_tx = int(size.max_message_count) if size is not None else 0
        if max_tx <= 0:
            return
        asts = [bundle.application_policy_ast(name)
                for name in ("Endorsement", "LifecycleEndorsement")]
        per_tx = 1 + max((len(pol.compile_plan(a).principals)
                          for a in asts if a is not None), default=0)
        seen: set = set()
        height = self.ledger.blocks.height
        for num in range(max(0, height - WARM_FROM_BLOCKS), height):
            block = self.ledger.blocks.get_block(num)
            if block is not None:  # under a snapshot's height: not held
                seen |= self.validator.tx_shapes(block)
        warmed = self.validator.warmup(max_tx, per_tx, seen)
        _log.info("%s: warmed %d verify and %d stage-2 programs (%d tx "
                  "shapes in blocks %d..%d) for blocks of up to %d tx in "
                  "%.1f s", self.id, warmed["verify"], warmed["stage2"],
                  len(seen), max(0, height - WARM_FROM_BLOCKS), height - 1,
                  max_tx, warmed["seconds"])

    # -- runtime re-knobbing (the traffic autopilot's actuator) ----------

    def apply_knob(self, knob: str, value) -> None:
        """Apply one autopilot knob step to this channel's live commit
        path.  Every setter latches and applies at a block boundary
        (pipeline.set_depth / set_coalesce_blocks, validator.
        set_verify_chunk), so actuation is always mid-stream-safe; a
        channel with no live pipeline just updates the value the next
        deliver session starts from."""
        if knob == "verify_chunk":
            fn = getattr(self.validator, "set_verify_chunk", None)
            if fn is not None:
                fn(int(value))
        elif knob == "host_stage_workers":
            # block-boundary pool resize (validator latch →
            # HostStagePool.set_workers drain-and-rebuild)
            fn = getattr(self.validator, "set_host_stage_workers", None)
            if fn is not None:
                fn(int(value))
        elif knob == "coalesce_blocks":
            # the deliver driver reads this attribute per iteration,
            # so the new group size takes effect on the next drain
            self.coalesce_blocks = int(value)
            if self.pipe is not None:
                self.pipe.set_coalesce_blocks(int(value))
        elif knob == "pipeline_depth":
            # persist so the NEXT deliver session (pipeline rebuilt at
            # reconnect from self.pipeline_depth) keeps the actuation,
            # and a channel with no live pipe doesn't lose it.  The
            # serial/pipelined boundary stays unconditional: a channel
            # configured serial (1) never becomes pipelined at runtime.
            if self.pipeline_depth > 1 and int(value) >= 2:
                self.pipeline_depth = int(value)
            if self.pipe is not None:
                self.pipe.set_depth(int(value))

    @property
    def height(self) -> int:
        return self.ledger.blocks.height

    def collection_config(self, ns: str, coll: str) -> dict | None:
        """Collection config (member orgs, peer counts, BTL) from the
        channel's policy provider — lifecycle-backed when a definition
        is committed, static otherwise; None = undefined."""
        fn = getattr(self.validator.policies, "collection", None)
        return fn(ns, coll) if fn else None

    def make_endorser(self, msp, signer, runtime):
        """Endorser over THIS channel's state, system chaincodes and
        ACLs — the single construction point shared by the Endorse RPC
        and the gateway (endorser.go:304 wiring)."""
        from fabric_tpu.peer.acl import PROPOSE
        from fabric_tpu.peer.chaincode import LayeredRuntime

        acl = getattr(self, "acl", None)
        return Endorser(
            msp, signer, self.ledger.state,
            LayeredRuntime(runtime, getattr(self, "syscc", {})),
            acl_check=(
                (lambda _ch, creator, msg, sig:
                 acl.check(PROPOSE, creator, msg, sig))
                if acl is not None else None
            ),
        )

    async def commit_block(self, block) -> bytes:
        """Validate + commit one block, strictly serially (the
        StoreBlock path).  Direct callers and the ``pipeline_depth=1``
        deliver loop use this; depth-2 streams go through
        ``_run_deliver_pipelined``/CommitPipeline instead, which
        overlaps block n's validation with block n-1's ledger commit.

        The validate call dispatches device kernels (and may compile on
        first use) — it runs in a worker thread so the node's RPC
        services stay responsive (the reference's validator pool,
        v20/validator.go:193)."""
        import time as _time

        loop = asyncio.get_event_loop()

        def _verify_and_validate(b):
            # signature + attestation checks are ECDSA-heavy: keep them
            # off the event loop with the rest of validation
            self.verify_block_signature(b)
            pend = self.validator.validate_launch(b)
            return pend, self.validator.validate_finish(pend)

        async with self.commit_lock.writer():
            t0 = _time.perf_counter()
            pend, (flt, batch, history) = await loop.run_in_executor(
                None, _verify_and_validate, block
            )
            t1 = _time.perf_counter()
            await self._commit_inner(
                block, pend.txs, flt, batch, history, pend.hd_bytes
            )
            # device-resident state (fabric_tpu/state): the serial /
            # anti-entropy path commits OUTSIDE the CommitPipeline, so
            # its write-set delta must reach the resident table here —
            # a bypassed scatter is exactly the stale-version hazard
            # FT015 polices (idempotent if a pipeline ever re-routes)
            rc = getattr(self.validator, "resident_commit", None)
            if rc is not None:
                rc(batch)
            t2 = _time.perf_counter()
        self._commit_metrics(flt, t1 - t0, t2 - t1, t2 - t0)
        self._signal_height()
        return flt

    async def _commit_inner(self, block, txs, flt, batch, history,
                            hd_bytes, root=None, sync=True) -> None:
        """Validated triple → committed ledger state: pvt-data phase,
        ledger commit + fsync, post-commit bookkeeping.  The caller
        holds the commit writer lock; ``txs`` are the block's parsed
        records (under pipelining ``validator.last_parsed`` already
        points at the NEXT launched block, so they ride in
        explicitly).

        ``root``: the block's tracer root span, passed EXPLICITLY —
        this coroutine runs on the event-loop thread, where the
        pipeline committer thread's span attachment cannot follow.

        ``sync=False`` — deep-pipelined commits with more of the
        window in flight behind them (``CommittedBlock.defer_sync``):
        skip the forced per-block fsync and let the blockstore's
        group-commit machinery batch the syncs across the pipeline
        window.  Every barrier/tail/idle-flush commit arrives with
        sync=True and closes the window, so the durability exposure is
        bounded by the ``group_commit`` knob (set it to 1 to fsync
        every add regardless) plus the deliver driver's idle flush; a
        crash inside the window reopens at the last synced boundary
        and replays forward (the PR-6 crash-replay story, re-pinned by
        the windowed-fsync tests)."""
        # pvt phase (StoreBlock, coordinator.go:190-220): cleartext
        # from transient/pull, hash-verified, into pvt namespaces
        from fabric_tpu.peer.transient import encode_kv

        pvt = await self.coordinator.gather(block.header.number, txs, flt)
        for hns, key, value, ver in pvt.updates:
            if value is None:
                batch.delete(hns, key, ver)
            else:
                batch.put(hns, key, value, ver)

        def _expiry(ns, coll):
            # BTL from the collection config: expiringBlk =
            # committingBlk + btl + 1 (pvtdatapolicy.BTLPolicy) —
            # the data stays queryable for btl FULL blocks past its
            # commit, then purge_expired erases store + pvt state
            btl = int((self.collection_config(ns, coll) or {})
                      .get("btl", 0) or 0)
            return block.header.number + btl + 1 if btl > 0 else 0

        pvt_store = {
            (txnum, ns, coll): (encode_kv(kv), _expiry(ns, coll))
            for txnum, colls in pvt.store_data.items()
            for (ns, coll), kv in colls.items()
        }

        # the storage commit runs ON the event-loop thread, as the
        # serial path always did: the transient/pvtdata sqlite stores
        # share single connections with loop-thread gossip handlers
        # (persist/reconcile), so moving this to a worker would
        # interleave transactions on one connection.  The pipeline's
        # overlap is unaffected — the NEXT block validates on the
        # feeder thread while this runs.
        from fabric_tpu import faults as _faults
        from fabric_tpu.observe import global_tracer

        _faults.fire("peer.ledger_commit", block=block.header.number)
        tracer = global_tracer()
        with tracer.span("ledger_commit", parent=root):
            self.ledger.commit_block(
                block, flt, batch, history, pvt_data=pvt_store,
                txids=[(p.txid, p.idx) for p in txs if p.txid],
                hd_bytes=hd_bytes,
            )
        if pvt.missing:
            self.ledger.pvtdata.commit_block(
                block.header.number, {},
                [(txnum, ns, coll, True)
                 for (txnum, _txid, ns, coll) in pvt.missing],
            )
        self.transient.purge_below(
            max(0, block.header.number - self.transient_retention)
        )
        # clients key retries off commit acknowledgment: force any
        # open group-commit fsync window closed BEFORE signalling
        # height / commit status, so an acknowledged block can never
        # be lost to a crash on a quiet channel (the add-block-time
        # lag check only runs while traffic flows).  Deep-pipelined
        # mid-window commits (sync=False) defer this to the window's
        # closing commit — the whole segment file syncs then.
        if sync:
            with tracer.span("fsync", parent=root):
                self.ledger.blocks.sync()
            # tx-flow durable fence (idempotent, first fence wins):
            # on the serial mem-state path this sync is the block's
            # first durability edge; on durable paths the ledger's own
            # fence already stamped and this is a no-op
            _txflow.block_durable(block.header.number)
        self._post_commit(block, flt, batch, txs)

    def _commit_metrics(self, flt: bytes, validate_s: float,
                        commit_s: float, total_s: float) -> None:
        # the reference's commit-path breakdown (kv_ledger.go:712-727)
        from fabric_tpu.ops_metrics import global_registry

        reg = global_registry()
        reg.histogram(
            "ledger_block_processing_time",
            "full StoreBlock wall clock per block (s)",
        ).observe(total_s, channel=self.id)
        reg.histogram(
            "validation_duration", "validate phase per block (s)"
        ).observe(validate_s, channel=self.id)
        reg.histogram(
            "ledger_statedb_commit_time", "storage commit per block (s)"
        ).observe(commit_s, channel=self.id)
        reg.gauge(
            "ledger_blockchain_height", "committed block height"
        ).set(self.height, channel=self.id)
        n_valid = sum(1 for c in flt if c == 0)
        reg.counter(
            "ledger_transaction_count", "committed txs by validity"
        ).add(n_valid, channel=self.id, status="valid")
        reg.counter(
            "ledger_transaction_count", "committed txs by validity"
        ).add(len(flt) - n_valid, channel=self.id, status="invalid")

    def _signal_height(self) -> None:
        self._height_changed.set()
        self._height_changed = asyncio.Event()

    async def _commit_from_pipeline(self, res) -> None:
        """Commit one CommittedBlock on behalf of the pipeline's
        committer thread (the pvt coordinator and the commit lock are
        loop-affine, so the thread bridges here via
        run_coroutine_threadsafe)."""
        import time as _time

        t0 = _time.perf_counter()
        async with self.commit_lock.writer():
            await self._commit_inner(
                res.block, res.pend.txs, res.tx_filter, res.batch,
                res.history, res.pend.hd_bytes, root=res.root_span,
                sync=not getattr(res, "defer_sync", False),
            )
        commit_s = _time.perf_counter() - t0
        # launch + finish ≈ the serial path's validate span, so a
        # depth-1 → depth-2 flip compares like for like (the prefetch
        # parse overlaps the predecessor and is deliberately excluded)
        validate_s = (res.stage_s.get("launch", 0.0)
                      + res.stage_s.get("finish", 0.0))
        self._commit_metrics(res.tx_filter, validate_s, commit_s,
                             validate_s + commit_s)
        self._signal_height()

    def _post_commit(self, block, flt: bytes, batch, txs=None) -> None:
        """Post-commit bookkeeping: lifecycle-cache invalidation when
        the block wrote ``_lifecycle`` (lifecycle.Cache StateListener
        analog) and channel-config bundle rotation for committed CONFIG
        txs (BundleSource update, core/peer/peer.go).

        Uses the block's already-parsed tx records (``txs``; falls back
        to the validator's last parse for legacy callers) — normal
        blocks cost zero extra parsing.  A failure to APPLY a committed
        config is a serious divergence and must be loud, not
        swallowed."""
        pol_provider = self.validator.policies
        if hasattr(pol_provider, "on_block_committed"):
            pol_provider.on_block_committed(batch)
        # record definition changes for point-in-time config queries
        # (confighistory/mgr.go, reconciler eligibility on old blocks)
        from fabric_tpu.peer.lifecycle import LIFECYCLE_NS

        # an upgrade (new committed sequence → possibly a new package/
        # endpoint) must drop lazily-resolved ccaas bindings
        wrote_lifecycle = batch.touches_namespace(LIFECYCLE_NS)
        rt = getattr(self, "runtime", None)
        if rt is not None and wrote_lifecycle:
            rt.invalidate_resolved()

        if wrote_lifecycle:
            prefix = "namespaces/fields/"
            for (ns, key), vv in batch.items():
                if ns == LIFECYCLE_NS and key.startswith(prefix)                         and key.endswith("/Definition") and vv.value:
                    cc_name = key[len(prefix):-len("/Definition")]
                    self.confighistory.record(
                        block.header.number, cc_name, vv.value
                    )
        proc = self.validator.config_processor
        if proc is None or not hasattr(proc, "apply"):
            return
        from fabric_tpu.protos import configtx_pb2, transaction_pb2

        if txs is None:
            txs = getattr(self.validator, "last_parsed", ())
        for ptx in txs:
            if not ptx.is_config or flt[ptx.idx] != transaction_pb2.TxValidationCode.VALID:
                continue
            try:
                env = protoutil.unmarshal(
                    common_pb2.Envelope, block.data.data[ptx.idx]
                )
                payload = protoutil.unmarshal(common_pb2.Payload, env.payload)
                cfg_env = protoutil.unmarshal(
                    configtx_pb2.ConfigEnvelope, payload.data
                )
            except DecodeError:
                continue  # malformed yet VALID can only be genesis noise
            try:
                new_bundle = proc.apply(cfg_env)
                # rotate the validator onto the new membership: stale
                # cached identities (revoked certs, rotated CAs) must
                # not keep validating (reference: bundle update
                # propagation, core/peer/peer.go BundleSource)
                if hasattr(new_bundle, "msp_manager"):
                    self.validator.msp = new_bundle.msp_manager
            except Exception:
                import logging

                logging.getLogger("fabric_tpu.peer").exception(
                    "%s: committed CONFIG tx %d of block %d failed to "
                    "apply — bundle is now STALE relative to the ledger",
                    self.id, ptx.idx, block.header.number,
                )
                continue
            try:
                # a raised MaxMessageCount, another policy: new programs
                self._warm_programs()
            except Exception:
                _log.exception(
                    "%s: warm-up after the CONFIG tx of block %d failed — "
                    "new programs are lowered where their blocks commit",
                    self.id, block.header.number)

    def verify_block_signature(self, block) -> None:
        """VerifyBlock at deliver (block_verification.go:243): a block
        arriving from ANY source — deliver stream, anti-entropy pull —
        must carry orderer signatures satisfying the channel's
        /Channel/Orderer/BlockValidation policy before it may commit.
        Without this, one compromised orderer (or an impostor peer) can
        fork peers by serving divergent, individually well-formed
        blocks.  The genesis block is the trust anchor (verified
        out-of-band by the joining admin), and channels whose config
        carries no orderer orgs (dev/test assemblies) have no identity
        set to verify against — both skip."""
        if block.header.number == 0:
            return
        bundle = getattr(self.processor, "bundle", None)
        if bundle is None:
            return
        ordg = bundle.config.channel_group.groups.get("Orderer")
        if ordg is None or not ordg.groups:
            return  # no orderer identity set configured
        from fabric_tpu.channelconfig import SignedData

        signed = [
            SignedData(identity=c, data=d, signature=s)
            for c, d, s in protoutil.block_signed_data(block)
        ]
        if not signed or not bundle.policy_manager.evaluate(
            "/Channel/Orderer/BlockValidation", signed
        ):
            raise ValueError(
                f"block {block.header.number}: orderer block-signature "
                "verification failed (BlockValidation policy not met)"
            )
        self._verify_bft_attestation(block, bundle)

    def _verify_bft_attestation(self, block, bundle) -> None:
        """For BFT channels a single orderer signature is NOT enough —
        one byzantine orderer could sign a forged block.  The block's
        consensus metadata must carry the 2f+1 signed COMMIT messages
        for (view, seq, digest-of-batch), each by a distinct, valid
        orderer-org identity, with the digest recomputed from the
        block's own envelopes and seq strictly increasing along the
        chain (reference: BFT quorum attestations,
        common/deliverclient/block_verification.go:278)."""
        import hashlib
        import json as _json

        from fabric_tpu.protos import orderer_pb2

        ct = bundle.orderer_value("ConsensusType", orderer_pb2.ConsensusType)
        if ct is None or ct.type != "bft":
            return
        meta = orderer_pb2.RaftConfigMetadata()
        meta.ParseFromString(ct.metadata)
        n = len(meta.consenters)
        quorum = 2 * ((n - 1) // 3) + 1 if n else 1

        idx = common_pb2.BlockMetadataIndex.ORDERER
        try:
            omd = _json.loads(bytes(block.metadata.metadata[idx]))
            proof = omd["bft_proof"]
            seq = int(omd["index"])
        except Exception:
            raise ValueError(
                f"block {block.header.number}: missing BFT commit proof"
            )
        payload = _json.dumps(
            [bytes(e).hex() for e in block.data.data]
        ).encode()
        want_digest = hashlib.sha256(payload).hexdigest()

        from fabric_tpu.ordering.bft import COMMIT, _signable

        # votes count only from the CONSENTER SET (identities pinned in
        # the channel config), deduped by identity — not by the
        # unauthenticated "from" label: a single compromised identity
        # cannot fabricate 2f+1 votes by inventing sender names, and no
        # non-consenter identity (app orgs, orderer-org admins/users)
        # can vote at all.  Channels whose config predates consenter
        # identities fall back to orderer-ORG membership.
        consenter_ids = {
            bytes(c.identity) for c in meta.consenters if c.identity
        }
        ordg = bundle.config.channel_group.groups.get("Orderer")
        orderer_orgs = set(ordg.groups) if ordg is not None else set()
        voters = set()  # distinct identity bytes
        for m in proof:
            if not isinstance(m, dict) or m.get("type") != COMMIT:
                continue
            if m.get("digest") != want_digest or int(m.get("seq", -1)) != seq:
                continue
            cert = m.get("from_cert")
            sig = m.get("sig")
            if not cert or not sig:
                continue
            try:
                raw_cert = bytes.fromhex(cert)
                if raw_cert in voters:
                    continue
                if consenter_ids:
                    if raw_cert not in consenter_ids:
                        continue
                ident = bundle.msp_manager.deserialize_identity(raw_cert)
                if not ident.is_valid or ident.msp_id not in orderer_orgs:
                    continue
                if not ident.verify(_signable(m), bytes.fromhex(sig)):
                    continue
            except Exception as e:
                _log.debug("attestation vote rejected: %s", e)
                continue
            voters.add(raw_cert)
        if len(voters) < quorum:
            raise ValueError(
                f"block {block.header.number}: BFT attestation has "
                f"{len(voters)} valid commits, quorum is {quorum}"
            )
        # seq monotonicity along the chain: a replayed proof from an
        # older batch cannot attest a later block
        prev_seq = getattr(self, "_last_bft_seq", None)
        if prev_seq is None and block.header.number >= 2:
            try:
                prev = self.ledger.blocks.get_block(block.header.number - 1)
                prev_seq = int(_json.loads(
                    bytes(prev.metadata.metadata[idx])
                )["index"])
            except Exception:
                prev_seq = None
        if prev_seq is not None and seq <= prev_seq:
            raise ValueError(
                f"block {block.header.number}: BFT proof seq {seq} does "
                f"not advance past predecessor's {prev_seq}"
            )
        self._last_bft_seq = seq

    async def run_deliver(self, orderer_addr: tuple[str, int]):
        """Pull blocks from the orderer starting at our height and
        commit them in order; reconnects forever (deliver client
        failover is caller-side: pass a different address).

        With ``pipeline_depth`` ≥ 2 (the default) blocks stream through
        the CommitPipeline so block n's validation, block n-1's ledger
        commit, and block n+1's parse + device launch overlap; depth 1
        commits strictly serially through ``commit_block``."""
        import contextlib

        dc = DeliverClient(*orderer_addr,
                           ssl_ctx=getattr(self, "client_ssl", None))
        async with contextlib.aclosing(dc.blocks(self.id, start=self.height)) as gen:
            if self.pipeline_depth > 1:
                await self._run_deliver_pipelined(gen)
                return
            async for blk in gen:
                # stream liveness for the censorship monitor: a block
                # ARRIVED (even if its validation is slow) — only a
                # silent stream counts as possible withholding
                self._deliver_progress = (
                    getattr(self, "_deliver_progress", 0) + 1
                )
                if blk.header.number < self.height:
                    continue  # replayed
                await self.commit_block(blk)

    # seconds of stream silence before the in-flight tail is flushed:
    # with depth 2 the newest block stays launched-but-uncommitted
    # until the NEXT submit, and a quiet channel must not leave it
    # dangling (clients block on height for their commit ack) —
    # pipelining engages only while blocks arrive back to back
    PIPELINE_IDLE_FLUSH_S = 0.05

    async def _run_deliver_pipelined(self, gen):
        """Depth-N deliver commit driver over peer.pipeline: the
        production analog of the reference's deliver prefetch +
        committer overlap (gossip/state/state.go:540) — the commit
        path stops paying full launch→finish→commit serialization per
        block.  At depth ≥ 3 up to N−1 predecessors' commits drain
        behind the launch under a merged overlay, with mid-window
        fsyncs deferred to the blockstore's group commit (the idle
        flush below closes the window on a quiet channel)."""
        from fabric_tpu.peer.pipeline import CommitPipeline

        loop = asyncio.get_event_loop()

        def commit_fn(res):
            # committer thread → event loop: the pvt coordinator and
            # commit lock are loop-affine (the loop is free — the
            # deliver task awaits pipeline calls in the executor).
            # Poll with a bounded wait instead of blocking forever: if
            # the loop is torn down before the coroutine runs, the
            # future never resolves and an unbounded .result() would
            # wedge the committer thread — and with it executor
            # shutdown and interpreter exit.
            import concurrent.futures as _cf

            fut = asyncio.run_coroutine_threadsafe(
                self._commit_from_pipeline(res), loop
            )
            while True:
                try:
                    return fut.result(timeout=5.0)
                except _cf.TimeoutError:
                    if fut.done():
                        # completed inside the race window (or the
                        # COMMIT itself raised builtin TimeoutError,
                        # py3.11+): a done future answers non-blocking
                        # with the real value or real error — never
                        # re-raise our own poll timeout as the work's
                        return fut.result(timeout=0)
                    if loop.is_closed():
                        fut.cancel()
                        raise RuntimeError(
                            f"{self.id}: event loop closed while "
                            f"committing block {res.block.header.number}"
                        ) from None

        # orderer block signatures + BFT attestation verify at LAUNCH
        # (caller thread), not at prefetch: a predecessor CONFIG block
        # rotates the orderer set at commit, and the barrier only
        # guarantees that rotation has landed by launch time — a
        # forged block must never launch, and a legitimate block must
        # never be judged by the pre-rotation bundle
        pipe = CommitPipeline(
            self.validator, commit_fn, depth=self.pipeline_depth,
            pre_launch_fn=self.verify_block_signature, channel=self.id,
            coalesce_blocks=self.coalesce_blocks, tracer=self.tracer,
        )
        # expose the live pipe to the autopilot's apply_knob for the
        # duration of this deliver session
        self.pipe = pipe
        # submit() blocks for device syncs and for the committer
        # thread — feeding from the shared default executor could
        # exhaust it when many channels block in submit at once,
        # starving everything else that needs a worker (endorsements,
        # other channels' commits).  A dedicated feeder thread per
        # channel keeps the pools independent.
        from concurrent.futures import ThreadPoolExecutor

        feeder = ThreadPoolExecutor(1, thread_name_prefix="fabtpu-feed")
        # blocks arrive through a reader task + queue so this driver
        # can flush the pipeline's in-flight tail when the stream goes
        # idle (see PIPELINE_IDLE_FLUSH_S) — asyncio.wait_for directly
        # on the generator would cancel its internal stream read
        reader_exc: list = []
        q: asyncio.Queue = asyncio.Queue(maxsize=4)

        async def reader():
            from fabric_tpu import faults as _faults

            try:
                async for blk in gen:
                    # chaos hook: a FaultPlan can cut the stream here
                    # (disconnect/truncate) — the reconnect loop's
                    # backoff + replay-from-height path must absorb it.
                    # afire so a latency fault slows THIS stream via
                    # asyncio.sleep instead of freezing the event loop
                    if _faults.plan() is not None:
                        await _faults.afire("deliver.read",
                                            block=blk.header.number)
                    await q.put(blk)
            except BaseException as e:
                reader_exc.append(e)
            finally:
                await q.put(None)

        rtask = asyncio.ensure_future(reader())
        # height lags the in-flight window, so replay detection tracks
        # the next EXPECTED number, not the committed height
        expect = self.height
        try:
            while True:
                try:
                    if pipe.inflight:
                        blk = await asyncio.wait_for(
                            q.get(), timeout=self.PIPELINE_IDLE_FLUSH_S
                        )
                    else:
                        blk = await q.get()
                except asyncio.TimeoutError:
                    # stream went quiet with a block in flight:
                    # commit the tail now — its clients are waiting
                    await loop.run_in_executor(feeder, pipe.flush)
                    continue
                if blk is None:
                    break  # stream ended (reader_exc carries errors)
                self._deliver_progress = (
                    getattr(self, "_deliver_progress", 0) + 1
                )
                # a concurrent anti-entropy pull may commit past our
                # window — resync to the live height so a redelivered
                # block is skipped (as the serial path does) instead
                # of validated and rejected at the ledger
                expect = max(expect, self.height)
                if blk.header.number < expect:
                    continue  # replayed
                expect = blk.header.number + 1
                if self.pipeline_depth <= 1:
                    # pinned to serial mid-stream (anti-entropy came
                    # up, see gossip.start_anti_entropy): drain the
                    # pipeline, then commit through the locked path
                    await loop.run_in_executor(feeder, pipe.flush)
                    await self.commit_block(blk)
                    continue
                # launch coalescing: opportunistically drain the
                # backlog (no await — only blocks ALREADY queued) so
                # their signature batches ride one device dispatch
                group, stream_end = [blk], False
                while (self.coalesce_blocks >= 2
                       and len(group) < self.coalesce_blocks):
                    try:
                        nxt = q.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                    if nxt is None:
                        stream_end = True
                        break
                    self._deliver_progress = (
                        getattr(self, "_deliver_progress", 0) + 1
                    )
                    if nxt.header.number < max(expect, self.height):
                        continue  # replayed
                    expect = nxt.header.number + 1
                    group.append(nxt)
                if len(group) == 1:
                    await loop.run_in_executor(feeder, pipe.submit, blk)
                else:
                    await loop.run_in_executor(
                        feeder, pipe.submit_many, group
                    )
                if stream_end:
                    break
            if reader_exc:
                raise reader_exc[0]
        except BaseException:
            # drop the in-flight tail: height never advanced for it,
            # so the reconnect re-delivers from the right place.  A
            # pipeline STAGE exception already failed the pipe closed
            # (quarantining the failing block — pipe.last_failure);
            # say which block so a deterministic poison pill is
            # diagnosable instead of an anonymous reconnect storm.
            if pipe.last_failure is not None:
                num, stage = pipe.last_failure
                _log.warning(
                    "%s: quarantining block %s after a %s-stage "
                    "failure; pipe drained, resuming deliver from "
                    "committed height %d", self.id, num, stage,
                    self.height,
                )
            await loop.run_in_executor(
                feeder, lambda: pipe.close(flush=False)
            )
            raise
        else:
            # stream closed cleanly: flush the verified tail
            await loop.run_in_executor(feeder, pipe.close)
        finally:
            self.pipe = None
            # await the cancelled reader before run_deliver's
            # aclosing() touches the generator: aclose() on a
            # still-running async generator raises and would MASK the
            # real stream/commit error
            rtask.cancel()
            await asyncio.gather(rtask, return_exceptions=True)
            feeder.shutdown(wait=False)

    def start_deliver(self, orderer_addrs: list[tuple[str, int]],
                      censorship_check_s: float = 2.0):
        """Background commit driver with orderer failover AND
        censorship monitoring: an orderer that keeps the Deliver
        stream open while withholding blocks is detected by
        cross-checking the OTHER orderers' reported heights — when the
        stream is silent but the rest of the cluster is ahead of us,
        the connection rotates (the deliver-client BFT stance,
        blocksprovider/bft_censorship_monitor.go + bft_deliverer.go;
        a disconnect-only failover cannot see withholding)."""
        import logging

        self.orderer_addrs = list(orderer_addrs)  # gateway Submit uses these

        log = logging.getLogger("fabric_tpu.peer.deliver")

        async def probe_height(addr) -> int:
            from fabric_tpu.comm.rpc import RpcClient

            cli = RpcClient(*addr, ssl_ctx=getattr(self, "client_ssl", None))
            try:
                await cli.connect()
                res = json.loads(await asyncio.wait_for(
                    cli.unary("Info", json.dumps(
                        {"channel": self.id}).encode()),
                    censorship_check_s,
                ))
                return int(res.get("height", -1)) if res.get(
                    "status") == 200 else -1
            except Exception:
                return -1
            finally:
                try:
                    await cli.close()
                except (OSError, RuntimeError):
                    pass  # orderer already gone

        async def censored(current) -> bool:
            # f+1 corroboration: ONE lying orderer (inflated Info
            # height) must not be able to tear down a healthy stream —
            # the BFT fault budget for the orderer list is
            # f = (N-1)//3, so f+1 distinct claims guarantee an honest
            # voucher
            others = [a for a in orderer_addrs if a != current]
            needed = (len(orderer_addrs) - 1) // 3 + 1
            ahead = 0
            for a in others:
                if await probe_height(a) > self.height:
                    ahead += 1
                    if ahead >= needed:
                        return True
            return False

        async def deliver_monitored(addr):
            t = asyncio.ensure_future(self.run_deliver(addr))
            idle_probes = 0
            try:
                while True:
                    p0 = getattr(self, "_deliver_progress", 0)
                    # quiet channels back the probing off (up to 8x):
                    # the monitor is for WITHHOLDING, not for idling
                    await asyncio.wait(
                        {t},
                        timeout=censorship_check_s * min(8, 1 + idle_probes),
                    )
                    if t.done():
                        return await t  # propagate stream errors
                    if getattr(self, "_deliver_progress", 0) != p0:
                        idle_probes = 0  # blocks are flowing (even if
                        continue         # validation is slow)
                    if len(orderer_addrs) > 1 and await censored(addr):
                        log.warning(
                            "%s: orderer %s serves a silent stream while "
                            "the cluster is ahead of height %d — "
                            "suspecting censorship, rotating",
                            self.id, addr, self.height,
                        )
                        raise RuntimeError("deliver censorship suspected")
                    idle_probes += 1
            finally:
                if not t.done():
                    # wait the cancelled stream out: its handler closes
                    # the CommitPipeline and the feeder thread, which a
                    # loop torn down first would leave running
                    t.cancel()
                    await asyncio.gather(t, return_exceptions=True)

        from fabric_tpu.ops_metrics import global_registry
        from fabric_tpu.utils.backoff import Backoff

        reconnects = global_registry().counter(
            "deliver_reconnects_total",
            "deliver stream reconnect attempts by channel",
        )

        async def loop():
            # capped exponential backoff + full jitter (utils.backoff):
            # the old fixed 0.2s retry turned an orderer outage into a
            # lockstep connect storm from every peer; progress (height
            # advanced during the attempt) resets the cadence so a
            # healthy stream that drops reconnects promptly
            bo = Backoff(base=0.2, cap=15.0, jitter=0.5)
            i = 0
            while True:
                addr = orderer_addrs[i % len(orderer_addrs)]
                i += 1
                h0 = self.height
                try:
                    await deliver_monitored(addr)
                except Exception as e:
                    # a deterministic commit failure re-fails forever;
                    # it must at least be VISIBLE
                    if self.height > h0:
                        bo.reset()
                    reconnects.add(1, channel=self.id)
                    delay = bo.next()
                    log.warning(
                        "%s deliver from %s: %s: %s — reconnecting "
                        "from height %d in %.2fs (attempt %d)",
                        self.id, addr, type(e).__name__, e,
                        self.height, delay, bo.attempt,
                    )
                    await asyncio.sleep(delay)

        self._deliver_task = asyncio.ensure_future(loop())

    async def snapshot(self, out_dir: str) -> dict:
        """Export a ledger snapshot at the current height, serialized
        against commits (snapshot_mgmt.go commitStart/commitDone)."""
        from fabric_tpu.ledger.snapshot import generate_snapshot

        cfg = b""
        proc = getattr(self, "processor", None)
        if proc is not None and hasattr(proc, "bundle"):
            cfg = proc.bundle.config.SerializeToString()
        loop = asyncio.get_event_loop()
        async with self.commit_lock.writer():
            # worker thread: a large state export must not freeze the
            # node's RPC services for its duration
            return await loop.run_in_executor(
                None,
                lambda: generate_snapshot(
                    self.ledger, out_dir, channel_id=self.id, config_bytes=cfg
                ),
            )

    async def replay_local(self, src_dir: str,
                           depth: int | None = None) -> dict:
        """Catch this channel up from a LOCAL block store directory
        (``peer ... replay_from`` — a serving peer's copied chain, an
        anti-entropy mirror, or this peer's own pre-wipe store) at
        full pipeline depth with zero inter-block think time
        (peer/replay.py).  Resumes from the committed height — a
        killed replay restarts exactly where it stopped — and holds
        the autopilot in throughput mode for the duration.  Returns
        the replay stats dict."""
        from fabric_tpu.ledger.blockstore import BlockStore
        from fabric_tpu.peer.replay import ReplayCheckpoint, ReplayDriver

        loop = asyncio.get_event_loop()

        def commit_fn(res):
            # committer thread → event loop, exactly the deliver
            # driver's bridge (commit lock + pvt coordinator are
            # loop-affine); bounded poll per the FT009 discipline
            import concurrent.futures as _cf

            fut = asyncio.run_coroutine_threadsafe(
                self._commit_from_pipeline(res), loop
            )
            while True:
                try:
                    return fut.result(timeout=5.0)
                except _cf.TimeoutError:
                    if fut.done():
                        return fut.result(timeout=0)
                    if loop.is_closed():
                        fut.cancel()
                        raise RuntimeError(
                            f"{self.id}: event loop closed while "
                            f"committing replayed block "
                            f"{res.block.header.number}"
                        ) from None

        def hook(pipe):
            self.pipe = pipe

        src = BlockStore(src_dir)
        drv = ReplayDriver(
            self.validator, commit_fn,
            depth=self.pipeline_depth if depth is None else depth,
            checkpoint=ReplayCheckpoint(
                f"{self.ledger.blocks.dir}/replay_checkpoint.json"
            ),
            pre_launch_fn=self.verify_block_signature, channel=self.id,
            coalesce_blocks=self.coalesce_blocks, tracer=self.tracer,
            pipe_hook=hook,
        )
        start = self.height
        from concurrent.futures import ThreadPoolExecutor

        # dedicated feeder thread, like the deliver driver: submit()
        # blocks on device syncs and must not starve the shared pool
        feeder = ThreadPoolExecutor(1, thread_name_prefix="fabtpu-replay")
        try:
            stats = await loop.run_in_executor(
                feeder, lambda: drv.run(src.iter_blocks(start),
                                        start=start)
            )
        finally:
            feeder.shutdown(wait=False)
            src.close()
        stats["resumed_from"] = start
        return stats

    async def wait_height(self, h: int, timeout: float = 30.0):
        loop = asyncio.get_event_loop()
        deadline = loop.time() + timeout
        while self.height < h:
            ev = self._height_changed
            remaining = deadline - loop.time()
            if remaining <= 0:
                raise asyncio.TimeoutError(f"height {self.height} < {h}")
            await asyncio.wait_for(ev.wait(), remaining)

    def stop(self):
        if self._deliver_task:
            self._deliver_task.cancel()
        self.validator.close()  # host staging pool worker threads
        self.transient.close()
        self.confighistory.close()
        self.ledger.close()


# single shared default with PeerConfig (nodeconfig is import-light)
from fabric_tpu.nodeconfig import DEFAULT_MAX_PACKAGE_SIZE  # noqa: E402


class PeerNode:
    def __init__(self, node_id: str, data_dir: str, msp_manager, signer,
                 runtime: ChaincodeRuntime | None = None,
                 host: str = "127.0.0.1", port: int = 0, tls=None,
                 max_package_size: int = DEFAULT_MAX_PACKAGE_SIZE,
                 install_require_admin: bool = False,
                 pipeline_depth: int = 2, verify_chunk: int = 0,
                 mesh_devices: int = 0, mesh_topology=None,
                 coalesce_blocks: int = 0,
                 host_stage_workers: int = 0, recode_device: bool = False,
                 trace_ring_blocks: int | None = None,
                 trace_slow_factor: float | None = None,
                 slos: str = "",
                 vitals_interval_s: float = 0.0,
                 vitals_retention: int = 240,
                 blackbox_dir: str = "",
                 device_ledger: bool = True,
                 autopilot: bool = False,
                 autopilot_tick_s: float = 1.0,
                 autopilot_knobs: str = "",
                 sign_device: bool = False,
                 sign_batch_max: int = 256,
                 sign_batch_wait_ms: float = 2.0,
                 sign_self_check: bool = False,
                 device_fail_threshold: int = 0,
                 device_retries: int = 2,
                 device_recovery_s: float = 30.0,
                 verify_deadline_ms: float = 0.0,
                 state_resident: bool = False,
                 state_resident_mb: int = 64,
                 state_resident_range_bits: int = 12,
                 faults: str = "",
                 sidecar_endpoint: str = "",
                 sidecar_weight: float = 1.0,
                 sidecar_recovery_s: float = 5.0,
                 sidecar_listen: str = "",
                 sidecar_queue_blocks: int = 8,
                 sidecar_coalesce: int = 4,
                 async_commit: bool = True,
                 apply_queue_blocks: int = 4,
                 tx_flow: bool = True):
        self.id = node_id
        self.dir = data_dir
        self.msp = msp_manager
        self.signer = signer
        self.runtime = runtime or ChaincodeRuntime()
        # commit-path knobs every joined channel inherits (nodeconfig
        # pipeline_depth / verify_chunk / mesh_devices / coalesce_blocks)
        self.pipeline_depth = int(pipeline_depth)
        # async group-commit storage engine (nodeconfig async_commit /
        # apply_queue_blocks, default ON; False = serial fallback)
        self.async_commit = bool(async_commit)
        self.apply_queue_blocks = int(apply_queue_blocks)
        self.verify_chunk = int(verify_chunk)
        self.mesh_devices = int(mesh_devices)
        # declarative mesh topology (parallel.topology.MeshTopology,
        # nodeconfig mesh_shape / mesh_distributed / mesh_coordinator):
        # when configured it wins over the bare mesh_devices count;
        # every joined channel's validator shares the resolved fabric
        self.mesh_topology = mesh_topology
        self.coalesce_blocks = int(coalesce_blocks)
        self.host_stage_workers = int(host_stage_workers)
        self.recode_device = bool(recode_device)
        # span-tracer knobs (None = leave the global tracer as-is)
        self.trace_ring_blocks = trace_ring_blocks
        self.trace_slow_factor = trace_slow_factor
        # SLO spec (nodeconfig ``slos``): armed at start(), like the
        # tracer knobs — a constructor side effect would let a second
        # node silently wipe the first's engine state
        self.slos = slos
        # flight-data recorder knobs (nodeconfig ``vitals_interval_s``
        # / ``vitals_retention`` / ``blackbox_dir``): armed at start(),
        # like the SLO engine — interval 0 (the default) builds no
        # sampler thread and leaves every incident hook a no-op
        self.vitals_interval_s = float(vitals_interval_s)
        self.vitals_retention = int(vitals_retention)
        self.blackbox_dir = blackbox_dir
        self.vitals = None
        self.blackbox = None
        # device-time launch ledger (nodeconfig ``device_ledger``,
        # default ON): armed refcounted at start() like the recorder —
        # colocated nodes share one ledger, the last release disarms
        self.device_ledger = bool(device_ledger)
        self.launch_ledger = None
        # per-tx flow journal (nodeconfig ``tx_flow``, default ON):
        # armed refcounted at start() like the launch ledger —
        # colocated nodes share one journal, the last release disarms
        self.tx_flow = bool(tx_flow)
        self.txflow_journal = None
        # traffic autopilot (nodeconfig ``autopilot`` / ``autopilot_
        # tick_s`` / ``autopilot_knobs``): built and started at
        # start() — OFF by default, so tier-1/CPU hosts never even
        # construct the controller
        self.autopilot = bool(autopilot)
        self.autopilot_tick_s = float(autopilot_tick_s)
        self.autopilot_knobs = autopilot_knobs
        self.autopilot_ctl = None
        # device-batched ESCC signing (peer/signlane.py): OFF keeps
        # the serial crypto/identity.py signer — batcher + provider
        # are built at start() so a never-started node owns no thread
        self.sign_device = bool(sign_device)
        self.sign_batch_max = int(sign_batch_max)
        self.sign_batch_wait_ms = float(sign_batch_wait_ms)
        self.sign_self_check = bool(sign_self_check)
        self.sign_batcher = None
        self.sign_signer = None
        # device-lane degradation knobs (peer/degrade.py): threshold 0
        # keeps the guard off — the safe default everywhere
        self.device_fail_threshold = int(device_fail_threshold)
        self.device_retries = int(device_retries)
        self.device_recovery_s = float(device_recovery_s)
        self.verify_deadline_ms = float(verify_deadline_ms)
        # device-resident MVCC state knobs (fabric_tpu/state): every
        # joined channel's validator pins an LRU key-range residency
        # cache in device memory.  OFF by default — CPU/tier-1 hosts
        # keep the exact host state_fill path.
        self.state_resident = bool(state_resident)
        self.state_resident_mb = int(state_resident_mb)
        self.state_resident_range_bits = int(state_resident_range_bits)
        # validation sidecar knobs (fabric_tpu/sidecar): endpoint =
        # this peer's channels validate through a remote sidecar;
        # listen = this process ALSO serves one from its device fabric
        self.sidecar_endpoint = sidecar_endpoint
        self.sidecar_weight = float(sidecar_weight)
        self.sidecar_recovery_s = float(sidecar_recovery_s)
        self.sidecar_listen = sidecar_listen
        self.sidecar_queue_blocks = int(sidecar_queue_blocks)
        self.sidecar_coalesce = int(sidecar_coalesce)
        self.sidecar_server = None
        if faults:
            # chaos spec (nodeconfig ``faults`` / FABTPU_FAULTS): arm
            # the process-global fault plan — staging/soak rigs only
            from fabric_tpu import faults as _faults_mod

            _faults_mod.configure(faults)
        # install-surface admission (see _on_install): a size cap
        # always, and optionally an admin-signed request envelope
        self.max_package_size = int(max_package_size)
        self.install_require_admin = bool(install_require_admin)
        from fabric_tpu.peer.ccpackage import PackageStore

        self.packages = PackageStore(data_dir)
        if self.runtime.resolver is None:
            self.runtime.resolver = self._resolve_chaincode
        self.tls = tls  # comm.rpc.TlsProfile: mTLS on every surface
        self.channels: dict[str, PeerChannel] = {}
        self.server = RpcServer(
            host, port, ssl_ctx=tls.server_ctx() if tls else None
        )
        from fabric_tpu.discovery import PeerRegistry

        self.registry = PeerRegistry()  # org → endorsing peers (gateway/discovery)
        # strong refs to fire-and-forget background tasks: the event
        # loop holds tasks weakly, so an unreferenced task can be GC'd
        # mid-flight and its exception is lost
        self._bg: set = set()

    # -- lifecycle install / package resolution ------------------------------

    async def _on_install(self, req: bytes) -> bytes:
        """InstallChaincode: persist a package to the install store
        (internal/peer/lifecycle/chaincode/install.go).

        Admission is layered: the node's mTLS client auth at the
        transport, an unconditional size cap (a connected client must
        not be able to fill the peer's data dir), and — with
        ``install_require_admin`` — a signed request envelope
        ``{"package": hex, "identity": hex, "signature": hex}`` whose
        identity must deserialize to a VALID admin of a known org and
        whose signature must cover the package bytes (the reference's
        install admin-policy check, compressed to one principal)."""
        # the admin envelope hex-encodes the package (2×) and adds
        # identity + signature fields: bound the WIRE request
        # generously before parsing, then cap the DECODED package
        # bytes against the configured max either way
        wire_bound = (
            2 * self.max_package_size + 65536
            if self.install_require_admin else self.max_package_size
        )
        if len(req) > wire_bound:
            return json.dumps({
                "status": 413,
                "message": (
                    f"install request too large: {len(req)} bytes "
                    f"exceeds the bound of {wire_bound}"
                ),
            }).encode()
        raw = req
        if self.install_require_admin:
            err, raw = self._check_install_auth(req)
            if err is not None:
                return err
        if len(raw) > self.max_package_size:
            return json.dumps({
                "status": 413,
                "message": (
                    f"package too large: {len(raw)} bytes exceeds the "
                    f"configured max of {self.max_package_size}"
                ),
            }).encode()
        try:
            info = self.packages.install(raw)
        except ValueError as e:
            return json.dumps({"status": 400, "message": str(e)}).encode()
        return json.dumps({"status": 200, **info}).encode()

    def _check_install_auth(self, req: bytes):
        """→ (error_response | None, package_bytes)."""
        from fabric_tpu.crypto.identity import ROLE_ADMIN

        def deny(msg: str) -> bytes:
            return json.dumps({"status": 403, "message": msg}).encode()

        try:
            envelope = json.loads(req)
            pkg = bytes.fromhex(envelope["package"])
            ident_ser = bytes.fromhex(envelope["identity"])
            sig = bytes.fromhex(envelope["signature"])
        except Exception:
            return deny(
                "install requires an admin-signed request envelope "
                '{"package", "identity", "signature"} (hex fields)'
            ), b""
        try:
            ident = self.msp.deserialize_identity(ident_ser)
        except Exception as e:
            return deny(f"unknown installer identity: {e}"), b""
        if not ident.is_valid:
            return deny("installer identity failed MSP validation"), b""
        my_msp = getattr(self.signer, "msp_id", None)
        if my_msp and ident.msp_id != my_msp:
            # the reference's install policy is LOCAL-MSP admins: an
            # admin of another channel org must not install here
            return deny(
                f"installer org '{ident.msp_id}' is not this peer's "
                f"org '{my_msp}'"
            ), b""
        if getattr(ident, "role", None) != ROLE_ADMIN:
            return deny(
                f"installer '{ident.msp_id}' is not an admin"
            ), b""
        if not ident.verify(pkg, sig):
            return deny("install signature does not cover package"), b""
        return None, pkg

    async def _on_query_installed(self, req: bytes) -> bytes:
        return json.dumps(
            {"status": 200, "installed": self.packages.list()}
        ).encode()

    def _resolve_chaincode(self, name: str, channel: str = ""):
        """Registry-miss launcher: a namespace with a COMMITTED
        lifecycle definition ON THIS CHANNEL whose package (the id
        bound by my org's approval) is installed here gets a ccaas
        proxy to the endpoint its connection.json names — the
        external-builder launch path, minus Docker (by design).  The
        channel scoping matters: the same name on two channels may
        bind different packages."""
        import re as _re

        from fabric_tpu.peer.ccaas import CCaaSProxy
        from fabric_tpu.peer.lifecycle import (
            LIFECYCLE_NS, ChaincodeDefinition, approval_key,
            definition_key,
        )

        ch = self.channels.get(channel)
        if ch is None:
            return None
        my_msp = getattr(self.signer, "msp_id", None)
        state = ch.ledger.state
        vv = state.get_state(LIFECYCLE_NS, definition_key(name))
        if vv is None:
            return None
        try:
            cd = ChaincodeDefinition.from_bytes(vv.value)
        except Exception:
            return None
        # the package THIS ORG approved for the current sequence
        av = state.get_state(
            LIFECYCLE_NS, approval_key(name, cd.sequence, my_msp or "")
        )
        if av is None:
            return None
        try:
            spec = json.loads(av.value)
            pkg_id = spec.get("package_id", "") if isinstance(
                spec, dict) else ""
        except Exception:
            return None
        conn = self.packages.connection(pkg_id) if pkg_id else None
        addr = (conn or {}).get("address", "")
        m = _re.fullmatch(r"(.+):(\d+)", addr)
        if m:
            return CCaaSProxy(name, m.group(1), int(m.group(2)))
        return None

    def join_channel(self, channel_id: str, policy_provider: PolicyProvider | None = None,
                     state_db=None, config_processor=None,
                     genesis_block=None, snapshot_dir=None) -> PeerChannel:
        anchored = genesis_block is not None or snapshot_dir is not None
        ch = PeerChannel(
            channel_id, f"{self.dir}/{channel_id}",
            None if anchored else self.msp,
            policy_provider, state_db, config_processor,
            genesis_block=genesis_block, snapshot_dir=snapshot_dir,
            pipeline_depth=self.pipeline_depth,
            verify_chunk=self.verify_chunk,
            mesh_devices=self.mesh_devices,
            mesh_topology=self.mesh_topology,
            coalesce_blocks=self.coalesce_blocks,
            host_stage_workers=self.host_stage_workers,
            recode_device=self.recode_device,
            trace_ring_blocks=self.trace_ring_blocks,
            trace_slow_factor=self.trace_slow_factor,
            device_fail_threshold=self.device_fail_threshold,
            device_retries=self.device_retries,
            device_recovery_s=self.device_recovery_s,
            verify_deadline_ms=self.verify_deadline_ms,
            state_resident=self.state_resident,
            state_resident_mb=self.state_resident_mb,
            state_resident_range_bits=self.state_resident_range_bits,
            sidecar_endpoint=self.sidecar_endpoint,
            sidecar_weight=self.sidecar_weight,
            sidecar_recovery_s=self.sidecar_recovery_s,
            sidecar_ssl=self.tls.client_ctx() if self.tls else None,
            async_commit=self.async_commit,
            apply_queue_blocks=self.apply_queue_blocks,
        )
        ch.client_ssl = self.tls.client_ctx() if self.tls else None
        ch.runtime = self.runtime  # resolved-binding invalidation hook
        self.channels[channel_id] = ch
        gsvc = getattr(self, "gossip_service", None)
        if gsvc is not None:
            ch.pvt_puller = gsvc.pull_pvt_for(channel_id)
        return ch

    # -- services ------------------------------------------------------------

    async def start(self, operations_port: int | None = None):
        self.server.register_unary("Endorse", self._on_endorse)
        self.server.register("DeliverBlocks", self._on_deliver_blocks)
        self.server.register_unary("Query", self._on_query)
        self.server.register_unary("Info", self._on_info)
        self.server.register_unary("Discover", self._on_discover)
        self.server.register_unary("Snapshot", self._on_snapshot)
        self.server.register_unary("InstallChaincode", self._on_install)
        self.server.register_unary("QueryInstalled", self._on_query_installed)
        from fabric_tpu.peer import gateway as gw

        self.gateway = gw.register(self)
        from fabric_tpu.gossip import GossipService

        self.gossip_service = GossipService(self).register()
        await self.server.start()
        self.port = self.server.port
        if self.slos:
            # arm the process-global burn-rate engine on the global
            # tracer's finished-block stream; /slo (operations server
            # below) serves its report.  Spec validity was checked at
            # config load (nodeconfig), so this cannot raise mid-start.
            from fabric_tpu.observe import slo as _slo

            _slo.configure(self.slos)
        if self.sidecar_listen:
            # nodeconfig ``sidecar_listen``: this peer's device fabric
            # ALSO serves a validation sidecar — other peers attach as
            # tenants (the many-peers-one-pod shape without a separate
            # sidecar process)
            from fabric_tpu.sidecar.server import SidecarServer
            from fabric_tpu.sidecar.client import parse_endpoint

            sc_host, sc_port = parse_endpoint(self.sidecar_listen)
            self.sidecar_server = await SidecarServer(
                sc_host, sc_port,
                mesh_devices=self.mesh_devices,
                mesh_topology=self.mesh_topology,
                verify_chunk=self.verify_chunk,
                recode_device=self.recode_device,
                queue_blocks=self.sidecar_queue_blocks,
                coalesce=self.sidecar_coalesce,
                ssl_ctx=self.tls.server_ctx() if self.tls else None,
            ).start()
        if self.sign_device:
            # device-batched ESCC signing: concurrent Endorse/gateway
            # sign requests coalesce into one padded fixed-base device
            # dispatch (ops/p256sign), RFC 6979 nonces — bit-equal to
            # the serial signer the OFF path keeps
            from fabric_tpu.peer import signlane

            try:
                d = signlane.private_scalar(self.signer)
            except ValueError as e:
                _log.warning(
                    "sign_device requested but %s — keeping the "
                    "serial signing path", e,
                )
            else:
                self.sign_batcher = signlane.SignBatcher(
                    signlane.device_sign_backend(
                        d, chunk=self.verify_chunk,
                        mesh_devices=self.mesh_devices,
                        verify_after=self.sign_self_check,
                    ),
                    batch_max=self.sign_batch_max,
                    wait_ms=self.sign_batch_wait_ms,
                ).start()
                self.sign_signer = signlane.BatchedSigner(
                    self.signer, self.sign_batcher
                )
                if self.slos:
                    # endorse-side SLOs: a peer that declares SLOs AND
                    # runs the sign lane arms the default
                    # endorse:latency / endorse_busy:busy pair (unless
                    # the operator's spec already names the endorse
                    # channel) and feeds them from the lane's
                    # per-request wait/BUSY telemetry — the same
                    # values its histograms record — so /slo and
                    # burns() cover the endorsement half of the flow
                    from fabric_tpu.observe import slo as _slo

                    engine = _slo.global_engine()
                    if not any(o.channel == _slo.ENDORSE_CHANNEL
                               for o in engine.objectives):
                        engine.set_objectives(
                            tuple(engine.objectives) + tuple(
                                _slo.parse_slos(
                                    _slo.DEFAULT_ENDORSE_SLOS
                                )
                            )
                        )
                    self.sign_batcher.observer = (
                        _slo.endorse_observer(engine)
                    )
        if self.autopilot:
            # close the adaptive-control loop: the controller reads
            # the global SLO engine + the sidecar scheduler (when this
            # process serves one) + the tracer's flight recorder, and
            # actuates every joined channel's runtime setters.  All
            # knobs stay inside the operator's validated clamp spec.
            from fabric_tpu.control import (
                Autopilot, host_clamped_specs, parse_knob_specs,
                resolve_host_workers_initial, set_global,
            )
            from fabric_tpu.observe.slo import global_engine

            def _apply(knob, value):
                # snapshot: this runs on the controller thread while
                # join_channel mutates the dict on the event loop
                for ch in list(self.channels.values()):
                    ch.apply_knob(knob, value)
                # a colocated sidecar server shares the coalescing
                # pressure signal (its scheduler's queue ages drive
                # the rule), so the cross-tenant dispatch cap follows
                # the same actuation through its drain-boundary setter
                if (knob == "coalesce_blocks"
                        and self.sidecar_server is not None):
                    self.sidecar_server.set_coalesce(int(value))
                # the sign batcher is node-level (one ESCC key, one
                # lane) — actuated here, not per channel
                if (knob == "sign_batch_max"
                        and self.sign_batcher is not None):
                    self.sign_batcher.set_batch_max(int(value))
                if (knob == "sign_batch_wait_ms"
                        and self.sign_batcher is not None):
                    self.sign_batcher.set_wait_ms(float(value))

            def _commit_stats():
                # worst trailing state-apply queue age across this
                # node's channels (same snapshot idiom as _apply:
                # join_channel mutates the dict on the event loop).
                # Serial-commit channels have no engine and contribute
                # nothing — an empty dict reads as signal-absent, so a
                # fully-serial node never fires the apply rule.
                ages = [
                    float(ch.ledger.engine.stats()
                          .get("oldest_age_ms", 0.0))
                    for ch in list(self.channels.values())
                    if getattr(ch.ledger, "engine", None) is not None
                ]
                return {"oldest_age_ms": max(ages)} if ages else {}

            from types import SimpleNamespace

            commit_src = SimpleNamespace(stats=_commit_stats)

            sched = (self.sidecar_server.scheduler
                     if self.sidecar_server is not None else None)
            # the host-workers ladder clamps to this machine's cores
            # (rungs the pool cannot take must not charge cooldowns or
            # log phantom decisions), and its starting value is the
            # RESOLVED pool size, not the raw config (−1 would snap to
            # 0 and invert the knob)
            specs = host_clamped_specs(
                parse_knob_specs(self.autopilot_knobs or None)
            )
            if self.sign_batch_wait_ms == 0:
                # wait_ms=0 is the operator's STATIC flush-immediately
                # choice (the spec parser itself refuses a 0 ladder
                # floor) — snapping it onto the 0.5 rung and stepping
                # "up" on the first empty flush would silently override
                # it, so the knob stays structurally inert here
                specs = {k: v for k, v in specs.items()
                         if k != "sign_batch_wait_ms"}
            self.autopilot_ctl = Autopilot(
                specs, _apply,
                set_weight=(sched.set_weight if sched else None),
                set_shed=(sched.set_shed if sched else None),
                slo=global_engine(), scheduler=sched,
                sign_source=self.sign_batcher,
                commit_source=commit_src,
                tick_s=self.autopilot_tick_s,
                initial={
                    "coalesce_blocks": self.coalesce_blocks,
                    "verify_chunk": self.verify_chunk,
                    "pipeline_depth": self.pipeline_depth,
                    "host_stage_workers": resolve_host_workers_initial(
                        self.host_stage_workers
                    ),
                    "sign_batch_max": self.sign_batch_max,
                    "sign_batch_wait_ms": self.sign_batch_wait_ms,
                },
            )
            if self.sidecar_server is not None:
                self.sidecar_server.autopilot = self.autopilot_ctl
            set_global(self.autopilot_ctl)
            self.autopilot_ctl.start()
        if self.vitals_interval_s > 0 or self.blackbox_dir:
            # flight-data recorder: the sampler keeps trailing metric
            # series (/vitals) and the black-box recorder freezes them
            # — plus trace trees, the autopilot decision log, scheduler
            # stats, SLO burn and fault stats — into one bundle per
            # incident edge.  Armed only here: the default config
            # builds neither the thread nor the recorder.
            from fabric_tpu.observe import blackbox as _blackbox
            from fabric_tpu.observe import timeseries as _timeseries

            # REFCOUNTED arming: colocated nodes share one sampler
            # and one recorder, and only the LAST stop() disarms —
            # neither the creator nor a later arriver stopping first
            # can strand the survivor (acquire/release in the observe
            # modules; a second acquire reuses the live instances)
            if self.vitals_interval_s > 0:
                self.vitals = _timeseries.acquire(
                    interval_s=self.vitals_interval_s,
                    retention=self.vitals_retention,
                )
            def _commit_report():
                # the commit-engine postmortem rows: apply-queue stats
                # plus applied-vs-appended height per async channel —
                # a crash bundle must answer "how far did state apply
                # trail the durable chain" without the process
                out = {}
                for cid, ch in list(self.channels.items()):
                    eng = getattr(ch.ledger, "engine", None)
                    if eng is None:
                        continue
                    st = eng.stats()
                    st["appended_height"] = ch.ledger.height
                    st["synced_height"] = ch.ledger.blocks.synced_height
                    out[cid] = st
                return out or None

            from types import SimpleNamespace as _NS

            self.blackbox = _blackbox.acquire(
                out_dir=self.blackbox_dir,
                scheduler=(self.sidecar_server.scheduler
                           if self.sidecar_server is not None else None),
                commit_source=_NS(report=_commit_report),
            )
        if self.device_ledger:
            # device-time launch ledger: per-launch compile/queue/
            # execute/transfer attribution, /launches, dev:* trace
            # lanes, the autopilot's device_queue_ms signal.  Same
            # refcounted sharing story as the recorder above.
            from fabric_tpu.observe import ledger as _ledgermod

            self.launch_ledger = _ledgermod.acquire()
        if self.tx_flow:
            # per-tx flow journal: endorse→sign→submit→order→durable→
            # apply milestone attribution on one monotonic clock,
            # /txflow, the tx_flow_* histograms and the bench
            # extras.tx_flow payload.  Same refcounted sharing story
            # as the launch ledger.
            from fabric_tpu.observe import txflow as _txflowmod

            self.txflow_journal = _txflowmod.acquire()
            if self.slos:
                # commit-path SLOs: a peer that declares SLOs AND runs
                # the journal arms the default commit_e2e:latency /
                # commit_valid:busy pair (unless the operator's spec
                # already names the commit channel) and feeds them one
                # event per COMPLETED flow — client-visible latency to
                # state visibility, not a per-block proxy
                from fabric_tpu.observe import slo as _slo

                engine = _slo.global_engine()
                if not any(o.channel == _slo.COMMIT_CHANNEL
                           for o in engine.objectives):
                    engine.set_objectives(
                        tuple(engine.objectives) + tuple(
                            _slo.parse_slos(_slo.DEFAULT_COMMIT_SLOS)
                        )
                    )
                self.txflow_journal.slo_feed = _slo.commit_feed(engine)
            if self.sign_batcher is not None:
                # the lane has ONE observer slot — chain the journal's
                # sign_wait stage feed behind whatever the SLO arming
                # installed (both contracts: (wait_ms, busy))
                prev = self.sign_batcher.observer
                txobs = _txflowmod.sign_observer()
                if prev is None:
                    self.sign_batcher.observer = txobs
                else:
                    def _sign_chain(wait_ms, busy, _a=prev, _b=txobs):
                        _a(wait_ms, busy)
                        _b(wait_ms, busy)

                    self.sign_batcher.observer = _sign_chain
        self.operations = None
        if operations_port is not None:
            from fabric_tpu.opsserver import HealthRegistry, OperationsServer

            health = HealthRegistry()
            health.register("rpc_server", lambda: None if self.server._server else "down")

            def _ledgers():  # evaluated per check: covers late joins
                for cid, ch in self.channels.items():
                    if ch.height < 0:
                        return f"ledger {cid} unhealthy"
                return None

            health.register("ledgers", _ledgers)

            def _device_lanes():
                # degraded is a WARNING state the fleet must see, but
                # the channel is still committing (CPU fallback) — so
                # /healthz reports it as a failed check with an
                # explanatory reason rather than silence
                for cid, ch in self.channels.items():
                    g = getattr(ch.validator, "device_guard", None)
                    if g is not None and g.degraded:
                        lane = (
                            "sidecar link"
                            if getattr(ch.validator, "link", None)
                            is not None else "device verify lane"
                        )
                        return (
                            f"channel {cid}: {lane} DEGRADED — "
                            "committing via CPU fallback, recovery "
                            "probe armed"
                        )
                return None

            health.register("device_verify_lane", _device_lanes)
            if self.sidecar_server is not None:
                health.register(
                    "sidecar_server", self.sidecar_server.health_check
                )
            self.operations = await OperationsServer(
                port=operations_port, health=health,
                autopilot=self.autopilot_ctl, vitals=self.vitals,
                blackbox=self.blackbox, launches=self.launch_ledger,
                txflow=self.txflow_journal,
            ).start()
        return self

    @property
    def endorse_signer(self):
        """The ESCC signing provider endorsements flow through: the
        batched device lane when ``sign_device`` armed one, else the
        serial signer — same ``sign``/``serialized`` surface either
        way (peer/signlane.BatchedSigner)."""
        return (self.sign_signer if self.sign_signer is not None
                else self.signer)

    async def stop(self):
        if self.sign_batcher is not None:
            self.sign_batcher.stop()
            self.sign_batcher = None
            self.sign_signer = None
        if self.vitals is not None:
            # refcounted: the shared sampler stops only when the last
            # colocated holder releases (see start())
            from fabric_tpu.observe import timeseries as _timeseries

            _timeseries.release()
            self.vitals = None
        if self.blackbox is not None:
            from fabric_tpu.observe import blackbox as _blackbox

            _blackbox.release()
            self.blackbox = None
        if self.launch_ledger is not None:
            from fabric_tpu.observe import ledger as _ledgermod

            _ledgermod.release()
            self.launch_ledger = None
        if self.txflow_journal is not None:
            from fabric_tpu.observe import txflow as _txflowmod

            _txflowmod.release()
            self.txflow_journal = None
        if self.autopilot_ctl is not None:
            # disable BEFORE stopping so /autopilot (and the gauge)
            # never reads a dead control loop as live, and release the
            # process-global handle if it is ours
            self.autopilot_ctl.set_enabled(False)
            self.autopilot_ctl.stop()
            from fabric_tpu.control import global_autopilot, set_global

            if global_autopilot() is self.autopilot_ctl:
                set_global(None)
        # deliver drivers first, awaited: each drops its in-flight
        # pipeline window and stops its worker threads before the
        # ledger under it closes
        deliver = [ch._deliver_task for ch in self.channels.values()
                   if ch._deliver_task is not None]
        for t in deliver:
            t.cancel()
        await asyncio.gather(*deliver, return_exceptions=True)
        for ch in self.channels.values():
            ch.stop()
        if getattr(self, "gossip_service", None) is not None:
            await self.gossip_service.stop()
        if getattr(self, "operations", None) is not None:
            await self.operations.stop()
        if self.sidecar_server is not None:
            await self.sidecar_server.stop()
        await self.server.stop()

    async def _on_endorse(self, req: bytes) -> bytes:
        signed = proposal_pb2.SignedProposal()
        signed.ParseFromString(req)
        prop = protoutil.unmarshal(proposal_pb2.Proposal, signed.proposal_bytes)
        header = protoutil.unmarshal(common_pb2.Header, prop.header)
        ch_hdr = protoutil.unmarshal(common_pb2.ChannelHeader, header.channel_header)
        chan = self.channels.get(ch_hdr.channel_id)
        if chan is None:
            pr = proposal_pb2.ProposalResponse()
            pr.response.status = 404
            pr.response.message = f"not joined to {ch_hdr.channel_id}"
            return pr.SerializeToString()
        endorser = chan.make_endorser(
            self.msp, self.endorse_signer, self.runtime
        )
        loop = asyncio.get_event_loop()
        async with chan.commit_lock.reader():  # stable height; parallel
            # off the event loop: ECDSA verify + chaincode execution
            # must not stall Deliver/Query/commit service latency
            result = await loop.run_in_executor(
                None, endorser.process_proposal, signed
            )
        if result.pvt_cleartext and result.tx_id:
            # endorsement-time pvt data: transient store + distribution
            # to eligible peers (gossip/privdata/distributor.go)
            chan.transient.persist(result.tx_id, result.pvt_cleartext, chan.height)
            gsvc = getattr(self, "gossip_service", None)
            if gsvc is not None:
                t = asyncio.ensure_future(gsvc.push_pvt(
                    ch_hdr.channel_id, result.tx_id,
                    result.pvt_cleartext, chan.height,
                ))
                self._bg.add(t)
                t.add_done_callback(self._bg.discard)
        return result.response.SerializeToString()

    async def _on_deliver_blocks(self, stream):
        req = json.loads(await stream.__anext__())
        chan = self.channels.get(req["channel"])
        if chan is None:
            await stream.error("no such channel")
            return
        num = req.get("start", 0)
        stop = req.get("stop")
        while stop is None or num <= stop:
            if num < chan.height:
                blk = chan.ledger.blocks.get_block(num)
                if blk is None:
                    # snapshot-pruned range: this peer cannot serve it
                    await stream.error(
                        f"block {num} unavailable (pre-snapshot)"
                    )
                    return
                await stream.send(blk.SerializeToString())
                num += 1
            else:
                # single event loop: no await between the height check
                # and grabbing the event, so no wakeup can be missed
                await chan._height_changed.wait()
        await stream.end()

    async def _on_query(self, req: bytes) -> bytes:
        q = json.loads(req)
        chan = self.channels.get(q["channel"])
        if chan is None:
            return json.dumps({"status": 404}).encode()
        vv = chan.ledger.state.get_state(q["ns"], q["key"])
        return json.dumps({
            "status": 200 if vv is not None else 404,
            # empty bytes is a real committed value, distinct from absent
            "value": vv.value.hex() if vv is not None and vv.value is not None else None,
            "version": list(vv.version) if vv is not None else None,
        }).encode()

    async def _on_info(self, req: bytes) -> bytes:
        q = json.loads(req)
        chan = self.channels.get(q["channel"])
        if chan is None:
            return json.dumps({"status": 404}).encode()
        return json.dumps({"status": 200, "height": chan.height}).encode()

    async def _on_snapshot(self, req: bytes) -> bytes:
        """Admin snapshot request: {channel, out_dir} → signable
        metadata (snapshotgrpc/snapshot_service.go analog)."""
        q = json.loads(req)
        chan = self.channels.get(q["channel"])
        if chan is None:
            return json.dumps({"status": 404}).encode()
        try:
            meta = await chan.snapshot(q["out_dir"])
        except Exception as e:
            return json.dumps({"status": 500, "error": str(e)}).encode()
        return json.dumps({"status": 200, "metadata": meta}).encode()

    async def _on_discover(self, req: bytes) -> bytes:
        """Discovery queries: peers / config / endorsers per channel
        (discovery/service.go analog over the node's registry +
        channel bundles)."""
        from fabric_tpu.discovery import DiscoveryService

        q = json.loads(req)
        channel = q.get("channel", "")

        def bundle_for(ch_id):
            ch = self.channels.get(ch_id)
            proc = getattr(ch, "processor", None) if ch else None
            return getattr(proc, "bundle", None)

        def policy_for(ch_id, cc):
            ch = self.channels.get(ch_id)
            if ch is None:
                return None
            info = ch.validator.policies.info(cc)
            return info.policy if info else None

        svc = DiscoveryService(self.registry, bundle_for, policy_for)
        kind = q.get("query", "peers")
        if kind == "peers":
            return json.dumps({"status": 200, "peers": svc.peers(channel)}).encode()
        if kind == "config":
            cfg = svc.config(channel)
            if cfg is None:
                return json.dumps({"status": 404}).encode()
            return json.dumps({"status": 200, "config": cfg}).encode()
        if kind == "endorsers":
            desc = svc.endorsement_descriptor(channel, q["chaincode"])
            if desc is None:
                return json.dumps({"status": 404}).encode()
            return json.dumps({"status": 200, "descriptor": desc}).encode()
        return json.dumps({"status": 400, "error": f"unknown query {kind}"}).encode()
