"""The block validator: TPU-batched equivalent of the reference's
commit-path validation (the north-star component).

Reference shape (SURVEY §3.2): TxValidator v20 runs a goroutine per tx
(core/committer/txvalidator/v20/validator.go:180-265) doing envelope
checks + creator ECDSA verify, dup-txid, then the plugin dispatcher
walks each namespace's validation plugin which verifies every
endorsement signature inside the policy tree
(statebased/validator_keylevel.go:244-260, cauthdsl.go:24-110); the
ledger then runs a serial MVCC loop (validation/validator.go:81-118).

TPU-first re-ordering — compute first, control flow after:

  phase 0 (host)  parse every envelope, collect EVERY signature in the
                  block — creator sigs and endorsement sigs alike — as
                  (digest, r, s, qx, qy) tuples; bulk-load committed
                  versions for every read key.
  phase 1 (TPU)   ONE batched ECDSA verify over all signatures
                  (ops.p256v3), ONE vectorized policy reduction per
                  distinct policy shape (peer.device_block).
  phase 2 (TPU)   ONE MVCC kernel call over the whole block (ops.mvcc)
                  with pre_ok = structural ∧ creator-sig ∧ policy.
  phase 3 (host)  TRANSACTIONS_FILTER codes, update batch, history
                  writes for the ledger.

The plugin SPI (``ValidationPlugin``) keeps the reference's pluggable
boundary (core/handlers/validation/api/validation.go:26-38): the
built-in ``DefaultValidation`` implements phase-1 policy logic; custom
plugins get the same per-namespace dispatch
(plugindispatcher/dispatcher.go:102-221).
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from dataclasses import dataclass, field
from functools import cached_property
from typing import NamedTuple

import numpy as np

from fabric_tpu import faults as _faults
from fabric_tpu import protoutil
from fabric_tpu.crypto import policy as pol
from fabric_tpu.crypto.identity import Identity, sig_to_ints
from fabric_tpu.ledger.rwset import TxRWSet
from fabric_tpu.ledger.statedb import UpdateBatch
from fabric_tpu.ops import mvcc as mvcc_ops
from fabric_tpu.ops import p256v3
from fabric_tpu.protos import common_pb2, configtx_pb2, transaction_pb2

C = transaction_pb2.TxValidationCode

_log = logging.getLogger("fabric_tpu.validator")


class ValidationPlugin:
    """SPI mirroring validation.Plugin (api/validation.go:26-38), but
    batch-shaped: given per-tx endorsement validity bits + identities,
    decide policy satisfaction for every tx at once."""

    def validate_batch(self, ctx: "BlockValidationCtx") -> np.ndarray:
        """→ [T] bool policy-ok for txs this plugin owns."""
        raise NotImplementedError


@dataclass
class NamespaceInfo:
    """Validation info for one namespace (the dispatcher's
    GetInfoForValidate analog, plugindispatcher/dispatcher.go:244-263)."""

    policy: object  # crypto.policy AST
    plugin: str = "default"
    # {coll: {"member_orgs": [...], "required_peer_count": int,
    #  "max_peer_count": int, "btl": int}} — static assemblies;
    # lifecycle-backed providers read the committed definition instead
    collections: dict = field(default_factory=dict)


class TxShape(NamedTuple):
    """What of a block's device programs its tx count does not give:
    read off a committed block by ``BlockValidator.tx_shapes``, and
    what ``BlockValidator.warmup`` pairs with every block size."""

    policy: tuple    # ``device_block.plan_sig`` of the plan its txs are
    #                  judged under, at buckets 0: the policy's structure
    signatures: int  # per tx: the creator's and its endorsements
    dims: tuple      # (reads, writes, range queries) as stage 2 pads them


class PolicyProvider:
    """namespace → NamespaceInfo; backed by the lifecycle cache once
    chaincode lifecycle lands (reference: _lifecycle state)."""

    def __init__(self, infos: dict[str, NamespaceInfo], default: NamespaceInfo | None = None):
        self.infos = dict(infos)
        self.default = default

    def info(self, namespace: str) -> NamespaceInfo | None:
        return self.infos.get(namespace) or self.default

    def collection(self, namespace: str, coll: str) -> dict | None:
        """Collection config for (namespace, coll), or None when
        undefined — undefined collections are treated as
        maximally-private (own org only) by the dissemination layer."""
        info = self.info(namespace)
        if info is None:
            return None
        return getattr(info, "collections", {}).get(coll)


@dataclass(slots=True)
class ParsedTx:
    idx: int
    code: int = C.NOT_VALIDATED
    txid: str = ""
    channel: str = ""
    creator: bytes = b""
    namespaces: tuple = ()
    endorsements: list = field(default_factory=list)  # (endorser_serialized, item)
    creator_item_idx: int = -1
    endo_item_idx: list = field(default_factory=list)
    is_config: bool = False
    rwset_bytes: bytes | None = None  # lazy wire form (native fast path)
    _rwset: object = None
    # creator verified HOST-side (idemix/anonymous creators carry no EC
    # key for the batch lane): creator_item_idx stays -1 and the device
    # path maps the tx to the always-true sentinel signature lane
    host_creator_ok: bool = False

    @property
    def undetermined(self) -> bool:
        return self.code == C.NOT_VALIDATED

    @property
    def rwset(self):
        """Parsed rwset; LAZY when the native fast path supplied flat
        arrays instead (only the rare host-fallback paths ever touch
        this).  A parse failure here is unreachable for txs the native
        parser validated, but fails closed (BAD_RWSET) regardless."""
        if self._rwset is None and self.rwset_bytes is not None:
            try:
                self._rwset = TxRWSet.from_bytes(self.rwset_bytes)
            except Exception:
                self.code = C.BAD_RWSET
                self._rwset = TxRWSet()
        return self._rwset

    @rwset.setter
    def rwset(self, value):
        self._rwset = value


@dataclass
class BlockValidationCtx:
    txs: list
    sig_valid: np.ndarray  # [n_items] bool, global signature batch
    msp_manager: object
    policy_provider: PolicyProvider


@dataclass
class PendingBlock:
    """A launched-but-not-synced block: the handle between
    validate_launch and validate_finish.  ``txids`` feeds the NEXT
    block's extra_txids; the triple is produced by validate_finish."""

    block: object
    txs: list
    items: object
    fetch: object          # p256v3 VerifyHandle
    dpre: object           # _DevicePre or None
    overlay: object = None  # predecessor UpdateBatch (in-flight commit)
    fetch2: object = None   # stage-2 packed fetch, set by _launch_device
    range_phantom: frozenset = frozenset()  # tx idxs failing range re-exec
    fb: object = None       # _FastBlock of a columnar parse, or None
    hd_bytes: bytes = None  # pre-serialized header+data (ledger commit)
    key_launch: object = None  # _KeyLaunch: stage 2 ran key-policy lanes

    @cached_property
    def txids(self) -> set:
        # hot-path consumers (dup checks, pipeline overlay handoff)
        # hit this repeatedly — the txid set is immutable after parse
        return {ptx.txid for ptx in self.txs if ptx.txid}


@dataclass
class _FastBlock:
    """Array-form block state for the fully vectorized (columnar)
    parse: everything the device-path stages need, with NO per-tx
    Python objects on the hot path.  ParsedTx objects still exist for
    the slow lanes and post-commit consumers, but their endorsement
    lists / namespaces are only materialized on demand
    (_materialize_for_host)."""

    native: object            # blockparse.ParsedBlock
    codes: object             # [n] int32 LIVE codes (synced with ptx)
    is_config: object         # [n] bool
    c_ok: object              # [n] bool: eligible columnar endorser txs
    creator_item: object      # [n] int64 global sig-item idx; -1 none
    uid_mat: object           # [n, S] int64 pool row (uid+1); 0 = pad
    endo_idx_mat: object      # [n, S] int32 global item idx; -1 = pad
    ecnt: object              # [n] included endorsement count
    idents: list              # uid → Identity | None
    sers: list                # uid → serialized identity bytes
    has_ec: object            # [n_ids+1] bool
    fallback_idx: list        # envelope indices parsed on the py path
    materialized: bool = False


class _SlowItems:
    """add_slow shim for fallback envelopes inside the columnar parse:
    collects legacy tuples; positions are LOCAL and get rebased past
    the fast block once its size is known."""

    __slots__ = ("slow",)

    def __init__(self):
        self.slow = []

    def add_slow(self, item) -> int:
        self.slow.append(item)
        return len(self.slow) - 1


class _HostVerifyHandle:
    """A completed CPU verify masquerading as a fetch handle: the
    degraded device lane routes blocks here (``ops/p256v3.verify_host``
    under ``faults.shield()``, pure-Python ``ec_ref`` as the last
    ditch).  It deliberately exposes NO ``device_out`` — the fused
    stage-2 program never launches for these blocks, so they take the
    host MVCC path with identical verdicts."""

    __slots__ = ("result",)

    def __init__(self, result: list):
        self.result = result

    def fetch(self) -> list:
        return self.result

    def __call__(self) -> list:
        return self.result


class _GuardedHandle:
    """A device VerifyHandle wrapped with the lane guard's success /
    failure / deadline accounting at the fetch (device sync) boundary.
    ``device_out`` forwards so the fused stage-2 path is unchanged; a
    fetch-side device failure re-verifies THIS block on the CPU
    (correctness first) and counts toward the degraded latch."""

    __slots__ = ("_h", "_guard", "_validator", "_items", "_result")

    def __init__(self, handle, guard, validator, items):
        self._h = handle
        self._guard = guard
        self._validator = validator
        self._items = items
        self._result = None

    @property
    def device_out(self):
        return getattr(self._h, "device_out", None)

    @property
    def n_real(self) -> int:
        return getattr(self._h, "n_real", 0)

    def fetch(self) -> list:
        if self._result is not None:
            return self._result
        t0 = time.perf_counter()
        try:
            out = self._h()
        except Exception as e:
            self._guard.record_failure(e)
            self._guard.count_fallback()  # this block rides the CPU
            _log.warning(
                "device verify sync failed (%s) — re-verifying this "
                "block on the CPU fallback", e,
            )
            self._result = self._validator._host_verify_fallback(
                self._items
            )
            return self._result
        if not self._guard.check_deadline(time.perf_counter() - t0):
            self._guard.record_success()
        self._result = out
        return out

    def __call__(self) -> list:
        return self.fetch()


@dataclass
class _DevicePre:
    """State-independent device-path inputs built at preprocess time
    (prefetch thread): policy groups + static MVCC arrays.  `policies`
    pins the provider the plans were compiled against — validate()
    re-preprocesses if the channel config rotated in between."""

    groups: list          # [(plan, match [E,S,P], endo_idx [E,S], tx_of [E])]
    group_entries: list   # parallel: [(ptx, info), ...] per group
    static: object        # mvcc_ops.StaticBlock
    has_range: bool
    policies: object
    rwp: object = None    # native mvcc_prep flat arrays (fast blocks)
    ns_names: list = None
    ukeys: list = None    # decoded unique key strings (shared w/ fill)
    # True iff fb.codes tracks every later per-tx code assignment (the
    # columnar builder + the launch-time dup check keep it live) — the
    # gate for the vectorized state_fill in _launch_device
    codes_synced: bool = False
    # key-level endorsement: the state-independent half of the block's
    # key-policy lanes (_KeyLanes), built here where the channel has
    # met a key policy or the block writes metadata, else at the first
    # launch that finds metadata in the state; ``key_lanes_of`` builds
    # it (no argument)
    key_lanes: object = None
    key_lanes_of: object = None
    # generic (non-flat) blocks with a metadata-only write: the MVCC
    # inputs ``static`` was made from, and [(tx, composite key)] of
    # those writes — a write to an absent key does not apply, which
    # only the launch knows
    mvcc_txs: list = None
    meta_only: list = None


#: distinct key policies a channel's stage-2 program carries lanes for;
#: a block that needs one more is judged on the host path
MAX_KEY_POLICIES = 16


@dataclass
class _KeyLanes:
    """The state-independent half of one block's key-policy lanes in
    the fused stage-2 program (``device_block.build_stage2``
    ``key_dims``), built on the prefetch thread.  The launch adds the
    one state-dependent vector: each entry's policy id, from the
    committed parameter of its key."""

    packed: np.ndarray    # [Kb, 2] int32: tx (-1 = padding) | namespace group
    ent_key: np.ndarray   # [KE] index into ``pairs``; -1: the (tx,
    #                       namespace) touches no key, the namespace's
    #                       verdict decides
    pairs: list           # (ns, key) the gather is asked about; flat
    #                       blocks: ``static.u_pairs``, by unique-key id
    want: np.ndarray      # [len(pairs)] bool: keys a live tx writes or
    #                       metadata-writes, whose metadata is asked
    n_live: int           # entries of a key-policy group: one a live tx
    group: object         # BatchPlan → (plan, packed_dev, Eb, S)
    meta_writes: bool     # a live tx writes metadata
    inblock_dep: bool     # a live tx touches a key that an earlier live
    #                       tx of the block metadata-writes: the host's
    dev: object = None    # ``packed`` on the device
    groups: list = field(default_factory=list)  # one a known key policy


@dataclass
class _KeyLaunch:
    """What the launch learnt for the block's key-policy lanes: the
    committed metadata of ``_KeyLanes.pairs``' keys (pending applies
    and the in-flight overlay winning), and how many key groups, of how
    many entries each, the program carried."""

    metas: list
    n_groups: int
    n_live: int


class BlockValidator:
    """Validate(block) → (tx_filter, UpdateBatch, history_writes)."""

    def __init__(
        self,
        msp_manager,
        policy_provider: PolicyProvider,
        state_db,
        block_store=None,
        plugins: dict[str, ValidationPlugin] | None = None,
        config_processor=None,
        verify_chunk: int = 0,
        mesh_devices: int = 0,
        host_stage_workers: int = 0,
        recode_device: bool = False,
        device_fail_threshold: int = 0,
        device_retries: int = 2,
        device_recovery_s: float = 30.0,
        verify_deadline_ms: float = 0.0,
        state_resident: bool = False,
        state_resident_mb: int = 64,
        state_resident_range_bits: int = 12,
        channel: str = "",
        mesh_topology=None,
    ):
        self.msp = msp_manager
        self.policies = policy_provider
        self.state = state_db
        self.blocks = block_store
        self.plugins = {"default": DefaultValidation(), **(plugins or {})}
        self.config_processor = config_processor
        self._device_pipeline = None
        # signature-batch microbatching: split each block's verify
        # batch into chunks of this many signatures with
        # double-buffered async dispatch (ops.p256v3), so chunk k's
        # device compute overlaps chunk k+1's host staging.  0 = one
        # monolithic launch (nodeconfig ``verify_chunk``).
        self.verify_chunk = int(verify_chunk)
        # latched by set_verify_chunk / set_host_stage_workers (the
        # autopilot actuators), applied at the next block boundary.
        # The latch is locked: the controller thread sets while the
        # prefetch thread applies, and a bare read-then-clear would
        # drop a step landing between the read and the None store.
        self._knob_lock = threading.Lock()
        self._pending_verify_chunk: int | None = None
        # device-mesh sharding of the production dispatch (nodeconfig
        # ``mesh_devices`` + the pod-scale topology knobs): batch
        # lanes of the verify kernel AND the fused stage-2 program
        # shard under the declarative partition rules
        # (fabric_tpu/parallel/mesh.py) over the resolved mesh —
        # mesh_devices 0 = off, -1 = all local, n = first n (the
        # 1-process special case); a ``mesh_topology``
        # (parallel.topology.MeshTopology) layers ``mesh_shape`` grids
        # and jax.distributed process-spanning fabrics on top.
        # Bit-equal to single-device (tests/test_multidevice.py,
        # tests/test_partition_rules.py); a 1-wide data axis degrades
        # to None so CPU-only hosts pay nothing.
        self.mesh_devices = int(mesh_devices)
        if mesh_topology is not None and mesh_topology.configured:
            self.mesh = mesh_topology.resolve()
        elif self.mesh_devices:
            from fabric_tpu.parallel.mesh import resolve_mesh

            self.mesh = resolve_mesh(self.mesh_devices)
        else:
            self.mesh = None
        # host staging pool (nodeconfig ``host_stage_workers``): the
        # per-block HOST pipeline — envelope parse fan-out in
        # preprocess_many, the per-signature admission + batch
        # inversion + residue dgemm in prepare_cols (sharded along the
        # lane axis at bucket boundaries), and device-path
        # preprocessing overlapping the next block's parse — shards
        # over a persistent worker pool.  0 = off (serial staging,
        # CPU-only hosts pay nothing), -1 = one worker per core.
        # Bit-equal to serial staging (every staged lane is
        # lane-independent; pinned the way sharded ≡ single-device is).
        self.host_stage_workers = int(host_stage_workers)
        if self.host_stage_workers:
            from fabric_tpu.parallel.hostpool import resolve_host_pool

            # threads: the staging is SHARED-MEMORY by design (workers
            # write row slabs into preallocated arrays in place, and
            # the hot loops release the GIL)
            self.host_pool = resolve_host_pool(self.host_stage_workers)
        else:
            self.host_pool = None
        # window recoding location (nodeconfig ``recode_device``):
        # ship u1/u2 as 16-bit limbs and derive the 4-bit window digits
        # in the stage-1 kernel — the packed H2D frame shrinks (window
        # planes 4×), so pooled shards and mesh shards upload less per
        # worker/chip.  Default False (host recode — the C ec_prepare
        # path computes windows for free, and CPU-only hosts see no
        # H2D bottleneck to shrink).  Bit-equal either way.
        self.recode_device = bool(recode_device)
        # device-lane degradation guard (peer/degrade.py, nodeconfig
        # device_fail_threshold / device_retries / device_recovery_s /
        # verify_deadline_ms): bounded-retry device launches that latch
        # a degraded CPU mode (ops/p256v3.verify_host + the host MVCC
        # path — correctness identical, the channel stays live) after
        # consecutive failures, with a periodic recovery probe.
        # threshold 0 = guard off entirely (today's raise-through
        # behavior; tier-1 and CPU-only hosts unchanged).
        self.channel = channel
        if device_fail_threshold > 0:
            from fabric_tpu.peer.degrade import DeviceLaneGuard

            self.device_guard = DeviceLaneGuard(
                retries=device_retries,
                fail_threshold=device_fail_threshold,
                recovery_s=device_recovery_s,
                deadline_ms=verify_deadline_ms,
                channel=channel,
            )
        else:
            self.device_guard = None
        # device-resident MVCC state (fabric_tpu/state, nodeconfig
        # ``state_resident`` / ``state_resident_mb`` /
        # ``state_resident_range_bits``): an LRU key-range residency
        # cache keeps committed versions in DEVICE memory across
        # blocks — the fused stage-2 program reads them there and the
        # per-block host state_fill shrinks to the miss/overlay set,
        # with each committed write-set applied as a delta scatter at
        # the commit boundary (CommitPipeline → resident_commit).
        # Default OFF: the host state_fill path — which also stays as
        # the bit-equal fallback oracle for misses, range queries,
        # eviction pressure and device failures — is the exact
        # existing path.
        if state_resident:
            from fabric_tpu.state import resolve_residency

            self.resident = resolve_residency(
                True, state_resident_mb, state_resident_range_bits,
                mesh=self.mesh, channel=channel,
            )
        else:
            self.resident = None
        # key-level (state-based) endorsement on the device path: the
        # distinct key policies this channel has met, in the order met.
        # Each is a policy group of the fused stage-2 program like a
        # namespace's; a key's committed parameter names one by its
        # place here (its ``pid``).  Grown at a launch (caller thread),
        # read by the prefetch thread: append-only.
        self._key_plans: list = []   # pid → BatchPlan
        self._key_pid_of: dict = {}  # parameter bytes → pid; -2: no parse
        # a key's stored metadata (``rwset.encode_metadata``) → the pid
        # of its VALIDATION_PARAMETER entry, -1 where it has none
        self._md_pid: dict = {None: -1}
        # a metadata write's entries as they lie in the block (a
        # keyless KVMetadataWrite) → the metadata the state stores
        self._md_enc: dict = {}
        # optional phase accumulator (seconds per phase, summed across
        # blocks) — the bench publishes it as the per-phase breakdown
        # artifact; None = no instrumentation overhead
        self.timings: dict | None = None
        # the same stages feed production telemetry unconditionally,
        # so a live peer's /metrics and BENCH_breakdown.json agree
        from fabric_tpu.ops_metrics import global_registry

        self._stage_hist = global_registry().histogram(
            "validator_stage_seconds",
            "per-block validator stage time (s), bench-breakdown stages",
        )
        # the span tracer mirrors the same stages onto the per-block
        # timeline: _t records each stage under whatever span the
        # calling thread is attached to (the pipeline's prefetch/
        # launch/finish spans), so no span handles thread through here
        from fabric_tpu.observe import global_tracer

        self._tracer = global_tracer()
        self._key_plans_gauge = global_registry().gauge(
            "key_policy_plans",
            "distinct key-level endorsement policies the channel's "
            "stage-2 program carries lanes for")
        self._key_host_ctr = global_registry().counter(
            "key_policy_host_blocks_total",
            "blocks under key-level endorsement judged on the host path, "
            "by reason")

    def close(self) -> None:
        """Release validator-owned resources — the host staging pool's
        worker threads outlive GC pins (bench result lists, channel
        registries), so teardown paths must call this (PeerChannel.stop
        does).  Idempotent."""
        pool, self.host_pool = self.host_pool, None
        if pool is not None:
            pool.shutdown()

    # -- runtime re-knobbing (autopilot actuator) --------------------------

    def set_verify_chunk(self, n: int) -> None:
        """Request a new signature-verify chunk size, applied at the
        next block boundary (the top of ``preprocess`` /
        ``preprocess_many``, where this block's verify dispatch has
        not started) — a block's chunked launch always runs under one
        chunk size, never a mid-window mix.  0 = monolithic."""
        with self._knob_lock:
            self._pending_verify_chunk = max(0, int(n))

    def set_host_stage_workers(self, n: int) -> None:
        """Request a new host staging pool size (the autopilot's
        ``host_stage_workers`` actuator), applied at the next block
        boundary: ``n >= 2`` resizes the live pool (HostStagePool.
        set_workers — drain-and-rebuild at a task boundary) or builds
        one where none existed; ``n < 2`` closes the pool back to
        serial staging.  Bit-equal either way — pooled ≡ serial is
        pinned, so the knob only moves time."""
        with self._knob_lock:
            self._pending_host_workers = max(0, int(n))

    def _apply_pending_knobs(self) -> None:
        with self._knob_lock:
            n, self._pending_verify_chunk = (
                self._pending_verify_chunk, None,
            )
            w = getattr(self, "_pending_host_workers", None)
            self._pending_host_workers = None
        if n is not None:
            self.verify_chunk = n
        if w is not None:
            if w < 2:
                pool, self.host_pool = self.host_pool, None
                self.host_stage_workers = 0
                if pool is not None:
                    pool.shutdown()
            elif self.host_pool is not None:
                from fabric_tpu.parallel.hostpool import clamp_workers

                self.host_pool.set_workers(w)
                # report the clamped TARGET (what the pool will be
                # after its idle-boundary swap) — pool.workers still
                # reads the pre-swap count here, and nothing would
                # ever write the attribute back after the swap
                self.host_stage_workers = clamp_workers(w)
            else:
                from fabric_tpu.parallel.hostpool import resolve_host_pool

                self.host_pool = resolve_host_pool(w)
                self.host_stage_workers = (
                    self.host_pool.workers
                    if self.host_pool is not None else 0
                )

    def _t(self, key: str, t0: float, handle=None) -> float:
        """``handle``: the verify launch a ``sig_prepare_launch`` stage
        made — on a traced path its span says the launch's real
        ``lanes`` and the ``bucket`` they were padded to."""
        t1 = time.perf_counter()
        if self.timings is not None:
            self.timings[key] = self.timings.get(key, 0.0) + (t1 - t0)
        self._stage_hist.observe(t1 - t0, stage=key)
        out = None
        if handle is not None and self._tracer.current() is not None:
            out = getattr(handle, "device_out", None)
        if out is None:
            self._tracer.add(key, t0, t1)  # no-op off the traced paths
        else:
            self._tracer.add(key, t0, t1, lanes=int(handle.n_real),
                             bucket=int(out.shape[0]))
        return t1

    def tx_shapes(self, block) -> set:
        """The :class:`TxShape` of each policy group of a committed
        block, as this validator would launch it now (its namespaces'
        policies resolved as they stand, the rwset dims as
        ``_device_preprocess`` pads them, signatures per tx as the
        block's signature batch over its envelopes).  Empty for a block
        the fused device path would not take (a configuration block, a
        custom plugin).  The block's own parse and ``device_pre``
        stages and nothing after them: no launch, and no state read
        but the policy provider's."""
        from fabric_tpu.peer.device_block import plan_sig

        txs, items, rwp, fb = self._parse(block)
        dpre = self._device_preprocess(txs, rwp, fb)
        if dpre is None or not txs:
            return set()
        per_tx = -(-len(items) // len(txs))
        return {TxShape(plan_sig(plan, 0, 0), per_tx,
                        tuple(dpre.static.dims))
                for plan, _packed, _e, _s in dpre.groups}

    def warmup(self, max_tx: int, signatures_per_tx: int, seen) -> dict:
        """Lower and compile (or load from the persistent cache) the
        device programs a channel's blocks key, before its first block.
        ``max_tx``: the most txs the channel's orderer puts in a block
        (``BatchSize.max_message_count``); ``signatures_per_tx``: the
        most signatures one tx carries (its creator's and one
        endorsement from each principal of the widest policy);
        ``seen``: the :class:`TxShape` of the blocks this channel has
        committed lately (``tx_shapes``; ``peer/node.py`` reads them
        off the block store when a channel opens), empty on a fresh
        join.

        Walks ``utils/batching.channel_shapes`` through the real
        launch path (``_verify_launch_guarded``, the fused stage 2 of
        ``DeviceBlockPipeline.run``) on inert inputs:

        * verify: EVERY bucket up to the one ``max_tx`` txs of
          ``signatures_per_tx`` signatures fill: the programs that
          cost seconds to tens of seconds each.  Afterwards no block
          of the channel lowers a verify program;
        * stage 2: for each shape of ``seen``, the programs of every
          block of 1..``max_tx`` txs of that shape.  Afterwards no
          block of 1..``max_tx`` txs that looks like a block of
          ``seen`` (one namespace a tx, that policy's structure, that
          many signatures a tx, those rwset dims) lowers a program.  A
          block that looks like none the channel has committed lately
          (on a fresh join: every first one) lowers its own stage-2
          program on first sight, which costs about a second and not
          the verify program's tens.

        Not walked: the chunked launch's tail shapes (``verify_chunk``)
        and the resident-state variant of stage 2 (both off by
        default), and the sidecar's programs (a ``SidecarValidator``
        owns no device lane).  → what was warmed, and the seconds
        stage 2 took of the whole."""
        from fabric_tpu.crypto import ec_ref
        from fabric_tpu.ops.p256v3 import SigCollector
        from fabric_tpu.utils.batching import block_shapes, channel_shapes

        t_start = time.perf_counter()
        stage2: dict = {}  # verify lanes → {(shapes, policy structure)}
        for shape in seen:
            for sh in channel_shapes(max_tx, shape.signatures, *shape.dims):
                stage2.setdefault(sh.verify, set()).add((sh, shape.policy))
        top = max([block_shapes(
            signatures=max_tx * signatures_per_tx).verify, *stage2])
        k = ec_ref.SigningKey.generate()
        e = ec_ref.digest_int(b"warmup")
        item = (e, *k.sign_digest(e), *k.public)
        done = {"verify": 0, "stage2": 0, "stage2_seconds": 0.0}
        lanes = 0
        while lanes < top:
            lanes = block_shapes(signatures=lanes + 1).verify
            batch = SigCollector()
            for _ in range(lanes):
                batch.add_slow(item)
            handle = self._verify_launch_guarded(batch)
            done["verify"] += 1
            if getattr(handle, "device_out", None) is not None:
                t0 = time.perf_counter()
                for sh, policy in sorted(stage2.get(lanes, ())):
                    self._warm_stage2(handle, sh, policy)
                    done["stage2"] += 1
                done["stage2_seconds"] += time.perf_counter() - t0
            if not all(handle.fetch()):
                raise RuntimeError(
                    f"warm-up: the {lanes}-lane verify program rejected "
                    "a valid signature")
        done["stage2_seconds"] = round(done["stage2_seconds"], 3)
        done["seconds"] = round(time.perf_counter() - t_start, 3)
        return done

    def _warm_stage2(self, handle, sh, policy) -> None:
        """One fused stage-2 launch of shapes ``sh`` under a plan of
        structure ``policy`` (who its principals are is not in the
        program's key, only how many) over ``handle``'s device output,
        every lane inert (no tx structural, no entry, no key)."""
        import jax.numpy as jnp

        from fabric_tpu.peer.device_block import DeviceBlockPipeline

        if self._device_pipeline is None:
            self._device_pipeline = DeviceBlockPipeline()
        T, E, S, (R, W, Q) = sh.txs, sh.entries, sh.slots, sh.dims
        P = policy.n_principals
        plan = pol.BatchPlan(
            principals=[None] * P, leaf_principal=list(policy.leaf_principal),
            leaf_rank=list(policy.leaf_rank),
            gates=[(n, list(c)) for n, c in policy.gates])
        launch_vec = np.zeros((T, 3), np.int32)
        launch_vec[:, 0] = -1
        gp = np.zeros((E, S * P + S + 1), np.int32)
        gp[:, S * P:] = -1
        static = jnp.asarray(np.full((T, R + W + 2 * Q), -1, np.int32))
        self._device_pipeline.run(
            handle, launch_vec, [(plan, self._put_group(gp), E, S)], static,
            (R, W, Q), T, mesh=self.mesh, n_txs=0,
        )()

    # -- device lane: guarded dispatch + CPU fallback ----------------------

    def _verify_launch_guarded(self, items):
        """One block's verify dispatch through the device-lane guard
        (bounded retry → degraded CPU fallback); the raw launch when no
        guard is configured — the zero-overhead default."""

        def launch():
            return p256v3.verify_launch(
                items, chunk=self.verify_chunk or None, mesh=self.mesh,
                pool=self.host_pool, recode_device=self.recode_device,
            )

        if self.device_guard is None:
            return launch()
        out = self.device_guard.run_launch(
            launch, lambda: self._host_verify_handle(items)
        )
        if isinstance(out, _HostVerifyHandle):
            return out
        return _GuardedHandle(out, self.device_guard, self, items)

    def _verify_launch_many_guarded(self, itemsets, pool=None):
        """Coalesced multi-block dispatch through the guard: one
        device attempt covers the group; a degraded lane verifies each
        block's batch on the CPU instead (every block counted on
        ``fallback_blocks_total``)."""

        def launch():
            return p256v3.verify_launch_many(
                itemsets, chunk=self.verify_chunk or None,
                mesh=self.mesh, pool=pool,
                recode_device=self.recode_device,
            )

        if self.device_guard is None:
            return launch()
        out = self.device_guard.run_launch(
            launch,
            lambda: [self._host_verify_handle(it) for it in itemsets],
            fallback_count=len(itemsets),
        )
        return [
            h if isinstance(h, _HostVerifyHandle)
            else _GuardedHandle(h, self.device_guard, self, it)
            for h, it in zip(out, itemsets)
        ]

    def _host_verify_handle(self, items) -> "_HostVerifyHandle":
        """The degraded route for one block's signature batch: a
        synchronous CPU verify with no async device handle, no fused
        stage-2, no mesh/chunk/pool machinery."""
        return _HostVerifyHandle(self._host_verify_fallback(items))

    def _host_verify_fallback(self, items) -> list:
        """items → list[bool] on the CPU lane.  ``ops/p256v3.verify_host``
        under ``faults.shield()`` first (the plain synchronous path);
        if even that lane is dead, the pure-Python ``ec_ref`` oracle
        verifies signature by signature — slow, dependency-free, and
        bit-identical in accept set (low-S included)."""
        tuples = items.tuples() if hasattr(items, "tuples") else list(items)
        if not tuples:
            return []
        try:
            with _faults.shield():
                return [bool(v) for v in p256v3.verify_host(tuples)]
        except Exception as e:
            _log.warning(
                "CPU verify_host lane failed too (%s) — falling back to "
                "the pure-Python reference verifier for %d signatures",
                e, len(tuples),
            )
            from fabric_tpu.crypto import ec_ref

            return [
                ec_ref.verify_digest((qx, qy), e_, r, s)
                for (e_, r, s, qx, qy) in tuples
            ]

    # -- phase 0: parse + collect -----------------------------------------

    def _parse(self, block: common_pb2.Block) -> tuple[list, list]:
        """Parse every envelope + collect the signature batch.

        Fast path: the native C++ pre-parser (fabric_tpu.native) walks
        the whole block's wire format, hashes every message and splits
        every DER signature in ONE call; envelopes it cannot fully
        handle (config txs, malformed bytes) fall back to the Python
        path below, envelope by envelope — identical verdicts either
        way (tests/test_native_parse.py pins the equivalence)."""
        from fabric_tpu.ops.p256v3 import SigCollector

        txs: list[ParsedTx] = []
        items = SigCollector()  # column-form signature batch
        seen_txids: dict[str, int] = {}
        native = None
        # config/genesis envelopes come back ok=0 from the native walk
        # and take the Python path per envelope — no number gate needed
        if len(block.data.data) >= 16:
            try:
                from fabric_tpu.native import blockparse as nbp

                native = nbp.parse_envelopes(list(block.data.data))
            except Exception:
                native = None
        if native is not None:
            out = self._parse_columnar(block, native)
            if out is not None:
                return out
        fast_ctx = self._fast_ctx(native) if native is not None else None
        for i, env_bytes in enumerate(block.data.data):
            if fast_ctx is not None and fast_ctx["ok"][i]:
                if self._parse_fast(i, fast_ctx, txs, items, seen_txids):
                    continue
                # fast path bowed out (e.g. an idemix creator whose
                # proof is not a DER signature): python path below
            self._parse_one_py(i, env_bytes, block, txs, items, seen_txids)

        # rwsets of native-fast endorser txs: ONE C call parses, interns
        # keys, and emits flat arrays; txs it cannot cover (ranges,
        # hashed collections, malformed, non-UTF8) take the Python
        # parser tx by tx
        rwp = None
        if native is not None:
            use = np.zeros(len(txs), bool)
            for ptx in txs:
                if (
                    native.ok[ptx.idx] and ptx.undetermined
                    and not ptx.is_config
                ):
                    use[ptx.idx] = True
            if use.any():
                try:
                    from fabric_tpu.native import mvccprep_py

                    rwp = mvccprep_py.prep(native, use)
                except Exception:
                    rwp = None
                ns_names = rwp.ns_names() if rwp is not None else None
                for ptx in txs:
                    i = ptx.idx
                    if not use[i]:
                        continue
                    if rwp is not None and rwp.status[i] == 0:
                        s = int(rwp.tx_ns_start[i])
                        c = int(rwp.tx_ns_count[i])
                        ptx.namespaces = tuple(sorted(
                            ns_names[j] for j in rwp.ns_ids_flat[s:s + c]
                        ))
                        ptx.rwset_bytes = (
                            native.span(native.results_span, i) or b""
                        )
                    else:
                        self._py_rwset(ptx, native)
        return txs, items, rwp, None

    def _parse_columnar(self, block, native):
        """Fully vectorized parse of a native-pre-parsed block: the
        per-tx Python loop of ``_parse`` becomes numpy over the C++
        parser's arrays — txid binding is a [k,64] hex compare, in-block
        dup detection a row-unique, the signature batch a set of column
        gathers, and the policy-group inputs scatter into [n,S]
        matrices.  Identities resolve ONCE per distinct cert.

        Envelopes the columnar lane cannot carry (config txs, idemix
        creators, malformed bytes) run through ``_parse_one_py`` in
        block order, sharing the dup registry.  Returns None when no
        envelope qualifies (the legacy loop takes over)."""
        from fabric_tpu.ops.p256v3 import ColumnarSigBatch
        from fabric_tpu.utils.batching import block_shapes

        n = len(block.data.data)
        blob = native.blob
        n_ids = native.n_ids
        NOTV = int(C.NOT_VALIDATED)

        # -- interned identity resolution (once per distinct cert) ----
        idents: list = [None] * n_ids
        sers: list = [None] * n_ids
        known = np.zeros(n_ids + 1, bool)
        ivalid = np.zeros(n_ids + 1, bool)
        has_ec = np.zeros(n_ids + 1, bool)
        idemix_like = np.zeros(n_ids + 1, bool)
        span = native.ident_span
        for u in range(n_ids):
            o, ln = int(span[u, 0]), int(span[u, 1])
            ser = blob[o:o + ln]
            sers[u] = ser
            try:
                ident = self.msp.deserialize_identity(ser)
            except Exception as e:
                _log.debug("undeserializable identity in block: %s", e)
                continue
            idents[u] = ident
            known[u] = True
            ivalid[u] = bool(ident.is_valid)
            try:
                ident.public_numbers
                ident.rns_pub
                has_ec[u] = True
            except Exception:
                idemix_like[u] = ivalid[u] and not hasattr(ident, "cert")

        ok = native.ok.astype(bool)
        cu = native.creator_uid.astype(np.int64)
        cu_valid = cu >= 0
        cuc = np.where(cu_valid, cu, n_ids)
        fallback = ~ok | (cu_valid & idemix_like[cuc])
        columnar = ~fallback
        if not columnar.any():
            return None

        # -- txid binding: tx_id must equal hex(sha256(nonce‖creator))
        t_off = native.txid_span[:, 0]
        t_len = native.txid_span[:, 1]
        blob_u8 = np.frombuffer(blob, np.uint8)
        cand = columnar & (t_off >= 0) & (t_len == 64)
        bind_ok = np.zeros(n, bool)
        crows = np.flatnonzero(cand)
        if len(crows):
            txh = blob_u8[t_off[crows][:, None] + np.arange(64)[None, :]]
            dg = native.txid_digest[crows]
            hi, lo = dg >> 4, dg & 15
            hx = np.empty((len(crows), 64), np.uint8)
            hx[:, 0::2] = np.where(hi < 10, hi + 48, hi + 87)
            hx[:, 1::2] = np.where(lo < 10, lo + 48, lo + 87)
            bind_ok[crows] = (txh == hx).all(axis=1)

        # decoded txid strings (ledger index + dup-vs-ledger checks)
        txid_strs = [""] * n
        off_l, len_l = t_off.tolist(), t_len.tolist()
        for i in np.flatnonzero(columnar & (t_off >= 0)).tolist():
            txid_strs[i] = blob[off_l[i]:off_l[i] + len_l[i]].decode(
                "utf-8", "replace"
            )

        # -- duplicate txids + fallback envelopes (block order) -------
        codes = np.full(n, NOTV, np.int32)
        dup = np.zeros(n, bool)
        fb_txs: dict[int, ParsedTx] = {}
        shim = _SlowItems()
        fallback_idx = np.flatnonzero(fallback).tolist()
        if not fallback_idx:
            brows = np.flatnonzero(bind_ok)
            if len(brows) > 1:
                keys = blob_u8[t_off[brows][:, None] + np.arange(64)[None, :]]
                _, first = np.unique(keys, axis=0, return_index=True)
                d = np.ones(len(brows), bool)
                d[first] = False
                dup[brows] = d
        else:
            # mixed block: interleave fallback parsing with columnar
            # txid claims in envelope order so dup semantics match the
            # serial path exactly
            seen: dict[str, int] = {}
            fall_l = fallback.tolist()
            bind_l = bind_ok.tolist()
            data = block.data.data
            for i in range(n):
                if fall_l[i]:
                    sub: list = []
                    self._parse_one_py(i, data[i], block, sub, shim, seen)
                    fb_txs[i] = sub[0]
                elif bind_l[i]:
                    t = txid_strs[i]
                    if t in seen:
                        dup[i] = True
                    else:
                        seen[t] = i

        codes[columnar & ~bind_ok] = int(C.BAD_PROPOSAL_TXID)
        codes[dup] = int(C.DUPLICATE_TXID)
        live = columnar & bind_ok & ~dup
        csig = native.creator_sig_ok.astype(bool)
        c_ok = live & cu_valid & known[cuc] & ivalid[cuc] & has_ec[cuc] & csig
        codes[live & ~c_ok] = int(C.BAD_CREATOR_SIGNATURE)

        # -- signature batch: column gathers, zero per-item Python ----
        m = int(native.endo_count[:n].sum())
        tx_of_e = np.repeat(np.arange(n), native.endo_count[:n])
        e_ok_m = native.e_ok[:m].astype(bool) & (native.e_dup[:m] == 0)
        eu = native.e_uid[:m].astype(np.int64)
        eu_valid = eu >= 0
        euc = np.where(eu_valid, eu, n_ids)
        mask_e = c_ok[tx_of_e] & e_ok_m & eu_valid & known[euc] & has_ec[euc]

        c_rows = np.flatnonzero(c_ok)
        nc = len(c_rows)
        creator_item = np.full(n, -1, np.int64)
        creator_item[c_rows] = np.arange(nc)
        e_rows = np.flatnonzero(mask_e)
        ne = len(e_rows)
        e_item = np.full(m, -1, np.int64)
        e_item[e_rows] = nc + np.arange(ne)

        from fabric_tpu.ops import rns

        qx_pool = np.zeros((n_ids + 1, 2 * rns.N_CH), np.int32)
        qy_pool = np.zeros((n_ids + 1, 2 * rns.N_CH), np.int32)
        for u in range(n_ids):
            if has_ec[u]:
                a, b = idents[u].rns_pub
                qx_pool[u], qy_pool[u] = a, b

        digest_b = np.concatenate(
            [native.payload_digest[c_rows], native.e_digest[:m][e_rows]]
        )
        r_b = np.concatenate(
            [native.creator_r[c_rows], native.e_r[:m][e_rows]]
        )
        s_b = np.concatenate(
            [native.creator_s[c_rows], native.e_s[:m][e_rows]]
        )
        uid_items = np.concatenate([cu[c_rows], eu[e_rows]])
        items = ColumnarSigBatch(
            digest_b, r_b, s_b, qx_pool[uid_items], qy_pool[uid_items],
            np.ones(nc + ne, bool), ident_of=uid_items, idents=idents,
        )

        # -- per-tx endorsement matrices (policy-group inputs) --------
        inc = mask_e.astype(np.int64)
        csum = np.cumsum(inc) if m else np.zeros(0, np.int64)
        csum0 = np.concatenate([[0], csum])
        start = native.endo_start[:n].astype(np.int64)
        ecnt = (np.bincount(tx_of_e[e_rows], minlength=n)
                if ne else np.zeros(n, np.int64))
        S = block_shapes(endorsements=int(ecnt.max()) if ne else 0).slots
        uid_mat = np.zeros((n, S), np.int64)
        endo_idx_mat = np.full((n, S), -1, np.int32)
        if ne:
            ordinal = (csum - 1) - csum0[start][tx_of_e]
            rr = tx_of_e[e_rows]
            cc = ordinal[e_rows]
            uid_mat[rr, cc] = eu[e_rows] + 1
            endo_idx_mat[rr, cc] = e_item[e_rows]

        # -- rwsets: one C call over the eligible txs -----------------
        rwp = None
        if c_ok.any():
            try:
                from fabric_tpu.native import mvccprep_py

                rwp = mvccprep_py.prep(native, c_ok)
            except Exception:
                rwp = None

        # -- ParsedTx shells (slow-lane fields left lazy) -------------
        code_l = codes.tolist()
        txs = [
            fb_txs[i] if i in fb_txs else
            ParsedTx(idx=i, code=code_l[i], txid=txid_strs[i])
            for i in range(n)
        ]
        ci_l = creator_item.tolist()
        cu_l = cu.tolist()
        if rwp is not None:
            st = rwp.status
            res_off = native.results_span[:, 0].tolist()
            res_len = native.results_span[:, 1].tolist()
            for i in c_rows.tolist():
                ptx = txs[i]
                ptx.creator = sers[cu_l[i]]
                ptx.creator_item_idx = ci_l[i]
                if st[i] == 0:
                    o = res_off[i]
                    ptx.rwset_bytes = blob[o:o + res_len[i]] if o >= 0 else b""
                else:
                    self._py_rwset(ptx, native)
        else:
            for i in c_rows.tolist():
                ptx = txs[i]
                ptx.creator = sers[cu_l[i]]
                ptx.creator_item_idx = ci_l[i]
                self._py_rwset(ptx, native)

        # fallback ptxs: rebase their slow item indices past the fast
        # block, then sync their codes into the live array
        if fallback_idx:
            base = items.n_fast
            items.slow = shim.slow
            is_cfg = np.zeros(n, bool)
            for i, ptx in fb_txs.items():
                if ptx.creator_item_idx >= 0:
                    ptx.creator_item_idx += base
                if ptx.endo_item_idx:
                    ptx.endo_item_idx = [k + base for k in ptx.endo_item_idx]
                codes[i] = int(ptx.code)
                is_cfg[i] = ptx.is_config
        else:
            is_cfg = np.zeros(n, bool)

        fb = _FastBlock(
            native=native, codes=codes, is_config=is_cfg, c_ok=c_ok,
            creator_item=creator_item, uid_mat=uid_mat,
            endo_idx_mat=endo_idx_mat, ecnt=ecnt, idents=idents,
            sers=sers, has_ec=has_ec, fallback_idx=fallback_idx,
        )
        return txs, items, rwp, fb

    def _materialize_for_host(self, txs, fb) -> None:
        """Fill the per-tx endorsement lists / namespaces the columnar
        parse left lazy — required before any host-dispatch validation
        path touches ParsedTx objects of a columnar block."""
        if fb is None or fb.materialized:
            return
        uid_mat, em = fb.uid_mat, fb.endo_idx_mat
        for i in np.flatnonzero(fb.c_ok).tolist():
            ptx = txs[i]
            k = int(fb.ecnt[i])
            if k and not ptx.endorsements:
                ptx.endo_item_idx = em[i, :k].tolist()
                ptx.endorsements = [
                    (fb.sers[int(uid_mat[i, s]) - 1],
                     fb.idents[int(uid_mat[i, s]) - 1])
                    for s in range(k)
                ]
            if not ptx.namespaces and ptx.rwset is not None:
                ptx.namespaces = tuple(sorted(ptx.rwset.ns))
        fb.materialized = True

    def _parse_one_py(self, i, env_bytes, block, txs, items, seen_txids):
        """Parse ONE envelope on the Python path (config txs, idemix
        creators, malformed bytes, non-native blocks) — appends a
        ParsedTx and its signature items.  Shared by the legacy loop
        and the columnar fast path's fallback lane; ``seen_txids`` is
        the block-order dup registry both lanes feed."""
        ptx = ParsedTx(idx=i)
        txs.append(ptx)
        if not env_bytes:
            ptx.code = C.NIL_ENVELOPE
            return
        try:
            env = protoutil.unmarshal(common_pb2.Envelope, env_bytes)
            payload = protoutil.unmarshal(common_pb2.Payload, env.payload)
            ch = protoutil.unmarshal(
                common_pb2.ChannelHeader, payload.header.channel_header
            )
            sh = protoutil.unmarshal(
                common_pb2.SignatureHeader, payload.header.signature_header
            )
        except Exception:
            ptx.code = C.BAD_PAYLOAD
            return
        ptx.txid, ptx.channel, ptx.creator = ch.tx_id, ch.channel_id, sh.creator

        if ch.type == common_pb2.HeaderType.CONFIG:
            # config txs go to the config machinery, not the
            # endorsement pipeline (v20/validator.go:397-419): the
            # creator signature still rides the block's signature
            # batch; structure + policy checks happen in
            # _validate_config after phase 1a.
            ptx.is_config = True
            if block.header.number == 0:
                return  # genesis: trust anchor, no creator check
            try:
                ident = self.msp.deserialize_identity(sh.creator)
                if not ident.is_valid:
                    raise ValueError("invalid creator identity")
                item = _sig_item(ident, env.payload, env.signature)
            except Exception:
                ptx.code = C.BAD_CREATOR_SIGNATURE
                return
            ptx.creator_item_idx = items.add_slow(item)
            return
        if ch.type != common_pb2.HeaderType.ENDORSER_TRANSACTION:
            ptx.code = C.UNKNOWN_TX_TYPE
            return
        # txid binding: tx_id must equal sha256(nonce ‖ creator) —
        # prevents txid squatting / DUPLICATE_TXID poisoning
        # (protoutil/proputils.go:362 CheckTxID)
        if not ch.tx_id or ch.tx_id != protoutil.compute_tx_id(
            sh.nonce, sh.creator
        ):
            ptx.code = C.BAD_PROPOSAL_TXID
            return
        # dup txid in-block (v20/validator.go:460-481); the
        # vs-ledger check happens in validate() — preprocess() must
        # be runnable BEFORE the previous block commits (pipeline)
        if ch.tx_id in seen_txids:
            ptx.code = C.DUPLICATE_TXID
            return
        seen_txids[ch.tx_id] = i

        # creator: deserializable, valid cert, sig over payload
        try:
            ident = self.msp.deserialize_identity(sh.creator)
        except Exception:
            ptx.code = C.BAD_CREATOR_SIGNATURE
            return
        if not ident.is_valid:
            ptx.code = C.BAD_CREATOR_SIGNATURE
            return
        item = None
        try:
            item = _sig_item(ident, env.payload, env.signature)
        except Exception:
            # identities without an EC public key (idemix anonymous
            # creators, msp/idemix.go) verify HOST-side: each
            # signature is a zero-knowledge presentation proof the
            # batch kernel has no lane for
            host_ok = False
            if ident.is_valid and not hasattr(ident, "cert"):
                try:
                    host_ok = ident.verify(env.payload, env.signature)
                except Exception:
                    host_ok = False
            if not host_ok:
                ptx.code = C.BAD_CREATOR_SIGNATURE
                return
            ptx.host_creator_ok = True
        if item is not None:
            ptx.creator_item_idx = items.add_slow(item)

        # endorsements + rwset
        try:
            _, _, cap, prp, cca = protoutil.extract_action(
                env, parsed=(payload, ch, sh)
            )
            ptx.rwset = TxRWSet.from_bytes(cca.results)
            ptx.namespaces = tuple(sorted(ptx.rwset.ns))
            prp_bytes = cap.action.proposal_response_payload
            seen_endorsers: set[bytes] = set()
            for e in cap.action.endorsements:
                # dedup by identity: a repeated endorser counts as
                # ONE signature toward the policy (reference
                # SignatureSetToValidIdentities,
                # common/policies/policy.go:360-363)
                if e.endorser in seen_endorsers:
                    continue
                try:
                    eident = self.msp.deserialize_identity(e.endorser)
                    eitem = _sig_item(eident, prp_bytes + e.endorser, e.signature)
                except Exception as exc:
                    # unparseable endorsement: contributes nothing
                    _log.debug("endorsement dropped: %s", exc)
                    continue
                seen_endorsers.add(e.endorser)
                ptx.endo_item_idx.append(items.add_slow(eitem))
                ptx.endorsements.append((e.endorser, eident))
        except protoutil.TxParseError as e:
            ptx.code = e.code
            return
        except Exception:
            ptx.code = C.BAD_RWSET
            return

    def _py_rwset(self, ptx, native) -> None:
        """Python rwset parse for one native-fast tx the flat path
        cannot cover — identical verdicts to the pure-Python path."""
        try:
            results = native.span(native.results_span, ptx.idx) or b""
            ptx.rwset = TxRWSet.from_bytes(results)
            ptx.namespaces = tuple(sorted(ptx.rwset.ns))
        except Exception:
            ptx.code = C.BAD_RWSET

    @staticmethod
    def _fast_ctx(native) -> dict:
        """Hoist the native arrays the per-tx loop touches into plain
        Python lists ONCE per block — numpy scalar indexing inside a
        1000-iteration loop costs more than the work it guards."""
        return {
            "native": native,
            "blob": native.blob,
            "ok": native.ok.tolist(),
            "txid": native.txid_span.tolist(),
            "channel": native.channel_span.tolist(),
            "txid_digest": [bytes(d).hex() for d in native.txid_digest],
            "creator_sig_ok": native.creator_sig_ok.tolist(),
            "endo_start": native.endo_start.tolist(),
            "endo_count": native.endo_count.tolist(),
            "e_ok": native.e_ok.tolist(),
            "c_arrs": (native.payload_digest, native.creator_r,
                       native.creator_s),
            "e_arrs": (native.e_digest, native.e_r, native.e_s),
            # interned identities: resolved (deserialized + EC-checked)
            # at most ONCE per distinct cert in the block
            "creator_uid": native.creator_uid.tolist(),
            "e_uid": native.e_uid[:].tolist(),
            "e_dup": native.e_dup.tolist(),
            "ident_span": native.ident_span,
            "idents": [None] * native.n_ids,
        }

    def _resolve_uid(self, ctx, uid: int):
        """uid → (Identity | None, serialized bytes, has_ec_key)."""
        pool = ctx["idents"]
        got = pool[uid]
        if got is None:
            span = ctx["ident_span"]
            o, ln = int(span[uid, 0]), int(span[uid, 1])
            ser = ctx["blob"][o:o + ln]
            try:
                ident = self.msp.deserialize_identity(ser)
            except Exception:
                got = (None, ser, False)
            else:
                try:
                    ident.public_numbers
                    got = (ident, ser, True)
                except Exception:
                    got = (ident, ser, False)
            pool[uid] = got
        return got

    def _parse_fast(self, i: int, ctx, txs, items, seen_txids) -> bool:
        """Native-pre-parsed endorser tx → ParsedTx + signature items;
        check order mirrors the Python path exactly.  Returns False
        (after unwinding its partial state) when the envelope needs the
        Python path after all — anonymous-credential creators have no
        DER signature for the native splitter."""
        ptx = ParsedTx(idx=i)
        txs.append(ptx)
        blob = ctx["blob"]
        to, tl = ctx["txid"][i]
        ho, hl = ctx["channel"][i]
        txid_b = blob[to:to + tl] if to >= 0 else None
        ptx.txid = txid_b.decode("utf-8", "replace") if txid_b else ""
        ptx.channel = (
            blob[ho:ho + hl].decode("utf-8", "replace") if ho >= 0 else ""
        )

        # txid binding: tx_id == sha256(nonce ‖ creator) hex
        if not ptx.txid or ptx.txid != ctx["txid_digest"][i]:
            ptx.code = C.BAD_PROPOSAL_TXID
            return True
        if ptx.txid in seen_txids:
            ptx.code = C.DUPLICATE_TXID
            return True
        seen_txids[ptx.txid] = i

        cu = ctx["creator_uid"][i]
        if cu < 0:
            ptx.code = C.BAD_CREATOR_SIGNATURE
            return True
        ident, ser, has_ec = self._resolve_uid(ctx, cu)
        ptx.creator = ser
        if ident is None:
            ptx.code = C.BAD_CREATOR_SIGNATURE
            return True
        if not has_ec:
            if ident.is_valid and not hasattr(ident, "cert"):
                # idemix creator: unwind and let the Python path do the
                # host-side proof verification
                txs.pop()
                del seen_txids[ptx.txid]
                return False
            ptx.code = C.BAD_CREATOR_SIGNATURE
            return True
        if not ident.is_valid or not ctx["creator_sig_ok"][i]:
            ptx.code = C.BAD_CREATOR_SIGNATURE
            return True
        ptx.creator_item_idx = items.add_fast(ctx["c_arrs"], i, ident)

        # rwset handling is deferred: the native mvcc_prep pass after
        # the envelope loop parses all rwsets in one C call (or the
        # Python fallback parses per tx) — see _parse.  Endorser dedup
        # (policy.go:360-363) came from the C interner (e_dup).
        e_ok, e_arrs = ctx["e_ok"], ctx["e_arrs"]
        e_uid, e_dup = ctx["e_uid"], ctx["e_dup"]
        resolve = self._resolve_uid
        base = ctx["endo_start"][i]
        for j in range(base, base + ctx["endo_count"][i]):
            if not e_ok[j] or e_dup[j]:
                continue  # unparseable/duplicate contributes nothing
            uid = e_uid[j]
            if uid < 0:
                continue
            eident, eser, ehas_ec = resolve(ctx, uid)
            if eident is None or not ehas_ec:
                continue
            ptx.endo_item_idx.append(items.add_fast(e_arrs, j, eident))
            ptx.endorsements.append((eser, eident))
        return True

    # -- the pipeline ------------------------------------------------------

    def preprocess(self, block: common_pb2.Block):
        """Host parse + ASYNC device-verify launch + state-independent
        device-path inputs (policy match matrices, static MVCC arrays)
        for one block.

        Safe to run for block n+1 while block n is still committing
        (touches no ledger state): the peer's deliver loop and the
        bench overlap the host phase of the next block with the device
        phase of the current one — the TPU-shaped analog of the
        reference's deliver prefetch + validator pool overlap
        (gossip/state/state.go:540, v20/validator.go:193)."""
        self._apply_pending_knobs()
        t0 = time.perf_counter()
        txs, items, rwp, fb = self._parse(block)
        t0 = self._t("host_parse", t0)
        fetch = self._verify_launch_guarded(items)
        t0 = self._t("sig_prepare_launch", t0, handle=fetch)
        dpre = self._device_preprocess(txs, rwp, fb)
        t0 = self._t("device_pre", t0)
        # header+data wire form for the ledger commit (the committer
        # only splices fresh metadata on — see blockstore.add_block)
        hd_bytes = protoutil.block_header_data_bytes(block)
        self._t("hd_frame", t0)
        # the MSP manager the identities were validated against: a
        # config tx in the PREVIOUS block may rotate membership between
        # preprocess and validate — validate() detects and re-parses
        return txs, items, fetch, self.msp, dpre, fb, hd_bytes

    def preprocess_many(self, blocks: list) -> list:
        """Coalesced ``preprocess`` over several in-flight blocks: each
        block parses as usual, then ALL their signature batches go up
        in ONE concatenated verify dispatch (p256v3.verify_launch_many),
        amortizing the ladder's dispatch latency across the blocks the
        pipeline has in flight.  Each returned tuple is a drop-in
        ``pre`` for ``validate_launch`` — the per-block VerifyHandle is
        a device-side slice with the exact lane layout a solo launch
        would produce, so stage-2 and the committer are unchanged."""
        blocks = list(blocks)
        self._apply_pending_knobs()
        if len(blocks) <= 1:
            return [self.preprocess(b) for b in blocks]
        if self.host_pool is not None:
            return self._preprocess_many_pooled(blocks)
        parsed = []
        for block in blocks:
            t0 = time.perf_counter()
            parsed.append(self._parse(block))
            self._t("host_parse", t0)
        t0 = time.perf_counter()
        fetches = self._verify_launch_many_guarded(
            [p[1] for p in parsed]
        )
        self._t("sig_prepare_launch", t0)
        out = []
        for block, (txs, items, rwp, fb), fetch in zip(
            blocks, parsed, fetches
        ):
            t0 = time.perf_counter()
            dpre = self._device_preprocess(txs, rwp, fb)
            t0 = self._t("device_pre", t0)
            hd_bytes = protoutil.block_header_data_bytes(block)
            self._t("hd_frame", t0)
            out.append((txs, items, fetch, self.msp, dpre, fb, hd_bytes))
        return out

    def _preprocess_many_pooled(self, blocks: list) -> list:
        """``preprocess_many`` with the host staging pool: every
        block's parse fans out across the workers at once, and each
        block's state-independent device preprocessing is submitted
        the moment its own parse lands — so device_pre(k) overlaps
        parse(k+1..) on the pool instead of serializing behind the
        whole parse train.  The coalesced verify staging then shards
        prepare_cols over the same pool inside verify_launch_many.

        Every task is block-local (parse builds per-block objects;
        _device_preprocess touches only its block's ParsedTx records —
        the shared plan/row caches are append-only dict memos whose
        worst concurrent case is a duplicated compute), so the pooled
        result is the serial result, pinned by the DeviceToyValidator
        battery in tests/test_multidevice.py.

        Stage timings record the CALLER's critical-path wait (the time
        the feeder actually stalls), like the pipeline's prefetch_wait;
        the per-shard work itself rides
        ``host_stage_pool_seconds{stage,worker}``."""
        pool = self.host_pool
        t0 = time.perf_counter()
        parse_futs = [
            pool.submit(self._parse, b, stage="host_parse")
            for b in blocks
        ]
        parsed, dpre_futs = [], []
        for f in parse_futs:
            p = f.result()
            parsed.append(p)
            dpre_futs.append(pool.submit(
                self._device_preprocess, p[0], p[2], p[3],
                stage="device_pre",
            ))
        self._t("host_parse", t0)
        t0 = time.perf_counter()
        fetches = self._verify_launch_many_guarded(
            [p[1] for p in parsed], pool=pool
        )
        t0 = self._t("sig_prepare_launch", t0)
        out = []
        for block, (txs, items, rwp, fb), fetch, df in zip(
            blocks, parsed, fetches, dpre_futs
        ):
            t0 = time.perf_counter()
            dpre = df.result()
            t0 = self._t("device_pre", t0)
            hd_bytes = protoutil.block_header_data_bytes(block)
            self._t("hd_frame", t0)
            out.append((txs, items, fetch, self.msp, dpre, fb, hd_bytes))
        return out

    def validate(self, block: common_pb2.Block, pre=None):
        return self.validate_finish(self.validate_launch(block, pre=pre))

    def validate_launch(
        self, block: common_pb2.Block, pre=None, overlay=None,
        extra_txids=None,
    ):
        """Run every pre-device-sync step for one block — structural
        codes, dup checks, committed-version fill, stage-2 dispatch —
        and return a PendingBlock; ``validate_finish`` syncs the device
        and produces (filter, batch, history).

        ``overlay``: the UpdateBatch of the in-flight predecessor
        WINDOW — one block's batch at pipeline depth 2, or the
        newest-wins MERGE of up to depth−1 predecessors' batches
        (``UpdateBatch.merged``) whose ledger commits may still be
        draining on the committer thread.  Its writes override
        committed-version lookups (and range re-execution, and the SBE
        metadata probes via the unioned ``has_meta``), so this block
        launches without waiting for any predecessor's fsync.
        ``extra_txids``: txids of EVERY in-flight predecessor for the
        duplicate-txid check (their block-store index inserts may not
        have landed yet).  The check asks the block store once a block
        (``existing_txids``, on the index's read connection) and is
        exact, because index ∪ ``extra_txids`` holds every txid of the
        chain at every pipeline depth: a read on that connection sees
        every index transaction committed before it began (WAL); the
        pipeline has waited out every commit it no longer carries in
        its window before it launches (``_drain_commits``), so those
        blocks' rows are committed; and the commits still in its
        window are the ones whose txids arrive here
        (``_launch_overlay``).  Nothing is remembered in between: no
        window of recent txids, no filter, nothing to rebuild after a
        restart.

        Pipelined callers must SERIALIZE around blocks that rotate
        validation inputs — config blocks (MSP/policy object rotation)
        and blocks writing the ``_lifecycle`` namespace (state-backed
        chaincode definitions feed the preprocess-time policy plans):
        commit such a predecessor fully, then launch with overlay=None.
        Launching with a lifecycle-writing overlay raises — a stale
        plan here would fork a pipelined peer from a serial one."""
        if overlay is not None and any(
            k[0] == "_lifecycle" for k in overlay.updates
        ):
            raise ValueError(
                "pipelined launch across a lifecycle-writing block: "
                "commit the predecessor before launching this block"
            )
        if pre is None:
            pre = self.preprocess(block)
        if pre[3] is not self.msp or (
            pre[4] is not None and pre[4].policies is not self.policies
        ):
            # membership or policy tree rotated after this block was
            # preprocessed (committed config tx): stale identity
            # validations / plans must not leak — redo the parse
            pre = self.preprocess(block)
        txs, items, fetch, _, dpre, fb = pre[:6]
        hd_bytes = pre[6] if len(pre) > 6 else None
        # parsed records for post-commit consumers (config rotation) —
        # the commit path is serialized per channel, so this is safe
        self.last_parsed = txs

        # dup txid vs committed ledger + in-flight predecessors
        # (deferred from preprocess).  fb.codes is kept in sync — the
        # vectorized state_fill reads it as the live verdict array.
        if self.blocks is not None or extra_txids:
            # ``dup_txid``: a child of the pipeline's ``launch`` (no-op
            # off a traced launch).  ``lookups`` is the txs checked,
            # ``queries`` the statements the block store's index was
            # sent for them (one a block; none when the in-flight
            # predecessors' txid set answered every tx), ``idx_wait_ms``
            # what readers of the index waited for their lock meanwhile
            with self._tracer.span("dup_txid") as dsp:
                blocks = self.blocks
                if dsp is not None:
                    waited0 = getattr(blocks, "idx_wait_reader_s", 0.0)
                    queries0 = getattr(blocks, "txid_queries", 0)
                inflight = extra_txids or ()
                cand = [ptx for ptx in txs
                        if ptx.undetermined and not ptx.is_config]
                committed = ()
                if blocks is not None:
                    committed = blocks.existing_txids(
                        [ptx.txid for ptx in cand
                         if ptx.txid not in inflight])
                hits = 0
                for ptx in cand:
                    if ptx.txid in inflight or ptx.txid in committed:
                        ptx.code = C.DUPLICATE_TXID
                        hits += 1
                        if fb is not None:
                            fb.codes[ptx.idx] = int(C.DUPLICATE_TXID)
                if dsp is not None:
                    dsp.attrs.update(
                        lookups=len(cand), hits=hits,
                        queries=getattr(blocks, "txid_queries", 0)
                        - queries0,
                        idx_wait_ms=(getattr(
                            blocks, "idx_wait_reader_s", 0.0)
                            - waited0) * 1000.0)

        pending = PendingBlock(
            block=block, txs=txs, items=items, fetch=fetch, dpre=dpre,
            overlay=overlay, fb=fb, hd_bytes=hd_bytes,
        )
        # fused single-sync device path: policy + MVCC consume the
        # verify output ON DEVICE (one dispatch + one readback per
        # block); falls back to the host path for custom plugins,
        # non-v3 kernels, consumption-unsafe blocks, and the blocks
        # under key-level endorsement that ``_launch_device`` hands
        # back (``key_policy_host_blocks_total`` says why); with the
        # device-resident state the SBE launch veto still takes every
        # block that writes a key under a committed parameter
        if (
            getattr(fetch, "device_out", None) is not None and txs and dpre
            and not (self.resident is not None
                     and self._sbe_launch_veto(txs, dpre, overlay))
        ):
            try:
                (pending.fetch2, pending.range_phantom,
                 pending.key_launch) = self._launch_device(
                    block, txs, fetch, dpre, overlay, fb=fb
                )
            except Exception as e:
                # fused stage-2 dispatch died: with a lane guard this
                # block degrades to the host MVCC path (fetch2 stays
                # None) instead of tearing the stream down
                if self.device_guard is None:
                    raise
                self.device_guard.record_failure(e)
                _log.warning(
                    "fused stage-2 dispatch failed (%s) — block %d "
                    "takes the host path", e, block.header.number,
                )
        return pending

    def _sbe_launch_veto(self, txs, dpre, overlay) -> bool:
        """With ``state_resident`` only (the resident variant of the
        stage-2 program has no key-policy lanes): True when a written
        key of this block carries a key-level endorsement policy in
        committed state (or the in-flight predecessor's batch), and the
        block re-routes to the host dispatch path.  Free on channels
        that never set validation parameters (meta_count 0).  In-block
        metadata WRITES never reach here: with ``state_resident`` the
        group builders return None for them at preprocess."""
        if not self._metaful(overlay):
            return False
        static = dpre.static
        if dpre.rwp is not None and getattr(static, "u_pairs", None):
            rwp = dpre.rwp
            for u in np.unique(rwp.w_uid[:rwp.n_writes]).tolist():
                ns, key = static.u_pairs[u]
                if self._committed_key_has_meta(ns, key, overlay):
                    return True
            return False
        for ptx in txs:
            if not ptx.undetermined or ptx.is_config or ptx.rwset is None:
                continue
            for ns_name, n in ptx.rwset.ns.items():
                for k in n.writes:
                    if self._committed_key_has_meta(ns_name, k, overlay):
                        return True
        return False

    def validate_finish(self, pending: "PendingBlock"):
        """Sync the device stage-2 of a launched block and produce the
        (filter, batch, history) triple.  With a device-lane guard, a
        stage-2 sync failure degrades THIS block to the host path (the
        guarded verify handle re-verifies on CPU if the device output
        is gone too) and counts toward the degraded latch."""
        if pending.fetch2 is not None:
            self._last_device_sync_s = 0.0
            try:
                result = self._finish_device(pending)
            except Exception as e:
                if self.device_guard is None:
                    raise
                self.device_guard.record_failure(e)
                _log.warning(
                    "device stage-2 sync failed (%s) — block %d "
                    "re-validating on the host path", e,
                    pending.block.header.number,
                )
                result = None
            else:
                if result is not None and self.device_guard is not None:
                    # only the fetch2() sync is the lane's latency —
                    # the host postprocess after it must not trip a
                    # deadline tuned for the device
                    if not self.device_guard.check_deadline(
                        self._last_device_sync_s
                    ):
                        self.device_guard.record_success()
            if result is not None:
                return result
        return self._validate_host(
            pending.block, pending.txs, pending.items, pending.fetch,
            overlay=pending.overlay, fb=pending.fb,
        )

    def _validate_host(self, block, txs, items, fetch, overlay=None,
                       fb=None):
        # a columnar parse leaves endorsement lists / namespaces lazy:
        # the host dispatch path walks them, so fill them first
        self._materialize_for_host(txs, fb)
        # phase 1a: one batched ECDSA verify for the whole block —
        # the host path's ONE intended device sync
        t0 = time.perf_counter()
        sig_valid = (
            np.asarray(fetch(), bool)  # fabtpu: noqa(FT003)
            if items else np.zeros(0, bool)
        )
        self._t("device_wait", t0)

        for ptx in txs:
            if ptx.undetermined and ptx.creator_item_idx >= 0:
                if not sig_valid[ptx.creator_item_idx]:
                    ptx.code = C.BAD_CREATOR_SIGNATURE

        # config txs: structural + signature + config-machinery checks
        # (v20/validator.go:397-419 — never rubber-stamped)
        for ptx in txs:
            if ptx.is_config and ptx.undetermined:
                ptx.code = self._validate_config(block, ptx)

        # phase 1b: per-namespace plugin dispatch (policy reduction).
        # A tx is valid only if EVERY written namespace's plugin
        # approves it (plugindispatcher/dispatcher.go:190-217).
        ctx = BlockValidationCtx(
            txs=txs, sig_valid=sig_valid, msp_manager=self.msp,
            policy_provider=self.policies,
        )
        by_plugin: dict[str, list[tuple[ParsedTx, tuple]]] = {}
        for ptx in txs:
            if not ptx.undetermined or ptx.is_config:
                continue
            infos = [self.policies.info(ns) for ns in ptx.namespaces]
            if not ptx.namespaces or any(i is None for i in infos):
                ptx.code = C.INVALID_CHAINCODE
                continue
            for ns, info in zip(ptx.namespaces, infos):
                name = info.plugin or "default"
                by_plugin.setdefault(name, []).append((ptx, ns))
        # key-level (state-based) endorsement: when any written key
        # carries a committed VALIDATION_PARAMETER — or any tx writes
        # one — the namespace verdicts become per-key fallbacks inside
        # the SBE pass instead of immediate failures
        sbe_active = self._sbe_active(txs, overlay)
        ns_verdicts: dict | None = {} if sbe_active else None
        for name, group in by_plugin.items():
            plug = self.plugins.get(name)
            if plug is None:
                for ptx, _ in group:
                    ptx.code = C.INVALID_OTHER_REASON
                continue
            if hasattr(plug, "validate_batch_group"):
                ok = plug.validate_batch_group(ctx, group)
            else:
                # legacy SPI returns [T] per-tx verdicts; realign to the
                # per-(tx, namespace) group entries by block position
                per_tx = plug.validate_batch(ctx)
                ok = [per_tx[ptx.idx] for ptx, _ in group]
            for (ptx, ns), good in zip(group, ok):
                if ns_verdicts is not None:
                    ns_verdicts[(ptx.idx, ns)] = bool(good)
                elif not good and ptx.undetermined:
                    ptx.code = C.ENDORSEMENT_POLICY_FAILURE
        if sbe_active:
            self._sbe_pass(txs, sig_valid, ns_verdicts, overlay)

        # phase 2: MVCC over the whole block
        mvcc_txs, committed = self._mvcc_inputs(txs, overlay=overlay)
        pre_ok = np.array([ptx.undetermined for ptx in txs], bool)
        if txs:
            valid, conflict, phantom = mvcc_ops.mvcc_validate_block(
                mvcc_txs, committed, pre_ok
            )
            for ptx, v, ph in zip(txs, valid, phantom):
                if not ptx.undetermined:
                    continue
                if v:
                    ptx.code = C.VALID
                else:
                    ptx.code = C.PHANTOM_READ_CONFLICT if ph else C.MVCC_READ_CONFLICT

        # phase 3: filter + update batch + history
        tx_filter = bytes(ptx.code for ptx in txs)
        batch, history = self._build_updates(
            block.header.number, txs, overlay=overlay, sbe=sbe_active
        )
        return tx_filter, batch, history

    # -- state-based (key-level) endorsement -------------------------------

    def _sbe_active(self, txs, overlay=None) -> bool:
        """True when key-level endorsement applies to this block:
        some tx writes key metadata, or a written key carries a
        committed (or in-flight predecessor) VALIDATION_PARAMETER.
        The committed probe only runs when the state reports any
        metadata at all (statedb.meta_count) — channels that never use
        SetStateValidationParameter pay nothing."""
        metaful = self._metaful(overlay)
        for ptx in txs:
            rw = ptx._rwset  # lazy rwsets (columnar) can't carry them
            if rw is None:
                if not metaful:
                    continue
                rw = ptx.rwset  # forces the parse only on SBE channels
                if rw is None:
                    continue
            for ns_name, n in rw.ns.items():
                if n.metadata_writes:
                    return True
                if metaful:
                    for k in n.writes:
                        # ANY committed metadata (not just a policy)
                        # activates the pass: plain value writes must
                        # PRESERVE existing metadata, which the fast
                        # update builder doesn't look up
                        if self._committed_key_has_meta(
                            ns_name, k, overlay
                        ):
                            return True
        return False

    def _metaful(self, overlay) -> bool:
        """Any key metadata anywhere the block could see it: committed
        state (meta_count) or the in-flight predecessor's batch."""
        return getattr(self.state, "meta_count", 0) > 0 or (
            overlay is not None and getattr(overlay, "has_meta", False)
        )

    def _committed_key_has_meta(self, ns: str, key: str, overlay) -> bool:
        if overlay is not None:
            vv = overlay.updates.get((ns, key))
            if vv is not None:
                return bool(vv.value is not None and vv.metadata)
        vv = self.state.get_state(ns, key)
        return vv is not None and bool(vv.metadata)

    def _committed_key_policy(self, ns: str, key: str, overlay):
        """Committed VALIDATION_PARAMETER bytes for (ns, key) — the
        in-flight predecessor's update batch overrides the state read
        (same serialization argument as _committed_versions)."""
        from fabric_tpu.ledger.rwset import (
            VALIDATION_PARAMETER, decode_metadata,
        )

        if overlay is not None:
            vv = overlay.updates.get((ns, key))
            if vv is not None:
                if vv.value is None or not vv.metadata:
                    return None
                return decode_metadata(vv.metadata).get(VALIDATION_PARAMETER)
        vv = self.state.get_state(ns, key)
        if vv is None or not vv.metadata:
            return None
        return decode_metadata(vv.metadata).get(VALIDATION_PARAMETER)

    def _sbe_pass(self, txs, sig_valid, ns_verdicts, overlay) -> None:
        """Key-level endorsement enforcement, in block order — the
        reference's dependency-managed walk
        (statebased/validator_keylevel.go:244-260 + the
        vpmanagerimpl.go:47-199 waits) collapsed to a serial pass: a
        tx's written keys are checked under the policies in effect AT
        ITS POSITION, where 'in effect' folds in metadata updates from
        earlier PLUGIN-valid txs of the same block (matching the
        reference: an earlier tx later killed by MVCC still had its
        update visible to the key-level validator).  Keys without a
        key-level policy fall back to the namespace verdict; a tx whose
        namespace has no written keys at all is judged by the
        namespace policy alone."""
        from fabric_tpu.ledger.rwset import VALIDATION_PARAMETER

        pending: dict = {}    # (ns, key) → policy bytes | None (cleared)
        pol_cache: dict = {}  # policy bytes → (ast, plan) | None
        comm_cache: dict = {}  # (ns, key) → committed policy probe
        for ptx in txs:
            if not ptx.undetermined or ptx.is_config or ptx.rwset is None:
                continue
            tx_ok = True
            for ns_name in ptx.namespaces:
                n = ptx.rwset.ns.get(ns_name)
                if n is None:
                    continue
                keys = sorted(set(n.writes) | set(n.metadata_writes))
                if not keys:
                    if not ns_verdicts.get((ptx.idx, ns_name), False):
                        tx_ok = False
                        break
                    continue
                for k in keys:
                    if (ns_name, k) in pending:
                        pb = pending[(ns_name, k)]
                    elif (ns_name, k) in comm_cache:
                        pb = comm_cache[(ns_name, k)]
                    else:
                        pb = comm_cache[(ns_name, k)] = (
                            self._committed_key_policy(ns_name, k, overlay)
                        )
                    if pb is None:
                        ok_k = ns_verdicts.get((ptx.idx, ns_name), False)
                    else:
                        ok_k = self._eval_key_policy(
                            pb, ptx, sig_valid, pol_cache
                        )
                    if not ok_k:
                        tx_ok = False
                        break
                if not tx_ok:
                    break
            if not tx_ok:
                ptx.code = C.ENDORSEMENT_POLICY_FAILURE
                continue
            # plugin-valid: this tx's metadata updates take effect for
            # every later tx in the block
            for ns_name, n in ptx.rwset.ns.items():
                for k, entries in n.metadata_writes.items():
                    pending[(ns_name, k)] = entries.get(VALIDATION_PARAMETER)

    def _eval_key_policy(self, policy_bytes, ptx, sig_valid, cache) -> bool:
        """Evaluate one key-level policy over the tx's sig-valid
        endorsements (the exact interpreter — key policies are rare
        and arbitrary, so no batch plan reuse is assumed)."""
        got = cache.get(policy_bytes, False)
        if got is False:
            try:
                from fabric_tpu.crypto.msp import policy_from_proto
                from fabric_tpu.protos import policies_pb2

                env = protoutil.unmarshal(
                    policies_pb2.SignaturePolicyEnvelope, policy_bytes
                )
                ast = policy_from_proto(env)
                plan = pol.compile_plan(ast)
                got = (ast, plan)
            except Exception:
                got = None  # unparseable policy: fail closed
            cache[policy_bytes] = got
        if got is None:
            return False
        ast, plan = got
        if not ptx.endorsements:
            return False
        idents = [ident for (_, ident) in ptx.endorsements]
        valid = np.array(
            [bool(sig_valid[i]) for i in ptx.endo_item_idx], bool
        )
        m = pol.match_matrix(idents, plan.principals) & valid[:, None]
        return bool(pol.evaluate(ast, m))

    # -- fused single-sync device path ------------------------------------

    def _put_group(self, gp):
        """Upload one policy-group pack (prefetch thread) under the
        ``policy_table`` partition rule when a mesh is configured.
        The bytes count on the launch ledger's ``stage2_prefetch``
        h2d lane — prefetch-thread uploads are device transfer time
        the launch-time accounting would otherwise miss."""
        import jax.numpy as jnp

        from fabric_tpu.observe import ledger as _ledger

        _ledger.note_h2d("stage2_prefetch", gp.nbytes)
        if self.mesh is None:
            return jnp.asarray(gp)
        from fabric_tpu.parallel.mesh import shard

        return shard(self.mesh, "policy_table", jnp.asarray(gp))

    def _device_preprocess(self, txs, rwp=None, fb=None):
        """State-INDEPENDENT device-path inputs: policy match matrices
        (vectorized gather over per-identity cached principal rows) and
        static MVCC arrays.  Runs in the prefetch thread, overlapping
        the previous block's device time; returns None when the block
        needs the host dispatch path (custom plugins).  When the native
        mvcc_prep covered every undetermined endorser tx (``rwp``),
        the static arrays come from numpy scatters over its flat
        output instead of per-read Python loops."""
        from fabric_tpu.ops import mvcc as mvcc_ops
        from fabric_tpu.utils.batching import block_shapes

        if not txs:
            return None
        default = self.plugins.get("default")
        if type(default).__name__ != "DefaultValidation":
            return None
        if fb is not None:
            dp = self._device_pre_columnar(txs, rwp, fb)
            if dp is not NotImplemented:
                return dp
            # block mixes lanes the columnar builder doesn't carry
            # (idemix creators, range queries, partial native parses):
            # materialize the per-tx lists and run the generic builder
            self._materialize_for_host(txs, fb)

        entries = []  # (ptx, ns, info)
        for ptx in txs:
            if not ptx.undetermined or ptx.is_config:
                continue
            if self.resident is not None and ptx.rwset is not None and any(
                n.metadata_writes for n in ptx.rwset.ns.values()
            ):
                # key-level endorsement rides this block: the resident
                # program variant has no key lanes → host dispatch path
                return None
            infos = [self.policies.info(ns) for ns in ptx.namespaces]
            if not ptx.namespaces or any(i is None for i in infos):
                ptx.code = C.INVALID_CHAINCODE  # same verdict on both paths
                continue
            if any((i.plugin or "default") != "default" for i in infos):
                return None  # custom plugin in play → host dispatch path
            for ns, info in zip(ptx.namespaces, infos):
                entries.append((ptx, ns, info))

        # policy groups (by policy object), padded to buckets; match
        # rows built once per distinct identity then gathered
        by_policy: dict[int, list] = {}
        plans: dict[int, object] = {}
        for ptx, ns, info in entries:
            key = id(info.policy)
            if key not in plans:
                plans[key] = default._plan(info.policy)
            by_policy.setdefault(key, []).append(ptx)

        def group_of(plan, ents):
            """One policy group over the txs ``ents``, packed and on
            the device."""
            P = len(plan.principals)
            sh = block_shapes(entries=len(ents), endorsements=max(
                (len(p.endorsements) for p in ents), default=0))
            S, E = sh.slots, sh.entries
            pool_rows = [np.zeros(P, bool)]  # row 0 = padding (no match)
            pool_of: dict[int, int] = {}
            idx_mat = np.zeros((E, S), np.int32)
            endo_idx = np.full((E, S), -1, np.int32)
            tx_of = np.full(E, -1, np.int32)
            for e, ptx in enumerate(ents):
                tx_of[e] = ptx.idx
                if ptx.endo_item_idx:
                    endo_idx[e, : len(ptx.endo_item_idx)] = ptx.endo_item_idx
                for s, (ser, ident) in enumerate(ptx.endorsements):
                    pi = pool_of.get(id(ident))
                    if pi is None:
                        pi = pool_of[id(ident)] = len(pool_rows)
                        pool_rows.append(default._match_row(plan, ser, ident))
                    idx_mat[e, s] = pi
            match = np.stack(pool_rows)[idx_mat]  # [E, S, P] gather
            # pack + upload NOW (prefetch thread): launch-time H2D is
            # latency-bound and sits on the critical path
            gp = np.empty((E, S * P + S + 1), np.int32)
            gp[:, :S * P] = match.reshape(E, -1)
            gp[:, S * P:S * P + S] = endo_idx
            gp[:, -1] = tx_of
            return plan, self._put_group(gp), E, S

        groups = []
        group_entries = []
        for key, ents in by_policy.items():
            groups.append(group_of(plans[key], ents))
            group_entries.append(ents)
        group_idx = {key: g for g, key in enumerate(by_policy)}

        # static MVCC arrays (committed-version fill deferred to
        # validate time — it needs the predecessor's state commit)
        flat_ok = rwp is not None and all(
            (not ptx.undetermined) or ptx.is_config
            or rwp.status[ptx.idx] == 0
            for ptx in txs
        )
        if flat_ok:
            ns_names = rwp.ns_names()
            ukeys = rwp.ukey_strs()
            composite = [
                ("pub", ns_names[rwp.ns_of_ukey[u]], ukeys[u])
                for u in range(rwp.n_keys)
            ]
            static = mvcc_ops.prepare_block_from_flat(len(txs), rwp, composite)
            static.u_pairs = [(c[1], c[2]) for c in composite]
            # key → unique-id index for the launch-time overlay
            # overrides — built HERE (prefetch thread) so the caller
            # thread's state_fill never pays the dict construction
            static.u_index = dict(zip(static.u_pairs,
                                      range(rwp.n_keys)))
            static.packed_static()
            if self.resident is not None:
                # expected-read plane for the device-resident compare:
                # state-independent, so it uploads HERE (prefetch
                # thread), never on the launch critical path
                static.packed_read_pv()
            dp = _DevicePre(
                groups=groups, group_entries=group_entries, static=static,
                has_range=False, policies=self.policies,
                rwp=rwp, ns_names=ns_names, ukeys=ukeys,
            )
            # the gather answers by unique-key id: so do the lanes
            lanes_of, meta_writes, _ = self._key_lanes_generic(
                entries, group_idx, group_of, static.u_pairs,
                static.u_index)
            if meta_writes and not self._resolve_meta_writes(rwp):
                return None  # entries the Python parser refuses
            return self._with_key_lanes(dp, lanes_of, meta_writes)
        lanes_of, meta_writes, meta_only = self._key_lanes_generic(
            entries, group_idx, group_of)
        mvcc_txs = []
        has_range = False
        for ptx in txs:
            if ptx.rwset is None or not ptx.undetermined:
                mvcc_txs.append(
                    mvcc_ops.TxRWSet(reads=[], writes=[], range_reads=[])
                )
                continue
            if any(n.range_queries for n in ptx.rwset.ns.values()):
                has_range = True
            reads, writes, rqs = ptx.rwset.mvcc_form()
            # a metadata-only write is a writer where its key exists
            # (``_mvcc_inputs``): taken as one here, and taken back at
            # the launch that finds the key absent
            writes += [k for i, k in meta_only if i == ptx.idx]
            mvcc_txs.append(
                mvcc_ops.TxRWSet(reads=reads, writes=writes, range_reads=rqs)
            )
        static = mvcc_ops.prepare_block_static(mvcc_txs, bucketed=True)
        static.packed_static()
        dp = _DevicePre(
            groups=groups, group_entries=group_entries, static=static,
            has_range=has_range, policies=self.policies,
            mvcc_txs=mvcc_txs if meta_only else None,
            meta_only=meta_only or None,
        )
        return self._with_key_lanes(dp, lanes_of, meta_writes)

    # -- key-level endorsement: the prefetch half ---------------------------

    def _with_key_lanes(self, dp, lanes_of, meta_writes: bool):
        """``dp`` with its key-policy lanes: built now (prefetch
        thread) where they will be wanted as far as can be known
        without a state read (the channel has met a key policy, or the
        block writes metadata), else left to the first launch that
        finds metadata in the state.  A channel that never set a
        parameter builds and uploads nothing."""
        dp.key_lanes_of = lanes_of
        if self.resident is None and (meta_writes or self._key_plans):
            dp.key_lanes = lanes_of()
        return dp

    def _finish_key_lanes(self, ent_tx, ent_g, ent_key, pairs, want,
                          n_live, group, meta_writes, inblock_dep):
        from fabric_tpu.utils.batching import block_shapes

        KE = len(ent_tx)
        packed = np.zeros((block_shapes(entries=KE).entries, 2), np.int32)
        packed[:, 0] = -1
        packed[:KE, 0] = ent_tx
        packed[:KE, 1] = ent_g
        kl = _KeyLanes(
            packed=packed, ent_key=np.asarray(ent_key, np.int64),
            pairs=pairs, want=want, n_live=n_live, group=group,
            meta_writes=meta_writes, inblock_dep=inblock_dep)
        if not inblock_dep:
            # every tx under every key policy the channel has met: the
            # launch only picks, by each key's committed parameter
            kl.dev = self._put_group(packed)
            kl.groups = [group(plan) for plan in list(self._key_plans)]
        return kl

    def _key_lanes_generic(self, entries, group_idx, group_of, pairs=None,
                           index=None):
        """The key-policy lanes of a block the generic builder carries
        (``entries``: its live ``(ptx, namespace, info)``, in block
        order) → (a no-argument builder of its :class:`_KeyLanes`,
        whether a live tx writes metadata, [(tx, composite key)] of the
        metadata-only writes).  ``pairs`` / ``index``: a flat block's
        unique-key table, by which its gather answers; without them
        the lanes bring their own."""
        if pairs is None:
            pairs, index = [], {}
        ent_tx, ent_g, ent_key = [], [], []
        wrote_meta: dict = {}   # (ns, key) → the first tx that did
        meta_only, live = [], {}
        dep = False
        for ptx, ns, info in entries:
            live[ptx.idx] = ptx
            n = ptx.rwset.ns.get(ns) if ptx.rwset is not None else None
            g = group_idx[id(info.policy)]
            keys = sorted(set(n.writes) | set(n.metadata_writes)) if n else ()
            if not keys:
                ent_tx.append(ptx.idx), ent_g.append(g), ent_key.append(-1)
                continue
            for k in keys:
                j = index.get((ns, k))
                if j is None:
                    j = index[(ns, k)] = len(pairs)
                    pairs.append((ns, k))
                ent_tx.append(ptx.idx), ent_g.append(g), ent_key.append(j)
                dep = dep or (ns, k) in wrote_meta
            for k in n.metadata_writes:
                wrote_meta.setdefault((ns, k), ptx.idx)
                if k not in n.writes:
                    meta_only.append((ptx.idx, ("pub", ns, k)))

        def lanes_of():
            want = np.zeros(len(pairs), bool)
            want[[j for j in ent_key if j >= 0]] = True
            ents = list(live.values())
            return self._finish_key_lanes(
                ent_tx, ent_g, ent_key, pairs, want, len(ents),
                lambda plan: group_of(plan, ents), bool(wrote_meta), dep)

        return lanes_of, bool(wrote_meta), meta_only

    def _resolve_meta_writes(self, rwp) -> bool:
        """``rwp.m_md``: for each metadata write of a flat block, the
        metadata the state will store (``rwset.encode_metadata`` of its
        entries; None: cleared).  Each DISTINCT entry list is parsed
        once a channel: an asset's owner policy comes a few hundred
        times a block.  → False where the Python parser refuses one."""
        from fabric_tpu.ledger.rwset import decode_metadata, encode_metadata

        blob, cache = rwp.blob, self._md_enc
        if len(cache) > 4096:
            cache.clear()
        out = []
        for o, ln in rwp.m_ent_span[:rwp.n_meta].tolist():
            raw = blob[o:o + ln]
            md = cache.get(raw, False)
            if md is False:
                try:
                    md = encode_metadata(decode_metadata(raw))
                except Exception:
                    return False
                cache[raw] = md
            out.append(md)
        rwp.m_md = out
        return True

    def _key_lanes_columnar(self, rwp, fb, n, live, etx, ens, ns_group,
                            pairs):
        """A columnar block's :class:`_KeyLanes`, from the flat write
        and metadata-write arrays: numpy over the rows, no loop a key."""
        nk = max(1, rwp.n_keys)
        n_ns = len(ns_group)
        ns_of = rwp.ns_of_ukey[:nk].astype(np.int64)
        w_tx = np.repeat(np.arange(n), rwp.w_count[:n])
        m_tx = np.repeat(np.arange(n), rwp.m_count[:n])
        w_uid = rwp.w_uid[:rwp.n_writes].astype(np.int64)[live[w_tx]]
        m_uid = rwp.m_uid[:rwp.n_meta].astype(np.int64)[live[m_tx]]
        w_tx, m_tx = w_tx[live[w_tx]], m_tx[live[m_tx]]
        t_tx = np.concatenate([w_tx, m_tx])
        t_uid = np.concatenate([w_uid, m_uid])
        comp = np.unique(t_tx * nk + t_uid)     # a (tx, key) once
        k_tx, k_uid = comp // nk, comp % nk
        # a (tx, namespace) that touches no key: its namespace's verdict
        has = np.zeros(n * n_ns, bool)
        has[k_tx * n_ns + ns_of[k_uid]] = True
        bare = ~has[etx * n_ns + ens]
        dep = False
        if len(m_uid):
            first = np.full(nk, n, np.int64)
            np.minimum.at(first, m_uid, m_tx)
            dep = bool((t_tx > first[t_uid]).any())
        want = np.zeros(len(pairs), bool)
        want[k_uid] = True
        gtx = np.flatnonzero(live)
        return self._finish_key_lanes(
            np.concatenate([k_tx, etx[bare]]),
            np.concatenate([ns_group[ns_of[k_uid]], ns_group[ens[bare]]]),
            np.concatenate([k_uid, np.full(int(bare.sum()), -1, np.int64)]),
            pairs, want, len(gtx),
            lambda plan: self._columnar_group(plan, fb, gtx),
            bool(len(m_uid)), dep)

    def _columnar_group(self, plan, fb, gtx):
        """One policy group over the txs ``gtx`` of a columnar block,
        packed and on the device: match rows from a per-identity pool
        gathered through the [n, S] uid matrix."""
        from fabric_tpu.utils.batching import block_shapes

        default = self.plugins["default"]
        P = len(plan.principals)
        S = fb.uid_mat.shape[1]
        n_pool = len(fb.idents)
        E = len(gtx)
        Eb = block_shapes(entries=E).entries
        row_pool = np.zeros((n_pool + 1, P), bool)
        for u in range(n_pool):
            if fb.has_ec[u]:
                row_pool[u + 1] = default._match_row(
                    plan, fb.sers[u], fb.idents[u]
                )
        gp = np.zeros((Eb, S * P + S + 1), np.int32)
        gp[:, S * P:S * P + S] = -1
        gp[:, -1] = -1
        if E:
            gp[:E, :S * P] = row_pool[fb.uid_mat[gtx]].reshape(E, -1)
            gp[:E, S * P:S * P + S] = fb.endo_idx_mat[gtx]
            gp[:E, -1] = gtx
        # ONE packed upload per group (prefetch thread)
        return plan, self._put_group(gp), Eb, S

    def _device_pre_columnar(self, txs, rwp, fb):
        """Policy-group + static-MVCC construction straight from the
        columnar arrays: match matrices come from a per-identity row
        pool gathered through the [n,S] uid matrix, entries from the
        flat (tx, ns) arrays — no per-entry Python loop.  Handles only
        blocks whose every live tx is a flat-rwset columnar tx;
        returns NotImplemented otherwise (caller falls back to the
        generic builder), or None for custom plugins (host path)."""
        from fabric_tpu.ops import mvcc as mvcc_ops
        from fabric_tpu.utils.batching import block_shapes

        if rwp is None:
            return NotImplemented
        default = self.plugins["default"]
        n = len(txs)
        codes = fb.codes
        NOTV = int(C.NOT_VALIDATED)
        live = (codes == NOTV) & ~fb.is_config
        st_ok = rwp.status[:n] == 0
        if bool((live & ~(fb.c_ok & st_ok)).any()) or not live.any():
            return NotImplemented

        tns_c = rwp.tx_ns_count[:n]
        # a tx writing no namespace → INVALID_CHAINCODE (same verdict
        # as the host dispatch path's entry collection)
        zero = live & (tns_c == 0)
        if zero.any():
            for i in np.flatnonzero(zero).tolist():
                txs[i].code = C.INVALID_CHAINCODE
                codes[i] = int(C.INVALID_CHAINCODE)
            live = live & ~zero
            if not live.any():
                return NotImplemented

        total_ns = int(tns_c.sum())
        etx = np.repeat(np.arange(n), tns_c)
        ens = rwp.ns_ids_flat[:total_ns]
        sel = live[etx]
        etx, ens = etx[sel], ens[sel]
        ns_names = rwp.ns_names()
        infos = [self.policies.info(nm) for nm in ns_names]
        bad_ids = [j for j, inf in enumerate(infos) if inf is None]
        if bad_ids:
            badsel = np.isin(ens, bad_ids)
            bad_txs = np.unique(etx[badsel])
            for i in bad_txs.tolist():
                txs[i].code = C.INVALID_CHAINCODE
                codes[i] = int(C.INVALID_CHAINCODE)
            keep = ~np.isin(etx, bad_txs)
            etx, ens = etx[keep], ens[keep]
        if any(
            inf is not None and (inf.plugin or "default") != "default"
            for inf in infos
        ):
            return None  # custom plugin in play → host dispatch path

        key_ns: dict[int, list] = {}
        key_info: dict[int, object] = {}
        for j, inf in enumerate(infos):
            if inf is None:
                continue
            key = id(inf.policy)
            key_ns.setdefault(key, []).append(j)
            key_info[key] = inf
        if rwp.n_meta and (self.resident is not None
                           or not self._resolve_meta_writes(rwp)):
            # the resident program variant has no key lanes; entries
            # the Python parser refuses are the host path's to judge
            return None if self.resident is not None else NotImplemented
        groups = []
        group_entries = []
        ns_group = np.full(len(ns_names), -1, np.int64)
        for key, ns_ids in key_ns.items():
            ns_group[ns_ids] = len(groups)
            plan = default._plan(key_info[key].policy)
            if len(key_ns) > 1:
                gtx = etx[np.isin(ens, ns_ids)]
            else:
                gtx = etx
            groups.append(self._columnar_group(plan, fb, gtx))
            group_entries.append(range(len(gtx)))

        ukeys = rwp.ukey_strs()
        ns_of = rwp.ns_of_ukey[:rwp.n_keys].tolist()
        pairs = [(ns_names[ns_of[u]], ukeys[u]) for u in range(rwp.n_keys)]
        composite = [("pub", ns, k) for ns, k in pairs]
        static = mvcc_ops.prepare_block_from_flat(n, rwp, composite)
        static.u_pairs = pairs
        # prefetch-thread key index (see _device_preprocess)
        static.u_index = dict(zip(pairs, range(rwp.n_keys)))
        static.packed_static()  # ONE H2D, prefetch thread
        if self.resident is not None:
            static.packed_read_pv()  # resident-compare expected plane
        dp = _DevicePre(
            groups=groups, group_entries=group_entries, static=static,
            has_range=False, policies=self.policies,
            rwp=rwp, ns_names=ns_names, ukeys=ukeys,
            codes_synced=True,
        )
        live = (codes[:n] == NOTV) & ~fb.is_config
        return self._with_key_lanes(
            dp, lambda: self._key_lanes_columnar(
                rwp, fb, n, live, etx, ens, ns_group, pairs),
            bool(rwp.n_meta and live[np.repeat(
                np.arange(n), rwp.m_count[:n])].any()))

    def _launch_device(self, block, txs, handle, dpre, overlay=None,
                       fb=None):
        """Host-side device-path launch: range re-execution, structural
        arrays, committed-version fill (+ overlay), stage-2 dispatch.
        → (the packed-output fetch, the txs a range re-execution
        failed, the block's :class:`_KeyLaunch` or None); the fetch is
        None where the block is the host path's after all (key-level
        endorsement the lanes do not carry: ``_key_host_block``).

        The ``state_fill`` stage here is fully vectorized for columnar
        blocks (``fb`` with codes kept in sync by the columnar builder
        — ``dpre.codes_synced``): the per-tx structural/creator loop
        becomes numpy masks over the live code array, and the
        committed-version fill is one fused backend column gather
        (``statedb.get_versions_cols``) with overlay overrides applied
        by iterating the (small) overlay instead of probing it per
        unique key."""
        from fabric_tpu.peer.device_block import DeviceBlockPipeline

        t0 = time.perf_counter()
        # committed-range phantom re-execution (host state reads, plus
        # the in-flight predecessor's writes when pipelined).  The CODE
        # is assigned at finish, AFTER the policy verdicts — the host
        # path's check order is creator → policy → mvcc/phantom, and a
        # tx failing both must report ENDORSEMENT_POLICY_FAILURE on
        # both paths; here the tx is only excluded from the kernel's
        # writer set (its writes must not kill other reads).
        range_phantom: set = set()
        if dpre.has_range:
            for ptx in txs:
                if (
                    ptx.undetermined and not ptx.is_config
                    and ptx.rwset is not None
                    and (self._committed_range_phantom(ptx, overlay)
                         or (overlay is not None
                             and _overlay_range_phantom(ptx, overlay)))
                ):
                    range_phantom.add(ptx.idx)

        # key-level endorsement: the lanes this block needs, if any
        kl = None
        metaful = self._metaful(overlay)
        if self.resident is None and (dpre.key_lanes is not None or metaful):
            kl = dpre.key_lanes
            if kl is None:
                # the first blocks of a channel whose state already
                # holds parameters: nothing said so on the prefetch thread
                kl = dpre.key_lanes = dpre.key_lanes_of()
            if kl.inblock_dep:
                return self._key_host_block("inblock_dependency")
            if not (kl.meta_writes or metaful):
                kl = None  # no parameter anywhere: today's program

        t_bucket = int(dpre.static.read_keys.shape[0])
        structural = np.zeros(t_bucket, bool)
        creator_idx = np.full(t_bucket, -1, np.int32)
        if (fb is not None and getattr(dpre, "codes_synced", False)
                and not dpre.has_range):
            # columnar fast lane: fb.codes IS the live verdict array
            # (the columnar builder and the dup check keep it synced),
            # every live tx is a flat columnar endorser tx (no idemix
            # -2 lanes, no range phantoms) — two masked assignments
            # replace the 1000-iteration Python loop
            n = len(txs)
            live = (fb.codes == int(C.NOT_VALIDATED)) & ~fb.is_config
            structural[:n] = live
            creator_idx[:n] = np.where(live, fb.creator_item, -1)
        else:
            for ptx in txs:
                if ptx.undetermined and not ptx.is_config:
                    structural[ptx.idx] = ptx.idx not in range_phantom
                    creator_idx[ptx.idx] = (
                        -2 if ptx.host_creator_ok else ptx.creator_item_idx
                    )  # -2 = host-verified (idemix) → always-true lane

        static = dpre.static
        resident_pack = None
        if (self.resident is not None and self.resident.enabled
                and getattr(static, "u_pairs", None) is not None
                and not dpre.has_range):
            # device-resident state path: the committed-version
            # compare runs ON DEVICE against the resident table; the
            # host gather below shrinks to the miss/overlay set.  Any
            # failure latches the cache off and this block (and every
            # later one) takes the host oracle path — verdicts never
            # change, only time does.
            try:
                resident_pack = self._resident_pack(static, overlay)
            except Exception as e:
                self.resident.disable(f"resident launch failed: {e}")
                _log.warning(
                    "resident state path failed for block %d (%s) — "
                    "falling back to host state_fill",
                    block.header.number, e,
                )
                resident_pack = None
        static_packed = static.packed_static()
        present = metas = None
        if resident_pack is not None:
            ver_ok = 1  # inert lane: computed on device from the table
        elif getattr(static, "u_pairs", None) is not None:
            # flat path: committed versions per UNIQUE key, compared on
            # host — one [T] bool rides to the device.  The metadata of
            # the keys the block writes rides the same gather
            ver_ok, present, metas = self._flat_ver_ok(
                static, overlay, None if kl is None else kl.want)
            if kl is not None and static.mo_rows is not None:
                gone = ~present[static.mo_uid]
                if gone.any():
                    # a metadata-only write to an absent key does not
                    # apply, so it is no writer (``_mvcc_inputs``)
                    wk = static.write_keys.copy()
                    wk[static.mo_rows[gone], static.mo_cols[gone]] = -1
                    static_packed = static.pack(wk)
        else:
            committed = self._committed_versions(
                static.read_key_set, overlay=overlay
            )
            ver_ok = static.host_ver_ok(committed)
        key_lanes = key_launch = None
        if kl is not None:
            if present is None:
                present, metas = self._gather_key_meta(kl, overlay)
                gone = [(i, k) for i, k in dpre.meta_only or ()
                        if not present[kl.pairs.index(k[1:])]]
                if gone:
                    static = mvcc_ops.prepare_block_static([
                        mvcc_ops.TxRWSet(
                            reads=t.reads, range_reads=t.range_reads,
                            writes=[k for k in t.writes
                                    if (i, k) not in gone])
                        for i, t in enumerate(dpre.mvcc_txs)
                    ], bucketed=True)
                    static_packed = static.packed_static()
            key_lanes = self._key_lanes_launch(kl, metas)
            if key_lanes is None:
                return self._key_host_block("too_many_policies")
            key_launch = _KeyLaunch(metas, len(key_lanes[0]), kl.n_live)
        # ONE launch-time H2D: creator_idx | structural | ver_ok
        launch_vec = np.empty((t_bucket, 3), np.int32)
        launch_vec[:, 0] = creator_idx
        launch_vec[:, 1] = structural
        launch_vec[:, 2] = ver_ok
        t0 = self._t("state_fill", t0)

        if self._device_pipeline is None:
            self._device_pipeline = DeviceBlockPipeline()
        _faults.fire("validator.stage2")  # chaos hook (no-op unarmed)
        fetch2 = self._device_pipeline.run(
            handle, launch_vec, dpre.groups, static_packed,
            static.dims, t_bucket, mesh=self.mesh,
            resident=resident_pack, n_txs=len(txs), key_lanes=key_lanes,
        )
        self._t("stage2_dispatch", t0)
        return fetch2, range_phantom, key_launch

    # -- key-level endorsement: the launch half -----------------------------

    def _key_host_block(self, reason: str):
        """One more block under key-level endorsement that the exact
        host interpreter judges, and why: ``inblock_dependency`` (a tx
        touches a key whose parameter an earlier tx of the block
        writes: the repo's in-block rule stays on the host, PERF.md
        section 7), ``too_many_policies`` (the channel's table is full:
        ``MAX_KEY_POLICIES``), ``unsafe`` (a signature matches two
        principals of one policy: the count-based reduction is not
        exact there).  → ``_launch_device``'s answer for such a block."""
        self._key_host_ctr.add(1, reason=reason)
        return None, frozenset(), None

    def _gather_key_meta(self, kl, overlay):
        """(present, metadata) of ``kl.pairs`` for a block whose version
        check does not ride ``get_versions_cols`` (the generic builder's
        blocks): the same gather, asked for these keys alone."""
        up, _uv, um = self.state.get_versions_cols(
            kl.pairs, np.ones(len(kl.pairs), bool))
        if overlay is not None and overlay.updates:
            for i, pr in enumerate(kl.pairs):
                vv = overlay.updates.get(pr)
                if vv is not None:
                    up[i] = vv.value is not None
                    um[i] = (vv.metadata or None) if up[i] else None
        return up, um

    def _key_pid(self, md):
        """The policy id of the parameter in a key's stored metadata:
        -1 none, -2 one that does not parse (fails closed, as
        ``_eval_key_policy`` does), else its place in the channel's
        table, which grows here.  None: the table is full."""
        from fabric_tpu.crypto.msp import policy_from_proto
        from fabric_tpu.ledger.rwset import (
            VALIDATION_PARAMETER, decode_metadata,
        )
        from fabric_tpu.protos import policies_pb2

        raw = decode_metadata(md).get(VALIDATION_PARAMETER)
        if raw is None:
            return -1
        pid = self._key_pid_of.get(raw)
        if pid is None:
            try:
                plan = self.plugins["default"]._plan(policy_from_proto(
                    protoutil.unmarshal(
                        policies_pb2.SignaturePolicyEnvelope, raw)))
            except Exception:
                pid = -2
            else:
                if len(self._key_plans) >= MAX_KEY_POLICIES:
                    return None
                self._key_plans.append(plan)
                pid = len(self._key_plans) - 1
                self._key_plans_gauge.set(len(self._key_plans))
            self._key_pid_of[raw] = pid
        return pid

    def _key_lanes_launch(self, kl, metas):
        """The launch-time half of the key-policy lanes, inside
        ``sf.key_lanes``: each entry's policy id from its key's
        committed metadata (a C-level map over the unique keys; Python
        runs once a DISTINCT metadata value not met before), uploaded
        as one int32 an entry.  → ``DeviceBlockPipeline.run``'s
        ``key_lanes``, or None where the table cannot take a policy
        this block needs."""
        import itertools

        import jax.numpy as jnp

        with self._tracer.span("sf.key_lanes") as ksp:
            known = self._md_pid
            if len(known) > 65536:
                known.clear()
                known[None] = -1
            pid_u = np.fromiter(
                map(known.get, metas, itertools.repeat(-3)), np.int32,
                len(metas))
            for u in np.flatnonzero((pid_u == -3) & kl.want).tolist():
                pid = known.get(metas[u])
                if pid is None:
                    pid = self._key_pid(metas[u])
                    if pid is None:
                        return None
                    known[metas[u]] = pid
                pid_u[u] = pid
            # policies met since the block was prefetched (this launch's
            # among them): their groups are built here, once each
            while len(kl.groups) < len(self._key_plans):
                kl.groups.append(kl.group(self._key_plans[len(kl.groups)]))
            if kl.dev is None:
                kl.dev = self._put_group(kl.packed)
            pid_vec = np.full(len(kl.packed), -1, np.int32)
            keyed = np.flatnonzero(kl.ent_key >= 0)
            pid_vec[keyed] = pid_u[kl.ent_key[keyed]]
            pid_dev = jnp.asarray(pid_vec)
            if ksp is not None:
                ksp.attrs.update(
                    entries=int((pid_vec != -1).sum()), lanes=len(keyed),
                    policies=len(self._key_plans), bytes=pid_vec.nbytes)
            return list(kl.groups), kl.dev, pid_dev

    # -- device-resident state (fabric_tpu/state) --------------------------

    def _resident_pack(self, static, overlay):
        """Build the resident-state stage-2 operands for one flat
        block — ``(table_snapshot, u_pack [Ub,4] i32, read_pv_dev)``
        — or None when the block must take the host oracle path.  The
        slot/host-lane packing (hit slots captured atomically with
        the table snapshot, misses host-gathered + admitted, overlay
        keys forced onto overlay-valued host lanes) is the
        subsystem's ``state.build_launch_pack``; this wrapper only
        supplies the prefetch-built key index and appends the
        expected-read plane the prefetch thread already uploaded."""
        from fabric_tpu.state import build_launch_pack

        pairs = static.u_pairs
        idx = getattr(static, "u_index", None)
        if idx is None:  # built on the prefetch thread normally
            idx = static.u_index = dict(zip(pairs, range(len(pairs))))
        out = build_launch_pack(
            self.resident, pairs, self.state, overlay=overlay,
            u_index=idx,
        )
        if out is None:
            return None
        table, u_pack = out
        return (table, u_pack, static.packed_read_pv())

    def resident_commit(self, batch) -> None:
        """Apply one COMMITTED block's write-set to the resident
        version table as a delta scatter — called at the commit
        boundary by the CommitPipeline (committer thread; inline for
        barriers/serial) and by the serial ``commit_block`` path, so
        the table never misses a committed delta regardless of which
        path a block rode.  Idempotent (a replayed batch scatters the
        same values); a device failure latches the cache off, never
        changes verdicts.  No-op when the cache is off or disabled."""
        res = self.resident
        if res is None or not res.enabled or batch is None:
            return
        try:
            res.apply_batch(batch)
        except Exception as e:
            res.disable(f"commit scatter failed: {e}")
            _log.warning(
                "resident commit scatter failed (%s) — cache disabled, "
                "blocks take the host state_fill path", e,
            )

    def _flat_ver_ok(self, static, overlay, want=None):
        """[T] bool committed-version check for a flat block: one FUSED
        column gather over the UNIQUE read keys (the
        preLoadCommittedVersionOfRSet analog —
        ``statedb.get_versions_cols`` fills the arrays in a single
        backend pass, no dict round-trip), overlay overrides for the
        in-flight predecessor window applied by walking the overlay's
        (small) write set against the prefetch-built key index instead
        of probing the overlay once per unique key, then a vectorized
        per-read compare reduced per tx (VecStaticBlock.ver_ok_from_u).
        A merged multi-batch overlay needs no special casing: its
        ``updates`` mapping is already newest-wins.

        ``want``: a [U] bool mask of the keys whose metadata the gather
        brings too (key-level endorsement: the keys the block writes),
        the overlay winning for it as for versions.  → (ver_ok, the
        keys' presence, their metadata or None where ``want`` is)."""
        pairs = static.u_pairs
        U = len(pairs)
        if not U:
            return static.ver_ok_from_u(
                np.zeros(0, bool), np.zeros((0, 2), np.uint32)
            ), np.zeros(0, bool), []
        um = None
        if want is None:
            up, uv = self.state.get_versions_cols(pairs)
        else:
            up, uv, um = self.state.get_versions_cols(pairs, want)
        if overlay is not None and overlay.updates:
            idx = getattr(static, "u_index", None)
            if idx is None:  # built on the prefetch thread normally
                idx = static.u_index = dict(zip(pairs, range(U)))
            iget = idx.get
            for pr, vv in overlay.updates.items():
                ui = iget(pr)
                if ui is None:
                    continue
                if vv.value is None:  # in-flight delete
                    up[ui] = False
                else:
                    up[ui] = True
                    uv[ui] = vv.version
                if um is not None:
                    um[ui] = (vv.metadata or None) if up[ui] else None
        return static.ver_ok_from_u(up, uv), up, um

    def _finish_device(self, pending: "PendingBlock"):
        """Consume the stage-2 packed output: final codes, filter,
        update batch.  Returns None to fall back to the host path
        (consumption-unsafe policy rows)."""
        block, txs = pending.block, pending.txs
        dpre = pending.dpre
        t0 = time.perf_counter()
        out = pending.fetch2()
        t1 = self._t("device_wait", t0)
        # sync-only duration for the guard's deadline: the host-side
        # postprocess below must not count against the DEVICE lane
        self._last_device_sync_s = t1 - t0
        t0 = t1

        # consumption-unsafe rows → exact host interpreter path (the
        # key-policy groups' bits follow the namespace groups': one
        # entry a live tx each)
        kla = pending.key_launch
        counts = [len(ents) for ents in dpre.group_entries]
        if kla is not None:
            counts += [kla.n_live] * kla.n_groups
        for safe_bits, count in zip(out["safe"], counts):
            if not np.all(safe_bits[:count]):
                if kla is not None:
                    self._key_host_ctr.add(1, reason="unsafe")
                return None

        # final code assignment, vectorized — same check order as the
        # reference: creator sig → config → policy → mvcc
        sig_valid = out["sig_valid"]
        n_sig = len(sig_valid)
        policy_ok, valid, phantom = out["policy_ok"], out["valid"], out["phantom"]
        nT = len(txs)
        final = np.fromiter((ptx.code for ptx in txs), np.int32, nT)
        und = final == int(C.NOT_VALIDATED)
        cfg = np.fromiter((ptx.is_config for ptx in txs), bool, nT)
        ci_arr = np.fromiter(
            (ptx.creator_item_idx for ptx in txs), np.int64, nT
        )
        svF = np.concatenate([sig_valid, [False]])
        ci_idx = np.where((ci_arr >= 0) & (ci_arr < n_sig), ci_arr, n_sig)
        creator_fail = und & (ci_arr >= 0) & ~svF[ci_idx]
        rp = np.zeros(nT, bool)
        for i in pending.range_phantom:
            rp[i] = True
        sel = np.select(
            [~policy_ok[:nT], rp, valid[:nT], phantom[:nT]],
            [int(C.ENDORSEMENT_POLICY_FAILURE), int(C.PHANTOM_READ_CONFLICT),
             int(C.VALID), int(C.PHANTOM_READ_CONFLICT)],
            default=int(C.MVCC_READ_CONFLICT),
        )
        upd = und & ~cfg & ~creator_fail
        final[upd] = sel[upd]
        final[und & creator_fail] = int(C.BAD_CREATOR_SIGNATURE)
        for i in np.flatnonzero(cfg & und & ~creator_fail).tolist():
            final[i] = self._validate_config(block, txs[i])  # rare
        fl = final.tolist()
        for ptx, c in zip(txs, fl):
            ptx.code = c
        tx_filter = bytes(fl)
        if dpre.rwp is not None:
            batch, history = self._build_updates_flat(
                block.header.number, txs, dpre.rwp, dpre.ns_names,
                dpre.ukeys, kla, pending.overlay,
            )
        else:
            batch, history = self._build_updates(
                block.header.number, txs, overlay=pending.overlay,
                sbe=kla is not None)
        self._t("postprocess", t0)
        return tx_filter, batch, history

    def _build_updates_flat(self, block_num: int, txs, rwp, ns_names, ukeys,
                            key_launch=None, overlay=None):
        """Columnar update batch + history from the native flat write
        arrays — the batch keeps the validator's numpy slabs
        (ColumnarUpdateBatch) so the sqlite backend can apply it with
        one statement per namespace, and its lazy ``updates`` dict is
        byte-identical (incl. per-tx (ns, key) sort order) to the old
        eager build over parsed rwsets.  Key strings come from the
        already-decoded unique-key table (``ukeys``).

        ``key_launch`` (key-level endorsement in play, as
        ``_build_updates``' ``sbe``): the rows get a metadata column.
        A value write carries its key's metadata along, as the launch
        gathered it (``key_launch.metas``, by unique-key id); a
        metadata write of the same tx replaces it; an earlier delete
        in the block clears it.  No later tx of the block touches a key
        an earlier one metadata-writes (such a block is the host
        path's), so the gathered metadata is what the key holds when
        the row lands.  A metadata-only write re-puts the key's value
        (this block's, the ``overlay``'s or the state's) under its new
        metadata, and is a no-op on an absent key: rare, and an
        override of the batch's (``put``) like the pvt phase's."""
        from fabric_tpu.ledger.statedb import ColumnarUpdateBatch

        history = []
        nw = rwp.n_writes  # slice REAL rows; the arrays are capacity-sized
        nk = rwp.n_keys
        w_uid = rwp.w_uid[:nw]
        w_is_del = rwp.w_is_del[:nw]
        vo = rwp.w_val_span[:nw, 0]
        vl = rwp.w_val_span[:nw, 1]
        neg = vo < 0
        if neg.any():  # negative span = empty value, normalize to b""
            vo = np.where(neg, 0, vo)
            vl = np.where(neg, 0, vl)
        ns_of = rwp.ns_of_ukey[:nk].tolist()
        # per-uid apply rank: ONE sort of the unique-key table by
        # (ns, key) replaces the old per-tx row-tuple sorts
        order = sorted(range(nk),
                       key=lambda u: (ns_names[ns_of[u]], ukeys[u]))
        rank = np.empty(nk, np.int64)
        rank[order] = np.arange(nk)
        w_start = rwp.w_start.tolist()
        w_count = rwp.w_count.tolist()
        row_sel = []   # global row indices in final apply order
        txn_chunks = []
        for ptx in txs:
            if ptx.code != C.VALID:
                continue
            i = ptx.idx
            s, c = w_start[i], w_count[i]
            if not c:
                continue
            uids = w_uid[s:s + c]
            ord_ = np.argsort(rank[uids], kind="stable")
            row_sel.append(np.arange(s, s + c)[ord_])
            txn_chunks.append(np.full(c, i, np.int64))
            for uid in uids[ord_].tolist():
                history.append((ns_names[ns_of[uid]], ukeys[uid], i))
        if row_sel:
            rows = np.concatenate(row_sel)
            row_txnum = np.concatenate(txn_chunks)
        else:
            rows = np.zeros(0, np.int64)
            row_txnum = np.zeros(0, np.int64)
        row_uid, row_del = w_uid[rows], w_is_del[rows]
        row_meta = None
        if key_launch is not None:
            row_meta = self._row_metadata(rwp, txs, row_uid, row_del,
                                          row_txnum, key_launch.metas)
        batch = ColumnarUpdateBatch(
            block_num, ns_names, ukeys, ns_of,
            row_uid, row_del, vo[rows], vl[rows],
            row_txnum, rwp.blob, row_meta,
        )
        if key_launch is not None and rwp.n_meta and rwp.m_only[
                :rwp.n_meta].any():
            self._put_meta_only(batch, block_num, txs, rwp, ns_names,
                                ukeys, ns_of, overlay)
        return batch, history

    @staticmethod
    def _row_metadata(rwp, txs, row_uid, row_del, row_txnum, metas):
        """The metadata column of a flat block's value-write rows (in
        apply order): see ``_build_updates_flat``."""
        uids = row_uid.tolist()
        row_meta = list(map(metas.__getitem__, uids))
        if row_del.any():
            # a key deleted earlier in the block comes back bare
            dead: set = set()
            for r, (u, d) in enumerate(zip(uids, row_del.tolist())):
                if u in dead or d:
                    row_meta[r] = None
                if d:
                    dead.add(u)
        nm = rwp.n_meta
        both = np.flatnonzero(rwp.m_only[:nm] == 0) if nm else ()
        if len(both):
            # rows whose tx also writes the key's metadata: (tx, key)
            # matched through one sorted composite
            nk, n = max(1, rwp.n_keys), len(txs)
            m_comp = (np.repeat(np.arange(n), rwp.m_count[:n])[both] * nk
                      + rwp.m_uid[:nm][both])
            order = np.argsort(m_comp)
            r_comp = row_txnum * nk + row_uid
            at = np.minimum(np.searchsorted(m_comp[order], r_comp),
                            len(order) - 1)
            hit = np.flatnonzero(m_comp[order][at] == r_comp)
            m_md = rwp.m_md
            for r, j in zip(hit.tolist(), both[order[at[hit]]].tolist()):
                row_meta[r] = m_md[j]
        return row_meta

    def _put_meta_only(self, batch, block_num, txs, rwp, ns_names, ukeys,
                       ns_of, overlay) -> None:
        """The valid txs' metadata-only writes, in block order, as
        overrides of ``batch``: the key's value re-put under the new
        metadata at the tx's version; nothing where the key is absent."""
        m_start, m_count = rwp.m_start.tolist(), rwp.m_count.tolist()
        for ptx in txs:
            if ptx.code != C.VALID or not m_count[ptx.idx]:
                continue
            s = m_start[ptx.idx]
            for j in range(s, s + m_count[ptx.idx]):
                if not rwp.m_only[j]:
                    continue
                uid = int(rwp.m_uid[j])
                ns, key = ns_names[ns_of[uid]], ukeys[uid]
                prev = batch.updates.get((ns, key))
                if prev is None and overlay is not None:
                    prev = overlay.updates.get((ns, key))
                if prev is None:
                    prev = self.state.get_state(ns, key)
                if prev is None or prev.value is None:
                    continue
                batch.put(ns, key, prev.value, (block_num, ptx.idx),
                          metadata=rwp.m_md[j])

    def _mvcc_inputs(self, txs, overlay=None):
        mvcc_txs = []
        all_read_keys = set()
        for ptx in txs:
            if ptx.rwset is None or not ptx.undetermined:
                mvcc_txs.append(mvcc_ops.TxRWSet(reads=[], writes=[], range_reads=[]))
                continue
            # re-execute range queries against COMMITTED state: a key
            # committed after simulation but inside the range is a
            # phantom even with no in-block writer (the reference
            # merges committed state into the range re-check,
            # validation/validator.go:205-247, combined_iterator.go:44).
            # Per-result version staleness rides the normal read checks;
            # in-block writers ride the id-interval kernel check.
            if self._committed_range_phantom(ptx, overlay) or (
                overlay is not None and _overlay_range_phantom(ptx, overlay)
            ):
                ptx.code = C.PHANTOM_READ_CONFLICT
                mvcc_txs.append(mvcc_ops.TxRWSet(reads=[], writes=[], range_reads=[]))
                continue
            reads, writes, rqs = ptx.rwset.mvcc_form()
            # metadata-only writes are writers iff they APPLY — the key
            # must exist in committed state (or the in-flight
            # predecessor's batch); a no-op metadata write on an absent
            # key must not conflict later readers (the reference's
            # applyWriteSet leaves the batch untouched there)
            for ns_name, n in ptx.rwset.ns.items():
                for k in n.metadata_writes:
                    if k not in n.writes and self._key_exists(
                        ns_name, k, overlay
                    ):
                        writes.append(("pub", ns_name, k))
            mvcc_txs.append(
                mvcc_ops.TxRWSet(reads=reads, writes=writes, range_reads=rqs)
            )
            all_read_keys.update(k for k, _ in reads)
        return mvcc_txs, self._committed_versions(all_read_keys, overlay=overlay)

    def _key_exists(self, ns: str, key: str, overlay) -> bool:
        if overlay is not None:
            vv = overlay.updates.get((ns, key))
            if vv is not None:
                return vv.value is not None
        return self.state.get_state(ns, key) is not None

    def _committed_versions(self, all_read_keys, overlay=None) -> dict:
        """Bulk-load committed versions for a set of mvcc-form keys
        (the preLoadCommittedVersionOfRSet analog,
        validation/validator.go:27-78).

        ``overlay`` is the predecessor block's UpdateBatch whose ledger
        commit may still be applying concurrently: its entries OVERRIDE
        whatever the racy state read returned — per-key reads are
        atomic and the override is exactly the value the in-flight
        apply will land, so the result equals a serialized read."""
        committed: dict = {}
        if all_read_keys:
            pub_keys = [
                (k[1], k[2]) for k in all_read_keys if k[0] == "pub"
            ]
            vers = self.state.get_versions_bulk(pub_keys)
            for k in all_read_keys:
                if k[0] == "pub" and (k[1], k[2]) in vers:
                    committed[k] = vers[(k[1], k[2])]
                elif k[0] == "pvt":
                    v = self.state.get_version(f"{k[1]}${k[2]}#hashed", _hex(k[3]))
                    if v is not None:
                        committed[k] = v
            if overlay is not None:
                for k in all_read_keys:
                    bk = (
                        (k[1], k[2]) if k[0] == "pub"
                        else (f"{k[1]}${k[2]}#hashed", _hex(k[3]))
                    )
                    vv = overlay.updates.get(bk)
                    if vv is None:
                        continue
                    if vv.value is None:  # delete
                        committed.pop(k, None)
                    else:
                        committed[k] = vv.version
        return committed

    def _committed_range_phantom(self, ptx, overlay=None) -> bool:
        """True iff some committed key falls inside a recorded range
        query but is missing from its recorded results (end_key == ''
        means unbounded, per the reference's open-ended iterators).

        Under pipelining the state walk may still see keys the
        IN-FLIGHT predecessor deleted — those are subtracted via the
        overlay (the insert arm is _overlay_range_phantom)."""
        for ns_name, n in ptx.rwset.ns.items():
            for start, end, results in n.range_queries:
                recorded = {k for k, _ in results}
                for key, _vv in self.state.get_state_range(ns_name, start, end):
                    if key in recorded:
                        continue
                    if overlay is not None:
                        ov = overlay.updates.get((ns_name, key))
                        if ov is not None and ov.value is None:
                            continue  # predecessor deleted it
                    return True
        return False

    def _validate_config(self, block, ptx) -> int:
        """Config-tx validation: structure must parse as a
        ConfigEnvelope and the configured processor must accept it —
        CONFIG envelopes are never rubber-stamped
        (v20/validator.go:397-419)."""
        try:
            env = protoutil.unmarshal(common_pb2.Envelope, block.data.data[ptx.idx])
            payload = protoutil.unmarshal(common_pb2.Payload, env.payload)
            cfg_env = protoutil.unmarshal(configtx_pb2.ConfigEnvelope, payload.data)
        except Exception:
            return C.BAD_PAYLOAD
        if block.header.number == 0:
            # genesis config is the channel's trust anchor — verified
            # out-of-band by the joining admin, not by prior state
            return C.VALID
        if self.config_processor is not None:
            try:
                return self.config_processor.validate_config_tx(ptx, cfg_env)
            except Exception:
                return C.INVALID_OTHER_REASON
        return C.VALID

    def _build_updates(self, block_num: int, txs, overlay=None, sbe=False):
        """Update batch + history for the block's VALID txs.  With
        ``sbe`` (key-level endorsement in play): metadata writes of
        valid txs commit — combined with a value write they ride the
        same put; alone they re-put the existing value with new
        metadata and a version bump (a no-op when the key does not
        exist, the reference's semantics); plain value writes PRESERVE
        the key's existing metadata; deletes clear it."""
        from fabric_tpu.ledger.rwset import encode_metadata

        batch = UpdateBatch()
        history = []

        def _prev(ns, key):
            vv = batch.updates.get((ns, key))
            if vv is not None:
                return vv
            if overlay is not None:
                vv = overlay.updates.get((ns, key))
                if vv is not None:
                    return vv
            return self.state.get_state(ns, key)

        for ptx in txs:
            if ptx.code != C.VALID or ptx.rwset is None:
                continue
            ver = (block_num, ptx.idx)
            for ns_name in sorted(ptx.rwset.ns):
                n = ptx.rwset.ns[ns_name]
                mws = n.metadata_writes if sbe else {}
                for key in sorted(n.writes):
                    val = n.writes[key]
                    if val is None:
                        batch.delete(ns_name, key, ver)
                    elif not sbe:
                        batch.put(ns_name, key, val, ver)
                    else:
                        if key in mws:
                            md = encode_metadata(mws[key])
                        else:
                            prev = _prev(ns_name, key)
                            md = (
                                prev.metadata
                                if prev is not None and prev.value is not None
                                else None
                            )
                        batch.put(ns_name, key, val, ver, metadata=md)
                    history.append((ns_name, key, ptx.idx))
                for key in sorted(mws):
                    if key in n.writes:
                        continue  # combined above
                    prev = _prev(ns_name, key)
                    if prev is None or prev.value is None:
                        continue  # metadata write on absent key: no-op
                    # NO history entry: the reference's history DB
                    # records value writes only (KvRwSet.Writes)
                    batch.put(
                        ns_name, key, prev.value, ver,
                        metadata=encode_metadata(mws[key]),
                    )
                for coll in sorted(n.hashed):
                    hns = f"{ns_name}${coll}#hashed"
                    for kh, (vh, is_del) in sorted(n.hashed[coll].get("writes", {}).items()):
                        if is_del:
                            batch.delete(hns, _hex(kh), ver)
                        else:
                            batch.put(hns, _hex(kh), vh, ver)
        return batch, history


class DefaultValidation(ValidationPlugin):
    """Built-in plugin (analog builtin/default_validation.go +
    v20/validation_logic.go): evaluate one (tx, namespace) pair's
    chaincode policy over the tx's verified endorsements.  Plans are
    compiled once per policy object and cached (the reference caches
    per plugin^channel, plugin_validator.go)."""

    def __init__(self):
        # keyed by the (frozen, hashable) policy AST itself — id()-keys
        # could alias a recycled address after a config update GCs the
        # old policy object
        self._plan_cache: dict[object, pol.BatchPlan] = {}

    def _plan(self, policy) -> pol.BatchPlan:
        plan = self._plan_cache.get(policy)
        if plan is None:
            plan = pol.compile_plan(policy)
            self._plan_cache[policy] = plan
        return plan

    def _match_row(self, plan: pol.BatchPlan, serialized: bytes, ident):
        """Memoized principal-match row for one endorser identity —
        a block re-presents the same few certs thousands of times."""
        cache = getattr(plan, "_row_cache", None)
        if cache is None:
            cache = plan._row_cache = {}
        hit = cache.get(serialized)
        if hit is not None and hit[0] is ident:
            return hit[1]
        # pin the Identity object in the entry: a hit requires the SAME
        # object, so an MSP-cache invalidation (new Identity instances)
        # can never be served a stale principal-match row
        row = np.array([p.matched_by(ident) for p in plan.principals], bool)
        cache[serialized] = (ident, row)
        return row

    def validate_batch_group(self, ctx: BlockValidationCtx, group):
        """ONE vectorized policy reduction per distinct policy over all
        its (tx, namespace) entries — the per-tx closure walk of the
        reference (cauthdsl.go:39) becomes a [T, S, P] count reduction;
        the exact consumption interpreter only runs for the rare rows
        where a signature matches two distinct principals."""
        out = [False] * len(group)
        by_policy: dict[int, list] = {}
        policies: dict[int, object] = {}
        for idx, (ptx, ns) in enumerate(group):
            info = ctx.policy_provider.info(ns)
            key = id(info.policy)
            policies[key] = info.policy
            by_policy.setdefault(key, []).append((idx, ptx))
        for key, entries in by_policy.items():
            policy = policies[key]
            plan = self._plan(policy)
            P = len(plan.principals)
            T = len(entries)
            S = max((len(p.endorsements) for _, p in entries), default=0) or 1
            M = np.zeros((T, S, P), bool)
            for t, (_, ptx) in enumerate(entries):
                for s, (ser, ident) in enumerate(ptx.endorsements):
                    if ctx.sig_valid[ptx.endo_item_idx[s]]:
                        M[t, s] = self._match_row(plan, ser, ident)
            safe = plan.consumption_safe_batch(M)
            ok = plan.evaluate_counts_batch(M)
            for t, (idx, ptx) in enumerate(entries):
                if safe[t]:
                    out[idx] = bool(ok[t])
                else:
                    m = M[t, : len(ptx.endorsements)]
                    out[idx] = bool(pol.evaluate(policy, m))
        return out


def _overlay_range_phantom(ptx, overlay) -> bool:
    """True iff a write of the in-flight predecessor block falls inside
    one of this tx's recorded range queries but is missing from its
    recorded results — the overlay arm of the committed-range
    re-execution (deleted keys ride the per-result read checks)."""
    for ns_name, n in ptx.rwset.ns.items():
        for start, end, results in n.range_queries:
            recorded = {k for k, _ in results}
            for (ns, key), vv in overlay.updates.items():
                if ns != ns_name or vv.value is None:
                    continue
                if key >= start and (not end or key < end) and key not in recorded:
                    return True
    return False


def _sig_item(ident: Identity, message: bytes, der_sig: bytes):
    r, s = sig_to_ints(der_sig)
    qx, qy = ident.public_numbers
    return (int.from_bytes(hashlib.sha256(message).digest(), "big"), r, s, qx, qy)


def _hex(b: bytes) -> str:
    return b.hex()
