"""Fused device stage for block validation: policy reduction + MVCC in
ONE dispatch consuming the verify batch's device-resident output.

Why fusion is the TPU-shaped design: the naive pipeline syncs the
device twice per block (signature bits → host policy walk → MVCC
dispatch → results).  Each sync pays a full device round trip.  Here
the boolean signature vector NEVER leaves the device: stage 2 gathers
it per endorsement,
runs the batch-plan policy reduction (fabric_tpu.crypto.policy
compile_plan semantics — counts vs leaf ranks, the vectorized
formulation of cauthdsl's consumption walk), AND-reduces per tx across
namespaces, feeds the result into the MVCC fixpoint as pre_ok, and
returns one packed int8 vector.  One dispatch, one readback, per block.

Exactness: the count-based policy path is exact iff no signature
matches two distinct principal columns (policy.py consumption_safe).
The device computes that predicate per entry and the host REDOES the
rare unsafe blocks on the exact interpreter path (validator fallback) —
fast path stays exact, slow path stays correct.

Reference anchors: plugin dispatch plugindispatcher/dispatcher.go:102,
policy evaluation common/cauthdsl/cauthdsl.go:24-110, MVCC
validation/validator.go:81-118, per-tx fan-out v20/validator.go:193.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from fabric_tpu.crypto import policy as pol
from fabric_tpu.observe import ledger as _ledger
from fabric_tpu.ops import mvcc as mvcc_ops


@dataclass(frozen=True)
class PlanSig:
    """Static (hashable) shape of one policy group inside the fused
    program — the jit cache key component."""

    leaf_principal: tuple
    leaf_rank: tuple
    gates: tuple  # ((n, (child_slots...)), ...)
    n_principals: int
    e_bucket: int
    s_bucket: int


def plan_sig(plan: pol.BatchPlan, e_bucket: int, s_bucket: int) -> PlanSig:
    return PlanSig(
        leaf_principal=tuple(plan.leaf_principal),
        leaf_rank=tuple(plan.leaf_rank),
        gates=tuple((n, tuple(c)) for n, c in plan.gates),
        n_principals=len(plan.principals),
        e_bucket=e_bucket,
        s_bucket=s_bucket,
    )


def _policy_reduce(sig_padded, match, endo_idx, sig: PlanSig):
    """[Eb] (ok, safe) for one policy group.

    sig_padded: [n_sig + 1] bool with a trailing False — endo_idx −1
    (padding) gathers the False lane."""
    n_sig = sig_padded.shape[0] - 1
    idx = jnp.where(endo_idx >= 0, endo_idx, n_sig)
    ev = sig_padded[idx]  # [Eb, S]
    M = match & ev[:, :, None]  # [Eb, S, P]
    counts = M.sum(axis=1)  # [Eb, P]
    cols = jnp.asarray(sorted(set(sig.leaf_principal)), jnp.int32)
    safe = (M[:, :, cols].sum(axis=2) <= 1).all(axis=1)
    leaf_p = jnp.asarray(sig.leaf_principal, jnp.int32)
    ranks = jnp.asarray(sig.leaf_rank, jnp.int32)
    vals = list((ranks[None, :] < counts[:, leaf_p]).T)  # n_leaves × [Eb]
    for n, children in sig.gates:
        acc = jnp.zeros(match.shape[0], jnp.int32)
        for c in children:
            acc = acc + vals[c].astype(jnp.int32)
        vals.append(acc >= n)
    return vals[-1], safe


def _resident_ver_ok(static_p, table, u_pack, read_pv, R: int,
                     u_bucket: int):
    """[T] bool committed-version check computed ON DEVICE from the
    resident version table — the device twin of
    ``VecStaticBlock.ver_ok_from_u`` (bit-equal: same validateKVRead
    reduction, committed rows gathered from ``table`` for resident
    slots and from the host-provided lanes of ``u_pack`` for misses
    and in-flight-overlay overrides).

      table    [cap, 3] i32: present | ver_block | ver_txnum
      u_pack   [Ub, 4] i32: slot (−1 = host lane) | present | vb | vt
      read_pv  [T, R, 3] i32: expected present | vb | vt per read
    """
    slot = u_pack[:, 0]
    use_host = slot < 0
    trow = table[jnp.where(slot >= 0, slot, 0)]          # [Ub, 3]
    urow = jnp.where(use_host[:, None], u_pack[:, 1:4], trow)
    up = jnp.concatenate(
        [urow[:, 0] != 0, jnp.zeros((1,), bool)]
    )  # + sentinel row for padding reads
    uv = jnp.concatenate(
        [urow[:, 1:3], jnp.zeros((1, 2), urow.dtype)]
    )
    rk = static_p[:, :R]                                  # [T, R]
    idx = jnp.where(rk >= 0, rk, u_bucket)
    cp = up[idx]                                          # [T, R]
    cv = uv[idx]                                          # [T, R, 2]
    rp = read_pv[:, :, 0] != 0
    rv = read_pv[:, :, 1:3]
    ver_eq = jnp.all(rv == cv, axis=-1)
    okr = jnp.where(rp & cp, ver_eq, rp == cp)
    return jnp.all(okr | (rk < 0), axis=-1)


def build_stage2(t_bucket: int, n_sig: int, group_sigs: tuple,
                 static_dims: tuple, resident_dims: tuple | None = None,
                 key_dims: tuple | None = None):
    """→ jitted stage2(sig_valid, launch_vec, *group_packed,
    static_packed[, table, u_pack, read_pv][, *key_group_packed,
    key_entries, key_pid]) → packed int8.

    Inputs arrive PACKED — one array per H2D transfer (each device_put
    carries a fixed host overhead whatever its size, so the
    interface is shaped around transfer count, not array count):
      launch_vec    [T, 3] i32: creator_idx | structural | ver_ok_host
      group_packed  [Eb, S·P + S + 1] i32: match | endo_idx | tx_of
      static_packed [T, R + W + 2Q] i32: read/write keys, rq bounds
    Output layout (host unpacks by static offsets):
      [0:T]    valid        [T:2T]  conflict      [2T:3T] phantom
      [3T:4T]  creator_ok   [4T:5T] policy_ok
      [5T:5T+n_sig] sig_valid
      then per group: [Eb] safe bits.

    ``resident_dims`` = (u_bucket, capacity) compiles the
    DEVICE-RESIDENT state variant (fabric_tpu/state): launch_vec's
    ver_ok column is ignored and the per-read committed-version check
    runs on device against the resident version table
    (:func:`_resident_ver_ok`) — the host ``state_fill`` gather only
    covers the miss/overlay lanes shipped inside ``u_pack``.

    ``key_dims`` = (key-policy PlanSigs, entry bucket) compiles the
    KEY-LEVEL ENDORSEMENT variant (statebased/validator_keylevel.go):
    a written key that carries a validation parameter is held to that
    policy INSTEAD of its namespace's, one without to the namespace's.
      key_group_packed  one pack a key policy the channel has met, laid
                        out as a namespace group's, one entry a live tx:
                        every tx under every known policy
      key_entries [Kb, 2] i32: tx (−1 = padding) | the namespace group
                        of the key's namespace.  One row a (tx, touched
                        key), and one a (tx, namespace) in which the tx
                        touches no key; state-independent
      key_pid     [Kb] i32, the one launch-time upload: the policy of
                        the key's committed parameter (its place among
                        the key groups), −1 = none: the namespace's
                        verdict decides the entry, −2 = a parameter
                        that does not parse: fails closed
    ``policy_ok[tx]`` is then the AND over the tx's entries, each
    judged by ``where(pid < 0, ns_ok[group, tx], key_ok[pid, tx])``;
    the key groups' safe bits follow the namespace groups' in the
    output.
    """
    R, W, Q = static_dims
    key_sigs, _key_bucket = key_dims if key_dims is not None else ((), 0)

    def stage2(sig_valid, launch_vec, *rest):
        g = len(group_sigs)
        gpacked = rest[:g]
        static_p = rest[g]
        creator_idx = launch_vec[:, 0]
        structural_ok = launch_vec[:, 1] != 0
        if resident_dims is not None:
            table, u_pack, read_pv = rest[g + 1:g + 4]
            ver_ok = _resident_ver_ok(
                static_p, table, u_pack, read_pv, R, resident_dims[0]
            )
        else:
            ver_ok = launch_vec[:, 2] != 0
        # two sentinel lanes past the batch: n_sig = missing creator
        # (False), n_sig+1 = HOST-verified creator (True — idemix
        # identities have no batch lane; validator encodes them as -2)
        svF = jnp.concatenate([
            sig_valid, jnp.zeros((1,), bool), jnp.ones((1,), bool),
        ])
        ns = sig_valid.shape[0]
        creator_ok = svF[jnp.where(
            creator_idx >= 0, creator_idx,
            jnp.where(creator_idx == -2, ns + 1, ns),
        )]

        safes = []

        def reduce_group(gp, sig):
            """One policy group → (each entry's slot in [T + 1], the
            last being padding's; its verdict, 1 for padding)."""
            S, P = sig.s_bucket, sig.n_principals
            match = (gp[:, : S * P] != 0).reshape(-1, S, P)
            endo_idx = gp[:, S * P: S * P + S]
            tx_of = gp[:, -1]
            ok_g, safe_g = _policy_reduce(svF, match, endo_idx, sig)
            safes.append(safe_g)
            t = jnp.where(tx_of >= 0, tx_of, t_bucket)
            contrib = jnp.where(tx_of >= 0, ok_g, True).astype(jnp.int8)
            return t, contrib

        if key_dims is None:
            policy_ok = jnp.ones(t_bucket + 1, jnp.int8)
            for gi, sig in enumerate(group_sigs):
                t, contrib = reduce_group(gpacked[gi], sig)
                policy_ok = policy_ok.at[t].min(contrib)
        else:
            # each group's verdicts by tx (a tx without an entry reads
            # 1), namespace groups first: [G + K, T + 1]
            kbase = len(rest) - len(key_sigs) - 2
            ok_all = jnp.stack([
                jnp.ones(t_bucket + 1, jnp.int8).at[t].min(contrib)
                for t, contrib in (
                    [reduce_group(gpacked[gi], sig)
                     for gi, sig in enumerate(group_sigs)]
                    + [reduce_group(rest[kbase + ki], sig)
                       for ki, sig in enumerate(key_sigs)])])
            entries, pid = rest[-2], rest[-1]
            etx = entries[:, 0]
            t = jnp.where(etx >= 0, etx, t_bucket)
            row = jnp.where(pid >= 0, g + pid, entries[:, 1])
            e_ok = jnp.where(pid == -2, 0, ok_all[row, t])
            policy_ok = jnp.ones(t_bucket + 1, jnp.int8).at[t].min(
                jnp.where(etx >= 0, e_ok, 1).astype(jnp.int8))
        policy_ok = policy_ok[:t_bucket].astype(bool)

        pre_ok = structural_ok & creator_ok & policy_ok
        valid, conflict, phantom = mvcc_ops.mvcc_validate_hostver(
            static_p[:, :R], ver_ok, static_p[:, R:R + W],
            static_p[:, R + W:R + W + Q], static_p[:, R + W + Q:],
            pre_ok,
        )

        parts = [valid, conflict, phantom, creator_ok, policy_ok, sig_valid]
        parts.extend(safes)
        return jnp.concatenate([p.astype(jnp.int8) for p in parts])

    return jax.jit(stage2)


_PROGRAM_CACHE: dict = {}


class DeviceBlockPipeline:
    """Caches compiled stage-2 programs keyed by static block shape +
    the set of policy plans in play.

    The cache is MODULE-global: the key (buckets + PlanSig tuples) is
    fully structural, so validators across channels/instances share the
    traced program — a fresh validator must not pay a retrace."""

    def __init__(self):
        self._cache = _PROGRAM_CACHE
        from fabric_tpu.ops_metrics import global_registry

        reg = global_registry()
        # stage-2 telemetry: dispatch cost (host side of the fused
        # launch) and the structural-program cache size — a growing
        # gauge on a stable workload means retraces are leaking in
        self._dispatch_hist = reg.histogram(
            "device_stage2_dispatch_seconds",
            "host-side fused stage-2 dispatch time (s)",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, float("inf")),
        )
        self._cache_gauge = reg.gauge(
            "device_stage2_programs", "compiled stage-2 program cache size"
        )
        self._shards_hist = reg.histogram(
            "device_mesh_shards",
            "mesh shards per fused stage-1+stage-2 launch (1 = unsharded)",
            buckets=(1, 2, 4, 8, 16, float("inf")),
        )

    def run(self, handle, launch_vec, groups, static_packed, static_dims,
            pre_ok_pad_len, mesh=None, resident=None, n_txs=None,
            key_lanes=None):
        """handle: p256v3.VerifyHandle; launch_vec np [T,3] i32;
        groups: list of (plan, packed_dev [Eb, S·P+S+1], Eb, S);
        static_packed: device [T, R+W+2Q] i32; static_dims: (R, W, Q).
        Returns a zero-arg fetch → dict of numpy arrays.

        Every shape in the program key is one ``utils/batching.
        block_shapes`` gave the caller (T = ``pre_ok_pad_len``, the
        verify lanes, each group's E and S, the dims), which is what
        lets ``BlockValidator.warmup`` walk ``channel_shapes`` for the
        tx shapes a channel has committed and leave no block like them
        a program to lower.  ``n_txs``: the
        block's real txs, for the launch ledger's row beside the T
        bucket.

        ``mesh``: parallel.mesh data mesh — the per-tx (launch_vec,
        static_packed) and per-endorsement (group) lanes shard axis 0
        over it; XLA gathers the policy scatter-min and the MVCC
        fixpoint's validity vector with collectives.  The signature
        vector (``handle.device_out``) keeps whatever sharding the
        verify dispatch gave it.  Bit-equal to unsharded: every device
        value is integer/boolean (the f32 fixpoint matvec sums 0/1
        counts < 2^24, exact in any reduction order).

        ``resident``: (table_dev [cap,3] i32, u_pack np [Ub,4] i32,
        read_pv_dev [T,R,3] i32) — the device-resident state operands
        (fabric_tpu/state): the program variant computes ver_ok ON
        DEVICE from the resident version table, launch_vec's ver_ok
        column is inert.  The table keeps whatever sharding the
        residency manager gave it (axis 0 over the same data mesh);
        u_pack is the only launch-time state upload.

        ``key_lanes``: (key groups, as ``groups``; entries_dev [Kb, 2]
        i32; pid_dev [Kb] i32, uploaded by the caller at this launch)
        — the key-level endorsement operands (:func:`build_stage2`).
        The fetch's ``safe`` list then carries the key groups' bits
        after the namespace groups'."""
        t_bucket = pre_ok_pad_len
        n_sig = int(handle.device_out.shape[0])
        gsigs = tuple(
            plan_sig(plan, eb, s) for plan, _, eb, s in groups
        )
        resident_dims = None
        if resident is not None:
            table_dev, u_pack, read_pv_dev = resident
            resident_dims = (int(u_pack.shape[0]),
                             int(table_dev.shape[0]))
        key = (t_bucket, n_sig, gsigs, static_dims, resident_dims)
        key_dims = None
        if key_lanes is not None:
            kgroups, entries_dev, pid_dev = key_lanes
            key_dims = (tuple(plan_sig(plan, eb, s)
                              for plan, _, eb, s in kgroups),
                        int(pid_dev.shape[0]))
            # a channel without key policies keys its program as ever
            key += (key_dims,)
        fn = self._cache.get(key)
        compiled = fn is None
        if compiled:
            fn = self._cache[key] = build_stage2(
                t_bucket, n_sig, gsigs, static_dims,
                resident_dims=resident_dims, key_dims=key_dims,
            )
            self._cache_gauge.set(len(self._cache))
        # launch ledger (observe/ledger.py): the program-cache verdict
        # is EXACT here — this class owns the cache.  The launch-time
        # H2D is the packed launch vector (+ the resident slot frame);
        # groups/static uploaded from the prefetch thread already.
        h2d = launch_vec.nbytes
        if resident is not None:
            h2d += resident[1].nbytes
        if key_lanes is not None:
            h2d += int(pid_dev.nbytes)  # one int32 a key entry
        from fabric_tpu.parallel import mesh as pmesh

        # partition-rule verdict BEFORE the puts: a mesh-configured
        # dispatch whose per-tx planes cannot shard (ragged axis 0)
        # runs single-device — tag the ledger row so /launches shows
        # it instead of mystery device_wait (untagged when no mesh)
        sharded = None
        if mesh is not None:
            data_planes = [launch_vec, static_packed]
            data_planes += [gp for _, gp, _, _ in groups]
            if resident is not None:
                data_planes.append(resident[2])
            if key_lanes is not None:
                data_planes += [gp for _, gp, _, _ in kgroups]
            sharded = all(pmesh.will_shard(mesh, a) for a in data_planes)
        rec = _ledger.launch("stage2", compiled=compiled,
                             lanes=t_bucket, h2d_bytes=h2d,
                             sharded=sharded,
                             shape=None if n_txs is None
                             else {"txs": int(n_txs)})
        # the fused path never calls the verify handle's fetch (the
        # signature vector stays on device as a stage-2 operand), so
        # its ledger record would never close: complete it
        # enqueue-only here — its compile/dispatch/h2d stand, and the
        # fused chain's device time is owned by THIS record's sync
        # (splitting verify execute out of one fused dependency chain
        # is not host-observable, so the ledger does not pretend to)
        vrec = getattr(handle, "rec", None)
        if vrec is not None:
            vrec.complete()
        t0 = time.perf_counter()
        self._shards_hist.observe(pmesh.data_axis_size(mesh))
        # every operand goes up under its family's partition rule
        # (fabric_tpu/parallel/mesh.py) — the declarative table is the
        # single sharding authority (FT019 polices the boundary)
        args = [handle.device_out,
                pmesh.shard(mesh, "launch_frame",
                            jnp.asarray(launch_vec))]
        args += [pmesh.shard(mesh, "policy_table", gp)
                 for _, gp, _, _ in groups]
        args += [pmesh.shard(mesh, "static_pack", static_packed)]
        if resident is not None:
            # table keeps the manager's key-range sharding; u_pack is
            # per-key (not per-tx) so it rides replicated — it is tiny
            args += [table_dev,
                     pmesh.shard(mesh, "unique_read_pack",
                                 jnp.asarray(u_pack)),
                     pmesh.shard(mesh, "read_versions", read_pv_dev)]
        if key_lanes is not None:
            # per-key entries, not per-tx: they ride as they were put
            args += [pmesh.shard(mesh, "policy_table", gp)
                     for _, gp, _, _ in kgroups]
            args += [entries_dev, pid_dev]
        from fabric_tpu.observe import device_annotation

        if rec is not None:
            # transient HBM pin: this block's launch frames (verify
            # output + packed operands) pinned on device until the
            # fetch — ADDITIVE, so depth-N concurrent blocks sum and
            # the watermark records the true concurrent peak; released
            # when the record completes
            rec.pin_hbm("launch_frames", sum(
                int(getattr(a, "nbytes", 0)) for a in args
            ))
        # lines the fused stage-2 dispatch up with the XLA timeline
        # when a jax profiler capture is running (real-TPU rounds)
        with device_annotation("fabtpu.stage2_dispatch"):
            packed = fn(*args)
        if hasattr(packed, "copy_to_host_async"):
            packed.copy_to_host_async()
        if rec is not None:
            rec.dispatched()
            rec.pin_hbm("outputs", int(getattr(packed, "nbytes", 0)))
        self._dispatch_hist.observe(time.perf_counter() - t0)

        def fetch():
            if rec is not None:
                rec.sync_begin()
            flat = np.asarray(packed)
            if rec is not None:
                rec.sync_end(d2h_bytes=flat.nbytes)
            flat = flat.astype(bool)
            T = t_bucket
            out = {
                "valid": flat[0:T],
                "conflict": flat[T:2 * T],
                "phantom": flat[2 * T:3 * T],
                "creator_ok": flat[3 * T:4 * T],
                "policy_ok": flat[4 * T:5 * T],
                "sig_valid": flat[5 * T:5 * T + n_sig],
            }
            off = 5 * T + n_sig
            safes = []
            for sig in gsigs + (key_dims[0] if key_dims else ()):
                safes.append(flat[off:off + sig.e_bucket])
                off += sig.e_bucket
            out["safe"] = safes
            return out

        return fetch
