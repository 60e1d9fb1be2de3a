"""Batched MVCC read-set validation as a JAX/XLA TPU kernel.

The reference validates a block's transactions SERIALLY: for each tx in
order, every read (namespace, key, version) is compared against the
committed state version, reads of keys already written by an earlier
*valid* tx in the same block are conflicts, range-query results are
re-checked for phantoms, and the write-set of each valid tx is applied
so later txs see it (reference:
core/ledger/kvledger/txmgmt/validation/validator.go:81-118
`validateAndPrepareBatch`, `validateKVRead` :179-200, range/phantom
:205-247; bulk preload hint `preLoadCommittedVersionOfRSet` :27-78).

TPU-first reformulation (not a port — the serial loop doesn't map to
hardware):

1. **Version checks are embarrassingly parallel**: the host bulk-loads
   committed versions for every read key (one state-DB gather, as the
   reference already does), the kernel compares all [T, R] reads at
   once.
2. **Intra-block conflicts become one dense compare**: with block-local
   dense key ids, reader-vs-writer conflict is a [T, T] matrix computed
   by a broadcast equality over [T, T, R, W] (XLA fuses the reduce; at
   1000-tx blocks this is microseconds on the VPU).  Range-query
   phantom constraints fold into the same matrix because keys get ids
   in lexicographic order, so a range is an id interval.
3. **The sequential validity chain becomes a fixpoint**: valid[j] =
   ver_ok[j] ∧ ¬∃i<j (valid[i] ∧ conflict[j,i]).  Jacobi iteration
   from the optimistic assignment converges in max conflict-chain-depth
   rounds (each round one [T,T]·[T] matvec); the unique fixpoint equals
   the serial result because dependencies form a DAG over tx order.

Key-id space: the HOST assigns dense ids to the union of keys touched
by the block, sorted lexicographically per (namespace, key) — including
hashed private-collection keys, which get ids in a disjoint namespace
range (reference hashed-key checks: validator.go:249-283).  Versions
are (block_height, tx_num) uint32 pairs; absent keys carry a present
flag (nil-version semantics of validateKVRead).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp


def mvcc_validate(
    read_keys,      # [T, R] int32 block-local key ids; -1 = padding
    read_present,   # [T, R] bool: simulation saw the key as existing
    read_vers,      # [T, R, 2] uint32 (block, txnum) seen at simulation
    comm_present,   # [T, R] bool: key exists in committed state
    comm_vers,      # [T, R, 2] uint32 committed version
    write_keys,     # [T, W] int32 block-local key ids; -1 = padding
    rq_lo,          # [T, Q] int32 range-query id interval start; -1 = pad
    rq_hi,          # [T, Q] int32 exclusive interval end
    pre_ok,         # [T] bool: upstream validity (sigs, policy, structure)
):
    """Returns (valid [T] bool, conflict [T] bool, phantom [T] bool).

    `valid` matches the serial reference semantics exactly; `conflict`
    / `phantom` distinguish MVCC_READ_CONFLICT from
    PHANTOM_READ_CONFLICT for the TRANSACTIONS_FILTER codes.
    """
    # per-read version check vs committed state (parallel over all);
    # the conflict matrices + fixpoint live in mvcc_validate_hostver
    pad = read_keys < 0
    ver_eq = jnp.all(read_vers == comm_vers, axis=-1)
    ok = jnp.where(
        read_present & comm_present,
        ver_eq,
        read_present == comm_present,  # both absent ok; presence flip = stale
    )
    ver_ok = jnp.all(ok | pad, axis=-1)  # [T]
    return mvcc_validate_hostver(
        read_keys, ver_ok, write_keys, rq_lo, rq_hi, pre_ok
    )


mvcc_validate_jit = jax.jit(mvcc_validate)


def mvcc_validate_hostver(
    read_keys,      # [T, R] int32 block-local key ids; -1 = padding
    ver_ok_host,    # [T] bool: per-tx committed-version check, HOST-side
    write_keys,     # [T, W] int32 block-local key ids; -1 = padding
    rq_lo,          # [T, Q] int32 range-query id interval start; -1 = pad
    rq_hi,          # [T, Q] int32 exclusive interval end
    pre_ok,         # [T] bool: upstream validity (sigs, policy, structure)
):
    """``mvcc_validate`` with the per-read committed-version compare
    done on HOST (StaticBlock.host_ver_ok): the compare is elementwise
    and state-dependent, so shipping the committed presence/version
    arrays to the device bought nothing but two latency-bound
    launch-time H2D transfers.  The device keeps what it is
    uniquely good at — the [T,T] conflict matrices and the validity
    fixpoint (validator.go:81-118's serial loop, reformulated)."""
    T = read_keys.shape[0]
    ver_ok = ver_ok_host & pre_ok

    w_valid = (write_keys >= 0)[None, :, None, :]
    r_valid = (read_keys >= 0)[:, None, :, None]
    eq = (
        read_keys[:, None, :, None] == write_keys[None, :, None, :]
    ) & w_valid & r_valid
    direct = jnp.any(eq, axis=(2, 3))

    q_valid = (rq_lo >= 0)[:, None, :, None]
    in_range = (
        (write_keys[None, :, None, :] >= rq_lo[:, None, :, None])
        & (write_keys[None, :, None, :] < rq_hi[:, None, :, None])
        & w_valid & q_valid
    )
    phantom_m = jnp.any(in_range, axis=(2, 3))

    order = jnp.tril(jnp.ones((T, T), jnp.bool_), k=-1)
    direct = direct & order
    phantom_m = phantom_m & order
    conflict_m = (direct | phantom_m).astype(jnp.float32)

    def body(state):
        v, _, it = state
        hit = conflict_m @ v.astype(jnp.float32) > 0
        return ver_ok & ~hit, v, it + 1

    def cond(state):
        v, prev, it = state
        return jnp.any(v != prev) & (it <= T + 1)

    valid, _, _ = jax.lax.while_loop(cond, body, (ver_ok, ~ver_ok, jnp.int32(0)))

    vf = valid.astype(jnp.float32)
    conflict = (direct.astype(jnp.float32) @ vf > 0) & ver_ok
    phantom = (phantom_m.astype(jnp.float32) @ vf > 0) & ver_ok
    return valid, conflict, phantom


def mvcc_in_shardings(mesh, arrays, *, trailing: int = 1):
    """Partition-rule shardings for a ``jax.jit(mvcc_validate, ...)``
    dispatch: one ``"mvcc_frame"`` NamedSharding per operand (axis 0 —
    the tx lane — split over the mesh data axis, trailing dims
    replicated), plus ``trailing`` extra 1-D frames for ``pre_ok``-style
    tail operands.

    This is the declarative replacement for hand-built
    ``batch_sharding`` tuples: every MVCC launch frame routes through
    the same PartitionRules family, so the rules table (and the FT019
    unruled-sharding check) see one canonical construction site.
    Returns ``None`` when ``mesh`` is None (unsharded dispatch).
    """
    if mesh is None:
        return None
    from fabric_tpu.parallel.mesh import sharding_for

    specs = tuple(sharding_for(mesh, "mvcc_frame", a.ndim) for a in arrays)
    specs += tuple(
        sharding_for(mesh, "mvcc_frame", 1) for _ in range(trailing)
    )
    return specs


# ---------------------------------------------------------------------------
# Host-side block preparation


@dataclass
class TxRWSet:
    """One transaction's read/write set in host form.

    reads: list of (key, version | None) — version is (block, txnum),
        None means the key was absent at simulation time.
    writes: list of keys written (values don't matter for validation).
    range_reads: list of (start_key, end_key_exclusive) phantom
        constraints; the per-result version checks ride in `reads`.
    Keys are arbitrary hashable tuples, e.g. (namespace, key) or
    (namespace, collection, key_hash).
    """

    reads: list
    writes: list
    range_reads: list


@dataclass
class StaticBlock:
    """State-INDEPENDENT device arrays for one block + the recipe to
    fill the committed-version arrays later.

    The split exists for the commit pipeline: everything here can be
    built in the prefetch thread while the previous block is still on
    device; only `fill_committed` (a gather against the state DB) must
    wait for the predecessor's state commit."""

    read_keys: np.ndarray      # [T, R] int32
    read_present: np.ndarray   # [T, R] bool
    read_vers: np.ndarray      # [T, R, 2] uint32
    write_keys: np.ndarray     # [T, W] int32
    rq_lo: np.ndarray          # [T, Q] int32
    rq_hi: np.ndarray          # [T, Q] int32
    read_fill: list            # [(j, a, key)] for committed-array fill
    read_key_set: set          # union of read keys
    _jnp: tuple = None         # uploaded static arrays (see upload())

    def fill_committed(self, committed: dict):
        """→ (comm_present [T,R] bool, comm_vers [T,R,2] uint32)."""
        T, R = self.read_keys.shape
        comm_present = np.zeros((T, R), bool)
        comm_vers = np.zeros((T, R, 2), np.uint32)
        for j, a, k in self.read_fill:
            cv = committed.get(k)
            if cv is not None:
                comm_present[j, a] = True
                comm_vers[j, a] = cv
        return comm_present, comm_vers

    def upload(self) -> None:
        """Push the state-independent arrays to device NOW — called
        from the prefetch thread so launch-time H2D is only the two
        committed-version arrays (small H2D transfers are
        latency-bound, so moving them off the critical path matters
        more than their size suggests)."""
        if self._jnp is None:
            self._jnp = (
                jnp.asarray(self.read_keys), jnp.asarray(self.read_present),
                jnp.asarray(self.read_vers), jnp.asarray(self.write_keys),
                jnp.asarray(self.rq_lo), jnp.asarray(self.rq_hi),
            )

    def device_args(self, committed: dict):
        """Assemble the full `mvcc_validate` argument tuple (minus
        pre_ok) in signature order."""
        comm_present, comm_vers = self.fill_committed(committed)
        self.upload()
        a = self._jnp
        return (
            a[0], a[1], a[2], jnp.asarray(comm_present),
            jnp.asarray(comm_vers), a[3], a[4], a[5],
        )

    def host_ver_ok(self, committed: dict) -> np.ndarray:
        """[T] bool: the per-read committed-version compare of
        ``mvcc_validate`` done on host numpy — bit-identical to the
        kernel's reduction (validateKVRead semantics: version equality
        when both present, presence flip = stale, padding inert)."""
        comm_present, comm_vers = self.fill_committed(committed)
        pad = self.read_keys < 0
        ver_eq = (self.read_vers == comm_vers).all(axis=-1)
        ok = np.where(
            self.read_present & comm_present,
            ver_eq,
            self.read_present == comm_present,
        )
        return np.logical_or(ok, pad).all(axis=-1)

    def device_args_hostver(self, committed: dict):
        """`mvcc_validate_hostver` argument tuple (minus pre_ok):
        static uploaded arrays + the ONE state-dependent [T] bool."""
        return self.device_args_verok(self.host_ver_ok(committed))

    def device_args_verok(self, ver_ok: np.ndarray):
        """`mvcc_validate_hostver` args from an already-computed [T]
        host version check."""
        self.upload()
        a = self._jnp
        return (a[0], jnp.asarray(ver_ok), a[3], a[4], a[5])

    @property
    def dims(self) -> tuple:
        """(R, W, Q) — the packed-static column split."""
        return (self.read_keys.shape[1], self.write_keys.shape[1],
                self.rq_lo.shape[1])

    def packed_static(self):
        """[T, R+W+2Q] int32 on device — read_keys | write_keys |
        rq_lo | rq_hi in ONE H2D transfer (the stage-2 hostver path
        slices by static offsets inside the jit)."""
        p = getattr(self, "_packed", None)
        if p is None:
            p = self._packed = self.pack()
        return p

    def pack(self, write_keys=None):
        """:meth:`packed_static`'s array, uncached, with ``write_keys``
        in the place of the block's own (the launch that finds a
        metadata-only write's key absent blanks its slot)."""
        return jnp.asarray(np.concatenate(
            [self.read_keys,
             self.write_keys if write_keys is None else write_keys,
             self.rq_lo, self.rq_hi], axis=1))

    def packed_read_pv(self):
        """[T, R, 3] int32 on device — (read_present, read_ver_block,
        read_ver_txnum) per read slot, the EXPECTED side of the
        per-read committed-version compare.  State-INDEPENDENT, so the
        device-resident state path (fabric_tpu/state) uploads it from
        the prefetch thread; the committed side is then gathered from
        the resident version table INSIDE the fused stage-2 program
        instead of being host-filled per block.  Versions ride as raw
        int32 bit patterns (equality-only compare — exact)."""
        p = getattr(self, "_packed_rpv", None)
        if p is None:
            T, R = self.read_keys.shape
            rpv = np.zeros((T, R, 3), np.int32)
            rpv[:, :, 0] = self.read_present
            rpv[:, :, 1:3] = self.read_vers.view(np.int32)
            p = self._packed_rpv = jnp.asarray(rpv)
        return p


def prepare_block_static(txs: list[TxRWSet], bucketed: bool = False) -> StaticBlock:
    """Build the state-independent device arrays for `mvcc_validate`.

    Key ids are assigned in lexicographic key order so range bounds map
    to id intervals over the block's key universe (sufficient for
    in-block phantom detection: only in-block writes can phantom a
    range within a block).

    bucketed: round T/R/W/Q up to powers of two so consecutive blocks
    of similar shape share one compiled executable (padding rows carry
    key id −1 and are inert).
    """
    from fabric_tpu.utils.batching import block_shapes

    universe = set()
    read_key_set = set()
    for tx in txs:
        for k, _ in tx.reads:
            universe.add(k)
            read_key_set.add(k)
        universe.update(tx.writes)
    for tx in txs:
        for lo, hi in tx.range_reads:
            universe.add(lo)  # ids for bounds; hi handled via bisect below
    skeys = sorted(universe)
    kid = {k: i for i, k in enumerate(skeys)}

    import bisect

    T = len(txs)
    R = max(1, max((len(t.reads) for t in txs), default=1))
    W = max(1, max((len(t.writes) for t in txs), default=1))
    Q = max(1, max((len(t.range_reads) for t in txs), default=1))
    if bucketed:
        sh = block_shapes(txs=T, reads=R, writes=W, ranges=Q)
        T, (R, W, Q) = sh.txs, sh.dims

    read_keys = np.full((T, R), -1, np.int32)
    read_present = np.zeros((T, R), bool)
    read_vers = np.zeros((T, R, 2), np.uint32)
    write_keys = np.full((T, W), -1, np.int32)
    rq_lo = np.full((T, Q), -1, np.int32)
    rq_hi = np.full((T, Q), -1, np.int32)
    read_fill: list = []

    for j, tx in enumerate(txs):
        for a, (k, ver) in enumerate(tx.reads):
            read_keys[j, a] = kid[k]
            if ver is not None:
                read_present[j, a] = True
                read_vers[j, a] = ver
            read_fill.append((j, a, k))
        for a, k in enumerate(tx.writes):
            write_keys[j, a] = kid[k]
        for a, (lo, hi) in enumerate(tx.range_reads):
            rq_lo[j, a] = bisect.bisect_left(skeys, lo)
            rq_hi[j, a] = bisect.bisect_left(skeys, hi)

    return StaticBlock(
        read_keys=read_keys, read_present=read_present, read_vers=read_vers,
        write_keys=write_keys, rq_lo=rq_lo, rq_hi=rq_hi,
        read_fill=read_fill, read_key_set=read_key_set,
    )


def prepare_block(txs: list[TxRWSet], committed: dict, bucketed: bool = False):
    """Build the full device-array tuple for `mvcc_validate` (static
    arrays + committed-version fill in one go)."""
    return prepare_block_static(txs, bucketed=bucketed).device_args(committed)


@dataclass
class VecStaticBlock(StaticBlock):
    """StaticBlock variant fed by the native mvcc_prep flat arrays:
    committed-version fill is a numpy gather over per-unique-key
    arrays instead of a per-read Python loop.  Key-id ORDER is
    arbitrary (hash interning) — valid because blocks with range
    queries never take this path (mvccprep.cpp forces the Python
    fallback for them)."""

    r_rows: np.ndarray = None   # [nr] tx row per flat read
    r_cols: np.ndarray = None   # [nr] slot per flat read
    r_uid: np.ndarray = None    # [nr] unique-key id per flat read
    u_composite: list = None    # [n_keys] composite mvcc keys
    u_pairs: list = None        # [n_keys] (ns, key) pairs (validator)
    mo_rows: np.ndarray = None  # metadata-only writes: tx row,
    mo_cols: np.ndarray = None  # ``write_keys`` column,
    mo_uid: np.ndarray = None   # unique-key id

    def fill_committed(self, committed: dict):
        U = len(self.u_composite)
        up = np.zeros(U, bool)
        uv = np.zeros((U, 2), np.uint32)
        for u, k in enumerate(self.u_composite):
            cv = committed.get(k)
            if cv is not None:
                up[u] = True
                uv[u] = cv
        T, R = self.read_keys.shape
        comm_present = np.zeros((T, R), bool)
        comm_vers = np.zeros((T, R, 2), np.uint32)
        if len(self.r_rows):
            comm_present[self.r_rows, self.r_cols] = up[self.r_uid]
            comm_vers[self.r_rows, self.r_cols] = uv[self.r_uid]
        return comm_present, comm_vers

    def ver_ok_from_u(self, up: np.ndarray, uv: np.ndarray) -> np.ndarray:
        """[T] bool from per-UNIQUE-key committed (present, version)
        arrays — the flat path's host-side validateKVRead reduction
        (no [T,R] scatter, no composite-key dict)."""
        Tb = self.read_keys.shape[0]
        if not len(self.r_rows):
            return np.ones(Tb, bool)
        rp = self.read_present[self.r_rows, self.r_cols]
        rv = self.read_vers[self.r_rows, self.r_cols]
        cp = up[self.r_uid]
        ver_eq = (rv == uv[self.r_uid]).all(axis=1)
        okr = np.where(rp & cp, ver_eq, rp == cp)
        bad_per_tx = np.bincount(
            self.r_rows[~okr], minlength=Tb
        )
        return bad_per_tx == 0


def prepare_block_from_flat(n_txs: int, rwp, composite_keys: list) -> VecStaticBlock:
    """Native mvcc_prep flat arrays → device-static arrays with pure
    numpy scatters (no per-read Python loop).  ``composite_keys``:
    [n_keys] mvcc-form keys for state lookups."""
    from fabric_tpu.utils.batching import block_shapes

    nr, nw = rwp.n_reads, rwp.n_writes
    rc = rwp.r_count[:n_txs]
    wc = rwp.w_count[:n_txs]
    # a metadata write to a key its tx writes no value to is a writer
    # too (it bumps the key's version): one more column of its tx's
    # row, after the value writes.  Whether the key exists, and so
    # whether the write applies at all, is known only at launch
    # (``BlockValidator._launch_device`` blanks the slot where it does
    # not): ``mo_*`` say where those slots are.
    mo = np.flatnonzero(rwp.m_only[:rwp.n_meta]) if rwp.n_meta else ()
    if len(mo):
        mo_rows = np.repeat(np.arange(n_txs),
                            rwp.m_count[:n_txs])[mo].astype(np.intp)
        first = np.flatnonzero(np.r_[True, mo_rows[1:] != mo_rows[:-1]])
        run = np.arange(len(mo)) - np.repeat(
            first, np.diff(np.r_[first, len(mo)]))
        mo_cols = (wc[mo_rows] + run).astype(np.intp)
        n_writers = int((mo_cols + 1).max())
    else:
        n_writers = 0
    sh = block_shapes(txs=n_txs, reads=int(rc.max()) if n_txs else 0,
                      writes=max(int(wc.max()) if n_txs else 0, n_writers))
    Tb, (R, W, _q) = sh.txs, sh.dims

    read_keys = np.full((Tb, R), -1, np.int32)
    read_present = np.zeros((Tb, R), bool)
    read_vers = np.zeros((Tb, R, 2), np.uint32)
    write_keys = np.full((Tb, W), -1, np.int32)
    rq_lo = np.full((Tb, 1), -1, np.int32)
    rq_hi = np.full((Tb, 1), -1, np.int32)

    if nr:
        r_rows = np.repeat(np.arange(n_txs), rc).astype(np.intp)
        r_cols = (np.arange(nr) - np.repeat(rwp.r_start[:n_txs], rc)).astype(np.intp)
        r_uid = rwp.r_uid[:nr]
        read_keys[r_rows, r_cols] = r_uid
        read_present[r_rows, r_cols] = rwp.r_has_ver[:nr].astype(bool)
        read_vers[r_rows, r_cols] = rwp.r_ver[:nr].astype(np.uint32)
    else:
        r_rows = np.zeros(0, np.intp)
        r_cols = np.zeros(0, np.intp)
        r_uid = np.zeros(0, np.int32)
    if nw:
        w_rows = np.repeat(np.arange(n_txs), wc).astype(np.intp)
        w_cols = (np.arange(nw) - np.repeat(rwp.w_start[:n_txs], wc)).astype(np.intp)
        write_keys[w_rows, w_cols] = rwp.w_uid[:nw]
    if len(mo):
        write_keys[mo_rows, mo_cols] = rwp.m_uid[mo]

    read_key_set = {composite_keys[u] for u in np.unique(r_uid)} if nr else set()
    return VecStaticBlock(
        read_keys=read_keys, read_present=read_present, read_vers=read_vers,
        write_keys=write_keys, rq_lo=rq_lo, rq_hi=rq_hi,
        read_fill=[], read_key_set=read_key_set,
        r_rows=r_rows, r_cols=r_cols, r_uid=r_uid,
        u_composite=composite_keys,
        **(dict(mo_rows=mo_rows, mo_cols=mo_cols, mo_uid=rwp.m_uid[mo])
           if len(mo) else {}),
    )


def mvcc_validate_block(txs: list[TxRWSet], committed: dict, pre_ok=None):
    """End-to-end host helper: prepare + run kernel → numpy bools."""
    arrays = prepare_block(txs, committed)
    if pre_ok is None:
        pre_ok = np.ones(len(txs), bool)
    outs = mvcc_validate_jit(*arrays, jnp.asarray(pre_ok))
    for o in outs:
        if hasattr(o, "copy_to_host_async"):
            o.copy_to_host_async()  # overlap readback latency
    valid, conflict, phantom = outs
    return np.asarray(valid), np.asarray(conflict), np.asarray(phantom)


def mvcc_serial_reference(txs: list[TxRWSet], committed: dict, pre_ok=None):
    """Direct re-implementation of the reference's serial semantics
    (validator.go:81-118) — the oracle the kernel is property-tested
    against."""
    if pre_ok is None:
        pre_ok = [True] * len(txs)
    updates: set = set()
    out = []
    for tx, ok0 in zip(txs, pre_ok):
        ok = bool(ok0)
        if ok:
            for k, ver in tx.reads:
                if k in updates:
                    ok = False
                    break
                if committed.get(k) != ver:
                    ok = False
                    break
        if ok:
            for lo, hi in tx.range_reads:
                if any(lo <= w < hi for w in updates):
                    ok = False
                    break
        if ok:
            updates.update(tx.writes)
        out.append(ok)
    return out
