"""Multi-device (mesh-sharded) production-dispatch tests — the first
pytest battery to actually USE conftest's 8 forced host devices.

Layers:

1. the verify kernel sharded over the data mesh is bit-equal to
   single-device (``verify_launch(mesh=...)``);
2. the FUSED stage-2 program (policy reduction + MVCC fixpoint
   consuming the device-resident signature vector) sharded through
   ``DeviceBlockPipeline.run(mesh=...)`` is bit-equal on every output
   lane, for 2- and 8-device meshes;
3. the depth-2 CommitPipeline with mesh sharding AND multi-block
   launch coalescing (``submit_many``/``preprocess_many``) produces
   filters and state identical to the serial unsharded oracle —
   crypto-free (ec_ref signatures), so it runs on containers without
   the ``cryptography`` package;
4. the full BlockValidator (real MSP identities) sharded vs
   single-device — crypto-gated, the seed condition on this container.

Shapes are chosen to reuse compile-cache entries other tier-1 tests
already create (buckets 16/64) — a new (shape × sharding) pair costs a
fresh XLA compile on the 2-core host.
"""

import json

import numpy as np
import jax.numpy as jnp
import pytest

from fabric_tpu import protoutil as pu
from fabric_tpu.crypto import ec_ref
from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
from fabric_tpu.ops import mvcc as mvcc_ops
from fabric_tpu.ops import p256v3 as v3
from fabric_tpu.parallel import mesh as pmesh
from fabric_tpu.peer.pipeline import CommitPipeline


def test_mesh_resolution():
    # conftest forces 8 host devices: auto (-1) sees all of them
    assert pmesh.resolve_mesh(0) is None
    m = pmesh.resolve_mesh(-1)
    assert m is not None and m.size == 8
    assert pmesh.resolve_mesh(2).size == 2
    assert pmesh.resolve_mesh(1) is None  # 1-device mesh = overhead only
    # ragged axis 0 degrades to unsharded instead of crashing
    arr = jnp.zeros((10, 3), jnp.int32)
    out = pmesh.shard_batch(pmesh.resolve_mesh(8), arr)
    assert out.shape == (10, 3)


@pytest.fixture(scope="module")
def key():
    return ec_ref.SigningKey.generate()


def _items(key, n, tag=b"md", bad_stride=3):
    out = []
    for i in range(n):
        e = ec_ref.digest_int(b"%s-%d" % (tag, i))
        r, s = key.sign_digest(e)
        if bad_stride and i % bad_stride == 2:
            s = ec_ref.N - s  # high-S reject lane
        out.append((e, r, s, *key.public))
    return out


def test_sharded_verify_bit_equal(key):
    """verify_launch over the full 8-device host mesh must reproduce
    the single-device accept set bit for bit (the verify is per-lane
    independent; sharding only partitions the batch dim)."""
    items = _items(key, 16)
    solo = v3.verify_launch(items)()
    mesh8 = pmesh.resolve_mesh(-1)
    assert v3.verify_launch(items, mesh=mesh8)() == solo
    assert any(solo) and not all(solo)


def test_sharded_fused_stage2_bit_equal():
    """The fused stage-1+stage-2 dispatch (DeviceBlockPipeline.run)
    sharded over 2- and 8-device meshes is bit-equal to single-device
    on every output lane — policy scatter-min and the MVCC fixpoint
    collectives included."""
    from fabric_tpu.crypto import policy as pol
    from fabric_tpu.peer.device_block import DeviceBlockPipeline

    rng = np.random.default_rng(20260803)
    policy = pol.from_dsl("OutOf(2, 'O1.peer', 'O2.peer', 'O3.peer')")
    plan = pol.compile_plan(policy)
    P = len(plan.principals)
    S, Eb, T, n_sig = 4, 16, 16, 16
    handle = v3.VerifyHandle(jnp.asarray(rng.random(n_sig) < 0.75), n_sig)
    match = np.zeros((Eb, S, P), np.int32)
    endo_idx = np.full((Eb, S), -1, np.int32)
    tx_of = np.full(Eb, -1, np.int32)
    for e in range(12):
        tx_of[e] = e % T
        for s in range(3):
            endo_idx[e, s] = (e * 3 + s) % n_sig
            match[e, s, s % P] = 1
    gp = np.zeros((Eb, S * P + S + 1), np.int32)
    gp[:, :S * P] = match.reshape(Eb, -1)
    gp[:, S * P:S * P + S] = endo_idx
    gp[:, -1] = tx_of
    # dependent writes so the fixpoint actually iterates (conflict
    # chains cross shard boundaries on the 8-way mesh)
    txs = [
        mvcc_ops.TxRWSet(
            reads=[("k%d" % i, (1, 0))],
            writes=["k%d" % ((i + 1) % 12)],
            range_reads=[],
        )
        for i in range(12)
    ]
    static = mvcc_ops.prepare_block_static(txs, bucketed=True)
    launch_vec = np.zeros((T, 3), np.int32)
    launch_vec[:, 0] = np.arange(T) % n_sig
    launch_vec[:12, 1] = 1
    launch_vec[:12, 2] = 1

    pipe = DeviceBlockPipeline()
    base = pipe.run(handle, launch_vec, [(plan, jnp.asarray(gp), Eb, S)],
                    static.packed_static(), static.dims, T)()
    for nd in (2, 8):
        mesh = pmesh.resolve_mesh(nd)
        groups = [(plan, pmesh.shard_batch(mesh, jnp.asarray(gp)), Eb, S)]
        got = pipe.run(handle, launch_vec, groups, static.packed_static(),
                       static.dims, T, mesh=mesh)()
        for k in ("valid", "conflict", "phantom", "creator_ok",
                  "policy_ok", "sig_valid"):
            assert np.array_equal(base[k], got[k]), (nd, k)
        assert all(
            np.array_equal(a, b) for a, b in zip(base["safe"], got["safe"])
        ), nd
    # something actually validated and something conflicted
    assert base["valid"][:12].any() and not base["valid"][:12].all()


# ---------------------------------------------------------------------------
# crypto-free pipelined equivalence: a device-backed toy validator


from dataclasses import dataclass  # noqa: E402


@dataclass
class _Ptx:
    txid: str
    idx: int
    is_config: bool = False


@dataclass
class _Pending:
    block: object
    txs: list
    raw: list
    overlay: object
    extra: object
    fetch: object  # device VerifyHandle — synced at validate_finish

    @property
    def txids(self):
        return {p.txid for p in self.txs if p.txid}


class DeviceToyValidator:
    """ToyValidator (tests/test_commit_pipeline.py) whose launch path
    REALLY dispatches the p256v3 device verify — per-tx ec_ref
    signatures ride ``verify_launch`` (solo) or ``verify_launch_many``
    (coalesced prefetch), optionally mesh-sharded — so the CommitPipeline
    equivalence below exercises the production device lane without the
    ``cryptography`` package.

    tx wire form: {"id", "sig": [e, r, s, qx, qy] (decimal strings),
    "reads": {key: [blk, tx]}, "writes": {key: val}}.
    """

    VALID, BADSIG, DUP, MVCC = 0, 4, 2, 11

    def __init__(self, state, mesh=None, chunk=0, pool=None,
                 recode_device=False):
        self.state = state
        self.mesh = mesh
        self.chunk = int(chunk)
        self.pool = pool
        self.recode_device = bool(recode_device)
        self.coalesced_calls = 0
        self.launch_order = []

    @staticmethod
    def _decode(block):
        raw = [json.loads(bytes(d)) for d in block.data.data]
        items = [tuple(int(x) for x in t["sig"]) for t in raw]
        return raw, items

    def preprocess(self, block):
        raw, items = self._decode(block)
        fetch = v3.verify_launch(items, chunk=self.chunk or None,
                                 mesh=self.mesh, pool=self.pool,
                                 recode_device=self.recode_device)
        return raw, fetch

    def preprocess_many(self, blocks):
        self.coalesced_calls += 1
        decoded = [self._decode(b) for b in blocks]
        fetches = v3.verify_launch_many(
            [items for _, items in decoded],
            chunk=self.chunk or None, mesh=self.mesh, pool=self.pool,
            recode_device=self.recode_device,
        )
        return [(raw, f) for (raw, _), f in zip(decoded, fetches)]

    def validate_launch(self, block, pre=None, overlay=None,
                        extra_txids=None):
        raw, fetch = pre if pre is not None else self.preprocess(block)
        self.launch_order.append((block.header.number, overlay is not None))
        txs = [_Ptx(t["id"], i) for i, t in enumerate(raw)]
        return _Pending(block, txs, raw, overlay, extra_txids, fetch)

    def _version(self, key, overlay):
        if overlay is not None:
            vv = overlay.updates.get(("ns", key))
            if vv is not None:
                return None if vv.value is None else list(vv.version)
        vv = self.state.get_state("ns", key)
        return None if vv is None else list(vv.version)

    def validate_finish(self, pend):
        bits = pend.fetch()  # device sync — the production seam
        codes = []
        batch = UpdateBatch()
        num = pend.block.header.number
        seen = set(pend.extra or ())
        for i, (ptx, t) in enumerate(zip(pend.txs, pend.raw)):
            if not bits[i]:
                codes.append(self.BADSIG)
                continue
            if ptx.txid in seen:
                codes.append(self.DUP)
                continue
            seen.add(ptx.txid)
            ok = all(
                self._version(k, pend.overlay) == want
                for k, want in t.get("reads", {}).items()
            )
            if not ok:
                codes.append(self.MVCC)
                continue
            codes.append(self.VALID)
            for k, val in t.get("writes", {}).items():
                batch.put("ns", k, val.encode(), (num, ptx.idx))
        return bytes(codes), batch, []


def _device_stream(key, n_blocks=6, n_tx=8):
    """Dependent block stream (overlay + stale lanes like
    test_commit_pipeline._stream) with REAL per-tx signatures; every
    third signature is corrupted so the device verdicts matter."""
    blocks, prev = [], b""
    for n in range(n_blocks):
        txs = []
        for i in range(n_tx):
            e = ec_ref.digest_int(b"tx%d_%d" % (n, i))
            r, s = key.sign_digest(e)
            if i % 3 == 2:
                s = ec_ref.N - s  # high-S → device rejects
            t = {
                "id": f"tx{n}_{i}",
                "sig": [str(v) for v in (e, r, s, *key.public)],
                "writes": {f"k{n}_{i}": f"v{n}"},
            }
            if n > 0 and i == 0:
                t["reads"] = {f"k{n-1}_0": [n - 1, 0]}  # fresh via overlay
            if n > 0 and i == 1:
                t["reads"] = {f"k{n-1}_1": [0, 0]}      # stale → MVCC
            txs.append(t)
        blk = pu.new_block(n, prev)
        for t in txs:
            blk.data.data.append(json.dumps(t).encode())
        blk = pu.finalize_block(blk)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)
    return blocks


def _device_stream_deep(key, n_blocks=6, n_tx=6):
    """Depth-3 shape with REAL signatures: RW dependencies spanning
    BOTH in-flight predecessors — block n reads block n−1's AND block
    n−2's writes at the written versions (fresh only through the
    merged overlay chain), a per-block stale k→k+2 lane, and the usual
    corrupted-signature lane so device verdicts stay load-bearing."""
    blocks, prev = [], b""
    for n in range(n_blocks):
        txs = []
        for i in range(n_tx):
            e = ec_ref.digest_int(b"dtx%d_%d" % (n, i))
            r, s = key.sign_digest(e)
            if i == 2:
                s = ec_ref.N - s  # high-S → device rejects
            t = {
                "id": f"dtx{n}_{i}",
                "sig": [str(v) for v in (e, r, s, *key.public)],
                "writes": {f"k{n}_{i}": f"v{n}"},
            }
            if n > 0 and i == 0:
                t["reads"] = {f"k{n-1}_0": [n - 1, 0]}   # k→k+1 fresh
            if n > 1 and i == 1:
                t["reads"] = {f"k{n-2}_1": [n - 2, 1]}   # k→k+2 fresh
            if n > 1 and i == 4:
                t["reads"] = {f"k{n-2}_4": [0, 0]}       # stale → MVCC
            txs.append(t)
        blk = pu.new_block(n, prev)
        for t in txs:
            blk.data.data.append(json.dumps(t).encode())
        blk = pu.finalize_block(blk)
        prev = pu.block_header_hash(blk.header)
        blocks.append(blk)
    return blocks


def _run_device_pipe(blocks, depth, mesh=None, coalesce=0, pool=None,
                     recode_device=False, chunk=0):
    state = MemVersionedDB()
    v = DeviceToyValidator(state, mesh=mesh, pool=pool,
                           recode_device=recode_device, chunk=chunk)
    filters = []

    def commit_fn(res):
        state.apply_updates(res.batch, (res.block.header.number, 0))

    with CommitPipeline(v, commit_fn, depth=depth,
                        coalesce_blocks=coalesce) as pipe:
        if coalesce >= 2:
            for r in pipe.submit_many(blocks):
                filters.append((r.block.header.number, list(r.tx_filter)))
        else:
            for b in blocks:
                r = pipe.submit(b)
                if r is not None:
                    filters.append(
                        (r.block.header.number, list(r.tx_filter))
                    )
        r = pipe.flush()
        if r is not None:
            filters.append((r.block.header.number, list(r.tx_filter)))
    filters.sort()
    return filters, dict(state._data), v


def test_sharded_coalesced_pipeline_matches_serial(key):
    """The tentpole acceptance gate: depth-2 CommitPipeline with the
    verify dispatch mesh-sharded over 2 devices AND coalesced 3 blocks
    per launch must produce filters and final state identical to the
    serial unsharded oracle — and it must have actually coalesced."""
    blocks = _device_stream(key, n_blocks=6, n_tx=8)
    f_serial, s_serial, _ = _run_device_pipe(blocks, depth=1)
    f_shard, s_shard, v = _run_device_pipe(
        blocks, depth=2, mesh=pmesh.resolve_mesh(2), coalesce=3
    )
    assert f_shard == f_serial
    assert s_shard == s_serial
    assert v.coalesced_calls == 2  # 6 blocks in groups of 3
    # depth-2 actually pipelined (overlay launches happened)
    assert any(ov for _, ov in v.launch_order)
    # the device verdicts are load-bearing: bad-sig lanes rejected
    for _, flt in f_serial:
        assert flt[2] == DeviceToyValidator.BADSIG
        assert DeviceToyValidator.VALID in flt


def test_depth3_device_pipeline_matches_serial(key):
    """THE depth-3 acceptance gate through the REAL device lane:
    a stream whose conflict chains span both in-flight predecessors
    (k→k+1 and k→k+2 fresh reads, k→k+2 stale lane, corrupted-sig
    lanes) must produce filters and final state identical to the
    serial oracle at depth 3 — solo, chunked (the double-buffered
    dispatch under the pipeline), and mesh-sharded + coalesced."""
    blocks = _device_stream_deep(key, n_blocks=6, n_tx=6)
    f1, s1, _ = _run_device_pipe(blocks, depth=1)
    # the stream exercises what it claims: bad-sig lanes rejected,
    # fresh k→k+2 lanes valid, stale lanes MVCC-failed
    for n, flt in f1:
        assert flt[2] == DeviceToyValidator.BADSIG
        if n > 1:
            assert flt[1] == DeviceToyValidator.VALID
            assert flt[4] == DeviceToyValidator.MVCC

    f3, s3, v = _run_device_pipe(blocks, depth=3)
    assert f3 == f1
    assert s3 == s1
    assert all(ov for n, ov in v.launch_order if n >= 1)

    f3c, s3c, _ = _run_device_pipe(blocks, depth=3, chunk=16)
    assert f3c == f1 and s3c == s1

    f3m, s3m, vm = _run_device_pipe(
        blocks, depth=3, mesh=pmesh.resolve_mesh(2), coalesce=3
    )
    assert f3m == f1 and s3m == s1
    assert vm.coalesced_calls == 2


def test_pooled_staging_pipeline_matches_serial(key):
    """The host-staging acceptance gate: depth-2 CommitPipeline with
    pooled host staging (2 workers), recode-on-device, the verify
    dispatch sharded over the full 8-device mesh AND 3-block launch
    coalescing must produce filters and final state identical to the
    serial unpooled/unsharded/host-recode oracle.  The coalesced
    3×bucket-16 concatenation pads to 64 lanes, so the pool really
    shards (two 32-lane slabs per staging call)."""
    from fabric_tpu.parallel.hostpool import HostStagePool

    blocks = _device_stream(key, n_blocks=6, n_tx=8)
    f_serial, s_serial, _ = _run_device_pipe(blocks, depth=1)
    with HostStagePool(2) as pool:
        f_pool, s_pool, v = _run_device_pipe(
            blocks, depth=2, mesh=pmesh.resolve_mesh(8), coalesce=3,
            pool=pool, recode_device=True,
        )
        stats = pool.stats()
    assert f_pool == f_serial
    assert s_pool == s_serial
    assert v.coalesced_calls == 2
    assert any(ov for _, ov in v.launch_order)  # depth-2 pipelined
    assert stats["tasks"] > 0  # the pool actually staged shards
    # device verdicts are load-bearing under pooling+recode too
    for _, flt in f_pool:
        assert flt[2] == DeviceToyValidator.BADSIG
        assert DeviceToyValidator.VALID in flt


def _signed_network(n_tx: int, n_blocks: int):
    """chip_smoke's network (3 orgs, 2-of-3) and stream (each tx reads
    a cold and a hot preloaded key, rewrites the cold one and writes a
    fresh one), tiny, over an in-memory state → (blocks, fresh_state,
    mgr, prov)."""
    import chip_smoke

    size = chip_smoke.Size(n_tx=n_tx, n_blocks=n_blocks, preload_keys=64,
                           hot_keys=8)
    mgr, prov, endorsers, client = chip_smoke.build_network()
    blocks, _plan = chip_smoke.build_blocks(
        size, np.random.default_rng(7), endorsers, client
    )

    def fresh_state():
        seed = UpdateBatch()
        for j in range(size.preload_keys):
            seed.put(chip_smoke.CC, chip_smoke.key_name(j), b"genesis",
                     chip_smoke.PRELOAD_VERSION)
        db = MemVersionedDB()
        db.apply_updates(seed, chip_smoke.PRELOAD_VERSION)
        return db

    return blocks, fresh_state, mgr, prov


def test_pooled_block_validator_preprocess_many(tmp_path):
    """BlockValidator._preprocess_many_pooled (parse fan-out + pooled
    device_pre + pooled coalesced staging) vs the serial
    preprocess_many: identical filters and update batches through
    validate_launch/finish.  Crypto-gated — the seed condition on
    containers without the ``cryptography`` package."""
    pytest.importorskip("cryptography")
    from fabric_tpu.peer.validator import BlockValidator
    from fabric_tpu.protos import common_pb2

    blocks, fresh_state, mgr, prov = _signed_network(6, 2)

    def run(workers, recode):
        state = fresh_state()
        v = BlockValidator(mgr, prov, state, host_stage_workers=workers,
                           recode_device=recode)
        out = []
        copies = []
        for blk in blocks:
            b = common_pb2.Block()
            b.CopyFrom(blk)
            copies.append(b)
        pres = v.preprocess_many(copies)
        for b, pre in zip(copies, pres):
            flt, batch, history = v.validate_finish(
                v.validate_launch(b, pre=pre)
            )
            state.apply_updates(batch, (b.header.number, 0))
            out.append((list(flt), sorted(batch.updates), history))
        v.close()  # staging pool worker threads
        return out

    assert run(2, True) == run(0, False)


def test_full_validator_sharded_block(tmp_path):
    """Full BlockValidator (real MSP identities, fused device path) on
    a 2-device mesh: bit-equal filter/updates vs single-device, through
    the pipelined validator.  Crypto-gated — the seed condition on
    containers without the ``cryptography`` package."""
    pytest.importorskip("cryptography")
    from fabric_tpu.peer.validator import BlockValidator

    blocks, fresh_state, mgr, prov = _signed_network(6, 2)

    def run(mesh_devices):
        state = fresh_state()
        v = BlockValidator(mgr, prov, state, mesh_devices=mesh_devices)
        out = []
        from fabric_tpu.protos import common_pb2

        for blk in blocks:
            b = common_pb2.Block()
            b.CopyFrom(blk)
            flt, batch, history = v.validate(b)
            state.apply_updates(batch, (b.header.number, 0))
            out.append((list(flt), sorted(batch.updates), history))
        return out

    assert run(2) == run(0)
