"""Batched ECDSA P-256 verification over RNS field arithmetic (the
Cox-Rower kernel) — the data-plane kernel, and the only ECDSA verify
kernel.

This is the hot loop of the reference's block-commit path: every
endorsement on every transaction is an ECDSA-P256 signature verified on
the host CPU one at a time (reference: msp/identities.go:170-199 →
bccsp.Verify; ~2-3 verifies per tx at a 2-of-3 policy, validator
fan-out in core/committer/txvalidator/v20/validator.go:193-208).  Here
the whole block's signatures are verified in ONE batched TPU dispatch.

Design:

* Field core: fabric_tpu.ops.rns — Montgomery multiplication whose only
  non-elementwise work is two DENSE [B,46]@[46,72] bf16 MXU matmuls
  (exact by 6-bit chunking) at DEFAULT (single-pass) precision.
* Scalar recoding on the HOST: u1 = e·s⁻¹, u2 = r·s⁻¹ (mod n)
  are computed with one Montgomery-batched inversion over the whole
  batch (3(B−1) 256-bit mults + ONE modular inversion, microseconds of
  numpy/Python work) — RNS has no cheap positional form, and the
  device has no business running a 256-round Fermat loop when the host
  does the whole batch in milliseconds.  The device receives 4-bit
  window digits.
* Point arithmetic: Renes–Costello–Batina 2016 COMPLETE projective
  formulas (a = −3), 64 ladder steps of
  [4 doublings + u2·Q table add + u1·G mixed add], in-kernel Q window
  table, host-precomputed Montgomery-form G table.
* Ladder body lives in a fori_loop with a FIXED loop-state bound
  contract (≤ 6p, asserted at trace time via rns.RV bound tracking),
  keeping the HLO graph ~64× smaller than a fully unrolled ladder.

Reference accept set matched exactly (bccsp/sw/ecdsa.go:41-58):
r,s ∈ [1, n−1], s ≤ n/2 (low-S), Q on curve and ≠ ∞,
R = u1·G + u2·Q ≠ ∞, x(R) ≡ r (mod n).  Bit-exact against
fabric_tpu.crypto.ec_ref (tests/test_p256v3.py).
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from fabric_tpu import faults as _faults
from fabric_tpu.crypto import ec_ref
from fabric_tpu.observe import ledger as _ledger
from fabric_tpu.ops import rns
from fabric_tpu.utils.batching import MIN_LANES, block_shapes

P = ec_ref.P
N = ec_ref.N
B_COEF = ec_ref.B
GX, GY = ec_ref.GX, ec_ref.GY
HALF_N = ec_ref.HALF_N

WINDOW = 4
STEPS = 64

# fixed bound contract for ladder-carried coordinates
_BND_STATE = 9 * P


def _ctx() -> rns.MontCtx:
    return rns.ctx_for(P)


def _const_rv(x: int) -> rns.RV:
    return rns.to_rns(x)


# ---------------------------------------------------------------------------
# RCB16 complete point ops (projective X:Y:Z, a = -3) over rns.RV.
# Op schedules of the paper's algorithms 4/5/6.


def pt_add(p1, p2, b_rv, ctx):
    """RCB16 algorithm 4 restaged into 3 stacked-mul dispatches:
    6 independent muls, then the 2 b-muls, then the 6 output muls —
    identical mathematics to the sequential schedule (the staging is
    checked mul-for-mul against it in tests)."""
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    sub = lambda a, b: rns.rv_sub(a, b, ctx)
    t0, t1, t2, s1, s2, s3 = rns.mont_mul_many(
        [(X1, X2), (Y1, Y2), (Z1, Z2),
         (X1 + Y1, X2 + Y2), (Y1 + Z1, Y2 + Z2), (X1 + Z1, X2 + Z2)],
        ctx,
    )
    t3 = sub(s1, t0 + t1)
    t4 = sub(s2, t1 + t2)
    y3a = sub(s3, t0 + t2)
    bz, by = rns.mont_mul_many([(b_rv, t2), (b_rv, y3a)], ctx)
    x3a = sub(y3a, bz)
    x3b = x3a + x3a + x3a
    z3a = sub(t1, x3b)
    x3c = t1 + x3b
    t2b = t2 + t2 + t2
    y3b = sub(sub(by, t2b), t0)
    y3c = y3b + y3b + y3b
    t0c = sub(t0 + t0 + t0, t2b)
    m1, m2, m3, m4, m5, m6 = rns.mont_mul_many(
        [(t4, y3c), (t0c, y3c), (x3c, z3a), (t3, x3c), (t4, z3a), (t3, t0c)],
        ctx,
    )
    return (sub(m4, m1), m3 + m2, m5 + m6)


def pt_add_mixed(p1, x2, y2, b_rv, ctx):
    """RCB16 algorithm 5 (Z2 = 1): P2 affine, must not be ∞.
    Staged: 6 + 1 + 6 muls in 3 stacked dispatches."""
    X1, Y1, Z1 = p1
    sub = lambda a, b: rns.rv_sub(a, b, ctx)
    t0, t1, s1, myz, mxz, bz1 = rns.mont_mul_many(
        [(X1, x2), (Y1, y2), (x2 + y2, X1 + Y1),
         (y2, Z1), (x2, Z1), (b_rv, Z1)],
        ctx,
    )
    t3 = sub(s1, t0 + t1)
    t4 = myz + Y1
    y3a = mxz + X1
    x3a = sub(y3a, bz1)
    x3b = x3a + x3a + x3a
    z3a = sub(t1, x3b)
    x3c = t1 + x3b
    (by,) = rns.mont_mul_many([(b_rv, y3a)], ctx)
    t2b = Z1 + Z1 + Z1
    y3b = sub(sub(by, t2b), t0)
    y3c = y3b + y3b + y3b
    t0c = sub(t0 + t0 + t0, t2b)
    m1, m2, m3, m4, m5, m6 = rns.mont_mul_many(
        [(t4, y3c), (t0c, y3c), (x3c, z3a), (t3, x3c), (t4, z3a), (t3, t0c)],
        ctx,
    )
    return (sub(m4, m1), m3 + m2, m5 + m6)


def pt_double(p, b_rv, ctx):
    """RCB16 algorithm 6 (a = −3) restaged: 6 + 2 + 5 muls in 3
    stacked dispatches.  The (x3a·y3c, x3a·t3) pair depends only on
    bt2 and the (·z3b, ·t1) triple only on bz, so the former stages 3
    and 4 are mutually independent and fuse into ONE stacked dispatch —
    with 4 doublings per ladder step this cuts 4 sequential Montgomery
    rounds (and their 2 matmuls each) from every step's critical path."""
    X, Y, Z = p
    sub = lambda a, b: rns.rv_sub(a, b, ctx)
    t0, t1, t2, xy, xz, yz = rns.mont_mul_many(
        [(X, X), (Y, Y), (Z, Z), (X, Y), (X, Z), (Y, Z)], ctx
    )
    t3 = xy + xy
    zz2 = xz + xz
    bt2, bz = rns.mont_mul_many([(b_rv, t2), (b_rv, zz2)], ctx)
    y3a = sub(bt2, zz2)
    y3b = y3a + y3a + y3a
    x3a = sub(t1, y3b)
    y3c = t1 + y3b
    t2b = t2 + t2 + t2
    z3a = sub(sub(bz, t2b), t0)
    z3b = z3a + z3a + z3a
    t0c = sub(t0 + t0 + t0, t2b)
    yz2 = yz + yz
    y3m, x3m, a1, a2, a3 = rns.mont_mul_many(
        [(x3a, y3c), (x3a, t3), (t0c, z3b), (yz2, z3b), (yz2, t1)], ctx
    )
    Z3 = a3 + a3
    return (sub(x3m, a2), y3m + a1, Z3 + Z3)


# ---------------------------------------------------------------------------
# Host-precomputed u1·G window table in Montgomery-RNS form:
# TG[d] = d·G affine, d = 1..15 (slot 0 unused; digit-0 is skipped).

_TG = np.zeros((16, 2, 2 * rns.N_CH), np.int32)
for _d in range(1, 16):
    _px, _py = ec_ref.pt_mul(_d, (GX, GY))
    _TG[_d, 0] = rns.ints_to_rns([(_px * rns.M_A) % P])[0]
    _TG[_d, 1] = rns.ints_to_rns([(_py * rns.M_A) % P])[0]
_TG_J = None  # jnp-ified lazily inside the traced fn

_MONT_ONE = (rns.M_A % P)


def _clamp(rv: rns.RV, bound: int) -> rns.RV:
    assert rv.bound <= bound, (rv.bound, bound)
    return rns.RV(rv.arr, bound)


def verify_batch(qx, qy, rr, rpn, w1, w2, rpn_ok, pre_ok):
    """Batched verify on RNS-residue inputs.

    qx, qy, rr, rpn: [B, 2n] canonical residues of Q.x, Q.y, r, r+n
        (plain domain, values < p).
    w1, w2: [B, 64] int32 4-bit window digits of u1, u2, MSB-first.
    rpn_ok: [B] bool, r+n < p.  pre_ok: [B] bool host admission checks.
    → [B] bool, the exact accept set of the reference verifier.
    """
    ctx = _ctx()
    mul = lambda a, b: rns.mont_mul(a, b, ctx)
    sub = lambda a, b: rns.rv_sub(a, b, ctx)

    def RVp(arr):
        return rns.RV(arr, P)

    qx_m = rns.to_mont(RVp(qx), ctx)
    qy_m = rns.to_mont(RVp(qy), ctx)
    b_m = _const_rv((B_COEF * rns.M_A) % P)

    # on-curve: y² == x³ − 3x + b   (Montgomery domain throughout)
    y2 = mul(qy_m, qy_m)
    x2 = mul(qx_m, qx_m)
    x3 = mul(x2, qx_m)
    three_x = qx_m + qx_m + qx_m
    rhs = sub(x3 + b_m, three_x)
    on_curve = rns.eq_const_mod_p(sub(y2, rhs), ctx)

    # u2·Q window table T[d] = d·Q, T[0] = ∞ = (0 : 1̃ : 0)
    zero = jnp.zeros_like(qx)
    one_m = jnp.broadcast_to(
        jnp.asarray(rns._to_res(_MONT_ONE, rns.BASE_A + rns.BASE_B)), qx.shape
    )
    inf = (rns.RV(zero, 0), rns.RV(one_m, _MONT_ONE), rns.RV(zero, 0))
    q1 = (qx_m, qy_m, rns.RV(one_m, _MONT_ONE))
    table = [inf, q1]
    acc = q1
    for _d in range(2, 16):
        acc = tuple(_clamp(c, _BND_STATE) for c in pt_add(acc, q1, b_m, ctx))
        table.append(acc)
    tq = jnp.stack(
        [jnp.stack([pt[0].arr, pt[1].arr, pt[2].arr], axis=-2) for pt in table],
        axis=-3,
    )  # [B, 16, 3, 2n]

    tg = jnp.asarray(_TG)  # [16, 2, 2n] constants

    def ladder_body(i, state):
        Xa, Ya, Za = state
        R = (rns.RV(Xa, _BND_STATE), rns.RV(Ya, _BND_STATE),
             rns.RV(Za, _BND_STATE))
        for _ in range(WINDOW):
            R = tuple(
                _clamp(c, _BND_STATE) for c in pt_double(R, b_m, ctx)
            )
        # add T_Q[w2[i]] — integer gather; complete add handles ∞ slot
        d2 = jax.lax.dynamic_index_in_dim(w2, i, axis=1, keepdims=False)
        sel = jnp.take_along_axis(
            tq, d2[:, None, None, None], axis=-3
        )[..., 0, :, :]
        T2 = (rns.RV(sel[..., 0, :], _BND_STATE),
              rns.RV(sel[..., 1, :], _BND_STATE),
              rns.RV(sel[..., 2, :], _BND_STATE))
        R = tuple(_clamp(c, _BND_STATE) for c in pt_add(R, T2, b_m, ctx))
        # add T_G[w1[i]] — affine constants, skipped when digit == 0
        d1 = jax.lax.dynamic_index_in_dim(w1, i, axis=1, keepdims=False)
        selg = jnp.take_along_axis(
            tg[None], d1[:, None, None, None], axis=-3
        )[..., 0, :, :]
        Rg = pt_add_mixed(
            R, rns.RV(selg[..., 0, :], P), rns.RV(selg[..., 1, :], P),
            b_m, ctx,
        )
        Rg = tuple(_clamp(c, _BND_STATE) for c in Rg)
        skip = (d1 == 0)[:, None]
        return (
            jnp.where(skip, R[0].arr, Rg[0].arr),
            jnp.where(skip, R[1].arr, Rg[1].arr),
            jnp.where(skip, R[2].arr, Rg[2].arr),
        )

    state0 = (zero, one_m, zero)
    Xr, Yr, Zr = jax.lax.fori_loop(0, STEPS, ladder_body, state0)
    X_rv = rns.RV(Xr, _BND_STATE)
    Z_rv = rns.RV(Zr, _BND_STATE)

    not_inf = ~rns.eq_const_mod_p(Z_rv, ctx)
    # x(R) ≡ r (mod n) ⟺ X ≡ r·Z or (r+n)·Z (mod p), r+n only if < p
    r_m = rns.to_mont(RVp(rr), ctx)
    rpn_m = rns.to_mont(RVp(rpn), ctx)
    cmp1 = rns.eq_const_mod_p(sub(X_rv, mul(r_m, Z_rv)), ctx)
    cmp2 = rns.eq_const_mod_p(sub(X_rv, mul(rpn_m, Z_rv)), ctx) & rpn_ok
    return pre_ok & on_curve & not_inf & (cmp1 | cmp2)


verify_batch_jit = jax.jit(verify_batch)


# ---------------------------------------------------------------------------
# Host side: admission checks, batched inversion, recoding, residues

MIN_BUCKET = MIN_LANES


def _bucket(n: int) -> int:
    """Lanes of the verify program a batch of ``n`` signatures runs in:
    ``utils/batching.block_shapes``'s verify axis (powers of two from 16
    to 512, then multiples of 512), the one rule the validator's
    warm-up enumerates too.  On the v5e one execution takes 2.4 ms at
    32 lanes, 3.4 at 64, 2.8 at 128, 3.6 at 256, 5.4 at 512, 9.2 at
    1024, 13.3 at 1536 and 26.9 at 3072 (PERF.md section 6, PR 28): a
    floor near 2.5 ms and 8.7 us a lane above 512 lanes, so a padded
    lane costs what a real one does and the small buckets earn the
    program each of them costs at a start."""
    return block_shapes(signatures=n).verify


def _batch_inv_mod_n(ss: list[int]) -> list[int]:
    """Montgomery's simultaneous inversion: one pow(·,−1,n) for the
    whole batch + 3(B−1) modmuls (the v20 validator's per-tx goroutine
    fan-out, collapsed into prefix products)."""
    B = len(ss)
    pref = [1] * (B + 1)
    for i, s in enumerate(ss):
        pref[i + 1] = (pref[i] * s) % N
    inv_all = pow(pref[B], -1, N)
    out = [0] * B
    for i in range(B - 1, -1, -1):
        out[i] = (pref[i] * inv_all) % N
        inv_all = (inv_all * ss[i]) % N
    return out


def _windows(us: list[int]) -> np.ndarray:
    """[B] ints → [B, 64] 4-bit window digits, MSB-first."""
    if not us:
        return np.zeros((0, STEPS), np.int32)
    raw = np.frombuffer(
        b"".join(int(u).to_bytes(32, "big") for u in us), np.uint8
    ).reshape(len(us), 32)
    hi, lo = raw >> 4, raw & 0xF
    return np.stack([hi, lo], axis=-1).reshape(len(us), 64).astype(np.int32)


# window recoding ON DEVICE: u1/u2 ship as 16 big-endian 16-bit limbs
# (32 int16 columns for the pair) instead of 128 window-digit columns —
# 4× less H2D for the window planes, ~1.4× for the whole packed frame —
# and the [B, 64] digits are derived in the stage-1 kernel with pure
# shift/mask lanes.  Bit-equality vs host _windows is pinned by
# tests/test_p256v3.py across random scalars and edge cases.
_PK_LIMBS = 16


def _limbs16(us) -> np.ndarray:
    """[B] ints (< 2^256) → [B, 16] int16 BIG-endian 16-bit limbs.
    Values ≥ 2^15 wrap into the sign bit (same bit pattern); the
    device re-masks with ``& 0xFFFF`` after widening."""
    if not len(us):
        return np.zeros((0, _PK_LIMBS), np.int16)
    raw = np.frombuffer(
        b"".join(int(u).to_bytes(32, "big") for u in us), np.uint8
    ).reshape(len(us), 32).astype(np.uint16)
    return ((raw[:, 0::2] << 8) | raw[:, 1::2]).astype(np.int16)


def windows_to_limbs(w: np.ndarray) -> np.ndarray:
    """[B, 64] window digits → [B, 16] int16 limbs — packs the native
    ec_prepare path's C-computed windows into the limb wire form (the
    exact inverse of the device recode; each digit < 16)."""
    if not len(w):
        return np.zeros((0, _PK_LIMBS), np.int16)
    d = w.astype(np.uint16).reshape(len(w), _PK_LIMBS, 4)
    return ((d[..., 0] << 12) | (d[..., 1] << 8) | (d[..., 2] << 4)
            | d[..., 3]).astype(np.int16)


def device_recode_windows(limbs):
    """[B, 16] int16 big-endian limbs → [B, 64] int32 window digits,
    ON DEVICE — limb j carries digits 4j..4j+3 MSB-first, matching the
    host ``_windows`` layout bit for bit."""
    l = limbs.astype(jnp.int32) & 0xFFFF
    d = (l[..., None] >> jnp.asarray([12, 8, 4, 0], jnp.int32)) & 0xF
    return d.reshape(*limbs.shape[:-1], STEPS)


def prepare(items, pad_to: int | None = None):
    """Host-side preparation for verify_batch: admission checks,
    batched s⁻¹, scalar recoding, residue conversion.  Returns the
    verify_batch argument tuple (jnp arrays).  ``pad_to`` pads the
    batch with always-rejected lanes."""
    items = list(items)
    if pad_to is not None:
        items = items + [(0, 1, 1, 0, 0)] * (pad_to - len(items))

    pre_ok, rpn_ok, rpns, u1s, u2s, ss = [], [], [], [], [], []
    for (e, r, s, qx, qy) in items:
        ok = (
            0 < r < N and 0 < s <= HALF_N
            and 0 <= qx < P and 0 <= qy < P and not (qx == 0 and qy == 0)
        )
        pre_ok.append(ok)
        rp = r + N
        rpn_ok.append(rp < P)
        rpns.append(rp if rp < P else 0)
        ss.append(s if 0 < s < N else 1)
    s_inv = _batch_inv_mod_n(ss)
    for (e, r, s, qx, qy), si in zip(items, s_inv):
        u1s.append((e * si) % N)
        u2s.append((r * si) % N)

    cols = list(zip(*items))
    return (
        jnp.asarray(rns.ints_to_rns(cols[3])),
        jnp.asarray(rns.ints_to_rns(cols[4])),
        jnp.asarray(rns.ints_to_rns(cols[1])),
        jnp.asarray(rns.ints_to_rns(rpns)),
        jnp.asarray(_windows(u1s)),
        jnp.asarray(_windows(u2s)),
        jnp.asarray(np.array(rpn_ok)),
        jnp.asarray(np.array(pre_ok)),
    )


class SigCollector:
    """Column-form signature batch for the commit path.

    Fast rows reference the native pre-parser's [., 32] byte arrays by
    row index — no per-item Python-int materialisation; slow rows carry
    legacy (digest, r, s, qx, qy) int tuples for envelopes the Python
    parser handled.  ``assemble`` gathers the byte columns with numpy
    fancy indexing, converts residues with one dgemm
    (rns.bytes_to_rns), and reuses per-identity cached pubkey residues
    (Identity.rns_pub) — the host cost the round-3 bench paid per item
    (~265 ms/block of bigint→limb conversion) collapses to a few ms."""

    __slots__ = ("entries", "slow", "n")

    def __init__(self):
        self.entries = []  # (arrs=(digest,r,s), row, ident, pos)
        self.slow = []     # (pos, (e, r, s, qx, qy))
        self.n = 0

    def add_fast(self, arrs, row: int, ident) -> int:
        pos = self.n
        self.entries.append((arrs, int(row), ident, pos))
        self.n += 1
        return pos

    def add_slow(self, item) -> int:
        pos = self.n
        self.slow.append((pos, item))
        self.n += 1
        return pos

    def __len__(self) -> int:
        return self.n

    def tuples(self) -> list:
        """(digest, r, s, qx, qy) int tuples — the host fallbacks and
        the sidecar's wire consume these."""
        out = [None] * self.n
        for arrs, row, ident, pos in self.entries:
            d, r, s = arrs
            qx, qy = ident.public_numbers
            out[pos] = (
                int.from_bytes(bytes(d[row]), "big"),
                int.from_bytes(bytes(r[row]), "big"),
                int.from_bytes(bytes(s[row]), "big"),
                qx, qy,
            )
        for pos, item in self.slow:
            out[pos] = item
        return out


class ColumnarSigBatch:
    """A signature batch ALREADY in column form — the validator's
    fully vectorized fast path assembles digest/r/s byte columns and
    per-identity cached pubkey residues straight from the native
    pre-parser's arrays with numpy gathers, so no per-item Python runs
    at all.  Slow rows (config-tx creators, host fallbacks) append as
    legacy int tuples after the fast block."""

    __slots__ = ("digest_b", "r_b", "s_b", "qx_res", "qy_res",
                 "pub_ok", "slow", "n_fast", "ident_of", "idents")

    def __init__(self, digest_b, r_b, s_b, qx_res, qy_res, pub_ok,
                 ident_of=None, idents=None):
        self.digest_b, self.r_b, self.s_b = digest_b, r_b, s_b
        self.qx_res, self.qy_res, self.pub_ok = qx_res, qy_res, pub_ok
        self.slow = []
        self.n_fast = len(digest_b)
        # per-fast-item identity (uid array + pool) — only for
        # tuples() (host fallbacks, the sidecar's wire)
        self.ident_of = ident_of
        self.idents = idents

    @property
    def n(self) -> int:
        return self.n_fast + len(self.slow)

    def __len__(self) -> int:
        return self.n

    def add_slow(self, item) -> int:
        pos = self.n
        self.slow.append(item)
        return pos

    def assemble(self):
        """→ the six prepare_cols arrays with slow rows appended."""
        if not self.slow:
            return (self.digest_b, self.r_b, self.s_b,
                    self.qx_res, self.qy_res, self.pub_ok)
        k = len(self.slow)
        pad = lambda a: np.concatenate(
            [a, np.zeros((k,) + a.shape[1:], a.dtype)]
        )
        digest_b, r_b, s_b = (pad(self.digest_b), pad(self.r_b),
                              pad(self.s_b))
        qx_res, qy_res = pad(self.qx_res), pad(self.qy_res)
        pub_ok = pad(self.pub_ok)
        for j, (e, r, s, qx, qy) in enumerate(self.slow):
            pos = self.n_fast + j
            if not (0 <= r < (1 << 256) and 0 <= s < (1 << 256)):
                continue  # row stays zero, pub_ok False (reject)
            digest_b[pos] = np.frombuffer(int(e).to_bytes(32, "big"), np.uint8)
            r_b[pos] = np.frombuffer(int(r).to_bytes(32, "big"), np.uint8)
            s_b[pos] = np.frombuffer(int(s).to_bytes(32, "big"), np.uint8)
            res = rns.ints_to_rns([qx, qy])
            qx_res[pos], qy_res[pos] = res[0], res[1]
            pub_ok[pos] = (
                0 <= qx < P and 0 <= qy < P and not (qx == 0 and qy == 0)
            )
        return digest_b, r_b, s_b, qx_res, qy_res, pub_ok

    def tuples(self) -> list:
        """Int-tuple form (host fallbacks, the sidecar's wire); pubkey
        ints come from the identity pool, not the residues."""
        out = []
        for i in range(self.n_fast):
            ident = self.idents[int(self.ident_of[i])]
            qx, qy = ident.public_numbers
            out.append((
                int.from_bytes(bytes(self.digest_b[i]), "big"),
                int.from_bytes(bytes(self.r_b[i]), "big"),
                int.from_bytes(bytes(self.s_b[i]), "big"),
                qx, qy,
            ))
        out.extend(self.slow)
        return out


def _assemble_cols(c: SigCollector):
    """SigCollector → (digest_b, r_b, s_b [B,32] u8; qx_res, qy_res
    [B,2n] i32; pub_ok [B] bool)."""
    B = c.n
    digest_b = np.zeros((B, 32), np.uint8)
    r_b = np.zeros((B, 32), np.uint8)
    s_b = np.zeros((B, 32), np.uint8)
    qx_res = np.zeros((B, 2 * rns.N_CH), np.int32)
    qy_res = np.zeros((B, 2 * rns.N_CH), np.int32)
    pub_ok = np.zeros(B, bool)

    groups: dict = {}  # id(digest array) → (arrs, [pos], [row])
    pool: dict = {}    # id(ident) → pool row
    pool_rows: list = []
    idx = np.zeros(B, np.int32)
    fast_pos: list = []
    for arrs, row, ident, pos in c.entries:
        g = groups.get(id(arrs[0]))
        if g is None:
            g = groups[id(arrs[0])] = (arrs, [], [])
        g[1].append(pos)
        g[2].append(row)
        k = id(ident)
        i = pool.get(k)
        if i is None:
            i = pool[k] = len(pool_rows)
            pool_rows.append(ident.rns_pub)
        idx[pos] = i
        fast_pos.append(pos)
    for arrs, poss, rows in groups.values():
        p = np.asarray(poss, np.intp)
        rr = np.asarray(rows, np.intp)
        digest_b[p] = arrs[0][rr]
        r_b[p] = arrs[1][rr]
        s_b[p] = arrs[2][rr]
    if pool_rows:
        qx_pool = np.stack([a for a, _ in pool_rows])
        qy_pool = np.stack([b for _, b in pool_rows])
        fp = np.asarray(fast_pos, np.intp)
        qx_res[fp] = qx_pool[idx[fp]]
        qy_res[fp] = qy_pool[idx[fp]]
        pub_ok[fp] = True  # cert-derived keys are real curve points
    for pos, (e, r, s, qx, qy) in c.slow:
        if not (0 <= r < (1 << 256) and 0 <= s < (1 << 256)):
            # r/s outside 256 bits can never satisfy 0 < · < n —
            # reject rather than wrap (wrapping would WIDEN the accept
            # set vs the legacy int path: consensus divergence)
            continue  # row stays all-zero with pub_ok False
        digest_b[pos] = np.frombuffer(int(e).to_bytes(32, "big"), np.uint8)
        r_b[pos] = np.frombuffer(int(r).to_bytes(32, "big"), np.uint8)
        s_b[pos] = np.frombuffer(int(s).to_bytes(32, "big"), np.uint8)
        res = rns.ints_to_rns([qx, qy])
        qx_res[pos], qy_res[pos] = res[0], res[1]
        pub_ok[pos] = (
            0 <= qx < P and 0 <= qy < P and not (qx == 0 and qy == 0)
        )
    return digest_b, r_b, s_b, qx_res, qy_res, pub_ok


def prepare_cols(digest_b, r_b, s_b, qx_res, qy_res, pub_ok,
                 pad_to: int | None = None, recode_device: bool = False,
                 out=None):
    """Column-form host preparation: same outputs (and accept set) as
    ``prepare`` but residues come from one dgemm over the byte columns
    and cached identity rows; only the admission checks and the
    batched inversion touch Python ints.

    ``recode_device``: skip host window recoding — the w1/w2 slots of
    the returned tuple carry [B, 16] int16 scalar LIMBS instead of
    [B, 64] digits, for the ``verify_batch_packed_limbs`` kernel that
    derives the digits on device (4× less H2D for the window planes).

    ``out``: optional 8-tuple of preallocated destinations (qx, qy,
    r_res, rpn_res, w1, w2, rpn_ok, pre_ok) with leading dim == the
    padded batch — every staged lane writes IN PLACE (the native
    ec_prepare digit planes and the residue dgemm land directly in the
    caller's row slabs), and the pad tail is zeroed.  This is how the
    pooled workers (``_prepare_cols_pooled``) avoid the
    allocate-then-copy that made pooled host-recode copy-bound; the
    result is bit-equal to the allocating form (tests/test_p256v3.py).
    Returns ``out`` when given, fresh arrays otherwise."""
    import ctypes

    B0 = len(r_b)
    Bp = pad_to if pad_to is not None else max(B0, 1)
    if out is not None:
        o_qx, o_qy, _o_r, o_rpn, o_w1, o_w2, o_rpn_ok, o_pre = out
        if len(o_pre) != Bp:
            raise ValueError(
                f"out arrays must have leading dim {Bp}, got {len(o_pre)}"
            )
        if Bp != B0:
            for a in out:  # pad tail = all-zero rejected lanes
                a[B0:] = 0
        o_qx[:B0] = qx_res
        o_qy[:B0] = qy_res
        pre_ok, rpn_ok = o_pre, o_rpn_ok
    else:
        o_w1 = o_w2 = None
        pre_ok = np.zeros(Bp, bool)
        rpn_ok = np.zeros(Bp, bool)
    full = lambda a: np.concatenate(
        [a, np.zeros((Bp - B0,) + a.shape[1:], a.dtype)]
    ) if Bp != B0 else a

    w1 = w2 = None
    done = False
    if B0:
        try:
            from fabric_tpu.native import ecprep_lib

            lib = ecprep_lib()
        except Exception:
            lib = None
        if lib is not None:
            # the native path needs NO Python bigints at all — the
            # admission flags, inversion, and windows all come from C
            # one GIL-releasing C call: admission flags + batch
            # inversion + window recoding for the whole batch
            eb = np.ascontiguousarray(digest_b)
            rb = np.ascontiguousarray(r_b)
            sb = np.ascontiguousarray(s_b)
            direct = False
            if out is not None and not recode_device:
                # C writes the digit planes straight into the
                # destination slabs (row-slab views stay contiguous)
                w1, w2 = o_w1[:B0], o_w2[:B0]
                direct = (w1.flags.c_contiguous and w2.flags.c_contiguous
                          and w1.dtype == np.int32)
            if not direct:
                w1 = np.zeros((B0, STEPS), np.int32)
                w2 = np.zeros((B0, STEPS), np.int32)
            flags = np.zeros(B0, np.uint8)
            ptr = lambda a: a.ctypes.data_as(ctypes.c_void_p)
            lib.ec_prepare(
                ptr(eb), ptr(rb), ptr(sb), ctypes.c_int64(B0),
                ptr(w1), ptr(w2), ptr(flags),
            )
            pre_ok[:B0] = pub_ok & (flags & 1).astype(bool)
            rpn_ok[:B0] = (flags & 2).astype(bool)
            if recode_device:
                # the C path hands back digits; pack them to limbs so
                # the wire form (and kernel) match the Python lane
                w1, w2 = windows_to_limbs(w1), windows_to_limbs(w2)
            if out is not None:
                if not direct:
                    o_w1[:B0] = w1
                    o_w2[:B0] = w2
            else:
                w1, w2 = full(w1), full(w2)
            done = True

    if B0 and not done:  # pure-Python fallback (no toolchain)
        ebuf, rbuf, sbuf = digest_b.tobytes(), r_b.tobytes(), s_b.tobytes()
        es = [int.from_bytes(ebuf[32 * i:32 * i + 32], "big") for i in range(B0)]
        rints = [int.from_bytes(rbuf[32 * i:32 * i + 32], "big") for i in range(B0)]
        sints = [int.from_bytes(sbuf[32 * i:32 * i + 32], "big") for i in range(B0)]
        ss = [1] * B0
        for i, (r, s) in enumerate(zip(rints, sints)):
            pre_ok[i] = bool(pub_ok[i]) and 0 < r < N and 0 < s <= HALF_N
            rpn_ok[i] = (r + N) < P
            ss[i] = s if 0 < s < N else 1
        s_inv = _batch_inv_mod_n(ss)
        u1s = [(e * si) % N for e, si in zip(es, s_inv)]
        u2s = [(r * si) % N for r, si in zip(rints, s_inv)]
        w1, w2 = ((_limbs16(u1s), _limbs16(u2s)) if recode_device
                  else (_windows(u1s), _windows(u2s)))
        if out is not None:
            o_w1[:B0] = w1
            o_w2[:B0] = w2
        else:
            w1, w2 = full(w1), full(w2)
    elif not B0 and out is None:
        wcols = _PK_LIMBS if recode_device else STEPS
        wdt = np.int16 if recode_device else np.int32
        w1 = np.zeros((Bp, wcols), wdt)
        w2 = np.zeros((Bp, wcols), wdt)

    primes = np.array(rns.BASE_A + rns.BASE_B, np.int32)
    n_res = rns._to_res(N, rns.BASE_A + rns.BASE_B)
    if out is not None:
        rv = rns.bytes_to_rns(r_b, out=_o_r[:B0]) if B0 else _o_r[:0]
        np.mod(rv + n_res[None, :], primes, out=o_rpn[:B0])
        o_rpn[~rpn_ok] = 0
        return out
    r_res = full(rns.bytes_to_rns(r_b))
    rpn_res = (r_res + n_res[None, :]) % primes
    rpn_res[~rpn_ok] = 0
    return (
        full(qx_res), full(qy_res), r_res, rpn_res, w1, w2, rpn_ok, pre_ok,
    )


# packed launch form: every residue is < 2^12 (the RNS primes) and
# every window digit < 16, so the WHOLE batch ships as ONE int16
# array — a single H2D transfer instead of eight (each device_put
# carries a fixed host overhead whatever its size).
_PK_R = 2 * rns.N_CH
_PK_COLS = 4 * _PK_R + 2 * STEPS + 2


def _pack_rows(out, args, lo, hi, w_cols: int) -> None:
    """Pack rows [lo, hi) of the eight staged columns into the int16
    launch frame ``out`` in place — the unit the host pool shards."""
    view = out[lo:hi]
    o = 0
    for a in args[:4]:
        view[:, o:o + _PK_R] = a[lo:hi]
        o += _PK_R
    for a in args[4:6]:
        view[:, o:o + w_cols] = a[lo:hi]
        o += w_cols
    view[:, o] = args[6][lo:hi]
    view[:, o + 1] = args[7][lo:hi]


def pack_cols(qx, qy, r_res, rpn_res, w1, w2, rpn_ok, pre_ok) -> np.ndarray:
    B = len(qx)
    out = np.empty((B, _PK_COLS), np.int16)
    _pack_rows(out, (qx, qy, r_res, rpn_res, w1, w2, rpn_ok, pre_ok),
               0, B, STEPS)
    return out


def _unpack_cols(packed):
    o = 0
    res = []
    for _ in range(4):
        res.append(packed[:, o:o + _PK_R].astype(jnp.int32))
        o += _PK_R
    w1 = packed[:, o:o + STEPS].astype(jnp.int32)
    o += STEPS
    w2 = packed[:, o:o + STEPS].astype(jnp.int32)
    o += STEPS
    return (*res, w1, w2, packed[:, o] != 0, packed[:, o + 1] != 0)


def verify_batch_packed(packed):
    return verify_batch(*_unpack_cols(packed))


verify_batch_packed_jit = jax.jit(verify_batch_packed)


# recode-on-device packed form: the two 64-digit window planes shrink
# to 16 limbs each — 218 int16 columns per lane instead of 314.
_PKL_COLS = 4 * _PK_R + 2 * _PK_LIMBS + 2


def pack_cols_limbs(qx, qy, r_res, rpn_res, l1, l2, rpn_ok, pre_ok) -> np.ndarray:
    """Packed launch frame with u1/u2 as [B, 16] int16 limbs (the
    ``prepare_cols(recode_device=True)`` outputs) — consumed by
    ``verify_batch_packed_limbs`` which recodes on device."""
    B = len(qx)
    out = np.empty((B, _PKL_COLS), np.int16)
    _pack_rows(out, (qx, qy, r_res, rpn_res, l1, l2, rpn_ok, pre_ok),
               0, B, _PK_LIMBS)
    return out


def _unpack_cols_limbs(packed):
    o = 0
    res = []
    for _ in range(4):
        res.append(packed[:, o:o + _PK_R].astype(jnp.int32))
        o += _PK_R
    w1 = device_recode_windows(packed[:, o:o + _PK_LIMBS])
    o += _PK_LIMBS
    w2 = device_recode_windows(packed[:, o:o + _PK_LIMBS])
    o += _PK_LIMBS
    return (*res, w1, w2, packed[:, o] != 0, packed[:, o + 1] != 0)


def verify_batch_packed_limbs(packed):
    return verify_batch(*_unpack_cols_limbs(packed))


verify_batch_packed_limbs_jit = jax.jit(verify_batch_packed_limbs)


def _pack_launch(args, recode_device: bool, pool=None) -> np.ndarray:
    """Staged columns → int16 launch frame; with a host pool the row
    slabs pack in parallel (the pack is a multi-MB strided copy that
    otherwise serializes behind the pooled staging)."""
    if pool is None:
        return (pack_cols_limbs(*args) if recode_device
                else pack_cols(*args))
    B = len(args[0])
    w_cols = _PK_LIMBS if recode_device else STEPS
    out = np.empty((B, _PKL_COLS if recode_device else _PK_COLS),
                   np.int16)
    bounds = pool.slice_bounds(B, align=MIN_BUCKET)
    if len(bounds) <= 1:
        _pack_rows(out, args, 0, B, w_cols)
        return out
    pool.map_slices(B, lambda lo, hi: _pack_rows(out, args, lo, hi,
                                                 w_cols),
                    stage="pack", align=MIN_BUCKET)
    return out


def _packed_kernel(recode_device: bool):
    return (verify_batch_packed_limbs_jit if recode_device
            else verify_batch_packed_jit)


def prepare_cols_packed(digest_b, r_b, s_b, qx_res, qy_res, pub_ok,
                        pad_to: int | None = None,
                        recode_device: bool = False,
                        out=None) -> np.ndarray:
    """Single-pass host staging STRAIGHT into the packed int16 launch
    frame — ``pack_cols(prepare_cols(...))`` collapsed into one pass.

    The two-phase form allocates eight full-size staging arrays, fills
    them, and then copies every plane AGAIN into the int16 frame; this
    writes each plane exactly once:

    * the native ``ec_prepare_pack`` emits the window digit (or limb)
      planes int16 and STRIDED, directly into the frame's window
      columns (no int32 digit temps, no pack copy),
    * the residue dgemm lands in one int32 scratch that casts straight
      into the frame's r/rpn columns,
    * qx/qy/flags are single cast-assignments.

    Byte-identical to ``pack_cols(prepare_cols(...))`` /
    ``pack_cols_limbs(...)`` — pinned by tests/test_p256v3.py — and
    ~2× less memory traffic per staged batch, which is most of what
    the serial ``sig_prepare_launch`` stage still paid in host cycles.
    ``out``: optional preallocated [Bp, cols] C-contiguous int16 frame
    (reused across blocks by callers that want zero allocation)."""
    import ctypes

    B0 = len(r_b)
    R = _PK_R
    wcols = _PK_LIMBS if recode_device else STEPS
    ncols = _PKL_COLS if recode_device else _PK_COLS
    Bp = pad_to if pad_to is not None else max(B0, 1)
    if out is not None:
        frame = out
        if (frame.shape != (Bp, ncols) or frame.dtype != np.int16
                or not frame.flags.c_contiguous):
            raise ValueError(
                f"out must be a C-contiguous int16 [{Bp}, {ncols}] "
                f"frame, got {frame.dtype} {frame.shape}"
            )
    else:
        frame = np.empty((Bp, ncols), np.int16)
    if Bp != B0:
        frame[B0:] = 0  # pad tail: all-zero always-rejected lanes
    if not B0:
        frame[:] = 0
        return frame

    o_w1 = 4 * R
    o_w2 = o_w1 + wcols
    o_rpn_ok = o_w2 + wcols

    eb = np.ascontiguousarray(digest_b)
    rb = np.ascontiguousarray(r_b)
    sb = np.ascontiguousarray(s_b)
    try:
        from fabric_tpu.native import ecprep_lib

        lib = ecprep_lib()
    except Exception:
        lib = None
    pre_ok = rpn_ok = None
    if lib is not None:
        flags = np.zeros(B0, np.uint8)
        ptr = lambda a: a.ctypes.data_as(ctypes.c_void_p)
        # strided C writes: row i's plane lands at base + i*row_width
        lib.ec_prepare_pack(
            ptr(eb), ptr(rb), ptr(sb), ctypes.c_int64(B0),
            ptr(frame[:B0, o_w1:]), ptr(frame[:B0, o_w2:]),
            ctypes.c_int64(frame.strides[0] // 2),
            ctypes.c_int32(1 if recode_device else 0), ptr(flags),
        )
        pre_ok = pub_ok & (flags & 1).astype(bool)
        rpn_ok = (flags & 2).astype(bool)
    else:  # pure-Python fallback (no toolchain)
        ebuf, rbuf, sbuf = eb.tobytes(), rb.tobytes(), sb.tobytes()
        es = [int.from_bytes(ebuf[32 * i:32 * i + 32], "big")
              for i in range(B0)]
        rints = [int.from_bytes(rbuf[32 * i:32 * i + 32], "big")
                 for i in range(B0)]
        sints = [int.from_bytes(sbuf[32 * i:32 * i + 32], "big")
                 for i in range(B0)]
        pre_ok = np.zeros(B0, bool)
        rpn_ok = np.zeros(B0, bool)
        ss = [1] * B0
        for i, (r, s) in enumerate(zip(rints, sints)):
            pre_ok[i] = bool(pub_ok[i]) and 0 < r < N and 0 < s <= HALF_N
            rpn_ok[i] = (r + N) < P
            ss[i] = s if 0 < s < N else 1
        s_inv = _batch_inv_mod_n(ss)
        u1s = [(e * si) % N for e, si in zip(es, s_inv)]
        u2s = [(r * si) % N for r, si in zip(rints, s_inv)]
        if recode_device:
            frame[:B0, o_w1:o_w2] = _limbs16(u1s)
            frame[:B0, o_w2:o_rpn_ok] = _limbs16(u2s)
        else:
            frame[:B0, o_w1:o_w2] = _windows(u1s)
            frame[:B0, o_w2:o_rpn_ok] = _windows(u2s)

    frame[:B0, :R] = qx_res
    frame[:B0, R:2 * R] = qy_res
    primes = np.array(rns.BASE_A + rns.BASE_B, np.int32)
    n_res = rns._to_res(N, rns.BASE_A + rns.BASE_B)  # int32 already
    scratch = rns.bytes_to_rns(rb)  # [B0, R] int32
    frame[:B0, 2 * R:3 * R] = scratch
    np.mod(scratch + n_res[None, :], primes, out=scratch)
    scratch[~rpn_ok] = 0
    frame[:B0, 3 * R:4 * R] = scratch
    frame[:B0, o_rpn_ok] = rpn_ok
    frame[:B0, o_rpn_ok + 1] = pre_ok
    return frame


def _prepare_cols_pooled(cols, pad_to, pool, recode_device: bool = False):
    """``prepare_cols`` sharded over the host staging pool along the
    lane axis at MIN_BUCKET boundaries.  Bit-equal to the serial call:
    every staged lane is independent (admission flags, window
    recoding, residue dgemm are per-row, and Montgomery batch
    inversion yields the exact per-lane modular inverse regardless of
    how the batch is grouped), so shard outputs ARE the serial output
    rows; the tail pad rows are all-zero/rejected in both forms.
    Pinned by tests/test_p256v3.py.

    The full-size output arrays are preallocated HERE and each worker
    stages its row slab IN PLACE through ``prepare_cols(out=...)`` —
    the admission flags, digit planes and residue dgemm land directly
    in the slab views, so no worker allocates shard outputs and then
    copies them over (the allocate-then-copy made pooled host-recode
    copy-bound on small hosts: one full extra frame copy per batch)."""
    B0 = len(cols[1])
    bounds = pool.slice_bounds(B0, align=MIN_BUCKET)
    if len(bounds) <= 1:
        return prepare_cols(*cols, pad_to=pad_to,
                            recode_device=recode_device)
    Bp = pad_to if pad_to is not None else B0
    R = 2 * rns.N_CH
    wcols = _PK_LIMBS if recode_device else STEPS
    wdt = np.int16 if recode_device else np.int32
    out = (
        np.zeros((Bp, R), np.int32),   # qx_res
        np.zeros((Bp, R), np.int32),   # qy_res
        np.zeros((Bp, R), np.int32),   # r_res
        np.zeros((Bp, R), np.int32),   # rpn_res
        np.zeros((Bp, wcols), wdt),    # w1 digits | u1 limbs
        np.zeros((Bp, wcols), wdt),    # w2 digits | u2 limbs
        np.zeros(Bp, bool),            # rpn_ok
        np.zeros(Bp, bool),            # pre_ok
    )

    def stage(lo, hi):
        prepare_cols(*(c[lo:hi] for c in cols),
                     recode_device=recode_device,
                     out=tuple(d[lo:hi] for d in out))

    pool.map_slices(B0, stage, stage="sig_prepare", align=MIN_BUCKET)
    return out


def _h2d_hist():
    from fabric_tpu.ops_metrics import global_registry

    return global_registry().histogram(
        "h2d_bytes_per_block",
        "packed verify-batch H2D bytes per launch",
        buckets=(1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22,
                 float("inf")),
    )


def _trc():
    from fabric_tpu.observe import global_tracer

    return global_tracer()


def _dev_ann(name: str):
    from fabric_tpu.observe import device_annotation

    return device_annotation(name)


class VerifyHandle:
    """An in-flight verify batch: the device-resident validity vector
    plus a fetch() that syncs to host.  Downstream device stages
    (policy + MVCC fusion) consume ``device_out`` directly so the
    signature bits never cross the device boundary on the critical
    path."""

    __slots__ = ("device_out", "n_real", "rec")

    def __init__(self, device_out, n_real: int, rec=None):
        self.device_out = device_out
        self.n_real = n_real
        # launch-ledger record (observe/ledger.py): fetch() brackets
        # the device sync so the ledger can attribute the wait
        self.rec = rec

    def fetch(self) -> list[bool]:
        rec = self.rec
        if rec is not None:
            rec.sync_begin()
        out = np.asarray(self.device_out)
        if rec is not None:
            rec.sync_end(d2h_bytes=out.nbytes)
        return [bool(v) for v in out[: self.n_real]]

    def __call__(self) -> list[bool]:
        return self.fetch()


def _chunk_metrics():
    from fabric_tpu.ops_metrics import global_registry

    reg = global_registry()
    return (
        reg.histogram(
            "verify_chunk_stage_seconds",
            "per-chunk host staging / dispatch time (s)",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, float("inf")),
        ),
        reg.histogram(
            "verify_chunks_per_batch",
            "microbatch chunks per verify batch",
            buckets=(1, 2, 4, 8, 16, 32, float("inf")),
        ),
    )


def _coalesce_metric():
    from fabric_tpu.ops_metrics import global_registry

    return global_registry().histogram(
        "coalesced_blocks_per_launch",
        "signature batches (blocks) concatenated per verify dispatch",
        buckets=(1, 2, 3, 4, 6, 8, float("inf")),
    )


def _shard(mesh, arr):
    """Axis-0 shard one verify dispatch input over the data mesh via the
    ``"verify_lanes"`` partition rule (no-op when mesh is None; ragged
    shapes fall back to single-device and are counted in
    ``mesh_shard_fallback_total``)."""
    if mesh is None:
        return arr
    from fabric_tpu.parallel.mesh import shard

    return shard(mesh, "verify_lanes", arr)


def _chunk_bounds(n_real: int, chunk: int) -> list[tuple[int, int, int]]:
    """[(lo, hi, pad)] microbatch slicing: every chunk except the last
    is EXACTLY ``chunk`` lanes and the last pads the total out to
    ``_bucket(n_real)`` — so item i lives at device index i of the
    concatenated output (no remapping for stage-2 gathers / creator /
    endorsement item indices) AND the concatenated length stays in the
    same bucket family as a monolithic launch, so chunking multiplies
    neither the tail's verify-kernel shapes nor the fused stage-2
    program shapes keyed on it."""
    bounds = []
    off = 0
    total = _bucket(n_real)
    while off < n_real:
        k = min(chunk, n_real - off)
        # intermediate chunks stay exact so global indices hold; the
        # tail absorbs all padding (total - off ≥ k since
        # _bucket(n_real) ≥ n_real)
        pad = chunk if off + k < n_real else total - off
        bounds.append((off, off + k, pad))
        off += k
    return bounds


def _launch_chunked(n_real: int, chunk: int, stage_fn,
                    dispatch_fn=None, pool=None, rec=None) -> VerifyHandle:
    """Microbatched double-buffered dispatch.

    Legacy form (``dispatch_fn`` None): ``stage_fn(lo, hi, pad)``
    stages [lo:hi) on the host AND dispatches it, returning the
    chunk's device output.  Because jax dispatch is asynchronous,
    staging chunk k+1 on the host overlaps chunk k's device compute —
    but only AFTER chunk k's H2D and dispatch were issued from the
    same thread.

    Split form (``dispatch_fn`` given): ``stage_fn(lo, hi, pad)`` is
    host-only (returns the packed launch frame) and ``dispatch_fn``
    ships+launches it.  With a host ``pool``, chunk k+1's staging is
    submitted to a pool worker BEFORE chunk k's dispatch runs on the
    caller thread — one-chunk lookahead, so chunk k+1's staging
    genuinely rides under chunk k's H2D + device compute instead of
    serializing behind the dispatch call (the lookahead worker stages
    its chunk serially; the parallelism comes from the overlap, which
    is why the pipelined commit path finally gives the double
    buffering something to hide).  Without a pool the split form
    degrades to the legacy serial order — CPU-only hosts unchanged.
    """
    stage_hist, chunks_hist = _chunk_metrics()
    bounds = _chunk_bounds(n_real, chunk)
    outs = []
    lookahead = pool is not None and dispatch_fn is not None
    nxt = (pool.submit(stage_fn, *bounds[0], stage="chunk_stage")
           if lookahead else None)
    for i, (lo, hi, pad) in enumerate(bounds):
        t0 = time.perf_counter()
        if dispatch_fn is None:
            out = stage_fn(lo, hi, pad)
        else:
            frame = nxt.result() if lookahead else stage_fn(lo, hi, pad)
            if lookahead and i + 1 < len(bounds):
                # stage k+1 NOW — it overlaps chunk k's H2D + dispatch
                # below and whatever device compute is already queued
                nxt = pool.submit(stage_fn, *bounds[i + 1],
                                  stage="chunk_stage")
            out = dispatch_fn(frame)
        t1 = time.perf_counter()
        stage_hist.observe(t1 - t0, stage="stage_dispatch")
        # per-chunk span on the block timeline (no-op off traced paths)
        _trc().add("verify_chunk", t0, t1, chunk=i, lanes=int(hi - lo))
        outs.append(out)
    chunks_hist.observe(len(bounds))
    dev = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    if hasattr(dev, "copy_to_host_async"):
        dev.copy_to_host_async()
    if rec is not None:
        rec.dispatched()
    return VerifyHandle(dev, n_real, rec)


def _stage_packed(cols, lo, hi, pad, pool, recode_device) -> np.ndarray:
    """Rows [lo, hi) of a column set → the packed int16 launch frame.
    Serial staging takes the single-pass ``prepare_cols_packed`` route
    (no intermediate eight-array allocation, native strided window
    writes); pooled staging keeps the slab-sharded two-phase form
    whose workers already write in place."""
    sl = cols if (lo == 0 and hi == len(cols[1])) else tuple(
        c[lo:hi] for c in cols
    )
    if pool is not None:
        args = _prepare_cols_pooled(sl, pad, pool,
                                    recode_device=recode_device)
        return _pack_launch(args, recode_device, pool=pool)
    return prepare_cols_packed(*sl, pad_to=pad,
                               recode_device=recode_device)


def _launch_cols(n_real, cols, chunk, mesh, pool, recode_device,
                 rec=None):
    """Column-form launch: stage straight into the packed wire frame
    (single-pass serial path, or slab-sharded over the host pool),
    dispatch (sharded), with the H2D frame size observed per
    dispatch."""
    kern = _packed_kernel(recode_device)
    rc = "device" if recode_device else "host"

    def dispatch(packed):
        _h2d_hist().observe(packed.nbytes, recode=rc)
        if rec is not None:
            rec.note_h2d(packed.nbytes)
            # re-anchor at the FIRST actual dispatch: the host
            # wire-frame staging above must not be booked as compile
            # (miss) or dispatch overhead (hit)
            rec.begin_dispatch()
        # the TraceAnnotation lines this dispatch up with the XLA
        # timeline when a jax profiler capture runs (real-TPU rounds)
        with _dev_ann("fabtpu.verify_dispatch"):
            return kern(_shard(mesh, packed))

    if chunk and n_real > chunk:
        # split stage/dispatch: with a host pool, _launch_chunked
        # stages chunk k+1 on a worker while chunk k dispatches (the
        # lookahead overlap the pipelined path needs).  The lookahead
        # worker may still SHARD its chunk across the pool when there
        # are ≥ 2 workers (map_slices from inside a worker completes
        # on the remaining slots); a 1-worker pool would deadlock on
        # itself, so it stages serially there.
        inner = pool if (pool is not None
                         and getattr(pool, "workers", 1) >= 2) else None

        def stage(lo, hi, pad):
            return _stage_packed(cols, lo, hi, pad, inner, recode_device)

        return _launch_chunked(n_real, chunk, stage, dispatch_fn=dispatch,
                               pool=pool, rec=rec)
    packed = _stage_packed(cols, 0, n_real, _bucket(n_real), pool,
                           recode_device)
    out = dispatch(packed)
    if hasattr(out, "copy_to_host_async"):
        out.copy_to_host_async()
    if rec is not None:
        rec.dispatched()
    return VerifyHandle(out, n_real, rec)


def verify_launch(items, chunk: int | None = None, mesh=None, pool=None,
                  recode_device: bool = False) -> VerifyHandle:
    """Asynchronously dispatch a verify batch; returns a VerifyHandle
    (callable as a zero-arg fetch for list[bool]).  The jax dispatch is
    non-blocking, so the device crunches while the caller's host thread
    moves on — the pipeline primitive the block validator builds on.

    Accepts either legacy (digest, r, s, qx, qy) int tuples or a
    SigCollector (the commit path's zero-bigint column form).

    ``chunk``: microbatch size — batches larger than this split into
    chunks dispatched back to back (double-buffered: chunk k+1's host
    staging overlaps chunk k's device compute).  None/0 = one
    monolithic launch.  The accept set is identical either way
    (tests/test_p256v3.py pins chunked ≡ monolithic).

    ``mesh``: a parallel.mesh data mesh — the packed batch is device_put
    with axis 0 sharded over it, so XLA partitions the whole ladder
    across the chips (the verify is per-lane independent: bit-equal to
    single-device, pinned by tests/test_multidevice.py).  None =
    default single-device placement.

    ``pool``: a parallel.hostpool.HostStagePool — the per-signature
    host staging (admission checks, Montgomery batch inversion, window
    recoding, residue dgemm) shards over its workers along the lane
    axis at bucket boundaries; bit-equal to serial staging (pinned by
    tests/test_p256v3.py).  None = serial staging.

    ``recode_device``: ship u1/u2 as 16-bit scalar limbs and derive
    the 4-bit window digits on device (``verify_batch_packed_limbs``),
    shrinking the packed H2D frame (the window planes drop 4×, the
    whole frame ~1.4×); bit-equal to host recoding."""
    # chaos hook (fabric_tpu.faults): a FaultPlan can fail/slow the
    # ops-level dispatch itself — no-op when no plan is armed
    _faults.fire("p256v3.verify_launch")
    chunk = max(int(chunk), MIN_BUCKET) if chunk else 0
    if isinstance(items, (ColumnarSigBatch, SigCollector)):
        if not items.n:
            return VerifyHandle(jnp.zeros((0,), bool), 0)
        n_real = items.n
        cols = (items.assemble() if isinstance(items, ColumnarSigBatch)
                else _assemble_cols(items))
        return _launch_cols(n_real, cols, chunk, mesh, pool,
                            recode_device, rec=_verify_rec(n_real, chunk,
                                                           mesh,
                                                           recode_device))
    items = list(items)
    if not items:
        return VerifyHandle(jnp.zeros((0,), bool), 0)
    n_real = len(items)
    rec = _verify_rec(n_real, chunk, mesh, recode_device)
    if pool is not None or recode_device:
        # pooled staging and device recoding are COLUMN lanes: lift
        # legacy tuples into the column form (accept-set equal — the
        # chunked/coalesced differentials already pin this route)
        n_real, cols = _to_cols(items)
        return _launch_cols(n_real, cols, chunk, mesh, pool,
                            recode_device, rec=rec)
    if chunk and n_real > chunk:
        def stage(lo, hi, pad):
            return verify_batch_jit(
                *(_shard(mesh, a) for a in prepare(items[lo:hi], pad_to=pad))
            )

        return _launch_chunked(n_real, chunk, stage, rec=rec)
    args = prepare(items, pad_to=_bucket(n_real))
    if rec is not None:
        rec.note_h2d(sum(a.nbytes for a in args))
        rec.begin_dispatch()  # prepare() above was host staging
    if mesh is not None:
        args = tuple(_shard(mesh, a) for a in args)
    with _dev_ann("fabtpu.verify_dispatch"):
        out = verify_batch_jit(*args)  # async under deferred execution
    if hasattr(out, "copy_to_host_async"):
        # start the D2H as soon as compute finishes: device→host
        # readback latency must overlap the caller's host work, not
        # serialize behind it
        out.copy_to_host_async()
    if rec is not None:
        rec.dispatched()
    return VerifyHandle(out, n_real, rec)


def _verify_rec(n_real: int, chunk: int, mesh, recode_device: bool):
    """Open a launch-ledger record for one verify dispatch (None when
    the ledger is disarmed — a single global read + None check).  The
    structural key drives the ledger's first-seen compile inference:
    the jitted kernel retraces per (padded bucket or chunk shape,
    recode variant, mesh layout).  The row's ``bucket`` is the lanes
    the launch ran padded: ``_bucket(n_real)`` for a chunked launch
    too, whose chunks add up to it (``_chunk_bounds``).  The gauge
    ``device_verify_programs`` is an operations metric like
    ``device_stage2_programs``, not tracing: armed or not, a launch
    pays one set lookup for it."""
    bucket = _bucket(n_real)
    shape = chunk if (chunk and n_real > chunk) else bucket
    key = (shape, bool(recode_device), mesh.size if mesh is not None else 0)
    if key not in _PROGRAM_KEYS:
        _note_program(key)
    return _ledger.launch("verify", key=key, lanes=n_real,
                          shape={"bucket": bucket})


#: structural keys of the verify dispatches this process has made: the
#: jitted kernel holds one program per key (a chunked launch's padded
#: tail may add one more, which is not counted)
_PROGRAM_KEYS: set = set()


def _note_program(key) -> None:
    """A verify shape's first dispatch: ``device_verify_programs``, the
    verify side of ``device_stage2_programs`` — a gauge that still
    grows on a warmed channel means a size class the warm-up missed."""
    from fabric_tpu.ops_metrics import global_registry

    _PROGRAM_KEYS.add(key)
    global_registry().gauge(
        "device_verify_programs",
        "distinct verify program shapes dispatched by this process",
    ).set(len(_PROGRAM_KEYS))


def _to_cols(items):
    """Any verify_launch input form → (n_real, six prepare_cols
    column arrays)."""
    if isinstance(items, ColumnarSigBatch):
        return items.n, items.assemble()
    if isinstance(items, SigCollector):
        return items.n, _assemble_cols(items)
    c = SigCollector()
    for it in items:
        c.add_slow(it)
    return c.n, _assemble_cols(c)


def verify_launch_many(batches, chunk: int | None = None,
                       mesh=None, pool=None,
                       recode_device: bool = False) -> list[VerifyHandle]:
    """Coalesced dispatch of SEVERAL blocks' signature batches as ONE
    device launch, amortizing the 64-step ladder's dispatch latency
    across the blocks the pipeline has in flight.

    Layout: block b's items occupy device indices
    [off_b, off_b + _bucket(n_b)) of the concatenated batch — each
    block keeps the exact lane layout a solo ``verify_launch`` would
    give it (item i at local index i, padded to its own bucket), so the
    returned per-block VerifyHandles expose ``device_out`` slices that
    stage-2 and the committer consume unchanged, with unchanged
    program-cache shapes.  The total is padded out to
    ``_bucket(Σ buckets)`` so the coalesced dispatch stays inside the
    same bucket family as monolithic launches.

    Composes with ``chunk`` (the concatenated batch microbatches like
    any other), ``mesh`` (axis-0 sharding), ``pool`` (host staging
    sharded over cores) and ``recode_device`` (limb wire form + device
    window recoding).  Accept-set-equivalence vs per-block launches is
    pinned by tests/test_p256v3.py."""
    batches = [
        b if isinstance(b, (ColumnarSigBatch, SigCollector)) else list(b)
        for b in batches
    ]
    sizes, colsets = [], []
    for b in batches:
        n, cols = (0, None) if _batch_len(b) == 0 else _to_cols(b)
        sizes.append(n)
        colsets.append(cols)
    live = [(n, cols) for n, cols in zip(sizes, colsets) if n]
    if not live:
        return [VerifyHandle(jnp.zeros((0,), bool), 0) for _ in batches]
    if len(live) == 1:
        # nothing to coalesce: solo launch for the one non-empty block
        _coalesce_metric().observe(1)
        out = []
        for b, n in zip(batches, sizes):
            out.append(
                verify_launch(b, chunk=chunk, mesh=mesh, pool=pool,
                              recode_device=recode_device) if n
                else VerifyHandle(jnp.zeros((0,), bool), 0)
            )
        return out

    # chaos hook — fired here (not at function entry) so the solo
    # delegation above doesn't double-count against a fault budget
    _faults.fire("p256v3.verify_launch")
    # concatenate per-block columns, each padded to its own bucket
    offs, total = [], 0
    for n in sizes:
        offs.append(total)
        total += _bucket(n) if n else 0
    grand = _bucket(total)
    cat = []
    for ci in range(6):
        ref = live[0][1][ci]
        col = np.zeros((grand,) + ref.shape[1:], ref.dtype)
        for off, n, cols in zip(offs, sizes, colsets):
            if n:
                col[off:off + n] = cols[ci]
        cat.append(col)
    _coalesce_metric().observe(len(live))

    chunk = max(int(chunk), MIN_BUCKET) if chunk else 0
    # all `grand` lanes are "real" to the chunker (padding lanes are
    # pre-rejected rows); its tail invariant pads to
    # _bucket(grand) == grand
    inner = _launch_cols(grand, tuple(cat), chunk, mesh, pool,
                         recode_device,
                         rec=_verify_rec(grand, chunk, mesh,
                                         recode_device))
    dev = inner.device_out
    out = [
        VerifyHandle(dev[off:off + _bucket(n)], n) if n
        else VerifyHandle(jnp.zeros((0,), bool), 0)
        for off, n in zip(offs, sizes)
    ]
    # ONE ledger record covers the coalesced dispatch: the first live
    # block's fetch closes it (slices sync the shared computation)
    for h in out:
        if h.n_real:
            h.rec = inner.rec
            break
    return out


def _batch_len(items) -> int:
    if isinstance(items, (ColumnarSigBatch, SigCollector)):
        return items.n
    return len(items)


def verify_host(items) -> list[bool]:
    """items: iterable of (digest_int, r, s, qx, qy) Python ints, or
    a column-form batch; the synchronous form of ``verify_launch``."""
    return verify_launch(items)()
