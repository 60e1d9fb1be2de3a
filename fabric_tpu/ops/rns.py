"""Residue-number-system (RNS) modular arithmetic for the MXU — the
"Cox-Rower" design (Kawamura et al., CHES 2000) that dedicated ECC
hardware uses, re-expressed as TPU matmuls.

Why RNS on TPU: a 256-bit value is its residues modulo ~23 small
coprime primes, so a big-int multiply is an ELEMENTWISE lane-wise
product — no convolution at all.  The only non-elementwise step is
Montgomery reduction's base extension, which is a DENSE
[B, 2n] @ [2n, 3n+…] matmul against a constant matrix — exactly the
shape the MXU wants (a digit-polynomial multiply is a one-hot
contraction whose MXU flops fall mostly on structural zeros and needs
HIGHEST, multi-pass, precision).  Here every matmul input is a 6-bit
chunk, so single-pass bf16×bf16→f32 MXU arithmetic is EXACT by
construction:
products ≤ 63·63 < 2^12, accumulated over ≤ 2n=46 rows < 2^18 « 2^24.

Representation.  Two bases A = {m_1..m_n}, B = {m'_1..m'_n} of 12-bit
primes, M = ΠA, M' = ΠB (each ≈ 2^276 » 4·2^256).  A value v (a
non-negative integer with a TRACKED Python-int bound, far below M·M')
is carried as its 2n canonical residues [..., 2n] int32.  Montgomery
multiplication (x, y) → x·y·M⁻¹ mod p follows Kawamura:

  t   = x ⊙ y                     (lane products, both bases)
  q   = t ⊙ (−p⁻¹) mod m_i        (base A lanes)
  q̂   : A → B base extension with a DOWN-BIASED rank α̂ = ⌊s − ε⌋ —
        q̂ ∈ {q, q+M}; the slack only adds one p to the result
  r   = (t + q̂·p) · M⁻¹ mod m'_j  (base B lanes) — r < 2p + 1
  r   : B → A base extension with an EXACT rank α = ⌊s + ¼⌋, exact
        because r < 3p « M'/4 (Kawamura's condition with margin ½)

Base extension v → ξ_i = v_i·(M/m_i)⁻¹ mod m_i, then
v = Σ ξ_i·(M/m_i) − α·M where α = ⌊Σ ξ_i/m_i⌋ computed in f32 (error
≈ n·2⁻²³ « ¼).  The Σ ξ_i·(M/m_i) mod m'_j term is the dense matmul:
inputs are ξ split into 6-bit chunks, weights are (M/m_i mod m'_j)
split into 6-bit chunks, three output columns per target prime
(lo·lo | lo·hi+hi·lo | hi·hi) recombined with shifts in int32.

Per-lane modular reduction by the prime vector uses the float
reciprocal trick (t < 2^24 exact in f32; quotient error ≤ 1 fixed by
one conditional add/sub), so there is no integer division anywhere.

Reference semantics anchored: this module exists to make
bccsp/sw/ecdsa.go:41-58's accept set fast; bit-exactness is enforced
by tests/test_rns.py property tests against Python ints (CRT
reconstruction of every result).
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Base construction (module constants: both ECDSA moduli share the bases)

N_CH = 23          # primes per base
CHUNK = 6          # bits per matmul chunk
CMASK = (1 << CHUNK) - 1


def _primes_below(limit: int, count: int) -> list[int]:
    sieve = np.ones(limit, dtype=bool)
    sieve[:2] = False
    for i in range(2, int(limit ** 0.5) + 1):
        if sieve[i]:
            sieve[i * i :: i] = False
    ps = np.nonzero(sieve)[0][::-1]  # descending
    return [int(p) for p in ps[:count]]


_ALL = _primes_below(1 << 12, 2 * N_CH)  # largest 46 primes under 2^12
BASE_A = _ALL[0::2]
BASE_B = _ALL[1::2]
M_A = 1
for _p in BASE_A:
    M_A *= _p
M_B = 1
for _p in BASE_B:
    M_B *= _p
assert M_A > 1 << 270 and M_B > 1 << 270

_EPS_DOWN = 32 * N_CH / (1 << 23)  # conservative f32 rank-sum error bound


def _to_res(x: int, primes) -> np.ndarray:
    return np.array([x % m for m in primes], np.int32)


class _Ext:
    """Constants for one direction of base extension src → dst."""

    def __init__(self, src: list[int], dst: list[int]):
        n = len(src)
        M = 1
        for m in src:
            M *= m
        self.M = M
        # ξ_i = v_i · (M/m_i)^{-1} mod m_i
        self.inv_w = np.array(
            [pow(M // m, -1, m) for m in src], np.int32
        )
        # W[i, j] = (M/m_i) mod dst_j, 6-bit chunked into the
        # (lo·lo | lo·hi + hi·lo | hi·hi) three-block weight matrix
        C = np.array([[(M // mi) % mj for mj in dst] for mi in src], np.int64)
        c_lo, c_hi = C & CMASK, C >> CHUNK
        nd = len(dst)
        W = np.zeros((2 * n, 3 * nd), np.float32)
        W[:n, 0:nd] = c_lo          # ξ_lo · c_lo
        W[:n, nd:2 * nd] = c_hi     # ξ_lo · c_hi
        W[n:, nd:2 * nd] = c_lo     # ξ_hi · c_lo
        W[n:, 2 * nd:] = c_hi       # ξ_hi · c_hi
        self.W = jnp.asarray(W, jnp.bfloat16)
        # α correction: M mod dst_j, plus a non-negativity offset
        self.M_mod_dst = np.array([M % mj for mj in dst], np.int64)
        self.alpha_max = n + 1
        self.inv_src_f32 = jnp.asarray(
            np.array([1.0 / m for m in src], np.float32)
        )


class Modulus:
    """Per-channel constants for one base (or both stacked)."""

    def __init__(self, primes: list[int]):
        self.primes = list(primes)
        self.m = jnp.asarray(np.array(primes, np.int32))
        self.m_f32 = self.m.astype(jnp.float32)
        self.inv_f32 = jnp.asarray(np.array([1.0 / m for m in primes], np.float32))
        self.c20 = jnp.asarray(
            np.array([(1 << 20) % m for m in primes], np.int32)
        )

    def rem24(self, t):
        """t int32 in [0, 2^24) → t mod m, exact (float reciprocal +
        one-step correction)."""
        q = jnp.floor(t.astype(jnp.float32) * self.inv_f32).astype(jnp.int32)
        r = t - q * self.m
        r = r + jnp.where(r < 0, self.m, 0)
        return r - jnp.where(r >= self.m, self.m, 0)

    def rem30(self, t):
        """t int32 in [0, 2^30) → t mod m (one 2^20 fold, then rem24)."""
        folded = (t >> 20) * self.c20 + (t & ((1 << 20) - 1))
        return self.rem24(folded)

    def mulmod_const(self, a, c_i32):
        """a canonical [.., n] times per-channel constant < m."""
        return self.rem24(a * c_i32)


MOD_A = Modulus(BASE_A)
MOD_B = Modulus(BASE_B)
MOD_ALL = Modulus(BASE_A + BASE_B)

EXT_AB = _Ext(BASE_A, BASE_B)
EXT_BA = _Ext(BASE_B, BASE_A)


def _extend(v, ext: _Ext, dst: Modulus, exact: bool):
    """Base extension: v [..., n] canonical residues of an integer
    < ext.M (exact mode: < ext.M/4) → [..., n_dst] canonical residues.

    exact=False: rank down-biased; result represents v or v + ext.M.
    exact=True:  result represents v exactly (caller guarantees the
    bound margin)."""
    n = v.shape[-1]
    xi = _xi(v, ext)
    s = jnp.sum(xi.astype(jnp.float32) * ext.inv_src_f32, axis=-1)
    if exact:
        alpha = jnp.floor(s + 0.25).astype(jnp.int32)
    else:
        alpha = jnp.floor(s - _EPS_DOWN).astype(jnp.int32)
        alpha = jnp.maximum(alpha, 0)
    chunks = jnp.concatenate([xi & CMASK, xi >> CHUNK], axis=-1)
    out3 = jax.lax.dot_general(
        chunks.astype(jnp.bfloat16), ext.W,
        (((chunks.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    nd = len(dst.primes)
    raw = out3[..., :nd] + (out3[..., nd:2 * nd] << CHUNK) + (
        out3[..., 2 * nd:] << (2 * CHUNK)
    )
    # keep raw − α·(M mod m_j) non-negative: add α_max·m_j (≡ 0 mod m_j)
    corr = jnp.asarray(
        (ext.alpha_max * np.array(dst.primes, np.int64)).astype(np.int32)
    )
    raw = raw + corr - alpha[..., None] * jnp.asarray(
        ext.M_mod_dst.astype(np.int32)
    )
    return dst.rem30(raw)


def _xi(v, ext: _Ext):
    """ξ_i = v_i · (M/m_i)^{-1} mod m_i on the SOURCE channels."""
    src_mod = MOD_A if ext is EXT_AB else MOD_B
    return src_mod.mulmod_const(v, jnp.asarray(ext.inv_w))


# ---------------------------------------------------------------------------
# Montgomery context for one odd modulus p (p or the group order n)


class MontCtx:
    """Montgomery-RNS context: x̃ = x·M_A mod p domain over BASE_A."""

    def __init__(self, p: int):
        # all constants numpy (concrete): a MontCtx may be constructed
        # lazily inside a jit trace and cached across traces — jnp
        # arrays created there would be leaked tracers
        self.p = p
        self.neg_p_inv_A = np.array(
            [(-pow(p, -1, m)) % m for m in BASE_A], np.int32
        )
        self.p_B = _to_res(p, BASE_B)
        self.invMA_B = np.array(
            [pow(M_A % m, -1, m) for m in BASE_B], np.int32
        )
        self.RR = to_rns((M_A * M_A) % p)        # Montgomery entry constant
        self.ONE = to_rns(1)
        self.p_res = np.concatenate([_to_res(p, BASE_A), _to_res(p, BASE_B)])
        self._lam_cache: dict[int, jnp.ndarray] = {}

    def lam_p(self, lam: int) -> np.ndarray:
        """Canonical residues of λ·p (subtraction offsets)."""
        got = self._lam_cache.get(lam)
        if got is None:
            # numpy (concrete), NOT jnp: this cache outlives traces —
            # a jnp array created inside a jit trace is a tracer and
            # leaking it across traces is an error
            got = np.concatenate([
                _to_res(lam * self.p, BASE_A), _to_res(lam * self.p, BASE_B)
            ])
            self._lam_cache[lam] = got
        return got


CTX_CACHE: dict[int, MontCtx] = {}


def ctx_for(p: int) -> MontCtx:
    if p not in CTX_CACHE:
        CTX_CACHE[p] = MontCtx(p)
    return CTX_CACHE[p]


# ---------------------------------------------------------------------------
# RV: residues + trace-time integer bound


class RV:
    """An RNS value: [..., 2n] int32 canonical residues (base A ‖ B)
    plus a Python-int bound on the represented non-negative integer.
    The bound rides along tracing, so Montgomery/extension preconditions
    are asserted while BUILDING the jaxpr."""

    __slots__ = ("arr", "bound")

    def __init__(self, arr, bound: int):
        self.arr = arr
        self.bound = int(bound)

    def __add__(self, other: "RV") -> "RV":
        t = self.arr + other.arr
        m = MOD_ALL.m
        return RV(t - jnp.where(t >= m, m, 0), self.bound + other.bound)


def rv_sub(x: RV, y: RV, ctx: MontCtx) -> RV:
    """x − y (mod p) kept non-negative by adding ⌈y.bound/p⌉·p."""
    lam = -(-y.bound // ctx.p)
    t = x.arr + ctx.lam_p(lam) - y.arr
    m = MOD_ALL.m
    t = t - jnp.where(t >= m, m, 0)
    t = t + jnp.where(t < 0, m, 0)
    return RV(t, x.bound + lam * ctx.p)


def _mont_mul_arr(xa, ya, ctx: MontCtx):
    """Array-level Montgomery pipeline on [..., 2n] canonical residues
    (leading dims arbitrary — the stacked-mul path rides them)."""
    t = MOD_ALL.rem24(xa * ya)
    n = N_CH
    tA, tB = t[..., :n], t[..., n:]
    q = MOD_A.mulmod_const(tA, ctx.neg_p_inv_A)
    qB = _extend(q, EXT_AB, MOD_B, exact=False)   # q or q + M_A
    u = MOD_B.mulmod_const(qB, ctx.p_B)
    num = MOD_B.rem24(tB + u)
    rB = MOD_B.mulmod_const(num, ctx.invMA_B)
    rA = _extend(rB, EXT_BA, MOD_A, exact=True)
    return jnp.concatenate([rA, rB], axis=-1)


def _mul_bound(x: RV, y: RV, ctx: MontCtx) -> int:
    T = x.bound * y.bound
    # extension-margin preconditions (trace-time)
    assert T // M_A + ctx.p < M_B // 4, "r-extension margin violated"
    assert T < M_A * M_B // 8, "product overflows the RNS range"
    return T // M_A + 2 * ctx.p + 1


def mont_mul(x: RV, y: RV, ctx: MontCtx) -> RV:
    """x·y·M_A⁻¹ mod p (Montgomery step); output bound
    x.b·y.b/M_A + 2p + 1 < 3p for all sane inputs."""
    out_bound = _mul_bound(x, y, ctx)
    return RV(_mont_mul_arr(x.arr, y.arr, ctx), out_bound)


def mont_mul_many(pairs, ctx: MontCtx) -> list:
    """k independent Montgomery muls as ONE stacked pipeline.

    The point formulas have 2-6 independent muls per stage; stacking
    them turns k tiny [B,46]@[46,72] matmuls into one [k·B,46]@[46,72]
    — same flops, ~k× fewer dispatches and better MXU occupancy.
    Operands are broadcast to a common shape before stacking
    (constants ride along as [2n] rows)."""
    bounds = [_mul_bound(x, y, ctx) for x, y in pairs]
    shape = np.broadcast_shapes(*(
        np.shape(v.arr) for pair in pairs for v in pair
    ))
    xs = jnp.stack([jnp.broadcast_to(x.arr, shape) for x, _ in pairs])
    ys = jnp.stack([jnp.broadcast_to(y.arr, shape) for _, y in pairs])
    out = _mont_mul_arr(xs, ys, ctx)
    return [RV(out[i], b) for i, b in enumerate(bounds)]


def to_mont(x: RV, ctx: MontCtx) -> RV:
    return mont_mul(x, ctx.RR, ctx)


def from_mont(x: RV, ctx: MontCtx) -> RV:
    return mont_mul(x, ctx.ONE, ctx)


def eq_const_mod_p(x: RV, ctx: MontCtx):
    """x ≡ 0 (mod p) for x = a Montgomery-domain value: reduce with a
    mont-by-one (strips M_A, bound < 3p) then compare residues against
    0, p and 2p exactly."""
    w = from_mont(x, ctx)
    assert w.bound <= 3 * ctx.p
    hits = jnp.all(w.arr == 0, axis=-1)
    for k in (1, 2):
        cres = _to_res(k * ctx.p, BASE_A + BASE_B)
        hits = hits | jnp.all(w.arr == cres, axis=-1)
    return hits


# ---------------------------------------------------------------------------
# Host conversions (numpy, vectorized — no per-digit Python loops)

_POW8 = None


def _pow8_table() -> np.ndarray:
    """[40, 2n] float64: 2^(8k) mod m for the limb contraction."""
    global _POW8
    if _POW8 is None:
        primes = BASE_A + BASE_B
        _POW8 = np.array(
            [[pow(2, 8 * k, m) for m in primes] for k in range(40)], np.float64
        )
    return _POW8


def ints_to_rns(xs) -> np.ndarray:
    """[B] Python ints (< 2^320) → [B, 2n] canonical residues.

    The limb contraction runs in float64 (BLAS dgemm — numpy's int64
    matmul is a scalar loop): 8-bit limbs × 12-bit table entries summed
    over 40 limbs stay < 2^43, exact in f64's 53-bit mantissa."""
    if not len(xs):
        return np.zeros((0, 2 * N_CH), np.int32)
    limbs = np.frombuffer(
        b"".join(int(x).to_bytes(40, "little") for x in xs), np.uint8
    ).reshape(len(xs), 40).astype(np.float64)
    acc = limbs @ _pow8_table()  # [B, 2n] exact in f64
    primes = np.array(BASE_A + BASE_B, np.int64)
    return (acc.astype(np.int64) % primes).astype(np.int32)


def bytes_to_rns(be: np.ndarray, out: np.ndarray | None = None) -> np.ndarray:
    """[B, 32] uint8 BIG-endian 256-bit values → [B, 2n] canonical
    residues — the zero-Python-int fast lane for values the native
    pre-parser already delivers as byte arrays (r, s, digests).  Same
    f64 dgemm as ints_to_rns; bytes reverse to little-endian limbs.

    ``out``: optional [B, 2n] int32 destination written in place (the
    pooled staging path hands row-slab views here so the residues land
    directly in the preallocated launch columns); returned either way."""
    if not len(be):
        return out if out is not None else np.zeros((0, 2 * N_CH), np.int32)
    le = be[:, ::-1].astype(np.float64)  # [B, 32] little-endian limbs
    acc = le @ _pow8_table()[:32]  # [B, 2n] exact in f64
    primes = np.array(BASE_A + BASE_B, np.int64)
    res = acc.astype(np.int64) % primes
    if out is not None:
        out[:] = res  # same values, cast into the caller's int32 slab
        return out
    return res.astype(np.int32)


def to_rns(x: int) -> RV:
    """Single constant → broadcastable RV (numpy-backed: constants
    must stay concrete across jit traces)."""
    return RV(_to_res(x, BASE_A + BASE_B), x)


def rv_to_ints(arr) -> list[int]:
    """CRT reconstruction over all 2n channels (tests/oracles only)."""
    primes = BASE_A + BASE_B
    Mall = M_A * M_B
    coeffs = [(Mall // m) * pow(Mall // m, -1, m) for m in primes]
    a = np.asarray(arr).reshape(-1, 2 * N_CH)
    return [
        sum(int(r) * c for r, c in zip(row, coeffs)) % Mall for row in a
    ]
