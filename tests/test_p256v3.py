"""RNS ECDSA kernel (ops.rns + ops.p256v3) verification tests.

Oracle layers:
1. field core — tests/test_rns.py;
2. RCB complete point formulas over RNS vs crypto.ec_ref point ops,
   including the degenerate lanes (doubling, inverses, infinity);
3. full verify_batch vs the reference accept set
   (bccsp/sw/ecdsa.go:41-58 semantics: low-S, ranges, on-curve).
"""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from fabric_tpu.crypto import ec_ref
from fabric_tpu.ops import p256v3 as v3
from fabric_tpu.ops import rns

P = ec_ref.P


def _pt_rv(points):
    """affine points (or None for ∞) → Montgomery projective RV triple."""
    xs, ys, zs = [], [], []
    for pt in points:
        if pt is None:
            xs.append(0); ys.append(rns.M_A % P); zs.append(0)
        else:
            xs.append(pt[0] * rns.M_A % P)
            ys.append(pt[1] * rns.M_A % P)
            zs.append(rns.M_A % P)
    return tuple(
        rns.RV(jnp.asarray(rns.ints_to_rns(v)), v3._BND_STATE)
        for v in (xs, ys, zs)
    )


def _affine(rv_triple):
    """RV projective triple → affine ints (or None for ∞) via CRT."""
    ctx = rns.ctx_for(P)
    out = []
    coords = [
        [v % P for v in rns.rv_to_ints(rns.from_mont(c, ctx).arr)]
        for c in rv_triple
    ]
    for x, y, z in zip(*coords):
        if z == 0:
            out.append(None)
        else:
            zi = pow(z, -1, P)
            out.append((x * zi % P, y * zi % P))
    return out


def test_rcb_complete_add_and_double(rng):
    """Complete addition handles: generic, doubling (P=Q), inverse
    (P=-Q → ∞), ∞ operands — all in one batch, no branches."""
    ctx = rns.ctx_for(P)
    b_m = v3._const_rv(v3.B_COEF * rns.M_A % P)
    G = (v3.GX, v3.GY)
    k2G = ec_ref.pt_mul(2, G)
    k3G = ec_ref.pt_mul(3, G)
    negG = (v3.GX, P - v3.GY)
    p1 = [G, G, G, None, k2G]
    p2 = [k2G, G, negG, k3G, None]
    want = [k3G, k2G, None, k3G, k2G]
    out = v3.pt_add(_pt_rv(p1), _pt_rv(p2), b_m, ctx)
    assert _affine(out) == want

    dbl = v3.pt_double(_pt_rv([G, k2G, None, k3G]), b_m, ctx)
    assert _affine(dbl) == [k2G, ec_ref.pt_mul(4, G), None, ec_ref.pt_mul(6, G)]


def test_rcb_mixed_add(rng):
    ctx = rns.ctx_for(P)
    b_m = v3._const_rv(v3.B_COEF * rns.M_A % P)
    G = (v3.GX, v3.GY)
    k2G = ec_ref.pt_mul(2, G)
    p1 = _pt_rv([k2G, None, G])
    # affine P2 = G for every lane (Montgomery residues)
    gx = rns.RV(jnp.asarray(rns.ints_to_rns([v3.GX * rns.M_A % P] * 3)), P)
    gy = rns.RV(jnp.asarray(rns.ints_to_rns([v3.GY * rns.M_A % P] * 3)), P)
    out = v3.pt_add_mixed(p1, gx, gy, b_m, ctx)
    assert _affine(out) == [ec_ref.pt_mul(3, G), G, k2G]


@pytest.fixture(scope="module")
def keys():
    return [ec_ref.SigningKey.generate() for _ in range(3)]


def test_verify_accepts_valid_and_rejects_adversarial(keys, rng):
    items, want = [], []
    for i in range(12):
        k = keys[i % 3]
        e = ec_ref.digest_int(b"payload-%d" % i)
        r, s = k.sign_digest(e)
        items.append((e, r, s, *k.public))
        want.append(True)
    e = ec_ref.digest_int(b"hs")
    r, s = keys[0].sign_digest(e)
    adversarial = [
        (ec_ref.digest_int(b"other"), r, s, *keys[0].public),  # wrong digest
        (e, r, ec_ref.N - s, *keys[0].public),                 # high-S
        (e, 0, s, *keys[0].public),                            # r = 0
        (e, r, 0, *keys[0].public),                            # s = 0
        (e, ec_ref.N, s, *keys[0].public),                     # r = n
        (e, s, r, *keys[0].public),                            # swapped
        (e, r, s, keys[0].public[0] + 1, keys[0].public[1]),   # off-curve Q
        (e, r, s, *keys[1].public),                            # wrong key
        (e, r, s, 0, 0),                                       # Q = ∞ encoding
    ]
    items += adversarial
    want += [False] * len(adversarial)
    got = v3.verify_host(items)
    assert got == want
    for (ei, ri, si, xi, yi), g in zip(items, got):
        assert g == ec_ref.verify_digest((xi, yi), ei, ri, si)


def test_verify_matches_oracle_randomized(keys, rng):
    items = []
    for i in range(48):
        k = keys[i % 3]
        e = ec_ref.digest_int(rng.bytes(16))
        r, s = k.sign_digest(e)
        kind = i % 6
        if kind == 1:
            r = (r + int(rng.integers(0, 3))) % ec_ref.N
        elif kind == 2:
            s = (s + int(rng.integers(0, 3))) % ec_ref.N
        elif kind == 3:
            e = (e + int(rng.integers(0, 2))) % (1 << 256)
        items.append((e, r, s, *k.public))
    got = v3.verify_host(items)
    want = [ec_ref.verify_digest((x, y), e, r, s) for (e, r, s, x, y) in items]
    assert got == want
    assert any(want) and not all(want)


def test_verify_batch_valid_and_corrupted(rng):
    keys = [
        ec_ref.SigningKey(d=int.from_bytes(rng.bytes(40), "big") % (ec_ref.N - 1) + 1)
        for _ in range(4)
    ]
    items, want = [], []
    for i in range(16):
        sk = keys[i % len(keys)]
        msg = b"payload-%d" % i
        e = ec_ref.digest_int(msg)
        r, s = sk.sign_digest(e)
        qx, qy = sk.public
        kind = i % 4
        if kind == 0:  # valid
            items.append((e, r, s, qx, qy))
            want.append(True)
        elif kind == 1:  # corrupted digest
            items.append((e ^ 1, r, s, qx, qy))
            want.append(False)
        elif kind == 2:  # corrupted s
            items.append((e, r, (s + 1) % ec_ref.N, qx, qy))
            want.append(False)
        else:  # wrong key
            ox, oy = keys[(i + 1) % len(keys)].public
            items.append((e, r, s, ox, oy))
            want.append(False)
    got = v3.verify_host(items)
    assert got == want
    # agree with the pure-python oracle on every case
    for (e, r, s, qx, qy), g in zip(items, got):
        assert ec_ref.verify_digest((qx, qy), e, r, s) == g


def test_verify_against_openssl_generated():
    """Cross-check with OpenSSL-generated (non-low-S-normalized) sigs."""
    from cryptography.hazmat.primitives.asymmetric import ec as cec
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

    items, want = [], []
    for i in range(16):
        key = cec.generate_private_key(cec.SECP256R1())
        pub = key.public_key().public_numbers()
        msg = b"openssl-%d" % i
        sig = key.sign(msg, cec.ECDSA(hashes.SHA256()))
        r, s = decode_dss_signature(sig)
        if s > ec_ref.HALF_N:
            s = ec_ref.N - s  # normalize as the reference signer does
        e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
        items.append((e, r, s, pub.x, pub.y))
        want.append(True)
    assert v3.verify_host(items) == want


def _forge(pub, seed: int):
    """A low-S (e, r, s) that verifies under ``pub`` without its private
    key: R = u1·G + u2·Q, r = x(R) mod n, s = r/u2, e = u1·s (mod n)."""
    G = (ec_ref.GX, ec_ref.GY)
    for u in range(seed, seed + 64):
        u1, u2 = u, u + 7
        R = ec_ref.pt_add(ec_ref.pt_mul(u1, G), ec_ref.pt_mul(u2, pub))
        r = R[0] % ec_ref.N
        s = r * pow(u2, -1, ec_ref.N) % ec_ref.N
        if 0 < r and 0 < s <= ec_ref.HALF_N:
            return u1 * s % ec_ref.N, r, s
    raise AssertionError("no low-S forgery in 64 tries")


# the accept set, one admission rule a lane (bccsp/sw/ecdsa.go:41-58)
ACCEPT_SET_CASES = (
    "valid_low_s", "high_s", "r_zero", "s_zero", "r_eq_n", "s_eq_n",
    "r_plus_n", "s_plus_one", "digest_bit_flipped", "other_keys_point",
    "negated_point", "off_curve_qy_plus_one", "coordinate_ge_p",
    "q_zero_zero", "digest_zero", "digest_plus_n",
)


@pytest.fixture(scope="module")
def accept_set(keys):
    """The sixteen lanes and the kernel's verdicts on them: ONE 16-lane
    launch a module."""
    N = ec_ref.N
    sk, other = keys[0], keys[1]
    qx, qy = sk.public
    e = ec_ref.digest_int(b"accept-set")
    r, s = sk.sign_digest(e)
    r0, s0 = sk.sign_digest(0)
    e_small = 0x1234567
    rs, ss = sk.sign_digest(e_small)
    # x = 0 is on the curve (y² = b): presented as x = P the point is
    # the same mod P, and only the range rule rejects it
    y0 = pow(ec_ref.B, (P + 1) // 4, P)
    assert ec_ref.is_on_curve((0, y0))
    ef, rf, sf = _forge((0, y0), 0xACCE97)
    assert ec_ref.verify_digest((0, y0), ef, rf, sf)
    lanes = {
        "valid_low_s": (e, r, s, qx, qy),
        "high_s": (e, r, N - s, qx, qy),
        "r_zero": (e, 0, s, qx, qy),
        "s_zero": (e, r, 0, qx, qy),
        "r_eq_n": (e, N, s, qx, qy),
        "s_eq_n": (e, r, N, qx, qy),
        "r_plus_n": (e, r + N if r + N < 1 << 256 else (1 << 256) - 1,
                     s, qx, qy),
        "s_plus_one": (e, r, s + 1, qx, qy),
        "digest_bit_flipped": (e ^ (1 << 77), r, s, qx, qy),
        "other_keys_point": (e, r, s, *other.public),
        "negated_point": (e, r, s, qx, P - qy),
        "off_curve_qy_plus_one": (e, r, s, qx, (qy + 1) % P),
        "coordinate_ge_p": (ef, rf, sf, P, y0),
        "q_zero_zero": (e, r, s, 0, 0),
        "digest_zero": (0, r0, s0, qx, qy),
        "digest_plus_n": (e_small + N, rs, ss, qx, qy),
    }
    assert tuple(lanes) == ACCEPT_SET_CASES
    items = list(lanes.values())
    return dict(zip(lanes, zip(items, v3.verify_host(items))))


@pytest.mark.parametrize("case", ACCEPT_SET_CASES)
def test_accept_set(accept_set, case):
    (e, r, s, qx, qy), got = accept_set[case]
    assert got == ec_ref.verify_digest((qx, qy), e, r, s), case
    # the three lanes that must verify; every other breaks one rule
    assert got == (case in ("valid_low_s", "digest_zero", "digest_plus_n"))


def test_chunked_launch_matches_monolithic(keys, rng):
    """Microbatched dispatch (verify_launch chunk=...) must reproduce
    the monolithic accept set bit for bit, with item i at device index
    i of the concatenated output — both for exact-multiple and ragged
    tails, and for chunk ≥ batch (degrades to one launch)."""
    items = []
    for i in range(41):  # ragged vs chunk=16: 16 + 16 + 9-lane tail
        k = keys[i % 3]
        e = ec_ref.digest_int(rng.bytes(16))
        r, s = k.sign_digest(e)
        if i % 5 == 1:
            s = ec_ref.N - s  # high-S reject lane
        elif i % 5 == 3:
            e = (e + 1) % (1 << 256)  # wrong digest
        items.append((e, r, s, *k.public))
    mono = v3.verify_launch(items)()
    assert any(mono) and not all(mono)
    for chunk in (16, 32, 64):
        got = v3.verify_launch(items, chunk=chunk)()
        assert got == mono, f"chunk={chunk}"
    # exact multiple of the chunk (no padded tail)
    assert v3.verify_launch(items[:32], chunk=16)() == mono[:32]
    # chunk below MIN_BUCKET clamps instead of exploding into
    # per-signature launches
    assert v3.verify_launch(items, chunk=1)() == mono


def test_coalesced_launch_matches_per_block(keys, rng):
    """Multi-block launch coalescing (verify_launch_many) must be
    accept-set-equivalent to independent per-block launches — item i of
    block b at device index off_b + i, empty blocks inert — and stay
    equivalent when composed with chunk microbatching and with mesh
    sharding (conftest's 8 forced host devices)."""
    from fabric_tpu.parallel import mesh as pmesh

    def mk(n, tag):
        out = []
        for i in range(n):
            k = keys[i % 3]
            e = ec_ref.digest_int(b"%s-%d" % (tag, i))
            r, s = k.sign_digest(e)
            if i % 3 == 2:
                s = ec_ref.N - s  # reject lane
            out.append((e, r, s, *k.public))
        return out

    blocks = [mk(5, b"a"), [], mk(9, b"b"), mk(3, b"c")]
    solo = [v3.verify_launch(b)() for b in blocks]
    assert any(any(s) for s in solo) and not all(all(s) for s in solo if s)

    co = [h() for h in v3.verify_launch_many(blocks)]
    assert co == solo
    # composes with chunk microbatching (the coalesced batch chunks
    # like any other; per-block slices unchanged)
    assert [h() for h in v3.verify_launch_many(blocks, chunk=16)] == solo
    # composes with mesh sharding over the forced host devices
    mesh = pmesh.resolve_mesh(2)
    assert [h() for h in v3.verify_launch_many(blocks, mesh=mesh)] == solo
    # degenerate inputs: all-empty, and a single live block (falls back
    # to a solo launch, no concatenation)
    empty = v3.verify_launch_many([[], []])
    assert [h() for h in empty] == [[], []]
    one = v3.verify_launch_many([[], mk(5, b"a")])
    assert [h() for h in one] == [[], solo[0]]


def test_batch_inv_and_windows(rng):
    ss = [int.from_bytes(rng.bytes(32), "big") % ec_ref.N or 1 for _ in range(33)]
    inv = v3._batch_inv_mod_n(ss)
    for s, si in zip(ss, inv):
        assert s * si % ec_ref.N == 1
    us = [0, 1, 15, 16, (1 << 256) - 1] + [
        int.from_bytes(rng.bytes(32), "big") for _ in range(5)
    ]
    w = v3._windows(us)
    for u, row in zip(us, w):
        back = 0
        for d in row:
            back = (back << 4) | int(d)
        assert back == u


def test_device_recode_matches_host_windows(rng):
    """Recode-on-device bit-equality: the [B, 64] window digits the
    stage-1 kernel derives from 16-bit scalar limbs must equal the
    host ``_windows`` output for random scalars AND the edge cases
    (0, 1, n−1, high-bit-set, all-ones) — the wire-form inverse
    (``windows_to_limbs``) must round-trip too."""
    us = [0, 1, ec_ref.N - 1, 1 << 255, (1 << 256) - 1, 15, 16] + [
        int.from_bytes(rng.bytes(32), "big") for _ in range(25)
    ]
    host = v3._windows(us)
    limbs = v3._limbs16(us)
    assert limbs.dtype == np.int16 and limbs.shape == (len(us), 16)
    dev = np.asarray(v3.device_recode_windows(jnp.asarray(limbs)))
    assert np.array_equal(dev, host)
    # the native ec_prepare path packs C-computed digits into limbs:
    # digits → limbs → device digits must be the identity
    assert np.array_equal(v3.windows_to_limbs(host), limbs)
    # empty batch degenerates cleanly
    assert v3._limbs16([]).shape == (0, 16)
    assert v3.windows_to_limbs(np.zeros((0, 64), np.int32)).shape == (0, 16)


def test_recode_device_launch_matches_host(keys, rng):
    """verify_launch(recode_device=True) — the packed limb wire form +
    on-device recoding — must reproduce the host-recoded accept set
    bit for bit, with adversarial lanes load-bearing, and compose with
    chunking and coalescing."""
    items = []
    for i in range(16):
        k = keys[i % 3]
        e = ec_ref.digest_int(rng.bytes(16))
        r, s = k.sign_digest(e)
        if i % 4 == 1:
            s = ec_ref.N - s  # high-S reject lane
        elif i % 4 == 3:
            e = (e + 1) % (1 << 256)  # wrong digest
        items.append((e, r, s, *k.public))
    base = v3.verify_launch(items)()
    assert any(base) and not all(base)
    assert v3.verify_launch(items, recode_device=True)() == base
    # prepared columns carry limbs, and the packed frame is smaller
    n, cols = v3._to_cols(items)
    args = v3.prepare_cols(*cols, pad_to=16, recode_device=True)
    assert args[4].shape == (16, 16) and args[4].dtype == np.int16
    assert v3._PKL_COLS < v3._PK_COLS
    # composes with coalescing (per-block slices unchanged)
    many = v3.verify_launch_many([items[:7], items[7:]],
                                 recode_device=True)
    assert many[0]() + many[1]() == base


def test_pooled_prepare_cols_matches_serial(keys, rng):
    """Host-pool-sharded staging must be BIT-equal to serial staging:
    all eight prepare_cols outputs identical (admission flags, batch
    inversion, window planes — host digits and device limbs alike —
    residues, padding lanes), and the pooled launch's accept set
    identical through the kernel."""
    from fabric_tpu.parallel.hostpool import HostStagePool

    items = []
    for i in range(100):
        k = keys[i % 3]
        e = ec_ref.digest_int(rng.bytes(16))
        r, s = k.sign_digest(e)
        if i % 3 == 2:
            s = ec_ref.N - s
        items.append((e, r, s, *k.public))
    n, cols = v3._to_cols(items)
    with HostStagePool(2) as pool:
        # shard boundaries land at MIN_BUCKET multiples
        bounds = pool.slice_bounds(100, align=v3.MIN_BUCKET)
        assert len(bounds) == 2 and bounds[0][1] % v3.MIN_BUCKET == 0
        for recode in (False, True):
            serial = v3.prepare_cols(*cols, pad_to=128,
                                     recode_device=recode)
            pooled = v3._prepare_cols_pooled(cols, 128, pool,
                                             recode_device=recode)
            for i, (a, b) in enumerate(zip(serial, pooled)):
                a, b = np.asarray(a), np.asarray(b)
                assert a.dtype == b.dtype and np.array_equal(a, b), i
        # and through the kernel on a warm bucket-16 shape
        base = v3.verify_launch(items[:16])()
        assert v3.verify_launch(items[:16], pool=pool)() == base
        assert v3.verify_launch(items[:16], pool=pool,
                                recode_device=True)() == base


def test_prepare_cols_out_views_match_alloc(keys, rng):
    """``prepare_cols(out=...)`` — the pooled workers' direct-slab
    write path (no allocate-then-copy) — must be BIT-equal to the
    allocating form for host digits and device limbs alike, with every
    destination element written (slabs prefilled with garbage) and the
    pad tail zeroed.  ``bytes_to_rns(out=)`` rides the same path."""
    items = []
    for i in range(48):
        k = keys[i % 3]
        e = ec_ref.digest_int(rng.bytes(16))
        r, s = k.sign_digest(e)
        if i % 4 == 1:
            s = ec_ref.N - s  # high-S reject lane
        if i % 11 == 0:
            r = ec_ref.N + 5  # out-of-range r
        items.append((e, r, s, *k.public))
    n, cols = v3._to_cols(items)
    pad = v3._bucket(n)
    assert pad > n  # the pad-tail zeroing is load-bearing here
    R = 2 * rns.N_CH
    for recode in (False, True):
        base = v3.prepare_cols(*cols, pad_to=pad, recode_device=recode)
        wcols = v3._PK_LIMBS if recode else v3.STEPS
        wdt = np.int16 if recode else np.int32
        out = (
            np.full((pad, R), 7, np.int32),
            np.full((pad, R), 7, np.int32),
            np.full((pad, R), 7, np.int32),
            np.full((pad, R), 7, np.int32),
            np.full((pad, wcols), 7, wdt),
            np.full((pad, wcols), 7, wdt),
            np.ones(pad, bool),
            np.ones(pad, bool),
        )
        got = v3.prepare_cols(*cols, pad_to=pad, recode_device=recode,
                              out=out)
        assert got is out
        for i, (a, b) in enumerate(zip(base, out)):
            a = np.asarray(a)
            assert a.dtype == b.dtype and np.array_equal(a, b), (recode, i)
        # row-slab views (what _prepare_cols_pooled hands workers):
        # stage [16:48) of fresh slabs in place, compare the rows
        slab = tuple(np.full_like(np.asarray(a), 3) for a in base)
        v3.prepare_cols(*(c[16:48] for c in cols), recode_device=recode,
                        out=tuple(d[16:48] for d in slab))
        for i, (a, b) in enumerate(zip(base, slab)):
            assert np.array_equal(np.asarray(a)[16:48], b[16:48]), (recode, i)

    # bytes_to_rns(out=) ≡ allocating form
    r_b = cols[1]
    dst = np.full((len(r_b), R), 9, np.int32)
    assert rns.bytes_to_rns(r_b, out=dst) is dst
    assert np.array_equal(dst, rns.bytes_to_rns(r_b))
    empty = np.zeros((0, R), np.int32)
    assert rns.bytes_to_rns(r_b[:0], out=empty) is empty

    # the mismatched-size guard fails loudly, not with silent wraps
    with pytest.raises(ValueError):
        v3.prepare_cols(*cols, pad_to=pad, out=tuple(a[:8] for a in out))


def test_prepare_cols_packed_matches_two_phase(keys, rng):
    """The single-pass packed staging (``prepare_cols_packed`` — the
    serial sig_prepare host-cycle eliminator: native STRIDED int16
    window/limb writes straight into the launch frame, one residue
    scratch, no intermediate eight-array staging) must be BYTE-equal
    to ``pack_cols(prepare_cols(...))`` / ``pack_cols_limbs(...)`` for
    host digits and device limbs alike — admission flags, reject
    lanes, out-of-range r, pad tail and all — with ``out=`` frame
    reuse over prefilled garbage, and identical through the kernel."""
    items = []
    for i in range(41):
        k = keys[i % 3]
        e = ec_ref.digest_int(rng.bytes(16))
        r, s = k.sign_digest(e)
        if i % 4 == 1:
            s = ec_ref.N - s  # high-S reject lane
        if i % 13 == 0:
            r = ec_ref.N + 5  # out-of-range r
        items.append((e, r, s, *k.public))
    n, cols = v3._to_cols(items)
    pad = v3._bucket(n)
    assert pad > n  # pad-tail zeroing is load-bearing
    for recode in (False, True):
        args = v3.prepare_cols(*cols, pad_to=pad, recode_device=recode)
        two_phase = (v3.pack_cols_limbs(*args) if recode
                     else v3.pack_cols(*args))
        packed = v3.prepare_cols_packed(*cols, pad_to=pad,
                                        recode_device=recode)
        assert packed.dtype == np.int16
        assert np.array_equal(two_phase, packed), recode
        # out= reuse over garbage: every element rewritten or zeroed
        buf = np.full(packed.shape, 77, np.int16)
        got = v3.prepare_cols_packed(*cols, pad_to=pad,
                                     recode_device=recode, out=buf)
        assert got is buf and np.array_equal(buf, two_phase)
        # mis-shaped out fails loudly
        with pytest.raises(ValueError):
            v3.prepare_cols_packed(*cols, pad_to=pad,
                                   recode_device=recode,
                                   out=buf[:, :-1].copy())
    # empty batch: an all-zero (all-rejected) frame
    empty = v3.prepare_cols_packed(*(c[:0] for c in cols), pad_to=16)
    assert empty.shape == (16, v3._PK_COLS) and not empty.any()
    # and the kernel sees the same accept set either way (the serial
    # launch path now stages through prepare_cols_packed)
    base = [
        ec_ref.verify_digest((qx, qy), e, r, s)
        for (e, r, s, qx, qy) in items[:16]
    ]
    assert v3.verify_launch(items[:16])() == base


def test_prepare_cols_native_matches_python():
    """The native ec_prepare (batch inversion + window recoding +
    admission flags in C) must be bit-exact with the Python prepare
    path across valid, high-S, out-of-range and degenerate rows."""
    import numpy as np

    import fabric_tpu.native as nat
    from fabric_tpu.crypto import ec_ref
    from fabric_tpu.ops import p256v3

    keys = [ec_ref.SigningKey.generate() for _ in range(3)]
    items = []
    for i in range(41):
        k = keys[i % 3]
        e = ec_ref.digest_int(b"m%d" % i)
        r, s = k.sign_digest(e)
        if i % 7 == 0:
            s = ec_ref.N - s  # high-S: must reject
        if i % 11 == 0:
            r = ec_ref.N + 5  # out-of-range r
        if i % 13 == 0:
            s = 0
        if i % 17 == 0:
            r = ec_ref.P - ec_ref.N + 3  # rpn_ok boundary region
        items.append((e, r, s, *k.public))
    items.append((5, 0, 1, 0, 0))
    c = p256v3.SigCollector()
    for it in items:
        c.add_slow(it)
    cols = p256v3._assemble_cols(c)
    pad = p256v3._bucket(len(items))
    a_native = p256v3.prepare_cols(*cols, pad_to=pad)
    if nat.ecprep_lib() is None:
        import pytest

        pytest.skip("no native toolchain")
    nat._lib_failed.add("ecprep")
    nat._libs.pop("ecprep", None)
    try:
        a_python = p256v3.prepare_cols(*cols, pad_to=pad)
    finally:
        nat._lib_failed.discard("ecprep")
    for x, y, name in zip(
        a_native, a_python,
        ["qx", "qy", "r", "rpn", "w1", "w2", "rpn_ok", "pre_ok"],
    ):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


def test_sigcollector_mixed_fast_slow_rows():
    """Interleaved fast (byte-array) and slow (tuple) rows through the
    collector must verify identically to the all-tuple path."""
    import numpy as np

    from fabric_tpu.crypto import ec_ref
    from fabric_tpu.ops import p256v3

    class _FakeIdent:
        def __init__(self, pub):
            self.public_numbers = pub

        @property
        def rns_pub(self):
            from fabric_tpu.ops import rns

            res = rns.ints_to_rns(list(self.public_numbers))
            return res[0], res[1]

    keys = [ec_ref.SigningKey.generate() for _ in range(2)]
    items = []
    for i in range(9):
        k = keys[i % 2]
        e = ec_ref.digest_int(b"x%d" % i)
        r, s = k.sign_digest(e)
        if i == 4:
            s = ec_ref.N - s  # invalid lane
        items.append((e, r, s, *k.public))
    n = len(items)
    d_arr = np.stack([
        np.frombuffer(int(e).to_bytes(32, "big"), np.uint8)
        for (e, r, s, qx, qy) in items
    ])
    r_arr = np.stack([
        np.frombuffer(int(r).to_bytes(32, "big"), np.uint8)
        for (e, r, s, qx, qy) in items
    ])
    s_arr = np.stack([
        np.frombuffer(int(s).to_bytes(32, "big"), np.uint8)
        for (e, r, s, qx, qy) in items
    ])
    c = p256v3.SigCollector()
    for i, it in enumerate(items):
        if i % 3 == 0:
            c.add_slow(it)
        else:
            c.add_fast((d_arr, r_arr, s_arr), i, _FakeIdent(it[3:]))
    got = p256v3.verify_launch(c)()
    want = p256v3.verify_host(items)
    assert got == want
    assert c.tuples() == items


def test_sigcollector_oversized_r_rejected():
    """A slow-row r or s ≥ 2^256 must be rejected, not wrapped — the
    column path truncating mod 2^256 would WIDEN the accept set vs the
    legacy int path (consensus divergence)."""
    from fabric_tpu.crypto import ec_ref
    from fabric_tpu.ops import p256v3

    k = ec_ref.SigningKey.generate()
    e = ec_ref.digest_int(b"oversize")
    r, s = k.sign_digest(e)
    bad = [
        (e, r + (1 << 256), s, *k.public),
        (e, r, s + (1 << 256), *k.public),
        (e, r, s, *k.public),  # control: valid
    ]
    c = p256v3.SigCollector()
    for it in bad:
        c.add_slow(it)
    got = p256v3.verify_launch(c)()
    assert got == [False, False, True]
    assert p256v3.verify_host(bad[:2]) == [False, False]
