"""TPU kernel (on CPU jax in tests) vs the Python oracle."""

import random

import numpy as np
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy tier (pytest.ini)

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from tpunode.verify import field as F
from tpunode.verify.curve import INFINITY, make_point, pt_add, pt_double
from tpunode.verify.ecdsa_cpu import (
    CURVE_N,
    GENERATOR,
    Point,
    point_add,
    point_double,
    point_mul,
    sign,
    verify,
)
from tpunode.verify.kernel import verify_batch_tpu

rng = random.Random(31337)


def to_proj(p: Point):
    """Affine oracle point -> limb-major projective batch of one (3, L, 1)."""
    if p.infinity:
        return INFINITY
    return make_point(
        jnp.array(F.to_limbs(p.x))[:, None],
        jnp.array(F.to_limbs(p.y))[:, None],
        jnp.asarray(F.ONE),
    )


def to_affine(proj) -> Point:
    x = F.from_limbs(F.canonical(proj[0]))
    y = F.from_limbs(F.canonical(proj[1]))
    z = F.from_limbs(F.canonical(proj[2]))
    if z == 0:
        return Point(None, None)
    zi = pow(z, -1, F.P)
    return Point(x * zi % F.P, y * zi % F.P)


def rand_point():
    k = rng.getrandbits(256) % CURVE_N or 1
    return point_mul(k, GENERATOR)


def test_pt_add_matches_oracle():
    for _ in range(5):
        a, b = rand_point(), rand_point()
        got = to_affine(pt_add(to_proj(a), to_proj(b)))
        assert got == point_add(a, b)


def test_pt_add_complete_cases():
    a = rand_point()
    neg = Point(a.x, F.P - a.y)
    # P + (-P) = O
    assert to_affine(pt_add(to_proj(a), to_proj(neg))).infinity
    # P + O = P ; O + P = P
    assert to_affine(pt_add(to_proj(a), INFINITY)) == a
    assert to_affine(pt_add(INFINITY, to_proj(a))) == a
    # P + P (degenerate for incomplete formulas) = 2P
    assert to_affine(pt_add(to_proj(a), to_proj(a))) == point_double(a)
    # O + O = O
    assert to_affine(pt_add(INFINITY, INFINITY)).infinity


def test_pt_double_matches_oracle():
    for _ in range(3):
        a = rand_point()
        assert to_affine(pt_double(to_proj(a))) == point_double(a)
    assert to_affine(pt_double(INFINITY)).infinity


def _random_batch(count, tamper_every=3):
    items, expected = [], []
    for i in range(count):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        z = rng.getrandbits(256)
        r, s = sign(priv, z, rng.getrandbits(256))
        if tamper_every and i % tamper_every == 1:
            if i % 2:
                z ^= 1
            else:
                s = (s + 1) % CURVE_N
            ok = verify(pub, z, r, s)  # almost surely False
        else:
            ok = True
        items.append((pub, z, r, s))
        expected.append(ok)
    return items, expected


def test_kernel_matches_oracle_random():
    items, expected = _random_batch(16)
    assert verify_batch_tpu(items) == expected


def test_kernel_degenerate_inputs():
    priv = 97
    pub = point_mul(priv, GENERATOR)
    z = rng.getrandbits(256)
    r, s = sign(priv, z, 555)
    items = [
        (pub, z, r, s),  # valid
        (pub, z, 0, s),  # r = 0
        (pub, z, r, 0),  # s = 0
        (pub, z, CURVE_N + 1, s),  # r out of range
        (None, z, r, s),  # missing pubkey
        (Point(None, None), z, r, s),  # infinity pubkey
        (Point(5, 5), z, r, s),  # off-curve pubkey
        (pub, 0, r, s),  # z = 0 is legal input (just won't verify)
    ]
    out = verify_batch_tpu(items)
    assert out[0] is True
    assert out[1:7] == [False] * 6
    assert out[7] is False


def test_kernel_z_zero_signature():
    # a signature genuinely made over z = 0 must verify (u1 = 0 edge)
    priv = 12345
    pub = point_mul(priv, GENERATOR)
    r, s = sign(priv, 0, 888)
    assert verify(pub, 0, r, s)
    assert verify_batch_tpu([(pub, 0, r, s)]) == [True]


def test_kernel_padding():
    items, expected = _random_batch(5)
    assert verify_batch_tpu(items, pad_to=8) == expected


def test_glv_split_properties():
    from tpunode.verify.kernel import LAMBDA, WINDOWS, WINDOW_BITS, glv_split

    bound = 1 << (WINDOW_BITS * WINDOWS)
    for _ in range(200):
        k = rng.getrandbits(256) % CURVE_N
        k1, k2 = glv_split(k)
        assert (k1 + k2 * LAMBDA - k) % CURVE_N == 0
        assert abs(k1) < bound and abs(k2) < bound
        # halves really are half-width (the point of the decomposition)
        assert abs(k1) < 1 << 129 and abs(k2) < 1 << 129


def test_beta_endomorphism_is_lambda_mul():
    from tpunode.verify.ecdsa_cpu import CURVE_P
    from tpunode.verify.kernel import BETA, LAMBDA

    for _ in range(5):
        p = rand_point()
        phi = Point(BETA * p.x % CURVE_P, p.y)
        assert phi == point_mul(LAMBDA, p)


def test_np_conversions_match_scalar():
    from tpunode.verify.kernel import (
        WINDOWS,
        _digits_base16,
        _ints_to_digits_np,
        _ints_to_limbs_np,
    )

    vals = [0, 1, F.P - 1, CURVE_N, (1 << 256) - 1] + [
        rng.getrandbits(256) for _ in range(50)
    ]
    got = _ints_to_limbs_np(vals)
    for v, row in zip(vals, got):
        assert (row == F.to_limbs(v)).all()
    dvals = [0, 1, (1 << 132) - 1] + [rng.getrandbits(132) for _ in range(50)]
    gotd = _ints_to_digits_np(dvals)
    for v, row in zip(dvals, gotd):
        assert row.tolist() == _digits_base16(v)


def test_program_choice_is_what_the_code_observes(monkeypatch):
    """Pallas on a TPU platform when the padded batch tiles into BLOCK,
    the XLA program otherwise — selected from jax.devices()[0] and the
    batch size, nothing else (no env knob, no sticky process flag)."""
    import types

    import jax as _jax

    import tpunode.verify.kernel as K
    import tpunode.verify.pallas_kernel as PK

    assert K._pallas_usable(PK.BLOCK) is False  # this box is cpu
    monkeypatch.setattr(
        _jax, "devices",
        lambda *a: [types.SimpleNamespace(platform="tpu")],
    )
    assert K._pallas_usable(PK.BLOCK) is True
    assert K._pallas_usable(4 * PK.BLOCK) is True
    assert K._pallas_usable(PK.BLOCK + 8) is False  # does not tile


def test_dispatch_propagates_mosaic_errors(monkeypatch):
    """On a chip that is present a Mosaic error means the kernel does not
    compile: _dispatch_prep raises it — no fall-through to the XLA
    program, which would silently run several times slower."""
    import tpunode.verify.kernel as K
    import tpunode.verify.pallas_kernel as PK

    def mosaic_boom(*a, **k):
        raise RuntimeError(
            "MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: "
            "Unsupported target bitwidth for truncation"
        )

    def xla_must_not_run(*a, **k):
        raise AssertionError("the XLA program stood in for the chip")

    monkeypatch.setattr(K, "_pallas_usable", lambda batch: True)
    monkeypatch.setattr(PK, "verify_blocked", mosaic_boom)
    monkeypatch.setattr(K, "verify_device", xla_must_not_run)
    items, _ = _random_batch(4)
    with pytest.raises(RuntimeError, match="MosaicError"):
        K.verify_batch_tpu(items, pad_to=16)
    with pytest.raises(RuntimeError, match="MosaicError"):
        K._dispatch_prep(K.prepare_batch(items, pad_to=16))


def test_acceptance_pows_gated_per_batch():
    """verify_core gates the jacobi/parity acceptance pows on a
    batch-level any() (lax.cond).  All four predicate combinations must
    verdict exactly like the oracle — including rejections that ONLY the
    gated pow can produce: signatures from a NON-canonicalized nonce,
    whose R satisfies the x-match but fails jacobi/parity.  (A naive
    s -> n-s tamper moves x(R) and dies at the x-match, which would let
    a wrongly-taken skip path hide — review r5.)"""
    from tpunode.verify.ecdsa_cpu import (
        bip340_challenge,
        jacobi,
        lift_x,
        schnorr_challenge,
        sign_bip340,
        sign_schnorr,
        verify_batch_cpu,
    )

    def ecdsa_items(n):
        out = []
        for i in range(n):
            priv = rng.getrandbits(256) % CURVE_N or 1
            pub = point_mul(priv, GENERATOR)
            z = rng.getrandbits(256)
            r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
            if i % 3 == 2:
                z ^= 1
            out.append((pub, z, r, s))
        return out

    def _nonce_with(pred):
        """A nonce k whose R = kG satisfies ``pred(R)`` (rejection twins:
        the signer's canonicalization step deliberately skipped)."""
        while True:
            k = rng.getrandbits(256) % CURVE_N or 1
            R = point_mul(k, GENERATOR)
            if pred(R):
                return k, R

    def schnorr_items(n):
        out = []
        for i in range(n):
            priv = rng.getrandbits(256) % CURVE_N or 1
            pub = point_mul(priv, GENERATOR)
            m = rng.getrandbits(256)
            if i % 3 == 2:
                # x-matching twin that ONLY the jacobi pow rejects
                k, R = _nonce_with(lambda R: jacobi(R.y) != 1)
                r = R.x
                e = schnorr_challenge(r, pub, m)
                s = (k + e * priv) % CURVE_N
            else:
                r, s = sign_schnorr(priv, m, rng.getrandbits(256))
                e = schnorr_challenge(r, pub, m)
            out.append((pub, e, r, s, "schnorr"))
        return out

    def bip340_items(n):
        out = []
        for i in range(n):
            priv = rng.getrandbits(256) % CURVE_N or 1
            P0 = point_mul(priv, GENERATOR)
            pub = lift_x(P0.x)
            # the secret for the even-y (lifted) pubkey
            d = priv if P0.y % 2 == 0 else CURVE_N - priv
            m = rng.getrandbits(256)
            if i % 3 == 2:
                # x-matching twin that ONLY the parity pow rejects
                k, R = _nonce_with(lambda R: R.y % 2 != 0)
                r = R.x
                e = bip340_challenge(r, P0.x, m)
                s = (k + e * d) % CURVE_N
            else:
                r, s = sign_bip340(priv, m, rng.getrandbits(256))
                e = bip340_challenge(r, P0.x, m)
            out.append((pub, e, r, s, "bip340"))
        return out

    sch, bip = schnorr_items(8), bip340_items(8)
    # the twins' ONLY defect is jacobi/parity: the oracle rejects exactly
    # the i % 3 == 2 lanes (had the x-match failed too, this test could
    # not distinguish a broken skip gate)
    assert verify_batch_cpu(sch) == [i % 3 != 2 for i in range(8)]
    assert verify_batch_cpu(bip) == [i % 3 != 2 for i in range(8)]
    batches = [
        ecdsa_items(8),                      # both pows skipped
        sch,                                 # jacobi pow only
        bip,                                 # parity pow only
        ecdsa_items(3) + schnorr_items(3) + bip340_items(2),  # both
    ]
    for items in batches:
        got = verify_batch_tpu(items, pad_to=8)
        expect = verify_batch_cpu(items)
        assert got == expect, (got, expect)
        assert True in got and False in got  # non-degenerate both ways


# ---------- ISSUE 8: affine MSM — mixed add, batch inversion, de-scan ------


def test_pt_add_mixed_matches_oracle():
    """curve.pt_add_mixed (RCB'16 Algorithm 8) against the affine oracle,
    including the completeness-in-P1 cases the window loop relies on:
    P1 = O, P1 = P2 (doubling degeneracy), P1 = -P2 (infinity out)."""
    from tpunode.verify.curve import pt_add_mixed

    def to_aff2(p: Point):
        return jnp.stack(
            [jnp.array(F.to_limbs(p.x))[:, None],
             jnp.array(F.to_limbs(p.y))[:, None]], axis=0)

    for _ in range(2):
        a, b = rand_point(), rand_point()
        assert to_affine(pt_add_mixed(to_proj(a), to_aff2(b))) == point_add(a, b)
    a = rand_point()
    q2 = to_aff2(a)
    assert to_affine(pt_add_mixed(to_proj(a), q2)) == point_double(a)
    neg = Point(a.x, F.P - a.y)
    assert to_affine(pt_add_mixed(to_proj(neg), q2)).infinity
    assert to_affine(pt_add_mixed(INFINITY, q2)) == a
    # negated-entry path (_signed): -Q as (x, -y) loose limbs
    negq = jnp.stack([q2[0], -q2[1]], axis=0)
    assert to_affine(pt_add_mixed(to_proj(a), negq)).infinity


def test_normalize_q_table_batch_inversion():
    """The Montgomery-trick batch normalization (prefix/suffix products
    + one shared Fermat ladder) recovers EXACTLY the affine multiples
    k*Q for every table entry and lane — pinned against ecdsa_cpu's
    affine arithmetic."""
    from tpunode.verify.kernel import _build_q_table, _normalize_q_table

    pts = [rand_point() for _ in range(2)]
    qx = jnp.stack([jnp.array(F.to_limbs(p.x)) for p in pts], axis=1)
    qy = jnp.stack([jnp.array(F.to_limbs(p.y)) for p in pts], axis=1)
    aff = _normalize_q_table(_build_q_table(qx, qy))
    assert aff.shape == (16, 2, F.NLIMBS, len(pts))
    for lane, p in enumerate(pts):
        for k in range(1, 16):
            exp = point_mul(k, p)
            x = F.from_limbs(F.canonical(aff[k, 0, :, lane : lane + 1]))
            y = F.from_limbs(F.canonical(aff[k, 1, :, lane : lane + 1]))
            assert (x, y) == (exp.x, exp.y), (lane, k)


def test_pow_const_modes_exact():
    """_pow_const under both ladder shapes (scan / de-scanned unroll)
    equals pow() for both constant exponents; _pow_table is the exact
    power table."""
    import numpy as np

    from tpunode.verify import kernel as K

    v = rng.getrandbits(256) % F.P
    t = jnp.array(F.to_limbs(v))[:, None]
    prev = (K.select_mode(), K.pow_ladder_mode())
    try:
        # one exponent per mode (crosswise) keeps this at 2 traced
        # programs — the tier-1 870s budget is seed-saturated
        for mode, digits, e in (
            ("scan", K._EULER_DIGITS, (F.P - 1) // 2),
            ("unroll", K._PM2_DIGITS, F.P - 2),
        ):
            K.set_kernel_modes(pow_ladder=mode)
            got = F.from_limbs(F.canonical(K._pow_const(t, digits)))
            assert got == pow(v, e, F.P), (mode, hex(e)[:8])
        table = K._pow_table(t)
        for k in range(16):
            assert F.from_limbs(F.canonical(table[k])) == pow(v, k, F.P)
    finally:
        K.set_kernel_modes(select=prev[0], pow_ladder=prev[1])


def test_select_entry_tree_matches_onehot():
    """The balanced 4-level select tree is entry-for-entry identical to
    the one-hot select — per-signature (4-D) and constant (3-D) tables,
    every digit value."""
    import numpy as np

    from tpunode.verify import kernel as K

    rng2 = np.random.default_rng(42)
    table4 = jnp.asarray(rng2.integers(-100, 100, (16, 3, F.NLIMBS, 16),
                                       dtype=np.int64).astype(np.int32))
    table3 = jnp.asarray(rng2.integers(-100, 100, (16, 2, F.NLIMBS),
                                       dtype=np.int64).astype(np.int32))
    digits = jnp.asarray(np.arange(16, dtype=np.int32))
    for table in (table4, table3):
        tree = np.asarray(K._select_entry_tree(table, digits))
        onehot = np.asarray(K._select_entry_onehot(table, digits))
        assert np.array_equal(tree, onehot)
        # and the tree really is a plain index per lane
        for b in range(16):
            want = np.asarray(table[b])
            if table.ndim == 4:
                want = want[..., b]
            assert np.array_equal(tree[..., b], want)


def test_batch_inverse_singleton_and_empty():
    """ISSUE 8 bugfix sweep: B == 1 short-circuits to the bare pow; the
    empty batch returns empty; the general path is unchanged."""
    from tpunode.verify.kernel import _batch_inverse_mod_n

    assert _batch_inverse_mod_n([]) == []
    v = 0x123456789ABCDEF
    assert _batch_inverse_mod_n([v]) == [pow(v, -1, CURVE_N)]
    vals = [3, 5, 7, v]
    assert _batch_inverse_mod_n(vals) == [pow(x, -1, CURVE_N) for x in vals]


def test_prepare_batch_empty_native_parity():
    """The native secp_prepare_batch path must agree with the Python
    path on the empty-batch edge (ISSUE 8 bugfix sweep pin)."""
    import numpy as np

    from tpunode.verify.cpu_native import load_native_verifier
    from tpunode.verify.kernel import prepare_batch as pb

    empty_py = pb([], pad_to=4, native=False)
    assert empty_py.count == 0
    assert not empty_py.host_valid.any()
    if load_native_verifier() is None:
        pytest.skip("native library unavailable")
    empty_nat = pb([], pad_to=4, native=True)
    assert empty_nat.count == 0
    for name in ("d1a", "d1b", "d2a", "d2b", "qx", "qy", "r1", "r2",
                 "r2_valid", "host_valid", "schnorr", "bip340"):
        a = np.asarray(getattr(empty_py, name))
        b = np.asarray(getattr(empty_nat, name))
        assert np.array_equal(a, b), name


@pytest.mark.slow  # a second full XLA compile (~2 min on CPU): the
# tier-1 870s budget is seed-saturated — the cheap unit pins above plus
# the campaign's zero-mismatch XLA run (PERF.md) carry tier-1; this
# full-program bit-identity check runs in the slow tier
def test_affine_matches_projective_and_oracle():
    """ISSUE 8 acceptance: the affine XLA program's verdicts are
    bit-identical to the projective program's AND the oracle's on a
    batch covering all three algorithms, degenerate inputs, and an
    off-curve pubkey (whose garbage table normalization must stay
    masked)."""
    from tpunode.verify import curve as C
    from tpunode.verify.ecdsa_cpu import (
        bip340_challenge,
        lift_x,
        schnorr_challenge,
        sign_bip340,
        sign_schnorr,
        verify_batch_cpu,
    )

    items = []
    for i in range(3):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        z = rng.getrandbits(256)
        r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
        if i == 1:
            z ^= 1
        items.append((pub, z, r, s))
    priv = 987654321
    pub = point_mul(priv, GENERATOR)
    r, s = sign_schnorr(priv, 44, 1717)
    items.append((pub, schnorr_challenge(r, pub, 44), r, s, "schnorr"))
    r, s = sign_bip340(priv, 45, 1718)
    items.append((lift_x(pub.x), bip340_challenge(r, pub.x, 45), r, s,
                  "bip340"))
    items.append((Point(5, 7), 1, 2, 3))  # off-curve
    items.append((None, 1, 2, 3))  # absent pubkey
    expect = verify_batch_cpu(items)
    assert True in expect and False in expect

    got_proj = verify_batch_tpu(items, pad_to=8)
    prev = C.set_point_form("affine")
    try:
        got_aff = verify_batch_tpu(items, pad_to=8)
    finally:
        C.set_point_form(prev)
    assert got_proj == expect
    assert got_aff == expect
    assert got_aff == got_proj  # bit-identical verdicts


@pytest.mark.slow  # compiles a second full XLA program (~2 min on CPU)
def test_kernel_matches_oracle_dot_general_formulation():
    """The XLA program under the dot_general limb-product formulation +
    dedicated sqr (ISSUE 4): verdict parity with the oracle."""
    from tpunode.verify import field as F

    items, expected = _random_batch(8)
    prev = F.field_modes()
    try:
        F.set_field_modes(mul="dot_general", sqr="half")
        assert verify_batch_tpu(items, pad_to=8) == expected
    finally:
        F.set_field_modes(mul=prev[0], sqr=prev[1])


# ---------- ISSUE 12: lazy reduction + window width ------------------------


@pytest.fixture
def restore_issue12_modes():
    from tpunode.verify import field as F
    from tpunode.verify import kernel as K

    prev_f = F.field_modes()
    prev_wb = K.window_bits()
    yield
    F.set_field_modes(mul=prev_f[0], sqr=prev_f[1], reduce=prev_f[2])
    K.set_kernel_modes(window_bits=prev_wb)


@pytest.mark.slow  # compiles a second full XLA program (~2 min on CPU)
def test_kernel_lazy_matches_oracle(restore_issue12_modes):
    """The XLA program under the lazy-reduction field pipeline, through
    _verify_device_jit (verify_batch_tpu): verdicts bit-identical to the
    eager program's and the oracle's."""
    from tpunode.verify import field as F

    items, expected = _random_batch(8)
    F.set_field_modes(reduce="lazy")
    assert verify_batch_tpu(items, pad_to=8) == expected


@pytest.mark.slow  # compiles a full XLA program per width (~2 min each)
def test_kernel_window_bits5_matches_oracle(restore_issue12_modes):
    """window_bits=5 (27 rounds, 32-entry tables) vs window_bits=4 vs
    the oracle: bit-identical verdicts."""
    from tpunode.verify import kernel as K

    items, expected = _random_batch(8)
    K.set_kernel_modes(window_bits=4)
    got4 = verify_batch_tpu(items, pad_to=8)
    K.set_kernel_modes(window_bits=5)
    got5 = verify_batch_tpu(items, pad_to=8)
    assert got4 == expected
    assert got5 == expected
    assert got4 == got5


def test_window5_digits_and_tables(restore_issue12_modes):
    """Host-side 5-bit structure: digit extraction (including digits
    that straddle 64-bit word edges — impossible at 4-bit, routine at
    5), the 32-entry constant tables, and the windows()/bound wiring."""
    from tpunode.verify import kernel as K
    from tpunode.verify.ecdsa_cpu import GENERATOR, point_mul

    K.set_kernel_modes(window_bits=5)
    assert K.windows() == 27 and K.window_bits() == 5
    rng5 = random.Random(0x5B175)
    vals = [rng5.getrandbits(5 * 27) for _ in range(32)] + [0, 1, (1 << 135) - 1]
    arr = K._ints_to_digits_np(vals)
    assert arr.shape == (len(vals), 27)
    for i, v in enumerate(vals):
        assert list(arr[i]) == K._digits_base16(v), v
        # digits reconstruct the value exactly (MSB-first base-32)
        acc = 0
        for d in arr[i]:
            acc = (acc << 5) | int(d)
        assert acc == v
    g, lg, g_aff, lg_aff = K.window_tables()
    assert g.shape == (32, 3, F.NLIMBS) and g_aff.shape == (32, 2, F.NLIMBS)
    for k in (1, 2, 17, 31):
        pt = point_mul(k, GENERATOR)
        assert F.from_limbs(g[k, 0]) == pt.x
        assert F.from_limbs(g[k, 1]) == pt.y
    lam17 = point_mul(17 * K.LAMBDA % CURVE_N, GENERATOR)
    assert F.from_limbs(lg[17, 0]) == lam17.x


def test_window_bits_knob_validation_and_cache_key(restore_issue12_modes):
    """set_kernel_modes validates window_bits, the ISSUE 13 native w5
    path closes the PR 12 gap (``native=True`` no longer raises at
    5-bit with a current library; only a STALE pre-w5 .so falls back to
    Python — and then ``native=True`` still fails loudly rather than
    silently down-grading), and both knobs ride the jit cache key."""
    from tpunode.verify import cpu_native as CN
    from tpunode.verify import field as F2
    from tpunode.verify import kernel as K

    with pytest.raises(ValueError):
        K.set_kernel_modes(window_bits=6)
    before = K.kernel_modes()
    K.set_kernel_modes(window_bits=5)
    assert K.kernel_modes() != before
    assert K.kernel_modes()[-1] == 5
    assert K.structure_modes()[-1] == 5
    nv = CN.load_native_verifier()
    if nv is not None and nv.supports_window_bits(5):
        # ISSUE 13 acceptance: native=True works at w5 on a current lib
        prep = K.prepare_batch([], native=True)
        assert prep.count == 0 and prep.d1a.shape[0] == 27
    F2.set_field_modes(reduce="lazy")
    assert "lazy" in K.kernel_modes()


def test_window_bits_stale_native_lib_falls_back(
    restore_issue12_modes, monkeypatch
):
    """A pre-w5 libsecp_cpu.so (no ``secp_prepare_batch_w`` symbol):
    auto prep quietly takes the Python path at 5-bit, ``native=True``
    raises loudly, and the binding itself refuses the width."""
    from tpunode.verify import cpu_native as CN
    from tpunode.verify import kernel as K

    nv = CN.load_native_verifier()
    if nv is None:
        pytest.skip("native verifier unavailable")
    K.set_kernel_modes(window_bits=5)
    monkeypatch.setattr(type(nv), "supports_window_bits",
                        lambda self, wb: wb == 4)
    items, _ = _random_batch(2)
    prep = K.prepare_batch(items, pad_to=8)  # auto: silent Python path
    assert prep.d1a.shape == (27, 8)
    with pytest.raises(RuntimeError, match="window_bits=5"):
        K.prepare_batch(items, native=True)
    with pytest.raises(RuntimeError, match="window_bits=5"):
        nv.prepare_batch_arrays(
            b"", b"", b"", b"", b"", b"", 0, 0, window_bits=5
        )


def test_native_w5_prep_bit_identical_to_python(restore_issue12_modes):
    """ISSUE 13 satellite acceptance: the native 5-bit batch prep
    (word-straddling digit extraction in C++) is bit-identical to the
    Python ``_ints_to_digits_np`` layout over every PreparedBatch field
    — ECDSA + both Schnorr variants + invalid/missing lanes, tuple AND
    raw paths — and the width-mismatch dispatch guard covers batches
    prepped natively."""
    import numpy as np

    from tpunode.verify import cpu_native as CN
    from tpunode.verify import kernel as K
    from tpunode.verify.raw import pack_items

    from tpunode.verify.ecdsa_cpu import (
        bip340_challenge,
        lift_x,
        schnorr_challenge,
        sign_bip340,
        sign_schnorr,
    )

    nv = CN.load_native_verifier()
    if nv is None or not nv.supports_window_bits(5):
        pytest.skip("w5-capable native library unavailable")
    items, _ = _random_batch(24)
    for i in range(12):  # both Schnorr variants exercise the u1/u2 path
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        m = rng.getrandbits(256)
        if i % 2:
            r, s = sign_schnorr(priv, m, rng.getrandbits(256))
            items.append((pub, schnorr_challenge(r, pub, m), r, s, "schnorr"))
        else:
            r, s = sign_bip340(priv, m, rng.getrandbits(256))
            items.append(
                (lift_x(pub.x), bip340_challenge(r, pub.x, m), r, s, "bip340")
            )
    items.append((None, 1, 1, 1))  # missing pubkey: host_valid False
    items.append((GENERATOR, 5, 0, 7))  # r=0: invalid by inspection
    fields = (
        "d1a", "d1b", "d2a", "d2b", "n1a", "n1b", "n2a", "n2b",
        "qx", "qy", "r1", "r2", "r2_valid", "host_valid",
        "schnorr", "bip340",
    )
    K.set_kernel_modes(window_bits=5)
    pn = K.prepare_batch(items, pad_to=48, native=True)
    pp = K.prepare_batch(items, pad_to=48, native=False)
    assert pn.d1a.shape == (27, 48)
    for f in fields:
        assert np.array_equal(
            np.asarray(getattr(pn, f), dtype=np.int64),
            np.asarray(getattr(pp, f), dtype=np.int64),
        ), f"w5 native/python diverge on {f}"
    pr = K.prepare_batch_raw(pack_items(items), pad_to=48)
    for f in fields:
        assert np.array_equal(
            np.asarray(getattr(pr, f), dtype=np.int64),
            np.asarray(getattr(pp, f), dtype=np.int64),
        ), f"w5 raw-native/python diverge on {f}"
    # the width-mismatch guard covers NATIVE-prepped batches too: a w5
    # native prep dispatched after the global flips back must raise
    K.set_kernel_modes(window_bits=4)
    with pytest.raises(RuntimeError, match="window"):
        K._dispatch_prep(pn)


def test_window_flip_between_prep_and_dispatch_raises(restore_issue12_modes):
    """window_bits is the one knob that changes HOST DATA layout: a
    batch prepped at one width dispatched after the global flips must
    raise loudly (review r12 — the silent alternative is wrong verdicts,
    since the window loop takes its trip count from the data but its
    doubling count from the global)."""
    from tpunode.verify import kernel as K

    K.set_kernel_modes(window_bits=4)
    items, _ = _random_batch(2)
    prep = K.prepare_batch(items, pad_to=8)
    K.set_kernel_modes(window_bits=5)
    with pytest.raises(RuntimeError, match="window"):
        K._dispatch_prep(prep)


def test_select_tree_handles_32_entries():
    """The shared select-tree fold generalizes to 32 entries (5 levels)
    and stays identical to the one-hot select."""
    import numpy as np

    from tpunode.verify.kernel import select_tree16

    rng32 = np.random.default_rng(5)
    entries = [jnp.asarray(rng32.integers(0, 100, size=(3, 4)).astype(np.int32))
               for _ in range(32)]
    digits = jnp.asarray(np.array([0, 7, 19, 31], dtype=np.int32))
    out = np.asarray(select_tree16(entries, digits))
    for lane, d in enumerate([0, 7, 19, 31]):
        assert (out[:, lane] == np.asarray(entries[d])[:, lane]).all()


def test_mode_flip_changes_the_traced_program():
    """Flipping the formulation must change what a fresh trace of
    verify_core CONTAINS (dot_general MACs present vs absent) — and the
    jitted entry points carry field_modes as a static cache key, because
    distinct jax.jit wrappers of one function SHARE a trace cache (a
    per-mode dict of wrappers silently reuses the first formulation;
    found the hard way in this PR's A/B measurements)."""
    import numpy as np

    from benchmarks.roofline import count_int_ops
    from tpunode.verify import field as F

    a = jnp.asarray(np.ones((F.NLIMBS, 4), np.int32))
    b = jnp.asarray(np.full((F.NLIMBS, 4), 2, np.int32))
    prev = F.field_modes()
    try:
        F.set_field_modes(mul="shift_add", sqr="half")
        shift = count_int_ops(F.mul, a, b)
        F.set_field_modes(mul="dot_general", sqr="half")
        dot = count_int_ops(F.mul, a, b)
    finally:
        F.set_field_modes(mul=prev[0], sqr=prev[1])
    assert shift.get("mac", 0) == 0  # pure VPU shift-add
    # the 47x576 contraction: 576 MACs per output limb per lane
    assert dot.get("mac", 0) == (2 * F.NLIMBS - 1) * F.NLIMBS * F.NLIMBS
    # and the jitted entries key their caches on the modes (static args)
    import inspect

    from tpunode.verify import kernel as K
    from tpunode.verify import pallas_kernel as PK

    assert "field_modes" in inspect.signature(K._verify_device_jit).parameters
    assert "field_modes" in inspect.signature(PK._verify_blocked_jit).parameters
