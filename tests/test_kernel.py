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


def _neg(p: Point) -> Point:
    return Point(p.x, F.P - p.y)


_O = Point(None, None)

# The complete-addition cases, one test each and per field stack: the XLA
# stack (field) and the Mosaic-friendly one the chip's kernel runs
# (pallas_field), which whole-kernel interpret runs alone reached before.
_COMPLETE_CASES = {
    "P+Q": lambda a, b: (a, b, point_add(a, b)),
    "P+P": lambda a, b: (a, a, point_double(a)),
    "P+(-P)": lambda a, b: (a, _neg(a), _O),
    "P+O": lambda a, b: (a, _O, a),
    "O+P": lambda a, b: (_O, a, a),
    "O+O": lambda a, b: (_O, _O, _O),
}


def _stack(name):
    from tpunode.verify import pallas_field as PF

    return {"field": F, "pallas_field": PF}[name]


def _same_point(got: Point, want: Point) -> bool:
    return got.infinity if want.infinity else got == want


@pytest.mark.parametrize("stack", ["field", "pallas_field"])
@pytest.mark.parametrize("case", sorted(_COMPLETE_CASES))
def test_pt_add_complete_cases(case, stack):
    p, q, want = _COMPLETE_CASES[case](rand_point(), rand_point())
    got = to_affine(pt_add(to_proj(p), to_proj(q), F=_stack(stack)))
    assert _same_point(got, want)


@pytest.mark.parametrize("stack", ["field", "pallas_field"])
@pytest.mark.parametrize("case", ["2P", "2O"])
def test_pt_double_complete_cases(case, stack):
    p = rand_point() if case == "2P" else _O
    want = point_double(p) if case == "2P" else _O
    got = to_affine(pt_double(to_proj(p), F=_stack(stack)))
    assert _same_point(got, want)


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


def test_pow_const_exact():
    """_pow_const (the lax.scan ladder) equals pow() for both constant
    exponents the acceptance tests use."""
    from tpunode.verify import kernel as K

    v = rng.getrandbits(256) % F.P
    t = jnp.array(F.to_limbs(v))[:, None]
    for digits, e in (
        (K._EULER_DIGITS, (F.P - 1) // 2),
        (K._PM2_DIGITS, F.P - 2),
    ):
        got = F.from_limbs(F.canonical(K._pow_const(t, digits)))
        assert got == pow(v, e, F.P), hex(e)[:8]


def test_select_entry_is_a_plain_index():
    """The balanced 4-level select tree picks table[digit] for every
    digit value and lane — per-signature (4-D) and constant (3-D)
    tables."""
    from tpunode.verify import kernel as K

    rng2 = np.random.default_rng(42)
    table4 = jnp.asarray(rng2.integers(-100, 100, (16, 3, F.NLIMBS, 16),
                                       dtype=np.int64).astype(np.int32))
    table3 = jnp.asarray(rng2.integers(-100, 100, (16, 3, F.NLIMBS),
                                       dtype=np.int64).astype(np.int32))
    digits = jnp.asarray(np.arange(16, dtype=np.int32))
    for table in (table4, table3):
        tree = np.asarray(K._select_entry(table, digits))
        assert tree.shape == (3, F.NLIMBS, 16)
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
    assert not empty_py.buf.any()
    if load_native_verifier() is None:
        pytest.skip("native library unavailable")
    empty_nat = pb([], pad_to=4, native=True)
    assert empty_nat.count == 0
    assert np.array_equal(empty_py.buf, empty_nat.buf)
