"""Property tests for the TPU limb field arithmetic vs Python ints.

Layout convention under test (see tpunode/verify/field.py): limb-major —
an element batch is shape ``(NLIMBS, B)``, a single element ``(NLIMBS, 1)``.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from tpunode.verify import field as F
from tpunode.verify import pallas_field as PF

rng = random.Random(2024)


def rand_fe():
    return rng.getrandbits(256) % F.P


def limbs(*vals):
    """Python ints -> limb-major batch (NLIMBS, B)."""
    return jnp.stack([jnp.array(F.to_limbs(v)) for v in vals], axis=1)


def ints(arr):
    """Limb-major array -> int (for (L,) / (L, 1)) or list of ints (L, B)."""
    arr = np.asarray(arr)
    if arr.ndim == 1 or arr.shape[1] == 1:
        return F.from_limbs(arr)
    return [F.from_limbs(arr[:, j]) for j in range(arr.shape[1])]


def test_limb_roundtrip():
    for _ in range(20):
        v = rng.getrandbits(256)
        assert F.from_limbs(F.to_limbs(v)) == v


def test_mul_random():
    a_vals = [rand_fe() for _ in range(32)]
    b_vals = [rand_fe() for _ in range(32)]
    out = F.mul(limbs(*a_vals), limbs(*b_vals))
    got = ints(out)
    for a, b, g in zip(a_vals, b_vals, got):
        assert g % F.P == a * b % F.P


def test_mul_edge_values():
    edge = [0, 1, 2, F.P - 1, F.P - 2, (1 << 255), F.C_INT, F.P // 2]
    for a in edge:
        for b in edge:
            out = F.mul(limbs(a), limbs(b))
            assert ints(out) % F.P == a * b % F.P


def test_mul_accepts_loose_negative_inputs():
    # a - b with a < b gives negative limbs; mul must stay exact
    a, b, c = 5, rand_fe(), rand_fe()
    la = limbs(a) - limbs(b)  # negative-valued loose vector
    out = F.mul(la, limbs(c))
    assert ints(out) % F.P == (a - b) * c % F.P


def test_mul_chain_stays_bounded():
    # repeated squaring: bounds must hold through long chains
    v = rand_fe()
    x = limbs(v)
    expect = v
    for _ in range(50):
        x = F.sqr(x)
        expect = expect * expect % F.P
        arr = np.asarray(x)
        assert np.abs(arr).max() < (1 << 13)
    assert ints(x) % F.P == expect


def test_add_sub_through_mul():
    a, b, c = rand_fe(), rand_fe(), rand_fe()
    la, lb, lc = limbs(a), limbs(b), limbs(c)
    out = F.mul(la + lb - lc, F.ONE)
    assert ints(out) % F.P == (a + b - c) % F.P


def test_canonical():
    vals = [0, 1, F.P - 1, F.P, F.P + 1, 2 * F.P - 1, rand_fe(), (1 << 256) - 1]
    for v in vals:
        enc = v % (1 << 256)  # what actually gets encoded into limbs
        c = F.canonical(limbs(enc))
        assert ints(c) == enc % F.P
        arr = np.asarray(c)
        assert arr.min() >= 0 and arr.max() <= F.MASK


def test_canonical_negative():
    a, b = 3, rand_fe()
    loose = limbs(a) - limbs(b)
    c = F.canonical(loose)
    assert ints(c) == (a - b) % F.P


def test_eq_and_is_zero():
    a = rand_fe()
    la = limbs(a)
    assert bool(F.is_zero(la - la)[0])
    # a ≡ a + p (mod p): build a+p in loose limbs by adding P_LIMBS
    lap = la + F.P_LIMBS
    assert bool(F.eq(la, lap)[0])
    assert not bool(F.eq(la, la + F.ONE)[0])


def test_select():
    ab = limbs(5, 5)
    bb = limbs(9, 9)
    mask = jnp.array([True, False])
    out = F.select(mask, ab, bb)
    assert ints(out) == [5, 9]


def test_mul_under_jit():
    f = jax.jit(F.mul)
    a_vals = [rand_fe() for _ in range(8)]
    b_vals = [rand_fe() for _ in range(8)]
    out = f(limbs(*a_vals), limbs(*b_vals))
    for a, b, g in zip(a_vals, b_vals, ints(out)):
        assert g % F.P == a * b % F.P


def test_mul_under_vmap():
    # kernel._lambda_table maps F.mul over a table axis prepended to the
    # limb-major (L, B) layout; keep that batching path covered here
    a_vals = [rand_fe() for _ in range(6)]
    b = rand_fe()
    stacked = jnp.stack([limbs(v, v) for v in a_vals])  # (6, L, 2)
    f = jax.vmap(lambda x: F.mul(x, limbs(b, b)))
    out = f(stacked)  # (6, L, 2)
    for i, a in enumerate(a_vals):
        assert ints(out[i])[0] % F.P == a * b % F.P


# ---------- the one limb-product formulation ------------------------------


def test_sqr_matches_mul_exactly():
    """The dedicated half-product sqr IS mul(a, a): same value, same limb
    representation, including through long chains (bounds hold)."""
    v = rand_fe()
    x = limbs(v)
    expect = v
    for _ in range(50):
        x2 = F.mul(x, x)
        x = F.sqr(x)
        assert (np.asarray(x) == np.asarray(x2)).all()
        expect = expect * expect % F.P
        assert np.abs(np.asarray(x)).max() < (1 << 13)
    assert ints(x) % F.P == expect


def test_sqr_t_contract():
    """sqr_t under mul_t's contract: pre-tight operands (every limb
    <= 2^13), including sums of two mul outputs (point coordinates)."""
    a, b = rand_fe(), rand_fe()
    m1 = F.mul(limbs(a), limbs(b))
    coord = m1 + m1  # sum of 2 mul outputs: <= 2^13
    got = F.sqr_t(coord)
    want = (2 * (a * b % F.P)) ** 2 % F.P
    assert ints(got) % F.P == want


# ---------- lazy-reduction wide API ---------------------------------------


def _adversarial_operands():
    """Contract-edge operands: canonical, negative-limb (a - b), and
    top-overflow (mul_small_red outputs carry a fat non-top profile;
    a tight value scaled by 8 carries a fat top limb)."""
    a, b = rand_fe(), rand_fe()
    canon = limbs(a)
    neg = limbs(3) - limbs(b)  # negative loose limbs
    m = F.mul(limbs(a), limbs(b))
    top = m * 8  # |limb| <= 2^15 incl the top: mul's contract edge
    return [(canon, a), (neg, (3 - b) % F.P), (m, a * b % F.P),
            (top, 8 * (a * b) % F.P)]


def test_wide_api_matches_eager_bit_exact():
    """reduce_wide(mul_wide(a, b)) IS mul(a, b) — bit-identical limbs,
    not just mod-p equal — on random and adversarial inputs; same for
    the _t and sqr variants."""
    for la, _ in _adversarial_operands():
        for lb, _ in _adversarial_operands():
            assert (
                np.asarray(F.reduce_wide(F.mul_wide(la, lb)))
                == np.asarray(F.mul(la, lb))
            ).all()
    a, b = rand_fe(), rand_fe()
    ta, tb = limbs(a), limbs(b)  # canonical: pre-tight
    assert (
        np.asarray(F.reduce_wide(F.mul_t_wide(ta, tb)))
        == np.asarray(F.mul_t(ta, tb))
    ).all()
    assert (
        np.asarray(F.reduce_wide(F.sqr_wide(ta))) == np.asarray(F.sqr(ta))
    ).all()
    assert (
        np.asarray(F.reduce_wide(F.sqr_t_wide(ta))) == np.asarray(F.sqr_t(ta))
    ).all()


def test_acc_add_and_loose_reduce_exact():
    """Accumulated wides reduce to the exact sum mod p, through both the
    tight and the loose tail; loose output limbs honor the documented
    <= 2^13 bound and re-enter the mul contracts."""
    a, b, c, d = (rand_fe() for _ in range(4))
    w = F.acc_add(
        F.mul_t_wide(limbs(a), limbs(b)), F.mul_t_wide(limbs(c), limbs(d))
    )
    want = (a * b + c * d) % F.P
    assert ints(F.reduce_wide(w)) % F.P == want
    loose = F.reduce_wide_loose(w)
    assert ints(loose) % F.P == want
    assert np.abs(np.asarray(loose)).max() <= (1 << 13)
    # subtraction of wides is plain limb arithmetic
    w2 = F.mul_t_wide(limbs(a), limbs(b)) - F.mul_t_wide(limbs(c), limbs(d))
    assert ints(F.reduce_wide(w2)) % F.P == (a * b - c * d) % F.P
    # loose outputs are legal downstream operands
    assert ints(F.mul_t(loose, loose)) % F.P == want * want % F.P


# Each wide primitive against Python ints, in BOTH field stacks (the XLA
# one and the Mosaic-friendly one the chip's kernel runs), on the three
# operand classes the formulas feed them.  Until PR 29 the second
# formulation of each was the reference; Python's ints are now.

STACKS = {"field": F, "pallas_field": PF}


def _operand_pair(kind: str):
    """Two (limbs, exact int value) operands of one class, 2 lanes each.
    ``canonical``: nonnegative limbs < 2^11.  ``negative``: differences
    of canonical values (limbs in ±2^11, negative VALUES too).
    ``edge``: mul's input contract edge — a reduced product scaled by 8
    (every limb, the top one included, up to 2^15) and a 3-term sum of
    mul_small_red outputs (fat non-top limbs)."""
    a, b, c, d = (rand_fe() for _ in range(4))
    if kind == "canonical":
        x, y = limbs(a, b), limbs(c, d)
    elif kind == "negative":
        x, y = limbs(3, a) - limbs(b, c), limbs(d, 7) - limbs(a, b)
    else:
        m = F.mul(limbs(a, b), limbs(c, d))
        x = m * 8
        r = F.mul_small_red(m, 21)
        y = r + r + r
    return (x, ints(x)), (y, ints(y))


@pytest.mark.parametrize("kind", ["canonical", "negative", "edge"])
@pytest.mark.parametrize("stack", sorted(STACKS))
@pytest.mark.parametrize(
    "prim", ["mul_wide", "sqr_wide", "acc_add", "reduce_wide",
             "reduce_wide_loose"],
)
def test_wide_primitive_matches_python_ints(prim, stack, kind):
    ns = STACKS[stack]
    (x, xv), (y, yv) = _operand_pair(kind)
    if prim == "mul_wide":
        # carry rounds and the convolution are value-exact over the
        # integers, not merely mod p
        w = ns.mul_wide(x, y)
        assert w.shape[0] == 2 * F.NLIMBS - 1
        assert ints(w) == [p * q for p, q in zip(xv, yv)]
    elif prim == "sqr_wide":
        w = ns.sqr_wide(x)
        assert ints(w) == [p * p for p in xv]
        # the half-product path is mul_wide(x, x) limb for limb
        assert (np.asarray(w) == np.asarray(ns.mul_wide(x, x))).all()
    elif prim == "acc_add":
        w1, w2, w3 = ns.mul_wide(x, y), ns.sqr_wide(x), ns.sqr_wide(y)
        acc = ns.acc_add(w1, w2, -w3)
        assert ints(acc) == [
            p * q + p * p - q * q for p, q in zip(xv, yv)
        ]
    else:
        tail = getattr(ns, prim)
        bound = 1 << (12 if prim == "reduce_wide" else 13)
        for w, want in (
            (ns.mul_wide(x, y), [p * q for p, q in zip(xv, yv)]),
            # a two-product accumulation, the shape pt_add reduces
            (
                ns.acc_add(ns.mul_wide(x, y), ns.sqr_wide(x)),
                [p * q + p * p for p, q in zip(xv, yv)],
            ),
        ):
            out = tail(w)
            assert out.shape[0] == F.NLIMBS
            assert [v % F.P for v in ints(out)] == [v % F.P for v in want]
            assert np.abs(np.asarray(out)).max() <= bound


def _oracle_point(k):
    from tpunode.verify.ecdsa_cpu import GENERATOR, point_mul

    return point_mul(k, GENERATOR)


def _loose_projective(pt, lam, rng_l):
    """A curve point as projective (λx : λy : λ) with LOOSE, partly
    negative limbs: each coordinate is limbs(v + k) - limbs(k)."""
    coords = []
    for v in (pt.x * lam % F.P, pt.y * lam % F.P, lam % F.P):
        k = rng_l.getrandbits(255)
        coords.append(limbs(v + k) - limbs(k))  # v + k < 2^264: 24 limbs
    return coords


def _affine(p):
    from tpunode.verify.ecdsa_cpu import Point

    x, y, z = (ints(F.canonical(p[i])) for i in range(3))
    if z == 0:
        return Point(None, None)
    zi = pow(z, -1, F.P)
    return Point(x * zi % F.P, y * zi % F.P)


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_formulas_match_oracle_on_loose_operands(stack):
    """curve.pt_add / pt_double — the one body each — against the
    oracle's affine arithmetic, on projective representatives with a
    random Z and loose, negative-limb coordinates (the operands the
    window loop really feeds them), in both field stacks.  (Until PR 29
    the eager twin bodies were the reference here.)"""
    from tpunode.verify.curve import pt_add, pt_double
    from tpunode.verify.ecdsa_cpu import point_add, point_double

    ns = STACKS[stack]
    rng_l = random.Random(99)
    for _ in range(2):
        a = _oracle_point(rng_l.getrandbits(200) + 1)
        b = _oracle_point(rng_l.getrandbits(200) + 1)
        p = _loose_projective(a, rng_l.getrandbits(256) % F.P or 1, rng_l)
        q = _loose_projective(b, rng_l.getrandbits(256) % F.P or 1, rng_l)
        assert min(int(np.asarray(c).min()) for c in p + q) < 0
        assert _affine(pt_add(p, q, F=ns)) == point_add(a, b)
        assert _affine(pt_double(p, F=ns)) == point_double(a)


def test_no_formulation_switch_is_left():
    """PR 29: one formulation.  tpunode/verify/ reads no TPUNODE_*
    formulation variable, VerifyConfig has none of the five fields, and
    the setters are gone."""
    import dataclasses
    import pathlib

    import tpunode.verify as V
    from tpunode.verify import curve, kernel
    from tpunode.verify.engine import VerifyConfig

    knobs = ("TPUNODE_FIELD_", "TPUNODE_POINT_FORM", "TPUNODE_SELECT16",
             "TPUNODE_POW_LADDER", "TPUNODE_WINDOW_BITS")
    for path in pathlib.Path(V.__file__).parent.glob("*.py"):
        text = path.read_text()
        for k in knobs:
            assert k not in text, (path.name, k)
    fields = {f.name for f in dataclasses.fields(VerifyConfig)}
    assert not fields & {"field_mul", "field_sqr", "point_form",
                         "field_reduce", "window_bits"}
    for mod, names in (
        (F, ("set_field_modes", "field_modes", "mul_mode", "sqr_mode",
             "reduce_mode", "_env_mode")),
        (curve, ("set_point_form", "point_form", "pt_add_mixed")),
        (kernel, ("set_kernel_modes", "structure_modes", "window_bits",
                  "windows", "select_mode", "pow_ladder_mode")),
    ):
        for n in names:
            assert not hasattr(mod, n), (mod.__name__, n)
    assert (kernel.WINDOW_BITS, kernel.WINDOWS) == (4, 33)
