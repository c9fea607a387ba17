"""Pallas verify kernel + Mosaic-friendly field ops.

The Pallas kernel only compiles on real TPU hardware; here it runs in
interpreter mode (numpy semantics, same program) with a small lane block.
The on-TPU path is exercised by bench.py and scratch drives; its verdicts
are pinned against the CPU oracle there too.
"""

import random

import numpy as np
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy tier (pytest.ini)

from tpunode.verify import field as F
from tpunode.verify import pallas_field as PF
from tpunode.verify.ecdsa_cpu import (
    CURVE_N,
    GENERATOR,
    Point,
    point_mul,
    sign,
    verify,
    verify_batch_cpu,
)
from tests.lane_ref import DEVICE_FIELDS, expand_np, flag
from tpunode.verify.kernel import prepare_batch
from tpunode.verify.pallas_kernel import verify_blocked

rng = random.Random(0xA11A5)


def col(v: int) -> jnp.ndarray:
    return jnp.asarray(F.to_limbs(v))[:, None]


def test_pallas_field_matches_field_exact():
    """mul/mul_t/canonical of pallas_field are exact vs Python ints and
    bit-compatible (mod p) with field.py."""
    for _ in range(40):
        a_i = rng.getrandbits(256)
        b_i = rng.getrandbits(256)
        a, b = col(a_i), col(b_i)
        assert F.from_limbs(np.asarray(PF.mul(a, b))) % F.P == a_i * b_i % F.P
        assert (
            F.from_limbs(np.asarray(PF.mul_t(a, b))) % F.P == a_i * b_i % F.P
        )
        assert F.from_limbs(np.asarray(PF.canonical(a - b))) == (
            a_i - b_i
        ) % F.P


def test_pallas_field_loose_negative_limbs():
    """mul_t contract: any limbs with |limb| <= 2^13, including negative."""
    for _ in range(40):
        av = np.array(
            [rng.randint(-(2**13), 2**13) for _ in range(F.NLIMBS)],
            dtype=np.int32,
        )[:, None]
        bv = np.array(
            [rng.randint(-(2**13), 2**13) for _ in range(F.NLIMBS)],
            dtype=np.int32,
        )[:, None]
        got = F.from_limbs(np.asarray(PF.mul_t(jnp.asarray(av), jnp.asarray(bv))))
        want = F.from_limbs(av) * F.from_limbs(bv)
        assert got % F.P == want % F.P


def test_pallas_field_mul_small_red_and_eq():
    for _ in range(20):
        a_i = rng.getrandbits(256)
        a = col(a_i)
        m = PF.mul(a, col(1))
        scaled = PF.mul_small_red(m, 21)
        assert F.from_limbs(np.asarray(scaled)) % F.P == a_i * 21 % F.P
        assert bool(np.asarray(PF.eq(scaled, col(a_i * 21 % F.P)))[0, 0])
        assert not bool(np.asarray(PF.eq(scaled, col((a_i * 21 + 1) % F.P)))[0, 0])


def _mixed_items(n):
    items, expected = [], []
    for i in range(n):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        z = rng.getrandbits(256)
        r, s = sign(priv, z, rng.getrandbits(256))
        if i % 4 == 1:
            z ^= 1  # invalid signature
            expected.append(False)
        else:
            expected.append(True)
        items.append((pub, z, r, s))
    items.append((None, 1, 2, 3))
    expected.append(False)
    items.append((Point(None, None), 4, 5, 6))
    expected.append(False)
    # not-on-curve pubkey must be rejected by the device's curve check
    items.append((Point(12345, 67890), items[0][1], items[0][2], items[0][3]))
    expected.append(False)
    return items, expected


@pytest.mark.parametrize("native", [False, True])
def test_pallas_kernel_interpret_matches_oracle(native):
    """The full Pallas program (interpret mode, small block) against the
    CPU oracle, fed by both prep paths."""
    items, expected = _mixed_items(9)
    prep = prepare_batch(items, pad_to=16, native=native)
    out = verify_blocked(jnp.asarray(prep.buf), interpret=True, block=8)
    got = [bool(x) for x in np.asarray(out)[: prep.count]]
    assert got == expected
    assert verify_batch_cpu(items) == expected


def test_oversized_der_scalars_rejected_on_all_backends():
    """r' = r + 2^256 (lax DER allows >32-byte ints) must be invalid on
    every backend — truncating mod 2^256 would alias it onto a valid r."""
    from tpunode.verify.cpu_native import load_native_verifier

    items, expected = _mixed_items(1)
    q, z, r, s = items[0]
    attack = [(q, z, r + (1 << 256), s), (q, z, r, s + (1 << 256))]
    want = [False, False]
    assert verify_batch_cpu(attack) == want
    nat = load_native_verifier()
    if nat is not None:
        assert nat.verify_batch(attack) == want
    for native in (False, True):
        prep = prepare_batch(attack, pad_to=8, native=native)
        assert not flag(prep.buf, "host_valid").any()


def test_native_prep_bit_identical_to_python():
    """secp_prepare_batch writes the Python reference path's buffer, byte
    for byte, and so bit-identical digits, negs, limbs and masks."""
    from tpunode.verify.cpu_native import load_native_verifier

    if load_native_verifier() is None:
        pytest.skip("native library unavailable")
    items, _ = _mixed_items(17)
    # adversarial ranges
    q0 = items[0][0]
    items += [
        (q0, items[0][1], 0, items[0][3]),
        (q0, items[0][1], CURVE_N, items[0][3]),
        (q0, items[0][1], items[0][2], CURVE_N + 7),
        (q0, 1 << 300, items[0][2], items[0][3]),  # huge digest reduced mod n
    ]
    py = prepare_batch(items, pad_to=32, native=False)
    nat = prepare_batch(items, pad_to=32, native=True)
    assert py.buf.tobytes() == nat.buf.tobytes()
    a, b = expand_np(py.buf), expand_np(nat.buf)
    for name, _nd in DEVICE_FIELDS:
        assert np.array_equal(a[name], b[name]), name


def test_pallas_schnorr_free_variant_matches_oracle():
    """The ECDSA-only program variant (acceptance pows pruned at trace
    time via the static schnorr_free flag) must verdict identically to
    the oracle AND to the full program on an ECDSA-only batch."""
    items, expected = _mixed_items(9)
    prep = prepare_batch(items, pad_to=16)
    assert prep.schnorr_free  # ECDSA-only
    buf = jnp.asarray(prep.buf)
    pruned = verify_blocked(buf, interpret=True, block=8, schnorr_free=True)
    full = verify_blocked(buf, interpret=True, block=8)
    got = [bool(x) for x in np.asarray(pruned)[: prep.count]]
    assert got == expected
    assert np.array_equal(np.asarray(pruned), np.asarray(full))


def test_dispatch_derives_schnorr_free_from_flags(monkeypatch):
    """kernel._dispatch_prep selects the pruned variant exactly when no
    lane carries a schnorr/bip340 flag — a wrong True on a mixed batch
    would accept jacobi/parity forgeries."""
    from tpunode.verify import kernel as K
    from tpunode.verify import pallas_kernel as PK
    from tpunode.verify.ecdsa_cpu import (
        schnorr_challenge,
        sign_schnorr,
    )

    seen = []

    def fake_blocked(buf, schnorr_free=False):
        seen.append(schnorr_free)
        return jnp.zeros((buf.shape[-1],), dtype=jnp.bool_)

    monkeypatch.setattr(PK, "verify_blocked", fake_blocked)
    monkeypatch.setattr(K, "_pallas_usable", lambda b: True)

    ecdsa, _ = _mixed_items(4)
    K._dispatch_prep(prepare_batch(ecdsa, pad_to=8))
    priv = 77
    pub = point_mul(priv, GENERATOR)
    r, s = sign_schnorr(priv, 99, 1234)
    mixed = ecdsa + [(pub, schnorr_challenge(r, pub, 99), r, s, "schnorr")]
    K._dispatch_prep(prepare_batch(mixed, pad_to=8))
    assert seen == [True, False]


def test_pallas_field_wide_api_matches_field_exact():
    """The Mosaic-form wide-accumulator API is bit-identical to
    field.py's: same wides, same reductions (tight and loose), same
    accumulated sums."""
    for _ in range(20):
        a_i, b_i, c_i, d_i = (rng.getrandbits(256) % F.P for _ in range(4))
        a, b, c, d = col(a_i), col(b_i), col(c_i), col(d_i)
        assert (
            np.asarray(PF.reduce_wide(PF.mul_wide(a, b)))
            == np.asarray(F.reduce_wide(F.mul_wide(a, b)))
        ).all()
        w_pf = PF.acc_add(PF.mul_t_wide(a, b), PF.mul_t_wide(c, d))
        w_f = F.acc_add(F.mul_t_wide(a, b), F.mul_t_wide(c, d))
        assert (np.asarray(w_pf) == np.asarray(w_f)).all()
        assert (
            np.asarray(PF.reduce_wide_loose(w_pf))
            == np.asarray(F.reduce_wide_loose(w_f))
        ).all()
        assert (
            np.asarray(PF.sqr_t_wide(a)) == np.asarray(F.sqr_t_wide(a))
        ).all()
        want = (a_i * b_i + c_i * d_i) % F.P
        got = F.from_limbs(np.asarray(PF.reduce_wide_loose(w_pf))) % F.P
        assert got == want


@pytest.mark.slow  # a fresh interpret trace (~1 min on CPU)
def test_pallas_two_grid_steps_match_oracle():
    """The schnorr_free program over TWO grid steps (batch 16, block 8)
    verdicts exactly like the oracle.  (Until PR 29 this compared the
    lazy against the eager program; the oracle is the reference now.)"""
    items, expected = _mixed_items(9)
    prep = prepare_batch(items, pad_to=16)
    out = verify_blocked(
        jnp.asarray(prep.buf), interpret=True, block=8, schnorr_free=True
    )
    got = [bool(x) for x in np.asarray(out)[: prep.count]]
    assert got == expected
