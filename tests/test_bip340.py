"""BIP340 (taproot) Schnorr as a verify primitive, across every backend.

Third algorithm over the same dual-scalar MSM: x-only pubkeys lifted to
the even-y point, a tagged challenge, and acceptance x(R) = r AND y(R)
EVEN (the device computes parity via a Fermat-inverse windowed pow).
Items are 5-tuples tagged "bip340" / RawBatch.present == 3.  Extraction
emits these for taproot KEYPATH spends given the extended prevout oracle
(tests/test_taproot.py); this file covers the primitive itself,
including the published BIP340 spec vectors (VERDICT r4 item 4 /
ADVICE r4: the self-signed tests alone could mask a joint spec
deviation in the shared challenge code).
"""

from __future__ import annotations

import random

import pytest

from tpunode.verify.ecdsa_cpu import (
    CURVE_N,
    CURVE_P,
    GENERATOR,
    bip340_challenge,
    lift_x,
    point_mul,
    sign_bip340,
    tagged_hash,
    verify_batch_cpu,
    verify_bip340,
    verify_bip340_e,
)

rng = random.Random(0xB1340)


def _item(corrupt: str = ""):
    priv = rng.getrandbits(256) % CURVE_N or 1
    px = point_mul(priv, GENERATOR).x
    m = rng.getrandbits(256)
    r, s = sign_bip340(priv, m, rng.getrandbits(256))
    if corrupt == "m":
        m ^= 1
    elif corrupt == "s":
        s = (s + 1) % CURVE_N
    e = bip340_challenge(r, px, m)
    return (lift_x(px), e, r, s, "bip340"), corrupt == ""


def _batch(n):
    items, expect = [], []
    for i in range(n):
        it, ok = _item("m" if i % 5 == 2 else "s" if i % 5 == 4 else "")
        items.append(it)
        expect.append(ok)
    return items, expect


def test_oracle_roundtrip_and_rules():
    for _ in range(6):
        priv = rng.getrandbits(256) % CURVE_N or 1
        px = point_mul(priv, GENERATOR).x
        m = rng.getrandbits(256)
        r, s = sign_bip340(priv, m, rng.getrandbits(256))
        assert verify_bip340(px, m, r, s)
        assert not verify_bip340(px, m ^ 1, r, s)
        # the lifted pubkey always has even y; R' of a valid sig too
        P = lift_x(px)
        assert P.y % 2 == 0
    (P, e, r, s, _), _ = _item()
    assert not verify_bip340_e(P, e, CURVE_P, s)  # r out of Fp range
    assert not verify_bip340_e(P, e, r, CURVE_N)  # s out of scalar range
    assert not verify_bip340_e(None, e, r, s)
    assert not verify_bip340(CURVE_P, 1, 1, 1)  # x not liftable


def test_tagged_hash_structure():
    # SHA256(SHA256(tag) || SHA256(tag) || data) — self-consistency probes
    import hashlib

    th = hashlib.sha256(b"BIP0340/challenge").digest()
    assert tagged_hash(b"BIP0340/challenge", b"xyz") == hashlib.sha256(
        th + th + b"xyz"
    ).digest()


def test_native_cpp_matches_oracle():
    from tpunode.verify.cpu_native import load_native_verifier

    nv = load_native_verifier()
    if nv is None:
        pytest.skip("native verifier unavailable")
    items, expect = _batch(30)
    assert nv.verify_batch(items) == expect
    assert True in expect and False in expect


def test_rawbatch_roundtrip():
    from tpunode.verify.raw import pack_items

    items, expect = _batch(10)
    raw = pack_items(items)
    assert (raw.present == 3).sum() == 10
    assert verify_batch_cpu(raw.to_tuples()) == expect


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_xla_kernel_mixed_with_other_algos():
    jax = pytest.importorskip("jax")
    del jax
    from tpunode.verify.ecdsa_cpu import (
        schnorr_challenge,
        sign,
        sign_schnorr,
    )
    from tpunode.verify.kernel import verify_batch_tpu

    items, expect = _batch(10)
    for i in range(10):  # interleave the other algorithms
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        m = rng.getrandbits(256)
        if i % 2 == 0:
            r, s = sign(priv, m, rng.getrandbits(256) % CURVE_N or 1)
            items.append((pub, m, r, s))
        else:
            r, s = sign_schnorr(priv, m, rng.getrandbits(256))
            items.append((pub, schnorr_challenge(r, pub, m), r, s, "schnorr"))
        expect.append(True)
    got = verify_batch_tpu(items, pad_to=32)
    assert got == expect


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_pallas_interpret():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from tpunode.verify.kernel import expand_lane, prepare_batch
    from tpunode.verify.pallas_kernel import verify_blocked_impl

    items, expect = _batch(8)
    prep = prepare_batch(items, pad_to=8)
    args = expand_lane(jnp.asarray(prep.buf))
    out = verify_blocked_impl(*args, interpret=True, block=8)
    assert [bool(b) for b in out[:8]] == expect
    del jax


# --- official BIP340 test vectors -------------------------------------------
#
# Rows from the BIP's test-vector CSV (index, seckey, pubkey, aux_rand,
# message, signature, result).  Positive vectors 0-4 include the
# "almost-zero r" vector 4; vector 5's famous not-on-curve pubkey is the
# off-curve negative.  Verification must NOT depend on in-repo signing:
# test_spec_sign_derivation below re-derives vectors 0-3 with an
# independent hashlib implementation of the BIP's signing algorithm.

BIP340_VECTORS = [
    # (seckey | None, pubkey_x, aux_rand | None, msg, sig, expected)
    ("0000000000000000000000000000000000000000000000000000000000000003",
     "F9308A019258C31049344F85F89D5229B531C845836F99B08601F113BCE036F9",
     "0000000000000000000000000000000000000000000000000000000000000000",
     "0000000000000000000000000000000000000000000000000000000000000000",
     "E907831F80848D1069A5371B402410364BDF1C5F8307B0084C55F1CE2DCA8215"
     "25F66A4A85EA8B71E482A74F382D2CE5EBEEE8FDB2172F477DF4900D310536C0",
     True),
    ("B7E151628AED2A6ABF7158809CF4F3C762E7160F38B4DA56A784D9045190CFEF",
     "DFF1D77F2A671C5F36183726DB2341BE58FEAE1DA2DECED843240F7B502BA659",
     "0000000000000000000000000000000000000000000000000000000000000001",
     "243F6A8885A308D313198A2E03707344A4093822299F31D0082EFA98EC4E6C89",
     "6896BD60EEAE296DB48A229FF71DFE071BDE413E6D43F917DC8DCF8C78DE3341"
     "8906D11AC976ABCCB20B091292BFF4EA897EFCB639EA871CFA95F6DE339E4B0A",
     True),
    ("C90FDAA22168C234C4C6628B80DC1CD129024E088A67CC74020BBEA63B14E5C9",
     "DD308AFEC5777E13121FA72B9CC1B7CC0139715309B086C960E18FD969774EB8",
     "C87AA53824B4D7AE2EB035A2B5BBBCCC080E76CDC6D1692C4B0B62D798E6D906",
     "7E2D58D8B3BCDF1ABADEC7829054F90DDA9805AAB56C77333024B9D0A508B75C",
     "5831AAEED7B44BB74E5EAB94BA9D4294C49BCF2A60728D8B4C200F50DD313C1B"
     "AB745879A5AD954A72C45A91C3A51D3C7ADEA98D82F8481E0E1E03674A6F3FB7",
     True),
    ("0B432B2677937381AEF05BB02A66ECD012773062CF3FA2549E44F58ED2401710",
     "25D1DFF95105F5253C4022F628A996AD3A0D95FBF21D468A1B33F8C160D8F517",
     "FFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF",
     "FFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF",
     "7EB0509757E246F19449885651611CB965ECC1A187DD51B64FDA1EDC9637D5EC"
     "97582B9CB13DB3933705B32BA982AF5AF25FD78881EBB32771FC5922EFC66EA3",
     True),
    (None,  # verify-only: r with 11 leading zero bytes
     "D69C3509BB99E412E68B0FE8544E72837DFA30746D8BE2AA65975F29D22DC7B9",
     None,
     "4DF3C3F68FCC83B27E9D42C90431A72499F17875C81A599B566C9889B9696703",
     "00000000000000000000003B78CE563F89A0ED9414F5AA28AD0D96D6795F9C63"
     "76AFB1548AF603B3EB45C9F8207DEE1060CB71C04E80F593060B07D28308D7F4",
     True),
]

# Not-on-curve public key (the BIP's first negative vector): lift_x fails.
BIP340_OFFCURVE_PUB = (
    "EEFDEA4CDB677750A420FEE807EACF21EB9898AE79B9768766E4FAA04A2D4A34"
)


def _vector_items():
    """All vector rows + systematic negatives, as engine tuples."""
    items, expect = [], []
    for _, pub, _, msg, sig, res in BIP340_VECTORS:
        px, m = int(pub, 16), int(msg, 16)
        r, s = int(sig[:64], 16), int(sig[64:], 16)
        e = bip340_challenge(r, px, m)
        items.append((lift_x(px), e, r, s, "bip340"))
        expect.append(res)
        if res:  # systematic negatives from each positive row
            items.append((lift_x(px), bip340_challenge(r, px, m ^ 1), r, s,
                          "bip340"))
            expect.append(False)
            s_bad = (s + 1) % CURVE_N
            items.append((lift_x(px), e, r, s_bad, "bip340"))
            expect.append(False)
    # off-curve pubkey: auto-invalid (pubkey None)
    assert lift_x(int(BIP340_OFFCURVE_PUB, 16)) is None
    items.append((None, 0, 1, 1, "bip340"))
    expect.append(False)
    # out-of-range r / s
    px0 = int(BIP340_VECTORS[0][1], 16)
    items.append((lift_x(px0), 1, CURVE_P, 1, "bip340"))
    expect.append(False)
    items.append((lift_x(px0), 1, 1, CURVE_N, "bip340"))
    expect.append(False)
    return items, expect


def test_vectors_oracle():
    for sk, pub, _, msg, sig, res in BIP340_VECTORS:
        px, m = int(pub, 16), int(msg, 16)
        r, s = int(sig[:64], 16), int(sig[64:], 16)
        assert verify_bip340(px, m, r, s) is res, pub
        if sk is not None:  # seckey column is consistent with the pubkey
            P = point_mul(int(sk, 16), GENERATOR)
            assert P.x == px


def test_spec_sign_derivation_reproduces_vectors():
    """Re-derive vectors 0-3 with an INDEPENDENT implementation of the
    BIP340 signing algorithm (hashlib only — no shared tagged_hash /
    challenge code), closing the sign/verify-share-a-bug loophole."""
    import hashlib

    def th(tag: bytes, data: bytes) -> bytes:
        t = hashlib.sha256(tag).digest()
        return hashlib.sha256(t + t + data).digest()

    for sk, pub, aux, msg, sig, _ in BIP340_VECTORS:
        if sk is None:
            continue
        d0 = int(sk, 16)
        P = point_mul(d0, GENERATOR)
        d = d0 if P.y % 2 == 0 else CURVE_N - d0
        t = d ^ int.from_bytes(th(b"BIP0340/aux", bytes.fromhex(aux)), "big")
        k0 = int.from_bytes(
            th(b"BIP0340/nonce",
               t.to_bytes(32, "big") + P.x.to_bytes(32, "big")
               + bytes.fromhex(msg)),
            "big") % CURVE_N
        R = point_mul(k0, GENERATOR)
        k = k0 if R.y % 2 == 0 else CURVE_N - k0
        e = int.from_bytes(
            th(b"BIP0340/challenge",
               R.x.to_bytes(32, "big") + P.x.to_bytes(32, "big")
               + bytes.fromhex(msg)),
            "big") % CURVE_N
        s = (k + e * d) % CURVE_N
        assert f"{R.x:064X}{s:064X}" == sig, pub


def test_vectors_native_cpp():
    from tpunode.verify.cpu_native import load_native_verifier

    nv = load_native_verifier()
    if nv is None:
        pytest.skip("native verifier unavailable")
    items, expect = _vector_items()
    assert nv.verify_batch(items) == expect


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_vectors_xla_kernel():
    jax = pytest.importorskip("jax")
    del jax
    from tpunode.verify.kernel import verify_batch_tpu

    items, expect = _vector_items()
    assert verify_batch_tpu(items, pad_to=32) == expect


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_vectors_pallas_interpret():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from tpunode.verify.kernel import expand_lane, prepare_batch
    from tpunode.verify.pallas_kernel import verify_blocked_impl

    items, expect = _vector_items()
    prep = prepare_batch(items, pad_to=32)
    args = expand_lane(jnp.asarray(prep.buf))
    out = verify_blocked_impl(*args, interpret=True, block=32)
    assert [bool(b) for b in out[: len(expect)]] == expect
    del jax


def test_native_prep_parity():
    import numpy as np

    from tests.lane_ref import flag
    from tpunode.verify.cpu_native import load_native_verifier
    from tpunode.verify.kernel import prepare_batch

    if load_native_verifier() is None:
        pytest.skip("native prep unavailable")
    items, _ = _batch(12)
    a = prepare_batch(items, pad_to=16, native=False)
    b = prepare_batch(items, pad_to=16, native=True)
    assert np.array_equal(a.buf, b.buf)
    assert flag(a.buf, "bip340").sum() == 12
