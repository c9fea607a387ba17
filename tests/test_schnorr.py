"""BCH Schnorr signatures (2019-05 upgrade spec) across every backend.

The verify equation R' = s·G − e·P shares the ECDSA kernel's dual-scalar
MSM, so one device program verifies mixed batches: per-lane the acceptance
test switches between x(R) ∈ {r, r+n} (ECDSA) and x(R) = r ∧ jacobi(y(R))
= 1 (Schnorr, via a windowed Euler pow).  Items are tagged by a 5th tuple
element / RawBatch.present == 2; the challenge e is precomputed at
extraction so no backend re-hashes.
"""

from __future__ import annotations

import random

import pytest

from tpunode.verify.ecdsa_cpu import (
    CURVE_N,
    CURVE_P,
    GENERATOR,
    Point,
    jacobi,
    point_mul,
    schnorr_challenge,
    sign,
    sign_schnorr,
    verify_batch_cpu,
    verify_schnorr,
    verify_schnorr_e,
)

rng = random.Random(0x5C40)


def test_independent_spec_construction():
    """Build BCH Schnorr signatures from scratch per the 2019 spec with an
    INDEPENDENT hashlib challenge (no shared schnorr_challenge code), and
    require the repo verifier to accept them — closing the
    sign/verify-share-a-bug loophole (ADVICE r4).  Also pins the repo
    challenge function byte-for-byte against the independent one."""
    import hashlib

    from tpunode.verify.ecdsa_cpu import CURVE_P as P_

    local = random.Random(0xBC45)
    for i in range(8):
        d = local.getrandbits(256) % CURVE_N or 1
        P = point_mul(d, GENERATOR)
        m = local.getrandbits(256)
        k = local.getrandbits(256) % CURVE_N or 1
        R = point_mul(k, GENERATOR)
        # spec: k is negated when jacobi(R.y) != 1, R.x is kept
        if jacobi(R.y) != 1:
            k = CURVE_N - k
        r = R.x
        compressed = bytes([2 + (P.y & 1)]) + P.x.to_bytes(32, "big")
        e_ind = (
            int.from_bytes(
                hashlib.sha256(
                    r.to_bytes(32, "big") + compressed + m.to_bytes(32, "big")
                ).digest(),
                "big",
            )
            % CURVE_N
        )
        assert e_ind == schnorr_challenge(r, P, m)  # challenge pinned
        s = (k + e_ind * d) % CURVE_N
        assert verify_schnorr(P, m, r, s), i
        assert not verify_schnorr(P, m ^ 1, r, s)
        assert not verify_schnorr(P, m, r, (s + 1) % CURVE_N)
        # odd-y pubkeys exercise the compressed-prefix byte
        if P.y & 1:
            break
    # jacobi rule: a signature built WITHOUT the k negation must fail
    # whenever jacobi(R.y) != 1 (the acceptance test is jacobi, not parity)
    d = 0xD1CE
    P = point_mul(d, GENERATOR)
    m = 0x1234
    for k in range(2, 40):
        R = point_mul(k, GENERATOR)
        if jacobi(R.y) == 1:
            continue
        compressed = bytes([2 + (P.y & 1)]) + P.x.to_bytes(32, "big")
        e = int.from_bytes(
            hashlib.sha256(
                R.x.to_bytes(32, "big") + compressed + m.to_bytes(32, "big")
            ).digest(), "big") % CURVE_N
        s_wrong = (k + e * d) % CURVE_N  # forgot the negation
        assert not verify_schnorr(P, m, R.x, s_wrong)
        s_right = ((CURVE_N - k) + e * d) % CURVE_N
        assert verify_schnorr(P, m, R.x, s_right)
        break
    assert 0 <= P.x < P_


def _schnorr_item(corrupt: str = ""):
    priv = rng.getrandbits(256) % CURVE_N or 1
    pub = point_mul(priv, GENERATOR)
    m = rng.getrandbits(256)
    r, s = sign_schnorr(priv, m, rng.getrandbits(256))
    if corrupt == "m":
        m ^= 1
    elif corrupt == "s":
        s = (s + 1) % CURVE_N
    e = schnorr_challenge(r, pub, m)
    return (pub, e, r, s, "schnorr"), corrupt == ""


def _ecdsa_item(corrupt: bool = False):
    priv = rng.getrandbits(256) % CURVE_N or 1
    pub = point_mul(priv, GENERATOR)
    z = rng.getrandbits(256)
    r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
    if corrupt:
        s = (s + 1) % CURVE_N or 1
    return (pub, z, r, s), not corrupt


def _mixed_batch(n):
    items, expect = [], []
    for i in range(n):
        if i % 2 == 0:
            it, ok = _schnorr_item("m" if i % 6 == 2 else "s" if i % 6 == 4 else "")
        else:
            it, ok = _ecdsa_item(corrupt=i % 5 == 3)
        items.append(it)
        expect.append(ok)
    return items, expect


# --- fixed spec vectors -----------------------------------------------------
#
# The BCH 2019-05 Schnorr spec adopts the construction of the pre-BIP340
# "bip-schnorr" draft (e = H(r ‖ compressed(P) ‖ m), jacobi(y(R)) = 1), and
# points at that draft's published test vectors.  Embedding them as literal
# constants closes the ADVICE-r4 loophole for this lane the same way
# tests/test_bip340.py does for taproot: acceptance cannot depend on any
# in-repo signing/challenge code agreeing with itself.  (The independent
# hashlib re-derivation above covers the signing side.)

BCH_SCHNORR_VECTORS = [
    # (compressed pubkey, msg, sig = r ‖ s, expected)
    ("0279BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798",
     "0000000000000000000000000000000000000000000000000000000000000000",
     "787A848E71043D280C50470E8E1532B2DD5D20EE912A45DBDD2BD1DFBF187EF6"
     "7031A98831859DC34DFFEEDDA86831842CCD0079E1F92AF177F7F22CC1DCED05",
     True),
    ("02DFF1D77F2A671C5F36183726DB2341BE58FEAE1DA2DECED843240F7B502BA659",
     "243F6A8885A308D313198A2E03707344A4093822299F31D0082EFA98EC4E6C89",
     "2A298DACAE57395A15D0795DDBFD1DCB564DA82B0F269BC70A74F8220429BA1D"
     "1E51A22CCEC35599B8F266912281F8365FFC2D035A230434A1A64DC59F7013FD",
     True),
    ("03FAC2114C2FBB091527EB7C64ECB11F8021CB45E8E7809D3C0938E4B8C0E5F84B",
     "5E2D58D8B3BCDF1ABADEC7829054F90DDA9805AAB56C77333024B9D0A508B75C",
     "00DA9B08172A9B6F0466A2DEFD817F2D7AB437E0D253CB5395A963866B3574BE"
     "00880371D01766935B92D2AB4CD5C8A2A5837EC57FED7660773A05F0DE142380",
     True),
    # negated message: the vector-2 signature over m with its low bit set
    # must NOT verify (draft's "negated message" negative, re-anchored to a
    # positive row so the constant stays self-checking)
    ("03FAC2114C2FBB091527EB7C64ECB11F8021CB45E8E7809D3C0938E4B8C0E5F84B",
     "5E2D58D8B3BCDF1ABADEC7829054F90DDA9805AAB56C77333024B9D0A508B75D",
     "00DA9B08172A9B6F0466A2DEFD817F2D7AB437E0D253CB5395A963866B3574BE"
     "00880371D01766935B92D2AB4CD5C8A2A5837EC57FED7660773A05F0DE142380",
     False),
]

# x not on the curve (same famous constant BIP340 uses as its first
# negative): SEC1 decode must fail, and the engine row is auto-invalid.
SCHNORR_OFFCURVE_PUB = (
    "02EEFDEA4CDB677750A420FEE807EACF21EB9898AE79B9768766E4FAA04A2D4A34"
)


def _fixed_vector_items():
    """Fixed vector rows + systematic negatives, as engine tuples."""
    from tpunode.verify.ecdsa_cpu import decode_pubkey

    items, expect = [], []
    for pub_hex, msg, sig, res in BCH_SCHNORR_VECTORS:
        if not res:
            # literal negatives are covered in test_fixed_vectors_oracle;
            # the m^1 systematic negative below would duplicate them here
            continue
        P = decode_pubkey(bytes.fromhex(pub_hex))
        assert P is not None
        m = int(msg, 16)
        r, s = int(sig[:64], 16), int(sig[64:], 16)
        items.append((P, schnorr_challenge(r, P, m), r, s, "schnorr"))
        expect.append(True)
        # systematic negatives from each positive row
        items.append((P, schnorr_challenge(r, P, m ^ 1), r, s, "schnorr"))
        expect.append(False)
        items.append((P, schnorr_challenge(r, P, m), r,
                      (s + 1) % CURVE_N, "schnorr"))
        expect.append(False)
    assert decode_pubkey(bytes.fromhex(SCHNORR_OFFCURVE_PUB)) is None
    items.append((None, 0, 1, 1, "schnorr"))
    expect.append(False)
    # out-of-range r / s
    P0 = decode_pubkey(bytes.fromhex(BCH_SCHNORR_VECTORS[0][0]))
    items.append((P0, 1, CURVE_P, 1, "schnorr"))
    expect.append(False)
    items.append((P0, 1, 1, CURVE_N, "schnorr"))
    expect.append(False)
    return items, expect


def test_fixed_vectors_oracle():
    from tpunode.verify.ecdsa_cpu import decode_pubkey

    for pub_hex, msg, sig, res in BCH_SCHNORR_VECTORS:
        P = decode_pubkey(bytes.fromhex(pub_hex))
        r, s = int(sig[:64], 16), int(sig[64:], 16)
        assert verify_schnorr(P, int(msg, 16), r, s) is res, pub_hex


def test_fixed_vectors_native_cpp():
    from tpunode.verify.cpu_native import load_native_verifier

    nv = load_native_verifier()
    if nv is None:
        pytest.skip("native verifier unavailable")
    items, expect = _fixed_vector_items()
    assert nv.verify_batch(items) == expect


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_fixed_vectors_xla_kernel():
    jax = pytest.importorskip("jax")
    del jax
    from tpunode.verify.kernel import verify_batch_tpu

    items, expect = _fixed_vector_items()
    assert verify_batch_tpu(items, pad_to=16) == expect


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_fixed_vectors_pallas_interpret():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from tpunode.verify.kernel import expand_lane, prepare_batch
    from tpunode.verify.pallas_kernel import verify_blocked_impl

    items, expect = _fixed_vector_items()
    prep = prepare_batch(items, pad_to=16)
    args = expand_lane(jnp.asarray(prep.buf))
    out = verify_blocked_impl(*args, interpret=True, block=16)
    assert [bool(b) for b in out[: len(expect)]] == expect
    del jax


# --- oracle ----------------------------------------------------------------


def test_oracle_sign_verify_roundtrip():
    for _ in range(8):
        priv = rng.getrandbits(256) % CURVE_N or 1
        pub = point_mul(priv, GENERATOR)
        m = rng.getrandbits(256)
        r, s = sign_schnorr(priv, m, rng.getrandbits(256))
        assert verify_schnorr(pub, m, r, s)
        assert not verify_schnorr(pub, m ^ 1, r, s)
        # signing forced jacobi(y(R)) = 1
        assert jacobi(point_mul((s - schnorr_challenge(r, pub, m) * priv) %
                                CURVE_N, GENERATOR).y) == 1


def test_oracle_range_and_degenerate_rules():
    (pub, e, r, s, _), _ = _schnorr_item()[0], None
    assert not verify_schnorr_e(pub, e, CURVE_P, s)  # r >= p
    assert not verify_schnorr_e(pub, e, r, CURVE_N)  # s >= n
    assert not verify_schnorr_e(None, e, r, s)
    assert not verify_schnorr_e(Point(None, None), e, r, s)


def test_oracle_batch_mixed():
    items, expect = _mixed_batch(24)
    assert verify_batch_cpu(items) == expect
    assert True in expect and False in expect


# --- C++ engine ------------------------------------------------------------


def test_native_cpp_matches_oracle():
    from tpunode.verify.cpu_native import load_native_verifier

    nv = load_native_verifier()
    if nv is None:
        pytest.skip("native verifier unavailable")
    items, expect = _mixed_batch(40)
    # range-edge rows exercise pack_items' schnorr rules
    (pub, e, r, s, tag), _ = _schnorr_item()[0], None
    items += [(pub, e, CURVE_P, s, tag), (pub, e, r, CURVE_N, tag), (None, e, r, s, tag)]
    expect += [False, False, False]
    assert nv.verify_batch(items) == expect


# --- raw round-trip --------------------------------------------------------


def test_rawbatch_roundtrip_preserves_algo():
    from tpunode.verify.raw import pack_items

    items, expect = _mixed_batch(12)
    raw = pack_items(items)
    assert set(raw.present.tolist()) <= {0, 1, 2}
    assert (raw.present == 2).sum() > 0 and (raw.present == 1).sum() > 0
    back = raw.to_tuples()
    assert verify_batch_cpu(back) == expect


# --- device kernels (cpu-jax XLA; pallas interpret) ------------------------


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_xla_kernel_mixed_batch():
    jax = pytest.importorskip("jax")
    del jax
    from tpunode.verify.kernel import verify_batch_tpu

    items, expect = _mixed_batch(24)
    assert verify_batch_tpu(items, pad_to=32) == expect


def test_native_prep_parity_with_python_prep():
    import numpy as np

    from tests.lane_ref import flag
    from tpunode.verify.cpu_native import load_native_verifier
    from tpunode.verify.kernel import prepare_batch

    if load_native_verifier() is None:
        pytest.skip("native prep unavailable")
    items, _ = _mixed_batch(20)
    a = prepare_batch(items, pad_to=32, native=False)
    b = prepare_batch(items, pad_to=32, native=True)
    assert np.array_equal(a.buf, b.buf)
    assert flag(a.buf, "schnorr").sum() > 0


@pytest.mark.heavy  # device-kernel compile (pytest.ini tiers)
def test_pallas_interpret_mixed_batch():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from tpunode.verify.kernel import expand_lane, prepare_batch
    from tpunode.verify.pallas_kernel import verify_blocked_impl

    items, expect = _mixed_batch(16)
    prep = prepare_batch(items, pad_to=16)
    args = expand_lane(jnp.asarray(prep.buf))
    out = verify_blocked_impl(*args, interpret=True, block=8)
    assert [bool(b) for b in out[:16]] == expect
    del jax


# --- extraction ------------------------------------------------------------


def _extract(tx, bch=True):
    from benchmarks.txgen import synth_amount
    from tpunode.txverify import (
        combine_verdicts,
        extract_sig_items,
        wants_amount,
    )

    amounts = {
        idx: synth_amount(ti.prevout.txid, ti.prevout.index)
        for idx, ti in enumerate(tx.inputs)
        if wants_amount(tx, idx, bch)
    }
    items, stats = extract_sig_items(
        tx, prevout_amounts=amounts or None, bch=bch
    )
    verdicts = verify_batch_cpu([i.verify_item for i in items])
    return items, stats, combine_verdicts(items, verdicts)


def test_extracts_schnorr_p2pkh_spend():
    from benchmarks.txgen import gen_mixed_txs

    txs = gen_mixed_txs(12, seed=77, schnorr_every=2)
    n_sch = 0
    for tx in txs:
        items, stats, per_sig = _extract(tx)
        for it in items:
            n_sch += it.algo == "schnorr"
        if stats.unsupported == 0:
            assert all(per_sig)
    assert n_sch > 0


def test_65_byte_sig_on_btc_is_unsupported():
    """Off BCH there is no Schnorr rule: a 65-byte blob fails DER parse
    and the input counts unsupported."""
    from benchmarks.txgen import gen_mixed_txs

    tx = gen_mixed_txs(2, seed=77, schnorr_every=1)[0]
    items, stats, _ = _extract(tx, bch=False)
    assert not items and stats.unsupported == len(tx.inputs)


def test_schnorr_in_multisig_is_auto_invalid():
    """2019 consensus: Schnorr (65-byte) sigs are NOT allowed in
    CHECKMULTISIG — candidates must come out auto-invalid, not verified."""
    from tests.test_multisig import _mk_msig_tx
    from tpunode.wire import Tx, TxIn

    tx, _ = _mk_msig_tx(2, 3, [0, 1], segwit=False, bch=True)
    # replace first sig push with a 65-byte schnorr-shaped blob
    from benchmarks.txgen import _push

    script = tx.inputs[0].script
    first_len = script[1]
    garbled = (
        b"\x00" + _push(bytes(65)) + script[2 + first_len :]
    )
    tx2 = Tx(1, (TxIn(tx.inputs[0].prevout, garbled, 0xFFFFFFFF),), tx.outputs, 0)
    items, stats, per_sig = _extract(tx2, bch=True)
    assert stats.extracted == 1
    assert per_sig[0] is False  # the schnorr-shaped sig matches no key


def test_native_extract_parity_with_schnorr():
    txextract = pytest.importorskip("tpunode.txextract")
    if not txextract.have_native_extract():
        pytest.skip("native txextract unavailable")
    from benchmarks.txgen import gen_mixed_txs, synth_amount
    from tpunode.txverify import wants_amount

    txs = gen_mixed_txs(60, seed=91, invalid_every=7, schnorr_every=3)
    data = b"".join(t.serialize() for t in txs)
    ext = []
    for tx in txs:
        for idx, ti in enumerate(tx.inputs):
            ext.append(
                synth_amount(ti.prevout.txid, ti.prevout.index)
                if wants_amount(tx, idx, True)
                else -1
            )
    raw = txextract.extract_raw(data, len(txs), bch=True, ext_amounts=ext)
    py_per_sig = []
    py_items = []
    for tx in txs:
        items, _, per_sig = _extract(tx)
        py_items.extend(items)
        py_per_sig.extend(per_sig)
    assert raw.count == len(py_items)
    for i, it in enumerate(py_items):
        want = 2 if (it.algo == "schnorr" and it.pubkey is not None) else None
        if want is not None:
            assert int(raw.present[i]) == want, i
    native_verd = verify_batch_cpu(raw.to_verify_items())
    assert raw.combine(native_verd) == py_per_sig


# --- node end-to-end -------------------------------------------------------


@pytest.mark.asyncio
async def test_node_block_ingest_with_schnorr():
    import asyncio

    from benchmarks.txgen import gen_mixed_txs, synth_amount
    from tests.fakenet import dummy_peer_connect
    from tests.fixtures import all_blocks
    from tpunode import BCH_REGTEST, Node, NodeConfig, Publisher, txextract
    from tpunode.node import TxVerdict
    from tpunode.peer import PeerConnected, PeerMessage
    from tpunode.store import MemoryKV
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import Block, BlockHeader, MsgBlock

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")
    txs = gen_mixed_txs(10, seed=0x5C7, schnorr_every=2)
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    msg = MsgBlock.deserialize_payload(
        Reader(Block(hdr, tuple(txs)).serialize())
    )
    pub = Publisher(name="ev")
    cfg = NodeConfig(
        net=BCH_REGTEST,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:1"],
        connect=lambda sa: dummy_peer_connect(BCH_REGTEST, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
        prevout_lookup=synth_amount,
    )
    seen = {}
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(15):
                peer = await events.receive_match(
                    lambda ev: ev.peer if isinstance(ev, PeerConnected) else None
                )
                node._peer_pub.publish(PeerMessage(peer, msg))
                while len(seen) < len(txs):
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        seen[ev.txid] = ev
    for tx in txs:
        ev = seen[tx.txid]
        assert ev.error is None
        if ev.stats.unsupported == 0:
            assert ev.valid, tx.txid.hex()
