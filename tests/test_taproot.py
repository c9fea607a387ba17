"""Taproot (BIP341/BIP340) keypath extraction tests.

Covers the Python reference path: BIP341 sighash construction, P2TR
detection from the prevout script, annex handling, the consensus-invalid
shapes (bad hash_type, out-of-range SIGHASH_SINGLE, off-curve output key)
and the unsupported shapes (script path, missing prevout info).  The
native extractor's parity with this path is covered by
tests/test_txextract.py and the differential fuzzer.

Reference parity note: the upstream node performs no script validation at
all (SURVEY.md §3.3); this is north-star capability — the verify surface
of libsecp256k1's schnorrsig module (reference stack.yaml:5).
"""

from __future__ import annotations

import dataclasses

import pytest

from tpunode.sighash import bip341_sighash, valid_taproot_hashtype
from tpunode.txverify import (
    combine_verdicts,
    extract_sig_items,
    intra_block_prevouts,
    is_p2tr,
)
from tpunode.verify.ecdsa_cpu import (
    GENERATOR,
    point_mul,
    sign_bip340,
    verify_batch_cpu,
)
from tpunode.wire import OutPoint, Tx, TxIn, TxOut


def p2tr_script(priv: int) -> bytes:
    P = point_mul(priv, GENERATOR)
    return b"\x51\x20" + P.x.to_bytes(32, "big")


def make_taproot_spend(
    privs,
    hashtypes=None,
    annexes=None,
    n_outputs: int = 2,
    sign_annex: bool = True,
):
    """A tx spending one P2TR prevout per priv; returns
    (tx, prevout_amounts, prevout_scripts)."""
    n = len(privs)
    hashtypes = hashtypes or [0x00] * n
    annexes = annexes or [None] * n
    inputs = tuple(
        TxIn(OutPoint(bytes([i + 1]) * 32, i), b"", 0xFFFFFFFE)
        for i in range(n)
    )
    outputs = tuple(
        TxOut(50_000 + i, b"\x00\x14" + bytes([i]) * 20)
        for i in range(n_outputs)
    )
    tx = Tx(2, inputs, outputs, 0, witnesses=tuple(() for _ in range(n)))
    amounts = {i: 100_000 + i for i in range(n)}
    scripts = {i: p2tr_script(privs[i]) for i in range(n)}
    wits = []
    for i, priv in enumerate(privs):
        digest = bip341_sighash(
            tx,
            i,
            [amounts[j] for j in range(n)],
            [scripts[j] for j in range(n)],
            hashtypes[i],
            annexes[i] if sign_annex else None,
        )
        assert digest is not None
        r, s = sign_bip340(priv, digest, nonce=0xA0_0000 + i)
        sig = r.to_bytes(32, "big") + s.to_bytes(32, "big")
        if hashtypes[i] != 0x00:
            sig += bytes([hashtypes[i]])
        stack = [sig]
        if annexes[i] is not None:
            stack.append(annexes[i])
        wits.append(tuple(stack))
    return dataclasses.replace(tx, witnesses=tuple(wits)), amounts, scripts


def run_extract(tx, amounts, scripts):
    items, stats = extract_sig_items(
        tx, prevout_amounts=amounts, prevout_scripts=scripts
    )
    verdicts = verify_batch_cpu([i.verify_item for i in items])
    return items, stats, combine_verdicts(items, verdicts)


def test_keypath_default_sighash_extracts_and_verifies():
    tx, amounts, scripts = make_taproot_spend([101, 202, 303])
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 3 and stats.unsupported == 0
    assert [i.algo for i in items] == ["bip340"] * 3
    assert per_sig == [True, True, True]


@pytest.mark.parametrize("hashtype", [0x01, 0x02, 0x03, 0x81, 0x82, 0x83])
def test_keypath_explicit_hashtypes_verify(hashtype):
    tx, amounts, scripts = make_taproot_spend([7], hashtypes=[hashtype])
    _, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 1
    assert per_sig == [True]


def test_hashtype_changes_digest():
    """Signing with one hash_type and presenting another must fail."""
    tx, amounts, scripts = make_taproot_spend([7], hashtypes=[0x01])
    sig = tx.witnesses[0][0][:64] + bytes([0x02])
    tx = dataclasses.replace(tx, witnesses=((sig,),))
    _, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 1
    assert per_sig == [False]


def test_annex_is_committed_to():
    annex = b"\x50annex-bytes"
    tx, amounts, scripts = make_taproot_spend([9], annexes=[annex])
    _, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 1 and per_sig == [True]
    # a signature that did NOT commit to the annex must fail
    tx2, amounts2, scripts2 = make_taproot_spend(
        [9], annexes=[annex], sign_annex=False
    )
    _, _, per_sig2 = run_extract(tx2, amounts2, scripts2)
    assert per_sig2 == [False]


def test_sixty_five_byte_sig_with_zero_hashtype_is_invalid():
    tx, amounts, scripts = make_taproot_spend([11])
    sig = tx.witnesses[0][0] + b"\x00"  # 65 bytes, explicit 0x00
    tx = dataclasses.replace(tx, witnesses=((sig,),))
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 1  # invalid spend, not unsupported
    assert items[0].pubkey is None  # auto-invalid item
    assert per_sig == [False]


def test_invalid_hashtype_and_bad_sig_length_are_invalid():
    tx, amounts, scripts = make_taproot_spend([12])
    for wit in (
        (tx.witnesses[0][0][:64] + b"\x04",),  # hash_type 0x04: invalid
        (tx.witnesses[0][0][:63],),  # 63 bytes: invalid
        (b"",),  # empty: invalid
    ):
        t2 = dataclasses.replace(tx, witnesses=(wit,))
        items, stats, per_sig = run_extract(t2, amounts, scripts)
        assert stats.extracted == 1 and items[0].pubkey is None
        assert per_sig == [False]


def test_single_without_matching_output_is_invalid():
    # input 2 with SIGHASH_SINGLE but only 2 outputs: BIP341 invalid
    # (sign with ALL first; the witness is then rewritten to SINGLE)
    tx, amounts, scripts = make_taproot_spend(
        [1, 2, 3], hashtypes=[0x01, 0x01, 0x01], n_outputs=2
    )
    sig2 = tx.witnesses[2][0][:64] + bytes([0x03])
    tx = dataclasses.replace(
        tx, witnesses=(tx.witnesses[0], tx.witnesses[1], (sig2,))
    )
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 3
    assert per_sig[0] and per_sig[1] and not per_sig[2]
    assert bip341_sighash(
        tx, 2, [0] * 3, [b""] * 3, 0x03
    ) is None


def test_off_curve_output_key_is_invalid():
    tx, amounts, scripts = make_taproot_spend([13])
    # x = 5 is not on the curve (5^3 + 7 is a non-residue)
    scripts[0] = b"\x51\x20" + (5).to_bytes(32, "big")
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 1 and items[0].pubkey is None
    assert per_sig == [False]


def test_script_path_and_missing_prevouts_are_unsupported():
    tx, amounts, scripts = make_taproot_spend([14])
    # script path: [stack-elem, tapscript, control-block]
    t2 = dataclasses.replace(
        tx, witnesses=((b"\x01", b"\x51", b"\xc0" + b"\x02" * 32),)
    )
    _, stats, _ = run_extract(t2, amounts, scripts)
    assert stats.unsupported == 1 and stats.extracted == 0
    # missing any input's prevout info -> unsupported (digest uncomputable)
    items, stats = extract_sig_items(
        tx, prevout_amounts=None, prevout_scripts=scripts
    )
    assert stats.unsupported == 1 and not items
    items, stats = extract_sig_items(
        tx, prevout_amounts=amounts, prevout_scripts=None
    )
    # without the prevout script the input isn't even recognized as P2TR
    assert stats.unsupported == 1 and not items


def test_anyonecanpay_needs_only_own_prevout():
    tx, amounts, scripts = make_taproot_spend([21, 22], hashtypes=[0x81, 0x81])
    # drop input 1's prevout info: input 0 (ACP) still extracts
    del amounts[1]
    del scripts[1]
    items, stats = extract_sig_items(
        tx, prevout_amounts=amounts, prevout_scripts=scripts
    )
    assert stats.extracted == 1 and stats.unsupported == 1
    verdicts = verify_batch_cpu([i.verify_item for i in items])
    assert combine_verdicts(items, verdicts) == [True]


def test_corrupted_signature_fails():
    tx, amounts, scripts = make_taproot_spend([31])
    sig = bytearray(tx.witnesses[0][0])
    sig[10] ^= 1
    tx = dataclasses.replace(tx, witnesses=((bytes(sig),),))
    _, stats, per_sig = run_extract(tx, amounts, scripts)
    assert per_sig == [False]


def test_mixed_tx_taproot_plus_p2wpkh():
    """Taproot and v0 inputs coexist; the v0 input still extracts with
    amounts alone, the taproot input needs the full prevout set."""
    from benchmarks.txgen import gen_mixed_txs  # noqa: F401 (mix sanity)
    from tpunode.verify.ecdsa_cpu import sign as ecdsa_sign

    priv_t, priv_w = 41, 42
    Pw = point_mul(priv_w, GENERATOR)
    wpub = (b"\x02" if Pw.y % 2 == 0 else b"\x03") + Pw.x.to_bytes(32, "big")
    import hashlib

    wh160 = hashlib.new(
        "ripemd160", hashlib.sha256(wpub).digest()
    ).digest()
    inputs = (
        TxIn(OutPoint(b"\x01" * 32, 0), b"", 0xFFFFFFFF),
        TxIn(OutPoint(b"\x02" * 32, 1), b"", 0xFFFFFFFF),
    )
    outputs = (TxOut(1000, b"\x00\x14" + b"\x07" * 20),)
    tx = Tx(2, inputs, outputs, 0, witnesses=((), ()))
    amounts = {0: 5000, 1: 7000}
    scripts = {0: p2tr_script(priv_t), 1: b"\x00\x14" + wh160}
    # sign taproot input 0
    digest = bip341_sighash(
        tx, 0, [amounts[0], amounts[1]], [scripts[0], scripts[1]], 0x00
    )
    r, s = sign_bip340(priv_t, digest, nonce=0xBEEF)
    wit0 = (r.to_bytes(32, "big") + s.to_bytes(32, "big"),)
    # sign P2WPKH input 1 (BIP143)
    from tpunode.sighash import bip143_sighash

    sc = b"\x76\xa9\x14" + wh160 + b"\x88\xac"
    z = bip143_sighash(tx, 1, sc, amounts[1], 0x01)
    r1, s1 = ecdsa_sign(priv_w, z, 0xD00D)
    from benchmarks.txgen import _der

    der = _der(r1, s1) + b"\x01"
    tx = dataclasses.replace(tx, witnesses=(wit0, (der, wpub)))
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 2
    assert sorted(i.algo for i in items) == ["bip340", "ecdsa"]
    assert per_sig == [True, True]


def test_bip341_digest_independence_properties():
    """Spec properties of the BIP341 message, checked structurally:
    ANYONECANPAY digests ignore sibling inputs; NONE ignores outputs;
    SINGLE commits only to the matching output; DEFAULT != ALL (the
    hash_type byte itself is committed); the annex always changes the
    digest; the BIP342 leaf extension always changes the digest."""
    import dataclasses as _dc

    tx, amounts, scripts = make_taproot_spend([81, 82], n_outputs=3)
    am = [amounts[i] for i in range(2)]
    sc = [scripts[i] for i in range(2)]

    def d(t, i, ht, annex=None, leaf=None):
        out = bip341_sighash(t, i, am, sc, ht, annex, leaf)
        # equality-only properties must never pass vacuously as None==None
        assert out is not None, hex(ht)
        return out

    # ACP: replacing the OTHER input leaves input 0's digest unchanged...
    tx2 = _dc.replace(
        tx,
        inputs=(tx.inputs[0],
                TxIn(OutPoint(b"\x99" * 32, 7), b"", 0x11111111)),
    )
    assert d(tx, 0, 0x81) == d(tx2, 0, 0x81)
    # ...while the non-ACP digest changes (prevouts/sequences committed)
    assert d(tx, 0, 0x01) != d(tx2, 0, 0x01)

    # NONE: outputs don't matter; ALL: they do
    tx3 = _dc.replace(tx, outputs=(TxOut(1, b"\x51"),))
    assert d(tx, 0, 0x02) == d(tx3, 0, 0x02)
    assert d(tx, 0, 0x01) != d(tx3, 0, 0x01)

    # SINGLE: only the matching output is committed
    other_out = _dc.replace(
        tx, outputs=(tx.outputs[0], TxOut(9, b"\x52"), tx.outputs[2])
    )
    assert d(tx, 0, 0x03) == d(other_out, 0, 0x03)  # output 1 changed
    own_out = _dc.replace(
        tx, outputs=(TxOut(9, b"\x52"),) + tx.outputs[1:]
    )
    assert d(tx, 0, 0x03) != d(own_out, 0, 0x03)  # output 0 changed

    # DEFAULT (0x00) and ALL (0x01) share semantics but differ as digests
    assert d(tx, 0, 0x00) != d(tx, 0, 0x01)
    # annex and leaf extension are committed
    assert d(tx, 0, 0x00) != d(tx, 0, 0x00, annex=b"\x50")
    leaf = b"\x01" * 32
    assert d(tx, 0, 0x00) != d(tx, 0, 0x00, leaf=leaf)
    assert d(tx, 0, 0x00, leaf=leaf) != d(tx, 0, 0x00, leaf=b"\x02" * 32)
    # amounts and scripts of EVERY input are committed (non-ACP)
    assert d(tx, 0, 0x00) != bip341_sighash(
        tx, 0, [am[0], am[1] + 1], sc, 0x00
    )
    assert d(tx, 0, 0x00) != bip341_sighash(
        tx, 0, am, [sc[0], b"\x51\x20" + b"\x03" * 32], 0x00
    )


def test_is_p2tr_and_hashtype_validity():
    assert is_p2tr(b"\x51\x20" + b"\x01" * 32)
    assert not is_p2tr(b"\x51\x21" + b"\x01" * 33)
    assert not is_p2tr(b"\x00\x20" + b"\x01" * 32)
    assert not is_p2tr(b"\x52\x20" + b"\x01" * 32)
    assert valid_taproot_hashtype(0x00)
    for ht in (0x04, 0x40, 0x80, 0x41, 0xFF):
        assert not valid_taproot_hashtype(ht)


def test_intra_block_prevouts_carries_scripts():
    tx, amounts, scripts = make_taproot_spend([51])
    outs = intra_block_prevouts([tx])
    assert outs[(tx.txid, 0)] == (50_000, b"\x00\x14" + b"\x00" * 20)


def test_native_parity_on_taproot_spends():
    """The C++ extractor's taproot lane is item-for-item identical to the
    Python reference (challenge, lifted key, r/s, present=3)."""
    import pytest as _pytest

    txextract = _pytest.importorskip("tpunode.txextract")
    if not txextract.have_native_extract():  # pragma: no cover
        _pytest.skip("native txextract unavailable")
    tx, amounts, scripts = make_taproot_spend(
        [101, 202, 303], hashtypes=[0x00, 0x81, 0x03], n_outputs=3
    )
    ext_amounts = [amounts[i] for i in range(3)]
    ext_scripts = [scripts[i] for i in range(3)]
    out = txextract.extract_raw(
        tx.serialize(), 1, ext_amounts=ext_amounts, ext_scripts=ext_scripts
    )
    assert out.present.tolist() == [3, 3, 3]
    py_items, _ = extract_sig_items(
        tx, prevout_amounts=amounts, prevout_scripts=scripts
    )
    for ni, pi in zip(out.to_verify_items(), py_items):
        assert ni == pi.verify_item
    assert verify_batch_cpu(out.to_verify_items()) == [True] * 3


def test_native_parity_on_invalid_and_annex_shapes():
    """Auto-invalid taproot shapes and annex-bearing witnesses agree
    between the two extractors."""
    import dataclasses as _dc

    import pytest as _pytest

    txextract = _pytest.importorskip("tpunode.txextract")
    if not txextract.have_native_extract():  # pragma: no cover
        _pytest.skip("native txextract unavailable")
    annex = b"\x50\x01\x02"
    base, amounts, scripts = make_taproot_spend([61], annexes=[annex])
    variants = [
        base,  # annex, valid
        _dc.replace(base, witnesses=((base.witnesses[0][0] + b"\x00",),)),
        _dc.replace(base, witnesses=((b"\xab" * 63,),)),
        _dc.replace(base, witnesses=((b"\x01", b"\x51", b"\xc0" + b"\x02" * 32),)),
    ]
    for tx in variants:
        py_items, py_st = extract_sig_items(
            tx, prevout_amounts=amounts, prevout_scripts=scripts
        )
        out = txextract.extract_raw(
            tx.serialize(), 1, ext_amounts=[amounts[0]],
            ext_scripts=[scripts[0]],
        )
        assert out.count == len(py_items)
        st = out.stats(0)
        assert (st.extracted, st.unsupported) == (
            py_st.extracted, py_st.unsupported
        )
        assert verify_batch_cpu(out.to_verify_items()) == verify_batch_cpu(
            [i.verify_item for i in py_items]
        )


def make_scriptpath_spend(leaf_privs, annexes=None, out_priv: int = 999):
    """A tx spending P2TR prevouts via the canonical single-key tapscript
    (script path, BIP342); returns (tx, amounts, scripts, leaf_scripts)."""
    import dataclasses as _dc

    from tpunode.sighash import tapleaf_hash

    n = len(leaf_privs)
    annexes = annexes or [None] * n
    inputs = tuple(
        TxIn(OutPoint(bytes([0x30 + i]) * 32, i), b"", 0xFFFFFFFE)
        for i in range(n)
    )
    outputs = (TxOut(70_000, b"\x00\x14" + b"\x09" * 20),)
    tx = Tx(2, inputs, outputs, 0, witnesses=tuple(() for _ in range(n)))
    amounts = {i: 200_000 + i for i in range(n)}
    scripts = {i: p2tr_script(out_priv) for i in range(n)}
    leaf_scripts = []
    wits = []
    for i, lp in enumerate(leaf_privs):
        LP = point_mul(lp, GENERATOR)
        leaf = b"\x20" + LP.x.to_bytes(32, "big") + b"\xac"
        leaf_scripts.append(leaf)
        control = b"\xc0" + scripts[i][2:34] + b"\x11" * 32  # one path node
        digest = bip341_sighash(
            tx, i,
            [amounts[j] for j in range(n)],
            [scripts[j] for j in range(n)],
            0x00, annexes[i], tapleaf_hash(leaf),
        )
        from tpunode.verify.ecdsa_cpu import sign_bip340 as _sign

        r, s = _sign(lp, digest, nonce=0x5C0 + i)
        stack = [r.to_bytes(32, "big") + s.to_bytes(32, "big"), leaf, control]
        if annexes[i] is not None:
            stack.append(annexes[i])
        wits.append(tuple(stack))
    return _dc.replace(tx, witnesses=tuple(wits)), amounts, scripts, leaf_scripts


def test_scriptpath_single_key_tapscript_extracts_and_verifies():
    tx, amounts, scripts, leaves = make_scriptpath_spend([401, 402])
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 2 and stats.unsupported == 0
    assert [i.algo for i in items] == ["bip340", "bip340"]
    # items verify against the LEAF keys, not the output key
    for it, leaf in zip(items, leaves):
        assert it.pubkey.x == int.from_bytes(leaf[1:33], "big")
    assert per_sig == [True, True]


def test_scriptpath_commits_to_the_leaf():
    """A signature over the KEYPATH digest presented via the script path
    must fail: the BIP342 extension (tapleaf hash) changes the digest."""
    tx, amounts, scripts, leaves = make_scriptpath_spend([411])
    keypath_digest = bip341_sighash(
        tx, 0, [amounts[0]], [scripts[0]], 0x00
    )
    r, s = sign_bip340(411, keypath_digest, nonce=0x123)
    wit = (r.to_bytes(32, "big") + s.to_bytes(32, "big"),
           tx.witnesses[0][1], tx.witnesses[0][2])
    tx2 = dataclasses.replace(tx, witnesses=(wit,))
    _, stats, per_sig = run_extract(tx2, amounts, scripts)
    assert stats.extracted == 1 and per_sig == [False]


def test_scriptpath_with_annex_and_native_parity():
    import pytest as _pytest

    annex = b"\x50\xaa\xbb"
    tx, amounts, scripts, _ = make_scriptpath_spend(
        [421, 422], annexes=[annex, None]
    )
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 2 and per_sig == [True, True]
    txextract = _pytest.importorskip("tpunode.txextract")
    if txextract.have_native_extract():
        out = txextract.extract_raw(
            tx.serialize(), 1,
            ext_amounts=[amounts[0], amounts[1]],
            ext_scripts=[scripts[0], scripts[1]],
        )
        assert out.present.tolist() == [3, 3]
        for ni, pi in zip(out.to_verify_items(), items):
            assert ni == pi.verify_item
        assert verify_batch_cpu(out.to_verify_items()) == [True, True]


def test_scriptpath_rejects_noncanonical_shapes():
    """Non-single-key tapscripts and malformed control blocks are
    unsupported (not invalid): the engine doesn't run tapscript."""
    tx, amounts, scripts, _ = make_scriptpath_spend([431])
    sig, leaf, control = tx.witnesses[0]
    bad_shapes = [
        (sig, b"\x51", control),                      # script: OP_1
        (sig, leaf + b"\x00", control),               # 35-byte script
        (sig, leaf, control[:32]),                    # control too short
        (sig, leaf, control + b"\x00"),               # not 33+32k
        (sig, leaf, b"\xa0" + control[1:]),           # wrong leaf version
        (sig, b"x", leaf, control),                   # 4 elements
    ]
    for wit in bad_shapes:
        t2 = dataclasses.replace(tx, witnesses=(tuple(wit),))
        items, stats = extract_sig_items(
            t2, prevout_amounts=amounts, prevout_scripts=scripts
        )
        assert stats.unsupported == 1 and not items, wit[1][:8]


def test_native_cache_lanes_cannot_cross_poison():
    """A scriptSig "pubkey" blob of 0x01||X (attacker-controlled, fails
    SEC1 decode) must not poison the taproot lift of the on-curve x-only
    key X — and vice versa.  Review r5 finding: an in-band namespace tag
    in a shared cache was forgeable; the caches are now separate objects."""
    import pytest as _pytest

    from benchmarks.txgen import _der
    from tpunode.verify.ecdsa_cpu import sign as ecdsa_sign

    txextract = _pytest.importorskip("tpunode.txextract")
    if not txextract.have_native_extract():  # pragma: no cover
        _pytest.skip("native txextract unavailable")
    priv = 505
    X = point_mul(priv, GENERATOR).x
    fake_pub = b"\x01" + X.to_bytes(32, "big")  # P2PKH-shaped, undecodable
    r0, s0 = ecdsa_sign(7, 0x1234, 0x777)
    sig0 = _der(r0, s0) + b"\x01"
    script_sig = bytes([len(sig0)]) + sig0 + bytes([len(fake_pub)]) + fake_pub
    inputs = (
        TxIn(OutPoint(b"\x41" * 32, 0), script_sig, 0xFFFFFFFF),
        TxIn(OutPoint(b"\x42" * 32, 1), b"", 0xFFFFFFFF),
    )
    outputs = (TxOut(10, b"\x51"),)
    tx = Tx(2, inputs, outputs, 0, witnesses=((), ()))
    amounts = {0: 1000, 1: 2000}
    scripts = {0: b"\x51", 1: b"\x51\x20" + X.to_bytes(32, "big")}
    digest = bip341_sighash(
        tx, 1, [amounts[0], amounts[1]], [scripts[0], scripts[1]], 0x00
    )
    r, s = sign_bip340(priv, digest, nonce=0x505)
    tx = dataclasses.replace(
        tx, witnesses=((), (r.to_bytes(32, "big") + s.to_bytes(32, "big"),))
    )
    py_items, _ = extract_sig_items(
        tx, prevout_amounts=amounts, prevout_scripts=scripts
    )
    py_verdicts = verify_batch_cpu([i.verify_item for i in py_items])
    assert py_verdicts == [False, True]  # fake pub auto-invalid; taproot OK
    out = txextract.extract_raw(
        tx.serialize(), 1,
        ext_amounts=[amounts[0], amounts[1]],
        ext_scripts=[scripts[0], scripts[1]],
    )
    assert out.present.tolist() == [0, 3]
    assert verify_batch_cpu(out.to_verify_items()) == [False, True]


def test_mixed_legacy_plus_taproot_inputs_extract():
    """A tx with BOTH a taproot keypath input and a legacy no-witness
    P2PKH input: the BIP341 digest needs the LEGACY sibling's prevout
    too, so the wants gate must be tx-level (review r5 finding — the
    per-input gate silently downgraded this common mainnet shape)."""
    from benchmarks.txgen import _der
    from tpunode.sighash import legacy_sighash
    from tpunode.txverify import _p2pkh_script_code, wants_amount
    from tpunode.verify.ecdsa_cpu import sign as ecdsa_sign

    priv_t, priv_l = 71, 72
    Pl = point_mul(priv_l, GENERATOR)
    lblob = (b"\x02" if Pl.y % 2 == 0 else b"\x03") + Pl.x.to_bytes(32, "big")
    inputs = (
        TxIn(OutPoint(b"\x0a" * 32, 0), b"", 0xFFFFFFFF),
        TxIn(OutPoint(b"\x0b" * 32, 1), b"", 0xFFFFFFFF),
    )
    outputs = (TxOut(900, b"\x00\x14" + b"\x05" * 20),)
    tx = Tx(2, inputs, outputs, 0, witnesses=((), ()))
    amounts = {0: 4000, 1: 6000}
    scripts = {0: p2tr_script(priv_t), 1: _p2pkh_script_code(lblob)}
    digest = bip341_sighash(
        tx, 0, [amounts[0], amounts[1]], [scripts[0], scripts[1]], 0x00
    )
    r, s = sign_bip340(priv_t, digest, nonce=0x71A)
    wit0 = (r.to_bytes(32, "big") + s.to_bytes(32, "big"),)
    sc = _p2pkh_script_code(lblob)
    z = legacy_sighash(tx, 1, sc, 0x01)
    r1, s1 = ecdsa_sign(priv_l, z, 0x72B)
    script_sig = (
        bytes([len(_der(r1, s1)) + 1]) + _der(r1, s1) + b"\x01"
        + bytes([len(lblob)]) + lblob
    )
    tx = Tx(
        2,
        (inputs[0], TxIn(inputs[1].prevout, script_sig, 0xFFFFFFFF)),
        outputs, 0, witnesses=(wit0, ()),
    )
    # the legacy input's prevout IS wanted (the signed tx has a witness)
    assert wants_amount(tx, 1, False)
    items, stats, per_sig = run_extract(tx, amounts, scripts)
    assert stats.extracted == 2 and stats.unsupported == 0
    assert sorted(i.algo for i in items) == ["bip340", "ecdsa"]
    assert per_sig == [True, True]
    # native parity on the same shape
    import pytest as _pytest

    txextract = _pytest.importorskip("tpunode.txextract")
    if txextract.have_native_extract():
        out = txextract.extract_raw(
            tx.serialize(), 1,
            ext_amounts=[amounts[0], amounts[1]],
            ext_scripts=[scripts[0], scripts[1]],
        )
        assert sorted(out.present.tolist()) == [1, 3]
        assert verify_batch_cpu(out.to_verify_items()) == [True, True]


@pytest.mark.asyncio
async def test_node_end_to_end_taproot_mempool():
    """A taproot keypath tx through the FULL node (BTC regtest): wire
    decode -> lazy ingest -> native batch extract with the extended
    (amount, script) oracle -> engine -> TxVerdict on the user bus."""
    import asyncio

    from benchmarks.txgen import gen_mixed_txs, synth_prevout
    from tests.fakenet import dummy_peer_connect
    from tests.fixtures import all_blocks
    from tpunode import PeerConnected, txextract
    from tpunode.actors import Publisher
    from tpunode.node import Node, NodeConfig, TxVerdict
    from tpunode.params import BTC_REGTEST
    from tpunode.peer import PeerMessage
    from tpunode.store import MemoryKV
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import MsgTx

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")
    txs = gen_mixed_txs(6, seed=0x7A12, mix=[(1.01, "p2tr")])
    msgs = [MsgTx.deserialize_payload(Reader(t.serialize())) for t in txs]
    pub = Publisher(name="tap-node")
    cfg = NodeConfig(
        net=BTC_REGTEST,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:18444"],
        connect=lambda sa: dummy_peer_connect(BTC_REGTEST, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
        prevout_lookup=synth_prevout,
    )
    got = {}
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(20):
                peer = await events.receive_match(
                    lambda ev: ev.peer if isinstance(ev, PeerConnected) else None
                )
                for m in msgs:
                    node._peer_pub.publish(PeerMessage(peer, m))
                while len(got) < len(txs):
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        got[ev.txid] = ev
    for tx in txs:
        ev = got[tx.txid]
        assert ev.error is None
        assert ev.valid and len(ev.verdicts) == len(tx.inputs)
        assert ev.stats.extracted == len(tx.inputs)


@pytest.mark.asyncio
@pytest.mark.parametrize("held_to", ["native", "reference"])
async def test_node_block_ingest_intra_block_taproot_spend(
    held_to, monkeypatch
):
    """A block where tx A creates a P2TR output and tx B key-spends it:
    the spend's (amount, script) resolve from the INTRA-BLOCK map (the
    C++ out_script lane — no oracle involved), through the full node's
    lazy-block ingest on BTC regtest.  ``reference``: the node's verdicts
    are the Python reference's (``txverify.intra_block_prevouts`` dict,
    ``tests/fixtures.py``), field by field."""
    import asyncio

    import tpunode.node as node_mod
    from tests.fixtures import reference_verdicts, tuples
    from tpunode import txextract

    if not txextract.have_native_extract():
        pytest.skip("native extractor unavailable")
    # the one ingest path is the lane that ran
    lane_calls = {"native": 0}
    orig_native = node_mod.Node._verify_txs_native

    def counting_native(self, *a, **k):
        lane_calls["native"] += 1
        return orig_native(self, *a, **k)

    monkeypatch.setattr(node_mod.Node, "_verify_txs_native", counting_native)
    from tests.fakenet import dummy_peer_connect
    from tests.fixtures import all_blocks
    from tpunode import PeerConnected
    from tpunode.actors import Publisher
    from tpunode.node import Node, NodeConfig, TxVerdict
    from tpunode.params import BTC_REGTEST
    from tpunode.peer import PeerMessage
    from tpunode.store import MemoryKV
    from tpunode.util import Reader
    from tpunode.verify.engine import VerifyConfig
    from tpunode.wire import Block, BlockHeader, MsgBlock

    priv_t = 602
    # tx A: funds a P2TR output for priv_t (inputs are unsupported shapes
    # — only its OUTPUT matters here)
    tx_a = Tx(
        2,
        (TxIn(OutPoint(b"\x61" * 32, 0), b"\x51", 0xFFFFFFFF),),
        (TxOut(123_456, p2tr_script(priv_t)),
         TxOut(5_000, b"\x00\x14" + b"\x01" * 20)),
        0,
    )
    # tx B: key-spends tx A's output 0 (same block)
    inputs = (TxIn(OutPoint(tx_a.txid, 0), b"", 0xFFFFFFFF),)
    outputs = (TxOut(100_000, b"\x00\x14" + b"\x02" * 20),)
    tx_b = Tx(2, inputs, outputs, 0, witnesses=((),))
    digest = bip341_sighash(
        tx_b, 0, [123_456], [p2tr_script(priv_t)], 0x00
    )
    r, s = sign_bip340(priv_t, digest, nonce=0x601)
    tx_b = dataclasses.replace(
        tx_b, witnesses=((r.to_bytes(32, "big") + s.to_bytes(32, "big"),),)
    )
    hdr = BlockHeader(1, b"\x00" * 32, b"\x00" * 32, 0, 0x207FFFFF, 0)
    raw_block = Block(hdr, (tx_a, tx_b)).serialize()
    msg = MsgBlock.deserialize_payload(Reader(raw_block))

    pub = Publisher(name="tap-block")
    cfg = NodeConfig(
        net=BTC_REGTEST,
        store=MemoryKV(),
        pub=pub,
        peers=["[::1]:18444"],
        connect=lambda sa: dummy_peer_connect(BTC_REGTEST, all_blocks()),
        verify=VerifyConfig(backend="cpu", max_wait=0.0),
        # NO oracle: everything must come from the intra-block map
        prevout_lookup=None,
    )
    got = {}
    async with pub.subscription() as events:
        async with Node(cfg) as node:
            async with asyncio.timeout(20):
                peer = await events.receive_match(
                    lambda ev: ev.peer if isinstance(ev, PeerConnected) else None
                )
                node._peer_pub.publish(PeerMessage(peer, msg))
                while len(got) < 2:
                    ev = await events.receive()
                    if isinstance(ev, TxVerdict):
                        got[ev.txid] = ev
    ev_b = got[tx_b.txid]
    assert ev_b.error is None and ev_b.valid
    assert len(ev_b.verdicts) == 1 and ev_b.stats.extracted == 1
    # tx A's garbage input is unsupported, not a failure
    assert got[tx_a.txid].stats.unsupported == 1
    assert lane_calls["native"] == 1
    if held_to == "reference":
        assert tuples([got[tx_a.txid], ev_b]) == reference_verdicts(
            [tx_a, tx_b], None, bch=False)


def test_taproot_heavy_mix_coverage():
    """Coverage >= 0.95 on a taproot-dominated mix with the extended
    oracle (VERDICT r4 item 3 acceptance), through the NATIVE path with
    the synthetic oracle — the production configuration."""
    import pytest as _pytest

    from benchmarks.txgen import (
        _MIX_TAPROOT_HEAVY,
        gen_mixed_txs,
        synth_prevout,
    )
    from tpunode.txverify import wants_amount

    txextract = _pytest.importorskip("tpunode.txextract")
    if not txextract.have_native_extract():  # pragma: no cover
        _pytest.skip("native txextract unavailable")
    txs = gen_mixed_txs(48, seed=0x7A9, mix=_MIX_TAPROOT_HEAVY)
    data = b"".join(t.serialize() for t in txs)
    with txextract.ParsedTxRegion(data, len(txs)) as region:
        pt, _, pv, pw = region.scan_outpoints(False)
        ext = [-1] * len(pw)
        scr: list = [None] * len(pw)
        for i in pw.nonzero()[0]:
            ext[int(i)], scr[int(i)] = synth_prevout(
                pt[i].tobytes(), int(pv[i])
            )
        out = region.extract(ext_amounts=ext, ext_scripts=scr)
    total = int(out.tx_n_inputs.sum()) - int(out.tx_coinbase.sum())
    extracted = int(out.tx_extracted.sum())
    coverage = extracted / total
    assert coverage >= 0.95, f"taproot-heavy coverage {coverage:.3f}"
    # every signature in the (uncorrupted) mix verifies
    per_sig = out.combine(verify_batch_cpu(out.to_verify_items()))
    assert all(per_sig)
    # the mix genuinely is taproot-heavy
    assert (out.present == 3).sum() > out.count * 0.5
    # python path agrees input-for-input
    py_extracted = 0
    py_total = 0
    for tx in txs:
        amounts = {}
        scripts = {}
        for idx, ti in enumerate(tx.inputs):
            if wants_amount(tx, idx, False):
                amounts[idx], scripts[idx] = synth_prevout(
                    ti.prevout.txid, ti.prevout.index
                )
        _, st = extract_sig_items(
            tx, prevout_amounts=amounts, prevout_scripts=scripts
        )
        py_extracted += st.extracted
        py_total += st.total_inputs - st.coinbase
    assert (py_extracted, py_total) == (extracted, total)
