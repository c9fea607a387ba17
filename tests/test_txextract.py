"""Parity tests: native txextract vs the pure-Python extract path.

The native extractor (native/txextract/txextract.cpp) must be a bit-exact
mirror of txverify.extract_sig_items + sighash.py + ecdsa_cpu's DER/pubkey
parsing — same items (z, r, s, decoded pubkey, present flag), same per-tx
stats, same txids, on every workload shape.  These tests drive both paths
over generated and hand-crafted transactions and compare everything.
"""

from __future__ import annotations

import random

import numpy as np

import pytest

from benchmarks.txgen import gen_signed_txs
from tpunode.sighash import SIGHASH_ANYONECANPAY, SIGHASH_NONE, SIGHASH_SINGLE
from tpunode.txverify import extract_sig_items, intra_block_amounts
from tpunode.verify.ecdsa_cpu import CURVE_N, GENERATOR, point_mul, sign
from tpunode.wire import OutPoint, Tx, TxIn, TxOut

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():  # pragma: no cover
    pytest.skip("native txextract unavailable", allow_module_level=True)

from tpunode.txextract import extract_raw  # noqa: E402


def _python_reference(txs, bch=False, lookup=None):
    """The Python reference extraction in the node's precedence:
    intra-block amounts first, then the embedder lookup."""
    block_outs = intra_block_amounts(txs) if len(txs) > 1 else {}
    all_items, all_stats = [], []
    for tx in txs:
        amounts = {}
        for idx, txin in enumerate(tx.inputs):
            key = (txin.prevout.txid, txin.prevout.index)
            amt = block_outs.get(key)
            if amt is None and lookup is not None:
                amt = lookup(*key)
            if amt is not None:
                amounts[idx] = amt
        items, stats = extract_sig_items(tx, prevout_amounts=amounts or None, bch=bch)
        all_items.extend(items)
        all_stats.append(stats)
    return all_items, all_stats


def _serialize_all(txs) -> bytes:
    return b"".join(tx.serialize() for tx in txs)


def _assert_parity(txs, bch=False, ext_amounts=None, lookup=None):
    raw = extract_raw(
        _serialize_all(txs), len(txs), bch=bch,
        intra_amounts=len(txs) > 1, ext_amounts=ext_amounts,
    )
    py_items, py_stats = _python_reference(txs, bch=bch, lookup=lookup)
    assert raw.count == len(py_items)
    native_items = raw.to_verify_items()
    for i, ((q_n, z_n, r_n, s_n), it) in enumerate(zip(native_items, py_items)):
        assert z_n == it.z % CURVE_N, f"item {i} digest"
        # oversized (>2^256) r/s come out as 0 natively: same verdict class
        assert r_n == (it.r if it.r < 2**256 else 0), f"item {i} r"
        assert s_n == (it.s if it.s < 2**256 else 0), f"item {i} s"
        if it.pubkey is None:
            assert q_n is None, f"item {i} pubkey should be undecodable"
        else:
            assert q_n is not None and (q_n.x, q_n.y) == (it.pubkey.x, it.pubkey.y)
        assert raw.item_tx[i] >= 0
        tx = txs[raw.item_tx[i]]
        assert it.txid == tx.txid
        assert it.input_index == raw.item_input[i]
    for ti, (tx, st) in enumerate(zip(txs, py_stats)):
        assert raw.txid(ti) == tx.txid, f"tx {ti} txid"
        got = raw.stats(ti)
        assert (got.total_inputs, got.extracted, got.coinbase, got.unsupported) == (
            st.total_inputs, st.extracted, st.coinbase, st.unsupported
        ), f"tx {ti} stats"
    return raw


def test_legacy_p2pkh_parity():
    _assert_parity(gen_signed_txs(40, inputs_per_tx=2, seed=1))


def test_segwit_mix_parity():
    txs = gen_signed_txs(60, inputs_per_tx=2, seed=2, segwit_every=3)
    _assert_parity(txs)


def test_invalid_mix_parity():
    txs = gen_signed_txs(50, inputs_per_tx=3, seed=3, invalid_every=4, segwit_every=5)
    _assert_parity(txs)


def test_bch_forkid_parity():
    """On a FORKID network legacy templates take the BIP143-style digest and
    need amounts; in-block spends resolve, external ones don't."""
    rng = random.Random(7)
    priv = rng.getrandbits(256) % CURVE_N or 1
    pub = point_mul(priv, GENERATOR)
    blob = bytes([2 + (pub.y & 1)]) + pub.x.to_bytes(32, "big")
    from benchmarks.txgen import _der, _p2pkh_script_code

    script = _p2pkh_script_code(blob)
    funding = Tx(
        1,
        (TxIn(OutPoint(rng.randbytes(32), 0), bytes([1, 0x51]) or b"", 0xFFFFFFFF),),
        (TxOut(77_000, script), TxOut(33_000, script)),
        0,
    )
    from tpunode.sighash import SIGHASH_FORKID, bip143_sighash

    hashtype = 0x41  # ALL | FORKID
    spend_inputs = (
        TxIn(OutPoint(funding.txid, 0), b"", 0xFFFFFFFF),
        TxIn(OutPoint(rng.randbytes(32), 1), b"", 0xFFFFFFFF),  # external: missing amount
    )
    unsigned = Tx(1, spend_inputs, (TxOut(50_000, script),), 0)
    signed = []
    for idx, amount in ((0, 77_000), (1, 12_345)):
        z = bip143_sighash(unsigned, idx, script, amount, hashtype)
        r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
        sig_blob = _der(r, s) + bytes([hashtype])
        signed.append(
            TxIn(
                spend_inputs[idx].prevout,
                bytes([len(sig_blob)]) + sig_blob + bytes([len(blob)]) + blob,
                0xFFFFFFFF,
            )
        )
    spend = Tx(1, tuple(signed), (TxOut(50_000, script),), 0)
    assert SIGHASH_FORKID & hashtype
    raw = _assert_parity([funding, spend], bch=True)
    # the in-block input extracted; the external one unsupported
    assert raw.stats(1).extracted == 1 and raw.stats(1).unsupported == 1


def test_ext_amounts_match_prevout_lookup():
    """ext_amounts (flattened per input) must mirror the Python path's
    embedder prevout_lookup channel for out-of-block P2WPKH spends."""
    rng = random.Random(11)
    priv = rng.getrandbits(256) % CURVE_N or 1
    pub = point_mul(priv, GENERATOR)
    blob = bytes([2 + (pub.y & 1)]) + pub.x.to_bytes(32, "big")
    from benchmarks.txgen import _der, _p2pkh_script_code
    from tpunode.sighash import bip143_sighash

    script = _p2pkh_script_code(blob)
    amount = 123_456
    prev_txid = rng.randbytes(32)
    inputs = (TxIn(OutPoint(prev_txid, 0), b"", 0xFFFFFFFF),)
    unsigned = Tx(2, inputs, (TxOut(99_000, script),), 0)
    z = bip143_sighash(unsigned, 0, script, amount, 0x01)
    r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
    sig_blob = _der(r, s) + b"\x01"
    tx = Tx(2, inputs, (TxOut(99_000, script),), 0, witnesses=((sig_blob, blob),))

    raw = extract_raw(tx.serialize(), 1, intra_amounts=False, ext_amounts=[amount])
    items = raw.to_verify_items()
    assert raw.count == 1

    def lookup(txid, idx):
        return amount if (txid, idx) == (prev_txid, 0) else None

    py_items, _ = _python_reference([tx], lookup=lookup)
    assert items[0][1] == py_items[0].z % CURVE_N
    # and with no amount at all, both sides say unsupported
    raw_none = extract_raw(tx.serialize(), 1, intra_amounts=False)
    assert raw_none.count == 0 and raw_none.stats(0).unsupported == 1


def test_hashtype_zoo_parity():
    """NONE / SINGLE (incl. the out-of-range z=1 quirk) / ANYONECANPAY
    combos through the legacy digest, all item-for-item identical."""
    rng = random.Random(13)
    priv = rng.getrandbits(256) % CURVE_N or 1
    pub = point_mul(priv, GENERATOR)
    blob = bytes([2 + (pub.y & 1)]) + pub.x.to_bytes(32, "big")
    from benchmarks.txgen import _der, _p2pkh_script_code
    from tpunode.sighash import legacy_sighash

    script = _p2pkh_script_code(blob)
    hashtypes = [
        0x01, SIGHASH_NONE, SIGHASH_SINGLE,
        0x01 | SIGHASH_ANYONECANPAY,
        SIGHASH_NONE | SIGHASH_ANYONECANPAY,
        SIGHASH_SINGLE | SIGHASH_ANYONECANPAY,
        0x00,  # base 0 behaves like ALL
    ]
    txs = []
    for ht in hashtypes:
        # 3 inputs, 2 outputs: input 2 with SIGHASH_SINGLE is out of range
        inputs = tuple(
            TxIn(OutPoint(rng.randbytes(32), i), b"", 0xFFFFFFF0 + i) for i in range(3)
        )
        outputs = (TxOut(10_000, script), TxOut(20_000, script))
        unsigned = Tx(1, inputs, outputs, 99)
        signed = []
        for i in range(3):
            z = legacy_sighash(unsigned, i, script, ht)
            r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
            sig_blob = _der(r, s) + bytes([ht])
            signed.append(
                TxIn(inputs[i].prevout,
                     bytes([len(sig_blob)]) + sig_blob + bytes([len(blob)]) + blob,
                     inputs[i].sequence)
            )
        txs.append(Tx(1, tuple(signed), outputs, 99))
    _assert_parity(txs)


def test_malformed_and_edge_inputs_parity():
    """Coinbase, non-push scripts, wrong push counts, bad pubkey lengths,
    undecodable pubkeys, short/garbage DER — stats and items must match."""
    rng = random.Random(17)
    garbage_pub_33 = b"\x02" + b"\xff" * 32  # x >= p: undecodable
    off_curve_33 = b"\x02" + (5).to_bytes(32, "big")  # x=5: non-residue y^2
    from benchmarks.txgen import _der, _p2pkh_script_code
    from tpunode.sighash import legacy_sighash

    priv = 0xDEADBEEF % CURVE_N
    pub = point_mul(priv, GENERATOR)
    blob = bytes([2 + (pub.y & 1)]) + pub.x.to_bytes(32, "big")
    script = _p2pkh_script_code(blob)

    def p2pkh_in(sig_blob: bytes, pub_blob: bytes, prevout=None):
        return TxIn(
            prevout or OutPoint(rng.randbytes(32), 0),
            bytes([len(sig_blob)]) + sig_blob + bytes([len(pub_blob)]) + pub_blob,
            0xFFFFFFFF,
        )

    cases = [
        # coinbase
        Tx(1, (TxIn(OutPoint(b"\x00" * 32, 0xFFFFFFFF), b"\x04abcd", 0),),
           (TxOut(50, b"\x51"),), 0),
        # non-push scriptSig (OP_DUP)
        Tx(1, (TxIn(OutPoint(rng.randbytes(32), 0), b"\x76\xa9", 0),),
           (TxOut(1, b""),), 0),
        # one push only
        Tx(1, (TxIn(OutPoint(rng.randbytes(32), 0), b"\x02\xab\xcd", 0),),
           (TxOut(1, b""),), 0),
        # pubkey-length not 33/65 => unsupported on the P2PKH path
        Tx(1, (p2pkh_in(b"\x30" * 10, b"\x02\x01"),), (TxOut(1, b""),), 0),
        # short sig blob (< 9 bytes)
        Tx(1, (p2pkh_in(b"\x30\x01\x02", blob),), (TxOut(1, b""),), 0),
        # garbage DER with valid-looking length
        Tx(1, (p2pkh_in(b"\x31" + b"\x00" * 20, blob),), (TxOut(1, b""),), 0),
        # undecodable pubkeys (right length): item with present=0
        Tx(1, (p2pkh_in(_mk_sig(priv, rng), garbage_pub_33),), (TxOut(1, b""),), 0),
        Tx(1, (p2pkh_in(_mk_sig(priv, rng), off_curve_33),), (TxOut(1, b""),), 0),
        # uncompressed pubkey, valid
        _uncompressed_case(priv, rng),
        # witness with non-2 item count => falls through, script empty => unsupported
        Tx(2, (TxIn(OutPoint(rng.randbytes(32), 0), b"", 0),), (TxOut(1, b""),), 0,
           witnesses=(((b"\x00" * 12),),)),
        # witness pubkey undecodable (any length allowed on witness path)
        Tx(2, (TxIn(OutPoint(rng.randbytes(32), 0), b"", 0),), (TxOut(1, b""),), 0,
           witnesses=((_mk_sig(priv, rng), b"\x09\x08"),)),
    ]
    for tx in cases:
        _assert_parity([tx])
    _assert_parity(cases)  # and all together as one "block"


def _mk_sig(priv: int, rng: random.Random) -> bytes:
    from benchmarks.txgen import _der

    r, s = sign(priv, 0x1234, rng.getrandbits(256) % CURVE_N or 1)
    return _der(r, s) + b"\x01"


def _uncompressed_case(priv: int, rng: random.Random) -> Tx:
    from benchmarks.txgen import _der, _p2pkh_script_code
    from tpunode.sighash import legacy_sighash

    pub = point_mul(priv, GENERATOR)
    blob65 = b"\x04" + pub.x.to_bytes(32, "big") + pub.y.to_bytes(32, "big")
    script = _p2pkh_script_code(blob65)
    inputs = (TxIn(OutPoint(rng.randbytes(32), 0), b"", 0xFFFFFFFF),)
    unsigned = Tx(1, inputs, (TxOut(5, b""),), 0)
    z = legacy_sighash(unsigned, 0, script, 0x01)
    r, s = sign(priv, z, rng.getrandbits(256) % CURVE_N or 1)
    sig_blob = _der(r, s) + b"\x01"
    return Tx(
        1,
        (TxIn(inputs[0].prevout,
              bytes([len(sig_blob)]) + sig_blob + bytes([len(blob65)]) + blob65,
              0xFFFFFFFF),),
        (TxOut(5, b""),),
        0,
    )


def test_verdicts_match_cpu_backend():
    """End to end: native-extracted raw arrays through the C++ verifier give
    the same verdicts as the Python extract + oracle."""
    from tpunode.verify.cpu_native import load_native_verifier
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    txs = gen_signed_txs(30, inputs_per_tx=2, seed=23, invalid_every=3, segwit_every=5)
    raw = extract_raw(_serialize_all(txs), len(txs))
    native_items = raw.to_verify_items()
    py_items, _ = _python_reference(txs)
    expected = verify_batch_cpu([i.verify_item for i in py_items])
    got_oracle = verify_batch_cpu(native_items)
    assert got_oracle == expected
    nv = load_native_verifier()
    if nv is not None:
        assert nv.verify_batch(native_items) == expected
    # the workload must actually exercise both verdicts
    assert True in expected and False in expected


def test_scan_reports_counts():
    txs = gen_signed_txs(12, inputs_per_tx=3, seed=29)
    data = _serialize_all(txs)
    from tpunode.txextract import load_txextract_lib
    import ctypes

    lib = load_txextract_lib()
    n_inputs = ctypes.c_long()
    assert lib.txx_scan(data, len(data), -1, ctypes.byref(n_inputs)) == 12
    assert n_inputs.value == 36


def test_malformed_data_raises():
    with pytest.raises(ValueError):
        extract_raw(b"\x01\x02\x03", 1)
    # claiming more txs than present
    txs = gen_signed_txs(2, seed=31)
    with pytest.raises(ValueError):
        extract_raw(_serialize_all(txs), 5)
    # huge claimed input count must fail fast, not allocate
    bad = (1).to_bytes(4, "little") + b"\xfe\x00\x00\x00\x01" + b"\x00" * 8
    with pytest.raises(ValueError):
        extract_raw(bad, 1)


# ---------------------------------------------------------------------------
# tx-range sharding (ISSUE 11): range extraction over the shared handle is
# bit-identical to the whole-region extract

def _merge_shards(shards):
    import numpy as np

    class _M:
        pass

    m = _M()
    for name in (
        "z", "px", "py", "r", "s", "present", "item_input", "item_sig",
        "item_key", "item_nsigs", "item_nkeys", "txids", "tx_n_inputs",
        "tx_extracted", "tx_items", "tx_sigs", "tx_coinbase",
        "tx_unsupported",
    ):
        setattr(m, name, np.concatenate([getattr(s, name) for s in shards]))
    m.count = sum(s.count for s in shards)
    return m


@pytest.mark.parametrize("cuts", [(0, 7, 40), (0, 1, 39), (0, 20)])
def test_extract_range_sharded_matches_serial(cuts):
    """Contiguous tx-range shards (shared intra map, range-local oracle
    rows) merge to EXACTLY the serial whole-region result — every item
    row, every per-tx stat."""
    import numpy as np

    from benchmarks.txgen import gen_mixed_txs, synth_prevout
    from tpunode.txextract import ParsedTxRegion

    txs = gen_mixed_txs(40, seed=0x5A5A)
    raw = _serialize_all(txs)
    with ParsedTxRegion(raw, len(txs)) as region:
        pv_txids, _, pv_vouts, pv_wants = region.scan_outpoints(False)
        ext = [-1] * len(pv_wants)
        scr = [None] * len(pv_wants)
        for i in pv_wants.nonzero()[0]:
            res = synth_prevout(pv_txids[i].tobytes(), int(pv_vouts[i]))
            if res is not None:
                ext[int(i)], scr[int(i)] = res
        serial = region.extract(
            intra_amounts=True, ext_amounts=ext, ext_scripts=scr
        )
        region.build_intra()
        off = region.input_offsets()
        bounds = list(cuts) + [len(txs)]
        shards = []
        for lo, hi in zip(bounds, bounds[1:]):
            fl, fh = int(off[lo]), int(off[hi])
            shards.append(region.extract_range(
                lo, hi, intra_amounts=True,
                ext_amounts=ext[fl:fh], ext_scripts=scr[fl:fh],
            ))
        merged = _merge_shards(shards)
        assert merged.count == serial.count
        for name in (
            "z", "px", "py", "r", "s", "present", "item_input",
            "item_sig", "item_key", "item_nsigs", "item_nkeys", "txids",
            "tx_n_inputs", "tx_extracted", "tx_items", "tx_sigs",
            "tx_coinbase", "tx_unsupported",
        ):
            assert np.array_equal(
                getattr(merged, name), getattr(serial, name)
            ), name
        # item_tx is range-relative: rebase and compare
        rebased = np.concatenate([
            s.item_tx + lo for s, lo in zip(shards, bounds)
        ])
        assert np.array_equal(rebased, serial.item_tx)


def test_extract_range_cross_shard_intra_spends():
    """An in-block spend whose funding tx lives in a DIFFERENT shard
    still resolves through the shared intra map — the whole point of
    building it once on the handle."""
    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import ParsedTxRegion

    # every 2nd tx is a P2WPKH spend of its predecessor's output 0
    txs = gen_signed_txs(8, inputs_per_tx=1, seed=0x17, segwit_every=2)
    raw = _serialize_all(txs)
    with ParsedTxRegion(raw, len(txs)) as region:
        serial = region.extract(intra_amounts=True)
        region.build_intra()
        # cut between a funding tx (index 4) and its segwit child (5)
        a = region.extract_range(0, 5, intra_amounts=True)
        b = region.extract_range(5, 8, intra_amounts=True)
        assert a.count + b.count == serial.count
        # the child extracted (not unsupported): its amount resolved
        # across the shard boundary
        assert int(b.tx_unsupported[0]) == int(serial.tx_unsupported[5])
        assert int(b.tx_extracted[0]) == int(serial.tx_extracted[5]) == 1


def test_extract_range_validates_bounds():
    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import ParsedTxRegion

    txs = gen_signed_txs(3, inputs_per_tx=1, seed=0x18)
    with ParsedTxRegion(_serialize_all(txs), 3) as region:
        with pytest.raises(ValueError):
            region.extract_range(2, 5)
        with pytest.raises(ValueError):
            region.extract_range(-1, 2)
        empty = region.extract_range(1, 1)
        assert empty.count == 0 and empty.n_txs == 0


# ---------------------------------------------------------------------------
# subset extraction (ISSUE 27): the txs of a block that no relay verdict
# answered, scattered through it, are extracted bit-identically to
# extract_range over the same txs, against the block's one intra map

_SHARD_ROWS = (
    "z", "px", "py", "r", "s", "present", "item_input", "item_sig",
    "item_key", "item_nsigs", "item_nkeys", "txids", "tx_n_inputs",
    "tx_extracted", "tx_items", "tx_sigs", "tx_coinbase", "tx_unsupported",
)


@pytest.mark.parametrize("subset", [
    (3,), (0, 39), (1, 2, 3), (5, 11, 17, 23, 38), tuple(range(0, 40, 2)),
    tuple(range(40)), (39, 7, 20),
], ids=["one", "ends", "run", "scattered", "every-other", "all", "unordered"])
def test_extract_subset_matches_extract_range(subset):
    """Each tx of the subset, row for row, equals the one-tx
    ``extract_range`` of the same tx (oracle rows and result rows are the
    subset's, in its order)."""
    import numpy as np

    from benchmarks.txgen import gen_mixed_txs, synth_prevout
    from tpunode.txextract import ParsedTxRegion

    txs = gen_mixed_txs(40, seed=0x5A5A)
    with ParsedTxRegion(_serialize_all(txs), len(txs)) as region:
        pv_txids, _, pv_vouts, pv_wants = region.scan_outpoints(False)
        ext = [-1] * len(pv_wants)
        scr = [None] * len(pv_wants)
        for i in pv_wants.nonzero()[0]:
            res = synth_prevout(pv_txids[i].tobytes(), int(pv_vouts[i]))
            if res is not None:
                ext[int(i)], scr[int(i)] = res
        region.build_intra()
        off = region.input_offsets()
        rows = [j for t in subset for j in range(int(off[t]), int(off[t + 1]))]
        got = region.extract_subset(
            subset, intra_amounts=True,
            ext_amounts=[ext[j] for j in rows],
            ext_scripts=[scr[j] for j in rows],
        )
        singles = [
            region.extract_range(
                t, t + 1, intra_amounts=True,
                ext_amounts=ext[int(off[t]):int(off[t + 1])],
                ext_scripts=scr[int(off[t]):int(off[t + 1])],
            )
            for t in subset
        ]
        want = _merge_shards(singles)
        assert got.n_txs == len(subset) and got.count == want.count
        for name in _SHARD_ROWS:
            assert np.array_equal(getattr(got, name), getattr(want, name)), name
        # item_tx is subset-relative: position k stands for subset[k]
        assert np.array_equal(got.item_tx, np.concatenate(
            [s.item_tx + k for k, s in enumerate(singles)]))
        assert [got.txid(k) for k in range(len(subset))] == [
            txs[t].txid for t in subset]


def test_extract_subset_resolves_in_block_spends_of_left_out_txs():
    """A subset tx spending a tx that is NOT in the subset still finds its
    amount: the intra map is the whole block's."""
    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import ParsedTxRegion

    txs = gen_signed_txs(8, inputs_per_tx=1, seed=0x17, segwit_every=2)
    with ParsedTxRegion(_serialize_all(txs), len(txs)) as region:
        serial = region.extract(intra_amounts=True)
    for build in (True, False):  # the shared map, or the one-shot local one
        with ParsedTxRegion(_serialize_all(txs), len(txs)) as region:
            if build:
                region.build_intra()
            sub = region.extract_subset([1, 5], intra_amounts=True)
        assert [int(x) for x in sub.tx_extracted] == [1, 1]
        assert [int(x) for x in sub.tx_unsupported] == [
            int(serial.tx_unsupported[1]), int(serial.tx_unsupported[5])] == [0, 0]


def test_extract_subset_validates_and_takes_an_empty_subset():
    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import ParsedTxRegion

    txs = gen_signed_txs(3, inputs_per_tx=1, seed=0x18)
    with ParsedTxRegion(_serialize_all(txs), 3) as region:
        for bad in ([3], [-1], [[0, 1]]):
            with pytest.raises(ValueError):
                region.extract_subset(bad)
        empty = region.extract_subset([])
        assert empty.count == 0 and empty.n_txs == 0


def test_wire_hashes_are_wtxids_for_witness_txs_and_txids_otherwise():
    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import ParsedTxRegion
    from tpunode.util import double_sha256

    txs = gen_signed_txs(6, inputs_per_tx=1, seed=0x27, segwit_every=2)
    assert any(t.has_witness for t in txs) and not all(t.has_witness for t in txs)
    with ParsedTxRegion(_serialize_all(txs), len(txs)) as region:
        wire = region.wire_hashes()
        txids = region.txids()
    for i, t in enumerate(txs):
        assert wire[i].tobytes() == double_sha256(t.serialize())
        assert txids[i].tobytes() == t.txid
        assert (wire[i].tobytes() == t.txid) == (not t.has_witness)


def test_utxo_ops_blob_layout():
    """The one-pass UTXO delta blob: creates (key -> amount+script) before
    spends, coinbase inputs skipped, v1 record framing."""
    import struct

    from benchmarks.txgen import gen_signed_txs
    from tpunode.txextract import ParsedTxRegion

    txs = gen_signed_txs(5, inputs_per_tx=2, seed=0x19)
    with ParsedTxRegion(_serialize_all(txs), 5) as region:
        blob, created, spent = region.utxo_ops()
        tids = region.txids()
    assert created == sum(len(t.outputs) for t in txs)
    assert spent == sum(len(t.inputs) for t in txs)  # no coinbase here
    rec = struct.Struct("<BII")
    pos = n_put = n_del = 0
    seen_del = False
    while pos < len(blob):
        op, klen, vlen = rec.unpack_from(blob, pos)
        pos += rec.size
        key = blob[pos : pos + klen]
        pos += klen
        val = blob[pos : pos + vlen]
        pos += vlen
        assert key[0:1] == b"o" and klen == 37
        if op == 1:
            assert not seen_del  # creates strictly before spends
            n_put += 1
            txid, vout = key[1:33], int.from_bytes(key[33:], "little")
            ti = next(
                i for i in range(len(txs)) if tids[i].tobytes() == txid
            )
            out = txs[ti].outputs[vout]
            assert val == struct.pack("<q", out.value) + out.script
        else:
            seen_del = True
            n_del += 1
    assert (n_put, n_del) == (created, spent)


# ---------------------------------------------------------------------------
# the node's resolve walk (ISSUE 30) against the walk as it stood


@pytest.mark.asyncio
async def test_node_publishes_the_reference_walks_verdicts(monkeypatch):
    """A sharded block and a relay drain through a node whose oracle rows
    come from the columnar walk put on the bus exactly the ``TxVerdict``s
    of a node whose rows come from the walk as it stood (a lookup chain a
    row) — and both are what the generator built."""
    import asyncio

    from chipbench import gen
    from tests.test_resolve_rows import MIX, reference_walk
    from tests.test_verdict_reuse import a_node, block_of, tuples
    from tpunode import node as node_mod
    from tpunode.mempool import MempoolConfig

    from tpunode.verify import cpu_native

    # the C++ verifier is built and loaded at its first use; on a fresh
    # checkout, beside five other test workers, that took longer than the
    # 10 s the drive below waits for its first verdicts (the driver's run
    # of PR 35's tree).  Build it before anything is timed.  The "ports"
    # are labels of an in-memory pipe: nothing binds them.
    cpu_native.load_native_verifier()
    monkeypatch.setattr(node_mod.Node, "MIN_SHARD_TXS", 16)
    mix = dict(MIX, adversarial_every=6)
    job = gen.gen_job(gen.jobs_for(mix, 5, 150, 150)[0])
    relayed = gen.gen_job(gen.jobs_for(mix, 6, 40, 40)[0])
    oracle = gen.Oracle()
    oracle.p2pk.update(job["p2pk"])
    oracle.p2pk.update(relayed["p2pk"])
    blk = block_of(job["raw"])
    walks = {"columns": node_mod.Node._resolve_ext_rows, "lists": reference_walk}
    seen: dict = {}
    for port, (name, walk) in enumerate(walks.items(), 17930):
        forms = []

        def recorded(self, region, bch, subset=None, _walk=walk, _forms=forms,
                     **kw):
            # the walk as it stood knows no shards (ISSUE 46): the block's
            # jobs then all go after it, as they did
            if _walk is reference_walk:
                kw.pop("shards", None)
            out = _walk(self, region, bch, subset, **kw)
            _forms.append(type(out[0]).__name__)
            return out

        monkeypatch.setattr(node_mod.Node, "_resolve_ext_rows", recorded)
        async with asyncio.timeout(120):
            async with a_node(oracle=oracle, utxo=True, port=port,
                              mempool=MempoolConfig()) as d:
                await d.relay(relayed["raw"])
                n_relay = len(d.verdicts)
                got, _, _ = await d.block(blk)
                shards = [s for s in d.submissions if s[0] == "block"]
                assert len(shards) > 1  # the block was cut into shards
                seen[name] = (tuples(d.verdicts[:n_relay]), tuples(got))
        assert set(forms) == {"list"}  # handed on as the extract's lists
        assert len(forms) >= 2  # the drain's shard(s) and the block
    assert seen["columns"] == seen["lists"]
    relay, block = seen["columns"]
    assert len(relay) == 40 and len(block) == 151
    expect = dict(zip(job["txids"] + relayed["txids"],
                      job["expect"] + relayed["expect"]))
    for txid, valid, verdicts, _, error in relay + block[1:]:
        assert error is None and verdicts == tuple(expect[txid])
        assert valid == all(expect[txid])


# ---------------------------------------------------------------------------
# a block's extract jobs leave from inside the walk (ISSUE 46): each shard's
# job is in the pool the moment that shard's rows are answered, and what the
# jobs extract is what one job after the whole walk extracted


def _handed_on_shapes() -> dict:
    import numpy as np

    from benchmarks.txgen import gen_mixed_txs
    from chipbench import wirefmt as w
    from tests.test_resolve_rows import _txs

    return {  # name -> (raw txs, bch, subset)
        "bch": lambda: ([w.coinbase(1)] + _txs(149, 46), True, None),
        "bch-subset": lambda: (
            [w.coinbase(2)] + _txs(149, 47), True,
            np.array([0] + list(range(2, 150, 3)), np.int32)),
        "segwit": lambda: (
            [tx.serialize() for tx in gen_mixed_txs(40, seed=0x46)], False,
            None),
    }


_NEW_COUNTERS = ("node.stream_blocks", "node.stream_jobs",
                 "node.stream_jobs_in_walk", "span.node.prefix.count")


def _region_counting_closes(raws):
    from tpunode.txextract import ParsedTxRegion

    class Counted(ParsedTxRegion):
        closes = 0

        def close(self):
            type(self).closes += bool(self._h)
            super().close()

    return Counted(b"".join(raws), len(raws)), Counted


@pytest.mark.asyncio
@pytest.mark.parametrize("sources", ["embedder", "utxo", "utxo+embedder"])
@pytest.mark.parametrize("shape", ["bch", "bch-subset", "segwit"])
async def test_jobs_handed_on_inside_the_walk_extract_the_serial_items(
        shape, sources):
    """Items, per-tx rows, the walk's two lists and the embedder's calls
    (once a row, ascending) equal the serial order's: the whole walk, then
    one job."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from tests.test_resolve_rows import ANSWERS, Embedder, a_node
    from tpunode.metrics import metrics
    from tpunode.node import Node, _ExtractJobs

    raws, bch, subset = _handed_on_shapes()[shape]()
    region, counted = _region_counting_closes(raws)
    n_jobs = 4
    serial_emb, emb = Embedder(ANSWERS["pair"]), Embedder(ANSWERS["pair"])
    # the serial order: every row, then one job over all of them
    ext, scr = Node._resolve_ext_rows(
        a_node(region, bch, subset, sources, serial_emb), region, bch, subset)
    if subset is None:
        serial = region.extract(bch, True, ext, scr)
    else:
        region.build_intra()
        serial = region.extract_subset(subset, bch, True, ext, scr)
    before = {k: metrics.get(k) for k in _NEW_COUNTERS}
    with ThreadPoolExecutor(max_workers=2) as pool:
        region.build_intra()  # the parse job's part
        jobs = _ExtractJobs(pool, region, bch, subset, n_jobs,
                            time.perf_counter())
        assert len(jobs.ranges) == n_jobs
        got = Node._resolve_ext_rows(
            a_node(region, bch, subset, sources, emb), region, bch, subset,
            shards=jobs)
        # every job went from inside the walk: nothing is left to submit
        assert len(jobs.cfuts) == n_jobs
        jobs.submit_rest(*got)
        jobs.release()
        assert len(jobs.cfuts) == len(jobs.jobs) == n_jobs
        shards = [f.result(timeout=60) for f in jobs.cfuts]
    assert got == (ext, scr)
    assert emb.calls == serial_emb.calls
    delta = {k: metrics.get(k) - v for k, v in before.items()}
    assert delta == {
        "node.stream_blocks": 1, "node.stream_jobs": n_jobs,
        # with a callback the last shard's job alone leaves with no row
        # left to ask; without one every shard's rows are answered at once
        "node.stream_jobs_in_walk": n_jobs - 1 if "embedder" in sources else 0,
        "span.node.prefix.count": 1,
    }
    merged = _merge_shards(shards)
    assert merged.count == serial.count > 0
    for name in _SHARD_ROWS:
        assert np.array_equal(getattr(merged, name), getattr(serial, name)), name
    assert np.array_equal(serial.item_tx, np.concatenate(
        [s.item_tx + lo for s, (lo, _) in zip(shards, jobs.ranges)]))
    # the last job out closed the region, once
    assert counted.closes == 1 and not region._h


@pytest.mark.asyncio
@pytest.mark.parametrize("n_jobs", [1, 4])
async def test_a_messages_jobs_and_items_are_freed_without_a_collection(n_jobs):
    """A job's future keeps its done-callbacks: the one that lets go of
    the region must not lead back to the jobs (a cycle would keep every
    message's items until a collection, and make the collections longer:
    ``gc.pause_share`` rose by a tenth to a quarter in every cell on a
    first form)."""
    import asyncio
    import gc
    import time
    import weakref
    from concurrent.futures import ThreadPoolExecutor

    from tpunode.node import _ExtractJobs

    raws, bch, _ = _handed_on_shapes()["bch"]()
    gc.collect()
    gc.disable()
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            region = txextract.ParsedTxRegion(b"".join(raws), len(raws))
            region.build_intra()
            jobs = _ExtractJobs(pool, region, bch, None, n_jobs)
            jobs.submit_rest(None, None)
            jobs.release()
            assert all(i.n_txs for i in await asyncio.gather(*jobs.jobs))
            gone = [weakref.ref(jobs)] + [weakref.ref(f) for f in jobs.cfuts]
            del jobs
            end = time.monotonic() + 10  # a worker lets its work item go
            while any(r() is not None for r in gone) and time.monotonic() < end:
                await asyncio.sleep(0.01)
            assert [r() for r in gone] == [None] * (n_jobs + 1)
            assert not region._h
    finally:
        gc.enable()


def test_several_jobs_want_the_parse_jobs_intra_map():
    """The map several jobs share is built off the loop, by the parse job:
    a cut into more than one job over a region without it is refused, not
    mended on the loop; one job builds its own."""
    from concurrent.futures import ThreadPoolExecutor

    from tpunode.node import _ExtractJobs

    raws, bch, _ = _handed_on_shapes()["bch"]()
    with ThreadPoolExecutor(max_workers=1) as pool:
        with txextract.ParsedTxRegion(b"".join(raws), len(raws)) as region:
            assert not region.intra_built
            with pytest.raises(AssertionError, match="no intra map"):
                _ExtractJobs(pool, region, bch, None, 4)
            assert len(_ExtractJobs(pool, region, bch, None, 1).ranges) == 1
            region.build_intra()
            assert region.intra_built
            assert len(_ExtractJobs(pool, region, bch, None, 4).ranges) == 4


@pytest.mark.parametrize("callback", [True, False], ids=["callback", "no-callback"])
def test_a_shard_is_handed_on_after_its_last_row_and_before_the_next_shards_first(
        callback):
    """The order of the walk's last leg: the callback's rows of shard k,
    shard k's submission with its slice of the two lists, the rows of
    shard k + 1.  Without a callback: the batch reads, then every shard."""
    from types import SimpleNamespace

    from tests.test_resolve_rows import (
        ANSWERS, Embedder, a_node, wanted_outpoints)
    from tpunode.node import Node

    raws, bch, subset = _handed_on_shapes()["bch"]()
    log: list = []

    class Logged(Embedder):
        def __call__(self, txid, vout):
            log.append(("row", (txid, vout)))
            return super().__call__(txid, vout)

    with txextract.ParsedTxRegion(b"".join(raws), len(raws)) as region:
        off = region.input_offsets()
        cut = [0, 40, 41, 100, len(raws)]  # a shard of one tx among them
        shards = SimpleNamespace(
            rows=[(int(off[lo]), int(off[hi])) for lo, hi in zip(cut, cut[1:])],
            submit=lambda k, amounts, scripts, in_walk=False: log.append(
                ("job", k, list(amounts), list(scripts), in_walk)))
        node = a_node(region, bch, subset, "utxo+embedder" if callback else "utxo",
                      Logged(ANSWERS["pair"]))
        amounts, scripts = Node._resolve_ext_rows(node, region, bch, shards=shards)
        txids, _, vouts, wants = region.scan_outpoints(bch)
        row_of = {(txids[i].tobytes(), int(vouts[i])): i
                  for i in np.flatnonzero(wants).tolist()}
        assert len(row_of) == len(wanted_outpoints(region, bch))
    jobs = [e for e in log if e[0] == "job"]
    assert [e[1] for e in jobs] == [0, 1, 2, 3]
    for (_, k, a, s, in_walk), (fl, fh) in zip(jobs, shards.rows):
        # a copy of the slice as the finished lists have it
        assert a == amounts[fl:fh] and s == scripts[fl:fh]
        assert in_walk == (callback and k < 3)
    asked = [row_of[e[1]] for e in log if e[0] == "row"]
    assert asked == sorted(set(asked))  # once a row, ascending
    assert bool(asked) == callback
    # between two submissions: exactly the rows of the later shard
    k = -1
    for e in log:
        if e[0] == "job":
            k = e[1]
        else:
            fl, fh = shards.rows[k + 1]
            assert fl <= row_of[e[1]] < fh


@pytest.mark.parametrize("answered", [True, False])
def test_a_read_that_is_not_final_hands_nothing_on(answered):
    """``final=False`` (a block read ahead of one beneath it): rows where
    every row has its answer, None where one has not — and no job
    submitted either way: the read may be made again."""
    from types import SimpleNamespace

    from tests.test_resolve_rows import ANSWERS, Embedder, a_node
    from tpunode.node import Node

    raws, bch, subset = _handed_on_shapes()["bch"]()
    out: list = []
    with txextract.ParsedTxRegion(b"".join(raws), len(raws)) as region:
        off = region.input_offsets()
        shards = SimpleNamespace(
            rows=[(0, int(off[75])), (int(off[75]), int(off[-1]))],
            submit=lambda *a, **kw: out.append(a))
        emb = Embedder(ANSWERS["pair" if answered else "none"])
        node = a_node(region, bch, subset, "utxo+embedder", emb)
        got = Node._resolve_ext_rows(node, region, bch, final=False,
                                     shards=shards)
        assert (got is not None) == answered
        assert out == [] and emb.calls
        # the final read of the same rows hands both shards on
        final = Node._resolve_ext_rows(node, region, bch, shards=shards)
        assert [a[0] for a in out] == [0, 1]
        if answered:
            assert final == got


def test_a_pool_that_takes_no_more_jobs_is_remembered_and_the_region_closed_once():
    from concurrent.futures import ThreadPoolExecutor

    from tpunode.node import _ExtractJobs

    raws, bch, _ = _handed_on_shapes()["bch"]()
    region, counted = _region_counting_closes(raws)
    region.build_intra()
    pool = ThreadPoolExecutor(max_workers=1)
    pool.shutdown()
    jobs = _ExtractJobs(pool, region, bch, None, 3)
    jobs.submit_rest(None, None)
    assert isinstance(jobs.refused, RuntimeError) and jobs.cfuts == []
    assert counted.closes == 0  # the submitter still holds it
    jobs.release()
    jobs.release()
    assert counted.closes == 1


@pytest.mark.asyncio
async def test_the_region_closes_once_and_under_no_live_job_whoever_lets_go_last():
    """The submitter's hold and the jobs' done-callbacks meet on one
    count from several threads: more workers than cores, the interpreter
    switching every 10 µs, the submitter letting go before, between and
    after the jobs' ends — each region is closed exactly once, and no job
    finds it closed."""
    import sys
    import time
    from concurrent.futures import ThreadPoolExecutor, wait

    from tpunode.node import _ExtractJobs

    raws, bch, _ = _handed_on_shapes()["segwit"]()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    deadline = time.monotonic() + 20
    try:
        with ThreadPoolExecutor(max_workers=32) as pool:
            for round_ in range(60):
                region, counted = _region_counting_closes(raws)
                region.build_intra()
                jobs = _ExtractJobs(pool, region, bch, None, 8)
                for k in range(round_ % 9):  # some before the release,
                    jobs.submit(k, None, None)
                if round_ % 2:
                    jobs.submit_rest(None, None)  # ... or all of them
                cfuts = list(jobs.cfuts)
                jobs.release()
                done, pending = wait(cfuts, timeout=max(0.1, deadline - time.monotonic()))
                assert not pending
                for f in done:
                    assert f.result().n_txs > 0  # "region closed" would raise
                # a job's waiters hear of its end before its callbacks run
                while region._h and time.monotonic() < deadline:
                    time.sleep(0.001)
                assert counted.closes == 1 and not region._h
    finally:
        sys.setswitchinterval(interval)
