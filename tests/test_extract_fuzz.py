"""Differential fuzzing: native vs Python extraction on mutated wire bytes.

The extractor is consensus-adjacent: a parser divergence between the C++
fast path and the Python reference means different txids/digests/verdicts
for the same bytes (exactly the class of bug ADVICE r3 found in varint
handling).  Seeded, bounded fuzz: take valid serialized tx regions, flip /
truncate / splice bytes, and require the two paths to agree — both reject,
or both produce identical items, stats and per-signature verdicts.
"""

from __future__ import annotations

import random

import pytest

# Heavy tier: ~75s of differential fuzzing on this box; the per-template
# native/Python parity tests stay in the fast tier (test_taproot,
# test_p2pk_wsh, test_txextract).
pytestmark = pytest.mark.heavy

from benchmarks.txgen import gen_mixed_txs, synth_prevout
from tpunode.txverify import (
    combine_verdicts,
    extract_sig_items,
    wants_amount,
)
from tpunode.util import Reader
from tpunode.verify.ecdsa_cpu import CURVE_N, verify_batch_cpu
from tpunode.wire import Tx

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():  # pragma: no cover
    pytest.skip("native txextract unavailable", allow_module_level=True)

from tpunode.txextract import ParsedTxRegion  # noqa: E402


def _python_path(data: bytes, n_txs: int, bch: bool):
    """Parse + extract via the pure-Python reference; None if unparseable."""
    r = Reader(data)
    try:
        txs = [Tx.deserialize(r) for _ in range(n_txs)]
        if r.remaining():
            return None
    except Exception:
        return None
    items = []
    sigs = []
    for tx in txs:
        amounts = {}
        scripts = {}
        for idx, ti in enumerate(tx.inputs):
            if wants_amount(tx, idx, bch):
                amounts[idx], scripts[idx] = synth_prevout(
                    ti.prevout.txid, ti.prevout.index
                )
        try:
            its, st = extract_sig_items(
                tx, prevout_amounts=amounts or None, bch=bch,
                prevout_scripts=scripts or None,
            )
        except Exception:
            return None
        items.extend(its)
        sigs.append(st)
    return txs, items, sigs


def _native_path(data: bytes, n_txs: int, bch: bool):
    try:
        region = ParsedTxRegion(data, n_txs)
    except ValueError:
        return None
    with region:
        pt, _, pv, pw = region.scan_outpoints(bch)
        ext = [-1] * len(pw)
        ext_scripts: list = [None] * len(pw)
        for i in pw.nonzero()[0]:
            ext[int(i)], ext_scripts[int(i)] = synth_prevout(
                pt[i].tobytes(), int(pv[i])
            )
        try:
            return region.extract(
                bch=bch, ext_amounts=ext, ext_scripts=ext_scripts
            )
        except ValueError:
            return None


def _compare(data: bytes, n_txs: int, bch: bool) -> str:
    """Run both paths; assert agreement.  Returns a tag for stats."""
    py = _python_path(data, n_txs, bch)
    nat = _native_path(data, n_txs, bch)
    if py is None or nat is None:
        # Parse acceptance may legitimately differ in ONE direction only:
        # Python's Tx.deserialize enforces nothing the native parser skips
        # (they mirror each other), so reject/accept must agree.
        assert (py is None) == (nat is None), (
            f"parse acceptance diverged: python={'reject' if py is None else 'accept'} "
            f"native={'reject' if nat is None else 'accept'} data={data.hex()[:120]}"
        )
        return "both-reject"
    txs, py_items, py_stats = py
    assert nat.count == len(py_items), "item count diverged"
    for i, it in enumerate(py_items):
        assert int(nat.item_input[i]) == it.input_index, i
        assert int(nat.item_sig[i]) == it.sig_index, i
        assert int(nat.item_key[i]) == it.key_index, i
        z_n = int.from_bytes(nat.z[i].tobytes(), "big")
        assert z_n == it.z % CURVE_N, (i, "digest diverged")
        r_n = int.from_bytes(nat.r[i].tobytes(), "big")
        assert r_n == (it.r if it.r < 2**256 else 0), (i, "r diverged")
    for ti, (tx, st) in enumerate(zip(txs, py_stats)):
        assert nat.txid(ti) == tx.txid, (ti, "txid diverged")
        got = nat.stats(ti)
        assert (
            got.total_inputs, got.extracted, got.coinbase,
            got.unsupported, got.sigs, got.candidates,
        ) == (
            st.total_inputs, st.extracted, st.coinbase,
            st.unsupported, st.sigs, st.candidates,
        ), (ti, "stats diverged")
    # verdict-level agreement (the consensus output)
    py_verd = combine_verdicts(
        py_items, verify_batch_cpu([i.verify_item for i in py_items])
    )
    nat_verd = nat.combine(verify_batch_cpu(nat.to_verify_items()))
    assert py_verd == nat_verd, "per-signature verdicts diverged"
    return "both-accept"


def _mutations(rng: random.Random, base: bytes):
    """A spread of adversarial byte-level edits."""
    n = len(base)
    yield base  # identity
    for _ in range(6):  # single byte flips
        b = bytearray(base)
        b[rng.randrange(n)] ^= 1 << rng.randrange(8)
        yield bytes(b)
    for _ in range(3):  # byte value swaps (hits varints/opcodes/lengths)
        b = bytearray(base)
        b[rng.randrange(n)] = rng.randrange(256)
        yield bytes(b)
    yield base[: rng.randrange(1, n)]  # truncation
    cut = rng.randrange(1, n - 1)  # splice: drop 1..7 bytes mid-buffer
    yield base[:cut] + base[cut + rng.randrange(1, min(8, n - cut) + 1) :]
    b = bytearray(base)  # varint-area targeted flips (first bytes of the tx)
    b[rng.randrange(min(8, n))] = rng.choice([0x00, 0xFD, 0xFE, 0xFF])
    yield bytes(b)


def test_differential_fuzz_taproot_witness_targeted():
    """Taproot-focused mutations: flip bytes specifically inside the
    WITNESS region (sig lengths, annex prefix, control-block bytes,
    tapscript opcodes) of keypath and script-path spends — the area where
    the two extractors' newest branch logic lives."""
    rng = random.Random(0x7A9F)
    txs = gen_mixed_txs(
        16, seed=0x7A90,
        mix=[(0.4, "p2tr"), (0.8, "p2tr-script"), (1.01, "unsupported")],
    )
    outcomes = {"both-accept": 0, "both-reject": 0}
    for tx in txs:
        base = tx.serialize()
        # witness region sits between the outputs and the 4-byte locktime;
        # its size = full - nonwitness - marker/flag(2)
        wit_len = len(base) - len(tx.serialize(include_witness=False)) - 2
        assert wit_len > 0  # every tx in this mix carries a witness
        lo, hi = len(base) - 4 - wit_len, len(base) - 4
        outcomes[_compare(base, 1, False)] += 1
        for _ in range(10):
            b = bytearray(base)
            b[rng.randrange(lo, hi)] ^= 1 << rng.randrange(8)
            outcomes[_compare(bytes(b), 1, False)] += 1
        for v in (0x50, 0xC0, 0xC1, 0x20, 0xAC, 0x00, 0x40, 0x41):
            b = bytearray(base)
            b[rng.randrange(lo, hi)] = v
            outcomes[_compare(bytes(b), 1, False)] += 1
    assert outcomes["both-accept"] > 20, outcomes


@pytest.mark.parametrize("bch", [False, True])
def test_differential_fuzz_single_tx(bch):
    rng = random.Random(0xF522 + bch)
    txs = gen_mixed_txs(24, seed=0xF00 + bch, schnorr_every=3 if bch else 0)
    outcomes = {"both-accept": 0, "both-reject": 0}
    for tx in txs:
        base = tx.serialize()
        for mutated in _mutations(rng, base):
            outcomes[_compare(mutated, 1, bch)] += 1
    # the fuzz must exercise both agreement modes to mean anything
    assert outcomes["both-accept"] > 10 and outcomes["both-reject"] > 10, outcomes


def test_differential_fuzz_multi_tx_region():
    rng = random.Random(0xB10B)
    txs = gen_mixed_txs(8, seed=0xB10B)
    base = b"".join(t.serialize() for t in txs)
    outcomes = {"both-accept": 0, "both-reject": 0}
    for mutated in _mutations(rng, base):
        outcomes[_compare(mutated, len(txs), False)] += 1
    for _ in range(24):  # extra random single-byte flips over the region
        b = bytearray(base)
        b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
        outcomes[_compare(bytes(b), len(txs), False)] += 1
    assert outcomes["both-accept"] > 0 and outcomes["both-reject"] > 0, outcomes
