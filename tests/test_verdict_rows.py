"""The verdict walk, columnar (ISSUE 28): ``RawSigItems.verdict_rows`` gives,
for the engine's per-candidate verdicts, each transaction's ``(txid, valid,
verdicts, stats)`` with every column converted once for the batch.

One parametrised family.  Every case holds the rows to (i) a copy of the walk
the node made before (``combine`` + ``sig_slices`` + ``txid`` + ``stats``,
element by element, kept here with its own consensus walk), (ii)
``txverify.combine_verdicts`` over the Python extractor's items, and pins the
types that reach the bus: ``bytes``, ``bool``, ``tuple``, ``int`` — never a
numpy scalar.  One node-level case: a sharded block and a relay drain publish
the same ``TxVerdict``s, in the same order, as that reference walk.
"""

from __future__ import annotations

import asyncio
import json
import os
import random

import numpy as np
import pytest

from chipbench import gen
from chipbench import wirefmt as w
from tests.test_verdict_reuse import a_node, block_of
from tpunode import TxVerdict
from tpunode.txverify import (
    ExtractStats, combine_verdicts, extract_sig_items, msig_match,
)
from tpunode.util import Reader
from tpunode.wire import Tx

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():
    pytest.skip("native txextract unavailable", allow_module_level=True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "chipbench", "traffic", "blocks.json")) as _f:
    # the blocks cell's own mix: P2PKH, Schnorr-P2PKH, bare P2PK, P2SH 2-of-3
    MIX = json.load(_f)["mix"]
MSIG_ONLY = {"pattern": [["msig"], ["msig", "msig"], ["p2pkh", "msig"]]}


# ---- the walk as the node made it before this change ------------------------


def reference_combine(items, verdicts) -> list:
    out: list = []
    k = 0
    while k < items.count:
        m, n = int(items.item_nsigs[k]), int(items.item_nkeys[k])
        if m == 1 and n == 1:
            out.append(bool(verdicts[k]))
            k += 1
            continue
        span = m * (n - m + 1)
        got = {}
        for idx in range(k, k + span):
            got[(int(items.item_sig[idx]), int(items.item_key[idx]))] = bool(
                verdicts[idx])
        out.extend(msig_match(m, n, lambda i, j: got.get((i, j), False)))
        k += span
    return out


def reference_rows(items, verdicts) -> list:
    per_sig = reference_combine(items, verdicts)
    rows = []
    for ti, sl in enumerate(items.sig_slices()):
        vs = tuple(per_sig[sl])
        rows.append((items.txid(ti), all(vs), vs, items.stats(ti)))
    return rows


# ---- the shapes --------------------------------------------------------------


def _no_signature_tx(rng) -> bytes:
    """Two inputs no template matches: unsupported, nothing to verify."""
    ins = [(rng.randbytes(32), 0, b"\x51", 0xFFFFFFFF),
           (rng.randbytes(32), 1, b"\x51\x51", 0xFFFFFFFF)]
    return w.ser_tx(2, ins, [(1000, b"\x51")], 0)


def _shape(name: str):
    """-> (raw txs, oracle)."""
    rng = random.Random(name)
    oracle = gen.Oracle()

    def mixed(n, mix=MIX, seed=28):
        job = gen.gen_job(gen.jobs_for(mix, seed, n, n)[0])
        oracle.p2pk.update(job["p2pk"])
        return job["raw"]

    raws = {
        "block-2000": lambda: [w.coinbase(1)] + mixed(1999),
        "block-64": lambda: [w.coinbase(2)] + mixed(63),
        "one-tx": lambda: mixed(1),
        "one-msig-tx": lambda: mixed(1, MSIG_ONLY),
        "coinbase-only": lambda: [w.coinbase(3)],
        "no-signature": lambda: [_no_signature_tx(rng)],
        "no-signature-among-others": lambda: (
            mixed(5) + [_no_signature_tx(rng)] + mixed(4, seed=29)
            + [_no_signature_tx(rng)]),
        "msig-back-to-back": lambda: mixed(30, MSIG_ONLY),
    }[name]()
    return raws, oracle


def native_items(raws: list, oracle):
    """The native extraction as the node makes it: oracle rows for the inputs
    the parse marks, then one extract over the region."""
    with txextract.ParsedTxRegion(b"".join(raws), len(raws)) as region:
        txids, _, vouts, wants = region.scan_outpoints(True)
        ext, scripts = [-1] * len(wants), [None] * len(wants)
        for i in np.flatnonzero(wants).tolist():
            ext[i], scripts[i] = oracle(txids[i].tobytes(), int(vouts[i]))
        return region.extract(bch=True, intra_amounts=len(raws) > 1,
                              ext_amounts=ext, ext_scripts=scripts)


_EXTRACTED: dict = {}


def extracted(name: str):
    """-> (RawSigItems, the Python extractor's SigItems, its per-tx stats)."""
    if name not in _EXTRACTED:
        raws, oracle = _shape(name)
        items = native_items(raws, oracle)
        py_items, py_stats = [], []
        for raw in raws:
            tx = Tx.deserialize(Reader(raw))
            info = [oracle(i.prevout.txid, i.prevout.index) for i in tx.inputs]
            got, stats = extract_sig_items(
                tx, prevout_amounts={k: a for k, (a, _) in enumerate(info)},
                bch=True,
                prevout_scripts={k: s for k, (_, s) in enumerate(info)})
            py_items.extend(got)
            py_stats.append(stats)
        _EXTRACTED[name] = (items, py_items, py_stats)
    return _EXTRACTED[name]


def candidate_verdicts(items, pattern: str) -> list:
    """What the engine might answer, one bool a candidate row."""
    n = items.count
    multi = (items.item_nsigs[:n] != 1) | (items.item_nkeys[:n] != 1)
    v = np.ones(n, bool)
    if pattern == "all-false":
        v[:] = False
    elif pattern == "random":
        v = np.random.default_rng(28).random(n) < 0.6
    elif pattern == "first-key-wrong":
        v[multi & (items.item_key[:n] == 0)] = False
    elif pattern == "last-sig-wrong":
        v[multi & (items.item_sig[:n] == items.item_nsigs[:n] - 1)] = False
    elif pattern == "windows-all-wrong":
        v[multi] = False
    elif pattern == "singles-wrong":
        v[~multi] = False
    else:
        assert pattern == "all-true"
    return v.tolist()


SHAPES = ["block-2000", "block-64", "one-tx", "one-msig-tx", "coinbase-only",
          "no-signature", "no-signature-among-others", "msig-back-to-back"]
PATTERNS = ["all-true", "all-false", "random", "first-key-wrong",
            "last-sig-wrong", "windows-all-wrong", "singles-wrong"]


def assert_bus_types(row) -> None:
    txid, valid, verdicts, stats = row
    assert type(txid) is bytes and len(txid) == 32
    assert type(valid) is bool
    assert type(verdicts) is tuple
    assert all(type(v) is bool for v in verdicts)
    assert type(stats) is ExtractStats
    for field in ("total_inputs", "extracted", "coinbase", "unsupported",
                  "sigs", "candidates"):
        assert type(getattr(stats, field)) is int, field


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("shape", SHAPES)
def test_verdict_rows_equal_the_reference_walk(shape, pattern):
    items, py_items, py_stats = extracted(shape)
    verdicts = candidate_verdicts(items, pattern)
    rows = list(items.verdict_rows(verdicts))
    assert len(rows) == items.n_txs == len(py_stats)
    assert rows == reference_rows(items, verdicts)
    for row in rows:
        assert_bus_types(row)
        assert row[1] == all(row[2]) and len(row[2]) == row[3].sigs
    # the consensus walk, against the Python path's over the Python
    # extractor's items: signature by signature, and tx by tx
    assert len(py_items) == items.count
    per_sig = combine_verdicts(py_items, verdicts)
    assert items.combine(verdicts) == per_sig
    assert all(type(v) is bool for v in items.combine(verdicts))
    assert [v for row in rows for v in row[2]] == per_sig
    assert [row[3] for row in rows] == py_stats
    # an engine answer may come as an array as well as a list
    assert list(items.verdict_rows(np.asarray(verdicts, bool))) == rows


def test_shapes_hold_what_their_names_say():
    items, _, _ = extracted("block-2000")
    assert items.n_txs == 2000 and items.stats(0).coinbase == 1
    assert int((items.item_nsigs[:items.count] == 2).sum()) == 250 * 8
    assert extracted("block-64")[0].n_txs == 64
    cb, _, _ = extracted("coinbase-only")
    assert (cb.count, cb.n_txs) == (0, 1)
    assert list(cb.verdict_rows([])) == [
        (cb.txid(0), True, (), ExtractStats(total_inputs=1, coinbase=1))]
    none, _, _ = extracted("no-signature")
    assert (none.count, none.n_txs) == (0, 1)
    (_, valid, verdicts, stats), = none.verdict_rows([])
    assert (valid, verdicts, stats.unsupported, stats.sigs) == (True, (), 2, 0)
    among, _, _ = extracted("no-signature-among-others")
    assert [r[2] for r in among.verdict_rows([True] * among.count)].count(()) == 2
    back, _, _ = extracted("msig-back-to-back")
    assert int(back.tx_items.max()) == 8  # two windows of four, adjacent


def test_real_verdicts_of_a_block_equal_construction():
    """The 64-tx block's candidates through the CPU verifier: the rows carry
    what the generator signed, failed candidates inside windows included."""
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    mix = dict(MIX, adversarial_every=6)
    job = gen.gen_job(gen.jobs_for(mix, 7, 63, 63)[0])
    assert any(not all(e) for e in job["expect"])
    oracle = gen.Oracle()
    oracle.p2pk.update(job["p2pk"])
    raws = [w.coinbase(4)] + job["raw"]
    items = native_items(raws, oracle)
    verdicts = verify_batch_cpu(items.to_verify_items())
    assert not all(verdicts)  # a 2-of-3 tries pairs that were never signed
    rows = list(items.verdict_rows(verdicts))
    assert rows == reference_rows(items, verdicts)
    assert [(r[0], r[2]) for r in rows[1:]] == list(
        zip(job["txids"], job["expect"]))


# ---- the node: a sharded block and a relay drain -----------------------------


@pytest.mark.asyncio
async def test_node_publishes_the_reference_walks_verdicts(monkeypatch):
    """Every ``RawSigItems`` the node hands its engine, with the engine's
    answer, through the reference walk: the bus carries exactly those
    ``TxVerdict``s, a batch's in the batch's order — for a block cut into
    shards (``_commit_items``) and for relayed txs (``_commit_drained``)."""
    from tpunode import node as node_mod

    monkeypatch.setattr(node_mod.Node, "MIN_SHARD_TXS", 16)
    job = gen.gen_job(gen.jobs_for(dict(MIX, adversarial_every=6), 3, 150, 150)[0])
    relayed = gen.gen_job(gen.jobs_for(dict(MIX, adversarial_every=6), 4, 40, 40)[0])
    oracle = gen.Oracle()
    oracle.p2pk.update(job["p2pk"])
    oracle.p2pk.update(relayed["p2pk"])
    blk = block_of(job["raw"])
    async with asyncio.timeout(120):
        async with a_node(oracle=oracle, port=17928) as d:
            batches: list = []  # (priority, reference rows), as answered
            plain = d.node.verify_engine.verify_raw

            async def recorded(items, priority="bulk", **kw):
                out = await plain(items, priority=priority, **kw)
                batches.append((priority, reference_rows(items, out)))
                return out

            d.node.verify_engine.verify_raw = recorded
            got, _, _ = await d.block(blk)
            block_batches = [rows for _, rows in batches]
            assert len(block_batches) > 1  # the block was cut into shards
            n_block = len(d.verdicts)
            del batches[:]
            await d.relay(relayed["raw"])
            relay_batches = [rows for p, rows in batches if p == "mempool"]
            published = list(d.verdicts)
    assert len(got) == blk.tx_count == 151
    for v in got[1:]:
        assert v.verdicts == dict(zip(job["txids"], job["expect"]))[v.txid]
    for v in published:
        assert_bus_types((v.txid, v.valid, v.verdicts, v.stats))
        assert v.error is None and v.peer is d.peer

    def as_rows(vs):
        return [(v.txid, v.valid, v.verdicts, v.stats) for v in vs]

    # every batch's verdicts stand on the bus together, in the batch's order
    # (one hold of the loop a shard), and nothing else stands there
    for pub, answered, n in ((as_rows(published[:n_block]), block_batches, 151),
                             (as_rows(published[n_block:]), relay_batches, 40)):
        assert len(pub) == n == sum(len(rows) for rows in answered)
        for rows in answered:
            at = pub.index(rows[0])
            assert pub[at:at + len(rows)] == rows
    # shards are contiguous tx ranges: in range order they are the block
    block_batches.sort(key=lambda rows: [v.txid for v in got].index(rows[0][0]))
    assert as_rows(got) == [r for rows in block_batches for r in rows]
    assert isinstance(published[0], TxVerdict)
