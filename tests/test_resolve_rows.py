"""The prevout walk, columnar (ISSUE 30): ``Node._resolve_ext_rows`` converts
every column of the native scan once a call, asks the sources a source at a
time over the rows still unanswered — the mempool and the UTXO set in one
batch read each, then the embedder's ``prevout_lookup`` row by row — and
hands the rows on as the two lists the native extract converts.

One parametrised family holds it to a kept copy of the walk the node made
before (the sources chained, one call a row through each): amounts
and scripts row for row, and the embedder's recorded calls — arguments,
their types, order, count.  Beside it: who shadows whom, an exception of
the embedder's, a source that changes between two calls (no memo), the span
and the two counters.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import gen
from chipbench import wirefmt as w
from tpunode.mempool import Mempool, MempoolConfig, TxState, _Entry
from tpunode.metrics import metrics
from tpunode.node import Node
from tpunode.params import BCH_REGTEST
from tpunode.store import MemoryKV, Namespaced
from tpunode.utxo import UTXO_NAMESPACE, UtxoStore

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():
    pytest.skip("native txextract unavailable", allow_module_level=True)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "chipbench", "traffic", "blocks.json")) as _f:
    MIX = json.load(_f)["mix"]


# ---- the walk as the node made it before this change ------------------------


def prevout_info(res) -> tuple:
    """A ``prevout_lookup`` result — a plain satoshi amount (the
    pre-taproot form), an ``(amount, scriptPubKey)`` tuple, or None — as
    ``(amount, script)``."""
    if res is None:
        return None, None
    if isinstance(res, tuple):
        return res[0], res[1]
    return res, None


def chained(sources: list):
    """One lookup over ``sources``: the first answer that is not None."""
    if not sources:
        return None

    def combined(txid: bytes, vout: int):
        for lookup in sources:
            res = lookup(txid, vout)
            if res is not None:
                return res
        return None

    return combined


def reference_oracle(node):
    """The prevout oracle as the node built it before the columnar walk:
    the sources in precedence, one call a row through each."""
    sources = []
    if node.mempool is not None and node.mempool.size():
        sources.append(node.mempool.lookup_prevout)
    if node.utxo is not None:
        sources.append(node.utxo.lookup)
    if node.cfg.prevout_lookup is not None:
        sources.append(node.cfg.prevout_lookup)
    return chained(sources)


def reference_walk(node, region, bch: bool, subset=None):
    lookup = reference_oracle(node)
    if lookup is None:
        return None, None
    pv_txids, _, pv_vouts, pv_wants = region.scan_outpoints(bch)
    if subset is not None:
        n_in, _ = region.tx_layout()
        keep = np.zeros(len(n_in), bool)
        keep[subset] = True
        rows = np.flatnonzero(np.repeat(keep, n_in))
        pv_txids, pv_vouts, pv_wants = (
            pv_txids[rows], pv_vouts[rows], pv_wants[rows]
        )
    ext = [-1] * len(pv_wants)
    ext_scripts = [None] * len(pv_wants)
    for i in pv_wants.nonzero()[0]:
        amt, script = prevout_info(
            lookup(pv_txids[i].tobytes(), int(pv_vouts[i]))
        )
        if amt is not None:
            ext[int(i)] = amt
        if script is not None:
            ext_scripts[int(i)] = script
    return ext, ext_scripts


# ---- shapes, sources, answers -------------------------------------------------


def _txs(n: int, seed: int) -> list:
    return gen.gen_job(gen.jobs_for(MIX, seed, n, n)[0])["raw"]


# name -> (raw txs, bch, subset)
SHAPES = {
    "one-tx": lambda: (_txs(1, 30), True, None),
    "block-64": lambda: ([w.coinbase(1)] + _txs(63, 31), True, None),
    "block-subset": lambda: (
        [w.coinbase(2)] + _txs(63, 32), True,
        np.array([0, 3, 4, 17, 40, 41, 63], np.int32)),
    # without FORKID digests only the bare-P2PK inputs want their prevout:
    # most rows stay -1 / unknown, and the coinbase row does in every shape
    "unwanted-rows": lambda: ([w.coinbase(3)] + _txs(40, 33), False, None),
}
SOURCES = ("none", "embedder", "utxo+embedder", "mempool+utxo+embedder")
ANSWERS = {
    "none": lambda txid, vout: None,
    "int": lambda txid, vout: gen.synth_amount(txid, vout),
    "pair": lambda txid, vout: (gen.synth_amount(txid, vout),
                                gen.synth_script(txid)),
    "amount-none": lambda txid, vout: (gen.synth_amount(txid, vout), None),
}


class Embedder:
    """``prevout_lookup`` that writes down how it was called."""

    def __init__(self, answer, fail_at=None):
        self.answer, self.fail_at = answer, fail_at
        self.calls: list = []

    def __call__(self, txid, vout):
        self.calls.append((txid, vout, type(txid), type(vout)))
        if self.fail_at is not None and len(self.calls) > self.fail_at:
            raise LookupError(f"embedder down at call {len(self.calls)}")
        return self.answer(txid, vout)


def a_mempool() -> Mempool:
    return Mempool(MempoolConfig(), BCH_REGTEST, submit=lambda peer, tx: None)


def admit(mp: Mempool, txid: bytes, outputs) -> None:
    mp._seen.insert(txid, _Entry(txid, txid, TxState.VALID, outputs=outputs))
    mp._size += 1


def wanted_outpoints(region, bch, subset=None) -> list:
    txids, _, vouts, wants = region.scan_outpoints(bch, subset)
    return [(txids[i].tobytes(), int(vouts[i]))
            for i in np.flatnonzero(wants).tolist()]


def a_node(region, bch, subset, sources: str, embedder) -> SimpleNamespace:
    """What ``_resolve_ext_rows`` reads of a node, with the program's own
    sources filled from the region's wanted outpoints: the UTXO set holds
    every third, the mempool every fifth (every fifteenth both, under
    another value), one mempool entry has dropped its outputs and one
    knows fewer outputs than the row asks for."""
    wanted = wanted_outpoints(region, bch, subset)
    utxo = mempool = None
    if "utxo" in sources:
        utxo = UtxoStore(Namespaced(MemoryKV(), UTXO_NAMESPACE))
        utxo.apply(1, b"\x11" * 32, [], [
            (txid, vout, 7_000 + k, b"\x76utxo" + bytes([k % 251]))
            for k, (txid, vout) in enumerate(wanted) if k % 3 == 0])
    if "mempool" in sources:
        mempool = a_mempool()
        for k, (txid, vout) in enumerate(wanted):
            if k % 5 == 0:
                outs = [(9_000 + k, b"\x51mem" + bytes([k % 251]))] * (vout + 1)
                admit(mempool, txid, tuple(outs))
            elif k % 7 == 1:
                admit(mempool, txid, None)  # confirmed: outputs dropped
            elif k % 7 == 2:
                admit(mempool, txid, ((1, b"\x51"),) * vout)  # vout too high
    return node_of(mempool, utxo, embedder if "embedder" in sources else None)


def node_of(mempool, utxo, prevout_lookup) -> SimpleNamespace:
    node = SimpleNamespace(mempool=mempool, utxo=utxo, _inflight=None,
                           cfg=SimpleNamespace(prevout_lookup=prevout_lookup))
    node._prevout_sources = lambda: Node._prevout_sources(node)
    return node


def rows_of(ext, scripts) -> tuple:
    """The walk's two lists, unknown = -1 / b""."""
    if ext is None:
        return None, None
    assert type(ext) is list and type(scripts) is list
    return ext, [s or b"" for s in scripts]


_REGIONS: dict = {}


def region_of(shape: str):
    if shape not in _REGIONS:
        raws, bch, subset = SHAPES[shape]()
        _REGIONS[shape] = (
            txextract.ParsedTxRegion(b"".join(raws), len(raws)), bch, subset)
    return _REGIONS[shape]


# ---- the family ---------------------------------------------------------------


@pytest.mark.parametrize("answer", sorted(ANSWERS))
@pytest.mark.parametrize("sources", SOURCES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_rows_and_embedder_calls_equal_the_reference_walk(shape, sources,
                                                          answer):
    region, bch, subset = region_of(shape)
    ref_emb, new_emb = Embedder(ANSWERS[answer]), Embedder(ANSWERS[answer])
    ref = reference_walk(a_node(region, bch, subset, sources, ref_emb),
                         region, bch, subset)
    node = a_node(region, bch, subset, sources, new_emb)
    got = Node._resolve_ext_rows(node, region, bch, subset)
    if sources == "none":
        assert got == (None, None) == ref
        return
    amounts, scripts = rows_of(*got)
    ref_amounts, ref_scripts = rows_of(*ref)
    assert amounts == ref_amounts
    assert scripts == ref_scripts
    # the embedder: the same calls, the same types, in the same order
    assert new_emb.calls == ref_emb.calls
    assert all(c[2:] == (bytes, int) for c in new_emb.calls)
    # ascending row order, each row once
    wanted = wanted_outpoints(region, bch, subset)
    asked = [(c[0], c[1]) for c in new_emb.calls]
    at = [wanted.index(a) for a in asked]
    assert at == sorted(set(at))
    if sources == "embedder":
        assert asked == wanted
    # unwanted rows stay unknown, and the rows are the extract's rows
    _, _, _, wants = region.scan_outpoints(bch, subset)
    assert len(amounts) == len(wants)
    for i in np.flatnonzero(wants == 0).tolist():
        assert (amounts[i], scripts[i]) == (-1, b"")
    assert wants[0] == 0 or shape == "one-tx"  # the coinbase row


@pytest.mark.parametrize("answer", sorted(ANSWERS))
@pytest.mark.parametrize("sources", SOURCES)
def test_the_walks_list_of_sources_is_the_reference_chains(sources, answer):
    """``_prevout_sources``, the one list the walk reads: the sources it
    names and no other, in the reference chain's precedence — asked a row
    at a time in its order they answer every wanted outpoint as the chain
    did before, and ask the embedder as often."""
    region, bch, subset = region_of("block-64")
    ref_emb, new_emb = Embedder(ANSWERS[answer]), Embedder(ANSWERS[answer])
    ref = reference_oracle(a_node(region, bch, subset, sources, ref_emb))
    node = a_node(region, bch, subset, sources, new_emb)
    mempool, inflight, utxo, embedder = node._prevout_sources()
    assert inflight is None  # no block in flight
    assert (mempool is not None) == ("mempool" in sources)
    assert (utxo is not None) == ("utxo" in sources)
    assert embedder is (new_emb if "embedder" in sources else None)
    got = chained([lookup for lookup in (
        mempool and mempool.lookup_prevout, utxo and utxo.lookup, embedder,
    ) if lookup is not None])
    if sources == "none":
        assert got is None and ref is None
        return
    wanted = wanted_outpoints(region, bch, subset)
    assert [got(*o) for o in wanted] == [ref(*o) for o in wanted]
    assert new_emb.calls == ref_emb.calls


def test_shapes_hold_what_their_names_say():
    region, bch, subset = region_of("unwanted-rows")
    _, _, _, wants = region.scan_outpoints(bch, subset)
    assert 0 < int(wants.sum()) < len(wants) - 1
    region, bch, subset = region_of("block-subset")
    n_in = region.tx_layout()[0]
    assert len(region.scan_outpoints(bch, subset)[3]) == int(n_in[subset].sum())
    assert region_of("one-tx")[0].n_txs == 1
    assert region_of("block-64")[0].n_txs == 64


@pytest.mark.parametrize("shape", ["block-64", "block-subset", "unwanted-rows"])
def test_scan_outpoints_gives_scan_prevouts_rows(shape):
    """The native scan off the handle: the rows of the module-level
    ``scan_prevouts`` (its own parse and its own loop), cut to ``subset``
    the way the walk used to cut them, and each outpoint as it stands on
    the wire — txid ++ vout as four little-endian bytes."""
    region, bch, subset = region_of(shape)
    raws = SHAPES[shape]()[0]
    txids, vouts, wants = txextract.scan_prevouts(b"".join(raws), len(raws), bch)
    whole = region.scan_outpoints(bch)
    for got, want in zip((whole[0], whole[2], whole[3]), (txids, vouts, wants)):
        assert got.dtype == want.dtype and (got == want).all()
    if subset is not None:
        keep = np.zeros(region.n_txs, bool)
        keep[subset] = True
        rows = np.flatnonzero(np.repeat(keep, region.tx_layout()[0]))
        txids, vouts, wants = txids[rows], vouts[rows], wants[rows]
    got_txids, outpoints, got_vouts, got_wants = region.scan_outpoints(
        bch, subset)
    assert (got_txids == txids).all() and (got_vouts == vouts).all()
    assert (got_wants == wants).all()
    assert outpoints.shape == (len(wants), 36)
    for i in range(len(wants)):
        assert outpoints[i].tobytes() == (
            txids[i].tobytes() + int(vouts[i]).to_bytes(4, "little"))
    with pytest.raises(ValueError):
        region.scan_outpoints(bch, [region.n_txs])


# ---- precedence ----------------------------------------------------------------


def test_a_mempool_hit_shadows_the_utxo_set_and_a_utxo_hit_the_embedder():
    region, bch, subset = region_of("block-64")
    emb = Embedder(ANSWERS["pair"])
    node = a_node(region, bch, subset, "mempool+utxo+embedder", emb)
    amounts, scripts = rows_of(*Node._resolve_ext_rows(node, region, bch))
    _, _, _, wants = region.scan_outpoints(bch)
    rows = np.flatnonzero(wants).tolist()
    wanted = wanted_outpoints(region, bch)
    asked = {(c[0], c[1]) for c in emb.calls}
    seen = {"mempool": 0, "utxo": 0, "embedder": 0}
    for k, (row, outpoint) in enumerate(zip(rows, wanted)):
        if k % 5 == 0:  # the mempool has it — also where the UTXO set does
            assert amounts[row] == 9_000 + k
            assert scripts[row].startswith(b"\x51mem")
            assert outpoint not in asked
            seen["mempool"] += 1
        elif k % 3 == 0:
            assert amounts[row] == 7_000 + k
            assert scripts[row].startswith(b"\x76utxo")
            assert outpoint not in asked
            seen["utxo"] += 1
        else:  # nobody of the program's: dropped outputs and a vout out of
            # range fall through like a miss
            assert amounts[row] == gen.synth_amount(*outpoint)
            assert outpoint in asked
            seen["embedder"] += 1
    assert all(seen.values()) and sum(seen.values()) == len(emb.calls) + (
        seen["mempool"] + seen["utxo"])
    # every fifteenth row was in both: the mempool's value stands there
    assert any(k % 15 == 0 for k in range(len(rows)))


def test_an_empty_mempool_is_not_asked():
    region, bch, _ = region_of("one-tx")
    emb = Embedder(ANSWERS["int"])
    node = a_node(region, bch, None, "embedder", emb)
    node.mempool = a_mempool()  # size() == 0

    def boom(*a):
        raise AssertionError("an empty mempool was read")

    node.mempool.lookup_prevouts = boom
    amounts, _ = rows_of(*Node._resolve_ext_rows(node, region, bch))
    assert amounts == [gen.synth_amount(t, v)
                       for t, v in wanted_outpoints(region, bch)]


# ---- the embedder's exception, and no memo ---------------------------------------


def test_the_embedders_exception_travels_as_before():
    region, bch, subset = region_of("block-64")
    ref_emb = Embedder(ANSWERS["pair"], fail_at=20)
    new_emb = Embedder(ANSWERS["pair"], fail_at=20)
    with pytest.raises(LookupError, match="call 21"):
        reference_walk(a_node(region, bch, subset, "utxo+embedder", ref_emb),
                       region, bch)
    with pytest.raises(LookupError, match="call 21"):
        Node._resolve_ext_rows(
            a_node(region, bch, subset, "utxo+embedder", new_emb), region, bch)
    assert new_emb.calls == ref_emb.calls and len(new_emb.calls) == 21


def test_a_source_changed_between_two_calls_is_seen_by_the_second():
    """No answer is kept from one call to the next: not the embedder's, not
    a miss of the UTXO set or of the mempool."""
    region, bch, _ = region_of("block-64")
    emb = Embedder(ANSWERS["int"])
    node = a_node(region, bch, None, "embedder", emb)
    node.utxo = UtxoStore(Namespaced(MemoryKV(), UTXO_NAMESPACE))
    node.mempool = a_mempool()
    _, _, _, wants = region.scan_outpoints(bch)
    rows = np.flatnonzero(wants).tolist()
    wanted = wanted_outpoints(region, bch)
    first, _ = rows_of(*Node._resolve_ext_rows(node, region, bch))
    assert [first[r] for r in rows] == [gen.synth_amount(*o) for o in wanted]
    n_calls = len(emb.calls)
    assert n_calls == len(wanted)
    # the embedder answers otherwise; the UTXO set learns row 3's outpoint,
    # the mempool row 5's
    emb.answer = lambda txid, vout: (gen.synth_amount(txid, vout) + 1, b"\x52")
    node.utxo.apply(1, b"\x22" * 32, [], [(*wanted[3], 123, b"\x53")])
    admit(node.mempool, wanted[5][0], ((456, b"\x54"),) * (wanted[5][1] + 1))
    second, scripts = rows_of(*Node._resolve_ext_rows(node, region, bch))
    for k, (r, o) in enumerate(zip(rows, wanted)):
        want = {3: (123, b"\x53"), 5: (456, b"\x54")}.get(
            k, (gen.synth_amount(*o) + 1, b"\x52"))
        assert (second[r], scripts[r]) == want
    assert len(emb.calls) == n_calls + len(wanted) - 2
    # and back: a spent output is a miss again
    node.utxo.apply(2, b"\x33" * 32, [wanted[3]], [])
    third, _ = rows_of(*Node._resolve_ext_rows(node, region, bch))
    assert third[rows[3]] == gen.synth_amount(*wanted[3]) + 1


# ---- one hold of the loop, its span and its counters ----------------------------


def test_the_walk_is_one_synchronous_call_under_its_span():
    assert not asyncio.iscoroutinefunction(Node._resolve_ext_rows)
    region, bch, subset = region_of("block-64")
    emb = Embedder(ANSWERS["pair"])
    node = a_node(region, bch, subset, "utxo+embedder", emb)
    wanted = wanted_outpoints(region, bch)
    before = {k: metrics.get(k) for k in (
        "node.resolve_rows", "node.resolve_oracle_calls",
        "span.node.resolve.count", "span.node.resolve.seconds")}
    Node._resolve_ext_rows(node, region, bch)
    delta = {k: metrics.get(k) - v for k, v in before.items()}
    assert delta["node.resolve_rows"] == len(wanted)
    assert delta["node.resolve_oracle_calls"] == len(emb.calls)
    assert 0 < len(emb.calls) < len(wanted)  # the UTXO set answered some
    assert delta["span.node.resolve.count"] == 1
    assert delta["span.node.resolve.seconds"] > 0
    # nothing can answer: no scan, no span, no row counted
    idle = node_of(None, None, None)
    assert Node._resolve_ext_rows(idle, region, bch) == (None, None)
    assert metrics.get("span.node.resolve.count") == (
        before["span.node.resolve.count"] + 1)


def test_a_vout_past_31_bits_reaches_the_embedder_unsigned():
    rng = random.Random(30)
    raw = w.ser_tx(2, [(rng.randbytes(32), 0xFFFFFFFE, b"\x01\x51", 0xFFFFFFFF)],
                   [(1000, b"\x51")], 0)
    emb = Embedder(ANSWERS["none"])
    node = node_of(None, UtxoStore(Namespaced(MemoryKV(), UTXO_NAMESPACE)), emb)
    with txextract.ParsedTxRegion(raw, 1) as region:
        amounts, _ = rows_of(*Node._resolve_ext_rows(node, region, True))
    assert amounts == [-1]
    assert [(c[1], c[3]) for c in emb.calls] == [(0xFFFFFFFE, int)]


# ---- an amount no int64 holds --------------------------------------------------


@pytest.mark.asyncio
@pytest.mark.parametrize("path", ["relay", "block"])
@pytest.mark.parametrize("source", ["mempool-u64", "embedder-str"])
async def test_an_amount_no_int64_holds_fails_where_it_always_did(source, path):
    """A mempool output's value is a peer's u64, and an embedder may answer
    anything.  A tx spending an output of 2**63 (or of ``"many"``) cannot be
    extracted: the rows reach the extract as they did before this change
    and it refuses them under its caller's handler — on the relay path one
    ``extract:`` error verdict for that tx and every other peer's verdict
    intact, on the block path the whole message's txs failed
    (``_verify_txs_native``'s rule) — and the node goes on."""
    from tests.test_verdict_reuse import a_node as a_real_node, block_of, make_txs

    rng = random.Random(30)
    script_sig = w.push(bytes([0x30, 6, 2, 1, 1, 2, 1, 1, 0x41])) + w.push(
        b"\x02" + rng.randbytes(32))
    parent = w.sha256d(rng.randbytes(60))
    child = w.ser_tx(2, [(parent, 0, script_sig, 0xFFFFFFFF)], [(1000, b"\x51")])
    others = make_txs(12, 77)
    oracle = gen.Oracle()
    oracle.p2pk.update(others["p2pk"])
    lookup = oracle
    if source == "embedder-str":
        def lookup(txid, vout):
            return "many" if txid == parent else oracle(txid, vout)
    expect = dict(zip(others["txids"], others["expect"]))
    raws = others["raw"][:6] + [child] + others["raw"][6:]
    port = 17931 + 2 * (source == "embedder-str") + (path == "block")
    async with asyncio.timeout(120):
        async with a_real_node(mempool=MempoolConfig(tick_interval=0.05),
                               oracle=lookup, port=port) as d:
            if source == "mempool-u64":
                admit(d.node.mempool, parent, ((2**63, b"\x51"),))
            if path == "relay":
                await d.relay(raws)
                got = list(d.verdicts)
            else:
                got, _, _ = await d.block(block_of(raws))
                got = got[1:]  # the coinbase
            assert len(got) == len(raws)
            for v in got:
                if path == "block" or v.txid == w.sha256d(child):
                    assert not v.valid and v.error.startswith("extract: ")
                else:
                    assert v.error is None
                    assert tuple(v.verdicts) == tuple(expect[v.txid])
            # the drain and the block path both survived it
            n0 = len(d.verdicts)
            more = make_txs(3, 78)
            oracle.p2pk.update(more["p2pk"])
            await d.relay(more["raw"])
            assert [v.error for v in d.verdicts[n0:]] == [None] * 3
