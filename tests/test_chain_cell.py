"""The cell ``bch-chain.ibd-recent`` (ISSUE 44): a node with a UTXO set and
no prevout callback syncs a chain that spends its own outputs — an earlier
tx of the same block, a block still in flight, a block long connected, the
snapshot — and answers every prevout from its own state: the block itself,
the in-flight output view (``tpunode.utxo.InflightOutputs``), the set.

(a) program = plain reference = construction, signature by signature, both
extractors; (b) the generator's shares, ages, amounts and scripts; (c)
blocks out of height order through ``Node``, held to construction and to
the Python reference; (d) a dropped and re-delivered block and a reorg
beneath blocks in flight leave nothing in the view; (e) the cell's
rehearsal through ``chipbench``; (f) the backlog rule for the seven backlog
cells.  The restart case stands beside ``tests/test_utxo.py``'s restart pin.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import random

import pytest

from chipbench import gen, gen_chain, harness, reference_chain
from chipbench import wirefmt as w
from chipbench.reference import _multisig, _pushes
from chipbench.tests.rehearse import rehearse
from tests.chain_cell import (
    BENCH, CELL, CONFIG, GENESIS, SHORT, TRAFFIC, a_node, chain, moved,
)
from tests.fakenet import poll_until
from tests.fixtures import (
    reference_set, reference_verdicts, tuples, utxo_records,
)
from tpunode import node as node_mod
from tpunode.ibd import IbdConfig
from tpunode.metrics import metrics

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():
    pytest.skip("native txextract unavailable", allow_module_level=True)

PER = TRAFFIC["txs_per_block"]
VIEW = ("node.resolve_missing", "node.resolve_oracle_calls",
        "node.resolve_gate_expired", "node.inflight_outputs_added",
        "node.inflight_outputs_retired", "node.inflight_outputs_dropped",
        "node.resolve_inflight_hits", "span.node.resolve_gate.count")


def delta(before: dict) -> dict:
    return {k: int(metrics.get(k) - v) for k, v in before.items()}


# ---- (a) program = reference = construction -----------------------------------


def _where(ch, txid: bytes) -> list:
    """Each input's source: 'snapshot', 'in_block', 'recent' or 'old'."""
    (_, ins, _, _), _ = w.parse_tx(ch.raw[txid])
    height = {t: b for b, ids in enumerate(ch.txids) for t in ids}
    out = []
    for prev, vout, _, _ in ins:
        if prev not in height:
            out.append("snapshot")
        else:
            age = height[txid] - height[prev]
            out.append("in_block" if age == 0 else
                       "recent" if age <= 48 else "old")
    return out


def _program_native(ch, txids: list) -> list:
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    amounts, scripts = [], []
    for t in txids:
        (_, ins, _, _), _ = w.parse_tx(ch.raw[t])
        for prev, vout, _, _ in ins:
            a, s = ch.prevout(prev, vout)
            amounts.append(a)
            scripts.append(s)
    items = txextract.extract_raw(
        b"".join(ch.raw[t] for t in txids), len(txids), bch=True,
        intra_amounts=False, ext_amounts=amounts, ext_scripts=scripts)
    assert int(items.tx_unsupported.sum()) == 0
    per_sig = items.combine(verify_batch_cpu(items.to_verify_items()))
    return [tuple(per_sig[sl]) for sl in items.sig_slices()]


def _program_python(ch, txids: list) -> list:
    from tpunode.txverify import combine_verdicts, extract_sig_items
    from tpunode.util import Reader
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu
    from tpunode.wire import Tx

    out = []
    for t in txids:
        tx = Tx.deserialize(Reader(ch.raw[t]))
        rows = [ch.prevout(i.prevout.txid, i.prevout.index) for i in tx.inputs]
        items, stats = extract_sig_items(
            tx, prevout_amounts={i: r[0] for i, r in enumerate(rows)},
            prevout_scripts={i: r[1] for i, r in enumerate(rows)}, bch=True)
        assert stats.unsupported == 0
        out.append(tuple(combine_verdicts(
            items, verify_batch_cpu([i.verify_item for i in items]))))
    return out


@pytest.mark.parametrize("extractor", ["native", "python"])
def test_program_reference_and_construction_agree(extractor):
    ch = chain()
    txids = [t for ids in ch.txids[50:57] for t in ids]  # all four sources
    program = (_program_native if extractor == "native"
               else _program_python)(ch, txids)
    ref = dict(reference_chain.check_job(
        {"raw": [ch.raw[t] for t in txids], "p2pk": ch.table(txids)}))
    for t, got in zip(txids, program):
        assert got == ref[t] == ch.expect[t], (t.hex(), _where(ch, t))
    seen = {s for t in txids for s in _where(ch, t)}
    assert seen == set(gen_chain.SOURCES)
    plan = gen.plan_adversarial(SHORT["mix"], 44, 0, 60 * PER, 60 * PER)
    kinds = {plan[t] for t in range(50 * PER, 57 * PER) if t in plan}
    assert kinds == set(SHORT["mix"]["adversarial"])
    invalid = [t for t in txids if not all(ch.expect[t])]
    assert len(invalid) >= 6  # every kind but the valid twin, at least once


def test_the_reference_needs_the_parents_output():
    """A spend of the chain's own output under the snapshot's function of
    the outpoint is another digest: the reference says invalid."""
    ch = chain()
    txid = next(t for ids in ch.txids[5:] for t in ids
                if "recent" in _where(ch, t) and all(ch.expect[t]))
    full = dict(reference_chain.check_job(
        {"raw": [ch.raw[txid]], "p2pk": ch.table([txid])}))[txid]
    bare = dict(reference_chain.check_job(
        {"raw": [ch.raw[txid]], "p2pk": {}}))[txid]
    assert all(full) and not all(bare)


# ---- (b) the generator --------------------------------------------------------


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_shares_and_ages_are_the_traffic_files(seed):
    c = TRAFFIC["chain"]
    assert c["sources"] == {"in_block": 0.10, "recent": 0.30, "old": 0.20,
                            "snapshot": 0.40}
    assert (c["strands"], c["recent_blocks"], c["old_blocks"]) == (
        16, [1, 6, 48], [49, 2000])
    n, per = 2600, PER // c["strands"]
    drawn, got, ages = {}, {}, {}
    for s in (0, 5, 9, 14):
        plan = gen_chain.plan_strand(c, seed, s, n, per)
        for acc, part in ((drawn, plan["drawn"]), (got, plan["got"]),
                          (ages, plan["ages"])):
            for k, v in part.items():
                acc[k] = acc.get(k, 0) + v
        # a claimed output is claimed once, by a later input or a later tx
        assert len(set(plan["source"].values()) - {None}) == len(plan["claims"])
        for parent, child in plan["claims"].items():
            assert parent[:2] < child[:2]
    total = sum(drawn.values())
    assert total == 4 * n * per * 2
    for k, share in c["sources"].items():
        assert abs(100 * drawn[k] / total - 100 * share) < 2, (k, drawn)
    # what falls to the snapshot is what reaches below height 1 (a chain of
    # 2,600 blocks loses a good part of its 'old' draws: ages to 2,000)
    assert got["in_block"] == drawn["in_block"]
    assert drawn["recent"] - got["recent"] < 0.02 * drawn["recent"]
    assert got["snapshot"] - drawn["snapshot"] == sum(
        drawn[k] - got[k] for k in ("recent", "old"))
    recent = sum(v for a, v in ages.items() if 1 <= a <= 48)
    near = sum(v for a, v in ages.items() if 1 <= a <= 6)
    assert recent == got["recent"] and abs(near / recent - 0.5) < 0.02
    assert ages[0] == got["in_block"]
    assert sum(v for a, v in ages.items() if a >= 49) == got["old"]
    assert max(ages) <= 2000
    # log-uniform: as many spends 49-313 blocks back as 314-2,000, but for
    # what the chain's start cuts off
    assert sum(v for a, v in ages.items() if 49 <= a < 314) > 0.4 * got["old"]


def test_every_spend_of_the_chain_carries_its_parents_amount_and_script():
    ch = chain()
    sources = {k: 0 for k in gen_chain.SOURCES}
    for ids in ch.txids:
        for txid in ids:
            (_, ins, outs, _), _ = w.parse_tx(ch.raw[txid])
            assert len(ins) == len(outs) == 2
            total = 0
            for (prev, vout, sig, _), where in zip(ins, _where(ch, txid)):
                sources[where] += 1
                amount, script = ch.prevout(prev, vout)
                total += amount
                if where == "snapshot":
                    continue
                pushes = _pushes(sig)
                if len(pushes) == 1:  # bare P2PK: the key is the output's
                    assert len(script) == 35 and script[-1] == 0xAC
                elif pushes[0] == b"":  # P2SH 2-of-3
                    assert _multisig(pushes[-1])[0] == 2
                    assert script == (b"\xa9\x14" + gen.hash160(pushes[-1])
                                      + b"\x87")
                elif len(pushes[1]) == 33:  # P2PKH, ECDSA or Schnorr
                    assert script == gen.p2pkh_code(pushes[1])
                else:  # off_curve_key: the key sent is not the key paid to
                    assert len(script) == 25 and not all(ch.expect[txid])
            fee = total - sum(v for v, _ in outs)
            assert fee == min(gen_chain.FEE, total // 4) and min(
                v for v, _ in outs) > 0
    assert sources == ch.got and min(sources.values()) > 100


# ---- (c) out of height order, through Node -------------------------------------


def _shuffled(n: int, seed: int, reach: int = 7) -> list:
    """Heights 1..n, each at most ``reach`` places from its own."""
    rng = random.Random(seed)
    order = list(range(1, n + 1))
    for lo in range(0, n, reach):
        part = order[lo:lo + reach]
        rng.shuffle(part)
        order[lo:lo + reach] = part
    return order


@pytest.mark.asyncio
@pytest.mark.parametrize("path", ["native", "reference"])
async def test_blocks_out_of_height_order_verify_as_in_order(path):
    """``native``: 28 blocks against construction.  ``reference``: 8, and
    the node's verdicts and its set held to the Python reference's
    (``tests/fixtures.py``), which is told every outpoint's truth as the
    raw blocks have it."""
    n = 28 if path == "native" else 8
    ch = chain()
    txids = [t for ids in ch.txids[:n] for t in ids]
    order = _shuffled(n, 3)
    assert order != sorted(order)
    async with a_node(ch) as d:
        before = moved(VIEW)
        for h in order:
            d.give(ch.block(h))
            await asyncio.sleep(0.002)
        await d.verdicts_of(txids)
        # the connect's thread retires a block's outputs after the height
        # is up: wait for the view too before its counters are read
        await poll_until(
            lambda: d.node.utxo.height == n
            and delta(before)["node.inflight_outputs_retired"] >= n * (2 * PER + 1),
            what="connects")
        got = delta(before)
        for t in txids:
            v = d.verdicts[t]
            assert v.error is None and tuple(v.verdicts) == ch.expect[t]
        assert got["span.node.resolve_gate.count"] == n
        assert got["node.resolve_missing"] == 0
        assert got["node.resolve_oracle_calls"] == 0
        assert got["node.resolve_gate_expired"] == 0
        assert got["node.inflight_outputs_added"] == n * (2 * PER + 1)
        assert got["node.inflight_outputs_retired"] == n * (2 * PER + 1)
        assert got["node.inflight_outputs_dropped"] == 0
        assert got["node.resolve_inflight_hits"] > 0
        if path == "reference":
            blocks = [ch.block(h) for h in range(1, n + 1)]
            ref = [row for blk in blocks for row in reference_verdicts(
                list(blk.txs), ch.prevout, bch=True)]
            assert tuples(d.verdicts[row[0]] for row in ref) == ref
            assert utxo_records(d.node) == reference_set(
                blocks, [ch.snapshot_blob()], GENESIS)
        assert d.clear_view()


@pytest.mark.asyncio
async def test_a_predecessor_that_never_comes_ends_the_wait_and_is_counted():
    ch = chain()
    async with a_node(ch, ibd=None) as d:
        d.node._gate_timeout = lambda: 0.3
        before = moved(VIEW)
        d.give(ch.block(2))  # block 1 never comes
        txids = ch.txids[1]
        await d.verdicts_of(txids)
        got = delta(before)
        assert got["node.resolve_gate_expired"] == 1
        # what block 1 made and block 2 spends, no source answered
        spends_1 = sum(
            prev in set(ch.txids[0])
            for t in txids for prev, _, _, _ in w.parse_tx(ch.raw[t])[0][1])
        assert got["node.resolve_missing"] == spends_1 > 0
        assert not d.node._gate_waiters


@pytest.mark.asyncio
async def test_blocks_held_at_the_gate_are_no_pressure_and_no_work_in_hand():
    """The planner defers every request while ``MAX_VERIFY_PENDING // 2``
    blocks are in verification: blocks that wait for a block beneath them
    must not count, or the request that would bring it is never made
    (``bch-wan.ibd-faults`` stood still for 30 s on a first form)."""
    n = 45
    ch = chain()
    txids = [t for ids in ch.txids[:n] for t in ids]
    async with a_node(ch, ibd=None) as d:
        node = d.node
        before = moved(VIEW)
        for h in range(2, n + 1):  # block 1 is late
            d.give(ch.block(h))
        await poll_until(
            lambda: node._inflight.blocks == n - 1 and node._verify_pending == 0,
            what="every block parsed, and held or through")
        # the blocks that spend an output of block 1; the others went on
        assert node._gate_held >= 20
        node.MAX_VERIFY_PENDING = 2 * node._gate_held  # they would be pressure
        assert not node._ibd_pressure()
        held = [h for h in range(2, n + 1)
                if not all(t in d.verdicts for t in ch.txids[h - 1])]
        assert len(held) == node._gate_held
        assert node.utxo.height == 0
        d.give(ch.block(1))
        await d.verdicts_of(txids)
        # (the connect's thread retires a block's outputs after the height
        # is up: the view empties a moment later)
        await poll_until(lambda: node.utxo.height == n and d.clear_view(),
                         what="connects")
        got = delta(before)
        assert all(tuple(d.verdicts[t].verdicts) == ch.expect[t] for t in txids)
        assert got["node.resolve_missing"] == 0
        assert got["node.resolve_gate_expired"] == 0
        assert got["span.node.resolve_gate.count"] == n
        assert d.clear_view()


@pytest.mark.asyncio
async def test_a_block_that_needs_nothing_of_the_blocks_it_is_ahead_of_goes_on():
    """An outpoint's value is fixed by its txid: whichever source answers
    says what the view would have said.  A node whose callback answers
    every row waits for no block (``bch-wan``, ``bch-node``, ``btc-node``)."""
    ch = chain()
    async with a_node(ch, ibd=None, lookup=ch.prevout) as d:
        before = moved(VIEW)
        waited = metrics.get("span.node.resolve_gate.seconds")
        for h in (9, 5, 7):  # none of the blocks beneath them comes
            d.give(ch.block(h))
        txids = [t for h in (9, 5, 7) for t in ch.txids[h - 1]]
        await d.verdicts_of(txids, timeout=20)
        got = delta(before)
        assert all(d.verdicts[t].error is None
                   and tuple(d.verdicts[t].verdicts) == ch.expect[t]
                   for t in txids)
        assert got["node.resolve_gate_expired"] == 0
        assert got["node.resolve_missing"] == 0
        assert got["node.resolve_oracle_calls"] > 0
        assert got["span.node.resolve_gate.count"] == 3
        assert metrics.get("span.node.resolve_gate.seconds") == waited
        assert d.node._gate_held == 0 and not d.node._gate_waiters


@pytest.mark.asyncio
async def test_no_more_blocks_wait_than_the_node_parks():
    ch = chain()
    async with a_node(ch, ibd=None) as d:
        node = d.node
        node.MAX_UTXO_PENDING = 2
        before = moved(VIEW)
        for h in (2, 3, 4):  # block 1 never comes
            d.give(ch.block(h))
        def done(h):
            return all(t in d.verdicts for t in ch.txids[h - 1])

        await poll_until(lambda: any(map(done, (2, 3, 4))), timeout=20,
                         what="the third to arrive going on")
        assert node._gate_held == 2
        assert delta(before)["node.resolve_gate_expired"] == 1
        assert sum(map(done, (2, 3, 4))) == 1


def test_the_wait_is_the_planners_refetch_twice_over():
    class N:
        cfg = type("C", (), {"ibd": IbdConfig(refetch_after=7.0)})
    assert node_mod.Node._gate_timeout(N()) == 14.0
    N.cfg.ibd = None
    assert node_mod.Node._gate_timeout(N()) == 2 * IbdConfig().refetch_after


# ---- (d) nothing stale ----------------------------------------------------------


@pytest.mark.asyncio
async def test_a_dropped_block_leaves_the_view_and_comes_back():
    """Block 3's verification fails once (the engine raises): error
    verdicts, no connect, its outputs dropped from the view; delivered
    again it verifies, connects, and the blocks above it follow."""
    ch = chain()
    async with a_node(ch) as d:
        eng = d.node.verify_engine
        plain, fail = eng.verify_raw, [True]
        bad = set(ch.txids[2])

        async def flaky(items, **kw):
            if fail[0] and any(items.txid(i) in bad
                               for i in range(items.n_txs)):
                fail[0] = False
                raise RuntimeError("engine down")
            return await plain(items, **kw)

        eng.verify_raw = flaky
        before = moved(VIEW)
        for h in (1, 2, 3):
            d.give(ch.block(h))
        await d.verdicts_of([t for ids in ch.txids[:3] for t in ids])
        await poll_until(lambda: d.node.utxo.height == 2, what="connects")
        assert all(d.verdicts[t].error for t in bad)
        await poll_until(lambda: d.clear_view(), what="the view to empty")
        assert delta(before)["node.inflight_outputs_dropped"] == 2 * PER + 1
        d.verdicts.clear()
        for h in (4, 3, 5):
            d.give(ch.block(h))
        txids = [t for ids in ch.txids[2:5] for t in ids]
        await d.verdicts_of(txids)
        await poll_until(lambda: d.node.utxo.height == 5 and d.clear_view(),
                         what="connects")
        for t in txids:
            assert tuple(d.verdicts[t].verdicts) == ch.expect[t]
        got = delta(before)
        assert got["node.resolve_missing"] == 0
        assert got["node.inflight_outputs_added"] == 6 * (2 * PER + 1)
        assert got["node.inflight_outputs_retired"] == 5 * (2 * PER + 1)
        assert d.clear_view()


@pytest.mark.asyncio
async def test_a_reorg_beneath_blocks_in_flight_leaves_nothing_stale():
    """Block 1 and a block 2a that the chain will leave are connected; the
    chain's own blocks 2-5 (longer) become best, and 4, 3, 2 arrive top
    first while verification is held: three blocks in the view, 3 and 4
    behind the gate until 2 is here (2a, at 2's height, is no predecessor
    of theirs).  In whatever order they finish — one unwinds 2a, and what
    was parked above it or came in under the old watermark is let go and
    comes again — the set ends on the chain, every verdict is the
    reference's, no row went unanswered and the view holds nothing."""
    from tests.chain_cell import Chain
    from tests.test_verdict_reuse import block_of

    ch, other = chain(), Chain(1, 45)
    b2a = block_of([other.raw[t] for t in other.txids[0]], height=2,
                   prev=ch.hashes[0], nonce_salt=1)
    async with a_node(ch, known=1, also=[other.snapshot_blob()]) as d:
        await d.know([b2a.header])
        for b in (ch.block(1), b2a):
            d.give(b)
        await poll_until(lambda: d.node.utxo.block_hash == b2a.header.hash,
                         what="connects")
        await d.know(ch.headers[1:5])
        await poll_until(lambda: d.node.chain.get_best().hash == ch.hashes[4],
                         what="the chain to be best")
        before = moved(VIEW + ("utxo.reorg_unwound",))
        d.hold = asyncio.Event()
        for h in (4, 3, 2):
            d.give(ch.block(h))
        await poll_until(lambda: d.node._inflight.blocks == 3,
                         what="three blocks in the view")
        assert len(d.node._inflight) == 3 * (2 * PER + 1)
        d.hold.set()
        d.hold = None
        txids = [t for ids in ch.txids[1:4] for t in ids]
        await d.verdicts_of(txids)
        for h in (2, 3, 4, 5):
            await asyncio.sleep(0.2)  # what was let go is asked again
            if d.node.utxo.height < h or (
                    h == 2 and d.node.utxo.block_hash == b2a.header.hash):
                d.give(ch.block(h))
            await poll_until(
                lambda: d.node.utxo.height >= h
                and d.node.utxo.block_hash != b2a.header.hash,
                what=f"height {h} of the chain")
        assert d.node.utxo.block_hash == ch.hashes[4]
        await d.verdicts_of(ch.txids[4])
        for t in txids + ch.txids[4]:
            assert tuple(d.verdicts[t].verdicts) == ch.expect[t]
        got = delta(before)
        assert got["utxo.reorg_unwound"] == 1
        assert got["node.resolve_missing"] == 0
        assert got["node.resolve_gate_expired"] == 0
        assert (got["node.inflight_outputs_added"]
                == got["node.inflight_outputs_retired"]
                + got["node.inflight_outputs_dropped"])
        assert d.node.utxo.height == 5 and d.clear_view()


@pytest.mark.parametrize("kind", ["InflightOutputs", "NativeInflightOutputs"])
def test_two_blocks_in_flight_that_made_the_same_outpoint(kind):
    """The view's own case of it: forgetting one leaves the other's rows."""
    from tpunode import utxo

    class Out:
        def __init__(self, value):
            self.value, self.script = value, b"\x51"

    class Tx:
        def __init__(self, txid, n):
            self.txid, self.outputs = txid, [Out(i + 1) for i in range(n)]

    view = getattr(utxo, kind)()
    view.publish_txs(b"a" * 32, b"0" * 32, [Tx(b"x" * 32, 2), Tx(b"y" * 32, 1)])
    view.publish_txs(b"b" * 32, b"0" * 32, [Tx(b"x" * 32, 2), Tx(b"z" * 32, 1)])
    assert len(view) == 4 and view.blocks == 2
    assert view.prev_of(b"a" * 32) == b"0" * 32 and view.prev_of(b"c" * 32) is None
    assert view.retire(b"a" * 32) == 3
    assert view.lookup(b"x" * 32, 1) == (2, b"\x51")
    assert view.lookup(b"y" * 32, 0) is None
    assert view.lookup_many([b"z" * 32 + bytes(4), b"q" * 36]) == [
        (1, b"\x51"), None]
    assert view.drop(b"b" * 32) == 3 and view.drop(b"b" * 32) == 0
    assert len(view) == 0 and view.blocks == 0


def test_the_native_view_answers_as_the_plain_one():
    """A block published from its open parse (the native view, one call)
    and from its parsed txs (the plain one): the same rows, the same
    answers, hit or miss, and the same counts as they leave."""
    from tpunode.utxo import InflightOutputs, NativeInflightOutputs

    ch = chain()
    native, plain = NativeInflightOutputs(), InflightOutputs()
    before = moved(VIEW)
    for h in (3, 1, 2, 2):  # any order, one of them twice
        blk = ch.block(h)
        with txextract.ParsedTxRegion(blk.raw_txs, blk.tx_count) as region:
            native.publish_region(blk.header.hash, blk.header.prev, region)
        plain.publish_txs(blk.header.hash, blk.header.prev, list(blk.txs))
    assert len(native) == len(plain) == 3 * (2 * PER + 1)
    assert native.blocks == plain.blocks == 3
    assert delta(before)["node.inflight_outputs_added"] == 8 * (2 * PER + 1)
    asked = list(ch.made)[:6 * PER] + [b"\x07" * 36]
    answers = native.lookup_many(asked)
    assert answers == plain.lookup_many(asked)
    assert answers == [ch.made[k] if k[:32] in set(sum(ch.txids[:3], []))
                       or k[:32] in {ch.bodies[h][1] for h in range(3)}
                       else None for k in asked]
    assert 0 < sum(a is not None for a in answers) < len(asked)
    key = next(k for k, a in zip(asked, answers) if a is not None)
    assert native.lookup(key[:32], int.from_bytes(key[32:], "little")) \
        == ch.made[key]
    # scripts longer than the reader's buffer: it grows, the answers hold
    native._scripts = native._scripts[:8].copy()
    assert native.lookup_many(asked) == answers
    for h in (1, 2, 3):
        blk = ch.block(h)
        assert native.prev_of(blk.header.hash) == blk.header.prev
        assert native.retire(blk.header.hash) == 2 * PER + 1
        assert native.drop(blk.header.hash) == 0
    assert len(native) == 0 and native.blocks == 0
    assert native.lookup_many(asked) == [None] * len(asked)


def test_in_block_spends_are_no_missing_rows_on_a_node_without_a_view():
    """ISSUE 44 item 5: under BCH rules the native scan marks every
    non-coinbase input as wanted, the in-block spends too; a node with no
    in-flight view (``utxo=False``) puts them to its sources, none answers,
    and the extractor's in-block map answers them a moment later: they are
    not ``node.resolve_missing``.  An outpoint of no tx of the region
    still is."""
    from types import SimpleNamespace

    ch = chain()
    blk = ch.block(6)
    region = txextract.ParsedTxRegion(blk.raw_txs, blk.tx_count)
    in_block = sum(s == "in_block" for t in ch.txids[5] for s in _where(ch, t))
    rows = 2 * PER
    assert in_block > 0
    node = SimpleNamespace(
        mempool=None, utxo=None, _inflight=None,
        cfg=SimpleNamespace(prevout_lookup=lambda txid, vout: None))
    node._prevout_sources = lambda: node_mod.Node._prevout_sources(node)
    before = moved(("node.resolve_missing", "node.resolve_rows"))
    node_mod.Node._resolve_ext_rows(node, region, True)
    got = delta(before)
    assert got["node.resolve_rows"] == rows
    assert got["node.resolve_missing"] == rows - in_block
    region.close()


# ---- (e) the cell ---------------------------------------------------------------


def _compared(res: dict) -> dict:
    return {k: v["value"] for k, v in res["compared"].items()}


def test_the_cell_rehearses_correct_through_node_from_its_own_files(capfd):
    res = rehearse(CELL)
    assert res["correct"] is True and res["failed"] == 0, _compared(res)
    assert res["attempted"] > 2000 and res["rehearsal"] is True
    assert set(res["metrics"]) == {"sigs_per_s", "host_cpu_ms_per_ksig",
                                   "setup_s"}
    got = _compared(res)
    assert all(v == 0 for v in got.values()), got
    for name in ("prevout_callback_calls", "rows_no_source_answered",
                 "utxo_lookup_misses", "utxo_entries_differ",
                 "utxo_sample_differs",
                 "spends_of_outputs_the_reference_set_lacked",
                 "outputs_left_in_the_view_after_the_last_connect",
                 "outputs_that_left_the_view_without_a_connect",
                 "resolves_that_gave_up_waiting",
                 "reference_vs_program", "reference_vs_construction"):
        assert name in got, name
    out = capfd.readouterr().out
    layer = json.loads(next(l for l in out.splitlines()
                            if '"per_layer_untraced"' in l))
    assert 20.0 < layer["resolve.inflight_share"] < 45.0
    assert 55.0 < layer["resolve.set_share"] < 80.0
    assert layer["resolve.inflight_share"] + layer["resolve.set_share"] == (
        pytest.approx(100.0))
    assert layer["resolve.missing_share"] == 0.0
    assert layer["inflight.dropped_share"] == 0.0
    assert layer["resolve.gate_ms_per_block"] >= 0.0
    assert layer["utxo.hit_share"] == 100.0
    chain_line = json.loads(next(l for l in out.splitlines()
                                 if '"line": "chain"' in l))
    assert chain_line["created_sample_spent_again"] > 100
    assert min(chain_line["prevouts_from"].values()) > 500


def test_the_configuration_is_bch_utxos_deployment_on_another_chain():
    utxo = harness.load_json(harness.ROOT, "chipbench", "configs",
                             "bch-utxo.json")
    for key in ("chips", "network", "node", "verify"):
        assert CONFIG[key] == utxo[key], key
    assert CONFIG["reference"] == "reference_chain"
    assert sorted(CONFIG["reduced"]) == ["chain_length", "peers", "utxo_set"]
    assert any("10%" in a and "30%" in a and "20%" in a and "40%" in a
               for a in CONFIG["assumed"])
    g, gu = CONFIG["guarantees"], utxo["guarantees"]
    assert {k: v for k, v in g.items()
            if k not in ("own_prevouts", "inflight_view")} == {
        k: v for k, v in gu.items() if k != "own_prevouts"}
    assert "in-flight view" in g["own_prevouts"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "bch-chain")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == sorted(CONFIG["reduced"])
    src = open(reference_chain.__file__).read()
    assert "import tpunode" not in src and "from tpunode" not in src
    assert "native" not in src.split('"""')[2]
    assert TRAFFIC["mix"] == harness.load_json(
        harness.ROOT, "chipbench", "traffic", "ibd-spend.json")["mix"]


def test_a_program_without_the_view_is_told_so_at_once(monkeypatch):
    """What the parent commit says of the cell: the driver asks the
    program for the source by name before any traffic is made."""
    from chipbench.drivers import ibd_chain
    from tpunode import utxo

    monkeypatch.delattr(utxo, "InflightOutputs")
    ctx = harness.Ctx(_wl(), BENCH, CONFIG, TRAFFIC, 1, 40.0, False, None, 0.0)
    with pytest.raises(SystemExit) as e:
        ibd_chain.Driver(ctx)
    assert "InflightOutputs" in str(e.value) and CELL in str(e.value)


def _wl() -> dict:
    return next(wl for wl in BENCH["workloads"] if wl["name"] == CELL)


# ---- (f) sizing: every finite backlog holds to 1.5 x its measured rate ------------

BACKLOG = next(m for m in BENCH["per_layer"]
               if m["name"] == "backlog.left_share")["workloads"]


def test_the_backlog_cells_are_seven():
    assert BACKLOG == [
        "bch-node.ibd", "bch-32mb.blocks", "bch-utxo.ibd-spend",
        "bch-32mb.single", "bch-wan.ibd-faults", "btc-node.ibd-taproot", CELL]
    assert len(BENCH["workloads"]) == 11 and len(BENCH["configs"]) == 8


@pytest.mark.parametrize("cell", BACKLOG)
def test_window_and_capture_hold_to_one_and_a_half_times_the_measured_rate(cell):
    _, _wl_, _, traffic = harness.load_cell(cell)
    driver = importlib.import_module("chipbench.drivers." + traffic["driver"])
    seconds = BENCH["run_seconds"]
    b = driver.backlog(traffic, seconds)
    note = traffic["backlog"]["note"]
    assert b["measured"] == traffic["backlog"]["measured_sigs_per_s"]
    assert "ledger" in note or "chip run" in note  # the rate names its origin
    assert b["window_holds_to"] >= 1.5 * b["measured"], b
    assert b["capture_holds_to"] >= b["window_holds_to"]
    assert b["blocks"] >= traffic["backlog"].get("min_blocks", 1)
    assert driver.backlog(traffic, seconds) == b
    assert driver.backlog(traffic, seconds / 2)["blocks"] <= b["blocks"]


def test_the_new_cells_backlog_by_hand():
    b = importlib.import_module("chipbench.drivers.ibd_chain").backlog(
        TRAFFIC, 40)
    per = gen.totals(TRAFFIC["mix"], PER)["sigs"]
    assert per == 144 and b["sigs"] == b["blocks"] * per
    steady = TRAFFIC["steady_until_share"] * b["sigs"]
    assert b["window_holds_to"] == pytest.approx(steady / 43.0)
    assert b["capture_holds_to"] == pytest.approx(steady / 39.0)
    assert TRAFFIC["ramp_seconds"] == 3.0 and TRAFFIC["trace_seconds"] == 4.0
    assert 12000 <= b["blocks"] <= 19000
    assert str(b["blocks"])[:2] in TRAFFIC["backlog"]["note"].replace(",", "")
    assert CONFIG["node"]["ibd"] == {"batch_blocks": 24, "tick_interval": 0.02}
