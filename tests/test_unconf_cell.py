"""The cell ``bch-unconf.tip-unconf`` (ISSUE 48): a synced BCH node whose
relay traffic spends unconfirmed outputs, from peers that deliver in no
agreed order, under blocks in canonical order.

(a) program = plain reference = construction for chains of P2PKH / Schnorr /
P2PK / 2-of-3 spends; (b) relayed parent-first and child-first through
``Node``: a child that comes before its parent waits for it and is then
verified whole — at the parent commit it was published valid with nothing
verified — and the wait ends by push, by fetch and by block; a child of an
output that only the UTXO set or only the in-flight view knows does not
wait; a parent that never comes leaves a degraded verdict, once; (c) a block
in canonical order gives what the same block gives parent-first; (d) the
generator's shares, amounts and order; (e) the cell's rehearsal through
``chipbench``, its three controls, what the parent commit says of it, and
the new per-layer metrics where their counters do not exist."""

from __future__ import annotations

import asyncio
import json
import random
import time

import pytest

from chipbench import gen, gen_unconf, harness
from chipbench import wirefmt as w
from chipbench.tests.rehearse import rehearse
from tests.fakenet import TxRelay, poll_until
from tests.fixtures import utxo_records
from tests.unconf_cell import (
    BENCH, CELL, CONFIG, GENESIS, KINDS, TRAFFIC, WL, Maker, Wire, a_node,
    block_of,
)
from tpunode.mempool import Mempool, TxState
from tpunode.metrics import metrics
from tpunode.utxo import snapshot_batch

txextract = pytest.importorskip("tpunode.txextract")
if not txextract.have_native_extract():
    pytest.skip("native txextract unavailable", allow_module_level=True)

MOVED = ("mempool.orphaned", "mempool.orphan_resolved", "mempool.fetched",
         "node.resolve_missing", "node.resolve_mempool_hits",
         "node.resolve_inflight_hits", "utxo.lookup_hits",
         "extract.unsupported_inputs", "mempool.orphan_expired",
         "span.mempool.orphan_wait.count",
         'mempool.orphan_resolved_by{how="push"}',
         'mempool.orphan_resolved_by{how="fetch"}',
         'mempool.orphan_resolved_by{how="block"}')


def before() -> dict:
    snap = metrics.snapshot()
    return {k: snap.get(k, 0) for k in MOVED}


def delta(was: dict) -> dict:
    snap = metrics.snapshot()
    return {k: int(snap.get(k, 0) - v) for k, v in was.items()}


def by(how: str) -> str:
    return f'mempool.orphan_resolved_by{{how="{how}"}}'


# ---- (a) program = reference = construction -----------------------------------


def _program(mk: Maker, txs: list) -> list:
    from tpunode.verify.ecdsa_cpu import verify_batch_cpu

    amounts, scripts = [], []
    for tx in txs:
        (_, ins, _, _), _ = w.parse_tx(tx.raw)
        for txid, vout, _, _ in ins:
            amount, script = mk.prevout(txid, vout)
            amounts.append(amount)
            scripts.append(script)
    items = txextract.extract_raw(
        b"".join(t.raw for t in txs), len(txs), bch=True, intra_amounts=False,
        ext_amounts=amounts, ext_scripts=scripts)
    assert int(items.tx_unsupported.sum()) == 0
    per_sig = items.combine(verify_batch_cpu(items.to_verify_items()))
    return [tuple(per_sig[sl]) for sl in items.sig_slices()]


@pytest.mark.parametrize("kind", KINDS)
def test_program_reference_and_construction_agree(kind):
    mk = Maker(1)
    txs = mk.chain(kind, 4)
    bad = mk.tx([(kind, (txs[-1], 0)), ("p2pkh", None)],
                adv="schnorr_s_flip" if kind == "schnorr" else "s_flip")
    txs.append(bad)
    want = [t.expect for t in txs]
    assert not all(bad.expect) and all(all(e) for e in want[:-1])
    assert _program(mk, txs) == want
    assert mk.reference(txs) == want


def test_the_reference_needs_the_parents_true_amount():
    """A spend of a traffic output under the funding outpoints' function of
    the outpoint is another digest: the reference says invalid."""
    mk = Maker(2)
    parent, child = mk.chain("p2pkh", 2)
    assert mk.reference([child]) == [(True, True)]
    assert mk.reference([child], table={}) == [(False, True)]


def test_the_extractor_leaves_a_forkid_input_without_its_amount_out():
    """What the gate is (native/txextract: no amount, no digest): the
    drain's probe over a tx with a row left names it, and names none whose
    rows are all here.  No rule in Python stands beside the extractor's."""
    from tpunode.node import Node

    for kind in KINDS:
        mk = Maker(3)
        _, child = mk.chain(kind, 2)
        (_, ins, _, _), _ = w.parse_tx(child.raw)
        (amount, script), (funding, fscript) = (
            mk.prevout(txid, vout) for txid, vout, _, _ in ins)
        for amounts, scripts, waits in (
                ([-1, funding], [None, fscript], [0]),
                ([amount, funding], [script, fscript], [])):
            region = txextract.ParsedTxRegion(child.raw, 1)
            got, items = Node._extract_kept_and_close(
                region, [0], True, amounts, scripts)
            assert got == waits, kind
            assert (items is None) == bool(waits), kind


# ---- (b) through Node -----------------------------------------------------------


@pytest.mark.asyncio
@pytest.mark.parametrize("first", ["parent", "child"])
@pytest.mark.parametrize("kind", KINDS)
async def test_a_chain_relayed_in_either_order_is_verified_whole(kind, first):
    """Three txs, each spending the one before.  Handed over oldest first
    nobody waits.  Youngest first, one at a time, the grandchild waits for
    the child; the child's admission lets it go on (a pending tx's outputs
    answer: the digest is over amounts its txid commits to) while the child
    itself waits for the parent.  At the parent commit the youngest-first
    P2PKH case gave ``TxVerdict(valid=True, verdicts=())`` for both
    children at once, with nothing verified."""
    mk = Maker(10)
    txs = mk.chain(kind, 3)
    parent, child, grandchild = txs
    was = before()
    async with a_node(mk.callback) as d:
        if first == "parent":
            for tx in txs:
                d.relay(tx)
            want = txs
        else:
            pool = d.node.mempool
            d.relay(grandchild)
            await poll_until(lambda: pool.orphan_count() == 1, what="a park")
            assert pool.state(grandchild.txid) == TxState.ORPHAN
            assert not d.order
            d.relay(child)
            await d.count(1)
            await poll_until(lambda: pool.state(child.txid) == TxState.ORPHAN,
                             what="the child to park")
            assert pool.orphan_count() == 1 and len(d.order) == 1
            d.relay(parent)
            want = [grandchild, parent, child]
        await d.count(3)
        await asyncio.sleep(0.1)
        assert [v.txid for v in d.order] == [t.txid for t in want]
        for tx in txs:
            v = d.verdicts[tx.txid]
            assert v.error is None and v.valid
            assert tuple(v.verdicts) == tx.expect and len(tx.expect) >= 2
            assert v.stats.unsupported == 0 and v.stats.extracted == 2
        assert mk.reference(txs) == [t.expect for t in txs]
        assert d.node.mempool.orphan_count() == 0
        assert d.node.mempool.size() == 3
    got = delta(was)
    waited = 0 if first == "parent" else 2
    assert got["mempool.orphaned"] == got["mempool.orphan_resolved"] == waited
    assert got[by("push")] == got["span.mempool.orphan_wait.count"] == waited
    assert got["node.resolve_missing"] == got["extract.unsupported_inputs"] == 0
    assert got["node.resolve_mempool_hits"] == 2  # counted once, verified once


@pytest.mark.asyncio
async def test_the_peer_that_sent_the_child_is_asked_for_the_parent():
    """Resolution by fetch: the peer pushes the child alone and holds the
    parent; the node's ``getdata`` brings it."""
    mk = Maker(11)
    parent, child = mk.chain("p2pkh", 2)
    relay = TxRelay([parent.lazy], announce=False, mode="serve",
                    push=[child.lazy])
    was = before()
    async with a_node(mk.callback, relay=relay, port=17949) as d:
        await d.count(2)
        assert [v.txid for v in d.order] == [parent.txid, child.txid]
        assert all(v.valid and v.stats.unsupported == 0 for v in d.order)
        await poll_until(lambda: d.node.mempool.stats()["wanted"] == 0,
                         what="the want-list to empty")
    got = delta(was)
    assert got["mempool.orphaned"] == got[by("fetch")] == 1
    assert got["mempool.fetched"] == 1 and got[by("push")] == 0


@pytest.mark.asyncio
async def test_a_child_beside_a_tx_the_extractor_refuses_still_waits():
    """The drain's failure path: one tx of a shard that the mempool's parse
    takes and the native one refuses (a witness stack of over 2**20 items)
    fails the shard's parse, and every tx of the shard is tried again
    alone.  Alone, a child whose parent is not here is handed back and
    parked like any other — it is not published with its input left out —
    the hostile tx gets its error verdict, the bystander its whole one."""
    mk = Maker(15)
    parent, child = mk.chain("p2pkh", 2)
    (bystander,) = mk.chain("p2pkh", 1)
    n = (1 << 20) + 1
    hostile = Wire(
        b"\x02\0\0\0\0\x01\x01" + b"\x11" * 36 + b"\0" + b"\xff" * 4
        + b"\x01" + (1000).to_bytes(8, "little") + b"\x01\x51"
        + b"\xfe" + n.to_bytes(4, "little") + b"\0" * n + b"\0" * 4)
    hostile.txid = hostile.lazy.txid  # a witness form: not its bytes' hash
    with pytest.raises(ValueError):
        txextract.ParsedTxRegion(hostile.raw, 1)
    was = before()
    async with a_node(mk.callback) as d:
        node, pool = d.node, d.node.mempool
        # the three in one drain batch: the drain waits for this future
        node._tx_drain = asyncio.get_running_loop().create_future()
        d.relay(child)
        d.relay(hostile)
        await poll_until(lambda: len(node._tx_accum) == 2, what="two held")
        node._tx_drain.set_result(None)
        d.relay(bystander)  # starts the drain: one shard of three
        await d.count(2)
        await poll_until(lambda: pool.state(child.txid) == TxState.ORPHAN,
                         what="the child to park")
        assert set(d.verdicts) == {bystander.txid, hostile.txid}
        bad = d.verdicts[hostile.txid]
        assert not bad.valid and bad.error.startswith("extract:")
        d.relay(parent)
        await d.count(4)
        for tx in (bystander, parent, child):
            v = d.verdicts[tx.txid]
            assert v.error is None and v.valid
            assert tuple(v.verdicts) == tx.expect
            assert v.stats.unsupported == 0 and v.stats.extracted == 2
        assert [v.txid for v in d.order[2:]] == [parent.txid, child.txid]
        assert pool.orphan_count() == 0
    got = delta(was)
    assert got["mempool.orphaned"] == got[by("push")] == 1
    assert got["node.resolve_missing"] == got["extract.unsupported_inputs"] == 0


@pytest.mark.asyncio
async def test_a_parent_that_comes_in_a_block_ends_the_wait():
    """Resolution by block: the parent was never relayed; the block's
    outputs (the in-flight view, then the set) answer the child."""
    mk = Maker(12)
    parent, child = mk.chain("p2pkh", 2)
    blk = block_of([parent])
    was = before()
    async with a_node(mk.callback) as d:
        await d.know(blk)
        d.relay(child)
        await poll_until(lambda: d.node.mempool.orphan_count() == 1,
                         what="the child to park")
        assert not d.order
        d.give(blk)
        await d.count(3)  # the coinbase, the parent, the child
        v = d.verdicts[child.txid]
        assert v.valid and tuple(v.verdicts) == child.expect
        assert v.stats.unsupported == 0
        assert d.node.mempool.state(parent.txid) == TxState.CONFIRMED
        await poll_until(lambda: d.node.utxo.height == 1, what="the connect")
    got = delta(was)
    assert got["mempool.orphaned"] == got[by("block")] == 1
    assert got["node.resolve_inflight_hits"] + got["utxo.lookup_hits"] == 1
    assert got["node.resolve_missing"] == 0


@pytest.mark.asyncio
async def test_a_child_of_an_output_only_the_set_knows_does_not_wait():
    """A confirmed output that was never relayed and that the embedder's
    callback does not hold: the node's own set answers, nobody is parked
    (the gate of before asked the callback alone)."""
    mk = Maker(13)
    parent, child = mk.chain("p2pkh", 2)
    entry = snapshot_batch([(parent.txid, 0) + parent.outs[0]])
    was = before()
    async with a_node(mk.callback) as d:
        d.node.utxo.load_snapshot(0, GENESIS, [entry])
        d.relay(child)
        await d.count(1)
        v = d.order[0]
        assert v.valid and tuple(v.verdicts) == child.expect
        assert v.stats.unsupported == 0
    got = delta(was)
    assert got["mempool.orphaned"] == 0 and got["utxo.lookup_hits"] == 1
    assert got["node.resolve_missing"] == 0


@pytest.mark.asyncio
async def test_a_child_of_an_output_only_the_view_knows_does_not_wait():
    """The parent stands in a block that is parsed and not yet connected."""
    mk = Maker(14)
    parent, child = mk.chain("schnorr", 2)
    blk = block_of([parent])
    was = before()
    async with a_node(mk.callback) as d:
        await d.know(blk)
        d.hold = asyncio.Event()
        d.give(blk)
        await poll_until(lambda: d.node._inflight.blocks == 1,
                         what="the block in flight")
        d.relay(child)
        await poll_until(
            lambda: delta(was)["node.resolve_inflight_hits"] == 1,
            what="the view's answer")
        assert d.node.mempool.orphan_count() == 0 and d.node.utxo.height < 1
        d.hold.set()
        await d.count(3)
        v = d.verdicts[child.txid]
        assert v.valid and tuple(v.verdicts) == child.expect
        assert v.stats.unsupported == 0
    got = delta(was)
    assert got["mempool.orphaned"] == 0 and got["node.resolve_missing"] == 0


@pytest.mark.asyncio
async def test_an_orphan_that_ages_out_is_verified_degraded_once():
    """The parent never comes: at ``orphan_ttl`` the child is admitted as
    it is, the walk does not hand it back a second time, and its verdict
    says what was left out."""
    mk = Maker(15)
    _, child = mk.chain("p2pkh", 2)
    was = before()
    async with a_node(mk.callback, orphan_ttl=0.2) as d:
        d.relay(child)
        await d.count(1)
        await asyncio.sleep(0.2)
        assert len(d.order) == 1
        v = d.order[0]
        assert v.stats.unsupported == 1 and tuple(v.verdicts) == (True,)
        assert d.node.mempool.orphan_count() == 0
    got = delta(was)
    assert got["mempool.orphaned"] == got["mempool.orphan_expired"] == 1
    assert got["node.resolve_missing"] == 1
    assert got["mempool.orphan_resolved"] == 0


@pytest.mark.asyncio
async def test_a_spend_signed_without_forkid_does_not_wait():
    """The extractor is the judge: on a FORKID network a spend under the
    legacy hash type needs no amount, verifies with its prevout unknown and
    must not be parked (the fakenet ingest tests relay such)."""
    from benchmarks.txgen import gen_signed_txs

    (tx,) = gen_signed_txs(1, inputs_per_tx=2, seed=0x48)
    was = before()
    async with a_node(None) as d:
        d.relay(Wire(tx.serialize()))
        await d.count(1)
        assert d.order[0].valid and d.order[0].stats.unsupported == 0
    assert delta(was)["mempool.orphaned"] == 0


# ---- (c) blocks in canonical order ----------------------------------------------


def _small(seed: int, n: int = 240, unseen: int = 24) -> tuple:
    """A strand of ``gen_unconf`` on a schedule of ``n`` relayed txs, 1 ms
    apart from 8 peers, and ``unseen`` never-pushed ones, all of one block."""
    traffic = harness.deep_merge(TRAFFIC, {
        "mix": {"adversarial_every": 16},
        "unconf": {"strands": 1, "far_s": [0.1, 0.2],
                   "confirmed": {"settle_s": 0.0}}})
    rng = random.Random(seed)
    times = [0.001 * k for k in range(n)] + sorted(
        rng.uniform(0.0, 0.001 * n) for _ in range(unseen))
    peers = [k % 8 for k in range(n)] + [-1] * unseen
    (job,) = gen_unconf.jobs_for(traffic, seed, 0xDAB5BFFA, times, peers,
                                 [0] * (n + unseen), n)
    return job, gen_unconf.strand_job(job)


def _parent_first(part: dict) -> list:
    """The strand's txs with every parent before its children."""
    at = {t: i for i, t in enumerate(part["txids"])}
    done, order = set(), []

    def visit(i: int) -> None:
        if i in done:
            return
        done.add(i)
        (_, ins, _, _), _ = w.parse_tx(part["raw"][i])
        for txid, _, _, _ in ins:
            if txid in at:
                visit(at[txid])
        order.append(i)

    for i in range(len(at)):
        visit(i)
    return order


@pytest.mark.asyncio
async def test_a_block_in_canonical_order_gives_what_parent_first_gives():
    """The same 264 txs — two in three relayed beforehand, the rest never —
    as one block in canonical order (children before their parents about
    half the time) and as one block parent-first: the same verdicts, all
    equal to construction, the same UTXO set, the same watermark."""
    job, part = _small(seed=48)
    n = len(part["txids"])
    expect = dict(zip(part["txids"], part["expect"]))
    funding = {part["funding"][i:i + 36] for i in range(0, len(part["funding"]), 36)}

    def callback(txid, vout):
        key = txid + vout.to_bytes(4, "little")
        if key in funding:
            return (gen.synth_amount(txid, vout),
                    part["p2pk"].get(key) or gen.synth_script(txid))
        return None

    topo = _parent_first(part)
    canon = [part["txids"].index(t) for t, _ in gen_unconf.canonical(
        list(zip(part["txids"], part["raw"])))]
    before_parent = sum(
        1 for pos, i in enumerate(canon)
        for txid, _, _, _ in w.parse_tx(part["raw"][i])[0][1]
        if txid in expect and canon.index(part["txids"].index(txid)) > pos)
    assert before_parent > 40  # the canonical order does put children first
    # never relayed: every seventh tx, and whatever spends one of those
    # (relayed, it would wait for the block)
    never: set = set()
    for i in topo:
        (_, ins, _, _), _ = w.parse_tx(part["raw"][i])
        if i % 7 == 0 or any(
                t in expect and part["txids"].index(t) in never
                for t, _, _, _ in ins):
            never.add(i)
    relayed = [i for i in topo if i not in never]
    assert len(never) > 40 and len(relayed) > 100
    sets, verdicts = [], []
    for order, port in ((canon, 17950), (topo, 17951)):
        blk = block_of([Wire(part["raw"][i]) for i in order])
        was = before()
        async with a_node(callback, port=port) as d:
            await d.know(blk)
            for i in relayed:  # parents first: none waits
                d.relay(Wire(part["raw"][i]))
            await d.count(len(relayed), timeout=60)
            d.give(blk)
            await d.count(len(relayed) + n + 1, timeout=60)
            await poll_until(lambda: d.node.utxo.height == 1, what="connect")
            got: dict = {}
            for v in d.order:
                got.setdefault(v.txid, []).append(tuple(v.verdicts))
                assert v.stats.unsupported == 0 and v.error is None
            for i in range(n):
                txid = part["txids"][i]
                assert got[txid] == [expect[txid]] * (1 if i in never else 2), i
            sets.append(utxo_records(d.node))
            verdicts.append({t: v[-1] for t, v in got.items()})
        moved = delta(was)
        assert moved["node.resolve_missing"] == 0
        assert moved["extract.unsupported_inputs"] == 0
        assert moved["mempool.orphaned"] == 0
    # another header, so another watermark record: the outputs are the same
    strip = lambda recs: {k: v for k, v in recs.items() if k.startswith(b"o")}
    assert strip(sets[0]) == strip(sets[1]) and len(strip(sets[0])) > n
    assert {k: v for k, v in verdicts[0].items() if v} == {
        k: v for k, v in verdicts[1].items() if v}


# ---- (d) the generator ------------------------------------------------------------


def test_every_period_holds_the_files_shares():
    counts = gen_unconf.period_counts(TRAFFIC["unconf"])
    assert counts == {"near": 81, "far": 81, "disorder": 18, "confirmed": 60,
                      "funding": 160}
    for seed in (1, 2 ** 31 + 9):
        got = gen_unconf.labels(TRAFFIC["unconf"], seed, 2000)
        for p in range(5):
            turn = got[400 * p:400 * (p + 1)]
            assert {k: turn.count(k) for k in counts} == counts
    assert (gen_unconf.labels(TRAFFIC["unconf"], 1, 800)
            != gen_unconf.labels(TRAFFIC["unconf"], 2, 800))
    with pytest.raises(ValueError):
        gen_unconf.period_counts(dict(TRAFFIC["unconf"], period=30))


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_amounts_order_and_claims_are_what_the_file_says(seed):
    job, part = _small(seed, n=600, unseen=30)
    at = {t: i for i, t in enumerate(part["txids"])}
    spent, kinds = set(), {"traffic": 0, "funding": 0}
    early = 0
    adversarial = {t for t, e in zip(part["txids"], part["expect"])
                   if not all(e)}
    for i, raw in enumerate(part["raw"]):
        (_, ins, outs, _), _ = w.parse_tx(raw)
        assert len(ins) == len(outs) == 2
        total = 0
        for txid, vout, _, _ in ins:
            assert (txid, vout) not in spent  # every output at most once
            spent.add((txid, vout))
            if txid in at:
                kinds["traffic"] += 1
                assert txid not in adversarial
                total += w.parse_tx(part["raw"][at[txid]])[0][2][vout][0]
                g, p = part["g"][i], part["g"][at[txid]]
                if g < 600 and job["time"][p] > job["time"][g]:
                    # due before its parent: 5-80 ms, through another peer
                    early += 1
                    assert 0.005 <= job["time"][p] - job["time"][g] <= 0.08
                    assert job["peer"][p] != job["peer"][g]
            else:
                kinds["funding"] += 1
                total += gen.synth_amount(txid, vout)
        assert sum(v for v, _ in outs) == total - gen_unconf.FEE
    assert part["drawn"] == {
        k: gen_unconf.labels(job["unconf"], seed, 1260).count(k)
        for k in gen_unconf.KINDS}
    assert sum(part["got"].values()) == 1260
    assert part["got"]["funding"] == kinds["funding"]
    assert early == part["got"]["disorder"] > 20
    assert 0.30 < (part["got"]["near"] + part["got"]["far"]) / 1260 < 0.45
    assert part["depth"] >= 5 and part["waits"] <= early
    assert part["small_fee"] == 0  # every tx above paid the whole fee


# ---- (e) the cell -----------------------------------------------------------------


def _compared(res: dict) -> dict:
    return {k: v["value"] for k, v in res["compared"].items()}


def test_the_cell_rehearses_correct_through_node_from_its_own_files(capfd):
    res = rehearse(CELL)
    got = _compared(res)
    assert res["correct"] is True and res["failed"] == 0, got
    assert res["attempted"] > 1500 and res["rehearsal"] is True
    assert set(res["metrics"]) == {"sigs_per_s", "verdict_p50_ms",
                                   "host_cpu_ms_per_ksig", "setup_s"}
    for name in ("inputs_the_extractor_left_out",
                 "prevout_rows_no_source_answered",
                 "orphans_that_left_the_pool_unresolved",
                 "orphans_left_after_the_drain",
                 "callback_answers_beyond_its_funding_outpoints",
                 "getdata_for_txs_the_traffic_does_not_hold",
                 "mempool.dedup_hits_beyond_txs_served_again",
                 "utxo_watermark_behind_last_verified",
                 "reference_vs_program", "reference_vs_construction"):
        assert got[name] == 0, name
    out = capfd.readouterr().out
    layer = json.loads(next(l for l in out.splitlines()
                            if '"per_layer_untraced"' in l))
    line = json.loads(next(l for l in out.splitlines()
                           if '"line": "unconf"' in l))
    # end to end the cell's p50 is its relay verdicts' median (the blocks'
    # is ``tip.block_verdict_p50_ms``), and every pool thread parsed a
    # block before the ramp
    opened = json.loads(next(l for l in out.splitlines()
                             if '"line": "open"' in l))
    assert res["metrics"]["verdict_p50_ms"]["value"] == opened["verdict_ms"]["p50"]
    assert opened["verdict_ms"]["n"] > 10 * opened["block_ms"]["n"]
    warm = json.loads(next(l for l in out.splitlines()
                           if '"line": "pool_warmup"' in l))
    assert warm["threads"] == len(warm["first_ms"]) == len(warm["second_ms"]) > 1
    assert warm["region_txs"] > 50
    drawn, realised = line["prevouts"]["drawn"], line["prevouts"]["got"]
    inputs = sum(realised.values())
    unconfirmed = 100 * sum(realised[k] for k in ("near", "far", "disorder")) / inputs
    assert 40.0 < unconfirmed < 47.0
    # (a parent whose block was taken in before its child's walk is the
    # view's or the set's to answer: a slow box moves a few rows over)
    assert abs(layer["resolve.mempool_share"] - unconfirmed) < 6.0
    assert 8.0 < layer["resolve.set_share"] + layer["resolve.inflight_share"] < 24.0
    assert 38.0 < layer["resolve.oracle_share"] < 46.0
    # one population on both sides: a row counts in every walk that
    # answered it, the row a parked tx waits for in none (the window's
    # edges may cut between the store's count and the walk's)
    shares = sum(layer["resolve." + s] for s in (
        "mempool_share", "inflight_share", "set_share", "oracle_share"))
    assert abs(shares - 100.0) < 0.2
    assert layer["resolve.missing_share"] == 0.0
    assert layer["extract.unsupported_share"] == 0.0
    assert layer["orphan.degraded_share"] == 0.0
    # on a slow box a child's walk runs after its parent has come: fewer park
    assert 1.0 < layer["orphan.parked_share"] < 12.0
    assert layer["orphan.wait_ms"] > 0.0
    assert 5.0 < layer["orphan.fetch_share"] <= 100.0
    assert layer["reuse.hit_share"] > 90.0
    assert line["orphaned"] == line["resolved"] > 20
    assert sum(line["resolved_by"].values()) == line["resolved"]
    assert line["callback"]["none"] > 0  # it was asked what it does not hold
    # whole periods hold one in ten exactly; the last is cut short
    assert abs(drawn["disorder"] * 10 - sum(
        drawn[k] for k in ("near", "far", "disorder"))) < 180
    assert line["prevouts"]["depth"] >= 10
    assert line["prevouts"]["small_fee_txs"] == 0


def test_with_the_gate_off_the_run_reads_not_correct(monkeypatch):
    """The parent commit's behaviour: nothing waits, the extractor leaves
    the inputs out, the verdicts fall short of construction."""
    monkeypatch.setattr(Mempool, "parks", lambda self, tx, parents: frozenset())
    got = _compared(res := rehearse(CELL))
    assert res["correct"] is False
    assert got["inputs_the_extractor_left_out"] > 0
    assert got["prevout_rows_no_source_answered"] > 0
    assert got["verdicts_differing_from_construction"] > 0
    assert got["orphans_that_left_the_pool_unresolved"] == 0


def test_a_reference_without_the_parents_amounts_reads_not_correct(monkeypatch):
    from chipbench.drivers import open_unconf

    monkeypatch.setattr(open_unconf.Prevouts, "table",
                        lambda self: dict(self.callback.p2pk))
    got = _compared(res := rehearse(CELL))
    assert res["correct"] is False
    assert got["reference_vs_program"] > 0
    assert got["reference_vs_program"] == got["reference_vs_construction"]
    assert got["verdicts_differing_from_construction"] == 0


def test_a_callback_that_answers_every_outpoint_reads_not_correct(monkeypatch):
    """An embedder's index that makes amounts up for outpoints it does not
    hold: nothing waits, the digests are over the wrong amounts."""
    from chipbench.drivers import open_unconf

    monkeypatch.setattr(
        open_unconf.Callback, "answer",
        lambda self, key, txid, vout: (
            gen.synth_amount(txid, vout),
            self.p2pk.get(key) or gen.synth_script(txid)))
    got = _compared(res := rehearse(CELL))
    assert res["correct"] is False
    assert got["callback_answers_beyond_its_funding_outpoints"] > 0
    assert got["verdicts_differing_from_construction"] > 0
    assert got["prevout_rows_no_source_answered"] == 0


def test_a_program_without_the_gate_is_told_so_at_once(monkeypatch):
    """What the parent commit says of the cell: the driver asks the program
    for the gate by name before any traffic is made."""
    from chipbench.drivers import open_unconf

    monkeypatch.delattr(Mempool, "orphaned")
    ctx = harness.Ctx(WL, BENCH, CONFIG, TRAFFIC, 1, 40.0, False, None, 0.0)
    t0 = time.monotonic()
    with pytest.raises(SystemExit) as e:
        open_unconf.Driver(ctx)
    assert time.monotonic() - t0 < 2.0
    assert "Mempool.orphaned" in str(e.value) and CELL in str(e.value)
    assert "verified by nothing" in str(e.value)


def test_the_configuration_is_bch_tips_node_under_other_traffic():
    tip = harness.load_json(harness.ROOT, "chipbench", "configs", "bch-tip.json")
    tip_traffic = harness.load_json(harness.ROOT, "chipbench", "traffic",
                                    "tip.json")
    for key in ("chips", "network", "node", "verify"):
        assert CONFIG[key] == tip[key], key
    # bch-tip's four cuts with its reasons; the set's says what is true
    # here: only the funding rows are the synthetic oracle's
    assert CONFIG["reduced"].keys() == tip["reduced"].keys()
    for key in ("block_interval", "chain_length", "peers"):
        assert CONFIG["reduced"][key] == tip["reduced"][key], key
    assert "true amounts" in CONFIG["reduced"]["utxo_set"]
    g = dict(CONFIG["guarantees"])
    assert "stats.unsupported" in g.pop("no_unverified_input")
    assert "orphan_evicted" in g.pop("orphans_resolved")
    assert g == tip["guarantees"]
    assert CONFIG["reference"] == "reference_chain"
    entry = next(c for c in BENCH["configs"] if c["name"] == "bch-unconf")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == sorted(CONFIG["reduced"])
    for key in ("peers", "txs_per_s", "block_every_s", "known_lag_s",
                "unseen_per_known", "ramp_seconds", "ramp_blocks",
                "schedule_slack_s", "end_to_end", "reference_sample_txs"):
        assert TRAFFIC[key] == tip_traffic[key], key
    assert {k: v for k, v in TRAFFIC["mix"].items() if k != "note"} == {
        k: v for k, v in tip_traffic["mix"].items() if k != "note"}
    u = TRAFFIC["unconf"]
    assert u["sources"] == {"unconfirmed": 0.45, "confirmed": 0.15,
                            "funding": 0.40}
    assert (u["near_s"], u["far_s"]) == ([0.0, 0.1], [0.1, 2.5])
    assert u["disorder"] == {"of_unconfirmed": [1, 10], "lead_s": [0.005, 0.08]}
    assert u["confirmed"]["blocks"] == 3 and gen_unconf.FEE == 300
    assert WL["chips"] == 1


NEW = ("resolve.mempool_share", "orphan.parked_share", "orphan.wait_ms",
       "orphan.fetch_share", "orphan.degraded_share")


def test_the_new_metrics_read_nothing_where_their_counters_are_not():
    """At a commit without the counter or the span a new metric's reader
    returns nothing, not 0; with them, what the table in ISSUE 48 says."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    ctx = harness.Ctx(WL, BENCH, CONFIG, TRAFFIC, 1, 40.0, True, None, 0.0)
    for name in NEW:
        assert entries[name]["workloads"] == [CELL], name
    parent = {"node.resolve_rows": 1000.0, "mempool.admitted": 500.0,
              "node.resolve_oracle_calls": 1000.0}
    got = harness.read_per_layer(
        ctx, harness.Reading(parent, 40.0, None, {}, {}))
    assert not set(NEW) & set(got)
    here = dict(parent, **{
        "node.resolve_mempool_hits": 450.0, "mempool.orphaned": 40.0,
        "mempool.orphan_resolved": 40.0, "span.mempool.orphan_wait.seconds": 0.1,
        'mempool.orphan_resolved_by{how="fetch"}': 30.0})
    got = harness.read_per_layer(ctx, harness.Reading(here, 40.0, None, {}, {}))
    assert {k: got[k]["value"] for k in NEW} == {
        "resolve.mempool_share": 45.0, "orphan.parked_share": 8.0,
        "orphan.wait_ms": 2.5, "orphan.fetch_share": 75.0,
        "orphan.degraded_share": 0.0}
    tip = [m["name"] for m in BENCH["per_layer"]
           if "bch-tip.tip" in m["workloads"]]
    assert all(CELL in entries[n]["workloads"] for n in tip) and len(tip) == 47


@pytest.mark.asyncio
async def test_an_output_its_parent_does_not_have_is_waited_for_by_nobody():
    """A child that names output 7 of a parent that is here with two: no
    arrival can bring it, so it is verified as it stands, at once — and is
    not sent round between the mempool and the walk."""
    mk = Maker(16)
    parent, child = mk.chain("p2pkh", 2)
    (v, ins, outs, lock), _ = w.parse_tx(child.raw)
    forged = w.ser_tx(v, [(ins[0][0], 7) + ins[0][2:]] + ins[1:], outs, lock)
    was = before()
    async with a_node(mk.callback) as d:
        d.relay(parent)
        await d.count(1)
        d.relay(Wire(forged))
        await d.count(2)
        await asyncio.sleep(0.1)
        assert d.order[1].stats.unsupported == 1 and len(d.order) == 2
        assert d.node.mempool.orphan_count() == 0
    got = delta(was)
    assert got["mempool.orphaned"] == 0 and got["node.resolve_missing"] == 1
